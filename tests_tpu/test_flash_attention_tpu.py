"""Re-run the flash-attention Pallas suite with the kernel compiled
NATIVELY on TPU (the CPU suite runs it in interpreter mode) — parity vs
dense MHA, causal masking, bf16, and the BERT attention_impl wiring —
plus the shapes the kernel exists for, which the interpreter suite is
too slow to reach."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_flash_attention import *   # noqa: F401,F403,E402

from mxnet_tpu.gluon.model_zoo.sambay import _flash_block  # noqa: E402
from mxnet_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402


def _dense(q, k, v, causal):
    """float32 reference of softmax(QK^T/sqrt(d))V on (B*H, S, D)."""
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    k, v = (jnp.repeat(a, q.shape[0] // k.shape[0], axis=0) for a in (k, v))
    s = jnp.einsum("bqd,bkd->bqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -1e30)
    return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)


# BERT-base at batch 64 (12 heads x 64, seq 128); a causal long-sequence
# shape; phi4_mini_flash.train_s4096's layer (40 query and 20 K/V heads of
# 64, causal, the block sambay.py gives) at the 2,048 positions whose dense
# float32 reference and its backward fit beside it;
# mellum2_12b_a2_5b.train_s8192's full layer (32 query and 4 K/V heads of
# 128: a group of EIGHT query heads summed into each K/V head's dk/dv in the
# kernel's grid) at the same 2,048 positions; all bf16
@pytest.mark.parametrize("bh,bh_kv,s,d,causal,dropout,block", [
    (768, 768, 128, 64, False, 0.0, 128),
    (768, 768, 128, 64, False, 0.1, 128),
    (96, 96, 512, 64, True, 0.0, 128),
    (40, 20, 2048, 64, True, 0.0, _flash_block(2048)),
    (32, 4, 2048, 128, True, 0.0, _flash_block(2048)),
])
def test_flash_real_shapes_compiled(bh, bh_kv, s, d, causal, dropout, block):
    """Forward and backward compile on Mosaic at the real shapes (three
    custom calls: fwd, dq, dk/dv — not the interpreter), and without
    dropout agree with dense float32 attention to bf16 accuracy."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(n, s, d) * 0.5, jnp.bfloat16)
               for n in (bh, bh_kv, bh_kv))
    seed = jnp.asarray([11], jnp.int32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    flash = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=block, block_k=block,
                                        dropout=dropout, seed=seed)),
        argnums=(0, 1, 2)))
    assert flash.lower(q, k, v).as_text().count("tpu_custom_call") == 3
    val, grads = flash(q, k, v)
    assert np.isfinite(float(val))
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in grads)
    if dropout:
        val2, _ = flash(q, k, v)      # same seed: same mask, same value
        assert float(val) == float(val2)
        return
    ref_val, ref_grads = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: _dense(q, k, v, causal)), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=2e-2)
    for name, g, r in zip("qkv", grads, ref_grads):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        rel = np.linalg.norm(g - r) / np.linalg.norm(r)
        assert rel < 2e-2, f"d{name}: relative error {rel:.3e}"
