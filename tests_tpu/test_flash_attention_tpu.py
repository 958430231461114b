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
from test_flash_attention import _dense_f32  # noqa: E402

from mxnet_tpu.gluon.model_zoo.sambay import _flash_block  # noqa: E402
from mxnet_tpu.ops.attention import _window_block  # noqa: E402
from mxnet_tpu.ops.pallas.flash_attention import flash_attention  # noqa: E402


# BERT-base at batch 64 (12 heads x 64, seq 128); a causal long-sequence
# shape; phi4_mini_flash.train_s4096's layer (40 query and 20 K/V heads of
# 64, causal, the block sambay.py gives) at the 2,048 positions whose dense
# float32 reference and its backward fit beside it;
# mellum2_12b_a2_5b.train_s8192's full layer (32 query and 4 K/V heads of
# 128: a group of EIGHT query heads summed into each K/V head's dk/dv in the
# kernel's grid) at the same 2,048 positions; then the two cells' WINDOW
# layers (512 at heads of 64, 1,024 at heads of 128: the windowed kernels,
# whose grid walks the band, at the block ``ops.window_attention`` gives)
# against the dense band; all bf16
@pytest.mark.parametrize("bh,bh_kv,s,d,causal,dropout,block,window", [
    (768, 768, 128, 64, False, 0.0, 128, None),
    (768, 768, 128, 64, False, 0.1, 128, None),
    (96, 96, 512, 64, True, 0.0, 128, None),
    (40, 20, 2048, 64, True, 0.0, _flash_block(2048), None),
    (32, 4, 2048, 128, True, 0.0, _flash_block(2048), None),
    (40, 20, 2048, 64, True, 0.0, _window_block(2048), 512),
    (32, 4, 2048, 128, True, 0.0, _window_block(2048), 1024),
])
def test_flash_real_shapes_compiled(bh, bh_kv, s, d, causal, dropout, block,
                                    window):
    """Forward and backward compile on Mosaic at the real shapes (two
    custom calls: fwd and the fused dq/dk/dv — not the interpreter), and
    without dropout agree with dense float32 "highest" attention to bf16
    accuracy."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(n, s, d) * 0.5, jnp.bfloat16)
               for n in (bh, bh_kv, bh_kv))
    seed = jnp.asarray([11], jnp.int32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) ** 2).sum()

    flash = jax.jit(jax.value_and_grad(loss(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        block_q=block, block_k=block,
                                        dropout=dropout, seed=seed,
                                        window=window)),
        argnums=(0, 1, 2)))
    text = flash.lower(q, k, v).as_text(debug_info=True)
    assert text.count("tpu_custom_call") == 2
    assert ("window_attention_fwd" in text) == (window is not None)
    assert "_attention_bwd_dqkv" in text
    val, grads = flash(q, k, v)
    assert np.isfinite(float(val))
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in grads)
    if dropout:
        val2, _ = flash(q, k, v)      # same seed: same mask, same value
        assert float(val) == float(val2)
        return
    with jax.default_matmul_precision("highest"):
        ref_val, ref_grads = jax.jit(jax.value_and_grad(loss(
            lambda q, k, v: _dense_f32(q, k, v, causal, window=window)),
            argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=2e-2)
    readings = {"value": abs(float(val) / float(ref_val) - 1)}
    for name, g, r in zip("qkv", grads, ref_grads):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        rel = np.linalg.norm(g - r) / np.linalg.norm(r)
        readings[f"d{name}"] = float(rel)
        assert rel < 2e-2, f"d{name}: relative error {rel:.3e}"
    print(f"\n[flash {bh}/{bh_kv} x {s} x {d} window {window}] "
          f"relative errors {readings}")


# the fused backward at the heads of the two cells it was made for, at the
# 2,048 positions whose dense float32 reference fits beside it:
# kanana2_30b_a3b.train_s8192's latent attention (32 heads, Q/K 192 beside
# V 128, group 1) and lfm2_8b_a1b.train_s8192's (32 query and 8 K/V heads of
# 64, group 4); bf16, causal, the block the decoders give
@pytest.mark.parametrize("bh,bh_kv,d,d_v", [(32, 32, 192, 128),
                                            (32, 8, 64, 64)],
                         ids=["kanana2_heads", "lfm2_heads"])
def test_fused_backward_real_heads_compiled(bh, bh_kv, d, d_v):
    """One ``flash_attention_bwd_dqkv`` call in the compiled gradient, no
    split kernel, and dq, dk, dv within bf16 accuracy of dense float32
    "highest" attention."""
    import re
    from mxnet_tpu.gluon.model_zoo._attention import \
        _flash_block as _decoder_block
    s = 2048
    block = _decoder_block(s)
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(n, s, d) * 0.5, jnp.bfloat16)
            for n in (bh, bh_kv))
    v = jnp.asarray(rng.randn(bh_kv, s, d_v) * 0.5, jnp.bfloat16)
    w = jnp.asarray(rng.randn(bh, s, d_v), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()
    flash = jax.jit(jax.grad(loss(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=block,
                                        block_k=block)), argnums=(0, 1, 2)))
    text = flash.lower(q, k, v).compile().as_text()
    assert "flash_attention_bwd_dqkv" in text
    assert not re.search(r"flash_attention_bwd_(dq|dkv)\b", text)
    grads = flash(q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.grad(loss(
            lambda q, k, v: _dense_f32(q, k, v, True)), argnums=(0, 1, 2)))(
            q, k, v)
    readings = {}
    for name, g, r in zip("qkv", grads, ref):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        readings[f"d{name}"] = float(np.linalg.norm(g - r) / np.linalg.norm(r))
        assert readings[f"d{name}"] < 2e-2, readings
    print(f"\n[fused backward {bh}/{bh_kv} x {s} x {d}/{d_v}] "
          f"relative errors {readings}")
