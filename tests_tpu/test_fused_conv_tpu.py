"""Re-run the Pallas fused norm-relu-conv suite with kernels compiled
NATIVELY on TPU (CPU runs them in interpreter mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_fused_conv import *        # noqa: F401,F403,E402

from mxnet_tpu.ops.pallas import fused_conv as fc  # noqa: E402


def _variant_args(k, stride, residual, n=2, hw=16, ci=64, co=64):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(n, hw, hw, ci), jnp.bfloat16)
    scale = jnp.asarray(rng.rand(ci) + 0.5, jnp.float32)
    shift = jnp.asarray(rng.randn(ci) * 0.1, jnp.float32)
    w = jnp.asarray(rng.randn(k, k, ci, co) * 0.05, jnp.bfloat16)
    res = jnp.asarray(rng.randn(n, hw, hw, ci), jnp.bfloat16) \
        if residual else None
    return x, scale, shift, w, res


@pytest.mark.parametrize("k,stride,residual",
                         [(1, 1, False), (3, 1, False), (3, 2, False),
                          (1, 2, False), (3, 1, True)])
def test_fused_conv_compile_only(k, stride, residual):
    """Lower + compile each fused variant on real Mosaic WITHOUT running it.

    Distinguishes 'Mosaic rejects the kernel' (this fails) from 'numerics
    drift on-chip' (the imported parity suite fails).
    Covers the forward kernel alone and the full fwd+bwd pair, since the
    two backward kernels (_dx, _dw) are separate Mosaic programs.
    """
    x, scale, shift, w, res = _variant_args(k, stride, residual)

    def fwd(x, scale, shift, w, res):
        return fc.norm_relu_conv(x, scale, shift, w, residual=res,
                                 stride=stride, interpret=False)

    fwd_low = jax.jit(fwd).lower(x, scale, shift, w, res)
    # a Mosaic program, not the interpreter's expansion
    assert fwd_low.as_text().count("tpu_custom_call") == 1
    fwd_low.compile()

    def loss(x, scale, shift, w, res):
        return fc.norm_relu_conv(x, scale, shift, w, residual=res,
                                 stride=stride,
                                 interpret=False).astype(jnp.float32).sum()

    grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))) \
        .lower(x, scale, shift, w, res)
    # the dx and dw kernels (a sum loss leaves the forward kernel dead)
    assert grads.as_text().count("tpu_custom_call") == 2
    grads.compile()
