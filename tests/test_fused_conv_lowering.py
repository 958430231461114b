"""Mosaic TPU lowering guard for the fused conv kernels (default tier —
runs in ~3 s; no hardware needed).  Split from test_fused_conv.py's slow
interpreter sweeps so every default run still catches Mosaic regressions."""
import numpy as np

import jax
import jax.numpy as jnp


def test_mosaic_tpu_lowering_all_variants():
    """Lower every (k, stride, residual) variant fwd+bwd for the REAL TPU
    platform via jax.export — the same client-side Mosaic path that
    rejected the round-4 kernels (strided vector slices; output
    block-shape rule).  Interpreter-mode parity
    cannot catch these; this test runs on CPU and needs no hardware."""
    import mxnet_tpu.ops.pallas.fused_conv as fc

    rng = np.random.RandomState(0)
    for (k, stride, residual) in [(3, 1, False), (1, 1, False),
                                  (3, 1, True), (3, 2, False),
                                  (1, 2, False)]:
        x = jnp.asarray(rng.randn(2, 16, 16, 64), jnp.bfloat16)
        scale = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
        shift = jnp.asarray(rng.randn(64) * 0.1, jnp.float32)
        w = jnp.asarray(rng.randn(k, k, 64, 64) * 0.1, jnp.bfloat16)
        res = (jnp.asarray(rng.randn(2, 16, 16, 64), jnp.bfloat16)
               if residual else None)

        def fwd(x, scale, shift, w, res):
            return fc.norm_relu_conv(x, scale, shift, w, residual=res,
                                     stride=stride, interpret=False)

        jax.export.export(jax.jit(fwd),
                          platforms=["tpu"])(x, scale, shift, w, res)

        def loss(x, scale, shift, w, res):
            return fc.norm_relu_conv(
                x, scale, shift, w, residual=res, stride=stride,
                interpret=False).astype(jnp.float32).sum()

        grads = jax.grad(loss, argnums=(0, 1, 2, 3))
        jax.export.export(jax.jit(grads),
                          platforms=["tpu"])(x, scale, shift, w, res)

def test_kernel_parity_smoke():
    """Fast default-tier parity guard over the changed kernel paths (one
    stride-1 and one stride-2 case, fwd + input grad, interpreter mode,
    co=256 so the grid has two channel tiles); the exhaustive sweeps live
    in the slow tier (test_fused_conv.py)."""
    from mxnet_tpu.ops.pallas.fused_conv import (norm_relu_conv,
                                                 norm_relu_conv_reference)
    rng = np.random.RandomState(0)
    for stride in (1, 2):
        x = jnp.asarray(rng.randn(2, 8, 8, 8).astype(np.float32))
        sc = jnp.asarray(rng.rand(8).astype(np.float32) + 0.5)
        sh = jnp.asarray(rng.randn(8).astype(np.float32) * 0.1)
        w = jnp.asarray(rng.randn(3, 3, 8, 256).astype(np.float32) * 0.2)
        out = norm_relu_conv(x, sc, sh, w, stride=stride)
        ref = norm_relu_conv_reference(x, sc, sh, w, stride=stride)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)

        def loss_f(x):
            o = norm_relu_conv(x, sc, sh, w, stride=stride)
            return (o.astype(jnp.float32) ** 2).sum()

        def loss_r(x):
            o = norm_relu_conv_reference(x, sc, sh, w, stride=stride)
            return (o.astype(jnp.float32) ** 2).sum()

        np.testing.assert_allclose(np.asarray(jax.grad(loss_f)(x)),
                                   np.asarray(jax.grad(loss_r)(x)),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"dx stride {stride}")
