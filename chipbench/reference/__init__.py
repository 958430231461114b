"""Plain references of the benchmark's configurations: float32 ``jax.numpy``
written from the published equations, independent of ``mxnet_tpu``'s ops and
kernels.  One copy, imported by tier-1 (``tests/``) and by ``tests_tpu/``."""
