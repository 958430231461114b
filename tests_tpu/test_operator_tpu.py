"""Re-run the CPU op + autograd suites on the real TPU context.

ref: tests/python/gpu/test_operator_gpu.py — the reference's key
portability trick is `from test_operator import *` with the default
context switched to GPU, re-running every CPU test on device.  Here the
switch is platform-level: this suite lives OUTSIDE tests/ (whose conftest
pins XLA:CPU) and only collects when jax's backend is an accelerator —
run it on the chip (through the builder's chip tool) with

    python -m pytest tests_tpu/ -q

sys.path and accelerator tolerances are set up by tests_tpu/conftest.py
before this module imports.
"""
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_operator import *          # noqa: F401,F403,E402
from test_autograd import *          # noqa: F401,F403,E402
from test_random_ops import *        # noqa: F401,F403,E402
