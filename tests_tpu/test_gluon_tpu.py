"""Re-run the gluon suite (blocks, trainer, data, hybridize, estimator)
on the real TPU chip (ref: tests/python/gpu/test_gluon_gpu.py)."""
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_gluon import *             # noqa: F401,F403,E402
