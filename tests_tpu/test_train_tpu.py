"""Re-run the end-to-end convergence gates on the real TPU chip
(ref: tests/python/train/ re-run under GPU context)."""
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_train import *             # noqa: F401,F403,E402
