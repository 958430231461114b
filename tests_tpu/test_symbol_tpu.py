"""Re-run the symbolic-stack suites (mx.sym executor + Module) on the
real TPU (ref: tests/python/gpu — the GPU re-run trick; see
test_operator_tpu.py for the mechanism).  The symbolic executor is a
jit-traced DAG, so this is the on-chip proof that bind/forward/backward
and Module.fit compile and run on hardware, not just XLA:CPU."""
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_symbol import *            # noqa: F401,F403,E402
from test_module import *            # noqa: F401,F403,E402
from test_rnn_cells import *         # noqa: F401,F403,E402
