"""Re-run the sparse storage suite (row_sparse/csr over BCOO) on the
real TPU chip (ref: tests/python/gpu/test_kvstore_gpu.py sparse rows)."""
import pytest

from mxnet_tpu.context import on_tpu

if not on_tpu():
    pytest.skip("TPU re-run suite needs the TPU backend",
                allow_module_level=True)

from test_sparse import *            # noqa: F401,F403,E402
