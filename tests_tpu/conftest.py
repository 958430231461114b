"""TPU re-run harness: same seeding as tests/conftest.py but WITHOUT the
XLA:CPU platform pin — the whole point is running on the accelerator
(ref: tests/python/gpu/test_operator_gpu.py setup).

This conftest imports before any test module, so three things happen here:
  * tests/ lands on sys.path for the `from test_X import *` re-run trick;
  * the few modules and tests that need true-f32 matmuls are named;
  * accelerator tolerances are patched into mxnet_tpu.test_utils BEFORE
    the star-imports capture the symbols (TPU transcendentals differ from
    host libm by more than the CPU suite's tight defaults — the reference
    widens per-context in check_consistency the same way).

The patch is GATED on jax actually being on the TPU: in a combined
`pytest tests tests_tpu` run on a CPU host this conftest still imports,
and patching unconditionally would silently loosen the CPU suite's
tolerances 20x.  (Each test module additionally carries its own inline
module-level skip rather than importing a helper from here — `import
conftest` resolution is ambiguous once tests/ is also on sys.path.)
"""
import os
import sys

import numpy as np
import pytest

_repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _repo)
sys.path.insert(0, os.path.join(_repo, "tests"))

import jax

from mxnet_tpu.config import setup_compile_cache
from mxnet_tpu.context import on_tpu

# Persistent compile cache shared with chip_smoke.py and chipbench/run.py: the
# full on-chip re-run suite spends most of its wall clock in XLA compiles.
setup_compile_cache()

# Everything runs at the default matmul precision, the one that is served
# and benchmarked (on the TPU an f32 matmul is one bf16 pass), except what
# the chip showed cannot (all of tests_tpu run once without any override,
# PR 21: 18 of 349 failed, PERF.md).  These get true-f32 MXU passes:
# the suites that hold f32 results to numpy references and numeric gradients,
_HIGHEST_MODULES = frozenset({"test_operator_tpu.py", "test_sparse_tpu.py"})
# a finite-difference check (eps 1e-3 drowns in a bf16 pass), and a resumed
# stream held token for token to the dense oracle, another implementation
# (it parted late in one sequence; ROADMAP S11)
_HIGHEST_TESTS = frozenset({
    "test_flash_dropout_gradients",
    "test_drain_handoff_exports_and_successor_resumes"})


@pytest.fixture(autouse=True)
def _matmul_precision(request):
    if request.node.path.name in _HIGHEST_MODULES \
            or request.node.originalname in _HIGHEST_TESTS:
        with jax.default_matmul_precision("highest"):
            yield
    else:
        yield


if on_tpu():
    import mxnet_tpu.test_utils as _tu

    _cpu_aae = _tu.assert_almost_equal

    def _aae_accel(a, b, rtol=1e-4, atol=1e-5, **kw):
        return _cpu_aae(a, b, rtol=max(rtol, 2e-3), atol=max(atol, 2e-4),
                        **kw)

    _cpu_cng = _tu.check_numeric_gradient

    def _cng_accel(op, inputs, kwargs=None, grad_inputs=None, eps=None,
                   rtol=2e-2, atol=2e-3, n_samples=8, seed=0):
        return _cpu_cng(op, inputs, kwargs=kwargs, grad_inputs=grad_inputs,
                        eps=eps, rtol=max(rtol, 5e-2), atol=max(atol, 5e-3),
                        n_samples=n_samples, seed=seed)

    _tu.assert_almost_equal = _aae_accel
    _tu.check_numeric_gradient = _cng_accel


@pytest.fixture(autouse=True)
def _seed_all():
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield
