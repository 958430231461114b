"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (ref: tests/python/gpu/test_operator_gpu.py
imports CPU suites with ctx switched): here the switch is platform-level — the
suite runs on XLA:CPU with 8 virtual devices so sharding/collective tests
exercise real multi-device paths without TPU hardware
(SURVEY.md §4 "distributed-without-a-cluster").
"""
import os

# Must happen before jax backend init: tier-1 runs on XLA:CPU.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="include @pytest.mark.slow tests (interpreter parity sweeps, "
             "CPU-training accuracy gates)")


def pytest_collection_modifyitems(config, items):
    """Default run excludes the slow tier so the suite stays under 15 min
    and keeps being run casually.  The on-chip re-run
    suite (tests_tpu/) has its own conftest and always runs everything."""
    full = os.environ.get("MXTPU_FULL_TESTS", "0").lower()
    if config.getoption("--runslow") or full not in ("", "0", "false"):
        return
    skip = pytest.mark.skip(
        reason="slow tier: pass --runslow or set MXTPU_FULL_TESTS=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _seed_all():
    """with_seed() equivalent (ref: tests/python/unittest/common.py)."""
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield
