"""MXNET_* env-var knob system (ref: env_var.md + dmlc::GetEnv usage;
SURVEY §5.6)."""
import os
import subprocess
import sys

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_registry_and_typed_get(monkeypatch):
    assert config.get("MXNET_CPU_WORKER_NTHREADS") == 0
    monkeypatch.setenv("MXNET_CPU_WORKER_NTHREADS", "3")
    assert config.get("MXNET_CPU_WORKER_NTHREADS") == 3
    monkeypatch.setenv("MXNET_CPU_WORKER_NTHREADS", "junk")
    assert config.get("MXNET_CPU_WORKER_NTHREADS") == 0  # fall back
    # unknown vars pass through raw
    monkeypatch.setenv("MXNET_SOMETHING_ELSE", "abc")
    assert config.get("MXNET_SOMETHING_ELSE") == "abc"


def test_describe_lists_all_knobs():
    table = config.describe()
    for name in config.KNOBS:
        assert name in table
    assert "NaiveEngine" in table


def test_naive_engine_subprocess():
    """MXNET_ENGINE_TYPE=NaiveEngine must force synchronous dispatch."""
    env = dict(os.environ, MXNET_ENGINE_TYPE="NaiveEngine",
               JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    code = (
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import engine\n"
        "assert engine._NAIVE\n"
        "x = mx.nd.ones((4, 4))\n"
        "y = mx.nd.dot(x, x)\n"
        "assert len(engine._RECENT) == 0\n"   # nothing queued: all sync
        "print('naive ok')\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, (p.stdout, p.stderr)
    assert "naive ok" in p.stdout


def test_profiler_autostart_subprocess(tmp_path):
    f = str(tmp_path / "auto.json")
    env = dict(os.environ, MXNET_PROFILER_AUTOSTART="1",
               MXNET_PROFILER_FILENAME=f, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    code = ("import mxnet_tpu as mx\n"
            "mx.nd.dot(mx.nd.ones((2,2)), mx.nd.ones((2,2))).asnumpy()\n")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, (p.stdout, p.stderr)
    import json
    with open(f) as fh:
        names = {e["name"] for e in json.load(fh)["traceEvents"]}
    assert "dot" in names


def test_seed_knob_subprocess():
    env = dict(os.environ, MXNET_SEED="1234", JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    code = ("import mxnet_tpu as mx\n"
            "from mxnet_tpu import np as mnp\n"
            "print(float(mnp.random.uniform(size=(1,)).item()))\n")
    outs = []
    for _ in range(2):
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=300)
        assert p.returncode == 0, (p.stdout, p.stderr)
        outs.append(p.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1]  # same seed, same stream


def test_dataloader_workers_default(monkeypatch):
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.dataset import ArrayDataset
    ds = ArrayDataset(np.arange(8, dtype=np.float32).reshape(8, 1))
    monkeypatch.setenv("MXNET_CPU_WORKER_NTHREADS", "0")
    dl = DataLoader(ds, batch_size=4)
    assert dl._num_workers == 0


def _recorded_cache_setup(monkeypatch):
    """Run setup_compile_cache() with jax.config.update recorded, not
    applied: the rest of the suite must not start writing a cache."""
    import jax
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    return config.setup_compile_cache(), calls


def test_compile_cache_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, the helper sets
    no directory in code and reports the one in use."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    cache_dir, calls = _recorded_cache_setup(monkeypatch)
    assert cache_dir == "/some/dir"
    assert "jax_compilation_cache_dir" not in calls


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch):
    """Unset: <checkout>/.jax_compile_cache — the same path on every call,
    never a temp name, pid or time (a cache that moves never hits)."""
    import tempfile
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir, calls = _recorded_cache_setup(monkeypatch)
    assert cache_dir == os.path.join(REPO, ".jax_compile_cache")
    assert calls["jax_compilation_cache_dir"] == cache_dir
    assert not cache_dir.startswith(tempfile.gettempdir())
    assert str(os.getpid()) not in cache_dir
    assert _recorded_cache_setup(monkeypatch)[0] == cache_dir


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py under JAX_PLATFORMS=cpu exits non-zero naming the
    platform, before importing the package or building any model, and
    prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "needs a TPU" in p.stderr
    assert p.stdout == ""
