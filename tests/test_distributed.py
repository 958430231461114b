"""Multi-process distributed rehearsal on localhost (SURVEY.md §4
"distributed-without-a-cluster": the reference tests dist kvstore by
launching real worker processes on one machine via tools/launch.py; same
technique here over jax.distributed + gloo CPU collectives)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "n", [2, pytest.param(4, marks=pytest.mark.slow)])
def test_launch_local_dist_workers(n):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # workers must not inherit this process's 8-device XLA_FLAGS
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", str(n), "--platform", "cpu", "--devices-per-worker", "2",
         sys.executable, os.path.join(REPO, "tests", "dist_worker.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    for r in range(n):
        assert f"worker {r}/{n} OK" in proc.stdout


def test_single_process_init_noop():
    """distributed.init() with no env/args must be a harmless no-op."""
    import mxnet_tpu as mx
    mx.distributed.init()
    assert mx.distributed.num_workers() >= 1
    assert mx.distributed.rank() == 0
    # collectives degrade to identity in single-process mode
    import numpy as np
    s = mx.distributed.all_sum(np.ones((2,), np.float32))
    np.testing.assert_allclose(np.asarray(s), np.ones((2,)))


def test_elastic_restart_recovers():
    """--max-restarts: a worker crashing on the first attempt must trigger
    a full-gang relaunch that then succeeds (SURVEY §5.3 failure
    recovery — the reference has no equivalent)."""
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "worker.py")
        with open(script, "w") as f:
            f.write(
                "import os, sys\n"
                "attempt = int(os.environ.get('DMLC_ATTEMPT', '0'))\n"
                "rank = os.environ['DMLC_WORKER_ID']\n"
                "if attempt == 0 and rank == '1':\n"
                "    sys.exit(3)  # simulated hardware failure\n"
                "import mxnet_tpu as mx\n"
                "from mxnet_tpu import distributed\n"
                "distributed.init()\n"
                "import numpy as np\n"
                "s = distributed.all_sum(np.ones((2,), np.float32))\n"
                "assert float(np.asarray(s)[0]) == distributed.num_workers()\n"
                "print(f'attempt {attempt} rank {rank} OK', flush=True)\n")
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "launch.py"),
             "-n", "2", "--platform", "cpu", "--max-restarts", "2",
             sys.executable, script],
            env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
        assert "restart 1/2" in proc.stderr
        assert "attempt 1 rank 0 OK" in proc.stdout
        assert "attempt 1 rank 1 OK" in proc.stdout
