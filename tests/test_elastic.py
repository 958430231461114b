"""Elastic training supervisor (ISSUE 9): exit-status classification,
heartbeat writer/cadence, fail-fast gang teardown, watchdog hang
detection, restart backoff, the progress-aware budget, graceful
supervisor stop, the fault points — all driven with tiny STUB worker
scripts (no jax import, sub-second legs) — plus the checkpoint
``latest_step`` probe, the barrier-timeout single-process contract, the
TrainStep/fit heartbeat wiring, and one real 2-worker localhost
rehearsal (heartbeats + shutdown→re-init round-trip + bounded barrier
against a dead peer) through ``tools/launch.py``."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from mxnet_tpu import callback, elastic, fault

pytestmark = pytest.mark.elastic

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Stub preamble: a jax-free heartbeat writer matching the documented
# schema (the real one is exercised by the rehearsal + wiring tests).
STUB_BEAT = """
import json, os, sys, time
HB = os.environ["MXTPU_HEARTBEAT_DIR"]
RANK = os.environ["DMLC_WORKER_ID"]
ATTEMPT = int(os.environ.get("DMLC_ATTEMPT", "0"))
def beat(step, phase="train"):
    p = os.path.join(HB, "heartbeat-r%s.json" % RANK)
    with open(p + ".tmp", "w") as f:
        json.dump({"rank": int(RANK), "attempt": ATTEMPT,
                   "global_step": step, "monotonic_stamp": time.monotonic(),
                   "phase": phase, "pid": os.getpid()}, f)
    os.replace(p + ".tmp", p)
"""


def _stub(tmp_path, body, name="stub.py"):
    path = tmp_path / name
    path.write_text(STUB_BEAT + body)
    return [sys.executable, str(path)]


def _events(sup):
    return [r["event"] for r in sup.log.records]


# ------------------------------------------------------------ exit status --
def test_classify_exit():
    assert elastic.classify_exit(0) == "ok"
    assert elastic.classify_exit(elastic.EXIT_PREEMPTED) == "preempted"
    assert elastic.classify_exit(elastic.EXIT_NONFINITE) == "nonfinite"
    assert elastic.classify_exit(1) == "crash"
    assert elastic.classify_exit(3) == "crash"
    assert elastic.classify_exit(-9) == "killed:SIGKILL"
    assert elastic.classify_exit(-15) == "killed:SIGTERM"
    assert elastic.classify_exit(None) == "unreaped"   # survived SIGKILL
    # the classified codes sit outside the conventional crash range
    assert elastic.EXIT_PREEMPTED not in (0, 1, 2)
    assert issubclass(elastic.NonFiniteAbortError, RuntimeError)


# -------------------------------------------------------------- heartbeat --
def test_heartbeat_schema_and_atomicity(tmp_path):
    hb = elastic.Heartbeat(tmp_path, rank=3, attempt=2)
    assert not os.path.exists(hb.path)   # construction does NOT stamp:
    # a slow first compile must not start a short watchdog's clock
    rec = hb.beat(7, phase="train")
    assert rec["rank"] == 3 and rec["attempt"] == 2
    assert rec["global_step"] == 7 and rec["phase"] == "train"
    assert rec["pid"] == os.getpid()
    on_disk = elastic.read_heartbeats(tmp_path)
    assert on_disk[3]["global_step"] == 7
    assert abs(on_disk[3]["monotonic_stamp"] - time.monotonic()) < 5
    assert not os.path.exists(hb.path + ".tmp")   # committed atomically


def test_heartbeat_cadence(tmp_path):
    hb = elastic.Heartbeat(tmp_path, rank=0, every_n_steps=5)
    assert hb.beat(1) is not None        # first beat always writes
    assert hb.beat(2) is None            # thinned (call 2 of 5)
    assert hb.beat(3) is None
    assert hb.beat(4) is None
    assert hb.beat(5) is not None        # every 5th call writes
    assert hb.beat(6, phase="snapshot") is not None   # phase always writes
    # thinning counts CALLS, not step values: a pinned step counter
    # (skip_nonfinite riding out bad batches) must still refresh the
    # stamp or the watchdog would hang-flag a live worker
    hb2 = elastic.Heartbeat(tmp_path, rank=2, every_n_steps=2)
    assert hb2.beat(7) is not None
    stamp0 = elastic.read_heartbeats(tmp_path)[2]["monotonic_stamp"]
    assert hb2.beat(7) is not None       # call 2 of 2 — writes despite
    assert elastic.read_heartbeats(tmp_path)[2]["monotonic_stamp"] \
        >= stamp0                        # the frozen step value
    # callable form auto-counts (the batch-end-callback wire)
    hb2 = elastic.Heartbeat(tmp_path, rank=1)
    hb2(None)
    hb2(None)
    assert elastic.read_heartbeats(tmp_path)[1]["global_step"] == 2
    cb = callback.do_heartbeat(hb2)
    cb(None)
    assert elastic.read_heartbeats(tmp_path)[1]["global_step"] == 3


def test_heartbeat_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv(elastic.HEARTBEAT_ENV, raising=False)
    assert elastic.Heartbeat.from_env() is None   # unsupervised: no-op wire
    monkeypatch.setenv(elastic.HEARTBEAT_ENV, str(tmp_path))
    monkeypatch.setenv("DMLC_WORKER_ID", "5")
    monkeypatch.setenv("DMLC_ATTEMPT", "3")
    monkeypatch.setenv("MXTPU_HEARTBEAT_EVERY", "2")
    hb = elastic.Heartbeat.from_env()
    assert (hb.rank, hb.attempt, hb.every_n_steps) == (5, 3, 2)


def test_read_heartbeats_skips_damage(tmp_path):
    elastic.Heartbeat(tmp_path, rank=0).beat(1)
    (tmp_path / "heartbeat-r1.json").write_text("{torn")
    out = elastic.read_heartbeats(tmp_path)
    assert 0 in out and 1 not in out


# ---------------------------------------------------------- progress scan --
def test_latest_committed_step(tmp_path):
    assert elastic.latest_committed_step(tmp_path) is None
    for n in (2, 10, 6):
        (tmp_path / f"ckpt-{n:08d}.npz").touch()
    (tmp_path / "ckpt-00000099.npz.tmp").touch()    # never committed
    (tmp_path / "other-00000050.npz").touch()       # different prefix
    assert elastic.latest_committed_step(tmp_path) == 10
    assert elastic.latest_checkpoint(tmp_path)[0] == 10
    assert [s for s, _ in elastic.scan_checkpoints(tmp_path)] == [2, 6, 10]


def test_checkpoint_manager_latest_step(tmp_path):
    from mxnet_tpu.parallel import checkpoint as ck
    assert ck.latest_step(tmp_path) is None
    for n in (4, 8):
        (tmp_path / f"ckpt-{n:08d}.npz").touch()
    assert ck.latest_step(tmp_path) == 8
    # the manager method reads the same probe (no TrainStep needed here:
    # latest_step never touches the step object)
    mgr = ck.CheckpointManager(object(), tmp_path)
    assert mgr.latest_step() == 8
    assert ck.list_checkpoints(tmp_path) == elastic.scan_checkpoints(tmp_path)


# ----------------------------------------------------------- supervisor ----
def test_supervisor_success_gang(tmp_path):
    cmd = _stub(tmp_path, """
beat(1)
print("rank", RANK, "done")
sys.exit(0)
""")
    sup = elastic.Supervisor(cmd, 2, graceful_secs=2,
                             heartbeat_dir=str(tmp_path / "hb"),
                             event_log=str(tmp_path / "ev.jsonl"))
    assert sup.run() == 0
    evs = _events(sup)
    assert evs.count("worker-exit") == 2 and evs[-1] == "done"
    with open(tmp_path / "ev.jsonl") as f:
        lines = [json.loads(x) for x in f]
    assert [r["event"] for r in lines] == evs    # parseable JSONL mirror


def test_supervisor_fail_fast_teardown(tmp_path):
    """One crashed worker tears the whole gang down (a partial gang
    deadlocks in collectives) — the sleeper must not run out its clock."""
    cmd = _stub(tmp_path, """
if RANK == "1":
    sys.exit(3)
beat(1)
time.sleep(600)
""")
    sup = elastic.Supervisor(cmd, 2, graceful_secs=1,
                             heartbeat_dir=str(tmp_path / "hb"))
    t0 = time.time()
    rc = sup.run()
    assert rc == 3 and time.time() - t0 < 30
    assert sup.worker_pids() == []               # everything reaped
    exits = {r["rank"]: r["status"] for r in sup.log.records
             if r["event"] == "worker-exit"}
    assert exits[1] == "crash"
    # the torn-down survivor is accounted too, so the event log and the
    # post-mortem never under-report the gang
    assert exits[0] == "killed:SIGTERM"
    assert "teardown" in _events(sup) and "giveup" in _events(sup)


def test_supervisor_watchdog_hang(tmp_path):
    """A worker whose heartbeat goes stale past watchdog_secs is declared
    hung and the gang is torn down."""
    cmd = _stub(tmp_path, """
beat(1)
time.sleep(600)
""")
    sup = elastic.Supervisor(cmd, 2, watchdog_secs=0.6, graceful_secs=1,
                             heartbeat_dir=str(tmp_path / "hb"))
    t0 = time.time()
    rc = sup.run()
    assert rc != 0 and time.time() - t0 < 30
    stale = [r for r in sup.log.records if r["event"] == "heartbeat-stale"]
    assert stale and stale[0]["rank"] in (0, 1)
    # stale_secs is rounded to 2dp: an age of 0.601 reports exactly
    # 0.6, so the boundary is inclusive
    assert stale[0]["stale_secs"] >= 0.6
    assert "hung" in [r for r in sup.log.records
                      if r["event"] == "giveup"][0]["reason"]


def test_supervisor_startup_grace(tmp_path):
    """A worker that never produces a heartbeat is hung too (wedged in
    bring-up, before step 1 exists) once startup_grace_secs passes."""
    cmd = _stub(tmp_path, "time.sleep(600)\n")
    sup = elastic.Supervisor(cmd, 1, watchdog_secs=30,
                             startup_grace_secs=0.5, graceful_secs=1,
                             heartbeat_dir=str(tmp_path / "hb"))
    t0 = time.time()
    assert sup.run() != 0
    assert time.time() - t0 < 30
    # never-beat is its own verdict (distinct from staleness, with the
    # grace bound in the event) so log consumers can tell a bring-up
    # wedge from a runtime hang
    nhb = [r for r in sup.log.records if r["event"] == "no-heartbeat"]
    assert nhb and nhb[0]["startup_grace_secs"] == 0.5
    assert "startup grace" in [r for r in sup.log.records
                               if r["event"] == "giveup"][0]["reason"]
    # an armed watchdog derives a bring-up grace by default (10x the
    # staleness bound, floor 60s) — a pre-first-beat wedge must not
    # outlive the very watchdog meant to kill it
    assert elastic.Supervisor(cmd, 1, watchdog_secs=30).startup_grace_secs \
        == 300
    assert elastic.Supervisor(cmd, 1, watchdog_secs=2).startup_grace_secs \
        == 60
    assert elastic.Supervisor(cmd, 1).startup_grace_secs is None


def test_supervisor_backoff_between_attempts(tmp_path):
    cmd = _stub(tmp_path, "sys.exit(1)\n")
    sup = elastic.Supervisor(cmd, 1, max_restarts=2, backoff_base=0.2,
                             graceful_secs=1,
                             heartbeat_dir=str(tmp_path / "hb"))
    assert sup.run() == 1
    restarts = [r for r in sup.log.records if r["event"] == "restart"]
    assert len(restarts) == 2
    # exponential growth: each planned delay >= base * 2^(k-1)
    for k, rec in enumerate(restarts, start=1):
        assert rec["delay"] >= 0.2 * 2 ** (k - 1)
    # and the spawns really waited the planned delay out
    spawns = [r["ts"] for r in sup.log.records if r["event"] == "spawn"]
    assert spawns[1] - spawns[0] >= 0.2
    assert spawns[2] - spawns[1] >= 0.4


def test_supervisor_progress_refill(tmp_path):
    """An attempt that advanced the committed checkpoint step refills the
    restart budget: 4 crashes survive a max_restarts=1 budget because
    each attempt made progress."""
    ck = tmp_path / "ck"
    ck.mkdir()
    cmd = _stub(tmp_path, """
a = ATTEMPT
open(os.path.join(os.environ["CKDIR"], "ckpt-%08d.npz" % ((a + 1) * 2)),
     "w").close()
sys.exit(0 if a >= 4 else 1)
""")
    sup = elastic.Supervisor(cmd, 1, max_restarts=1, backoff_base=0.05,
                             graceful_secs=1, progress_dir=str(ck),
                             heartbeat_dir=str(tmp_path / "hb"),
                             extra_env={"CKDIR": str(ck)})
    assert sup.run() == 0
    assert sup.restarts == 4
    assert _events(sup).count("budget-refill") == 3


def test_supervisor_crash_loop_exhausts(tmp_path):
    """No progress → the budget burns down fast and the giveup event
    carries a post-mortem."""
    cmd = _stub(tmp_path, "beat(1)\nsys.exit(1)\n")
    sup = elastic.Supervisor(cmd, 1, max_restarts=1, backoff_base=0.05,
                             graceful_secs=1, progress_dir=str(tmp_path),
                             heartbeat_dir=str(tmp_path / "hb"))
    assert sup.run() == 1
    assert sup.restarts == 1
    giveup = [r for r in sup.log.records if r["event"] == "giveup"]
    assert len(giveup) == 1
    pm = giveup[0]["post_mortem"]
    assert pm["attempts"] == 2 and pm["restarts"] == 1
    assert "crash" in pm["last_reason"]
    assert pm["heartbeats"]["0"]["global_step"] == 1


def test_supervisor_graceful_stop_collects_snapshots(tmp_path):
    """request_stop (the programmatic supervisor-SIGTERM) forwards
    SIGTERM, waits for the snapshot-then-exit path, and returns 0 with
    every worker classified preempted."""
    snaps = tmp_path / "snaps"
    snaps.mkdir()
    cmd = _stub(tmp_path, """
import signal
flag = []
signal.signal(signal.SIGTERM, lambda s, f: flag.append(s))
n = 0
while not flag:
    n += 1
    beat(n)
    time.sleep(0.02)
beat(n, phase="snapshot")
open(os.path.join(os.environ["SNAPDIR"], "snap-r" + RANK), "w").close()
sys.exit(43)
""")
    sup = elastic.Supervisor(cmd, 2, graceful_secs=10,
                             heartbeat_dir=str(tmp_path / "hb"),
                             extra_env={"SNAPDIR": str(snaps)})
    threading.Timer(0.6, sup.request_stop).start()
    assert sup.run() == 0
    assert sorted(os.listdir(snaps)) == ["snap-r0", "snap-r1"]
    statuses = [r["status"] for r in sup.log.records
                if r["event"] == "worker-exit"]
    assert statuses == ["preempted", "preempted"]
    assert "forward-sigterm" in _events(sup)
    assert _events(sup)[-1] == "preempted"


def test_supervisor_nonfinite_status(tmp_path):
    cmd = _stub(tmp_path, "sys.exit(44)\n")
    sup = elastic.Supervisor(cmd, 1, graceful_secs=1,
                             heartbeat_dir=str(tmp_path / "hb"))
    assert sup.run() == 44
    assert [r["status"] for r in sup.log.records
            if r["event"] == "worker-exit"] == ["nonfinite"]
    assert "nonfinite" in [r for r in sup.log.records
                           if r["event"] == "giveup"][0]["reason"]


def test_supervisor_fault_points(tmp_path):
    for p in ("supervisor.spawn", "supervisor.heartbeat",
              "supervisor.watchdog", "supervisor.restart"):
        assert p in fault.points()
    cmd = _stub(tmp_path, "sys.exit(0)\n")
    with fault.inject("supervisor.spawn", RuntimeError("spawn fault")) as h:
        sup = elastic.Supervisor(cmd, 1, graceful_secs=1,
                                 heartbeat_dir=str(tmp_path / "hb"))
        with pytest.raises(RuntimeError, match="spawn fault"):
            sup.run()
    assert h.fired == 1
    # a watchdog-thread fault forwards to the owner thread and re-raises
    # there (the producer convention — a silently dead watchdog would
    # un-guard the gang)
    cmd2 = _stub(tmp_path, "beat(1)\ntime.sleep(600)\n")
    with fault.inject("supervisor.heartbeat",
                      RuntimeError("watchdog fault")) as h2:
        sup2 = elastic.Supervisor(cmd2, 1, watchdog_secs=5, graceful_secs=1,
                                  heartbeat_dir=str(tmp_path / "hb2"))
        with pytest.raises(RuntimeError, match="watchdog fault"):
            sup2.run()
    assert h2.fired == 1
    assert sup2.worker_pids() == []    # the gang still tore down


def test_supervisor_worker_env_contract(tmp_path):
    """Workers see the DMLC_* contract + heartbeat dir; an inherited
    device-count XLA flag is REPLACED, not doubled."""
    out = tmp_path / "env.json"
    cmd = _stub(tmp_path, """
with open(os.environ["OUT"], "w") as f:
    json.dump({k: os.environ.get(k) for k in
               ("DMLC_ROLE", "DMLC_PS_ROOT_URI", "DMLC_PS_ROOT_PORT",
                "DMLC_NUM_WORKER", "DMLC_WORKER_ID", "DMLC_ATTEMPT",
                "MXTPU_HEARTBEAT_DIR", "JAX_PLATFORMS", "XLA_FLAGS")}, f)
""")
    hb = str(tmp_path / "hb")
    sup = elastic.Supervisor(cmd, 1, platform="cpu", devices_per_worker=2,
                             graceful_secs=1, heartbeat_dir=hb,
                             extra_env={"OUT": str(out)})
    env_backup = os.environ.get("XLA_FLAGS")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    try:
        assert sup.run() == 0
    finally:
        if env_backup is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = env_backup
    env = json.loads(out.read_text())
    assert env["DMLC_ROLE"] == "worker" and env["DMLC_NUM_WORKER"] == "1"
    assert env["DMLC_WORKER_ID"] == "0" and env["DMLC_ATTEMPT"] == "0"
    assert env["MXTPU_HEARTBEAT_DIR"] == hb
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].count("device_count") == 1
    assert "device_count=2" in env["XLA_FLAGS"]


def test_supervisor_prefixed_output_and_log_dir(tmp_path):
    """[r<rank>] prefixing makes interleaved gang output attributable;
    --log-dir tees to per-rank files instead."""
    cmd = _stub(tmp_path, 'print("marker-out"); '
                          'print("marker-err", file=sys.stderr)\n')
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", *cmd],
        capture_output=True, text=True, timeout=120,
        env={**os.environ,
             "PYTHONPATH": REPO + os.pathsep +
             os.environ.get("PYTHONPATH", "")})
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    for r in (0, 1):
        assert f"[r{r}] marker-out" in proc.stdout
        assert f"[r{r}] marker-err" in proc.stderr
    log_dir = tmp_path / "logs"
    sup = elastic.Supervisor(cmd, 2, graceful_secs=2, log_dir=str(log_dir),
                             heartbeat_dir=str(tmp_path / "hb"))
    assert sup.run() == 0
    for r in (0, 1):
        assert "marker-out" in (log_dir / f"r{r}.log").read_text()


# ------------------------------------------------- worker-side wiring ------
def test_trainstep_heartbeat_wiring(tmp_path):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    hb = elastic.Heartbeat(tmp_path, rank=0)
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3))
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mx.optimizer.create("sgd"), heartbeat=hb)
    rng = np.random.RandomState(0)
    for _ in range(3):
        step(rng.randn(8, 3).astype(np.float32), rng.randint(0, 4, (8,)))
    rec = elastic.read_heartbeats(tmp_path)[0]
    assert rec["global_step"] == 3 and rec["phase"] == "train"


def test_module_fit_heartbeat_from_env(tmp_path, monkeypatch):
    import numpy as np
    import mxnet_tpu as mx

    monkeypatch.setenv(elastic.HEARTBEAT_ENV, str(tmp_path))
    monkeypatch.setenv("DMLC_ATTEMPT", "1")
    data = mx.symbol.Variable("data")
    out = mx.symbol.FullyConnected(data, num_hidden=2, name="fc")
    net = mx.symbol.SoftmaxOutput(out, name="softmax")
    x = np.random.RandomState(0).randn(12, 3).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 2, (12,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=4)
    mod = mx.mod.Module(net)
    mod.fit(it, num_epoch=2)
    rec = elastic.read_heartbeats(tmp_path)[0]
    assert rec["global_step"] == 6      # 3 batches x 2 epochs
    assert rec["attempt"] == 1 and rec["phase"] == "train"
    # the validation pass beats too (phase "eval") — a long eval must
    # not read as a hang to the supervisor's watchdog
    val = mx.io.NDArrayIter(x[:4], y[:4], batch_size=4)
    mx.mod.Module(net).fit(it, eval_data=val, num_epoch=1)
    assert elastic.read_heartbeats(tmp_path)[0]["phase"] == "eval"


def test_barrier_timeout_single_process_noop(monkeypatch):
    from mxnet_tpu import distributed
    monkeypatch.delenv("DMLC_NUM_WORKER", raising=False)
    distributed.barrier("elastic-noop", timeout=0.1)   # must not raise
    # ...but a configured gang with NO coordination service (between
    # shutdown() and init()) must refuse rather than silently "succeed"
    monkeypatch.setenv("DMLC_NUM_WORKER", "2")
    with pytest.raises(RuntimeError, match="no coordination service"):
        distributed.barrier("elastic-gang", timeout=0.1)


# ------------------------------------------------- the real rehearsal ------
def test_launch_elastic_rehearsal(tmp_path):
    """One real 2-worker gang through tools/launch.py: heartbeats under a
    live watchdog, CheckpointManager progress the supervisor reads,
    distributed shutdown→re-init round-trip, and the bounded barrier
    failing fast against a dead peer."""
    ck = tmp_path / "ckpt"
    hb = tmp_path / "hb"
    ev = tmp_path / "events.jsonl"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update({"MXTPU_TARGET_STEP": "6", "MXTPU_STEP_SLEEP": "0.01",
                "MXTPU_CKPT_DIR": str(ck), "MXTPU_ROUNDTRIP": "1"})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--platform", "cpu", "--devices-per-worker", "1",
         "--watchdog-secs", "60", "--startup-grace-secs", "240",
         "--heartbeat-dir", str(hb), "--event-log", str(ev),
         "--progress-dir", str(ck),
         sys.executable, os.path.join(REPO, "tests", "elastic_worker.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    assert "coordination round-trip OK" in proc.stdout
    assert "barrier-timeout OK" in proc.stdout
    for r in (0, 1):
        assert f"[r{r}] " in proc.stdout          # attributable gang output
        assert f"rank {r} reached target 6" in proc.stdout
    beats = elastic.read_heartbeats(hb)
    assert sorted(beats) == [0, 1]
    assert all(b["global_step"] >= 6 for b in beats.values())
    assert elastic.latest_committed_step(ck) >= 6
    events = [json.loads(line) for line in ev.read_text().splitlines()]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "spawn" and kinds[-1] == "done"
    assert [e["status"] for e in events
            if e["event"] == "worker-exit"] == ["ok", "ok"]
