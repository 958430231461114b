"""The benchmark's own tests; not part of tier-1.  From the root of the repo:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""
import copy
import importlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

# four virtual CPU devices for the dp=4 rehearsal; must precede jax's start
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
    + " --xla_force_host_platform_device_count=4"

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import manifest, readers, traffic           # noqa: E402
from chipbench.trace import reduce                         # noqa: E402

TINY_TRACE = os.path.join(os.path.dirname(__file__), "data",
                          "tiny_v5e.xplane.pb")


# ------------------------------------------------------------- manifest --
@pytest.fixture(scope="module")
def real():
    return manifest.load(ROOT)


def test_benchmark_json_meets_the_contract(real):
    assert manifest.validate(real, ROOT) == []
    for cell in real["workloads"]:
        view = manifest.cell(real, ROOT, cell["name"])
        # a cell's kind is a module of chipbench/kinds/ with a run(ctx); its
        # driver a legal name, which that module reads (train reads none)
        kind = importlib.import_module(
            f"chipbench.kinds.{view['cfg']['kind']}")
        assert callable(kind.run)
        assert manifest.NAME.match(view["wl"]["driver"])
        assert len(view["end_to_end"]) >= 2 and view["per_layer"]


def _break(m, what):
    m = copy.deepcopy(m)
    if what == "layer is prose":
        m["per_layer"][0]["layer"] = "the compile cache, as PERF.md has it"
    elif what == "unit over 16 characters":
        m["end_to_end"][0]["unit"] = "samples/second/chip"
    elif what == "unit with a space":
        m["end_to_end"][0]["unit"] = "tokens per s"
    elif what == "source over 200 characters":
        m["configs"][0]["source"] = "x" * 201
    elif what == "bound over the limit":
        m["end_to_end"][0]["bound"] = 0.2
    elif what == "a key too many":
        m["per_layer"][0]["why"] = "because"
    elif what == "moves a metric its cells do not report":
        m["end_to_end"].append(dict(m["end_to_end"][0], name="other",
                                    workloads=["nowhere"]))
        m["workloads"].append(dict(m["workloads"][0], name="nowhere",
                                   traffic="nowhere"))
        m["per_layer"][0]["moves"] = "other"
    elif what == "two four-chip cells of two":
        m["workloads"][0]["chips"] = 4
        m["workloads"].append(dict(m["workloads"][0], name="again",
                                   traffic="again"))
    elif what == "a config no cell uses":
        m["configs"].append(dict(m["configs"][0], name="spare",
                                 file="chipbench/configs/spare.json"))
    elif what == "a width in reduced":
        m["configs"][0]["reduced"] = ["head_dim"]
    elif what == "no setup_s":
        m["end_to_end"] = [r for r in m["end_to_end"]
                           if r["name"] != "setup_s"]
    elif what == "a metric with no reader file":
        m["per_layer"].append(dict(m["per_layer"][0], name="unwritten"))
    elif what == "run_seconds over the limit":
        m["run_seconds"] = 52
    return m


@pytest.mark.parametrize("what", [
    "layer is prose", "unit over 16 characters", "unit with a space",
    "source over 200 characters", "bound over the limit", "a key too many",
    "moves a metric its cells do not report", "two four-chip cells of two",
    "a config no cell uses", "a width in reduced", "no setup_s",
    "a metric with no reader file", "run_seconds over the limit"])
def test_manifest_check_refuses(real, what):
    assert manifest.validate(_break(real, what), ROOT) != []


# ----------------------------- a tiny benchmark, added as files and entries --
TOY = {"image_size": 32, "classes": 10}
TINY_CELLS = {      # cell: (config, chips, images per chip)
    "resnet50_v1.train_b256": ("resnet50_v1", 1, 8),
    # a second configuration and a four-chip cell, as a later PR adds them
    "resnet50_toy.train_dp4": ("resnet50_toy", 4, 2),
}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A throw-away benchmark in a temporary directory: the repo's
    configuration, cell and per-layer metric files at toy sizes, and beside
    them a new configuration, a new cell and a new metric over an existing
    reader, each a new file plus one BENCHMARK.json entry.  Nothing under
    ``chipbench/`` is edited, which is how a later PR adds a cell."""
    root = str(tmp_path_factory.mktemp("tinybench"))
    src = os.path.join(ROOT, "chipbench")
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(os.path.join(root, "chipbench", d))
    m = {"command": ["python3", "chipbench/run.py"], "paths": ["chipbench"],
         "run_seconds": 2, "configs": [], "workloads": [],
         "end_to_end": [
             {"name": "setup_s", "unit": "s", "better": "lower",
              "bound": 0.1, "source": "host_clock"},
             {"name": "train_samples_per_s", "unit": "samples/s/chip",
              "better": "higher", "bound": 0.05, "source": "host_clock"}],
         "per_layer": []}
    for cell, (config, chips, per_chip) in TINY_CELLS.items():
        cfg = manifest.load_json(src, "configs/resnet50_v1.json")
        cfg["model"].update(TOY)
        # float32 and a gentle step: at toy batch sizes bf16 noise drowns
        # both the fall of the loss and the agreement with the reference
        cfg.update(compute_dtype="float32", optimizer={
            "name": "sgd", "args": {"learning_rate": 1e-3}},
            check={"loss_atol": 0.02})
        rel = f"chipbench/configs/{config}.json"
        with open(os.path.join(root, rel), "w") as f:
            json.dump(cfg, f)
        m["configs"].append({"name": config, "source": cfg["source"],
                             "file": rel, "reduced": [], "why": "toy"})
        wl = manifest.load_json(src, "workloads/resnet50_v1.train_b256.json")
        wl.update(batch_per_chip=per_chip, trace_s=1.0)
        with open(os.path.join(root, manifest.workload_file(cell)), "w") as f:
            json.dump(wl, f)
        m["workloads"].append({"name": cell, "config": config, "chips": chips,
                               "traffic": cell.split(".", 1)[1],
                               "why": wl["why"][:200]})
    for f in sorted(os.listdir(os.path.join(src, "layer_metrics"))):
        shutil.copy(os.path.join(src, "layer_metrics", f),
                    os.path.join(root, "chipbench", "layer_metrics", f))
        m["per_layer"].append({"name": f[:-len(".json")], "unit": "x",
                               "better": "lower", "source": "host_clock",
                               "layer": "toy", "moves": "setup_s"})
    # a new metric over an existing reader is one more file and one entry
    with open(os.path.join(root, manifest.metric_file("step_ms_p99")),
              "w") as f:
        json.dump({"reader": {"fn": "series_percentile", "series": "step_ms",
                              "q": 99}}, f)
    m["per_layer"].append({"name": "step_ms_p99", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "toy", "moves": "train_samples_per_s"})
    # and so is a number the program publishes: its own name is the key
    with open(os.path.join(root, manifest.metric_file("executables")),
              "w") as f:
        json.dump({"reader": {"fn": "counter",
                              "key": "compile_executables_created"}}, f)
    m["per_layer"].append({"name": "executables", "unit": "1",
                           "better": "lower", "source": "program_counter",
                           "layer": "toy", "moves": "setup_s"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    assert manifest.validate(m, root) == []
    return root, m


def _rehearse(tiny, cell, trace, capsys, seconds=2.0):
    import jax
    from chipbench import run
    root, m = tiny
    view = manifest.cell(m, root, cell)
    if len(jax.devices()) < view["chips"]:
        pytest.skip(f"jax started with {len(jax.devices())} devices")
    res = run.run_cell(view, jax.devices()[:view["chips"]], 2**31 + 11,
                       seconds, trace)
    assert set(res) == {"correct", "attempted", "failed", "metrics", "device",
                        "checks"}
    assert list(res)[-1] == "checks"            # last on the line
    # a CPU run can never pass for a result, and that is its only fault
    assert res["correct"] is False
    out = capsys.readouterr().out
    fails = [l for l in out.splitlines() if "[FAIL]" in l]
    assert len(fails) == 1 and "runs on a TPU" in fails[0], out
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == view["chips"]
    assert res["attempted"] > 0 and res["failed"] == 0
    return res


@pytest.mark.parametrize("cell", list(TINY_CELLS))
def test_cpu_rehearsal_untraced(tiny, cell, capsys):
    res = _rehearse(tiny, cell, 0, capsys)
    assert set(res["metrics"]) == {"setup_s", "train_samples_per_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_cpu_rehearsal_traced(tiny, capsys):
    got = set(_rehearse(tiny, "resnet50_v1.train_b256", 1, capsys)["metrics"])
    # the metric added as a file and an entry is read like the others
    assert got == {"step_ms_p50", "step_ms_p99", "compile_ms_total",
                   "executables"}
    # left out, not zero: no peak is known for a CPU, it has no device plane,
    # and a ResNet has no expert layer whose gauges could be read


def test_the_program_s_snapshot_under_its_own_names():
    """What a traced run hands to ``counter``: every counter and gauge of the
    program's registry, and ``telemetry.compile_stats()`` as compile_<key>."""
    from chipbench.kinds import train
    from mxnet_tpu import telemetry
    telemetry.registry().gauge("moe.held_share").set(0.5)
    telemetry.registry().counter("toy.requests").inc(3)
    try:
        snap = train.program_snapshot()
    finally:
        telemetry.registry().remove("moe.held_share")
        telemetry.registry().remove("toy.requests")
    assert snap["moe.held_share"] == 0.5 and snap["toy.requests"] == 3
    assert {"compile_ms_total", "compile_events", "compile_hits",
            "compile_misses", "compile_executables_created",
            "compile_persistent_cache_hits", "compile_backend_compile_s"} \
        <= set(snap)
    assert all(isinstance(v, (int, float)) for v in snap.values()), snap


def test_a_wrong_forward_fails_the_reference_check(tiny, capsys, monkeypatch):
    """The comparison with the float32 reference has teeth: a TrainStep
    whose loss is computed from other logits than the reference's is told
    apart, where a mean loss on random labels would let it pass."""
    import jax
    from chipbench import run
    from chipbench.families import resnet_v1
    from chipbench.kinds import train
    from mxnet_tpu.ndarray import NDArray
    root, m = tiny
    build, reference, skew = resnet_v1.build, train._reference, [True]

    def skewed(model):
        net, loss_fn, batch = build(model)

        def loss(out, labels):      # TrainStep sees the classes reversed
            return loss_fn(NDArray(out._data[:, ::-1]) if skew[0] else out,
                           labels)
        return net, loss, batch

    def straight(*args):
        skew[0] = False
        try:
            return reference(*args)
        finally:
            skew[0] = True
    monkeypatch.setattr(resnet_v1, "build", skewed)
    monkeypatch.setattr(train, "_reference", straight)
    view = manifest.cell(m, root, "resnet50_v1.train_b256")
    run.run_cell(view, jax.devices()[:1], 7, 1.0, 0)
    fails = [l for l in capsys.readouterr().out.splitlines() if "[FAIL]" in l]
    assert any("within 0.02 of the float32 reference" in l for l in fails)


# ------------------------------------------------------------ arithmetic --
def test_percentile_on_hand_made_samples():
    assert traffic.percentile([], 90) is None
    assert traffic.percentile(range(1, 102), 90) == pytest.approx(91.0)
    assert traffic.percentile([1.0, 2.0], 50) == pytest.approx(1.5)

    class Ctx:
        series = {"late": [0, 4]}
        counters = {"compile_ms_total": 12.5}
    assert readers.series_percentile(Ctx, "late", 50) == pytest.approx(2.0)
    assert readers.series_percentile(Ctx, "missing", 50) is None
    assert readers.counter(Ctx, "compile_ms_total") == 12.5
    assert readers.counter(Ctx, "missing") is None
    assert traffic.fold_seed(2**31 + 5) < 2**31


def test_the_seed_draws_the_batch():
    def draw(seed):
        return traffic.image_batch(np.random.default_rng(
            traffic.fold_seed(seed)), 4, 8, 10)
    (a,), (la,) = draw(2**31 + 5)
    (b,), (lb,) = draw(2**31 + 5)
    (c,), _ = draw(2**31 + 6)
    assert a.shape == (4, 8, 8, 3) and la.dtype == np.int32
    assert np.array_equal(a, b) and np.array_equal(la, lb)
    assert not np.array_equal(a, c)


def test_resnet50_required_flops():
    """He et al. table 1 gives 3.8e9 multiply-adds for the 50-layer net
    (stride on the 3x3, v1.5-style counts read 4.1e9): a training step is
    three forwards of two operations a multiply-add."""
    from chipbench.families import resnet_v1
    model = manifest.load_json(ROOT, "chipbench/configs/resnet50_v1.json")
    per_image = resnet_v1.train_flops(model["model"])
    assert 3 * 2 * 3.5e9 < per_image < 3 * 2 * 4.2e9


# ----------------------------------------------------------------- trace --
def test_union_of_intervals():
    total, merged = reduce.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)])
    assert total == 30.0 and merged.tolist() == [[0, 20], [30, 40]]
    assert reduce.union_ns([])[0] == 0.0


@pytest.mark.parametrize("hlo, want", [
    ("%fusion.9 = (bf16[256]{0:T(256)(128)(2,1)S(1)}, /*index=5*/bf16[256,56,"
     "56,256]{3,0,2,1:T(8,128)(2,1)}) fusion(bf16[256,56,56,256]{3,0,2,1} "
     "%gte.2), kind=kOutput, calls=%fused_computation.2",
     "fusion.9 kOutput bf16[256,56,56,256]"),
    ('%step.1 = f32[512,512]{1,0:T(8,128)S(1)} custom-call(f32[512,512]{1,0} '
     '%fusion), custom_call_target="tpu_custom_call", operand_layout_'
     'constraints={f32[512,512]{1,0}}', "step.1 tpu_custom_call f32[512,512]"),
    ("%all-reduce.3 = f32[1024,768]{1,0} all-reduce(f32[1024,768]{1,0} %x), "
     "replica_groups={{0,1,2,3}}, to_apply=%add",
     "all-reduce.3 all-reduce f32[1024,768]"),
    ("x" * 300, "x" * 64)])
def test_breakdown_names_are_short(hlo, want):
    assert reduce.short_name(hlo) == want and len(want) <= 64


def test_reduction_of_a_trace_recorded_on_the_chip():
    """``record_tiny_trace.py`` on a v5e: four runs of a program of a
    matmul, a Pallas add and a tanh-sum, 10 ms of host sleep after each."""
    t = reduce.Trace(TINY_TRACE)
    assert list(t.devices) == ["/device:TPU:0"]
    assert 0.03 < t.window_s < 0.2
    # device events lead the host's clock by ~1.1 ms, so the first of the
    # four runs (five ops each) falls before the host's window annotation
    ops = t.ops()
    assert len(ops) == 15
    by_hand = sum(e - s for s, e, _ in ops) / 1e9      # ops do not overlap
    assert t.busy_s() == pytest.approx(by_hand, rel=1e-6)
    assert 0 < t.busy_s() < 1e-3 * t.window_s          # a nearly idle chip
    top = t.device_ops()
    assert len(top) == 5 and all(len(n) <= 64 for n, _ in top), top
    assert sum(s for _, s in top) == pytest.approx(by_hand, rel=1e-6)
    assert any("tpu_custom_call" in n for n, _ in top)
    gaps = t.idle_gaps()
    assert sum(s for _, s in gaps) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-2)
    assert gaps[0][0] == "chipbench.wait"              # the host slept
