"""The set-up timeline (ISSUE 36): a ``profiler.scope(cat="setup")`` is kept
whatever ``telemetry.enable``'s ``sample`` says, ``TrainStep``'s first call
leaves ``deferred_init`` (``infer_shapes``, ``materialize``), ``state_init``
and ``compile`` on one clock, ``compile_stats()`` holds jax's own split of a
compile, the package stamps when its import began, and the benchmark's new
readers read all of it."""
import json
import os
import subprocess
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, parallel, profiler, telemetry
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon import parameter as _parameter

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, readers                    # noqa: E402

SETUP = ["TrainStep.deferred_init", "TrainStep.infer_shapes",
         "TrainStep.materialize", "TrainStep.state_init",
         "TrainStep.compile"]
NEW_METRICS = [
    "runtime_start_ms", "program_import_ms", "deferred_init_ms",
    "state_init_ms", "first_step_ms", "step_trace_ms", "step_lower_ms",
    "step_backend_ms", "backend_compile_ms_total", "cache_hit_pct",
    "setup_rest_ms"]


@pytest.fixture(autouse=True)
def _clean():
    config.watch_compiles()
    telemetry.disable()
    telemetry.reset_compiles()
    telemetry.registry().clear("TrainStep.")
    telemetry.registry().clear("EvalStep.")
    telemetry.registry().clear("compile::")
    telemetry.enable(collect=True)       # fresh, empty stores
    telemetry.disable()
    yield
    telemetry.disable()


def _net(units=8):
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(units, activation="relu"), nn.Dense(2))
    net.initialize()                     # shapes deferred to the first call
    return net


def _step(units=8):
    return parallel.TrainStep(
        _net(units), gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1))


X = np.zeros((16, 4), np.float32)
Y = np.zeros((16,), np.int32)


def _histograms():
    return {k: v["count"] for k, v in
            telemetry.registry().snapshot()["histograms"].items()}


def _histograms_of(prefix):
    return {k: v for k, v in _histograms().items() if k.startswith(prefix)}


# ------------------------------------------------------------ the spans --
def test_setup_spans_survive_a_sample_of_zero():
    telemetry.enable(sample=0.0)
    step = _step()
    for _ in range(3):
        step(X, Y).asnumpy()
    counts = _histograms()
    assert {n: counts.get(f"{n}_ms") for n in SETUP} == dict.fromkeys(SETUP, 1)
    assert not {"TrainStep.step_ms", "TrainStep.dispatch_ms",
                "TrainStep.h2d_ms"} & set(counts)


def test_children_lie_inside_their_parents_on_one_clock():
    telemetry.enable(sample=0.0, collect=True)
    step = _step()
    t0 = telemetry.now_us()
    step(X, Y).asnumpy()
    t1 = telemetry.now_us()
    step(X, Y).asnumpy()
    by = {sp.name: sp for sp in telemetry.scope_spans()}
    assert sorted(by) == sorted(SETUP)
    init = by["TrainStep.deferred_init"]
    for child in ("TrainStep.infer_shapes", "TrainStep.materialize"):
        assert by[child].parent_id == init.sid
        assert init.t0 <= by[child].t0 and by[child].t1 <= init.t1
    assert by["TrainStep.infer_shapes"].t1 <= by["TrainStep.materialize"].t0
    tops = [init, by["TrainStep.state_init"], by["TrainStep.compile"]]
    assert all(sp.parent_id is None for sp in tops)     # roots of their own
    assert t0 <= tops[0].t0 and tops[-1].t1 <= t1
    assert all(a.t1 <= b.t0 for a, b in zip(tops, tops[1:]))
    assert sum(sp.dur_us for sp in tops) <= t1 - t0
    for sp in tops:
        assert telemetry.audit_spans(sp.trace) == []
    assert init.attrs["params"] == 4


def test_a_sampled_root_holds_the_setup_spans_as_children():
    telemetry.enable(sample=1.0, collect=True)
    step = _step()
    step(X, Y).asnumpy()
    (root,) = telemetry.scope_spans("TrainStep.step")
    kids = [sp.name for sp in root.trace.spans if sp.parent_id == root.sid]
    assert kids == ["TrainStep.deferred_init", "TrainStep.state_init",
                    "TrainStep.h2d", "TrainStep.compile"]
    assert _histograms()["TrainStep.step_ms"] == 1


def test_suppress_still_silences_a_setup_scope():
    telemetry.enable(sample=0.0, collect=True)
    with telemetry.suppress():
        with profiler.scope("Toy.build", cat="setup"):
            pass
    with profiler.scope("Toy.build", cat="setup"):
        with profiler.scope("Toy.inner"):      # nested: a child, kept too
            pass
    with profiler.scope("Toy.step"):           # a root: sampled out
        with profiler.scope("Toy.load", cat="setup", n=1):
            pass
    names = [(sp.name, sp.parent_id is None) for sp in telemetry.scope_spans()]
    assert names == [("Toy.build", True), ("Toy.inner", False),
                     ("Toy.load", True)]
    assert _histograms_of("Toy.") == {"Toy.build_ms": 1, "Toy.inner_ms": 1,
                                      "Toy.load_ms": 1}
    telemetry.registry().clear("Toy.")


def test_eval_step_gets_the_same_spans_through_the_same_code():
    telemetry.enable(sample=0.0)
    ev = parallel.EvalStep(_net())
    ev(X), ev(X)
    assert _histograms_of("EvalStep.") == {
        "EvalStep.deferred_init_ms": 1, "EvalStep.infer_shapes_ms": 1,
        "EvalStep.materialize_ms": 1, "EvalStep.state_init_ms": 1,
        "EvalStep.compile_ms": 1}


def test_materialize_says_stored_or_lowered(tmp_path):
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        seen = []
        for _ in range(2):
            _parameter._PROGRAMS.clear()        # as a new process would be
            telemetry.enable(sample=0.0, collect=True)
            _step(units=5)(X, Y).asnumpy()
            (sp,) = telemetry.scope_spans("TrainStep.materialize")
            seen.append(sp.attrs.get("program"))
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        _parameter._PROGRAMS.clear()
    assert seen == ["lowered", "stored"]


# ------------------------------------------------------------- the dark --
def test_dark_builds_no_span_and_no_histogram(monkeypatch):
    built = []
    init = telemetry.Span.__init__

    def counting(self, *a, **k):
        built.append(a)
        init(self, *a, **k)
    monkeypatch.setattr(telemetry.Span, "__init__", counting)
    assert not telemetry.ACTIVE
    step = _step()
    for _ in range(3):
        step(X, Y).asnumpy()
    parallel.EvalStep(_net())(X)
    assert built == []
    assert not _histograms_of("TrainStep.") and not _histograms_of("EvalStep.")
    assert telemetry.compile_site_stats("TrainStep")["misses"] == 0


def test_dark_scope_runs_the_statements_it_ran():
    """Telemetry off, ``scope.__enter__`` and ``__exit__`` execute five and
    four lines, as before ``cat="setup"`` existed: the category is looked at
    inside the ``_telemetry.ACTIVE`` branch alone."""
    assert not telemetry.ACTIVE and not profiler.ACTIVE
    lines = {"__enter__": 0, "__exit__": 0}
    codes = {profiler.scope.__enter__.__code__: "__enter__",
             profiler.scope.__exit__.__code__: "__exit__"}

    def tracer(frame, event, _arg):
        which = codes.get(frame.f_code)
        if which is None:
            return None
        if event == "line":
            lines[which] += 1
        return tracer
    sys.settrace(tracer)
    try:
        with profiler.scope("Toy.dark", cat="setup"):
            pass
    finally:
        sys.settrace(None)
    assert lines == {"__enter__": 5, "__exit__": 4}


# -------------------------------------------------- jax's own split of it --
def test_compile_stats_splits_a_fresh_jit_and_not_a_cached_call():
    fn = jax.jit(lambda x: jnp.tanh(x) @ x.T + 36.0)
    x = jnp.ones((8, 8))
    before = telemetry.compile_stats()
    assert {"jaxpr_trace_s", "lower_s", "cache_retrieval_s"} <= set(before)
    t = time.perf_counter()
    fn(x).block_until_ready()
    wall = time.perf_counter() - t
    first = telemetry.compile_stats()
    fn(x).block_until_ready()
    again = telemetry.compile_stats()
    split = telemetry.compile_split(before, first)
    assert split["trace_ms"] > 0 and split["lower_ms"] > 0 \
        and split["backend_ms"] > 0
    # the jitted functions the traced one calls (tanh, the product) are
    # traced inside its own time: regions that nest count once
    assert split["trace_ms"] + split["lower_ms"] + split["backend_ms"] \
        <= wall * 1e3 + 1.0
    assert first["executables_created"] == before["executables_created"] + 1
    quiet = telemetry.compile_split(first, again)
    assert quiet == {"trace_ms": 0.0, "lower_ms": 0.0, "backend_ms": 0.0,
                     "cache_retrieval_ms": 0.0, "cache_hit": None}


def test_a_region_inside_another_counts_once():
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    telemetry.note_jax_region(trace)                 # outer opens
    telemetry.note_jax_region(trace)                 # a callee's trace
    telemetry.note_jax_event(trace, 0.25)
    telemetry.note_jax_region(backend)               # an eager op compiles
    telemetry.note_jax_event(backend, 0.5)
    telemetry.note_jax_event(trace, 2.0)             # outer closes
    telemetry.note_jax_event(backend, 1.0)           # no opening heard: kept
    stats = telemetry.compile_stats()
    assert stats["jaxpr_trace_s"] == 2.0 and stats["backend_compile_s"] == 1.0
    assert stats["executables_created"] == 2


def test_compile_event_and_site_counters_agree():
    telemetry.enable(sample=0.0, collect=True)
    step = _step(units=7)
    step(X, Y).asnumpy()
    step(X, Y).asnumpy()
    (event,) = [e for e in telemetry.compile_events()
                if e["site"] == "TrainStep"]
    attrs = event["attrs"]
    counters = telemetry.registry().snapshot()["counters"]
    for attr, name in (("trace_ms", "jaxpr_trace_ms"), ("lower_ms", "lower_ms"),
                       ("backend_ms", "backend_ms")):
        assert attrs[attr] > 0
        assert counters[f"compile::TrainStep::{name}"] == attrs[attr]
    assert attrs["trace_ms"] + attrs["lower_ms"] + attrs["backend_ms"] \
        <= event["ms"]
    assert {"cache_retrieval_ms", "persistent_cache_hit"} <= set(attrs)
    (span,) = telemetry.scope_spans("TrainStep.compile")
    assert {"trace_ms", "lower_ms", "backend_ms", "cache_retrieval_ms",
            "cache_hit"} <= set(span.attrs)
    assert span.attrs["trace_ms"] == attrs["trace_ms"]
    assert event["ms"] <= span.dur_us / 1e3
    # a steady step is a hit: no event, the counters stand
    site = telemetry.compile_site_stats("TrainStep")
    assert (site["hits"], site["misses"]) == (1, 1)


def test_reset_compiles_zeroes_the_new_sums():
    jax.jit(lambda x: x * 36.5)(jnp.ones(3)).block_until_ready()
    assert telemetry.compile_stats()["jaxpr_trace_s"] > 0
    telemetry.reset_compiles()
    stats = telemetry.compile_stats()
    assert [stats[k] for k in ("jaxpr_trace_s", "lower_s", "cache_retrieval_s",
                               "backend_compile_s")] == [0.0] * 4
    assert isinstance(stats["jaxpr_trace_s"], float)


# ------------------------------------------------ when the program began --
def test_import_gauges_in_a_fresh_process():
    code = (
        "import json, time; t = time.perf_counter(); import mxnet_tpu\n"
        "from mxnet_tpu import telemetry\n"
        "g = telemetry.registry().snapshot()['gauges']\n"
        "print(json.dumps([t, g['process.import_t0_s'], "
        "g['process.import_ms'], time.perf_counter()]))")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, check=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    before, t0, ms, now = json.loads(out.stdout.splitlines()[-1])
    assert before <= t0 < now
    assert 0 < ms <= (now - before) * 1e3
    assert t0 + ms / 1e3 <= now
    assert mx._IMPORT_T0 < time.perf_counter()


# --------------------------------------------------------- the readers --
def _ctx(counters=None, t0=100.0, setup_s=None):
    return types.SimpleNamespace(
        counters=dict(counters or {}), t0=t0, reduced=None,
        e2e={} if setup_s is None else {"setup_s": setup_s},
        cell={"root": ROOT})


@pytest.mark.parametrize("fn, kwargs, counters, want", [
    ("since_start_ms", {"key": "process.import_t0_s"},
     {"process.import_t0_s": 109.5}, 9500.0),
    ("since_start_ms", {"key": "process.import_t0_s"}, {}, None),
    # the package was imported before the run began: not this run's start
    ("since_start_ms", {"key": "process.import_t0_s"},
     {"process.import_t0_s": 3.0}, None),
    ("counter_ratio_pct", {"num": ["h"], "den": ["h", "m"]},
     {"h": 9, "m": 3}, 75.0),
    ("counter_ratio_pct", {"num": ["h"], "den": ["h", "m"]},
     {"h": 0, "m": 0}, None),
    ("counter_ratio_pct", {"num": ["h"], "den": ["h", "m"]}, {"h": 4}, None),
    ("counter", {"key": "compile_backend_compile_s", "scale": 1000},
     {"compile_backend_compile_s": 1.5}, 1500.0),
])
def test_new_readers_on_a_hand_built_context(fn, kwargs, counters, want):
    got = readers.find(fn)(_ctx(counters), **kwargs)
    assert got == want if want is None else got == pytest.approx(want)


def test_span_total_ms_reads_the_registry_s_histogram():
    read = readers.find("span_total_ms")
    assert read(_ctx(), name="Toy.never") is None
    telemetry.enable(sample=0.0)
    for _ in range(2):
        with profiler.scope("Toy.setup", cat="setup") as sc:
            time.sleep(0.002)
    assert read(_ctx(), name="Toy.setup") >= 4.0
    assert read(_ctx(), name="Toy.setup") <= 2 * sc.seconds * 1e3 + 50.0
    telemetry.registry().counter("Toy.count_ms").inc()     # not a histogram
    assert read(_ctx(), name="Toy.count") is None
    telemetry.registry().clear("Toy.")


def test_setup_rest_ms_is_what_the_parts_leave():
    read = readers.find("setup_rest_ms")
    parts = manifest.load_json(
        ROOT, manifest.metric_file("setup_rest_ms"))["reader"]["parts"]
    assert parts == NEW_METRICS[:5]
    reg = telemetry.registry()
    for name, ms in (("TrainStep.deferred_init", 700.0),
                     ("TrainStep.state_init", 300.0),
                     ("TrainStep.compile", 6000.0)):
        reg.histogram(f"{name}_ms", telemetry.SPAN_MS_BUCKETS).observe(ms)
    counters = {"process.import_t0_s": 109.0, "process.import_ms": 3000.0}
    ctx = _ctx(counters, t0=100.0, setup_s=25.0)
    assert read(ctx, parts=parts) == pytest.approx(
        25000.0 - 9000.0 - 3000.0 - 700.0 - 300.0 - 6000.0)
    # a part that is missing leaves no rest to name
    assert read(_ctx(counters, setup_s=None), parts=parts) is None
    del counters["process.import_ms"]
    assert read(_ctx(counters, setup_s=25.0), parts=parts) is None
    reg.remove("TrainStep.state_init_ms")
    assert read(ctx, parts=parts) is None


# ------------------------------------------------- through the benchmark --
@pytest.fixture
def toy_cell(tmp_path):
    """The real benchmark's ResNet cell at toy sizes, its per-layer entries
    and metric files as they are."""
    root, src = str(tmp_path), os.path.join(ROOT, "chipbench")
    real = manifest.load(ROOT)
    cell = "resnet50_v1.train_b256"
    for d in ("configs", "workloads", "layer_metrics"):
        os.makedirs(os.path.join(root, "chipbench", d))
    cfg = manifest.load_json(src, "configs/resnet50_v1.json")
    cfg["model"].update(image_size=32, classes=10)
    cfg.update(compute_dtype="float32", check={"loss_atol": 0.02},
               optimizer={"name": "sgd", "args": {"learning_rate": 1e-3}})
    wl = manifest.load_json(src, f"workloads/{cell}.json")
    wl.update(batch_per_chip=8, trace_s=1.0)
    m = dict(real, run_seconds=2)
    m["configs"] = [c for c in real["configs"] if c["name"] == "resnet50_v1"]
    m["workloads"] = [w for w in real["workloads"] if w["name"] == cell]
    for section in ("end_to_end", "per_layer"):
        m[section] = [dict(r, workloads=[cell]) if "workloads" in r else r
                      for r in real[section]
                      if cell in r.get("workloads", [cell])]
    for rel, body in [(m["configs"][0]["file"], cfg),
                      (manifest.workload_file(cell), wl),
                      ("BENCHMARK.json", m)] + [
            (manifest.metric_file(r["name"]),
             manifest.load_json(ROOT, manifest.metric_file(r["name"])))
            for r in m["per_layer"]]:
        with open(os.path.join(root, rel), "w") as f:
            json.dump(body, f)
    assert manifest.validate(m, root) == []
    return manifest.cell(m, root, cell)


def test_a_traced_rehearsal_reports_what_needs_no_chip(toy_cell, capsys):
    from chipbench import run
    had_stamp = telemetry.registry().get("process.import_ms") is not None
    res = run.run_cell(toy_cell, jax.devices()[:1], 2**31 + 36, 2.0, 1)
    capsys.readouterr()
    got = res["metrics"]
    want = {"deferred_init_ms", "state_init_ms", "first_step_ms",
            "step_trace_ms", "step_lower_ms", "step_backend_ms",
            "backend_compile_ms_total"} | (
        {"program_import_ms"} if had_stamp else set())
    assert want <= set(got), sorted(got)
    assert all(got[k]["value"] > 0 and got[k]["unit"] == "ms" for k in want)
    # the package was imported long before this run began, so its start is
    # not this run's, and without it there is no rest to name
    assert "runtime_start_ms" not in got and "setup_rest_ms" not in got
    assert got["step_trace_ms"]["value"] + got["step_lower_ms"]["value"] \
        + got["step_backend_ms"]["value"] <= got["first_step_ms"]["value"]
    assert got["first_step_ms"]["value"] <= got["compile_ms_total"]["value"] \
        + 1e3      # the span holds the site's compile and the first execution
    assert not telemetry.ACTIVE


def test_every_new_metric_is_an_entry_a_file_and_a_reader():
    real = manifest.load(ROOT)
    cells = [w["name"] for w in real["workloads"]]
    rows = {r["name"]: r for r in real["per_layer"]}
    # in order, one block (a later PR appends its own entries after it)
    names = [r["name"] for r in real["per_layer"]]
    at = names.index(NEW_METRICS[0])
    assert names[at:at + len(NEW_METRICS)] == NEW_METRICS
    for name in NEW_METRICS:
        row = rows[name]
        assert row["moves"] == "setup_s" and row["workloads"] == cells
        spec = manifest.load_json(ROOT, manifest.metric_file(name))
        assert spec["what"] and readers.find(spec["reader"]["fn"])
    assert rows["cache_hit_pct"]["better"] == "higher"
