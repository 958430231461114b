"""mx.telemetry (ISSUE 13): end-to-end request tracing + unified metrics.

Covers the metrics substrate (Counter/Gauge/Histogram, log-spaced
buckets, mergeable snapshots, interpolated quantiles), the one JSONL
sink (schema, atomic lines, rotation; elastic ``EventLog`` riding it),
the span layer (trees, sampling, the off-switch, the tracer-never-fails-
a-request contract), the end-to-end span trees of all three serving
paths (InferenceServer, GenerationServer fused + disaggregated,
ServingFleet failover), the unified ``telemetry()`` exposition schema,
the ``audit_spans`` attribution contract, and Chrome-trace export
validity (profiler stream round-trip).

All tier-1 (JAX_PLATFORMS=cpu, conftest's virtual mesh).  The
``telemetry`` marker selects this suite.
"""
import json
import threading
import time

import numpy as np
import pytest
import jax

from mxnet_tpu import elastic, fault, profiler, telemetry
from mxnet_tpu.gluon.model_zoo.causal_lm import CausalLMConfig, init_causal_lm
from mxnet_tpu.serving import (BucketSpec, CircuitBreaker, GenerationServer,
                               HotSwapApply, InferenceServer, ServingFleet)
from mxnet_tpu.serving.admission import ClassStats
from mxnet_tpu.serving.autoscale import FleetAutoscaler, ScalingPolicy

pytestmark = pytest.mark.telemetry
chaos = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _telemetry_clean():
    """Telemetry is process-global: every test starts dark and leaves
    nothing behind (registry series, collected traces, the fault
    observer)."""
    telemetry.disable()
    yield
    telemetry.disable()
    cfg = telemetry.config()
    if cfg.sink is not None:
        cfg.sink.close()
    cfg.sink = None
    cfg.collect = False
    cfg.collected.clear()
    cfg.sample = 1.0
    telemetry.registry().clear()
    telemetry.reset_compiles()
    fl = telemetry.flight()
    fl.enabled = False
    fl.clear()
    fl.directory = None
    fl.last_path = None
    profiler.counters_clear()
    fault.set_observer(None)
    fault.set_exit_observer(None)


# ------------------------------------------------------------------ helpers --
def make_server(delay=0.0, **kw):
    @jax.jit
    def f(x):
        return x * 2.0

    def apply(x):
        if delay:
            time.sleep(delay)
        return np.asarray(f(x))

    kw.setdefault("max_delay", 0.002)
    kw.setdefault("sample", np.zeros((3,), np.float32))
    srv = InferenceServer(apply, buckets=(1, 2, 4), **kw)
    srv.start()
    return srv


def _ex(v, n=3):
    return np.full((n,), float(v), np.float32)


CFG = CausalLMConfig(vocab_size=48, n_layers=2, n_heads=2, head_dim=8,
                     d_ff=32)
PARAMS = init_causal_lm(CFG, seed=3)


def make_genserver(**kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_pages", 17)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("seed", 0)
    name = kw.pop("name", f"GenTel-{time.monotonic_ns()}")
    return GenerationServer(PARAMS, CFG,
                            buckets=BucketSpec(batch=(1,), length=(8,)),
                            name=name, **kw)


class FlakyApply(HotSwapApply):
    def __init__(self, fn, params):
        super().__init__(fn, params)
        self.fail = False

    def __call__(self, *leaves):
        if self.fail:
            raise RuntimeError("replica wedged")
        return super().__call__(*leaves)


def make_fleet(n=3, **kw):
    @jax.jit
    def fwd(params, x):
        (w,) = params
        return x @ w

    w0 = np.eye(4, dtype=np.float32)
    applies = [FlakyApply(fwd, [w0]) for _ in range(n)]
    kw.setdefault("max_delay", 0.002)
    kw.setdefault("buckets", (1, 2, 4))
    fleet = ServingFleet(applies, sample=np.ones((4,), np.float32), **kw)
    fleet.apply_fns = applies
    return fleet


# ------------------------------------------------------------------ metrics --
def test_log_buckets_layout():
    b = telemetry.log_buckets(1e-3, 1e3, per_decade=4)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1e3
    # log-spaced: constant ratio between neighbours
    ratios = [b[i + 1] / b[i] for i in range(len(b) - 1)]
    assert all(r == pytest.approx(ratios[0]) for r in ratios)
    with pytest.raises(ValueError):
        telemetry.log_buckets(0, 1.0)
    with pytest.raises(ValueError):
        telemetry.log_buckets(2.0, 1.0)


def test_histogram_observe_quantile_merge():
    h = telemetry.Histogram("lat", telemetry.LATENCY_BUCKETS_S)
    assert h.quantile(0.5) is None          # empty
    for v in [0.001] * 50 + [0.010] * 45 + [1.0] * 5:
        h.observe(v)
    assert h.count == 100
    p50, p99 = h.quantile(0.50), h.quantile(0.99)
    assert 0.0005 < p50 < 0.002
    assert p99 > 0.5
    assert p50 < h.quantile(0.9) < p99      # quantiles stay ordered
    # mergeable: two snapshots of one series sum bucket-wise
    m = telemetry.merge_snapshots([h.snapshot(), h.snapshot()])
    assert m["count"] == 200
    assert telemetry.histogram_quantile(m, 0.5) == pytest.approx(p50)
    # overflow lands above every bound and still reports a number
    h2 = telemetry.Histogram("of", (1.0, 2.0))
    h2.observe(99.0)
    assert h2.quantile(0.5) == 2.0


def test_merge_snapshots_bounds_mismatch_keeps_larger():
    a = telemetry.Histogram("a", (1.0, 2.0))
    b = telemetry.Histogram("b", (1.0, 2.0, 4.0))
    for _ in range(3):
        a.observe(0.5)
    for _ in range(10):
        b.observe(3.0)
    m = telemetry.merge_snapshots([a.snapshot(), b.snapshot()])
    assert m["count"] == 10 and len(m["bounds"]) == 3
    assert telemetry.merge_snapshots([]) is None


def test_registry_get_or_create_and_type_conflict():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c            # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("x")                      # same name, different type
    reg.histogram("h").observe(1.0)
    reg.gauge("g").set(7)
    snap = reg.snapshot()
    assert snap["counters"] == {"x": 0}
    assert snap["gauges"] == {"g": 7}
    assert snap["histograms"]["h"]["count"] == 1


def test_registry_snapshot_prefix_strip_and_clear():
    reg = telemetry.MetricsRegistry()
    reg.counter("Srv::admitted").add(5)
    reg.counter("Other::admitted").add(9)
    snap = reg.snapshot(prefix="Srv::")
    assert snap["counters"] == {"admitted": 5}   # prefix stripped
    snap = reg.snapshot(prefix="Srv::", strip=False)
    assert snap["counters"] == {"Srv::admitted": 5}
    reg.clear(prefix="Srv::")
    assert reg.get("Srv::admitted") is None
    assert reg.get("Other::admitted") is not None


def test_counter_gauge_concurrent_increments():
    c = telemetry.Counter("c")
    g = telemetry.Gauge("g")

    def work():
        for _ in range(1000):
            c.add()
            g.add(2)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 4000
    assert g.value == 8000


# ------------------------------------------------------------ profiler shim --
def test_profiler_counter_shim_shares_one_cell():
    """The satellite contract: profiler.Counter and the telemetry
    registry can never report different values for one series."""
    c = profiler.Counter(None, "TelShim::depth", value=3)
    g = telemetry.registry().get("TelShim::depth")
    assert g is not None and g.value == 3
    c.increment(4)
    assert profiler.counter_value("TelShim::depth") == 7
    assert g.value == 7
    g.add(1)                                # written from either side
    assert profiler.counters("TelShim::")["TelShim::depth"] == 8
    c.decrement(8)
    assert g.value == 0
    # re-creating under the same name resets the shared series
    profiler.Counter(None, "TelShim::depth", value=1)
    assert telemetry.registry().get("TelShim::depth").value == 1


def test_stale_counter_instance_cannot_bleed_into_replacement():
    """A replaced server's background threads keep a detached cell: a
    same-named fresh Counter gets a NEW gauge, so stale increments
    never show on the replacement's live series."""
    old = profiler.Counter(None, "TelStale::n", value=5)
    new = profiler.Counter(None, "TelStale::n", value=0)
    old.increment(100)                       # a draining server's thread
    assert profiler.counter_value("TelStale::n") == 0
    assert telemetry.registry().get("TelStale::n").value == 0
    new.increment(2)
    assert profiler.counter_value("TelStale::n") == 2
    assert old._value == 105                 # old instance still works


def test_counters_clear_drops_both_namespaces():
    profiler.Counter(None, "TelClear::a", value=5)
    profiler.counters_clear("TelClear::")
    assert profiler.counter_value("TelClear::a") is None
    assert telemetry.registry().get("TelClear::a") is None


# --------------------------------------------------------------- JSONL sink --
def test_jsonl_sink_schema_and_rotation(tmp_path):
    p = tmp_path / "events.jsonl"
    sink = telemetry.JsonlSink(p, max_bytes=1000)   # rotates once below
    for i in range(20):
        rec = sink.write("event", "tick", i=i)
        # the shared schema every stream carries
        assert set(rec) >= {"ts", "mono", "kind", "name"}
        assert rec["kind"] == "event" and rec["name"] == "tick"
    sink.close()
    assert (tmp_path / "events.jsonl.1").exists()   # rotated by size
    lines = [json.loads(ln)
             for f in (tmp_path / "events.jsonl.1", p)
             for ln in f.read_text().splitlines()]
    assert len(lines) == 20                  # one rotation loses nothing
    assert all(set(r) >= {"ts", "mono", "kind", "name"} for r in lines)
    # monotonic stamps are non-decreasing in write order
    monos = [r["mono"] for r in sorted(lines, key=lambda r: r["i"])]
    assert monos == sorted(monos)


def test_eventlog_rides_jsonl_sink(tmp_path):
    """The elastic EventLog (and through it the autoscaler log) rides
    JsonlSink: every record now carries the monotonic stamp autoscale
    events previously lacked, and the legacy ``event`` key survives for
    existing parsers."""
    log = elastic.EventLog(tmp_path / "sup.jsonl")
    rec = log.emit("spawn", attempt=1, pids=[1, 2])
    assert rec["event"] == "spawn" and rec["name"] == "spawn"
    assert "mono" in rec and "ts" in rec and rec["kind"] == "event"
    log.close()
    on_disk = json.loads((tmp_path / "sup.jsonl").read_text())
    assert on_disk["event"] == "spawn" and on_disk["attempt"] == 1


# -------------------------------------------------------------- span layer --
def test_manual_trace_tree_audits_clean():
    tr = telemetry.Trace("request", server="S")
    a = tr.open("admit", parent=tr.root)
    a.end()
    q = tr.open("queue", parent=tr.root)
    time.sleep(0.002)
    q.end()
    tr.root.end()
    assert telemetry.audit_spans(tr) == []
    recs = tr.records()
    assert {r["name"] for r in recs} == {"request", "admit", "queue"}
    assert all(r["trace"] == tr.trace_id for r in recs)


def test_audit_flags_unclosed_orphan_and_bad_attribution():
    tr = telemetry.Trace("request", server="S")
    sp = tr.open("queue", parent=tr.root)
    tr.root.end()
    probs = telemetry.audit_spans(tr)        # queue never closed
    assert any("never closed" in p for p in probs)
    sp.end()
    recs = tr.records()
    recs[1]["parent"] = 999999               # orphan parent id
    assert any("does not exist" in p
               for p in telemetry.audit_spans(recs))
    # attribution: a 100 ms root whose children cover ~0 ms fails
    t0 = telemetry.now_us()
    bad = [{"kind": "span", "name": "request", "trace": "t", "span": 1,
            "parent": None, "server": "S", "t0_us": t0,
            "dur_us": 400_000.0, "tid": 1, "attrs": {}, "events": []},
           {"kind": "span", "name": "step", "trace": "t", "span": 2,
            "parent": 1, "server": "S", "t0_us": t0, "dur_us": 10.0,
            "tid": 1, "attrs": {}, "events": []}]
    assert any("attribution" in p for p in telemetry.audit_spans(bad))
    # two roots is a malformed tree
    two = [dict(bad[0]), dict(bad[0], span=2)]
    assert any("exactly 1 root" in p for p in telemetry.audit_spans(two))


def test_off_switch_and_sampling():
    srv = make_server()
    try:
        # dark (never enabled): no trace state is ever allocated
        r = srv.submit(_ex(1))
        r.result(10)
        assert r.trace is None and r.tspans is None
        # sample=0.0: armed but tracing nothing
        telemetry.enable(sample=0.0, collect=True)
        r = srv.submit(_ex(2))
        r.result(10)
        assert r.trace is None
        assert telemetry.finished_traces() == []
        # disable() is the hard off-switch
        telemetry.enable(sample=1.0, collect=True)
        telemetry.disable()
        r = srv.submit(_ex(3))
        r.result(10)
        assert r.trace is None
    finally:
        srv.drain()


def test_suppress_blocks_infrastructure_traces():
    """Fleet quarantine/update probes ride the full serving path but
    are not client requests — inside ``telemetry.suppress()`` a
    front-door submit births no trace (trees == accepted CLIENT
    requests stays exact, and a probe queued into a dead replica can't
    pollute ``queue_ms``)."""
    srv = make_server()
    try:
        telemetry.enable(sample=1.0, collect=True)
        with telemetry.suppress():
            r = srv.submit(_ex(1))
            r.result(10)
        assert r.trace is None and r.tspans is None
        assert telemetry.finished_traces() == []
        r = srv.submit(_ex(2))               # outside: traced again
        r.result(10)
        assert len(telemetry.finished_traces()) == 1
    finally:
        srv.drain()


def test_fleet_probe_requests_are_untraced():
    """The quarantine probe heals a replica without exporting a span
    tree of its own — only client requests count."""
    telemetry.enable(sample=1.0, collect=True)
    fleet = make_fleet(n=2, name="TelProbe")
    fleet.start()
    try:
        fleet.quarantine(0)
        # served by the live replica (fwd is x @ eye(4) — identity)
        out = fleet(np.full((4,), 3.0, np.float32))
        np.testing.assert_allclose(out, np.full((4,), 3.0))
        fleet.readmit(0)
        deadline = time.monotonic() + 10.0
        while fleet.healthz()["replicas"]["r0"]["quarantined"] \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not fleet.healthz()["replicas"]["r0"]["quarantined"]
    finally:
        fleet.drain()
    trees = telemetry.finished_traces()
    assert len(trees) == 1                   # the client request only
    assert trees[0].server == "TelProbe"


def test_off_switch_guard_cost_is_tiny():
    """The off path is one module attribute read + branch; even a noisy
    CI machine clears 2 µs/check by orders of magnitude."""
    assert telemetry.guard_cost(50_000) < 2e-6


def test_tracer_failure_never_fails_a_request():
    class PoisonSink(telemetry.JsonlSink):
        def __init__(self):
            super().__init__(None)

        def write(self, *a, **k):
            raise RuntimeError("sink wedged")

    telemetry.enable(sink=PoisonSink(), collect=True)
    before = telemetry.config().errors
    srv = make_server()
    try:
        out = srv(_ex(5))                    # resolves despite the sink
        np.testing.assert_allclose(out, np.full((3,), 10.0))
    finally:
        srv.drain()
    assert telemetry.config().errors > before
    assert len(telemetry.finished_traces()) >= 1   # trace still kept


# ------------------------------------------------- end-to-end span trees --
def test_inference_server_span_tree_and_exposition():
    telemetry.enable(collect=True)
    srv = make_server(name="TelSrv")
    try:
        reqs = [srv.submit(_ex(i)) for i in range(8)]
        for r in reqs:
            r.result(10)
    finally:
        srv.drain()
    traces = telemetry.finished_traces()
    assert len(traces) == 8                  # every accepted request
    for tr in traces:
        assert telemetry.audit_spans(tr) == []
        names = [sp.name for sp in tr.spans]
        assert names[0] == "request"
        assert {"admit", "queue", "coalesce", "step"} <= set(names)
        step = next(sp for sp in tr.spans if sp.name == "step")
        assert step.attrs["batch"] >= 1
    # span durations fed the per-phase histograms the exposition serves
    pay = srv.telemetry()
    assert pay["schema"] == telemetry.SCHEMA
    assert pay["histograms"]["queue_ms"]["count"] == 8
    assert pay["counters"]["completed"] == 8
    # the per-class cumulative latency series rides the histograms map
    cls = pay["histograms"]["class_default_latency_s"]
    assert cls["count"] == 8
    assert list(cls["bounds"]) == list(telemetry.LATENCY_BUCKETS_S)
    prom = srv.telemetry("prom")
    assert 'mxtpu_completed_total{kind="inference_server"' in prom
    assert "_bucket{" in prom and 'le="+Inf"' in prom
    with pytest.raises(ValueError):
        srv.telemetry("xml")


@chaos
def test_failed_request_tree_closes_with_fault_event():
    telemetry.enable(collect=True)
    srv = make_server(name="TelFail")
    try:
        with fault.inject("serving.step", RuntimeError("boom"), times=1):
            r = srv.submit(_ex(1))
            with pytest.raises(RuntimeError):
                r.result(10)
    finally:
        srv.drain()
    traces = telemetry.finished_traces()
    assert len(traces) == 1
    tr = traces[0]
    assert telemetry.audit_spans(tr) == []   # error paths still close
    assert tr.root.attrs.get("error") == "RuntimeError"
    # the fault firing landed as a span event on the in-flight step span
    step = next(sp for sp in tr.spans if sp.name == "step")
    assert any(ev["name"] == "fault"
               and ev["attrs"]["point"] == "serving.step"
               for ev in step.events)


@pytest.mark.parametrize("prefill_workers", [0, 1],
                         ids=["fused", "disaggregated"])
def test_generation_server_span_tree(prefill_workers):
    telemetry.enable(collect=True)
    srv = make_genserver(prefill_workers=prefill_workers)
    srv.start()
    try:
        reqs = [srv.submit(np.array([5, 6, 7], np.int32),
                           max_new_tokens=4) for _ in range(4)]
        for r in reqs:
            r.result(60)
    finally:
        srv.drain()
    traces = telemetry.finished_traces()
    assert len(traces) == 4
    want = {"admit", "queue", "prefill", "decode"}
    if prefill_workers:
        want.add("handoff")                  # the disaggregated hop
    for tr in traces:
        assert telemetry.audit_spans(tr) == []
        names = {sp.name for sp in tr.spans}
        assert want <= names
        pre = next(sp for sp in tr.spans if sp.name == "prefill")
        assert "worker" in pre.attrs         # who ran the prefill
        if prefill_workers:
            assert "prefill-w" in pre.attrs["worker"]
        dec = next(sp for sp in tr.spans if sp.name == "decode")
        assert dec.attrs["tokens"] == 4 and "slot" in dec.attrs
    pay = srv.telemetry()
    assert pay["kind"] == "generation_server"
    assert pay["histograms"]["decode_ms"]["count"] == 4
    assert pay["counters"]["retired"] == 4


def test_fleet_failover_spans_carry_replica_names():
    telemetry.enable(collect=True)
    fleet = make_fleet(n=3, name="TelFleet")
    fleet.start()
    try:
        for i in range(4):
            fleet.submit(np.full((4,), float(i), np.float32)).result(10)
        fleet.apply_fns[0].fail = True       # wedge r0 → failover hops
        reqs = [fleet.submit(np.ones((4,), np.float32))
                for _ in range(6)]
        for r in reqs:
            r.result(10)
    finally:
        fleet.drain()
    traces = telemetry.finished_traces()
    assert len(traces) == 10
    hopped = []
    for tr in traces:
        assert telemetry.audit_spans(tr) == []
        names = [sp.name for sp in tr.spans]
        assert names.count("request") == 1
        # replica-side phases nest under the fleet's dispatch span
        for sp in tr.spans:
            if sp.name in ("queue", "coalesce", "step"):
                parent = next(p for p in tr.spans
                              if p.sid == sp.parent_id)
                assert parent.name == "dispatch"
            if sp.name == "dispatch":
                assert sp.attrs["replica"].startswith("r")
        if "failover" in names:
            hopped.append(tr)
    assert hopped                            # the wedge forced re-dispatch
    fo = next(sp for sp in hopped[0].spans if sp.name == "failover")
    assert fo.attrs["from_replica"] == "r0"
    # fleet exposition aggregates replicas under one schema
    pay = fleet.telemetry()
    assert pay["kind"] == "serving_fleet"
    assert pay["counters"]["replica_completed"] == 10
    # one queue span per completed request, plus one per failed hop —
    # the fleet-wide distribution lives under the FLEET's exposition
    assert pay["histograms"]["queue_ms"]["count"] >= 10


# -------------------------------------------------------------- exposition --
def test_exposition_schema_is_uniform_across_runtimes(tmp_path):
    telemetry.enable()
    srv = make_server(name="TelUni")
    fleet = make_fleet(n=1, name="TelUniFleet")
    fleet.start()
    scaler = FleetAutoscaler(fleet, ScalingPolicy(max_replicas=2),
                             event_log=tmp_path / "as.jsonl")
    sup = elastic.Supervisor(["true"], 1)
    try:
        payloads = [srv.telemetry(), fleet.telemetry(),
                    scaler.telemetry(), sup.telemetry()]
        keys = [tuple(sorted(p)) for p in payloads]
        assert len(set(keys)) == 1           # identical key schemas
        kinds = {p["kind"] for p in payloads}
        assert kinds == {"inference_server", "serving_fleet",
                         "fleet_autoscaler", "supervisor"}
        # the ISSUE 15 gauge families ride EVERY runtime's exposition
        # with identical keys (compile-cache behavior + stamped memory)
        families = {"compile_executables", "compile_cache_hits",
                    "compile_cache_misses", "compile_ms_total",
                    "recompiles_unexpected", "mem_argument_bytes",
                    "mem_peak_bytes", "mem_per_device_argument_bytes",
                    "mem_per_device_peak_bytes"}
        for p in payloads:
            assert p["schema"] == telemetry.SCHEMA
            assert families <= set(p["gauges"]), p["kind"]
            # every payload renders to prometheus text
            text = telemetry.render_prometheus(p)
            assert f'kind="{p["kind"]}"' in text
    finally:
        fleet.drain()
        srv.drain()


def test_merge_payloads_sums_and_merges():
    h = telemetry.Histogram("x", (1.0, 2.0))
    h.observe(0.5)
    a = telemetry.exposition("s", "a", {"done": 2}, {"depth": 3},
                             {"lat": h.snapshot()})
    b = telemetry.exposition("s", "b", {"done": 5}, {"depth": 4},
                             {"lat": h.snapshot()})
    m = telemetry.merge_payloads([a, b])
    assert m["counters"]["done"] == 7
    assert m["gauges"]["depth"] == 7
    assert m["histograms"]["lat"]["count"] == 2


def test_classstats_rehosted_on_histogram():
    cs = ClassStats()
    snap = cs.snapshot()
    assert snap["p50_ms"] is None            # empty
    for _ in range(90):
        cs.observe(0.010, "completed", False)
    for _ in range(10):
        cs.observe(1.0, "completed", True)
    snap = cs.snapshot()
    assert snap["completed"] == 100 and snap["deadline_miss"] == 10
    assert 5.0 < snap["p50_ms"] < 20.0
    assert snap["p99_ms"] > 500.0
    # the mergeable form rides the same fixed bucket layout
    m = telemetry.merge_snapshots([cs.latency_snapshot(),
                                   cs.latency_snapshot()])
    assert m["count"] == 200
    # healthz quantiles are sliding-window: after the incident ages out
    # of the window, p99 decays (routers see CURRENT behaviour) while
    # the cumulative exposition histogram keeps the full history
    for _ in range(256):
        cs.observe(0.010, "completed", False)
    snap = cs.snapshot()
    assert snap["p99_ms"] < 100.0
    assert cs.latency_snapshot()["count"] == 356


# -------------------------------------------- Chrome-trace export validity --
def test_chrome_trace_validity_and_jsonl_roundtrip(tmp_path):
    """The satellite: profiler.dump() with profiler spans + counters +
    trace export all active parses as JSON with well-formed events and
    per-tid monotonic ``ts``; the JSONL sink round-trips the span
    trees."""
    trace_path = tmp_path / "trace.json"
    jsonl_path = tmp_path / "spans.jsonl"
    telemetry.enable(sink=jsonl_path, collect=True)
    profiler.set_config(filename=str(trace_path))
    profiler.start()
    try:
        c = profiler.Counter(None, "TelChrome::tick")
        srv = make_server(name="TelChrome")
        try:
            reqs = [srv.submit(_ex(i)) for i in range(6)]
            for r in reqs:
                c.increment()
                r.result(10)
        finally:
            srv.drain()
    finally:
        profiler.stop()
    profiler.dump()
    telemetry.config().sink.close()

    payload = json.loads(trace_path.read_text())  # parses as JSON
    events = payload["traceEvents"]
    # the program's scopes land in the Chrome buffer under their own
    # names, once (request trees go to the JSONL sink, not here)
    steps = [e for e in events if e.get("name") == "TelChrome.step"]
    assert steps and all(e["cat"] == "serving" for e in steps)
    assert "trace" not in {e.get("cat") for e in events}
    by_tid = {}
    for e in events:
        assert e["ph"] in ("X", "C", "i", "B", "E")
        assert "pid" in e and "ts" in e
        if e["ph"] == "X":
            assert "tid" in e and e["dur"] >= 0
            by_tid.setdefault(e["tid"], []).append(e["ts"])
        if e["ph"] == "C":
            assert "value" in e["args"]
    assert any(e["ph"] == "C" for e in events)    # counters present
    for ts_list in by_tid.values():          # ts monotonic per tid
        assert ts_list == sorted(ts_list)

    # JSONL round-trip reconstructs every span tree
    assert telemetry.audit_jsonl(jsonl_path) == {}
    trees = telemetry.read_spans(jsonl_path)
    scoped = {sp.trace.trace_id for sp in telemetry.scope_spans()}
    assert {e["name"] for e in steps} == {
        sp.name for sp in telemetry.scope_spans()}
    assert len(steps) == len(scoped)
    live = {tr.trace_id: tr for tr in telemetry.finished_traces()}
    assert set(trees) - scoped == set(live)
    trees = {tid: recs for tid, recs in trees.items() if tid in live}
    for tid, recs in trees.items():
        assert len(recs) == len(live[tid].spans)
        ids = {r["span"] for r in recs}
        assert all(r["parent"] is None or r["parent"] in ids
                   for r in recs)


def test_scope_keeps_out_of_chrome_buffer_while_profiler_is_off():
    """A scope feeds mx.profiler's Chrome buffer only while mx.profiler
    runs — its span still reaches the telemetry store — so an idle
    profiler never grows a dead buffer."""
    telemetry.enable(collect=True)
    profiler.reset()
    srv = make_server(name="TelNoProf")
    try:
        srv(_ex(1))
    finally:
        srv.drain()
    assert telemetry.finished_traces()
    assert [sp.name for sp in telemetry.scope_spans()] == ["TelNoProf.step"]
    assert not profiler._P.events


# ================================================== ISSUE 15: introspection --
# Compile-event stream, live memory gauges, training-step spans, and the
# crash flight recorder.

def _tiny_train_step(heartbeat=None, **kw):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    mx.random.seed(9)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=4),
            nn.Dense(2, in_units=8))
    net.initialize()
    return parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1),
        heartbeat=heartbeat, **kw)


def test_compile_stream_generation_census_and_jit_cache():
    """The acceptance contract: one compile event per executable — the
    site's miss count equals the static census AND the runtime jit-cache
    count, before and after full-grid traffic; traffic itself only
    records hits."""
    telemetry.enable(collect=True)
    srv = GenerationServer(PARAMS, CFG,
                           buckets=BucketSpec(batch=(1, 2),
                                              length=(8, 16)),
                           n_slots=2, n_pages=33, page_size=4,
                           max_new_tokens=4, seed=0, name="CensusGen")
    try:
        srv.start()
        st = telemetry.compile_site_stats("CensusGen")
        assert st["misses"] == srv.census() == srv.jit_cache_count()
        assert st["pinned"] == srv.census()
        # full-grid traffic: both length buckets, batched pairs
        reqs = [srv.submit(np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=3)
                for n in (3, 3, 12, 12)]
        for r in reqs:
            r.result(60)
    finally:
        srv.drain()
    st = telemetry.compile_site_stats("CensusGen")
    assert st["misses"] == srv.census() == srv.jit_cache_count()
    assert st["hits"] > 0                      # the steady state
    assert st["unexpected"] == 0
    assert st["ms_total"] > 0
    # one event RECORD per executable, each carrying the site cache size
    evs = [e for e in telemetry.compile_events()
           if e["site"] == "CensusGen"]
    assert len(evs) == srv.census()
    assert evs[-1]["n_executables"] == srv.census()


def test_compile_stream_signature_fallback_and_unexpected_recompile():
    """A server over an opaque apply fn tracks compiles by dispatched
    signature; a post-warmup NEW signature (pin_signature=False) is an
    unexpected recompile — counted, never silent."""
    telemetry.enable()
    srv = make_server(name="SigComp", pin_signature=False)
    try:
        st = telemetry.compile_site_stats("SigComp")
        assert st["misses"] == 3               # warmup grid: b1/b2/b4
        assert st["pinned"] == 3
        srv(_ex(1))                            # known signature: a hit
        st = telemetry.compile_site_stats("SigComp")
        assert st["hits"] >= 1 and st["misses"] == 3
        assert st["unexpected"] == 0
        srv(_ex(1, n=5))                       # foreign shape compiles
    finally:
        srv.drain()
    st = telemetry.compile_site_stats("SigComp")
    assert st["misses"] == 4
    assert st["unexpected"] == 1
    assert telemetry.registry().get(
        "compile::recompiles_unexpected").value == 1


def test_fleet_hotswap_compile_events_share_the_jit_cache():
    """Replica warmups against the fleet's ONE shared HotSwapApply jit
    fn must not fabricate compile events: replica 0 records the real
    compiles, its siblings record hits."""
    telemetry.enable()
    fleet = make_fleet(n=3, name="CompFleet")
    fleet.start()
    try:
        r0 = telemetry.compile_site_stats("CompFleet-r0")
        assert r0["misses"] == 3               # the real grid compiles
        for i in (1, 2):
            ri = telemetry.compile_site_stats(f"CompFleet-r{i}")
            assert ri["misses"] == 0           # shared cache absorbed it
            assert ri["hits"] == 3
    finally:
        fleet.drain()


def test_costguard_entrypoint_builds_emit_census_events():
    """The committed-entrypoint half of the acceptance contract: a
    builder's compile events == its census == its program count."""
    from tools.costguard import entrypoints

    telemetry.enable()
    for entry in ("serving_mlp_grid", "mlp_apply_tp1"):
        eb = entrypoints.build(entry)
        st = telemetry.compile_site_stats(f"costguard::{entry}")
        assert st["misses"] == eb.census == len(eb.programs), entry


def test_trainstep_step_spans_and_compile_events():
    telemetry.enable(collect=True)
    step = _tiny_train_step()
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
    y = np.zeros((16,), np.int32)
    for _ in range(3):
        step(x, y).asnumpy()
    st = telemetry.compile_site_stats("TrainStep")
    assert st["misses"] == 1 and st["hits"] == 2
    assert telemetry.finished_traces() == []      # no request was served
    roots = telemetry.scope_spans("TrainStep.step")
    assert len(roots) == 3
    for i, root in enumerate(roots):
        tr = root.trace
        assert telemetry.audit_spans(tr) == []
        assert root.attrs["num_update"] == i + 1
        names = [sp.name for sp in tr.spans if sp.parent_id == root.sid]
        # the first call makes the parameters initialize() recorded and
        # compiles, later ones dispatch: a span that ends when the dispatch
        # returns is not called compute
        assert names == (["TrainStep.deferred_init", "TrainStep.state_init",
                          "TrainStep.h2d", "TrainStep.compile"] if i == 0
                         else ["TrainStep.h2d", "TrainStep.dispatch"])
    snap = telemetry.registry().snapshot()
    assert snap["histograms"]["TrainStep.step_ms"]["count"] == 3
    assert snap["histograms"]["TrainStep.dispatch_ms"]["count"] == 2
    assert "TrainStep::feed_wait_ms" not in snap["histograms"]


def test_trainstep_steps_untraced_when_dark():
    step = _tiny_train_step()
    x = np.zeros((16, 4), np.float32)
    y = np.zeros((16,), np.int32)
    step(x, y).asnumpy()
    assert telemetry.finished_traces() == []
    assert telemetry.compile_site_stats("TrainStep")["misses"] == 0


def test_heartbeat_carries_step_fields(tmp_path):
    hb = elastic.Heartbeat(tmp_path, rank=0, every_n_steps=50)
    rec = hb.beat(1, last_step_ms=12.5)
    assert rec["last_step_ms"] == 12.5
    assert rec["compile_in_progress"] is False
    # the compile flag flipping ALWAYS writes, whatever the cadence
    rec = hb.beat(1, compile_in_progress=True)
    assert rec is not None and rec["compile_in_progress"] is True
    rec = hb.beat(2, last_step_ms=800.0)
    assert rec is not None and rec["compile_in_progress"] is False
    # steady state: the 50-step cadence thins unchanged-flag beats out
    assert hb.beat(3, last_step_ms=1.0) is None
    on_disk = elastic.read_heartbeats(tmp_path)[0]
    assert on_disk["last_step_ms"] == 800.0


def test_trainstep_heartbeat_gains_step_time_and_compile_flag(tmp_path):
    hb = elastic.Heartbeat(tmp_path, rank=0)
    step = _tiny_train_step(heartbeat=hb)
    x = np.zeros((16, 4), np.float32)
    y = np.zeros((16,), np.int32)
    step(x, y).asnumpy()
    rec = elastic.read_heartbeats(tmp_path)[0]
    assert rec["last_step_ms"] is not None and rec["last_step_ms"] > 0
    assert rec["compile_in_progress"] is False   # cleared post-compile


def test_supervisor_step_ms_histogram_and_exposition():
    sup = elastic.Supervisor(["true"], 1)
    sup._note_heartbeat(0, {"last_step_ms": 10.0, "global_step": 5})
    sup._note_heartbeat(0, {"last_step_ms": 10.0, "global_step": 5})
    sup._note_heartbeat(1, {"last_step_ms": 30.0, "global_step": 5})
    sup._note_heartbeat(0, {"last_step_ms": 20.0, "global_step": 6})
    p = sup.telemetry()
    assert p["histograms"]["step_ms"]["count"] == 3   # dupe folded once
    assert "compiling_workers" in p["gauges"]
    assert "compile_executables" in p["gauges"]       # uniform families
    assert "mem_peak_bytes" in p["gauges"]


def test_memory_report_stamps_exposition_gauges():
    telemetry.enable()
    report = {"argument_bytes": 1000, "peak_bytes": 2000,
              "per_device": {"argument_bytes": 125, "peak_bytes": 250}}
    srv = make_genserver(memory_report=report, name="MemGen")
    srv.start()
    try:
        g = srv.telemetry()["gauges"]
        assert g["mem_argument_bytes"] == 1000
        assert g["mem_per_device_argument_bytes"] == 125
        srv.stamp_memory_report({"argument_bytes": 7})
        g = srv.telemetry()["gauges"]
        assert g["mem_argument_bytes"] == 7
        assert g["mem_peak_bytes"] == 0        # unstamped keys stay, zero
    finally:
        srv.drain()


def test_generation_exposition_carries_registry_gauges_and_slot_pages():
    """The ISSUE 15 satellite fix: page_occupancy/tokens_out (profiler
    counter series) are visible in telemetry() as gauges, and per-slot
    page occupancy lands in the slot_pages histogram at retirement."""
    telemetry.enable()
    srv = make_genserver(name="PageGen")
    srv.start()
    try:
        srv.submit(np.array([1, 2, 3], np.int32),
                   max_new_tokens=3).result(30)
        pay = srv.telemetry()
        assert pay["gauges"]["tokens_out"] == pay["counters"]["tokens_out"]
        assert "page_occupancy" in pay["gauges"]
        assert pay["gauges"]["used_pages"] == 0        # retired: freed
        snap = pay["histograms"]["slot_pages"]
        assert snap["count"] == 1                      # one retirement
        assert snap["sum"] >= 1                        # held >= 1 page
    finally:
        srv.drain()


def test_generation_salvage_counters_ride_exposition():
    """ISSUE 19 satellite: the salvage/resume counter family rides the
    GenerationServer exposition under the SAME snake_case key schema as
    every other counter, and the resume-prefill page-remap gauge is
    present (zero included) — dashboards never probe for optional
    keys."""
    import re
    telemetry.enable()
    srv = make_genserver(name="SalvTel")
    srv.start()
    try:
        with fault.inject("generate.decode", RuntimeError("injected"),
                          times=1):
            srv.submit(np.array([1, 2, 3], np.int32),
                       max_new_tokens=4).result(60)
        pay = srv.telemetry()
        ctr = pay["counters"]
        for key in ("tokens_salvaged", "resumes", "salvage_retries",
                    "journal_restores"):
            assert key in ctr, key
            assert re.fullmatch(r"[a-z][a-z0-9_]*", key)
        assert ctr["tokens_salvaged"] >= 1 and ctr["resumes"] >= 1
        assert ctr["salvage_retries"] == 1
        assert ctr["journal_restores"] == 0
        assert "resume_prefill_pages_remapped" in pay["gauges"]
        text = telemetry.render_prometheus(pay)
        assert "tokens_salvaged" in text
        assert "resume_prefill_pages_remapped" in text
    finally:
        srv.drain()


# ------------------------------------------------------------ flight recorder
def test_flight_ring_is_bounded():
    fl = telemetry.flight()
    fl.configure(limit=4, enabled=True)
    for i in range(10):
        fl.record("x", str(i))
    names = [r["name"] for r in fl.records()]
    assert names == ["6", "7", "8", "9"]


def test_flight_dump_bundle_roundtrips_through_audit(tmp_path):
    """The bundle is ONE JSONL file: header, ring (complete span trees
    only), metrics snapshot — and audit_jsonl applies to it unchanged."""
    telemetry.enable(collect=True)
    telemetry.enable_flight(directory=tmp_path, limit=4096)
    srv = make_server(name="FlightSrv")
    try:
        for i in range(4):
            srv(_ex(i))
    finally:
        srv.drain()
    telemetry.compile_event("FlightSite", key="k", ms=1.0)
    path = telemetry.flight().dump(reason="test-dump")
    assert path is not None and path.startswith(str(tmp_path))
    assert telemetry.audit_jsonl(path) == {}
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert recs[0]["kind"] == "flight" and recs[0]["reason"] == "test-dump"
    kinds = {r["kind"] for r in recs}
    assert {"flight", "span", "compile", "metrics"} <= kinds
    trees = telemetry.read_spans(path)
    requests = [t for t in trees.values()
                if any(r["name"] == "request" for r in t)]
    assert len(requests) == 4                         # all four trees
    # beside them the device steps' own scope trees, each naming the
    # requests it served
    steps = [t for t in trees.values() if t[0]["name"] == "FlightSrv.step"]
    assert len(steps) == 4 and len(requests) + len(steps) == len(trees)
    assert {s[0]["attrs"]["traces"][0] for s in steps} == {
        r["trace"] for t in requests for r in t}
    # the metrics snapshot is the LAST line and carries the registry
    assert recs[-1]["kind"] == "metrics"
    assert "gauges" in recs[-1]


def test_flight_dump_drops_rootless_trace_tails(tmp_path):
    """Span records whose trace root was evicted from the ring must not
    reach the bundle — a half tree would fail the audit the bundle
    exists to pass."""
    telemetry.enable(collect=True)
    srv = make_server(name="EvictSrv")
    try:
        srv(_ex(1))
    finally:
        srv.drain()
    tr = [t for t in telemetry.finished_traces()
          if t.server == "EvictSrv"][0]
    fl = telemetry.flight()
    fl.configure(directory=tmp_path, limit=len(tr.spans) - 1,
                 enabled=True)
    for rec in tr.records():                   # root evicted by the tail
        rec.pop("kind")
        fl.record("span", rec.pop("name"), **rec)
    path = fl.dump(reason="evict-test")
    assert telemetry.read_spans(path) == {}    # rootless tail dropped
    assert telemetry.audit_jsonl(path) == {}


def test_breaker_open_trips_flight_dump(tmp_path):
    telemetry.enable()
    telemetry.enable_flight(directory=tmp_path)
    srv = make_server(name="TripSrv",
                      breaker=CircuitBreaker(threshold=2, base_delay=5.0))
    try:
        with fault.inject("serving.step", RuntimeError("dead device")):
            for _ in range(2):
                with pytest.raises(RuntimeError):
                    srv(_ex(1), timeout=10)
    finally:
        srv.drain()
    path = telemetry.flight().last_path
    assert path is not None
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert recs[0]["reason"] == "breaker-open"
    # the fault firings that killed the replica are on the record
    assert any(r["kind"] == "fault" and r["name"] == "serving.step"
               for r in recs)


def test_nonfinite_abort_trips_flight_dump(tmp_path):
    telemetry.enable(collect=True)          # the dying step is traced
    # a ring of its own size: the eviction test above leaves the shared
    # recorder at four entries, one fewer than a first step's spans
    telemetry.enable_flight(directory=tmp_path, limit=4096)
    step = _tiny_train_step(skip_nonfinite=True, nonfinite_budget=1)
    x = np.full((16, 4), np.nan, np.float32)
    y = np.zeros((16,), np.int32)
    with pytest.raises(elastic.NonFiniteAbortError):
        step(x, y)
    path = telemetry.flight().last_path
    assert path is not None
    header = json.loads(open(path).readline())
    assert header["reason"] == "nonfinite-abort"
    assert header["consecutive_skips"] == 1
    # the bundle contains the spans of the very step that DIED (the
    # review-pass regression: an aborting traced step leaked its open
    # trace, so the post-mortem documented every step except the fatal
    # one), marked with the abort error
    recs = [json.loads(line) for line in open(path)]
    fatal = [r for r in recs if r.get("kind") == "span"
             and r.get("name") == "TrainStep.step"
             and r.get("attrs", {}).get("error") == "NonFiniteAbortError"]
    assert fatal, [r.get("name") for r in recs]


def test_flight_dump_survives_concurrent_ring_feeds(tmp_path):
    """record() and dump() are lock-serialized: a concurrent append
    mid-snapshot must never cost the dying process its bundle (the
    'deque mutated during iteration' review-pass regression)."""
    fl = telemetry.flight()
    fl.configure(directory=tmp_path, limit=256, enabled=True)
    stop = threading.Event()

    def feeder():
        i = 0
        while not stop.is_set():
            fl.record("x", str(i))
            i += 1

    t = threading.Thread(target=feeder)
    t.start()
    try:
        paths = [fl.dump(reason="stress") for _ in range(50)]
    finally:
        stop.set()
        t.join()
    assert all(p is not None for p in paths)


def test_enable_flight_resets_trip_coalescing(tmp_path):
    """Re-arming the recorder is a fresh episode: the 1-second
    same-reason coalesce window from a PREVIOUS episode must not
    swallow the new episode's first trip."""
    telemetry.enable_flight(directory=tmp_path)
    p1 = telemetry.flight_trip("same-reason")
    assert p1 is not None
    telemetry.flight().enabled = False
    telemetry.enable_flight(directory=tmp_path)
    p2 = telemetry.flight_trip("same-reason")   # within 1s of p1
    assert p2 is not None and p2 != p1


def test_graceful_exit_trips_flight_dump(tmp_path):
    import os
    import signal

    telemetry.enable_flight(directory=tmp_path)
    with fault.GracefulExit() as g:
        if not g.enabled:
            pytest.skip("not on the main thread")
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5
        while not g.requested and time.monotonic() < deadline:
            time.sleep(0.01)
    assert g.requested
    # the dump runs on a short-lived thread, NOT in the signal handler
    # (lock re-entrance would deadlock the snapshot-then-exit path)
    deadline = time.monotonic() + 5
    while telemetry.flight().last_path is None \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    path = telemetry.flight().last_path
    assert path is not None
    header = json.loads(open(path).readline())
    assert header["reason"] == "graceful-exit"
    assert header["signum"] == int(signal.SIGTERM)


def test_flight_dump_never_raises(tmp_path):
    fl = telemetry.flight()
    fl.configure(directory=tmp_path, enabled=True)
    fl.record("x", "y")
    # an unwritable target must yield None, not an exception — the
    # recorder runs in dying processes (the failure-matrix contract)
    assert fl.dump(reason="r", path="/nonexistent-dir/nope/f.jsonl") \
        is None
    assert fl.dump(reason="r") is not None     # and stays functional


def test_flight_disabled_is_inert(tmp_path):
    fl = telemetry.flight()
    assert fl.enabled is False
    fl.record("x", "y")
    assert fl.records() == []
    assert telemetry.flight_trip("anything") is None


def test_lazy_generation_server_compiles_are_not_unexpected():
    """A warmup=False server compiles lazily by choice: nothing is
    pinned at start, so bring-up compiles must stay ordinary events
    (the review-pass regression: pinning outside the warmup branch
    froze the census at 0 and flagged every lazy compile)."""
    telemetry.enable()
    srv = make_genserver(name="LazyGen")
    srv.start(warmup=False)
    try:
        srv.submit(np.array([1, 2, 3], np.int32),
                   max_new_tokens=2).result(60)
    finally:
        srv.drain()
    st = telemetry.compile_site_stats("LazyGen")
    assert st["misses"] > 0                    # the lazy compiles
    assert st["pinned"] is None
    assert st["unexpected"] == 0


def test_failed_new_signature_dispatch_records_no_phantom_compile():
    """Probe-less signature tracking: a dispatch of a NEW signature
    that RAISES proves no executable exists — recording the assumed
    miss would double-count every retry until one succeeds."""
    telemetry.enable()
    srv = make_server(name="PhantomSrv", pin_signature=False)
    try:
        assert telemetry.compile_site_stats("PhantomSrv")["misses"] == 3
        with fault.inject("serving.step", RuntimeError("transient")):
            with pytest.raises(RuntimeError):
                srv(_ex(1, n=5), timeout=10)   # new shape, step fails
        st = telemetry.compile_site_stats("PhantomSrv")
        assert st["misses"] == 3               # no phantom event
        assert st["unexpected"] == 0
        srv(_ex(1, n=5))                       # now it really compiles
    finally:
        srv.drain()
    st = telemetry.compile_site_stats("PhantomSrv")
    assert st["misses"] == 4
    assert st["unexpected"] == 1               # past the pinned census


def test_compile_events_registry_counter_counts_misses_only():
    telemetry.enable()
    telemetry.compile_event("EvSite", key="a", ms=1.0)
    telemetry.compile_event("EvSite", key="a", cache_hit=True)
    telemetry.compile_event("EvSite", key="a", cache_hit=True)
    reg = telemetry.registry()
    assert reg.get("compile::events").value == 1
    assert reg.get("compile::cache_hits").value == 2
    assert telemetry.compile_stats()["events"] == 1
