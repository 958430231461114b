"""The program's one span path (ISSUE 25): ``profiler.scope`` is a
``jax.profiler.TraceAnnotation`` under anyone's capture, a ``telemetry.Span``
of the same name while tracing is armed, and a Chrome-buffer event while
``mx.profiler`` runs; device programs carry ``jax.named_scope`` names and
every Pallas kernel its ``name=``; ``compile_stats()`` counts jax's own
compile and cache events with telemetry never enabled."""
import glob
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, gluon, parallel, profiler, telemetry
from mxnet_tpu.gluon import nn

STEPS = 6
# an annotation opens before and closes after the span it shares a ``with``
# with, and both clocks are read from Python
SLACK_US = 2_000.0


@pytest.fixture(autouse=True)
def _clean():
    telemetry.disable()
    telemetry.reset_compiles()
    telemetry.enable(collect=True)       # fresh, empty stores
    telemetry.disable()
    yield
    telemetry.disable()


def _tiny_step(in_units=4, **kw):
    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu", in_units=in_units),
            nn.Dense(2, in_units=8))
    net.initialize()
    return parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.1), **kw)


def _batches(n):
    x = np.zeros((16, 4), np.float32)
    y = np.zeros((16,), np.int32)
    return [(x, y)] * n


def _feed_and_step(step, n):
    with parallel.DevicePrefetcher(_batches(n), step=step, depth=2) as feed:
        for x, y in feed:
            step(x, y).asnumpy()
        return dict(feed.stats)


def _host_lines(logdir):
    """[(line, [(name, start_us, dur_us)])] of the host plane's threads."""
    from jax.profiler import ProfileData
    path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name == "/host:CPU")
    return [(i, [(e.name, e.start_ns / 1e3, e.duration_ns / 1e3)
                 for e in line.events])
            for i, line in enumerate(plane.lines)]


@pytest.fixture(scope="module")
def foreign_trace(tmp_path_factory):
    """A DevicePrefetcher-fed TrainStep under a capture that ``mx.profiler``
    knows nothing of, with telemetry collecting beside it."""
    logdir = str(tmp_path_factory.mktemp("foreign"))
    step = _tiny_step()
    _feed_and_step(step, 1)              # compile outside the capture
    telemetry.enable(collect=True)
    try:
        jax.profiler.start_trace(logdir)
        try:
            stats = _feed_and_step(step, STEPS)
        finally:
            jax.profiler.stop_trace()
        spans = telemetry.scope_spans()
    finally:
        telemetry.disable()
    assert not profiler._P.active        # mx.profiler never ran
    return {"lines": _host_lines(logdir), "spans": spans, "stats": stats}


def _line_of(lines, name):
    found = {i for i, events in lines for n, _, _ in events if n == name}
    assert len(found) == 1, (name, found)
    return found.pop()


def test_foreign_trace_threads(foreign_trace):
    lines = foreign_trace["lines"]
    consumer = _line_of(lines, "DevicePrefetcher.consumer_wait")
    producer = _line_of(lines, "DevicePrefetcher.device_put")
    assert producer != consumer
    assert _line_of(lines, "DevicePrefetcher.producer_wait") == producer
    for name in ("TrainStep.step", "TrainStep.h2d", "TrainStep.dispatch"):
        assert _line_of(lines, name) == consumer


def test_foreign_trace_nesting(foreign_trace):
    events = dict(foreign_trace["lines"])[
        _line_of(foreign_trace["lines"], "TrainStep.step")]
    steps = [e for e in events if e[0] == "TrainStep.step"]
    assert len(steps) == STEPS
    for _, t0, dur in steps:
        inside = [n for n, s, d in events
                  if n.startswith("TrainStep.") and n != "TrainStep.step"
                  and t0 <= s and s + d <= t0 + dur]
        assert inside == ["TrainStep.h2d", "TrainStep.dispatch"]
    waits = [e for e in events if e[0] == "DevicePrefetcher.consumer_wait"]
    assert len(waits) == STEPS + 1       # the last one meets the end
    for _, w0, wd in waits:              # a wait is never inside a step
        assert not any(t0 < w0 + wd and w0 < t0 + dur for _, t0, dur in steps)


@pytest.mark.parametrize("name", [
    "TrainStep.step", "TrainStep.h2d", "TrainStep.dispatch",
    "DevicePrefetcher.consumer_wait", "DevicePrefetcher.device_put",
    "DevicePrefetcher.producer_wait"])
def test_memory_spans_match_annotations(foreign_trace, name):
    """Same names, same count, and durations within the stated slack."""
    mem = [sp for sp in foreign_trace["spans"] if sp.name == name]
    ann = [e for _, events in foreign_trace["lines"] for e in events
           if e[0] == name]
    assert len(mem) == len(ann) > 0
    over = [dur - sp.dur_us for sp, (_, _, dur) in zip(
        sorted(mem, key=lambda s: s.t0), sorted(ann, key=lambda e: e[1]))]
    # the annotation encloses the span; a thread switched out between the
    # two stamps can widen one pair, not the median
    assert min(over) >= -50.0 and np.median(over) <= SLACK_US, over
    assert max(over) <= 50 * SLACK_US, over


def test_memory_span_parents_and_threads(foreign_trace):
    spans = foreign_trace["spans"]
    by_id = {sp.sid: sp for sp in spans}
    for sp in spans:
        if sp.name in ("TrainStep.h2d", "TrainStep.dispatch"):
            assert by_id[sp.parent_id].name == "TrainStep.step"
            assert by_id[sp.parent_id].tid == sp.tid
        else:
            assert sp.parent_id is None
    tid = {n: {sp.tid for sp in spans if sp.name == n}
           for n in ("TrainStep.step", "DevicePrefetcher.consumer_wait",
                     "DevicePrefetcher.device_put")}
    assert tid["TrainStep.step"] == tid["DevicePrefetcher.consumer_wait"] \
        == {threading.get_ident()}
    assert len(tid["DevicePrefetcher.device_put"]) == 1
    assert tid["DevicePrefetcher.device_put"] != tid["TrainStep.step"]


def test_prefetcher_stats_come_from_the_spans_stamps(foreign_trace):
    spans, stats = foreign_trace["spans"], foreign_trace["stats"]
    for key, name in (("consumer_wait_s", "DevicePrefetcher.consumer_wait"),
                      ("producer_wait_s", "DevicePrefetcher.producer_wait")):
        total = sum(sp.dur_us for sp in spans if sp.name == name) / 1e6
        assert stats[key] <= total <= stats[key] + 50 * SLACK_US / 1e6
    assert stats["consumed"] == STEPS
    assert stats["produced"] == stats["consumed"] + stats["queue_depth"]


def test_wait_counters_are_gone_and_queue_depth_stays():
    step = _tiny_step()
    _feed_and_step(step, 2)
    assert profiler.counter_value("DevicePrefetcher::queue_depth") is not None
    for gone in ("DevicePrefetcher::consumer_wait_ms",
                 "DevicePrefetcher::producer_wait_ms"):
        assert profiler.counter_value(gone) is None
    assert not hasattr(step, "_feed_wait_seen")


def test_dark_scopes_allocate_no_span(monkeypatch):
    made = []
    init = telemetry.Span.__init__

    def counting(self, *a, **k):
        made.append(1)
        init(self, *a, **k)
    monkeypatch.setattr(telemetry.Span, "__init__", counting)
    step = _tiny_step()
    _feed_and_step(step, 3)
    with profiler.scope("Dark.region"):
        pass
    assert made == [] and telemetry.scope_spans() == []
    telemetry.enable(collect=True)
    with profiler.scope("Lit.region"):
        pass
    assert len(made) == 1


def test_dark_scope_cost_is_bounded():
    """Every instrumented site pays this with tracing off; the chip's host
    read 1.2 us (PERF.md), and a regression to tens of us would show in a
    decode loop."""
    assert not telemetry.ACTIVE and not profiler.ACTIVE
    assert profiler.scope_cost(20_000) < 20e-6


def test_unsampled_scopes_leave_no_orphans():
    telemetry.enable(sample=0.0, collect=True)
    step = _tiny_step()
    _feed_and_step(step, 2)
    # the per-step and feed scopes are sampled out; a set-up scope never is
    # (it runs once), and under a root that was it roots a trace of its own
    kept = telemetry.scope_spans()
    assert {sp.name for sp in kept} == {
        "TrainStep.deferred_init", "TrainStep.infer_shapes",
        "TrainStep.materialize", "TrainStep.state_init",
        "TrainStep.compile"}
    for sp in kept:
        assert telemetry.audit_spans(sp.trace) == []


def test_scope_error_and_attrs_reach_the_span():
    telemetry.enable(collect=True)
    with pytest.raises(KeyError):
        with profiler.scope("Err.outer", k=1) as sc:
            sc.set(seen=True)
            with profiler.scope("Err.inner"):
                raise KeyError("x")
    outer, inner = telemetry.scope_spans()
    assert outer.attrs == {"k": 1, "seen": True, "error": "KeyError"}
    assert inner.attrs["error"] == "KeyError" and inner.parent_id == outer.sid
    # the thread's scope stack unwound: the next scope is a root again
    with profiler.scope("Err.after"):
        pass
    assert telemetry.scope_spans("Err.after")[0].parent_id is None


def test_task_start_stop_out_of_order_keeps_scopes_sound():
    telemetry.enable(collect=True)
    a, b = profiler.Task(None, "a"), profiler.Task(None, "b")
    a.start(), b.start(), a.stop(), b.stop()
    with profiler.scope("After.tasks"):
        pass
    (sp,) = telemetry.scope_spans()
    assert sp.name == "After.tasks" and sp.parent_id is None


def test_first_call_spans_deferred_init_and_compile():
    telemetry.enable(collect=True)
    step = _tiny_step(in_units=0)            # shapes inferred at first call
    x, y = _batches(1)[0]
    step(x, y).asnumpy()
    step(x, y).asnumpy()
    first, second = telemetry.scope_spans("TrainStep.step")
    kids = [sp.name for sp in first.trace.spans if sp.parent_id == first.sid]
    assert kids == ["TrainStep.deferred_init", "TrainStep.state_init",
                    "TrainStep.h2d", "TrainStep.compile"]
    (comp,) = telemetry.scope_spans("TrainStep.compile")
    assert "cache_hit" in comp.attrs
    assert [sp.name for sp in second.trace.spans][1:] == [
        "TrainStep.h2d", "TrainStep.dispatch"]


@pytest.mark.parametrize("in_units", [0, 4])
def test_deferred_init_span_counts_params_and_executables(in_units):
    # shapes deferred or given, the first call makes every parameter that
    # initialize() recorded, in one program: the span says how many, and
    # how many executables jax created under it
    config.watch_compiles()
    telemetry.enable(collect=True)
    step = _tiny_step(in_units=in_units)
    x, y = _batches(1)[0]
    step(x, y).asnumpy()
    (sp,) = telemetry.scope_spans("TrainStep.deferred_init")
    assert sp.attrs["params"] == 4
    assert 0 <= sp.attrs["executables"] <= 1     # 0: another test's program
    assert all(p._deferred_init is None
               for p in step.net.collect_params().values())


# ------------------------------------------------ always-on compile counts --
def test_compile_stats_counts_with_telemetry_never_enabled():
    config.watch_compiles()
    config.watch_compiles()                  # registers once
    x = jnp.ones((3, 5))
    before = telemetry.compile_stats()

    @jax.jit
    def fresh(x):
        return jnp.tanh(x) * 3.25 + 0.125

    fresh(x).block_until_ready()
    fresh(x).block_until_ready()                     # jit-cache hit
    after = telemetry.compile_stats()
    assert not telemetry.ACTIVE
    assert after["executables_created"] - before["executables_created"] == 1
    assert after["backend_compile_s"] > before["backend_compile_s"]
    assert after["events"] == before["events"] == 0  # tracked sites: dark


def test_compile_stats_sees_persistent_cache_misses_then_hits(tmp_path):
    config.watch_compiles()
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    from jax._src import compilation_cache
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        compilation_cache.reset_cache()

        def make():          # two functions, one program: the second
            def body(x):     # misses jax's in-memory caches only
                return jnp.cos(x) * 7.5 - 0.375
            return jax.jit(body)

        x = jnp.ones((7, 3))
        s0 = telemetry.compile_stats()
        make()(x).block_until_ready()
        s1 = telemetry.compile_stats()
        make()(x).block_until_ready()
        s2 = telemetry.compile_stats()
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
        compilation_cache.reset_cache()
    assert s1["persistent_cache_misses"] - s0["persistent_cache_misses"] == 1
    assert s1["persistent_cache_hits"] == s0["persistent_cache_hits"]
    assert s2["persistent_cache_hits"] - s1["persistent_cache_hits"] == 1
    assert s2["executables_created"] - s0["executables_created"] == 2


# ------------------------------------------------------- device-side names --
def _pallas_names(fn, *args):
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else (v,):
                    inner = getattr(sub, "jaxpr", sub)
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _flash(grad, split=False):
    """The flash kernels' names in a program: the forward, or its gradient
    through the fused backward or (``split``: no VMEM budget) the pair."""
    import importlib
    fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")
    q = jnp.ones((2, 128, 64), jnp.float32)
    if not grad:
        return _pallas_names(lambda q: fa.flash_attention(q, q, q), q)
    held = fa.FUSED_VMEM_BUDGET
    fa.FUSED_VMEM_BUDGET = 0 if split else held
    try:
        return _pallas_names(jax.grad(
            lambda q, k, v: fa.flash_attention(q, k, v).sum(), (0, 1, 2)),
            q, q, q)
    finally:
        fa.FUSED_VMEM_BUDGET = held


def _conv(grad):
    from mxnet_tpu.ops.pallas.fused_conv import norm_relu_conv
    x = jnp.ones((2, 8, 8, 8), jnp.float32)
    sc = jnp.ones((8,), jnp.float32)
    w = jnp.ones((3, 3, 8, 128), jnp.float32)
    if not grad:
        return _pallas_names(lambda x: norm_relu_conv(x, sc, sc, w), x)
    return _pallas_names(jax.grad(
        lambda x, w: norm_relu_conv(x, sc, sc, w).sum(), (0, 1)), x, w)


def _paged():
    from mxnet_tpu.ops.pallas.paged_attention import \
        paged_decode_attention_pallas
    q = jnp.zeros((2, 2, 8))
    pool = jnp.zeros((4, 8, 2, 8))
    return _pallas_names(
        paged_decode_attention_pallas, q, pool, pool,
        jnp.zeros((2, 2), jnp.int32), jnp.ones((2,), jnp.int32))


def _scan(grad):
    from mxnet_tpu.ops.pallas.selective_scan import selective_scan
    x = jnp.ones((1, 32, 128), jnp.float32)
    a, b = -jnp.ones((128, 8), jnp.float32), jnp.ones((1, 32, 8), jnp.float32)

    def total(x, dt, a, b, c):
        return selective_scan(x, dt, a, b, c, chunk=16).sum()
    if not grad:
        return _pallas_names(total, x, x, a, b, b)
    return _pallas_names(jax.grad(total, range(5)), x, x, a, b, b)


@pytest.mark.parametrize("name,found", [
    ("paged_attention", _paged),
    ("flash_attention_fwd", lambda: _flash(False)),
    ("flash_attention_bwd_dq", lambda: _flash(True, split=True)),
    ("flash_attention_bwd_dkv", lambda: _flash(True, split=True)),
    ("fused_conv_fwd", lambda: _conv(False)),
    ("fused_conv_bwd_dx", lambda: _conv(True)),
    ("fused_conv_bwd_dw", lambda: _conv(True)),
    ("selective_scan_fwd", lambda: _scan(False)),
    ("selective_scan_bwd", lambda: _scan(True)),
    ("flash_attention_bwd_dqkv", lambda: _flash(True)),
])
def test_every_pallas_call_carries_its_name(name, found):
    names = found()
    assert name in names and None not in names


def test_kernel_name_reaches_the_tpu_lowering():
    """What the device trace shows: Mosaic's ``kernel_name`` in the
    ``tpu_custom_call``, lowered here for the TPU without one."""
    from mxnet_tpu.ops.pallas.paged_attention import \
        paged_decode_attention_pallas
    q = jnp.zeros((8, 4, 128))
    pool = jnp.zeros((8, 16, 4, 128))
    text = jax.jit(lambda *a: paged_decode_attention_pallas(
        *a, interpret=False)).trace(
        q, pool, pool, jnp.zeros((8, 2), jnp.int32),
        jnp.ones((8,), jnp.int32)).lower(
        lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "paged_attention"' in text


@pytest.mark.parametrize("name,grad", [("selective_scan_fwd", False),
                                       ("selective_scan_bwd", True)])
def test_scan_kernels_lower_for_the_tpu_at_the_cell_shape(name, grad):
    """Pallas' lowering to Mosaic at the widths ``phi4_mini_flash.
    train_s4096`` runs (one sequence of 4,096, D 5120, N 16, bf16 x, b, c):
    what it refuses (a block shape, a loop it cannot unroll, a primitive
    without a rule) shows here, without a chip."""
    from mxnet_tpu.ops import state_space
    from mxnet_tpu.ops.pallas.selective_scan import selective_scan
    wide = jax.ShapeDtypeStruct((1, 4096, 5120), jnp.bfloat16)
    col = jax.ShapeDtypeStruct((1, 4096, 16), jnp.bfloat16)

    def total(x, dt, a, b, c):
        return selective_scan(x, dt, a, b, c, chunk=state_space.SCAN_CHUNK,
                              interpret=False).sum()
    fn = jax.grad(total, range(5)) if grad else total
    text = jax.jit(fn).trace(
        wide, jax.ShapeDtypeStruct(wide.shape, jnp.float32),
        jax.ShapeDtypeStruct((5120, 16), jnp.float32), col, col).lower(
        lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{name}"' in text


@pytest.mark.parametrize("name", ["flash_attention_fwd",
                                  "flash_attention_bwd_dq",
                                  "flash_attention_bwd_dkv",
                                  "flash_attention_bwd_dqkv"])
def test_flash_kernels_lower_for_the_tpu_at_the_cell_shape(name,
                                                           monkeypatch):
    """The same for the flash kernels: 40 query and 20 K/V heads of 64 over
    4,096 positions in bf16, causal, the block ``sambay.py`` gives (whole
    registers of row statistics, their transposes, the clamped index
    maps).  The cell's shape takes the fused backward; the split pair's
    two kernels are lowered at it with no VMEM budget."""
    import importlib
    from mxnet_tpu.gluon.model_zoo.sambay import _flash_block
    from mxnet_tpu.ops.pallas.flash_attention import flash_attention
    if name in ("flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        monkeypatch.setattr(importlib.import_module(
            "mxnet_tpu.ops.pallas.flash_attention"), "FUSED_VMEM_BUDGET", 0)
    q = jax.ShapeDtypeStruct((40, 4096, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((20, 4096, 64), jnp.bfloat16)
    block = _flash_block(4096)

    def total(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block,
                               block_k=block, interpret=False
                               ).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(total, (0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)).as_text()
    assert f'kernel_name = "{name}"' in text
    if name == "flash_attention_bwd_dqkv":
        assert 'kernel_name = "flash_attention_bwd_dq"' not in text
        assert 'kernel_name = "flash_attention_bwd_dkv"' not in text


def _scope_paths(lowered):
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


def test_train_step_program_names_its_phases_and_stages():
    from mxnet_tpu.gluon.model_zoo import vision
    mx.random.seed(1)
    net = vision.resnet18_v1(layout="NHWC", classes=10, thumbnail=True)
    net.initialize()
    step = parallel.TrainStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mx.optimizer.create("sgd", learning_rate=0.01))
    paths = _scope_paths(step.lower(np.zeros((8, 16, 16, 3), np.float32),
                                    np.zeros((8,), np.int32)))

    def has(fragment):
        return any(fragment in p for p in paths)
    assert has("/jvp(forward)/") and has("/jvp(loss)/")
    assert has("/optimizer/")
    # the backward pass is the forward's transpose, by jax's own naming
    assert has("/transpose(jvp(forward))/")
    for stage in ("stage1", "stage2", "stage3", "stage4"):
        assert has(f"/jvp(forward)/{stage}/")
        assert has(f"/transpose(jvp(forward))/{stage}/")
    # parameter names are the prefix's, as before the stage was scoped
    assert any(n.endswith("_stage1_conv2d0_weight")
               for n in net.collect_params())


@pytest.fixture(scope="module")
def lm():
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)
    cfg = CausalLMConfig(vocab_size=64, n_layers=2, n_heads=2, head_dim=8,
                         d_ff=32)
    return cfg, init_causal_lm(cfg, 0)


def _serving_program(which, cfg, params):
    from mxnet_tpu.serving import generate as g
    S, P, page = 2, 2, 8
    pool = jnp.zeros((cfg.n_layers, 4, page, cfg.n_heads, cfg.head_dim))
    i32 = lambda *s: jnp.zeros(s, jnp.int32)          # noqa: E731
    slot = (i32(S), jnp.ones((S,), bool), i32(S, P))
    sampling = (jnp.zeros((S,), jnp.uint32), jnp.zeros((S,)), i32(S))
    if which == "decode":
        fn = g.build_decode_step(cfg, page, attention_impl="jnp")
        args = (params, pool, pool, i32(S)) + slot + (i32(S), i32(S)) \
            + sampling
    elif which == "prefill":
        fn = g.build_prefill_step(cfg, page)
        args = (params, pool, pool, i32(S, 8)) + slot + sampling
    else:
        fn = g.build_handoff_step(cfg, page)
        kv = jnp.zeros((cfg.n_layers, S, 8, cfg.n_heads, cfg.head_dim))
        args = (pool, pool, kv, kv) + slot
    return jax.jit(fn).lower(*args)


@pytest.mark.parametrize("which,scopes", [
    ("decode", ("kv_write", "attention", "mlp")),
    ("prefill", ("kv_write", "attention", "mlp")),
    ("handoff", ("kv_write",)),
])
def test_serving_programs_name_their_layers(lm, which, scopes):
    cfg, params = lm
    paths = _scope_paths(_serving_program(which, cfg, params))
    for layer in range(cfg.n_layers):
        for scope in scopes:
            assert any(f"/layer{layer}/{scope}/" in p for p in paths), \
                (layer, scope)
    if which != "handoff":
        assert any("/sample/" in p for p in paths)
    if which == "decode":            # the copy-on-write lanes, before layer0
        assert any("/cow/" in p for p in paths)
