"""The per-layer yardstick: the xplane metadata parser, SELF time, the phase
rule, whole steps, the generic readers and where readers are found.  CPU
only; reads traces recorded on the chip (``data/``).  From the root:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q -p no:cacheprovider
"""
import copy
import glob
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import manifest, readers                    # noqa: E402
from chipbench.layer_readers import (idle_gap_max_ms, scope_ms,  # noqa: E402
                                     span_ms)
from chipbench.trace import reduce, xplane                 # noqa: E402

DATA = os.path.join(os.path.dirname(__file__), "data")
TINY = os.path.join(DATA, "tiny_v5e.xplane.pb")
SCOPES = os.path.join(DATA, "tiny_scopes_v5e.xplane.pb")
TPU = "/device:TPU:0"


# ----------------------------------------------------- the metadata parser --
@pytest.mark.parametrize("path", [TINY, SCOPES])
def test_metadata_of_a_recorded_trace(path):
    planes = xplane.event_metadata(path)
    assert TPU in planes and "/host:CPU" in planes
    ops = [m for m in planes[TPU].values() if m["name"].startswith("%")]
    assert ops and all(isinstance(m["stats"].get("program_id"), int)
                       for m in ops)
    scopes = xplane.op_scopes(path)[TPU]
    assert scopes and all(s.startswith("jit(") for s in scopes.values())
    # every executed instruction is in the metadata under the event's name
    from jax.profiler import ProfileData
    plane = next(p for p in ProfileData.from_file(path).planes
                 if p.name == TPU)
    names = {m["name"] for m in planes[TPU].values()}
    line = next(l for l in plane.lines if l.name == "XLA Ops")
    assert {e.name for e in line.events} <= names


@pytest.mark.parametrize("path", [TINY, SCOPES])
def test_metadata_agrees_with_the_generated_protobuf(path):
    """Against tensorflow's generated ``xplane_pb2`` where the wheel is
    installed (loaded by path: tensorflow itself is never imported)."""
    found = [p for d in sys.path for p in glob.glob(os.path.join(
        d, "tensorflow", "tsl", "profiler", "protobuf", "xplane_pb2.py"))]
    if not found:
        pytest.skip("no tensorflow wheel with xplane_pb2 here")
    spec = importlib.util.spec_from_file_location("xplane_pb2", found[0])
    pb2 = importlib.util.module_from_spec(spec)
    try:
        spec.loader.exec_module(pb2)
    except Exception as e:              # a protobuf runtime it cannot load
        pytest.skip(f"xplane_pb2 does not load: {e!r}")
    space = pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    mine, compared = xplane.event_metadata(path), 0
    assert list(mine) == [p.name for p in space.planes]
    for plane in space.planes:
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        assert set(mine[plane.name]) == set(plane.event_metadata)
        for key, meta in plane.event_metadata.items():
            want = {}
            for s in meta.stats:
                kind = s.WhichOneof("value")
                value = getattr(s, kind)
                want[stat_names[s.metadata_id]] = \
                    stat_names[value] if kind == "ref_value" else value
            got = mine[plane.name][key]
            assert (got["name"], got["display_name"], got["stats"]) == \
                (meta.name, meta.display_name, want)
            compared += len(want)
    assert compared > 50


def test_the_wire_walk_on_hand_made_bytes():
    # field 1 varint 300; field 2 bytes "ab"; field 3 fixed64; field 4 fixed32
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab" \
        + bytes([0x19]) + bytes(8) + bytes([0x25]) + bytes(4)
    got = [(n, w, v if isinstance(v, int) else bytes(v))
           for n, w, v in xplane.fields(buf)]
    assert got == [(1, 0, 300), (2, 2, b"ab"), (3, 1, bytes(8)),
                   (4, 5, bytes(4))]
    with pytest.raises(ValueError):
        list(xplane.fields(bytes([0x0B])))         # a group: not an xplane


# ------------------------------------------------------------- SELF time --
def test_self_time_under_a_while():
    # a while of 100 with a body of 20 + 20 (one of them a conditional that
    # holds 5), then an instruction on its own; given out of order
    events = [(120, 130, "alone"), (0, 100, "while"), (10, 30, "body.a"),
              (40, 45, "cond.branch"), (30, 50, "body.cond")]
    assert reduce.self_ns(events) == [10.0, 60.0, 20.0, 5.0, 15.0]
    # SELF times add up to the union of the intervals: nothing counts twice
    assert sum(reduce.self_ns(events)) == reduce.union_ns(
        [e[:2] for e in events])[0]
    assert reduce.self_ns([]) == []


# ------------------------------------------------------- the phase rule --
# op_name strings of lfm2_8b_a1b's TrainStep as XLA carries them (a CPU
# lowering of the cell's family at toy widths; the chip's tf_op is the same)
REAL = {
    "jit(fn)/jvp(forward)/layer1/attention/rope": "forward",
    "jit(fn)/jvp(forward)/embed/jit(_take)": "forward",
    "jit(fn)/jvp(loss)/jit(log_softmax)": "forward",
    "jit(fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
    "rematted_computation/layer1/moe/router": "recompute",
    "jit(fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
    "rematted_computation/layer1/attention/jit(_flash_fwd)/"
    "flash_attention_fwd": "recompute",
    "jit(fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/layer1/moe/"
    "combine/while/body": "backward",
    "jit(fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/layer1/"
    "attention/jit(_flash_bwd)/flash_attention_bwd_dq": "backward",
    "jit(fn)/transpose(jvp(forward))/head": "backward",
    "jit(fn)/transpose(jvp(loss))/jit(log_softmax)": "backward",
    "jit(fn)/optimizer": "optimizer",
    "jit(fn)/optimizer/jit(_where)": "optimizer",
    "jit(fn)/forward/layer0/bn": "forward",     # aux state, not differentiated
    "": "other",                                # ragged-dot: no tf_op at all
    "jit(fn)/jit(main)/add": "other",
    "jit(fn)/my_optimizer_state/add": "other",  # a word, not the scope
}


@pytest.mark.parametrize("scope", sorted(REAL))
def test_phase_of_real_scope_paths(scope):
    assert reduce.phase_of(scope) == REAL[scope]
    assert REAL[scope] in reduce.PHASES


# ----------------------------------------------------------- whole steps --
def hand_made(window=(100, 1100)):
    """A trace without a file: a step program of 200 ns that runs six times
    from 0 (so the window cuts the first and the last), a short program that
    runs more often, and per step a forward op, a ``while`` with two body
    ops in the backward pass, a grouped product without scope and an
    optimizer kernel."""
    t = reduce.Trace.__new__(reduce.Trace)
    fwd = "%fusion.1 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %p), kind=kLoop"
    loop = "%while.2 = (s32[], bf16[8,8]{1,0}) while(%tuple), body=%b"
    body = "%fusion.3 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %q), kind=kLoop"
    dot = ('%ragged-dot-none.4 = bf16[8,8]{1,0} custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call"')
    opt = ('%tiny_update.5 = f32[8,8]{1,0} custom-call(%w, %g), '
           'custom_call_target="tpu_custom_call"')
    modules, ops = [], []
    for k in range(6):
        s = 200 * k
        modules.append((s, s + 190, "jit_fn(1)"))
        modules.append((s + 192, s + 198, "jit_small(2)"))
        ops += [(s, s + 40, fwd), (s + 50, s + 130, loop),
                (s + 60, s + 80, body), (s + 90, s + 120, body),
                (s + 130, s + 150, dot), (s + 160, s + 190, opt),
                (s + 192, s + 198, fwd)]        # the small program's op
    t.devices = {TPU: {"XLA Modules": modules, "XLA Ops": ops}}
    t.host = [(window[0], window[1], reduce.WINDOW)]
    for k in range(6):
        t.host += [(200 * k - 8, 200 * k + 195, "chipbench.step"),
                   (200 * k - 6, 200 * k - 2, "TrainStep.dispatch")]
    t.t0, t.t1 = window
    t.window_s = (t.t1 - t.t0) / 1e9
    t.first = TPU
    t.scopes = {
        fwd: "jit(fn)/jvp(forward)/layer0/mlp",
        loop: "jit(fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
              "layer1/moe/combine/while",
        body: "jit(fn)/transpose(jvp(forward))/jvp(forward)/checkpoint/"
              "layer1/moe/combine/while/body",
        opt: "jit(fn)/optimizer/tiny_update"}
    t._step_ops = None
    return t


def test_whole_steps_at_a_cut_window():
    t = hand_made()
    # 0..190 starts before the window, 1000..1190 ends after it
    assert t.whole_steps() == [(200, 390), (400, 590), (600, 790),
                               (800, 990)]
    # the first execution the trace holds may have begun before the trace
    # did, so it is never counted, window or not
    assert hand_made((-10, 1100)).whole_steps()[0] == (200, 390)
    # the short program is not the step, and its op is in no step
    assert {r[0] for r in t.step_ops()} == {0, 1, 2, 3}
    assert len(t.step_ops()) == 4 * 6
    # a window that holds no whole step
    assert hand_made((210, 380)).whole_steps() == []


class Ctx:
    def __init__(self, t, counters=None):
        self.reduced, self.counters = t, counters or {}


def test_scope_ms_by_scope_op_phase_and_exclusion():
    ctx = Ctx(hand_made())
    ms = 1e-6                                   # the hand-made unit is ns
    assert scope_ms.read(ctx, phase="forward") == pytest.approx(40 * ms)
    # the while keeps 80 - 20 - 30; its body's two ops count once
    assert scope_ms.read(ctx, phase="backward") == pytest.approx(80 * ms)
    assert scope_ms.read(ctx, phase="optimizer") == pytest.approx(30 * ms)
    assert scope_ms.read(ctx, phase="recompute") == 0.0
    assert scope_ms.read(ctx, phase="other") == pytest.approx(20 * ms)
    # the phases add up to the busy time of a whole step
    assert sum(scope_ms.read(ctx, phase=p) for p in reduce.PHASES) \
        == pytest.approx(170 * ms)
    moe = [r"layer\d+/moe(/|$)"]
    assert scope_ms.read(ctx, scopes=moe) == pytest.approx(80 * ms)
    # the grouped product has no scope: found by name, and by name alone
    assert scope_ms.read(ctx, ops=[r"^ragged-dot"]) == pytest.approx(20 * ms)
    assert scope_ms.read(ctx, scopes=moe, ops=[r"^ragged-dot"]) \
        == pytest.approx(100 * ms)
    assert scope_ms.read(ctx, scopes=moe, ops=[r"^ragged-dot"],
                         not_ops=[r"^ragged-dot"]) == pytest.approx(80 * ms)
    assert scope_ms.read(ctx, scopes=moe, phase="forward") == 0.0
    assert scope_ms.read(ctx, ops=[r"^tiny_update"], phase="optimizer") \
        == pytest.approx(30 * ms)
    # nothing to read: no trace, or no whole step in the window
    assert scope_ms.read(Ctx(None), phase="forward") is None
    assert scope_ms.read(Ctx(hand_made((210, 380))), phase="forward") is None


def test_gaps_name_the_program_and_the_longest_is_read():
    t = hand_made()
    gaps = t.gaps()
    assert all(g1 - g0 >= 1 for g0, g1, _ in gaps) and gaps == []
    # MIN_GAP_NS hides the hand-made gaps; lower it as a 1 ns clock would
    old, reduce.MIN_GAP_NS = reduce.MIN_GAP_NS, 1
    try:
        gaps = t.gaps()
        # ten a step between ops, and the two ns before a step's first op
        by_name = dict(t.idle_gaps())
        assert set(by_name) == {"chipbench.step"}
        assert max(g1 - g0 for g0, g1, _ in gaps) == 10
        assert idle_gap_max_ms.read(Ctx(t)) == pytest.approx(10e-6)
        # a gap whose middle lies in the program's span is the program's
        t.host.append((441, 449, "TrainStep.h2d"))
        assert dict(t.idle_gaps())["TrainStep.h2d"] == pytest.approx(10e-9)
    finally:
        reduce.MIN_GAP_NS = old
    assert idle_gap_max_ms.read(Ctx(None)) is None


def test_span_names_follow_the_program_s_rule():
    for name in ("TrainStep.dispatch", "DevicePrefetcher.consumer_wait",
                 "GenerationServer.prefill"):
        assert reduce.SPAN.match(name)
    for name in ("chipbench.step", "PjitFunction(fn)", "TrainStep",
                 "$pjit.py:123 cache_miss", "ThreadpoolListener::Region"):
        assert not reduce.SPAN.match(name)


# ------------------------------- the trace of record_tiny_scopes.py (v5e) --
@pytest.fixture(scope="module")
def recorded():
    return reduce.Trace(SCOPES)


def test_recorded_step_splits_into_phases(recorded):
    t = recorded
    steps = t.whole_steps()
    assert 4 <= len(steps) <= 8
    ctx = Ctx(t)
    by_phase = {p: scope_ms.read(ctx, phase=p) for p in reduce.PHASES}
    assert all(by_phase[p] > 0 for p in
               ("forward", "recompute", "backward", "optimizer"))
    # SELF times of the phases add up to the chip's busy time in the steps
    busy = reduce.union_ns([(s, e) for s, e, _ in t.ops(t.first) if any(
        a <= s and e <= b for a, b in steps)])[0] / len(steps) / 1e6
    assert sum(by_phase.values()) == pytest.approx(busy, rel=1e-6)
    # layer1's loop is a while on the device: it keeps only what its body's
    # instructions do not cover, a few ns of many thousand
    whiles = [r for r in t.step_ops() if r[2].startswith("while")]
    plain = sum(e - s for s, e, n in t.ops(t.first)
                if reduce.short_name(n).startswith("while"))
    assert whiles and sum(r[1] for r in whiles) < 0.05 * plain
    body = scope_ms.read(ctx, scopes=[r"layer1/attention/while/body(/|$)"])
    assert body > 0.5 * plain / len(steps) / 1e6
    # the recomputed block is layer0's alone
    assert scope_ms.read(ctx, scopes=[r"layer0/mlp(/|$)"], phase="recompute") \
        == pytest.approx(by_phase["recompute"])
    assert scope_ms.read(ctx, scopes=[r"layer1/attention(/|$)"],
                         phase="recompute") == 0.0
    # the Pallas kernel by its name=, inside the optimizer scope
    kernel = scope_ms.read(ctx, ops=[r"^tiny_update"])
    assert 0 < kernel <= by_phase["optimizer"]
    assert scope_ms.read(ctx, scopes=[r"(^|/)optimizer(/|$)"],
                         not_ops=[r"^tiny_update"]) \
        == pytest.approx(by_phase["optimizer"] - kernel)


def test_recorded_device_ops_are_self_time(recorded):
    top = recorded.device_ops(top=100)
    assert sum(s for _, s in top) == pytest.approx(
        recorded.busy_s(), rel=1e-6)            # a while counts once
    assert all(len(n) <= 64 for n, _ in top)


def test_recorded_spans_and_gaps(recorded):
    t = recorded
    spans = t.spans("TrainStep.dispatch")
    assert 6 <= len(spans) <= 8 and all(0.01 < ms < 50 for ms in spans)
    ctx = Ctx(t)
    p50 = span_ms.read(ctx, name="TrainStep.dispatch", q=50)
    assert min(spans) <= p50 <= max(spans)
    assert span_ms.read(ctx, name="TrainStep.nothing", q=50) is None
    assert span_ms.read(Ctx(None), name="TrainStep.dispatch", q=50) is None
    gaps = dict(t.idle_gaps())
    # the host slept 5 ms after every other step, under chipbench.wait
    assert gaps["chipbench.wait"] > 4 * 0.004
    assert sum(gaps.values()) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-2)
    assert 4.0 < idle_gap_max_ms.read(ctx) < 50.0


# ------------------------------------------------------ readers by name --
def test_a_reader_is_found_in_either_home():
    assert readers.find("counter") is readers.counter
    assert readers.find("mfu_pct") is readers.mfu_pct
    assert readers.find("scope_ms") is scope_ms.read
    assert readers.find("span_ms") is span_ms.read
    assert readers.find("idle_gap_max_ms") is idle_gap_max_ms.read
    for fn in ("nowhere", "find", "importlib", "traffic", "re", "_NAME",
               "../run", "layer_readers.scope_ms", "", None, 3):
        assert readers.find(fn) is None, fn


def test_counter_scales_and_leaves_out_what_is_missing():
    ctx = Ctx(None, {"moe.held_share": 0.354})
    assert readers.counter(ctx, "moe.held_share") == 0.354
    assert readers.counter(ctx, "moe.held_share", scale=100) \
        == pytest.approx(35.4)
    assert readers.counter(ctx, "moe.load_max_over_mean", scale=100) is None


def test_validate_refuses_a_reader_found_in_neither_home(tmp_path):
    m = manifest.load(ROOT)
    assert manifest.validate(m, ROOT) == []
    root = str(tmp_path)
    for rel in ["BENCHMARK.json"] + [c["file"] for c in m["configs"]] \
            + [manifest.workload_file(w["name"]) for w in m["workloads"]] \
            + [manifest.metric_file(r["name"]) for r in m["per_layer"]]:
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(ROOT, rel)) as f, \
                open(os.path.join(root, rel), "w") as g:
            g.write(f.read())
    assert manifest.validate(m, root) == []
    m = copy.deepcopy(m)
    m["per_layer"].append(dict(m["per_layer"][-1], name="conjured_ms"))
    with open(os.path.join(root, manifest.metric_file("conjured_ms")),
              "w") as f:
        json.dump({"reader": {"fn": "conjure"}}, f)
    errors = manifest.validate(m, root)
    assert len(errors) == 1 and "no reader 'conjure'" in errors[0]


def test_every_new_metric_names_its_cells():
    m = manifest.load(ROOT)
    old = {"compile_ms_total", "step_ms_p50", "mfu_pct",
           "device_idle_pct.train"}
    new = [r for r in m["per_layer"] if r["name"] not in old]
    assert len(new) >= 15 and all(r.get("workloads") for r in new)
    for r in new:
        spec = manifest.load_json(ROOT, manifest.metric_file(r["name"]))
        assert spec["what"] and readers.find(spec["reader"]["fn"])
