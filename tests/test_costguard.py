"""costguard (ISSUE 6): compiled-program cost budgets + recompile audit.

The tier-1 gate for the compile boundary: every committed budget golden
(tests/goldens/budgets/) is re-lowered, re-compiled, and diffed with
per-metric tolerances — a graph inflation (extra bucket, fatter dtype,
new executable) fails HERE with a readable per-metric diff, before it
ships.  Nothing in this file executes a training step: everything goes
through the lower-only AOT path under JAX_PLATFORMS=cpu.

The ``costguard`` marker selects this suite; the gate runs through the
``.costguard_cache/`` report cache (HLO-hash keyed, so it can never go
stale against the code) to keep repeat runs cheap.
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools import costguard  # noqa: E402
from tools.costguard import (DEFAULT_TOLERANCES,  # noqa: E402
                             Program, budgeted, collective_payload_bytes,
                             diff_report, executable_census,
                             grid_signatures, instruction_counts,
                             load_golden, report_for_programs, run_check)
from tools.costguard import entrypoints  # noqa: E402
from tools.costguard.report import donation_counts  # noqa: E402

pytestmark = pytest.mark.costguard


# ------------------------------------------------------------- extraction --
def test_report_normalization_mlp():
    built = entrypoints.build("mnist_mlp_train")
    rep = report_for_programs(built.programs)
    assert rep["n_executables"] == 1 == built.census
    assert rep["flops"] > 0 and rep["bytes_accessed"] > 0
    assert rep["instructions"]["dot"] > 0
    assert rep["memory"]["argument_bytes"] > 0
    # every budgeted row is extracted, and a golden commits those alone
    committed = budgeted(rep)
    assert {r.metric for r in diff_report(rep, {"report": committed})} \
        == set(DEFAULT_TOLERANCES)
    assert "peak_bytes" in rep["memory"]       # reported, not budgeted
    assert "peak_bytes" not in committed["memory"]
    d = rep["donation"]
    # params/opt-states/step-counter are donated; key/lr/batch are not
    assert 0 < d["donated_args"] < d["total_args"]


def test_instruction_parser_on_synthetic_hlo():
    hlo = textwrap.dedent("""\
        HloModule jit_f, input_output_alias={ {0}: (0, {}, may-alias), {1}: (3, {}, must-alias) }

        %fused_computation (p: f32[8]) -> f32[8] {
          %p = f32[8]{0} parameter(0)
          ROOT %m = f32[8]{0} multiply(%p, %p)
        }

        ENTRY %main (a: f32[8,16], b: f32[16,4]) -> (f32[8,4], f32[8]) {
          %a = f32[8,16]{1,0} parameter(0)
          %b = f32[16,4]{1,0} parameter(1)
          %d = f32[8,4]{1,0} dot(%a, %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
          %c = f32[8,16]{1,0} convolution(%a, %b), window={}, dim_labels=bf_io->bf
          %f = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation
          %ar = f32[8]{0} all-reduce(%f), replica_groups={}
          %cp = f32[8]{0} copy(%ar)
          ROOT %t = (f32[8,4]{1,0}, f32[8]{0}) tuple(%d, %cp)
        }
        """)
    counts = instruction_counts(hlo)
    assert counts["dot"] == 1 and counts["convolution"] == 1
    assert counts["fusion"] == 1 and counts["collective"] == 1
    assert counts["copy"] == 1
    assert counts["total"] == 8          # entry computation ONLY
    don = donation_counts(hlo, n_args=4)
    assert don == {"donated_args": 2, "total_args": 4}


def test_serving_grid_report_counts_every_signature():
    built = entrypoints.build("serving_mlp_grid")
    rep = report_for_programs(built.programs)
    assert rep["n_executables"] == built.census == 6
    # 2 matmuls per executable, summed across the grid
    assert rep["instructions"]["dot"] == 12


def test_collective_payload_bytes_parser():
    """Result-shape byte accounting of entry collectives: async pairs
    count once (-start skipped), tuple shapes (the CPU all-to-all form)
    sum per-peer buffers, non-collectives are ignored."""
    hlo = textwrap.dedent("""\
        HloModule jit_f

        ENTRY %main (x: f32[8]) -> f32[32] {
          %x = f32[8]{0} parameter(0)
          %ar = f32[8]{0} all-reduce(%x), replica_groups={}
          %a2a = (s8[1,4]{1,0}, s8[1,4]{1,0}) all-to-all(s8[1,4]{1,0} %q, s8[1,4]{1,0} %q2), dimensions={0}
          %ags = (f32[4]{0}, f32[32]{0}) all-gather-start(f32[4]{0} %p), dimensions={0}
          ROOT %agd = f32[32]{0} all-gather-done(%ags)
        }
        """)
    # 8*4 (all-reduce) + 2*4 (s8 tuple) + 32*4 (the -done; -start skipped)
    assert collective_payload_bytes(hlo) == 32 + 8 + 128
    assert instruction_counts(hlo)["collective"] == 4


# --------------------------------------- ISSUE 8: committed byte budgets --
def test_gradq_int8_collective_byte_budget():
    """The tentpole's headline, pinned: the committed int8
    gradient-collective golden moves >= 25% fewer collective payload
    bytes than its f32 sibling.  This diffs the TWO COMMITTED goldens —
    the win regresses in tier-1 if either side drifts, independently of
    each golden's own tolerance gate."""
    f32 = load_golden("mnist_mlp_train", REPO)["report"]
    q8 = load_golden("mnist_mlp_train_gradq_int8", REPO)["report"]
    assert f32["collective_bytes"] > 0
    assert q8["collective_bytes"] <= 0.75 * f32["collective_bytes"], (
        f"int8 grad collectives moved {q8['collective_bytes']} bytes vs "
        f"f32's {f32['collective_bytes']} — the committed >=25% "
        f"reduction no longer holds")
    # same model, same pinned-executable contract
    assert q8["n_executables"] == f32["n_executables"] == 1


def test_serving_int8_weight_buffer_budget():
    """The serving-side headline, pinned the same way: the int8 grid's
    compiled weight buffer (argument bytes — weights are jit ARGUMENTS
    in the HotSwapApply serving shape) is >= 25% smaller than the f32
    grid's, over the identical bucket census."""
    f32 = load_golden("serving_mlp_grid", REPO)["report"]
    q8 = load_golden("serving_mlp_grid_int8", REPO)["report"]
    assert f32["memory"]["argument_bytes"] > 0
    assert q8["memory"]["argument_bytes"] <= \
        0.75 * f32["memory"]["argument_bytes"], (
            f"int8 serving weight buffer {q8['memory']['argument_bytes']}"
            f" vs f32 {f32['memory']['argument_bytes']} — the committed "
            f">=25% reduction no longer holds")
    assert q8["n_executables"] == f32["n_executables"] == 6


# ------------------------------------- ISSUE 10: paged-KV byte commitment --
def test_llm_paged_kv_byte_budget():
    """The continuous-batching tentpole's structural-HBM win, pinned as
    a committed golden PAIR (PR 8 pattern): the paged decode step's
    ``memory.argument_bytes`` — the resident pool + weights + slot
    state the ONE decode executable touches — is >= 40% below the
    dense max-length-cache variant's, over the identical model, slot
    grid, and sampling program.  Both sides are committed goldens, so
    the WIN regresses in tier-1 if either drifts."""
    paged = load_golden("llm_decode_step", REPO)["report"]
    dense = load_golden("llm_decode_step_dense", REPO)["report"]
    assert dense["memory"]["argument_bytes"] > 0
    assert paged["memory"]["argument_bytes"] <= \
        0.60 * dense["memory"]["argument_bytes"], (
            f"paged decode-step argument bytes "
            f"{paged['memory']['argument_bytes']} vs dense "
            f"{dense['memory']['argument_bytes']} — the committed "
            f">=40% paged-KV reduction no longer holds")
    # both are the SAME one-executable contract: any in-flight mix of
    # sequence lengths/ages runs the single compiled decode program
    assert paged["n_executables"] == dense["n_executables"] == 1


def test_llm_serving_census_is_prefill_grid_plus_one():
    """The LLM serving executable space is exactly the prefill bucket
    grid plus THE decode program — committed across the two goldens."""
    prefill = load_golden("llm_prefill_grid", REPO)
    decode = load_golden("llm_decode_step", REPO)
    grid = (len(prefill["meta"]["batch_buckets"])
            * len(prefill["meta"]["length_buckets"]))
    assert prefill["report"]["n_executables"] == prefill["census"] == grid
    assert decode["report"]["n_executables"] == decode["census"] == 1


def test_llm_prefix_sharing_admission_budget():
    """The CoW prefix-sharing win (ISSUE 16), pinned as a committed
    golden PAIR: at a 90%-shared prefix (176 of 192 prompt tokens),
    worst-case-fit admission charges only NON-shared pages, so (a) at
    the FIXED 128-page pool the admissible concurrency multiplier is
    >= 2x the unshared baseline, and (b) serving the SAME 8-slot worst
    case needs <= 55% of the unshared pool's decode-step
    ``argument_bytes``.  The plan numbers in the goldens' meta are
    recomputed here through the LIVE ``prefix_admission_plan`` — a
    drive-by change to the admission math trips this gate, not just a
    regen."""
    from mxnet_tpu.serving.generate import prefix_admission_plan

    unshared = load_golden("llm_admission_unshared", REPO)
    shared = load_golden("llm_admission_shared", REPO)
    mu, ms = unshared["meta"], shared["meta"]
    # identical traffic contract on both sides
    for k in ("prompt_len", "max_new", "shared_prefix_len", "page_size",
              "n_slots"):
        assert mu[k] == ms[k], k
    plan = prefix_admission_plan(mu["n_pages"], mu["page_size"],
                                 mu["prompt_len"], mu["max_new"],
                                 mu["shared_prefix_len"])
    for k, v in plan.items():
        assert mu[k] == v, (k, mu[k], v)
    assert plan["admissible_unshared"] == mu["n_slots"] == 8
    assert plan["admissible_shared"] >= 2 * plan["admissible_unshared"], (
        f"prefix sharing admits {plan['admissible_shared']} vs "
        f"{plan['admissible_unshared']} unshared — the committed >=2x "
        f"concurrency multiplier at 90% shared prefix no longer holds")
    ub = unshared["report"]["memory"]["argument_bytes"]
    sb = shared["report"]["memory"]["argument_bytes"]
    assert ub > 0
    assert sb <= 0.55 * ub, (
        f"shared-prefix decode argument bytes {sb} vs unshared {ub} — "
        f"the committed page-bytes/sequence reduction no longer holds")
    assert unshared["report"]["n_executables"] == \
        shared["report"]["n_executables"] == 1


def test_llm_speculative_census_is_plus_one():
    """Speculative decoding adds EXACTLY one executable — the pinned
    verify step — to the serving census; the draft model never gets a
    program of its own (its proposal loop lives inside the verify
    executable).  Committed as a golden so a second speculative
    program (a stray draft forward, an unrolled variant) trips tier-1."""
    verify = load_golden("llm_verify_step", REPO)
    assert verify["report"]["n_executables"] == verify["census"] == 1
    assert verify["meta"]["spec_k"] >= 1
    # the verify step prices BOTH param sets: speculation is not free
    decode = load_golden("llm_decode_step", REPO)["report"]
    assert verify["report"]["memory"]["argument_bytes"] > \
        decode["memory"]["argument_bytes"]


# ------------------------- ISSUE 11: sharded per-device cost budgets --
def test_program_num_partitions_parser():
    from tools.costguard.report import program_num_partitions
    sharded = ("HloModule jit_f, is_scheduled=true, num_partitions=8, "
               "entry_computation_layout={()->f32[1]{0}}\n")
    single = "HloModule jit_f, is_scheduled=true\n"
    assert program_num_partitions(sharded) == 8
    assert program_num_partitions(single) == 1
    assert program_num_partitions("") == 1


def test_per_device_merge_takes_worst_single_program():
    from tools.costguard.report import merge_reports
    base = {"n_executables": 1, "flops": 1.0, "bytes_accessed": 1.0,
            "transcendentals": 0.0, "collective_bytes": 0.0,
            "memory": {}, "donation": {"donated_args": 0,
                                       "total_args": 1},
            "instructions": {"total": 1}}
    u1 = dict(base, per_device={"n_devices": 8, "argument_bytes": 100,
                                "peak_bytes": 200,
                                "collective_bytes": 32.0})
    u2 = dict(base, per_device={"n_devices": 8, "argument_bytes": 300,
                                "peak_bytes": 150,
                                "collective_bytes": 8.0})
    merged = merge_reports([u1, u2])
    # executables run one at a time: the budgetable per-device figure
    # is the worst single program, not a fictitious sum
    assert merged["per_device"] == {"n_devices": 8,
                                    "argument_bytes": 300,
                                    "peak_bytes": 200,
                                    "collective_bytes": 32.0}
    # key UNION: a unit whose memory extraction failed (per_device
    # missing the byte keys) must not drop the metrics others report
    u3 = dict(base, per_device={"n_devices": 8,
                                "collective_bytes": 4.0})
    merged = merge_reports([u3, u1])
    assert merged["per_device"]["argument_bytes"] == 100
    assert merged["per_device"]["peak_bytes"] == 200
    assert merged["per_device"]["collective_bytes"] == 32.0


def test_dp_sharded_per_device_byte_budget():
    """The dp golden pair, diffed: on a pure-dp mesh the params are
    replicated and ONLY the batch shards, so the dp=8 entry's
    per-device argument bytes must sit exactly 7/8 of the batch bytes
    below the committed dp=1 control — per-device bytes ∝ 1/shards for
    the sharded tensors, as a diff of two COMMITTED goldens."""
    dp8 = load_golden("mnist_mlp_train", REPO)["report"]
    dp1 = load_golden("mnist_mlp_train_dp1", REPO)["report"]
    assert dp8["per_device"]["n_devices"] == 8
    assert dp1["per_device"]["n_devices"] == 1
    batch_bytes = 64 * 784 * 4 + 64 * 4          # x f32 + y i32
    saved = dp1["per_device"]["argument_bytes"] \
        - dp8["per_device"]["argument_bytes"]
    expect = batch_bytes * 7 // 8
    assert abs(saved - expect) <= 0.02 * expect, (
        f"dp=8 per-device argument bytes save {saved} vs the expected "
        f"7/8 of the batch ({expect}) — the batch is no longer "
        f"dp-sharded (or something else leaked into the signature)")
    # the sharded side pays its gradient collectives; the control is
    # collective-free
    assert dp8["per_device"]["collective_bytes"] > 0
    assert dp1["per_device"]["collective_bytes"] == 0
    assert dp8["n_executables"] == dp1["n_executables"] == 1


def test_tp_sharded_per_device_byte_budget():
    """The TP golden pair, diffed: column/row-sharded weights put
    1/shards of the weight bytes on each device, so the tp=8 apply's
    per-device argument bytes must be >= 70% below the tp=1 control
    (committed: ~87% — weights dominate this entry by construction),
    with the output all-reduce visible in the collective columns.  This
    is THE gate ROADMAP item 1's tensor-parallel decode lands on."""
    tp8 = load_golden("mlp_apply_tp8", REPO)["report"]
    tp1 = load_golden("mlp_apply_tp1", REPO)["report"]
    assert tp8["per_device"]["n_devices"] == 8
    assert tp1["per_device"]["n_devices"] == 1
    assert tp1["per_device"]["argument_bytes"] > 0
    assert tp8["per_device"]["argument_bytes"] <= \
        0.30 * tp1["per_device"]["argument_bytes"], (
            f"tp=8 per-device argument bytes "
            f"{tp8['per_device']['argument_bytes']} vs tp=1 "
            f"{tp1['per_device']['argument_bytes']} — the committed "
            f">=70% per-device weight reduction no longer holds")
    # the two Megatron collectives collapse to ONE all-reduce here
    # (activations replicated); the control has none
    assert tp8["per_device"]["collective_bytes"] > 0
    assert tp1["per_device"]["collective_bytes"] == 0
    assert tp8["n_executables"] == tp1["n_executables"] == 1


def test_tp_sharded_census_matches_runtime_jit_cache():
    """Census == runtime jit-cache count, preserved on the SHARDED
    entry: executing the tp=8 apply with real mesh-sharded arrays (two
    distinct batches) compiles exactly the one executable the golden
    budgets."""
    import jax
    import jax.numpy as jnp

    from tools.costguard.entrypoints import tp_mlp_apply

    apply, avals, mesh = tp_mlp_apply(8)
    args = [jnp.ones(a.shape, a.dtype) for a in avals]
    out1 = apply(*args)
    args[-1] = jnp.full(avals[-1].shape, 2.0, avals[-1].dtype)
    out2 = apply(*args)
    assert out1.shape == out2.shape == avals[-1].shape
    assert apply._cache_size() == 1 == \
        load_golden("mlp_apply_tp8", REPO)["report"]["n_executables"]


# ------------------- ISSUE 14: tensor-parallel sharded decode budgets --
def test_tp_sharded_decode_per_device_pool_byte_budget():
    """The sharded-decode golden pair, diffed (PR 8/11 cross-golden
    pattern): ``llm_decode_step_tp8`` lowers the IDENTICAL model, pool
    geometry, and slot grid as ``llm_decode_step`` over an 8-way tp
    mesh — head-sharded pools + Megatron column/row weights — so its
    per-device ``argument_bytes`` must sit exactly 7/8 of the pool +
    sharded-weight bytes below the single-chip entry (±2%): per-device
    KV-pool HBM ∝ 1/shards, the ISSUE 14 acceptance."""
    tp8 = load_golden("llm_decode_step_tp8", REPO)
    base = load_golden("llm_decode_step", REPO)
    assert tp8["meta"]["n_pages"] == base["meta"]["n_pages"]
    assert tp8["meta"]["page_size"] == base["meta"]["page_size"]
    assert tp8["report"]["per_device"]["n_devices"] == 8
    assert base["report"]["per_device"]["n_devices"] == 1
    # the sharded argument bytes, from the entry's committed geometry:
    # two f32 pools [L, pages, psz, H, D] + the column/row-sharded
    # causal-LM weights (wqkv+bqkv+wo+w1+b1+w2 at L=2, d=32, ff=64)
    L, d, ff = 2, 32, 64
    pool_bytes = 2 * (L * tp8["meta"]["n_pages"] * tp8["meta"]["page_size"]
                      * 8 * 4) * 4
    sharded_w = 4 * L * (d * 3 * d + 3 * d + d * d + d * ff + ff + ff * d)
    saved = base["report"]["per_device"]["argument_bytes"] \
        - tp8["report"]["per_device"]["argument_bytes"]
    expect = (pool_bytes + sharded_w) * 7 // 8
    assert abs(saved - expect) <= 0.02 * expect, (
        f"tp=8 per-device argument bytes save {saved} vs the expected "
        f"7/8 of the pool + sharded weights ({expect}) — the head "
        f"shard of the KV pool is no longer ∝ 1/shards")
    # the Megatron all-reduces are visible on the sharded side only,
    # and BOTH sides keep the one-pinned-executable contract
    assert tp8["report"]["per_device"]["collective_bytes"] > 0
    assert base["report"]["per_device"]["collective_bytes"] == 0
    assert tp8["report"]["n_executables"] == \
        base["report"]["n_executables"] == 1


def test_tp_decode_int8_collective_byte_budget():
    """The decode-collective quantization floor, as a diff of two
    COMMITTED goldens: with ``tp_collectives="int8"`` the per-layer
    activation all-reduces (chunked int8 all_to_all/all_gather,
    parallel.quantize) must move >= 25% fewer per-device collective
    bytes than the f32 sibling (committed: ~44% — chunk-scale overhead
    is what keeps it under the asymptotic 4x) over the identical
    model, mesh, and census."""
    f32 = load_golden("llm_decode_step_tp8", REPO)["report"]
    q8 = load_golden("llm_decode_step_tp8_q8", REPO)["report"]
    assert f32["per_device"]["collective_bytes"] > 0
    assert q8["per_device"]["collective_bytes"] <= \
        0.75 * f32["per_device"]["collective_bytes"], (
            f"int8 decode collectives moved "
            f"{q8['per_device']['collective_bytes']} bytes vs f32's "
            f"{f32['per_device']['collective_bytes']} — the committed "
            f">=25% reduction no longer holds")
    assert q8["per_device"]["n_devices"] == \
        f32["per_device"]["n_devices"] == 8
    assert q8["n_executables"] == f32["n_executables"] == 1


def test_regen_device_count_guard():
    """The census guard's device-count leg: a SHARDED golden refuses
    regeneration when the visible device count differs from the one it
    embeds (prevents committing a 1-device 'sharded' budget by
    accident); unsharded goldens and matching environments pass."""
    from tools.costguard.budget import device_count_guard

    sharded = {"n_devices": 8, "meta": {"sharded": True}}
    assert device_count_guard(sharded, 8, "e") is None
    msg = device_count_guard(sharded, 1, "e")
    assert msg is not None and "refusing" in msg and "8" in msg
    unsharded = {"n_devices": 8, "meta": {"sharded": False}}
    assert device_count_guard(unsharded, 1, "e") is None
    assert device_count_guard({"n_devices": 8, "meta": {}}, 1, "e") is None


# ----------------------------------------------------------------- census --
def test_executable_census_components():
    from mxnet_tpu.serving import BucketSpec
    spec = BucketSpec(batch=(1, 2, 4), length=(8, 16))
    assert len(grid_signatures(spec)) == 6
    assert executable_census(spec) == 6
    assert executable_census(spec, 2) == 8           # extra known shapes
    assert executable_census(BucketSpec(batch=(1, 2, 4))) == 3
    with pytest.raises(TypeError):
        executable_census(object())
    with pytest.raises(TypeError):
        executable_census(True)
    with pytest.raises(ValueError):
        executable_census(-1)


def test_executable_census_train_step():
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    step = parallel.TrainStep(net, gluon.loss.L2Loss(),
                              mx.optimizer.create("sgd", learning_rate=0.1),
                              mesh=parallel.make_mesh(dp=-1))
    assert executable_census(step) == 1


# --------------------------------------------------------------- THE GATE --
def test_budget_gate_committed_tree():
    """Every committed budget golden holds against a fresh lower+compile
    of its entry point, the static census matches the budgeted
    executable count, and no golden is stale.  This is the regression
    floor ROADMAP items 3 and 5 refactor against: moving compile
    plumbing around must keep these numbers (or consciously regen)."""
    result = run_check(root=REPO, use_cache=True)
    assert len(result.entries) >= 3
    assert result.ok, "\n" + result.render()
    for e in result.entries:
        assert e.gated, (f"{e.name}: golden environment does not match "
                         f"the tier-1 bring-up — regen the goldens")
        assert e.census == e.report["n_executables"]


def test_budget_gate_trips_on_extra_bucket():
    """Inflating the serving grid by one batch bucket must FAIL the
    committed budget with a readable per-metric diff (the recompile
    ceiling is part of the budget, not a comment)."""
    built = entrypoints.build("serving_mlp_grid",
                              batch_buckets=(1, 2, 4, 8))
    rep = report_for_programs(built.programs)
    golden = load_golden("serving_mlp_grid", REPO)
    rows = diff_report(rep, golden)
    bad = {r.metric: r for r in rows if not r.ok}
    assert "n_executables" in bad          # 8 executables > budgeted 6
    assert "flops" in bad                  # and the program as written grew
    assert "donation.total_args" in bad    # with two more argument lists
    assert bad["n_executables"].rel > 0
    text = "\n".join(r.render() for r in rows)
    assert "REGRESSION" in text and "n_executables" in text, text


def test_budget_gate_trips_on_inflated_activations():
    """The graph-inflation form of the ISSUE 6 acceptance: the serving
    grid with the activation width doubled (features 32 → 64; the dtype
    version of this fixture is a no-op on CPU, where bf16 is emulated
    via converts and costs MORE — see the entry point's docstring) must
    trip the bytes budget (the lowered module's bytes: what the program
    we wrote touches, whatever XLA:CPU fuses) with a readable per-metric
    diff."""
    built = entrypoints.build("serving_mlp_grid", features=64)
    rep = report_for_programs(built.programs)
    golden = load_golden("serving_mlp_grid", REPO)
    rows = diff_report(rep, golden)
    bad = {r.metric: r for r in rows if not r.ok}
    assert "bytes_accessed" in bad, [r.render() for r in rows]
    assert bad["bytes_accessed"].rel > 0
    # and the diff is readable: budget, actual, and the tolerance all
    # appear in the rendered row
    line = bad["bytes_accessed"].render()
    assert "budget=" in line and "actual=" in line and "±" in line
    assert "REGRESSION" in line


def test_budget_diff_fails_on_missing_metric():
    """A budgeted metric the fresh report no longer carries (an
    extraction path going dark) must FAIL the row, not skip it."""
    golden = load_golden("mnist_mlp_train", REPO)
    rep = json.loads(json.dumps(golden["report"]))
    rep["memory"] = {}                  # memory_analysis went dark
    rows = diff_report(rep, golden)
    missing = [r for r in rows if r.metric.startswith("memory.")]
    assert missing and not any(r.ok for r in missing)
    assert "missing" in missing[0].render()
    # and the failure report stays STRICT json (NaN/inf never leak to
    # the wire — CI tooling must be able to parse the failing audit)
    from tools.costguard import CheckResult, EntryResult
    res = CheckResult(entries=[EntryResult(name="x", report=rep,
                                           golden=golden, rows=rows)],
                      stale_goldens=[])
    log = json.loads(res.to_json())     # json.loads is strict on NaN
    assert log["ok"] is False


def test_stale_golden_detected_with_explicit_entries(tmp_path):
    """Deleting a registration while keeping its golden must fail even
    when the audit names explicit entries (the documented path-target
    invocation resolves to an explicit list)."""
    import shutil
    gdir = tmp_path / "tests" / "goldens" / "budgets"
    gdir.mkdir(parents=True)
    shutil.copy(REPO / "tests" / "goldens" / "budgets"
                / "serving_mlp_grid.json", gdir / "serving_mlp_grid.json")
    (gdir / "ghost_entry.json").write_text("{}")
    res = run_check(entries=["serving_mlp_grid"], root=tmp_path)
    assert res.stale_goldens == ["ghost_entry"]
    assert not res.ok
    assert "ghost_entry" in res.render()


def test_environment_mismatch_reports_without_gating(tmp_path):
    """A golden recorded in a different environment (e.g. on-TPU) must
    not gate here: CPU bytes are not TPU bytes (PERF.md) — the entry
    reports, flags nothing, and is marked not-gated."""
    from tools.costguard import check_entry
    golden = load_golden("serving_mlp_grid", REPO)
    foreign = dict(golden, n_devices=1)     # pretend: recorded elsewhere
    gdir = tmp_path / "tests" / "goldens" / "budgets"
    gdir.mkdir(parents=True)
    (gdir / "serving_mlp_grid.json").write_text(json.dumps(foreign))
    res = check_entry("serving_mlp_grid", tmp_path)
    assert res.gated is False
    assert res.ok and not res.rows and not res.problems
    from tools.costguard import CheckResult
    rendered = CheckResult(entries=[res], stale_goldens=[]).render()
    assert "report-only" in rendered


def test_failing_entry_names_the_goldens_jax(tmp_path):
    """The jax version does not gate: the budgeted rows are meant to
    survive a bump.  A FAILING entry whose golden was cut under another
    jax says so in one line; a passing one says nothing."""
    from tools.costguard import check_entry
    golden = load_golden("mlp_apply_tp1", REPO)
    gdir = tmp_path / "tests" / "goldens" / "budgets"
    gdir.mkdir(parents=True)
    stale = dict(golden, jax_version="0.0.1")
    (gdir / "mlp_apply_tp1.json").write_text(json.dumps(stale))
    res = check_entry("mlp_apply_tp1", tmp_path)
    assert res.gated and res.ok and not res.problems
    stale["report"] = dict(golden["report"],
                           flops=golden["report"]["flops"] * 2)
    (gdir / "mlp_apply_tp1.json").write_text(json.dumps(stale))
    res = check_entry("mlp_apply_tp1", tmp_path)
    assert not res.ok
    assert any("golden cut under jax 0.0.1" in p for p in res.problems)


def test_budget_diff_flags_stale_improvement():
    """Beating the budget beyond tolerance is ALSO a failure — the
    golden must be ratcheted, not quietly slack."""
    golden = load_golden("mnist_mlp_train", REPO)
    shrunk = json.loads(json.dumps(golden["report"]))
    shrunk["flops"] = golden["report"]["flops"] * 0.5
    shrunk["bytes_accessed"] = golden["report"]["bytes_accessed"] * 0.5
    rows = diff_report(shrunk, golden)
    row = [r for r in rows if r.metric == "flops"][0]
    assert not row.ok and row.rel < 0
    assert "ratchet" in row.render()


# ------------------------------------------------------------ report cache --
def test_report_cache_roundtrip(tmp_path):
    built = entrypoints.build("serving_mlp_grid")
    cold = report_for_programs(built.programs, root=tmp_path,
                               use_cache=True, cache_dir=tmp_path / "c")
    assert list((tmp_path / "c").glob("*.json"))    # records written
    built2 = entrypoints.build("serving_mlp_grid")
    warm = report_for_programs(built2.programs, root=tmp_path,
                               use_cache=True, cache_dir=tmp_path / "c")
    assert cold == warm
    # a DIFFERENT program must miss (the key is the lowered HLO hash,
    # not the entry name): same name, wider feature dim
    built3 = entrypoints.build("serving_mlp_grid", features=48)
    other = report_for_programs(built3.programs, root=tmp_path,
                                use_cache=True, cache_dir=tmp_path / "c")
    assert other["bytes_accessed"] != cold["bytes_accessed"]


# ------------------------------------------------------------------- CLI ---
def test_cli_exits_zero_on_committed_tree_with_json():
    """The documented gate invocation (fast entries; the in-process gate
    above already compiled the full set through the shared cache)."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.costguard", "mnist_mlp_train",
         "serving_mlp_grid", "--format", "json", "--root", str(REPO)],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    log = json.loads(proc.stdout)
    assert log["ok"] is True
    assert {e["name"] for e in log["entries"]} == {"mnist_mlp_train",
                                                   "serving_mlp_grid"}
    for e in log["entries"]:
        assert e["report"]["n_executables"] == e["census"]


def test_cli_list_and_bad_target():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.costguard", "--list"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0
    for name in ("resnet50_nhwc_train", "mnist_mlp_train",
                 "serving_mlp_grid"):
        assert name in proc.stdout
    proc = subprocess.run(
        [sys.executable, "-m", "tools.costguard", "no_such_entry"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 2            # usage error, not a crash
    # a path with no registered entries still audits the goldens
    # directory (the reverse check is selection-independent)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.costguard", "examples",
         "--root", str(REPO)],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "auditing goldens only" in proc.stderr


def test_cli_path_target_maps_to_entries():
    """``python -m tools.costguard mxnet_tpu/`` audits the registered
    surface: path targets resolve to entry points (selection logic only
    — the full audit of that invocation is the in-process gate test)."""
    from tools.costguard.__main__ import _selects_entry
    assert _selects_entry("resnet50_nhwc_train",
                          (REPO / "mxnet_tpu").resolve(), REPO)
    assert _selects_entry("resnet50_nhwc_train",
                          (REPO / "tools").resolve(), REPO)   # builder file
    assert not _selects_entry("resnet50_nhwc_train",
                              (REPO / "examples").resolve(), REPO)
