"""hloguard: structural lint over lowered HLO (tools/hloguard).

Four legs:

* **Parser fixtures** — synthetic StableHLO text exercising exactly the
  structures the rules read: donation attrs, convert up/down chains,
  collectives inside while bodies (directly and via ``func.call`` —
  the fori_loop lowering shape), duplicate vs shape-normalized
  custom-call payloads, malformed-module graceful skip.
* **Seeded regressions** — one fixture per rule that TRIPS: a dropped
  donation, an f32 dot injected into a bf16-policy entry, a duplicated
  custom call moving the census.
* **Engine contract** — goldens, suppressions (justification required,
  stale flagged, bad-suppression unsuppressible), environment gating,
  the HLO-hash facts cache, SARIF output.
* **The committed-tree gate** — ``run_check`` over every registered
  surface must be OK with zero unsuppressed findings (the tier-1
  acceptance; docs/analysis.md "Structural HLO lint").
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.hloguard import (REPORT_VERSION, RULES, check_entry,  # noqa: E402
                            engine, load_golden, run_check)
from tools.hloguard import hlo, rules, surfaces  # noqa: E402
from tools.hloguard.engine import facts_for_programs  # noqa: E402
from tools.hloguard.rules import (census_findings, donation_gaps,  # noqa: E402
                                  entry_census, extract_facts,
                                  pattern_findings)

pytestmark = pytest.mark.hloguard


# ---------------------------------------------------------------------------
# synthetic StableHLO fixtures
# ---------------------------------------------------------------------------

# 256x256xf32 = 256 KiB: comfortably above DONATION_BYTES_FLOOR.
# %arg0: candidate with a matching output, NOT donated  -> the gap
# %arg1: same shape, donated via tf.aliasing_output     -> covered
# %arg2: tiny                                           -> below floor
DONATION_TEXT = """\
module @jit_step {
  func.func public @main(%arg0: tensor<256x256xf32>, %arg1: tensor<256x256xf32> {tf.aliasing_output = 0 : i32}, %arg2: tensor<4xf32> {jax.buffer_donor = true}) -> (tensor<256x256xf32>, tensor<256x256xf32>) {
    %0 = stablehlo.add %arg0, %arg1 : tensor<256x256xf32>
    %1 = stablehlo.add %0, %arg1 : tensor<256x256xf32>
    return %0, %1 : tensor<256x256xf32>, tensor<256x256xf32>
  }
}
"""

F32_DOT_TEXT = """\
module @jit_fwd {
  func.func public @main(%arg0: tensor<128x128xf32>, %arg1: tensor<128x128xf32>) -> (tensor<128x128xf32>) {
    %0 = stablehlo.dot_general %arg0, %arg1, contracting_dims = [1] x [0] : (tensor<128x128xf32>, tensor<128x128xf32>) -> tensor<128x128xf32>
    return %0 : tensor<128x128xf32>
  }
}
"""

BF16_DOT_TEXT = F32_DOT_TEXT.replace("f32", "bf16")

# i8 -> f32 -> (compute-free interlude) -> i8: the laundering chain
LAUNDER_TEXT = """\
module @jit_q {
  func.func public @main(%arg0: tensor<128xi8>) -> (tensor<128xi8>) {
    %0 = stablehlo.convert %arg0 : (tensor<128xi8>) -> tensor<128xf32>
    %1 = stablehlo.add %0, %0 : tensor<128xf32>
    %2 = stablehlo.convert %1 : (tensor<128xf32>) -> tensor<128xi8>
    return %2 : tensor<128xi8>
  }
}
"""

# same round trip but THROUGH a dot: the f32 interlude is the compute
# (the quantized-wire dequant->matmul->quant pattern) — not laundering
WIRE_TEXT = """\
module @jit_q {
  func.func public @main(%arg0: tensor<128x128xi8>, %arg1: tensor<128x128xf32>) -> (tensor<128x128xi8>) {
    %0 = stablehlo.convert %arg0 : (tensor<128x128xi8>) -> tensor<128x128xf32>
    %1 = stablehlo.dot_general %0, %arg1, contracting_dims = [1] x [0] : (tensor<128x128xf32>, tensor<128x128xf32>) -> tensor<128x128xf32>
    %2 = stablehlo.convert %1 : (tensor<128x128xf32>) -> tensor<128x128xi8>
    return %2 : tensor<128x128xi8>
  }
}
"""

WHILE_COLLECTIVE_TEXT = """\
module @jit_loop {
  func.func public @main(%arg0: tensor<8xf32>) -> (tensor<8xf32>) {
    %0 = "stablehlo.all_gather"(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    %1 = stablehlo.while(%iterArg = %0) cond {
      stablehlo.return %iterArg : tensor<8xf32>
    } do {
      %2 = "stablehlo.all_reduce"(%iterArg) : (tensor<8xf32>) -> tensor<8xf32>
      stablehlo.return %2 : tensor<8xf32>
    }
    return %1 : tensor<8xf32>
  }
}
"""

# fori_loop shape: the while body is a func.call to a private func, and
# the collective lives in the CALLEE — only call-graph transitivity sees
# it (and @helper one call deeper still)
WHILE_CALL_TEXT = """\
module @jit_loop {
  func.func public @main(%arg0: tensor<8xf32>) -> (tensor<8xf32>) {
    %0 = stablehlo.while(%iterArg = %arg0) cond {
      stablehlo.return %iterArg : tensor<8xf32>
    } do {
      %1 = func.call @body(%iterArg) : (tensor<8xf32>) -> tensor<8xf32>
      stablehlo.return %1 : tensor<8xf32>
    }
    return %0 : tensor<8xf32>
  }
  func.func private @body(%arg0: tensor<8xf32>) -> (tensor<8xf32>) {
    %0 = func.call @helper(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    return %0 : tensor<8xf32>
  }
  func.func private @helper(%arg0: tensor<8xf32>) -> (tensor<8xf32>) {
    %0 = "stablehlo.all_reduce"(%arg0) : (tensor<8xf32>) -> tensor<8xf32>
    return %0 : tensor<8xf32>
  }
}
"""


def _custom_call_text(payloads):
    ops = "\n".join(
        f'    %{i} = stablehlo.custom_call @tpu_custom_call(%arg0) '
        f'{{backend_config = "{p}"}} : '
        f'(tensor<8x128xf32>) -> tensor<8x128xf32>'
        for i, p in enumerate(payloads))
    last = len(payloads) - 1
    return (
        "module @jit_k {\n"
        "  func.func public @main(%arg0: tensor<8x128xf32>) -> "
        "(tensor<8x128xf32>) {\n"
        f"{ops}\n"
        f"    return %{last} : tensor<8x128xf32>\n"
        "  }\n"
        "}\n")


# ---------------------------------------------------------------------------
# parser
# ---------------------------------------------------------------------------

def test_parse_donation_attrs():
    mod = hlo.parse_module(DONATION_TEXT)
    assert mod.ok and mod.main is not None
    p0, p1, p2 = mod.main.params
    assert (p0.aliased, p0.donor) == (False, False)
    assert p1.aliased and not p1.donor
    assert p2.donor and not p2.aliased
    assert p0.dims == (256, 256) and p0.dtype == "f32"
    assert [dt for _, dt in mod.main.results] == ["f32", "f32"]


def test_parse_collective_in_while_direct():
    facts = extract_facts(WHILE_COLLECTIVE_TEXT)
    assert facts["ok"]
    assert facts["collectives"]["by_kind"] == {"all_gather": 1,
                                               "all_reduce": 1}
    # the all_gather is outside the loop; only the all_reduce is inside
    assert facts["collectives"]["in_while"] == 1


def test_parse_collective_in_while_via_call():
    mod = hlo.parse_module(WHILE_CALL_TEXT)
    assert mod.ok
    # transitively: main's while calls @body, @body calls @helper
    assert hlo.funcs_reached_from_while(mod) == {"body", "helper"}
    facts = extract_facts(WHILE_CALL_TEXT)
    assert facts["collectives"]["in_while"] == 1
    findings = pattern_findings("e", {}, {"p": facts})
    assert any(r == "collective-schedule" and "inside while" in m
               for r, _s, m in findings)


def test_parse_custom_call_payload_duplicates():
    facts = extract_facts(_custom_call_text(["PAYLOAD_A", "PAYLOAD_A",
                                             "PAYLOAD_B"]))
    cc = facts["custom_calls"]
    assert cc["targets"] == {"tpu_custom_call": 3}
    assert len(cc["payloads"]) == 3 and len(set(cc["payloads"])) == 2


def test_partitioner_markers_are_not_counted():
    """``Sharding`` / ``SPMD*ToShape`` custom calls are jax's lowering of
    shard_map, not our program: the census leaves them out of targets
    and total, and counts every other target."""
    text = _custom_call_text(["KERN_A"]).replace(
        "    return", "".join(
            f'    %m{i} = stablehlo.custom_call @{t}(%arg0) : '
            f'(tensor<8x128xf32>) -> tensor<8x128xf32>\n'
            for i, t in enumerate(("Sharding", "SPMDFullToShardShape",
                                   "SPMDShardToFullShape", "my_ffi")))
        + "    return")
    cc = entry_census({"p": extract_facts(text)})["custom_calls"]
    assert cc["targets"] == {"my_ffi": 1, "tpu_custom_call": 1}
    assert cc["total"] == 2 and cc["pallas_total"] == 1


def test_parse_custom_call_shape_normalized():
    # same kernel at two geometries: raw payloads differ, the
    # shape-normalized forms collapse (ROADMAP item 4's dedup signal)
    facts = extract_facts(_custom_call_text(
        ["kern grid=8 tensor<8x128xf32>", "kern grid=16 tensor<16x128xf32>"]))
    cc = facts["custom_calls"]
    assert len(set(cc["payloads"])) == 2
    assert len(set(cc["normalized"])) == 1


def test_parse_malformed_graceful_skip():
    for bad in ("module @m {\n  func.func public @main() -> () {\n",
                "not hlo at all", ""):
        mod = hlo.parse_module(bad)
        assert not mod.ok and mod.error
    facts = extract_facts("module @m {")
    assert not facts["ok"]
    findings = pattern_findings("e", {}, {"p": facts})
    assert [(r, s) for r, s, _m in findings] == [("hlo-structure",
                                                  "warning")]
    # and a broken program still contributes to the census as a parse
    # error rather than silently vanishing
    assert entry_census({"p": facts})["parse_errors"] == 1


# ---------------------------------------------------------------------------
# rules: seeded regressions (one trip per rule)
# ---------------------------------------------------------------------------

def test_seeded_donation_gap_trips():
    facts = extract_facts(DONATION_TEXT)
    gaps = donation_gaps(facts)
    assert [g["index"] for g in gaps] == [0]
    findings = pattern_findings("e", {}, {"p": facts})
    assert any(r == "donation-gap" and "%arg0" in m and "256 KiB" in m
               for r, _s, m in findings)
    census = entry_census({"p": facts})
    assert census["donation"] == {"candidates": 2, "donated": 1,
                                  "gaps": 1}
    # donating the param clears the finding
    fixed = DONATION_TEXT.replace(
        "%arg0: tensor<256x256xf32>,",
        "%arg0: tensor<256x256xf32> {tf.aliasing_output = 1 : i32},")
    assert donation_gaps(extract_facts(fixed)) == []


def test_seeded_f32_dot_in_bf16_entry_trips():
    facts = extract_facts(F32_DOT_TEXT)
    hits = [m for r, _s, m in
            pattern_findings("e", {"precision": "bf16"}, {"p": facts})
            if r == "precision-leak"]
    assert hits and "f32 dot_general in bf16-policy entry" in hits[0]
    def leaks(meta, f):
        return [m for r, _s, m in pattern_findings("e", meta, {"p": f})
                if r == "precision-leak"]
    # the same dot in an f32-policy entry is fine ...
    assert not leaks({"precision": "f32"}, facts)
    # ... and a bf16 dot in the bf16 entry is fine
    assert not leaks({"precision": "bf16"}, extract_facts(BF16_DOT_TEXT))


def test_seeded_launder_chain_trips():
    facts = extract_facts(LAUNDER_TEXT)
    assert [(c["src"], c["dst"]) for c in facts["launder"]] == [("i8",
                                                                 "i8")]
    hits = [m for r, _s, m in
            pattern_findings("e", {"precision": "int8"}, {"p": facts})
            if "launders" in m]
    assert hits and "i8->f32->i8" in hits[0]
    # dequant -> dot -> quant is the intended wire pattern, not a chain
    assert extract_facts(WIRE_TEXT)["launder"] == []


def test_seeded_duplicate_custom_call_trips_census():
    base = extract_facts(_custom_call_text(["KERN_A", "KERN_B"]))
    golden = entry_census({"p": base})
    dup = extract_facts(_custom_call_text(["KERN_A", "KERN_B", "KERN_A"]))
    now = entry_census({"p": dup})
    trips = census_findings("e", golden, now)
    paths = {m.split(" changed")[0] for r, _s, m in trips
             if r == "custom-call-census"}
    # total moved, unique did not: a re-instantiation, not a new kernel
    assert "e: custom_calls.pallas_total" in paths
    assert now["custom_calls"]["pallas_unique"] == \
        golden["custom_calls"]["pallas_unique"]
    # identical census diffs clean
    assert census_findings("e", golden, entry_census({"p": base})) == []


def test_census_all_reduce_vs_two_phase_message():
    golden = {"collectives": {"total": 2, "in_while": 0,
                              "by_kind": {"all_gather": 1,
                                          "all_to_all": 1}}}
    now = {"collectives": {"total": 3, "in_while": 0,
                           "by_kind": {"all_gather": 1, "all_to_all": 1,
                                       "all_reduce": 1}}}
    trips = census_findings("e", golden, now)
    assert any(r == "collective-schedule"
               and "two-phase exchange" in m for r, _s, m in trips)


def test_census_copy_churn_trips_both_directions():
    g = {"copies": {"copy": 2, "transpose": 1}}
    up = census_findings("e", g, {"copies": {"copy": 5, "transpose": 1}})
    down = census_findings("e", g, {"copies": {"copy": 0,
                                               "transpose": 1}})
    assert any(r == "copy-churn" for r, _s, _m in up)
    assert any(r == "copy-churn" for r, _s, _m in down)


# ---------------------------------------------------------------------------
# engine: goldens, suppressions, gating, cache
# ---------------------------------------------------------------------------

CHEAP = "mlp_apply_tp1"


def _doctored_root(tmp_path, mutate):
    """Tmp repo root with the CHEAP surface's real golden, mutated."""
    gdir = tmp_path / engine.GOLDEN_SUBDIR
    gdir.mkdir(parents=True)
    golden = load_golden(CHEAP, REPO)
    assert golden is not None
    mutate(golden)
    (gdir / f"{CHEAP}.json").write_text(json.dumps(golden))
    return tmp_path


def test_missing_golden_is_an_error(tmp_path):
    res = check_entry(CHEAP, tmp_path)
    assert not res.ok
    assert [f.rule for f in res.findings] == ["missing-golden"]


def test_golden_roundtrip_is_clean(tmp_path):
    root = _doctored_root(tmp_path, lambda g: None)
    res = check_entry(CHEAP, root)
    assert res.gated and res.ok and res.findings == []


def test_census_drift_trips(tmp_path):
    def mutate(g):
        g["census"]["copies"]["copy"] += 7
    res = check_entry(CHEAP, _doctored_root(tmp_path, mutate))
    assert not res.ok
    assert any(f.rule == "copy-churn" and "golden" in f.message
               for f in res.findings)


def test_env_mismatch_audits_without_gating(tmp_path):
    def mutate(g):
        g["backend"] = "tpu"
        g["census"]["copies"]["copy"] += 7   # would trip if gated
    res = check_entry(CHEAP, _doctored_root(tmp_path, mutate))
    assert not res.gated
    assert res.ok and not any(f.rule == "copy-churn"
                              for f in res.findings)


def test_failing_surface_names_the_goldens_jax(tmp_path):
    """The jax version does not gate; a FAILING surface whose golden was
    cut under another jax says so, a passing one says nothing."""
    def stale(g):
        g["jax_version"] = "0.0.1"
    res = check_entry(CHEAP, _doctored_root(tmp_path / "ok", stale))
    assert res.gated and res.ok and res.findings == []

    def stale_and_drifted(g):
        stale(g)
        g["census"]["copies"]["copy"] += 7
    res = check_entry(CHEAP, _doctored_root(tmp_path / "bad",
                                            stale_and_drifted))
    assert not res.ok
    assert any("golden cut under jax 0.0.1" in f.message
               for f in res.findings)


def test_schema_mismatch_requires_regen(tmp_path):
    def mutate(g):
        g["report_version"] = "0.0"
    res = check_entry(CHEAP, _doctored_root(tmp_path, mutate))
    assert not res.ok
    assert any(f.rule == "hlo-structure" and "regenerate" in f.message
               for f in res.findings)


def test_bad_suppression_is_unsuppressible(tmp_path):
    def mutate(g):
        g["census"]["copies"]["copy"] += 1
        g["suppressions"] = [{"rule": "copy-churn", "match": "copy",
                              "justification": "   "}]
    res = check_entry(CHEAP, _doctored_root(tmp_path, mutate))
    assert not res.ok
    by_rule = {f.rule for f in res.findings if not f.suppressed}
    # the drift stays live AND the empty justification is its own error
    assert {"copy-churn", "bad-suppression"} <= by_rule


def test_justified_suppression_and_stale_warning(tmp_path):
    def mutate(g):
        g["census"]["copies"]["copy"] += 1
        g["suppressions"] = [
            {"rule": "copy-churn", "match": "copies.copy",
             "justification": "seeded drift for the suppression test"},
            {"rule": "donation-gap", "match": "never matches",
             "justification": "left stale on purpose"}]
    res = check_entry(CHEAP, _doctored_root(tmp_path, mutate))
    assert res.ok   # the drift is justified-suppressed
    sup = [f for f in res.findings if f.suppressed]
    assert len(sup) == 1 and sup[0].rule == "copy-churn"
    assert any(f.rule == "stale-suppression"
               and f.severity == "warning" for f in res.findings)


def test_stale_golden_sweep(tmp_path):
    gdir = tmp_path / engine.GOLDEN_SUBDIR
    gdir.mkdir(parents=True)
    (gdir / "no_such_surface.json").write_text("{}")
    res = run_check(entries=[], root=tmp_path)
    assert not res.ok
    assert [f.rule for f in res.extra_findings] == ["stale-golden"]


def test_facts_cache_roundtrip(tmp_path, monkeypatch):
    progs = [("p", DONATION_TEXT), ("q", WHILE_COLLECTIVE_TEXT)]
    cold = facts_for_programs(progs, root=tmp_path, use_cache=True)
    assert (tmp_path / engine.CACHE_DIR_NAME).is_dir()

    def boom(_text):
        raise AssertionError("cache miss on identical text")
    monkeypatch.setattr(engine, "extract_facts", boom)
    warm = facts_for_programs(progs, root=tmp_path, use_cache=True)
    assert json.dumps(warm, sort_keys=True) == json.dumps(cold,
                                                          sort_keys=True)
    # changed text must miss (the HLO-hash key, not the name)
    with pytest.raises(AssertionError):
        facts_for_programs([("p", F32_DOT_TEXT)], root=tmp_path,
                           use_cache=True)


# ---------------------------------------------------------------------------
# output formats + CLI
# ---------------------------------------------------------------------------

def test_sarif_output_shape(tmp_path):
    res = run_check(entries=[], root=tmp_path)   # no goldens: clean
    doc = json.loads(res.to_sarif())
    driver = doc["runs"][0]["tool"]["driver"]
    assert driver["name"] == "hloguard"
    assert driver["version"] == REPORT_VERSION
    assert {r["id"] for r in driver["rules"]} == set(RULES)
    parsed = json.loads(res.to_json())
    assert parsed["ok"] and parsed["report_version"] == REPORT_VERSION


def test_cli_list_and_bad_target(capsys):
    from tools.hloguard.__main__ import main
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    for name in ("pallas_fused_conv_tpu", "llm_decode_step",
                 "resnet50_nhwc_train"):
        assert name in out
    assert "tpu-export" in out and "entrypoint" in out
    with pytest.raises(SystemExit) as e:
        main(["definitely_not_a_surface"])
    assert e.value.code == 2


@pytest.mark.slow
def test_cli_end_to_end_json():
    # a full CLI run re-lowers in a fresh process — slow tier only
    proc = subprocess.run(
        [sys.executable, "-m", "tools.hloguard", CHEAP, "--format",
         "json", "--no-cache"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] and doc["entries"][0]["name"] == CHEAP


# ---------------------------------------------------------------------------
# the committed-tree gate (tier-1 acceptance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("surface, total, unique", [
    # two layers, ONE lowering of each kernel: _scan_fwd and _scan_bwd
    # are jax.jits (PR 28), so the second layer calls the first's
    ("pallas_selective_scan_tpu", 2, 2),
    # the same for flash attention's fwd and its one backward kernel
    ("pallas_flash_attention_tpu", 2, 2),
    # the fused tower repeats one 3x3 geometry
    ("pallas_fused_conv_tpu", 3, 2),
    # paged attention runs two geometries of one kernel
    ("pallas_paged_attention_tpu", 2, 2),
])
def test_export_surface_census_dedup(surface, total, unique):
    """The Pallas census must see through re-instantiation: total is
    the kernels a program traces and lowers before its first step,
    unique the distinct ones among them."""
    s = surfaces.build(surface)
    cc = entry_census(facts_for_programs(s.programs))["custom_calls"]
    assert (cc["pallas_total"], cc["pallas_unique"]) == (total, unique)
    assert cc["targets"] == {"tpu_custom_call": total}
    assert load_golden(surface, REPO)["census"]["custom_calls"] == cc


def test_hloguard_gate_committed_tree():
    """THE gate: every registered surface, against its committed golden,
    in the tier-1 environment — zero unsuppressed findings."""
    res = run_check(root=REPO, use_cache=True)
    assert [e.name for e in res.entries] == surfaces.names()
    ungated = [e.name for e in res.entries if not e.gated]
    assert not ungated, (
        f"surfaces not gated (golden/env mismatch): {ungated}")
    bad = [f.render() for f in res.findings
           if f.severity == "error" and not f.suppressed]
    assert res.ok and not bad, "hloguard gate failed:\n" + "\n".join(bad)
    # every registered costguard entry point is covered
    from tools.costguard import entrypoints
    assert set(entrypoints.names()) <= {e.name for e in res.entries}
