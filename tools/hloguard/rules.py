"""hloguard rules: structural facts → findings + per-entry census.

Two enforcement layers, deliberately redundant (docs/analysis.md
"Structural HLO lint"):

1. **Pattern findings** — donation-gap, precision-leak,
   collective-schedule — are per-site diagnostics with the mxlint
   clean-tree discipline: fix it, or suppress it in the entry's golden
   with a written justification.
2. **Census pins** — every rule also contributes exact counts to the
   entry's structural census, diffed leaf-for-leaf against the
   committed golden.  A suppressed pattern can therefore never silently
   absorb NEW regressions: the counts move, the census trips.

Facts extraction is pure text → JSON (cacheable under the HLO-hash
FileCache); rule evaluation over facts is cheap and always runs.
"""
from __future__ import annotations

import hashlib

from . import hlo

#: bump when facts extraction or any rule's logic changes — keys the
#: .hloguard_cache signature AND is recorded in structural goldens, so
#: neither a stale cached record nor an old-schema golden can pass
REPORT_VERSION = "1.1"

#: a parameter smaller than this never raises donation-gap — tiny
#: scalars/counters are not worth donation plumbing (64 KiB)
DONATION_BYTES_FLOOR = 1 << 16

_FLOAT = {"f32", "f64", "bf16", "f16"}
#: "quantized" dtypes for the laundering chain rule: a convert UP from
#: one of these to f32 reaching a convert DOWN back is the pattern that
#: silently forfeits the int8 win (EQuARX, arXiv:2506.17615)
_QUANT = {"i8", "i4", "s8", "u8", "s4", "u4", "f8e4m3fn", "f8e5m2"}
_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "i8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "i16": 2,
    "s32": 4, "u32": 4, "f32": 4, "i32": 4,
    "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "f64": 8, "i64": 8, "c64": 8, "c128": 16,
}

#: custom calls that are jax's own lowering of ``shard_map`` and sharding
#: constraints (markers for the SPMD partitioner), not part of the program
#: we wrote: how many it emits is a jax version's business (58 in the tp
#: decode step under 0.4.37, none under 0.9.0), so the census leaves them out
_PARTITIONER_MARKERS = frozenset(
    {"Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape"})

RULES = {
    "donation-gap": (
        "large float ENTRY parameter matches an output shape/dtype but "
        "is not donated (input_output_alias / jax.buffer_donor)"),
    "precision-leak": (
        "f32 dot/conv in a bf16/int8-policy entry, or a convert up/down "
        "chain laundering quantized values through f32"),
    "collective-schedule": (
        "per-entry collective census by kind, collectives inside while "
        "bodies, all-reduce where the golden pins a two-phase exchange"),
    "copy-churn": (
        "copy/transpose instruction counts pinned per entry — layout "
        "regressions caught before they show up as bytes"),
    "custom-call-census": (
        "unique-vs-total Pallas/Mosaic custom-call payloads per entry "
        "(the static dedup metric for ROADMAP item 4)"),
    "hlo-structure": (
        "program count / parse health of the entry's lowered modules"),
    "missing-golden": (
        "registered surface has no committed structural golden under "
        "tests/goldens/hloguard/"),
    "stale-golden": (
        "committed structural golden whose surface is no longer "
        "registered"),
    "stale-suppression": (
        "golden suppression that matched no finding — delete it or fix "
        "its match string"),
    "bad-suppression": (
        "golden suppression without a written justification (cannot "
        "itself be suppressed)"),
}


def _short_hash(text: str) -> str:
    return hashlib.sha256(text.encode("utf-8")).hexdigest()[:12]


def _nbytes(dims, dtype) -> int:
    unit = _DTYPE_BYTES.get(dtype or "", 0)
    n = unit
    for d in dims or ():
        n *= d
    return n


def extract_facts(text: str) -> dict:
    """Parse one lowered module and distil the JSON-safe facts every
    rule consumes.  This is the expensive half (memoized by the
    HLO-hash cache); rules over facts are cheap and always run."""
    mod = hlo.parse_module(text)
    if not mod.ok or mod.main is None:
        return {"ok": False,
                "error": mod.error or "no public entry function"}
    reach = hlo.reachable_funcs(mod)
    while_funcs = hlo.funcs_reached_from_while(mod)
    main = mod.main

    params = [{
        "index": p.index,
        "dtype": p.dtype,
        "dims": list(p.dims) if p.dims is not None else None,
        "bytes": _nbytes(p.dims, p.dtype),
        "aliased": p.aliased,
        "donor": p.donor,
    } for p in main.params]
    outputs = [{"dtype": dt, "dims": list(dims) if dims is not None
                else None} for dims, dt in main.results]

    f32_dot_conv = []
    launder = []
    coll_by_kind: dict = {}
    coll_in_while = 0
    copies = {"copy": 0, "transpose": 0}
    cc_targets: dict = {}
    pallas_payloads = []
    pallas_normalized = []

    def _is_up_convert(op):
        return (op.kind == "convert" and op.operand_types
                and op.result_types
                and op.operand_types[0][1] in _QUANT
                and op.result_types[0][1] in ("f32", "f64"))

    for fname in sorted(reach):
        func = mod.funcs[fname]
        in_while_func = fname in while_funcs
        for op in func.ops:
            if op.kind in ("dot_general", "dot", "convolution"):
                op_dts = [dt for _, dt in op.operand_types[:2]]
                if len(op_dts) >= 2 and all(dt == "f32" for dt in op_dts):
                    f32_dot_conv.append(
                        {"kind": op.kind, "func": fname, "line": op.line})
            elif op.kind == "convert":
                if (op.operand_types and op.result_types
                        and op.operand_types[0][1] in ("f32", "f64")
                        and op.result_types[0][1] in _QUANT):
                    # a dot/conv between the converts means the f32
                    # interlude IS the compute (the quantized-wire
                    # dequant->matmul->quant pattern, which is the
                    # point) — only a compute-free up/down round trip
                    # launders
                    up = hlo.trace_back(
                        func, op, _is_up_convert,
                        stop=lambda d: d.kind in ("dot_general", "dot",
                                                  "convolution"))
                    if up is not None:
                        launder.append({
                            "func": fname, "line": op.line,
                            "src": up.operand_types[0][1],
                            "dst": op.result_types[0][1]})
            elif op.kind in hlo.COLLECTIVE_KINDS:
                coll_by_kind[op.kind] = coll_by_kind.get(op.kind, 0) + 1
                if op.in_while or in_while_func:
                    coll_in_while += 1
            elif op.kind in copies:
                copies[op.kind] += 1
            if (op.kind == "custom_call"
                    and op.target not in _PARTITIONER_MARKERS):
                tgt = op.target or "?"
                cc_targets[tgt] = cc_targets.get(tgt, 0) + 1
                if tgt == "tpu_custom_call" and op.payload is not None:
                    pallas_payloads.append(_short_hash(op.payload))
                    pallas_normalized.append(
                        _short_hash(hlo.normalize_payload(op.payload)))

    return {
        "ok": True,
        "error": None,
        "n_funcs": len(reach),
        "params": params,
        "outputs": outputs,
        "f32_dot_conv": f32_dot_conv,
        "launder": launder,
        "collectives": {"by_kind": coll_by_kind, "in_while": coll_in_while},
        "copies": copies,
        "custom_calls": {"targets": cc_targets,
                         "payloads": pallas_payloads,
                         "normalized": pallas_normalized},
    }


def donation_gaps(facts: dict) -> list:
    """Undonated candidate params of one program: float, above the
    bytes floor, shape/dtype-matching some output, not aliased and not
    a declared donor."""
    if not facts.get("ok"):
        return []
    out_shapes = {(tuple(o["dims"] or ()), o["dtype"])
                  for o in facts["outputs"]}
    gaps = []
    for p in facts["params"]:
        if p["dtype"] not in _FLOAT or p["bytes"] < DONATION_BYTES_FLOOR:
            continue
        if p["aliased"] or p["donor"]:
            continue
        if (tuple(p["dims"] or ()), p["dtype"]) in out_shapes:
            gaps.append(p)
    return gaps


def donation_counts(facts: dict) -> dict:
    """Census row: candidates (big float params matching an output) /
    donated (aliased or donor) / gaps."""
    if not facts.get("ok"):
        return {"candidates": 0, "donated": 0, "gaps": 0}
    out_shapes = {(tuple(o["dims"] or ()), o["dtype"])
                  for o in facts["outputs"]}
    cand = don = 0
    for p in facts["params"]:
        if p["dtype"] not in _FLOAT or p["bytes"] < DONATION_BYTES_FLOOR:
            continue
        if (tuple(p["dims"] or ()), p["dtype"]) not in out_shapes:
            continue
        cand += 1
        if p["aliased"] or p["donor"]:
            don += 1
    return {"candidates": cand, "donated": don, "gaps": cand - don}


def entry_census(facts_by_prog: dict) -> dict:
    """Aggregate per-program facts into the entry's structural census —
    the exact record a golden pins."""
    donation = {"candidates": 0, "donated": 0, "gaps": 0}
    precision = {"f32_dot_conv": 0, "launder_chains": 0}
    by_kind: dict = {}
    in_while = 0
    copies = {"copy": 0, "transpose": 0}
    targets: dict = {}
    payloads: list = []
    normalized: list = []
    total_cc = 0
    parse_errors = 0
    for _prog, f in sorted(facts_by_prog.items()):
        if not f.get("ok"):
            parse_errors += 1
            continue
        d = donation_counts(f)
        for k in donation:
            donation[k] += d[k]
        precision["f32_dot_conv"] += len(f["f32_dot_conv"])
        precision["launder_chains"] += len(f["launder"])
        for k, v in f["collectives"]["by_kind"].items():
            by_kind[k] = by_kind.get(k, 0) + v
        in_while += f["collectives"]["in_while"]
        for k in copies:
            copies[k] += f["copies"][k]
        for k, v in f["custom_calls"]["targets"].items():
            targets[k] = targets.get(k, 0) + v
        payloads.extend(f["custom_calls"]["payloads"])
        normalized.extend(f["custom_calls"]["normalized"])
        total_cc += sum(f["custom_calls"]["targets"].values())
    return {
        "donation": donation,
        "precision": precision,
        "collectives": {"total": sum(by_kind.values()),
                        "in_while": in_while,
                        "by_kind": dict(sorted(by_kind.items()))},
        "copies": copies,
        "custom_calls": {"total": total_cc,
                         "pallas_total": len(payloads),
                         "pallas_unique": len(set(payloads)),
                         "pallas_unique_normalized": len(set(normalized)),
                         "targets": dict(sorted(targets.items()))},
        "programs": len(facts_by_prog),
        "parse_errors": parse_errors,
    }


def pattern_findings(entry: str, meta: dict, facts_by_prog: dict) -> list:
    """Per-site diagnostics: (rule, severity, message) triples."""
    out = []
    policy = (meta or {}).get("precision")
    for prog, f in sorted(facts_by_prog.items()):
        if not f.get("ok"):
            out.append(("hlo-structure", "warning",
                        f"{prog}: HLO parse skipped: {f.get('error')}"))
            continue
        for p in donation_gaps(f):
            dims = "x".join(str(d) for d in (p["dims"] or ()))
            out.append((
                "donation-gap", "error",
                f"{prog}: param %arg{p['index']} "
                f"{p['dtype']}[{dims}] ({p['bytes'] // 1024} KiB) "
                f"matches an output shape but is not donated"))
        if policy in ("bf16", "int8"):
            for d in f["f32_dot_conv"]:
                out.append((
                    "precision-leak", "error",
                    f"{prog}: f32 {d['kind']} in {policy}-policy entry "
                    f"(func @{d['func']} line {d['line']})"))
            for ch in f["launder"]:
                out.append((
                    "precision-leak", "error",
                    f"{prog}: convert chain {ch['src']}->f32->{ch['dst']} "
                    f"launders quantized values through f32 "
                    f"(func @{ch['func']} line {ch['line']})"))
        # collectives inside while bodies serialize every iteration on
        # the slowest device — flag each kind once per program
        if f["collectives"]["in_while"]:
            out.append((
                "collective-schedule", "error",
                f"{prog}: {f['collectives']['in_while']} collective(s) "
                f"inside while bodies"))
    return out


def census_findings(entry: str, golden_census: dict, census: dict) -> list:
    """Leaf-for-leaf census diff vs the committed golden.  Both
    directions fail — a regression AND a stale golden (the costguard
    ratchet discipline)."""
    _SECTION_RULE = {
        "donation": "donation-gap", "precision": "precision-leak",
        "collectives": "collective-schedule", "copies": "copy-churn",
        "custom_calls": "custom-call-census",
    }

    def leaves(prefix, d):
        for k, v in d.items():
            p = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, dict):
                yield from leaves(p, v)
            else:
                yield p, v

    gold = dict(leaves("", golden_census))
    now = dict(leaves("", census))
    out = []
    for path in sorted(set(gold) | set(now)):
        g, n = gold.get(path, 0), now.get(path, 0)
        if g == n:
            continue
        rule = _SECTION_RULE.get(path.split(".")[0], "hlo-structure")
        msg = (f"{entry}: {path} changed: golden {g} -> now {n} "
               f"(regen tests/goldens/hloguard/ if intended)")
        if (path.startswith("collectives.by_kind.all_reduce") and n > g
                and golden_census.get("collectives", {})
                                 .get("by_kind", {}).get("all_to_all")):
            msg = (f"{entry}: {path} {g} -> {n}: all-reduce introduced "
                   f"where the golden pins the quantized "
                   f"all_to_all->all_gather two-phase exchange")
        out.append((rule, "error", msg))
    return out
