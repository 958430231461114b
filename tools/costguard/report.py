"""Normalized cost reports of lowered and compiled XLA programs.

Static analysis of a program is a count, not a device measurement; it
gates structure, not speed.  This module turns one program unit into a
*normalized report* and merges per-executable reports into one
per-entry-point record that ``budget.py`` diffs against committed
goldens.  FLOPs, bytes accessed and transcendentals are the LOWERED
module's, before XLA optimises it: what the compiled module reports of
them is XLA:CPU's fusion and scheduling at toy shapes (bytes moved up to
+168% over one jax bump with no change of ours), what the lowered
module reports is the program we wrote.  Argument bytes, donation coverage,
collective payload bytes, the device count and the entry computation's
instruction categories need the compiled module.

Nothing here ever executes a step: the inputs are AOT ``Lowered`` /
``Compiled`` objects (``TrainStep.lower()`` or ``jax.jit(f).lower``),
so the whole pipeline runs under ``JAX_PLATFORMS=cpu`` in tier-1.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

#: bump when the report schema or extraction logic changes — it keys the
#: report cache AND is recorded in budget goldens, so a stale cached
#: report (or a golden from an older schema) can never pass silently
REPORT_VERSION = "2.0"

# HloModule header attribute stamped by the SPMD partitioner: how many
# devices one copy of this program spans (1 when absent — a
# single-device or replicated program)
_NUM_PARTITIONS_RE = re.compile(r"\bnum_partitions=(\d+)")

# entry-computation instruction line:  ``%name = SHAPE opcode(...)``.
# SHAPE is either a bare token (f32[8,16]{1,0}) or a tuple type — which
# contains spaces but no nested parens in optimized entry HLO.  Group 1
# is the result shape (the collective-payload accounting reads it),
# group 2 the opcode.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(\([^()]*\)|\S+)\s+"
    r"([a-z][a-z0-9\-]*)\(")

# one typed buffer inside a (possibly tuple) shape: ``f32[8,16]{1,0}``
_SHAPE_TOK = re.compile(r"\b(pred|[a-z]+\d+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_TOK.findall(shape_text):
        unit = _DTYPE_BYTES.get(dt)
        if unit is None:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += unit * n
    return total

# one input/output alias entry on the HloModule header line:
# ``{0}: (5, {}, may-alias)`` — the parameter number is group 1
_ALIAS_RE = re.compile(r"\((\d+), \{\}, (?:may|must)-alias\)")

#: opcode → category.  Anything unlisted is "other"; the categories are
#: the traffic-relevant families from PERF.md's entry-computation
#: accounting table (convs, fusions, copies, collectives, ...).
_CATEGORY = {
    "convolution": "convolution",
    "dot": "dot",
    "fusion": "fusion",
    "custom-call": "custom-call",
    "all-reduce": "collective", "all-reduce-start": "collective",
    "all-reduce-done": "collective", "all-gather": "collective",
    "all-gather-start": "collective", "all-gather-done": "collective",
    "reduce-scatter": "collective", "all-to-all": "collective",
    "collective-permute": "collective",
    "collective-broadcast": "collective",
    "copy": "copy", "copy-start": "copy", "copy-done": "copy",
    "reduce": "reduce", "reduce-window": "reduce",
}
CATEGORIES = ("convolution", "dot", "fusion", "custom-call", "collective",
              "copy", "reduce", "other")


@dataclasses.dataclass
class Program:
    """One AOT-lowered program unit of an entry point (a TrainStep has
    one; a serving bucket grid has one per padded signature)."""
    name: str
    lowered: object          # jax ``Lowered``
    n_args: int              # flattened input leaf count (donation denom.)
    meta: Optional[dict] = None


def _entry_lines(hlo_text: str):
    """Lines of the ENTRY computation only — fusion subcomputations
    repeat every fused elementwise op and would drown the categories
    that matter (PERF.md counts the entry computation)."""
    inside = False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if inside:
            if line.startswith("}"):
                return
            yield line


def instruction_counts(hlo_text: str) -> Dict[str, int]:
    counts = {c: 0 for c in CATEGORIES}
    total = 0
    for line in _entry_lines(hlo_text):
        m = _INSTR_RE.match(line)
        if not m:
            continue
        total += 1
        counts[_CATEGORY.get(m.group(2), "other")] += 1
    counts["total"] = total
    return counts


def collective_payload_bytes(hlo_text: str) -> int:
    """Summed result-shape bytes of the ENTRY computation's collective
    instructions — the gradient/weight *wire* traffic of the program,
    the number ISSUE 8's quantized collectives exist to shrink.  Async
    pairs count once (the ``*-start`` half is skipped; its ``-done``
    carries the payload), and a tuple-shaped result (the CPU backend's
    all-to-all form) sums its per-peer buffers."""
    total = 0
    for line in _entry_lines(hlo_text):
        m = _INSTR_RE.match(line)
        if not m:
            continue
        op = m.group(2)
        if _CATEGORY.get(op) != "collective" or op.endswith("-start"):
            continue
        total += _shape_bytes(m.group(1))
    return total


def donation_counts(hlo_text: str, n_args: int) -> Dict[str, int]:
    """Donated-parameter coverage from the ``input_output_alias`` header
    attribute: which inputs XLA actually reuses as outputs.  This is the
    *post-compile truth* — a donate_argnums entry the compiler could not
    use does not count."""
    donated = set()
    for line in hlo_text.splitlines():
        if line.startswith("HloModule"):
            donated.update(int(p) for p in _ALIAS_RE.findall(line))
            break
    return {"donated_args": len(donated), "total_args": int(n_args)}


def program_num_partitions(hlo_text: str) -> int:
    """How many devices one copy of this program spans — the SPMD
    partitioner stamps ``num_partitions=N`` on the HloModule header.
    1 when absent: a single-device (or trivially replicated) program."""
    for line in hlo_text.splitlines():
        if line.startswith("HloModule"):
            m = _NUM_PARTITIONS_RE.search(line)
            return int(m.group(1)) if m else 1
    return 1


def unit_report(lowered, n_args: int) -> dict:
    """Normalized report of ONE program unit: compiles ``lowered``.

    Post-SPMD HLO is the PER-DEVICE program: shapes are shard shapes,
    ``memory_analysis`` accounts one device's buffers.  The
    ``per_device`` section makes that semantic explicit (and budgetable
    — a sharded entry commits that these numbers scale as 1/shards),
    alongside the device count the partitioner stamped.  The three
    ``cost_analysis`` rows are the lowered module's, so they count the
    whole program before it is partitioned."""
    costs = lowered.cost_analysis() or {}
    compiled = lowered.compile()
    text = compiled.as_text()
    try:
        ma = compiled.memory_analysis()
        mem = {"argument_bytes": int(ma.argument_size_in_bytes),
               "peak_bytes": int(ma.argument_size_in_bytes
                                 + ma.output_size_in_bytes
                                 + ma.temp_size_in_bytes
                                 - ma.alias_size_in_bytes)}
    except Exception:   # noqa: BLE001 — some backends can't account memory
        mem = {}        # absent, not fabricated: the diff skips it
    wire = float(collective_payload_bytes(text))
    return {
        "n_executables": 1,
        "flops": float(costs.get("flops", 0.0)),
        "bytes_accessed": float(costs.get("bytes accessed", 0.0)),
        "transcendentals": float(costs.get("transcendentals", 0.0)),
        "collective_bytes": wire,
        "memory": mem,
        "per_device": dict(mem, n_devices=program_num_partitions(text),
                           collective_bytes=wire),
        "donation": donation_counts(text, n_args),
        "instructions": instruction_counts(text),
    }


def merge_reports(units: List[dict]) -> dict:
    """One entry-point report from its per-executable unit reports.

    Additive metrics (flops, bytes, instruction counts, donation
    counts, executable count) sum — the grid's total traffic budget.
    Memory is the **max** over units: executables run one at a time, so
    the budgetable figure is the worst single program, not a fictitious
    sum."""
    if not units:
        raise ValueError("merge_reports: no unit reports")
    out = {
        "n_executables": sum(u["n_executables"] for u in units),
        "flops": sum(u["flops"] for u in units),
        "bytes_accessed": sum(u["bytes_accessed"] for u in units),
        "transcendentals": sum(u["transcendentals"] for u in units),
        "collective_bytes": sum(u.get("collective_bytes", 0.0)
                                for u in units),
        "memory": {},
        "donation": {
            "donated_args": sum(u["donation"]["donated_args"]
                                for u in units),
            "total_args": sum(u["donation"]["total_args"] for u in units),
        },
        "instructions": {
            k: sum(u["instructions"].get(k, 0) for u in units)
            for k in CATEGORIES + ("total",)
        },
    }
    mems = [u["memory"] for u in units if u["memory"]]
    if mems:
        out["memory"] = {k: max(m.get(k, 0) for m in mems)
                         for k in mems[0]}
    # per-device numbers merge like memory: executables run one at a
    # time, so the budgetable per-device figure is the worst single
    # program on one device, not a sum across the grid
    pds = [u.get("per_device") for u in units]
    pds = [p for p in pds if p]
    if pds:
        # key UNION, not pds[0]'s keys: one unit whose memory_analysis
        # failed (its per_device carries only n_devices+collective)
        # must not silently un-gate the byte metrics the others report
        keys = set().union(*(p.keys() for p in pds))
        out["per_device"] = {k: max(p.get(k, 0) for p in pds)
                             for k in sorted(keys)}
    return out


def report_for_programs(programs: List[Program], root=None,
                        use_cache: bool = False, cache_dir=None) -> dict:
    """Compile each program unit (or hit the report cache) and merge.

    The cache key is a hash of the **lowered HLO text** — any change to
    the model, the step plumbing, or jax itself changes the text, so a
    cached report can never go stale against the code (the same
    soundness argument as mxlint's content-hash cache, one level up the
    stack: lowering is cheap and always runs; only the expensive
    XLA compile + extraction is memoized).  ``.costguard_cache/`` under
    ``root``; writes are atomic and best-effort."""
    import jax

    cache = None
    if use_cache and root is not None:
        from pathlib import Path

        from tools.analysis.cache import FileCache
        sig = (f"costguard-{REPORT_VERSION}-jax{jax.__version__}-"
               f"{jax.default_backend()}-{jax.device_count()}d")
        cache = FileCache(Path(root),
                          cache_dir or Path(root) / ".costguard_cache",
                          signature=sig)
    units = []
    for prog in programs:
        text = prog.lowered.as_text()
        key = rec = None
        if cache is not None:
            key = cache.key(prog.name, text.encode("utf-8"))
            rec = cache.get(prog.name, key)
        if rec is not None:
            units.append(rec["report"])
            continue
        u = unit_report(prog.lowered, prog.n_args)
        units.append(u)
        if cache is not None:
            cache.put(prog.name, key, {"relpath": prog.name, "report": u})
    return merge_reports(units)
