"""Budget goldens: committed per-entry-point cost reports with
per-metric relative tolerances, and the diff/check machinery the tier-1
gate and the CLI share.

A golden (``tests/goldens/budgets/<entry>.json``) commits the budgeted
rows of the normalized report (``DEFAULT_TOLERANCES`` names them) plus
the environment it was recorded in.  The check re-lowers + re-compiles
the entry point and compares metric by metric:

- within tolerance → ok;
- above budget beyond tolerance → **REGRESSION**, the gate fails;
- below budget beyond tolerance → also fails, as a *stale budget*: an
  improvement must be ratcheted into the golden
  (``python tests/goldens/budgets/regen_budgets.py``) so the next
  regression is measured from the new floor, not the old slack.

Goldens gate only in a matching environment (backend + device count):
CPU byte counts are not TPU byte counts (PERF.md), so a TPU run of the
same entries reports without gating.  The jax version is not part of
that key: the budgeted rows are meant to survive a bump, and a failing
entry whose golden was cut under another jax says so.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, List, Optional

from . import entrypoints
from .report import REPORT_VERSION, report_for_programs

GOLDEN_SUBDIR = Path("tests") / "goldens" / "budgets"

#: THE list of budgeted metrics: dotted metric → relative tolerance.
#: ``regen_budgets.py`` writes these rows of a report and no others.
#:
#: A row is here when OUR code decides it: executables a configuration
#: can compile, donated arguments, bytes of the arguments and of the
#: collectives' payloads (shapes), devices a program spans, convolutions,
#: dots and custom calls of its entry computation, and the cost of the
#: module as lowered.  What XLA:CPU's optimiser decides at toy shapes is
#: reported and not budgeted: the fusion, copy, collective and total
#: instruction counts (it combines and splits all-reduces: 321 → 99 in
#: resnet50 with ``collective_bytes`` equal to the byte; hloguard pins
#: kinds and counts on the lowered text), its buffer assignment's peak
#: bytes, the compiled module's flops and bytes.  Over jax 0.4.37 →
#: 0.9.0 those failed 13 of 16 entries with no change of ours, and hid
#: the one real stale golden (docs/analysis.md "Cost budgets").
#:
#: The ``per_device`` byte rows MIRROR ``memory.argument_bytes`` and
#: ``collective_bytes`` (worst single executable against the grid's
#: sum): per_device is the committed unit of the sharded pairs.
DEFAULT_TOLERANCES: Dict[str, float] = {
    "n_executables": 0.0,
    "donation.donated_args": 0.0,
    "donation.total_args": 0.0,
    "flops": 0.01,
    "bytes_accessed": 0.02,
    "transcendentals": 0.05,
    "collective_bytes": 0.02,
    "memory.argument_bytes": 0.02,
    "instructions.convolution": 0.0,
    "instructions.dot": 0.15,
    "instructions.custom-call": 0.25,
    "per_device.n_devices": 0.0,
    "per_device.argument_bytes": 0.02,
    "per_device.collective_bytes": 0.02,
}


@dataclasses.dataclass
class MetricRow:
    metric: str
    budget: float
    actual: float
    rel: float              # (actual - budget) / budget
    tol: float
    ok: bool

    def render(self) -> str:
        mark = "ok  " if self.ok else "FAIL"
        if self.actual != self.actual:       # NaN: budgeted, not reported
            return (f"  [{mark}] {self.metric:28s} "
                    f"budget={self.budget:>14.6g} actual=<missing>  "
                    f"<- the fresh report has no such metric "
                    f"(extraction failed?) — a budgeted metric may not "
                    f"silently stop being gated")
        verdict = ""
        if not self.ok:
            verdict = ("  <- REGRESSION over budget" if self.rel > 0 else
                       "  <- beats budget: ratchet the golden "
                       "(regen_budgets.py)")
        return (f"  [{mark}] {self.metric:28s} budget={self.budget:>14.6g} "
                f"actual={self.actual:>14.6g} ({self.rel:+.2%} vs "
                f"±{self.tol:.1%}){verdict}")


@dataclasses.dataclass
class EntryResult:
    name: str
    report: Optional[dict] = None
    golden: Optional[dict] = None
    rows: List[MetricRow] = dataclasses.field(default_factory=list)
    census: Optional[int] = None
    problems: List[str] = dataclasses.field(default_factory=list)
    gated: bool = True      # False = environment mismatch, report-only

    @property
    def ok(self) -> bool:
        return not self.problems and all(r.ok for r in self.rows)

    def to_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok, "gated": self.gated,
                "census": self.census, "problems": list(self.problems),
                "rows": [{k: _json_num(v) for k, v in
                          dataclasses.asdict(r).items()}
                         for r in self.rows],
                "report": self.report}


def _json_num(v):
    """Strict-JSON-safe value: failure rows carry NaN (budgeted metric
    missing) and ±inf (zero-budget regression), which RFC-8259 parsers
    reject — exactly when the report matters most.  None / "inf" are
    the wire forms."""
    if isinstance(v, float):
        if v != v:
            return None
        if v == float("inf") or v == float("-inf"):
            return "inf" if v > 0 else "-inf"
    return v


def golden_path(name: str, root) -> Path:
    return Path(root) / GOLDEN_SUBDIR / f"{name}.json"


def device_count_guard(golden: dict, n_devices: int,
                       name: str) -> Optional[str]:
    """Why a SHARDED golden must not be regenerated right now, or None.

    A sharded entry's contract IS its per-device scaling — regenerating
    it from an environment whose visible device count differs from the
    committed golden's (a shell without the
    ``--xla_force_host_platform_device_count`` bring-up, a 1-chip TPU
    VM) would silently commit a 1-device "sharded" budget that gates
    nothing.  ``regen_budgets.py`` refuses; delete the golden first if
    the device-count change is intentional."""
    if not (golden.get("meta") or {}).get("sharded"):
        return None
    old = golden.get("n_devices")
    if old is not None and int(old) != int(n_devices):
        return (f"{name}: refusing to regenerate a SHARDED golden "
                f"recorded with {old} visible device(s) from an "
                f"environment with {n_devices} — its per-device byte "
                f"contract depends on the shard count.  Re-run under "
                f"the recorded bring-up (XLA_FLAGS=--xla_force_host_"
                f"platform_device_count={old}, the tests/conftest.py "
                f"environment), or delete the golden first if the "
                f"device-count change is intentional")
    return None


def load_golden(name: str, root) -> Optional[dict]:
    p = golden_path(name, root)
    if not p.exists():
        return None
    return json.loads(p.read_text(encoding="utf-8"))


def _lookup(report: dict, dotted: str):
    cur = report
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def budgeted(report: dict) -> dict:
    """The rows of ``report`` that ``DEFAULT_TOLERANCES`` names, nested
    as the report nests them: what a golden commits."""
    out: dict = {}
    for metric in sorted(DEFAULT_TOLERANCES):
        value = _lookup(report, metric)
        if value is None:
            continue
        *sections, leaf = metric.split(".")
        cur = out
        for part in sections:
            cur = cur.setdefault(part, {})
        cur[leaf] = value
    return out


def diff_report(report: dict, golden: dict) -> List[MetricRow]:
    """Per-metric comparison of a fresh report against a golden's.
    Tolerances: golden ``tolerances`` override ``DEFAULT_TOLERANCES``
    per metric.  A metric absent from the GOLDEN is skipped (the golden
    is the committed, visible contract — it never budgeted that
    number); a budgeted metric absent from the FRESH report FAILS: an
    extraction path going dark (e.g. ``memory_analysis`` breaking on a
    backend change) must not quietly stop gating what the golden
    commits."""
    tols = dict(DEFAULT_TOLERANCES)
    tols.update(golden.get("tolerances") or {})
    budget_rep = golden["report"]
    rows = []
    for metric, tol in sorted(tols.items()):
        b, a = _lookup(budget_rep, metric), _lookup(report, metric)
        if b is None:
            continue
        if a is None:
            rows.append(MetricRow(metric=metric, budget=float(b),
                                  actual=float("nan"), rel=float("inf"),
                                  tol=tol, ok=False))
            continue
        b, a = float(b), float(a)
        if b == 0.0:
            rel = 0.0 if a == 0.0 else float("inf")
        else:
            rel = (a - b) / b
        rows.append(MetricRow(metric=metric, budget=b, actual=a, rel=rel,
                              tol=tol, ok=abs(rel) <= tol))
    return rows


def environment() -> dict:
    import jax
    return {"backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "jax_version": jax.__version__,
            "report_version": REPORT_VERSION}


def check_entry(name: str, root, use_cache: bool = False,
                cache_dir=None) -> EntryResult:
    """Build + lower + compile one entry point and judge it against its
    golden.  Never executes a step."""
    res = EntryResult(name=name)
    built = entrypoints.build(name)
    res.census = built.census
    res.report = report_for_programs(built.programs, root=root,
                                     use_cache=use_cache,
                                     cache_dir=cache_dir)
    if res.report["n_executables"] != built.census:
        res.problems.append(
            f"executable census mismatch: the signature space enumerates "
            f"{built.census} executables but the build lowered "
            f"{res.report['n_executables']} — a program exists outside "
            f"the declared signature grid (recompile hazard)")
    golden = load_golden(name, root)
    if golden is None:
        res.problems.append(
            f"no committed budget golden at {golden_path(name, root)} — "
            f"a registered entry point must carry a budget "
            f"(tests/goldens/budgets/regen_budgets.py writes one)")
        return res
    res.golden = golden
    env = environment()
    if golden.get("report_version") != REPORT_VERSION:
        res.problems.append(
            f"golden schema {golden.get('report_version')!r} != analyzer "
            f"schema {REPORT_VERSION!r} — regenerate the goldens")
        return res
    if (golden.get("backend"), golden.get("n_devices")) != \
            (env["backend"], env["n_devices"]):
        res.gated = False     # audit-only: numbers are not comparable
        return res
    if golden["report"].get("n_executables") != built.census:
        res.problems.append(
            f"budgeted executable count "
            f"{golden['report'].get('n_executables')} != static census "
            f"{built.census} — the golden no longer matches the "
            f"signature grid")
    res.rows = diff_report(res.report, golden)
    if not res.ok and golden.get("jax_version") != env["jax_version"]:
        res.problems.append(
            f"golden cut under jax {golden.get('jax_version')}, running "
            f"{env['jax_version']}: if the rows above moved with the bump "
            f"and not with the code, regenerate (regen_budgets.py)")
    return res


@dataclasses.dataclass
class CheckResult:
    entries: List[EntryResult]
    stale_goldens: List[str]

    @property
    def ok(self) -> bool:
        return not self.stale_goldens and all(e.ok for e in self.entries)

    def to_json(self) -> str:
        return json.dumps(
            {"ok": self.ok, "stale_goldens": list(self.stale_goldens),
             "entries": [e.to_dict() for e in self.entries]},
            indent=2, sort_keys=True, allow_nan=False)

    def render(self) -> str:
        out = []
        for e in self.entries:
            status = "ok" if e.ok else "FAIL"
            scope = "" if e.gated else \
                " (environment != golden's: report-only, not gated)"
            out.append(f"[{status}] {e.name}: "
                       f"{e.report['n_executables']} executable(s), "
                       f"census {e.census}{scope}")
            for p in e.problems:
                out.append(f"  [FAIL] {p}")
            for r in e.rows:
                out.append(r.render())
        for name in self.stale_goldens:
            out.append(f"[FAIL] stale golden: tests/goldens/budgets/"
                       f"{name}.json has no registered entry point — "
                       f"delete it or restore the registration")
        out.append(f"costguard: "
                   f"{sum(1 for e in self.entries if e.ok)}/"
                   f"{len(self.entries)} entry points within budget"
                   + ("" if self.ok else " — CHECK FAILED"))
        return "\n".join(out)


def run_check(entries=None, root=None, use_cache: bool = False,
              cache_dir=None) -> CheckResult:
    """The whole audit: every selected entry point against its golden,
    plus the reverse direction — goldens whose registration is gone.
    ``entries=None`` selects everything; an explicit empty list audits
    no entry but still runs the (selection-independent) reverse
    check."""
    root = Path(root) if root is not None else Path.cwd()
    selected = entrypoints.names() if entries is None else list(entries)
    results = [check_entry(n, root, use_cache=use_cache,
                           cache_dir=cache_dir) for n in selected]
    # the reverse check is selection-independent: a golden whose
    # registration is GONE is stale no matter which subset this run
    # audits — every invocation (incl. the documented
    # `python -m tools.costguard mxnet_tpu/` path form) must see it
    stale = []
    gdir = root / GOLDEN_SUBDIR
    if gdir.is_dir():
        registered = set(entrypoints.names())
        stale = sorted(p.stem for p in gdir.glob("*.json")
                       if p.stem not in registered)
    return CheckResult(entries=results, stale_goldens=stale)
