"""hloguard engine: surface → facts (cached) → findings vs goldens.

The run contract mirrors costguard's ``budget.run_check``:

* every selected surface is lowered fresh (lowering is cheap and the
  text hash is the soundness anchor), the expensive parse/extract step
  is memoized in ``.hloguard_cache/`` keyed on the lowered text, and
  the rules run over facts every time;
* a surface gates only when its golden's recorded backend/device-count
  environment matches (CPU-vs-TPU lowering differs structurally — a
  golden from one bring-up must not fail the other); the jax version is
  not part of that key, and a failing surface whose golden was cut under
  another jax says so;
* both directions fail: an unsuppressed finding AND a stale golden /
  stale suppression — the audited surface stays audited.

Suppressions live in the golden (``suppressions: [{rule, match,
justification}]``), matched by rule id + message substring, and the
justification is REQUIRED: an empty one raises ``bad-suppression``,
which cannot itself be suppressed (the mxlint contract).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import List, Optional

from tools.analysis.core import Finding

from . import surfaces
from .rules import (REPORT_VERSION, RULES, census_findings, entry_census,
                    extract_facts, pattern_findings)

GOLDEN_SUBDIR = "tests/goldens/hloguard"
CACHE_DIR_NAME = ".hloguard_cache"


def golden_path(name: str, root) -> Path:
    return Path(root) / GOLDEN_SUBDIR / f"{name}.json"


def load_golden(name: str, root) -> Optional[dict]:
    p = golden_path(name, root)
    if not p.exists():
        return None
    return json.loads(p.read_text(encoding="utf-8"))


def environment() -> dict:
    import jax
    return {"backend": jax.default_backend(),
            "n_devices": jax.device_count(),
            "jax_version": jax.__version__,
            "report_version": REPORT_VERSION}


def _cache(root, cache_dir):
    import jax

    from tools.analysis.cache import FileCache
    sig = (f"hloguard-{REPORT_VERSION}-jax{jax.__version__}-"
           f"{jax.default_backend()}-{jax.device_count()}d")
    return FileCache(Path(root), cache_dir or Path(root) / CACHE_DIR_NAME,
                     signature=sig)


def facts_for_programs(programs, root=None, use_cache: bool = False,
                       cache_dir=None) -> dict:
    """{program name: facts} with the HLO-hash cache in front of the
    parse/extract step — the costguard ``report_for_programs`` pattern
    one compile earlier (nothing here ever invokes XLA)."""
    cache = _cache(root, cache_dir) if use_cache and root is not None \
        else None
    out = {}
    for prog_name, text in programs:
        key = rec = None
        if cache is not None:
            key = cache.key(prog_name, text.encode("utf-8"))
            rec = cache.get(prog_name, key)
        if rec is not None:
            out[prog_name] = rec["facts"]
            continue
        f = extract_facts(text)
        out[prog_name] = f
        if cache is not None:
            cache.put(prog_name, key, {"relpath": prog_name, "facts": f})
    return out


@dataclasses.dataclass
class EntryResult:
    name: str
    census: Optional[dict] = None
    findings: List[Finding] = dataclasses.field(default_factory=list)
    golden: Optional[dict] = None
    gated: bool = True        # False = golden from another environment

    @property
    def ok(self) -> bool:
        return not any(f.severity == "error" and not f.suppressed
                       for f in self.findings)

    def to_dict(self) -> dict:
        return {"name": self.name, "ok": self.ok, "gated": self.gated,
                "census": self.census,
                "findings": [f.to_dict() for f in self.findings]}


@dataclasses.dataclass
class CheckResult:
    entries: List[EntryResult]
    extra_findings: List[Finding] = dataclasses.field(default_factory=list)

    @property
    def findings(self) -> List[Finding]:
        out = [f for e in self.entries for f in e.findings]
        out.extend(self.extra_findings)
        return out

    @property
    def ok(self) -> bool:
        return (all(e.ok for e in self.entries)
                and not any(f.severity == "error" and not f.suppressed
                            for f in self.extra_findings))

    def to_json(self) -> str:
        return json.dumps(
            {"ok": self.ok, "report_version": REPORT_VERSION,
             "entries": [e.to_dict() for e in self.entries],
             "extra_findings": [f.to_dict()
                                for f in self.extra_findings]},
            indent=2, sort_keys=True)

    def to_sarif(self) -> str:
        from tools.analysis.sarif import to_sarif
        return to_sarif(sorted(self.findings,
                               key=lambda f: (f.path, f.line, f.rule,
                                              f.message)),
                        rules=sarif_rules(), tool_version=REPORT_VERSION,
                        tool_name="hloguard")

    def render(self) -> str:
        lines = []
        for e in self.entries:
            n_sup = sum(1 for f in e.findings if f.suppressed)
            tag = "ok" if e.ok else "FAIL"
            if not e.gated:
                tag += " (not gated: golden from another environment)"
            extra = f", {n_sup} suppressed" if n_sup else ""
            lines.append(f"{e.name:28s} {tag}{extra}")
            for f in e.findings:
                if not f.suppressed:
                    lines.append(f"  {f.render()}")
        for f in self.extra_findings:
            lines.append(f.render())
        n_bad = sum(1 for f in self.findings
                    if f.severity == "error" and not f.suppressed)
        lines.append(f"hloguard: {len(self.entries)} surface(s), "
                     f"{n_bad} unsuppressed finding(s): "
                     f"{'OK' if self.ok else 'FAIL'}")
        return "\n".join(lines)


class _SarifRule:
    def __init__(self, rid, desc):
        self.id = rid
        self.description = desc
        self.default_severity = ("warning"
                                 if rid == "stale-suppression" else "error")


def sarif_rules():
    return [_SarifRule(rid, desc) for rid, desc in sorted(RULES.items())]


def _finding(rule, severity, message, path, line=1) -> Finding:
    return Finding(rule=rule, path=path, line=line, col=1,
                   message=message, severity=severity)


def _relpath(name: str, root) -> str:
    src = surfaces.source_of(name)
    try:
        return src.resolve().relative_to(Path(root).resolve()).as_posix()
    except ValueError:
        return src.as_posix()


def _apply_suppressions(found: List[Finding], golden: Optional[dict],
                        entry: str, path: str) -> List[Finding]:
    """Golden suppressions over findings, with the mxlint contract:
    justification required, unused suppressions flagged, and the
    suppression checker itself unsuppressible."""
    sups = (golden or {}).get("suppressions") or []
    used = [False] * len(sups)
    out = []
    for f in found:
        for i, s in enumerate(sups):
            if s.get("rule") != f.rule:
                continue
            if s.get("match", "") not in f.message:
                continue
            if not (s.get("justification") or "").strip():
                # matched but unjustified: the finding stays live AND
                # the suppression itself is a finding
                continue
            f.suppressed = True
            f.justification = s["justification"]
            used[i] = True
            break
        out.append(f)
    for i, s in enumerate(sups):
        if not (s.get("justification") or "").strip():
            out.append(_finding(
                "bad-suppression", "error",
                f"{entry}: suppression for rule {s.get('rule')!r} "
                f"(match {s.get('match', '')!r}) has no justification — "
                f"write down WHY or delete it", path))
        elif not used[i]:
            out.append(_finding(
                "stale-suppression", "warning",
                f"{entry}: suppression for rule {s.get('rule')!r} "
                f"(match {s.get('match', '')!r}) matched no finding — "
                f"delete it or fix its match string", path))
    return out


def check_entry(name: str, root, use_cache: bool = False,
                cache_dir=None) -> EntryResult:
    """Lower one surface and judge its structure against the golden.
    Never compiles, never executes a step."""
    res = EntryResult(name=name)
    path = _relpath(name, root)
    surface = surfaces.build(name)
    facts = facts_for_programs(surface.programs, root=root,
                               use_cache=use_cache, cache_dir=cache_dir)
    res.census = entry_census(facts)
    found = [_finding(rule, sev, msg, path)
             for rule, sev, msg in
             pattern_findings(name, surface.meta, facts)]
    golden = load_golden(name, root)
    if golden is None:
        found.append(_finding(
            "missing-golden", "error",
            f"{name}: no structural golden at {golden_path(name, root)} "
            f"— tests/goldens/hloguard/regen_hloguard.py writes one",
            path))
        res.findings = found
        return res
    res.golden = golden
    env = environment()
    if golden.get("report_version") != REPORT_VERSION:
        found.append(_finding(
            "hlo-structure", "error",
            f"{name}: golden schema {golden.get('report_version')!r} != "
            f"analyzer schema {REPORT_VERSION!r} — regenerate", path))
        res.findings = found
        return res
    if (golden.get("backend"), golden.get("n_devices")) != \
            (env["backend"], env["n_devices"]):
        res.gated = False     # audit-only: lowerings are not comparable
        res.findings = _apply_suppressions(found, golden, name, path)
        return res
    found.extend(_finding(rule, sev, msg, path)
                 for rule, sev, msg in
                 census_findings(name, golden.get("census") or {},
                                 res.census))
    res.findings = _apply_suppressions(found, golden, name, path)
    if not res.ok and golden.get("jax_version") != env["jax_version"]:
        res.findings.append(_finding(
            "hlo-structure", "warning",
            f"{name}: golden cut under jax {golden.get('jax_version')}, "
            f"running {env['jax_version']}: if the census moved with the "
            f"bump and not with the code, regenerate", path))
    return res


def run_check(entries=None, root=None, use_cache: bool = False,
              cache_dir=None) -> CheckResult:
    """The whole structural audit: every selected surface against its
    golden, plus the selection-independent reverse check (goldens whose
    surface is gone)."""
    root = Path(root) if root is not None else Path.cwd()
    selected = surfaces.names() if entries is None else list(entries)
    results = [check_entry(n, root, use_cache=use_cache,
                           cache_dir=cache_dir) for n in selected]
    extra = []
    gdir = root / GOLDEN_SUBDIR
    if gdir.is_dir():
        registered = set(surfaces.names())
        for p in sorted(gdir.glob("*.json")):
            if p.stem not in registered:
                extra.append(_finding(
                    "stale-golden", "error",
                    f"{p.stem}: structural golden committed but no such "
                    f"surface is registered — delete "
                    f"{GOLDEN_SUBDIR}/{p.name} or restore the surface",
                    f"{GOLDEN_SUBDIR}/{p.name}"))
    return CheckResult(entries=results, extra_findings=extra)
