"""mxlint rule engine.

The analysis counterpart of the runtime's fault harness: where
``mx.fault`` makes concurrency/preemption failures *repeatable*, mxlint
makes the invariants that PREVENT them *mechanical*.  TensorFlow's
production experience (PAPERS.md: Abadi et al.) is that large dataflow
frameworks survive on invariant checking in CI, not review; the
whole-program-compile stacks (Julia→TPU, PAPERS.md) show that
trace/compile-boundary discipline is the correctness frontier.  This
engine walks Python sources with ``ast`` (no imports, no execution — it
must be runnable on a broken tree) and applies per-file and
whole-project rules.

Since the CFG/dataflow upgrade the engine has three layers:

- **per-file rules** (``Rule.check_module``) — including the CFG-hosted
  concurrency/lifecycle suite.  Their findings depend ONLY on the one
  file's content, which is what makes the incremental cache sound.
- **project rules** (``ProjectRule``) — cross-file invariants.  Each
  extracts a small serializable *facts* record per file
  (``ProjectRule.facts``) and judges the union
  (``ProjectRule.check_facts``): the op-registry table, the docs symbol
  index, the global lock-acquisition graph.  Facts ride in the same
  cache records as findings, so a fully-cached run never parses a file.
- **the cache** (``.mxlint_cache/``) — per-file JSON records keyed by a
  hash of (engine version, rule set, relative path, file bytes).  See
  ``cache.py``.  ``analyze(use_cache=True)`` opts in; the tier-1 gate
  does, which is how the full-tree gate stays inside its wall-time
  budget as the rule suite grows.

Suppression contract (docs/analysis.md):

    x = float(traced)  # mxlint: disable=trace-host-sync -- verdict scalar,
                       # one round-trip per step by design

``disable=`` names one or more comma-separated rule ids; the text after
``--`` is a REQUIRED justification.  A disable comment without a
justification does not suppress anything and itself raises
``bad-suppression`` — an unexplained suppression is how invariants rot.
The comment suppresses findings on its own line, or (as a standalone
comment line) on the next code line.
"""
from __future__ import annotations

import ast
import dataclasses
import json
import re
import subprocess
import sys
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

SEVERITIES = ("error", "warning", "info")

#: bump when ANY rule's logic changes: it keys the incremental cache,
#: and a stale record must never survive an analyzer upgrade
ENGINE_VERSION = "3.2"

# id of the meta-rule emitted for malformed disable comments; it cannot
# itself be suppressed (suppressing the suppression-checker is turtles).
BAD_SUPPRESSION = "bad-suppression"

# project-scope roots: cross-file facts (docs symbol index, registry
# table, lock graph) are always gathered over these subtrees of the
# root when they exist, regardless of which subset a run analyzes —
# linting one file must not make every doc row look stale
PROJECT_SCOPE = ("mxnet_tpu", "tools")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str
    line: int
    col: int
    message: str
    severity: str = "error"
    suppressed: bool = False
    justification: Optional[str] = None

    def to_dict(self):
        return dataclasses.asdict(self)

    def render(self):
        tag = " (suppressed)" if self.suppressed else ""
        return (f"{self.path}:{self.line}:{self.col}: "
                f"[{self.severity}] {self.rule}: {self.message}{tag}")


@dataclasses.dataclass
class ModuleInfo:
    """One parsed source file, shared by every rule."""
    path: Path          # absolute
    relpath: str        # repo-root-relative (stable in output/tests)
    source: str
    tree: ast.Module
    lines: List[str]


class Rule:
    """Per-file rule: ``check_module`` yields findings for one file."""

    id: str = ""
    default_severity: str = "error"
    description: str = ""

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        return ()

    def finding(self, mod, node, message, rule_id=None):
        return Finding(rule=rule_id or self.id, path=mod.relpath,
                       line=getattr(node, "lineno", 1),
                       col=getattr(node, "col_offset", 0) + 1,
                       message=message)


class ProjectRule(Rule):
    """Whole-project rule: extracts a JSON-serializable facts record per
    file (cached alongside findings) and judges the union.

    ``check_facts(facts, root, analyzed)`` receives ``facts`` as a list
    of ``(relpath, record)`` pairs covering the analyzed set plus the
    project scope, and ``analyzed`` as the set of relpaths this run was
    actually asked about — findings anchored in source files should be
    restricted to it (docs findings are the exception: they anchor in
    the doc, which is never "analyzed")."""

    def facts(self, mod: ModuleInfo):
        return None

    def check_facts(self, facts: List[Tuple[str, object]], root: Path,
                    analyzed: set) -> Iterable[Finding]:
        return ()


# --------------------------------------------------------------------------
# suppression comments
# --------------------------------------------------------------------------

_DISABLE_RE = re.compile(
    r"#\s*mxlint:\s*disable=([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
    r"(?:\s*--\s*(.*\S))?\s*$")


def parse_suppressions(mod: ModuleInfo):
    """line -> (set of rule ids, justification | None) and the
    bad-suppression findings for comments missing a justification.

    A suppression comment applies to its own line; when the line holds
    ONLY the comment, it applies to the next line instead (the long-line
    form).  Consecutive standalone comment lines chain, so a wrapped
    justification still points at the first code line after the block.
    """
    table: Dict[int, Tuple[set, Optional[str]]] = {}
    bad: List[Finding] = []
    pending: Optional[Tuple[set, Optional[str]]] = None
    for i, text in enumerate(mod.lines, start=1):
        m = _DISABLE_RE.search(text)
        stripped = text.strip()
        if m:
            rules = {r.strip() for r in m.group(1).split(",")}
            just = m.group(2)
            if not just:
                bad.append(Finding(
                    rule=BAD_SUPPRESSION, path=mod.relpath, line=i, col=1,
                    message=f"mxlint disable={','.join(sorted(rules))} has "
                            f"no justification: write "
                            f"'# mxlint: disable=RULE -- why it is safe'"))
                pending = None
                continue
            if stripped.startswith("#"):
                pending = (rules, just)      # standalone: arm for next code line
            else:
                table[i] = (rules, just)     # inline
                pending = None
        elif pending is not None:
            if stripped.startswith("#") or not stripped:
                continue                     # comment block / blank: keep arming
            table[i] = pending
            pending = None
    return table, bad


# --------------------------------------------------------------------------
# config + engine
# --------------------------------------------------------------------------

class Config:
    """Per-rule enable/severity knobs (CLI: --disable / --severity)."""

    def __init__(self, disabled=(), severities=None):
        self.disabled = set(disabled)
        self.severities = dict(severities or {})
        for rid, sev in self.severities.items():
            if sev not in SEVERITIES:
                raise ValueError(f"unknown severity {sev!r} for rule {rid!r} "
                                 f"(one of {SEVERITIES})")

    def enabled(self, rule_id):
        return rule_id not in self.disabled

    def severity(self, rule: Rule):
        return self.severities.get(rule.id, rule.default_severity)

    def severity_of(self, rule_id, default="error"):
        return self.severities.get(rule_id, default)


def default_rules() -> List[Rule]:
    from .trace_rules import (HostSyncRule, TracedBranchRule,
                              MutableGlobalRule, UnhashableStaticRule)
    from .thread_rules import UnlockedAttrRule
    from .donation_rules import DonatedReuseRule
    from .compile_rules import JitInLoopRule, UnbudgetedEntrypointRule
    from .concurrency_rules import (BlockingUnderLockRule, LockOrderRule,
                                    SignalHandlerRule)
    from .lifecycle_rules import ResourceLeakRule
    from .registry_rules import (DuplicateRegistrationRule,
                                 MissingGradientRule, StaleDocSymbolRule)
    from .spmd_rules import (SpmdAxisUnknownRule, SpmdSpecArityRule,
                             SpmdReplicationClaimRule,
                             SpmdCollectiveInLoopRule)

    return [HostSyncRule(), TracedBranchRule(), MutableGlobalRule(),
            UnhashableStaticRule(), UnlockedAttrRule(), DonatedReuseRule(),
            BlockingUnderLockRule(), LockOrderRule(), SignalHandlerRule(),
            ResourceLeakRule(), JitInLoopRule(),
            SpmdAxisUnknownRule(), SpmdSpecArityRule(),
            SpmdReplicationClaimRule(), SpmdCollectiveInLoopRule(),
            DuplicateRegistrationRule(), MissingGradientRule(),
            StaleDocSymbolRule(), UnbudgetedEntrypointRule()]


def _collect_files(paths) -> List[Path]:
    out = []
    for p in paths:
        p = Path(p)
        if p.is_dir():
            out.extend(sorted(f for f in p.rglob("*.py")
                              if ".mxlint_cache" not in f.parts))
        elif p.suffix == ".py":
            out.append(p)
    return out


def _relpath(path: Path, root: Path) -> str:
    try:
        return str(path.resolve().relative_to(root.resolve()))
    except ValueError:
        return str(path)


def load_module(path: Path, root: Path,
                source: Optional[str] = None) -> Optional[ModuleInfo]:
    if source is None:
        source = path.read_text(encoding="utf-8", errors="replace")
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError:
        return None  # a syntax error is the interpreter's finding, not ours
    return ModuleInfo(path=path, relpath=_relpath(path, root),
                      source=source, tree=tree, lines=source.splitlines())


def _git_changed(root: Path) -> Optional[set]:
    """RESOLVED absolute paths differing from HEAD (tracked changes +
    untracked files), or None when git is unavailable — the caller then
    falls back to analyzing everything (fail open, never silently
    narrow).  git reports paths relative to the repository TOPLEVEL,
    which need not be ``root`` (linting a subpackage), so names are
    anchored there before comparison."""
    try:
        top = subprocess.run(
            ["git", "-C", str(root), "rev-parse", "--show-toplevel"],
            capture_output=True, text=True, timeout=15)
        if top.returncode != 0 or not top.stdout.strip():
            return None
        toplevel = Path(top.stdout.strip())
        # run from the toplevel: `diff --name-only` is toplevel-relative
        # but `ls-files` is cwd-relative — one anchor for both
        diff = subprocess.run(
            ["git", "-C", str(toplevel), "diff", "--name-only", "HEAD"],
            capture_output=True, text=True, timeout=15)
        if diff.returncode != 0:
            return None
        untracked = subprocess.run(
            ["git", "-C", str(toplevel), "ls-files", "--others",
             "--exclude-standard"],
            capture_output=True, text=True, timeout=15)
        names = {l.strip() for l in diff.stdout.splitlines() if l.strip()}
        if untracked.returncode == 0:
            names |= {l.strip() for l in untracked.stdout.splitlines()
                      if l.strip()}
        return {(toplevel / n).resolve() for n in names}
    except Exception:
        return None


def _cache_signature(rules) -> str:
    pyver = ".".join(str(v) for v in sys.version_info[:2])
    return f"mxlint-{ENGINE_VERSION}-py{pyver}-" \
           + ",".join(sorted(r.id for r in rules))


def _file_record(path: Path, root: Path, per_file, project, cache,
                 findings_needed: bool = True):
    """Per-file analysis record: raw findings of every per-file rule,
    the suppression table, bad-suppression findings, and each project
    rule's facts.  Pure function of the file content (plus the rule
    set), which is exactly the cache key.

    ``findings_needed=False`` is the facts-only path for PROJECT_SCOPE
    extras: the (expensive) per-file rule suite is skipped and the
    record is marked ``partial`` — a later run that needs the same
    file's findings treats a partial record as a cache miss and
    upgrades it."""
    relpath = _relpath(path, root)
    try:
        data = path.read_bytes()
    except OSError:
        return {"relpath": relpath, "findings": [], "bad": [],
                "suppress": {}, "facts": {}}
    key = cache.key(relpath, data) if cache is not None else None
    if key is not None:
        rec = cache.get(relpath, key)
        if rec is not None and rec.get("relpath") == relpath \
                and not (findings_needed and rec.get("partial")):
            return rec
    mod = load_module(path, root,
                      source=data.decode("utf-8", errors="replace"))
    if mod is None:
        rec = {"relpath": relpath, "findings": [], "bad": [],
               "suppress": {}, "facts": {}}
    else:
        table, bad = parse_suppressions(mod)
        findings = []
        if findings_needed:
            for rule in per_file:
                for f in rule.check_module(mod):
                    findings.append({"rule": f.rule, "line": f.line,
                                     "col": f.col, "message": f.message})
        rec = {
            "relpath": relpath,
            "findings": findings,
            "bad": [{"line": b.line, "col": b.col, "message": b.message}
                    for b in bad],
            "suppress": {str(line): [sorted(rules), just]
                         for line, (rules, just) in table.items()},
            "facts": {},
        }
        if not findings_needed:
            rec["partial"] = True
        for rule in project:
            fact = rule.facts(mod)
            if fact is not None:
                rec["facts"][rule.id] = fact
    if key is not None:
        cache.put(relpath, key, rec)
    return rec


def analyze(paths, config: Optional[Config] = None, rules=None,
            root: Optional[Path] = None, use_cache: bool = False,
            cache_dir=None, changed_only: bool = False) -> List[Finding]:
    """Run every enabled rule over ``paths`` (files or directories).

    Returns ALL findings, with suppressed ones marked rather than
    dropped — the JSON output keeps them visible (an audit of what is
    being waived), the exit code ignores them.

    ``use_cache=True`` reads/writes per-file records under
    ``<root>/.mxlint_cache/`` (or ``cache_dir``); only files whose
    content changed are re-analyzed.  ``changed_only=True`` restricts
    the analyzed set to files ``git`` reports as differing from HEAD
    (the ``--changed`` CLI flag).  Passing a custom ``rules`` list
    disables the cache — cached records are keyed on the default rule
    set's identity, not arbitrary rule objects.
    """
    config = config or Config()
    custom_rules = rules is not None
    rules = list(rules) if custom_rules else default_rules()
    root = Path(root) if root is not None else Path.cwd()
    per_file = [r for r in rules if not isinstance(r, ProjectRule)]
    project = [r for r in rules if isinstance(r, ProjectRule)]
    defaults = {r.id: r.default_severity for r in rules}

    files = _collect_files(paths)
    if changed_only:
        changed = _git_changed(root)
        if changed is not None:
            files = [f for f in files if f.resolve() in changed]

    cache = None
    if use_cache and not custom_rules:
        from .cache import FileCache
        cache = FileCache(root, cache_dir,
                          signature=_cache_signature(rules))

    records = []
    analyzed_rel = set()
    seen_paths = set()
    for f in files:
        rp = f.resolve()
        if rp in seen_paths:
            continue
        seen_paths.add(rp)
        rec = _file_record(f, root, per_file, project, cache)
        rec["_analyzed"] = True
        analyzed_rel.add(rec["relpath"])
        records.append(rec)
    if project:
        extra = []
        for sub in PROJECT_SCOPE:
            p = root / sub
            if p.exists():
                extra.extend(_collect_files([p]))
        for f in extra:
            rp = f.resolve()
            if rp in seen_paths:
                continue
            seen_paths.add(rp)
            rec = _file_record(f, root, per_file, project, cache,
                               findings_needed=False)
            rec["_analyzed"] = False
            records.append(rec)

    findings: List[Finding] = []
    for rec in records:
        if not rec["_analyzed"]:
            continue
        for fd in rec["findings"]:
            rid = fd["rule"]
            if not config.enabled(rid):
                continue
            findings.append(Finding(
                rule=rid, path=rec["relpath"], line=fd["line"],
                col=fd["col"], message=fd["message"],
                severity=config.severity_of(rid,
                                            defaults.get(rid, "error"))))
        if config.enabled(BAD_SUPPRESSION):
            for bd in rec["bad"]:
                findings.append(Finding(
                    rule=BAD_SUPPRESSION, path=rec["relpath"],
                    line=bd["line"], col=bd["col"],
                    message=bd["message"]))

    for rule in project:
        if not config.enabled(rule.id):
            continue
        fact_list = [(rec["relpath"], rec["facts"][rule.id])
                     for rec in records if rule.id in rec["facts"]]
        sev = config.severity(rule)
        for f in rule.check_facts(fact_list, root, analyzed_rel):
            f.severity = sev
            findings.append(f)

    # apply suppressions (bad-suppression is exempt by design)
    tables = {rec["relpath"]: rec["suppress"] for rec in records
              if rec["_analyzed"]}
    for f in findings:
        if f.rule == BAD_SUPPRESSION:
            continue
        hit = tables.get(f.path, {}).get(str(f.line))
        if hit and f.rule in set(hit[0]):
            f.suppressed = True
            f.justification = hit[1]

    # sort + dedupe: interprocedural walks legitimately reach the same
    # site via several paths (helper under two locks, finally bodies
    # duplicated per continuation) — one finding per anchor
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    out, seen = [], set()
    for f in findings:
        k = (f.rule, f.path, f.line, f.col)
        if k in seen:
            continue
        seen.add(k)
        out.append(f)
    return out


def summarize(findings: List[Finding]) -> str:
    active = [f for f in findings if not f.suppressed]
    sup = len(findings) - len(active)
    errs = sum(1 for f in active if f.severity == "error")
    return (f"{len(active)} finding(s) ({errs} error(s)), "
            f"{sup} suppressed")


def to_json(findings: List[Finding]) -> str:
    return json.dumps([f.to_dict() for f in findings], indent=2)


def exit_code(findings: List[Finding]) -> int:
    return 1 if any(not f.suppressed and f.severity == "error"
                    for f in findings) else 0


# --------------------------------------------------------------------------
# shared AST helpers (used by the rule modules)
# --------------------------------------------------------------------------

def dotted_name(node) -> Optional[str]:
    """'jax.numpy.asarray' for nested Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def last_component(node) -> Optional[str]:
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def assigned_names(target) -> set:
    """Names bound by an assignment target (handles tuple unpacking)."""
    out = set()
    for n in ast.walk(target):
        if isinstance(n, ast.Name) and isinstance(n.ctx, (ast.Store,
                                                          ast.Del)):
            out.add(n.id)
    return out
