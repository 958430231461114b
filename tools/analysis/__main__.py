"""mxlint CLI: ``python -m tools.analysis mxnet_tpu/``.

Exit code 0 = no unsuppressed error-severity findings (the tier-1 gate
in tests/test_mxlint.py asserts exactly this), 1 = findings, 2 = usage.

Incremental mode is the default: per-file records are cached under
``<root>/.mxlint_cache/`` keyed by content hash, so a re-run after a
small edit re-analyzes only the edited files (``--no-cache`` opts out;
``--changed`` additionally restricts the analyzed set to what
``git diff --name-only`` reports).  ``--format sarif`` emits a SARIF
2.1.0 log for CI annotation tooling.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

from .core import (Config, analyze, default_rules, exit_code, summarize,
                   to_json)
from .sarif import to_sarif


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.analysis",
        description="mxlint: trace-safety / thread-safety / donation / "
                    "concurrency / lifecycle / registry static analysis "
                    "(docs/analysis.md)")
    parser.add_argument("paths", nargs="*", default=[],
                        help="files or directories to analyze "
                             "(default: mxnet_tpu; with --changed, the "
                             "whole gated surface — mxnet_tpu, tools, "
                             "examples, chip_smoke.py — so an edit anywhere "
                             "the gate covers is seen)")
    parser.add_argument("--format", choices=("human", "json", "sarif"),
                        default="human", dest="fmt",
                        help="output format (sarif = SARIF 2.1.0 for CI "
                             "annotation ingestion)")
    parser.add_argument("--json", action="store_true",
                        help="shorthand for --format json (suppressed "
                             "findings included, marked)")
    parser.add_argument("--disable", action="append", default=[],
                        metavar="RULE", help="disable a rule id")
    parser.add_argument("--severity", action="append", default=[],
                        metavar="RULE=LEVEL",
                        help="override a rule's severity "
                             "(error|warning|info)")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="also print suppressed findings")
    parser.add_argument("--root", default=None,
                        help="repo root for relative paths + docs "
                             "(default: cwd)")
    parser.add_argument("--changed", action="store_true",
                        help="lint only files git reports as changed "
                             "(diff vs HEAD + untracked); no-op when "
                             "git is unavailable")
    parser.add_argument("--no-cache", action="store_true",
                        help="disable the .mxlint_cache/ incremental "
                             "cache (always re-analyze)")
    parser.add_argument("--cache-dir", default=None,
                        help="cache directory (default: "
                             "<root>/.mxlint_cache)")
    parser.add_argument("--list-rules", action="store_true",
                        help="list rule ids and exit")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in default_rules():
            print(f"{rule.id:26s} {rule.description}")
        return 0

    severities = {}
    for spec in args.severity:
        if "=" not in spec:
            parser.error(f"--severity expects RULE=LEVEL, got {spec!r}")
        rid, sev = spec.split("=", 1)
        severities[rid] = sev
    config = Config(disabled=args.disable, severities=severities)

    root = Path(args.root) if args.root else Path.cwd()
    paths = args.paths
    if not paths:
        # defaults are anchored at --root (explicit paths stay
        # cwd-relative, normal CLI semantics).  With --changed the
        # default set is the whole gated surface: "lint what I
        # changed" silently skipping a changed tools/ or examples/
        # file would be a false all-clear
        defaults = ("mxnet_tpu", "tools", "examples", "chip_smoke.py") \
            if args.changed else ("mxnet_tpu",)
        paths = [root / p for p in defaults if (root / p).exists()]
    findings = analyze(paths, config=config, root=root,
                       use_cache=not args.no_cache,
                       cache_dir=args.cache_dir,
                       changed_only=args.changed)

    fmt = "json" if args.json else args.fmt
    if fmt == "json":
        print(to_json(findings))
    elif fmt == "sarif":
        print(to_sarif(findings))
    else:
        for f in findings:
            if f.suppressed and not args.show_suppressed:
                continue
            print(f.render())
        print(summarize(findings))
    return exit_code(findings)


if __name__ == "__main__":
    sys.exit(main())
