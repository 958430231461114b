"""hloguard CLI: ``python -m tools.hloguard [target ...]``.

Exit code 0 = every selected surface structurally clean (0 unsuppressed
findings, no stale goldens), 1 = findings / drift / missing golden,
2 = usage.

Targets are surface names, or paths — a path selects every registered
surface whose builder is defined under it (the costguard CLI contract:
``python -m tools.hloguard mxnet_tpu/`` audits the whole registered
surface).  No target = everything.

Environment: forces ``JAX_PLATFORMS=cpu`` with an 8-device virtual mesh
unless the caller already chose a platform — structural goldens record
their bring-up and only *gate* in a matching backend/device-count
environment (the CPU-vs-TPU lowering caveat in docs/analysis.md).
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path


def _env_bringup():
    """Same pre-jax-import bring-up as tests/conftest.py — must run
    before anything imports jax."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if os.environ["JAX_PLATFORMS"] == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.hloguard",
        description="structural lint over lowered HLO "
                    "(docs/analysis.md \"Structural HLO lint\")")
    parser.add_argument("targets", nargs="*", default=[],
                        help="surface names and/or paths (a path selects "
                             "the surfaces defined under it); default: "
                             "every registered surface")
    parser.add_argument("--format", choices=("human", "json", "sarif"),
                        default="human", dest="fmt")
    parser.add_argument("--list", action="store_true",
                        help="list registered surfaces and exit")
    parser.add_argument("--root", default=None,
                        help="repo root for goldens/cache (default: cwd)")
    parser.add_argument("--no-cache", action="store_true",
                        help="disable the .hloguard_cache/ facts cache "
                             "(always re-parse)")
    parser.add_argument("--cache-dir", default=None,
                        help="cache directory (default: "
                             "<root>/.hloguard_cache)")
    args = parser.parse_args(argv)

    _env_bringup()
    from . import run_check, surfaces

    if args.list:
        for name in surfaces.names():
            kind = ("tpu-export" if name in surfaces.export_names()
                    else "entrypoint")
            print(f"{name:28s} {kind}")
        return 0

    root = Path(args.root).resolve() if args.root else Path.cwd()
    known = surfaces.names()
    selected = []
    for t in args.targets:
        if t in known:
            selected.append(t)
            continue
        p = Path(t)
        if p.exists():
            rp = p.resolve()
            hits = [n for n in known if _selects(n, rp, root)]
            selected.extend(h for h in hits if h not in selected)
            if not hits:
                print(f"# note: no registered surface under {t}",
                      file=sys.stderr)
            continue
        parser.error(f"{t!r} is neither a registered surface nor a "
                     f"path (see --list)")
    if args.targets and not selected:
        print("hloguard: no registered surfaces under the given targets "
              "— auditing goldens only", file=sys.stderr)
    result = run_check(entries=selected if args.targets else None,
                       root=root, use_cache=not args.no_cache,
                       cache_dir=args.cache_dir)
    if args.fmt == "json":
        print(result.to_json())
    elif args.fmt == "sarif":
        print(result.to_sarif())
    else:
        print(result.render())
    return 0 if result.ok else 1


def _selects(name: str, path: Path, root: Path) -> bool:
    """Does a path target cover surface ``name``?  Its builder file is
    under the path, or the path contains the mxnet_tpu package (every
    surface audits that package's lowered programs)."""
    from . import surfaces
    if surfaces.source_of(name).resolve().is_relative_to(path):
        return True
    pkg = (root / "mxnet_tpu").resolve()
    return pkg == path or pkg.is_relative_to(path)


if __name__ == "__main__":
    sys.exit(main())
