#!/usr/bin/env python
"""Regenerate the committed cost-budget goldens
(``tests/goldens/budgets/*.json``).

Run after an INTENTIONAL change to a budgeted model/step/serving
program — a traffic optimization to ratchet in, a new layer, a schema
bump — then review the diff like any other source change: the golden
IS the performance contract tier-1 regresses against
(``tests/test_costguard.py::test_budget_gate_committed_tree``)::

    python tests/goldens/budgets/regen_budgets.py            # all
    python tests/goldens/budgets/regen_budgets.py mnist_mlp_train

Budgets are recorded under the tier-1 bring-up (JAX_PLATFORMS=cpu,
8-device virtual mesh) and only gate in a matching environment; the
CPU-vs-TPU byte-count caveat is PERF.md's.  Compilation is fresh —
no report cache — so a regen can never launder a stale number.
"""
import json
import os
import sys
from pathlib import Path

# must precede any jax import — same bring-up as tests/conftest.py
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

REPO = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(REPO))


def main(argv=None):
    from tools.costguard import (budget, entrypoints, environment,
                                 report_for_programs)

    names = (argv if argv else sys.argv[1:]) or entrypoints.names()
    out_dir = REPO / budget.GOLDEN_SUBDIR
    out_dir.mkdir(parents=True, exist_ok=True)
    env = environment()
    # census guard, device-count leg, checked for EVERY requested name
    # BEFORE anything is written: a sharded golden regenerated from a
    # shell whose visible device count differs from the committed one
    # would silently gate nothing — refuse, and refuse before the loop
    # half-rewrites the directory
    for name in names:
        path = out_dir / f"{name}.json"
        if path.exists():
            old = json.loads(path.read_text(encoding="utf-8"))
            msg = budget.device_count_guard(old, env["n_devices"], name)
            if msg:
                raise SystemExit(msg)
    for name in names:
        built = entrypoints.build(name)
        report = report_for_programs(built.programs)   # no cache: fresh
        if report["n_executables"] != built.census:
            raise SystemExit(
                f"{name}: lowered {report['n_executables']} executables "
                f"but the static census says {built.census} — fix the "
                f"entry point before committing a golden")
        golden = dict(env)
        golden.update({"entry": name, "meta": built.meta,
                       "census": built.census,
                       "report": budget.budgeted(report)})
        path = out_dir / f"{name}.json"
        path.write_text(json.dumps(golden, indent=2, sort_keys=True)
                        + "\n", encoding="utf-8")
        print(f"wrote {path.relative_to(REPO)} "
              f"({report['n_executables']} executable(s), "
              f"{report['flops'] / 1e9:.3f} GFLOP, "
              f"{report['bytes_accessed'] / 1e6:.2f} MB accessed as "
              f"lowered)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
