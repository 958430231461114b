"""costguard — compiled-program cost budgets and recompile audit.

The static-analysis instrument for the compile boundary (ISSUE 6):
mxlint gates the Python-source surface; costguard gates the programs
handed to XLA.  It lowers and compiles each registered entry point
(model train step / serving bucket grid) WITHOUT executing a step,
extracts a normalized report and diffs the rows our code decides —
executable count, donation coverage, argument and collective bytes,
conv/dot/custom-call counts, the lowered module's FLOPs and bytes:
``budget.DEFAULT_TOLERANCES`` is the list — against committed per-model
budget goldens (``tests/goldens/budgets/*.json``) with per-metric
relative tolerances.
The static executable census makes "traffic can never trigger a
recompile" a checked invariant rather than a comment.

Usage (CLI)::

    python -m tools.costguard                    # audit all entry points
    python -m tools.costguard mxnet_tpu/         # entries defined under a path
    python -m tools.costguard mnist_mlp_train --format json
    python -m tools.costguard --list

Usage (API, what tests/test_costguard.py drives)::

    from tools import costguard
    result = costguard.run_check(root=repo_root)
    assert result.ok, result.render()

Budgets regenerate via ``python tests/goldens/budgets/regen_budgets.py``
(review the diff like source).  Docs: docs/analysis.md "Cost budgets".
"""
from .budget import (DEFAULT_TOLERANCES, CheckResult, EntryResult,
                     MetricRow, budgeted, check_entry, diff_report,
                     environment, golden_path, load_golden, run_check)
from .census import executable_census, grid_signatures
from .entrypoints import EntryBuild, build, entrypoint, names, source_of
from .report import (REPORT_VERSION, Program, collective_payload_bytes,
                     instruction_counts, merge_reports,
                     report_for_programs)

__all__ = [
    "DEFAULT_TOLERANCES", "CheckResult", "EntryResult", "MetricRow",
    "budgeted", "check_entry", "diff_report", "environment",
    "golden_path", "load_golden", "run_check",
    "executable_census", "grid_signatures",
    "EntryBuild", "build", "entrypoint", "names", "source_of",
    "REPORT_VERSION", "Program", "collective_payload_bytes",
    "instruction_counts", "merge_reports", "report_for_programs",
]
