"""Readers of per-layer metrics.  A metric's file names one and its
arguments; each takes the run's context (counters, series, the reduced
trace, configuration) and returns a number, or None when it finds nothing
to read, and the harness then leaves the metric out of the line.

A reader has one of two homes, and ``find`` looks in both: a function of
this file, or ``read`` of a file of its own, ``layer_readers/<fn>.py``,
which is how a later PR brings one without editing anything."""
import importlib
import importlib.util
import re

from chipbench import traffic

_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_]{0,63}$")


def find(fn):
    """The reader called ``fn``, or None where neither home has one."""
    if not isinstance(fn, str) or not _NAME.match(fn) or fn == "find":
        return None
    here = globals().get(fn)
    if callable(here) and getattr(here, "__module__", None) == __name__:
        return here
    module = f"chipbench.layer_readers.{fn}"
    if importlib.util.find_spec(module) is None:
        return None
    read = getattr(importlib.import_module(module), "read", None)
    return read if callable(read) else None


def counter(ctx, key, scale=1.0):
    """A counter or gauge of the program's snapshot under the program's own
    name; ``scale`` 100 reports a share kept as 0..1 in %."""
    value = ctx.counters.get(key)
    return None if value is None else scale * value


def series_percentile(ctx, series, q):
    return traffic.percentile(ctx.series.get(series, []), q)


def device_idle_pct(ctx):
    t = ctx.reduced
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)


def mfu_pct(ctx, rate):
    """Required training FLOPs per sample x samples/s/chip over the peak."""
    r = ctx.e2e.get(rate)
    if r is None or ctx.peaks is None:
        return None
    family = importlib.import_module(f"chipbench.families.{ctx.cfg['family']}")
    return 100.0 * family.train_flops(ctx.cfg["model"]) * r \
        / ctx.peaks["bf16_flops_per_s"]
