"""Readers of per-layer metrics.  A metric's file names one of these and its
arguments; each takes the run's context (counters, series, the reduced
trace, configuration) and returns a number, or None when it finds nothing
to read, and the harness then leaves the metric out of the line."""
import importlib

from chipbench import traffic


def counter(ctx, key):
    return ctx.counters.get(key)


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
