"""``counter_ratio_pct``: one sum of the program's counters over another,
in % (``cache_hit_pct``: persistent-cache hits over hits and misses)."""


def read(ctx, num, den):
    """100 x the sum of the counters ``num`` over the sum of the counters
    ``den`` (lists of keys of the program's snapshot); None where a key is
    missing or the denominator is zero."""
    if any(ctx.counters.get(k) is None for k in num + den):
        return None
    total = sum(ctx.counters[k] for k in den)
    return 100.0 * sum(ctx.counters[k] for k in num) / total if total \
        else None
