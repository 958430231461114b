"""``idle_gap_max_ms``: the longest single interval in which the first chip
ran nothing, of the same intervals that ``breakdown.idle_gaps`` sums by host
activity.  Beside the idle share it tells one stall of seconds from a host
that holds the chip back a little at every step."""


def read(ctx):
    gaps = ctx.reduced.gaps() if ctx.reduced is not None else []
    if not gaps:
        return None
    return max(g1 - g0 for g0, g1, _ in gaps) / 1e6
