"""``span_ms``: a percentile of the durations of one of the program's spans
(``profiler.scope(name)``, a host event of the device's own trace)."""
from chipbench import traffic


def read(ctx, name, q):
    """The q-th percentile, in ms, of the events called ``name`` that lie
    wholly inside the traced window; None where there is none."""
    if ctx.reduced is None:
        return None
    return traffic.percentile(ctx.reduced.spans(name), q)
