"""``span_total_ms``: the total time, in ms, of one of the program's spans
(``profiler.scope(name)``) over the whole process, from the registry's
histogram ``<name>_ms``: what reads a span that closes before the traced
window opens, as every set-up span does."""


def read(ctx, name):
    """The ``sum`` of ``telemetry.registry()``'s histogram ``<name>_ms``;
    None where the program recorded no such span.  The program is imported
    here and not above: ``manifest.validate`` imports this file before the
    TPU runtime starts, and the program's import belongs after it."""
    from mxnet_tpu import telemetry
    hist = telemetry.registry().get(f"{name}_ms")
    snap = hist.snapshot() if hasattr(hist, "snapshot") else None
    return snap["sum"] if snap and snap["count"] else None
