"""``since_start_ms``: from the start of the process to a stamp the program
left as a gauge on ``time.perf_counter`` (``process.import_t0_s``: when the
program's import began), the clock of the harness's own start."""


def read(ctx, key):
    """``(ctx.counters[key] - ctx.t0)`` in ms; None where the program left no
    such stamp, or left it before ``ctx.t0`` (a rehearsal that calls
    ``run_cell`` with the package long imported)."""
    stamp = ctx.counters.get(key)
    if stamp is None or stamp < ctx.t0:
        return None
    return (stamp - ctx.t0) * 1e3
