"""``setup_rest_ms``: what is left of this run's ``setup_s`` when the parts
that a span or stamp of the program covers are taken away, so that the parts
and the rest add up to ``setup_s`` by construction."""
from chipbench import manifest, readers


def read(ctx, parts):
    """``setup_s`` in ms less the metrics named in ``parts``, each read as
    its own file says; None where ``setup_s`` or any part is missing."""
    total = ctx.e2e.get("setup_s")
    if total is None:
        return None
    rest = total * 1e3
    for name in parts:
        spec = dict(manifest.load_json(
            ctx.cell["root"], manifest.metric_file(name))["reader"])
        value = readers.find(spec.pop("fn"))(ctx, **spec)
        if value is None:
            return None
        rest -= value
    return rest
