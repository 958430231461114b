"""``scope_ms``: SELF time on the device, in ms a whole step, of the
instructions a metric's file selects by ``jax.named_scope`` path, by
instruction name, by phase of the step, or any of them together."""
import re


def read(ctx, scopes=None, ops=None, not_ops=None, phase=None):
    """Mean over the whole steps inside the traced window, first chip, of
    the SELF time of the instructions whose scope path matches any regex of
    ``scopes`` OR whose name (``reduce.short_name``: ``flash_attention_fwd.3
    tpu_custom_call bf16[..]``) matches any of ``ops``, less those whose
    name matches any of ``not_ops``, within ``phase`` if given.  With no
    ``scopes`` and no ``ops`` everything of the phase counts.  A fusion
    carries the scope of its root instruction.  None when the window holds
    no whole step."""
    t = ctx.reduced
    if t is None or not t.whole_steps():
        return None
    scopes, ops, not_ops = ([re.compile(p) for p in pats or ()]
                            for pats in (scopes, ops, not_ops))
    total = 0.0
    for _, own, name, scope, its_phase in t.step_ops():
        if phase is not None and its_phase != phase:
            continue
        if (scopes or ops) and not (
                any(p.search(scope) for p in scopes)
                or any(p.search(name) for p in ops)):
            continue
        if any(p.search(name) for p in not_ops):
            continue
        total += own
    return total / len(t.whole_steps()) / 1e6
