"""``kernel_roofline_pct``: the share of the chip's peak that some kernels
reached, from the device trace: the FLOPs their executions in the traced
whole steps required, by the configuration's family, over those executions'
SELF time and the peak."""
import importlib
import re


def read(ctx, flops):
    """``flops`` names a function of the cell's family that gives ``{kernel
    name: FLOPs of one execution for one sequence}``; each execution of a
    kernel of that name (an instruction whose name is the kernel's, then
    ``.``, a space or its end) in the whole steps counts those FLOPs times
    the sequences a chip's step holds (``batch_per_chip``).  A recomputed
    forward counts as the execution it is.  In % of the peak (``peaks.json``'s
    ``bf16_flops_per_s``).  None where the family has no such function or
    the window holds no whole step or no such kernel."""
    t = ctx.reduced
    if t is None or ctx.peaks is None or not t.whole_steps():
        return None
    family = importlib.import_module(
        f"chipbench.families.{ctx.cfg['family']}")
    count = getattr(family, flops, None)
    if count is None:
        return None
    per = count(ctx.cfg["model"])
    sequences = ctx.wl.get("batch_per_chip", 1)
    kernel = re.compile(r"^(" + "|".join(map(re.escape, per)) + r")(\.|\s|$)")
    done, own_ns = 0.0, 0.0
    for _, own, name, _, _ in t.step_ops():
        m = kernel.match(name)
        if m:
            done += per[m.group(1)] * sequences
            own_ns += own
    if not own_ns:
        return None
    return 100.0 * done / (own_ns / 1e9) / ctx.peaks["bf16_flops_per_s"]
