"""``kind: train``: a net of ``families/<family>.py`` under
``parallel.TrainStep`` on a ``dp`` mesh over the cell's chips, driven by
``train_loop``: one batch resident on the device, at most two steps in
flight, every loss fetched."""
import importlib
import time

import jax
import jax.numpy as jnp
import mxnet_tpu as mx
import numpy as np
from mxnet_tpu import parallel, telemetry
from mxnet_tpu.gluon.block import _flatten_nd, _unflatten_nd
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel.functional import (FunctionalState, functional_call,
                                           param_names_and_values)

from chipbench import traffic

WARMUP_STEPS = 6
# the reference's loss on the batch's own labels must lie this many
# tolerances above its loss on the check labels, else the comparison could
# not tell a right forward from a wrong one
TEETH = 10


def _reference(net, loss_fn, check_labels, step, data, labels, key):
    """One jitted float32 forward of ``net`` in training mode at "highest"
    matmul precision with ``step``'s current parameters, outside TrainStep.
    Returns the family's check labels (made from that forward's outputs),
    the mean loss on them, and the mean loss on the batch's own labels."""
    names, plist, _ = param_names_and_values(net)
    held = step.params
    arrays = [held[n] for n in names]
    leaves, tree = _flatten_nd(tuple(NDArray(d) for d in data))
    state = FunctionalState()

    def f32(a):
        return a.astype(jnp.float32) \
            if jnp.issubdtype(a.dtype, jnp.floating) else a

    def fn(arrays, key, leaves, labels):
        outs = functional_call(net, plist, [f32(a) for a in arrays], tree,
                               [f32(l) for l in leaves], key, True, state)
        out = _unflatten_nd(state.out_tree, tuple(NDArray(o) for o in outs))

        def mean_loss(lab):
            lab = tuple(NDArray(l) for l in lab)
            loss = loss_fn(out, lab[0] if len(lab) == 1 else lab)
            return jnp.mean(loss._data.astype(jnp.float32))
        made = check_labels(out)
        return made, mean_loss(made), mean_loss(labels)

    with jax.default_matmul_precision("highest"):
        made, on_made, on_own = jax.jit(fn)(
            arrays, key, [l._data for l in leaves], list(labels))
    return made, float(on_made), float(on_own)


def program_snapshot():
    """Every counter and gauge of the program's registry under its own name,
    and every number of ``telemetry.compile_stats()`` as ``compile_<key>``
    (``compile_ms_total``; a tracked site's count as
    ``compile_sites.<site>``): what a metric file's ``counter`` can read."""
    snap = telemetry.registry().snapshot()
    out = {**snap["counters"], **snap["gauges"]}
    stats = telemetry.compile_stats()
    for site, count in stats.pop("sites").items():
        out[f"compile_sites.{site}"] = count
    out.update({f"compile_{k}": v for k, v in stats.items()})
    return out


def run(ctx):
    cfg, wl, n = ctx.cfg, ctx.wl, len(ctx.devices)
    if ctx.trace:
        telemetry.enable(sample=0.0)     # the compile-event stream alone
    seed = traffic.fold_seed(ctx.seed)
    mx.random.seed(seed)
    family = importlib.import_module(f"chipbench.families.{cfg['family']}")
    net, loss_fn, make_batch = family.build(cfg["model"])
    net.initialize()
    net.cast(cfg["compute_dtype"])
    opt = mx.optimizer.create(cfg["optimizer"]["name"],
                              **cfg["optimizer"]["args"])
    step = parallel.TrainStep(
        net, loss_fn, opt, mesh=parallel.make_mesh(dp=n, devices=ctx.devices))

    batch = wl["batch_per_chip"] * n
    data, labels = make_batch(np.random.default_rng(seed), batch)

    def place(a, cast):
        a = jax.device_put(a, step.data_sharding)
        return a.astype(cfg["compute_dtype"]) \
            if cast and a.dtype == np.float32 else a

    def one(arrays):
        return arrays[0] if len(arrays) == 1 else arrays
    data = tuple(place(a, True) for a in data)       # inputs as computed in
    labels = tuple(place(a, False) for a in labels)
    x, y = one(data), one(labels)

    # ---- warm-up on the one batch: build + compile + step 1
    t = time.perf_counter()
    first = float(step(x, y).asnumpy())
    ctx.log(f"first step (deferred init, build, compile) "
            f"{time.perf_counter() - t:.1f} s, loss {first:.4f}")
    # step 2 against the reference, on labels made from the reference's own
    # outputs; the same key for the reference and the step that follows it
    tol = cfg["check"]["loss_atol"]
    mx.random.seed(seed + 1)
    made, ref, ref_own = _reference(net, loss_fn, family.check_labels, step,
                                    data, labels, mx.random.next_key())
    made = tuple(jax.device_put(a, step.data_sharding) for a in made)
    mx.random.seed(seed + 1)
    got = float(step(x, one(made)).asnumpy())
    ctx.check(f"TrainStep's loss on the check labels within {tol} of the "
              f"float32 reference's", abs(got - ref) <= tol,
              f"{got:.5f} vs {ref:.5f} (off by {abs(got - ref):.2e})",
              key="loss_err", value=abs(got - ref), limit=f"<= {tol}")
    ctx.check(f"a forward that misses the reference's logits would show: its "
              f"loss on the batch's own labels is over {TEETH} tolerances "
              f"above", ref_own - ref >= TEETH * tol,
              f"{ref_own:.4f} on the batch's labels, {ref:.4f} on the check "
              f"labels", key="own_label_gap", value=ref_own - ref,
              limit=f">= {TEETH * tol}")
    losses = [float(step(x, y).asnumpy()) for _ in range(WARMUP_STEPS)]
    ctx.check("loss falls over the warm-up steps", losses[-1] < losses[0],
              str([round(v, 4) for v in losses]), key="warmup_loss_fall",
              value=losses[0] - losses[-1], limit="> 0")
    programs = step._jit._cache_size()
    created = ctx.executables
    tracked = telemetry.compile_stats()["events"]

    # ---- the window: dispatch step i, then fetch the loss of step i-1
    fetched, stamps, pending = [], [], None

    def fetch(loss):
        with jax.profiler.TraceAnnotation("chipbench.wait"):
            fetched.append(float(loss.asnumpy()))
        stamps.append(time.perf_counter())
    t0 = ctx.open_window()
    while True:
        now = time.perf_counter() - t0
        if now >= ctx.seconds:
            break
        ctx.poll_trace(now)
        with jax.profiler.TraceAnnotation("chipbench.step"):
            nxt = step(x, y)
        if pending is not None:
            fetch(pending)
        pending = nxt
    fetch(pending)
    elapsed = stamps[-1] - t0
    ctx.close_trace()

    ctx.check("every fetched loss is finite", bool(np.isfinite(fetched).all()),
              f"{len(fetched)} steps, last {fetched[-1]:.4f}",
              key="nonfinite_losses",
              value=int((~np.isfinite(fetched)).sum()), limit="== 0")
    # jax's own compile events (every executable the process creates or
    # loads, eager ops included), TrainStep's jit cache, and in a traced run
    # the program's compile-event stream
    ctx.check("no executable was created inside the window",
              ctx.executables == created
              and step._jit._cache_size() == programs
              and telemetry.compile_stats()["events"] == tracked,
              f"jax {created} -> {ctx.executables}, TrainStep "
              f"{programs} -> {step._jit._cache_size()}, telemetry {tracked} "
              f"-> {telemetry.compile_stats()['events']}",
              key="executables_in_window", value=ctx.executables - created,
              limit="== 0")
    step_ms = np.diff(stamps) * 1e3
    ctx.log(f"{len(fetched)} steps in {elapsed:.3f} s; step ms "
            f"{[round(float(v), 1) for v in step_ms]}")
    ctx.attempted, ctx.failed = len(fetched), 0
    ctx.e2e["train_samples_per_s"] = len(fetched) * batch / elapsed / n
    ctx.series["step_ms"] = step_ms.tolist()
    if ctx.trace:
        # the program's own numbers under the program's own names: the expert
        # layers' loads are aux state of the step, published as gauges
        step.sync_params_to_net()
        loads = parallel.publish_load(net)
        ctx.counters.update(program_snapshot())
        if not any(loads.values()):     # no expert layer: nothing to read
            for gauge in loads:
                del ctx.counters[gauge]
