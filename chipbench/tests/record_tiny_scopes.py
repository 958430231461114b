"""Record ``data/tiny_scopes_v5e.xplane.pb``, the trace that the SELF-time,
phase, whole-step, ``scope_ms`` and ``span_ms`` tests read.  Run on the chip
from the root of the repo (``chiprun -- python3
chipbench/tests/record_tiny_scopes.py``); the trace comes back under
``chiprun_out/tiny_scopes/`` and is copied beside this file by hand.

The program is a training step in small: a ``forward`` scope with two layer
scopes (a ``jax.checkpoint`` block; a ``fori_loop``, which the backward pass
turns into a second ``while``), a ``loss`` scope, and an ``optimizer`` scope
whose update is one Pallas call (``name="tiny_update"``), under the
program's own span ``TrainStep.dispatch``."""
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
from jax.experimental import pallas as pl                  # noqa: E402
from mxnet_tpu import profiler                             # noqa: E402

from chipbench.trace import reduce                         # noqa: E402

STEPS, LOOP_TRIPS = 8, 12


def update_kernel(w_ref, g_ref, o_ref):
    o_ref[...] = w_ref[...] - 1e-3 * g_ref[...]


@jax.checkpoint
def layer0(w, x):
    """Two products deep, so that the backward pass has to run the first
    again: the block's output alone does not give the inner tanh."""
    with jax.named_scope("layer0/mlp"):
        return jnp.tanh(jnp.tanh(x @ w) @ w)


def loss_of(w, x):
    with jax.named_scope("forward"):
        h = layer0(w, x)
        with jax.named_scope("layer1/attention"):
            h = jax.lax.fori_loop(0, LOOP_TRIPS,
                                  lambda i, h: jnp.sin(h @ w), h)
    with jax.named_scope("loss"):
        return jnp.mean(h * h)


@jax.jit
def tiny_train_step(w, x):
    loss, g = jax.value_and_grad(loss_of)(w, x)
    with jax.named_scope("optimizer"):
        w = pl.pallas_call(update_kernel, name="tiny_update",
                           out_shape=jax.ShapeDtypeStruct(w.shape, w.dtype),
                           interpret=jax.default_backend() != "tpu")(w, g)
    return w, loss


def main():
    x = jnp.ones((512, 512), jnp.float32) * 0.01
    w = jnp.eye(512, dtype=jnp.float32)
    w, loss = tiny_train_step(w, x)
    loss.block_until_ready()
    out = "chiprun_out/tiny_scopes"
    with reduce.capture(out + "/trace"):
        for i in range(STEPS):
            with jax.profiler.TraceAnnotation("chipbench.step"):
                with profiler.scope("TrainStep.dispatch"):
                    w, loss = tiny_train_step(w, x)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                loss.block_until_ready()
                if i % 2:                   # every other step the host idles
                    time.sleep(0.005)
    path = reduce.find_xplane(out + "/trace")
    shutil.copy(path, out + "/tiny_scopes_v5e.xplane.pb")
    t = reduce.Trace(path)
    print(os.path.getsize(path), t.window_s, t.busy_s(), len(t.whole_steps()))
    for rec in t.step_ops():
        if rec[0] == 0:
            print(rec)
    print(t.device_ops(), t.idle_gaps(), t.spans("TrainStep.dispatch"))


if __name__ == "__main__":
    main()
