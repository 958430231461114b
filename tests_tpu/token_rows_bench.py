"""One call of each token-side pass of the dropless expert layer, timed on
the chip at the three expert cells' shapes: the kernels of
``ops/pallas/token_rows.py`` against the XLA passes they replace (a whole
gather of the N x k sorted rows, a mask, a sum)::

    python tests_tpu/token_rows_bench.py [--shares 0.18 0.35 0.66]
        [--blocks 32 64 128] [--calls 20]

Synthetic routing: each assignment goes to one of the held experts with
probability ``share`` and past them otherwise, sorted stably as the op sorts;
the rows past ``total`` are NaN, as a producer that never wrote them may
leave them.  Every line is printed and written to
``chiprun_out/token_rows_bench.json``.  Needs the chip to itself."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from mxnet_tpu.ops.pallas import token_rows  # noqa: E402

# (N, k, d, held) of mellum2_12b_a2_5b, lfm2_8b_a1b and kanana2_30b_a3b's
# train_s8192 cells, and the held share each reads at a run's end
CELLS = {"mellum2_12b_a2_5b": (16384, 8, 2304, 16, 0.66),
         "lfm2_8b_a1b": (32768, 4, 2048, 8, 0.35),
         "kanana2_30b_a3b": (16384, 6, 2048, 16, 0.18)}


def routing(rng, n, k, held, share):
    key = np.where(rng.random((n, k)) < share,
                   rng.integers(0, held, (n, k)), held).reshape(-1)
    order = np.argsort(key, kind="stable")
    back = np.empty(n * k, np.int32)
    back[order] = np.arange(n * k, dtype=np.int32)
    sizes = np.bincount(key, minlength=held + 1)[:held]
    offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int32)
    return jnp.asarray(back.reshape(n, k)), jnp.asarray(offsets)


@jax.jit
def xla_combine(out, back, total, gates):
    """The parent's combine forward: the gathered rows are kept."""
    held = jnp.where((back < total)[..., None], out[back], 0)
    weighed = jnp.sum(held.astype(jnp.float32) * gates[..., None], axis=1)
    return weighed.astype(out.dtype), held


@jax.jit
def xla_dispatch_bwd(dy, back, total):
    held = jnp.where((back < total)[..., None], dy[back], 0)
    return jnp.sum(held, axis=1).astype(dy.dtype)


@jax.jit
def xla_gates_grad(held, dy):
    return jnp.sum(held.astype(jnp.float32)
                   * dy.astype(jnp.float32)[:, None, :], axis=-1)


def timed(fn, *args, calls):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        got = fn(*args)
    jax.block_until_ready(got)
    return (time.perf_counter() - t0) / calls * 1e3


def err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shares", type=float, nargs="*")
    ap.add_argument("--blocks", type=int, nargs="+", default=[64])
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--cells", nargs="+", default=sorted(CELLS))
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(f"[bench] {dev.platform} {dev.device_kind}", flush=True)
    lines = []
    rng = np.random.default_rng(40)
    for cell in a.cells:
        n, k, d, held, own = CELLS[cell]
        for share in a.shares or [own]:
            back, offsets = routing(rng, n, k, held, share)
            total = offsets[-1]
            m = n * k
            out = jnp.asarray(rng.standard_normal((m, d)), jnp.bfloat16)
            out = jnp.where(jnp.arange(m)[:, None] < total, out, jnp.nan
                            ).astype(jnp.bfloat16)
            gates = jnp.asarray(rng.random((n, k)), jnp.float32)
            dy = jnp.asarray(rng.standard_normal((n, d)), jnp.bfloat16)
            ones = jnp.ones((n, k), jnp.float32)
            want, held_rows = xla_combine(out, back, total, gates)
            want_bwd = xla_dispatch_bwd(out, back, total)
            want_dot = xla_gates_grad(held_rows, dy)
            line = dict(cell=cell, n=n, k=k, d=d, held=held, share=share,
                        held_share=float(total) / m,
                        xla_combine_ms=timed(xla_combine, out, back, total,
                                             gates, calls=a.calls),
                        xla_dispatch_bwd_ms=timed(xla_dispatch_bwd, out,
                                                  back, total,
                                                  calls=a.calls),
                        xla_gates_grad_ms=timed(xla_gates_grad, held_rows,
                                                dy, calls=a.calls))
            del held_rows
            for block in a.blocks:
                # a jit of its own: another block's map has these shapes
                token_rows._TOKENS = block
                build = jax.jit(token_rows.token_map.__wrapped__)
                tmap = build(back, offsets)
                got = token_rows.moe_token_sum(out, tmap, gates)
                got_bwd = token_rows.moe_token_sum(out, tmap, ones)
                got_dot = token_rows.moe_token_dot(out, tmap, dy)
                line[f"T{block}"] = dict(
                    map_ms=timed(build, back, offsets, calls=a.calls),
                    sum_ms=timed(token_rows.moe_token_sum, out, tmap, gates,
                                 calls=a.calls),
                    dot_ms=timed(token_rows.moe_token_dot, out, tmap, dy,
                                 calls=a.calls),
                    sum_err=err(got, want), unit_err=err(got_bwd, want_bwd),
                    dot_err=err(got_dot, want_dot),
                    finite=bool(np.isfinite(np.asarray(
                        got, np.float32)).all() and np.isfinite(
                        np.asarray(got_dot)).all()),
                    fetched_rows=int(8 * np.asarray(tmap.chunks).sum()))
            print(json.dumps(line), flush=True)
            lines.append(line)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "token_rows_bench.json"),
              "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
