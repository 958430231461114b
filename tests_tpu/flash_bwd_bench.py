"""Per-call time of the flash backward at the decoder cells' attention
shapes: the fused kernel (``*_attention_bwd_dqkv``) against the split pair
(``*_bwd_dq`` then ``*_bwd_dkv``), each the module's jitted ``_flash_bwd``
on the same operands, and how far apart their dq, dk and dv lie.

    python tests_tpu/flash_bwd_bench.py [--cells kanana2 lfm2 ...] [--calls 20]
        [--out results.json]

Runs on the TPU only; prints one line a cell, and with ``--out`` writes them
all to that file as JSON."""
import argparse
import importlib
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from mxnet_tpu.context import on_tpu  # noqa: E402

fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

# (query heads, K/V heads, positions, Q/K head, V head, window) as each
# cell's step calls the kernels: batch and heads together, blocks of 512
CELLS = {
    "kanana2": (64, 64, 8192, 192, 128, None),
    "lfm2": (128, 32, 8192, 64, 64, None),
    "mellum2_full": (64, 8, 8192, 128, 128, None),
    "mellum2_window": (64, 8, 8192, 128, 128, 1024),
    "phi4_full": (40, 20, 4096, 64, 64, None),
    "phi4_window": (40, 20, 4096, 64, 64, 512)}
BLOCK = 512


def _timed(fn, args, calls):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(calls):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - start)
    return 1e3 * statistics.median(times)


def measure(cell, calls):
    heads, kv_heads, s, d, d_v, window = CELLS[cell]
    rng = np.random.RandomState(0)
    q, k = (jnp.asarray(rng.randn(n, s, d) * 0.5, jnp.bfloat16)
            for n in (heads, kv_heads))
    v = jnp.asarray(rng.randn(kv_heads, s, d_v) * 0.5, jnp.bfloat16)
    do = jnp.asarray(rng.randn(heads, s, d_v), jnp.bfloat16)
    seed = jnp.zeros((1,), jnp.int32)
    scale = 1.0 / d ** 0.5
    o, lse = fa._flash_fwd(q, k, v, seed, scale, True, BLOCK, BLOCK, False,
                           0.0, window)

    def backward(fused):
        return lambda *a: fa._flash_bwd(*a, scale, True, BLOCK, BLOCK, False,
                                        0.0, window, fused)
    args = (q, k, v, seed, o, lse, do)
    row = {"cell": cell, "shape": [heads, kv_heads, s, d, d_v, window],
           "takes_fused": fa.takes_fused(s, d, d_v, 2),
           "fused_vmem_mib": fa.fused_vmem_bytes(s, d, d_v, 2) / 2 ** 20}
    row["split_ms"] = _timed(backward(False), args, calls)
    row["fused_ms"] = _timed(backward(True), args, calls)
    row["fused_over_split"] = row["fused_ms"] / row["split_ms"]
    split, fused = backward(False)(*args), backward(True)(*args)
    for name, a, b in zip(("dq", "dk", "dv"), fused, split):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        row[f"{name}_rel_to_split"] = float(
            np.linalg.norm(a - b) / np.linalg.norm(b))
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", nargs="+", default=list(CELLS),
                    choices=list(CELLS))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--out", help="write the rows here as JSON")
    args = ap.parse_args()
    if not on_tpu():
        sys.exit("flash_bwd_bench: the TPU backend is needed")
    rows = []
    for cell in args.cells:
        rows.append(measure(cell, args.calls))
        print(json.dumps(rows[-1]), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": str(jax.devices()[0].device_kind),
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
