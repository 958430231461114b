"""The precision controls of ``kanana2_30b_a3b.train_s8192``: the cell run
through the benchmark's own harness (``chipbench/run.py``'s ``run_cell``,
so ``chipbench/kinds/train.py``'s ``loss_err`` check) with its
``parallel.TrainStep`` traced with parts that the configuration states as
float32 in bf16, while the harness's float32 reference stays as it is::

    python tests_tpu/kanana2_controls.py --controls router combined \\
        --seeds 9300000001 9300000013 [--seconds 1]

Each (control, seed) runs in turn in this one process; every result line
is printed and written to ``chiprun_out/kanana2_controls.json``.  The
controls (``CONTROLS``), each a set of parts:

- ``angles``: the rotary angles (``ops.rotary._ANGLE_DTYPE``);
- ``router``: the router's logits, sigmoid, bias and gates
  (``parallel.moe._ROUTER_DTYPE``);
- ``latent_norm``: the moments of each latent attention block's RMSNorm;
- ``norms``: the moments of every RMSNorm of the net;
- ``logits``: the head's float32 logits rounded to bf16;
- ``combined``: all of the above at once, every float32 part of the
  forward that the configuration states, but the softmax inside the flash
  kernels (the kernel takes no such switch).  The AdamW state and master
  weights reach the compared loss only through one step at lr 1e-5.

``tests_tpu/test_kanana2_tpu.py`` reads the same controls on the plain
reference (``precision``).  Needs the chip to itself."""
import argparse
import contextlib
import gc
import json
import os
import sys
import time

T0 = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from mxnet_tpu import parallel                              # noqa: E402
from mxnet_tpu.gluon.nn import RMSNorm                      # noqa: E402
from mxnet_tpu.ndarray import NDArray                       # noqa: E402
from mxnet_tpu.ops import rotary                            # noqa: E402
from mxnet_tpu.parallel import moe                          # noqa: E402

CELL = "kanana2_30b_a3b.train_s8192"
PARTS = ("angles", "router", "latent_norm", "norms", "logits")
CONTROLS = {"sound": (), "angles": ("angles",), "router": ("router",),
            "latent_norm": ("latent_norm",),
            "combined": ("angles", "router", "norms", "logits")}


def _bf16(v):
    """``v`` rounded to bf16 in its own dtype, whatever precision XLA keeps
    inside a fusion (it keeps a bf16 fusion's intermediates in float32:
    moments merely computed on bf16 operands read the sound net's numbers
    to the last digit on the chip)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _bf16_moments(eps):
    """An RMSNorm's ``hybrid_forward`` with its moments rounded to bf16
    where the net keeps them in float32: the squares, their mean and the
    inverse root."""
    def hybrid_forward(F, x, gamma):
        a = x._data
        ms = _bf16(jnp.mean(_bf16(jnp.square(a.astype(jnp.float32))),
                            axis=-1, keepdims=True))
        scale = _bf16(jax.lax.rsqrt(ms + eps))
        return NDArray(a * scale.astype(a.dtype) * gamma._data)
    return hybrid_forward


def _bf16_logits(forward):
    def rounded(ids):
        return NDArray(_bf16(forward(ids)._data))
    return rounded


def _norms(net, parts):
    if "norms" in parts:
        found = []
        net.apply(lambda b: found.append(b) if isinstance(b, RMSNorm)
                  else None)
        return found
    if "latent_norm" in parts:
        return [layer.mixer.kv_norm for layer in net.layers]
    return []


@contextlib.contextmanager
def precision(parts, net):
    """Trace what runs inside with ``parts`` (names of ``PARTS``) of ``net``
    in bf16; as the configuration states it when ``parts`` is empty."""
    unknown = set(parts) - set(PARTS)
    if unknown:
        raise ValueError(f"parts {sorted(unknown)}: none of {PARTS}")
    held = rotary._ANGLE_DTYPE, moe._ROUTER_DTYPE
    norms = _norms(net, parts)
    if "angles" in parts:
        rotary._ANGLE_DTYPE = jnp.bfloat16
    if "router" in parts:
        moe._ROUTER_DTYPE = jnp.bfloat16
    for norm in norms:
        norm.hybrid_forward = _bf16_moments(norm._eps)
    if "logits" in parts:
        net.forward = _bf16_logits(type(net).forward.__get__(net))
    try:
        yield
    finally:
        rotary._ANGLE_DTYPE, moe._ROUTER_DTYPE = held
        for norm in norms:
            del norm.hybrid_forward
        if "logits" in parts:
            del net.forward


def _step_class(parts):
    class ControlStep(parallel.TrainStep):
        """TrainStep traced under ``precision(parts)``; nothing else is."""

        def step(self, data, label):
            with precision(parts, self.net):
                return super().step(data, label)
    return ControlStep


def run(control, seed, seconds):
    """The harness's result line of ``CELL`` at ``seed`` under ``control``."""
    from chipbench import manifest
    from chipbench.run import run_cell
    m = manifest.load(ROOT)
    cell = manifest.cell(m, ROOT, CELL)
    held = parallel.TrainStep
    parallel.TrainStep = _step_class(CONTROLS[control])
    try:
        result = run_cell(cell, jax.devices()[:cell["chips"]], seed, seconds,
                          0, t0=time.perf_counter())
    finally:
        parallel.TrainStep = held
    gc.collect()
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--controls", nargs="+", choices=sorted(CONTROLS),
                    required=True)
    ap.add_argument("--seeds", nargs="+", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit(f"{CELL}'s controls need the TPU; jax found "
                 f"{jax.devices()[0].platform}")
    out = os.path.join(ROOT, "chiprun_out", "kanana2_controls.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    readings = {}
    for control in args.controls:
        for seed in args.seeds:
            result = run(control, seed, args.seconds)
            readings[f"{control}.seed{seed}"] = result
            print(json.dumps({"control": control, "seed": seed,
                              "loss_err": result["checks"]["loss_err"],
                              "correct": result["correct"]}), flush=True)
            with open(out, "w") as f:
                json.dump(readings, f, indent=1)
    print(f"kanana2 controls: {len(readings)} runs in "
          f"{time.perf_counter() - T0:.0f} s", flush=True)


if __name__ == "__main__":
    main()
