#!/usr/bin/env python
"""Compiled-HLO cost accounting for the headline ResNet train step.

XLA's own `cost_analysis()` on the compiled train step: FLOPs and HBM
bytes accessed per step — a static count, not a device measurement.
This is the tool behind PERF.md's 44.2 GB/step ResNet accounting
(an unverified hypothesis until a device trace confirms it) and the
fused-conv A/B target (fused <= 38 GB/step from 44.2).

  python benchmark/hlo_costs.py            # unfused NHWC resnet50
  MXTPU_BENCH_FUSED=1 python benchmark/hlo_costs.py

Prints one JSON line: {"fused": bool, "flops_T": .., "bytes_GB": ..,
"batch": N, "platform": ..}.  Runs on CPU too, but CPU byte counts are
not comparable to TPU's — the line names the platform it compiled for.

Since ISSUE 6 this is a thin CLI over `tools/costguard`: the step is
built by the same `resnet50_train_step` the committed budget golden
uses, and the numbers come from `TrainStep.cost_analysis()`'s
lower-only path — no step executes.
"""
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import jax

    from mxnet_tpu.config import setup_compile_cache
    from tools.costguard.entrypoints import resnet50_train_step

    jax.config.update("jax_default_matmul_precision", "bfloat16")
    setup_compile_cache()

    fused = bool(int(os.environ.get("MXTPU_BENCH_FUSED") or "0"))
    batch = int(os.environ.get("MXTPU_COST_BATCH") or "256")
    step, x, y = resnet50_train_step(batch=batch, fused=fused)
    costs = step.cost_analysis(x, y)   # AOT: lower+compile, zero steps
    print(json.dumps({
        "fused": fused,
        "batch": batch,
        "platform": jax.devices()[0].platform,
        "flops_T": round(costs.get("flops", float("nan")) / 1e12, 3),
        "bytes_GB": round(costs.get("bytes accessed", float("nan")) / 1e9,
                          2),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
