#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two hot paths once, through the constructors a user calls, at a
realistic width, with seeded random weights:

1. trainer — ``resnet50_v1(layout="NHWC")`` in bf16, global batch 256 at
   224x224, ``parallel.TrainStep`` with SGD momentum over
   ``make_mesh(dp=len(jax.devices()))``: one fixed batch, a few steps.
2. server — ``serving.GenerationServer`` (vocab 4096,
   4 layers, 8 heads x 64, d_ff 2048, 64 slots, 512 pages x 64, buckets
   (1,2,4)x(32,64), ``attention_impl=None``): warmup, a few requests, drain;
   then two checks that the kernel is the kernel.

One process, no children (a chip belongs to one process).  On a host with
several chips the same two phases run data-parallel / tensor-parallel over
all of them and additionally check that the work really spans the devices.

Refuses to start unless ``jax.devices()[0].platform == "tpu"``.  Any failed
check or exception exits non-zero and prints no result line.  On success the
last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Timings it prints are smoke readings for the builder, not benchmark results.
"""
import json
import os
import sys
import time

# trainer: the resnet50_v1.train_b256 cell's model and batch
BATCH, IMAGE, CLASSES = 256, 224, 1000
TRAIN_STEPS = 6
# lr 0.1 overshoots on the first steps of a fixed random batch;
# the smoke checks that the loss falls, so it steps gently
TRAIN_LR = 0.01
# dp=N vs dp=1 first-step loss, bf16 activations: reduction order only
DP_LOSS_RTOL = 2e-2

# server
LM = dict(vocab_size=4096, n_layers=4, n_heads=8, head_dim=64, d_ff=2048)
N_SLOTS, N_PAGES, PAGE_SIZE, MAX_NEW = 64, 512, 64, 64
BUCKET_BATCH, BUCKET_LENGTH = (1, 2, 4), (32, 64)
PROMPT_LENGTHS = (3, 17, 32, 33, 47, 60)
# Pallas kernel vs the jnp gather (reference at "highest" matmul precision)
# on float32 pools; a wrong page or mask is an O(1) error
PAGED_ATOL = 2e-2

_FAILED = []


def check(name, ok, detail=""):
    """Record one check; the script fails at the end if any failed, so one
    chip run reports every check, not just the first to break."""
    print(f"  [{'ok' if ok else 'FAIL'}] {name}" +
          (f": {detail}" if detail else ""), flush=True)
    if not ok:
        _FAILED.append(name)


def train_phase(devices):
    """ResNet-50 TrainStep over ``dp=len(devices)``.  Returns the per-step
    losses."""
    import ml_dtypes
    import numpy as np

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    n = len(devices)
    mx.random.seed(0)
    net = resnet50_v1(layout="NHWC", classes=CLASSES)
    net.initialize()
    net.cast("bfloat16")  # bf16 compute, fp32 master weights in the optimizer
    mesh = parallel.make_mesh(dp=n, devices=devices)
    opt = mx.optimizer.create("sgd", learning_rate=TRAIN_LR, momentum=0.9,
                              wd=1e-4)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
                              mesh=mesh)

    rng = np.random.RandomState(0)
    # place the batch the way a DevicePrefetcher does (host bf16 straight
    # onto the mesh), so its spread over the devices can be looked at; the
    # step takes a placed leaf as it is
    x = jax.device_put(rng.randn(BATCH, IMAGE, IMAGE, 3).astype(np.float32)
                       .astype(ml_dtypes.bfloat16), step.data_sharding)
    y = jax.device_put(rng.randint(0, CLASSES, (BATCH,)).astype(np.int32),
                       step.data_sharding)

    t0 = time.perf_counter()
    losses = [float(step(x, y).asnumpy())]       # build + compile + step 1
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(TRAIN_STEPS - 1):
        losses.append(float(step(x, y).asnumpy()))
    step_ms = (time.perf_counter() - t0) / (TRAIN_STEPS - 1) * 1e3
    print(f"  dp={n}: first step (build + compile) {compile_s:.1f} s; "
          f"steady step {step_ms:.1f} ms "
          f"({BATCH / step_ms * 1e3 / n:.0f} img/s/chip, host-synced every "
          f"step); losses {[round(v, 4) for v in losses]}", flush=True)

    check(f"dp={n} loss finite at every step",
          all(np.isfinite(v) for v in losses), str(losses))
    check(f"dp={n} loss lower at the last step than the first",
          losses[-1] < losses[0], f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    if n > 1:
        spans = [len(a.sharding.device_set)
                 for a in [x, y, *step.params.values()]]
        check(f"batch and every parameter span {n} devices",
              all(s == n for s in spans), f"min span {min(spans)}")
        shard = x.addressable_shards[0].data.shape
        check("batch is split, not replicated",
              shard[0] == BATCH // n, f"per-device shard {shard}")
        stats = [d.memory_stats()["bytes_in_use"] for d in devices]
        check("per-device bytes_in_use roughly equal, not all on chip 0",
              min(stats) > 0.5 * max(stats),
              f"{[round(b / 2**20) for b in stats]} MiB")
    return losses


def serve_phase(devices):
    """GenerationServer at the bench shape, tensor-parallel over
    ``devices`` when there are several."""
    import numpy as np

    import jax
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)
    from mxnet_tpu.serving import BucketSpec, GenerationServer

    n = len(devices)
    cfg = CausalLMConfig(**LM)
    params = init_causal_lm(cfg, seed=0)
    srv = GenerationServer(
        params, cfg,
        buckets=BucketSpec(batch=BUCKET_BATCH, length=BUCKET_LENGTH),
        n_slots=N_SLOTS, n_pages=N_PAGES, page_size=PAGE_SIZE,
        max_new_tokens=MAX_NEW, seed=0, tp_shards=n, name="ChipSmoke")
    t0 = time.perf_counter()
    srv.start()                       # warmup compiles the whole census
    compile_s = time.perf_counter() - t0

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=L).astype(np.int32)
               for L in PROMPT_LENGTHS]
    # two copies of one sampled request ride along: same prompt, same seed.
    # All eight are in flight together, so the copies sit in different rows
    # of the prefill buckets and the slot grid
    t0 = time.perf_counter()
    reqs = [srv.submit(p) for p in prompts]
    reqs += [srv.submit(prompts[1], temperature=0.8, top_k=40, seed=1234)
             for _ in range(2)]
    outs = [np.asarray(r.result(timeout=600)) for r in reqs]
    dt = time.perf_counter() - t0
    n_tok = sum(len(o) for o in outs)
    print(f"  tp={n}: start() with warmup {compile_s:.1f} s "
          f"(census {srv.census()}); {len(outs)} requests, {n_tok} tokens "
          f"in {dt:.2f} s ({n_tok / dt:.0f} tokens/s, mostly-empty slot "
          f"grid)", flush=True)

    check("every request returns max_new_tokens tokens",
          all(len(o) == MAX_NEW for o in outs), str([len(o) for o in outs]))
    check("every token is in the vocabulary",
          all(((o >= 0) & (o < cfg.vocab_size)).all() for o in outs))
    check("same prompt and seed twice gives the same tokens",
          np.array_equal(outs[-1], outs[-2]),
          f"{outs[-1].tolist()} vs {outs[-2].tolist()}"
          if not np.array_equal(outs[-1], outs[-2]) else "")
    check("jit_cache_count() == census()",
          srv.jit_cache_count() == srv.census(),
          f"{srv.jit_cache_count()} vs {srv.census()}")

    # the served decode program, not a rebuilt one: one Mosaic custom call
    # per layer means neither the interpreter nor the jnp gather
    n_kernels = srv.lower_decode().as_text().count("tpu_custom_call")
    check("decode program holds n_layers tpu_custom_calls",
          n_kernels == cfg.n_layers, f"{n_kernels} vs {cfg.n_layers}")
    if n > 1:
        pool_shape = (cfg.n_layers, N_PAGES, PAGE_SIZE, cfg.n_heads,
                      cfg.head_dim)
        pools = [a for a in jax.live_arrays() if a.shape == pool_shape]
        check(f"both K/V pools span {n} devices, split by head",
              len(pools) == 2 and all(
                  len(p.sharding.device_set) == n
                  and p.addressable_shards[0].data.shape[3]
                  == cfg.n_heads // n for p in pools),
              f"{len(pools)} pools")
    check("drain() returned with the loop stopped", srv.drain(timeout=120))
    return cfg, srv.pages_per_seq


def paged_kernel_phase(cfg, pages_per_seq):
    """``paged_decode_attention(impl="pallas")`` against ``impl="jnp"`` on
    the server's pool shape with ragged lengths.  Warmup and the requests
    above never check the kernel's arithmetic: warmup runs with every slot
    inactive, and greedy tokens from near-flat random logits cannot be
    compared across implementations."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.paged_attention import paged_decode_attention

    rng = np.random.RandomState(1)
    pool = (N_PAGES, PAGE_SIZE, cfg.n_heads, cfg.head_dim)
    q = jnp.asarray(rng.randn(N_SLOTS, cfg.n_heads, cfg.head_dim)
                    .astype(np.float32))
    kp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    vp = jnp.asarray(rng.randn(*pool).astype(np.float32))
    full = pages_per_seq * PAGE_SIZE
    lengths = rng.randint(1, full + 1, size=N_SLOTS).astype(np.int32)
    # an inactive slot, single tokens, page boundaries, a full table
    lengths[:7] = [0, 1, PAGE_SIZE - 1, PAGE_SIZE, PAGE_SIZE + 1,
                   full - 1, full]
    # distinct pages per slot; page 0 is the allocator's sink, never mapped
    tables = rng.permutation(np.arange(1, N_PAGES))[
        :N_SLOTS * pages_per_seq].reshape(N_SLOTS, pages_per_seq) \
        .astype(np.int32)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(lengths))

    kernel = jax.jit(lambda *a: paged_decode_attention(*a, impl="pallas"))
    n_kernels = kernel.lower(*args).as_text().count("tpu_custom_call")
    check("impl='pallas' lowers to one tpu_custom_call", n_kernels == 1,
          str(n_kernels))
    out = np.asarray(kernel(*args))
    gather = jax.jit(lambda *a: paged_decode_attention(*a, impl="jnp"))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(gather(*args))
    live = lengths > 0
    err = float(np.abs(out[live] - ref[live]).max())
    check(f"Pallas vs jnp paged attention within atol {PAGED_ATOL}",
          bool(np.isfinite(out).all()) and err <= PAGED_ATOL,
          f"max abs err {err:.3e} over {int(live.sum())} ragged slots")
    check("a length-0 slot's output row is zeros",
          bool((out[~live] == 0.0).all()))


def main():
    import jax
    import jaxlib

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU: jax {jax.__version__} found "
                 f"platform {dev.platform!r} ({dev.device_kind})")
    devices = jax.devices()
    n = len(devices)

    import mxnet_tpu as mx
    from mxnet_tpu.config import setup_compile_cache

    # the bench's precision: bf16 MXU passes for the trainer and the server
    jax.config.update("jax_default_matmul_precision", "bfloat16")
    cache_dir = setup_compile_cache()
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}; "
          f"{n} x {dev.device_kind} ({dev.platform})")
    print(f"devices in enumeration order: "
          f"{[(d.id, d.coords) for d in devices]}")
    print(f"compile cache: {cache_dir} ({n_cached} entries at start)")
    print(f"features: {mx.runtime.Features()}", flush=True)

    t_all = time.perf_counter()
    print("phase 1: trainer", flush=True)
    losses = train_phase(devices)
    if n > 1:
        ref_losses = train_phase(devices[:1])
        rel = abs(losses[0] - ref_losses[0]) / abs(ref_losses[0])
        check(f"dp={n} first-step loss matches dp=1 within "
              f"rtol {DP_LOSS_RTOL}", rel <= DP_LOSS_RTOL,
              f"{losses[0]:.5f} vs {ref_losses[0]:.5f} (rel {rel:.2e})")

    print("phase 2: server", flush=True)
    cfg, pages_per_seq = serve_phase(devices)
    paged_kernel_phase(cfg, pages_per_seq)
    print(f"total {time.perf_counter() - t_all:.1f} s", flush=True)

    if _FAILED:
        sys.exit(f"chip_smoke.py: {len(_FAILED)} check(s) failed: "
                 f"{_FAILED}")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind, "count": n}}))


if __name__ == "__main__":
    main()
