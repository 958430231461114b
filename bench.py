#!/usr/bin/env python
"""Headline benchmarks (one JSON line each, driver contract: default = ResNet).

  python bench.py           # ResNet-50 v1 train throughput, img/s/chip
  python bench.py bert      # BERT-base seq-128 masked-LM pretrain, tokens/s/chip
  python bench.py lstm      # 2x650 LSTM LM train (PTB recipe), tokens/s/chip
  python bench.py ssd       # SSD-512 ResNet-50 train, img/s/chip
  python bench.py all       # every config (one JSON line each)

ref: example/image-classification/benchmark_score.py (synthetic-data img/s),
gluonnlp scripts/bert/run_pretraining.py (masked-LM+NSP step),
example/gluon/word_language_model (PTB LSTM), GluonCV train_ssd.py —
BASELINE.md configs 2-5.  The whole train step (fwd+bwd+optimizer) is one XLA
program via parallel.TrainStep; matmul precision bf16 puts the FLOPs on the MXU.

Runs on the TPU only, in this one process (a chip belongs to one process):
no accelerator, or any exception in a bench, is a non-zero exit.
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 800.0     # BASELINE.md: V100 fp16 ~700-800 img/s, target bar
BASELINE_TOK_S = 3000.0    # BASELINE.md: BERT-base >=3k tokens/s/chip bar
BASELINE_LSTM_TOK_S = 30000.0  # BASELINE.md config 3: V100 cuDNN-RNN "order";
                               # ~20-40k wps for the 2x650 PTB medium recipe
BASELINE_SSD_IMG_S = 40.0  # BASELINE.md config 5: >=40 img/s/chip train bar

_METRIC_NAMES = {"resnet": "resnet50_train_throughput",
                 "bert": "bert_base_pretrain_throughput",
                 "lstm": "lstm_lm_train_throughput",
                 "ssd": "ssd512_train_throughput",
                 "llm": "llm_decode_throughput"}


def _quant_mode():
    """MXTPU_BENCH_QUANT={off,bf16,int8}: the ``grad_reduce`` wire
    format for every bench TrainStep (ISSUE 8 A/B knob).  The chosen
    mode rides in the BENCH JSON line next to the cost fields, so the
    perf trajectory records what was measured."""
    v = os.environ.get("MXTPU_BENCH_QUANT", "off").lower()
    if v in ("", "off", "0", "f32"):
        return "f32"
    if v not in ("bf16", "int8"):
        print(f"MXTPU_BENCH_QUANT={v!r} (expected off|bf16|int8)",
              file=sys.stderr)
        sys.exit(1)
    return v


def _tp_mode():
    """MXTPU_BENCH_TP={off,N,N:f32,N:int8}: tensor-parallel shards (and
    the decode-collective wire format) for the LLM bench's
    ``GenerationServer`` (ISSUE 14 A/B knob).  ``N`` must divide the
    bench model's head count and d_ff — the server validates loudly.
    The chosen mode rides in the BENCH JSON line (``tp_shards`` /
    ``tp_collectives``) next to the per-device cost fields, so the perf
    trajectory records what was measured."""
    v = os.environ.get("MXTPU_BENCH_TP", "").strip().lower()
    if v in ("", "off", "0", "1"):
        return 1, "f32"
    shards, _, coll = v.partition(":")
    coll = coll or "f32"
    # tp_shards=1 builds mesh-free with NO collectives at all, so a
    # "1:int8" (or "0:...") line would record a mode that never ran —
    # the trajectory must say what was measured
    if not shards.isdigit() or coll not in ("f32", "int8") \
            or int(shards) < (1 if coll == "f32" else 2):
        print(f"MXTPU_BENCH_TP={v!r} (expected N or N:f32|N:int8, "
              f"N >= 2 for int8)", file=sys.stderr)
        sys.exit(1)
    return (int(shards), coll) if int(shards) > 1 else (1, "f32")


def _cost_fields(step):
    """costguard report fields for a bench's JSON line: the static
    accounting (tools/costguard; PERF.md methodology) rides next to the
    measured throughput in every BENCH artifact.  cost_analysis() is an
    AOT recompile of the already-run step — cached per signature, warm
    via the persistent compile cache.  MXTPU_BENCH_COSTS=0 disables."""
    if os.environ.get("MXTPU_BENCH_COSTS", "1").lower() in ("0", "false"):
        return {}
    costs = step.cost_analysis()
    return {
        "grad_reduce": getattr(step, "_grad_reduce", "f32"),
        "flops_T": round(costs.get("flops", 0.0) / 1e12, 3),
        "bytes_GB": round(costs.get("bytes accessed", 0.0) / 1e9, 2),
        "n_executables": int(step._jit._cache_size()),
    }


def _hlo_fields(src):
    """Structural-HLO columns for a BENCH line (ISSUE 18):
    ``donation_coverage`` (donated / donation-candidate entry params —
    1.0 means every large float param that matches an output is
    actually aliased) and ``collectives_n`` (collective op count),
    computed by tools/hloguard's facts extractor over the SAME lowered
    program the throughput came from — the structural numbers the
    tier-1 hloguard gate pins, riding next to the measurement they
    explain.  ``src`` is a TrainStep (lowered via ``.lower()``), an
    already-lowered jax object, or raw module text.
    ``MXTPU_BENCH_HLO=0`` opts out."""
    if os.environ.get("MXTPU_BENCH_HLO", "1").lower() in ("0", "false"):
        return {}
    from tools.hloguard.rules import entry_census, extract_facts
    text = src if isinstance(src, str) else (
        src.as_text() if hasattr(src, "as_text")
        else src.lower().as_text())
    census = entry_census({"bench": extract_facts(text)})
    d = census["donation"]
    cov = (round(d["donated"] / d["candidates"], 3)
           if d["candidates"] else 1.0)
    return {"donation_coverage": cov,
            "collectives_n": census["collectives"]["total"]}


def _trace_on(sample=1.0):
    """Arm the request tracer for a bench (ISSUE 13).  Returns True
    when armed.  ``sample=0.0`` arms ONLY the compile-event stream
    (ISSUE 15) — the training benches use it so the measured loop pays
    no span allocation while the BENCH line still gets its
    ``compile_ms_total``/``compile_cache_hits`` columns.
    ``MXTPU_BENCH_TRACE=0`` opts out."""
    if os.environ.get("MXTPU_BENCH_TRACE", "1").lower() in ("0", "false"):
        return False
    from mxnet_tpu import telemetry
    telemetry.enable(sample=sample)
    return True


def _trace_fields(server_name,
                  phases=("queue", "prefill", "handoff", "decode",
                          "coalesce", "step")):
    """Per-phase latency breakdown for a serving bench's JSON line:
    p50/p99 (ms) of the request tracer's span-duration histograms
    (``<server>::<phase>_ms``), measured on the SAME traffic the
    throughput number comes from — where the time went, not just how
    much there was.  Keys are stable (``<phase>_ms_p50``/``_p99``);
    phases the serving path never entered (e.g. ``handoff`` on a fused
    decode server) report null.  Disarms the tracer on the way out."""
    from mxnet_tpu import telemetry
    fields = {}
    try:
        hists = telemetry.registry().snapshot(
            prefix=f"{server_name}::")["histograms"]
        for phase in phases:
            snap = hists.get(f"{phase}_ms")
            for q, tag in ((0.50, "p50"), (0.99, "p99")):
                v = None if snap is None \
                    else telemetry.histogram_quantile(snap, q)
                fields[f"{phase}_ms_{tag}"] = None if v is None \
                    else round(v, 3)
    finally:
        telemetry.disable()  # a later bench must not run traced
    return fields


def _compile_fields():
    """Compile-event-stream columns for a BENCH line (ISSUE 15):
    ``compile_ms_total`` (wall-ms spent creating executables),
    ``compile_cache_hits`` (dispatches the jit caches absorbed), and
    ``recompiles_unexpected`` (post-warmup misses — the number that must
    be zero or the measured throughput was paid for with compile
    stalls).  Honors the ``MXTPU_BENCH_TRACE=0`` opt-out; disarms the
    tracer on the way out so a later bench never runs traced."""
    if os.environ.get("MXTPU_BENCH_TRACE", "1").lower() in ("0", "false"):
        return {}
    from mxnet_tpu import telemetry
    try:
        cs = telemetry.compile_stats()
        return {"compile_ms_total": round(cs["ms_total"], 1),
                "compile_cache_hits": cs["hits"],
                "recompiles_unexpected": cs["unexpected"]}
    finally:
        telemetry.disable()


def _ckpt_fields(step):
    """Snapshot-stall columns for a training BENCH line (ISSUE 17):
    ``ckpt_sync_ms`` — wall time of one synchronous ``save_train_step``
    (fetch + serialize + fsync + commit) on the bench's real payload —
    and ``ckpt_stall_ms`` — what the step loop actually pays per
    snapshot on the async pipeline (device→host fetch only).  The ratio
    is the async win the tier-1 stall test bounds.  Writes to a temp
    dir; ``MXTPU_BENCH_CKPT=0`` opts out."""
    if os.environ.get("MXTPU_BENCH_CKPT", "1").lower() in ("0", "false"):
        return {}
    import shutil
    import tempfile
    fields = {}
    d = tempfile.mkdtemp(prefix="mxtpu_bench_ckpt_")
    try:
        from mxnet_tpu.parallel import checkpoint as _ck
        t0 = time.perf_counter()
        _ck.save_train_step(step, os.path.join(d, "ckpt-00000001.npz"))
        fields["ckpt_sync_ms"] = round((time.perf_counter() - t0) * 1e3, 2)
        snap = _ck.AsyncSnapshotter()
        try:
            t0 = time.perf_counter()
            snap.save(step, os.path.join(d, "ckpt-00000002.npz"))
            fields["ckpt_stall_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            snap.wait_until_finished(timeout=120.0)
        finally:
            snap.close(timeout=120.0)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return fields


def _setup():
    """Every bench starts here: refuse anything but the TPU (a CPU run
    must never print a number under a device metric's name), then the
    shared persistent compile cache."""
    import jax

    from mxnet_tpu.config import setup_compile_cache
    from mxnet_tpu.context import on_tpu

    if not on_tpu():
        sys.exit(f"bench.py measures the TPU; jax found platform "
                 f"{jax.devices()[0].platform!r} "
                 f"({jax.devices()[0].device_kind}) — rehearse the code "
                 f"paths with the tier-1 tests instead")
    jax.config.update("jax_default_matmul_precision", "bfloat16")
    setup_compile_cache()
    return jax


def bench_resnet():
    jax = _setup()
    _trace_on(sample=0.0)   # compile-event stream only (ISSUE 15)

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    # MXTPU_BENCH_BATCH: A/B knob for batch-size sweeps (throughput is
    # reported per-image so runs are comparable)
    batch = int(os.environ.get("MXTPU_BENCH_BATCH") or 256)
    iters = 20
    # MXTPU_BENCH_FEED=prefetch: feed fresh HOST batches through
    # parallel.DevicePrefetcher (async H2D + donated inputs) instead of the
    # default device-resident tensors — measures the full input pipeline,
    # not just the step.
    feed = os.environ.get("MXTPU_BENCH_FEED", "device")

    # channel-last: the TPU-native layout (features on lanes; see PERF.md).
    # MXTPU_BENCH_FUSED=1 swaps in the Pallas fused norm-relu-conv blocks
    # (A/B knob while the fused path earns its keep on-chip).
    fused = bool(int(os.environ.get("MXTPU_BENCH_FUSED") or "0"))
    net = resnet50_v1(layout="NHWC", fused=fused)
    net.initialize()
    net.cast("bfloat16")  # bf16 compute, fp32 master weights in the optimizer
    mesh = parallel.make_mesh(dp=len(jax.devices()))
    opt = mx.optimizer.create("sgd", learning_rate=0.1, momentum=0.9, wd=1e-4)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(), opt,
                              mesh=mesh, donate_batch=(feed == "prefetch"),
                              grad_reduce=_quant_mode())

    rng = np.random.RandomState(0)
    xh = rng.randn(batch, 224, 224, 3).astype(np.float32)
    yh = rng.randint(0, 1000, (batch,)).astype(np.int32)

    if feed == "prefetch":
        import ml_dtypes
        # keep the batch a HOST numpy array (bf16 via ml_dtypes): every
        # yield then pays the real H2D transfer the pipeline must overlap
        xh16 = xh.astype(ml_dtypes.bfloat16)

        def host_batches(n):
            for _ in range(n):
                yield (xh16, yh)

        # compile + warmup through the same placed path
        for d, l in parallel.DevicePrefetcher(host_batches(2), step=step):
            step(d, l).asnumpy()
        t0 = time.perf_counter()
        with parallel.DevicePrefetcher(host_batches(iters), step=step,
                                       depth=2) as src:
            for d, l in src:
                loss = step(d, l)
        loss.asnumpy()  # block
        dt = time.perf_counter() - t0
    else:
        x = mx.nd.array(xh).astype("bfloat16")
        y = mx.nd.array(yh)

        # compile + warmup
        step(x, y).asnumpy()
        step(x, y).asnumpy()

        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(x, y)
        loss.asnumpy()  # block
        dt = time.perf_counter() - t0

    # global batch is data-parallel over every device: report PER-CHIP rate
    img_s = batch * iters / dt / len(jax.devices())
    print(json.dumps({
        "metric": _METRIC_NAMES["resnet"],
        "value": round(img_s, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 4),
        **_cost_fields(step),
        **_hlo_fields(step),
        **_ckpt_fields(step),
        **_compile_fields(),
    }))


def bench_bert():
    """BERT-base (L12 H768 A12, vocab 30522) masked-LM + NSP pretraining step,
    seq 128, ~15% masked (20 positions), LAMB — the reference's phase-1 recipe
    (ref: gluonnlp scripts/bert/run_pretraining.py)."""
    jax = _setup()
    _trace_on(sample=0.0)   # compile-event stream only (ISSUE 15)

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.bert import BERTModel, BERTPretrainLoss

    batch = 64
    seq_len, n_pred, vocab = 128, 20, 30522
    iters = 20

    net = BERTModel(vocab_size=vocab, units=768, hidden_size=3072,
                    num_layers=12, num_heads=12, max_length=512, dropout=0.1)
    net.initialize()
    net.cast("bfloat16")
    loss_blk = BERTPretrainLoss()

    def loss_fn(out, labels):
        nsp_scores, mlm_scores = out[2], out[3]
        mlm_labels, mlm_weights, nsp_labels = labels
        return loss_blk(mlm_scores, nsp_scores, mlm_labels, mlm_weights,
                        nsp_labels)

    mesh = parallel.make_mesh(dp=len(jax.devices()))
    opt = mx.optimizer.create("lamb", learning_rate=1e-3, wd=0.01)
    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh,
                              grad_reduce=_quant_mode())

    rng = np.random.RandomState(0)
    tok = mx.nd.array(rng.randint(0, vocab, (batch, seq_len)).astype(np.int32))
    tt = mx.nd.array(rng.randint(0, 2, (batch, seq_len)).astype(np.int32))
    vl = mx.nd.array(np.full((batch,), seq_len, np.int32))
    mpos = mx.nd.array(rng.randint(0, seq_len, (batch, n_pred)).astype(np.int32))
    mlab = mx.nd.array(rng.randint(0, vocab, (batch, n_pred)).astype(np.int32))
    mw = mx.nd.array(np.ones((batch, n_pred), np.float32))
    nsp = mx.nd.array(rng.randint(0, 2, (batch,)).astype(np.int32))

    x = (tok, tt, vl, mpos)
    labels = (mlab, mw, nsp)
    step(x, labels).asnumpy()
    step(x, labels).asnumpy()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, labels)
    loss.asnumpy()
    dt = time.perf_counter() - t0

    # global batch is data-parallel over every device: report PER-CHIP rate
    tok_s = batch * seq_len * iters / dt / len(jax.devices())
    print(json.dumps({
        "metric": _METRIC_NAMES["bert"],
        "value": round(tok_s, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_s / BASELINE_TOK_S, 4),
        **_cost_fields(step),
        **_hlo_fields(step),
        **_ckpt_fields(step),
        **_compile_fields(),
    }))


def bench_lstm():
    """PTB-medium LSTM LM (2 layers x 650, embed 650, vocab 10k, bptt 35) —
    the reference's word_language_model recipe over the fused lax.scan RNN op
    (ref: src/operator/rnn.cc cuDNN path; BASELINE config 3)."""
    jax = _setup()
    _trace_on(sample=0.0)   # compile-event stream only (ISSUE 15)

    import mxnet_tpu as mx
    from mxnet_tpu import parallel, gluon
    from mxnet_tpu.gluon.model_zoo.language_model import rnn_lm
    from jax.sharding import PartitionSpec

    batch = 64 * len(jax.devices())
    bptt, vocab = 35, 10000
    iters = 20

    net = rnn_lm(vocab_size=vocab, embed_size=650, hidden_size=650,
                 num_layers=2, dropout=0.5)
    net.initialize()
    net.cast("bfloat16")
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def loss_fn(out, label):
        return ce(out.reshape((-1, vocab)), label.reshape((-1,)))

    mesh = parallel.make_mesh(dp=len(jax.devices()))
    opt = mx.optimizer.create("sgd", learning_rate=20.0 / batch)
    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh,
                              data_spec=PartitionSpec(None, "dp"),
                              grad_reduce=_quant_mode())

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randint(0, vocab, (bptt, batch)).astype(np.int32))
    y = mx.nd.array(rng.randint(0, vocab, (bptt, batch)).astype(np.int32))
    step(x, y).asnumpy()
    step(x, y).asnumpy()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, y)
    loss.asnumpy()
    dt = time.perf_counter() - t0

    tok_s = batch * bptt * iters / dt / len(jax.devices())
    print(json.dumps({
        "metric": _METRIC_NAMES["lstm"],
        "value": round(tok_s, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(tok_s / BASELINE_LSTM_TOK_S, 4),
        **_cost_fields(step),
        **_hlo_fields(step),
        **_ckpt_fields(step),
        **_compile_fields(),
    }))


BASELINE_LLM_TOK_S = 1000.0   # decode tokens/s/chip order for a tiny LM;
                              # the interesting columns are occupancy + the
                              # paged-vs-dense cost fields, not this bar


def bench_llm():
    """Continuous-batching decode throughput (ISSUE 10): a
    ``GenerationServer`` over the paged KV cache under saturating
    mixed-length traffic.  Emits decode tokens/s/chip, mean in-flight
    slot occupancy, and the costguard fields of THE decode executable
    (one program serves every traffic mix — ``n_executables`` in the
    line is the full serving census: prefill grid + 1).  Selected by
    ``python bench.py llm`` or ``MXTPU_BENCH_LLM=1`` (which also adds
    it to ``all``).  ``MXTPU_BENCH_TP=N[:f32|:int8]`` serves through a
    tensor-parallel N-way server (ISSUE 14) — the JSON line then adds
    ``per_device_bytes_GB``/``per_device_collective_KB`` from
    costguard's per-device section next to ``tp_shards``/
    ``tp_collectives``.  ``MXTPU_BENCH_PREFIX=1`` switches traffic to
    the 90%-shared-prefix shape (ISSUE 16): every request repeats one
    common system prompt plus a short random tail, so CoW prefix
    sharing carries the load — the line then adds ``page_bytes_per_seq``
    (pool bytes actually CHARGED per sequence), ``pages_shared_mapped``
    and ``cow_faults``."""
    jax = _setup()

    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)
    from mxnet_tpu.serving import BucketSpec, GenerationServer

    cfg = CausalLMConfig(vocab_size=4096, n_layers=4, n_heads=8,
                         head_dim=64, d_ff=2048)
    n_slots = 64
    n_pages, page_size = 512, 64
    max_new = 64
    n_requests = 256
    tp_shards, tp_collectives = _tp_mode()
    params = init_causal_lm(cfg, seed=0)
    traced = _trace_on()    # per-phase latency breakdown (ISSUE 13)
    srv = GenerationServer(
        params, cfg, buckets=BucketSpec(batch=(1, 2, 4), length=(32, 64)),
        n_slots=n_slots, n_pages=n_pages, page_size=page_size,
        max_new_tokens=max_new, max_queue=n_requests, seed=0,
        tp_shards=tp_shards, tp_collectives=tp_collectives,
        name="BenchGen")
    srv.start()                       # warmup compiles the whole census

    prefix_mode = os.environ.get("MXTPU_BENCH_PREFIX", "").lower() \
        not in ("", "0", "false")
    rng = np.random.RandomState(0)
    if prefix_mode:
        # one system prompt shared by EVERY request: 90% of a fixed
        # prompt length, covering whole pages so the prefix index can
        # map them (the 10% tail is per-request random)
        plen = page_size * 5 // 2                     # 160
        shared = rng.randint(0, cfg.vocab_size,
                             size=int(plen * 0.9)).astype(np.int32)

        def make_prompt():
            tail = rng.randint(0, cfg.vocab_size,
                               size=plen - len(shared)).astype(np.int32)
            return np.concatenate([shared, tail])
    else:
        def make_prompt():
            return rng.randint(0, cfg.vocab_size,
                               size=int(rng.randint(4, 60))) \
                .astype(np.int32)
    occupancy = []
    stop = [False]

    def sampler():
        while not stop[0]:
            # active_slots = sequences actually SEATED in the decode
            # grid (in_flight would also count the queue and read ~100%
            # whenever one exists — useless for slot-packing)
            occupancy.append(srv.healthz()["active_slots"])
            time.sleep(0.01)

    import threading
    t = threading.Thread(target=sampler, daemon=True)
    t.start()
    try:
        try:
            t0 = time.perf_counter()
            reqs = [srv.submit(make_prompt()) for _ in range(n_requests)]
            for r in reqs:
                r.result(timeout=600)
            dt = time.perf_counter() - t0
        finally:
            stop[0] = True     # sampler exit condition, then join below
    finally:
        t.join()
    st = srv.stats
    census, jit_count = srv.census(), srv.jit_cache_count()
    # THE decode program as the server dispatches it (lower-only — no
    # compile): feeds both the cost column and the structural-HLO one
    lowered = srv.lower_decode()
    srv.drain()
    trace_fields = _trace_fields("BenchGen") if traced else {}

    fields = {}
    hlo_fields = _hlo_fields(lowered)
    if os.environ.get("MXTPU_BENCH_COSTS", "1").lower() \
            not in ("0", "false"):
        # the compile is the expensive half — cost column only
        from tools.costguard.report import unit_report
        rep = unit_report(lowered.compile(),
                          n_args=len(jax.tree.leaves(params)) + 11)
        pd = rep.get("per_device", {})
        fields = {
            "flops_T": round(rep.get("flops", 0.0) / 1e12, 6),
            "bytes_GB": round(rep.get("bytes_accessed", 0.0) / 1e9, 4),
            "per_device_bytes_GB":
                round(pd["argument_bytes"] / 1e9, 4)
                if "argument_bytes" in pd else None,
            "per_device_collective_KB":
                round(pd.get("collective_bytes", 0.0) / 1e3, 3),
        }
    prefix_fields = {}
    if prefix_mode:
        page_bytes = (2 * cfg.n_layers * page_size * cfg.n_heads
                      * cfg.head_dim * 4)
        prefix_fields = {
            "prefix_shared_frac": 0.9,
            "page_bytes_per_seq": round(
                st["pages_charged"] * page_bytes
                / max(st["completed"], 1)),
            "pages_shared_mapped": st["pages_shared_mapped"],
            "cow_faults": st["cow_faults"],
        }
    tok_s = st["tokens_out"] / dt / len(jax.devices())
    print(json.dumps({
        "metric": _METRIC_NAMES["llm"],
        "value": round(tok_s, 2),
        "unit": "decode tokens/s/chip",
        "vs_baseline": round(tok_s / BASELINE_LLM_TOK_S, 4),
        "occupancy_pct": round(100 * float(np.mean(occupancy))
                               / n_slots, 1) if occupancy else None,
        "sequences": st["completed"],
        "preempted": st["preempted"],
        "tokens_salvaged": st.get("tokens_salvaged", 0),
        "resumes": st.get("resumes", 0),
        "n_executables": jit_count,
        "census": census,
        "tp_shards": tp_shards,
        "tp_collectives": tp_collectives,
        **fields,
        **hlo_fields,
        **prefix_fields,
        **trace_fields,
        **_compile_fields(),
    }))


def bench_ssd():
    """SSD-512 ResNet-50 train step: forward + MultiBoxTarget matching +
    cls/loc loss + backward + SGD, one XLA program (ref: GluonCV
    train_ssd.py; BASELINE config 5)."""
    jax = _setup()
    _trace_on(sample=0.0)   # compile-event stream only (ISSUE 15)

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo.ssd import (ssd_512_resnet50_v1,
                                               SSDMultiBoxLoss)
    from mxnet_tpu import ndarray as F

    batch = 16
    iters = 10
    size = 512

    net = ssd_512_resnet50_v1(classes=20)
    net.initialize()
    net.cast("bfloat16")
    box_loss = SSDMultiBoxLoss()

    def loss_fn(out, label):
        cls_pred, loc_pred, anchor = out
        bt, bm, ct = F.MultiBoxTarget(anchor, label, cls_pred,
                                      overlap_threshold=0.5,
                                      negative_mining_ratio=3.0,
                                      negative_mining_thresh=0.5)
        return box_loss(cls_pred, loc_pred, ct, bt, bm)

    mesh = parallel.make_mesh(dp=len(jax.devices()))
    opt = mx.optimizer.create("sgd", learning_rate=1e-3, momentum=0.9,
                              wd=5e-4)
    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh,
                              grad_reduce=_quant_mode())

    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.randn(batch, 3, size, size)
                    .astype(np.float32)).astype("bfloat16")
    label = np.full((batch, 8, 5), -1.0, np.float32)
    for i in range(batch):
        for j in range(rng.randint(1, 4)):
            cls = rng.randint(0, 20)
            x1, y1 = rng.uniform(0.05, 0.5, 2)
            label[i, j] = [cls, x1, y1, x1 + rng.uniform(0.1, 0.4),
                           y1 + rng.uniform(0.1, 0.4)]
    label = mx.nd.array(label)

    step(x, label).asnumpy()
    step(x, label).asnumpy()

    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(x, label)
    loss.asnumpy()
    dt = time.perf_counter() - t0

    img_s = batch * iters / dt / len(jax.devices())
    print(json.dumps({
        "metric": _METRIC_NAMES["ssd"],
        "value": round(img_s, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(img_s / BASELINE_SSD_IMG_S, 4),
        **_cost_fields(step),
        **_hlo_fields(step),
        **_ckpt_fields(step),
        **_compile_fields(),
    }))


BENCHES = {"resnet": bench_resnet, "bert": bench_bert,
           "lstm": bench_lstm, "ssd": bench_ssd, "llm": bench_llm}
assert set(BENCHES) == set(_METRIC_NAMES)


def main():
    which = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    if which not in tuple(BENCHES) + ("all",):
        print(f"unknown benchmark {which!r} "
              f"(expected {'|'.join(BENCHES)}|all)", file=sys.stderr)
        sys.exit(1)
    names = list(BENCHES) if which == "all" else [which]
    if which == "all" and os.environ.get("MXTPU_BENCH_LLM",
                                         "0").lower() in ("", "0",
                                                          "false"):
        # the driver contract predates the LLM bench: `all` stays the
        # four training configs unless MXTPU_BENCH_LLM=1 opts in
        # (`python bench.py llm` always runs it)
        names.remove("llm")

    # one process, one chip: every named bench runs here, and any
    # exception is this process's non-zero exit
    for name in names:
        BENCHES[name]()


if __name__ == "__main__":
    main()
