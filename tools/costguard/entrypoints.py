"""Budgeted entry points: the named model/step/serving configurations
whose compiled-program costs are committed as goldens.

Each entry point is a builder that LOWERS its program(s) without ever
executing a step (``TrainStep.lower(sample)`` / ``jax.jit(f).lower``),
so budgets compute under ``JAX_PLATFORMS=cpu`` in tier-1.  Registration
is the budget *contract*: mxlint's ``unbudgeted-entrypoint`` rule fails
the gate when a registered name has no golden under
``tests/goldens/budgets/``, and the costguard CLI fails on goldens whose
registration disappeared — the two directions of "every audited surface
stays audited".

CPU-vs-TPU caveat (PERF.md): byte counts from the CPU backend are not
comparable to TPU's.  Goldens record their backend + device count and
are only *gated* in a matching environment; a TPU run of the same entry
points is an audit, not a gate.
"""
from __future__ import annotations

import dataclasses
import inspect
from pathlib import Path
from typing import Callable, Dict, List

from .census import executable_census, grid_signatures
from .report import Program

_REGISTRY: Dict[str, Callable] = {}


@dataclasses.dataclass
class EntryBuild:
    """What a builder returns: the lowered program units, the static
    executable census, and the metadata the golden records."""
    name: str
    meta: dict
    programs: List[Program]
    census: int


def entrypoint(name: str):
    """Register a budgeted entry point (decorator).  The literal name is
    what mxlint's ``unbudgeted-entrypoint`` facts extract — keep it a
    string literal, and matching ``tests/goldens/budgets/<name>.json``."""
    def deco(fn):
        if name in _REGISTRY:
            raise ValueError(f"entrypoint {name!r} registered twice")
        _REGISTRY[name] = fn
        fn.entrypoint_name = name
        return fn
    return deco


def names() -> List[str]:
    return sorted(_REGISTRY)


def build(name: str, **overrides) -> EntryBuild:
    if name not in _REGISTRY:
        raise KeyError(f"unknown entry point {name!r} "
                       f"(registered: {names()})")
    import time
    t0 = time.perf_counter()
    eb = _REGISTRY[name](**overrides)
    _note_compile_events(eb, (time.perf_counter() - t0) * 1e3)
    return eb


def _note_compile_events(eb: EntryBuild, total_ms: float) -> None:
    """ISSUE 15: the costguard builders are one of the compile paths the
    telemetry compile-event stream covers — one event per lowered
    program unit at site ``costguard::<entry>``, so
    ``sum(events) == the entry's census`` holds here exactly like it
    does for the runtime jit caches.  No-op while the tracer is dark;
    never fails a build."""
    try:
        from mxnet_tpu import telemetry
        if not telemetry.ACTIVE:
            return
        per_ms = round(total_ms / max(1, len(eb.programs)), 3)
        for prog in eb.programs:
            telemetry.compile_event(f"costguard::{eb.name}",
                                    key=prog.name, ms=per_ms)
    except Exception:  # noqa: BLE001 — observability never fails a build
        pass


def source_of(name: str) -> Path:
    """The file defining an entry point's builder — what lets the CLI
    map a path argument (``python -m tools.costguard mxnet_tpu/``) onto
    the entry points whose models live under it."""
    fn = _REGISTRY[name]
    return Path(inspect.getsourcefile(fn)).resolve()


def _mesh_and_opt(opt_name="sgd", dp=None, **opt_kw):
    """Default: every visible device on one ``dp`` axis.  ``dp=N`` pins
    the mesh to the first N devices — the 1-device CONTROL of the
    per-device-scaling golden pairs uses ``dp=1``."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    if dp is None:
        mesh = parallel.make_mesh(dp=-1)
    else:
        mesh = parallel.make_mesh(dp=dp, devices=jax.devices()[:dp])
    return mesh, mx.optimizer.create(opt_name, **opt_kw)


def _train_step_build(name, step, x, y, meta) -> EntryBuild:
    import jax

    lowered = step.lower(x, y)
    n_args = len(jax.tree.leaves(step._last_avals))
    meta = dict(meta, backend_note=(
        "CPU-backend byte counts are NOT comparable to TPU's (PERF.md); "
        "this golden gates the compile boundary, not on-chip traffic"))
    return EntryBuild(name=name, meta=meta, census=executable_census(step),
                      programs=[Program(name, lowered, n_args)])


@entrypoint("resnet50_nhwc_train")
def build_resnet50_nhwc_train(batch=8):
    """ResNet-50 v1 NHWC bf16 train step (fwd+bwd+SGD momentum, one XLA
    program on the dp mesh) — the PERF.md headline workload."""
    import ml_dtypes
    import numpy as np

    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1

    net = resnet50_v1(layout="NHWC")
    net.initialize()
    net.cast("bfloat16")
    mesh, opt = _mesh_and_opt("sgd", learning_rate=0.1, momentum=0.9,
                              wd=1e-4)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              opt, mesh=mesh)
    # the sample batch as HOST arrays: nothing is placed or executed
    x = np.zeros((batch, 224, 224, 3), ml_dtypes.bfloat16)
    y = np.zeros((batch,), np.int32)
    return _train_step_build(
        "resnet50_nhwc_train", step, x, y,
        {"model": "resnet50_v1", "layout": "NHWC", "dtype": "bfloat16",
         "precision": "bf16", "batch": batch,
         "optimizer": "sgd(momentum=0.9, wd=1e-4)", "sharded": True})


def _mnist_mlp_step(batch=64, dtype="float32", grad_reduce="f32",
                    dp=None):
    """The examples/train_mnist_mlp.py recipe: 784-128-10 MLP train
    step, f32, SGD momentum — shared by the f32 entry, its
    ``grad_reduce="int8"`` sibling (same model, same sample batch, so
    the two goldens diff leaf-for-leaf), and the ``dp=1`` unsharded
    control of the per-device-scaling pair."""
    import ml_dtypes
    import numpy as np

    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu", in_units=784),
            nn.Dense(10, in_units=128))
    net.initialize()
    if dtype != "float32":
        net.cast(dtype)
    mesh, opt = _mesh_and_opt("sgd", dp=dp, learning_rate=0.1,
                              momentum=0.9)
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              opt, mesh=mesh, grad_reduce=grad_reduce)
    np_dtype = ml_dtypes.bfloat16 if dtype == "bfloat16" else np.float32
    x = np.zeros((batch, 784), np_dtype)
    y = np.zeros((batch,), np.int32)
    return step, x, y


@entrypoint("mnist_mlp_train")
def build_mnist_mlp_train(batch=64, dtype="float32"):
    step, x, y = _mnist_mlp_step(batch=batch, dtype=dtype)
    return _train_step_build(
        "mnist_mlp_train", step, x, y,
        {"model": "mlp 784-128-10", "dtype": dtype, "precision": "f32",
         "batch": batch, "optimizer": "sgd(momentum=0.9)", "sharded": True,
         "dp_shards": int(step.mesh.devices.size)})


@entrypoint("mnist_mlp_train_dp1")
def build_mnist_mlp_train_dp1(batch=64, dtype="float32"):
    """``mnist_mlp_train`` pinned to a 1-device ``dp`` mesh: the
    UNSHARDED control of the dp per-device-scaling pair.  The committed
    contract — asserted by tests/test_costguard.py::
    test_dp_sharded_per_device_byte_budget — is that the dp=8 entry's
    per-device ``argument_bytes`` drop by ~7/8 of the batch bytes vs
    this control (params are replicated on a pure-dp mesh, so ONLY the
    batch shard scales — exactly what "per-device bytes ∝ 1/shards for
    the sharded tensors" means here)."""
    step, x, y = _mnist_mlp_step(batch=batch, dtype=dtype, dp=1)
    return _train_step_build(
        "mnist_mlp_train_dp1", step, x, y,
        {"model": "mlp 784-128-10", "dtype": dtype, "precision": "f32",
         "batch": batch, "optimizer": "sgd(momentum=0.9)",
         "sharded": False, "dp_shards": 1})


@entrypoint("mnist_mlp_train_gradq_int8")
def build_mnist_mlp_train_gradq_int8(batch=64, dtype="float32"):
    """``mnist_mlp_train`` with ``grad_reduce="int8"``: the explicit
    shard_map gradient-reduction stage (quantize → all_to_all /
    all_gather of int8 payloads → dequantize) replacing the implicit
    f32 all-reduce.  The committed contract vs the f32 golden —
    asserted by tests/test_costguard.py::test_gradq_int8_collective_
    byte_budget — is >= 25% fewer ``collective_bytes``.  NB on the CPU
    backend ``bytes_accessed``/``flops`` go UP (int8 + stochastic
    rounding are emulated); the wire payload is what this entry
    budgets.  (ResNet-50 was measured too: its master grads are
    already bf16, so the int8 modeled-payload win there is marginal —
    the f32-gradient MLP is the honest A/B.)"""
    step, x, y = _mnist_mlp_step(batch=batch, dtype=dtype,
                                 grad_reduce="int8")
    return _train_step_build(
        "mnist_mlp_train_gradq_int8", step, x, y,
        {"model": "mlp 784-128-10", "dtype": dtype, "precision": "int8",
         "batch": batch, "optimizer": "sgd(momentum=0.9)",
         "grad_reduce": "int8", "sharded": True})


def _serving_mlp_grid_build(name, batch_buckets, length_buckets, features,
                            dtype, quantize):
    """One jitted MLP apply lowered at EVERY padded (batch, length)
    signature the ``BucketSpec`` admits — the whole executable space an
    ``InferenceServer`` on this spec can ever compile.  The params are
    ARGUMENTS of the jitted fn (the ``fleet.HotSwapApply`` serving
    shape: a weight update is a pointer swap), so the compiled weight
    buffer is visible in ``memory.argument_bytes`` — the metric the
    int8 variant commits a >= 25% reduction on.  n_executables in the
    golden == the static census == the runtime jit-cache count
    (tests/test_serving.py, tests/test_quantize.py)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import BucketSpec

    spec = BucketSpec(batch=batch_buckets, length=length_buckets)
    hidden, out = 64, 16
    dt = jnp.dtype(dtype)
    params = [jnp.zeros((features, hidden), dt), jnp.zeros((hidden,), dt),
              jnp.zeros((hidden, out), dt), jnp.zeros((out,), dt)]

    def fwd(p, x):                     # x: (batch, length, features)
        h = jnp.tanh(x @ p[0] + p[1])
        return h @ p[2] + p[3]

    meta = {"model": f"mlp {features}-{hidden}-{out} apply",
            "dtype": dtype, "precision": "int8" if quantize else "f32",
            "batch_buckets": list(spec.batch),
            "length_buckets": list(spec.length)}
    if quantize:
        # the int8 serving shape: per-channel PTQ payload/scale pairs as
        # the compiled program's weight arguments, dequant folded inside
        from mxnet_tpu.amp import Int8Quantizer
        quantizer = Int8Quantizer(axis=1)      # x @ w: out-features last
        params = quantizer.quantize(params)
        apply = jax.jit(quantizer.wrap(fwd))
        meta["weights"] = "int8 per-channel PTQ (amp.Int8Quantizer)"
    else:
        apply = jax.jit(fwd)
    p_avals = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params]
    programs = []
    for b, L in grid_signatures(spec):
        aval = jax.ShapeDtypeStruct((b, L, features), dt)
        # mxlint: disable=jit-in-loop -- this loop IS the census: one
        # lower per bucket signature, bounded by the static grid, and
        # the expensive compile is memoized by the report cache
        lowered = apply.lower(p_avals, aval)
        programs.append(Program(f"{name}/b{b}_l{L}", lowered,
                                n_args=len(params) + 1))
    return EntryBuild(name=name, meta=meta, programs=programs,
                      census=executable_census(spec))


def _llm_parts(vocab=256, n_layers=2, n_heads=8, head_dim=4, d_ff=64,
               n_slots=8, n_pages=64, page_size=16, pages_per_seq=16):
    """Shared pieces of the LLM serving entry points: the tiny causal
    LM's param avals (``jax.eval_shape`` — zero device work) and the
    fixed decode-grid geometry.  ``n_pages * page_size`` (1024 cache
    tokens) is HALF of ``n_slots * pages_per_seq * page_size`` (2048) —
    the pool is deliberately oversubscribed 2:1 against the worst case,
    which is exactly the HBM the paged design reclaims and the
    ``llm_decode_step`` vs ``llm_decode_step_dense`` golden pair
    commits (>= 40% fewer decode-step argument bytes, gated by
    tests/test_costguard.py::test_llm_paged_kv_byte_budget).  The head
    layout is 8 heads x 4 (``d_model`` 32, same pool bytes as the
    original 2 x 16) so ``llm_decode_step_tp8`` shards the IDENTICAL
    model/geometry 8 ways — the tp pair diffs like-for-like."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)

    cfg = CausalLMConfig(vocab_size=vocab, n_layers=n_layers,
                         n_heads=n_heads, head_dim=head_dim, d_ff=d_ff)
    p_avals = jax.eval_shape(lambda: init_causal_lm(cfg, 0))
    geom = {"n_slots": n_slots, "n_pages": n_pages,
            "page_size": page_size, "pages_per_seq": pages_per_seq,
            "max_context": pages_per_seq * page_size}
    sds = jax.ShapeDtypeStruct
    slot_avals = {
        "tokens": sds((n_slots,), jnp.int32),
        "lengths": sds((n_slots,), jnp.int32),
        "active": sds((n_slots,), jnp.bool_),
        "tables": sds((n_slots, pages_per_seq), jnp.int32),
        "cow_src": sds((n_slots,), jnp.int32),
        "cow_dst": sds((n_slots,), jnp.int32),
        "seeds": sds((n_slots,), jnp.uint32),
        "temps": sds((n_slots,), jnp.float32),
        "topks": sds((n_slots,), jnp.int32),
    }
    return cfg, p_avals, geom, slot_avals


def _n_leaves(*trees):
    import jax
    return sum(len(jax.tree.leaves(t)) for t in trees)


@entrypoint("llm_decode_step")
def build_llm_decode_step():
    """THE continuous-batching decode executable (serving/generate.py):
    one token for every in-flight sequence over the fixed slot grid,
    K/V held in the shared paged pool addressed by page tables.  Its
    census is 1 by construction — every traffic mix runs this program —
    and its ``memory.argument_bytes`` is the paged-KV headline the
    golden pair below commits."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving.generate import build_decode_step

    cfg, p_avals, g, s = _llm_parts()
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, g["n_pages"], g["page_size"], cfg.n_heads,
         cfg.head_dim), jnp.float32)
    step = jax.jit(build_decode_step(cfg, g["page_size"], "jnp"),
                   donate_argnums=(1, 2))
    lowered = step.lower(p_avals, pool, pool, s["tokens"], s["lengths"],
                         s["active"], s["tables"], s["cow_src"],
                         s["cow_dst"], s["seeds"], s["temps"], s["topks"])
    n_args = _n_leaves(p_avals) + 2 + 9
    meta = {"model": f"causal_lm {cfg.vocab_size}v {cfg.n_layers}L "
                     f"{cfg.n_heads}h{cfg.head_dim}", "kv": "paged",
            "precision": "f32", **g}
    return EntryBuild(name="llm_decode_step", meta=meta, census=1,
                      programs=[Program("llm_decode_step", lowered,
                                        n_args)])


def _llm_decode_step_tp(name, collectives, shards=8):
    """Shared builder of the tensor-parallel decode entries (ISSUE 14):
    the IDENTICAL model, pool geometry, and slot grid as
    ``llm_decode_step``, lowered ONCE over a tp mesh — head-sharded
    pools, Megatron column/row weights, per-layer activation
    all-reduces in the ``collectives`` wire format.  Census stays 1:
    sharding is a lowering property, not a new executable."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel
    from mxnet_tpu.serving.generate import build_decode_step

    cfg, p_avals, g, s = _llm_parts()
    mesh = parallel.make_mesh(tp=shards, devices=jax.devices()[:shards])
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, g["n_pages"], g["page_size"], cfg.n_heads,
         cfg.head_dim), jnp.float32)
    step = jax.jit(build_decode_step(cfg, g["page_size"], "jnp",
                                     mesh=mesh, tp_collectives=collectives),
                   donate_argnums=(1, 2))
    lowered = step.lower(p_avals, pool, pool, s["tokens"], s["lengths"],
                         s["active"], s["tables"], s["cow_src"],
                         s["cow_dst"], s["seeds"], s["temps"], s["topks"])
    n_args = _n_leaves(p_avals) + 2 + 9
    meta = {"model": f"causal_lm {cfg.vocab_size}v {cfg.n_layers}L "
                     f"{cfg.n_heads}h{cfg.head_dim}", "kv": "paged",
            "precision": "int8" if collectives == "int8" else "f32",
            "sharded": True, "tp_shards": shards,
            "tp_collectives": collectives, **g}
    return EntryBuild(name=name, meta=meta, census=1,
                      programs=[Program(name, lowered, n_args)])


@entrypoint("llm_decode_step_tp8")
def build_llm_decode_step_tp8():
    """The tensor-parallel decode executable at tp=8, f32 collectives:
    head-parallel paged attention (each device owns 1 of 8 head shards
    of BOTH pools) + column/row-sharded projections/FFN with the two
    Megatron all-reduces per layer.  The committed contract vs
    ``llm_decode_step`` — asserted by tests/test_costguard.py::
    test_tp_sharded_decode_per_device_pool_byte_budget — is per-device
    ``argument_bytes`` down by 7/8 of the pool + sharded weight bytes
    (±2%): per-device KV-pool HBM ∝ 1/shards, the ISSUE 14 headline."""
    return _llm_decode_step_tp("llm_decode_step_tp8", "f32")


@entrypoint("llm_decode_step_tp8_q8")
def build_llm_decode_step_tp8_q8():
    """``llm_decode_step_tp8`` with ``tp_collectives="int8"``: the
    per-layer activation all-reduces run through the chunked int8
    quantize/all_to_all/all_gather machinery (parallel.quantize, the
    EQuARX trade — decode is latency-bound on collective bytes).  The
    committed contract vs the f32 sibling — asserted by
    tests/test_costguard.py::test_tp_decode_int8_collective_byte_budget
    — is >= 25% fewer per-device ``collective_bytes`` over the same
    model, mesh, and executable census."""
    return _llm_decode_step_tp("llm_decode_step_tp8_q8", "int8")


@entrypoint("llm_decode_step_dense")
def build_llm_decode_step_dense():
    """The dense max-length-cache decode variant: identical model, slot
    grid, and sampling, but every slot owns a full ``max_context``
    cache stripe.  Committed as the golden the paged entry is diffed
    against — the pair IS the structural-HBM-win regression floor
    (PR 8 pattern: the win itself is gated, not just each side)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving.generate import build_dense_decode_step

    cfg, p_avals, g, s = _llm_parts()
    cache = jax.ShapeDtypeStruct(
        (cfg.n_layers, g["n_slots"], g["max_context"], cfg.n_heads,
         cfg.head_dim), jnp.float32)
    step = jax.jit(build_dense_decode_step(cfg, g["max_context"]),
                   donate_argnums=(1, 2))
    lowered = step.lower(p_avals, cache, cache, s["tokens"], s["lengths"],
                         s["active"], s["seeds"], s["temps"], s["topks"])
    n_args = _n_leaves(p_avals) + 2 + 6
    meta = {"model": f"causal_lm {cfg.vocab_size}v {cfg.n_layers}L "
                     f"{cfg.n_heads}h{cfg.head_dim}",
            "kv": "dense max-length", "precision": "f32", **g}
    return EntryBuild(name="llm_decode_step_dense", meta=meta, census=1,
                      programs=[Program("llm_decode_step_dense", lowered,
                                        n_args)])


@entrypoint("llm_verify_step")
def build_llm_verify_step(spec_k=3, spec_window=16):
    """THE speculative-decoding verify executable (ISSUE 16): the draft
    LM proposes ``spec_k`` tokens per slot and the target model scores
    all ``spec_k + 1`` flattened lanes in this ONE program — the census
    of a speculative server is the non-speculative census plus exactly
    this entry.  Draft params ride along as ordinary arguments (a
    1-layer sibling of the target config, same vocab), so
    ``argument_bytes`` prices the full speculation tax."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.gluon.model_zoo.causal_lm import (draft_config,
                                                     init_causal_lm)
    from mxnet_tpu.serving.generate import build_verify_step

    cfg, p_avals, g, s = _llm_parts()
    dcfg = draft_config(cfg, n_layers=1)
    d_avals = jax.eval_shape(lambda: init_causal_lm(dcfg, 0))
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, g["n_pages"], g["page_size"], cfg.n_heads,
         cfg.head_dim), jnp.float32)
    sds = jax.ShapeDtypeStruct
    step = jax.jit(build_verify_step(cfg, dcfg, g["page_size"], spec_k,
                                     spec_window, "jnp"),
                   donate_argnums=(2, 3))
    lowered = step.lower(
        p_avals, d_avals, pool, pool, s["tokens"],
        sds((g["n_slots"], spec_window), jnp.int32),
        sds((g["n_slots"],), jnp.int32), s["lengths"], s["active"],
        s["tables"], s["cow_src"], s["cow_dst"], s["seeds"], s["temps"],
        s["topks"])
    n_args = _n_leaves(p_avals, d_avals) + 2 + 11
    meta = {"model": f"causal_lm {cfg.vocab_size}v {cfg.n_layers}L "
                     f"{cfg.n_heads}h{cfg.head_dim}",
            "draft": f"causal_lm {dcfg.vocab_size}v {dcfg.n_layers}L "
                     f"{dcfg.n_heads}h{dcfg.head_dim}",
            "kv": "paged", "precision": "f32", "spec_k": spec_k,
            "spec_window": spec_window, **g}
    return EntryBuild(name="llm_verify_step", meta=meta, census=1,
                      programs=[Program("llm_verify_step", lowered,
                                        n_args)])


def _llm_admission(name, n_pages, shared_prefix_len, prompt_len=192,
                   max_new=64):
    """Shared builder of the prefix-sharing admission golden pair: the
    IDENTICAL decode program and slot grid, lowered over a pool sized
    to admit the same worst-case traffic with and without CoW prefix
    sharing.  Admission charges only NON-shared pages
    (``prefix_admission_plan``), so at a 90%-shared prefix the shared
    pool shrinks to sink + one resident prefix + charged pages per
    slot — the committed ``argument_bytes`` gap IS the
    page-bytes-per-sequence win, and the plan in ``meta`` pins the
    >= 2x admissible-concurrency multiplier at fixed pool size."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving.generate import (build_decode_step,
                                            prefix_admission_plan)

    cfg, p_avals, g, s = _llm_parts(n_pages=n_pages)
    plan = prefix_admission_plan(n_pages, g["page_size"], prompt_len,
                                 max_new, shared_prefix_len)
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, g["n_pages"], g["page_size"], cfg.n_heads,
         cfg.head_dim), jnp.float32)
    step = jax.jit(build_decode_step(cfg, g["page_size"], "jnp"),
                   donate_argnums=(1, 2))
    lowered = step.lower(p_avals, pool, pool, s["tokens"], s["lengths"],
                         s["active"], s["tables"], s["cow_src"],
                         s["cow_dst"], s["seeds"], s["temps"], s["topks"])
    n_args = _n_leaves(p_avals) + 2 + 9
    meta = {"model": f"causal_lm {cfg.vocab_size}v {cfg.n_layers}L "
                     f"{cfg.n_heads}h{cfg.head_dim}", "kv": "paged",
            "precision": "f32", "prompt_len": prompt_len,
            "max_new": max_new,
            "shared_prefix_len": shared_prefix_len, **plan, **g}
    return EntryBuild(name=name, meta=meta, census=1,
                      programs=[Program(name, lowered, n_args)])


@entrypoint("llm_admission_unshared")
def build_llm_admission_unshared():
    """Unshared admission baseline: every sequence is charged its full
    worst case (16 pages: 192-token prompt + 64 new at page_size 16),
    so the 8-slot grid needs a 128-page pool (n_pages 129 with the
    sink).  ``meta.admissible_unshared`` = 8."""
    return _llm_admission("llm_admission_unshared", n_pages=129,
                          shared_prefix_len=176)


@entrypoint("llm_admission_shared")
def build_llm_admission_shared():
    """The 90%-shared-prefix sibling: 176 of 192 prompt tokens are a
    common system prefix (11 full pages resident ONCE), so admission
    charges 5 pages per sequence and the same 8-slot worst case fits in
    sink + 16 + 7x5 = 52 pages.  Diffed against
    ``llm_admission_unshared`` by tests/test_costguard.py — the
    committed floors are argument-bytes ratio and the >= 2x
    admissible-concurrency multiplier at the FIXED 128-page pool
    (``prefix_admission_plan(129, 16, 192, 64, 176)`` admits 23 shared
    vs 8 unshared)."""
    return _llm_admission("llm_admission_shared", n_pages=52,
                          shared_prefix_len=176)


@entrypoint("llm_prefill_grid")
def build_llm_prefill_grid(batch_buckets=(1, 2), length_buckets=(32, 64)):
    """The prompt-prefill side of the LLM serving census: ONE jitted
    prefill program lowered at every padded (batch, length) bucket the
    ``GenerationServer``'s BucketSpec admits.  Together with
    ``llm_decode_step`` this is the ENTIRE executable space of the
    serving loop — runtime jit caches are asserted equal to this census
    under mixed-length traffic in tests/test_generate.py."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serving import BucketSpec
    from mxnet_tpu.serving.generate import build_prefill_step

    cfg, p_avals, g, s = _llm_parts()
    spec = BucketSpec(batch=batch_buckets, length=length_buckets)
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, g["n_pages"], g["page_size"], cfg.n_heads,
         cfg.head_dim), jnp.float32)
    step = jax.jit(build_prefill_step(cfg, g["page_size"]),
                   donate_argnums=(1, 2))
    sds = jax.ShapeDtypeStruct
    programs = []
    for b, L in grid_signatures(spec):
        # mxlint: disable=jit-in-loop -- this loop IS the census: one
        # lower per bucket signature, bounded by the static grid, and
        # the expensive compile is memoized by the report cache
        lowered = step.lower(
            p_avals, pool, pool, sds((b, L), jnp.int32),
            sds((b,), jnp.int32), sds((b,), jnp.bool_),
            sds((b, g["pages_per_seq"]), jnp.int32),
            sds((b,), jnp.uint32),
            sds((b,), jnp.float32), sds((b,), jnp.int32))
        programs.append(Program(f"llm_prefill_grid/b{b}_l{L}", lowered,
                                n_args=_n_leaves(p_avals) + 2 + 7))
    meta = {"model": f"causal_lm {cfg.vocab_size}v {cfg.n_layers}L "
                     f"{cfg.n_heads}h{cfg.head_dim}",
            "precision": "f32", "batch_buckets": list(spec.batch),
            "length_buckets": list(spec.length), **g}
    return EntryBuild(name="llm_prefill_grid", meta=meta,
                      programs=programs,
                      census=executable_census(spec))


@entrypoint("serving_mlp_grid")
def build_serving_mlp_grid(batch_buckets=(1, 2, 4), length_buckets=(8, 16),
                           features=32, dtype="float32"):
    """The f32 serving bucket grid (see ``_serving_mlp_grid_build``).
    NB the dtype knob exists for on-TPU audits (bf16 serving, ROADMAP
    item 2), but the committed golden is f32: on the CPU backend bf16
    compute is EMULATED via converts and *costs* bytes rather than
    saving them — the PERF.md caveat, visible in the numbers."""
    return _serving_mlp_grid_build("serving_mlp_grid", batch_buckets,
                                   length_buckets, features, dtype,
                                   quantize=False)


def tp_mlp_apply(shards, features=256, hidden=1024, batch=8):
    """The tensor-parallel MLP apply the TP golden pair budgets — and
    the exact collective shape ROADMAP item 1's sharded FFN uses:
    ``w1`` column-sharded over ``tp`` (hidden split), ``w2``
    row-sharded (the partial products), one all-reduce restoring the
    replicated output — the standard two-collective-per-layer Megatron
    layout collapsed to its one-layer core.  Returns ``(apply, avals,
    mesh)`` with the jitted apply carrying the shardings, so tests can
    EXECUTE it (census == runtime jit-cache proof) while the entry
    points only lower it."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from mxnet_tpu import parallel

    mesh = parallel.make_mesh(tp=shards, devices=jax.devices()[:shards])

    def fwd(w1, b1, w2, b2, x):
        h = jax.nn.gelu(x @ w1 + b1)
        return h @ w2 + b2

    def sh(*spec):
        return NamedSharding(mesh, PartitionSpec(*spec))

    apply = jax.jit(fwd,
                    in_shardings=(sh(None, "tp"), sh("tp"),
                                  sh("tp", None), sh(), sh()),
                    out_shardings=sh())
    avals = [jax.ShapeDtypeStruct(s, jnp.float32)
             for s in ((features, hidden), (hidden,),
                       (hidden, features), (features,),
                       (batch, features))]
    return apply, avals, mesh


def _tp_mlp_build(name, shards, features=256, hidden=1024, batch=8):
    apply, avals, _mesh = tp_mlp_apply(shards, features=features,
                                       hidden=hidden, batch=batch)
    lowered = apply.lower(*avals)
    meta = {"model": f"mlp {features}-{hidden}-{features} apply",
            "dtype": "float32", "precision": "f32", "batch": batch,
            "tp_shards": shards, "sharded": shards > 1,
            "layout": "w1 column-sharded / w2 row-sharded over tp; "
                      "activations replicated; one all-reduce on the "
                      "output"}
    return EntryBuild(name=name, meta=meta, census=1,
                      programs=[Program(name, lowered, n_args=5)])


@entrypoint("mlp_apply_tp8")
def build_mlp_apply_tp8(shards=8):
    """Tensor-parallel (tp=8) MLP apply: weights sharded column/row over
    the mesh, output restored by ONE all-reduce.  The committed
    contract vs ``mlp_apply_tp1`` — asserted by tests/test_costguard.py
    ::test_tp_sharded_per_device_byte_budget — is per-device
    ``argument_bytes`` ∝ 1/shards for the sharded weights (>= 70% below
    the unsharded control at tp=8), with the all-reduce visible in
    ``per_device.collective_bytes`` — the literal gate ROADMAP item 1
    (tensor-parallel decode) lands on top of."""
    return _tp_mlp_build("mlp_apply_tp8", shards)


@entrypoint("mlp_apply_tp1")
def build_mlp_apply_tp1():
    """The tp=1 control of the TP golden pair: identical model and
    batch on a 1-device mesh — full weight bytes per device, zero
    collectives.  Exists so the TP win is a diff of two COMMITTED
    goldens (the PR 8 pattern), not a number recomputed at test time."""
    return _tp_mlp_build("mlp_apply_tp1", 1)


@entrypoint("serving_mlp_grid_int8")
def build_serving_mlp_grid_int8(batch_buckets=(1, 2, 4),
                                length_buckets=(8, 16), features=32,
                                dtype="float32"):
    """``serving_mlp_grid`` with int8 post-training weight quantization:
    same model, same bucket grid, but the compiled programs take int8
    payloads + f32 per-channel scales as their weight arguments (the
    ``amp.Int8Quantizer.wrap`` fold).  The committed contract vs the
    f32 golden — asserted by tests/test_costguard.py::test_serving_
    int8_weight_buffer_budget — is >= 25% less compiled weight-buffer
    memory (``memory.argument_bytes``)."""
    return _serving_mlp_grid_build("serving_mlp_grid_int8", batch_buckets,
                                   length_buckets, features, dtype,
                                   quantize=True)
