"""Quantized gradient collectives.

ref: the reference's KVStore moves full-precision gradients between
devices (src/kvstore/comm.h — CommDevice reduces in the array dtype);
its only compression is 2-bit gradient compression on the PS path
(src/kvstore/gradient_compression.cc), which never made it to the dense
allreduce.  PERF.md establishes the hot paths here are bandwidth-bound,
not FLOP-bound — and MULTICHIP runs still move f32/bf16 gradients over
ICI.  *EQuARX* (arXiv:2506.17615, PAPERS.md) shows a quantized
AllReduce recovers most of that wire traffic at negligible quality
cost.  This module is that trade, jax-native:

- **Chunked symmetric quantization** (``quantize_chunked`` /
  ``dequantize_chunked``): int8 payloads with one f32 scale per
  ``chunk`` elements (amax / 127), so one outlier only poisons its own
  chunk, not the tensor.  Rounding is *stochastic* when a PRNG key is
  supplied — ``floor(x/scale + u)``, ``u ~ U[0,1)`` — which makes the
  quantizer unbiased: over steps the rounding error averages out
  instead of accumulating as a directional drift (the property the
  tier-1 unbiasedness test checks statistically).
- **Stochastic bf16 cast** (``cast_bf16``): the same unbiasedness for
  the bf16 wire format, via integer arithmetic on the f32 bit pattern
  (adding 16 random low bits carries into the kept mantissa with
  probability equal to the truncated remainder).
- **The reduction stage** (``reduce_gradients``): called INSIDE a
  ``shard_map`` over the data-parallel axis, it replaces the
  sharding-inserted full-precision all-reduce with a two-phase
  compressed exchange — quantize the local gradient, ``all_to_all``
  the int8 slices (a reduce-scatter whose wire payload is 1/4 the f32
  bytes), dequantize + sum the owned slice, re-quantize it, and
  ``all_gather`` the int8 result.  Every device dequantizes identical
  payloads, so the output is bit-identical fleet-wide and may be
  declared replicated.  ``bf16`` mode is simpler: one ``psum`` over the
  stochastically-cast payload (half the f32 bytes).

Non-finite gradients survive the round-trip as non-finite (an inf amax
poisons its chunk's scale), so ``TrainStep(skip_nonfinite=True)``'s
fused guard keeps working unchanged on the dequantized values.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["GRAD_REDUCE_MODES", "ACTIVATION_REDUCE_MODES",
           "quantize_chunked", "dequantize_chunked", "cast_bf16",
           "reduce_gradients", "all_reduce_activations"]

#: the TrainStep ``grad_reduce=`` vocabulary ("f32" = the implicit
#: sharding-inserted full-precision collective, the pre-ISSUE-8 path)
GRAD_REDUCE_MODES = ("f32", "bf16", "int8")

#: the GenerationServer ``tp_collectives=`` vocabulary — the wire
#: format of the per-layer activation all-reduce on the tensor-parallel
#: decode path (EQuARX, arXiv:2506.17615: decode is latency-bound on
#: collective bytes, so the activation exchange quantizes)
ACTIVATION_REDUCE_MODES = ("f32", "int8")

#: default elements per quantization chunk (one f32 scale each: 1.6%
#: overhead on the int8 payload)
DEFAULT_CHUNK = 256

# key decorrelation: phase-2 rounding must not reuse phase-1's stream
_PHASE2_SALT = 0x5EED


def _blocks(x, chunk):
    """``(..., L)`` → ``(..., nc, c)`` zero-padded chunk view,
    ``c = min(chunk, L)``."""
    L = x.shape[-1]
    c = max(1, min(int(chunk), L))
    nc = -(-L // c)
    pad = nc * c - L
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (nc, c))


def quantize_chunked(x, chunk=DEFAULT_CHUNK, key=None):
    """Symmetric per-chunk int8 quantization over the last axis.

    Returns ``(q, scales)``: ``q`` int8 of shape ``(..., nc, c)`` (the
    last axis zero-padded up to a whole number of chunks) and
    ``scales`` f32 of shape ``(..., nc)``.  With ``key`` the rounding
    is stochastic (unbiased); without, round-to-nearest (deterministic
    — what post-training weight quantization wants)."""
    xb = _blocks(x.astype(jnp.float32), chunk)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    # != 0, not > 0: a NaN amax (any NaN element) must KEEP its NaN
    # scale so the whole chunk dequantizes non-finite — `> 0` is False
    # for NaN and would silently launder the poison into finite zeros,
    # under the nose of TrainStep's skip_nonfinite guard
    scales = jnp.where(amax != 0, amax / 127.0, 1.0)
    y = xb / scales[..., None]
    if key is None:
        q = jnp.round(y)
    else:
        q = jnp.floor(y + jax.random.uniform(key, y.shape))
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8), scales


def dequantize_chunked(q, scales, length, dtype=jnp.float32):
    """Inverse of ``quantize_chunked``: ``(..., nc, c)`` int8 + scales
    → ``(..., length)`` in ``dtype`` (padding stripped)."""
    y = q.astype(jnp.float32) * scales[..., None]
    y = y.reshape(y.shape[:-2] + (-1,))
    return y[..., :length].astype(dtype)


def cast_bf16(x, key=None):
    """bf16 cast; stochastic (unbiased) when ``key`` is given.

    Works on the f32 bit pattern: adding 16 random low bits carries
    into the kept mantissa with probability equal to the truncated
    remainder, so ``E[cast_bf16(x, key)] == x`` for finite x.  Exactly
    representable values never move.  Non-finite inputs are not
    preserved bit-exactly (a carry out of the mantissa can walk an inf
    into NaN) — they stay non-finite, which is all the skip_nonfinite
    guard needs."""
    x32 = x.astype(jnp.float32)
    if key is None:
        return x32.astype(jnp.bfloat16)
    u = lax.bitcast_convert_type(x32, jnp.uint32)
    u = u + (jax.random.bits(key, x32.shape, jnp.uint32) & jnp.uint32(0xFFFF))
    return lax.bitcast_convert_type((u >> jnp.uint32(16)).astype(jnp.uint16),
                                    jnp.bfloat16)


def _reduce_leaf_int8(g, axis_name, n_dev, key, chunk, mean):
    """Two-phase int8 reduction of ONE gradient leaf (inside shard_map).

    Phase 1 (reduce-scatter shape): slice the local gradient ``n_dev``
    ways, quantize, ``all_to_all`` — int8 moves, each device ends up
    holding every peer's version of the slice it owns, dequantizes and
    sums.  Phase 2 (all-gather shape): the owner re-quantizes its
    reduced slice once; ``all_gather`` hands every device the same int8
    payloads, so the dequantized result is bit-identical everywhere
    (the replication the out_specs claim)."""
    shape, dtype, n = g.shape, g.dtype, g.size
    m = -(-n // n_dev)
    flat = g.astype(jnp.float32).reshape(-1)
    if n_dev * m != n:
        flat = jnp.pad(flat, (0, n_dev * m - n))
    x = flat.reshape(n_dev, m)
    q, s = quantize_chunked(x, chunk, key)
    q = lax.all_to_all(q, axis_name, 0, 0, tiled=True)
    s = lax.all_to_all(s, axis_name, 0, 0, tiled=True)
    owned = jnp.sum(dequantize_chunked(q, s, m), axis=0)        # (m,)
    if mean:
        owned = owned / n_dev
    key2 = None if key is None else jax.random.fold_in(key, _PHASE2_SALT)
    q2, s2 = quantize_chunked(owned, chunk, key2)
    gq = lax.all_gather(q2, axis_name, axis=0)                  # (n_dev, ...)
    gs = lax.all_gather(s2, axis_name, axis=0)
    out = dequantize_chunked(gq, gs, m)                         # (n_dev, m)
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


def all_reduce_activations(x, axis_name, n_dev, mode="int8", key=None,
                           chunk=DEFAULT_CHUNK):
    """Cross-device SUM of one activation tensor with a compressed wire
    format — the serving twin of ``reduce_gradients``, called INSIDE a
    ``shard_map`` over ``axis_name`` with ``x`` the device's local
    partial product (Megatron row-parallel matmul output).  Returns the
    summed activations in ``x``'s dtype, bit-identical on every device
    (the int8 path all-gathers ONE set of quantized payloads that every
    device dequantizes the same way — the replication an out_spec may
    honestly claim).

    ``mode``: ``"f32"`` = uncompressed: ``all_gather`` the partials and
    add them in device order, the same order for every element.  Not
    ``psum``: on the v5e 2x2 the all-reduce adds the four partials in an
    order that depends on where the element sits in the buffer and on
    the buffer's size (measured, PERF.md PR 21), so one sequence got
    different last bits in another slot or prefill bucket, a bf16 matmul
    pass turned those into different logits, and sampled and greedy
    tokens parted.  The gather moves ``n_dev - 1`` buffers to each
    device where a ring all-reduce moves ``2 (n_dev - 1) / n_dev``.
    ``"int8"`` = the two-phase chunked exchange (``all_to_all`` int8
    slices → dequant+sum the owned slice → requantize → ``all_gather``)
    at ~1/4 the f32 wire bytes.  ``key=None`` (the serving default)
    rounds to nearest: decode wants the same traffic to produce the
    same tokens on every replica, and the inference forward takes one
    bounded quantization error per layer rather than accumulating drift
    across steps — the unbiasedness stochastic rounding buys gradients
    has no equivalent payoff here."""
    if mode not in ACTIVATION_REDUCE_MODES:
        raise ValueError(f"all_reduce_activations: mode {mode!r} not in "
                         f"{ACTIVATION_REDUCE_MODES}")
    if mode == "f32":
        parts = lax.all_gather(x, axis_name)            # (n_dev, ...)
        out = parts[0]
        for i in range(1, n_dev):
            out = out + parts[i]
        return out
    return _reduce_leaf_int8(x, axis_name, n_dev, key, chunk, mean=False)


def reduce_gradients(grads, axis_name, n_dev, mode="int8", key=None,
                     reduce="mean", chunk=DEFAULT_CHUNK):
    """Cross-device gradient reduction with a compressed wire format.

    Call INSIDE a ``shard_map`` over ``axis_name`` with ``grads`` the
    local (per-device, full-size) gradient leaves.  Returns the reduced
    leaves — the cross-device mean (``reduce="mean"``) or sum — in each
    leaf's original dtype, identical on every device.

    ``mode``: ``"f32"`` = plain ``psum`` (the uncompressed reference
    point), ``"bf16"`` = stochastic-cast payload + psum (2x fewer wire
    bytes vs f32), ``"int8"`` = two-phase chunked int8 exchange (4x).
    ``key`` drives the stochastic rounding (fold the device index in
    BEFORE calling, so replicas round independently); ``key=None``
    rounds to nearest — deterministic, but biased over many steps."""
    if mode not in GRAD_REDUCE_MODES:
        raise ValueError(f"reduce_gradients: mode {mode!r} not in "
                         f"{GRAD_REDUCE_MODES}")
    if reduce not in ("mean", "sum"):
        raise ValueError(f"reduce_gradients: reduce {reduce!r} not in "
                         f"('mean', 'sum')")
    mean = reduce == "mean"
    out = []
    for i, g in enumerate(grads):
        lkey = None if key is None else jax.random.fold_in(key, i)
        if mode == "f32":
            # mxlint: disable=spmd-collective-in-loop -- deliberate
            # per-leaf loop: gradient leaves have heterogeneous
            # shapes/dtypes, flattening them into one collective would
            # defeat the per-chunk scales (and XLA overlaps the
            # unrolled per-leaf collectives on ICI anyway)
            r = lax.psum(g, axis_name)
            r = (r / n_dev).astype(g.dtype) if mean else r
        elif mode == "bf16":
            h = cast_bf16(g.astype(jnp.float32) / n_dev if mean else g, lkey)
            # mxlint: disable=spmd-collective-in-loop -- same deliberate
            # per-leaf loop as the f32 branch (heterogeneous leaves)
            r = lax.psum(h, axis_name).astype(g.dtype)
        else:
            r = _reduce_leaf_int8(g, axis_name, n_dev, lkey, chunk, mean)
        out.append(r)
    return out
