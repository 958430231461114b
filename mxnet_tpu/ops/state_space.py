"""State-space ops: Mamba's selective scan (Gu & Dao, arXiv:2312.00752, eq.
2 with the zero-order-hold step of section 3.2) and the causal depthwise
convolution that precedes it.  No reference analogue (the reference's
recurrences are the fused ``RNN`` op).

Both are plain XLA: the scan is a ``lax.scan`` over chunks of ``SCAN_CHUNK``
positions carrying the state, each chunk a ``lax.scan`` over its positions
under ``jax.checkpoint``, so the backward pass keeps the states at chunk
boundaries and rebuilds one chunk's at a time (never the ``[T, N, D]`` tensor
of all states: 1.3 GB at T 4096, D 5120, N 16).  The state is laid out
``[B, N, D]``: the channels D fill the TPU's 128 lanes, the N states its
sublanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op

# positions a chunk holds: what the backward pass rebuilds at a time
SCAN_CHUNK = 256
# the recurrence's state, its step and its decay are float32 whatever the
# inputs' type: a product of T decays close to 1 does not survive bf16
_STATE_DTYPE = jnp.float32


@register_op("causal_conv1d")
def _causal_conv1d(data, weight, bias=None):
    """Depthwise causal convolution along time: ``data`` (B, T, D),
    ``weight`` (K, D), ``y[t] = sum_k weight[k] * data[t - (K-1) + k]`` with
    zeros before the sequence's start, plus ``bias`` (D,).  K shifted
    multiply-adds, which XLA fuses into one pass."""
    taps, t = weight.shape[0], data.shape[1]
    padded = jnp.pad(data, ((0, 0), (taps - 1, 0), (0, 0)))
    out = padded[:, :t] * weight[0]
    for k in range(1, taps):
        out = out + padded[:, k:k + t] * weight[k]
    return out if bias is None else out + bias


def _scan_chunk(h, chunk, a):
    """One chunk, position by position.  ``h`` (B, N, D); ``chunk`` = (dt, x,
    b, c) time-major; ``a`` (N, D).  Returns the state after the chunk and
    its outputs (L, B, D)."""
    f = _STATE_DTYPE

    def step(h, xs):
        dt, x, b, c = (v.astype(f) for v in xs)
        h = jnp.exp(dt[:, None, :] * a) * h \
            + b[:, :, None] * (dt * x)[:, None, :]
        return h, jnp.sum(h * c[:, :, None], axis=1)

    return jax.lax.scan(step, h, chunk)


@register_op("selective_scan")
def _selective_scan(x, dt, a, b, c, d=None):
    """``h[t] = exp(dt[t] * A) * h[t-1] + (dt[t] * x[t]) B[t]^T``,
    ``y[t] = h[t] C[t] + D * x[t]``, from ``h[-1] = 0``.

    ``x`` (B, T, D) inputs; ``dt`` (B, T, D) positive steps; ``a`` (D, N)
    the negative decay rates A; ``b``, ``c`` (B, T, N) input and output
    maps; ``d`` (D,) the skip.  Returns y (B, T, D) in ``x``'s type; the
    state, ``dt`` and A are float32 throughout."""
    f = _STATE_DTYPE
    bsz, t, dim = x.shape
    size = min(SCAN_CHUNK, t)
    n_chunks = -(-t // size)
    pad = n_chunks * size - t

    def chunks(v):      # (B, T, F) -> (chunks, size, B, F); a padded step
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))     # of 0 changes nothing
        return jnp.moveaxis(v, 1, 0).reshape(n_chunks, size, bsz, v.shape[-1])

    a = a.astype(f).T
    h0 = jnp.zeros((bsz, a.shape[0], dim), f)
    _, y = jax.lax.scan(
        jax.checkpoint(lambda h, chunk: _scan_chunk(h, chunk, a)), h0,
        (chunks(dt), chunks(x), chunks(b), chunks(c)))
    y = jnp.moveaxis(y.reshape(n_chunks * size, bsz, dim), 0, 1)[:, :t]
    if d is not None:
        y = y + d.astype(f) * x.astype(f)
    return y.astype(x.dtype)
