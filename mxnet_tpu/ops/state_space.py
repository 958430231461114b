"""State-space ops: Mamba's selective scan (Gu & Dao, arXiv:2312.00752, eq.
2 with the zero-order-hold step of section 3.2) and the causal depthwise
convolution that precedes it.  No reference analogue (the reference's
recurrences are the fused ``RNN`` op).

The convolution is plain XLA.  The scan is a pair of Pallas kernels
(``ops/pallas/selective_scan.py``, ``selective_scan_fwd`` and
``selective_scan_bwd``) behind a ``jax.custom_vjp``: a grid step holds
``SCAN_CHUNK`` positions of a tile of channels and the state never leaves
VMEM; the forward keeps the states at chunk boundaries and the backward
rebuilds one chunk's at a time (never the ``[T, N, D]`` tensor of all states:
1.3 GB at T 4096, D 5120, N 16).  The state of a tile is laid out ``[N,
bd]``: the channels fill the TPU's 128 lanes, the N states its sublanes.  Off
the TPU the same kernels run in the Pallas interpreter.
"""
from __future__ import annotations

import jax.numpy as jnp

from .pallas import selective_scan as _kernels
from .registry import register_op

# positions a grid step of the kernels holds: what the backward pass rebuilds
# at a time
SCAN_CHUNK = 128
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


@register_op("selective_scan")
def _selective_scan(x, dt, a, b, c, d=None):
    """``h[t] = exp(dt[t] * A) * h[t-1] + (dt[t] * x[t]) B[t]^T``,
    ``y[t] = h[t] C[t] + D * x[t]``, from ``h[-1] = 0``.

    ``x`` (B, T, D) inputs; ``dt`` (B, T, D) positive steps; ``a`` (D, N)
    the negative decay rates A; ``b``, ``c`` (B, T, N) input and output
    maps; ``d`` (D,) the skip.  Returns y (B, T, D) in ``x``'s type; the
    state, ``dt`` and A are float32 throughout."""
    f = _STATE_DTYPE
    y = _kernels.selective_scan(x, dt, a, b, c, chunk=SCAN_CHUNK,
                                state_dtype=f)
    if d is not None:
        y = y + d.astype(f) * x.astype(f)
    return y.astype(x.dtype)
