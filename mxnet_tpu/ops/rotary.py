"""Rotary position embedding (Su et al., arXiv:2104.09864) and the frequency
tables a decoder's configuration names: the plain table and YaRN's (Peng et
al., arXiv:2309.00071).  No reference analogue.

A table is ``(inv_freq, factor)``: ``D / 2`` inverse frequencies and the
factor on both ``cos`` and ``sin`` (YaRN's attention factor; 1 for the plain
table).  One model may hold several (window layers under one, full layers
under another), so the op takes the table and keeps none.

Plain XLA, element-wise: the angles ``t * f`` are float32 whatever the
operand's type (at 8,192 positions a bf16 angle is off by whole radians);
the rotation is in the operand's type.
"""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from .registry import register_op

__all__ = ["rope_frequencies"]

# positions times frequencies, whatever the operand's type: bf16 has 8 bits
# of mantissa, so past position 256 a bf16 angle is off by radians
_ANGLE_DTYPE = jnp.float32


def _default_frequencies(theta, dim):
    return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


def _yarn_frequencies(p, dim):
    """Wavelengths short of the original context are kept, those beyond it
    are stretched by ``factor``, with a linear ramp between the rotary pairs
    that turn ``beta_fast`` and ``beta_slow`` times over the original
    context (the pairs' indices floored and ceiled, clipped to 0..dim-1)."""
    theta, factor = p["rope_theta"], p["factor"]
    original = p["original_max_position_embeddings"]
    extrapolated = _default_frequencies(theta, dim)

    def pair_of(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(pair_of(p.get("beta_fast", 32))), 0)
    high = min(math.ceil(pair_of(p.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = extrapolated / factor * ramp + extrapolated * (1.0 - ramp)
    attention_factor = p.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq, attention_factor


def rope_frequencies(parameters, dim):
    """``(inv_freq, factor)`` for heads of ``dim`` from one entry of a
    configuration's ``rope_parameters``: ``rope_type`` ``default`` (``f_i =
    theta^(-2i/dim)``, factor 1) or ``yarn`` (every key of the entry is
    read; ``attention_factor`` defaults to ``0.1 ln(factor) + 1``).
    ``inv_freq`` is a tuple of ``dim / 2`` floats rounded to float32."""
    kind = parameters.get("rope_type", "default")
    if kind == "default":
        inv_freq, factor = _default_frequencies(
            parameters["rope_theta"], dim), 1.0
    elif kind == "yarn":
        inv_freq, factor = _yarn_frequencies(parameters, dim)
    else:
        raise ValueError(f"rope_type {kind!r} is neither 'default' nor "
                         f"'yarn'")
    return tuple(float(f) for f in inv_freq.astype(np.float32)), float(factor)


@register_op("rotary_embedding")
def _rotary_embedding(data, inv_freq=(), heads=1, factor=1.0,
                      interleaved=False):
    """``data`` (B, T, heads*D) at positions 0..T-1, each head rotated:
    ``x * cos + rotate_half(x) * sin`` with ``rotate_half([x1, x2]) = [-x2,
    x1]`` over the two halves of D, ``cos = factor * cos(t * [f, f])`` and
    ``sin`` alike, ``f = inv_freq`` (D / 2 of them).  ``interleaved`` turns
    the pairs of neighbours instead: dims ``(2i, 2i+1)`` by ``t * f[i]``,
    each pair kept in its place (``rope_interleave``)."""
    b, t, hd = data.shape
    d = hd // heads
    if len(inv_freq) * 2 != d:
        raise ValueError(f"{len(inv_freq)} inverse frequencies for heads of "
                         f"{d}: {d // 2} are needed")
    angles = jnp.arange(t, dtype=_ANGLE_DTYPE)[:, None] \
        * jnp.asarray(inv_freq, _ANGLE_DTYPE)[None, :]            # (T, D/2)
    cos = (factor * jnp.cos(angles)).astype(data.dtype)[None, :, None, :]
    sin = (factor * jnp.sin(angles)).astype(data.dtype)[None, :, None, :]
    if interleaved:
        x = data.reshape(b, t, heads, d // 2, 2)
        even, odd = x[..., 0], x[..., 1]
        out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                        axis=-1)
        return out.reshape(b, t, hd)
    x = data.reshape(b, t, heads, d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(b, t, hd)
