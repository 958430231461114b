"""Attention ops — the BERT hot path.

ref: src/operator/contrib/transformer.{cc,cu} —
``_contrib_interleaved_matmul_selfatt_qk`` / ``_contrib_interleaved_matmul_selfatt_valatt``
(cuBLAS strided-batched matmuls over head-interleaved QKV projections).
TPU-native: the same interleaved layout (seq, batch, heads*3*head_dim) feeds
lax.dot_general batched matmuls the MXU eats directly; a fused
``multi_head_attention`` op additionally keeps softmax(QK^T)V in one XLA
fusion.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op


def _split_interleaved(qkv, heads):
    """(S, B, H*3*D) -> three (B*H, S, D) tensors, reference layout."""
    s, b, hd3 = qkv.shape
    d = hd3 // (heads * 3)
    x = qkv.reshape(s, b, heads, 3, d)
    # -> (B, H, S, D) per projection, flattened to (B*H, S, D)
    def pick(i):
        t = x[:, :, :, i, :]  # (S, B, H, D)
        return jnp.transpose(t, (1, 2, 0, 3)).reshape(b * heads, s, d)
    return pick(0), pick(1), pick(2)


@register_op("interleaved_matmul_selfatt_qk",
             aliases=("_contrib_interleaved_matmul_selfatt_qk",))
def _selfatt_qk(queries_keys_values, heads=1):
    """scores = (1/sqrt(d)) Q K^T, output (B*H, S, S) like the reference."""
    q, k, _ = _split_interleaved(queries_keys_values, heads)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@register_op("interleaved_matmul_selfatt_valatt",
             aliases=("_contrib_interleaved_matmul_selfatt_valatt",))
def _selfatt_valatt(queries_keys_values, attention, heads=1):
    """out = attn @ V, back to (S, B, H*D)."""
    _, _, v = _split_interleaved(queries_keys_values, heads)
    s, b = queries_keys_values.shape[0], queries_keys_values.shape[1]
    d = v.shape[-1]
    out = jnp.matmul(attention, v)  # (B*H, S, D)
    out = out.reshape(b, heads, s, d)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(s, b, heads * d)


@register_op("multi_head_attention", needs_rng=True)
def _multi_head_attention(q, k, v, mask=None, heads=1, dropout=0.0,
                          causal=False, training=None):
    """Fused MHA on (B, S, H*D)-shaped projections; XLA fuses scale+softmax.

    No reference analogue as a single op (GluonNLP composes the two contrib
    ops); provided because one fused op is the idiomatic TPU formulation.
    ``dropout`` drops attention probabilities (the reference cell's
    _attention_dropout), train-mode only.
    """
    from .. import autograd as _autograd
    from .. import random as _random
    if training is None:
        training = _autograd.is_training()
    b, sq, hd = q.shape
    d = hd // heads
    def to_bhsd(x):
        return jnp.transpose(x.reshape(b, -1, heads, d), (0, 2, 1, 3))
    qh, kh, vh = to_bhsd(q), to_bhsd(k), to_bhsd(v)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, q.dtype))
    scores = jnp.einsum("bhqd,bhkd->bhqk", qh * scale, kh)
    if causal:
        sk = kh.shape[2]
        cm = jnp.tril(jnp.ones((sq, sk), bool))
        scores = jnp.where(cm, scores, jnp.asarray(-1e30, scores.dtype))
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, jnp.asarray(-1e30, scores.dtype))
    attn = jax.nn.softmax(scores, axis=-1)
    if dropout > 0.0 and training:
        keep = jax.random.bernoulli(_random.next_key(), 1.0 - dropout,
                                    shape=attn.shape)
        attn = jnp.where(keep, attn / (1.0 - dropout),
                         jnp.zeros((), attn.dtype))
    out = jnp.einsum("bhqk,bhkd->bhqd", attn, vh)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, sq, hd)


def _flash(q, k, v, heads, kv_heads, causal, block_q, block_k, dropout=0.0,
           seed=None, window=None):
    """(B, S, H*D) projections, k (B, S, kv_heads*D; None: as many as
    ``heads``) and v (B, S, kv_heads*D_v, its own head width), through the
    Pallas kernels, which take (B*H, S, D), and back to (B, S, H*D_v)."""
    from .pallas import flash_attention
    b, sq, _ = q.shape
    kv_heads = heads if kv_heads is None else kv_heads
    def to_bhsd(x, h=heads):
        d = x.shape[-1] // h
        return jnp.transpose(x.reshape(b, -1, h, d),
                             (0, 2, 1, 3)).reshape(b * h, -1, d)
    out = flash_attention(to_bhsd(q), to_bhsd(k, kv_heads),
                          to_bhsd(v, kv_heads), None, causal, block_q,
                          block_k, None, dropout, seed, window)
    d_v = out.shape[-1]
    out = out.reshape(b, heads, sq, d_v)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, sq, heads * d_v)


@register_op("flash_attention")
def _flash_attention_op(q, k, v, heads=1, causal=False, block_q=128,
                        block_k=128, dropout=0.0, training=None,
                        kv_heads=None):
    """Flash MHA on (B, S, H*D) projections via the Pallas kernel
    (ops/pallas/flash_attention.py) — O(S·D) memory instead of the dense
    op's O(S^2) scores; the long-context single-chip path.  ``dropout``
    applies attention-probability dropout inside the kernel (training only),
    seeded from the framework RNG stream each call.  ``kv_heads`` < ``heads``
    is grouped-query attention: k and v are (B, S, kv_heads*D) and each
    serves ``heads // kv_heads`` query heads.  v's head may be narrower or
    wider than Q's and K's (latent attention's 128 beside 192): the output
    is (B, S, heads*D_v), the scale ``1 / sqrt(D)`` of Q's head."""
    from .. import autograd as _autograd
    from .. import random as _random
    if training is None:
        training = _autograd.is_training()
    drop = float(dropout) if training else 0.0
    seed = None
    if drop > 0.0:
        seed = jax.random.randint(_random.next_key(), (1,), 0, 2 ** 31 - 1)
    return _flash(q, k, v, heads, kv_heads, causal, block_q, block_k, drop,
                  seed)


def _window_block(s):
    """Query and key block of the windowed flash kernels, from the sequence
    alone: 512, or a shorter sequence whole in lanes of 128.  The window
    does not move it: on the chip a layer's four kernel calls take
    15.6 ms at 512 x 512 against 18.4-25.4 at ``block_k = window`` and
    five other shapes (a window of 1,024, heads of 128, 8,192 positions)
    and 3.4 against 4.0-11.4 (512, heads of 64, 4,096): PERF.md 6, PR 32."""
    return min(512, -(-s // 128) * 128)


@register_op("window_attention")
def _window_attention(q, k, v, heads=1, kv_heads=None, window=512):
    """Causal sliding-window attention on (B, S, H*D) projections, k and v
    (B, S, kv_heads*D): query t sees keys t-window+1 .. t, itself included.
    Exact, through the Pallas flash kernels with a window
    (ops/pallas/flash_attention.py, ``window_attention_fwd`` /
    ``_bwd_dqkv`` in a trace): their grid walks the band's block pairs and
    no others, and the scores never leave VMEM.  A sequence that the block
    does not divide is padded at the end (padded keys lie after every real
    query: causality masks them) and cut back."""
    s = q.shape[1]
    block = _window_block(s)
    pad = -s % block
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in (q, k, v))
    out = _flash(q, k, v, heads, kv_heads, True, block, block, window=window)
    return out[:, :s] if pad else out


@register_op("div_sqrt_dim", aliases=("_contrib_div_sqrt_dim",))
def _div_sqrt_dim(x):
    return x / jnp.sqrt(jnp.asarray(x.shape[-1], x.dtype))


@register_op("ring_attention", mesh_aware=True)
def _ring_attention(q, k, v, heads=1, causal=False, axis="sp",
                    batch_axis="dp", dropout=0.0, training=None):
    """Sequence-parallel attention over the active mesh's ``sp`` axis
    (no reference analogue — SURVEY.md §5.7 gap, first-class here).
    Requires a parallel.MeshScope (or TrainStep/EvalStep, which provide one)."""
    from .. import autograd as _autograd
    from ..parallel.sequence import ring_attention
    if training is None:
        training = _autograd.is_training()
    return ring_attention(q, k, v, heads, axis=axis, batch_axis=batch_axis,
                          causal=causal, dropout=dropout, training=training)


@register_op("ulysses_attention", mesh_aware=True)
def _ulysses_attention(q, k, v, heads=1, causal=False, axis="sp",
                       batch_axis="dp", dropout=0.0, training=None):
    """Ulysses head-sharded attention over the active mesh (see above)."""
    from .. import autograd as _autograd
    from ..parallel.sequence import ulysses_attention
    if training is None:
        training = _autograd.is_training()
    return ulysses_attention(q, k, v, heads, axis=axis, batch_axis=batch_axis,
                             causal=causal, dropout=dropout, training=training)
