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
    serves ``heads // kv_heads`` query heads."""
    from .. import autograd as _autograd
    from .. import random as _random
    from .pallas import flash_attention
    if training is None:
        training = _autograd.is_training()
    b, sq, hd = q.shape
    d = hd // heads
    def to_bhsd(x, h=heads):
        return jnp.transpose(x.reshape(b, -1, h, d),
                             (0, 2, 1, 3)).reshape(b * h, -1, d)
    kvh = heads if kv_heads is None else kv_heads
    drop = float(dropout) if training else 0.0
    seed = None
    if drop > 0.0:
        seed = jax.random.randint(_random.next_key(), (1,), 0, 2 ** 31 - 1)
    out = flash_attention(to_bhsd(q), to_bhsd(k, kvh), to_bhsd(v, kvh), None,
                          causal, block_q, block_k, None, drop, seed)
    out = out.reshape(b, heads, sq, d)
    return jnp.transpose(out, (0, 2, 1, 3)).reshape(b, sq, hd)


@register_op("window_attention")
def _window_attention(q, k, v, heads=1, kv_heads=None, window=512):
    """Causal sliding-window attention on (B, S, H*D) projections, k and v
    (B, S, kv_heads*D): query t sees keys t-window+1 .. t, itself included.
    Exact, in XLA: the sequence is cut into blocks of ``window`` queries,
    and a block attends to itself and to its predecessor under the band
    mask, so the scores are (window, 2*window) a block and never (S, S).
    One block at a time (``lax.map``), recomputed in the backward pass."""
    b, s, hd = q.shape
    kvh = heads if kv_heads is None else kv_heads
    d, group = hd // heads, heads // kvh
    w = min(window, s)
    nb = -(-s // w)
    pad = nb * w - s     # padded keys lie after every real query: masked

    def blocks(x, h):    # (B, S, h*D) -> (blocks, B, h, w, D)
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, nb, w, h, d)
        return jnp.transpose(x, (1, 0, 3, 2, 4))

    def with_previous(x):                   # -> (blocks, B, h, 2w, D)
        return jnp.concatenate(
            [jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]]), x], axis=3)

    qi = jnp.arange(w)[:, None] + w         # a query's place among its 2w keys
    kj = jnp.arange(2 * w)[None, :]
    band = (kj <= qi) & (kj > qi - window)
    scale = 1.0 / (d ** 0.5)

    def one(args):
        i, qb, kb, vb = args
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb,
                            preferred_element_type=jnp.float32) * scale
        # the first block has no predecessor
        scores = jnp.where(band & ((kj >= w) | (i > 0)), scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(vb.dtype)
        return jnp.einsum("bhgqk,bhkd->bhgqd", p, vb)

    out = jax.lax.map(jax.checkpoint(one), (
        jnp.arange(nb), blocks(q, heads).reshape(nb, b, kvh, group, w, d),
        with_previous(blocks(k, kvh)), with_previous(blocks(v, kvh))))
    out = jnp.transpose(out.reshape(nb, b, heads, w, d), (1, 0, 3, 2, 4))
    return out.reshape(b, nb * w, hd)[:, :s]


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
