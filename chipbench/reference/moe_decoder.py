"""The plain reference of ``family: moe_decoder`` (Mellum2-12B-A2.5B-Instruct,
``model_type`` ``mellum``; YaRN per arXiv:2309.00071 as ``transformers``
computes it with ``truncate`` at its default): float32 ``jax.numpy`` at
"highest" matmul precision, no kernel, no sort, no grouped product, no
recomputation.  Attention is a dense masked softmax (in blocks of query rows,
so that 8,192 positions fit), the experts a loop over the held experts with a
dense mask, the loss mean token cross-entropy, gradients ``jax.grad``.

It takes a flat dict of arrays under the net's structural names
(``params_from_net``), the list of layer kinds and the widths.  Every layer
i: ``a = x + Attn_i(RMSNorm(x)); x' = a + MoE_i(RMSNorm(a))``; then an
RMSNorm and ``logits = h W_head^T``, the head its own matrix.

The share: ``first_expert`` and ``held`` say which experts' weights the
arrays hold (``gate_up`` (held, d, 2F), ``down`` (held, F, d)); the router
has all ``E`` outputs, the gates are normalised over all k chosen, and only
the held experts' terms of the sum are added.  What the absent experts would
have added is left out, and that partial result goes on to the next layer.

Departures, the same as the net's: no multi-token-prediction head (the
config has no key for one), no QK-norm, no load-balancing loss.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512       # rows of the score matrix held at a time


def params_from_net(net):
    """The gluon net's trained parameters as float32 arrays under their
    structural names (``layer0.mixer.qkv.weight``)."""
    return {name: jnp.asarray(p.data()._data, jnp.float32)
            for name, p in net._collect_params_with_prefix().items()
            if p.grad_req != "null"}


def rope_table(entry, dim):
    """``(f (dim/2,) float32, m)``: the inverse frequencies and the factor
    on cos and sin of one ``rope_parameters`` entry."""
    i = np.arange(dim // 2, dtype=np.float64)
    e = float(entry["rope_theta"]) ** (-2.0 * i / dim)
    if entry.get("rope_type", "default") == "default":
        return jnp.asarray(e, jnp.float32), 1.0
    assert entry["rope_type"] == "yarn", entry
    scale, original = entry["factor"], entry["original_max_position_embeddings"]

    def c(r):       # the rotary pair that turns r times over the original context
        return dim * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(entry["rope_theta"]))
    low = max(math.floor(c(entry["beta_fast"])), 0)
    high = min(math.ceil(c(entry["beta_slow"])), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    f = e / scale * ramp + e * (1.0 - ramp)
    m = entry.get("attention_factor", 0.1 * math.log(scale) + 1.0)
    return jnp.asarray(f, jnp.float32), float(m)


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotate(z, table):
    """z (B, T, H, d) at positions 0..T-1: ``z cos + rotate_half(z) sin``."""
    f, m = table
    half = z.shape[-1] // 2
    angle = jnp.arange(z.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-z[..., half:], z[..., :half]], axis=-1)
    return z * (m * jnp.cos(angle)) + turned * (m * jnp.sin(angle))


def _attention(q, k, v, window):
    """q (B, T, H, d), k and v (B, T, G, d): causal softmax attention, query
    t over keys max(0, t-window+1) .. t (``window`` None: over 0 .. t), query
    head h reading K/V head h // (H / G), scale 1/sqrt(d)."""
    b, t, heads, d = q.shape
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)
    key_at = jnp.arange(t)[None, :]

    def rows(start, q_rows):
        at = start + jnp.arange(q_rows.shape[1])[:, None]
        seen = key_at <= at
        if window is not None:
            seen &= key_at > at - window
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    if t <= QUERY_BLOCK or t % QUERY_BLOCK:
        out = rows(0, q)
    else:
        n = t // QUERY_BLOCK
        blocks = jnp.moveaxis(q.reshape(b, n, QUERY_BLOCK, heads, d), 1, 0)
        out = jax.lax.map(lambda a: rows(a[0], a[1]),
                          (jnp.arange(n) * QUERY_BLOCK, blocks))
        out = jnp.moveaxis(out, 0, 1)
    return out.reshape(b, t, heads * d)


def route(u, router, k):
    """``(w (..., E), chosen (..., k))``: the gate of every expert (0 unless
    among the token's k largest softmax probabilities, the k normalised to
    sum to 1) and the chosen experts."""
    p = jax.nn.softmax(u @ router, axis=-1)
    top, chosen = jax.lax.top_k(p, k)
    among = (chosen[..., None] == jnp.arange(p.shape[-1])).any(axis=-2)
    return jnp.where(among, p, 0.0) / top.sum(-1, keepdims=True), chosen


def moe(u, router, gate_up, down, k, first_expert):
    """The held experts' part of the expert layer's result on ``u`` (...,
    d): ``sum_e w_e W_down,e (silu(W_gate,e u) * W_up,e u)`` over the experts
    ``first_expert .. first_expert + held`` that are among the token's k."""
    w, _ = route(u, router, k)
    width = down.shape[1]
    out = jnp.zeros_like(u)
    for j in range(gate_up.shape[0]):      # every token through every expert
        h = u @ gate_up[j]
        y = (_silu(h[..., :width]) * h[..., width:]) @ down[j]
        out = out + w[..., first_expert + j, None] * y
    return out


def forward(params, layers, ids, heads, kv_heads, head_dim, window, eps, k,
            first_expert, rope, chosen=None):
    """ids (B, T) int -> logits (B, T, V) float32.  ``rope`` maps a layer
    kind to its ``rope_parameters`` entry.  ``chosen``, a list, is given each
    layer's chosen experts (B, T, k) in turn."""
    with jax.default_matmul_precision("highest"):
        tables = {kind: rope_table(entry, head_dim)
                  for kind, entry in rope.items()}
        x = params["embed.weight"][ids]
        b, t = ids.shape
        nq, nkv = heads * head_dim, kv_heads * head_dim
        for i, kind in enumerate(layers):
            at = f"layer{i}."
            u = _rms_norm(x, params[at + "norm1.gamma"], eps)
            qkv = u @ params[at + "mixer.qkv.weight"].T
            q = qkv[..., :nq].reshape(b, t, heads, head_dim)
            k_ = qkv[..., nq:nq + nkv].reshape(b, t, kv_heads, head_dim)
            v_ = qkv[..., nq + nkv:].reshape(b, t, kv_heads, head_dim)
            if kind in tables:
                q, k_ = _rotate(q, tables[kind]), _rotate(k_, tables[kind])
            if kind not in ("window", "full"):
                raise ValueError(f"layer {i}: unknown kind {kind!r}")
            out = _attention(q, k_, v_, window if kind == "window" else None)
            x = x + out @ params[at + "mixer.out_proj.weight"].T
            u = _rms_norm(x, params[at + "norm2.gamma"], eps)
            x = x + moe(u, params[at + "moe.router"],
                        params[at + "moe.gate_up"],
                        params[at + "moe.down"], k, first_expert)
            if chosen is not None:
                chosen.append(route(u, params[at + "moe.router"], k)[1])
        h = _rms_norm(x, params["norm.gamma"], eps)
        return h @ params["head_weight"].T


def loss(params, layers, ids, labels, **widths):
    """Mean token cross-entropy of ``forward``'s logits on ``labels``."""
    logp = jax.nn.log_softmax(forward(params, layers, ids, **widths), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss_and_grads(params, layers, ids, labels, **widths):
    """``loss`` and its gradient for every array of ``params``."""
    with jax.default_matmul_precision("highest"):       # the backward's too
        return jax.value_and_grad(loss)(params, layers, ids, labels, **widths)
