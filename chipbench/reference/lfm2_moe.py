"""The plain reference of ``family: lfm2_moe`` (LFM2-8B-A1B, ``model_type``
``lfm2_moe``, huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``): float32
``jax.numpy`` at "highest" matmul precision, written from the layer equations
and not from the net.  No kernel, no sort, no grouped product (a loop over the
held experts with a dense mask), no fused projection (Q, K and V, the
operator's three chunks and an expert's two input matrices are each a matrix
of their own), no recomputation.  Attention is a dense masked softmax in
blocks of query rows, so that 8,192 positions fit; the loss is mean token
cross-entropy, gradients ``jax.grad``.

Every layer i, u the normed input:  ``a = x + Op_i(RMSNorm(x));  x' = a +
FF_i(RMSNorm(a))``; an RMSNorm after the last layer; logits ``h E^T`` with
the embedding E (tied).

- ``conv``:  ``B = W_B u, C = W_C u, v = W_v u``; ``z[t] = sum_j w[j] (B *
  v)[t - (L-1) + j]`` over the L taps, zeros before the start, no bias, no
  activation; ``Op = W_out (C * z)``.
- ``full``:  ``q = W_q u, k = W_k u, v = W_v u``; an RMSNorm with a learned
  gain over each head of q and of k; rotate-half rotary on both from the plain
  table at ``rope_theta``; causal grouped-query softmax attention, scale
  ``1 / sqrt(head_dim)``; ``W_o``.
- ``dense``:  ``W_2 (silu(W_1 u) * W_3 u)``.
- ``sparse``: ``s = sigmoid(W_r u)`` over all E experts; the chosen k are
  ``top_k(s + b)`` with ``b`` the ``expert_bias`` (a buffer: no gradient);
  ``g_e = scale * s_e / (sum of the chosen s + 1e-6)``; ``FF = sum_e g_e
  W_2,e (silu(W_1,e u) * W_3,e u)`` over the chosen experts that are HELD.

The share: ``first_expert`` and the leading size of ``w1`` / ``w2`` / ``w3``
say which experts' weights the arrays hold; the router has all E outputs, the
gates are normalised over all k chosen, and only the held experts' terms are
added.  What the absent experts would have added is left out, and that
partial result goes on to the next layer.

Departures from the published description, each the configuration's
``assumed``: the head is tied to the embedding (the parameter count says so,
the catalog's config lacks the key); the order of the operator's chunks is
[B, C, v]; the 1e-6 in the gates' divisor; no load-balancing loss and no rule
that updates ``expert_bias``.

``params_from_net`` / ``grads_to_net`` translate between the gluon net's
fused arrays and this file's own names; nothing else here knows the net.
"""
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512       # rows of the score matrix held at a time
NORM_TOPK_EPS = 1e-6


# the net's fused arrays, this file's names for their parts, and the axis
# along which the parts lie
FUSED = (("mixer.qkv.weight", ("mixer.w_q", "mixer.w_k", "mixer.w_v"), 0),
         ("mixer.in_proj.weight", ("mixer.w_B", "mixer.w_C", "mixer.w_v"), 0),
         ("moe.gate_up", ("moe.w1", "moe.w3"), -1),
         ("moe.down", ("moe.w2",), 0))


def _of_net(name, a, heads, kv_heads, head_dim):
    """One array of the net under this file's names: a fused matrix as its
    parts (equal ones, but for Q against K and V)."""
    for fused, parts, axis in FUSED:
        if name.endswith(fused):
            cuts = [heads * head_dim, (heads + kv_heads) * head_dim] \
                if fused == "mixer.qkv.weight" else len(parts)
            at = name[:-len(fused)]
            return dict(zip([at + p for p in parts],
                            jnp.split(a, cuts, axis=axis)))
    return {name: a}


def params_from_net(net, heads, kv_heads, head_dim):
    """``(params, buffers)``: the gluon net's trained arrays in float32
    under this file's names, and its ``expert_bias`` buffers."""
    params, buffers = {}, {}
    for name, p in net._collect_params_with_prefix().items():
        a = jnp.asarray(p.data()._data)
        if name.endswith("expert_bias"):
            buffers[name] = a.astype(jnp.float32)
        elif p.grad_req != "null":
            params.update(_of_net(name, a.astype(jnp.float32), heads,
                                  kv_heads, head_dim))
    return params, buffers


def grads_to_net(grads):
    """Gradients under this file's names as the net's fused arrays."""
    out = dict(grads)
    for fused, parts, axis in FUSED:
        for first in [n for n in out if n.endswith(parts[0])]:
            at = first[:-len(parts[0])]
            out[at + fused] = jnp.concatenate(
                [out.pop(at + p) for p in parts], axis=axis)
    return out


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rotate(z, theta):
    """z (B, T, H, d) at positions 0..T-1: ``z cos + rotate_half(z) sin``,
    the angles ``t * theta^(-2i/d)``."""
    d = z.shape[-1]
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(z.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-z[..., d // 2:], z[..., :d // 2]], axis=-1)
    return z * jnp.cos(angle) + turned * jnp.sin(angle)


def _attention(q, k, v):
    """q (B, T, H, d), k and v (B, T, G, d): causal softmax attention, query
    head h reading K/V head h // (H / G), scale 1/sqrt(d)."""
    b, t, heads, d = q.shape
    k = jnp.repeat(k, heads // k.shape[2], axis=2)
    v = jnp.repeat(v, heads // v.shape[2], axis=2)
    key_at = jnp.arange(t)[None, :]

    def rows(start, q_rows):
        at = start + jnp.arange(q_rows.shape[1])[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(key_at <= at, scores, -jnp.inf), axis=-1)
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


def short_conv(x, w):
    """``z[t] = sum_j w[j] x[t - (L-1) + j]`` along time, x (B, T, D), w (L,
    D), zeros before the start."""
    taps, t = w.shape[0], x.shape[1]
    z = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        z = z + w[j] * jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
    return z


def route(u, router, bias, k, scale=1.0):
    """``(g (..., E), chosen (..., k))``: every expert's gate (0 unless
    among the token's k largest of sigmoid score + bias; the chosen ones'
    UNBIASED scores over (their sum + 1e-6), times ``scale``) and the chosen
    experts."""
    s = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    among = (chosen[..., None] == jnp.arange(s.shape[-1])).any(axis=-2)
    kept = jnp.where(among, s, 0.0)
    return scale * kept / (kept.sum(-1, keepdims=True) + NORM_TOPK_EPS), chosen


def moe(u, router, bias, w1, w3, w2, k, first_expert, scale=1.0):
    """The held experts' part of the sparse feed-forward on ``u`` (..., d):
    ``w1`` and ``w3`` (held, d, F), ``w2`` (held, F, d)."""
    g, _ = route(u, router, bias, k, scale)
    out = jnp.zeros_like(u)
    for j in range(w1.shape[0]):           # every token through every expert
        y = (_silu(u @ w1[j]) * (u @ w3[j])) @ w2[j]
        out = out + g[..., first_expert + j, None] * y
    return out


def forward(params, buffers, layers, mlp_layers, ids, heads, kv_heads,
            head_dim, eps, k, first_expert, rope_theta, scale=1.0,
            chosen=None):
    """ids (B, T) int -> logits (B, T, V) float32.  ``layers`` lists the
    operators (``conv`` | ``full``), ``mlp_layers`` the feed-forwards
    (``dense`` | ``sparse``).  ``chosen``, a list, is given each sparse
    layer's chosen experts (B, T, k) in turn."""
    with jax.default_matmul_precision("highest"):
        x = params["embed.weight"][ids]
        b, t = ids.shape
        for i, (kind, ff) in enumerate(zip(layers, mlp_layers)):
            at = f"layer{i}."
            u = _rms_norm(x, params[at + "norm1.gamma"], eps)
            if kind == "conv":
                gate_in = u @ params[at + "mixer.w_B"].T
                gate_out = u @ params[at + "mixer.w_C"].T
                value = u @ params[at + "mixer.w_v"].T
                z = short_conv(gate_in * value,
                               params[at + "mixer.conv_weight"])
                out = gate_out * z
            elif kind == "full":
                q = (u @ params[at + "mixer.w_q"].T).reshape(
                    b, t, heads, head_dim)
                k_ = (u @ params[at + "mixer.w_k"].T).reshape(
                    b, t, kv_heads, head_dim)
                v_ = (u @ params[at + "mixer.w_v"].T).reshape(
                    b, t, kv_heads, head_dim)
                q = _rms_norm(q, params[at + "mixer.q_norm.gamma"], eps)
                k_ = _rms_norm(k_, params[at + "mixer.k_norm.gamma"], eps)
                out = _attention(_rotate(q, rope_theta),
                                 _rotate(k_, rope_theta), v_)
            else:
                raise ValueError(f"layer {i}: unknown operator {kind!r}")
            x = x + out @ params[at + "mixer.out_proj.weight"].T
            u = _rms_norm(x, params[at + "norm2.gamma"], eps)
            if ff == "dense":
                hidden = _silu(u @ params[at + "mlp.gate.weight"].T) \
                    * (u @ params[at + "mlp.up.weight"].T)
                x = x + hidden @ params[at + "mlp.down.weight"].T
            elif ff == "sparse":
                router = params[at + "moe.router"]
                bias = buffers[at + "moe.expert_bias"]
                x = x + moe(u, router, bias, params[at + "moe.w1"],
                            params[at + "moe.w3"], params[at + "moe.w2"], k,
                            first_expert, scale)
                if chosen is not None:
                    chosen.append(route(u, router, bias, k, scale)[1])
            else:
                raise ValueError(f"layer {i}: unknown feed-forward {ff!r}")
        h = _rms_norm(x, params["norm.gamma"], eps)
        return h @ params["embed.weight"].T


def loss(params, buffers, layers, mlp_layers, ids, labels, **widths):
    """Mean token cross-entropy of ``forward``'s logits on ``labels``."""
    logp = jax.nn.log_softmax(
        forward(params, buffers, layers, mlp_layers, ids, **widths), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss_and_grads(params, buffers, layers, mlp_layers, ids, labels,
                   **widths):
    """``loss`` and its gradient for every array of ``params``."""
    with jax.default_matmul_precision("highest"):       # the backward's too
        return jax.value_and_grad(loss)(params, buffers, layers, mlp_layers,
                                        ids, labels, **widths)
