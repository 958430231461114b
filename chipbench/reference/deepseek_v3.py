"""The plain reference of ``family: deepseek_v3`` (Kanana-2-30B-A3B,
``model_type`` ``deepseek_v3``, huggingface.co/kakaocorp/
kanana-2-30b-a3b-instruct-2601 ``config.json``): float32 ``jax.numpy`` at
"highest" matmul precision, written from the layer equations and not from
the net.  No kernel, no sort, no grouped product (a loop over the held
experts with a dense mask), no fused projection (the latent's two parts, K's
and V's up-projections and an expert's two input matrices are each a matrix
of their own), no recomputation.  Attention is a dense masked softmax in
blocks of query rows, so that 8,192 positions fit; the loss is mean token
cross-entropy, gradients ``jax.grad``.

Every layer i, u the normed input:  ``a = x + MLA(RMSNorm(x))``; a dense
layer ``x' = a + FF(RMSNorm(a))``, an expert layer ``x' = a + Routed(u') +
Shared(u')`` with ``u' = RMSNorm(a)``; an RMSNorm after the last layer;
logits ``h W_head^T`` (untied).

- MLA, per head h:  ``[q_nope_h; q_pe_h] = (W_q u)_h``; ``c = RMSNorm(W_c
  u)`` (a learned gain), ``k_pe = W_kr u`` (one head, shared by all);
  ``k_nope_h = W_kb,h c``, ``v_h = W_vb,h c``; ``q_pe_h`` and ``k_pe``
  rotated by INTERLEAVED pairs: dims (2i, 2i+1) turn by ``t *
  theta^(-2i/d_rope)``; ``o_h = softmax_causal([q_nope_h; q_pe_h] [k_nope_h;
  k_pe]^T / sqrt(d_nope + d_rope)) v_h``; ``MLA = W_o [o_1 .. o_H]``.
- ``dense`` and ``Shared``:  ``W_2 (silu(W_1 u) * W_3 u)``.
- ``Routed``:  ``s = sigmoid(W_r u)`` over all E experts; the chosen k are
  ``top_k(s + b)`` with ``b`` the ``expert_bias`` (a buffer: no gradient);
  ``g_e = scale * s_e / (sum of the chosen s + 1e-20)``; ``sum_e g_e W_2,e
  (silu(W_1,e u) * W_3,e u)`` over the chosen experts that are HELD.

The share: ``first_expert`` and the leading size of ``w1`` / ``w2`` / ``w3``
say which experts' weights the arrays hold; the router has all E outputs, the
gates are normalised over all k chosen, and only the held experts' terms are
added.  What the absent experts would have added is left out, and that
partial result goes on to the next layer; the shared expert is whole.

Departures from the published description, each the configuration's
``assumed``: the 1e-20 (transformers' ``deepseek_v3``); the pairs of the
interleave kept in place (transformers de-interleaves Q and K alike, which
leaves every score as it is); one shared feed-forward of ``n_shared_experts
x moe_intermediate_size``; no load-balancing loss and no rule that updates
``expert_bias``.

``params_from_net`` / ``grads_to_net`` translate between the gluon net's
fused arrays and this file's own names; nothing else here knows the net.
"""
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512       # rows of the score matrix held at a time
NORM_TOPK_EPS = 1e-20


def _split(name, a, widths):
    """A fused array of the net as this file's parts, or None if it is none
    of them.  ``widths``: (heads, kv_rank, nope, v)."""
    heads, rank, nope, v = widths
    if name.endswith("mixer.kv_a.weight"):
        at = name[:-len("kv_a.weight")]
        return {at + "w_c": a[:rank], at + "w_kr": a[rank:]}
    if name.endswith("mixer.kv_b.weight"):
        at = name[:-len("kv_b.weight")]
        per_head = a.reshape(heads, nope + v, rank)
        return {at + "w_kb": per_head[:, :nope], at + "w_vb": per_head[:, nope:]}
    if name.endswith("moe.gate_up"):
        at = name[:-len("gate_up")]
        f = a.shape[-1] // 2
        return {at + "w1": a[..., :f], at + "w3": a[..., f:]}
    if name.endswith("moe.down"):
        return {name[:-len("down")] + "w2": a}
    return None


def of_net(name, a, widths):
    """One array of the net under this file's names."""
    parts = _split(name, a, widths)
    return {name: a} if parts is None else parts


def params_from_net(net, widths):
    """``(params, buffers)``: the gluon net's trained arrays in float32
    under this file's names, and its ``expert_bias`` buffers."""
    params, buffers = {}, {}
    for name, p in net._collect_params_with_prefix().items():
        a = jnp.asarray(p.data()._data)
        if name.endswith("expert_bias"):
            buffers[name] = a.astype(jnp.float32)
        elif p.grad_req != "null":
            params.update(of_net(name, a.astype(jnp.float32), widths))
    return params, buffers


def grads_to_net(grads):
    """Gradients under this file's names as the net's fused arrays."""
    out = dict(grads)
    for name in [n for n in out if n.endswith("mixer.w_c")]:
        at = name[:-len("w_c")]
        out[at + "kv_a.weight"] = jnp.concatenate(
            [out.pop(at + "w_c"), out.pop(at + "w_kr")], axis=0)
        kb, vb = out.pop(at + "w_kb"), out.pop(at + "w_vb")
        out[at + "kv_b.weight"] = jnp.concatenate([kb, vb], axis=1).reshape(
            -1, kb.shape[-1])
    for name in [n for n in out if n.endswith("moe.w1")]:
        at = name[:-len("w1")]
        out[at + "gate_up"] = jnp.concatenate(
            [out.pop(at + "w1"), out.pop(at + "w3")], axis=-1)
        out[at + "down"] = out.pop(at + "w2")
    return out


def _rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def rotate_pairs(z, theta):
    """z (B, T, H, d) at positions 0..T-1: each pair of neighbours (2i,
    2i+1) turned by the angle ``t * theta^(-2i/d)``, in its place."""
    d = z.shape[-1]
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(z.shape[1], dtype=jnp.float32)[:, None] * f[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    even, odd = z[..., 0::2], z[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                       axis=-1)
    return turned.reshape(z.shape)


def _attention(q, k, v):
    """q and k (B, T, H, d), v (B, T, H, d_v): causal softmax attention,
    scale 1/sqrt(d)."""
    b, t, heads, d = q.shape
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
    return out.reshape(b, t, -1)


def latent_attention(params, at, u, heads, nope, rope, eps, rope_theta):
    """The MLA block of layer ``at`` on ``u`` (B, T, hidden), before W_o."""
    b, t, _ = u.shape
    q = (u @ params[at + "mixer.q.weight"].T).reshape(b, t, heads, -1)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    c = _rms_norm(u @ params[at + "mixer.w_c"].T,
                  params[at + "mixer.kv_norm.gamma"], eps)
    k_pe = (u @ params[at + "mixer.w_kr"].T).reshape(b, t, 1, rope)
    k_nope = jnp.einsum("btr,hnr->bthn", c, params[at + "mixer.w_kb"])
    v = jnp.einsum("btr,hvr->bthv", c, params[at + "mixer.w_vb"])
    q = jnp.concatenate([q_nope, rotate_pairs(q_pe, rope_theta)], axis=-1)
    k_pe = jnp.broadcast_to(rotate_pairs(k_pe, rope_theta), (b, t, heads, rope))
    k = jnp.concatenate([k_nope, k_pe], axis=-1)
    return _attention(q, k, v)


def gated(u, w1, w3, w2):
    """``W_2 (silu(W_1 u) * W_3 u)`` with the matrices as ``u`` multiplies
    them: w1, w3 (d, F), w2 (F, d)."""
    return (_silu(u @ w1) * (u @ w3)) @ w2


def route(u, router, bias, k, scale=1.0):
    """``(g (..., E), chosen (..., k))``: every expert's gate (0 unless
    among the token's k largest of sigmoid score + bias; the chosen ones'
    UNBIASED scores over (their sum + 1e-20), times ``scale``) and the chosen
    experts."""
    s = jax.nn.sigmoid(u @ router)
    _, chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    among = (chosen[..., None] == jnp.arange(s.shape[-1])).any(axis=-2)
    kept = jnp.where(among, s, 0.0)
    return scale * kept / (kept.sum(-1, keepdims=True) + NORM_TOPK_EPS), chosen


def moe(u, router, bias, w1, w3, w2, k, first_expert, scale=1.0):
    """The held experts' part of the routed feed-forward on ``u`` (..., d):
    ``w1`` and ``w3`` (held, d, F), ``w2`` (held, F, d)."""
    g, _ = route(u, router, bias, k, scale)
    out = jnp.zeros_like(u)
    for j in range(w1.shape[0]):           # every token through every expert
        out = out + g[..., first_expert + j, None] * gated(u, w1[j], w3[j],
                                                           w2[j])
    return out


def forward(params, buffers, mlp_layers, ids, heads, nope, rope, eps, k,
            first_expert, rope_theta, scale=1.0, chosen=None):
    """ids (B, T) int -> logits (B, T, V) float32.  Every layer is latent
    attention; ``mlp_layers`` lists the feed-forwards (``dense`` |
    ``sparse``: routed experts and the shared one).  ``chosen``, a list, is
    given each sparse layer's chosen experts (B, T, k) in turn."""
    with jax.default_matmul_precision("highest"):
        x = params["embed.weight"][ids]
        for i, ff in enumerate(mlp_layers):
            at = f"layer{i}."
            u = _rms_norm(x, params[at + "norm1.gamma"], eps)
            out = latent_attention(params, at, u, heads, nope, rope, eps,
                                   rope_theta)
            x = x + out @ params[at + "mixer.out_proj.weight"].T
            u = _rms_norm(x, params[at + "norm2.gamma"], eps)
            if ff == "dense":
                x = x + gated(u, params[at + "mlp.gate.weight"].T,
                              params[at + "mlp.up.weight"].T,
                              params[at + "mlp.down.weight"].T)
            elif ff == "sparse":
                router = params[at + "moe.router"]
                bias = buffers[at + "moe.expert_bias"]
                x = x + moe(u, router, bias, params[at + "moe.w1"],
                            params[at + "moe.w3"], params[at + "moe.w2"], k,
                            first_expert, scale)
                x = x + gated(u, params[at + "shared_experts.gate.weight"].T,
                              params[at + "shared_experts.up.weight"].T,
                              params[at + "shared_experts.down.weight"].T)
                if chosen is not None:
                    chosen.append(route(u, router, bias, k, scale)[1])
            else:
                raise ValueError(f"layer {i}: unknown feed-forward {ff!r}")
        h = _rms_norm(x, params["norm.gamma"], eps)
        return h @ params["head_weight"].T


def loss(params, buffers, mlp_layers, ids, labels, **widths):
    """Mean token cross-entropy of ``forward``'s logits on ``labels``."""
    logp = jax.nn.log_softmax(
        forward(params, buffers, mlp_layers, ids, **widths), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss_and_grads(params, buffers, mlp_layers, ids, labels, **widths):
    """``loss`` and its gradient for every array of ``params``."""
    with jax.default_matmul_precision("highest"):       # the backward's too
        return jax.value_and_grad(loss)(params, buffers, mlp_layers, ids,
                                        labels, **widths)
