"""The plain reference of ``family: sambay`` (Phi-4-mini-flash-reasoning,
SambaY, arXiv:2507.06607; Mamba, arXiv:2312.00752; the cross-decoder after
YOCO, arXiv:2405.05254): float32 ``jax.numpy`` at "highest" matmul
precision, no kernel, no chunking, no recomputation.  The scan is a
``lax.scan`` over single positions, attention a dense masked softmax (in
blocks of query rows, so that 4,096 positions fit), the loss mean token
cross-entropy, gradients ``jax.grad``.

It takes a flat dict of arrays under the net's structural names
(``params_from_net``) and the list of layer kinds.  Every layer i:
``x += Mixer_i(LN(x)); x += W_down(silu(g) * v)``, ``[g, v] = W_gate_up
LN'(x)``; then a LayerNorm and ``logits = h E^T``.

Departures from the paper, the same as the net's: plain softmax attention
(no differential attention), no positional encoding, the memory taken after
the D skip and before the gate, the window counting the query itself.
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512       # rows of the score matrix held at a time


def params_from_net(net):
    """The gluon net's parameters as float32 arrays under their structural
    names (``layer0.mixer.in_proj.weight``)."""
    return {name: jnp.asarray(p.data()._data, jnp.float32)
            for name, p in net._collect_params_with_prefix().items()}


def _layer_norm(x, gamma, beta, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _attention(q, k, v, heads, kv_heads, window):
    """q (B, T, H*d), k and v (B, T, H_kv*d): causal softmax attention,
    query t over keys max(0, t-window+1) .. t (``window`` None: over 0 ..
    t), query head h reading K/V head h // (H / H_kv), scale 1/sqrt(d)."""
    b, t, _ = q.shape
    d = q.shape[-1] // heads
    q = q.reshape(b, t, heads, d)
    k = jnp.repeat(k.reshape(b, t, kv_heads, d), heads // kv_heads, axis=2)
    v = jnp.repeat(v.reshape(b, t, kv_heads, d), heads // kv_heads, axis=2)
    key_at = jnp.arange(t)[None, :]

    def rows(start, q_rows):
        at = start + jnp.arange(q_rows.shape[1])[:, None]
        seen = key_at <= at
        if window is not None:
            seen &= key_at > at - window
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / jnp.sqrt(1.0 * d)
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


def _mamba(p, u):
    """Returns the mixer's output and the scan's output y."""
    inner, state = p["a_log"].shape
    rank = p["dt_weight"].shape[1]
    taps = p["conv_weight"].shape[0]
    t = u.shape[1]
    xz = u @ p["in_proj.weight"].T
    xs, z = xz[..., :inner], xz[..., inner:]
    padded = jnp.pad(xs, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + t] * p["conv_weight"][k] for k in range(taps))
    xc = _silu(conv + p["conv_bias"])
    proj = xc @ p["x_proj.weight"].T
    r, bm, cm = (proj[..., :rank], proj[..., rank:rank + state],
                 proj[..., rank + state:])
    dt = jax.nn.softplus(r @ p["dt_weight"].T + p["dt_bias"])
    a = -jnp.exp(p["a_log"])                                    # (inner, N)

    def step(h, at):                                            # h (B, inner, N)
        dt_t, x_t, b_t, c_t = at
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h0 = jnp.zeros((u.shape[0], inner, state), jnp.float32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a_, 1, 0)
                                        for a_ in (dt, xc, bm, cm)))
    y = jnp.moveaxis(y, 0, 1) + p["d"] * xc
    return (y * _silu(z)) @ p["out_proj.weight"].T, y


def forward(params, layers, ids, heads, kv_heads, window, eps):
    """ids (B, T) int -> logits (B, T, V) float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed.weight"][ids]
        memory = k = v = None
        for i, kind in enumerate(layers):
            at = f"layer{i}."
            p = {n[len(at + "mixer."):]: a for n, a in params.items()
                 if n.startswith(at + "mixer.")}
            u = _layer_norm(x, params[at + "norm1.gamma"],
                            params[at + "norm1.beta"], eps)
            if kind == "mamba":
                out, memory = _mamba(p, u)
            elif kind in ("window", "full"):
                hidden = u.shape[-1]
                kv = hidden // heads * kv_heads
                qkv = u @ p["qkv.weight"].T
                q, k_, v_ = (qkv[..., :hidden], qkv[..., hidden:hidden + kv],
                             qkv[..., hidden + kv:])
                out = _attention(q, k_, v_, heads, kv_heads,
                                 window if kind == "window" else None)
                out = out @ p["out_proj.weight"].T
                if kind == "full":
                    k, v = k_, v_
            elif kind == "gmu":
                out = (memory * _silu(u @ p["in_proj.weight"].T)) \
                    @ p["out_proj.weight"].T
            elif kind == "cross":
                out = _attention(u @ p["q_proj.weight"].T, k, v, heads,
                                 kv_heads, None) @ p["out_proj.weight"].T
            else:
                raise ValueError(f"layer {i}: unknown kind {kind!r}")
            x = x + out
            gu = _layer_norm(x, params[at + "norm2.gamma"],
                             params[at + "norm2.beta"], eps) \
                @ params[at + "gate_up.weight"].T
            half = gu.shape[-1] // 2
            x = x + (_silu(gu[..., :half]) * gu[..., half:]) \
                @ params[at + "down.weight"].T
        h = _layer_norm(x, params["norm.gamma"], params["norm.beta"], eps)
        return h @ params["embed.weight"].T


def loss(params, layers, ids, labels, **widths):
    """Mean token cross-entropy of ``forward``'s logits on ``labels``."""
    logp = jax.nn.log_softmax(forward(params, layers, ids, **widths), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


def loss_and_grads(params, layers, ids, labels, **widths):
    """``loss`` and its gradient for every array of ``params``."""
    with jax.default_matmul_precision("highest"):       # the backward's too
        return jax.value_and_grad(loss)(params, layers, ids, labels, **widths)
