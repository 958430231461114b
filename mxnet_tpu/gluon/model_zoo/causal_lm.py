"""Small causal (decoder-only) transformer LM for the serving decode loop.

The gluon blocks (``bert.py``, ``language_model.py``) drive training-time
whole-sequence forwards through the NDArray frontend; autoregressive
*serving* needs something those forwards cannot express: an incremental
apply that threads an explicit KV cache through every layer so one new
token costs one token of compute (``serving/generate.py`` builds its
paged prefill/decode executables from the pieces here).  The model is
therefore **functional** — params are a flat dict of jnp arrays,
applies are pure — while the architecture mirrors ``BERTLayer``
(pre-LN here, fused QKV projection, GELU FFN) with a causal mask and a
weight-tied LM head (``RNNModel(tie_weights=True)``'s trick).

Layer params are stacked on a leading ``[n_layers, ...]`` axis so the
serving decode loop can index or scan them inside one compiled program.
Full-sequence attention reuses ``ops.multi_head_attention`` (the BERT
hot path); single-token decode attention is
``ops.paged_decode_attention`` over the serving page pool.

**Tensor parallelism (ISSUE 14).**  Every apply here takes an optional
``reduce`` hook: ``None`` is the single-chip path (bit-identical to the
pre-TP code), a callable is the Megatron shape — QKV and FFN-in weights
column-sharded over the ``tp`` mesh axis (each device computes its OWN
heads' q/k/v and its own slice of the FFN hidden), output/FFN-out
weights row-sharded so each device holds a partial product, and
``reduce`` (an all-reduce over ``tp``) restores the replicated hidden —
the standard two collectives per layer.  Row-parallel biases (``bo``,
``b2``) are added once, AFTER the reduce, never per shard.  The local
head count is derived from the (possibly sharded) ``wqkv`` argument
shape, so one body serves every shard count.  ``tp_shard_params`` is
the host-side one-time relayout + placement: ``wqkv``/``bqkv`` columns
are permuted into shard-grouped ``[q_s | k_s | v_s]`` order so a plain
contiguous ``PartitionSpec`` chunk hands each device its own heads'
fused projection (``causal_lm_tp_rules`` in ``parallel.sharding`` is
the spec table).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.registry import OPS

__all__ = ["CausalLMConfig", "init_causal_lm", "prefill_forward",
           "sequence_logits", "decode_hidden", "lm_logits",
           "draft_config", "window_logits", "verify_logits",
           "tp_param_specs", "tp_permute_qkv", "tp_shard_params",
           "tp_validate"]

_mha = OPS["multi_head_attention"]


@dataclasses.dataclass(frozen=True)
class CausalLMConfig:
    """Static architecture hyperparameters (hashable, so builders can
    close over an instance and stay jit-cache-friendly)."""
    vocab_size: int = 256
    n_layers: int = 2
    n_heads: int = 2
    head_dim: int = 16
    d_ff: int = 64

    @property
    def d_model(self) -> int:
        return self.n_heads * self.head_dim


def init_causal_lm(config: CausalLMConfig, seed: int = 0) -> dict:
    """Random-init params: a flat dict of jnp arrays, per-layer weights
    stacked on axis 0 (``[n_layers, ...]``)."""
    c = config
    d, ff, L = c.d_model, c.d_ff, c.n_layers
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    s = 0.02

    def norm(key, shape):
        return (s * jax.random.normal(key, shape)).astype(jnp.float32)

    return {
        "embed": norm(keys[0], (c.vocab_size, d)),
        "wqkv": norm(keys[1], (L, d, 3 * d)),
        "bqkv": jnp.zeros((L, 3 * d), jnp.float32),
        "wo": norm(keys[2], (L, d, d)),
        "bo": jnp.zeros((L, d), jnp.float32),
        "ln1_s": jnp.ones((L, d), jnp.float32),
        "ln1_b": jnp.zeros((L, d), jnp.float32),
        "ln2_s": jnp.ones((L, d), jnp.float32),
        "ln2_b": jnp.zeros((L, d), jnp.float32),
        "w1": norm(keys[3], (L, d, ff)),
        "b1": jnp.zeros((L, ff), jnp.float32),
        "w2": norm(keys[4], (L, ff, d)),
        "b2": jnp.zeros((L, d), jnp.float32),
        "lnf_s": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
    }


def _ln(x, scale, bias, eps=1e-6):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _ffn(x, w1, b1, w2, b2):
    return jax.nn.gelu(x @ w1 + b1) @ w2 + b2


def _layer_tail(params, layer, h, ctx, reduce):
    """Residual + output projection + FFN tail of one layer, shared by
    the decode and whole-sequence paths (``ctx`` already merged to
    ``[..., d_local]``): ``reduce=None`` keeps the exact single-chip
    expression order; a callable reduces the two row-parallel partial
    products, with the row-parallel biases (``bo``, ``b2``) added once
    AFTER it, never per shard.  One body — the TP token-parity
    contract cannot diverge between prefill and decode."""
    with jax.named_scope("attention"):
        proj = ctx @ params["wo"][layer]
        h = h + (proj if reduce is None else reduce(proj)) \
            + params["bo"][layer]
    with jax.named_scope("mlp"):
        x2 = _ln(h, params["ln2_s"][layer], params["ln2_b"][layer])
        if reduce is None:
            return h + _ffn(x2, params["w1"][layer], params["b1"][layer],
                            params["w2"][layer], params["b2"][layer])
        return h + reduce(jax.nn.gelu(x2 @ params["w1"][layer]
                                      + params["b1"][layer])
                          @ params["w2"][layer]) + params["b2"][layer]


def lm_logits(params, h):
    """Weight-tied LM head: hidden → vocab logits through the embedding
    matrix (``RNNModel(tie_weights=True)``)."""
    return _ln(h, params["lnf_s"], params["lnf_b"]) @ params["embed"].T


def decode_hidden(params, layer, h, attend, reduce=None):
    """One pre-LN transformer layer for a SINGLE token position.

    ``h`` is ``[slots, d_model]``; ``attend(k, v) -> ctx`` is the
    caller's cache hook: it receives this layer's new per-slot K/V
    (``[slots, heads, head_dim]`` — LOCAL heads under tensor
    parallelism), owns writing them into its cache (paged pool or dense
    stripe), and returns the attention context over that cache.
    Splitting here keeps the model free of any cache layout while the
    serving layer stays free of the architecture.

    ``reduce`` is the tensor-parallel all-reduce hook (see the module
    docstring): ``None`` keeps the exact single-chip expression order;
    a callable reduces the two row-parallel partial products, with the
    row-parallel biases added once after it."""
    # device-side names (``layer<i>/attention``, ``.../mlp``; the caller's
    # ``attend`` adds ``.../kv_write``): a profile reads them per layer
    with jax.named_scope(f"layer{layer}"):
        with jax.named_scope("attention"):
            x = _ln(h, params["ln1_s"][layer], params["ln1_b"][layer])
            qkv = x @ params["wqkv"][layer] + params["bqkv"][layer]
            q, k, v = jnp.split(qkv, 3, axis=-1)
        slots = h.shape[0]
        ctx = attend(q, k, v)               # [slots, H_local, D] resolved
        return _layer_tail(params, layer, h, ctx.reshape(slots, -1), reduce)


def _stack_forward(params, config: CausalLMConfig, tokens, lengths,
                   reduce=None):
    """The shared whole-sequence transformer stack: causal
    ``ops.multi_head_attention`` with positions beyond a row's
    ``lengths`` masked as keys (``lengths=None`` = every position
    valid).  Returns ``(h [b, L, d], k_all, v_all)`` with K/V stacked
    ``[n_layers, b, L, heads, head_dim]`` — LOCAL heads when ``reduce``
    (the tensor-parallel all-reduce hook) is given; the head count is
    derived from the ``wqkv`` argument, not the config, so sharded and
    replicated params run the same body."""
    c = config
    b, L = tokens.shape
    heads = params["wqkv"].shape[-1] // 3 // c.head_dim     # local under tp
    h = params["embed"][tokens]                   # [b, L, d]
    if lengths is None:
        mask = jnp.ones((b, 1, 1, L), jnp.float32)
    else:
        mask = (jnp.arange(L)[None, :]
                < lengths[:, None]).astype(jnp.float32)[:, None, None, :]
    ks, vs = [], []
    for layer in range(c.n_layers):
        with jax.named_scope(f"layer{layer}"):
            with jax.named_scope("attention"):
                x = _ln(h, params["ln1_s"][layer], params["ln1_b"][layer])
                qkv = x @ params["wqkv"][layer] + params["bqkv"][layer]
                q, k, v = jnp.split(qkv, 3, axis=-1)  # each [b, L, d_local]
                ks.append(k.reshape(b, L, heads, c.head_dim))
                vs.append(v.reshape(b, L, heads, c.head_dim))
                ctx = _mha(q, k, v, mask=mask, heads=heads, causal=True,
                           dropout=0.0, training=False)
            h = _layer_tail(params, layer, h, ctx, reduce)
    return h, jnp.stack(ks), jnp.stack(vs)


def prefill_forward(params, config: CausalLMConfig, tokens, lengths,
                    reduce=None):
    """Whole-prompt forward: ``tokens [b, L]`` int32, ``lengths [b]``.

    Returns ``(logits_last [b, vocab], k_all, v_all)`` with K/V stacked
    ``[n_layers, b, L, heads, head_dim]`` — everything the serving
    layer needs to seed its cache and sample the first new token.  The
    "last" hidden state is gathered at ``lengths - 1``.  Under tensor
    parallelism (``reduce`` given) the returned K/V carry only the
    device's OWN head shard — exactly what its shard of the paged pool
    stores."""
    b, L = tokens.shape
    h, ks, vs = _stack_forward(params, config, tokens, lengths,
                               reduce=reduce)
    last = jnp.clip(lengths - 1, 0, L - 1)
    h_last = h[jnp.arange(b), last]               # [b, d]
    return lm_logits(params, h_last), ks, vs


def draft_config(config: CausalLMConfig, *, n_layers=1, n_heads=None,
                 head_dim=None, d_ff=None) -> CausalLMConfig:
    """The DRAFT-model constructor for speculative decoding: a smaller
    config in the same family sharing the target's vocabulary (the
    acceptance test compares distributions over the same token space —
    a vocab mismatch can never be exact, so it is not a parameter).
    Defaults shrink depth only; width knobs override the target's."""
    return CausalLMConfig(
        vocab_size=config.vocab_size,
        n_layers=int(n_layers),
        n_heads=config.n_heads if n_heads is None else int(n_heads),
        head_dim=config.head_dim if head_dim is None else int(head_dim),
        d_ff=config.d_ff if d_ff is None else int(d_ff))


def window_logits(params, config: CausalLMConfig, tokens, n_valid,
                  reduce=None):
    """Last-position next-token logits over a RIGHT-ALIGNED dense token
    window ``tokens [S, W]`` with ``n_valid [S]`` trailing entries
    valid — the draft model's forward in the speculative verify step:
    no KV cache, no page pool, just a bounded re-read of recent
    context.  Right alignment keeps the newest token at position
    ``W - 1``, so "the last position" needs no gather; the mask
    invalidates the ``W - n_valid`` leading slots as KEYS, and with a
    causal mask on top the last position attends to exactly the valid
    suffix.  Returns ``[S, vocab]``."""
    S, W = tokens.shape
    heads = params["wqkv"].shape[-1] // 3 // config.head_dim
    h = params["embed"][tokens]                           # [S, W, d]
    mask = (jnp.arange(W)[None, :]
            >= (W - n_valid)[:, None]).astype(jnp.float32)[:, None,
                                                           None, :]
    for layer in range(config.n_layers):
        x = _ln(h, params["ln1_s"][layer], params["ln1_b"][layer])
        qkv = x @ params["wqkv"][layer] + params["bqkv"][layer]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        ctx = _mha(q, k, v, mask=mask, heads=heads, causal=True,
                   dropout=0.0, training=False)
        h = _layer_tail(params, layer, h, ctx, reduce)
    return lm_logits(params, h[:, -1])


def verify_logits(params, config: CausalLMConfig, tokens, attend,
                  reduce=None):
    """Next-token logits at EVERY position of a candidate block
    ``tokens [S, K1]`` — the TARGET model's forward in the speculative
    verify step.  The ``S * K1`` lanes flatten into one
    ``decode_hidden`` stack pass; ``attend(layer, q, k, v) -> ctx`` is
    the caller's cache hook over the flattened lanes (it owns the paged
    pool writes and per-lane causal masking via attention lengths —
    exactly the ``decode_hidden`` contract, plus the layer index so one
    hook serves the whole stack).  Returns ``[S, K1, vocab]``."""
    S, K1 = tokens.shape
    h = params["embed"][tokens].reshape(S * K1, -1)
    for layer in range(config.n_layers):
        h = decode_hidden(
            params, layer, h,
            (lambda q, k, v, _l=layer: attend(_l, q, k, v)),
            reduce=reduce)
    return lm_logits(params, h).reshape(S, K1, -1)


def sequence_logits(params, config: CausalLMConfig, tokens,
                    lengths=None):
    """Next-token logits for EVERY position, ``[b, L, vocab]`` — the
    training-side apply (differentiate a cross-entropy over this with
    plain ``jax.grad``; examples/serve_llm.py does exactly that)."""
    h, _, _ = _stack_forward(params, config, tokens, lengths)
    return lm_logits(params, h)


# ----------------------------------------------------- tensor parallelism --
def tp_validate(config: CausalLMConfig, shards: int):
    """Raise ``ValueError`` when this architecture cannot shard
    ``shards`` ways: attention shards by WHOLE heads and the FFN hidden
    by contiguous slices, so both must divide."""
    if shards < 1:
        raise ValueError(f"tp shards must be >= 1, got {shards}")
    if config.n_heads % shards:
        raise ValueError(
            f"n_heads {config.n_heads} not divisible by tp shards "
            f"{shards} — head-parallel attention shards whole heads")
    if config.d_ff % shards:
        raise ValueError(
            f"d_ff {config.d_ff} not divisible by tp shards {shards}")


def tp_permute_qkv(params, config: CausalLMConfig, shards: int):
    """Host-side one-time relayout of the fused QKV projection: permute
    ``wqkv``/``bqkv`` columns from ``[q | k | v]`` (each head-major)
    into shard-grouped ``[q_0 k_0 v_0 | q_1 k_1 v_1 | ...]`` order, so
    the plain contiguous chunk a ``PartitionSpec`` hands each device is
    that device's own heads' q, k, AND v — and ``jnp.split(qkv, 3)``
    inside the sharded program still works unchanged.  ``shards == 1``
    is the identity (the permutation is its own single-group order).
    Returns a NEW dict; the inputs are never mutated."""
    tp_validate(config, shards)
    if shards == 1:
        return dict(params)
    d, hd = config.d_model, config.head_dim
    per = config.n_heads // shards * hd           # shard-local width
    idx = np.concatenate([np.arange(part * d + s * per,
                                    part * d + (s + 1) * per)
                          for s in range(shards) for part in range(3)])
    out = dict(params)
    out["wqkv"] = jnp.asarray(params["wqkv"])[..., idx]
    out["bqkv"] = jnp.asarray(params["bqkv"])[..., idx]
    return out


def tp_param_specs(config: CausalLMConfig, mesh, axis: str = "tp"):
    """``PartitionSpec`` per param name for the Megatron layout —
    ``causal_lm_tp_rules`` (parallel.sharding) applied to this
    architecture's shapes (``jax.eval_shape``: zero device work).
    Everything the rules don't name (embeddings, norms, row-parallel
    biases) replicates."""
    from ...parallel.sharding import causal_lm_tp_rules

    rules = causal_lm_tp_rules(axis)
    shapes = jax.eval_shape(lambda: init_causal_lm(config, 0))
    return {k: rules.spec_for(k, v.shape, mesh)
            for k, v in shapes.items()}


def tp_shard_params(params, config: CausalLMConfig, mesh,
                    axis: str = "tp"):
    """Place params for tensor-parallel serving: permute the fused QKV
    into shard-grouped order, then ``device_put`` every leaf with its
    ``tp_param_specs`` sharding — committed sharded arrays, so the
    serving programs never re-transfer them per call."""
    from jax.sharding import NamedSharding

    shards = int(mesh.shape[axis])
    p = tp_permute_qkv(params, config, shards)
    specs = tp_param_specs(config, mesh, axis)
    return {k: jax.device_put(jnp.asarray(v),
                              NamedSharding(mesh, specs[k]))
            for k, v in p.items()}
