"""A sparse mixture-of-experts decoder whose layers are configuration: the
mixer of a layer is window attention, full attention, latent attention or a
gated short convolution, its feed-forward an expert layer (with or without a
shared expert beside it) or a dense gated one.  Three published
architectures run on it: Mellum2-12B-A2.5B-Instruct
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct ``config.json``,
``model_type`` ``mellum``; YaRN per Peng et al., arXiv:2309.00071),
LFM2-8B-A1B (huggingface.co/LiquidAI/LFM2-8B-A1B ``config.json``,
``model_type`` ``lfm2_moe``) and Kanana-2-30B-A3B
(huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``,
``model_type`` ``deepseek_v3``).  No reference analogue.

Every layer i, pre-norm:  ``a = x + Op_i(RMSNorm(x));  x' = a +
FF_i(RMSNorm(a))``; an RMSNorm after the last layer; logits in float32,
``h W_head^T`` with the head its own matrix or, ``tie_head``, ``h E^T`` with
the embedding.

- ``window`` / ``full``: grouped-query causal softmax attention behind one
  fused QKV projection without bias (``_attention._Attention``, shared with
  ``sambay.py``); with ``qk_norm`` an RMSNorm over each head of Q and of K,
  then Q and K rotated by a rotary table.  A ``window`` layer's query t sees
  keys t-window+1 .. t, a ``full`` layer's the whole prefix; each kind has
  its own table (``rope_parameters``: one entry a kind, as
  ``ops.rotary.rope_frequencies`` reads it).
- ``conv``: ``[B, C, v] = W_in u`` (three equal chunks, in this order); ``z =
  conv(B * v)``, a causal depthwise convolution over time with ``conv_taps``
  taps, zeros before the start, no bias, no activation; ``W_out (C * z)``.
  No heads, no K/V, no rotary table.
- ``latent``: multi-head latent attention (``_attention._LatentAttention``):
  Q by one projection, K and V from a normed latent of ``kv_lora_rank``, a
  rotary slice of ``qk_rope_head_dim`` on each Q head and on one K head that
  all share (pairs of neighbours turned together), heads of
  ``qk_nope_head_dim + qk_rope_head_dim`` for Q and K and of ``v_head_dim``
  for V through the causal flash kernels.
- ``sparse``: ``parallel.DroplessMoEFFN``: a router over all ``num_experts``
  (softmax, or sigmoid scores chosen by score + ``expert_bias``), top-k
  re-normalised, gated SwiGLU experts, no bias; with ``shared_experts`` a
  dense gated feed-forward of that width on the same normed input, added
  beside the routed experts' part (scope ``shared_experts``).  The
  decoder is told which experts it holds (``first_expert``,
  ``held_experts``: one chip's share under expert parallelism) and computes
  their part of each layer's result; that partial result goes on to the next
  layer.
- ``dense``: ``W_2 (silu(W_1 u) * W_3 u)`` of ``intermediate_size``.

The layer lists are configuration: ``published_layers(28)`` is mellum's
map, a cut is a shorter list of whole periods.  Each expert layer's
assignments per expert come back out of it and are kept as aux state by the
decoder's own forward (``parallel.publish_load`` reads them after a step), so
that a layer can be ``recompute()``d.
"""
from __future__ import annotations

import jax

from ... import initializer as init_mod
from ..block import HybridBlock
from ..nn import Embedding, RMSNorm
from ._attention import _Attention, _LatentAttention, _dense

__all__ = ["MoEDecoder", "MoEDecoderLayer", "published_layers", "KINDS",
           "FEED_FORWARDS"]

KINDS = ("window", "full", "conv", "latent")
FEED_FORWARDS = ("sparse", "dense")
_SCOPE = {"window": "window_attention", "full": "attention",
          "conv": "short_conv", "latent": "attention"}
PERIOD = ("window", "window", "window", "full")


def published_layers(n):
    """The published map for ``n`` layers: three window layers then one
    full layer, repeated (28: 21 window, 7 full)."""
    if n % len(PERIOD):
        raise ValueError(f"{n} layers are not whole periods of "
                         f"{len(PERIOD)}")
    return list(PERIOD) * (n // len(PERIOD))


class _ShortConv(HybridBlock):
    """The gated short convolution: ``W_out (C * conv(B * v))`` with ``[B, C,
    v] = W_in u``, the convolution causal, depthwise, ``taps`` long, without
    bias (``ops.causal_conv1d``; XLA fuses the two gates around it)."""

    def __init__(self, hidden, taps, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = _dense(3 * hidden, hidden)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(taps, hidden),
                init=init_mod.Normal(0.02))
            self.out_proj = _dense(hidden, hidden)

    def hybrid_forward(self, F, u, conv_weight):
        b, c, v = F.split(self.in_proj(u), num_outputs=3, axis=-1)
        with jax.named_scope("conv"):
            gated = c * F.causal_conv1d(b * v, conv_weight)
        return self.out_proj(gated)


class _GatedFFN(HybridBlock):
    """The dense feed-forward: ``W_2 (silu(W_1 u) * W_3 u)``, no bias."""

    def __init__(self, hidden, width, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.gate = _dense(width, hidden)
            self.up = _dense(width, hidden)
            self.down = _dense(hidden, width)

    def forward(self, u):
        from ... import ndarray as F
        return self.down(F.silu(self.gate(u)) * self.up(u))


class MoEDecoderLayer(HybridBlock):
    """One decoder layer: a mixer of ``kind`` and a feed-forward
    (``feed_forward``: a dict of ``DroplessMoEFFN``'s arguments for a sparse
    one, a width for a dense one), each behind its RMSNorm and added to the
    residual.  A ``sparse`` layer returns the residual and the expert layer's
    assignments per expert, a dense one the residual alone.  ``latent`` is
    a dict of ``_LatentAttention``'s widths for a ``latent`` mixer;
    ``shared`` the width of a sparse layer's shared expert (None: none)."""

    def __init__(self, index, kind, hidden, heads, kv_heads, head_dim,
                 window, eps, rope, feed_forward, qk_norm=None, conv_taps=3,
                 latent=None, shared=None, **kwargs):
        super().__init__(**kwargs)
        from ...parallel.moe import DroplessMoEFFN
        if kind not in KINDS:
            raise ValueError(f"layer {index}: kind {kind!r} is none of "
                             f"{KINDS}")
        self._index, self._kind = index, kind
        with self.name_scope():
            self.norm1 = RMSNorm(epsilon=eps, in_channels=hidden)
            if kind == "conv":
                self.mixer = _ShortConv(hidden, conv_taps)
            elif kind == "latent":
                self.mixer = _LatentAttention(hidden, heads, rope=rope,
                                              eps=eps, **latent)
            else:
                self.mixer = _Attention(
                    hidden, heads, kv_heads,
                    window if kind == "window" else None,
                    head_dim=head_dim, rope=rope, qk_norm=qk_norm)
            self.norm2 = RMSNorm(epsilon=eps, in_channels=hidden)
            if isinstance(feed_forward, dict):
                self.moe = DroplessMoEFFN(hidden, **feed_forward)
                if shared:
                    self.shared_experts = _GatedFFN(hidden, shared)
            else:
                self.mlp = _GatedFFN(hidden, feed_forward)

    @property
    def sparse(self):
        return "moe" in self._children

    def forward(self, x):
        with jax.named_scope(f"layer{self._index}"):
            with jax.named_scope(_SCOPE[self._kind]):
                out = self.mixer(self.norm1(x))
            x = x + (out[0] if isinstance(out, tuple) else out)
            if not self.sparse:
                with jax.named_scope("mlp"):
                    return x + self.mlp(self.norm2(x))
            with jax.named_scope("moe"):
                u = self.norm2(x)
                out, load = self.moe(u)
            if "shared_experts" in self._children:
                with jax.named_scope("shared_experts"):
                    out = out + self.shared_experts(u)
            x = x + out
        return x, load


class MoEDecoder(HybridBlock):
    """``forward(ids[B, T] int32) -> logits [B, T, vocab_size] float32``.

    ``layers`` is the list of mixer kinds, in order, and ``mlp_layers`` the
    list of feed-forwards beside it (``sparse`` | ``dense``; default: every
    layer sparse), a dense one of ``intermediate_size``.  ``rope_parameters``
    maps an attention kind to its rotary entry (``{"rope_type": "default" |
    "yarn", "rope_theta": ..., ...}``); a kind without an entry gets no rotary
    step, and ``conv`` takes none.  A ``latent`` layer takes the widths
    ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim`` and
    ``v_head_dim`` (``num_key_value_heads`` and ``head_dim`` are not read
    for it), its table is over ``qk_rope_head_dim`` and turns pairs of
    neighbours.  ``shared_experts`` (a width, default none)
    puts a dense gated feed-forward beside each sparse layer's experts.
    ``qk_norm`` puts an RMSNorm (eps
    ``rms_norm_eps``) on every head of Q and K.  ``held_experts`` (default:
    all) from ``first_expert`` on are the experts this decoder holds of each
    layer's ``num_experts``; ``score_function``, ``use_expert_bias``,
    ``norm_topk_eps`` and ``routed_scaling_factor`` are the router's
    (``parallel.DroplessMoEFFN``: ``score``, ``selection_bias``, ``norm_eps``,
    ``scale``).  ``tie_head`` reads the logits off the embedding and creates
    no ``head_weight``.  Every matrix is drawn normal(0.02), the embedding
    and the head too."""

    def __init__(self, vocab_size, layers, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, moe_intermediate_size,
                 num_experts, num_experts_per_tok, sliding_window=None,
                 rms_norm_eps=1e-6, rope_parameters=None, held_experts=None,
                 first_expert=0, norm_topk_prob=True, mlp_layers=None,
                 intermediate_size=None, conv_taps=3, qk_norm=False,
                 score_function="softmax", use_expert_bias=False,
                 norm_topk_eps=0.0, routed_scaling_factor=1.0,
                 tie_head=False, kv_lora_rank=None, qk_nope_head_dim=None,
                 qk_rope_head_dim=None, v_head_dim=None,
                 shared_experts=None, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        from ...ops.rotary import rope_frequencies
        layers = list(layers)
        mlp_layers = list(mlp_layers or ["sparse"] * len(layers))
        if len(mlp_layers) != len(layers) \
                or set(mlp_layers) - set(FEED_FORWARDS):
            raise ValueError(f"mlp_layers {mlp_layers}: one of "
                             f"{FEED_FORWARDS} for each of the "
                             f"{len(layers)} layers")
        if "dense" in mlp_layers and not intermediate_size:
            raise ValueError("a dense layer needs intermediate_size")
        latent = None
        if "latent" in layers:
            latent = dict(kv_rank=kv_lora_rank, nope_dim=qk_nope_head_dim,
                          rope_dim=qk_rope_head_dim, v_dim=v_head_dim)
            if not all(latent.values()):
                raise ValueError("a latent layer needs kv_lora_rank, "
                                 "qk_nope_head_dim, qk_rope_head_dim and "
                                 "v_head_dim")
        tables = {kind: rope_frequencies(
                      entry, qk_rope_head_dim if kind == "latent" else head_dim)
                  for kind, entry in (rope_parameters or {}).items()}
        if set(tables) - {"window", "full", "latent"}:
            raise ValueError(f"rope_parameters for {sorted(tables)}: the "
                             f"kinds that rotate are 'window', 'full' and "
                             f"'latent'")
        moe = dict(hidden_size=moe_intermediate_size, num_experts=num_experts,
                   k=num_experts_per_tok, held=held_experts,
                   first_expert=first_expert, renormalise=norm_topk_prob,
                   score=score_function, selection_bias=use_expert_bias,
                   norm_eps=norm_topk_eps, scale=routed_scaling_factor)
        with self.name_scope():
            self.embed = Embedding(
                vocab_size, hidden_size,
                weight_initializer=init_mod.Normal(0.02))
            self.layers = []
            for i, (kind, ff) in enumerate(zip(layers, mlp_layers)):
                layer = MoEDecoderLayer(
                    i, kind, hidden_size, num_attention_heads,
                    num_key_value_heads, head_dim, sliding_window,
                    rms_norm_eps, tables.get(kind),
                    moe if ff == "sparse" else intermediate_size,
                    qk_norm=rms_norm_eps if qk_norm else None,
                    conv_taps=conv_taps, latent=latent, shared=shared_experts,
                    prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm = RMSNorm(epsilon=rms_norm_eps, in_channels=hidden_size)
            if not tie_head:
                self.head_weight = self.params.get(
                    "head_weight", shape=(vocab_size, hidden_size),
                    init=init_mod.Normal(0.02))

    def forward(self, ids):
        from ... import ndarray as F
        with jax.named_scope("embed"):
            x = self.embed(ids)
        for layer in self.layers:
            x = layer(x)
            if layer.sparse:
                x, load = x
                # outside the layer, which may be recomputed
                layer.moe.record_load(load)
        with jax.named_scope("head"):
            weight = getattr(self, "head_weight", self.embed.weight).data()
            return F.FullyConnected(
                self.norm(x), weight, num_hidden=weight.shape[0],
                no_bias=True, flatten=False, out_dtype="float32")
