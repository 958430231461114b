"""A sparse mixture-of-experts decoder with window and full attention mixed
(the architecture of Mellum2-12B-A2.5B-Instruct, huggingface.co/JetBrains/
Mellum2-12B-A2.5B-Instruct ``config.json``, ``model_type`` ``mellum``; YaRN
per Peng et al., arXiv:2309.00071).  No reference analogue.

Every layer i, pre-norm:  ``a = x + Attn_i(RMSNorm(x));  x' = a +
MoE_i(RMSNorm(a))``; an RMSNorm after the last layer; logits ``h W_head^T``
in float32, the head its own matrix (untied).

- Attention: grouped-query causal softmax attention behind one fused QKV
  projection without bias (``_attention._Attention``, shared with
  ``sambay.py``); Q and K rotated by a rotary table in front of it.  A
  ``window`` layer's query t sees keys t-window+1 .. t, a ``full`` layer's
  the whole prefix; each kind has its own table (``rope_parameters``: one
  entry a kind, as ``ops.rotary.rope_frequencies`` reads it).
- MoE: ``parallel.DroplessMoEFFN``: a softmax router over all
  ``num_experts``, top-k re-normalised, gated SwiGLU experts, no shared
  expert, no bias.  The decoder is told which experts it holds
  (``first_expert``, ``held_experts``: one chip's share under expert
  parallelism) and computes their part of each layer's result; that partial
  result goes on to the next layer.

The layer list is configuration: ``published_layers(28)`` is the published
map, a cut is a shorter list of whole periods.  Each layer's assignments
per expert come back out of it and are kept as aux state by the decoder's
own forward (``parallel.publish_load`` reads them after a step), so that a
layer can be ``recompute()``d.
"""
from __future__ import annotations

import jax

from ... import initializer as init_mod
from ..block import HybridBlock
from ..nn import Embedding, RMSNorm
from ._attention import _Attention

__all__ = ["MoEDecoder", "MoEDecoderLayer", "published_layers", "KINDS"]

KINDS = ("window", "full")
_SCOPE = {"window": "window_attention", "full": "attention"}
PERIOD = ("window", "window", "window", "full")


def published_layers(n):
    """The published map for ``n`` layers: three window layers then one
    full layer, repeated (28: 21 window, 7 full)."""
    if n % len(PERIOD):
        raise ValueError(f"{n} layers are not whole periods of "
                         f"{len(PERIOD)}")
    return list(PERIOD) * (n // len(PERIOD))


class MoEDecoderLayer(HybridBlock):
    """One decoder layer: attention of ``kind`` and the expert layer, each
    behind its RMSNorm and added to the residual.  Returns the residual and
    the expert layer's assignments per expert."""

    def __init__(self, index, kind, hidden, heads, kv_heads, head_dim,
                 window, eps, rope, expert_width, num_experts, k, held,
                 first_expert, renormalise, **kwargs):
        super().__init__(**kwargs)
        from ...parallel.moe import DroplessMoEFFN
        if kind not in KINDS:
            raise ValueError(f"layer {index}: kind {kind!r} is none of "
                             f"{KINDS}")
        self._index, self._kind = index, kind
        with self.name_scope():
            self.norm1 = RMSNorm(epsilon=eps, in_channels=hidden)
            self.mixer = _Attention(
                hidden, heads, kv_heads, window if kind == "window" else None,
                head_dim=head_dim, rope=rope)
            self.norm2 = RMSNorm(epsilon=eps, in_channels=hidden)
            self.moe = DroplessMoEFFN(
                hidden, expert_width, num_experts, k, held=held,
                first_expert=first_expert, renormalise=renormalise)

    def forward(self, x):
        with jax.named_scope(f"layer{self._index}"):
            with jax.named_scope(_SCOPE[self._kind]):
                out = self.mixer(self.norm1(x))
            x = x + (out[0] if isinstance(out, tuple) else out)
            with jax.named_scope("moe"):
                out, load = self.moe(self.norm2(x))
            x = x + out
        return x, load


class MoEDecoder(HybridBlock):
    """``forward(ids[B, T] int32) -> logits [B, T, vocab_size] float32``.

    ``layers`` is the list of layer kinds, in order.  ``rope_parameters``
    maps a kind to its rotary entry (``{"rope_type": "default" | "yarn",
    "rope_theta": ..., ...}``); a kind without an entry gets no rotary
    step.  ``held_experts`` (default: all) from ``first_expert`` on are the
    experts this decoder holds of each layer's ``num_experts``.  Every matrix
    is drawn normal(0.02), the embedding and the head too."""

    def __init__(self, vocab_size, layers, hidden_size, num_attention_heads,
                 num_key_value_heads, head_dim, moe_intermediate_size,
                 num_experts, num_experts_per_tok, sliding_window,
                 rms_norm_eps=1e-6, rope_parameters=None, held_experts=None,
                 first_expert=0, norm_topk_prob=True, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        from ...ops.rotary import rope_frequencies
        tables = {kind: rope_frequencies(entry, head_dim)
                  for kind, entry in (rope_parameters or {}).items()}
        if set(tables) - set(KINDS):
            raise ValueError(f"rope_parameters for {sorted(tables)}: the "
                             f"kinds are {KINDS}")
        with self.name_scope():
            self.embed = Embedding(
                vocab_size, hidden_size,
                weight_initializer=init_mod.Normal(0.02))
            self.layers = []
            for i, kind in enumerate(layers):
                layer = MoEDecoderLayer(
                    i, kind, hidden_size, num_attention_heads,
                    num_key_value_heads, head_dim, sliding_window,
                    rms_norm_eps, tables.get(kind), moe_intermediate_size,
                    num_experts, num_experts_per_tok, held_experts,
                    first_expert, norm_topk_prob, prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm = RMSNorm(epsilon=rms_norm_eps, in_channels=hidden_size)
            self.head_weight = self.params.get(
                "head_weight", shape=(vocab_size, hidden_size),
                init=init_mod.Normal(0.02))

    def forward(self, ids):
        from ... import ndarray as F
        with jax.named_scope("embed"):
            x = self.embed(ids)
        for layer in self.layers:
            x, load = layer(x)
            # outside the layer, which may be recomputed
            layer.moe.record_load(load)
        with jax.named_scope("head"):
            weight = self.head_weight.data()
            return F.FullyConnected(
                self.norm(x), weight, num_hidden=weight.shape[0],
                no_bias=True, flatten=False, out_dtype="float32")
