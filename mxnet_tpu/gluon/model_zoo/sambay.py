"""SambaY — the hybrid decoder of Phi-4-mini-flash-reasoning (Ren et al.,
arXiv:2507.06607): a self-decoder of Mamba and sliding-window attention
layers ending in one full-attention layer, then a cross-decoder whose layers
alternate Gated Memory Units, which read the LAST Mamba layer's scan output,
and cross-attention over the full-attention layer's K and V (the
cross-decoder after YOCO, arXiv:2405.05254).  No reference analogue.

Every layer i:  ``x = x + Mixer_i(LN(x));  x = x + W_down(silu(g) * v)``
with ``[g, v] = W_gate_up LN'(x)``; a LayerNorm after the last layer; logits
``h E^T`` with the embedding E (tied), in float32.  The mixers:

- ``mamba``:  ``[xs, z] = W_in u``; ``xc = silu(conv(xs) + b)``, a causal
  depthwise convolution; ``[r, B, C] = W_x xc``; ``dt = softplus(W_dt r +
  b_dt)``; ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + (dt_t xc_t)
  B_t^T``; ``y_t = h_t C_t + D xc_t``; out ``W_out (y * silu(z))``.  Hands
  on ``m = y`` (after the D skip, before the gate).
- ``window``: grouped-query causal softmax attention, query t sees keys
  t-window+1 .. t; fused QKV, no bias.
- ``full``:   the same with no window; hands on its projected K and V.
- ``gmu``:    ``W_2 (m * silu(W_1 u))``, position by position.
- ``cross``:  ``q = W_q u`` attends causally over the handed K and V; W_o.

The layer list is configuration: ``layer_kinds(32)`` is the published map,
a cut is a shorter list of the same kinds.  No positional encoding (the
paper's NoPE: the Mamba layers carry position).  Plain softmax attention;
the paper's differential-attention variant is not built.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ... import initializer as init_mod
from ... import random as _random
from ...base import dtype_np
from ..block import HybridBlock
from ..nn import Embedding, LayerNorm
from ._attention import (_Attention, _causal_attention, _dense,  # noqa: F401
                         _flash_block)

__all__ = ["SambaY", "SambaYLayer", "layer_kinds", "KINDS"]

KINDS = ("mamba", "window", "full", "gmu", "cross")
# a mixer's jax.named_scope under ``layer<i>/``, as the decoder of
# causal_lm.py names its own (``attention``, ``mlp``)
_SCOPE = {"mamba": "mamba", "window": "window_attention",
          "full": "attention", "gmu": "gmu", "cross": "cross_attention"}


def layer_kinds(n):
    """The published map for ``n`` layers (32: 9 mamba, 8 window, 1 full, 7
    gmu, 7 cross): the self-decoder is layers 0 .. n/2+1."""
    half = n // 2

    def kind(i):
        if i <= half:
            return "mamba" if i % 2 == 0 else "window"
        if i == half + 1:
            return "full"
        return "gmu" if i % 2 == 0 else "cross"
    return [kind(i) for i in range(n)]


class _Everywhere(init_mod.Initializer):
    """An initializer that applies whatever the parameter's name ends in
    (the base class zeroes every ``*bias``)."""

    def __call__(self, name, shape, dtype="float32"):
        return self.init_array(shape, dtype)


class _ALog(_Everywhere):
    """``A_log[d, n] = log(n + 1)``: S4D-real, Mamba's own."""

    def init_array(self, shape, dtype="float32"):
        row = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(row, shape).astype(dtype_np(dtype))


class _DtBias(_Everywhere):
    """The inverse softplus of steps drawn log-uniform in [lo, hi], so that
    ``softplus(bias)`` starts there: Mamba's own."""

    def __init__(self, lo=1e-3, hi=1e-1):
        super().__init__(lo=lo, hi=hi)
        self.lo, self.hi = lo, hi

    def init_array(self, shape, dtype="float32"):
        u = jax.random.uniform(_random.next_key(), shape, jnp.float32)
        dt = jnp.exp(u * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype_np(dtype))


class _Mamba(HybridBlock):
    def __init__(self, hidden, state, taps, expand, dt_rank, **kwargs):
        super().__init__(**kwargs)
        inner = expand * hidden
        self._rank, self._state = dt_rank, state
        with self.name_scope():
            self.in_proj = _dense(2 * inner, hidden)
            self.conv_weight = self.params.get(
                "conv_weight", shape=(taps, inner),
                init=init_mod.Uniform(1.0 / math.sqrt(taps)))
            self.conv_bias = self.params.get("conv_bias", shape=(inner,),
                                             init="zeros")
            self.x_proj = _dense(dt_rank + 2 * state, inner)
            self.dt_weight = self.params.get(
                "dt_weight", shape=(inner, dt_rank),
                init=init_mod.Normal(0.02))
            self.dt_bias = self.params.get("dt_bias", shape=(inner,),
                                           init=_DtBias())
            self.a_log = self.params.get("a_log", shape=(inner, state),
                                         init=_ALog())
            self.d = self.params.get("d", shape=(inner,),
                                     init=init_mod.Constant(1.0))
            self.out_proj = _dense(hidden, inner)

    def hybrid_forward(self, F, u, conv_weight, conv_bias, dt_weight,
                       dt_bias, a_log, d):
        xs, z = F.split(self.in_proj(u), num_outputs=2, axis=-1)
        with jax.named_scope("conv"):
            xc = F.silu(F.causal_conv1d(xs, conv_weight, conv_bias))
        r, b, c = F.split_v2(
            self.x_proj(xc), axis=-1,
            indices=(self._rank, self._rank + self._state))
        # the step and the decay rates in float32 whatever the compute type
        dt = F.Activation(F.FullyConnected(
            r, dt_weight, dt_bias, num_hidden=dt_weight.shape[0],
            flatten=False, out_dtype="float32"), act_type="softrelu")
        with jax.named_scope("scan"):
            y = F.selective_scan(xc, dt, -F.exp(a_log.astype("float32")),
                                 b, c, d)
        return self.out_proj(y * F.silu(z)), y


class _GMU(HybridBlock):
    def __init__(self, hidden, inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.in_proj = _dense(inner, hidden)
            self.out_proj = _dense(hidden, inner)

    def forward(self, u, memory):
        from ... import ndarray as F
        return self.out_proj(memory * F.silu(self.in_proj(u)))


class _CrossAttention(HybridBlock):
    """Queries of its own over another layer's K and V; none of its own."""

    def __init__(self, hidden, heads, kv_heads, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads = heads, kv_heads
        with self.name_scope():
            self.q_proj = _dense(hidden, hidden)
            self.out_proj = _dense(hidden, hidden)

    def forward(self, u, k, v):
        from ... import ndarray as F
        return self.out_proj(_causal_attention(
            F, self.q_proj(u), k, v, self._heads, self._kv_heads))


class SambaYLayer(HybridBlock):
    """One decoder layer: a mixer of ``kind`` and the gated feed-forward,
    each behind its LayerNorm and added to the residual.  Called with the
    residual and whatever its mixer reads from an earlier layer; returns the
    residual and whatever its mixer hands on (``mamba``: the scan output;
    ``full``: K and V)."""

    def __init__(self, index, kind, hidden, heads, kv_heads, intermediate,
                 window, eps, state, taps, expand, dt_rank, **kwargs):
        super().__init__(**kwargs)
        if kind not in KINDS:
            raise ValueError(f"layer {index}: kind {kind!r} is none of "
                             f"{KINDS}")
        self._index, self._kind = index, kind
        with self.name_scope():
            self.norm1 = LayerNorm(epsilon=eps, in_channels=hidden)
            if kind == "mamba":
                self.mixer = _Mamba(hidden, state, taps, expand, dt_rank)
            elif kind in ("window", "full"):
                self.mixer = _Attention(hidden, heads, kv_heads,
                                        window if kind == "window" else None)
            elif kind == "gmu":
                self.mixer = _GMU(hidden, expand * hidden)
            else:
                self.mixer = _CrossAttention(hidden, heads, kv_heads)
            self.norm2 = LayerNorm(epsilon=eps, in_channels=hidden)
            self.gate_up = _dense(2 * intermediate, hidden)
            self.down = _dense(hidden, intermediate)

    def forward(self, x, *read):
        from ... import ndarray as F
        with jax.named_scope(f"layer{self._index}"):
            with jax.named_scope(_SCOPE[self._kind]):
                out = self.mixer(self.norm1(x), *read)
            mixed, *handed = out if isinstance(out, tuple) else (out,)
            x = x + mixed
            with jax.named_scope("mlp"):
                gate, value = F.split(self.gate_up(self.norm2(x)),
                                      num_outputs=2, axis=-1)
                x = x + self.down(F.silu(gate) * value)
        return (x, *handed)


class SambaY(HybridBlock):
    """``forward(ids[B, T] int32) -> logits [B, T, vocab_size] float32``.

    ``layers`` is the list of layer kinds, in order; the defaults are
    Phi-4-mini-flash-reasoning's published widths, with Mamba-1's own for
    the sizes its config lacks (state 16, 4 taps, expansion 2, dt rank
    ceil(hidden / 16))."""

    def __init__(self, vocab_size, layers, hidden_size=2560,
                 num_attention_heads=40, num_key_value_heads=20,
                 intermediate_size=10240, sliding_window=512,
                 layer_norm_eps=1e-5, state_size=16, conv_kernel=4, expand=2,
                 dt_rank=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        layers = list(layers)
        if "gmu" in layers and "mamba" not in layers[:layers.index("gmu")]:
            raise ValueError("a gmu layer reads an earlier mamba layer's "
                             "scan output, and none precedes it")
        if "cross" in layers and "full" not in layers[:layers.index("cross")]:
            raise ValueError("a cross layer reads an earlier full layer's K "
                             "and V, and none precedes it")
        dt_rank = dt_rank or -(-hidden_size // 16)
        with self.name_scope():
            self.embed = Embedding(vocab_size, hidden_size,
                                   weight_initializer=init_mod.Normal(0.02))
            self.layers = []
            for i, kind in enumerate(layers):
                layer = SambaYLayer(
                    i, kind, hidden_size, num_attention_heads,
                    num_key_value_heads, intermediate_size, sliding_window,
                    layer_norm_eps, state_size, conv_kernel, expand, dt_rank,
                    prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm = LayerNorm(epsilon=layer_norm_eps,
                                  in_channels=hidden_size)

    def forward(self, ids):
        from ... import ndarray as F
        with jax.named_scope("embed"):
            x = self.embed(ids)
        memory = kv = ()            # what the last mamba / full layer handed on
        for layer in self.layers:
            read = {"gmu": memory, "cross": kv}.get(layer._kind, ())
            x, *handed = layer(x, *read)
            if layer._kind == "mamba":
                memory = handed
            elif layer._kind == "full":
                kv = handed
        with jax.named_scope("head"):
            weight = self.embed.weight.data()
            return F.FullyConnected(
                self.norm(x), weight, num_hidden=weight.shape[0],
                no_bias=True, flatten=False, out_dtype="float32")
