"""The self-attention block the decoders of this zoo share (``sambay.py``,
``moe_decoder.py``): grouped-query causal softmax attention behind one fused
QKV projection, over a sliding window (``ops.window_attention``) or the whole
prefix (``ops.flash_attention``): the same Pallas kernels either way, the
window's on a grid that walks the band and under names of their own
(``window_attention_fwd`` / ``_bwd_dq`` / ``_bwd_dkv``), which picks its block
itself because it pads what the block does not divide.  An optional rotary
step on Q and K stands in front of either."""
from __future__ import annotations

import jax

from ... import initializer as init_mod
from ..block import HybridBlock
from ..nn import Dense


def _flash_block(t):
    """Query and key block of the flash kernel: the largest of 512, 256,
    128 that divides the sequence, else the sequence whole."""
    return next((b for b in (512, 256, 128) if t % b == 0), t)


def _dense(units, in_units):
    return Dense(units, use_bias=False, flatten=False, in_units=in_units,
                 weight_initializer=init_mod.Normal(0.02))


def _causal_attention(F, q, k, v, heads, kv_heads):
    block = _flash_block(q.shape[1])
    return F.flash_attention(q, k, v, heads=heads, kv_heads=kv_heads,
                             causal=True, block_q=block, block_k=block)


class _Attention(HybridBlock):
    """``window`` (an int) or ``full`` (None) self-attention, grouped-query,
    one fused QKV projection laid out [Q; K; V].  ``head_dim`` defaults to
    ``hidden / heads``; ``rope`` is a table of ``ops.rotary.
    rope_frequencies`` (``(inv_freq, factor)``) or None for no positional
    encoding.  A window layer returns its output; a full layer hands on its
    K and V (as attention reads them: rotated) beside it."""

    def __init__(self, hidden, heads, kv_heads, window, head_dim=None,
                 rope=None, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._window = heads, kv_heads, window
        head_dim = head_dim or hidden // heads
        self._q, self._kv = head_dim * heads, head_dim * kv_heads
        self._rope = rope
        with self.name_scope():
            self.qkv = _dense(self._q + 2 * self._kv, hidden)
            self.out_proj = _dense(hidden, self._q)

    def forward(self, u):
        from ... import ndarray as F
        q, k, v = F.split_v2(
            self.qkv(u), axis=-1, indices=(self._q, self._q + self._kv))
        if self._rope is not None:
            inv_freq, factor = self._rope
            with jax.named_scope("rope"):
                q = F.rotary_embedding(q, inv_freq=inv_freq,
                                       heads=self._heads, factor=factor)
                k = F.rotary_embedding(k, inv_freq=inv_freq,
                                       heads=self._kv_heads, factor=factor)
        if self._window is not None:
            return self.out_proj(F.window_attention(
                q, k, v, heads=self._heads, kv_heads=self._kv_heads,
                window=self._window))
        return self.out_proj(_causal_attention(
            F, q, k, v, self._heads, self._kv_heads)), k, v
