"""The self-attention block the decoders of this zoo share (``sambay.py``,
``moe_decoder.py``): grouped-query causal softmax attention behind one fused
QKV projection, over a sliding window (``ops.window_attention``) or the whole
prefix (``ops.flash_attention``): the same Pallas kernels either way, the
window's on a grid that walks the band and under names of their own
(``window_attention_fwd`` / ``_bwd_dqkv``), which picks its block
itself because it pads what the block does not divide.  An optional RMSNorm
over each head of Q and of K, then an optional rotary step on both, stand in
front of either.  ``_LatentAttention`` is multi-head latent attention in its
training form: K and V made from a normed low-rank latent, a rotary slice of
each head, and the causal flash kernels at a V head narrower than Q's and
K's."""
from __future__ import annotations

import jax

from ... import initializer as init_mod
from ..block import HybridBlock
from ..nn import Dense, RMSNorm


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
    encoding.  ``qk_norm`` (an epsilon, or None for none) puts an RMSNorm
    over ``head_dim`` on every head of Q and of K, before the rotary step:
    ``q_norm`` and ``k_norm``, a learned gain of ``head_dim`` each, the
    moments float32 as in ``nn.RMSNorm``.  A window layer returns its output;
    a full layer hands on its K and V (as attention reads them: normed and
    rotated) beside it."""

    def __init__(self, hidden, heads, kv_heads, window, head_dim=None,
                 rope=None, qk_norm=None, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads, self._window = heads, kv_heads, window
        self._head_dim = head_dim = head_dim or hidden // heads
        self._q, self._kv = head_dim * heads, head_dim * kv_heads
        self._rope, self._qk_norm = rope, qk_norm is not None
        with self.name_scope():
            self.qkv = _dense(self._q + 2 * self._kv, hidden)
            if self._qk_norm:
                self.q_norm = RMSNorm(epsilon=qk_norm, in_channels=head_dim)
                self.k_norm = RMSNorm(epsilon=qk_norm, in_channels=head_dim)
            self.out_proj = _dense(hidden, self._q)

    def _per_head(self, norm, x):
        """``norm`` over each head's ``head_dim`` of ``x`` (B, T, H * d)."""
        return norm(x.reshape((0, 0, -1, self._head_dim))).reshape(x.shape)

    def forward(self, u):
        from ... import ndarray as F
        q, k, v = F.split_v2(
            self.qkv(u), axis=-1, indices=(self._q, self._q + self._kv))
        if self._qk_norm:
            with jax.named_scope("qk_norm"):
                q = self._per_head(self.q_norm, q)
                k = self._per_head(self.k_norm, k)
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


class _LatentAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434), the
    training form, causal, no bias anywhere.  Per head ``h``:

    - ``q_h = [q_nope_h; q_pe_h] = (W_q u)_h``, ``nope_dim + rope_dim`` wide;
    - ``[c; k_pe] = W_kva u`` (``kv_rank + rope_dim``), ``c = RMSNorm(c)``
      (a learned gain of ``kv_rank``, moments float32);
      ``[k_nope_h; v_h] = (W_kvb c)_h`` (``nope_dim + v_dim``);
    - ``q_pe_h`` and ``k_pe`` rotated by ``rope`` (a table of ``ops.rotary.
      rope_frequencies`` for heads of ``rope_dim``), pairs of neighbours
      (2i, 2i+1) turned together; ``k_pe`` is ONE head that every query
      head reads;
    - ``k_h = [k_nope_h; k_pe]``, ``o_h = softmax_causal(q_h k_h^T /
      sqrt(nope_dim + rope_dim)) v_h`` through ``ops.flash_attention`` with
      heads of ``nope_dim + rope_dim`` and ``v_dim``, V not padded;
    - ``W_o [o_1 .. o_H]``.

    Scopes: ``latent`` holds ``kv_a``, the latent norm and ``kv_b``;
    ``rope`` both rotations.  Returns the output alone."""

    def __init__(self, hidden, heads, kv_rank, nope_dim, rope_dim, v_dim,
                 rope, eps, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._rank = heads, kv_rank
        self._nope, self._rope_dim, self._v = nope_dim, rope_dim, v_dim
        self._rope = rope
        with self.name_scope():
            self.q = _dense(heads * (nope_dim + rope_dim), hidden)
            self.kv_a = _dense(kv_rank + rope_dim, hidden)
            self.kv_norm = RMSNorm(epsilon=eps, in_channels=kv_rank)
            self.kv_b = _dense(heads * (nope_dim + v_dim), kv_rank)
            self.out_proj = _dense(hidden, heads * v_dim)

    def _rotate(self, F, x, heads):
        inv_freq, factor = self._rope
        return F.rotary_embedding(x, inv_freq=inv_freq, heads=heads,
                                  factor=factor, interleaved=True)

    def forward(self, u):
        from ... import ndarray as F
        b, t = u.shape[0], u.shape[1]
        heads, nope, rope = self._heads, self._nope, self._rope_dim
        q = self.q(u).reshape((b, t, heads, nope + rope))
        q_nope, q_pe = F.split_v2(q, axis=-1, indices=(nope,))
        with jax.named_scope("latent"):
            c, k_pe = F.split_v2(self.kv_a(u), axis=-1, indices=(self._rank,))
            kv = self.kv_b(self.kv_norm(c)).reshape(
                (b, t, heads, nope + self._v))
            k_nope, v = F.split_v2(kv, axis=-1, indices=(nope,))
        with jax.named_scope("rope"):
            q_pe = self._rotate(F, q_pe.reshape((b, t, heads * rope)), heads)
            k_pe = self._rotate(F, k_pe, 1)
        q = F.concat(q_nope, q_pe.reshape((b, t, heads, rope)), dim=-1)
        k = F.concat(k_nope, F.broadcast_to(
            k_pe.reshape((b, t, 1, rope)), shape=(b, t, heads, rope)), dim=-1)
        width = heads * (nope + rope)
        return self.out_proj(_causal_attention(
            F, q.reshape((b, t, width)), k.reshape((b, t, width)),
            v.reshape((b, t, heads * self._v)), heads, heads))
