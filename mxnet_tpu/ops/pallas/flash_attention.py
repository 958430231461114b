"""Flash attention Pallas kernels — the long-context hot path.

The reference's attention is two cuBLAS strided-batched matmuls with the
full (B*H, S, S) score matrix materialised (ref: src/operator/contrib/
transformer.cc).  On TPU that matrix is the HBM wall at long sequence; these
kernels compute softmax(QK^T)V blockwise with the online-softmax recurrence
(SURVEY §7.0.2 names this kernel).

v2 design:
- K/V are **streamed block-by-block through the grid** — the kernel never
  holds a whole (S, D) K or V in VMEM, so sequence length is bounded by HBM,
  not VMEM.  Grid (B·H, S/bq, S/bk); accumulators (acc, m, l) live in VMEM
  scratch carried across the k-dimension of the grid.
- The forward also emits the per-row log-sum-exp, and the **backward is two
  Pallas kernels** (dq, then dk/dv) using the standard recompute-from-lse
  formulation — O(S·D) memory end to end.
- **Attention-probability dropout runs inside the kernel**: a counter-based
  integer hash (SplitMix32 finaliser) of (head, q-pos, k-pos, seed) drawn
  identically in forward and backward, so no mask is ever materialised.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu

_NEG_INF = -1e30


def _uniform01(h_idx, q_pos, k_pos, seed):
    """Deterministic U[0,1) per (head, q, k) via a SplitMix32-style hash.
    Counter-based, so forward and backward regenerate the same draw without
    storing any mask.  (Statistical-quality RNG, not crypto — exactly what
    dropout needs.)"""
    x = (q_pos.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
         + k_pos.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B)
         + jnp.uint32(h_idx) * jnp.uint32(0xC2B2AE35)
         + jnp.uint32(seed))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # Mosaic has no uint32 -> float32 cast; the top 24 bits fit an int32
    # exactly, so reinterpret first (same value, same float)
    bits = jax.lax.bitcast_convert_type(x >> 8, jnp.int32)
    return bits.astype(jnp.float32) * (1.0 / 16777216.0)


def _positions(bq, bk, qi, kj, block_q, block_k):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos, k_pos


def _row_spec(block_q, index_map):
    """BlockSpec of a per-query-row statistic (lse, delta).  Mosaic wants
    the last two block dims divisible by (8, 128) or equal to the array's,
    which a ``(1, block_q)`` block of a ``(bh, s)`` array is not; carried
    as ``(bh, s // block_q, 1, block_q)`` the block's last two dims ARE
    the array's, for any block_q."""
    return pl.BlockSpec((1, 1, 1, block_q), index_map)


# ------------------------------------------------------------- forward ------
def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale, causal, block_q, block_k, n_k,
                dropout):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale      # (bq, D)
        k = k_ref[0].astype(jnp.float32)              # (bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = q @ k.T                                   # (bq, bk)
        q_pos, k_pos = _positions(s.shape[0], s.shape[1], qi, kj,
                                  block_q, block_k)
        if causal:
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        # l tracks the TRUE softmax normaliser (pre-dropout), so lse is exact
        l_new = l_prev * alpha + p.sum(axis=-1)
        if dropout > 0.0:
            keep = _uniform01(b, q_pos, k_pos, seed_ref[0]) >= dropout
            p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # skip fully-masked future blocks: ~2x fewer matmuls at long S
        pl.when(kj * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == n_k - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = m_ref[...] + jnp.log(l)


def _kv_row(q, k):
    """Grouped-query heads: ``q`` holds ``group`` times the rows of ``k``
    (rows are batch-major, then heads, so query row ``b`` reads K/V row
    ``b // group``).  Returns the map from a query row to its K/V row."""
    group, rest = divmod(q.shape[0], k.shape[0])
    assert rest == 0, (q.shape, k.shape)
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k, interpret,
               dropout):
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    n_k = s // block_k
    kv = _kv_row(q, k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, dropout=dropout)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1,), lambda b, i, j: (0,)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _row_spec(block_q, lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, s // block_q, 1, block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret,
    )(seed, q, k, v)
    return out, lse.reshape(bh, s)


# ------------------------------------------------------------ backward ------
def _recompute_p(q_ref, k_ref, lse_ref, b, qi, kj, scale, causal,
                 block_q, block_k):
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    s = q @ k.T
    q_pos, k_pos = _positions(s.shape[0], s.shape[1], qi, kj,
                              block_q, block_k)
    if causal:
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
    # true softmax probs (pre-dropout)
    p = jnp.exp(s - lse_ref[0, 0, 0][:, None])
    return p, q_pos, k_pos


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, scale, causal, block_q, block_k, n_k,
               dropout):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        p, q_pos, k_pos = _recompute_p(q_ref, k_ref, lse_ref, b, qi, kj,
                                       scale, causal, block_q, block_k)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        dp = do @ v.T                                 # (bq, bk)
        if dropout > 0.0:
            keep = _uniform01(b, q_pos, k_pos, seed_ref[0]) >= dropout
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout))
        ds = p * (dp - delta_ref[0, 0, 0][:, None])
        dq_acc[...] += (ds @ k_ref[0].astype(jnp.float32)) * scale

    if causal:
        pl.when(kj * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(kj == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                block_k, n_q, dropout):
    b = pl.program_id(0)
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        p, q_pos, k_pos = _recompute_p(q_ref, k_ref, lse_ref, b, qi, kj,
                                       scale, causal, block_q, block_k)
        do = do_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        if dropout > 0.0:
            keep = _uniform01(b, q_pos, k_pos, seed_ref[0]) >= dropout
            pd = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        else:
            pd = p
        dv_acc[...] += pd.T @ do
        dp = do @ v.T
        if dropout > 0.0:
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout))
        ds = p * (dp - delta_ref[0, 0, 0][:, None])
        dk_acc[...] += (ds.T @ (q_ref[0].astype(jnp.float32))) * scale

    if causal:
        pl.when(kj * block_k <= qi * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, seed, o, lse, do, scale, causal, block_q, block_k,
               interpret, dropout):
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    n_q, n_k = s // block_q, s // block_k
    kv = _kv_row(q, k)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse = lse.reshape(bh, n_q, 1, block_q)
    delta = delta.reshape(bh, n_q, 1, block_q)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          dropout=dropout),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1,), lambda b, i, j: (0,)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            _row_spec(block_q, lambda b, i, j: (b, i, 0, 0)),
            _row_spec(block_q, lambda b, i, j: (b, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(seed, q, k, v, do, lse, delta)

    # grouped-query heads: the kernel writes one float32 part per QUERY
    # head, and a K/V head's gradient is the sum over its group
    grouped = k.shape[0] != bh
    part = jnp.float32 if grouped else k.dtype
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_q=n_q,
                          dropout=dropout),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1,), lambda b, j, i: (0,)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (kv(b), j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (kv(b), j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            _row_spec(block_q, lambda b, j, i: (b, i, 0, 0)),
            _row_spec(block_q, lambda b, j, i: (b, i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), part),
            jax.ShapeDtypeStruct((bh, s, d), part),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(seed, q, k, v, do, lse, delta)
    if grouped:
        dk, dv = (g.reshape(k.shape[0], -1, s, d).sum(axis=1).astype(k.dtype)
                  for g in (dk, dv))
    return dq, dk, dv


# ----------------------------------------------------------- public api -----
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_attention_core(q, k, v, seed, scale, causal, block_q, block_k,
                          interpret, dropout):
    out, _ = _flash_fwd_rule(q, k, v, seed, scale, causal, block_q, block_k,
                             interpret, dropout)
    return out


def flash_attention(q, k, v, scale=None, causal=False, block_q=128,
                    block_k=128, interpret=None, dropout=0.0, seed=None):
    """softmax(scale · Q Kᵀ [, causal]) V without materialising S×S.

    q: (B*H, S, D); k, v the same, or (B*H_kv, S, D) for grouped-query
    heads with H a multiple of H_kv: query head h reads K/V head
    h // (H / H_kv) through the kernels' index maps, and nothing is repeated
    in memory.  ``dropout`` applies attention-probability dropout
    inside the kernel (the mask is regenerated from a counter-based hash in
    forward AND backward — never stored).  ``seed`` may be a traced int32
    scalar so each training step draws a fresh mask without retracing.
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU (CPU-mesh
    tests) and the compiled kernel on TPU."""
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    return _flash_attention_core(q, k, v, seed, scale, causal, block_q,
                                 block_k, interpret, dropout)


def _resolve(scale, d, interpret):
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = not on_tpu()
    return scale, interpret


def _flash_fwd_rule(q, k, v, seed, scale, causal, block_q, block_k,
                    interpret, dropout):
    scale, interpret = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k,
                          interpret, float(dropout))
    return out, (q, k, v, seed, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, dropout,
                    res, do):
    q, k, v, seed, o, lse = res
    scale, interpret = _resolve(scale, q.shape[-1], interpret)
    dq, dk, dv = _flash_bwd(q, k, v, seed, o, lse, do, scale, causal,
                            block_q, block_k, interpret, float(dropout))
    return dq, dk, dv, None


_flash_attention_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)
