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
- The forward also emits the per-row log-sum-exp, and the **backward is one
  Pallas kernel** (``bwd_dqkv``) using the standard recompute-from-lse
  formulation — O(S·D) memory end to end.  It makes each pair's scores,
  probabilities, ``dp`` and ``ds`` once for dq, dk and dv, and holds their
  float32 accumulators for a whole sequence in VMEM; a sequence too long for
  ``FUSED_VMEM_BUDGET`` takes the split pair (``bwd_dq``, then ``bwd_dkv``),
  which makes them twice and holds a block of each.
- **Attention-probability dropout runs inside the kernel**: a counter-based
  integer hash (SplitMix32 finaliser) of (head, q-pos, k-pos, seed) drawn
  identically in forward and backward, so no mask is ever materialised.

The block bodies (PR 30; PERF.md 6 has Mosaic's schedules and the chip's
times):
- **Operands meet the matrix unit in the dtype they arrive in**
  (``dot_general`` contracting the last dimensions, float32 out; ``p`` and
  ``ds`` cast to that dtype for their products), so a bf16 caller multiplies
  bf16 and a float32 caller float32.  Scores, statistics, ``exp`` and the
  accumulators are float32 either way.
- **Row statistics live in the layout they are used in**: ``m`` and ``l`` (and
  the dq kernel's ``lse`` and ``delta``) as lane-replicated ``(block_q, 128)``
  scratch, turned from or into the ``(1, block_q)`` rows that cross HBM once a
  query block; the dk/dv kernel holds the score block transposed, where the
  rows are what it needs.
- **Causal pairs**: of a head's block pairs those wholly in the future are
  skipped and name the block already held, so they fetch nothing; the mask is
  applied only where the diagonal crosses a pair (at 4,096 positions in
  blocks of 512: 36 of 64 pairs computed, 8 of them masked).

With a ``window`` (PR 32; query ``t`` sees keys ``t-window+1 .. t``) the same
bodies run under the names ``window_attention_fwd`` / ``_bwd_dqkv`` (or
``_bwd_dq`` / ``_bwd_dkv``), and **the grid's inner axis walks the band, not
the sequence**: ``_band_steps`` key blocks a query block (and query blocks a
key block), the block of each step worked out in the index map, a step off
the sequence's edge clamped onto it and skipped.  The mask also cuts a pair
that the window's far edge crosses; ``band_pairs`` counts both (at 8,192
positions, blocks of 512 and a window of 1,024: 45 pairs a head, 30 of them
masked, where the causal kernels compute 136).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry
from ...context import on_tpu

_NEG_INF = -1e30
_LANES = 128
# contract the last dimension of both operands: ``a @ b.T`` with no transpose
_NT = (((1,), (1,)), ((), ()))
# contract the first dimension of both: ``a.T @ b``
_TN = (((0,), (0,)), ((), ()))
# the most VMEM the fused backward's whole-sequence accumulators and output
# blocks may take (``fused_vmem_bytes``): 40 MiB at 8,192 positions and
# heads of 192 / 128 in bf16; its limit leaves room for the blocks of the
# pairs and their scores beside them
FUSED_VMEM_BUDGET = 48 * 1024 * 1024
_FUSED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",) * 4,
    vmem_limit_bytes=FUSED_VMEM_BUDGET + 16 * 1024 * 1024)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    """A product on the matrix unit: operands in the dtype they arrive in,
    float32 out."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


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


def _positions(shape, qi, kj, block_q, block_k, q_axis=0):
    """Sequence positions of a score block's queries and keys; the queries
    run along ``q_axis`` (the dk/dv kernel holds the block transposed)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                    1 - q_axis)
    return q_pos, k_pos


def _scores(a, b, scale, masked, dropout, qi, kj, block_q, block_k, window,
            q_axis=0):
    """``scale * a b^T`` in float32, the mask applied if the diagonal or the
    window's far edge crosses this pair, and the block's (query, key)
    positions where the mask or dropout needs them (else None)."""
    s = _dot(a, b, _NT) * scale
    if not masked and dropout == 0.0:
        return s, None
    q_pos, k_pos = _positions(s.shape, qi, kj, block_q, block_k, q_axis)
    if masked:
        seen = q_pos >= k_pos
        if window is not None:
            seen &= k_pos > q_pos - window
        s = jnp.where(seen, s, _NEG_INF)
    return s, (q_pos, k_pos)


def _keep(head, pos, seed, dropout):
    """The dropout mask of a block's positions (None without dropout): the
    same draw in the forward and both backward kernels."""
    if dropout == 0.0:
        return None
    return _uniform01(head, *pos, seed) >= dropout


def _dropped(x, keep, dropout):
    """``x`` under the dropout mask ``keep``, rescaled."""
    if keep is None:
        return x
    return jnp.where(keep, x, 0.0) * (1.0 / (1.0 - dropout))


def _row_spec(block_q, index_map):
    """BlockSpec of a per-query-row statistic (lse, delta).  Mosaic wants
    the last two block dims divisible by (8, 128) or equal to the array's,
    which a ``(1, block_q)`` block of a ``(bh, s)`` array is not; carried
    as ``(bh, s // block_q, 1, block_q)`` the block's last two dims ARE
    the array's, for any block_q."""
    return pl.BlockSpec((1, 1, 1, block_q), index_map)


# ------------------------------------------------------------- forward ------
def _across(stat, n):
    """A row statistic kept lane-replicated ``(rows, 128)``, as ``(rows, n)``:
    whole registers repeated, so nothing moves between lanes."""
    if n % _LANES == 0:
        return pltpu.repeat(stat, n // _LANES, axis=1)
    if n < _LANES:
        return stat[:, :n]
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], n))


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref,
                m_ref, l_ref, *, scale, causal, block_q, block_k, n_k,
                dropout, window, seq):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kj = step if window is None else _band_key_block(
        qi, step, block_q, block_k, n_k)

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute(masked):
        v = v_ref[0]
        s, pos = _scores(q_ref[0], k_ref[0], scale, masked, dropout, qi, kj,
                         block_q, block_k, window)    # (bq, bk) float32
        m_prev = m_ref[...]                           # (bq, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        # a row the window hides from a whole pair holds no score yet: its
        # alpha is exp(-1e30 - m) = 0 at the first key it sees, so what it
        # summed until then is dropped
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _across(m_new, s.shape[1]))
        # l tracks the TRUE softmax normaliser (pre-dropout), so lse is exact
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        m_ref[...] = m_new
        p = _dropped(p, _keep(b, pos, seed_ref[0], dropout), dropout)
        acc_ref[...] = (acc_ref[...] * _across(alpha, v.shape[1])
                        + _dot(p.astype(v.dtype), v))

    _visible_pairs(_compute, causal, qi, kj, block_q, block_k, window, seq)

    @pl.when(step == n_k - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / _across(l, acc_ref.shape[1])
                    ).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l)).T[:1]


def _whole(first_q, last_q, first_k, last_k, window, s):
    """Every query of the pair sees every key of it: no mask."""
    whole = last_k <= first_q
    if window is None:
        return whole
    return (whole & (first_k > last_q - window)
            & (first_k >= 0) & (first_q < s))


def _crossed(first_q, last_q, first_k, last_k, window, s):
    """Some query of the pair sees some key of it, and the diagonal or the
    window's far edge crosses it: computed under the mask."""
    if window is None:
        return (first_k <= last_q) & (last_k > first_q)
    return ((first_k <= last_q) & (last_k > first_q - window)
            & ((last_k > first_q) | (first_k <= last_q - window))
            & (first_k >= 0) & (first_q < s))       # else a clamped step


def _edges(qi, kj, block_q, block_k):
    """First and last position of the pair's queries, then of its keys."""
    return (qi * block_q, qi * block_q + block_q - 1,
            kj * block_k, kj * block_k + block_k - 1)


def _visible_pairs(compute, causal, qi, kj, block_q, block_k, window, s):
    """Run ``compute(masked)`` on the block pair ``(qi, kj)``: not at all
    where no query sees a key (every key in the future, or behind the
    window of every query, or the pair a step off the sequence's edge),
    with the mask where the diagonal or the window's far edge crosses the
    pair, and without it between them."""
    if not causal:
        return compute(False)
    edges = _edges(qi, kj, block_q, block_k)
    pl.when(_whole(*edges, window, s))(lambda: compute(False))
    pl.when(_crossed(*edges, window, s))(lambda: compute(True))


def band_pairs(s, block_q, block_k, window):
    """``(computed, masked)`` block pairs of one head under a causal
    ``window`` (None: causal alone), by the rule the kernels branch on: how
    often the mechanism engages is static."""
    pairs = [_edges(i, j, block_q, block_k)
             for i in range(s // block_q) for j in range(s // block_k)]
    masked = sum(bool(_crossed(*e, window, s)) for e in pairs)
    return sum(bool(_whole(*e, window, s)) for e in pairs) + masked, masked


def _band_steps(s, block_q, block_k, window):
    """Steps of the grid's inner axis under a window: the most key blocks a
    query block's band touches (keys ``first_q - window + 1 .. last_q``),
    and the most query blocks that see a key block (queries ``first_k ..
    last_k + window - 1``), the sequence's edges cutting both.  Where
    ``block_k`` divides ``block_q`` the first is ``ceil((block_q + window -
    1) / block_k)``: 3 at blocks of 512 and a window of 1,024, 2 at 512."""
    n_q, n_k = s // block_q, s // block_k
    keys = max((i * block_q + block_q - 1) // block_k
               - max(i * block_q - window + 1, 0) // block_k + 1
               for i in range(n_q))
    queries = max(min((j * block_k + block_k + window - 2) // block_q,
                      n_q - 1) - j * block_k // block_q + 1
                  for j in range(n_k))
    return keys, queries


def _band_key_block(qi, step, block_q, block_k, n_steps):
    """The key block of a step of query block ``qi``'s walk, which ends on
    the block that holds its last query; negative before the sequence."""
    return (qi * block_q + block_q - 1) // block_k - (n_steps - 1) + step


def _band_query_block(kj, step, block_q, block_k):
    """The query block of a step of key block ``kj``'s walk, which starts
    on the block that holds its first key; past ``s`` after the sequence."""
    return kj * block_k // block_q + step


def _needed(causal, block_q, block_k, window, s):
    """``(key steps, key block of (i, j), query steps, query block of
    (i, j))``: the length of the grid's inner axis in the forward and dq
    kernels and in the dk/dv and fused backward kernels, and their index
    maps, ``i`` on the query side and ``j`` on the key side.  Causal: the
    whole sequence, clamped to the last key block of query block ``i`` and
    the first query block of key block ``j``; a grid step past them names
    the block it already holds, and fetches nothing.  With a window: the
    band alone, clamped to the sequence."""
    n_q, n_k = s // block_q, s // block_k
    if not causal:
        return n_k, (lambda i, j: j), n_q, (lambda i, j: i)
    if window is None:
        return (n_k, lambda i, j: jnp.minimum(j, (i * block_q + block_q - 1)
                                              // block_k),
                n_q, lambda i, j: jnp.maximum(i, j * block_k // block_q))
    k_steps, q_steps = _band_steps(s, block_q, block_k, window)
    return (k_steps, lambda i, j: jnp.maximum(_band_key_block(
                i, j, block_q, block_k, k_steps), 0),
            q_steps, lambda i, j: jnp.minimum(_band_query_block(
                j, i, block_q, block_k), n_q - 1))


def _name(kernel, window):
    """A kernel's name in the program and in a device trace: the windowed
    calls under their own, so that a reader tells a window layer's from a
    full layer's at the same operand shape."""
    return ("flash" if window is None else "window") + "_attention_" + kernel


# jitted, so that a program traces and lowers each kernel once however many
# layers (and recomputations) call it: twelve calls a step in the benchmark's
# cell
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k, interpret,
               dropout, window=None):
    bh, s, d = q.shape
    d_v = v.shape[-1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    # grouped-query heads: ``q`` holds ``group`` times the rows of ``k``
    # (batch-major, then heads), so query row ``b`` reads K/V row b // group
    group, rest = divmod(bh, k.shape[0])
    assert rest == 0, (q.shape, k.shape)
    n_k, key_block, _, _ = _needed(causal, block_q, block_k, window, s)

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, i, j: (b // group, key_block(i, j), 0))

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k=n_k, dropout=dropout, window=window, seq=s)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1,), lambda b, i, j: (0,)),
            q_spec(d), kv_spec(d), kv_spec(d_v),
        ],
        out_specs=[
            q_spec(d_v),
            _row_spec(block_q, lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, s // block_q, 1, block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d_v), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        name=_name("fwd", window),
        interpret=interpret,
    )(seed, q, k, v)
    return out, lse.reshape(bh, s)


# ------------------------------------------------------------ backward ------
def _columns(row):
    """A ``(1, n)`` row of per-query statistics as ``(n, 128)``, lane-
    replicated: one transpose where the registers allow it."""
    n = row.shape[1]
    if n % _LANES == 0:
        return jnp.broadcast_to(row, (_LANES, n)).T
    return jnp.broadcast_to(row.reshape(n, 1), (n, _LANES))


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, lse_col, delta_col, *, scale, causal,
               block_q, block_k, n_k, dropout, window, seq):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    step = pl.program_id(2)
    kj = step if window is None else _band_key_block(
        qi, step, block_q, block_k, n_k)

    @pl.when(step == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        # the statistics arrive as rows; every pair of this query block
        # uses them as columns
        lse_col[...] = _columns(lse_ref[0, 0])
        delta_col[...] = _columns(delta_ref[0, 0])

    def _compute(masked):
        k = k_ref[0]
        s, pos = _scores(q_ref[0], k, scale, masked, dropout, qi, kj,
                         block_q, block_k, window)    # (bq, bk) float32
        # true softmax probs (pre-dropout)
        p = jnp.exp(s - _across(lse_col[...], s.shape[1]))
        dp = _dropped(_dot(do_ref[0], v_ref[0], _NT),
                      _keep(b, pos, seed_ref[0], dropout), dropout)
        ds = p * (dp - _across(delta_col[...], s.shape[1]))
        dq_acc[...] += _dot(ds.astype(k.dtype), k)

    _visible_pairs(_compute, causal, qi, kj, block_q, block_k, window, seq)

    @pl.when(step == n_k - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                block_k, group, n_q, dropout, window, seq):
    """The score block TRANSPOSED, keys down and queries across: the row
    statistics are rows as they arrive, and ``p^T do`` and ``ds^T q`` are
    plain products.  A K/V head accumulates over its ``group`` query
    heads."""
    bkv = pl.program_id(0)
    kj = pl.program_id(1)
    g = pl.program_id(2)
    step = pl.program_id(3)
    qi = step if window is None else _band_query_block(
        kj, step, block_q, block_k)

    @pl.when((g == 0) & (step == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q = q_ref[0]
        do = do_ref[0]
        st, pos = _scores(k_ref[0], q, scale, masked, dropout, qi, kj,
                          block_q, block_k, window, q_axis=1)  # (bk, bq)
        pt = jnp.exp(st - lse_ref[0, 0])
        dpt = _dot(v_ref[0], do, _NT)
        keep = _keep(bkv * group + g, pos, seed_ref[0], dropout)
        dv_acc[...] += _dot(_dropped(pt, keep, dropout).astype(do.dtype), do)
        dst = pt * (_dropped(dpt, keep, dropout) - delta_ref[0, 0])
        dk_acc[...] += _dot(dst.astype(q.dtype), q)

    _visible_pairs(_compute, causal, qi, kj, block_q, block_k, window, seq)

    @pl.when((g == group - 1) & (step == n_q - 1))
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dqkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                 causal, block_q, block_k, group, n_q, dropout, window, seq):
    """dq, dk and dv from one pass over the pairs: each pair's scores,
    probabilities, ``dp`` and ``ds`` made once, held transposed as in the
    dk/dv kernel, and ``dq += ds k`` read from ``ds^T`` by a product that
    contracts its first dimension.  The grid walks a query head's key blocks
    and, inside each, its query blocks; dq accumulates over the whole
    sequence of one query head, dk and dv over the whole sequence of one K/V
    head and its ``group`` query heads, each written once at its end."""
    bkv = pl.program_id(0)
    g = pl.program_id(1)
    kj = pl.program_id(2)
    step = pl.program_id(3)
    qi = step if window is None else _band_query_block(
        kj, step, block_q, block_k)
    first = (kj == 0) & (step == 0)
    last = (kj == pl.num_programs(2) - 1) & (step == n_q - 1)

    @pl.when(first)
    def _init_q():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first & (g == 0))
    def _init_kv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q = q_ref[0]
        k = k_ref[0]
        do = do_ref[0]
        st, pos = _scores(k, q, scale, masked, dropout, qi, kj, block_q,
                          block_k, window, q_axis=1)           # (bk, bq)
        pt = jnp.exp(st - lse_ref[0, 0])
        dpt = _dot(v_ref[0], do, _NT)
        keep = _keep(bkv * group + g, pos, seed_ref[0], dropout)
        keys = pl.ds(pl.multiple_of(kj * block_k, block_k), block_k)
        dv_acc[keys, :] += _dot(_dropped(pt, keep, dropout).astype(do.dtype),
                                do)
        dst = (pt * (_dropped(dpt, keep, dropout) - delta_ref[0, 0])
               ).astype(q.dtype)
        dk_acc[keys, :] += _dot(dst, q)
        queries = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_acc[queries, :] += _dot(dst, k, _TN)

    _visible_pairs(_compute, causal, qi, kj, block_q, block_k, window, seq)

    @pl.when(last)
    def _finish_q():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    @pl.when(last & (g == group - 1))
    def _finish_kv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _padded(width):
    """Lanes a row of ``width`` takes in VMEM."""
    return -(-width // _LANES) * _LANES


def fused_vmem_bytes(s, d, d_v, itemsize):
    """VMEM that the fused backward holds whole-sequence: the float32
    accumulators of dq, dk (``d`` wide) and dv (``d_v``), and the output
    blocks of the same widths, double-buffered in the operands' dtype."""
    lanes = 2 * _padded(d) + _padded(d_v)
    return s * lanes * (4 + 2 * itemsize)


def takes_fused(s, d, d_v, itemsize):
    """Whether a backward of this shape takes the fused kernel: its
    whole-sequence blocks fit ``FUSED_VMEM_BUDGET``; a longer sequence
    takes the split pair, whose VMEM does not grow with it."""
    return fused_vmem_bytes(s, d, d_v, itemsize) <= FUSED_VMEM_BUDGET


@functools.partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12, 13, 14))
def _flash_bwd(q, k, v, seed, o, lse, do, scale, causal, block_q, block_k,
               interpret, dropout, window, fused):
    bh, s, d = q.shape
    d_v = v.shape[-1]
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    group = bh // k.shape[0]
    n_k, key_block, n_q, query_block = _needed(causal, block_q, block_k,
                                               window, s)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse = lse.reshape(bh, s // block_q, 1, block_q)
    delta = delta.reshape(bh, s // block_q, 1, block_q)
    operands = (seed, q, k, v, do, lse, delta)
    if not fused:
        return _split_bwd(operands, scale, causal, block_q, block_k, group,
                          (n_k, key_block, n_q, query_block), interpret,
                          dropout, window)

    # grouped-query heads: the grid walks a K/V head's group of query heads
    # one after another, each over every (key block, query block) pair
    def q_spec(width):
        return pl.BlockSpec(
            (1, block_q, width),
            lambda b, g, j, i: (b * group + g, query_block(i, j), 0))

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width), lambda b, g, j, i: (b, j, 0))
    row_spec = _row_spec(
        block_q, lambda b, g, j, i: (b * group + g, query_block(i, j), 0, 0))
    return pl.pallas_call(
        functools.partial(_dqkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group,
                          n_q=n_q, dropout=dropout, window=window, seq=s),
        grid=(k.shape[0], group, s // block_k, n_q),
        in_specs=[pl.BlockSpec((1,), lambda b, g, j, i: (0,)),
                  q_spec(d), kv_spec(d), kv_spec(d_v), q_spec(d_v), row_spec,
                  row_spec],
        out_specs=[
            pl.BlockSpec((1, s, d), lambda b, g, j, i: (b * group + g, 0, 0)),
            pl.BlockSpec((1, s, d), lambda b, g, j, i: (b, 0, 0)),
            pl.BlockSpec((1, s, d_v), lambda b, g, j, i: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((s, d), jnp.float32),
                        pltpu.VMEM((s, d_v), jnp.float32)],
        compiler_params=_FUSED_PARAMS,
        name=_name("bwd_dqkv", window),
        interpret=interpret,
    )(*operands)


def _split_bwd(operands, scale, causal, block_q, block_k, group, needed,
               interpret, dropout, window):
    """dq, then dk and dv, by two kernels that each make the pairs' scores
    and probabilities: VMEM holds a block of each, for any length."""
    seed, q, k, v, do, lse, delta = operands
    bh, s, d = q.shape
    d_v = v.shape[-1]
    n_k, key_block, n_q, query_block = needed

    def q_spec(width):
        return pl.BlockSpec((1, block_q, width), lambda b, i, j: (b, i, 0))

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width),
                            lambda b, i, j: (b // group, key_block(i, j), 0))
    row_spec = _row_spec(block_q, lambda b, i, j: (b, i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, n_k=n_k,
                          dropout=dropout, window=window, seq=s),
        grid=(bh, s // block_q, n_k),
        in_specs=[pl.BlockSpec((1,), lambda b, i, j: (0,)),
                  q_spec(d), kv_spec(d), kv_spec(d_v), q_spec(d_v), row_spec,
                  row_spec],
        out_specs=q_spec(d),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        name=_name("bwd_dq", window),
        interpret=interpret,
    )(*operands)

    # grouped-query heads: a K/V head's gradient is the sum over its group
    # of query heads, which the grid walks before it moves to the next block
    def q_spec(width):
        return pl.BlockSpec(
            (1, block_q, width),
            lambda b, j, g, i: (b * group + g, query_block(i, j), 0))

    def kv_spec(width):
        return pl.BlockSpec((1, block_k, width), lambda b, j, g, i: (b, j, 0))
    row_spec = _row_spec(
        block_q, lambda b, j, g, i: (b * group + g, query_block(i, j), 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, group=group,
                          n_q=n_q, dropout=dropout, window=window, seq=s),
        grid=(k.shape[0], s // block_k, group, n_q),
        in_specs=[pl.BlockSpec((1,), lambda b, j, g, i: (0,)),
                  q_spec(d), kv_spec(d), kv_spec(d_v), q_spec(d_v), row_spec,
                  row_spec],
        out_specs=[kv_spec(d), kv_spec(d_v)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d_v), jnp.float32)],
        name=_name("bwd_dkv", window),
        interpret=interpret,
    )(*operands)
    return dq, dk, dv


# ----------------------------------------------------------- public api -----
# The forward rule tags the kernel's output and log-sum-exp with these names.
# A recomputed block (``gluon.Block.recompute``) keeps what carries them for
# its backward pass, which then runs the rest of the block's forward again
# but not this kernel.  Outside a ``jax.checkpoint`` a name is the identity.
KEPT = ("flash_attention.out", "flash_attention.lse")
_traced = threading.local()


def traced_bytes():
    """The bytes of ``out`` and ``lse`` of every call made on this thread so
    far: across the trace of a recomputed block, what that block keeps."""
    return getattr(_traced, "bytes", 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_attention_core(q, k, v, seed, scale, causal, block_q, block_k,
                          interpret, dropout, window):
    out, _ = _flash_fwd_rule(q, k, v, seed, scale, causal, block_q, block_k,
                             interpret, dropout, window)
    return out


def flash_attention(q, k, v, scale=None, causal=False, block_q=128,
                    block_k=128, interpret=None, dropout=0.0, seed=None,
                    window=None):
    """softmax(scale · Q Kᵀ [, causal]) V without materialising S×S.

    q: (B*H, S, D); k the same, or (B*H_kv, S, D) for grouped-query
    heads with H a multiple of H_kv: query head h reads K/V head
    h // (H / H_kv) through the kernels' index maps, and nothing is repeated
    in memory.  v is k's shape but for its head, ``D_v``, which may differ
    from Q's and K's ``D`` (latent attention: 192 / 128); the output is
    (B*H, S, D_v), and the scale defaults to ``1 / sqrt(D)``.  ``dropout`` applies attention-probability dropout
    inside the kernel (the mask is regenerated from a counter-based hash in
    forward AND backward — never stored).  ``seed`` may be a traced int32
    scalar so each training step draws a fresh mask without retracing.
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU (CPU-mesh
    tests) and the compiled kernel on TPU.  ``window=w`` (causal only):
    query ``t`` sees keys ``t-w+1 .. t``, itself included, and the kernels'
    grids walk that band alone."""
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window!r} needs causal=True and at least one key")
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1,))
    out = _flash_attention_core(q, k, v, seed, scale, causal, block_q,
                                block_k, interpret, dropout, window)
    _traced.bytes = traced_bytes() + out.size * out.dtype.itemsize \
        + out.shape[0] * out.shape[1] * 4          # lse: float32 (B*H, S)
    return out


def _resolve(scale, d, interpret):
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = not on_tpu()
    return scale, interpret


def _flash_fwd_rule(q, k, v, seed, scale, causal, block_q, block_k,
                    interpret, dropout, window):
    scale, interpret = _resolve(scale, q.shape[-1], interpret)
    out, lse = _flash_fwd(q, k, v, seed, scale, causal, block_q, block_k,
                          interpret, float(dropout), window)
    # the primal output carries the name too: the output projection's
    # gradient reads it, and an untagged copy would run the kernel again
    out, lse = checkpoint_name(out, KEPT[0]), checkpoint_name(lse, KEPT[1])
    return out, (q, k, v, seed, out, lse)


def backward_paths():
    """``(fused, split)``: the backward calls traced on this thread so far
    that took the fused kernel and those that took the split pair."""
    return getattr(_traced, "fused", 0), getattr(_traced, "split", 0)


@contextlib.contextmanager
def publish_backward_paths():
    """Around one trace of a differentiated program: set the gauge
    ``flash.bwd_fused_share`` to the share of the backward calls traced
    inside that took the fused kernel (1: all of them).  Left alone where
    none was traced."""
    before = backward_paths()
    yield
    fused, split = (n - m for n, m in zip(backward_paths(), before))
    if fused + split:
        telemetry.registry().gauge("flash.bwd_fused_share").set(
            fused / (fused + split))


def _flash_bwd_rule(scale, causal, block_q, block_k, interpret, dropout,
                    window, res, do):
    q, k, v, seed, o, lse = res
    fused = takes_fused(q.shape[1], q.shape[-1], v.shape[-1],
                        q.dtype.itemsize)
    path = "fused" if fused else "split"
    setattr(_traced, path, getattr(_traced, path, 0) + 1)
    scale, interpret = _resolve(scale, q.shape[-1], interpret)
    dq, dk, dv = _flash_bwd(q, k, v, seed, o, lse, do, scale, causal,
                            block_q, block_k, interpret, float(dropout),
                            window, fused)
    return dq, dk, dv, None


_flash_attention_core.defvjp(_flash_fwd_rule, _flash_bwd_rule)
