"""Each token's ``k`` rows of a sorted row buffer, reduced per token, with
only the held rows fetched.

A dropless expert layer (``parallel/moe.py``) sorts its N x k assignments by
held expert into a buffer of ``M = N * k`` rows: rows ``offsets[e] ..
offsets[e + 1]`` belong to held expert ``e``, and rows past ``total =
offsets[-1]`` to no held expert.  ``back`` (N, k) names, token by token, the
sorted rows of its ``k`` assignments.  Two kernels read a token's held rows:

- ``moe_token_sum``: ``sum_j weights[n, j] * rows[back[n, j]]`` (N, d) over
  the ``j`` with ``back[n, j] < total``, accumulated in float32 and cast to
  the rows' type: the combine's weighted sum, and with unit weights the
  backward pass of the gather in (one lowering for both);
- ``moe_token_dot``: ``<rows[back[n, j]], dy[n]>`` (N, k) float32 where
  ``back[n, j] < total``, 0 elsewhere: the gates' gradient.

**What the DMA can fetch.**  A bf16 buffer lies in HBM in tiles of 8 rows,
and Mosaic copies no slice of fewer (one row's halfwords are interleaved with
its neighbour's).  But the sort is stable, so the assignments of a block of
consecutive tokens to one expert are one contiguous RUN of sorted rows.  A
grid step takes a block of tokens and DMAs, expert by expert, the 8-row
chunks that cover its run into consecutive VMEM slots (a **token map**,
``token_map``, built once a layer from ``back`` and the experts' offsets,
says where each run starts, how many chunks it takes and in which slot each
assignment lands); the next block's copies start before this block's are
waited for, into the other of two sets of slots.  No copy is made for an
expert with no row in the block, nor for any row past the last held run.

**How a block is reduced.**  On the MXU, 128 slots at a time, and only as
many times as the block's chunks fill: the sum is ``W @ slots`` with ``W``
(tokens, 128) each slot's weight in its token's row (float32 split into two
bf16 parts, so the weights keep 16 bits), the dot is ``dy @ slots.T`` with a
select of each assignment's slot.  Rows of a chunk past ``total`` (which the
buffer's producer may never have written) are zeroed in VMEM before they
meet a zero of ``W``, and both sets of slots are zeroed once a call.

Each kernel is one module-level ``jax.jit`` over its jaxpr traced once a
process (``once.bind``), as the grouped products are.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu
from .once import bind

# tokens a grid step: the MXU's work grows with it (a block's slots against
# its tokens), the chunks' rounding and the steps' overhead shrink
_TOKENS = 64
# rows a DMA: one HBM tile of the row buffer; slots the MXU takes at once
_CHUNK = 8
_SLOTS = 128
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=48 * 1024 * 1024)


class TokenMap(NamedTuple):
    """Where a block of tokens' held rows are fetched from and land
    (int32): ``first`` and ``chunks`` (blocks, held) each run's first
    8-row chunk and its count of chunks (0 for an empty run); ``slots``
    (blocks x tokens, k) each assignment's slot among its block's fetched
    rows, -1 for an assignment past ``total``; ``total`` (1,).  The kernels
    take the block of tokens from these shapes."""
    first: jax.Array
    chunks: jax.Array
    slots: jax.Array
    total: jax.Array


def _block(n):
    """Tokens a grid step, from the shapes alone."""
    return min(_TOKENS, n)


def _slot_rows(block, k, held):
    """Slots a set: every assignment of a block held, each run's rounding
    to whole chunks at both ends, in whole groups of ``_SLOTS``."""
    return pl.cdiv(block * k + 2 * (_CHUNK - 1) * held, _SLOTS) * _SLOTS


def _chunk_start(first, i, m):
    """The first row of a run's ``i``-th chunk: whole chunks of 8, the last
    of a buffer whose rows are no multiple of 8 ending at its last row."""
    return jnp.minimum((first + i) * _CHUNK, m - min(_CHUNK, m))


@jax.jit
def token_map(back, offsets):
    """The map of ``back`` (N, k) over a buffer of N x k sorted rows whose
    held experts start at ``offsets`` (held + 1,) int32 (a device value, the
    last one ``total``).  A ``jax.jit``, built once a layer."""
    n, k = back.shape
    m, block, held = back.size, _block(n), offsets.shape[0] - 1
    steps = pl.cdiv(n, block)
    back = jnp.pad(back, ((0, steps * block - n), (0, 0)), constant_values=m)
    # each assignment's held expert, ``held`` past ``total``: a dense
    # comparison, no search loop
    expert = jnp.sum(back[..., None] >= offsets[None, None, 1:], axis=-1,
                     dtype=jnp.int32)
    onehot = expert.reshape(steps, block * k, 1) == jnp.arange(
        held, dtype=jnp.int32)                        # (steps, T * k, held)
    runs = jnp.sum(onehot, axis=1, dtype=jnp.int32)           # (steps, held)
    start = offsets[:-1] + jnp.cumsum(runs, axis=0) - runs
    first = start // _CHUNK
    chunks = jnp.where(runs > 0, (start + runs + _CHUNK - 1) // _CHUNK - first,
                       0)
    base = (jnp.cumsum(chunks, axis=1) - chunks) * _CHUNK

    def pick(table):
        # each assignment's entry of its block's (steps, held) table: a
        # select over the held experts, where a gather would be a slow op
        return jnp.sum(jnp.where(onehot, table[:, None, :], 0), axis=-1,
                       dtype=jnp.int32).reshape(steps * block, k)
    back_first = pick(first)
    i = back // _CHUNK - back_first
    slots = pick(base) + i * _CHUNK + back - _chunk_start(back_first, i, m)
    return TokenMap(first, chunks,
                    jnp.where(expert < held, slots, -1).astype(jnp.int32),
                    offsets[-1:].astype(jnp.int32))


def _fetch(first_ref, chunks_ref, total_ref, rows_hbm, rows_ref, sems):
    """Start the next block's copies into the other set of slots (the first
    step its own as well), wait for this block's, zero what of them lies past
    ``total``.  Returns (this block's set, its count of chunks)."""
    i, steps = pl.program_id(0), pl.num_programs(0)
    held = first_ref.shape[0] // steps
    m, rows = rows_hbm.shape[0], min(_CHUNK, rows_hbm.shape[0])
    total = total_ref[0]

    def copy(source, slot, s):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(source, rows)],
            rows_ref.at[s, pl.ds(pl.multiple_of(slot, _CHUNK), rows)],
            sems.at[s])

    def start(b, s):
        def expert(e, slot):
            first, chunks = first_ref[b * held + e], chunks_ref[b * held + e]

            def chunk(c, slot):
                copy(_chunk_start(first, c, m), slot, s).start()
                return slot + _CHUNK
            return jax.lax.fori_loop(0, chunks, chunk, slot)
        jax.lax.fori_loop(0, held, expert, 0)

    @pl.when(i == 0)
    def _():
        # slots no chunk fills this call may meet a zero weight
        rows_ref[...] = jnp.zeros_like(rows_ref)
        start(0, 0)

    @pl.when(i + 1 < steps)
    def _():
        start(i + 1, (i + 1) % 2)
    s = i % 2
    count = jax.lax.fori_loop(
        0, held, lambda e, n: n + chunks_ref[i * held + e], 0)

    def wait(c, carry):
        copy(0, 0, s).wait()
        return carry
    jax.lax.fori_loop(0, count, wait, 0)

    def past(e, slot):
        # a run's last chunk may hold rows past ``total``, which the
        # buffer's producer may never have written
        chunks = chunks_ref[i * held + e]
        last = slot + (chunks - 1) * _CHUNK
        source = _chunk_start(first_ref[i * held + e], chunks - 1, m)

        @pl.when((chunks > 0) & (source + rows > total))
        def _():
            at = source + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            got = rows_ref[s, pl.ds(pl.multiple_of(last, _CHUNK), rows)]
            rows_ref[s, pl.ds(pl.multiple_of(last, _CHUNK), rows)] = \
                jnp.where(at < total, got, jnp.zeros_like(got))
        return slot + chunks * _CHUNK
    jax.lax.fori_loop(0, held, past, 0)
    return s, count


def _groups(count, body, init):
    """``body(the group's first slot, carry)`` over the groups of
    ``_SLOTS`` slots the block's ``count`` chunks fill."""
    def each(g, carry):
        return body(pl.multiple_of(g * _SLOTS, _SLOTS), carry)
    return jax.lax.fori_loop(0, (count * _CHUNK + _SLOTS - 1) // _SLOTS,
                             each, init)


def _dot(a, b, dims):
    """A product on the MXU in float32: bf16 operands in one pass, float32
    ones in full precision."""
    exact = a.dtype == b.dtype == jnp.bfloat16
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=None if exact else jax.lax.Precision.HIGHEST)


def _sum_kernel(first_ref, chunks_ref, total_ref, slots_ref, w_ref,
                rows_hbm, out_ref, rows_ref, acc_ref, sems):
    s, count = _fetch(first_ref, chunks_ref, total_ref, rows_hbm, rows_ref,
                      sems)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    k = slots_ref.shape[1]

    def group(g, carry):
        at = g + jax.lax.broadcasted_iota(jnp.int32, (1, _SLOTS), 1)
        weigh = jnp.zeros((out_ref.shape[0], _SLOTS), jnp.float32)
        for j in range(k):
            weigh = jnp.where(slots_ref[:, j:j + 1] == at, w_ref[:, j:j + 1],
                              weigh)
        rows = rows_ref[s, pl.ds(g, _SLOTS)]
        dims = ((1,), (0,))
        if rows.dtype == jnp.bfloat16:
            # the weights in two bf16 parts: 16 bits of each, exact products
            high = weigh.astype(jnp.bfloat16)
            low = (weigh - high.astype(jnp.float32)).astype(jnp.bfloat16)
            acc_ref[...] += _dot(high, rows, dims) + _dot(low, rows, dims)
        else:
            acc_ref[...] += _dot(weigh.astype(rows.dtype), rows, dims)
        return carry
    _groups(count, group, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _dot_kernel(first_ref, chunks_ref, total_ref, slots_ref, dy_ref,
                rows_hbm, out_ref, rows_ref, sems):
    s, count = _fetch(first_ref, chunks_ref, total_ref, rows_hbm, rows_ref,
                      sems)
    out_ref[...] = jnp.zeros_like(out_ref)
    dy = dy_ref[...]

    def group(g, carry):
        at = g + jax.lax.broadcasted_iota(jnp.int32, (1, _SLOTS), 1)
        rows = rows_ref[s, pl.ds(g, _SLOTS)]
        got = _dot(dy.astype(rows.dtype), rows, ((1,), (1,)))
        for j in range(slots_ref.shape[1]):
            out_ref[:, j:j + 1] += jnp.sum(
                jnp.where(slots_ref[:, j:j + 1] == at, got, 0.0), axis=1,
                keepdims=True)
        return carry
    _groups(count, group, 0)


@functools.partial(jax.jit, static_argnames=("dot", "interpret"))
def _reduce(rows, tmap, other, *, dot, interpret):
    """One of the two kernels, ``other`` the weights (N, k) float32 or
    ``dy`` (N, d).  A ``jax.jit``, so that a model's layers and passes share
    one lowering of each, over the kernel traced once a process."""
    return bind(_build, (rows, *tmap, other), dot=dot,
                interpret=interpret)[0]


def _build(rows, *operands, dot, interpret):
    tmap, other = TokenMap(*operands[:4]), operands[4]
    (steps, held), (blocks, k) = tmap.first.shape, tmap.slots.shape
    n, d, block = other.shape[0], rows.shape[1], blocks // steps

    def tokens(i, first, chunks, total):
        return i, 0
    kernel, name, out = (
        (_dot_kernel, "moe_token_dot",
         jax.ShapeDtypeStruct((n, k), jnp.float32)) if dot else
        (_sum_kernel, "moe_token_sum",
         jax.ShapeDtypeStruct((n, d), rows.dtype)))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[pl.BlockSpec((block, k), tokens),
                      pl.BlockSpec((block, other.shape[1]), tokens),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, out.shape[1]), tokens),
            scratch_shapes=[
                pltpu.VMEM((2, _slot_rows(block, k, held), d), rows.dtype),
                *([] if dot else [pltpu.VMEM((block, d), jnp.float32)]),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=out, compiler_params=_PARAMS, name=name,
        interpret=interpret,
    )(tmap.first.reshape(-1), tmap.chunks.reshape(-1), tmap.total,
      tmap.slots, other, rows)


def moe_token_sum(rows, tmap, weights):
    """``sum_j weights[n, j] * rows[back[n, j]]`` (N, d) in ``rows``' type
    over the ``j`` with ``back[n, j] < total``: ``rows`` (M, d), ``tmap``
    the ``token_map`` of ``back`` (N, k), ``weights`` (N, k).  Rows at or
    past ``total`` may hold anything: none reaches the result."""
    return _reduce(rows, tmap, weights.astype(jnp.float32), dot=False,
                 interpret=not on_tpu())


def moe_token_dot(rows, tmap, dy):
    """``<rows[back[n, j]], dy[n]>`` (N, k) float32 where ``back[n, j] <
    total``, 0 elsewhere: ``rows`` (M, d), ``tmap`` the ``token_map`` of
    ``back`` (N, k), ``dy`` (N, d).  Rows at or past ``total`` may hold
    anything: none reaches the result."""
    return _reduce(rows, tmap, dy, dot=True, interpret=not on_tpu())
