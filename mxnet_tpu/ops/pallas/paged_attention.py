"""Ragged paged decode attention — Pallas TPU kernel.

One query token per decode slot attends over that slot's paged KV
context (PAPERS.md: *Ragged Paged Attention*, arXiv:2604.15464).  The
page table and per-slot lengths ride as **scalar-prefetch** operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V block index maps resolve
each grid step's page id *before* the body runs: pages stream
HBM→VMEM one at a time, the kernel never materialises a slot's dense
``[max_ctx, H, D]`` context, and — the ragged part — a slot's grid
steps past its own length are skipped entirely (``pl.when``), so a
batch mixing 3-token and 3000-token sequences pays each slot only its
own pages.  Shapes are configuration constants (pool, table, slot
count), so every traffic mix runs this ONE program.

Accumulation is the online-softmax recurrence across a slot's pages
(same scheme as ``flash_attention.py``'s k-axis), carried in VMEM
scratch across the page axis of the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu

_NEG = -1e30


def _kernel(tables_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, page_size, pages_per_seq):
    s = pl.program_id(0)          # decode slot
    j = pl.program_id(1)          # page index within the slot's table
    length = len_ref[s]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * page_size < length)
    def _page():
        q = q_ref[0].astype(jnp.float32)            # (H, D)
        k = k_ref[0].astype(jnp.float32)            # (page, H, D)
        v = v_ref[0].astype(jnp.float32)
        heads = q.shape[0]
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
        qs = q * scale
        # Mosaic only lowers 2D dots, so the batched ``hd,phd->hp``
        # einsum is unrolled into one (1,D)·(page,D) contraction per
        # head (H is a small compile-time constant)
        sc = jnp.concatenate([
            jax.lax.dot_general(
                qs[h][None, :], k[:, h, :],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for h in range(heads)
        ], axis=0)                                   # (H, page)
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)                  # (H, page)
        sc = jnp.where(pos < length, sc, _NEG)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[:, None])
        l_ref[...] = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.concatenate([
            jax.lax.dot_general(
                p[h][None, :], v[:, h, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for h in range(heads)
        ], axis=0)                                   # (H, D)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == pages_per_seq - 1)
    def _finish():
        l = l_ref[...]
        # an inactive slot (length 0) never ran a page: l stays 0 and the
        # output row is zeros, mirroring the jnp path's "garbage, never
        # NaN" contract
        norm = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[...] / norm[:, None]).astype(o_ref.dtype)


def paged_decode_attention_pallas(q, k_pages, v_pages, page_tables,
                                  lengths, interpret=None):
    """Pallas path of ``ops.paged_attention.paged_decode_attention``
    (same argument contract).  ``interpret=None`` auto-selects the
    Pallas interpreter off-TPU so parity tests run anywhere."""
    if interpret is None:
        interpret = not on_tpu()
    n_pages, page_size, heads, head_dim = k_pages.shape
    slots, pages_per_seq = page_tables.shape
    kernel = functools.partial(_kernel, page_size=page_size,
                               pages_per_seq=pages_per_seq)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(slots, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, heads, head_dim), lambda s, j, t, ln: (s, 0, 0)),
            # the scalar-prefetched page table drives the DMA: grid step
            # (s, j) pulls page t[s, j] of the pool into VMEM
            pl.BlockSpec((1, page_size, heads, head_dim),
                         lambda s, j, t, ln: (t[s, j], 0, 0, 0)),
            pl.BlockSpec((1, page_size, heads, head_dim),
                         lambda s, j, t, ln: (t[s, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, heads, head_dim),
                               lambda s, j, t, ln: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((heads, head_dim), jnp.float32),
            pltpu.VMEM((heads,), jnp.float32),
            pltpu.VMEM((heads,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots, heads, head_dim), q.dtype),
        name="paged_attention",
        interpret=interpret,
    )(page_tables, lengths, q, k_pages, v_pages)
