"""Paged decode attention — single-query attention over a paged KV cache.

The serving decode loop (serving/generate.py) keeps every in-flight
sequence's K/V in fixed-size *pages* drawn from one shared pool
``[n_pages, page_size, heads, head_dim]`` per layer, addressed through a
per-slot page table.  This op computes, for each decode slot, attention
of its single query token over its own (ragged-length) cached context —
the PAPERS.md *Ragged Paged Attention* formulation (arXiv:2604.15464):
sequences of any mix of lengths share ONE compiled program, because the
pool/table/length shapes are configuration constants, never functions
of traffic.

Two execution paths, selected like ``ops/pallas/flash_attention.py``:

- **pure-jnp** (default off-TPU): gather pages by table, mask past each
  slot's length, softmax — runs under ``JAX_PLATFORMS=cpu`` so the whole
  serving stack (and tier-1) needs no accelerator.  The gather
  materialises a ``[slots, max_ctx, H, D]`` temp, which is fine on CPU:
  the *resident* state is still the paged pool.
- **Pallas ragged kernel** (``ops/pallas/paged_attention.py``) on TPU:
  pages stream HBM→VMEM through a scalar-prefetched page-table index
  map, with the online-softmax recurrence across a slot's pages and a
  skip for pages past the slot's length — no dense temp, no per-length
  recompile.

``dense_decode_attention`` is the max-length dense-cache reference the
paged path is budgeted against (the costguard ``llm_decode_step`` vs
``llm_decode_step_dense`` golden pair) and parity-tested with.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..context import on_tpu
from .registry import register_op

_NEG = -1e30


def _masked_softmax(scores, valid):
    """Softmax over the key axis with invalid keys masked.  A slot with
    ZERO valid keys (an inactive decode slot) degrades to uniform
    weights, not NaN: every score is the same ``_NEG`` constant, and
    softmax subtracts the max before exponentiating — callers ignore
    inactive rows, they must not poison the batch with NaN."""
    scores = jnp.where(valid, scores, jnp.asarray(_NEG, scores.dtype))
    return jax.nn.softmax(scores, axis=-1)


@register_op("paged_decode_attention")
def paged_decode_attention(q, k_pages, v_pages, page_tables, lengths,
                           impl=None):
    """Single-query attention over a paged KV cache.

    Args:
      q:           ``[slots, heads, head_dim]`` — one query token per
                   decode slot.
      k_pages:     ``[n_pages, page_size, heads, head_dim]`` shared pool.
      v_pages:     same shape as ``k_pages``.
      page_tables: ``[slots, pages_per_seq]`` int32 page ids per slot
                   (page 0 is the serving allocator's write sink; unused
                   table entries may be 0 — they are masked by length).
      lengths:     ``[slots]`` int32 — valid KV tokens per slot,
                   INCLUDING the just-written current token.  0 marks an
                   inactive slot (output row is garbage, never NaN).
      impl:        None (auto: Pallas on TPU, jnp elsewhere), "jnp", or
                   "pallas".

    Returns ``[slots, heads, head_dim]`` attention output.
    """
    if impl is None:
        impl = "pallas" if on_tpu() else "jnp"
    if impl == "pallas":
        from .pallas.paged_attention import paged_decode_attention_pallas
        return paged_decode_attention_pallas(q, k_pages, v_pages,
                                             page_tables, lengths)
    if impl != "jnp":
        raise ValueError(f"paged_decode_attention: impl={impl!r} "
                         f"(expected None, 'jnp', or 'pallas')")
    n_pages, page_size, heads, head_dim = k_pages.shape
    slots, pages_per_seq = page_tables.shape
    ctx = pages_per_seq * page_size
    # gather each slot's pages: [slots, pages_per_seq, page, H, D] and
    # flatten the (page-table, in-page) axes into one context axis
    k_ctx = k_pages[page_tables].reshape(slots, ctx, heads, head_dim)
    v_ctx = v_pages[page_tables].reshape(slots, ctx, heads, head_dim)
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, q.dtype))
    scores = jnp.einsum("shd,schd->shc", q * scale, k_ctx)
    pos = jnp.arange(ctx, dtype=lengths.dtype)
    valid = (pos[None, None, :] < lengths[:, None, None])
    w = _masked_softmax(scores, valid)
    return jnp.einsum("shc,schd->shd", w, v_ctx)


@register_op("dense_decode_attention")
def dense_decode_attention(q, k_cache, v_cache, lengths):
    """The dense max-length-cache reference: every slot owns a
    ``[max_ctx, H, D]`` stripe of a ``[slots, max_ctx, H, D]`` cache
    whether it uses it or not — the per-sequence HBM reservation the
    paged pool exists to reclaim.  Same masking/length semantics as
    ``paged_decode_attention``; the two are parity-tested token-exact
    (up to float assoc) in tests/test_generate.py."""
    slots, ctx, heads, head_dim = k_cache.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, q.dtype))
    scores = jnp.einsum("shd,schd->shc", q * scale, k_cache)
    pos = jnp.arange(ctx, dtype=lengths.dtype)
    valid = (pos[None, None, :] < lengths[:, None, None])
    w = _masked_softmax(scores, valid)
    return jnp.einsum("shc,schd->shd", w, v_cache)
