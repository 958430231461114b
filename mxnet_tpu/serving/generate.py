"""Continuous-batching LLM serving: paged KV cache + one-executable decode.

Every other serving surface in the stack batches *requests*; an
autoregressive LM needs token-level batching — sequences join and leave
the in-flight batch at every decode step.  Done naively (one jitted call
per sequence, a dense ``[max_len]`` cache per sequence) that is the worst
possible shape for a bandwidth-bound chip: recompiles keyed on traffic,
and HBM reserved for contexts that mostly aren't there.  This module is
the PAPERS.md *Ragged Paged Attention* / Gemma-serving design
(arXiv:2604.15464, 2605.25645) on top of the PR 4 serving substrate:

- **Paged KV cache** — one fixed pool ``[n_layers, n_pages, page_size,
  heads, head_dim]`` per K and V; sequences hold *pages* through a page
  table and a host-side free list (``PageAllocator``).  HBM cost is the
  pool, a configuration constant sized for expected concurrency — not
  ``n_slots × max_len`` dense stripes (the costguard
  ``llm_decode_step`` vs ``llm_decode_step_dense`` golden pair commits
  the ≥ 40% argument-bytes win in tier-1).
- **One pinned decode executable** — every decode step, whatever the
  in-flight mix of sequence lengths/ages/sampling modes, runs the SAME
  jitted program over a fixed slot grid: slot-mask + page-table + length
  arrays are the arguments, shapes are constants.  Traffic can never
  recompile; the executable census is ``len(batch buckets) ×
  len(length buckets) + 1`` (prefill grid + decode), asserted against
  the runtime jit-cache count in tests.
- **Continuous-batching scheduler** (``GenerationServer``) — prompts
  prefill through the existing ``BucketSpec`` length buckets (each
  bucket warmup-compiled before readiness), sequences are admitted into
  fixed decode slots, retire per-step on EOS/max-tokens/deadline (pages
  freed and queued sequences admitted the *same* step), and pool
  exhaustion preempts the youngest sequence back onto the queue instead
  of deadlocking.  Admission control (bounded queue, token bucket,
  deadlines, ``Request`` futures), the circuit breaker, ``healthz`` and
  ``drain()``/SIGTERM semantics are all the PR 4 pieces reused: an
  accepted sequence ALWAYS resolves to tokens or an explicit error.

Sampling is greedy or temperature/top-k per request, drawn from a
PER-POSITION PRNG schedule inside the compiled program: every sequence
carries its own sampling seed (derived from the server seed and its
admission ordinal, or set explicitly at ``submit``) and the key for the
token at absolute position ``p`` of prompt+output is
``fold_in(PRNGKey(seed), p)`` — a pure function of (sequence, position),
never of the step counter or slot index.  That is what makes generation
RESUMABLE token-exact (ISSUE 19): a sequence preempted, salvaged off a
failed step, handed to another replica, or restored from the decode
journal after kill -9 re-prefills its prompt + generated-so-far through
the existing bucket grid and then samples the IDENTICAL future tokens
the uninterrupted run would have (greedy and seeded sampling alike).
``SequenceSnapshot`` is the portable resume state; ``drain(handoff=
True)`` exports it instead of finishing, and ``restore_journal``
re-imports a crashed sibling's in-flight set.

``tp_shards=N`` shards the whole stack tensor-parallel over an N-way
``tp`` mesh (``parallel.mesh``): head-parallel paged attention (each
device owns a head shard of the page pools), Megatron column/row
sharded projections/FFN, and per-layer activation all-reduces on the
decode path in f32 or chunked-int8 wire format
(``tp_collectives=``, ``parallel.quantize.all_reduce_activations``).
The census, scheduler, and failure semantics are shard-count
invariant — see the ``GenerationServer`` docstring.

Failure paths are deterministic tests via the ``generate.prefill`` /
``generate.decode`` / ``generate.evict`` fault points
(``tools/chaos_check.py --mode llm`` drives all of them plus SIGTERM).
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

import queue

from .. import fault as _fault
from .. import profiler as _profiler
from .. import telemetry as _telemetry
from .admission import (CircuitOpenError, DeadlineExceededError,
                        RejectedError, Request, ServerClosedError,
                        TenantQoS, TokenBucket)
from .batcher import BucketSpec
from .breaker import CircuitBreaker

__all__ = ["PageAllocator", "PoolExhaustedError", "GenerationServer",
           "SequenceSnapshot", "build_decode_step", "build_prefill_step",
           "build_prefill_kv_step", "build_handoff_step",
           "build_dense_decode_step", "build_verify_step",
           "prefix_admission_plan"]


class PoolExhaustedError(RuntimeError):
    """The page pool has no free page.  Internal scheduler signal — the
    decode loop preempts a sequence and retries; it never reaches a
    client, who instead sees either admission-time ``RejectedError``
    (a request whose worst case could never fit) or a later result."""


class PageAllocator:
    """Host-side REFCOUNTED free list over the fixed page pool.

    Page 0 is reserved as the *write sink*: masked/inactive lanes of the
    compiled programs scatter their K/V there, so the executables never
    branch on occupancy.  Pages ``1..n_pages-1`` are allocatable.  All
    methods are thread-safe (one lock, no blocking under it); the free
    list is LIFO, so a freed sequence's pages are immediately reused —
    fragmentation cannot accrete by construction (any free page serves
    any sequence; there is nothing contiguous to fragment).

    **Prefix sharing (ISSUE 16).**  Every live page carries a refcount:
    ``alloc`` hands out pages at refcount 1, ``share`` maps additional
    holders onto already-resident pages (a prompt whose leading blocks
    are already cached pays NOTHING for them), and ``free`` decrements —
    a page returns to the free list only when its LAST holder lets go.
    The allocator stays layout-free (a page id addresses every tp
    shard's stripe of that page at once), so sharing composes with
    head-sharded pools with no extra bookkeeping.  ``free`` on a page
    this allocator does not consider live (double-free, or an id that
    was never allocated) raises ``ValueError`` instead of silently
    corrupting the free list — load-bearing once refcounts arbitrate
    page lifetime across sequences."""

    def __init__(self, n_pages, page_size):
        if n_pages < 2:
            raise ValueError("PageAllocator: need >= 2 pages (page 0 is "
                             "the reserved write sink)")
        if page_size < 1:
            raise ValueError("PageAllocator: page_size must be >= 1")
        self.n_pages = int(n_pages)
        self.page_size = int(page_size)
        self._lock = threading.Lock()
        self._free = list(range(1, self.n_pages))   # LIFO tail = next out
        self._refs = {}                             # page -> live refcount

    @property
    def allocatable(self):
        """Pages a sequence can ever hold (pool minus the sink)."""
        return self.n_pages - 1

    def free_count(self):
        with self._lock:
            return len(self._free)

    def pages_for(self, n_tokens):
        """Pages needed to hold ``n_tokens`` cache entries."""
        return -(-int(n_tokens) // self.page_size)

    def alloc(self, n_pages):
        """Take ``n_pages`` pages or raise ``PoolExhaustedError`` (taking
        nothing — allocation is all-or-nothing so a half-admitted
        sequence can never strand pages).  Fresh pages start at
        refcount 1."""
        n = int(n_pages)
        if n <= 0:
            return []     # a fully shared prompt allocates nothing
        with self._lock:
            if n > len(self._free):
                raise PoolExhaustedError(
                    f"need {n} pages, {len(self._free)} free "
                    f"(pool {self.allocatable})")
            taken, self._free[-n:] = self._free[-n:], []
            for p in taken:
                self._refs[p] = 1
            return taken

    def share(self, pages):
        """Add one holder to each of ``pages`` (all must be live) —
        the prefix-sharing mapping: the new sequence holds the SAME
        resident pages instead of allocating copies.  Raises
        ``ValueError`` on a page that is not live (the prefix index
        may only hand out pages somebody still holds)."""
        with self._lock:
            for p in pages:
                if p not in self._refs:
                    raise ValueError(
                        f"PageAllocator.share: page {p} is not live — "
                        f"the prefix index handed out a freed page")
            for p in pages:
                self._refs[p] += 1
        return list(pages)

    def refcount(self, page):
        """Live holders of ``page`` (0 when free/unknown)."""
        with self._lock:
            return self._refs.get(int(page), 0)

    def shared_pages(self):
        """Pages currently held by MORE than one sequence."""
        with self._lock:
            return sum(1 for c in self._refs.values() if c > 1)

    def extra_refs(self):
        """Total holders beyond the first, over all live pages — the
        number of page copies prefix sharing made unnecessary
        (``bytes_saved_by_sharing`` = this x page bytes)."""
        with self._lock:
            return sum(c - 1 for c in self._refs.values() if c > 1)

    def live_pages(self):
        """Count of live (allocated, refcount >= 1) pages."""
        with self._lock:
            return len(self._refs)

    def free(self, pages):
        """Drop one holder from each of ``pages``; a page whose LAST
        holder lets go returns to the LIFO free list.  Returns the list
        of pages actually released (the caller's prefix index drops
        exactly those).  A page with no live refcount — a double free,
        or an id never allocated — raises ``ValueError`` with nothing
        freed: silently extending the free list would hand the same
        page to two sequences and corrupt both caches."""
        with self._lock:
            drops = {}
            for p in pages:
                drops[p] = drops.get(p, 0) + 1
            for p, n in drops.items():
                if self._refs.get(p, 0) < n:
                    raise ValueError(
                        f"PageAllocator.free: page {p} is not live "
                        f"(double free, or never allocated) — refusing "
                        f"to corrupt the free list")
            released = []
            for p in pages:
                self._refs[p] -= 1
                if self._refs[p] == 0:
                    del self._refs[p]
                    self._free.append(p)
                    released.append(p)
            return released


# --------------------------------------------------------------- samplers --
def _scaled_masked(logits, temps, topks):
    """Temperature-scaled, top-k-masked logits — the SHARED sampling
    transform: ``softmax`` of this is each row's sampling distribution.
    Factored out of ``_sample_tokens`` because the speculative verify
    step must evaluate the SAME distribution twice (the draft's ``q``
    and the target's ``p``) for the acceptance ratio to be exact."""
    import jax.numpy as jnp

    vocab = logits.shape[-1]
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    order = jnp.sort(scaled, axis=-1)[:, ::-1]          # descending
    kidx = jnp.clip(topks - 1, 0, vocab - 1)
    thr = jnp.take_along_axis(order, kidx[:, None], axis=1)
    cut = (topks[:, None] > 0) & (scaled < thr)
    return jnp.where(cut, jnp.asarray(-1e30, scaled.dtype), scaled)


def _position_keys(seeds, positions, domain=None):
    """The per-position PRNG schedule (ISSUE 19): the key for row ``i``
    is ``fold_in(PRNGKey(seeds[i]), positions[i])`` — a pure function
    of (sequence seed, absolute token position), never of the step
    counter or the slot index.  A resumed sequence therefore draws the
    IDENTICAL randomness the uninterrupted run would have at every
    future position.  ``domain`` sub-derives disjoint streams for the
    speculative roles (draft proposal / acceptance / correction) that
    all consume randomness at the same position."""
    import jax

    def one(sd, p):
        k = jax.random.fold_in(jax.random.PRNGKey(sd), p)
        return k if domain is None else jax.random.fold_in(k, domain)
    return jax.vmap(one)(seeds, positions)


def _sample_tokens(logits, seeds, positions, temps, topks):
    """Per-slot next-token choice inside the compiled program: greedy
    where ``temps == 0``, temperature softmax-sampling elsewhere, with
    an optional top-k cut (``topks > 0``).  Both arms always compute —
    that is what keeps a mixed greedy/sampling batch ONE executable —
    and row ``i`` draws from its position-keyed stream
    ``fold_in(PRNGKey(seeds[i]), positions[i])``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("sample"):
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        masked = _scaled_masked(logits, temps, topks)
        keys = _position_keys(seeds, positions)
        drawn = jax.vmap(jax.random.categorical)(keys, masked) \
            .astype(jnp.int32)
        return jnp.where(temps > 0.0, drawn, greedy)


# -------------------------------------------------------- program builders --
def _tp_pieces(config, mesh, axis):
    """Shared tensor-parallel plumbing of the program builders: shard
    count, local head count, the param/pool PartitionSpecs, and the
    ``shard_map`` wrapper (``parallel.mesh`` — call-time axis
    validation) curried with the mesh."""
    import functools

    from jax.sharding import PartitionSpec

    from ..gluon.model_zoo.causal_lm import tp_param_specs, tp_validate
    from ..parallel.mesh import shard_map

    shards = int(mesh.shape[axis])
    tp_validate(config, shards)
    pspecs = tp_param_specs(config, mesh, axis)
    pool_spec = PartitionSpec(None, None, None, axis, None)
    repl = PartitionSpec()
    wrap = functools.partial(shard_map, mesh=mesh, check_vma=False)
    return shards, config.n_heads // shards, pspecs, pool_spec, repl, wrap


def build_decode_step(config, page_size, attention_impl=None, mesh=None,
                      tp_axis="tp", tp_collectives="f32"):
    """The ONE decode executable: every in-flight mix of sequences runs
    this program over the fixed slot grid.

    Signature (all shapes configuration constants):
      ``(params, k_pool, v_pool, tokens[S], lengths[S], active[S],
      tables[S, P], cow_src[S], cow_dst[S], seeds[S], temps[S],
      topks[S])`` → ``(next_tokens[S], k_pool, v_pool)``.

    ``seeds[s]`` is slot ``s``'s per-sequence sampling seed; the next
    token (absolute position ``lengths[s] + 1`` of prompt+output) is
    drawn from ``fold_in(PRNGKey(seeds[s]), lengths[s] + 1)`` — the
    position-keyed schedule that makes resumed sequences token-exact
    (ISSUE 19).

    ``lengths[s]`` is the slot's cache occupancy BEFORE this step; the
    input token's K/V is written at position ``lengths[s]`` (page
    ``tables[s, lengths[s] // page_size]``), inactive slots sink to
    page 0, and attention covers ``lengths[s] + 1`` positions.  Pools
    are donated by the caller, so the update is in-place on device.

    ``cow_src``/``cow_dst`` are the copy-on-write fault lanes (ISSUE
    16): before anything else the program copies page ``cow_src[s]``
    onto page ``cow_dst[s]`` in both pools — the in-graph K/V page copy
    of a sequence diverging from a shared prefix, already remapped in
    ``tables`` by the host.  Slots without a fault pass ``(0, 0)``, a
    self-copy of the sink page — so the copy is ALWAYS part of the one
    pinned program and a CoW fault can never compile anything.

    With ``mesh`` (a ``tp_axis`` mesh) the SAME program lowers once
    over the mesh as one ``shard_map``: each device owns a head shard
    of the K/V pools (head-parallel paged attention — per-device pool
    HBM ∝ 1/shards), QKV/FFN-in are column-sharded and the output/
    FFN-out projections row-sharded (Megatron), and the two per-layer
    partial-product all-reduces run through
    ``parallel.quantize.all_reduce_activations`` in the
    ``tp_collectives`` wire format (``"f32"`` | ``"int8"`` — EQuARX:
    decode is latency-bound on collective bytes).  Slot state, tokens,
    and the sampled output stay replicated, so the serving loop drives
    both shapes identically."""
    import jax
    import jax.numpy as jnp

    from ..gluon.model_zoo.causal_lm import decode_hidden, lm_logits
    from ..ops.paged_attention import paged_decode_attention
    from ..parallel.quantize import (ACTIVATION_REDUCE_MODES,
                                     all_reduce_activations)

    if tp_collectives not in ACTIVATION_REDUCE_MODES:
        raise ValueError(f"tp_collectives={tp_collectives!r} not in "
                         f"{ACTIVATION_REDUCE_MODES}")
    n_layers = config.n_layers
    heads, head_dim = config.n_heads, config.head_dim
    if mesh is None:
        shards, heads_l, reduce_fn = 1, heads, None
    else:
        shards, heads_l, pspecs, pool_spec, repl, wrap = _tp_pieces(
            config, mesh, tp_axis)

        def reduce_fn(x):
            return all_reduce_activations(x, tp_axis, shards,
                                          mode=tp_collectives)

    def decode_step(params, k_pool, v_pool, tokens, lengths, active,
                    tables, cow_src, cow_dst, seeds, temps, topks):
        slots = tokens.shape[0]
        # CoW fault lanes first: dst pages take on src pages' content
        # BEFORE this step's writes/reads (faultless slots self-copy
        # the page-0 sink).  The gather reads the pre-step pool, so a
        # lane whose src page was concurrently recycled still copies
        # the prefix content it diverged from.
        with jax.named_scope("cow"):
            k_pool = k_pool.at[:, cow_dst].set(k_pool[:, cow_src])
            v_pool = v_pool.at[:, cow_dst].set(v_pool[:, cow_src])
        h = params["embed"][tokens]                     # [S, d]
        pos = lengths
        page = jnp.take_along_axis(tables, (pos // page_size)[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(active, page, 0)               # sink inactive
        off = pos % page_size
        att_len = jnp.where(active, lengths + 1, 0)

        for layer in range(n_layers):
            def attend(q, k, v, _l=layer):
                nonlocal k_pool, v_pool
                k = k.reshape(slots, heads_l, head_dim)
                v = v.reshape(slots, heads_l, head_dim)
                q = q.reshape(slots, heads_l, head_dim)
                with jax.named_scope("kv_write"):
                    k_pool = k_pool.at[_l, page, off].set(k)
                    v_pool = v_pool.at[_l, page, off].set(v)
                with jax.named_scope("attention"):
                    return paged_decode_attention(
                        q, k_pool[_l], v_pool[_l], tables, att_len,
                        impl=attention_impl)
            h = decode_hidden(params, layer, h, attend, reduce=reduce_fn)
        nxt = _sample_tokens(lm_logits(params, h), seeds, lengths + 1,
                             temps, topks)
        return nxt, k_pool, v_pool

    if mesh is None:
        return decode_step
    return wrap(decode_step,
                in_specs=(pspecs, pool_spec, pool_spec) + (repl,) * 9,
                out_specs=(repl, pool_spec, pool_spec))


def build_prefill_step(config, page_size, attention_impl=None, mesh=None,
                       tp_axis="tp"):
    """One prefill executable per ``(batch, length)`` bucket: the whole
    prompt forward (``causal_lm.prefill_forward``), K/V scattered into
    the paged pools by page table, and the FIRST new token sampled —
    so a prefilled sequence enters the decode grid already one token
    ahead.  Padded rows/positions sink their writes to page 0.

    With ``mesh`` the forward is Megatron-sharded like the decode step
    and each device scatters its OWN head shard of the prompt K/V into
    its pool shard.  Prefill collectives stay f32 (the order-fixed sum
    of ``all_reduce_activations``, so a prompt's K/V bits do not depend
    on its bucket or row): the prompt forward is compute-bound, not
    latency-bound on collective bytes (the ``tp_collectives`` knob is a
    decode-path trade)."""
    import jax
    import jax.numpy as jnp

    from ..gluon.model_zoo.causal_lm import prefill_forward
    from ..parallel.quantize import all_reduce_activations

    del attention_impl      # prefill is dense-causal (ops.multi_head_attention)

    if mesh is None:
        reduce_fn = None
    else:
        shards, _hl, pspecs, pool_spec, repl, wrap = _tp_pieces(
            config, mesh, tp_axis)

        def reduce_fn(x):
            return all_reduce_activations(x, tp_axis, shards, mode="f32")

    def prefill_step(params, k_pool, v_pool, tokens, lengths, active,
                     tables, seeds, temps, topks):
        b, L = tokens.shape
        logits, k_all, v_all = prefill_forward(params, config, tokens,
                                               lengths, reduce=reduce_fn)
        pos = jnp.arange(L)
        valid = (pos[None, :] < lengths[:, None]) & active[:, None]
        page = jnp.where(valid, tables[:, pos // page_size], 0)  # [b, L]
        off = jnp.broadcast_to((pos % page_size)[None, :], (b, L))
        for layer in range(config.n_layers):
            with jax.named_scope(f"layer{layer}"), \
                    jax.named_scope("kv_write"):
                k_pool = k_pool.at[layer, page, off].set(k_all[layer])
                v_pool = v_pool.at[layer, page, off].set(v_all[layer])
        # the first generated token sits at absolute position lengths[i]
        # (0-based) of prompt+output — same schedule the decode step
        # continues at lengths + 1
        first = _sample_tokens(logits, seeds, lengths, temps, topks)
        return first, k_pool, v_pool

    if mesh is None:
        return prefill_step
    return wrap(prefill_step,
                in_specs=(pspecs, pool_spec, pool_spec) + (repl,) * 7,
                out_specs=(repl, pool_spec, pool_spec))


def build_prefill_kv_step(config, attention_impl=None, mesh=None,
                          tp_axis="tp"):
    """The DISAGGREGATED prefill executable (one per ``(batch, length)``
    bucket): whole-prompt forward returning the first sampled token plus
    the prompt's K/V stacked ``[n_layers, b, L, heads, head_dim]`` —
    and NO pool arguments.  Because it neither reads nor donates the
    paged pools, it can run on a PREFILL-group worker concurrently with
    the decode group's pinned step: a 2048-token prompt no longer stalls
    every in-flight decode for its step, and a failed prefill can no
    longer consume the donated pools out from under the decode group's
    bystanders.  The output is the handoff payload ``build_handoff_step``
    scatters into the decode group's pool.

    With ``mesh`` the forward is Megatron-sharded (f32 collectives, see
    ``build_prefill_step``) and the payload comes back with its head
    axis sharded over ``tp_axis`` — the wire shape the sharded handoff
    scatter consumes."""
    import jax.numpy as jnp

    from ..gluon.model_zoo.causal_lm import prefill_forward
    from ..parallel.quantize import all_reduce_activations

    del attention_impl      # prefill is dense-causal (ops.multi_head_attention)

    if mesh is None:
        reduce_fn = None
    else:
        shards, _hl, pspecs, pool_spec, repl, wrap = _tp_pieces(
            config, mesh, tp_axis)

        def reduce_fn(x):
            return all_reduce_activations(x, tp_axis, shards, mode="f32")

    def prefill_kv_step(params, tokens, lengths, seeds, temps, topks):
        logits, k_all, v_all = prefill_forward(params, config, tokens,
                                               lengths, reduce=reduce_fn)
        first = _sample_tokens(logits, seeds, lengths, temps, topks)
        # zero the padding positions so the handoff buffer stays inert
        # wherever lengths don't reach (the scatter sinks them to page 0
        # anyway — this just keeps the payload deterministic)
        L = tokens.shape[1]
        valid = (jnp.arange(L)[None, :]
                 < lengths[:, None])[None, :, :, None, None]
        return first, jnp.where(valid, k_all, 0.0), \
            jnp.where(valid, v_all, 0.0)

    if mesh is None:
        return prefill_kv_step
    return wrap(prefill_kv_step,
                in_specs=(pspecs,) + (repl,) * 5,
                out_specs=(repl, pool_spec, pool_spec))


def build_handoff_step(config, page_size, mesh=None, tp_axis="tp"):
    """The ONE handoff executable of a disaggregated server: scatter a
    batch of prefilled sequences' K/V (``[n_layers, B, L, H, D]``, a
    FIXED ``(B, L)`` staging shape — the model of the prefill→decode
    wire transfer) into the decode group's paged pools by page table.
    Inactive lanes and positions past ``lengths`` sink to page 0.
    Pools are donated; shapes are configuration constants, so however
    sequences are re-packed across handoffs this is always the same
    program — the census grows by exactly one.

    With ``mesh`` the payload AND the pools are head-sharded over
    ``tp_axis``: each device scatters its own head shard, no
    collectives at all (the scatter indices are head-independent)."""
    import jax
    import jax.numpy as jnp

    if mesh is not None:
        _sh, _hl, _ps, pool_spec, repl, wrap = _tp_pieces(
            config, mesh, tp_axis)

    def handoff_step(k_pool, v_pool, k_all, v_all, lengths, active,
                     tables):
        B, L = k_all.shape[1], k_all.shape[2]
        pos = jnp.arange(L)
        valid = (pos[None, :] < lengths[:, None]) & active[:, None]
        page = jnp.where(valid, tables[:, pos // page_size], 0)   # [B, L]
        off = jnp.broadcast_to((pos % page_size)[None, :], (B, L))
        for layer in range(config.n_layers):
            with jax.named_scope(f"layer{layer}"), \
                    jax.named_scope("kv_write"):
                k_pool = k_pool.at[layer, page, off].set(k_all[layer])
                v_pool = v_pool.at[layer, page, off].set(v_all[layer])
        return k_pool, v_pool

    if mesh is None:
        return handoff_step
    return wrap(handoff_step,
                in_specs=(pool_spec, pool_spec, pool_spec, pool_spec,
                          repl, repl, repl),
                out_specs=(pool_spec, pool_spec))


def build_dense_decode_step(config, max_ctx, attention_impl=None):
    """The dense max-length-cache decode variant: identical model and
    sampling, but every slot owns a ``[max_ctx, H, D]`` stripe of
    ``[n_layers, slots, max_ctx, H, D]`` caches — the per-sequence HBM
    reservation the paged pool replaces.  Exists for the parity tests
    and as the costguard ``llm_decode_step_dense`` golden the paged
    win is committed against; the serving loop never runs it."""
    import jax
    import jax.numpy as jnp

    from ..gluon.model_zoo.causal_lm import decode_hidden, lm_logits
    from ..ops.paged_attention import dense_decode_attention

    del attention_impl
    n_layers = config.n_layers
    heads, head_dim = config.n_heads, config.head_dim

    def dense_step(params, k_cache, v_cache, tokens, lengths, active,
                   seeds, temps, topks):
        slots = tokens.shape[0]
        h = params["embed"][tokens]
        row = jnp.arange(slots)
        pos = jnp.clip(lengths, 0, max_ctx - 1)
        att_len = jnp.where(active, lengths + 1, 0)

        for layer in range(n_layers):
            def attend(q, k, v, _l=layer):
                nonlocal k_cache, v_cache
                k = k.reshape(slots, heads, head_dim)
                v = v.reshape(slots, heads, head_dim)
                q = q.reshape(slots, heads, head_dim)
                with jax.named_scope("kv_write"):
                    k_cache = k_cache.at[_l, row, pos].set(k)
                    v_cache = v_cache.at[_l, row, pos].set(v)
                with jax.named_scope("attention"):
                    return dense_decode_attention(q, k_cache[_l],
                                                  v_cache[_l], att_len)
            h = decode_hidden(params, layer, h, attend)
        nxt = _sample_tokens(lm_logits(params, h), seeds, lengths + 1,
                             temps, topks)
        return nxt, k_cache, v_cache

    return dense_step


def build_verify_step(config, draft_cfg, page_size, spec_k, window,
                      attention_impl=None, mesh=None, tp_axis="tp",
                      tp_collectives="f32"):
    """The ONE speculative-decoding executable: a small draft LM
    proposes ``spec_k`` tokens and the target model scores all
    ``spec_k + 1`` positions in the SAME compiled program — the census
    grows by exactly one whatever the traffic does.

    Signature (all shapes configuration constants):
      ``(params, draft_params, k_pool, v_pool, tokens[S],
      window[S, W], n_valid[S], lengths[S], active[S], tables[S, P],
      cow_src[S], cow_dst[S], seeds[S], temps[S], topks[S])`` →
      ``(emitted[S, spec_k + 1], n_accept[S], k_pool, v_pool)``.

    Randomness follows the same position-keyed schedule as the decode
    step (``seeds[s]`` + absolute token position), with a disjoint
    domain per speculative role at each position — draft proposal
    (domain 1), acceptance uniform (2), correction/bonus draw (3) — so
    a resumed sequence replays the identical accept/reject trajectory
    the uninterrupted run would have taken (ISSUE 19).

    Per slot the program (1) applies the CoW fault copy exactly like
    ``build_decode_step``, (2) runs the draft ``spec_k`` times over a
    right-aligned dense token window (``window``/``n_valid`` — the
    draft needs no pool), sampling proposal ``d_i`` from the SAME
    tempered/top-k distribution family as the target, (3) flattens the
    ``spec_k + 1`` candidate positions of all slots into ``S*(k+1)``
    lanes of the paged target forward — K/V for every lane written at
    ``lengths[s] + i``, attention masked to ``lengths[s] + i + 1``, so
    causality per lane is exact — and (4) accepts a leading run of
    proposals.  Greedy slots accept while ``d_i`` equals the target
    argmax (token-identical to plain decode by construction); sampling
    slots accept ``d_i`` with probability ``min(1, p_i(d_i)/q_i(d_i))``
    and on rejection draw from ``normalize(max(p_i - q_i, 0))``
    (all-accepted slots draw the bonus token from ``p_k``) — the
    Leviathan/Chen speculative-sampling identity, so the emitted
    process is distribution-EXACT whatever the draft proposes.

    ``emitted[s, :n_accept[s] + 1]`` are the step's real tokens (the
    ``+1`` is the correction/bonus, which becomes the next pending
    token); later entries are dead lanes.  K/V written past the
    accepted run is stale but masked — ``lengths`` advances only over
    accepted tokens, and the next step overwrites those positions.

    With ``mesh`` the target forward shards exactly like
    ``build_decode_step`` (head-parallel pools, Megatron weights,
    ``tp_collectives`` wire format); the draft params stay replicated —
    a draft small enough to speculate with is small enough to
    replicate."""
    import jax
    import jax.numpy as jnp

    from ..gluon.model_zoo.causal_lm import (init_causal_lm,
                                             verify_logits, window_logits)
    from ..ops.paged_attention import paged_decode_attention
    from ..parallel.quantize import (ACTIVATION_REDUCE_MODES,
                                     all_reduce_activations)

    if tp_collectives not in ACTIVATION_REDUCE_MODES:
        raise ValueError(f"tp_collectives={tp_collectives!r} not in "
                         f"{ACTIVATION_REDUCE_MODES}")
    if int(spec_k) < 1:
        raise ValueError(f"spec_k must be >= 1, got {spec_k}")
    if draft_cfg.vocab_size != config.vocab_size:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab_size} != target vocab "
            f"{config.vocab_size} — speculative acceptance compares "
            f"distributions over the SAME token space")
    k = int(spec_k)
    K1 = k + 1
    n_layers = config.n_layers
    heads, head_dim = config.n_heads, config.head_dim
    if mesh is None:
        heads_l, reduce_fn = heads, None
    else:
        shards, heads_l, pspecs, pool_spec, repl, wrap = _tp_pieces(
            config, mesh, tp_axis)
        # the draft is replicated: every leaf gets the empty spec (its
        # key set comes from an eval_shape init — zero device work)
        draft_pspecs = {name: repl for name in jax.eval_shape(
            lambda: init_causal_lm(draft_cfg, 0))}

        def reduce_fn(x):
            return all_reduce_activations(x, tp_axis, shards,
                                          mode=tp_collectives)

    def verify_step(params, draft_params, k_pool, v_pool, tokens, window,
                    n_valid, lengths, active, tables, cow_src, cow_dst,
                    seeds, temps, topks):
        S = tokens.shape[0]
        W = window.shape[1]
        # (1) CoW fault lanes, exactly as in the decode step
        with jax.named_scope("cow"):
            k_pool = k_pool.at[:, cow_dst].set(k_pool[:, cow_src])
            v_pool = v_pool.at[:, cow_dst].set(v_pool[:, cow_src])

        # (2) draft proposes k tokens from the dense right-aligned
        # window (pool-free; the draft runs replicated under tp).  q_i
        # is the proposal distribution the acceptance ratio divides by
        # — the SAME tempered/top-k transform the target uses.
        # Proposal i is a candidate for absolute position
        # lengths + 1 + i — keyed there (domain 1).
        drafts, qprobs = [], []
        win, nv = window, n_valid
        for i in range(k):
            lg = window_logits(draft_params, draft_cfg, win, nv)
            masked = _scaled_masked(lg, temps, topks)
            qprobs.append(jax.nn.softmax(masked, axis=-1))
            keys_i = _position_keys(seeds, lengths + 1 + i, domain=1)
            drawn = jax.vmap(jax.random.categorical)(
                keys_i, masked).astype(jnp.int32)
            d_i = jnp.where(temps > 0.0, drawn,
                            jnp.argmax(lg, axis=-1).astype(jnp.int32))
            drafts.append(d_i)
            win = jnp.concatenate([win[:, 1:], d_i[:, None]], axis=1)
            nv = jnp.minimum(nv + 1, W)

        # (3) ONE target forward over S*(k+1) flattened lanes: lane
        # (s, i) holds candidate token i of slot s at position
        # lengths[s] + i.  All lanes write K/V first, then attend with
        # att_len = pos + 1 — later lanes see earlier candidates,
        # earlier lanes mask later writes: per-lane causality is exact.
        T = jnp.stack([tokens] + drafts, axis=1)          # [S, K1]
        lanes = S * K1
        pos_l = (lengths[:, None]
                 + jnp.arange(K1)[None, :]).reshape(lanes)
        tables_l = jnp.repeat(tables, K1, axis=0)         # [lanes, P]
        active_l = jnp.repeat(active, K1)
        page_l = jnp.take_along_axis(
            tables_l, (pos_l // page_size)[:, None], axis=1)[:, 0]
        page_l = jnp.where(active_l, page_l, 0)           # sink inactive
        off_l = pos_l % page_size
        att_len = jnp.where(active_l, pos_l + 1, 0)

        def attend(_l, q, kk, vv):
            nonlocal k_pool, v_pool
            kk = kk.reshape(lanes, heads_l, head_dim)
            vv = vv.reshape(lanes, heads_l, head_dim)
            q = q.reshape(lanes, heads_l, head_dim)
            with jax.named_scope("kv_write"):
                k_pool = k_pool.at[_l, page_l, off_l].set(kk)
                v_pool = v_pool.at[_l, page_l, off_l].set(vv)
            with jax.named_scope("attention"):
                return paged_decode_attention(q, k_pool[_l], v_pool[_l],
                                              tables_l, att_len,
                                              impl=attention_impl)
        logits = verify_logits(params, config, T, attend,
                               reduce=reduce_fn)          # [S, K1, V]

        # (4) leading-run acceptance, both arms always computed
        vocab = logits.shape[-1]
        d_all = jnp.stack(drafts, axis=1)                 # [S, k]
        q_all = jnp.stack(qprobs, axis=1)                 # [S, k, V]
        masked_all = _scaled_masked(
            logits.reshape(S * K1, vocab),
            jnp.repeat(temps, K1), jnp.repeat(topks, K1)
        ).reshape(S, K1, vocab)
        p_all = jax.nn.softmax(masked_all, axis=-1)       # [S, K1, V]
        tgt_greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        a_greedy = jnp.cumprod(
            (d_all == tgt_greedy[:, :k]).astype(jnp.int32),
            axis=1).sum(axis=1)
        p_d = jnp.take_along_axis(p_all[:, :k], d_all[:, :, None],
                                  axis=2)[..., 0]
        q_d = jnp.take_along_axis(q_all, d_all[:, :, None],
                                  axis=2)[..., 0]
        # one scalar uniform per (slot, proposal), keyed at the
        # proposal's absolute position (domain 2)
        prop_pos = (lengths[:, None]
                    + 1 + jnp.arange(k)[None, :]).reshape(S * k)
        ukeys = _position_keys(jnp.repeat(seeds, k), prop_pos, domain=2)
        u = jax.vmap(lambda kk: jax.random.uniform(kk, ()))(
            ukeys).reshape(S, k)
        a_sample = jnp.cumprod(
            (u <= p_d / jnp.maximum(q_d, 1e-30)).astype(jnp.int32),
            axis=1).sum(axis=1)
        a = jnp.where(temps > 0.0, a_sample, a_greedy).astype(jnp.int32)

        # correction at the first rejection (residual p - q, renormed;
        # a zero residual means p == q there — fall back to p), bonus
        # from p_k when everything was accepted
        resid = jnp.maximum(p_all[:, :k] - q_all, 0.0)
        rsum = resid.sum(axis=-1, keepdims=True)
        resid = jnp.where(rsum > 0.0, resid / jnp.maximum(rsum, 1e-30),
                          p_all[:, :k])
        corr_dist = jnp.concatenate([resid, p_all[:, k:]], axis=1)
        # correction lane j replaces absolute position
        # lengths + 1 + j — keyed there (domain 3)
        corr_pos = (lengths[:, None]
                    + 1 + jnp.arange(K1)[None, :]).reshape(lanes)
        ckeys = _position_keys(jnp.repeat(seeds, K1), corr_pos, domain=3)
        corr_drawn = jax.vmap(jax.random.categorical)(
            ckeys, jnp.log(jnp.maximum(
                corr_dist.reshape(lanes, vocab), 1e-38))
        ).astype(jnp.int32).reshape(S, K1)
        corr = jnp.where(temps[:, None] > 0.0, corr_drawn, tgt_greedy)
        d_ext = jnp.concatenate(
            [d_all, jnp.zeros((S, 1), jnp.int32)], axis=1)
        j = jnp.arange(K1)[None, :]
        emitted = jnp.where(j < a[:, None], d_ext, corr)
        return emitted, a, k_pool, v_pool

    if mesh is None:
        return verify_step
    return wrap(verify_step,
                in_specs=(pspecs, draft_pspecs, pool_spec, pool_spec)
                + (repl,) * 11,
                out_specs=(repl, repl, pool_spec, pool_spec))


def prefix_admission_plan(n_pages, page_size, prompt_len, max_new,
                          shared_prefix_len):
    """Worst-case-fit admission math under prefix sharing — the pure
    arithmetic the scheduler's budgeting implements and the costguard
    ``llm_admission_*`` golden pair pins (docs/api.md "LLM serving").

    A sequence's worst case is ``pages_for(prompt_len + max_new)``
    pages.  With a resident shared prefix of ``shared_prefix_len``
    tokens, its leading FULL blocks map onto already-resident pages at
    zero cost, so admission charges only the ``charged_pages``
    remainder — the first holder of the prefix still pays in full.
    Returns the per-sequence page counts and the admissible concurrent
    sequences with and without sharing at this pool size."""
    ps = int(page_size)
    pool = int(n_pages) - 1                   # page 0 is the write sink
    total = -(-(int(prompt_len) + int(max_new)) // ps)
    shared = min(int(shared_prefix_len) // ps,
                 int(prompt_len) // ps)
    charged = total - shared
    unshared = pool // total if total else 0
    if pool < total:
        with_sharing = 0
    elif charged == 0:
        with_sharing = pool                   # every follower is free
    else:
        with_sharing = 1 + (pool - total) // charged
    return {"pages_per_seq": total, "shared_pages": shared,
            "charged_pages": charged, "admissible_unshared": unshared,
            "admissible_shared": with_sharing,
            "multiplier": with_sharing / max(unshared, 1)}


class SequenceSnapshot:
    """Resumable state of one in-flight generation (ISSUE 19) —
    capturable at any step boundary, portable across processes and
    replicas, JSON-serializable (the decode journal's record shape).

    Because sampling is position-keyed (``fold_in(PRNGKey(seed),
    position)``), this is ALL the state resume needs: re-prefilling
    ``prompt + out`` through the existing bucket grid reconstructs the
    KV cache, and every future draw coincides with the uninterrupted
    run's — greedy and seeded sampling alike.  ``deadline_wall`` is the
    absolute wall-clock expiry (``time.time()`` base — monotonic clocks
    don't survive a process), converted back to a remaining-seconds
    deadline at ``submit_resume``."""

    __slots__ = ("rid", "prompt", "out", "max_new", "temperature",
                 "top_k", "seed", "priority", "deadline_wall", "tenant",
                 "klass")

    def __init__(self, rid, prompt, out, max_new, temperature, top_k,
                 seed, priority=0, deadline_wall=None, tenant=None,
                 klass=None):
        self.rid = int(rid)
        self.prompt = [int(t) for t in prompt]
        self.out = [int(t) for t in out]
        self.max_new = int(max_new)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.priority = int(priority)
        self.deadline_wall = None if deadline_wall is None \
            else float(deadline_wall)
        self.tenant = tenant
        self.klass = klass

    def to_json(self):
        return {name: getattr(self, name) for name in self.__slots__}

    @classmethod
    def from_json(cls, d):
        return cls(**{name: d[name] for name in cls.__slots__
                      if name in d})

    def __repr__(self):
        return (f"SequenceSnapshot(rid={self.rid}, "
                f"prompt_len={len(self.prompt)}, "
                f"generated={len(self.out)}/{self.max_new}, "
                f"seed={self.seed})")


# ---------------------------------------------------------------- scheduler --
class _Seq:
    """Decode-loop-private state of one admitted sequence."""

    __slots__ = ("req", "prompt", "max_new", "temp", "top_k", "slot",
                 "pages", "cached", "out", "stamp", "ran", "priority",
                 "shared_n", "seed", "rid", "salvage", "replay")

    def __init__(self, req, prompt, max_new, temp, top_k, priority=0):
        self.req = req
        self.prompt = prompt
        self.max_new = max_new
        self.temp = temp
        self.top_k = top_k
        self.priority = priority  # QoS class priority — scheduling order
        self.slot = None
        self.pages = []
        self.cached = 0          # tokens whose K/V is in the pool
        self.out = []            # generated token ids (EOS excluded)
        self.stamp = 0.0         # admission order — eviction picks youngest
        self.ran = False         # ever prefilled (survives preemption)
        self.shared_n = 0        # leading pages mapped from the prefix index
        self.seed = 0            # per-sequence sampling seed (position-keyed)
        self.rid = -1            # admission ordinal — the journal's key
        self.salvage = 0         # failure-salvage retries consumed
        self.replay = []         # recorded tokens still to force post-resume


class GenerationServer:
    """Continuous-batching autoregressive generation server.

    Lifecycle mirrors ``InferenceServer``: construct → ``start()``
    (warmup-compiles the full prefill bucket grid AND the single decode
    executable before readiness flips) → ``submit()``/``__call__`` →
    ``drain()`` or ``serve_forever()``.  ``submit`` returns a
    ``Request`` future resolving to the generated token ids
    (``np.int32``, EOS excluded) or an explicit error.

    One decode loop thread owns all device state (pools, slot arrays,
    allocator traffic); client threads touch only the admission deque,
    the lock-guarded stats, and ``Request`` futures.

    **Disaggregated prefill/decode (ISSUE 12).**  With
    ``prefill_workers >= 1`` the server splits into two replica groups:
    prefill runs on a worker-thread group through POOL-FREE executables
    (``build_prefill_kv_step`` — in a multi-chip deployment these
    workers pin the prefill group's chips) while the decode loop — the
    decode group — keeps stepping its pinned executable undisturbed.  A
    finished prefill hands its KV payload + first token off through a
    staging buffer; the decode loop scatters it into the paged pool with
    the single fixed-shape ``build_handoff_step`` program and seats the
    sequence in a slot.  Consequences, both chaos-tested: a long prompt
    no longer stalls in-flight decodes for its step, and a prefill-side
    failure can no longer destroy the donated pools under the decode
    group's bystanders (the pool-free program never touches them).  The
    executable census becomes ``prefill grid + 2`` (handoff + decode).

    **Tensor-parallel sharded decode (ISSUE 14).**  ``tp_shards=N``
    lowers every program — the prefill grid, THE decode step, and (when
    disaggregated) the handoff scatter — once over an N-way ``tp`` mesh
    as ``shard_map`` programs: each device owns a head shard of the K/V
    page pools (per-device pool HBM ∝ 1/shards, so servable model size
    AND aggregate slot count multiply with the mesh), the causal LM's
    QKV/FFN weights are Megatron column/row-sharded, and the two
    per-layer partial-product all-reduces on the decode path run in the
    ``tp_collectives`` wire format (``"f32"`` or ``"int8"`` via
    ``parallel.quantize.all_reduce_activations`` — EQuARX's trade:
    decode is latency-bound on collective bytes).  Everything host-side
    is UNCHANGED: the ``PageAllocator`` stays layout-free (a page id
    addresses every device's shard of that page), slot arrays stay
    replicated, and the census contract survives — still prefill grid +
    decode (+ handoff), each lowered once over the mesh, so warmup,
    donation, preemption, QoS seating, and telemetry span trees are
    identical to the single-chip server.

    **Per-tenant QoS.**  ``qos=TenantQoS(...)`` adds priority classes
    and per-tenant token buckets at admission: the scheduler seats
    higher-priority classes first (FIFO within a class; eviction stays
    strictly seniority-ordered, so the livelock proof is untouched), an
    abusive tenant sheds alone with ``TenantThrottledError``, and
    ``healthz()["classes"]`` reports per-class deadline-miss and
    p50/p99 latency — the same keys ``InferenceServer`` serves, so
    fleet routers rank LLM and classifier replicas uniformly.

    Profiler series: ``<name>::tokens_out``, ``<name>::page_occupancy``
    (percent of allocatable pages held), ``<name>::preempted``,
    ``<name>::retired`` (sequences leaving a slot for any terminal
    reason: completed, failed, or expired).
    """

    _IDLE_TICK = 0.005

    def __init__(self, params, config, *, buckets=None, n_slots=8,
                 n_pages=64, page_size=16, max_context=None,
                 max_queue=128, rate=None, burst=None, breaker=None,
                 default_deadline=None, max_new_tokens=32, eos_id=None,
                 seed=0, attention_impl=None, prefill_workers=0,
                 qos=None, tp_shards=1, tp_collectives="f32",
                 draft=None, draft_config=None, spec_k=3,
                 spec_window=16, salvage_retries=2, journal=None,
                 journal_every=8, memory_report=None,
                 name="GenerationServer"):
        import jax
        import jax.numpy as jnp

        from ..parallel.quantize import ACTIVATION_REDUCE_MODES

        self.config = config
        # speculative decoding (ISSUE 16): a draft model switches the
        # scheduler's step from the decode program to the verify
        # program — spec_k proposals scored per step, output
        # distribution exact (greedy: token-identical)
        self._spec_k = int(spec_k)
        self._spec_window = int(spec_window)
        self._draft_cfg = draft_config
        if draft is not None:
            if draft_config is None:
                raise ValueError(f"{name}: draft= needs draft_config= "
                                 f"(the draft's CausalLMConfig)")
            if draft_config.vocab_size != config.vocab_size:
                raise ValueError(
                    f"{name}: draft vocab {draft_config.vocab_size} != "
                    f"target vocab {config.vocab_size}")
            if self._spec_k < 1:
                raise ValueError(f"{name}: spec_k must be >= 1")
            if self._spec_window < 1:
                raise ValueError(f"{name}: spec_window must be >= 1")
        self.tp_shards = int(tp_shards)
        if tp_collectives not in ACTIVATION_REDUCE_MODES:
            raise ValueError(
                f"{name}: tp_collectives={tp_collectives!r} not in "
                f"{ACTIVATION_REDUCE_MODES}")
        self.tp_collectives = tp_collectives
        if self.tp_shards > 1:
            from ..gluon.model_zoo.causal_lm import tp_validate
            from ..parallel.mesh import make_mesh

            tp_validate(config, self.tp_shards)
            devices = jax.devices()
            if self.tp_shards > len(devices):
                raise ValueError(
                    f"{name}: tp_shards={self.tp_shards} exceeds the "
                    f"{len(devices)} visible devices")
            self._mesh = make_mesh(tp=self.tp_shards,
                                   devices=devices[:self.tp_shards])
        else:
            self._mesh = None
        if buckets is None:
            buckets = BucketSpec(batch=(1, 2), length=(16, 32))
        # a bare batch tuple wraps like InferenceServer's — and then
        # fails the length-bucket requirement below LOUDLY, instead of
        # silently serving the default grid
        self.buckets = buckets if isinstance(buckets, BucketSpec) \
            else BucketSpec(buckets)
        if self.buckets.length is None:
            raise ValueError(f"{name}: buckets must define length "
                             f"buckets — prompts are sequences")
        self.n_slots = int(n_slots)
        self.alloc = PageAllocator(n_pages, page_size)
        # per-sequence page-table width: enough for the longest prompt
        # bucket plus the default generation budget (the table is a
        # configuration constant — it shapes the compiled programs);
        # speculative mode adds spec_k — the verify step writes k
        # lookahead positions past the pending token
        if max_context is None:
            max_context = max(self.buckets.length) + int(max_new_tokens) \
                + (self._spec_k if draft is not None else 0)
        if max_context < max(self.buckets.length) + 1:
            raise ValueError(
                f"{name}: max_context {max_context} cannot hold the "
                f"largest length bucket {max(self.buckets.length)} plus "
                f"one generated token")
        self.pages_per_seq = self.alloc.pages_for(max_context)
        self.max_context = self.pages_per_seq * self.alloc.page_size
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self._limiter = None if rate is None else TokenBucket(rate, burst)
        self._qos = qos if qos is not None else TenantQoS()
        self._default_deadline = default_deadline
        self._max_new = int(max_new_tokens)
        self._eos = None if eos_id is None else int(eos_id)
        self._name = name
        self._max_queue = int(max_queue)

        if self._mesh is not None:
            from ..gluon.model_zoo.causal_lm import tp_shard_params

            # one-time host relayout + committed sharded placement: the
            # compiled programs never re-transfer weights per call
            self._params = tp_shard_params(params, config, self._mesh)
        else:
            self._params = jax.tree.map(jnp.asarray, params)
        self._decode = jax.jit(
            build_decode_step(config, self.alloc.page_size,
                              attention_impl, mesh=self._mesh,
                              tp_collectives=self.tp_collectives),
            donate_argnums=(1, 2))
        if draft is not None:
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec

                # the draft replicates over the mesh (tiny by design)
                rep = NamedSharding(self._mesh, PartitionSpec())
                self._draft_params = {
                    kname: jax.device_put(jnp.asarray(v), rep)
                    for kname, v in draft.items()}
            else:
                self._draft_params = jax.tree.map(jnp.asarray, draft)
            self._verify = jax.jit(
                build_verify_step(config, draft_config,
                                  self.alloc.page_size, self._spec_k,
                                  self._spec_window, attention_impl,
                                  mesh=self._mesh,
                                  tp_collectives=self.tp_collectives),
                donate_argnums=(2, 3))
        else:
            self._draft_params = None
            self._verify = None
        self._n_prefill_workers = int(prefill_workers)
        if self._n_prefill_workers > 0:
            # disaggregated: pool-free prefill grid + ONE handoff scatter
            self._prefill = jax.jit(
                build_prefill_kv_step(config, attention_impl,
                                      mesh=self._mesh))
            self._handoff = jax.jit(
                build_handoff_step(config, self.alloc.page_size,
                                   mesh=self._mesh),
                donate_argnums=(0, 1))
        else:
            self._prefill = jax.jit(
                build_prefill_step(config, self.alloc.page_size,
                                   attention_impl, mesh=self._mesh),
                donate_argnums=(1, 2))
            self._handoff = None
        # per-position PRNG (ISSUE 19): the server seed only SALTS the
        # per-sequence seed derivation (admission ordinal → splitmix) —
        # no step counter exists anywhere, so randomness is a pure
        # function of (sequence seed, token position) and resume is
        # token-exact by construction
        self._seed_root = int(seed) & 0xFFFFFFFFFFFFFFFF
        self._admit_ord = 0                 # _admit_lock-guarded
        # failure salvage + decode journal (ISSUE 19)
        self._salvage_retries = max(0, int(salvage_retries))
        self._journal = None if journal is None \
            else _telemetry.JsonlSink(journal)
        self._journal_every = max(1, int(journal_every))
        self._jsteps = 0                    # decode-loop-private
        self._handoff_exit = threading.Event()
        self.exported = []                  # SequenceSnapshots from handoff

        # decode-loop-private device + slot state (created in start())
        self._k_pool = self._v_pool = None
        self._seqs = {}                                  # slot -> _Seq
        self._tokens = np.zeros((self.n_slots,), np.int32)
        self._lengths = np.zeros((self.n_slots,), np.int32)
        self._active = np.zeros((self.n_slots,), bool)
        self._tables = np.zeros((self.n_slots, self.pages_per_seq),
                                np.int32)
        self._temps = np.zeros((self.n_slots,), np.float32)
        self._topks = np.zeros((self.n_slots,), np.int32)
        self._seeds = np.zeros((self.n_slots,), np.uint32)
        # CoW fault lanes, reset each step; (0, 0) = inert sink self-copy
        self._cow_src = np.zeros((self.n_slots,), np.int32)
        self._cow_dst = np.zeros((self.n_slots,), np.int32)
        # speculative draft context: right-aligned token windows
        self._window = np.zeros((self.n_slots, self._spec_window),
                                np.int32)
        self._nvalid = np.ones((self.n_slots,), np.int32)
        # prefix index (decode-loop-private): parent page (0 = root) →
        # {full-block token tuple: resident page}, plus the reverse map
        # releases use.  A chain walk from the root maps a new prompt's
        # leading blocks onto resident pages (``_match_prefix``).
        self._children = {}
        self._indexed_by_page = {}

        self._pending = collections.deque()
        self._admit_lock = threading.Lock()
        self._lock = threading.Lock()
        self._stats = {"admitted": 0, "completed": 0, "failed": 0,
                       "expired": 0, "rejected": 0, "retired": 0,
                       "preempted": 0, "tokens_out": 0, "prefills": 0,
                       "handoffs": 0, "decode_steps": 0, "active_slots": 0,
                       "verify_steps": 0, "spec_proposed": 0,
                       "spec_accepted": 0, "cow_faults": 0,
                       "pages_charged": 0, "pages_shared_mapped": 0,
                       "tokens_salvaged": 0, "resumes": 0,
                       "salvage_retries": 0, "journal_restores": 0,
                       "journal_errors": 0, "resume_pages_remapped": 0,
                       "handoff_exports": 0}
        self._last_error = None
        self._ready = threading.Event()
        self._draining = threading.Event()
        self._stop = threading.Event()
        self._loop_exited = threading.Event()
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._loop, name=name,
                                        daemon=True)
        # disaggregated-mode plumbing: the prefill group's work queue
        # (bounded — the decode loop is the only producer and checks
        # full() first, so put_nowait cannot race), the handoff queue
        # (prefill workers → decode loop), the flight registry (groups a
        # worker currently owns, swept on loop exit so a dying worker
        # can never strand its group), and the decode-loop-local seat
        # backlog of prefilled sequences waiting for slots/pages.
        self._prefill_q = queue.Queue(
            maxsize=max(2, 2 * self._n_prefill_workers))
        self._handoff_q = queue.Queue()
        self._prefill_flight = {}          # id(group) -> group, _lock-guarded
        self._handoff_backlog = []         # decode-loop-private
        self._prefill_threads = [
            threading.Thread(target=self._prefill_worker,
                             name=f"{name}-prefill-w{i}", daemon=True)
            for i in range(self._n_prefill_workers)]
        self._c_tokens = _profiler.Counter(None, f"{name}::tokens_out")
        self._c_pages = _profiler.Counter(None, f"{name}::page_occupancy")
        self._c_preempted = _profiler.Counter(None, f"{name}::preempted")
        self._c_retired = _profiler.Counter(None, f"{name}::retired")
        # live memory gauges (ISSUE 15): per-device argument/peak bytes
        # from an already-parsed costguard report, stamped at warmup
        self._mem_gauges = _telemetry.memory_gauges(memory_report)
        # per-slot page-occupancy histogram: observed at every
        # retirement, so the exposition shows how sequences actually
        # used the pool (not just the aggregate free count)
        self._h_slot_pages = _telemetry.registry().histogram(
            f"{name}::slot_pages",
            _telemetry.log_buckets(1.0, 4096.0, per_decade=4))
        # per-step draft acceptance rate (accepted / spec_k), observed
        # per slot each verify step — the speculative win's live gauge
        self._h_accept = _telemetry.registry().histogram(
            f"{name}::spec_accept_rate", [i / 8 for i in range(1, 9)])

    # ------------------------------------------------------------ lifecycle --
    def start(self, warmup=True):
        """Allocate the pools and (by default) compile the WHOLE
        executable space — every prefill bucket signature plus the one
        decode program — with inert all-inactive arguments (writes sink
        to page 0, the allocator is untouched) before readiness flips.
        After warmup the jit caches hold exactly ``census()`` entries
        and live traffic can never add one."""
        if self._draining.is_set():
            raise ServerClosedError(f"{self._name}: already drained")
        # the decode thread owns the pools once it starts (two lines
        # down); the lock here is for the thread-contract checker —
        # nothing races a thread that does not exist yet.  The pool
        # device_put (sharded placement under tp) runs BEFORE taking it:
        # only the attribute assignment needs the lock.
        pools = self._new_pools()
        with self._admit_lock:
            self._k_pool, self._v_pool = pools
        if warmup:
            for b in self.buckets.batch:
                for L in self.buckets.length:
                    if self._n_prefill_workers > 0:
                        self._run_prefill_kv(
                            np.zeros((b, L), np.int32),
                            np.zeros((b,), np.int32),
                            np.zeros((b,), np.uint32),
                            np.zeros((b,), np.float32),
                            np.zeros((b,), np.int32))
                    else:
                        self._run_prefill(
                            np.zeros((b, L), np.int32),
                            np.zeros((b,), np.int32),
                            np.zeros((b,), bool),
                            np.zeros((b, self.pages_per_seq), np.int32),
                            np.zeros((b,), np.uint32),
                            np.zeros((b,), np.float32),
                            np.zeros((b,), np.int32))
            if self._n_prefill_workers > 0:
                self._run_handoff(*self._staging(), np.zeros(
                    (self.buckets.max_batch,), np.int32),
                    np.zeros((self.buckets.max_batch,), bool),
                    np.zeros((self.buckets.max_batch, self.pages_per_seq),
                             np.int32))
            self._run_decode()
            if self._verify is not None:
                # the verify program joins the pinned set: inert
                # all-inactive arguments, writes sink to page 0
                self._run_verify()
            # the whole executable space exists now (census() programs):
            # any later compile at this site is an UNEXPECTED recompile —
            # the counter chaos_check --mode obs asserts stays zero.  A
            # warmup=False server compiles lazily by choice, so nothing
            # is pinned and its compiles stay ordinary events.
            if _telemetry.ACTIVE:
                _telemetry.pin_compile_census(self._name)
        self._started.set()
        self._thread.start()
        for t in self._prefill_threads:
            t.start()
        self._ready.set()
        return self

    def __enter__(self):
        if not self._started.is_set():
            self.start()
        return self

    def __exit__(self, *exc):
        self.drain()
        return False

    def census(self):
        """The static executable count: one prefill program per (batch,
        length) bucket plus THE decode program — plus THE handoff
        program when disaggregated (``prefill_workers >= 1``), plus THE
        verify program when speculative (``draft=`` — census grows by
        exactly one).  ``jit_cache_count()`` must equal this after
        warmup, forever."""
        grid = len(self.buckets.batch) * len(self.buckets.length)
        return grid + 1 + (1 if self._n_prefill_workers > 0 else 0) \
            + (1 if self._verify is not None else 0)

    def jit_cache_count(self):
        """Runtime executables actually compiled (every jit cache)."""
        n = self._prefill._cache_size() + self._decode._cache_size()
        if self._handoff is not None:
            n += self._handoff._cache_size()
        if self._verify is not None:
            n += self._verify._cache_size()
        return n

    def lower_decode(self):
        """THE decode program, lowered (not compiled) at the signature
        the loop dispatches: this server's params and slot grid, and its
        pools' shape, dtype and sharding.  What a check of the served
        program reads — kernel custom-calls, cost, structure — instead
        of rebuilding ``build_decode_step`` by hand.  Needs ``start()``
        (the pools exist from there on); adds nothing to
        ``jit_cache_count()``."""
        import jax

        # avals, not the arrays: the loop owns them (it donates the pools
        # and rewrites the slot grid every step) — only their shape,
        # dtype and placement enter the lowering
        with self._admit_lock:
            if self._k_pool is None:
                raise RuntimeError(
                    f"{self._name}: lower_decode() before start() — the "
                    f"pools do not exist yet")
            avals = [jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=getattr(a, "sharding", None))
                for a in (self._k_pool, self._v_pool, self._tokens,
                          self._lengths, self._active, self._tables,
                          self._cow_src, self._cow_dst, self._seeds,
                          self._temps, self._topks)]
        return self._decode.lower(self._params, *avals)

    # ------------------------------------------------------------ admission --
    def submit(self, tokens, *, max_new_tokens=None, temperature=0.0,
               top_k=0, deadline=None, tenant=None, klass=None,
               seed=None, trace_parent=None):
        """Admit one prompt; returns a ``Request`` future resolving to
        the generated ``np.int32`` token ids (EOS excluded).

        ``tenant``/``klass`` are the QoS labels (``TenantQoS``): the
        class supplies the default deadline, its priority orders the
        scheduler's seating, and the resolution lands in the class's
        ``healthz()["classes"]`` stats.

        ``seed`` pins this sequence's sampling seed explicitly (any
        uint32); by default it derives from the server seed and the
        admission ordinal.  Two servers given the same seed and prompt
        produce the same sampled stream — the oracle lever of the
        resume-exactness tests (ISSUE 19).

        Refusals are immediate and explicit (PR 4 contract):
        ``ServerClosedError`` draining, ``CircuitOpenError`` fast-fail,
        ``RejectedError`` for rate limit / full queue / a prompt no
        length bucket holds / a worst case that could never fit the
        page pool, ``TenantThrottledError`` for an over-rate tenant.
        None of them touched the device."""
        t0_us = _telemetry.now_us() if _telemetry.ACTIVE else None
        if self._draining.is_set():
            self._bump("rejected")
            raise ServerClosedError(f"{self._name}: draining — "
                                    f"not admitting")
        if not self._ready.is_set():
            self._bump("rejected")
            raise RejectedError(f"{self._name}: not started")
        if not self._thread.is_alive():
            self._bump("rejected")
            raise ServerClosedError(f"{self._name}: decode loop is not "
                                    f"running — not admitting")
        if self.breaker.engaged():
            self._bump("rejected")
            raise CircuitOpenError(
                f"{self._name}: circuit open after repeated step failures "
                f"— fast-failing until a probe succeeds")
        raw = np.asarray(tokens)
        if not np.issubdtype(raw.dtype, np.integer):
            raise ValueError(
                f"{self._name}: prompt dtype {raw.dtype} is not an "
                f"integer token array — casting would silently "
                f"truncate; tokenize first")
        prompt = raw.astype(np.int32)
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(f"{self._name}: prompt must be a 1-D, "
                             f"non-empty int sequence")
        max_new = self._max_new if max_new_tokens is None \
            else int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if float(temperature) < 0.0 or int(top_k) < 0:
            raise ValueError("temperature must be >= 0 and top_k >= 0")
        n = prompt.shape[0]
        try:
            if n > max(self.buckets.length):
                raise RejectedError(
                    f"prompt length {n} exceeds the largest length bucket "
                    f"{max(self.buckets.length)} — no prefill executable "
                    f"exists for this shape")
            # speculative mode verifies spec_k lookahead positions past
            # the pending token — the worst case must hold them too
            spare = self._spec_k if self._verify is not None else 0
            if n + max_new + spare > self.max_context:
                raise RejectedError(
                    f"prompt {n} + max_new_tokens {max_new}"
                    + (f" + spec_k {spare}" if spare else "")
                    + f" exceeds the page capacity {self.max_context} "
                    f"per sequence")
            if self.alloc.pages_for(n + max_new + spare) \
                    > self.alloc.allocatable:
                raise RejectedError(
                    f"worst case needs "
                    f"{self.alloc.pages_for(n + max_new + spare)} "
                    f"pages, pool holds {self.alloc.allocatable} — this "
                    f"request could never be served")
        except RejectedError:
            self._bump("rejected")
            raise
        # QoS verdict AFTER structural checks (an unservable prompt must
        # not burn a tenant token), BEFORE the global limiter
        try:
            qc = self._qos.classify(tenant=tenant, klass=klass)
        except RejectedError:
            self._bump("rejected")
            raise
        if deadline is None:
            deadline = qc.deadline if qc.deadline is not None \
                else self._default_deadline
        if self._limiter is not None and not self._limiter.try_acquire():
            self._qos.refund(tenant, qc)
            self._bump("rejected")
            raise RejectedError(f"{self._name}: rate limit exceeded — "
                                f"shedding")
        req = Request((prompt,), deadline=deadline, tenant=tenant,
                      klass=qc.name)
        seq = _Seq(req, prompt, max_new, float(temperature), int(top_k),
                   priority=qc.priority)
        seq.stamp = time.monotonic()
        # a class's admit_frac is a threshold on TOTAL queue depth:
        # low-priority work sheds once the whole backlog reaches its
        # fraction, keeping the rest of the queue exclusively for the
        # classes above it (the queue-depth twin of the fleet's
        # in-flight threshold)
        queue_cap = self._max_queue if qc.admit_frac >= 1.0 \
            else int(qc.admit_frac * self._max_queue)
        # trace BEFORE joining the queue — the decode loop may pop the
        # sequence immediately and needs the queue span already open.  A
        # refusal below never resolves the request, so the trace is
        # never exported.
        if trace_parent is not None or t0_us is not None:
            _telemetry.begin_request(req, self._name, t0_us=t0_us,
                                     parent=trace_parent)
        with self._admit_lock:
            admitted = not self._stop.is_set() \
                and len(self._pending) < queue_cap
            if admitted:
                seq.rid = self._admit_ord
                self._admit_ord += 1
                seq.seed = self._derive_seed(seq.rid) if seed is None \
                    else int(seed) & 0xFFFFFFFF
                self._pending.append(seq)
            else:
                stopped = self._stop.is_set()
        if not admitted:
            if self._limiter is not None:
                self._limiter.refund()
            self._qos.refund(tenant, qc)
            self._bump("rejected")
            _telemetry.abort_request(req)
            if stopped:
                raise ServerClosedError(f"{self._name}: draining — "
                                        f"not admitting")
            raise RejectedError(
                f"{self._name}: request queue at class "
                f"{qc.name!r}'s cap ({queue_cap} of "
                f"{self._max_queue}) — shedding")
        self._qos.track(qc, req)
        self._bump("admitted")
        self._journal_admit(seq)
        return req

    def submit_resume(self, snapshot, *, deadline=None):
        """Admit a ``SequenceSnapshot`` — the resume half of ISSUE 19:
        the sequence re-enters the queue WITH its generated-so-far
        tokens and its ORIGINAL sampling seed, re-prefills prompt +
        generated through the existing bucket grid, and completes
        token-exact with what the uninterrupted run would have
        produced.  Fleet failover and journal restore both land here.

        ``deadline`` (seconds from now) overrides the snapshot's
        wall-clock expiry; with neither, the sequence has no deadline.
        QoS classification is NOT re-applied (the request paid at its
        original admission); the snapshot's priority orders seating.
        Refusals match ``submit``: ``ServerClosedError`` draining,
        ``CircuitOpenError`` fast-fail, ``RejectedError`` full queue /
        structurally unservable."""
        t0_us = _telemetry.now_us() if _telemetry.ACTIVE else None
        if isinstance(snapshot, dict):
            snapshot = SequenceSnapshot.from_json(snapshot)
        if self._draining.is_set():
            self._bump("rejected")
            raise ServerClosedError(f"{self._name}: draining — "
                                    f"not admitting")
        if not self._ready.is_set():
            self._bump("rejected")
            raise RejectedError(f"{self._name}: not started")
        if not self._thread.is_alive():
            self._bump("rejected")
            raise ServerClosedError(f"{self._name}: decode loop is not "
                                    f"running — not admitting")
        if self.breaker.engaged():
            self._bump("rejected")
            raise CircuitOpenError(
                f"{self._name}: circuit open after repeated step failures "
                f"— fast-failing until a probe succeeds")
        prompt = np.asarray(snapshot.prompt, np.int32)
        n = prompt.shape[0]
        max_new = int(snapshot.max_new)
        spare = self._spec_k if self._verify is not None else 0
        try:
            if n < 1:
                raise RejectedError("snapshot prompt is empty")
            if n > max(self.buckets.length):
                raise RejectedError(
                    f"snapshot prompt length {n} exceeds the largest "
                    f"length bucket {max(self.buckets.length)} on this "
                    f"server — no prefill executable exists")
            if n + max_new + spare > self.max_context \
                    or self.alloc.pages_for(n + max_new + spare) \
                    > self.alloc.allocatable:
                raise RejectedError(
                    f"snapshot worst case ({n} + {max_new} new) does not "
                    f"fit this server's page capacity")
        except RejectedError:
            self._bump("rejected")
            raise
        if deadline is None and snapshot.deadline_wall is not None:
            deadline = snapshot.deadline_wall - time.time()
        req = Request((prompt,), deadline=deadline,
                      tenant=snapshot.tenant, klass=snapshot.klass)
        seq = _Seq(req, prompt, max_new, float(snapshot.temperature),
                   int(snapshot.top_k), priority=snapshot.priority)
        seq.out = [int(t) for t in snapshot.out]
        seq.stamp = time.monotonic()
        if len(seq.out) >= max_new:
            # complete already (the journal caught it between its last
            # token and its retirement record) — resolve without work
            self._bump("admitted")
            req.set_result(np.asarray(seq.out[:max_new], np.int32))
            self._bump("completed")
            self._bump("retired")
            return req
        if t0_us is not None:
            _telemetry.begin_request(req, self._name, t0_us=t0_us)
        with self._admit_lock:
            admitted = not self._stop.is_set() \
                and len(self._pending) < self._max_queue
            if admitted:
                seq.rid = self._admit_ord
                self._admit_ord += 1
                seq.seed = int(snapshot.seed) & 0xFFFFFFFF
                self._pending.append(seq)
            else:
                stopped = self._stop.is_set()
        if not admitted:
            self._bump("rejected")
            _telemetry.abort_request(req)
            if stopped:
                raise ServerClosedError(f"{self._name}: draining — "
                                        f"not admitting")
            raise RejectedError(f"{self._name}: request queue full "
                                f"({self._max_queue}) — shedding")
        self._bump("admitted")
        self._journal_admit(seq)
        return req

    def restore_journal(self, path):
        """Import a crashed sibling's decode journal (ISSUE 19): replay
        ``gen_admit``/``gen_snapshot``/``gen_handoff``/``gen_retire``
        records in order, reconstruct every sequence that was admitted
        but never retired, and ``submit_resume`` each — the restored
        server completes them token-exact (position-keyed sampling +
        the journaled seed).  Stale in-flight snapshots are harmless:
        the missing tail regenerates identically.

        Reads the rotated ``<path>.1`` first, then ``path``; a torn
        tail line (kill -9 mid-write) is skipped.  Returns ``{rid:
        Request}`` for the resumed sequences (rids from the DEAD
        server's journal).  Sequences this server must refuse
        structurally raise through; call on a started, healthy server
        before opening it to traffic."""
        import json
        import os

        live = {}
        for p in (str(path) + ".1", str(path)):
            if not os.path.exists(p):
                continue
            with open(p, "r", encoding="utf-8") as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue               # torn tail — kill -9
                    if rec.get("kind") != "generate":
                        continue
                    nm, rid = rec.get("name"), rec.get("rid")
                    if rid is None:
                        continue
                    if nm in ("gen_admit", "gen_handoff"):
                        live[rid] = dict(rec)
                    elif nm == "gen_snapshot" and rid in live:
                        live[rid]["out"] = list(rec.get("out", []))
                    elif nm == "gen_retire":
                        live.pop(rid, None)
        restored = {}
        for rid, rec in live.items():
            snap = SequenceSnapshot.from_json(rec)
            restored[rid] = self.submit_resume(snap)
            self._bump("journal_restores")
        return restored

    def __call__(self, tokens, timeout=None, **kw):
        """Blocking convenience: submit + ``result()``."""
        return self.submit(tokens, **kw).result(timeout)

    def _bump(self, key, n=1):
        with self._lock:
            self._stats[key] += n

    def _note_step_failure(self, exc):
        with self._lock:
            self._last_error = (type(exc).__name__, time.monotonic())

    # ---------------------------------------------------- snapshots + journal --
    def _snapshot_of(self, seq):
        """Capture one sequence's resumable state (step boundary —
        decode-loop thread, or admission state not yet seated)."""
        dw = None
        if seq.req.deadline is not None:
            dw = time.time() + (seq.req.deadline - time.monotonic())
        return SequenceSnapshot(
            rid=seq.rid, prompt=seq.prompt, out=seq.out,
            max_new=seq.max_new, temperature=seq.temp, top_k=seq.top_k,
            seed=seq.seed, priority=seq.priority, deadline_wall=dw,
            tenant=seq.req.tenant, klass=seq.req.klass)

    def _journal_event(self, name, **fields):
        """Append one record to the decode journal.  Write failures are
        swallowed into the ``journal_errors`` counter — the journal is
        a durability aid, never a serving liability (``generate.journal``
        is the fault point that proves it)."""
        if self._journal is None:
            return
        try:
            _fault.fire("generate.journal")
            self._journal.write("generate", name=name, **fields)
        except Exception:   # noqa: BLE001 — journaling must not fail serving
            self._bump("journal_errors")

    def _journal_admit(self, seq):
        """One ``gen_admit`` record per accepted sequence — the full
        snapshot (out included: a resumed admission re-journals its
        salvaged tokens, so restore needs no cross-file history)."""
        if self._journal is not None:
            self._journal_event("gen_admit", **self._snapshot_of(seq)
                                .to_json())

    def _journal_tick(self):
        """Periodic in-flight snapshots (every ``journal_every``
        successful steps): bounds how many trailing tokens a kill -9
        can force the restored server to regenerate — regeneration is
        token-exact either way, this only trades journal bytes against
        recompute."""
        if self._journal is None:
            return
        self._jsteps += 1
        if self._jsteps % self._journal_every:
            return
        for seq in self._seqs.values():
            self._journal_event("gen_snapshot", rid=seq.rid,
                                out=list(seq.out))

    # ----------------------------------------------------------- decode loop --
    def _derive_seed(self, ordinal):
        """The per-sequence sampling seed: a splitmix64-style mix of the
        server seed and the admission ordinal.  Stable across processes
        (pure arithmetic — no RNG object, no clock), so a journal
        restore or a fleet redispatch carries the ORIGINAL seed and the
        resumed sequence samples the original stream.  ``submit(seed=)``
        overrides it per request."""
        x = (self._seed_root
             + (int(ordinal) + 1) * 0x9E3779B97F4A7C15) \
            & 0xFFFFFFFFFFFFFFFF
        x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
        x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
        return (x ^ (x >> 31)) & 0xFFFFFFFF

    def _run_prefill(self, tokens, lengths, active, tables, seeds,
                     temps, topks):
        """One prefill program invocation (pools donated/reassigned)."""
        with _telemetry.compile_guard(
                self._name, self._prefill,
                key=f"prefill/b{tokens.shape[0]}_l{tokens.shape[1]}"):
            first, self._k_pool, self._v_pool = self._prefill(
                self._params, self._k_pool, self._v_pool, tokens, lengths,
                active, tables, seeds, temps, topks)
        return np.asarray(first)

    def _run_prefill_kv(self, tokens, lengths, seeds, temps, topks):
        """One POOL-FREE prefill invocation (disaggregated mode; any
        prefill-group worker thread).  Host-realizes the outputs so the
        device wait lands on the worker, never the decode loop."""
        with _telemetry.compile_guard(
                self._name, self._prefill,
                key=f"prefill/b{tokens.shape[0]}_l{tokens.shape[1]}"):
            first, k_all, v_all = self._prefill(
                self._params, tokens, lengths, seeds, temps, topks)
        return np.asarray(first), np.asarray(k_all), np.asarray(v_all)

    def _staging(self):
        """Fresh zeroed host staging buffers for one handoff batch —
        the fixed ``(B, L)`` shape that keeps the scatter ONE program."""
        c = self.config
        B, L = self.buckets.max_batch, max(self.buckets.length)
        shape = (c.n_layers, B, L, c.n_heads, c.head_dim)
        return np.zeros(shape, np.float32), np.zeros(shape, np.float32)

    def _run_handoff(self, k_all, v_all, lengths, active, tables):
        """One handoff-scatter invocation (pools donated/reassigned)."""
        with _telemetry.compile_guard(self._name, self._handoff, key="handoff"):
            self._k_pool, self._v_pool = self._handoff(
                self._k_pool, self._v_pool, k_all, v_all, lengths, active,
                tables)

    def _new_pools(self):
        """Fresh zeroed K/V pools — head axis sharded over the tp mesh
        when one exists (each device hosts ``n_heads / tp_shards`` of
        every page: per-device pool HBM ∝ 1/shards), plain single-device
        arrays otherwise."""
        import jax
        import jax.numpy as jnp

        c, npg, psz = self.config, self.alloc.n_pages, self.alloc.page_size
        shape = (c.n_layers, npg, psz, c.n_heads, c.head_dim)
        if self._mesh is None:
            return jnp.zeros(shape, jnp.float32), \
                jnp.zeros(shape, jnp.float32)
        from jax.sharding import NamedSharding, PartitionSpec
        # NB trailing-None-free spec: jax normalizes the sharding it
        # stamps on jit OUTPUTS to PartitionSpec(None, None, None,
        # "tp"), and the lowering cache keys on spec equality — a
        # 5-entry spec here would make the warmup entry (fresh pools)
        # and the live entries (pools round-tripped through the donated
        # programs) TWO executables, breaking census == jit-cache
        sh = NamedSharding(self._mesh,
                           PartitionSpec(None, None, None, "tp"))
        return (jax.device_put(jnp.zeros(shape, jnp.float32), sh),
                jax.device_put(jnp.zeros(shape, jnp.float32), sh))

    def _recover_pools(self):
        """A device call that failed MID-EXECUTION already consumed the
        donated pools — every in-flight sequence's cache is gone with
        them.  Re-zero the pools and fail the sequences explicitly (the
        error path that got here resolves its own group; this sweeps the
        bystanders whose state was collateral).  A host-side failure
        (e.g. an armed fault point) never reaches this: the pools are
        intact and bystanders keep decoding.  Under tensor parallelism
        this is also the mid-decode SHARD-LOSS path: a device falling
        out of the gang fails the collective, the step raises, and the
        re-zeroed pools come back sharded over the same mesh — the
        breaker keeps the server fast-failing until the gang answers
        again (docs/api.md failure matrix).  Bystanders whose cache was
        collateral are SALVAGED (ISSUE 19): their tokens requeue for a
        token-exact resume against the fresh pools, unbudgeted — the
        failing step was not theirs."""
        if self._k_pool is not None and not self._k_pool.is_deleted() \
                and not self._v_pool.is_deleted():
            return
        self._k_pool, self._v_pool = self._new_pools()
        self._salvage_seated(ServerClosedError(
            "KV pool lost to a failed device step"), budgeted=False)

    def _run_decode(self):
        """One decode program invocation over the full slot grid."""
        with _telemetry.compile_guard(self._name, self._decode, key="decode"):
            nxt, self._k_pool, self._v_pool = self._decode(
                self._params, self._k_pool, self._v_pool, self._tokens,
                self._lengths, self._active, self._tables,
                self._cow_src, self._cow_dst, self._seeds,
                self._temps, self._topks)
        return np.asarray(nxt)

    def _run_verify(self):
        """One verify program invocation over the full slot grid
        (speculative mode's decode step; pools donated/reassigned)."""
        with _telemetry.compile_guard(self._name, self._verify, key="verify"):
            emitted, n_acc, self._k_pool, self._v_pool = self._verify(
                self._params, self._draft_params, self._k_pool,
                self._v_pool, self._tokens, self._window, self._nvalid,
                self._lengths, self._active, self._tables,
                self._cow_src, self._cow_dst, self._seeds,
                self._temps, self._topks)
        return np.asarray(emitted), np.asarray(n_acc)

    def _pipeline_idle(self):
        """True when the disaggregated prefill pipeline holds no work
        (trivially true in fused mode).  Order matters: a group stays in
        ``_prefill_flight`` until AFTER its handoff payloads are
        enqueued, so flight must be checked FIRST — checking the queues
        first races a worker finishing between the two checks, and the
        stale verdict would let drain strand a prefilled sequence."""
        if self._n_prefill_workers == 0:
            return True
        with self._lock:
            if self._prefill_flight:
                return False
        return not self._handoff_backlog and self._handoff_q.empty() \
            and self._prefill_q.empty()

    def _loop(self):
        try:
            while True:
                if self._stop.is_set() and self._handoff_exit.is_set():
                    # handoff drain: export unfinished work for a
                    # successor instead of generating to completion
                    self._export_all()
                    return
                if self._stop.is_set() and not self._seqs \
                        and not self._pending and self._pipeline_idle():
                    return
                worked = self._retire_expired()
                if self._draining.is_set() and self.breaker.engaged():
                    # drain must terminate: an open breaker during drain
                    # cannot half-open through traffic it refuses, so
                    # everything still accepted resolves explicitly now
                    # (handoff mode exports instead — same termination
                    # guarantee, no work destroyed)
                    if self._handoff_exit.is_set():
                        self._export_all()
                        return
                    self._fail_everything(CircuitOpenError(
                        f"{self._name}: circuit open during drain — "
                        f"fast-failing accepted work"))
                    return
                if self._n_prefill_workers > 0:
                    worked = self._drain_handoffs() or worked
                    worked = self._dispatch_prefill() or worked
                else:
                    worked = self._admit() or worked
                if self._seqs:
                    if self._verify is not None:
                        self._verify_once()
                    else:
                        self._decode_once()
                    worked = True
                if not worked and not self._seqs:
                    time.sleep(self._IDLE_TICK)
        finally:
            with self._admit_lock:
                self._stop.set()
            # only NOW may the prefill group stand down: drain() sets
            # _stop while the loop is still feeding queued work through
            # the workers — a worker that exits on _stop alone deadlocks
            # the drain (groups pile up in a queue nobody serves and
            # _pipeline_idle never goes true).  Workers key off THIS
            # event instead, set strictly after the loop stopped
            # producing.  Then stop them BEFORE the residue sweep: a
            # worker mid-prefill could otherwise stage its payload after
            # the sweep and strand the client forever.  Sentinels are a
            # fast-path; the timeout-get + _loop_exited check is the
            # guarantee.
            self._loop_exited.set()
            for _ in self._prefill_threads:
                try:
                    self._prefill_q.put_nowait(None)
                except queue.Full:
                    break
            for t in self._prefill_threads:
                t.join(timeout=30)
            self._fail_residue()

    # ---- prefix sharing ----
    def _release(self, pages):
        """Drop one hold on ``pages`` and withdraw the prefix-index
        entries of every page that actually left residency — the ONLY
        way scheduler code returns pages (a raw ``alloc.free`` would
        leave the index advertising free-listed pages).  Decode-loop
        thread only, like every index touch."""
        released = self.alloc.free(pages)
        for p in released:
            ent = self._indexed_by_page.pop(p, None)
            if ent is not None:
                parent, toks = ent
                kids = self._children.get(parent)
                if kids is not None:
                    kids.pop(toks, None)
                    if not kids:
                        self._children.pop(parent, None)
        for p in released:
            # a released parent takes its child table with it (its
            # children were released in the same call — nothing live
            # can outlive the prefix it chains from)
            self._children.pop(p, None)
        return released

    def _deindex(self, page):
        """Withdraw one page's prefix-index entry (about to be written
        by its sole holder — the advertised block content would lie)."""
        ent = self._indexed_by_page.pop(int(page), None)
        if ent is not None:
            parent, toks = ent
            kids = self._children.get(parent)
            if kids is not None:
                kids.pop(toks, None)
                if not kids:
                    self._children.pop(parent, None)

    def _match_prefix(self, prompt):
        """Resident pages a new prompt's leading blocks can map onto:
        walk the index chain from the root matching FULL token blocks
        exactly; the final PARTIAL block may additionally map onto a
        resident full block whose leading tokens match (a superset —
        the extra tokens are masked by ``lengths``, and the sequence's
        first write into that page takes the CoW fault).  Returns the
        (possibly empty) list of resident page ids, prefix order."""
        ps = self.alloc.page_size
        n = int(prompt.shape[0])
        shared, parent = [], 0
        for b in range(-(-n // ps)):
            kids = self._children.get(parent)
            if not kids:
                break
            chunk = prompt[b * ps:(b + 1) * ps]
            if chunk.shape[0] == ps:
                page = kids.get(tuple(int(t) for t in chunk))
                if page is None:
                    break
                shared.append(page)
                parent = page
            else:
                r = chunk.shape[0]
                part = tuple(int(t) for t in chunk)
                for toks, page in kids.items():
                    if toks[:r] == part:
                        shared.append(page)     # superset: CoW on write
                        break
                break
        return shared

    def _index_prompt(self, seq):
        """Publish a seated sequence's FULL prompt blocks to the prefix
        index (first writer wins — a block already resident elsewhere
        keeps its canonical page).  Only full blocks are indexable:
        their content is complete and, because decode writes always
        land past the prompt, immutable while resident."""
        ps = self.alloc.page_size
        parent = 0
        for b in range(int(seq.prompt.shape[0]) // ps):
            toks = tuple(int(t) for t in seq.prompt[b * ps:(b + 1) * ps])
            page = seq.pages[b]
            kids = self._children.get(parent)
            cur = None if kids is None else kids.get(toks)
            if cur is None:
                if kids is None:
                    kids = self._children.setdefault(parent, {})
                kids[toks] = page
                self._indexed_by_page[page] = (parent, toks)
                cur = page
            if cur != page:
                # the canonical chain diverged from our residency (a
                # twin indexed first) — stop; the canonical pages
                # already serve future matches
                break
            parent = page

    def _map_pages(self, seq):
        """Hand one admitted sequence its prompt pages: leading blocks
        resident in the prefix index are SHARED (a refcount bump, zero
        pool cost); only the remainder is allocated — all-or-nothing,
        so ``PoolExhaustedError`` leaves nothing taken."""
        ptoks = self._prefill_tokens(seq)
        n = int(ptoks.shape[0])
        shared = self._match_prefix(ptoks)
        own = self.alloc.alloc(self.alloc.pages_for(n) - len(shared))
        self.alloc.share(shared)
        seq.pages = shared + own
        seq.shared_n = len(shared)
        self._bump("pages_charged", len(own))
        if shared:
            self._bump("pages_shared_mapped", len(shared))
            if seq.out:
                # resume re-maps onto still-resident pages — the
                # prefix-index dividend that makes preemption cheap
                self._bump("resume_pages_remapped", len(shared))
        # index NOW, not at seat time: the same program call that maps
        # these pages fills them (prefill scatter / handoff), so a
        # LATER sequence in the same batch can already share them — a
        # fleet of identical system prompts shares from request two
        # onward.  A failed prefill releases the pages, which withdraws
        # the entries again.
        self._index_prompt(seq)

    def _scatter_table_row(self, seq):
        """The page-table row a PREFILL/HANDOFF scatter may write
        through: shared blocks are zeroed so their writes sink to page
        0 — resident shared pages must never be rewritten (a superset-
        shared page holds MORE tokens than this prompt claims, and the
        program zero-pads past ``lengths``).  The DECODE table keeps
        the real ids: attention reads the resident prefix."""
        row = np.zeros((self.pages_per_seq,), np.int32)
        row[:len(seq.pages)] = seq.pages
        row[:seq.shared_n] = 0
        return row

    # ---- retirement ----
    def _vacate(self, seq):
        """Release a sequence's slot + pages (no request resolution)."""
        if seq.req.trace is not None:
            _telemetry.end_span(seq.req, "decode", tokens=len(seq.out))
        if seq.slot is not None:
            s = seq.slot
            self._bump("active_slots", -1)
            self._active[s] = False
            self._lengths[s] = 0
            self._tokens[s] = 0
            self._tables[s, :] = 0
            self._temps[s] = 0.0
            self._topks[s] = 0
            self._cow_src[s] = 0
            self._cow_dst[s] = 0
            self._window[s, :] = 0
            self._nvalid[s] = 1
            self._seqs.pop(s, None)
            seq.slot = None
        if seq.pages:
            self._release(seq.pages)
            seq.pages = []
        seq.shared_n = 0
        self._note_occupancy()

    def _note_occupancy(self):
        total = self.alloc.allocatable
        held = total - self.alloc.free_count()
        self._c_pages.set_value(int(100 * held / total))

    def _retire(self, seq, error=None, stat="completed"):
        """Terminal retirement: vacate, resolve the future, account.
        Journaled (retirement granularity) — EXCEPT in handoff-drain
        mode, where exported sequences must stay importable: a retire
        record would erase the handoff record the next server reads."""
        if seq.pages:
            self._h_slot_pages.observe(len(seq.pages))
        self._vacate(seq)
        if error is None:
            seq.req.set_result(np.asarray(seq.out, np.int32))
        else:
            seq.req.set_error(error)
        if not self._handoff_exit.is_set():
            self._journal_event("gen_retire", rid=seq.rid, status=stat)
        self._bump(stat)
        self._bump("retired")
        self._c_retired.increment()

    def _retire_expired(self):
        """Deadline sweep: queued sequences expire without device work,
        in-flight ones mid-generation (pages freed either way; the
        error carries the partial tokens — progress is visible, ISSUE
        19, not discarded silently)."""
        worked = False
        now = time.monotonic()
        for seq in [s for s in self._seqs.values()
                    if s.req.expired(now)]:
            self._retire(seq, DeadlineExceededError(
                f"deadline exceeded mid-generation after "
                f"{len(seq.out)} of {seq.max_new} tokens — pages freed, "
                f"partial output on the error",
                tokens_generated=len(seq.out),
                partial_tokens=[int(t) for t in seq.out]),
                stat="expired")
            worked = True
        with self._admit_lock:
            queued = [s for s in self._pending if s.req.expired(now)]
            for s in queued:
                self._pending.remove(s)
        for seq in queued:
            self._retire(seq, DeadlineExceededError(
                "deadline exceeded in queue after preemption — partial "
                "tokens on the error" if seq.ran else
                "deadline exceeded in queue — the request never touched "
                "the device",
                tokens_generated=len(seq.out),
                partial_tokens=[int(t) for t in seq.out]),
                stat="expired")
            worked = True
        return worked

    # ---- admission into slots ----
    def _free_slots(self):
        return [s for s in range(self.n_slots) if s not in self._seqs]

    def _bucket_len(self, n):
        return next(L for L in self.buckets.length if L >= n)

    def _prefill_len(self, seq):
        """Tokens a (re-)prefill of this sequence runs through the
        bucket grid.  Fresh sequence: the prompt.  Resume (``seq.out``
        non-empty): prompt + generated-so-far minus the pending token —
        the exact step-boundary cache occupancy — capped at the largest
        length bucket.  The overflow tail becomes ``seq.replay``,
        forced one token per step through the pinned decode/verify
        program (a chunked prefill through the grid is impossible: the
        bucket programs recompute the whole context, so a chunk's
        forward would need K/V the grid cannot be given).  Either way
        resume reuses ONLY existing executables — the census contract
        is untouched."""
        n = int(seq.prompt.shape[0])
        if not seq.out:
            return n
        return min(n + len(seq.out) - 1, max(self.buckets.length))

    def _prefill_tokens(self, seq):
        """The token array a (re-)prefill feeds the bucket grid."""
        if not seq.out:
            return seq.prompt
        full = np.concatenate([seq.prompt,
                               np.asarray(seq.out, np.int32)])
        return full[:self._prefill_len(seq)]

    def _take_prefill_group(self, need_resources=True):
        """Pop one same-length-bucket group of queued sequences, highest
        QoS priority first (FIFO by admission stamp within a class —
        the per-class p99 ordering the SLO chaos mode asserts).  With
        ``need_resources`` (the fused path) the group is also capped by
        free slots and budgeted against free pages; the disaggregated
        path prefills ahead of seat availability — flow control is the
        bounded prefill queue.  Returns [] when nothing can start."""
        if need_resources:
            limit = min(len(self._free_slots()), self.buckets.max_batch)
        else:
            limit = self.buckets.max_batch
        if limit == 0:
            return []
        with self._admit_lock:
            if not self._pending:
                return []
            ordered = sorted(self._pending,
                             key=lambda s: (-s.priority, s.stamp))
            bucket = self._bucket_len(self._prefill_len(ordered[0]))
            group, budget = [], self.alloc.free_count()
            for seq in ordered:
                if len(group) >= limit:
                    break
                if self._bucket_len(self._prefill_len(seq)) != bucket:
                    continue
                if need_resources:
                    # charge only NON-shared pages: blocks resident in
                    # the prefix index cost nothing — the concurrency
                    # multiplier of prefix sharing lands here
                    need = self.alloc.pages_for(self._prefill_len(seq)) \
                        - len(self._match_prefix(
                            self._prefill_tokens(seq)))
                    if need > budget:
                        break   # keep order: don't starve the big one
                    budget -= need
                group.append(seq)
            for seq in group:
                self._pending.remove(seq)
        return group

    def _admit(self):
        """Admit queued sequences into free decode slots (prefill).
        While the breaker fast-fails nothing is admitted; once its probe
        timer expires a SINGLE group goes through as the trial — its
        verdict closes or re-opens the circuit (the
        ``InferenceServer`` admission stance, at group granularity)."""
        if self.breaker.engaged():
            return False
        cautious = self.breaker.state_code() != 0
        worked = False
        while True:
            group = self._take_prefill_group()
            if not group:
                return worked
            worked = True
            self._prefill_group(group)
            if cautious:
                return worked

    # ---- disaggregated prefill group ----
    def _dispatch_prefill(self):
        """Feed queued sequences to the prefill worker group (bounded
        queue = flow control; only the decode loop produces, so
        ``full()`` then ``put_nowait`` cannot race).  Mirrors
        ``_admit``'s breaker stance: nothing while engaged, a single
        trial group while cautious."""
        if self.breaker.engaged():
            return False
        cautious = self.breaker.state_code() != 0
        worked = False
        while not self._prefill_q.full() \
                and len(self._handoff_backlog) <= self.n_slots:
            group = self._take_prefill_group(need_resources=False)
            if not group:
                return worked
            for seq in group:          # queue ends at dispatch; prefill
                if seq.req.trace is not None:   # covers the worker leg
                    _telemetry.end_span(seq.req, "queue")
                    _telemetry.open_span(seq.req, "prefill")
            with self._lock:
                self._prefill_flight[id(group)] = group
            self._prefill_q.put_nowait(group)
            worked = True
            if cautious:
                return worked
        return worked

    def _prefill_worker(self):
        """One prefill-group worker: pull a group, run the pool-free
        prefill, stage the KV payload onto the handoff queue.  Never
        touches the pools, the allocator, or the slot arrays — the
        decode group's state is not this thread's to break."""
        while True:
            try:
                group = self._prefill_q.get(timeout=self._IDLE_TICK * 4)
            except queue.Empty:
                # NOT self._stop: drain() sets that while the decode loop
                # is still dispatching queued work through this group —
                # exiting then strands every group it would have served.
                # The loop signals _loop_exited once it truly stops.
                if self._loop_exited.is_set():
                    return
                continue
            if group is None:              # drain sentinel, one per worker
                return
            try:
                self._do_prefill_kv(group)
            finally:
                with self._lock:
                    self._prefill_flight.pop(id(group), None)

    def _do_prefill_kv(self, group):
        """Run one group through the pool-free prefill and hand off the
        per-sequence payloads.  Resumed members run prompt + generated
        through the same bucket executables.  A failure resolves the
        whole group explicitly (breaker sees it; resumed members are
        salvaged against their retry budget); the pools are untouched
        either way — prefill-side faults cannot hurt seated
        sequences."""
        k = len(group)
        bucket = self._bucket_len(max(self._prefill_len(s)
                                      for s in group))
        b = self.buckets.batch_bucket(k)
        tokens = np.zeros((b, bucket), np.int32)
        lengths = np.zeros((b,), np.int32)
        seeds = np.zeros((b,), np.uint32)
        temps = np.zeros((b,), np.float32)
        topks = np.zeros((b,), np.int32)
        pspans = None
        worker = threading.current_thread().name
        for i, seq in enumerate(group):
            ptoks = self._prefill_tokens(seq)
            n = ptoks.shape[0]
            tokens[i, :n] = ptoks
            lengths[i] = n
            seeds[i] = seq.seed
            temps[i] = seq.temp
            topks[i] = seq.top_k
            if seq.req.trace is not None:
                sp = _telemetry.get_span(seq.req, "prefill")
                if sp is not None:
                    sp.attrs["worker"] = worker     # who ran the prefill
                    if pspans is None:
                        pspans = []
                    pspans.append(sp)
        if pspans is not None:
            _telemetry.push_current(pspans)
        try:
            _fault.fire("generate.prefill")
            if any(s.out for s in group):
                _fault.fire("generate.resume")
            with _profiler.scope(f"{self._name}.prefill", cat="serving"):
                first, k_all, v_all = self._run_prefill_kv(
                    tokens, lengths, seeds, temps, topks)
        except Exception as exc:    # noqa: BLE001 — resolved per sequence
            self.breaker.record_failure()
            self._note_step_failure(exc)
            err = _fault.with_context(exc, f"{self._name} prefill of {k}")
            for seq in group:
                if seq.out:
                    self._requeue_salvaged(seq, err)
                else:
                    self._retire(seq, err, stat="failed")
            return
        finally:
            if pspans is not None:
                _telemetry.pop_current()
        self.breaker.record_success()
        self._bump("prefills")
        for i, seq in enumerate(group):
            n = self._prefill_len(seq)
            if seq.req.trace is not None:   # handoff wait + scatter next
                _telemetry.end_span(seq.req, "prefill")
                _telemetry.open_span(seq.req, "handoff")
            # per-sequence payload: the decode loop re-packs any mix of
            # these into the fixed-shape handoff batch.  Copied — a view
            # parked in the handoff backlog would pin the whole
            # [n_layers, b, L, H, D] batch output, not just its own rows
            self._handoff_q.put((seq, int(first[i]),
                                 k_all[:, i, :n].copy(),
                                 v_all[:, i, :n].copy()))

    def _drain_handoffs(self):
        """Seat prefilled sequences: pack every seatable payload (free
        slot + pages, deadline not passed) into ONE fixed-shape handoff
        batch, scatter it into the pools, seat the sequences.  Payloads
        that cannot seat yet stay in the backlog for the next tick —
        slots free every step as sequences retire."""
        backlog = self._handoff_backlog
        self._handoff_backlog = []
        while True:
            try:
                backlog.append(self._handoff_q.get_nowait())
            except queue.Empty:
                break
        if not backlog:
            return False
        worked = False
        batch, still = [], []
        now = time.monotonic()
        free_slots = self._free_slots()
        budget = self.alloc.free_count()
        for entry in backlog:
            seq, first_tok, k_seq, v_seq = entry
            if seq.req.expired(now):
                self._retire(seq, DeadlineExceededError(
                    "deadline exceeded before the prefilled sequence "
                    "reached a decode slot — pages never held",
                    tokens_generated=len(seq.out),
                    partial_tokens=[int(t) for t in seq.out]),
                    stat="expired")
                worked = True
                continue
            need = self.alloc.pages_for(self._prefill_len(seq)) \
                - len(self._match_prefix(self._prefill_tokens(seq)))
            if len(batch) >= min(len(free_slots), self.buckets.max_batch) \
                    or need > budget:
                still.append(entry)
                continue
            budget -= need
            batch.append(entry)
        self._handoff_backlog = still
        if not batch:
            return worked
        B = self.buckets.max_batch
        kbuf, vbuf = self._staging()
        lengths = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        tables = np.zeros((B, self.pages_per_seq), np.int32)
        seated = []
        hspans = None
        for seq, _t, _k, _v in batch:
            if seq.req.trace is not None:
                sp = _telemetry.get_span(seq.req, "handoff")
                if sp is not None:
                    if hspans is None:
                        hspans = []
                    hspans.append(sp)
        if hspans is not None:
            _telemetry.push_current(hspans)
        try:
            _fault.fire("fleet.handoff")
            for j, (seq, first_tok, k_seq, v_seq) in enumerate(batch):
                n = k_seq.shape[1]
                self._map_pages(seq)
                kbuf[:, j, :n] = k_seq
                vbuf[:, j, :n] = v_seq
                lengths[j] = n
                active[j] = True
                tables[j] = self._scatter_table_row(seq)
                seated.append(seq)
            with _profiler.scope(f"{self._name}.handoff", cat="serving"):
                self._run_handoff(kbuf, vbuf, lengths, active, tables)
        except Exception as exc:    # noqa: BLE001 — resolved per sequence
            self.breaker.record_failure()
            self._note_step_failure(exc)
            err = _fault.with_context(
                exc, f"{self._name} handoff of {len(batch)}")
            for seq, _t, _k, _v in batch:
                if seq.out:
                    self._requeue_salvaged(seq, err)
                else:
                    self._retire(seq, err, stat="failed")
            self._recover_pools()
            return True
        finally:
            if hspans is not None:
                _telemetry.pop_current()
        self._bump("handoffs")
        slots = self._free_slots()
        for j, (seq, first_tok, _k, _v) in enumerate(batch):
            self._seat(seq, slots[j], first_tok)
        self._note_occupancy()
        return True

    def _prefill_group(self, group):
        """Prefill one bucket-aligned group and seat it in decode slots.
        Resumed members (``seq.out`` non-empty) run prompt + generated
        through the SAME bucket executables — their sampled first token
        is overridden at seat time by the recorded one."""
        k = len(group)
        bucket = self._bucket_len(max(self._prefill_len(s)
                                      for s in group))
        b = self.buckets.batch_bucket(k)
        slots = self._free_slots()[:k]
        pspans = None
        worker = threading.current_thread().name
        for seq in group:              # queue ended at the pop; prefill
            if seq.req.trace is not None:   # covers alloc + the program
                _telemetry.end_span(seq.req, "queue")
                sp = _telemetry.open_span(seq.req, "prefill",
                                          worker=worker)
                if sp is not None:
                    if pspans is None:
                        pspans = []
                    pspans.append(sp)
        try:
            for seq in group:
                self._map_pages(seq)
        except PoolExhaustedError:
            # _take_prefill_group budgeted against the free count, so
            # only a racing... nothing else allocates; defensive re-queue
            for seq in group:
                self._vacate(seq)
                if seq.req.trace is not None:
                    _telemetry.end_span(seq.req, "prefill")
                    _telemetry.open_span(seq.req, "queue", requeued=True)
            with self._admit_lock:
                self._pending.extendleft(reversed(group))
            return
        tokens = np.zeros((b, bucket), np.int32)
        lengths = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        tables = np.zeros((b, self.pages_per_seq), np.int32)
        seeds = np.zeros((b,), np.uint32)
        temps = np.zeros((b,), np.float32)
        topks = np.zeros((b,), np.int32)
        for i, seq in enumerate(group):
            ptoks = self._prefill_tokens(seq)
            n = ptoks.shape[0]
            tokens[i, :n] = ptoks
            lengths[i] = n
            active[i] = True
            tables[i] = self._scatter_table_row(seq)
            seeds[i] = seq.seed
            temps[i] = seq.temp
            topks[i] = seq.top_k
        if pspans is not None:
            _telemetry.push_current(pspans)
        try:
            _fault.fire("generate.prefill")
            if any(s.out for s in group):
                _fault.fire("generate.resume")
            with _profiler.scope(f"{self._name}.prefill", cat="serving"):
                first = self._run_prefill(tokens, lengths, active, tables,
                                          seeds, temps, topks)
        except Exception as exc:    # noqa: BLE001 — resolved per sequence
            self.breaker.record_failure()
            self._note_step_failure(exc)
            err = _fault.with_context(exc, f"{self._name} prefill of {k}")
            for seq in group:
                if seq.out:
                    # a resumed member's tokens survive the failed
                    # re-prefill — salvage against its retry budget
                    self._requeue_salvaged(seq, err)
                else:
                    self._retire(seq, err, stat="failed")
            self._recover_pools()
            return
        finally:
            if pspans is not None:
                _telemetry.pop_current()
        self.breaker.record_success()
        self._bump("prefills")
        for i, seq in enumerate(group):
            if seq.req.trace is not None:
                _telemetry.end_span(seq.req, "prefill")
            self._seat(seq, slots[i], int(first[i]))
        self._note_occupancy()

    def _seat(self, seq, slot, tok):
        """Seat one prefilled sequence in a decode slot: slot init is
        seat-time only — the per-token path advances ``_tokens`` /
        ``_lengths``; ``_ensure_capacity`` appends table entries.

        A RESUMED sequence (``seq.out`` non-empty) re-enters here after
        its re-prefill covered ``full[:H]`` (``full`` = prompt ++
        generated, ``H = _prefill_len``): the pending token is forced to
        the recorded ``full[H]`` (the prefill's sampled first token is
        identical under position-keyed sampling, but the record is
        authoritative), recorded tokens past ``H`` replay one per step
        through the pinned decode path, and only then does live sampling
        continue — token-exact, zero new executables."""
        if seq.req.trace is not None:
            _telemetry.end_span(seq.req, "handoff")   # no-op when fused
            _telemetry.open_span(seq.req, "decode", slot=slot)
        seq.cached = seq.prompt.shape[0]
        seq.ran = True
        s = seq.slot = slot
        self._seqs[s] = seq
        self._bump("active_slots")
        self._tables[s, :] = 0
        # the REAL table: shared pages included — decode attention
        # reads the resident prefix (the scatter row already sank its
        # writes to page 0)
        self._tables[s, :len(seq.pages)] = seq.pages
        self._temps[s] = seq.temp
        self._topks[s] = seq.top_k
        self._seeds[s] = seq.seed
        self._active[s] = True
        self._cow_src[s] = 0
        self._cow_dst[s] = 0
        if seq.out:
            full = np.concatenate(
                [seq.prompt, np.asarray(seq.out, np.int32)])
            H = self._prefill_len(seq)
            seq.cached = H
            seq.replay = [int(t) for t in full[H + 1:]]
            self._tokens[s] = int(full[H])
            self._lengths[s] = H
            self._bump("resumes")
            if seq.req.trace is not None:
                _telemetry.span_event(seq.req, "resume",
                                      tokens=len(seq.out),
                                      replay=len(seq.replay))
            if self._verify is not None:
                self._refresh_window(seq)
            return
        if not self._finish_token(seq, tok) and self._verify is not None:
            self._refresh_window(seq)

    def _finish_token(self, seq, tok):
        """Account one newly generated token; True if the sequence
        retired (EOS or max-tokens).  A continuing sequence's per-token
        slot state advances so the next decode step consumes ``tok``
        (the page-table row is owned by seat-time init +
        ``_ensure_capacity`` — never rewritten here)."""
        if self._eos is not None and tok == self._eos:
            self._retire(seq)
            return True
        seq.out.append(tok)
        self._bump("tokens_out")
        self._c_tokens.increment()
        if len(seq.out) >= seq.max_new:
            self._retire(seq)
            return True
        s = seq.slot
        self._tokens[s] = tok
        self._lengths[s] = seq.cached
        return False

    # ---- decode ----
    def _ensure_capacity(self, seq, lookahead=0):
        """Guarantee pages exist for this step's write positions (the
        pending token plus ``lookahead`` speculative candidates), then
        arm the slot's CoW fault if the write block is shared.  When
        the pool is dry, eviction is strictly seniority-ordered: a
        sequence may only preempt YOUNGER neighbours (later admission
        stamp — preserved across preemptions, so a restarted sequence
        keeps its place in line); with no younger neighbour it yields
        ITSELF back to the queue.  The oldest in-flight sequence is
        therefore never evicted — combined with admission's
        worst-case-fit check (its full need fits the pool alone) that
        is the global progress guarantee: symmetric mutual eviction, the
        livelock where two sequences endlessly restart each other, is
        impossible by construction.  Returns False when ``seq`` yielded
        (the caller must skip it this step)."""
        while True:
            try:
                while self.alloc.pages_for(seq.cached + 1 + lookahead) \
                        > len(seq.pages):
                    seq.pages.extend(self.alloc.alloc(1))
                    self._tables[seq.slot, len(seq.pages) - 1] = \
                        seq.pages[-1]
                self._cow_guard(seq)
                return True
            except PoolExhaustedError:
                victims = [s for s in self._seqs.values()
                           if s is not seq and s.stamp > seq.stamp]
                if victims:
                    self._preempt(max(victims, key=lambda s: s.stamp))
                elif len(self._seqs) > 1:
                    self._preempt(seq)     # we are the youngest: yield
                    return False
                else:
                    raise     # alone and dry: admission math was violated

    def _cow_guard(self, seq):
        """Copy-on-write fault check for this step's write block.  Only
        the block holding position ``seq.cached`` can be shared (all
        shared blocks are prompt blocks and writes land at or past the
        prompt's tail; later lookahead positions are in freshly
        allocated pages), so ONE check per slot per step suffices.  On
        a fault: allocate a fresh page (``PoolExhaustedError``
        propagates to the caller's preemption loop), drop our hold on
        the shared page, remap table + page list, and arm the in-graph
        page copy lanes.  A sole-holder write into a still-indexed page
        instead withdraws the index entry — the block's advertised
        content is about to change."""
        s = seq.slot
        blk = seq.cached // self.alloc.page_size
        page = seq.pages[blk]
        if self.alloc.refcount(page) > 1:
            fresh = self.alloc.alloc(1)[0]
            self._release([page])          # others still hold it
            seq.pages[blk] = fresh
            seq.shared_n = min(seq.shared_n, blk)
            self._tables[s, blk] = fresh
            self._cow_src[s] = page
            self._cow_dst[s] = fresh
            self._bump("cow_faults")
        elif page in self._indexed_by_page:
            self._deindex(page)

    def _refresh_window(self, seq):
        """Right-align the draft's token context: the last
        ``spec_window`` tokens through the PENDING token (the draft
        proposes its successors).  At steady state that is all of
        prompt + generated; during resume replay the pending token sits
        at position ``seq.cached`` and later recorded tokens must stay
        out of the draft's view."""
        s = seq.slot
        W = self._spec_window
        toks = np.concatenate(
            [seq.prompt,
             np.asarray(seq.out, np.int32)])[:seq.cached + 1][-W:]
        self._window[s, :] = 0
        self._window[s, W - len(toks):] = toks
        self._nvalid[s] = len(toks)

    def _preempt(self, victim):
        """Evict a sequence: free its pages and requeue it at the FRONT
        WITH its generated-so-far tokens (ISSUE 19) — re-admission
        re-prefills prompt + generated through the existing bucket grid
        and the position-keyed sampler continues the identical stream,
        so preemption costs latency, never work.  The request future is
        untouched: preemption is invisible to the client beyond that
        latency.  Preemption is scheduling, not failure — it does NOT
        consume the salvage-retry budget."""
        _fault.fire("generate.evict")
        self._vacate(victim)
        victim.cached = 0
        victim.replay = []
        if victim.out:
            self._bump("tokens_salvaged", len(victim.out))
        self._bump("preempted")
        self._c_preempted.increment()
        self._journal_event("gen_snapshot", rid=victim.rid,
                            out=list(victim.out))
        if victim.req.trace is not None:
            # preemption is a span event on the tree, and the requeue
            # wait is a fresh queue span — the restarted life (queue →
            # prefill → decode again) stays attributed
            _telemetry.span_event(victim.req, "preempt",
                                  tokens_salvaged=len(victim.out))
            _telemetry.open_span(victim.req, "queue", requeued=True)
        with self._admit_lock:
            self._pending.appendleft(victim)

    def _requeue_salvaged(self, seq, err, budgeted=True):
        """Salvage one accepted sequence off a failure domain (ISSUE
        19): keep its generated tokens, requeue it for a token-exact
        resume.  ``budgeted`` failures (the sequence sat in the failing
        step) consume the per-sequence ``salvage_retries`` budget —
        exhausted, the sequence retires with a terminal error carrying
        ``tokens_generated`` / ``partial_tokens`` / ``snapshot``, which
        is what fleet failover redispatches to the next replica.
        Unbudgeted salvage (breaker fast-fail, collateral pool loss)
        preserves work without charging the sequence for a failure
        that was not its own.  Returns True when the sequence was
        requeued, False when it retired terminally."""
        if budgeted:
            seq.salvage += 1
            if seq.salvage > self._salvage_retries:
                terminal = _fault.with_context(
                    err, f"{self._name}: salvage budget "
                    f"({self._salvage_retries}) exhausted after "
                    f"{len(seq.out)} of {seq.max_new} tokens — partial "
                    f"output and a resume snapshot ride the error")
                terminal.tokens_generated = len(seq.out)
                terminal.partial_tokens = [int(t) for t in seq.out]
                terminal.snapshot = self._snapshot_of(seq)
                self._retire(seq, terminal, stat="failed")
                return False
            self._bump("salvage_retries")
        try:
            _fault.fire("generate.salvage")
        except Exception as sexc:   # noqa: BLE001 — salvage path faulted
            terminal = _fault.with_context(
                sexc, f"{self._name}: salvage of sequence {seq.rid} "
                f"failed — resolving with partial output")
            terminal.tokens_generated = len(seq.out)
            terminal.partial_tokens = [int(t) for t in seq.out]
            terminal.snapshot = self._snapshot_of(seq)
            self._retire(seq, terminal, stat="failed")
            return False
        self._vacate(seq)
        seq.cached = 0
        seq.replay = []
        self._bump("tokens_salvaged", len(seq.out))
        self._journal_event("gen_snapshot", rid=seq.rid,
                            out=list(seq.out))
        if seq.req.trace is not None:
            _telemetry.end_span(seq.req, "prefill")
            _telemetry.end_span(seq.req, "handoff")
            _telemetry.span_event(seq.req, "salvage",
                                  tokens_salvaged=len(seq.out),
                                  retry=seq.salvage)
            _telemetry.open_span(seq.req, "queue", requeued=True)
        with self._admit_lock:
            self._pending.appendleft(seq)
        return True

    def _salvage_seated(self, err, budgeted=True):
        """Requeue every seated sequence with its tokens intact — the
        ISSUE 19 replacement for failing everything on a device step
        failure or a breaker fast-fail."""
        for seq in list(self._seqs.values()):
            self._requeue_salvaged(seq, err, budgeted=budgeted)

    def _decode_once(self):
        """One token for every in-flight sequence: capacity, the pinned
        decode executable, then per-slot retirement/advance."""
        self._cow_src[:] = 0        # fault lanes re-arm per step
        self._cow_dst[:] = 0
        try:
            # oldest first: seniors claim pages (evicting juniors if the
            # pool is dry) before juniors decide whether to yield
            for seq in sorted(self._seqs.values(), key=lambda s: s.stamp):
                if seq.slot is None:
                    continue     # preempted by an earlier neighbour
                self._ensure_capacity(seq)
        except PoolExhaustedError as exc:
            # unreachable via admission's worst-case check; resolve
            # rather than wedge if it ever happens
            self._fail_everything(_fault.with_context(
                exc, f"{self._name} page pool wedged"))
            return
        if not self._seqs:
            return
        if not self.breaker.allow():
            # breaker fast-fail: salvage, don't destroy — seated work
            # goes back to the queue with tokens intact and re-seats
            # when the probe succeeds.  Unbudgeted: the breaker being
            # open is not this sequence's failure.
            self._salvage_seated(CircuitOpenError(
                f"{self._name}: circuit open — fast-failing in-flight "
                f"generation"), budgeted=False)
            return
        dspans = None
        for seq in self._seqs.values():    # fault firings → span events
            if seq.req.trace is not None:
                sp = _telemetry.get_span(seq.req, "decode")
                if sp is not None:
                    if dspans is None:
                        dspans = []
                    dspans.append(sp)
        if dspans is not None:
            _telemetry.push_current(dspans)
        try:
            _fault.fire("generate.decode")
            with _profiler.scope(f"{self._name}.decode", cat="serving"):
                nxt = self._run_decode()
        except Exception as exc:    # noqa: BLE001 — resolved per sequence
            self.breaker.record_failure()
            self._note_step_failure(exc)
            err = _fault.with_context(
                exc, f"{self._name} decode step over "
                f"{len(self._seqs)} sequences")
            self._salvage_seated(err)
            self._recover_pools()
            return
        finally:
            if dspans is not None:
                _telemetry.pop_current()
        self.breaker.record_success()
        self._bump("decode_steps")
        for seq in list(self._seqs.values()):
            seq.cached += 1          # this step wrote the input token
            if seq.replay:
                # resume replay: the step re-derived this recorded
                # token (position-keyed sampling); advance the slot
                # from the record — never re-append to seq.out
                tok = seq.replay.pop(0)
                self._tokens[seq.slot] = tok
                self._lengths[seq.slot] = seq.cached
                if self._verify is not None:
                    self._refresh_window(seq)
                continue
            self._finish_token(seq, int(nxt[seq.slot]))
        self._journal_tick()

    def _verify_once(self):
        """One SPECULATIVE step for every in-flight sequence: capacity
        with ``spec_k`` lookahead, the pinned verify executable, then
        1..k+1 accepted tokens per slot.  Mirrors ``_decode_once``'s
        failure/breaker/span semantics exactly — same fault point, so
        chaos drives both paths with one name."""
        self._cow_src[:] = 0
        self._cow_dst[:] = 0
        try:
            for seq in sorted(self._seqs.values(), key=lambda s: s.stamp):
                if seq.slot is None:
                    continue     # preempted by an earlier neighbour
                self._ensure_capacity(seq, lookahead=self._spec_k)
        except PoolExhaustedError as exc:
            self._fail_everything(_fault.with_context(
                exc, f"{self._name} page pool wedged"))
            return
        if not self._seqs:
            return
        if not self.breaker.allow():
            self._salvage_seated(CircuitOpenError(
                f"{self._name}: circuit open — fast-failing in-flight "
                f"generation"), budgeted=False)
            return
        dspans = None
        for seq in self._seqs.values():
            if seq.req.trace is not None:
                sp = _telemetry.get_span(seq.req, "decode")
                if sp is not None:
                    if dspans is None:
                        dspans = []
                    dspans.append(sp)
        if dspans is not None:
            _telemetry.push_current(dspans)
        try:
            _fault.fire("generate.decode")
            with _profiler.scope(f"{self._name}.verify", cat="serving"):
                emitted, n_acc = self._run_verify()
        except Exception as exc:    # noqa: BLE001 — resolved per sequence
            self.breaker.record_failure()
            self._note_step_failure(exc)
            err = _fault.with_context(
                exc, f"{self._name} verify step over "
                f"{len(self._seqs)} sequences")
            self._salvage_seated(err)
            self._recover_pools()
            return
        finally:
            if dspans is not None:
                _telemetry.pop_current()
        self.breaker.record_success()
        self._bump("decode_steps")
        self._bump("verify_steps")
        k = self._spec_k
        for seq in list(self._seqs.values()):
            s = seq.slot
            if seq.replay:
                # resume replay: force ONE recorded token per step and
                # skip speculative accounting — the draft window is
                # truncated at the pending position, so acceptance
                # stats over replayed steps would be meaningless
                seq.cached += 1
                tok = seq.replay.pop(0)
                self._tokens[s] = tok
                self._lengths[s] = seq.cached
                self._refresh_window(seq)
                continue
            a = int(n_acc[s])
            self._bump("spec_proposed", k)
            self._bump("spec_accepted", a)
            self._h_accept.observe(a / k)
            # positions 0..a hold real K/V (pending + accepted drafts);
            # emitted[a] is the correction/bonus — the next pending
            # token, K/V not yet written
            for j in range(a + 1):
                seq.cached += 1
                if self._finish_token(seq, int(emitted[s, j])):
                    break
            else:
                self._refresh_window(seq)
        self._journal_tick()

    def _export_error(self, seq):
        """Resolve one exported sequence's request (handoff drain): the
        snapshot — and the partial tokens — ride a ``ServerClosedError``
        so the caller (typically a fleet router) can redispatch it
        token-exact, and the journal gains a ``gen_handoff`` record a
        successor's ``restore_journal`` re-admits."""
        snap = self._snapshot_of(seq)
        self.exported.append(snap)
        self._journal_event("gen_handoff", **snap.to_json())
        self._bump("handoff_exports")
        err = ServerClosedError(
            f"{self._name}: drained with handoff after {len(seq.out)} "
            f"of {seq.max_new} tokens — resume snapshot exported")
        err.tokens_generated = len(seq.out)
        err.partial_tokens = [int(t) for t in seq.out]
        err.snapshot = snap
        return err

    def _export_all(self):
        """Handoff-drain sweep: every accepted sequence still alive —
        seated or queued — exports instead of finishing.  Disaggregated
        pipeline residue is swept by ``_fail_residue``, which routes
        through the same exporter in handoff mode."""
        for seq in list(self._seqs.values()):
            self._retire(seq, self._export_error(seq), stat="failed")
        with self._admit_lock:
            residue = list(self._pending)
            self._pending.clear()
        for seq in residue:
            self._retire(seq, self._export_error(seq), stat="failed")

    def _fail_everything(self, err, queued=True):
        """Explicitly resolve every in-flight (and optionally queued)
        sequence — the terminal sweep for breaker-open-during-drain and
        never-happens pool wedges.  Nothing is silently dropped."""
        for seq in list(self._seqs.values()):
            self._retire(seq, err, stat="failed")
        if not queued:
            return
        with self._admit_lock:
            residue = list(self._pending)
            self._pending.clear()
        for seq in residue:
            self._retire(seq, err, stat="failed")

    def _fail_residue(self):
        """Loop-exit sweep (a clean drain leaves nothing; a crashed loop
        may): every accepted-but-unresolved sequence gets an explicit
        terminal error — wherever it was parked, including the
        disaggregated prefill/handoff pipeline (workers are already
        joined by the caller, so these containers have no producers)."""
        residue = list(self._seqs.values())
        self._seqs = {}
        with self._admit_lock:
            residue += list(self._pending)
            self._pending.clear()
        while True:
            try:
                item = self._prefill_q.get_nowait()
            except queue.Empty:
                break
            if item is not None:
                residue += list(item)
        while True:
            try:
                residue.append(self._handoff_q.get_nowait()[0])
            except queue.Empty:
                break
        residue += [entry[0] for entry in self._handoff_backlog]
        self._handoff_backlog = []
        with self._lock:
            flight = list(self._prefill_flight.values())
            self._prefill_flight = {}
        for group in flight:
            residue += list(group)
        for seq in residue:
            if seq.slot is not None:
                seq.slot = None
                self._bump("active_slots", -1)
            if seq.req.done():
                continue
            if seq.pages:
                self._release(seq.pages)
                seq.pages = []
                seq.shared_n = 0
            if self._handoff_exit.is_set():
                seq.req.set_error(self._export_error(seq))
            else:
                seq.req.set_error(ServerClosedError(
                    "server stopped before this sequence finished"))
            self._bump("failed")
            self._bump("retired")

    # ---------------------------------------------------------------- health --
    def alive(self):
        return self._thread.is_alive()

    def ready(self):
        return (self._ready.is_set() and self.alive()
                and not self._draining.is_set()
                and not self.breaker.engaged())

    def healthz(self):
        """Router-rankable snapshot: the same keys as
        ``InferenceServer.healthz`` — ``breaker_state`` / ``in_flight`` /
        ``queue_depth`` / ``classes`` (per-class deadline-miss + p50/p99
        from ``TenantQoS.snapshot``) / ``last_error`` — so a
        ``ServingFleet`` ranks LLM and classifier replicas uniformly,
        plus the paging/disaggregation gauges.  Non-blocking: host
        counters and primitives only."""
        with self._admit_lock:
            depth = len(self._pending)
        with self._lock:
            s = self._stats
            in_flight = (s["admitted"] - s["completed"] - s["failed"]
                         - s["expired"])
            active = s["active_slots"]
            last = self._last_error
            prefill_flight = len(self._prefill_flight)
        return {"alive": self.alive(), "ready": self.ready(),
                "draining": self._draining.is_set(),
                "breaker": self.breaker.state,
                "breaker_state": self.breaker.state_code(),
                "queue_depth": depth,
                "in_flight": max(0, in_flight),
                "active_slots": active,
                "free_pages": self.alloc.free_count(),
                "total_pages": self.alloc.allocatable,
                "pages_shared": self.alloc.shared_pages(),
                "speculative": int(self._verify is not None),
                "prefill_workers": self._n_prefill_workers,
                "prefill_inflight": prefill_flight,
                "tp_shards": self.tp_shards,
                "tp_collectives": self.tp_collectives,
                "classes": self._qos.snapshot(),
                "last_error": None if last is None else
                {"type": last[0], "age": time.monotonic() - last[1]}}

    def _page_bytes(self):
        """HBM bytes one page id addresses across BOTH pools (f32
        K + V, every layer, all heads — the whole stripe a shared
        page avoids duplicating)."""
        c = self.config
        return (2 * c.n_layers * self.alloc.page_size * c.n_heads
                * c.head_dim * 4)

    @property
    def stats(self):
        with self._lock:
            out = dict(self._stats)
        out["free_pages"] = self.alloc.free_count()
        out["pages_shared"] = self.alloc.shared_pages()
        out["breaker"] = self.breaker.state
        return out

    def stamp_memory_report(self, report):
        """Stamp a costguard-style memory report (``argument_bytes`` /
        ``peak_bytes`` / ``per_device``) onto this server's ``mem_*``
        exposition gauges — the bytes are a property of the compiled
        program set, so one stamp at warmup is live until the census
        changes (see ``InferenceServer.stamp_memory_report``)."""
        self._mem_gauges = _telemetry.memory_gauges(report)
        return self._mem_gauges

    def telemetry(self, fmt="json"):
        """The unified metrics exposition (ISSUE 13): lifecycle counters,
        paging/disaggregation gauges, per-phase latency histograms
        (``queue``/``prefill``/``handoff``/``decode`` span durations,
        ms), and the per-class SLO rows — the SAME
        ``telemetry.exposition`` key schema every runtime serves.
        ``fmt="prom"`` renders Prometheus-style text."""
        h = self.healthz()
        with self._lock:
            counters = dict(self._stats)
        counters.pop("active_slots", None)     # a gauge, reported below
        gauges = {"queue_depth": h["queue_depth"],
                  "in_flight": h["in_flight"],
                  "breaker_state": h["breaker_state"],
                  "active_slots": h["active_slots"],
                  "free_pages": h["free_pages"],
                  "used_pages": h["total_pages"] - h["free_pages"],
                  "total_pages": h["total_pages"],
                  # prefix-sharing gauges (ISSUE 16): resident pages
                  # with >1 holder, CoW faults taken, and the pool
                  # bytes sharing is currently standing in for
                  "pages_shared": h["pages_shared"],
                  "pages_cow_faults": counters.get("cow_faults", 0),
                  "bytes_saved_by_sharing":
                      self.alloc.extra_refs() * self._page_bytes(),
                  "spec_k": self._spec_k if self._verify is not None
                      else 0,
                  # resume economics (ISSUE 19): pages a resumed
                  # sequence re-mapped from the prefix index instead of
                  # re-allocating — the preemption-is-cheap dividend
                  "resume_prefill_pages_remapped":
                      counters.get("resume_pages_remapped", 0),
                  "prefill_workers": h["prefill_workers"],
                  "prefill_inflight": h["prefill_inflight"],
                  "tp_shards": h["tp_shards"],
                  "ready": int(h["ready"]), "alive": int(h["alive"]),
                  "draining": int(h["draining"])}
        # the runtime-introspection families (ISSUE 15): jit-cache
        # behavior + stamped memory bytes, same keys on every runtime
        gauges.update(_telemetry.compile_gauges(self._name))
        gauges.update(self._mem_gauges)
        gauges.update(_telemetry.ckpt_gauges())
        snap = _telemetry.registry().snapshot(prefix=f"{self._name}::")
        # the registry gauges under this server's prefix ride along too
        # (page_occupancy/tokens_out/preempted/retired were previously
        # invisible to the exposition — the ISSUE 15 satellite fix);
        # healthz-derived values win on key collision
        for k, v in snap["gauges"].items():
            gauges.setdefault(k, v)
        hist = snap["histograms"]
        for cname, csnap in self._qos.latency_snapshots().items():
            hist[f"class_{cname}_latency_s"] = csnap
        payload = _telemetry.exposition("generation_server", self._name,
                                        counters, gauges, hist,
                                        h["classes"])
        return _telemetry.render(payload, fmt)

    # ----------------------------------------------------------------- drain --
    def drain(self, timeout=None, handoff=False):
        """Graceful shutdown: stop admitting (submits raise
        ``ServerClosedError``), finish EVERY accepted sequence — queued
        ones included; generation is bounded by per-request max-tokens —
        then stop the loop.  After ``drain()`` every ``Request`` ever
        returned is ``done()``.  True when the loop exited in time.

        ``handoff=True`` (ISSUE 19, rolling updates): instead of
        finishing long generations, EXPORT every unfinished sequence as
        a ``SequenceSnapshot`` — collected in ``self.exported`` and
        written to the journal as ``gen_handoff`` records — and resolve
        its request with a ``ServerClosedError`` carrying the snapshot
        and partial tokens.  A successor server completes them
        token-exact via ``submit_resume`` / ``restore_journal``."""
        if handoff:
            self._handoff_exit.set()
        self._draining.set()
        self._ready.clear()
        with self._admit_lock:
            self._stop.set()
        if self._started.is_set():
            self._thread.join(timeout)
        if not self._thread.is_alive():
            self._fail_residue()
        return not self._thread.is_alive()

    close = drain

    def serve_forever(self, poll=0.05, handoff=False):
        """Block until SIGTERM/SIGINT (``fault.GracefulExit``), then
        drain — accepted sequences resolve, mid-decode work finishes
        (``handoff=True``: they export for a successor instead)."""
        with _fault.GracefulExit() as g:
            while not g.requested and self.alive():
                time.sleep(poll)
        return self.drain(handoff=handoff)
