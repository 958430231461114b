"""Continuous-batching LLM serving (ISSUE 10): paged KV allocator,
paged-vs-dense attention parity, the one-executable decode contract
(census == runtime jit cache under mixed-length traffic), scheduler
admit/retire/EOS/preemption, deadline expiry mid-generation,
drain/SIGTERM, and sampling determinism.

All tier-1 (JAX_PLATFORMS=cpu, conftest's virtual mesh).  The
``generate`` marker selects this suite; signal tests also carry
``chaos``.
"""
import os
import signal
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mxnet_tpu import fault, profiler
from mxnet_tpu.context import on_tpu
from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                 init_causal_lm,
                                                 prefill_forward)
from mxnet_tpu.ops.paged_attention import (dense_decode_attention,
                                           paged_decode_attention)
from mxnet_tpu.ops.pallas.paged_attention import \
    paged_decode_attention_pallas
from mxnet_tpu.serving import (BucketSpec, CircuitBreaker,
                               CircuitOpenError, DeadlineExceededError,
                               GenerationServer, PageAllocator,
                               PoolExhaustedError, RejectedError,
                               ServerClosedError)

pytestmark = pytest.mark.generate
chaos = pytest.mark.chaos

CFG = CausalLMConfig(vocab_size=48, n_layers=2, n_heads=2, head_dim=8,
                     d_ff=32)
PARAMS = init_causal_lm(CFG, seed=3)
# amplified weights give varied (non-degenerate) greedy continuations,
# so parity/EOS tests exercise real token diversity
LOUD = {k: v * 8.0 if k in ("embed", "wqkv", "wo", "w1", "w2") else v
        for k, v in PARAMS.items()}


def make_server(params=LOUD, *, buckets=None, name=None, **kw):
    buckets = buckets or BucketSpec(batch=(1,), length=(8,))
    kw.setdefault("n_slots", 2)
    kw.setdefault("n_pages", 17)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("seed", 0)
    name = name or f"GenSrv-{time.monotonic_ns()}"
    return GenerationServer(params, CFG, buckets=buckets, name=name, **kw)


def oracle_greedy(params, prompt, steps, pad_to=32):
    """Reference continuation: re-run the FULL forward for every token."""
    seq = list(int(t) for t in prompt)
    out = []
    for _ in range(steps):
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, :len(seq)] = seq
        logits, _, _ = prefill_forward(
            params, CFG, jnp.asarray(toks),
            jnp.asarray([len(seq)], np.int32))
        t = int(np.argmax(np.asarray(logits)[0]))
        out.append(t)
        seq.append(t)
    return np.asarray(out, np.int32)


# -------------------------------------------------------------- allocator --
def test_allocator_alloc_extend_free():
    a = PageAllocator(9, 4)
    assert a.allocatable == 8 and a.free_count() == 8
    assert a.pages_for(1) == 1 and a.pages_for(4) == 1
    assert a.pages_for(5) == 2 and a.pages_for(0) == 0
    p1 = a.alloc(3)
    assert len(p1) == 3 and 0 not in p1       # page 0 is the sink
    p2 = a.alloc(5)
    assert a.free_count() == 0
    assert set(p1) | set(p2) == set(range(1, 9))
    a.free(p2)
    assert a.free_count() == 5


def test_allocator_exhaustion_is_all_or_nothing():
    a = PageAllocator(5, 2)
    a.alloc(2)
    before = a.free_count()
    with pytest.raises(PoolExhaustedError):
        a.alloc(3)
    assert a.free_count() == before           # nothing was taken


def test_allocator_fragmentation_reuse():
    """Freed pages are immediately reusable whatever the free/hold
    interleaving — any page serves any sequence, so there is no
    fragmentation regime at all."""
    a = PageAllocator(9, 4)
    held = [a.alloc(2) for _ in range(4)]     # pool exhausted
    assert a.free_count() == 0
    a.free(held[0])                            # free a non-contiguous pair
    a.free(held[2])
    again = a.alloc(4)                         # one alloc spans both holes
    assert sorted(again) == sorted(held[0] + held[2])


def test_allocator_validation():
    with pytest.raises(ValueError):
        PageAllocator(1, 4)                    # sink needs a sibling
    with pytest.raises(ValueError):
        PageAllocator(4, 0)


# ----------------------------------------------------- attention parity --
def _paged_fixture(seed=0, slots=3, pages_per_seq=3, page=4, heads=2, d=8,
                   n_pages=12):
    rng = np.random.RandomState(seed)
    q = rng.randn(slots, heads, d).astype(np.float32)
    kp = rng.randn(n_pages, page, heads, d).astype(np.float32)
    vp = rng.randn(n_pages, page, heads, d).astype(np.float32)
    tables = np.zeros((slots, pages_per_seq), np.int32)
    used = iter(range(1, n_pages))
    lengths = np.asarray([11, 5, 0], np.int32)[:slots]
    for s in range(slots):
        for j in range(-(-int(lengths[s]) // page)):
            tables[s, j] = next(used)
    return q, kp, vp, tables, lengths


def test_paged_vs_dense_attention_parity():
    q, kp, vp, tables, lengths = _paged_fixture()
    out = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lengths), impl="jnp"))
    slots, P = tables.shape
    page = kp.shape[1]
    ctx = P * page
    kc = kp[tables].reshape(slots, ctx, *kp.shape[2:])
    vc = vp[tables].reshape(slots, ctx, *vp.shape[2:])
    ref = np.asarray(dense_decode_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        jnp.asarray(lengths)))
    np.testing.assert_allclose(out[:2], ref[:2], rtol=1e-5, atol=1e-5)
    assert np.all(np.isfinite(out))            # inactive row: garbage, not NaN


def test_paged_attention_pallas_interpret_parity():
    """The TPU ragged kernel against the jnp path (Pallas interpreter
    off-TPU), including the inactive-slot zero-output contract."""
    q, kp, vp, tables, lengths = _paged_fixture()
    ref = np.asarray(paged_decode_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lengths), impl="jnp"))
    out = np.asarray(paged_decode_attention_pallas(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tables), jnp.asarray(lengths)))
    # on the TPU both sides run the MXU's default single bf16 pass on these
    # f32 inputs: 3.2e-3 apart measured on the chip (PR 21); a wrong page or
    # mask is an O(1) error
    tol = 2e-2 if on_tpu() else 1e-5
    np.testing.assert_allclose(out[:2], ref[:2], rtol=tol, atol=tol)
    assert np.all(out[2] == 0.0)               # length-0 slot never ran a page


def test_incremental_decode_matches_full_forward():
    """The strong contract: greedy generation through the paged
    incremental decode loop is token-exact against re-running the whole
    forward per token."""
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(8,)),
                      n_pages=33, max_new_tokens=10).start()
    prompt = np.asarray([5, 9, 2, 7, 1], np.int32)
    try:
        out = srv.submit(prompt, max_new_tokens=10).result(timeout=60)
    finally:
        assert srv.drain(30)
    np.testing.assert_array_equal(out, oracle_greedy(LOUD, prompt, 10))


# ---------------------------------------------------- census / recompiles --
def test_census_equals_runtime_jit_cache_under_mixed_traffic():
    """ISSUE 10 acceptance: one compiled decode executable serves ANY
    in-flight mix.  A mixed-length, mixed-sampling traffic replay over
    the full bucket grid compiles exactly ``prefill buckets + 1``
    executables — the static census — and not one more."""
    spec = BucketSpec(batch=(1, 2), length=(8, 16))
    srv = make_server(buckets=spec, n_slots=4, n_pages=33,
                      max_new_tokens=4).start()
    census = srv.census()
    assert census == 2 * 2 + 1
    assert srv.jit_cache_count() == census     # warmup compiled the space
    try:
        rng = np.random.RandomState(0)
        reqs = []
        for i in range(12):                    # ragged lengths, mixed modes
            n = int(rng.randint(1, 15))
            reqs.append(srv.submit(
                rng.randint(0, CFG.vocab_size, size=n).astype(np.int32),
                max_new_tokens=int(rng.randint(1, 5)),
                temperature=float(i % 2),      # greedy and sampled mixed
                top_k=int(3 * (i % 2))))
        for r in reqs:
            r.result(timeout=60)
        assert srv.jit_cache_count() == census, \
            "traffic triggered a recompile — the pinned-signature " \
            "contract is broken"
        assert srv.stats["decode_steps"] > 0
    finally:
        assert srv.drain(30)
    assert srv.jit_cache_count() == census


# ------------------------------------------------------------- scheduler --
def test_admit_retire_eos():
    """A sequence retires the step its EOS appears, the token stream
    excludes EOS, and its slot+pages free for queued work."""
    free = oracle_greedy(LOUD, np.asarray([7, 11, 13], np.int32), 6)
    assert free[3] != free[0]                  # diversity sanity
    eos = int(free[3])
    srv = make_server(eos_id=eos, n_pages=33, max_new_tokens=6).start()
    try:
        out = srv.submit(np.asarray([7, 11, 13], np.int32),
                         max_new_tokens=6).result(timeout=60)
        np.testing.assert_array_equal(out, free[:3])
        st = srv.stats
        assert st["completed"] == 1 and st["retired"] == 1
        assert srv.alloc.free_count() == srv.alloc.allocatable
    finally:
        assert srv.drain(30)


def test_queued_sequences_admitted_as_slots_free():
    """More accepted sequences than decode slots: retirement admits the
    queue the same loop, everyone resolves, pages fully reclaimed."""
    srv = make_server(n_slots=2, n_pages=17, max_new_tokens=3).start()
    try:
        reqs = [srv.submit(np.asarray([i + 1, i + 2], np.int32))
                for i in range(6)]
        outs = [r.result(timeout=60) for r in reqs]
        assert all(len(o) == 3 for o in outs)
        assert srv.stats["completed"] == 6
    finally:
        assert srv.drain(30)
    assert srv.alloc.free_count() == srv.alloc.allocatable


def test_pool_exhaustion_preempts_youngest_and_recovers():
    """Two sequences that each fit the pool alone but not together: the
    younger is evicted back to the queue (generate.evict fires, the
    ``preempted`` stat moves) and BOTH still resolve."""
    name = f"GenSrv-preempt-{time.monotonic_ns()}"
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(4,)),
                      n_slots=2, n_pages=8, page_size=4,
                      max_new_tokens=24, name=name).start()
    try:
        with fault.inject("generate.evict", RuntimeError("probe"),
                          after_n=10 ** 9) as h:   # count, never raise
            r1 = srv.submit(np.asarray([1, 2, 3, 4], np.int32),
                            max_new_tokens=24)
            r2 = srv.submit(np.asarray([5, 6, 7, 8], np.int32),
                            max_new_tokens=24)
            o1, o2 = r1.result(timeout=120), r2.result(timeout=120)
        assert len(o1) == 24 and len(o2) == 24
        st = srv.stats
        assert st["preempted"] >= 1
        assert h.calls >= 1                     # evict point actually fired
        assert profiler.counter_value(f"{name}::preempted") >= 1
    finally:
        assert srv.drain(60)
    assert srv.alloc.free_count() == srv.alloc.allocatable


def test_admission_rejections():
    srv = make_server(n_pages=9, max_new_tokens=4).start()
    try:
        with pytest.raises(RejectedError):     # no bucket holds length 9
            srv.submit(np.arange(9, dtype=np.int32))
        with pytest.raises(RejectedError):     # worst case > pool
            srv.submit(np.asarray([1, 2], np.int32), max_new_tokens=31)
        with pytest.raises(ValueError):
            srv.submit(np.asarray([1], np.int32), max_new_tokens=0)
        with pytest.raises(ValueError):
            srv.submit(np.asarray([[1, 2]], np.int32))   # not 1-D
        assert srv.stats["rejected"] == 2      # ValueErrors are not sheds
    finally:
        assert srv.drain(30)
    with pytest.raises(ServerClosedError):
        srv.submit(np.asarray([1], np.int32))


def test_deadline_expiry_mid_generation_frees_pages():
    """A deadline that lands mid-decode resolves the request with an
    explicit mid-generation DeadlineExceededError and reclaims its
    pages; a queued-only expiry reports it never touched the device."""
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(4,)),
                      n_slots=1, n_pages=129, page_size=4,
                      max_new_tokens=500, max_context=512).start()
    orig = srv._run_decode          # pace decode so the deadline lands
    srv._run_decode = lambda: (time.sleep(0.02), orig())[1]
    try:
        req = srv.submit(np.asarray([1, 2], np.int32),
                         max_new_tokens=500, deadline=0.25)
        # a second sequence queued behind the only slot expires unserved
        q = srv.submit(np.asarray([3, 4], np.int32),
                       max_new_tokens=500, deadline=0.05)
        err = req.exception(timeout=120)
        assert isinstance(err, DeadlineExceededError)
        assert "mid-generation" in str(err)
        qerr = q.exception(timeout=120)
        assert isinstance(qerr, DeadlineExceededError)
        assert srv.stats["expired"] == 2
    finally:
        assert srv.drain(30)
    assert srv.alloc.free_count() == srv.alloc.allocatable


# ------------------------------------------------------- sampling modes --
def test_sampling_determinism_fixed_seed():
    """Same seed + same traffic order → identical sampled streams, on
    fresh servers; a different seed diverges (vocab is big enough that
    a 6-token collision is ~impossible)."""
    def run(seed):
        srv = make_server(n_pages=33, seed=seed).start()
        try:
            return srv.submit(np.asarray([3, 1, 4], np.int32),
                              max_new_tokens=6, temperature=1.0,
                              top_k=8).result(timeout=60)
        finally:
            assert srv.drain(30)
    a, b, c = run(7), run(7), run(8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_greedy_and_sampled_share_one_executable():
    """temperature=0 (greedy) and temperature>0 (top-k sampled) rows
    coexist in one decode batch — no per-mode executable exists."""
    srv = make_server(n_slots=2, n_pages=33).start()
    try:
        g = srv.submit(np.asarray([3, 1, 4], np.int32), temperature=0.0)
        s = srv.submit(np.asarray([3, 1, 4], np.int32), temperature=1.5,
                       top_k=4)
        g.result(timeout=60), s.result(timeout=60)
        assert srv.jit_cache_count() == srv.census()
    finally:
        assert srv.drain(30)


# ------------------------------------------------------ failure lifecycle --
def test_decode_fault_salvages_inflight_token_exact():
    """An armed generate.decode fault no longer destroys in-flight work
    (ISSUE 19): the seated sequence is SALVAGED — generated tokens
    intact — requeued, re-prefilled through the same bucket grid, and
    completes with exactly the stream an unfaulted run produces."""
    prompt = np.asarray([1, 2], np.int32)
    oracle = oracle_greedy(LOUD, prompt, 6)
    srv = make_server(n_pages=33,
                      breaker=CircuitBreaker(threshold=3)).start()
    try:
        with fault.inject("generate.decode", RuntimeError("injected"),
                          times=1) as h:
            out = srv.submit(prompt).result(timeout=120)
        assert h.fired == 1
        np.testing.assert_array_equal(np.asarray(out), oracle)
        st = srv.stats
        assert st["completed"] == 1 and st["failed"] == 0
        assert st["salvage_retries"] == 1
        assert st["tokens_salvaged"] >= 1 and st["resumes"] >= 1
        assert srv.alloc.free_count() == srv.alloc.allocatable
    finally:
        assert srv.drain(30)


def test_salvage_budget_exhausted_is_terminal_with_partials():
    """With ``salvage_retries=0`` a step failure retires the sequence
    terminally — and the error carries ``tokens_generated``, the
    partial token list, and a resume snapshot (the fleet-failover
    payload)."""
    prompt = np.asarray([1, 2], np.int32)
    srv = make_server(n_pages=33, salvage_retries=0,
                      breaker=CircuitBreaker(threshold=3)).start()
    try:
        with fault.inject("generate.decode", RuntimeError("injected"),
                          times=1) as h:
            err = srv.submit(prompt).exception(timeout=60)
        assert h.fired == 1
        assert err is not None and "salvage budget" in str(err)
        assert err.tokens_generated == len(err.partial_tokens) >= 1
        snap = err.snapshot
        assert snap.out == err.partial_tokens
        assert list(snap.prompt) == [1, 2]
        st = srv.stats
        assert st["failed"] == 1 and st["completed"] == 0
        assert srv.alloc.free_count() == srv.alloc.allocatable
    finally:
        assert srv.drain(30)


def test_prefill_fault_fails_only_its_group():
    """An armed generate.prefill fault errors the admitted group while a
    sequence already decoding is untouched (host-side fault: the pools
    were never consumed)."""
    srv = make_server(n_slots=2, n_pages=33, max_new_tokens=30,
                      breaker=CircuitBreaker(threshold=3)).start()
    try:
        first = srv.submit(np.asarray([1, 2], np.int32),
                           max_new_tokens=30)
        deadline = time.monotonic() + 20
        while srv.stats["prefills"] < 1 and time.monotonic() < deadline:
            time.sleep(0.002)
        with fault.inject("generate.prefill", RuntimeError("boom"),
                          times=1) as h:
            second = srv.submit(np.asarray([3, 4], np.int32),
                                max_new_tokens=2)
            err = second.exception(timeout=60)
        assert h.fired == 1 and err is not None and "boom" in str(err)
        out = first.result(timeout=120)
        assert len(out) == 30                  # bystander fully served
    finally:
        assert srv.drain(60)


# --------------------------------------------------------- drain / SIGTERM --
def test_drain_resolves_everything_accepted():
    srv = make_server(n_slots=2, n_pages=17, max_new_tokens=4).start()
    reqs = [srv.submit(np.asarray([i + 1], np.int32)) for i in range(5)]
    assert srv.drain(60)
    assert all(r.done() for r in reqs)
    outs = [r.result(timeout=0) for r in reqs]
    assert all(len(o) == 4 for o in outs)      # drain SERVES queued work
    assert not srv.alive()
    assert srv.alloc.free_count() == srv.alloc.allocatable
    with pytest.raises(ServerClosedError):
        srv.submit(np.asarray([1], np.int32))


@chaos
def test_sigterm_serve_forever_drains():
    srv = make_server(n_slots=2, n_pages=17, max_new_tokens=4).start()
    reqs = [srv.submit(np.asarray([i + 1, i + 2], np.int32))
            for i in range(4)]
    threading.Timer(0.05, os.kill,
                    (os.getpid(), signal.SIGTERM)).start()
    assert srv.serve_forever(poll=0.01)
    assert all(r.done() for r in reqs)
    assert all(r.exception(timeout=0) is None for r in reqs)
    assert srv.alloc.free_count() == srv.alloc.allocatable


# ------------------------------------------------------- plumbing details --
def test_generate_fault_points_registered():
    pts = fault.points()
    for p in ("generate.prefill", "generate.decode", "generate.evict",
              "generate.resume", "generate.salvage", "generate.journal"):
        assert p in pts
    with pytest.raises(ValueError):
        fault.inject("generate.decoed", RuntimeError("typo")).__enter__()
    with pytest.raises(ValueError):
        fault.inject("generate.salvge", RuntimeError("typo")).__enter__()


def test_profiler_counters_and_healthz():
    name = f"GenSrv-counters-{time.monotonic_ns()}"
    srv = make_server(name=name, n_pages=33).start()
    try:
        srv.submit(np.asarray([1, 2], np.int32),
                   max_new_tokens=3).result(timeout=60)
        assert profiler.counter_value(f"{name}::tokens_out") >= 3
        assert profiler.counter_value(f"{name}::retired") == 1
        assert profiler.counter_value(f"{name}::page_occupancy") == 0
        h = srv.healthz()
        assert h["alive"] and h["ready"] and not h["draining"]
        assert h["free_pages"] == h["total_pages"]
        assert h["in_flight"] == 0 and h["last_error"] is None
        st = srv.stats
        assert st["admitted"] == st["completed"] + st["failed"] \
            + st["expired"]
    finally:
        assert srv.drain(30)
        assert not srv.healthz()["alive"]


# =============================== ISSUE 12: disaggregated prefill/decode --
slo = pytest.mark.slo


@slo
def test_disaggregated_greedy_parity_census_and_handoff():
    """Disaggregation is a SCHEDULING change, not a math change: the
    pool-free prefill + handoff-scatter path produces token-identical
    greedy continuations to the fused server, the census is grid + 2
    (handoff + decode) and the runtime jit cache equals it under
    traffic, and every page returns to the pool."""
    prompts = [np.asarray(p, np.int32)
               for p in ([3, 1, 4], [1, 5], [9, 2, 6, 5], [3, 5, 8])]
    fused = make_server(name=f"GenFused-{time.monotonic_ns()}",
                        n_pages=33).start()
    try:
        want = [fused.submit(p, max_new_tokens=4).result(60)
                for p in prompts]
    finally:
        assert fused.drain(30)
    dis = make_server(name=f"GenDis-{time.monotonic_ns()}", n_pages=33,
                      prefill_workers=2).start()
    try:
        assert dis.census() == 1 * 1 + 2       # grid + handoff + decode
        assert dis.jit_cache_count() == dis.census()
        got = [dis.submit(p, max_new_tokens=4).result(60)
               for p in prompts]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert dis.stats["handoffs"] >= 1      # the path actually ran
        assert dis.jit_cache_count() == dis.census()   # no recompile
        assert dis.alloc.free_count() == dis.alloc.allocatable
        h = dis.healthz()
        assert h["prefill_workers"] == 2 and h["prefill_inflight"] == 0
    finally:
        assert dis.drain(30)


@slo
def test_disaggregated_drain_under_deep_backlog_resolves_everything():
    """Regression: ``drain()`` sets ``_stop`` while the decode loop is
    still feeding queued work through the prefill worker group.  Workers
    used to exit on ``_stop`` + a momentarily-empty queue, stranding
    every group dispatched after that — the loop then spun forever on a
    pipeline that could never go idle.  A deep backlog drained
    immediately after submission must resolve EVERY accepted sequence
    and terminate."""
    srv = make_server(buckets=BucketSpec(batch=(1, 2), length=(8,)),
                      n_slots=2, n_pages=33, max_new_tokens=4,
                      max_queue=64, prefill_workers=2,
                      name=f"GenBacklog-{time.monotonic_ns()}").start()
    reqs = [srv.submit(np.asarray([1 + (i % 7), 2], np.int32))
            for i in range(24)]
    assert srv.drain(60)                       # used to hang forever
    assert all(r.done() for r in reqs)
    assert all(r.exception(0) is None for r in reqs)   # served, not swept
    assert srv.alloc.free_count() == srv.alloc.allocatable
    st = srv.stats
    assert st["admitted"] == st["completed"] + st["failed"] + st["expired"]


@slo
@chaos
def test_handoff_fault_fails_group_explicitly_spares_bystanders():
    """fleet.handoff fires host-side, BEFORE the scatter touches the
    pools: the staged group fails explicitly, a seated bystander keeps
    decoding on intact pools, and the server serves on."""
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(8,)),
                      n_slots=2, n_pages=33, max_new_tokens=24,
                      prefill_workers=1,
                      name=f"GenHandoffFault-{time.monotonic_ns()}").start()
    try:
        bystander = srv.submit(np.asarray([2, 7], np.int32),
                               max_new_tokens=24)
        t0 = time.time()                       # wait until it is seated
        while srv.stats["handoffs"] < 1 and time.time() - t0 < 30:
            time.sleep(0.005)
        with fault.inject("fleet.handoff", RuntimeError("wire lost")):
            doomed = srv.submit(np.asarray([5], np.int32))
            with pytest.raises(RuntimeError, match="wire lost"):
                doomed.result(30)
        out = bystander.result(60)             # bystander unharmed
        assert len(out) == 24
        # healthy after: a fresh sequence serves end to end
        assert len(srv.submit(np.asarray([4], np.int32),
                              max_new_tokens=3).result(60)) == 3
        assert srv.jit_cache_count() == srv.census()
    finally:
        assert srv.drain(30)
        assert srv.alloc.free_count() == srv.alloc.allocatable


def needs_devices(n):
    """Tier-1 has 8 virtual CPU devices; the on-chip re-run has as many
    as the host has chips."""
    return pytest.mark.skipif(
        jax.device_count() < n,
        reason=f"needs {n} devices, jax sees {jax.device_count()}")


# ======================= ISSUE 14: tensor-parallel sharded decode --
# CFG has 2 heads (tp=2-divisible); the 8-way acceptance needs a head
# per shard — same d_model, 8 x 4 heads
TP8_CFG = CausalLMConfig(vocab_size=48, n_layers=2, n_heads=8,
                         head_dim=2, d_ff=32)
TP8_PARAMS = init_causal_lm(TP8_CFG, seed=5)
TP8_LOUD = {k: v * 8.0 if k in ("embed", "wqkv", "wo", "w1", "w2") else v
            for k, v in TP8_PARAMS.items()}


@needs_devices(2)
def test_tp_sharded_decode_token_exact_parity():
    """ISSUE 14: sharding is a lowering property, not a math change —
    the tp=2 server (head-sharded pools, Megatron weights, f32
    collectives) produces token-identical greedy continuations to the
    single-chip path on the same prompts/seeds, over prompts long
    enough to cross page boundaries."""
    prompts = [np.asarray(p, np.int32)
               for p in ([5, 9, 2, 7, 1], [3, 1], [11, 4, 6], [8])]
    single = make_server(n_pages=33, max_new_tokens=8,
                         name=f"GenTP-s-{time.monotonic_ns()}").start()
    try:
        want = [single.submit(p, max_new_tokens=8).result(60)
                for p in prompts]
    finally:
        assert single.drain(30)
    tp = make_server(n_pages=33, max_new_tokens=8, tp_shards=2,
                     name=f"GenTP-2-{time.monotonic_ns()}").start()
    try:
        h = tp.healthz()
        assert h["tp_shards"] == 2 and h["tp_collectives"] == "f32"
        got = [tp.submit(p, max_new_tokens=8).result(60)
               for p in prompts]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert tp.jit_cache_count() == tp.census()
    finally:
        assert tp.drain(30)
    assert tp.alloc.free_count() == tp.alloc.allocatable


@needs_devices(2)
def test_tp_int8_collectives_bounded_divergence():
    """``tp_collectives="int8"`` trades exactness for wire bytes on
    the decode path ONLY: the first token (prefill — f32 collectives)
    is exact vs the f32-collective server, later tokens may diverge
    but generation stays well-formed (full length, in-vocab, census
    intact, pages reclaimed) and is deterministic under a fixed seed."""
    prompts = [np.asarray(p, np.int32) for p in ([5, 9, 2], [7, 1, 3])]

    def run(coll):
        srv = make_server(n_pages=33, max_new_tokens=6, tp_shards=2,
                          tp_collectives=coll, seed=0,
                          name=f"GenTPq-{coll}-{time.monotonic_ns()}"
                          ).start()
        try:
            return [srv.submit(p, max_new_tokens=6).result(60)
                    for p in prompts]
        finally:
            assert srv.drain(30)
            assert srv.alloc.free_count() == srv.alloc.allocatable

    f32 = run("f32")
    q8a, q8b = run("int8"), run("int8")
    for w, g, g2 in zip(f32, q8a, q8b):
        assert g[0] == w[0]              # prefill-sampled token: exact
        assert len(g) == len(w) == 6
        assert all(0 <= t < CFG.vocab_size for t in g)
        np.testing.assert_array_equal(g, g2)   # deterministic


@needs_devices(8)
def test_tp8_census_matches_runtime_jit_cache_on_real_mesh():
    """The ISSUE 14 acceptance: a tp=8 GenerationServer on the real
    8-device mesh — mixed-length, mixed-sampling traffic replay —
    compiles exactly the static census (prefill grid + decode) at
    warmup and not one more under sharded traffic."""
    spec = BucketSpec(batch=(1, 2), length=(8,))
    srv = GenerationServer(TP8_LOUD, TP8_CFG, buckets=spec, n_slots=4,
                           n_pages=33, page_size=4, max_new_tokens=3,
                           seed=0, tp_shards=8,
                           name=f"GenTP8-{time.monotonic_ns()}")
    srv.start()
    census = srv.census()
    assert census == 2 * 1 + 1
    assert srv.jit_cache_count() == census
    try:
        rng = np.random.RandomState(0)
        reqs = [srv.submit(
            rng.randint(0, TP8_CFG.vocab_size,
                        size=int(rng.randint(1, 8))).astype(np.int32),
            max_new_tokens=int(rng.randint(1, 4)),
            temperature=float(i % 2), top_k=int(3 * (i % 2)))
            for i in range(6)]
        for r in reqs:
            r.result(timeout=120)
        assert srv.jit_cache_count() == census, \
            "sharded traffic triggered a recompile — the pinned " \
            "multi-device executable contract is broken"
        assert srv.stats["decode_steps"] > 0
    finally:
        assert srv.drain(60)
    assert srv.jit_cache_count() == census
    assert srv.alloc.free_count() == srv.alloc.allocatable


@slo
@needs_devices(2)
def test_tp_disaggregated_handoff_sharded():
    """Disaggregation composes with sharding: a tp=2 server with a
    prefill worker group (pool-free sharded prefill → head-sharded
    handoff scatter) is token-identical to the single-chip fused path,
    census = grid + 2, no recompiles, pages reclaimed."""
    prompts = [np.asarray(p, np.int32)
               for p in ([3, 1, 4], [1, 5], [9, 2, 6, 5])]
    fused = make_server(n_pages=33,
                        name=f"GenTPd-s-{time.monotonic_ns()}").start()
    try:
        want = [fused.submit(p, max_new_tokens=4).result(60)
                for p in prompts]
    finally:
        assert fused.drain(30)
    dis = make_server(n_pages=33, tp_shards=2, prefill_workers=1,
                      name=f"GenTPd-2-{time.monotonic_ns()}").start()
    try:
        assert dis.census() == 1 * 1 + 2       # grid + handoff + decode
        assert dis.jit_cache_count() == dis.census()
        got = [dis.submit(p, max_new_tokens=4).result(60)
               for p in prompts]
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert dis.stats["handoffs"] >= 1
        assert dis.jit_cache_count() == dis.census()
    finally:
        assert dis.drain(30)
    assert dis.alloc.free_count() == dis.alloc.allocatable


def test_tp_validation_errors():
    """Unservable shard requests fail LOUDLY at construction: a head
    count the mesh can't divide, an unknown collective format, more
    shards than devices."""
    with pytest.raises(ValueError, match="n_heads"):
        make_server(tp_shards=3)               # 2 heads % 3
    with pytest.raises(ValueError, match="tp_collectives"):
        make_server(tp_shards=2, tp_collectives="bf16")
    cfg16 = CausalLMConfig(vocab_size=48, n_layers=1, n_heads=16,
                           head_dim=2, d_ff=32)
    with pytest.raises(ValueError, match="devices"):
        GenerationServer(init_causal_lm(cfg16, 0), cfg16, tp_shards=16,
                         buckets=BucketSpec(batch=(1,), length=(8,)))
    cfg = CausalLMConfig(vocab_size=48, n_layers=1, n_heads=4,
                         head_dim=4, d_ff=30)   # ff % 4 != 0
    with pytest.raises(ValueError, match="d_ff"):
        GenerationServer(init_causal_lm(cfg, 0), cfg, tp_shards=4,
                         buckets=BucketSpec(batch=(1,), length=(8,)))


@slo
def test_priority_class_jumps_the_queue():
    """Scheduler seating is priority-ordered: with one decode slot and a
    deep bronze queue, a late gold submission seats (and finishes)
    before the queued bronze work."""
    from mxnet_tpu.serving import QoSClass, TenantQoS
    qos = TenantQoS(classes=[QoSClass("gold", priority=10),
                             QoSClass("bronze", priority=0)],
                    default_class="bronze")
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(8,)),
                      n_slots=1, n_pages=17, max_new_tokens=24, qos=qos,
                      name=f"GenPrio-{time.monotonic_ns()}").start()
    order, lock = [], threading.Lock()

    def watch(tag, req):
        req.add_done_callback(
            lambda r: (lock.acquire(), order.append(tag), lock.release()))
        return req

    try:
        bronze = [watch(f"b{i}",
                        srv.submit(np.asarray([i + 1], np.int32),
                                   klass="bronze")) for i in range(4)]
        gold = watch("gold", srv.submit(np.asarray([6], np.int32),
                                        klass="gold"))
        gold.result(120)
        for r in bronze:
            r.result(120)
        # gold seated ahead of every still-queued bronze: at most ONE
        # bronze (the one already in the slot) may finish before it
        assert order.index("gold") <= 1, order
        classes = srv.healthz()["classes"]
        assert classes["gold"]["completed"] == 1
        assert classes["bronze"]["completed"] == 4
    finally:
        assert srv.drain(30)


@slo
def test_generation_tenant_throttle_and_class_queue_cap():
    """GenerationServer admission: an abusive tenant sheds alone
    (its bucket, nobody else's) and a low class's admit_frac caps its
    share of the QUEUE, preserving admission headroom for gold."""
    from mxnet_tpu.serving import (QoSClass, TenantQoS,
                                   TenantThrottledError)
    qos = TenantQoS(classes=[QoSClass("gold", priority=10),
                             QoSClass("bronze", priority=0,
                                      admit_frac=0.5)],
                    default_class="bronze", tenant_rate=1.0,
                    tenant_burst=2)
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(8,)),
                      n_slots=1, n_pages=17, max_new_tokens=24,
                      max_queue=4, qos=qos,
                      name=f"GenQoS-{time.monotonic_ns()}").start()
    try:
        # phase 1: the abusive tenant burns its bucket and sheds ALONE
        ab = [srv.submit(np.asarray([3], np.int32), tenant="abuser",
                         klass="gold") for _ in range(2)]
        with pytest.raises(TenantThrottledError):
            srv.submit(np.asarray([3], np.int32), tenant="abuser",
                       klass="gold")
        srv.submit(np.asarray([5], np.int32), tenant="t0",
                   klass="gold").result(120)   # neighbour untouched
        for r in ab:
            r.result(120)
        # phase 2: one seated + two queued bronze = bronze AT its
        # 0.5 * 4 share of the queue
        reqs = [srv.submit(np.asarray([1], np.int32), tenant="t1")]
        t0 = time.time()                       # wait for it to seat
        while srv.healthz()["queue_depth"] > 0 and time.time() - t0 < 30:
            time.sleep(0.005)
        reqs += [srv.submit(np.asarray([i + 2], np.int32),
                            tenant=f"t{i + 2}") for i in range(2)]
        with pytest.raises(RejectedError, match="cap"):
            srv.submit(np.asarray([9], np.int32), tenant="t9")
        gold = srv.submit(np.asarray([7], np.int32), tenant="g0",
                          klass="gold")       # headroom reserved for gold
        gold.result(120)
        for r in reqs:
            r.result(120)
        snap = srv.healthz()["classes"]
        assert snap["bronze"]["shed"] >= 1
        assert snap["gold"]["throttled"] >= 1
    finally:
        assert srv.drain(60)
    st = srv.stats
    assert st["admitted"] == st["completed"] + st["failed"] + st["expired"]


# ------------------------ ISSUE 16: CoW prefix sharing + speculation --
def _draft_pair(seed=5):
    from mxnet_tpu.gluon.model_zoo.causal_lm import draft_config
    dcfg = draft_config(CFG, n_layers=1)
    return dcfg, init_causal_lm(dcfg, seed=seed)


def test_allocator_double_free_and_unknown_page_raise():
    """A page with no live refcount — freed twice, or an id never
    allocated — raises ``ValueError`` with NOTHING freed: silently
    re-listing it would hand the same page to two sequences."""
    a = PageAllocator(9, 4)
    held = a.alloc(3)
    a.free(held)
    before = a.free_count()
    with pytest.raises(ValueError, match="not live"):
        a.free(held[:1])                       # double free
    assert a.free_count() == before
    keep = a.alloc(2)
    with pytest.raises(ValueError, match="not live"):
        a.free(keep + [keep[0]])               # dup inside ONE call
    assert a.free_count() == before - 2        # nothing freed
    with pytest.raises(ValueError, match="not live"):
        a.free([0])                            # the sink is never live
    with pytest.raises(ValueError, match="not live"):
        a.free([999])                          # never allocated
    a.free(keep)
    assert a.free_count() == a.allocatable


def test_allocator_refcount_share_semantics():
    """share() adds holders to LIVE pages only; free() releases a page
    when its LAST holder lets go; the sharing gauges follow."""
    a = PageAllocator(9, 4)
    pages = a.alloc(2)
    assert [a.refcount(p) for p in pages] == [1, 1]
    a.share(pages)
    a.share(pages[:1])
    assert a.refcount(pages[0]) == 3 and a.refcount(pages[1]) == 2
    assert a.shared_pages() == 2 and a.extra_refs() == 3
    assert a.live_pages() == 2
    assert a.free(pages) == []                 # (3,2) -> (2,1): still held
    assert a.free(pages[:1]) == []             # (2,1) -> (1,1)
    assert sorted(a.free(pages)) == sorted(pages)   # last holders let go
    assert a.free_count() == a.allocatable and a.live_pages() == 0
    with pytest.raises(ValueError, match="not live"):
        a.share(pages[:1])                     # freed pages can't be shared
    assert a.refcount(pages[0]) == 0


def test_prefix_admission_plan_math():
    from mxnet_tpu.serving import prefix_admission_plan
    plan = prefix_admission_plan(129, 16, 192, 64, 176)
    assert plan["pages_per_seq"] == 16 and plan["shared_pages"] == 11
    assert plan["charged_pages"] == 5
    assert plan["admissible_unshared"] == 8
    assert plan["admissible_shared"] == 23
    assert plan["multiplier"] == pytest.approx(23 / 8)
    # no sharing at all → both sides agree
    base = prefix_admission_plan(129, 16, 192, 64, 0)
    assert base["admissible_shared"] == base["admissible_unshared"] == 8
    # shared prefix can never exceed the prompt's own full blocks
    cap = prefix_admission_plan(129, 16, 32, 64, 10_000)
    assert cap["shared_pages"] == 2


def test_prefix_sharing_cow_exactness_and_drain_invariants():
    """The tentpole acceptance: a sharer mapped onto a donor's resident
    prefix pages (one of them a SUPERSET partial-block match, so the
    first divergent write takes a real CoW fault) decodes token-
    identically to the unshared oracle, and after drain every refcount
    returned to zero — free list == pool."""
    donor = ((np.arange(8, dtype=np.int32) * 5) + 1) % CFG.vocab_size
    sharer = donor[:6].copy()                  # 1 full block + superset tail
    srv = make_server(buckets=BucketSpec(batch=(1, 2), length=(8,)),
                      n_slots=4, n_pages=33).start()
    try:
        r1 = srv.submit(donor)                 # one prefill group of two:
        r2 = srv.submit(sharer)                # sharing is map-time, not
        o1 = r1.result(timeout=60)             # seat-time
        o2 = r2.result(timeout=60)
        np.testing.assert_array_equal(o1, oracle_greedy(LOUD, donor, 6))
        np.testing.assert_array_equal(o2, oracle_greedy(LOUD, sharer, 6))
        st = srv.stats
        assert st["pages_shared_mapped"] >= 2  # full + superset block
        assert st["cow_faults"] >= 1           # divergence at token 6
        g = srv.telemetry()["gauges"]
        assert g["pages_cow_faults"] >= 1
        assert "bytes_saved_by_sharing" in g and "pages_shared" in g
    finally:
        assert srv.drain(30)
    assert srv.alloc.free_count() == srv.alloc.allocatable
    assert srv.alloc.live_pages() == 0 and srv.alloc.shared_pages() == 0


def test_sharer_retire_never_frees_referenced_pages():
    """A donor retiring EARLY only drops ITS hold: the sharer keeps
    decoding through the shared pages, and a later sequence reusing the
    freed pool cannot clobber them (exactness is the proof — a
    wrongly-freed page would be rewritten under the sharer)."""
    donor = ((np.arange(8, dtype=np.int32) * 7) + 2) % CFG.vocab_size
    clobber = ((np.arange(8, dtype=np.int32) * 11) + 5) % CFG.vocab_size
    srv = make_server(buckets=BucketSpec(batch=(1, 2), length=(8,)),
                      n_slots=4, n_pages=17).start()
    try:
        r1 = srv.submit(donor, max_new_tokens=2)   # retires first
        r2 = srv.submit(donor, max_new_tokens=6)   # full-prompt sharer
        o1 = r1.result(timeout=60)
        r3 = srv.submit(clobber, max_new_tokens=4)  # churns the free list
        o2 = r2.result(timeout=60)
        o3 = r3.result(timeout=60)
        np.testing.assert_array_equal(o1, oracle_greedy(LOUD, donor, 2))
        np.testing.assert_array_equal(o2, oracle_greedy(LOUD, donor, 6))
        np.testing.assert_array_equal(o3, oracle_greedy(LOUD, clobber, 4))
        assert srv.stats["pages_shared_mapped"] >= 2
    finally:
        assert srv.drain(30)
    assert srv.alloc.free_count() == srv.alloc.allocatable


def test_sharer_preemption_with_shared_pages_recovers_exactly():
    """Pool-pressure preemption of a SHARER must not free pages the
    donor still references: two sequences share one prompt page, each
    fits the pool alone but not together, the younger is evicted and
    restarted — both streams still match the oracle and the drain
    invariant holds."""
    prompt = np.asarray([1, 2, 3, 4], np.int32)     # exactly one block
    srv = make_server(buckets=BucketSpec(batch=(1,), length=(4,)),
                      n_slots=2, n_pages=8, page_size=4,
                      max_new_tokens=24).start()
    try:
        r1 = srv.submit(prompt, max_new_tokens=24)
        r2 = srv.submit(prompt, max_new_tokens=24)
        o1 = r1.result(timeout=120)
        o2 = r2.result(timeout=120)
        want = oracle_greedy(LOUD, prompt, 24)
        np.testing.assert_array_equal(o1, want)
        np.testing.assert_array_equal(o2, want)
        st = srv.stats
        assert st["preempted"] >= 1
        assert st["pages_shared_mapped"] >= 1
    finally:
        assert srv.drain(60)
    assert srv.alloc.free_count() == srv.alloc.allocatable
    assert srv.alloc.live_pages() == 0


def test_speculative_greedy_token_identical_to_oracle():
    """Distribution exactness, greedy arm: a speculative server (draft
    proposals + ONE pinned verify step) emits byte-identical streams to
    the non-speculative oracle, whatever the accept rate."""
    dcfg, dparams = _draft_pair()
    srv = make_server(buckets=BucketSpec(batch=(1, 2), length=(8,)),
                      n_slots=4, n_pages=33, draft=dparams,
                      draft_config=dcfg, spec_k=2).start()
    try:
        prompts = [((np.arange(n, dtype=np.int32) * m) + 1)
                   % CFG.vocab_size
                   for n, m in ((3, 5), (6, 7), (8, 11), (5, 2))]
        reqs = [srv.submit(p) for p in prompts]
        for p, r in zip(prompts, reqs):
            np.testing.assert_array_equal(r.result(timeout=120),
                                          oracle_greedy(LOUD, p, 6))
        st = srv.stats
        assert st["verify_steps"] > 0 and st["spec_proposed"] > 0
        # greedy draft-vs-target agreement is high on a shared family
        # but never total — both branches of accept/reject ran
        assert 0 < st["spec_accepted"] <= st["spec_proposed"]
    finally:
        assert srv.drain(60)
    assert srv.alloc.free_count() == srv.alloc.allocatable


def test_speculative_sampling_statistical_identity():
    """Distribution exactness, sampling arm (Leviathan/Chen rejection
    scheme): the FIRST emitted token's marginal under speculative
    verify equals the target model's tempered top-k distribution —
    regardless of the draft's proposal quality.  Empirical check over
    many fixed-seed draws of the verify executable against the
    analytically computed target distribution."""
    from mxnet_tpu.serving.generate import (build_prefill_step,
                                            build_verify_step)
    dcfg, dparams = _draft_pair()
    page, n_prompt, temp, topk = 4, 6, 1.0, 8
    prompt = ((np.arange(n_prompt, dtype=np.int32) * 5) + 2) \
        % CFG.vocab_size
    pool = jnp.zeros((CFG.n_layers, 9, page, CFG.n_heads, CFG.head_dim),
                     jnp.float32)
    tables = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :n_prompt] = prompt
    pre = jax.jit(build_prefill_step(CFG, page))
    t0, kp, vp = pre(LOUD, pool, pool, jnp.asarray(toks),
                     jnp.asarray([n_prompt], np.int32),
                     jnp.asarray([True]), tables,
                     jnp.asarray([0], jnp.uint32), jnp.asarray([0.0]),
                     jnp.asarray([0], np.int32))    # greedy pending token
    t0 = int(t0[0])
    # analytic target marginal for the token AFTER the pending one
    full = np.zeros((1, 16), np.int32)
    full[0, :n_prompt] = prompt
    full[0, n_prompt] = t0
    logits, _, _ = prefill_forward(LOUD, CFG, jnp.asarray(full),
                                   jnp.asarray([n_prompt + 1], np.int32))
    z = np.asarray(logits)[0] / temp
    kth = np.sort(z)[-topk]
    z = np.where(z >= kth, z, -np.inf)
    p_ref = np.exp(z - z.max())
    p_ref /= p_ref.sum()
    vf = jax.jit(build_verify_step(CFG, dcfg, page, spec_k=2, window=8))
    window = np.zeros((1, 8), np.int32)
    window[0, -(n_prompt + 1):] = list(prompt) + [t0]
    args = (jnp.asarray([t0], jnp.int32), jnp.asarray(window),
            jnp.asarray([n_prompt + 1], np.int32),
            jnp.asarray([n_prompt], np.int32), jnp.asarray([True]),
            tables, jnp.asarray([0], jnp.int32),
            jnp.asarray([0], jnp.int32))
    counts = np.zeros(CFG.vocab_size)
    n_draws = 600
    for i in range(n_draws):
        # a fresh per-sequence seed per draw: position-keyed sampling
        # (ISSUE 19) derives every draw from (seed, position), so
        # varying the seed IS the fresh-randomness lever
        emitted, _, _, _ = vf(LOUD, dparams, kp, vp, *args,
                              jnp.asarray([i], jnp.uint32),
                              jnp.asarray([temp], jnp.float32),
                              jnp.asarray([topk], jnp.int32))
        counts[int(emitted[0, 0])] += 1
    emp = counts / n_draws
    assert emp[np.asarray(p_ref) == 0].sum() == 0   # never off-support
    tv = 0.5 * np.abs(emp - p_ref).sum()
    assert tv < 0.12, (
        f"speculative first-token marginal diverges from the target "
        f"distribution: TV={tv:.3f}\n emp={np.nonzero(counts)[0]}")
    # determinism: the same seed replays the same acceptance decisions
    e1 = vf(LOUD, dparams, kp, vp, *args, jnp.asarray([42], jnp.uint32),
            jnp.asarray([temp], jnp.float32),
            jnp.asarray([topk], jnp.int32))[0]
    e2 = vf(LOUD, dparams, kp, vp, *args, jnp.asarray([42], jnp.uint32),
            jnp.asarray([temp], jnp.float32),
            jnp.asarray([topk], jnp.int32))[0]
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_census_with_speculative_and_shared_traffic():
    """ISSUE 16 acceptance: the speculative census is the prefill grid
    + decode + EXACTLY ONE verify executable, and a mixed replay —
    shared-prefix pairs, unshared ragged prompts, greedy and sampled
    rows — never compiles one more."""
    dcfg, dparams = _draft_pair()
    spec = BucketSpec(batch=(1, 2), length=(8, 16))
    srv = make_server(buckets=spec, n_slots=4, n_pages=65,
                      draft=dparams, draft_config=dcfg, spec_k=2,
                      max_new_tokens=4).start()
    try:
        census = srv.census()
        assert census == 2 * 2 + 1 + 1         # grid + decode + verify
        assert srv.jit_cache_count() == census
        rng = np.random.RandomState(0)
        system = rng.randint(0, CFG.vocab_size, size=8).astype(np.int32)
        reqs = []
        for i in range(10):
            if i % 2:                          # shared-prefix traffic
                tail = rng.randint(0, CFG.vocab_size,
                                   size=1 + (i % 3)).astype(np.int32)
                p = np.concatenate([system, tail])
            else:                              # unshared ragged
                p = rng.randint(0, CFG.vocab_size,
                                size=int(rng.randint(1, 15))) \
                    .astype(np.int32)
            reqs.append(srv.submit(p, temperature=float(i % 2),
                                   top_k=int(4 * (i % 2))))
        for r in reqs:
            r.result(timeout=120)
        assert srv.jit_cache_count() == census, \
            "speculative/shared traffic triggered a recompile"
        st = srv.stats
        assert st["pages_shared_mapped"] >= 2
        assert st["verify_steps"] > 0
    finally:
        assert srv.drain(60)
    assert srv.jit_cache_count() == census
    assert srv.alloc.free_count() == srv.alloc.allocatable


def test_speculative_validation_errors():
    dcfg, dparams = _draft_pair()
    with pytest.raises(ValueError, match="draft_config"):
        make_server(draft=dparams)
    bad = CausalLMConfig(vocab_size=CFG.vocab_size + 1, n_layers=1,
                         n_heads=2, head_dim=8, d_ff=32)
    with pytest.raises(ValueError, match="vocab"):
        make_server(draft=dparams, draft_config=bad)
    with pytest.raises(ValueError, match="spec_k"):
        make_server(draft=dparams, draft_config=dcfg, spec_k=0)
    with pytest.raises(ValueError, match="spec_window"):
        make_server(draft=dparams, draft_config=dcfg, spec_window=0)


# ------------------------------------------------ ISSUE 19: preempt / resume --
def _storm_server(**kw):
    """A pool sized so two worst-case sequences CANNOT coexist: the
    junior one is repeatedly preempted mid-generation and must resume
    through the bucket grid — the ISSUE 19 salvage treadmill."""
    kw.setdefault("n_pages", 5)              # 4 allocatable
    kw.setdefault("page_size", 4)
    kw.setdefault("max_new_tokens", 10)
    return make_server(**kw)


def test_preempt_storm_token_exact_greedy():
    """Preemption no longer discards generated tokens: under a starved
    pool every sequence still completes with EXACTLY the uninterrupted
    greedy stream, through the existing executables only."""
    prompts = [np.asarray([1, 2], np.int32),
               np.asarray([7, 3, 5], np.int32)]
    oracles = [oracle_greedy(LOUD, p, 10) for p in prompts]
    srv = _storm_server().start()
    try:
        reqs = [srv.submit(p) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        for o, e in zip(outs, oracles):
            np.testing.assert_array_equal(np.asarray(o), e)
        st = srv.stats
        assert st["completed"] == 2 and st["failed"] == 0
        assert st["preempted"] >= 1 and st["tokens_salvaged"] >= 1
        assert st["resumes"] >= 1
        assert st["salvage_retries"] == 0     # preemption is unbudgeted
        assert srv.jit_cache_count() == srv.census()
        assert srv.alloc.free_count() == srv.alloc.allocatable
    finally:
        assert srv.drain(60)


def test_preempt_storm_token_exact_seeded_sampling():
    """Same treadmill, stochastic decoding: position-keyed sampling
    makes the resumed draws coincide with the uninterrupted run's, so
    a fixed ``submit(seed=)`` yields identical streams on a calm pool
    and on a storming one."""
    prompts = [np.asarray([1, 2], np.int32),
               np.asarray([7, 3, 5], np.int32)]
    seeds = [101, 202]
    ref = make_server(n_pages=33, max_new_tokens=10).start()
    try:
        expected = [np.asarray(
            ref.submit(p, temperature=0.8, top_k=4, seed=s)
               .result(timeout=120)) for p, s in zip(prompts, seeds)]
        assert ref.stats["preempted"] == 0    # the calm oracle run
    finally:
        assert ref.drain(60)
    srv = _storm_server().start()
    try:
        reqs = [srv.submit(p, temperature=0.8, top_k=4, seed=s)
                for p, s in zip(prompts, seeds)]
        outs = [np.asarray(r.result(timeout=180)) for r in reqs]
        st = srv.stats
        assert st["preempted"] >= 1 and st["resumes"] >= 1
        for o, e in zip(outs, expected):
            np.testing.assert_array_equal(o, e)
        assert srv.jit_cache_count() == srv.census()
    finally:
        assert srv.drain(60)


def test_disaggregated_salvage_token_exact_greedy():
    """The resume prefill also rides the DISAGGREGATED path: a decode
    fault on a prefill-worker server salvages, re-prefills via the
    prefill-KV executables, and completes greedy-token-exact."""
    prompt = np.asarray([4, 1, 3], np.int32)
    oracle = oracle_greedy(LOUD, prompt, 6)
    srv = make_server(n_pages=33, prefill_workers=1,
                      breaker=CircuitBreaker(threshold=4)).start()
    try:
        with fault.inject("generate.decode", RuntimeError("injected"),
                          times=1) as h:
            out = srv.submit(prompt).result(timeout=120)
        assert h.fired == 1
        np.testing.assert_array_equal(np.asarray(out), oracle)
        st = srv.stats
        assert st["completed"] == 1 and st["resumes"] >= 1
        assert srv.jit_cache_count() == srv.census()
        assert srv.alloc.free_count() == srv.alloc.allocatable
    finally:
        assert srv.drain(30)


def test_disaggregated_preempt_storm_seeded_sampling_token_exact():
    """Disaggregated + starved pool + fixed-seed sampling: the resumed
    prefill-KV handoffs reproduce the calm run's stream exactly."""
    prompts = [np.asarray([6, 2], np.int32),
               np.asarray([3, 8, 1], np.int32)]
    seeds = [11, 23]
    ref = make_server(n_pages=33, max_new_tokens=10,
                      prefill_workers=1).start()
    try:
        expected = [np.asarray(
            ref.submit(p, temperature=0.7, top_k=6, seed=s)
               .result(timeout=120)) for p, s in zip(prompts, seeds)]
    finally:
        assert ref.drain(60)
    srv = _storm_server(prefill_workers=1).start()
    try:
        reqs = [srv.submit(p, temperature=0.7, top_k=6, seed=s)
                for p, s in zip(prompts, seeds)]
        outs = [np.asarray(r.result(timeout=180)) for r in reqs]
        assert srv.stats["preempted"] >= 1 and srv.stats["resumes"] >= 1
        for o, e in zip(outs, expected):
            np.testing.assert_array_equal(o, e)
        assert srv.jit_cache_count() == srv.census()
    finally:
        assert srv.drain(60)


def test_breaker_fastfail_salvages_seated_unbudgeted():
    """A breaker trip mid-generation fast-fails the STEP, not the
    sequences: seated work is salvaged without spending the salvage
    budget, waits out the cooldown, resumes, completes token-exact."""
    class _Gate:
        """Self-arming OPEN window: defer to the real breaker until the
        server has emitted ``arm_at`` tokens, then deny the next
        ``deny`` dispatch gates (a window the decode thread cannot
        immediately close again), then defer again.  Installing the
        gate BEFORE submit makes the trip deterministic — no poll race
        against a decode thread that can finish the whole sequence in
        a few milliseconds."""

        def __init__(self, inner, srv, arm_at, deny):
            self._inner, self._srv = inner, srv
            self._arm_at, self.deny = arm_at, deny

        def allow(self):
            if self.deny > 0 and self._srv.stats["tokens_out"] >= self._arm_at:
                self.deny -= 1
                return False
            return self._inner.allow()

        def __getattr__(self, name):
            return getattr(self._inner, name)

    prompt = np.asarray([3, 1, 2], np.int32)
    oracle = oracle_greedy(LOUD, prompt, 10)
    srv = make_server(n_pages=33, max_new_tokens=10,
                      breaker=CircuitBreaker(threshold=3)).start()
    try:
        srv.breaker = _Gate(srv.breaker, srv, arm_at=2, deny=2)
        req = srv.submit(prompt)
        out = req.result(timeout=120)
        np.testing.assert_array_equal(np.asarray(out), oracle)
        st = srv.stats
        assert st["completed"] == 1 and st["failed"] == 0
        assert st["resumes"] >= 1 and st["tokens_salvaged"] >= 1
        assert st["salvage_retries"] == 0    # fast-fail is unbudgeted
    finally:
        assert srv.drain(30)


def test_salvage_storm_allocator_and_prefix_index_invariants():
    """Shared prefixes + starved pool + injected step failures: after
    the storm every page is back on the free list and the host prefix
    index advertises nothing — no leaked refcount, no stale entry."""
    base = [5, 9, 2, 6]
    prompts = [np.asarray(base + [i], np.int32) for i in range(4)]
    srv = _storm_server(salvage_retries=8,
                        breaker=CircuitBreaker(threshold=6)).start()
    try:
        with fault.inject("generate.decode", RuntimeError("injected"),
                          times=2) as h:
            reqs = [srv.submit(p) for p in prompts]
            outs = [r.result(timeout=240) for r in reqs]
        assert h.fired == 2
        st = srv.stats
        assert st["completed"] == 4 and st["failed"] == 0
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(
                np.asarray(o), oracle_greedy(LOUD, p, 10))
        assert srv.alloc.free_count() == srv.alloc.allocatable
        assert srv._indexed_by_page == {}
        assert srv._children == {}
        assert srv.jit_cache_count() == srv.census()
    finally:
        assert srv.drain(60)


def test_journal_restore_completes_token_exact(tmp_path):
    """The crash-consistency tentpole leg: a server whose journal goes
    dark mid-flight (the kill -9 point — admits recorded, retires
    never) is survivable.  A FRESH server imports the journal and
    completes every in-flight sequence with exactly the stream the
    dead server would have produced — greedy and seeded sampling."""
    jpath = str(tmp_path / "decode.jsonl")
    p_greedy = np.asarray([1, 2, 6], np.int32)
    p_sampled = np.asarray([8, 4], np.int32)
    a = make_server(n_pages=33, max_new_tokens=8, journal=jpath,
                    journal_every=1).start()
    try:
        r1 = a.submit(p_greedy)
        r2 = a.submit(p_sampled, temperature=0.9, top_k=6, seed=77)
        a._journal = None     # kill -9: nothing after this line lands
        exp1 = np.asarray(r1.result(timeout=120))
        exp2 = np.asarray(r2.result(timeout=120))
    finally:
        assert a.drain(30)
    np.testing.assert_array_equal(exp1, oracle_greedy(LOUD, p_greedy, 8))

    b = make_server(n_pages=33, max_new_tokens=8).start()
    try:
        restored = b.restore_journal(jpath)
        assert len(restored) == 2
        assert b.stats["journal_restores"] == 2
        got = sorted(tuple(int(t) for t in r.result(timeout=120))
                     for r in restored.values())
        want = sorted(tuple(int(t) for t in e) for e in (exp1, exp2))
        assert got == want
        assert b.stats["completed"] == 2 and b.stats["failed"] == 0
        assert b.alloc.free_count() == b.alloc.allocatable
    finally:
        assert b.drain(30)


def test_drain_handoff_exports_and_successor_resumes(tmp_path):
    """``drain(handoff=True)`` (rolling update): unfinished sequences
    EXPORT instead of finishing — snapshots in ``.exported`` +
    ``gen_handoff`` journal records, requests resolved with a
    ``ServerClosedError`` carrying the partial tokens — and a successor
    restores them token-exact."""
    jpath = str(tmp_path / "decode.jsonl")
    prompts = [np.asarray([2, 7], np.int32),
               np.asarray([9, 1, 4], np.int32),
               np.asarray([5, 5, 8], np.int32),
               np.asarray([1, 6], np.int32)]
    # long generations + more work than slots: the immediate handoff
    # drain below is guaranteed to catch unfinished sequences
    a = make_server(n_pages=65, max_new_tokens=48, journal=jpath,
                    journal_every=1).start()
    reqs = [a.submit(p) for p in prompts]
    limit = time.monotonic() + 60
    while a.stats["tokens_out"] < 1 and time.monotonic() < limit:
        time.sleep(0.001)
    assert a.drain(30, handoff=True)
    errs = [r.exception(timeout=5) for r in reqs]
    exported = [e for e in errs if e is not None]
    assert len(exported) >= 1                  # caught mid-flight
    for e in exported:
        assert isinstance(e, ServerClosedError)
        assert hasattr(e, "snapshot")
        assert e.tokens_generated == len(e.partial_tokens)
    assert a.stats["handoff_exports"] == len(exported)
    assert len(a.exported) == len(exported)

    b = make_server(n_pages=65, max_new_tokens=48).start()
    try:
        restored = b.restore_journal(jpath)
        assert len(restored) == len(exported)
        assert b.stats["journal_restores"] == len(exported)
        got = sorted(tuple(int(t) for t in r.result(timeout=180))
                     for r in restored.values())
        want = sorted(tuple(int(t) for t in
                            oracle_greedy(LOUD, e.snapshot.prompt, 48,
                                          pad_to=64))
                      for e in exported)
        assert got == want
    finally:
        assert b.drain(60)


def test_fleet_failover_redispatches_with_salvaged_tokens():
    """The fleet leg: a replica that retires a sequence terminally
    (salvage budget exhausted) hands the fleet an error CARRYING the
    resume snapshot; the router re-dispatches to the next replica via
    ``submit_resume`` and the client sees the uninterrupted stream."""
    from mxnet_tpu.serving.fleet import ServingFleet
    prompt = np.asarray([3, 1, 2], np.int32)
    oracle = oracle_greedy(LOUD, prompt, 6)
    fleet = ServingFleet([lambda x: x, lambda x: x], buckets=(1,),
                         sample=None, name=f"GenFleet-{time.monotonic_ns()}")
    fleet.start()
    gens, olds = [], []
    try:
        for rep in fleet.replicas:
            g = make_server(n_pages=33, salvage_retries=0,
                            breaker=CircuitBreaker(threshold=4)).start()
            gens.append(g)
            olds.append(rep.server)
            rep.server = g
        for s in olds:
            s.drain(10)
        with fault.inject("generate.decode", RuntimeError("injected"),
                          times=1) as h:
            out = fleet.submit(prompt, deadline=120).result(timeout=120)
        assert h.fired == 1
        np.testing.assert_array_equal(np.asarray(out), oracle)
        assert fleet._stats["resumed"] >= 1
        assert fleet._stats["redispatched"] >= 1
        assert sum(g.stats["failed"] for g in gens) == 1
        assert sum(g.stats["completed"] for g in gens) == 1
    finally:
        fleet.drain(timeout=30)
