#!/usr/bin/env python
"""Chaos smoke: kill-and-resume (train), inject-and-drain (serve),
replica-kill + rolling-update (fleet), the incremental-analyzer
contract (lint), and the budget-audit contract (cost).

``--mode train`` (default) runs a small training loop with periodic
checkpoints, injects a crash mid-run via ``fault.inject``, rediscovers
the newest snapshot with ``resume_latest``, and checks the resumed loss
trajectory matches an uninterrupted run bit-exactly — the acceptance
contract of ISSUE 2.

``--mode serve`` starts an ``mx.serving.InferenceServer``, drives it
from client threads while injecting a ``serving.step`` failure burst,
then lands a SIGTERM mid-flight: the drain must complete with every
ACCEPTED request resolved (result or explicit error — zero silently
dropped) and the breaker must have tripped and fast-failed — the
acceptance contract of ISSUE 4::

    python tools/chaos_check.py [--mode train|serve|lint] [--steps 8] ...

``--mode fleet`` runs the ISSUE 7 acceptance end to end: a 3-replica
``mx.serving.ServingFleet`` under continuous client traffic has one
replica hard-killed mid-flight, two training snapshots (written by a
real ``TrainStep`` + ``CheckpointManager``) streamed through a rolling
weight update, and finally a SIGTERM drain.  The contract: **zero
dropped accepted requests** end to end (every fleet-accepted request
resolves with a result) and **zero recompiles** (the runtime jit-cache
count equals the static bucket census before and after both swaps).
A second leg (ISSUE 8) then boots an **int8 fleet** (per-channel PTQ
weights via ``amp.Int8Quantizer``, dequant folded into the compiled
apply) and streams a fresh **f32** training snapshot through a rolling
update under traffic — re-quantized on ingest by the fleet's
quantizer, 0 drops, census unchanged.

``--mode llm`` runs the ISSUE 10 acceptance end to end — against a
**tensor-parallel sharded gang** since ISSUE 14: a
``mx.serving.GenerationServer(tp_shards=2, tp_collectives="int8")``
(head-sharded paged KV pools, Megatron-sharded weights, quantized
decode collectives, one pinned multi-device decode executable) streams
generations from client threads while a ``generate.decode`` failure
burst fires, then lands a SIGTERM mid-decode.  The contract: **zero
dropped accepted sequences** (every accepted ``Request`` resolves to
tokens or an explicit error), **zero recompiles** (runtime jit-cache
count == the prefill-grid + 1 census before and after the chaos —
sharding must not add an executable), and **pages fully reclaimed**
after the drain (free list == allocatable pool size).

``--mode lint`` runs the full mxlint analyzer twice against a fresh
cache directory and asserts the second (fully cached) run is >= 5x
faster AND byte-identical in findings — the incremental-mode contract
of ISSUE 5 (a cache that changes answers is worse than no cache).

``--mode cost`` runs the full costguard budget audit (every committed
golden in tests/goldens/budgets/) twice against a fresh report cache:
the cold run compiles every entry point, the warm run must hit the
HLO-hash report cache (lowering still runs — that is what keys the
cache), come back byte-identical in verdicts, pass the budget check
both times, and land inside the wall-clock budgets — the ISSUE 6
analogue of the lint contract.

``--mode hlo`` runs the full hloguard structural audit (every surface
with a golden in tests/goldens/hloguard/) twice against a fresh facts
cache, with every lowering prebuilt OUTSIDE the timed window: the cold
run parses/extracts facts from ~2 MB of StableHLO text, the warm run
must hit the HLO-hash facts cache, come back byte-identical in
verdicts (findings, suppressions and censuses included), be >= 5x
faster, and pass the structural gate both times — the ISSUE 18
analogue of the lint and cost contracts.

``--mode elastic`` runs the ISSUE 9 acceptance end to end: an
``elastic.Supervisor`` drives a real 2-worker CPU training gang
(``tests/elastic_worker.py``) to a target step while the harness
SIGKILLs one worker mid-epoch, SIGSTOPs the other to force a watchdog
trip, and finally (fresh gang) SIGTERMs the supervisor itself.  The
contract: the job reaches the target step, restarts stay within the
progress-aware budget, every restarted attempt resumes from a strictly
increasing committed step (never step 0), the supervisor SIGTERM ends
with every worker exiting ``EXIT_PREEMPTED`` after its snapshot, the
event log parses as JSONL, and zero worker processes leak.

``--mode slo`` runs the ISSUE 12 acceptance end to end: a mixed-tenant
traffic storm (two priority classes with per-tenant token buckets, one
abusive tenant) against a grouped ``ServingFleet`` while — all at once —
one replica is hard-killed, a ``FleetAutoscaler`` runs a full scale-up/
scale-down cycle, and a rolling weight update streams through; then a
disaggregated ``GenerationServer`` (prefill worker group + handoff)
serves a long-prefill + decode mix under the same two classes.  The
contract: **0 dropped accepted requests** on both legs, **high-priority
p99 below low-priority p99**, **tenant isolation** (the abusive tenant
is throttled, its neighbours' requests all resolve), and the runtime
jit cache equals the static census before and after.

``--mode obs`` runs the ISSUE 13 acceptance: with request tracing armed
(``telemetry.enable``, JSONL sink + in-memory collection), a 3-replica
``ServingFleet`` storm absorbs a ``serving.step`` fault burst and a
replica hard-kill, then a ``GenerationServer`` streams sequences
through a ``generate.decode`` burst.  The contract: **0 dropped
accepted requests** on both legs, **every accepted request yields a
complete, correctly-parented span tree** (``telemetry.audit_spans`` —
children contained, durations attributed to within tolerance), fault
firings land as span events, the JSONL export reconstructs the same
clean trees, and the tracing-off path costs **< 5%** of request
latency (per-guard cost × a generous guards-per-request budget vs the
measured untraced per-request latency).  The ISSUE 15 flight leg kills
a traced generation worker mid-step with an unbounded decode fault
storm: the breaker trip must leave a complete flight-recorder bundle
(audit-clean span trees, the fatal ``generate.decode`` firing on
record, a metrics snapshot, compile events == the serving census, and
``recompiles_unexpected == 0``) while every accepted sequence still
resolves explicitly.

``--mode ckpt`` runs the ISSUE 17 acceptance: a subprocess snapshot
storm is SIGKILLed mid-write repeatedly (every committed name must
still pass ``verify_checkpoint`` — atomic commit + fsync means a kill
can truncate only the invisible ``.tmp``), a fault-armed
``BitFlipInjection`` commits a container-consistent but
digest-poisoned snapshot (``verify_checkpoint`` /
``load_snapshot_params`` / ``resume_latest`` must all treat it as
damage), and a live ``WeightUpdater`` streams snapshots under
``keep_last=1`` retention pruning while one mid-stream snapshot is
corrupt.  The contract: **resume always lands on an intact verified
snapshot**, **0 silently-loaded corrupt bytes** (trained on or
served), and **0 dropped rolling updates** — a pruned path is stale
(re-poll), never a skipped snapshot.

``--list-modes`` prints the mode registry and exits.

Exit code 0 on success, 1 on any mismatch.  Forces ``JAX_PLATFORMS=cpu``
(and an 8-device virtual mesh) so it runs anywhere, TPU or not (lint
mode never imports jax at all — mxlint is pure ast).
"""
import argparse
import os
import sys
import tempfile
import time

# must precede any jax import — same bring-up as tests/conftest.py
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def serve_mode(args):
    """Inject-and-drain smoke on the serving runtime (ISSUE 4)."""
    import signal
    import threading

    import jax
    from mxnet_tpu import fault, serving

    rng = np.random.RandomState(0)
    w = rng.randn(8, 4).astype(np.float32)

    @jax.jit
    def mlp(x):
        return x @ w

    def apply(x):
        time.sleep(0.01)           # keep work in flight when SIGTERM lands
        return np.asarray(mlp(x))

    srv = serving.InferenceServer(
        apply, buckets=(1, 2, 4), max_delay=0.002, max_queue=64,
        sample=np.zeros((8,), np.float32),
        breaker=serving.CircuitBreaker(threshold=3, base_delay=0.02,
                                       max_delay=0.1))
    srv.start()
    print(f"[chaos_check] serve: warmed {len(srv.distinct_shapes)} "
          f"bucket executables, ready={srv.ready()}")

    accepted, sheds = [], [0]
    count_lock = threading.Lock()
    stop_submitting = threading.Event()

    def client(k):
        r = np.random.RandomState(k).randn(8).astype(np.float32)
        for i in range(args.requests):
            if stop_submitting.is_set():
                return
            try:
                req = srv.submit(r)
                with count_lock:
                    accepted.append(req)
            except serving.RejectedError:
                with count_lock:
                    sheds[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(4)]
    with fault.inject("serving.step", RuntimeError("injected step fault"),
                      after_n=5, times=4) as h:
        for t in threads:
            t.start()
        # SIGTERM lands while clients are still submitting and batches are
        # in flight — serve_forever must drain, not drop
        threading.Timer(0.25, os.kill, (os.getpid(), signal.SIGTERM)).start()
        drained = srv.serve_forever(poll=0.01)
    stop_submitting.set()
    for t in threads:
        t.join()

    resolved = sum(1 for r in accepted if r.done())
    oks, errs = 0, 0
    for r in accepted:
        if not r.done():
            continue                 # counted as dropped below — the very
            #                          failure this smoke exists to catch
        if r.exception(timeout=0) is None:
            oks += 1
        else:
            errs += 1
    st = srv.stats
    print(f"[chaos_check] serve: accepted={len(accepted)} ok={oks} "
          f"errored={errs} shed={sheds[0]} injected_fired={h.fired} "
          f"breaker_trips={srv.breaker.trips} stats={st}")
    fails = []
    if not drained:
        fails.append("drain did not complete")
    if resolved != len(accepted):
        fails.append(f"{len(accepted) - resolved} accepted requests were "
                     f"silently dropped")
    if h.fired == 0:
        fails.append("injected step faults never fired")
    if errs == 0:
        fails.append("no request surfaced the injected failure")
    if srv.alive():
        fails.append("batch thread survived the drain")
    if len(st_shapes := srv.distinct_shapes) > 3:
        fails.append(f"bucketing leaked {len(st_shapes)} signatures (> 3)")
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: drain completed with every accepted "
          f"request resolved ({oks} served, {errs} explicitly errored, "
          f"0 dropped)")
    return 0


def llm_mode(args):
    """Continuous-batching LLM serving chaos (ISSUE 10, sharded gang
    since ISSUE 14): stream generations through a tensor-parallel
    tp=2 server with int8 decode collectives under a decode-fault
    burst + SIGTERM mid-decode."""
    import signal
    import threading

    from mxnet_tpu import fault, serving
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)

    cfg = CausalLMConfig(vocab_size=64, n_layers=2, n_heads=2,
                         head_dim=8, d_ff=32)
    srv = serving.GenerationServer(
        init_causal_lm(cfg, seed=0), cfg,
        buckets=serving.BucketSpec(batch=(1, 2), length=(8, 16)),
        n_slots=4, n_pages=33, page_size=8, max_new_tokens=6,
        max_queue=256, seed=0, tp_shards=2, tp_collectives="int8",
        breaker=serving.CircuitBreaker(threshold=3, base_delay=0.02,
                                       max_delay=0.1),
        name="ChaosGen")
    srv.start()
    census = srv.census()
    warm = srv.jit_cache_count()
    h = srv.healthz()
    print(f"[chaos_check] llm: warmed {warm} executables "
          f"(census {census}: prefill grid + 1 decode) over "
          f"tp_shards={h['tp_shards']} "
          f"({h['tp_collectives']} decode collectives), "
          f"ready={srv.ready()}")

    accepted, sheds = [], [0]
    count_lock = threading.Lock()
    stop_submitting = threading.Event()

    def client(k):
        rng = np.random.RandomState(k)
        for i in range(args.requests):
            if stop_submitting.is_set():
                return
            prompt = rng.randint(0, 64, size=int(rng.randint(1, 15)))
            try:
                req = srv.submit(prompt.astype(np.int32),
                                 max_new_tokens=int(rng.randint(1, 7)),
                                 temperature=float(i % 2), top_k=4)
                with count_lock:
                    accepted.append(req)
            except serving.RejectedError:
                with count_lock:
                    sheds[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(4)]
    with fault.inject("generate.decode",
                      RuntimeError("injected decode fault"),
                      after_n=5, times=3) as h:
        for t in threads:
            t.start()
        # SIGTERM lands while sequences are mid-decode and clients are
        # still submitting — serve_forever must drain, not drop
        threading.Timer(0.3, os.kill, (os.getpid(), signal.SIGTERM)).start()
        drained = srv.serve_forever(poll=0.01)
    stop_submitting.set()
    for t in threads:
        t.join()

    resolved = sum(1 for r in accepted if r.done())
    oks = sum(1 for r in accepted
              if r.done() and r.exception(timeout=0) is None)
    errs = resolved - oks
    st = srv.stats
    print(f"[chaos_check] llm: accepted={len(accepted)} ok={oks} "
          f"errored={errs} shed={sheds[0]} injected_fired={h.fired} "
          f"tokens_out={st['tokens_out']} preempted={st['preempted']} "
          f"stats={st}")
    fails = []
    if not drained:
        fails.append("drain did not complete")
    if resolved != len(accepted):
        fails.append(f"{len(accepted) - resolved} accepted sequences "
                     f"were silently dropped")
    if h.fired == 0:
        fails.append("injected decode faults never fired")
    if errs == 0 and st["tokens_salvaged"] == 0:
        # ISSUE 19: a decode fault SALVAGES in-flight work (bounded
        # budget) — visible as either a budget-exhausted error or
        # salvaged tokens, never as silence
        fails.append("injected failures neither errored nor salvaged "
                     "any sequence")
    if oks == 0:
        fails.append("no sequence was actually served")
    if srv.jit_cache_count() != warm or warm != census:
        fails.append(f"recompile: jit cache {srv.jit_cache_count()} vs "
                     f"warmup {warm} vs census {census}")
    if srv.alloc.free_count() != srv.alloc.allocatable:
        fails.append(f"page leak: {srv.alloc.free_count()} free of "
                     f"{srv.alloc.allocatable} after drain")
    if srv.alive():
        fails.append("decode loop survived the drain")
    fails.extend(_llm_spec_leg(args))
    fails.extend(_llm_salvage_leg(args))
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: drain completed with every accepted "
          f"sequence resolved ({oks} served, {errs} explicitly errored, "
          f"0 dropped), 0 recompiles ({warm} executables == census), "
          f"pages fully reclaimed; shared-prefix + speculative + "
          f"salvage/journal legs clean")
    return 0


def _llm_spec_leg(args):
    """ISSUE 16 leg: CoW prefix sharing + speculative decoding under
    chaos — 4 clients stream prompts built on ONE common system prompt
    through a speculative server (draft LM proposals, ONE pinned verify
    executable) while a ``generate.decode`` fault burst fires and
    SIGTERM lands mid-decode.  Must hold: 0 dropped accepted sequences,
    ``recompiles_unexpected == 0``, free list == pool after drain.
    Returns failure strings."""
    import signal
    import threading

    from mxnet_tpu import fault, serving
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     draft_config,
                                                     init_causal_lm)

    cfg = CausalLMConfig(vocab_size=64, n_layers=2, n_heads=2,
                         head_dim=8, d_ff=32)
    dcfg = draft_config(cfg, n_layers=1)
    srv = serving.GenerationServer(
        init_causal_lm(cfg, seed=0), cfg,
        buckets=serving.BucketSpec(batch=(1, 2), length=(16,)),
        n_slots=4, n_pages=65, page_size=4, max_new_tokens=6,
        max_queue=256, seed=0,
        draft=init_causal_lm(dcfg, seed=1), draft_config=dcfg, spec_k=2,
        breaker=serving.CircuitBreaker(threshold=3, base_delay=0.02,
                                       max_delay=0.1),
        name="ChaosSpecGen")
    srv.start()
    census, warm = srv.census(), srv.jit_cache_count()
    print(f"[chaos_check] llm spec leg: warmed {warm} executables "
          f"(census {census}: prefill grid + decode + verify), spec_k=2, "
          f"one system prompt over 4 clients")

    # every client's prompt = the SAME system prompt + a short random
    # tail: the prefix index maps the leading pages once, everyone else
    # shares them (CoW on first divergence)
    system = np.random.RandomState(7).randint(0, 64, size=8) \
        .astype(np.int32)
    accepted, sheds = [], [0]
    lock = threading.Lock()
    stop = threading.Event()

    def client(k):
        rng = np.random.RandomState(200 + k)
        for i in range(args.requests):
            if stop.is_set():
                return
            tail = rng.randint(0, 64,
                               size=int(rng.randint(1, 7))).astype(np.int32)
            try:
                req = srv.submit(np.concatenate([system, tail]),
                                 max_new_tokens=int(rng.randint(1, 7)),
                                 temperature=float(i % 2), top_k=4)
                with lock:
                    accepted.append(req)
            except serving.RejectedError:
                with lock:
                    sheds[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(4)]
    with fault.inject("generate.decode",
                      RuntimeError("injected verify fault"),
                      after_n=5, times=3) as h:
        for t in threads:
            t.start()
        threading.Timer(0.3, os.kill, (os.getpid(), signal.SIGTERM)).start()
        drained = srv.serve_forever(poll=0.01)
    stop.set()
    for t in threads:
        t.join()

    resolved = sum(1 for r in accepted if r.done())
    oks = sum(1 for r in accepted
              if r.done() and r.exception(timeout=0) is None)
    errs = resolved - oks
    st = srv.stats
    recomp = srv.telemetry()["gauges"].get("recompiles_unexpected", 0)
    print(f"[chaos_check] llm spec leg: accepted={len(accepted)} ok={oks} "
          f"errored={errs} shed={sheds[0]} injected_fired={h.fired} "
          f"verify_steps={st['verify_steps']} "
          f"spec_accepted={st['spec_accepted']}/{st['spec_proposed']} "
          f"pages_shared_mapped={st['pages_shared_mapped']} "
          f"cow_faults={st['cow_faults']}")
    fails = []
    if not drained:
        fails.append("spec leg: drain did not complete")
    if resolved != len(accepted):
        fails.append(f"spec leg: {len(accepted) - resolved} accepted "
                     f"sequences were silently dropped")
    if h.fired == 0:
        fails.append("spec leg: injected decode faults never fired")
    if errs == 0 and st["tokens_salvaged"] == 0:
        fails.append("spec leg: injected failures neither errored nor "
                     "salvaged any sequence")
    if oks == 0:
        fails.append("spec leg: no sequence was actually served")
    if st["verify_steps"] == 0:
        fails.append("spec leg: the verify executable never ran")
    if st["pages_shared_mapped"] == 0:
        fails.append("spec leg: the common system prompt never shared a "
                     "page")
    if recomp != 0:
        fails.append(f"spec leg: recompiles_unexpected == {recomp}")
    if srv.jit_cache_count() != warm or warm != census:
        fails.append(f"spec leg: jit cache {srv.jit_cache_count()} vs "
                     f"warmup {warm} vs census {census}")
    if srv.alloc.free_count() != srv.alloc.allocatable:
        fails.append(f"spec leg: page leak — {srv.alloc.free_count()} "
                     f"free of {srv.alloc.allocatable} after drain")
    if srv.alive():
        fails.append("spec leg: decode loop survived the drain")
    return fails


def _llm_salvage_leg(args):
    """ISSUE 19 leg: token-exact preempt/resume under chaos.  Two
    probes: (1) a STARVED pool (two worst-case sequences cannot
    coexist) plus a ``generate.decode`` fault burst — every victim is
    salvaged with its tokens and completes with EXACTLY the stream an
    unfaulted big-pool oracle produces; (2) a sibling process running
    with a decode journal is kill -9'd mid-generation and a fresh
    server restores its in-flight sequences from the journal,
    token-exact.  Must hold: 0 dropped, ``tokens_salvaged > 0``,
    ``journal_restores > 0``, ``recompiles_unexpected == 0``, free
    list == pool.  Returns failure strings."""
    import signal
    import subprocess
    import tempfile

    from mxnet_tpu import fault, serving
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)

    cfg = CausalLMConfig(vocab_size=64, n_layers=2, n_heads=2,
                         head_dim=8, d_ff=32)
    params = init_causal_lm(cfg, seed=0)
    buckets = serving.BucketSpec(batch=(1,), length=(8,))
    prompts = [np.asarray([3, 1, 2], np.int32),
               np.asarray([5, 4], np.int32),
               np.asarray([9, 2, 7], np.int32),
               np.asarray([1, 6], np.int32)]
    kinds = [dict(), dict(temperature=0.9, top_k=6),
             dict(), dict(temperature=0.7, top_k=4)]
    seeds = [11, 22, 33, 44]
    fails = []

    # ---- unfaulted oracle: calm pool, same prompts + explicit seeds
    oracle = serving.GenerationServer(
        params, cfg, buckets=buckets, n_slots=2, n_pages=33,
        page_size=4, max_new_tokens=10, seed=0, name="ChaosSalvOracle")
    oracle.start()
    expected = []
    for p, kw, s in zip(prompts, kinds, seeds):
        expected.append(tuple(int(t) for t in
                              oracle.submit(p, seed=s, **kw).result(60)))
    oracle.drain(30)

    # ---- probe 1: preemption storm + fault burst on a starved pool
    srv = serving.GenerationServer(
        params, cfg, buckets=buckets, n_slots=2, n_pages=5,
        page_size=4, max_new_tokens=10, seed=0, salvage_retries=8,
        breaker=serving.CircuitBreaker(threshold=6, base_delay=0.02,
                                       max_delay=0.1),
        name="ChaosSalvGen")
    srv.start()
    census, warm = srv.census(), srv.jit_cache_count()
    with fault.inject("generate.decode",
                      RuntimeError("injected decode fault"),
                      after_n=3, times=2) as h:
        reqs = [srv.submit(p, seed=s, **kw)
                for p, kw, s in zip(prompts, kinds, seeds)]
        got = [tuple(int(t) for t in r.result(timeout=240)) for r in reqs]
    st = srv.stats
    recomp = srv.telemetry()["gauges"].get("recompiles_unexpected", 0)
    print(f"[chaos_check] llm salvage leg: storm served "
          f"{st['completed']}/{len(prompts)} "
          f"(preempted={st['preempted']} "
          f"tokens_salvaged={st['tokens_salvaged']} "
          f"resumes={st['resumes']} "
          f"salvage_retries={st['salvage_retries']} "
          f"injected_fired={h.fired})")
    if h.fired == 0:
        fails.append("salvage leg: injected decode faults never fired")
    if st["completed"] != len(prompts) or st["failed"] != 0:
        fails.append(f"salvage leg: {st['failed']} sequences failed — "
                     f"salvage dropped accepted work")
    if st["tokens_salvaged"] == 0:
        fails.append("salvage leg: the storm salvaged no tokens")
    if st["preempted"] == 0 or st["resumes"] == 0:
        fails.append("salvage leg: the starved pool never preempted/"
                     "resumed — the storm probe probed nothing")
    if got != expected:
        fails.append("salvage leg: salvaged streams diverge from the "
                     "unfaulted oracle — resume is not token-exact")
    if recomp != 0:
        fails.append(f"salvage leg: recompiles_unexpected == {recomp}")
    if srv.jit_cache_count() != warm or warm != census:
        fails.append(f"salvage leg: jit cache {srv.jit_cache_count()} vs "
                     f"warmup {warm} vs census {census}")
    if srv.alloc.free_count() != srv.alloc.allocatable:
        fails.append(f"salvage leg: page leak — {srv.alloc.free_count()} "
                     f"free of {srv.alloc.allocatable} after drain")
    if not srv.drain(30):
        fails.append("salvage leg: storm server drain did not complete")

    # ---- probe 2: kill -9 mid-generation, restore from the journal
    jdir = tempfile.mkdtemp(prefix="chaos_salvage_")
    jpath = os.path.join(jdir, "decode.jsonl")
    child_src = (
        "import os, sys, time\n"
        "import numpy as np\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from mxnet_tpu import serving\n"
        "from mxnet_tpu.gluon.model_zoo.causal_lm import "
        "CausalLMConfig, init_causal_lm\n"
        "cfg = CausalLMConfig(vocab_size=64, n_layers=2, n_heads=2, "
        "head_dim=8, d_ff=32)\n"
        "srv = serving.GenerationServer(\n"
        "    init_causal_lm(cfg, seed=0), cfg,\n"
        "    buckets=serving.BucketSpec(batch=(1,), length=(8,)),\n"
        "    n_slots=2, n_pages=33, page_size=4, max_new_tokens=32,\n"
        "    seed=0, journal=sys.argv[1], journal_every=1,\n"
        "    name='ChaosJournalGen')\n"
        "srv.start()\n"
        "srv.submit(np.asarray([3, 1, 2], np.int32), seed=11)\n"
        "srv.submit(np.asarray([5, 4], np.int32), temperature=0.9, "
        "top_k=6, seed=22)\n"
        "limit = time.monotonic() + 60\n"
        "while srv.stats['tokens_out'] < 2 "
        "and time.monotonic() < limit:\n"
        "    time.sleep(0.002)\n"
        "print('READY', flush=True)\n"
        "time.sleep(120)\n")
    child = subprocess.Popen([sys.executable, "-c", child_src, jpath],
                             stdout=subprocess.PIPE, text=True)
    ready = False
    line = child.stdout.readline()          # blocks until READY/EOF
    ready = line.strip() == "READY"
    if ready:
        os.kill(child.pid, signal.SIGKILL)  # the actual kill -9
    child.wait()
    if not ready:
        fails.append("salvage leg: journal child never reached READY")
        return fails

    rsrv = serving.GenerationServer(
        params, cfg, buckets=buckets, n_slots=2, n_pages=33,
        page_size=4, max_new_tokens=32, seed=0, name="ChaosRestoreGen")
    rsrv.start()
    exp = [tuple(int(t) for t in
                 rsrv.submit(np.asarray([3, 1, 2], np.int32),
                             seed=11).result(120)),
           tuple(int(t) for t in
                 rsrv.submit(np.asarray([5, 4], np.int32),
                             temperature=0.9, top_k=6,
                             seed=22).result(120))]
    restored = rsrv.restore_journal(jpath)
    outs = sorted(tuple(int(t) for t in r.result(timeout=240))
                  for r in restored.values())
    rst = rsrv.stats
    print(f"[chaos_check] llm salvage leg: kill -9 restore — "
          f"journal_restores={rst['journal_restores']} "
          f"restored={len(restored)} resumes={rst['resumes']}")
    if rst["journal_restores"] == 0 or len(restored) != 2:
        fails.append(f"salvage leg: journal restore recovered "
                     f"{len(restored)} of 2 in-flight sequences")
    if outs != sorted(exp):
        fails.append("salvage leg: restored streams diverge from the "
                     "uninterrupted oracle — journal restore is not "
                     "token-exact")
    if not rsrv.drain(30):
        fails.append("salvage leg: restore server drain did not complete")
    return fails


def _fleet_int8_leg(step, mgr):
    """ISSUE 8 leg: an int8 fleet (per-channel PTQ weights, dequant
    folded into the compiled apply) ingests an f32 training snapshot
    through a rolling update under live traffic — 0 dropped accepted
    requests, executable census unchanged.  Returns failure strings."""
    import threading

    import jax
    import jax.numpy as jnp
    from mxnet_tpu import amp, serving
    from mxnet_tpu.parallel.checkpoint import load_snapshot_params
    from tools.costguard import executable_census

    params, _names = load_snapshot_params(mgr.checkpoints()[-1][1])
    shapes = [tuple(p.shape) for p in params]
    iw1, ib1 = shapes.index((16, 8)), shapes.index((16,))
    iw2, ib2 = shapes.index((4, 16)), shapes.index((4,))
    quant = amp.Int8Quantizer(axis=0)      # (units, in_units) kernels

    def fwd(p, x):
        h = jnp.maximum(x @ p[iw1].T + p[ib1], 0.0)
        return h @ p[iw2].T + p[ib2]

    qfn = jax.jit(quant.wrap(fwd))
    fleet = serving.ServingFleet.replicated(
        qfn, quant.quantize([jnp.asarray(p) for p in params]), 3,
        quantizer=quant.quantize, buckets=(1, 2, 4), max_delay=0.002,
        sample=np.ones((8,), np.float32), name="ChaosInt8Fleet")
    fleet.start()
    census = executable_census(fleet.buckets)
    updater = serving.WeightUpdater(fleet, mgr, poll=0.02).start()
    n_int8 = sum(1 for p in fleet.replicas[0].apply.params
                 if p.dtype == jnp.int8)
    print(f"[chaos_check] int8 fleet: 3 replicas up, census={census}, "
          f"{n_int8} int8 weight payload(s) served")

    accepted, sheds = [], [0]
    lock = threading.Lock()
    stop = threading.Event()

    def client(k):
        r = np.random.RandomState(100 + k).randn(8).astype(np.float32)
        while not stop.is_set():
            try:
                req = fleet.submit(r)
                with lock:
                    accepted.append(req)
            except serving.RejectedError:
                with lock:
                    sheds[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(2)]
    for t in threads:
        t.start()
    fails = []
    try:
        time.sleep(0.1)
        # one more f32 training step -> a fresh f32 snapshot the int8
        # fleet must re-quantize on ingest
        rng = np.random.RandomState(42)
        step(rng.randn(16, 8).astype(np.float32),
             rng.randint(0, 4, (16,)))
        mgr.save()
        t0 = time.time()
        while updater.applied < 1 and time.time() - t0 < 30:
            time.sleep(0.01)
        if updater.applied < 1:
            fails.append(f"int8 fleet: f32 snapshot did not roll out "
                         f"within 30s (applied={updater.applied}, "
                         f"skipped={updater.skipped})")
        time.sleep(0.1)
    finally:
        stop.set()
        for t in threads:
            t.join()
        updater.stop(timeout=10)
        drained = fleet.drain(timeout=30)
    resolved = sum(1 for r in accepted if r.done())
    errs = [r.exception(0) for r in accepted
            if r.done() and r.exception(0) is not None]
    print(f"[chaos_check] int8 fleet: accepted={len(accepted)} "
          f"resolved={resolved} errored={len(errs)} shed={sheds[0]} "
          f"swaps={fleet.stats['swaps']} jit_cache={qfn._cache_size()}")
    if not drained:
        fails.append("int8 fleet: drain did not complete")
    if resolved != len(accepted):
        fails.append(f"int8 fleet: {len(accepted) - resolved} accepted "
                     f"requests dropped")
    if errs:
        fails.append(f"int8 fleet: {len(errs)} accepted requests errored "
                     f"(first: {errs[0]!r})")
    if qfn._cache_size() > census:
        fails.append(f"int8 fleet: recompile leak — jit cache "
                     f"{qfn._cache_size()} > census {census}")
    if n_int8 != 2:        # both Dense kernels; biases stay f32
        fails.append(f"int8 fleet: expected 2 int8 weight payloads, "
                     f"served {n_int8}")
    # the rolled-out weights are the NEW snapshot's, re-quantized
    new_params, _ = load_snapshot_params(mgr.checkpoints()[-1][1])
    ref = quant.dequantize(quant.quantize(
        [jnp.asarray(p) for p in new_params]))
    x1 = np.ones((1, 8), np.float32)
    want = np.asarray(fwd([np.asarray(r) for r in ref], x1))[0]
    got = np.asarray(fleet.replicas[0].apply(x1))[0]
    if not np.allclose(got, want, atol=1e-5):
        fails.append("int8 fleet: replica 0 does not serve the "
                     "re-quantized final snapshot")
    return fails


def fleet_mode(args):
    """Replica-kill + rolling-update + SIGTERM smoke (ISSUE 7)."""
    import signal
    import tempfile as _tempfile
    import threading

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import fault, gluon, parallel, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.checkpoint import (CheckpointManager,
                                               load_snapshot_params)
    from tools.costguard import executable_census

    # -- a real training job feeding the snapshot stream -------------------
    mx.random.seed(7)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize()
    mesh = parallel.make_mesh(dp=len(jax.devices()))
    step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                              mx.optimizer.create("adam"), mesh=mesh)
    rng = np.random.RandomState(0)
    batches = [(rng.randn(16, 8).astype(np.float32),
                rng.randint(0, 4, (16,))) for _ in range(6)]
    d = _tempfile.mkdtemp(prefix="chaos_fleet_")
    mgr = CheckpointManager(step, d, keep_last=5)
    for x, y in batches[:2]:
        step(x, y)
    mgr.save()
    params, names = load_snapshot_params(mgr.checkpoints()[-1][1])
    first_seen = mgr.checkpoints()[-1][0]

    # -- the serving side: one shared jitted forward, 3 hot-swap replicas --
    shapes = [tuple(p.shape) for p in params]
    iw1, ib1 = shapes.index((16, 8)), shapes.index((16,))
    iw2, ib2 = shapes.index((4, 16)), shapes.index((4,))
    traces = []

    @jax.jit
    def fwd(p, x):
        traces.append(x.shape)
        h = jnp.maximum(x @ p[iw1].T + p[ib1], 0.0)
        return h @ p[iw2].T + p[ib2]

    class KillableApply(serving.HotSwapApply):
        def __init__(self, params):
            super().__init__(lambda p, x: np.asarray(fwd(p, x)), params)
            self.dead = False

        def __call__(self, *leaves):
            if self.dead:
                raise SystemExit("replica killed")
            time.sleep(0.003)          # keep work in flight at kill time
            return super().__call__(*leaves)

    applies = [KillableApply(list(params)) for _ in range(3)]
    fleet = serving.ServingFleet(
        applies, buckets=(1, 2, 4), max_delay=0.002,
        sample=np.ones((8,), np.float32), name="ChaosFleet")
    fleet.start()
    census = executable_census(fleet.buckets)
    warm = len(set(traces))
    print(f"[chaos_check] fleet: 3 replicas warm, census={census} "
          f"compiled={warm} jit_cache={fwd._cache_size()} "
          f"ready={fleet.ready()}")

    updater = serving.WeightUpdater(fleet, mgr, last_seen=first_seen,
                                    poll=0.02)
    updater.start()

    accepted, sheds = [], [0]
    count_lock = threading.Lock()
    stop_submitting = threading.Event()

    def client(k):
        r = np.random.RandomState(k).randn(8).astype(np.float32)
        while not stop_submitting.is_set():
            try:
                req = fleet.submit(r)
                with count_lock:
                    accepted.append(req)
            except serving.RejectedError:
                with count_lock:
                    sheds[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    fails = []
    try:
        time.sleep(0.15)
        applies[1].dead = True         # hard-kill replica 1 under traffic
        time.sleep(0.15)
        for round_no in (1, 2):        # stream two snapshots through
            for x, y in batches[2 * round_no:2 * round_no + 2]:
                step(x, y)
            mgr.save()
            t0 = time.time()
            while updater.applied < round_no and time.time() - t0 < 30:
                time.sleep(0.01)
            if updater.applied < round_no:
                fails.append(f"rolling update {round_no} did not apply "
                             f"within 30s (applied={updater.applied}, "
                             f"skipped={updater.skipped})")
        # SIGTERM lands while clients are still submitting
        threading.Timer(0.1, os.kill, (os.getpid(), signal.SIGTERM)).start()
        drained = fleet.serve_forever(poll=0.01)
    finally:
        stop_submitting.set()
        for t in threads:
            t.join()
        updater.stop(timeout=10)

    resolved = sum(1 for r in accepted if r.done())
    errs = [r.exception(0) for r in accepted
            if r.done() and r.exception(0) is not None]
    st = fleet.stats
    print(f"[chaos_check] fleet: accepted={len(accepted)} "
          f"resolved={resolved} errored={len(errs)} shed={sheds[0]} "
          f"redispatched={st['redispatched']} swaps={st['swaps']} "
          f"probes={st['probes']} compiled={len(set(traces))} "
          f"jit_cache={fwd._cache_size()}")
    if not drained:
        fails.append("fleet drain did not complete")
    if resolved != len(accepted):
        fails.append(f"{len(accepted) - resolved} accepted requests were "
                     f"silently dropped")
    if errs:
        fails.append(f"{len(errs)} accepted requests errored — failover "
                     f"should have served them (first: {errs[0]!r})")
    if st["redispatched"] < 1:
        fails.append("the replica kill never exercised failover")
    if updater.applied != 2:
        fails.append(f"expected 2 applied rolling updates, got "
                     f"{updater.applied}")
    if len(set(traces)) > census or fwd._cache_size() > census:
        fails.append(f"recompile leak: {len(set(traces))} traced / "
                     f"{fwd._cache_size()} cached > census {census}")
    if fleet.alive():
        fails.append("a replica batch thread survived the drain")
    # the survivors must actually serve the LAST snapshot's weights
    want = np.asarray(fwd([jnp.asarray(p) for p in
                           load_snapshot_params(mgr.checkpoints()[-1][1])[0]],
                          np.ones((1, 8), np.float32)))[0]
    got = np.asarray(applies[0](np.ones((1, 8), np.float32)))[0]
    if not np.allclose(got, want):
        fails.append("replica 0 does not serve the final snapshot weights")
    # ISSUE 8 leg: f32 snapshot -> int8 fleet rolling update
    fails += _fleet_int8_leg(step, mgr)
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: replica kill + 2 rolling updates + SIGTERM "
          f"with 0 dropped accepted requests, 0 recompiles "
          f"({len(set(traces))}/{census} executables), "
          f"{st['redispatched']} failovers; int8-fleet f32-snapshot "
          f"rolling update clean")
    return 0


def _slo_fleet_leg():
    """The fleet half of the SLO storm: gold/bronze replica groups, an
    abusive tenant, a replica kill, one autoscale up/down cycle, and a
    rolling weight update — concurrently.  Returns failure strings."""
    import threading

    import jax
    from mxnet_tpu import profiler, serving

    W = np.eye(4, dtype=np.float32)

    @jax.jit
    def fwd(params, x):
        (w,) = params
        return x @ w

    class KillableApply(serving.HotSwapApply):
        def __init__(self, delay):
            super().__init__(lambda p, x: np.asarray(fwd(p, x)), [W])
            self.dead = False
            self.delay = delay

        def __call__(self, *leaves):
            if self.dead:
                raise SystemExit("replica killed")
            time.sleep(self.delay)
            return super().__call__(*leaves)

    qos = serving.TenantQoS(
        classes=[serving.QoSClass("gold", priority=10, deadline=5.0,
                                  group="gold"),
                 serving.QoSClass("bronze", priority=0, deadline=5.0,
                                  admit_frac=0.8, group="bronze")],
        default_class="bronze", tenant_rate=200, tenant_burst=200)
    gold = [KillableApply(0.001)]
    bronze = [KillableApply(0.004) for _ in range(2)]
    fleet = serving.ServingFleet(
        {"gold": gold, "bronze": bronze}, buckets=(1, 2, 4),
        max_delay=0.002, max_inflight=16, qos=qos,
        sample=np.ones((4,), np.float32), name="SloFleet")
    fleet.start()
    census = fleet.grid_census
    warm = fwd._cache_size()
    scaler = serving.FleetAutoscaler(
        fleet, serving.ScalingPolicy(
            min_replicas=1, max_replicas=3, up_occupancy=0.25,
            down_occupancy=0.1, up_queue_depth=4, up_ticks=2,
            down_ticks=10, cooldown=0.1),
        group="bronze", tick=0.02, watchdog_secs=60).start()
    updater = serving.WeightUpdater(fleet)
    print(f"[chaos_check] slo fleet: groups gold=1 bronze=2, census="
          f"{census}, autoscaler on bronze, ready={fleet.ready()}")

    stop = threading.Event()
    lock = threading.Lock()
    served = {}                 # tenant -> [accepted Requests]
    throttled = {}              # tenant -> count

    def client(tenant, klass, pause):
        x = np.random.RandomState(hash(tenant) % 97).randn(4) \
            .astype(np.float32)
        while not stop.is_set():
            try:
                r = fleet.submit(x, tenant=tenant, klass=klass)
                with lock:
                    served.setdefault(tenant, []).append(r)
            except serving.TenantThrottledError:
                with lock:
                    throttled[tenant] = throttled.get(tenant, 0) + 1
            except serving.RejectedError:
                pass
            time.sleep(pause)

    specs = [("g0", "gold", 0.008), ("g1", "gold", 0.008),
             ("b0", "bronze", 0.008), ("b1", "bronze", 0.008),
             ("abuser", "bronze", 0.0005)]    # ~2000/s — way over rate
    threads = [threading.Thread(target=client, args=s) for s in specs]
    for t in threads:
        t.start()
    fails = []
    try:
        time.sleep(0.3)
        bronze[1].dead = True       # replica kill mid-storm
        # rolling weight update mid-storm (validated, quarantine→swap→
        # probe→readmit per replica, autoscaler racing on bronze).  The
        # updater skips dead/retired replicas; a kill that has not hit a
        # batch yet can still race the roll, so one retry is legitimate
        # (the real WeightUpdater watch loop re-polls the same way).
        try:
            updater.update([2.0 * W])
        except serving.UpdateRolledBackError:
            updater.update([2.0 * W])
        t0 = time.time()
        while scaler.stats["scale_ups"] < 1 and time.time() - t0 < 30:
            time.sleep(0.02)
        time.sleep(0.3)             # let the scaled fleet absorb the storm
    finally:
        stop.set()
        for t in threads:
            t.join()
    # storm over: the autoscaler should give the capacity back
    t0 = time.time()
    while scaler.stats["scale_downs"] < 1 and time.time() - t0 < 30:
        time.sleep(0.05)
    scaler.stop(timeout=10)
    drained = fleet.drain(timeout=30)
    classes = fleet.healthz()["classes"]
    all_reqs = [r for reqs in served.values() for r in reqs]
    resolved = sum(1 for r in all_reqs if r.done())
    errs = [r.exception(0) for r in all_reqs
            if r.done() and r.exception(0) is not None]
    st = scaler.stats
    print(f"[chaos_check] slo fleet: accepted={len(all_reqs)} "
          f"resolved={resolved} errored={len(errs)} "
          f"throttled={throttled} scale={st} "
          f"gold_p99={classes['gold']['p99_ms']} "
          f"bronze_p99={classes['bronze']['p99_ms']} "
          f"jit_cache={fwd._cache_size()}")
    if not drained:
        fails.append("slo fleet: drain did not complete")
    if resolved != len(all_reqs):
        fails.append(f"slo fleet: {len(all_reqs) - resolved} accepted "
                     f"requests silently dropped")
    if errs:
        fails.append(f"slo fleet: {len(errs)} accepted requests errored "
                     f"(first: {errs[0]!r})")
    if throttled.get("abuser", 0) < 10:
        fails.append(f"slo fleet: abusive tenant was not throttled "
                     f"({throttled})")
    for tenant in ("g0", "g1", "b0", "b1"):
        if throttled.get(tenant, 0) > 0:
            fails.append(f"slo fleet: well-behaved tenant {tenant} was "
                         f"throttled {throttled[tenant]}x — isolation "
                         f"failed")
        if not served.get(tenant):
            fails.append(f"slo fleet: tenant {tenant} had nothing served")
    if not (classes["gold"]["p99_ms"] < classes["bronze"]["p99_ms"]):
        fails.append(f"slo fleet: per-class p99 ordering failed "
                     f"(gold {classes['gold']['p99_ms']} ms >= bronze "
                     f"{classes['bronze']['p99_ms']} ms)")
    if st["scale_ups"] < 1 or st["scale_downs"] < 1:
        fails.append(f"slo fleet: no full autoscale cycle ({st})")
    if updater.applied != 1:
        fails.append(f"slo fleet: rolling update did not apply "
                     f"({updater.applied})")
    if not np.allclose(np.asarray(gold[0](np.ones((1, 4), np.float32)))[0],
                       2.0 * np.ones(4, np.float32)):
        fails.append("slo fleet: gold replica does not serve the rolled "
                     "weights")
    if fwd._cache_size() != warm or warm > census:
        fails.append(f"slo fleet: recompile — jit cache "
                     f"{fwd._cache_size()} vs warm {warm} vs census "
                     f"{census}")
    # (r1's fate depends on which bronze replica the scaler retired —
    # either way the counter-leak sweep below proves membership
    # accounting held)
    leaked = [s for s in profiler.counters("SloFleet-r").keys()
              if s.split("::")[0].replace("SloFleet-r", "") not in
              {str(rep.index) for rep in fleet.replicas}]
    if leaked:
        fails.append(f"slo fleet: retired replicas leaked counter "
                     f"series: {leaked}")
    return fails


def _slo_llm_leg():
    """The generation half: a disaggregated server (prefill worker
    group + handoff) under a long-prefill + decode mix with two
    priority classes.  Returns failure strings."""
    import threading

    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)

    cfg = CausalLMConfig(vocab_size=64, n_layers=2, n_heads=2,
                         head_dim=8, d_ff=32)
    qos = serving.TenantQoS(
        classes=[serving.QoSClass("gold", priority=10, deadline=20.0),
                 serving.QoSClass("bronze", priority=0, deadline=20.0,
                                  admit_frac=0.5)],
        default_class="bronze")
    srv = serving.GenerationServer(
        init_causal_lm(cfg, seed=0), cfg,
        buckets=serving.BucketSpec(batch=(1, 2), length=(8, 32)),
        n_slots=2, n_pages=41, page_size=8, max_new_tokens=8,
        max_queue=128, seed=0, prefill_workers=2, qos=qos,
        name="SloGen")
    srv.start()
    census, warm = srv.census(), srv.jit_cache_count()
    print(f"[chaos_check] slo llm: disaggregated (2 prefill workers), "
          f"census={census} (grid + handoff + decode), warmed {warm}")

    stop = threading.Event()
    lock = threading.Lock()
    accepted = {"gold": [], "bronze": []}

    def client(k, klass, long_prompts, pause):
        rng = np.random.RandomState(k)
        while not stop.is_set():
            if long_prompts:
                n, new = int(rng.randint(24, 31)), int(rng.randint(5, 9))
            else:
                n, new = int(rng.randint(1, 8)), int(rng.randint(1, 4))
            try:
                r = srv.submit(rng.randint(0, 64, size=n).astype(np.int32),
                               max_new_tokens=new,
                               tenant=f"t{k}", klass=klass)
                with lock:
                    accepted[klass].append(r)
            except serving.RejectedError:
                pass
            time.sleep(pause)

    # three bronze clients streaming LONG prompts oversubscribe the two
    # decode slots (a deep low-priority queue); gold's short prompts
    # must jump it — the per-class p99 ordering under exactly the
    # long-prefill interference this mode exists to check
    threads = [threading.Thread(target=client, args=(k, klass, lng, p))
               for k, (klass, lng, p) in enumerate(
                   [("gold", False, 0.01), ("bronze", True, 0.001),
                    ("bronze", True, 0.001), ("bronze", True, 0.001)])]
    for t in threads:
        t.start()
    time.sleep(1.2)
    stop.set()
    for t in threads:
        t.join()
    drained = srv.drain(timeout=60)
    classes = srv.healthz()["classes"]
    fails = []
    all_reqs = accepted["gold"] + accepted["bronze"]
    resolved = sum(1 for r in all_reqs if r.done())
    oks = sum(1 for r in all_reqs
              if r.done() and r.exception(0) is None)
    print(f"[chaos_check] slo llm: accepted={len(all_reqs)} "
          f"resolved={resolved} ok={oks} "
          f"gold_p99={classes['gold']['p99_ms']} "
          f"bronze_p99={classes['bronze']['p99_ms']} "
          f"handoffs={srv.stats['handoffs']} "
          f"jit_cache={srv.jit_cache_count()}")
    if not drained:
        fails.append("slo llm: drain did not complete")
    if resolved != len(all_reqs):
        fails.append(f"slo llm: {len(all_reqs) - resolved} accepted "
                     f"sequences silently dropped")
    if oks == 0 or not accepted["gold"] or not accepted["bronze"]:
        fails.append("slo llm: traffic did not actually flow")
    if srv.stats["handoffs"] < 1:
        fails.append("slo llm: no prefill→decode handoff happened — the "
                     "disaggregated path was not exercised")
    if srv.jit_cache_count() != warm or warm != census:
        fails.append(f"slo llm: recompile — jit cache "
                     f"{srv.jit_cache_count()} vs warm {warm} vs census "
                     f"{census}")
    if srv.alloc.free_count() != srv.alloc.allocatable:
        fails.append(f"slo llm: page leak ({srv.alloc.free_count()} of "
                     f"{srv.alloc.allocatable} free)")
    if not (classes["gold"]["p99_ms"] < classes["bronze"]["p99_ms"]):
        fails.append(f"slo llm: per-class p99 ordering failed (gold "
                     f"{classes['gold']['p99_ms']} ms >= bronze "
                     f"{classes['bronze']['p99_ms']} ms)")
    return fails


def _obs_fleet_leg():
    """The fleet half of the observability storm: a traced 3-replica
    fleet under client traffic with a ``serving.step`` fault burst and
    one replica hard-killed — every accepted request must resolve AND
    yield a complete, attribution-clean span tree.  Returns (failure
    strings, accepted count)."""
    import threading

    import jax
    from mxnet_tpu import fault, serving, telemetry

    W = np.eye(4, dtype=np.float32)

    @jax.jit
    def fwd(params, x):
        (w,) = params
        return x @ w

    class KillableApply(serving.HotSwapApply):
        def __init__(self):
            super().__init__(lambda p, x: np.asarray(fwd(p, x)), [W])
            self.dead = False

        def __call__(self, *leaves):
            if self.dead:
                raise SystemExit("replica killed")
            time.sleep(0.002)      # keep work in flight at kill time
            return super().__call__(*leaves)

    applies = [KillableApply() for _ in range(3)]
    fleet = serving.ServingFleet(
        applies, buckets=(1, 2, 4), max_delay=0.002,
        sample=np.ones((4,), np.float32), name="ObsFleet")
    fleet.start()

    accepted, sheds = [], [0]
    count_lock = threading.Lock()
    stop_submitting = threading.Event()

    def client(k):
        r = np.random.RandomState(k).randn(4).astype(np.float32)
        while not stop_submitting.is_set():
            try:
                req = fleet.submit(r)
                with count_lock:
                    accepted.append(req)
            except serving.RejectedError:
                with count_lock:
                    sheds[0] += 1
            time.sleep(0.002)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(4)]
    fails = []
    for t in threads:
        t.start()
    try:
        time.sleep(0.1)
        # a fault burst the failover path absorbs — firings must land
        # as span events on the in-flight step spans
        with fault.inject("serving.step", RuntimeError("injected storm"),
                          times=3):
            time.sleep(0.15)
        applies[1].dead = True     # hard-kill replica 1 under traffic
        time.sleep(0.2)
    finally:
        stop_submitting.set()
        for t in threads:
            t.join()
    fleet.drain()

    unresolved = sum(1 for r in accepted if not r.done())
    errs = [r.exception(0) for r in accepted
            if r.done() and r.exception(0) is not None]
    if unresolved:
        fails.append(f"obs fleet: {unresolved} accepted requests were "
                     f"silently dropped")
    if errs:
        fails.append(f"obs fleet: {len(errs)} accepted requests errored "
                     f"— failover should have absorbed the chaos "
                     f"(first: {errs[0]!r})")

    traces = telemetry.finished_traces(clear=True)
    if len(traces) != len(accepted):
        fails.append(f"obs fleet: {len(accepted)} accepted requests but "
                     f"{len(traces)} span trees — tracing is lossy")
    bad = 0
    fault_events = 0
    failovers = 0
    for tr in traces:
        problems = telemetry.audit_spans(tr)
        if problems:
            bad += 1
            if bad == 1:
                fails.append(f"obs fleet: incomplete/mis-attributed span "
                             f"tree {tr.trace_id}: {problems}")
        for sp in tr.spans:
            failovers += sp.name == "failover"
            fault_events += sum(1 for ev in sp.events
                                if ev["name"] == "fault")
    if bad > 1:
        fails.append(f"obs fleet: {bad} of {len(traces)} span trees "
                     f"failed the audit")
    if fault_events < 1:
        fails.append("obs fleet: the injected fault burst left no span "
                     "events — fault.fire observer not wired")
    if failovers < 1:
        fails.append("obs fleet: the replica kill produced no failover "
                     "spans")
    st = fleet.stats
    print(f"[chaos_check] obs fleet: accepted={len(accepted)} "
          f"shed={sheds[0]} trees={len(traces)} audit_bad={bad} "
          f"failover_spans={failovers} fault_events={fault_events} "
          f"redispatched={st['redispatched']}")
    return fails, len(accepted)


def _obs_llm_leg():
    """The generation half: a traced ``GenerationServer`` streams
    sequences through a ``generate.decode`` fault burst — accepted
    sequences resolve (tokens or explicit error) and every one yields a
    complete queue→prefill→decode span tree."""
    import threading

    from mxnet_tpu import fault, serving, telemetry
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)

    cfg = CausalLMConfig(vocab_size=48, n_layers=2, n_heads=2,
                         head_dim=8, d_ff=32)
    params = init_causal_lm(cfg, seed=3)
    srv = serving.GenerationServer(
        params, cfg, buckets=serving.BucketSpec(batch=(1,), length=(8,)),
        n_slots=2, n_pages=17, page_size=4, max_new_tokens=6, seed=0,
        name="ObsGen")
    srv.start()

    accepted = []
    count_lock = threading.Lock()
    fails = []

    def client(k):
        rng = np.random.RandomState(k)
        for _ in range(4):
            prompt = rng.randint(1, 40, (3,)).astype(np.int32)
            try:
                req = srv.submit(prompt, max_new_tokens=4)
                with count_lock:
                    accepted.append(req)
            except serving.RejectedError:
                pass
            time.sleep(0.01)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    with fault.inject("generate.decode", RuntimeError("decode storm"),
                      times=2):
        for t in threads:
            t.join()
        srv.drain()

    unresolved = sum(1 for r in accepted if not r.done())
    if unresolved:
        fails.append(f"obs llm: {unresolved} accepted sequences were "
                     f"silently dropped")
    traces = telemetry.finished_traces(clear=True)
    if len(traces) != len(accepted):
        fails.append(f"obs llm: {len(accepted)} accepted sequences but "
                     f"{len(traces)} span trees")
    bad = 0
    for tr in traces:
        problems = telemetry.audit_spans(tr)
        if problems:
            bad += 1
            if bad == 1:
                fails.append(f"obs llm: bad span tree {tr.trace_id}: "
                             f"{problems}")
        names = {sp.name for sp in tr.spans}
        if not {"admit", "queue", "prefill"} <= names:
            fails.append(f"obs llm: trace {tr.trace_id} is missing "
                         f"generation phases ({sorted(names)})")
            break
    if bad > 1:
        fails.append(f"obs llm: {bad} of {len(traces)} span trees "
                     f"failed the audit")
    errored = sum(1 for r in accepted
                  if r.done() and r.exception(0) is not None)
    print(f"[chaos_check] obs llm: accepted={len(accepted)} "
          f"errored_explicitly={errored} trees={len(traces)} "
          f"audit_bad={bad}")
    return fails


def _obs_flight_leg():
    """The crash flight recorder (ISSUE 15): a traced generation worker
    is killed mid-step by a decode fault storm that trips the breaker —
    the breaker-OPEN trigger must leave a complete post-mortem bundle
    (audit-clean span trees, the fatal fault firing on record,
    ``recompiles_unexpected == 0``) and every accepted sequence must
    still resolve explicitly.  Returns failure strings."""
    import json as _json
    import tempfile as _tempfile
    import threading

    from mxnet_tpu import fault, serving, telemetry
    from mxnet_tpu.gluon.model_zoo.causal_lm import (CausalLMConfig,
                                                     init_causal_lm)

    d = _tempfile.mkdtemp(prefix="chaos_flight_")
    telemetry.enable_flight(directory=d, limit=4096)
    fails = []
    cfg = CausalLMConfig(vocab_size=48, n_layers=2, n_heads=2,
                         head_dim=8, d_ff=32)
    srv = serving.GenerationServer(
        init_causal_lm(cfg, seed=5), cfg,
        buckets=serving.BucketSpec(batch=(1,), length=(8,)),
        n_slots=2, n_pages=17, page_size=4, max_new_tokens=6, seed=0,
        # threshold=1: the FIRST mid-step death trips OPEN (prefill
        # successes interleave with decode failures, so a higher
        # threshold never sees consecutive ones on this tiny model)
        breaker=serving.CircuitBreaker(threshold=1, base_delay=0.5),
        name="FlightGen")
    try:
        srv.start()       # traced warmup: compile events == census

        accepted = []
        count_lock = threading.Lock()

        def client(k):
            rng = np.random.RandomState(k)
            for _ in range(3):
                try:
                    req = srv.submit(rng.randint(1, 40, (3,))
                                     .astype(np.int32), max_new_tokens=4)
                    with count_lock:
                        accepted.append(req)
                except (serving.RejectedError, serving.ServerClosedError):
                    pass
                time.sleep(0.01)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(3)]
        # an unbounded decode fault storm armed BEFORE traffic (two
        # clean steps, then every decode step fails): the worker dies
        # mid-generation and keeps dying until the breaker trips OPEN —
        # THE mid-step kill the recorder exists for
        with fault.inject("generate.decode",
                          RuntimeError("decode storm — worker killed"),
                          after_n=2):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            deadline = time.monotonic() + 15
            while srv.breaker.state_code() != 2 \
                    and time.monotonic() < deadline:
                time.sleep(0.01)
    finally:
        srv.drain()

    unresolved = sum(1 for r in accepted if not r.done())
    if unresolved:
        fails.append(f"obs flight: {unresolved} accepted sequences were "
                     f"silently dropped")
    bundle = telemetry.flight().last_path
    if bundle is None:
        fails.append("obs flight: the breaker trip left no "
                     "flight-recorder bundle")
        telemetry.flight().enabled = False
        return fails
    bad = telemetry.audit_jsonl(bundle)
    if bad:
        tid, problems = next(iter(bad.items()))
        fails.append(f"obs flight: bundle has {len(bad)} bad span trees "
                     f"(e.g. {tid}: {problems})")
    with open(bundle) as f:
        recs = [_json.loads(line) for line in f if line.strip()]
    header = recs[0]
    if header.get("kind") != "flight" \
            or header.get("reason") != "breaker-open":
        fails.append(f"obs flight: bundle header is {header.get('kind')}/"
                     f"{header.get('reason')}, expected a breaker-open "
                     f"dump")
    fatal = [r for r in recs if r.get("kind") == "fault"
             and r.get("name") == "generate.decode"]
    if not fatal:
        fails.append("obs flight: the fatal generate.decode firing is "
                     "not in the bundle")
    if not any(r.get("kind") == "metrics" for r in recs):
        fails.append("obs flight: bundle carries no metrics snapshot")
    cs = telemetry.compile_site_stats("FlightGen")
    if cs["unexpected"] != 0:
        fails.append(f"obs flight: {cs['unexpected']} unexpected "
                     f"recompiles (must be 0)")
    if cs["misses"] != srv.census():
        fails.append(f"obs flight: {cs['misses']} compile events != "
                     f"census {srv.census()}")
    telemetry.flight().enabled = False
    print(f"[chaos_check] obs flight: accepted={len(accepted)} "
          f"bundle={os.path.basename(bundle)} records={len(recs)} "
          f"fault_recs={len(fatal)} compile_events={cs['misses']} "
          f"census={srv.census()} recompiles_unexpected="
          f"{cs['unexpected']}")
    return fails


def _obs_overhead_leg():
    """The off-switch bound: with telemetry disabled, the serving stack
    pays one module-attribute read + branch per instrumentation site.
    A/B wall-clock on a storm workload is hopelessly noisy at smoke
    scale, so the bound is measured deterministically: per-guard cost ×
    a generous guards-per-request budget must stay under 5% of the
    measured per-request latency of an untraced server."""
    import jax
    from mxnet_tpu import serving, telemetry

    telemetry.disable()

    @jax.jit
    def f(x):
        return x * 2.0

    srv = serving.InferenceServer(
        lambda x: np.asarray(f(x)), buckets=(1, 2, 4), max_delay=0.002,
        sample=np.zeros((3,), np.float32), name="ObsBase")
    srv.start()
    n, wave = 200, 50                # waves stay inside the admit queue
    t0 = time.perf_counter()
    for lo in range(0, n, wave):
        reqs = [srv.submit(np.full((3,), float(i % 7), np.float32))
                for i in range(lo, lo + wave)]
        for r in reqs:
            r.result(30)
    per_request = (time.perf_counter() - t0) / n
    srv.drain()

    per_guard = telemetry.guard_cost()
    # every instrumentation site on the longest path (admit, offer,
    # queue pop, coalesce, step, resolution, done-callback…) is well
    # under this budget
    guards_per_request = 64
    frac = per_guard * guards_per_request / per_request
    print(f"[chaos_check] obs overhead: per_guard={per_guard * 1e9:.1f}ns "
          f"x {guards_per_request} guards vs per_request="
          f"{per_request * 1e6:.0f}us -> {frac * 100:.3f}% (< 5% required)")
    if frac >= 0.05:
        return [f"obs overhead: off-switch costs {frac * 100:.2f}% of "
                f"request latency (>= 5%)"]
    return []


def obs_mode(args):
    """Traced storm + replica kill + fault burst: zero dropped accepted
    requests, 100% complete span trees, attribution within tolerance,
    JSONL export audit-clean, off-switch overhead bounded (ISSUE 13)."""
    import tempfile as _tempfile

    from mxnet_tpu import telemetry

    d = _tempfile.mkdtemp(prefix="chaos_obs_")
    sink_path = os.path.join(d, "spans.jsonl")
    telemetry.enable(sample=1.0, sink=sink_path, collect=True,
                     collect_limit=65536)
    try:
        fails, n_fleet = _obs_fleet_leg()
        fails += _obs_llm_leg()
        fails += _obs_flight_leg()
    finally:
        telemetry.disable()
        telemetry.config().sink.close()
        telemetry.config().sink = None
        telemetry.flight().enabled = False
    # the JSONL export must reconstruct to the same clean trees
    bad_jsonl = telemetry.audit_jsonl(sink_path)
    n_exported = len(telemetry.read_spans(sink_path))
    if bad_jsonl:
        tid, problems = next(iter(bad_jsonl.items()))
        fails.append(f"obs: JSONL round-trip has {len(bad_jsonl)} bad "
                     f"trees (e.g. {tid}: {problems})")
    fails += _obs_overhead_leg()
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: traced storm survived — 0 dropped "
          f"accepted requests, 100% complete span trees on all legs "
          f"({n_exported} trees exported + JSONL audit clean), "
          f"attribution within tolerance, breaker-trip flight bundle "
          f"audit-clean with 0 unexpected recompiles, off-switch "
          f"overhead < 5%")
    return 0


def slo_mode(args):
    """Mixed-tenant SLO storm + replica kill + autoscale cycle +
    rolling update, then the disaggregated-generation leg (ISSUE 12)."""
    fails = _slo_fleet_leg()
    fails += _slo_llm_leg()
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print("[chaos_check] PASS: mixed-tenant storm survived — 0 dropped "
          "accepted requests on both legs, abusive tenant isolated, "
          "per-class p99 ordering held, full autoscale cycle + rolling "
          "update under fire, census unchanged")
    return 0


def lint_mode(args):
    """Incremental-analyzer smoke: cold run, warm run, compare (ISSUE 5).

    Both runs cover the full gate surface (mxnet_tpu + tools +
    chip_smoke.py) with ALL findings serialized — suppressed ones included —
    so the byte-comparison covers the suppression/justification channel,
    not just the live-findings one.
    """
    import json
    import shutil

    from tools.analysis import analyze, to_sarif

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cache_dir = tempfile.mkdtemp(prefix="chaos_lint_cache_")
    paths = [os.path.join(root, "mxnet_tpu"),
             os.path.join(root, "tools"),
             os.path.join(root, "chip_smoke.py")]
    try:
        t0 = time.perf_counter()
        cold = analyze(paths, root=root, use_cache=True,
                       cache_dir=cache_dir)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = analyze(paths, root=root, use_cache=True,
                       cache_dir=cache_dir)
        warm_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    cold_json = json.dumps([f.to_dict() for f in cold], sort_keys=True)
    warm_json = json.dumps([f.to_dict() for f in warm], sort_keys=True)
    speedup = cold_s / warm_s if warm_s > 0 else float("inf")
    print(f"[chaos_check] lint: cold={cold_s:.2f}s warm={warm_s:.2f}s "
          f"speedup={speedup:.1f}x findings={len(cold)} "
          f"(live={sum(1 for f in cold if not f.suppressed)})")
    fails = []
    if cold_json != warm_json:
        fails.append("cached re-run changed the findings (byte mismatch)")
    if to_sarif(cold) != to_sarif(warm):
        fails.append("cached re-run changed the SARIF serialization")
    if speedup < 5.0:
        fails.append(f"cached re-run only {speedup:.1f}x faster (< 5x): "
                     f"the cache is not actually short-circuiting")
    if cold_s > 30.0:
        fails.append(f"cold full-tree run took {cold_s:.1f}s (> 30s "
                     f"budget)")
    if warm_s > 5.0:
        fails.append(f"warm run took {warm_s:.1f}s (> 5s budget)")
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: warm run {speedup:.1f}x faster, "
          f"byte-identical findings")
    return 0


def cost_mode(args):
    """Cold-vs-warm budget audit over every committed budget (ISSUE 6).

    The costguard report cache is keyed by a hash of the LOWERED HLO
    text, so the warm run still builds and lowers every entry point
    (that work is what proves the cache key matches the code) but must
    skip every XLA compile.  A cache that changes a verdict — or that
    does not actually shortcut the compiles — fails here.
    """
    import shutil

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from tools import costguard

    cache_dir = tempfile.mkdtemp(prefix="chaos_cost_cache_")
    try:
        t0 = time.perf_counter()
        cold = costguard.run_check(root=root, use_cache=True,
                                   cache_dir=cache_dir)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = costguard.run_check(root=root, use_cache=True,
                                   cache_dir=cache_dir)
        warm_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    speedup = cold_s / warm_s if warm_s > 0 else float("inf")
    n = len(cold.entries)
    sharded = [e for e in cold.entries
               if (e.report.get("per_device") or {}).get("n_devices",
                                                         1) > 1]
    print(f"[chaos_check] cost: cold={cold_s:.2f}s warm={warm_s:.2f}s "
          f"speedup={speedup:.1f}x entries={n} "
          f"(sharded={len(sharded)}) "
          f"executables={sum(e.report['n_executables'] for e in cold.entries)}")
    fails = []
    if not cold.ok:
        fails.append("cold budget audit FAILED:\n" + cold.render())
    if not warm.ok:
        fails.append("warm budget audit FAILED:\n" + warm.render())
    if cold.to_json() != warm.to_json():
        fails.append("cached re-run changed the audit verdicts "
                     "(byte mismatch)")
    # ISSUE 11: the cold-vs-warm byte-identity must cover SHARDED
    # goldens too — per-device numbers ride the same report cache, and
    # a cache that dropped (or fabricated) a per_device section would
    # silently un-gate the ∝ 1/shards contracts
    if not sharded:
        fails.append("no sharded entry (per_device.n_devices > 1) in "
                     "the audited set — the per-device budget surface "
                     "is not covered")
    for e in sharded:
        pd = e.report["per_device"]
        if not (pd.get("argument_bytes", 0) > 0
                and pd.get("peak_bytes", 0) > 0):
            fails.append(f"sharded entry {e.name}: per_device bytes "
                         f"missing/zero ({pd}) — extraction went dark")
    if speedup < 1.5:
        fails.append(f"cached re-run only {speedup:.1f}x faster (< 1.5x): "
                     f"the report cache is not skipping compiles "
                     f"(lower/build still run warm — by design — so the "
                     f"bar is lower than lint's)")
    if cold_s > 150.0:
        fails.append(f"cold full audit took {cold_s:.1f}s (> 150s budget)")
    if warm_s > 75.0:
        fails.append(f"warm audit took {warm_s:.1f}s (> 75s budget)")
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: warm audit {speedup:.1f}x faster, "
          f"byte-identical verdicts, all {n} budgets green")
    return 0


def hlo_mode(args):
    """Cold-vs-warm structural-lint audit over every hloguard surface
    (ISSUE 18).

    Lowering every surface is deterministic and paid ONCE up front
    (``surfaces.build`` memoizes per process) so the cold/warm timings
    isolate exactly what the ``.hloguard_cache`` shortcuts: the
    parse/extract stage keyed by the lowered-text hash.  The warm run
    must come back byte-identical in verdicts — findings, suppressions
    and censuses included — and actually skip the parse.
    """
    import shutil

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    from tools import hloguard
    from tools.hloguard import surfaces as hlo_surfaces

    t0 = time.perf_counter()
    names = hlo_surfaces.names()
    n_programs = sum(len(hlo_surfaces.build(n).programs) for n in names)
    build_s = time.perf_counter() - t0

    cache_dir = tempfile.mkdtemp(prefix="chaos_hlo_cache_")
    try:
        t0 = time.perf_counter()
        cold = hloguard.run_check(root=root, use_cache=True,
                                  cache_dir=cache_dir)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = hloguard.run_check(root=root, use_cache=True,
                                  cache_dir=cache_dir)
        warm_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)

    speedup = cold_s / warm_s if warm_s > 0 else float("inf")
    n_sup = sum(1 for f in cold.findings if f.suppressed)
    print(f"[chaos_check] hlo: build={build_s:.2f}s cold={cold_s:.2f}s "
          f"warm={warm_s:.2f}s speedup={speedup:.1f}x "
          f"surfaces={len(cold.entries)} programs={n_programs} "
          f"(suppressed={n_sup})")
    fails = []
    if not cold.ok:
        fails.append("cold structural audit FAILED:\n" + cold.render())
    if not warm.ok:
        fails.append("warm structural audit FAILED:\n" + warm.render())
    if cold.to_json() != warm.to_json():
        fails.append("cached re-run changed the audit verdicts "
                     "(byte mismatch)")
    ungated = [e.name for e in cold.entries if not e.gated]
    if ungated:
        fails.append(f"surfaces not gated (golden/env mismatch): "
                     f"{ungated} — the audit went dark on them")
    if speedup < 5.0:
        fails.append(f"cached re-run only {speedup:.1f}x faster (< 5x): "
                     f"the facts cache is not skipping the parse "
                     f"(lowering is prebuilt, so parse/extract is all "
                     f"the cold run pays)")
    if cold_s > 60.0:
        fails.append(f"cold parse/extract audit took {cold_s:.1f}s "
                     f"(> 60s budget)")
    if warm_s > 10.0:
        fails.append(f"warm audit took {warm_s:.1f}s (> 10s budget)")
    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: warm audit {speedup:.1f}x faster, "
          f"byte-identical verdicts, all {len(cold.entries)} surfaces "
          f"structurally green")
    return 0


def elastic_mode(args):
    """Supervised-gang chaos (ISSUE 9): SIGKILL + SIGSTOP-hang +
    supervisor-SIGTERM legs over a real 2-worker CPU training gang."""
    import json
    import signal
    import threading

    from mxnet_tpu import elastic

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(root, "tests", "elastic_worker.py")
    fails = []

    def wait_for(pred, timeout, what):
        t0 = time.time()
        while time.time() - t0 < timeout:
            v = pred()
            if v:
                return v
            time.sleep(0.05)
        fails.append(f"timed out after {timeout}s waiting for {what}")
        return None

    def spawn_pids(sup, attempt):
        for rec in sup.log.records:
            if rec["event"] == "spawn" and rec["attempt"] == attempt:
                return rec["pids"]
        return None

    def hb_step(sup, rank, attempt):
        rec = elastic.read_heartbeats(sup.heartbeat_dir).get(rank)
        if rec and int(rec.get("attempt", -1)) == attempt:
            return int(rec["global_step"])
        return 0

    def assert_reaped(sup):
        pids = {p for r in sup.log.records if r["event"] == "spawn"
                for p in r["pids"]}
        for pid in sorted(pids):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                continue
            fails.append(f"worker pid {pid} leaked past supervisor exit")

    def build(td, target, max_restarts, env):
        return elastic.Supervisor(
            [sys.executable, worker], 2, platform="cpu",
            devices_per_worker=1, max_restarts=max_restarts,
            watchdog_secs=5.0, startup_grace_secs=180.0,
            graceful_secs=30.0, backoff_base=0.2,
            heartbeat_dir=os.path.join(td, "hb"),
            event_log=os.path.join(td, "events.jsonl"),
            progress_dir=os.path.join(td, "ckpt"),
            extra_env=dict(env, MXTPU_TARGET_STEP=str(target),
                           MXTPU_CKPT_DIR=os.path.join(td, "ckpt"),
                           PYTHONPATH=root + os.pathsep +
                           os.environ.get("PYTHONPATH", "")))

    # ---- leg A: SIGKILL one worker mid-epoch, SIGSTOP the other ----------
    target = 14
    td = tempfile.mkdtemp(prefix="chaos_elastic_")
    sup = build(td, target, max_restarts=2,
                env={"MXTPU_STEP_SLEEP": "0.15", "MXTPU_ROUNDTRIP": "1"})
    stopped = []

    def chaos_script():
        # SIGKILL rank 1 once attempt 0 committed real progress
        if wait_for(lambda: hb_step(sup, 1, 0) >= 5, 300,
                    "attempt 0 rank 1 to pass step 5") is None:
            sup.request_stop()
            return
        os.kill(spawn_pids(sup, 0)[1], signal.SIGKILL)
        print("[chaos_check] elastic: SIGKILLed rank 1 mid-epoch",
              flush=True)
        # SIGSTOP rank 0 of attempt 1 once it advanced further
        if wait_for(lambda: hb_step(sup, 0, 1) >= 9, 300,
                    "attempt 1 rank 0 to pass step 9") is None:
            sup.request_stop()
            return
        pid = spawn_pids(sup, 1)[0]
        os.kill(pid, signal.SIGSTOP)
        stopped.append(pid)
        print("[chaos_check] elastic: SIGSTOPed rank 0 (watchdog bait)",
              flush=True)

    t = threading.Thread(target=chaos_script)
    t.start()
    try:
        rc = sup.run()
    finally:
        t.join()
        for pid in stopped:        # belt+braces: never leave one stopped
            try:
                os.kill(pid, signal.SIGCONT)
            except ProcessLookupError:
                pass
    evs = [r["event"] for r in sup.log.records]
    final = elastic.latest_committed_step(sup.progress_dir)
    restarts = evs.count("restart")
    starts = [r["progress"] for r in sup.log.records
              if r["event"] == "spawn"]
    print(f"[chaos_check] elastic: rc={rc} final_step={final} "
          f"restarts={restarts} spawn_progress={starts} events={evs}")
    if rc != 0:
        fails.append(f"leg A: supervisor exited rc={rc}, wanted 0")
    if final is None or final < target:
        fails.append(f"leg A: committed step {final} < target {target}")
    if restarts != 2:
        fails.append(f"leg A: expected exactly 2 restarts "
                     f"(SIGKILL + watchdog), saw {restarts}")
    if "heartbeat-stale" not in evs:
        fails.append("leg A: the SIGSTOP never tripped the watchdog")
    if "giveup" in evs:
        fails.append("leg A: supervisor gave up inside budget")
    resumes = [s for s in starts[1:]]
    if any(s in (None, 0) for s in resumes):
        fails.append(f"leg A: a restart resumed from step 0: {starts}")
    if resumes != sorted(resumes) or len(set(resumes)) != len(resumes):
        fails.append(f"leg A: per-attempt resume steps not strictly "
                     f"increasing: {starts}")
    with open(sup.event_log) as f:
        for line in f:
            json.loads(line)       # every event line is valid JSON
    assert_reaped(sup)

    # ---- leg B: SIGTERM the supervisor itself ----------------------------
    td2 = tempfile.mkdtemp(prefix="chaos_elastic_term_")
    sup2 = build(td2, target=10_000, max_restarts=1,
                 env={"MXTPU_STEP_SLEEP": "0.15"})

    def term_script():
        if wait_for(lambda: hb_step(sup2, 0, 0) >= 4 and
                    hb_step(sup2, 1, 0) >= 4, 300,
                    "leg B workers to pass step 4") is None:
            sup2.request_stop()
            return
        os.kill(os.getpid(), signal.SIGTERM)
        print("[chaos_check] elastic: SIGTERMed the supervisor",
              flush=True)

    t2 = threading.Thread(target=term_script)
    t2.start()
    try:
        rc2 = sup2.run()
    finally:
        t2.join()
    evs2 = [r["event"] for r in sup2.log.records]
    statuses = [r["status"] for r in sup2.log.records
                if r["event"] == "worker-exit"]
    final2 = elastic.latest_committed_step(sup2.progress_dir)
    print(f"[chaos_check] elastic: SIGTERM leg rc={rc2} "
          f"statuses={statuses} snapshot_step={final2} events={evs2}")
    if rc2 != 0:
        fails.append(f"leg B: supervisor SIGTERM exit rc={rc2}, wanted 0")
    if "preempted" not in evs2 or "forward-sigterm" not in evs2:
        fails.append(f"leg B: missing forward-sigterm/preempted events: "
                     f"{evs2}")
    if statuses != ["preempted", "preempted"]:
        fails.append(f"leg B: workers did not snapshot-then-exit: "
                     f"{statuses}")
    if not final2:
        fails.append("leg B: no snapshot committed before exit")
    assert_reaped(sup2)

    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: SIGKILL + SIGSTOP-hang recovered within "
          f"budget ({restarts} restarts, resumes {resumes}, reached step "
          f"{final}); supervisor SIGTERM drained to {statuses} with "
          f"snapshot at step {final2}; 0 leaked workers")
    return 0


_CKPT_WORKER = r"""
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import jax
import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel.checkpoint import CheckpointManager

mx.random.seed(7)
net = nn.HybridSequential()
net.add(nn.Dense(16, activation="relu", in_units=8),
        nn.Dense(4, in_units=16))
net.initialize()
mesh = parallel.make_mesh(dp=len(jax.devices()))
step = parallel.TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                          mx.optimizer.create("adam"), mesh=mesh)
rng = np.random.RandomState(0)
x, y = rng.randn(16, 8).astype(np.float32), rng.randint(0, 4, (16,))
step(x, y)
mgr = CheckpointManager(step, sys.argv[1], every_n_steps=1, keep_last=4)
mgr.resume_latest()
while True:                     # snapshot storm until SIGKILLed
    step(x, y)
    mgr.maybe_save()
"""


def ckpt_mode(args):
    """Durable-checkpoint chaos (ISSUE 17): kill -9 mid-write storm +
    fault-armed bit-flip corruption + retention pruning under a live
    WeightUpdater.  Resume must always land on an intact digest-verified
    snapshot; corrupted bytes must never be trained on or served."""
    import signal
    import subprocess
    import tempfile as _tempfile

    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import fault, gluon, parallel, serving
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.checkpoint import (BitFlipInjection,
                                               CheckpointCorruptError,
                                               CheckpointManager,
                                               list_checkpoints,
                                               load_snapshot_params,
                                               resume_latest,
                                               verify_checkpoint)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fails = []

    def step_for(seed):
        mx.random.seed(seed)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
        net.initialize()
        mesh = parallel.make_mesh(dp=len(jax.devices()))
        return parallel.TrainStep(net,
                                  gluon.loss.SoftmaxCrossEntropyLoss(),
                                  mx.optimizer.create("adam"), mesh=mesh)

    rng = np.random.RandomState(0)
    x, y = rng.randn(16, 8).astype(np.float32), rng.randint(0, 4, (16,))
    survivor = step_for(99)
    survivor(x, y)                       # build once, reused every leg

    # ---- leg A: kill -9 a snapshot storm, repeatedly ---------------------
    d = _tempfile.mkdtemp(prefix="chaos_ckpt_")
    env = dict(os.environ, PYTHONPATH=root + os.pathsep +
               os.environ.get("PYTHONPATH", ""))
    kills = 3
    def newest(directory):
        cks = list_checkpoints(directory)
        return cks[-1][0] if cks else 0

    for round_no in range(kills):
        # retention caps the COUNT at keep_last, so progress is measured
        # by the newest committed num_update, not directory size
        before = newest(d)
        proc = subprocess.Popen([sys.executable, "-c", _CKPT_WORKER, d],
                                env=env)
        t0 = time.time()
        while newest(d) < before + 2 and \
                time.time() - t0 < 120 and proc.poll() is None:
            time.sleep(0.02)
        if proc.poll() is not None:
            fails.append(f"leg A round {round_no}: worker exited "
                         f"rc={proc.returncode} before the kill")
            break
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        cks = list_checkpoints(d)
        if newest(d) < before + 2:
            fails.append(f"leg A round {round_no}: storm advanced the "
                         f"newest snapshot from {before} to {newest(d)}, "
                         f"wanted >= {before + 2}")
        for _, path in cks:              # every COMMITTED name verifies —
            try:                         # atomic commit + fsync means a
                verify_checkpoint(path)  # kill can never corrupt one
            except Exception as exc:     # noqa: BLE001
                fails.append(f"leg A round {round_no}: committed snapshot "
                             f"{os.path.basename(path)} failed "
                             f"verification after kill -9: {exc}")
        n = resume_latest(survivor, d)
        if n is None:
            fails.append(f"leg A round {round_no}: resume found nothing")
        print(f"[chaos_check] ckpt: kill round {round_no}: "
              f"{len(cks)} committed, all verified, resumed at step {n}",
              flush=True)

    # ---- leg B: fault-armed bit-flip — damage, never poison --------------
    d2 = _tempfile.mkdtemp(prefix="chaos_ckpt_flip_")
    victim = step_for(7)
    victim(x, y)
    mgr2 = CheckpointManager(victim, d2, keep_last=10)
    mgr2.save()                          # intact
    good = int(victim._num_update)
    victim(x, y)
    with fault.inject("checkpoint.serialize", BitFlipInjection(), times=1):
        corrupt_path = mgr2.save()       # committed but digest-poisoned
    try:
        verify_checkpoint(corrupt_path)
        fails.append("leg B: verify_checkpoint passed a bit-flipped "
                     "snapshot")
    except CheckpointCorruptError:
        pass
    try:
        load_snapshot_params(corrupt_path)
        fails.append("leg B: load_snapshot_params served corrupted bytes")
    except CheckpointCorruptError:
        pass
    n = resume_latest(survivor, d2)
    if n != good:
        fails.append(f"leg B: resume landed on step {n}, wanted the "
                     f"older intact snapshot {good}")
    print(f"[chaos_check] ckpt: bit-flip rejected everywhere, resume "
          f"fell back to intact step {good}", flush=True)

    # ---- leg C: prune race + corrupt stream under a live updater ---------
    d3 = _tempfile.mkdtemp(prefix="chaos_ckpt_race_")
    trainer = step_for(7)
    trainer(x, y)
    # keep_last=1: retention prunes everything but the newest — the
    # tightest possible race against the polling reader
    mgr3 = CheckpointManager(trainer, d3, keep_last=1)
    mgr3.save()
    params, _ = load_snapshot_params(mgr3.checkpoints()[-1][1])
    shapes = [tuple(p.shape) for p in params]
    iw1, ib1 = shapes.index((16, 8)), shapes.index((16,))
    iw2, ib2 = shapes.index((4, 16)), shapes.index((4,))

    @jax.jit
    def fwd(p, xx):
        h = jnp.maximum(xx @ p[iw1].T + p[ib1], 0.0)
        return h @ p[iw2].T + p[ib2]

    applies = [serving.HotSwapApply(
        lambda p, xx: np.asarray(fwd(p, xx)), list(params))
        for _ in range(2)]
    fleet = serving.ServingFleet(applies, buckets=(1, 4), max_delay=0.002,
                                 sample=np.ones((8,), np.float32),
                                 name="ChaosCkptFleet")
    fleet.start()
    updater = serving.WeightUpdater(fleet, mgr3, poll=0.01)
    updater.start()
    corrupt_round = 3
    try:
        for round_no in range(1, 6):
            trainer(x, y)
            if round_no == corrupt_round:
                with fault.inject("checkpoint.serialize",
                                  BitFlipInjection(), times=1):
                    mgr3.save()
                t0 = time.time()
                while updater.skipped < 1 and time.time() - t0 < 30:
                    time.sleep(0.01)
                if updater.skipped < 1:
                    fails.append("leg C: the corrupt snapshot was never "
                                 "rejected by the updater")
            else:
                want_applied = updater.applied + 1
                mgr3.save()
                t0 = time.time()
                while updater.applied < want_applied and \
                        time.time() - t0 < 30:
                    time.sleep(0.01)
                if updater.applied < want_applied:
                    fails.append(f"leg C: rolling update {round_no} "
                                 f"dropped (applied={updater.applied}, "
                                 f"skipped={updater.skipped})")
        # deterministic prune-vs-reader race: the path vanishes between
        # discovery and read — stale (re-poll), never a bad snapshot
        pruned = os.path.join(d3, "ckpt-99999999.npz")
        final = mgr3.checkpoints()[-1][1]
        import shutil
        shutil.copy(final, pruned)
        os.remove(pruned)
        skipped_before = updater.skipped
        try:
            updater.update(pruned)
            fails.append("leg C: updating a pruned path did not raise")
        except serving.SnapshotPrunedError:
            pass
        except Exception as exc:        # noqa: BLE001
            fails.append(f"leg C: pruned path raised {type(exc).__name__}"
                         f" instead of SnapshotPrunedError: {exc}")
        if updater.skipped != skipped_before:
            fails.append("leg C: a pruned (stale) path was counted as a "
                         "skipped snapshot")
    finally:
        updater.stop(timeout=10)
        fleet.drain(timeout=10)
    # the fleet must serve the FINAL committed snapshot's weights — the
    # corrupt round's bytes must never have reached a replica
    want = np.asarray(fwd(
        [jnp.asarray(p) for p in
         load_snapshot_params(mgr3.checkpoints()[-1][1])[0]],
        np.ones((1, 8), np.float32)))[0]
    got = np.asarray(applies[0](np.ones((1, 8), np.float32)))[0]
    if not np.allclose(got, want):
        fails.append("leg C: replica does not serve the final intact "
                     "snapshot's weights")
    print(f"[chaos_check] ckpt: race leg applied={updater.applied} "
          f"skipped={updater.skipped} (corrupt stream rejected, prune "
          f"race re-polled)", flush=True)

    if fails:
        for f in fails:
            print(f"[chaos_check] FAIL: {f}")
        return 1
    print(f"[chaos_check] PASS: {kills} kill -9 rounds left only "
          f"verified-intact committed snapshots; bit-flip rejected by "
          f"verify/load/resume; live updater under keep_last=1 pruning "
          f"applied {updater.applied} updates, rejected the corrupt "
          f"one, and served the final intact weights")
    return 0


MODES = {
    "train": ("kill-and-resume training smoke (ISSUE 2)", None),
    "serve": ("inject-and-drain serving smoke (ISSUE 4)", serve_mode),
    "fleet": ("replica-kill + rolling weight updates + SIGTERM "
              "(ISSUES 7/8)", fleet_mode),
    "llm": ("decode-fault burst + SIGTERM mid-decode on the "
            "continuous-batching LLM server (ISSUE 10)", llm_mode),
    "lint": ("incremental-analyzer cold-vs-warm contract (ISSUE 5)",
             lint_mode),
    "cost": ("cold-vs-warm compiled-cost budget audit (ISSUE 6)",
             cost_mode),
    "hlo": ("cold-vs-warm structural HLO lint audit over every "
            "hloguard surface (ISSUE 18)", hlo_mode),
    "elastic": ("supervised-gang SIGKILL + SIGSTOP-hang + supervisor "
                "SIGTERM (ISSUE 9)", elastic_mode),
    "slo": ("mixed-tenant QoS storm + replica kill + autoscale cycle + "
            "rolling update, plus disaggregated prefill/decode "
            "(ISSUE 12)", slo_mode),
    "obs": ("traced storm + replica kill + fault burst: complete span "
            "trees, attribution sums, off-switch overhead bound "
            "(ISSUE 13)", obs_mode),
    "ckpt": ("kill -9 mid-write storm + armed bit-flip corruption + "
             "retention-prune race under a live WeightUpdater "
             "(ISSUE 17)", ckpt_mode),
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=tuple(MODES), default="train",
                    help="train: kill-and-resume; serve: inject-and-"
                         "drain; fleet: replica-kill + rolling weight "
                         "updates + SIGTERM; lint: incremental analyzer "
                         "contract; cost: cold-vs-warm budget audit; "
                         "elastic: supervised-gang chaos")
    ap.add_argument("--list-modes", action="store_true",
                    help="print the mode registry and exit")
    ap.add_argument("--steps", type=int, default=8,
                    help="total training steps in the reference run")
    ap.add_argument("--every", type=int, default=2,
                    help="checkpoint cadence (steps)")
    ap.add_argument("--keep", type=int, default=2,
                    help="retention: keep-last-K snapshots")
    ap.add_argument("--crash-after", type=int, default=None,
                    help="crash on this step call (default: steps//2 + 1)")
    ap.add_argument("--requests", type=int, default=25,
                    help="serve mode: requests per client thread")
    args = ap.parse_args(argv)
    if args.list_modes:
        for name, (desc, _) in MODES.items():
            print(f"{name:<10} {desc}")
        return 0
    mode_fn = MODES[args.mode][1]
    if mode_fn is not None:
        return mode_fn(args)
    crash_after = (args.crash_after if args.crash_after is not None
                   else args.steps // 2 + 1)

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import fault, gluon, parallel
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel.checkpoint import CheckpointManager, resume_latest

    def net(seed):
        mx.random.seed(seed)
        n = nn.HybridSequential()
        n.add(nn.Dense(16, activation="relu", in_units=8),
              nn.Dense(4, in_units=16))
        n.initialize()
        return n

    def step_for(seed):
        mesh = parallel.make_mesh(dp=len(jax.devices()))
        return parallel.TrainStep(net(seed),
                                  gluon.loss.SoftmaxCrossEntropyLoss(),
                                  mx.optimizer.create("adam"), mesh=mesh)

    rng = np.random.RandomState(0)
    batches = [(rng.randn(16, 8).astype(np.float32),
                rng.randint(0, 4, (16,))) for _ in range(args.steps)]

    print(f"[chaos_check] reference run: {args.steps} steps")
    ref = []
    ref_step = step_for(7)
    for x, y in batches:
        ref.append(float(ref_step(x, y).asnumpy()))

    d = tempfile.mkdtemp(prefix="chaos_check_")
    print(f"[chaos_check] victim run: checkpoints every {args.every} steps "
          f"to {d}, crash injected on step {crash_after}")
    victim = step_for(7)
    mgr = CheckpointManager(victim, d, every_n_steps=args.every,
                            keep_last=args.keep)
    crashed = False
    with fault.inject("step", RuntimeError("injected preemption"),
                      after_n=crash_after - 1):
        try:
            for x, y in batches:
                victim(x, y)
                mgr.maybe_save()
        except RuntimeError as exc:
            crashed = True
            print(f"[chaos_check] victim died as planned: {exc}")
    if not crashed:
        print("[chaos_check] FAIL: injected crash never fired")
        return 1
    del victim, mgr

    survivor = step_for(99)        # different init — checkpoint must win
    survivor(*batches[0])          # build/compile
    n = resume_latest(survivor, d)
    if n is None:
        print("[chaos_check] FAIL: resume_latest found no checkpoint")
        return 1
    print(f"[chaos_check] resumed from step {n}, replaying "
          f"{args.steps - n} steps")
    resumed = [float(survivor(x, y).asnumpy()) for x, y in batches[n:]]

    if resumed == ref[n:]:
        print(f"[chaos_check] PASS: resumed trajectory bit-exact over "
              f"{len(resumed)} steps")
        return 0
    diff = np.max(np.abs(np.array(resumed) - np.array(ref[n:])))
    print(f"[chaos_check] FAIL: trajectories diverge (max |diff|={diff})")
    print(f"  reference: {ref[n:]}")
    print(f"  resumed  : {resumed}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
