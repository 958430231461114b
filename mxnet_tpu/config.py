"""Environment-variable configuration (the ``MXNET_*`` knob system).

ref: docs/static_site/src/pages/api/faq/env_var.md + the ``dmlc::GetEnv``
pattern used throughout src/ — every tunable behavior is controlled by an
``MXNET_*`` environment variable with a documented default.

This module is the single registry: each knob declares its type, default,
and what it drives.  Knobs whose reference meaning is subsumed by XLA/PJRT
(thread pools, GPU memory pools, cuDNN autotune) are registered as
``accepted`` so reference launch scripts run unchanged, but changing them
is a documented no-op here.  ``describe()`` prints the full table.
"""
from __future__ import annotations

import os

__all__ = ["get", "describe", "KNOBS", "setup_compile_cache"]


class Knob:
    __slots__ = ("name", "default", "type", "doc", "wired")

    def __init__(self, name, default, type_, doc, wired=True):
        self.name = name
        self.default = default
        self.type = type_
        self.doc = doc
        self.wired = wired


def _as_bool(v):
    return str(v).lower() in ("1", "true", "yes", "on")


KNOBS = {k.name: k for k in [
    # --- live knobs (change behavior in this build) ----------------------
    Knob("MXNET_ENGINE_TYPE", "ThreadedEnginePerDevice", str,
         "Execution engine. 'NaiveEngine' forces synchronous dispatch "
         "(every op blocks until complete) — the reference's race-bisect "
         "debugging mode (SURVEY §5.2)."),
    Knob("MXNET_CPU_WORKER_NTHREADS", 0, int,
         "Default DataLoader worker-process count when num_workers is not "
         "passed (0 = in-process loading)."),
    Knob("MXNET_PROFILER_AUTOSTART", 0, int,
         "1 = start the profiler at import; dump to MXNET_PROFILER_FILENAME "
         "at exit."),
    Knob("MXNET_PROFILER_FILENAME", "profile.json", str,
         "Trace output path for the autostarted profiler."),
    Knob("MXNET_SEED", None, int,
         "Global PRNG seed applied at import (mx.random.seed)."),
    # --- accepted for compatibility (no-ops under XLA/PJRT, documented) --
    Knob("MXNET_STORAGE_FALLBACK_LOG_VERBOSE", 1, int,
         "No silent sparse→dense fallback exists here: dense-only ops raise "
         "a storage-type error instead (mxnet_tpu/ndarray).", wired=False),
    Knob("MXNET_KVSTORE_BIGARRAY_BOUND", 1000000, int,
         "Server-side big-array sharding bound — no parameter servers here "
         "(collectives over ICI/DCN).", wired=False),
    Knob("MXNET_EXEC_BULK_EXEC_TRAIN", 1, int,
         "Engine bulking — subsumed by hybridize/jit whole-graph compile.",
         wired=False),
    Knob("MXNET_EXEC_BULK_EXEC_INFERENCE", 1, int,
         "Engine bulking — subsumed by jit.", wired=False),
    Knob("MXNET_GPU_MEM_POOL_RESERVE", 5, int,
         "Percent of MXNET_HOST_MEM_POOL_LIMIT_MB kept out of the host "
         "staging-buffer pool (device HBM itself is managed by PJRT; see "
         "mxnet_tpu/storage.py)."),
    Knob("MXNET_GPU_MEM_POOL_TYPE", "Naive", str,
         "Host staging-buffer pool strategy: Naive (exact-size buckets), "
         "Round (pow2 buckets below the linear cutoff), or Unpooled "
         "(ref: pooled_storage_manager.h; device HBM stays with PJRT)."),
    Knob("MXNET_GPU_MEM_POOL_ROUND_LINEAR_CUTOFF", 24, int,
         "Round-pool strategy: sizes below 2^cutoff round to a power of "
         "two; above, to a page multiple."),
    Knob("MXNET_HOST_MEM_POOL_LIMIT_MB", 256, int,
         "Upper bound on host staging buffers retained by the pool."),
    Knob("MXNET_ENGINE_TRACK_BYTES_MB", 64, int,
         "Byte budget for the waitall tracking ring: newest arrays are "
         "held (strongly) up to this budget so waitall stays a true "
         "barrier without pinning unbounded HBM."),
    Knob("MXNET_STORAGE_ACCOUNTING", 1, int,
         "1 = every NDArray registers its bytes with the storage manager "
         "(mx.storage.stats(), gpu_memory_info fallback); 0 disables."),
    Knob("MXNET_TPU_HBM_CAPACITY_MB", 16384, int,
         "Assumed per-chip HBM capacity when the PJRT plugin reports no "
         "memory_stats (v5e = 16 GB); used by gpu_memory_info."),
    Knob("MXNET_CUDNN_AUTOTUNE_DEFAULT", 1, int,
         "cuDNN algo search — XLA picks conv strategies at compile time.",
         wired=False),
    Knob("MXNET_ENFORCE_DETERMINISM", 0, int,
         "XLA TPU execution is deterministic by construction.", wired=False),
    Knob("MXNET_SAFE_ACCUMULATION", 1, int,
         "Wide-accumulator reductions — always on (norm ops accumulate in "
         "f32 regardless; see ops/nn.py _moments).", wired=False),
    Knob("MXNET_GPU_WORKER_NTHREADS", 2, int,
         "Per-GPU worker threads — PJRT streams replace them.", wired=False),
]}


def get(name, default=None):
    """Typed read of a knob (env var wins over registry default)."""
    knob = KNOBS.get(name)
    raw = os.environ.get(name)
    if knob is None:
        return raw if raw is not None else default
    if raw is None:
        return knob.default if default is None else default
    if knob.type is int:
        try:
            return int(raw)
        except ValueError:
            return knob.default
    if knob.type is bool:
        return _as_bool(raw)
    return raw


def describe():
    """Render the knob table (ref: env_var.md)."""
    out = [f"{'variable':<38s}{'default':<26s}{'wired':<7s}description"]
    for k in KNOBS.values():
        out.append(f"{k.name:<38s}{str(k.default):<26s}"
                   f"{'yes' if k.wired else 'n/a':<7s}{k.doc}")
    return "\n".join(out)


def setup_compile_cache():
    """Point JAX's persistent compilation cache somewhere a later process
    finds again, and return the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` wins: jax reads it itself, so nothing is
    set here and whoever runs the program places the cache.  Otherwise
    the cache lives at ``<checkout>/.jax_compile_cache`` — a fixed path,
    never a temp name, pid or time, because a cache that moves never
    hits.  Every executable is kept (0 s floor): a generation server is
    a handful of programs of a few seconds each, a model's initializer
    program one, the eager ops around them sub-second ones, and a warm
    start should recompile none of them.  Also registers ``watch_compiles()``.
    Entry points call this before their first compile (``chip_smoke.py``,
    ``chipbench/run.py``, ``tests_tpu/``, the examples)."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_compile_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    watch_compiles()
    return cache_dir


_WATCHING = False


def watch_compiles():
    """Feed ``telemetry.compile_stats()``'s always-on counters from jax's
    own monitoring events (seconds tracing, lowering, compiling or loading;
    executables created; persistent-cache hits, misses, seconds saved and
    seconds reading).  Registered once per process; the listeners run only
    when jax traces, lowers, compiles or loads."""
    global _WATCHING
    if _WATCHING:
        return
    _WATCHING = True
    from jax import monitoring

    from . import telemetry
    monitoring.register_event_listener(telemetry.note_jax_event)
    monitoring.register_event_duration_secs_listener(telemetry.note_jax_event)
    monitoring.register_scalar_listener(telemetry.note_jax_region)


def _apply_startup():
    """Run once at package import: knobs that act at process start."""
    seed = get("MXNET_SEED")
    if seed is not None:
        from . import random as _random
        _random.seed(int(seed))
    if not get("MXNET_STORAGE_ACCOUNTING"):
        from . import storage
        storage.set_accounting(False)
    if get("MXNET_PROFILER_AUTOSTART"):
        import atexit

        from . import profiler
        profiler.set_config(filename=get("MXNET_PROFILER_FILENAME"))
        profiler.start()
        atexit.register(lambda: (profiler.stop(), profiler.dump()))


def naive_engine():
    """True when MXNET_ENGINE_TYPE=NaiveEngine (synchronous dispatch)."""
    return get("MXNET_ENGINE_TYPE") == "NaiveEngine"
