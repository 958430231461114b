"""Storage manager — memory accounting + pooled host staging buffers.

ref: src/storage/storage.cc — ``Storage::Get()->Alloc/Free``;
src/storage/pooled_storage_manager.h — ``GPUPooledStorageManager`` (naive
exact-size buckets) and ``GPUPooledRoundedStorageManager`` (power-of-two
buckets below ``MXNET_GPU_MEM_POOL_ROUND_LINEAR_CUTOFF``); knobs
``MXNET_GPU_MEM_POOL_TYPE`` / ``MXNET_GPU_MEM_POOL_RESERVE``.

TPU substitution: device (HBM) allocation inside compiled programs is
planned by XLA and owned by PJRT — a user-level HBM pool would fight the
runtime, so this build does NOT re-implement device pooling.  What stays
the framework's job, and what this module provides:

1. **Device-side accounting.**  Every live ``NDArray`` registers its
   buffer bytes here, so live / peak / alloc-count introspection
   (``storage.stats()``, ``mx.context.gpu_memory_info``) works even where
   the backend reports no ``memory_stats`` (XLA:CPU reports none; the
   TPU does, and ``gpu_memory_info`` uses it there).  Counts are *logical tensor bytes held by the framework* — XLA
   scratch and executable temps are intentionally out of scope (they are
   visible via ``Context.memory_info`` where the plugin supports it).

2. **Pooled host staging buffers.**  The data pipeline's batchify/pin
   path and RecordIO readers reuse page-sized numpy buffers instead of
   malloc churn, with the reference's two pooling strategies selected by
   ``MXNET_GPU_MEM_POOL_TYPE``: ``Naive`` (exact-size free-lists) and
   ``Round`` (power-of-two buckets below the linear cutoff).
   ``MXNET_GPU_MEM_POOL_RESERVE`` caps the pool the same way the
   reference reserves a fraction of device memory: the pool holds at most
   ``(100 - reserve)%`` of ``MXNET_HOST_MEM_POOL_LIMIT_MB``.
"""
from __future__ import annotations

import threading
import weakref

import numpy as np
from jax import core as _jax_core

__all__ = ["Storage", "Handle", "stats", "reset_peak", "pool_info",
           "release_all", "on_create"]


# ---------------------------------------------------------------------------
# device-side accounting
# ---------------------------------------------------------------------------

class _DeviceStats:
    __slots__ = ("live_bytes", "peak_bytes", "num_allocs", "num_frees",
                 "live_arrays")

    def __init__(self):
        self.live_bytes = 0
        self.peak_bytes = 0
        self.num_allocs = 0
        self.num_frees = 0
        self.live_arrays = 0

    def as_dict(self):
        return {"live_bytes": self.live_bytes,
                "peak_bytes": self.peak_bytes,
                "num_allocs": self.num_allocs,
                "num_frees": self.num_frees,
                "live_arrays": self.live_arrays}


_lock = threading.Lock()
_by_device: dict[str, _DeviceStats] = {}
# Buffers currently accounted, by id().  The finalizer is attached to the
# BUFFER (jax/numpy array), not the NDArray wrapper: wrappers rebind
# ``_data`` freely (in-place ops, out=, jit write-back) and several
# wrappers can share one buffer (detach()) — tying lifetime to the buffer
# makes the count exact under both, and id() reuse is safe because an
# entry is removed at the instant its buffer is collected.
_registered: set[int] = set()
_enabled = True


def set_accounting(enabled: bool):
    """Toggle per-NDArray accounting (MXNET_STORAGE_ACCOUNTING knob)."""
    global _enabled
    _enabled = bool(enabled)


def _dec(devkey: str, nbytes: int, bufkey: int):
    with _lock:
        if bufkey not in _registered:
            return
        _registered.discard(bufkey)
        st = _by_device.get(devkey)
        if st is not None:
            st.live_bytes -= nbytes
            st.num_frees += 1
            st.live_arrays -= 1


def on_create(nd) -> None:
    """Register the buffer behind a freshly constructed NDArray.

    Called from ``NDArray.__init__``; must stay cheap.  Tracers (abstract
    values inside jit) and zero-size arrays are skipped; a buffer already
    seen (shared or re-wrapped) costs one set lookup.
    """
    if not _enabled:
        return
    data = nd._data
    if isinstance(data, _jax_core.Tracer):
        return  # abstract value inside jit/vjp tracing — no buffer exists
    nbytes = getattr(data, "nbytes", None)
    if not nbytes or not isinstance(nbytes, int):
        return
    devkey = str(nd._ctx)
    bufkey = id(data)
    with _lock:
        if bufkey in _registered:
            return
        _registered.add(bufkey)
        st = _by_device.get(devkey)
        if st is None:
            st = _by_device[devkey] = _DeviceStats()
        st.live_bytes += nbytes
        st.num_allocs += 1
        st.live_arrays += 1
        if st.live_bytes > st.peak_bytes:
            st.peak_bytes = st.live_bytes
    try:
        weakref.finalize(data, _dec, devkey, nbytes, bufkey)
    except TypeError:  # non-weakref-able buffer type: drop the entry
        _dec(devkey, nbytes, bufkey)


def stats(device=None):
    """Per-device accounting snapshot.

    ``stats()`` → ``{devkey: {live_bytes, peak_bytes, ...}}``;
    ``stats(ctx_or_key)`` → the one device's dict (zeros if unseen).
    """
    with _lock:
        if device is None:
            return {k: v.as_dict() for k, v in _by_device.items()}
        key = device if isinstance(device, str) else str(device)
        st = _by_device.get(key)
        return st.as_dict() if st is not None else _DeviceStats().as_dict()


def live_bytes(device=None) -> int:
    with _lock:
        if device is None:
            return sum(st.live_bytes for st in _by_device.values())
        key = device if isinstance(device, str) else str(device)
        st = _by_device.get(key)
        return st.live_bytes if st is not None else 0


def reset_peak():
    """Reset peak watermarks to current live bytes (profiler epoch reset)."""
    with _lock:
        for st in _by_device.values():
            st.peak_bytes = st.live_bytes


# ---------------------------------------------------------------------------
# pooled host staging buffers
# ---------------------------------------------------------------------------

class Handle:
    """An allocated host buffer (ref: ``Storage::Handle`` — dptr/size/ctx)."""

    __slots__ = ("dptr", "size", "ctx", "_bucket", "_ptr", "_fin",
                 "__weakref__")

    def __init__(self, dptr, size, ctx, bucket, ptr=None):
        self.dptr = dptr          # numpy uint8 view, length == size
        self.size = size
        self.ctx = ctx
        self._bucket = bucket     # rounded size the pool stores it under
        self._ptr = ptr           # native pool address (None: python pool)
        self._fin = None          # leak guard for native buffers


def _pool_config():
    """(strategy, round_cutoff, limit_bytes) from the MXNET_* knobs —
    shared by the python and native pools so the reserve formula lives
    in one place."""
    from . import config
    strategy = str(config.get("MXNET_GPU_MEM_POOL_TYPE") or "Naive")
    cutoff = int(config.get("MXNET_GPU_MEM_POOL_ROUND_LINEAR_CUTOFF") or 24)
    reserve = int(config.get("MXNET_GPU_MEM_POOL_RESERVE") or 5)
    limit_mb = int(config.get("MXNET_HOST_MEM_POOL_LIMIT_MB") or 256)
    limit = limit_mb * (1 << 20) * max(0, 100 - reserve) // 100
    return strategy, cutoff, limit


class _HostPool:
    """Free-list pool over page-sized numpy buffers.

    Strategies (MXNET_GPU_MEM_POOL_TYPE):
      - ``Naive``:  exact-size buckets (GPUPooledStorageManager);
      - ``Round``:  power-of-two buckets below ``2**cutoff``, linear
        (page-rounded) above (GPUPooledRoundedStorageManager);
      - ``Unpooled``: passthrough malloc/free.
    """

    PAGE = 4096

    def __init__(self):
        self._free: dict[int, list[np.ndarray]] = {}
        self._held = 0          # bytes sitting in free lists
        self._hits = 0
        self._misses = 0
        self._lock = threading.Lock()
        self._configured = False
        self._strategy = "Naive"
        self._cutoff = 24
        self._limit = 0

    def _configure(self):
        self._strategy, self._cutoff, self._limit = _pool_config()
        self._configured = True

    def _bucket_of(self, nbytes: int) -> int:
        if self._strategy == "Round":
            if nbytes <= 0:
                return self.PAGE
            if nbytes < (1 << self._cutoff):
                return 1 << max(nbytes - 1, 1).bit_length()
            # linear region: round up to page
        return -(-max(nbytes, 1) // self.PAGE) * self.PAGE

    def alloc(self, nbytes: int, ctx=None) -> Handle:
        if not self._configured:
            self._configure()
        if self._strategy == "Unpooled":
            buf = np.empty(max(nbytes, 1), dtype=np.uint8)
            return Handle(buf[:nbytes], nbytes, ctx, -1)
        bucket = self._bucket_of(nbytes)
        with self._lock:
            lst = self._free.get(bucket)
            if lst:
                buf = lst.pop()
                self._held -= bucket
                self._hits += 1
            else:
                buf = None
                self._misses += 1
        if buf is None:
            buf = np.empty(bucket, dtype=np.uint8)
        return Handle(buf[:nbytes], nbytes, ctx, bucket)

    def free(self, handle: Handle):
        with self._lock:
            if handle._bucket < 0:
                return
            buf = (handle.dptr.base if handle.dptr.base is not None
                   else handle.dptr)
            # guard fields flip under the lock so concurrent frees of one
            # handle cannot both pass
            bucket, handle._bucket = handle._bucket, -1
            handle.dptr = None  # view must not outlive the pooled buffer
            if self._held + bucket > self._limit:
                return  # over reserve cap — drop to the allocator
            self._free.setdefault(bucket, []).append(buf)
            self._held += bucket

    def direct_free(self, handle: Handle):
        with self._lock:
            handle._bucket = -1  # numpy buffer: the GC reclaims it

    def release_all(self):
        with self._lock:
            self._free.clear()
            self._held = 0

    def info(self):
        with self._lock:
            return {"strategy": self._strategy, "native": False,
                    "held_bytes": self._held,
                    "limit_bytes": self._limit,
                    "hits": self._hits,
                    "misses": self._misses,
                    "buckets": {k: len(v) for k, v in self._free.items()}}


class _NativePool:
    """ctypes binding over src/storage_pool.cc (the native free-list pool,
    parity with the reference's C++ pooled storage managers).  Same
    interface as ``_HostPool``; selected automatically when the shared
    object builds/loads, unless the strategy is Unpooled."""

    def __init__(self, lib):
        self._lib = lib
        self._pool = None
        self._strategy = "Naive"
        self._limit = 0
        self._lock = threading.Lock()

    def _configure(self):
        self._strategy, cutoff, limit = _pool_config()
        self._limit = limit
        self._pool = self._lib.sp_create(
            1 if self._strategy == "Round" else 0, limit, cutoff)

    def alloc(self, nbytes: int, ctx=None) -> Handle:
        import ctypes
        with self._lock:
            if self._pool is None:
                self._configure()
        if self._strategy == "Unpooled":
            buf = np.empty(max(nbytes, 1), dtype=np.uint8)
            return Handle(buf[:nbytes], nbytes, ctx, -1)
        bucket = ctypes.c_int64(0)
        ptr = self._lib.sp_alloc(self._pool, max(nbytes, 1),
                                 ctypes.byref(bucket))
        if not ptr:
            raise MemoryError(f"native pool: alloc({nbytes}) failed")
        cbuf = (ctypes.c_uint8 * bucket.value).from_address(ptr)
        arr = np.frombuffer(cbuf, dtype=np.uint8, count=bucket.value)
        handle = Handle(arr[:nbytes], nbytes, ctx, bucket.value, ptr)
        # A dropped handle must not leak the malloc'd block (the python
        # pool's numpy buffers are GC-owned; native ones are not).  The
        # finalizer rides the base VIEW, not the Handle: any escaped
        # dptr-derived view keeps `arr` alive through its .base chain, so
        # GC reclamation can never free memory a live view still sees.
        # Explicit free()/direct_free() detach it (the caller asserts no
        # views remain — the documented pool contract).
        handle._fin = weakref.finalize(arr, self._lib.sp_free,
                                       self._pool, ptr, bucket.value)
        return handle

    def _sever(self, handle: Handle):
        """Detach handle fields under the lock; returns (ptr, bucket) or
        (None, -1) if another thread already freed it."""
        with self._lock:
            # detach BEFORE dropping dptr: clearing the view may collect
            # the base array immediately (refcounting) and a still-armed
            # finalizer would return the buffer a second time
            fin, handle._fin = handle._fin, None
            if fin is not None:
                fin.detach()
            ptr, handle._ptr = handle._ptr, None
            bucket, handle._bucket = handle._bucket, -1
            handle.dptr = None
            return ptr, bucket

    def free(self, handle: Handle):
        ptr, bucket = self._sever(handle)
        if ptr is not None:
            self._lib.sp_free(self._pool, ptr, bucket)

    def direct_free(self, handle: Handle):
        ptr, _ = self._sever(handle)
        if ptr is not None:
            self._lib.sp_free(self._pool, ptr, -1)

    def release_all(self):
        if self._pool is not None:
            self._lib.sp_release_all(self._pool)

    def info(self):
        import ctypes
        held = ctypes.c_int64(0)
        hits = ctypes.c_int64(0)
        misses = ctypes.c_int64(0)
        if self._pool is not None:
            self._lib.sp_info(self._pool, ctypes.byref(held),
                              ctypes.byref(hits), ctypes.byref(misses))
        return {"strategy": self._strategy, "native": True,
                "held_bytes": held.value, "limit_bytes": self._limit,
                "hits": hits.value, "misses": misses.value,
                "buckets": {}}  # native pool does not expose per-bucket fill


def _load_native_pool():
    """dlopen src/storage_pool.cc's library (building if needed), or None."""
    import ctypes

    from .base import load_native_lib
    lib = load_native_lib("libstoragepool.so", "storage_pool.cc")
    if lib is None:
        return None
    lib.sp_create.restype = ctypes.c_void_p
    lib.sp_create.argtypes = [ctypes.c_int, ctypes.c_int64, ctypes.c_int]
    lib.sp_alloc.restype = ctypes.c_void_p
    lib.sp_alloc.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                             ctypes.POINTER(ctypes.c_int64)]
    lib.sp_free.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64]
    lib.sp_release_all.argtypes = [ctypes.c_void_p]
    lib.sp_info.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
                            ctypes.POINTER(ctypes.c_int64),
                            ctypes.POINTER(ctypes.c_int64)]
    lib.sp_destroy.argtypes = [ctypes.c_void_p]
    return _NativePool(lib)


_pool = _load_native_pool() or _HostPool()


class Storage:
    """Singleton facade matching the reference's ``Storage::Get()`` API."""

    _instance = None

    @classmethod
    def get(cls) -> "Storage":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def alloc(self, size: int, ctx=None) -> Handle:
        return _pool.alloc(size, ctx)

    def free(self, handle: Handle):
        _pool.free(handle)

    def direct_free(self, handle: Handle):
        """Bypass the pool (ref: Storage::DirectFree)."""
        _pool.direct_free(handle)

    def release_all(self, ctx=None):
        _pool.release_all()

    def stats(self, device=None):
        return stats(device)

    def pool_info(self):
        return _pool.info()


def pool_info():
    return _pool.info()


def release_all():
    _pool.release_all()
