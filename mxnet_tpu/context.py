"""Device context model.

TPU-native equivalent of the reference's ``Context`` (ref: include/mxnet/base.h
— struct Context, Context::CPU/GPU).  A Context names a logical device;
placement is realised through JAX's device objects / shardings rather than CUDA
device ids.  ``mx.tpu()`` is the headline context; ``mx.cpu()`` maps to the XLA
CPU backend; ``mx.gpu()`` is accepted for API compatibility and resolves to an
accelerator if one exists.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "tpu", "gpu", "cpu_pinned", "current_context",
           "num_tpus", "num_gpus", "gpu_memory_info", "on_tpu"]

_tls = threading.local()


def on_tpu() -> bool:
    """THE predicate for "this process computes on a TPU": the Pallas
    kernels compile (not interpret) and paged attention takes the kernel
    (not the jnp gather) exactly when this holds; the benches and
    ``chip_smoke.py`` refuse to run when it does not."""
    return jax.default_backend() == "tpu"


def _accelerator_devices():
    """Local (addressable) TPU devices, or [] off-TPU.

    Uses ``jax.local_devices()`` — never the global ``jax.devices()`` — so
    that under ``jax.distributed`` each rank resolves onto a device it can
    actually address (device_put to a non-addressable device raises)."""
    return jax.local_devices() if on_tpu() else []


class Context:
    """A logical device. Usable as a context manager like the reference's.

    device_type in {'cpu', 'tpu', 'gpu', 'cpu_pinned', 'cpu_shared'}; 'gpu' and
    the pinned/shared cpu flavours are compat aliases that resolve onto the
    accelerator / cpu backends respectively.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    def __init__(self, device_type, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devstr2type:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution -------------------------------------------------------
    @property
    def device(self):
        """Resolve to a concrete jax.Device (fallback-tolerant for CI hosts)."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                devs = jax.local_devices()
            return devs[min(self.device_id, len(devs) - 1)]
        accel = _accelerator_devices()
        if accel:
            return accel[min(self.device_id, len(accel) - 1)]
        # No accelerator on this host (e.g. CPU-only test run): fall back.
        return jax.local_devices()[0]

    @property
    def real_device_type(self) -> str:
        return self.device.platform

    # -- protocol ---------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _tls.stack.pop()

    # MXNet API compat
    def empty_cache(self):
        """Free cached device memory (pool is managed by PJRT; best-effort)."""
        import gc

        gc.collect()

    def memory_info(self):
        """Device memory statistics from PJRT (the storage-manager
        introspection surface; ref: storage.cc GetMemoryPoolInfo /
        mx.context.gpu_memory_info).  Keys follow PJRT's memory_stats
        (bytes_in_use, peak_bytes_in_use, bytes_limit, ...); CPU backends
        without stats return the framework-side storage accounting only
        (mxnet_tpu/storage.py)."""
        stats = self.device.memory_stats()
        out = dict(stats) if stats else {}
        from . import storage
        out["framework_live_bytes"] = storage.live_bytes(str(self))
        out["framework_peak_bytes"] = storage.stats(str(self))["peak_bytes"]
        return out

    @classmethod
    def default_ctx(cls):
        return current_context()


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Compat alias: resolves to the accelerator backend on TPU hosts."""
    return Context("gpu", device_id)


def num_tpus() -> int:
    return len(_accelerator_devices())


def num_gpus() -> int:
    # API-compat: on a TPU host there are no CUDA devices.
    try:
        return len(jax.devices("gpu"))
    except RuntimeError:
        return 0


def gpu_memory_info(device_id: int = 0):
    """(free, total) bytes for the accelerator (ref: mx.context.
    gpu_memory_info; 'gpu' meaning the accelerator backend here)."""
    stats = Context("tpu", device_id).memory_info()
    if on_tpu():
        # the chip reports its own limit and allocator occupancy
        total, used = stats["bytes_limit"], stats["bytes_in_use"]
    else:
        # XLA:CPU has no memory_stats: the assumed HBM capacity minus
        # framework-accounted live bytes, so CPU rehearsals of memory
        # planning code see chip-sized numbers.  tpu(N)/gpu(N) are compat
        # aliases for the same accelerator, so sum both accounting keys.
        from . import config, storage
        total = int(config.get("MXNET_TPU_HBM_CAPACITY_MB")) << 20
        used = (storage.live_bytes(f"tpu({device_id})")
                + storage.live_bytes(f"gpu({device_id})"))
    return (max(0, total - used), total)


def current_context() -> Context:
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return tpu(0) if _accelerator_devices() else cpu(0)
