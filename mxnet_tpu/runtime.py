"""mx.runtime — build/runtime feature detection.

ref: python/mxnet/runtime.py — ``Features()`` exposes which optional
capabilities this build has (the reference reports CUDA/CUDNN/MKLDNN/...;
here the meaningful axes are the accelerator backend, Pallas, and the
native components)."""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    __slots__ = ("name", "enabled")

    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return f"[{'✔' if self.enabled else '✖'} {self.name}]"


def _detect():
    import jax

    feats = {}

    def add(name, enabled):
        feats[name] = Feature(name, enabled)

    from .context import on_tpu
    add("TPU", on_tpu())
    add("CPU", True)
    add("CUDA", jax.default_backend() in ("gpu", "cuda"))
    add("CUDNN", False)
    add("MKLDNN", False)
    add("BF16", True)           # native on TPU; emulated on XLA:CPU
    add("INT8", True)           # quantized ops (ops/quantization.py)
    try:
        from jax.experimental import pallas  # noqa: F401
        add("PALLAS", True)
    except Exception:
        add("PALLAS", False)
    from .base import load_native_lib
    add("RECORDIO_NATIVE",
        load_native_lib("librecordio.so", "recordio.cc") is not None)
    add("STORAGE_POOL_NATIVE",
        load_native_lib("libstoragepool.so", "storage_pool.cc") is not None)
    add("DIST_KVSTORE", True)   # jax.distributed-backed dist_* types
    add("ONNX", True)
    add("PROFILER", True)
    return feats


class Features(dict):
    """ref: runtime.Features — dict of Feature with is_enabled()."""

    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, name):
        f = self.get(name)
        if f is None:
            raise RuntimeError(f"unknown feature {name!r}; known: "
                               f"{sorted(self)}")
        return f.enabled

    def __repr__(self):
        return " ".join(repr(f) for f in self.values())


def feature_list():
    """ref: libinfo.features."""
    return list(Features().values())
