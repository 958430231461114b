"""Device mesh management.

ref: the reference scales via KVStore device lists (src/kvstore/comm.h —
CommDevice over ctx lists) and `group2ctx` device groups
(src/executor/graph_executor.cc — AssignContext).  TPU-native, placement is a
`jax.sharding.Mesh` with named axes; every parallelism strategy is an axis:

    dp    data parallel (batch sharded; grads all-reduced by XLA over ICI)
    fsdp  ZeRO-style parameter sharding on top of dp traffic
    tp    tensor parallel (megatron-style sharded matmuls)
    pp    pipeline parallel (stage-sharded layer stacks, microbatch schedule)
    sp    sequence/context parallel (ring attention / Ulysses)
    ep    expert parallel (MoE dispatch)

The reference has only dp + limited model parallel (SURVEY.md §2.3); the rest
are first-class here.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["AXES", "make_mesh", "current_mesh", "default_mesh", "MeshScope",
           "replicated", "named_sharding", "shard_map", "validate_specs"]


def _spec_axis_names(specs):
    """Every axis name appearing in a specs pytree (PartitionSpec
    leaves; entries may be names or tuples of names)."""
    out = []
    for spec in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, PartitionSpec)):
        if not isinstance(spec, PartitionSpec):
            continue
        for entry in spec:
            names = entry if isinstance(entry, tuple) else (entry,)
            for name in names:
                if isinstance(name, str):
                    out.append(name)
    return out


def validate_specs(mesh, in_specs=None, out_specs=None):
    """Raise ``ValueError`` naming the axis when an in/out spec names a
    mesh axis that does not exist — the runtime twin of mxlint's
    ``spmd-axis-unknown``.  Without this a typo'd axis surfaces as a
    deep jax internal error far from the call site."""
    axes = set(getattr(mesh, "axis_names", ()) or ())
    if not axes:
        return
    for role, specs in (("in_specs", in_specs), ("out_specs", out_specs)):
        for name in _spec_axis_names(specs):
            if name not in axes:
                raise ValueError(
                    f"shard_map {role} names axis {name!r}, which is "
                    f"not one of the mesh axes {tuple(sorted(axes))} — "
                    f"a typo'd axis would otherwise fail deep inside "
                    f"jax (or silently change the partitioning)")


def shard_map(f=None, *, mesh=None, in_specs=None, out_specs=None, **kw):
    """``jax.shard_map`` with call-time axis validation: every axis
    named in ``in_specs``/``out_specs`` must exist in
    ``mesh.axis_names`` (``validate_specs``).  Currying (``f=None``)
    is preserved; every other keyword (``check_vma``, ...) passes
    through."""
    if mesh is not None:
        validate_specs(mesh, in_specs, out_specs)
    inner = {}
    if mesh is not None:
        inner["mesh"] = mesh
    if in_specs is not None:
        inner["in_specs"] = in_specs
    if out_specs is not None:
        inner["out_specs"] = out_specs
    inner.update(kw)
    if f is None:
        return lambda g: jax.shard_map(g, **inner)
    return jax.shard_map(f, **inner)

# Canonical axis order: collectives that ride adjacent devices (tp, sp) go
# last so they land on the fastest ICI neighbours in the device enumeration.
AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")

_tls = threading.local()


def make_mesh(axes=None, devices=None, **axis_sizes):
    """Build a named-axis mesh, e.g. ``make_mesh(dp=2, tp=4)``.

    Axis sizes must multiply to the device count; any remainder axis may be
    given as -1 (inferred).  With no args, all devices go onto one ``dp`` axis
    — the TPU-native equivalent of KVStore "device" over all local GPUs.
    """
    if axes:
        axis_sizes = dict(axes, **axis_sizes)
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if not axis_sizes:
        axis_sizes = {"dp": n}
    ordered = OrderedDict()
    for name in AXES:
        if name in axis_sizes:
            ordered[name] = axis_sizes.pop(name)
    for name, size in axis_sizes.items():  # user-defined extra axes
        ordered[name] = size
    infer = [k for k, v in ordered.items() if v == -1]
    if len(infer) > 1:
        raise ValueError("at most one axis size may be -1")
    known = int(np.prod([v for v in ordered.values() if v != -1]))
    if infer:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        ordered[infer[0]] = n // known
        known = n
    if known != n:
        raise ValueError(f"mesh axes {dict(ordered)} need {known} devices, "
                         f"have {n}")
    arr = np.asarray(devices).reshape(tuple(ordered.values()))
    return Mesh(arr, tuple(ordered.keys()))


class MeshScope:
    """``with MeshScope(mesh):`` makes it the framework-current mesh."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        stack.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _tls.stack.pop()


def current_mesh():
    stack = getattr(_tls, "stack", None)
    if stack:
        return stack[-1]
    return None


def default_mesh():
    """Current mesh, or an all-``dp`` mesh over every device."""
    m = current_mesh()
    if m is None:
        m = make_mesh()
    return m


def replicated(mesh):
    return NamedSharding(mesh, PartitionSpec())


def named_sharding(mesh, *spec):
    return NamedSharding(mesh, PartitionSpec(*spec))
