"""A Pallas kernel traced once a process, however many layers, passes and
contexts call it.

A kernel behind a module-level ``jax.jit`` is lowered once a program where
its callers share one trace of it, but the jit's trace cache keys on the
abstract mesh of the caller's trace and on the arguments' shardings: a
step's forward pass, its recomputation and its backward pass differ in the
first, deferred initialisation's shape pass and the step in the second, and
each miss traces the kernel again (~20 ms a kernel here, ~4x that on the
chip's host; PERF.md 6, PR 38).  ``bind`` calls a kernel's builder through
its jaxpr, traced once by the arguments' shapes and types and the trace-time
configuration alone: a jit's miss then binds the jaxpr's equations again,
and does not trace the kernel.
"""
from __future__ import annotations

import functools

import jax
from jax._src import config
from jax.extend.core import jaxpr_as_fun


def _unmeshed():
    return jax.sharding.use_abstract_mesh(jax.sharding.AbstractMesh((), ()))


@functools.lru_cache(maxsize=None)
def traced(build, avals, static, context):
    """``build``'s jaxpr for arguments of ``avals`` ((shape, dtype), ...)
    and the keywords ``static`` ((name, value), ...), traced with no mesh.
    ``context`` is the caller's trace-time configuration with no mesh
    (default precision, x64, dtype promotion, ...): what a jit's trace
    cache keys on, the mesh aside."""
    del context
    with _unmeshed():
        return jax.make_jaxpr(functools.partial(build, **dict(static)))(
            *(jax.ShapeDtypeStruct(s, d) for s, d in avals))


def bind(build, args, **static):
    """``build(*args, **static)`` (a list of its outputs) through ``traced``:
    its jaxpr's equations bound again, the kernel not traced again."""
    with _unmeshed():
        context = config.trace_context()
    closed = traced(build, tuple((a.shape, a.dtype) for a in args),
                    tuple(sorted(static.items())), context)
    return jaxpr_as_fun(closed)(*args)
