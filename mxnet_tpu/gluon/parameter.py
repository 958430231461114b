"""Gluon Parameter / ParameterDict.

ref: python/mxnet/gluon/parameter.py — class Parameter (deferred init on first
forward via shape-0 wildcards, grad_req, initialize/set_data/zero_grad),
class ParameterDict (prefix-scoped registry, get(), save/load).

TPU-native notes: a Parameter owns one NDArray per framework (no per-device
replica list — replication is a sharding annotation, see mxnet_tpu.parallel);
``list_data()`` is kept for API parity and returns a one-element list. Casting
to bf16 for AMP is ``cast()``, matching the reference.

``initialize()`` records and draws the initializer's PRNG keys; nothing is
allocated until someone needs the array.  ``data()`` materialises that one
parameter eagerly; a consumer of the whole model (``parallel.TrainStep``,
``EvalStep``, a hybridized block's first call) materialises every pending
parameter in ONE compiled program, ``materialize``, after
``gluon.block.infer_shapes`` has filled the shapes.  Both run the same
initializers on the same keys.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import itertools
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import initializer as init_mod
from .. import random as _random
from .. import telemetry as _telemetry
from ..base import MXNetError, dtype_np
from ..context import current_context
from ..ndarray import NDArray

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError",
           "materialize"]


class DeferredInitializationError(MXNetError):
    """ref: gluon/parameter.py — raised when data() is read before shapes known."""


# ``met``: the pending parameters the running ``gluon.block.infer_shapes``
# pass has handed a stand-in, in the order its forward asked for them
_shape_pass = threading.local()


@functools.cache
def _key_aval():
    return jax.eval_shape(lambda: jax.random.key(0))


_Plan = collections.namedtuple("_Plan", "initializer dtype keys value")


def _plan(initializer, name, shape, dtype):
    """What materialising needs besides the shape.  One abstract run of the initializer counts the keys it
    draws, and that many are drawn from the framework stream NOW, where an
    eager ``initialize()`` would have drawn them.  An initializer that
    builds its array on the host (``Bilinear``, ``LSTMBias``, anything
    through numpy) is run now instead and ``value`` holds the result: the
    bulk program takes it as an argument, never as a constant."""
    scopes = []

    def run(key):
        with _random.RandomScope(key) as scope:
            scopes.append(scope)
            return initializer(name, shape, dtype)

    try:
        host_made = bool(jax.make_jaxpr(run)(_key_aval()).consts)
    except jax.errors.JAXTypeError:     # it reads values back: not traceable
        host_made = True
    if host_made:
        return _Plan(initializer, dtype, (), initializer(name, shape, dtype))
    keys = tuple(_random.next_key() for _ in range(scopes[0]._count))
    return _Plan(initializer, dtype, keys, None)


def _as_cast(value, planned, dtype):
    """A ``cast()`` between ``initialize()`` and materialisation converts
    the initializer's result, as it would have converted the array."""
    if dtype_np(planned) != dtype_np(dtype):
        return value.astype(dtype_np(dtype))
    return value


class Parameter:
    """A weight/bias/state tensor of a Block (ref: class Parameter)."""

    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._data: Optional[NDArray] = None
        # pending, i.e. initialize()d and not materialised yet:
        # (init, ctx, default_init), and once the shape is known the
        # _plan() with the keys drawn for it
        self._deferred_init = None
        self._init_plan = None
        self._stype = stype
        self._grad_stype = grad_stype

    # ----------------------------------------------------------------- reqs --
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null")
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._data._grad = None
                self._data._grad_req = "null"
            else:
                self._data.attach_grad(req)

    # ----------------------------------------------------------------- init --
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        """ref: Parameter.initialize — records the initializer and, once the
        shape is known, draws its PRNG keys; the array is made on first use
        (``data()``) or in bulk (``materialize``)."""
        if not force_reinit and (self._data is not None
                                 or self._init_plan is not None):
            return
        if default_init is None:
            default_init = init_mod.Uniform()
        if ctx is None:
            ctx = current_context()
        if not self._shape_known() and not self.allow_deferred_init:
            raise ValueError(
                f"cannot initialize parameter '{self.name}' with unknown shape "
                f"{self.shape}; set allow_deferred_init=True or give a full shape")
        self._data = self._init_plan = None
        self._deferred_init = (init, ctx, default_init)
        if self._shape_known():
            self._plan_init()

    def _shape_known(self):
        return self.shape is not None and all(s != 0 for s in self.shape)

    def _plan_init(self):
        if self._init_plan is None:
            init, _, default_init = self._deferred_init
            initializer = init_mod.create(
                init if init is not None else
                (self.init if self.init is not None else default_init))
            self._init_plan = _plan(initializer, self.name, self.shape,
                                    self.dtype)
        return self._init_plan

    def _finish_init(self):
        """Materialise this one pending parameter, eagerly."""
        met = getattr(_shape_pass, "met", None)
        if met is not None:
            # under infer_shapes: a stand-in of the right shape and dtype,
            # which the pass takes away again
            met.append(self)
            self._data = NDArray(jnp.zeros(self.shape, dtype_np(self.dtype)))
            return
        plan = self._plan_init()
        value = plan.value
        if value is None:
            with _random.KeyTape(plan.keys):
                value = plan.initializer(self.name, self.shape, plan.dtype)
        self._adopt(_as_cast(value, plan.dtype, self.dtype))

    def _adopt(self, array, grad=None):
        ctx = self._deferred_init[1]
        self._data = NDArray(array, ctx=ctx)
        if grad is not None:        # attach_grad, its zeros made elsewhere
            self._data._grad = NDArray(grad, ctx=ctx)
            self._data._grad_req = self._grad_req
        elif self._grad_req != "null":
            self._data.attach_grad(self._grad_req)
        self._deferred_init = self._init_plan = None

    def _finish_deferred_init(self, inferred_shape=None):
        """Called by layers at first forward once input shapes are known
        (ref: Parameter._finish_deferred_init)."""
        if inferred_shape is not None:
            if self.shape is not None:
                merged = tuple(i if s == 0 else s
                               for s, i in zip(self.shape, inferred_shape))
                self.shape = merged
            else:
                self.shape = tuple(inferred_shape)
        if self._deferred_init is None:
            raise DeferredInitializationError(
                f"parameter '{self.name}' was not initialize()d")
        if self._data is None:
            self._finish_init()

    # ----------------------------------------------------------------- data --
    def data(self, ctx=None):
        """ref: Parameter.data — the NDArray, raising if deferred/uninitialised."""
        if self._data is None:
            if self._deferred_init is None:
                raise RuntimeError(
                    f"parameter '{self.name}' has not been initialized; "
                    f"call .initialize() first")
            if not self._shape_known():
                raise DeferredInitializationError(
                    f"parameter '{self.name}' deferred-init pending: run a forward "
                    f"pass (or pass in_units/in_channels) before accessing data()")
            self._finish_init()
        from .. import numpy_extension as _npx
        from ..numpy import ndarray as _np_nd
        # np mode (npx.set_np): retype the parameter array in place (layout-
        # compatible subclass, identity preserved for the tape) so block
        # outputs become mx.np arrays — the reference's set_np mechanism
        want = _np_nd if _npx.is_np_array() else NDArray
        if type(self._data) is not want and \
                type(self._data) in (NDArray, _np_nd):
            self._data.__class__ = want
        return self._data

    def list_data(self):
        return [self.data()]

    def set_data(self, data):
        arr = data._data if isinstance(data, NDArray) else jnp.asarray(data)
        if self._data is None:
            if self._init_plan is not None:
                # pending with its shape known: held to the shape and dtype
                # the array initialize() recorded would have had
                if tuple(arr.shape) != self.shape:
                    raise ValueError(
                        f"shape mismatch for '{self.name}': "
                        f"{tuple(arr.shape)} vs {self.shape}")
                arr = arr.astype(dtype_np(self.dtype))
            self.shape = tuple(arr.shape)
            self._data = NDArray(arr)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)
            self._deferred_init = self._init_plan = None
            return
        if tuple(arr.shape) != self.shape:
            raise ValueError(
                f"shape mismatch for '{self.name}': {tuple(arr.shape)} vs {self.shape}")
        self._data._data = arr.astype(self._data._data.dtype)

    def grad(self, ctx=None):
        d = self.data(ctx)
        if d.grad is None:
            raise RuntimeError(f"parameter '{self.name}' has grad_req='null'")
        if self._grad_stype == "row_sparse":
            # sparse-grad parameters (Embedding(sparse_grad=True)) hand the
            # optimizer a row_sparse view for lazy row-wise updates.  The
            # tape accumulates dense (XLA scatter-add is the TPU-native
            # form); the rsp view is the update/communication format.
            from .. import sparse as _sp
            return _sp.cast_storage(d.grad, "row_sparse")
        return d.grad

    def list_grad(self):
        return [self.grad()]

    def zero_grad(self):
        if self._data is not None and self._data.grad is not None:
            g = self._data.grad
            g._data = jnp.zeros_like(g._data)

    def reset_ctx(self, ctx):
        pass  # single logical device; placement is sharding (mxnet_tpu.parallel)

    def list_ctx(self):
        if self._data is not None:
            return [self._data.context]
        return [self._deferred_init[1]] if self._init_plan is not None else []

    def cast(self, dtype):
        """ref: Parameter.cast — used by AMP to make bf16 master copies.  On
        a pending parameter the dtype is recorded and the initializer's
        result converted when the array is made."""
        self.dtype = dtype
        if self._data is not None:
            self._data._data = self._data._data.astype(dtype_np(dtype))
            if self._data.grad is not None:
                self._data.attach_grad(self._grad_req)

    def var(self):
        return self.data()

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"


class Constant(Parameter):
    """Non-differentiable parameter holding a fixed value (ref: class Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, np.ndarray):
            value = np.asarray(value.asnumpy() if isinstance(value, NDArray) else value)
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype.name,
                         init=init_mod.Constant(0))

    def _plan_init(self):
        if self._init_plan is None:
            self._init_plan = _Plan(None, self.dtype, (),
                                    jnp.asarray(self.value))
        return self._init_plan


# compiled programs by _program_key: see _run_program
_PROGRAMS = {}
_MAX_PROGRAMS = 32


def _program_key(traced, args, out_shardings):
    """Names a traced program by what decides its compiled form: its text
    (every shape, dtype and literal), the placement of its arguments and
    results, the platform, and every jax setting (some choose a lowering:
    the default matmul precision, ``jax_threefry_partitionable``)."""
    parts = (str(traced.jaxpr),
             [getattr(a, "sharding", None) for a in jax.tree.leaves(args)],
             jax.tree.leaves(out_shardings), jax.default_backend(),
             jax.__version__, sorted(jax.config.values.items(), key=str))
    return hashlib.sha256(repr(parts).encode()).hexdigest()


def _exported(jitted, args, key):
    """``jitted`` as ``jax.export`` serializes it (StableHLO), kept beside
    jax's persistent compile cache under ``key``.  jax's cache is keyed by
    the LOWERED module, so a warm process still lowers before it can hit,
    and lowering is where an initializer program's time goes on the TPU:
    threefry is traced anew for every distinct shape (3.0 of 3.3 s for
    ResNet-50's 21 shapes, chip run, PR 26), while the stored module lowers
    in a tenth of that.  None where there is no cache directory, or the
    program holds something ``jax.export`` will not serialize.  Which
    it was goes onto the span open around the call (``program``: ``stored``
    or ``lowered``)."""
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        return None
    path = os.path.join(cache_dir, f"mxnet_tpu-program-{key}.stablehlo")
    try:
        with open(path, "rb") as f:
            exported = jax.export.deserialize(bytearray(f.read()))
        _telemetry.scope_note(program="stored")
        return exported
    except OSError:
        pass                    # not there yet
    except Exception:  # noqa: BLE001 — a cache must not stop the program:
        pass           # damaged, or another jax's format; written anew
    _telemetry.scope_note(program="lowered")
    try:
        exported = jax.export.export(jitted)(*args)
    except ValueError:          # e.g. a custom call outside export's list
        return None
    try:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            f.write(exported.serialize())
        os.replace(tmp, path)
    except OSError:
        pass                    # a read-only cache still serves this run
    return exported


def _run_program(fn, args, out_shardings=None):
    """``jax.jit(fn, out_shardings=out_shardings)(*args)``, compiled once
    per PROGRAM rather than once per function object: ``fn`` is a fresh
    closure over some model's parameters every time, and a second model of
    the same shapes must not compile again, in this process (``_PROGRAMS``)
    or, for a program placed nowhere in particular, the next
    (``_exported``).  A program that closes over an array is not kept: its
    text cannot tell two such apart."""
    jitted = jax.jit(fn, out_shardings=out_shardings)
    traced = jitted.trace(*args)
    if traced.jaxpr.consts:
        return traced.lower().compile()(*args)
    key = _program_key(traced, args, out_shardings)
    if key not in _PROGRAMS:
        if len(_PROGRAMS) >= _MAX_PROGRAMS:
            del _PROGRAMS[next(iter(_PROGRAMS))]
        exported = None if out_shardings is not None \
            else _exported(jitted, args, key)
        _PROGRAMS[key] = traced.lower().compile() if exported is None \
            else jax.jit(exported.call).lower(*args).compile()
    return _PROGRAMS[key](*args)


def materialize(params):
    """Make the arrays of every pending, fully-shaped Parameter among
    ``params`` in ONE compiled program: each initializer traced on the keys
    it drew (those that have not drawn yet draw now, in the order given),
    a recorded ``cast()`` included, and the zeroed gradient buffers beside
    them; the keys and any host-made array are ARGUMENTS, so the program
    depends on names, shapes and dtypes alone and another seed runs the
    same executable.  The arrays live where eager initialization puts them,
    uncommitted on the default device, so the net stays usable eagerly
    whatever mesh its consumer runs on.  Returns how many it made."""
    todo = [p for p in params
            if p._deferred_init is not None and p._shape_known()]
    if not todo:
        return 0
    plans = [p._plan_init() for p in todo]
    keys = [k for plan in plans for k in plan.keys]
    made = [plan.value for plan in plans if plan.value is not None]

    def program(keys, made):
        keys, made, out = iter(keys), iter(made), []
        for p, plan in zip(todo, plans):
            if plan.value is not None:
                value = next(made)
            else:
                with _random.KeyTape(itertools.islice(keys, len(plan.keys))):
                    value = plan.initializer(p.name, p.shape, plan.dtype)
            value = _as_cast(value, plan.dtype, p.dtype)
            out.append((value, None if p._grad_req == "null"
                        else jnp.zeros_like(value)))
        return out

    for p, (array, grad) in zip(todo, _run_program(program, (keys, made))):
        p._adopt(array, grad)
    return len(todo)


class ParameterDict:
    """Prefix-scoped parameter registry (ref: class ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = {}
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __iter__(self):
        return iter(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def __len__(self):
        return len(self._params)

    def __contains__(self, k):
        return k in self._params

    def __getitem__(self, k):
        return self._params[k]

    def get(self, name, **kwargs):
        """Create-or-retrieve ``prefix+name`` (ref: ParameterDict.get)."""
        full = self._prefix + name
        if full in self._params:
            p = self._params[full]
            for k, v in kwargs.items():
                if v is not None and getattr(p, k, None) in (None, (), 0):
                    setattr(p, k, v)
            return p
        if self._shared is not None and full in self._shared:
            self._params[full] = self._shared[full]
            return self._params[full]
        p = Parameter(full, **kwargs)
        self._params[full] = p
        return p

    def get_constant(self, name, value=None):
        full = self._prefix + name
        if full not in self._params:
            self._params[full] = Constant(full, value)
        return self._params[full]

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise ValueError(f"duplicate parameter name '{k}'")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx, default_init=init, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def save(self, filename, strip_prefix=""):
        """ref: ParameterDict.save — via the ndarray container format."""
        from .. import ndarray as nd
        d = {}
        for name, p in self._params.items():
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            d[name] = p.data()
        nd.save(filename, d)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from .. import ndarray as nd
        loaded = nd.load(filename)
        if restore_prefix:
            loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self._params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise ValueError(f"parameter '{name}' missing in file {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise ValueError(f"file {filename} has extra parameters {sorted(extra)}")

    def __repr__(self):
        body = "\n".join(f"  {p!r}" for p in self._params.values())
        return f"ParameterDict '{self._prefix}' (\n{body}\n)"
