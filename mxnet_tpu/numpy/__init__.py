"""``mx.np`` — the numpy-semantics frontend.

ref: python/mxnet/numpy/multiarray.py — mx.np.ndarray and the numpy-compat
function surface (src/operator/numpy/ implements them as ~100 C++ ops).
TPU-native: jax.numpy *is* a numpy-semantics array library compiled by XLA,
so this frontend is a thin typed layer — ``mx.np.ndarray`` subclasses the
core NDArray (sharing autograd, device placement, and the async engine) and
the module functions delegate to jnp, wrapping results back.  That keeps
one implementation for both frontends instead of the reference's parallel
operator tree, which is the §7.0 "delegate to the compiler" stance.

Use with ``mx.npx.set_np()`` like the reference (it flips the default array
type used by gluon blocks), or call these functions directly.
"""
from __future__ import annotations

import builtins
import sys

import numpy as _onp
import jax
import jax.numpy as jnp

from ..base import dtype_np
from ..context import current_context
from ..ndarray.ndarray import NDArray, invoke
from ..ndarray import array as _nd_array
from . import random  # noqa: F401  (mx.np.random)
from . import linalg  # noqa: F401  (mx.np.linalg)

pi = _onp.pi
e = _onp.e
inf = _onp.inf
nan = _onp.nan
newaxis = None

# dtypes re-exported like numpy's namespace
float16 = _onp.float16
float32 = _onp.float32
float64 = _onp.float64
int8 = _onp.int8
int32 = _onp.int32
int64 = _onp.int64
uint8 = _onp.uint8
bool_ = _onp.bool_
bfloat16 = jnp.bfloat16


class ndarray(NDArray):
    """mx.np.ndarray (ref: multiarray.py — class ndarray).

    Subclass of the core NDArray: same buffer, autograd tape, and context
    machinery; numpy-flavoured surface (``.ndim``/``.T``/item()/tolist(),
    scalar-producing reductions, numpy operator semantics from jnp)."""

    # layout-compatible with NDArray so npx.set_np can retype parameter
    # arrays in place (identity-preserving — the tape keys on object id)
    __slots__ = ()

    def item(self):
        if self.size != 1:
            raise ValueError("can only convert an array of size 1 to a "
                             "Python scalar")
        return self._data.reshape(()).item()

    def tolist(self):
        return _onp.asarray(self._data).tolist()

    def as_nd_ndarray(self):
        """Back to the legacy frontend type (ref: ndarray.as_nd_ndarray)."""
        return NDArray(self._data, ctx=self._ctx)

    # numpy-style named methods delegating to the module functions
    def mean(self, axis=None, dtype=None, keepdims=False):
        return mean(self, axis=axis, dtype=dtype, keepdims=keepdims)

    def sum(self, axis=None, dtype=None, keepdims=False):
        return sum(self, axis=axis, dtype=dtype, keepdims=keepdims)

    def std(self, axis=None, keepdims=False):
        return std(self, axis=axis, keepdims=keepdims)

    def var(self, axis=None, keepdims=False):
        return var(self, axis=axis, keepdims=keepdims)

    # reshape/transpose/astype inherit the base (taped, type-preserving)
    # implementations; only the numpy *axes signature needs adapting
    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return NDArray.transpose(self, axes or None)

    def copy(self):
        return type(self)(jnp.asarray(self._data), ctx=self._ctx)

    def __repr__(self):
        return repr(_onp.asarray(self._data)).replace("array(", "array(", 1)


def _wrap(data):
    return ndarray(data, ctx=current_context())


def _unwrap(x):
    if isinstance(x, NDArray):
        return x._data
    return x


def array(object, dtype=None, ctx=None):
    """ref: mx.np.array — numpy default dtype rules (float32 default for
    floats, like the reference's mx.np)."""
    base = _nd_array(object, ctx=ctx, dtype=dtype)
    return ndarray(base._data, ctx=base._ctx)


# ---------------------------------------------------------------- factory ---
def zeros(shape, dtype="float32", ctx=None):
    return _wrap(jnp.zeros(shape, dtype_np(dtype)))


def ones(shape, dtype="float32", ctx=None):
    return _wrap(jnp.ones(shape, dtype_np(dtype)))


def full(shape, fill_value, dtype=None, ctx=None):
    return _wrap(jnp.full(shape, fill_value,
                          dtype_np(dtype) if dtype else None))


def empty(shape, dtype="float32", ctx=None):
    return zeros(shape, dtype, ctx)


def zeros_like(a, dtype=None):
    return _wrap(jnp.zeros_like(_unwrap(a), dtype))


def ones_like(a, dtype=None):
    return _wrap(jnp.ones_like(_unwrap(a), dtype))


def full_like(a, fill_value, dtype=None):
    return _wrap(jnp.full_like(_unwrap(a), fill_value, dtype))


def arange(start, stop=None, step=1, dtype=None, ctx=None):
    return _wrap(jnp.arange(start, stop, step,
                            dtype_np(dtype) if dtype else None))


def linspace(start, stop, num=50, endpoint=True, dtype=None, ctx=None):
    return _wrap(jnp.linspace(start, stop, num, endpoint=endpoint,
                              dtype=dtype_np(dtype) if dtype else None))


def eye(N, M=None, k=0, dtype="float32", ctx=None):
    return _wrap(jnp.eye(N, M, k, dtype_np(dtype)))


def identity(n, dtype="float32", ctx=None):
    return eye(n, dtype=dtype)




# ---------------------------------- mechanically generated jnp delegates ----
_UNARY = [
    "exp", "expm1", "log", "log2", "log10", "log1p", "sqrt", "cbrt",
    "square", "abs", "absolute", "sign", "negative", "reciprocal",
    "sin", "cos", "tan", "arcsin", "arccos", "arctan", "sinh", "cosh",
    "tanh", "arcsinh", "arccosh", "arctanh", "degrees", "radians",
    "floor", "ceil", "rint", "trunc", "logical_not",
    "isnan", "isinf", "isfinite", "isneginf", "isposinf",
]
_BINARY = [
    "add", "subtract", "multiply", "divide", "true_divide", "floor_divide",
    "power", "mod", "remainder", "fmod", "maximum", "minimum", "hypot",
    "arctan2", "copysign", "logaddexp", "equal", "not_equal", "greater",
    "greater_equal", "less", "less_equal", "logical_and", "logical_or",
    "logical_xor", "bitwise_and", "bitwise_or", "bitwise_xor",
    "left_shift", "right_shift", "gcd", "lcm",
]
_SHAPE = [
    "reshape", "ravel", "moveaxis", "swapaxes", "expand_dims", "squeeze",
    "broadcast_to", "flip", "fliplr", "flipud", "roll", "rot90", "tile",
    "repeat", "atleast_1d", "atleast_2d", "atleast_3d",
]
_OTHER = [
    "where", "clip", "tril", "triu", "diag", "trace", "sort", "argsort",
    "searchsorted", "unique", "cumsum", "cumprod", "diff", "ediff1d",
    "nan_to_num", "around", "round", "real", "imag", "interp",
    "take", "take_along_axis", "nonzero", "count_nonzero", "allclose",
    "array_equal", "isclose", "may_share_memory", "shares_memory",
    "histogram", "bincount", "pad", "insert", "delete", "flatnonzero",
    "tensordot", "dot", "matmul", "inner", "outer", "vdot", "kron",
    "cross", "einsum", "average",
]
_REDUCE = [
    "sum", "prod", "mean", "std", "var", "max", "min", "amax", "amin",
    "argmax", "argmin", "all", "any", "nansum", "nanprod", "nanmean",
    "nanmax", "nanmin", "median", "percentile", "quantile", "ptp",
]
_CONCAT = ["concatenate", "stack", "vstack", "hstack", "dstack",
           "column_stack", "split", "array_split", "vsplit", "hsplit",
           "dsplit"]

# Long tail of numpy API delegated wholesale (ref: src/operator/numpy/ —
# the reference mirrors most of numpy; names jnp lacks are skipped by the
# hasattr guard below).
_EXTRA = [
    "logspace", "indices", "tri", "diagonal", "positive", "heaviside",
    "angle", "conj", "conjugate", "unwrap", "sinc", "nanstd", "nanvar",
    "nanargmax", "nanargmin", "nancumsum", "nancumprod",
    "digitize", "partition", "argpartition", "lexsort", "union1d",
    "intersect1d", "setdiff1d", "setxor1d", "isin", "broadcast_arrays",
    # NOTE: fill_diagonal / put_along_axis are deliberately absent — jnp
    # requires inplace=False (immutable arrays) so plain delegation can't
    # honor numpy's mutate-in-place contract.
    "append", "resize", "trim_zeros", "gradient", "iscomplex", "isreal",
    "iscomplexobj", "isrealobj", "nextafter", "spacing", "ldexp", "frexp",
    "modf", "deg2rad", "rad2deg", "invert", "argwhere", "extract",
    "choose", "compress", "select", "signbit",
    "float_power", "divmod", "cov", "corrcoef", "convolve", "correlate",
    "empty_like", "ascontiguousarray", "copy", "rollaxis", "block",
    "apply_along_axis", "apply_over_axes", "triu_indices", "tril_indices",
    "triu_indices_from", "tril_indices_from", "diag_indices",
    "diag_indices_from", "unravel_index", "ravel_multi_index", "ix_",
    "packbits", "unpackbits", "poly", "polyadd",
    "polyder", "polyfit", "polyint", "polymul", "polysub", "polyval",
]

# dtype objects and non-array-returning utilities pass through raw (they
# return dtypes/functions, so the ndarray wrapper — and its autograd vjp
# path — must not touch them)
_PASSTHROUGH = ["float16", "float64", "uint16", "uint32", "uint64",
                "int16", "complex64", "complex128", "promote_types",
                "can_cast", "vectorize"]
for _dt in _PASSTHROUGH:
    if not hasattr(sys.modules[__name__], _dt) and hasattr(jnp, _dt):
        setattr(sys.modules[__name__], _dt, getattr(jnp, _dt))

_this = sys.modules[__name__]


def _apply(fn, name, nd_args, call):
    """Run ``call(*raw)`` with the three dispatch modes of ``nd.invoke``:
    trace-through under jit, VJP-record on the autograd tape, plain eager —
    so mx.np functions differentiate exactly like mx.nd ops do."""
    from .. import autograd as _autograd

    raw = [a._data for a in nd_args]
    tracing = builtins.any(isinstance(r, jax.core.Tracer) for r in raw)
    if not tracing and _autograd.is_recording():
        result, pullback = jax.vjp(call, *raw)

        def _pull(cts, _pb=pullback):
            return list(_pb(cts[0] if not isinstance(result, tuple) else cts))

        outs_t = result if isinstance(result, tuple) else (result,)
        out_nds = tuple(_wrap(o) for o in outs_t)
        node = _autograd.TapeNode(list(nd_args), list(out_nds), _pull,
                                  name=f"np.{name}")
        _autograd.append_node(node)
        return out_nds if isinstance(result, tuple) else out_nds[0]
    out = call(*raw)
    if isinstance(out, (tuple, list)):
        return type(out)(_wrap(o) if isinstance(o, jax.Array) else o
                         for o in out)
    if isinstance(out, jax.Array):
        return _wrap(out)
    return out


def _delegate(name):
    fn = getattr(jnp, name)

    def wrapper(*args, **kwargs):
        kwargs = {k: _unwrap(v) for k, v in kwargs.items()}
        # split array args (tape inputs) from static args, keeping a
        # template to rebuild the call — handles sequences of arrays
        # (concatenate/stack) and static prefixes (einsum) uniformly
        template, nd_args = [], []
        for a in args:
            if isinstance(a, NDArray):
                template.append(("nd", len(nd_args)))
                nd_args.append(a)
            elif isinstance(a, (tuple, list)) and a and \
                    builtins.all(isinstance(x, (NDArray, jax.Array,
                                                _onp.ndarray)) for x in a):
                wrapped = [NDArray(jnp.asarray(_unwrap(x)))
                           if not isinstance(x, NDArray) else x for x in a]
                template.append(("seq", len(nd_args), len(wrapped)))
                nd_args.extend(wrapped)
            else:
                template.append(("static", a))

        def call(*raw):
            rebuilt = []
            for t in template:
                if t[0] == "nd":
                    rebuilt.append(raw[t[1]])
                elif t[0] == "seq":
                    rebuilt.append(list(raw[t[1]:t[1] + t[2]]))
                else:
                    rebuilt.append(t[1])
            return fn(*rebuilt, **kwargs)

        return _apply(fn, name, nd_args, call)

    wrapper.__name__ = name
    wrapper.__qualname__ = name
    wrapper.__doc__ = f"numpy-semantics {name} (delegates to jnp.{name})"
    return wrapper


for _n in (_UNARY + _BINARY + _SHAPE + _OTHER + _REDUCE + _CONCAT + _EXTRA):
    if not hasattr(_this, _n) and hasattr(jnp, _n):
        setattr(_this, _n, _delegate(_n))

fix = trunc  # noqa: F821 — numpy's name for it; jnp.fix is deprecated
abs = _delegate("abs")          # shadow builtins deliberately, like numpy
round = _delegate("round")
sum = _delegate("sum")
max = _delegate("max")
min = _delegate("min")
all = _delegate("all")
any = _delegate("any")


transpose = _delegate("transpose")
meshgrid = _delegate("meshgrid")


def asnumpy(a):
    return _onp.asarray(_unwrap(a))


def shape(a):
    return tuple(_unwrap(a).shape)


def ndim(a):
    return _unwrap(a).ndim


def size(a):
    return int(_unwrap(a).size)


def result_type(*args):
    return jnp.result_type(*[_unwrap(a) for a in args])


def asarray(a, dtype=None):
    if isinstance(a, NDArray) and dtype is None:
        return a if isinstance(a, ndarray) else _wrap(a._data)
    return array(a, dtype=dtype)


# only names that actually resolved (the hasattr(jnp, ...) guard skips
# entries this jax version lacks) — a star-import must never NameError
__all__ = [n for n in
           (["ndarray", "array", "asarray", "zeros", "ones", "full",
             "empty", "zeros_like", "ones_like", "full_like", "arange",
             "linspace", "eye", "identity", "meshgrid", "transpose",
             "asnumpy", "shape", "ndim", "size", "result_type", "random",
             "linalg", "pi", "e", "inf", "nan", "newaxis"]
            + _UNARY + _BINARY + _SHAPE + _OTHER + _REDUCE + _CONCAT
            + _EXTRA + _PASSTHROUGH)
           if hasattr(sys.modules[__name__], n)]
