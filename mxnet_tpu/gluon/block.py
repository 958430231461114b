"""Gluon Block / HybridBlock.

ref: python/mxnet/gluon/block.py — class Block (imperative container,
child/param registration via __setattr__, collect_params, save/load),
class HybridBlock (hybridize() switches execution to a captured graph —
src/imperative/cached_op.cc CachedOp::Forward/Backward).

TPU-native design: because NDArray transparently wraps either a concrete
jax.Array or a tracer, ONE Python ``forward`` serves both modes. ``hybridize``
compiles the whole forward (self + children) into a single XLA computation via
``jax.jit`` — the 100% version of the reference's CachedOp/static_alloc. The
recorded-training path takes ``jax.vjp`` of the same jitted callable and pushes
ONE tape node whose pullback is the compiled backward (CachedOp::Backward
analogue). RNG (dropout) enters as a traced key argument; the train/predict
flag is a static jit argument, so both modes get their own executable.
"""
from __future__ import annotations

import contextlib
import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from .. import autograd as _autograd
from .. import random as _random
from .. import telemetry as _telemetry
from ..base import dtype_np
from ..context import current_context
from ..ndarray import NDArray
from ..ops.pallas.flash_attention import KEPT as _KEPT
from ..ops.pallas.flash_attention import traced_bytes as _traced_bytes
from . import parameter as _parameter
from .parameter import DeferredInitializationError, Parameter, ParameterDict

__all__ = ["Block", "HybridBlock", "SymbolBlock", "infer_shapes"]

_naming = threading.local()


def _scope_stack():
    if not hasattr(_naming, "stack"):
        _naming.stack = [({}, "")]  # (per-scope counters, accumulated prefix)
    return _naming.stack


def _make_prefix(explicit, hint: str) -> str:
    """Compose block prefix with the enclosing name scope
    (ref: gluon/block.py — _BlockScope.create)."""
    counters, cur = _scope_stack()[-1]
    if explicit is not None:
        return cur + explicit
    idx = counters.get(hint, 0)
    counters[hint] = idx + 1
    return f"{cur}{hint}{idx}_"


class _NameScope:
    """ref: gluon/block.py — _BlockScope; nested name scoping for children."""

    def __init__(self, block):
        self._block = block

    def __enter__(self):
        _scope_stack().append((self._block._scope_counters, self._block._prefix))
        return self

    def __exit__(self, *exc):
        _scope_stack().pop()


def _flatten_nd(value):
    """Flatten nested tuples/lists of NDArray into (leaves, treedef).
    The treedef distinguishes a bare NDArray ("*" at top level) from a
    1-tuple, so hybridized forward preserves output structure exactly."""
    leaves = []

    def _walk(a):
        if isinstance(a, NDArray):
            leaves.append(a)
            return "*"
        if isinstance(a, (tuple, list)):
            return tuple(_walk(x) for x in a)
        return ("#", a)  # static leaf

    tree = _walk(value)
    return leaves, tree


def _tree_to_json(tree):
    """Output-tree structure as plain json types (lists for tuples).
    Static leaves must be json-serializable — true for every framework
    output structure (Nones/scalars); anything else fails loudly here
    rather than at import time."""
    if tree == "*":
        return "*"
    if isinstance(tree, tuple) and len(tree) == 2 and tree[0] == "#":
        return ["#", tree[1]]
    return [_tree_to_json(t) for t in tree]


def _tree_from_json(tree):
    if tree == "*":
        return "*"
    if isinstance(tree, list) and len(tree) == 2 and tree[0] == "#":
        return ("#", tree[1])
    return tuple(_tree_from_json(t) for t in tree)


def _unflatten_nd(tree, leaves):
    it = iter(leaves)

    def _walk(t):
        if t == "*":
            return next(it)
        if isinstance(t, tuple) and len(t) == 2 and t[0] == "#":
            return t[1]
        return tuple(_walk(x) for x in t)

    return _walk(tree)


def infer_shapes(block, *args):
    """Fill the shapes of ``block``'s deferred parameters from ``args``
    without running an operation: ``Block.__call__`` under
    ``jax.eval_shape``, the layers' ``infer_shape`` hooks doing what they do
    in an eager first forward, every pending parameter standing in as an
    abstract array of its shape.  Returns the tree's pending parameters
    whose shape is now known, first those the forward asked for, in its
    order (the order an eager first forward would have initialized them
    in), then the rest: hand it to ``parameter.materialize``.

    The whole tree runs eagerly-styled (no child serves or builds a jit
    cache), paused and in predict mode.  Nothing outlives the pass but the
    shapes: every parameter gets back the array, or none, it held before,
    and layers that draw random numbers draw from a traced key, never from
    the framework's stream."""
    leaves, tree = _flatten_nd(args)
    owned = list(block.collect_params().values())
    held = [p._data for p in owned]
    prev_dry = getattr(_naming, "dry_run", False)
    _naming.dry_run = True
    _parameter._shape_pass.met = met = []

    def dry(key, *avals):
        inputs = _unflatten_nd(tree, tuple(NDArray(a) for a in avals))
        with _random.RandomScope(key), _autograd.pause():
            out = Block.__call__(block, *inputs)
        return [o._data for o in _flatten_nd(out)[0]]

    try:
        jax.eval_shape(
            dry, _parameter._key_aval(),
            *[jax.ShapeDtypeStruct(l.shape, jax.dtypes.canonicalize_dtype(
                l._data.dtype)) for l in leaves])
    finally:
        _parameter._shape_pass.met = None
        _naming.dry_run = prev_dry
        for p in met:
            p._data = None
        for p, d in zip(owned, held):
            p._data = d
    seen = set(map(id, met))
    return met + [p for p in owned if id(p) not in seen
                  and p._deferred_init is not None and p._shape_known()]


# what a recomputed block keeps besides its inputs: the attention kernels'
# output and log-sum-exp (a block without the kernels keeps its inputs alone)
_KEEP = jax.checkpoint_policies.save_only_these_names(*_KEPT)


@contextlib.contextmanager
def publish_kept():
    """Around one trace of a differentiated program: set the gauge
    ``recompute.kept_attention_bytes`` to the bytes of attention-kernel
    results that the recomputed blocks traced inside keep for the backward
    pass.  Left alone where no recomputed block ran."""
    outer = getattr(_naming, "kept", None)
    _naming.kept = kept = []
    try:
        yield
    finally:
        _naming.kept = outer
    if kept:
        _telemetry.registry().gauge(
            "recompute.kept_attention_bytes").set(sum(kept))


def _recomputed(block, args):
    """``block(*args)`` under ``jax.checkpoint``: the backward pass keeps
    the block's inputs and its attention kernels' results (``_KEEP``) and
    runs its forward again for everything else.  The block's parameters
    enter as the traced values they already are."""
    leaves, tree = _flatten_nd(args)
    held = [(p, p._data._data) for p in block.collect_params().values()
            if p._data is not None]
    seen = {}

    def body(*arrays):
        out = block._forward_hooked(
            *_unflatten_nd(tree, tuple(NDArray(a) for a in arrays)))
        for p, a in held:
            if p._data._data is not a:
                raise NotImplementedError(
                    f"{block.name}.recompute(): the forward rewrites "
                    f"{p.name} (aux state such as BatchNorm's running "
                    f"statistics), which cannot leave a recomputed block")
        out_leaves, seen["out"] = _flatten_nd(out)
        return [o._data for o in out_leaves]

    depth, before = getattr(_naming, "recomputing", 0), _traced_bytes()
    _naming.recomputing = depth + 1
    try:
        outs = jax.checkpoint(body, policy=_KEEP)(*[l._data for l in leaves])
    finally:
        _naming.recomputing = depth
    if depth == 0 and getattr(_naming, "kept", None) is not None:
        _naming.kept.append(_traced_bytes() - before)
    return _unflatten_nd(seen["out"], tuple(NDArray(o) for o in outs))


class _HookHandle:
    """Removable hook registration (ref: mxnet.gluon.utils.HookHandle)."""

    __slots__ = ("_hooks", "_hook")

    def __init__(self, hooks, hook):
        self._hooks = hooks
        self._hook = hook

    def detach(self):
        if self._hook is not None and self._hook in self._hooks:
            self._hooks.remove(self._hook)
        self._hook = None

    remove = detach  # torch-style alias


class Block:
    """Base neural-network container (ref: gluon/block.py — class Block)."""

    _recompute = False      # see recompute()

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix = _make_prefix(prefix, self._alias())
        self._scope_counters = {}
        self._params = ParameterDict(self._prefix, shared=params)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return type(self).__name__.lower()

    # ------------------------------------------------------------ registry --
    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
                self._params._params[value.name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_parameter(self, name, param):
        self._reg_params[name] = param
        self._params._params[param.name] = param

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return _HookHandle(self._forward_hooks, hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return _HookHandle(self._forward_pre_hooks, hook)

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._prefix[:-1] if self._prefix.endswith("_") else self._prefix

    @property
    def params(self):
        return self._params

    def name_scope(self):
        return _NameScope(self)

    def collect_params(self, select=None) -> ParameterDict:
        """ref: Block.collect_params — own + descendants, optional regex."""
        out = ParameterDict(self._params.prefix)
        pattern = re.compile(select) if select else None
        def _add(block):
            for name, p in block._params.items():
                if pattern is None or pattern.search(name):
                    out._params[name] = p
            for c in block._children.values():
                _add(c)
        _add(self)
        return out

    # --------------------------------------------------------------- setup --
    def initialize(self, init=None, ctx=None, verbose=False, force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)
        return self

    def cast(self, dtype):
        for p in self.collect_params().values():
            p.cast(dtype)
        for b in self._children.values():
            b.cast(dtype)
        self._invalidate_cache()
        return self

    def apply(self, fn):
        for c in self._children.values():
            c.apply(fn)
        fn(self)
        return self

    def hybridize(self, active=True, **kwargs):
        """ref: HybridBlock.hybridize; on plain Blocks, recurse to children."""
        for c in self._children.values():
            c.hybridize(active, **kwargs)

    def _invalidate_cache(self):
        for c in self._children.values():
            c._invalidate_cache()

    def recompute(self):
        """Trade compute for memory: wherever this block runs under a trace
        that is differentiated (``parallel.TrainStep``, a hybridized
        parent, ``jax.grad`` over ``functional_call``), keep only its
        inputs, and its attention kernels' results, for the backward pass and
        run the rest of its forward a second time there (``jax.checkpoint``
        with ``save_only_these_names`` on the flash kernels' ``out`` and
        ``lse``: ``B*H*T*D_v`` values of the output's dtype and ``B*H*T``
        float32 a call, so the kernel runs once a step).  Values and
        gradients are unchanged.
        Marks this block alone: mark each layer of a stack to hold one
        layer's intermediates at a time.  A block whose forward rewrites
        aux state (BatchNorm) raises.  Eager calls are not affected (the
        tape keeps every op's result).  Returns the block."""
        self._recompute = True
        self._invalidate_cache()
        return self

    # ---------------------------------------------------------------- save --
    def _collect_params_with_prefix(self, prefix=""):
        """Structural names ("features.0.weight") independent of name scopes
        (ref: Block._collect_params_with_prefix)."""
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        """ref: Block.save_parameters — structural-name flat param file."""
        from .. import ndarray as nd
        d = {k: p.data() for k, p in self._collect_params_with_prefix().items()}
        nd.save(filename, d)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        from .. import ndarray as nd
        loaded = nd.load(filename)
        by_key = self._collect_params_with_prefix()
        for key, p in by_key.items():
            if key in loaded:
                v = loaded[key]
                if cast_dtype and dtype_source == "current" and p._data is not None:
                    v = v.astype(p._data.dtype)
                p.set_data(v)
            elif not allow_missing:
                raise ValueError(f"missing parameter '{key}' in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(by_key)
            if extra:
                raise ValueError(f"extra parameters in {filename}: {sorted(extra)}")

    # ------------------------------------------------------------- forward --
    def __call__(self, *args):
        if self._recompute and not getattr(_naming, "dry_run", False) \
                and any(isinstance(a._data, jax.core.Tracer)
                        for a in _flatten_nd(args)[0]):
            return _recomputed(self, args)
        return self._forward_hooked(*args)

    def _forward_hooked(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a per-block table (ref: Block.summary).  With example
        ``inputs``, runs one hooked forward and includes each block's
        output shape, like the reference; without inputs, prints the
        param-count table only."""
        shapes = {}
        if inputs:
            removers = []

            def _capture(blk, _args, out):
                leaf = out[0] if isinstance(out, (tuple, list)) else out
                if hasattr(leaf, "shape"):
                    shapes[id(blk)] = tuple(leaf.shape)

            def _hook_all(b):
                removers.append(b.register_forward_hook(_capture))
                for c in b._children.values():
                    _hook_all(c)

            _hook_all(self)
            # dry_run keeps the WHOLE tree eager: hybridized children must
            # not serve (or build) jit caches — hooks only fire on real
            # eager calls, and a warm child cache would skip them.
            prev_dry = getattr(_naming, "dry_run", False)
            _naming.dry_run = True
            try:
                from .. import autograd as _ag
                with _ag.pause():
                    Block.__call__(self, *inputs)
            finally:
                _naming.dry_run = prev_dry
                for r in removers:
                    r.detach()

        rows = []

        def _walk(b, depth):
            n = sum(int(np.prod(p.shape)) for p in b._params.values()
                    if p.shape is not None)
            rows.append(("  " * depth + type(b).__name__, b.name, n,
                         shapes.get(id(b), "")))
            for c in b._children.values():
                _walk(c, depth + 1)
        _walk(self, 0)
        total = sum(int(np.prod(p.shape)) for p in self.collect_params().values()
                    if p.shape is not None)
        shp = bool(shapes)
        hdr = f"{'Layer':<34}{'Name':<24}{'Params':>10}"
        if shp:
            hdr += f"  {'Output Shape'}"
        lines = [hdr, "-" * (80 if shp else 68)]
        for a, b, c, s in rows:
            line = f"{a:<34}{b:<24}{c:>10}"
            if shp:
                line += f"  {s}"
            lines.append(line)
        lines += ["-" * (80 if shp else 68),
                  f"{'Total params:':<58}{total:>10}"]
        print("\n".join(lines))

    def __repr__(self):
        kids = "\n".join(f"  ({k}): {v!r}".replace("\n", "\n  ")
                         for k, v in self._children.items())
        return f"{type(self).__name__}(\n{kids}\n)" if kids else f"{type(self).__name__}()"


class HybridBlock(Block):
    """Block whose forward can be captured and compiled (ref: class HybridBlock)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._jit_fn = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Switch to compiled execution (ref: HybridBlock.hybridize →
        CachedOp with static_alloc/static_shape; jit subsumes both flags)."""
        self._active = active
        self._flags = dict(static_alloc=static_alloc, static_shape=static_shape,
                           **kwargs)
        self._invalidate_cache()
        for c in self._children.values():
            c.hybridize(active, static_alloc=static_alloc,
                        static_shape=static_shape, **kwargs)

    def _invalidate_cache(self):
        self._jit_fn = None
        for c in self._children.values():
            c._invalidate_cache()

    # ------------------------------------------------------ deferred shapes --
    def infer_shape(self, *args):
        """Layer hook: fill wildcard (0) dims of own params from inputs.
        ref: HybridBlock._deferred_infer_shape (symbolic infer replaced by
        per-layer rules; composite blocks infer via ``infer_shapes``)."""
        raise DeferredInitializationError(
            f"{type(self).__name__} cannot infer parameter shapes; "
            f"initialize with fully-specified shapes")

    def _ensure_init(self, *args):
        """Finish any pending deferred initialization using input shapes."""
        pending = [p for p in self._reg_params.values() if p._deferred_init is not None]
        if pending:
            self.infer_shape(*args)
            for p in pending:
                p._finish_deferred_init()

    def _has_pending(self):
        if getattr(self, "_pending_done", False):
            return False
        for p in self.collect_params().values():
            if p._deferred_init is not None:
                return True
        self._pending_done = True
        return False

    # -------------------------------------------------------------- forward --
    def __call__(self, *args):
        if (self._active and not getattr(_naming, "dry_run", False)
                and not any(
                    isinstance(a, NDArray) and isinstance(a._data, jax.core.Tracer)
                    for a in args)):
            if self._has_pending():
                # one abstract pass resolves every deferred shape in the
                # tree, one program makes every pending array
                _parameter.materialize(infer_shapes(self, *args))
            return self._call_cached(*args)
        return Block.__call__(self, *args)

    def forward(self, x, *args):
        """Gather own params and delegate to hybrid_forward (ref:
        HybridBlock.forward — NDArray branch)."""
        from .. import ndarray as ndmod
        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._ensure_init(x, *args)
            params = {k: p.data() for k, p in self._reg_params.items()}
        return self.hybrid_forward(ndmod, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # ------------------------------------------------------------ cached op --
    def _param_list(self):
        params = self.collect_params()
        names = sorted(params.keys())
        return names, [params[n] for n in names]

    def _build_jit(self):
        self_ref = self

        def jit_body(param_arrays, rng_key, training, tree, sig, *leaves):
            names, plist = self_ref._param_list()
            saved = [(p, p._data) for p in plist]
            prev_train = _autograd.set_training(training)
            try:
                for p, arr in zip(plist, param_arrays):
                    p._data = NDArray(arr)
                wrapped = tuple(NDArray(l) for l in leaves)
                inputs = _unflatten_nd(tree, wrapped)
                with _random.RandomScope(rng_key):
                    out = Block.__call__(self_ref, *inputs)
                # Aux-state mutation (BatchNorm running stats): a layer that
                # reassigns a Parameter's array during the trace produces an
                # extra output, written back after execution (the reference
                # mutates aux NDArrays through the engine; under XLA state is
                # explicit — ref: cached_op.cc handling of aux_states).
                mutated_idx, mutated_vals = [], []
                for i, (p, arr) in enumerate(zip(plist, param_arrays)):
                    cur = p._data
                    if isinstance(cur, NDArray) and cur._data is not arr:
                        mutated_idx.append(i)
                        mutated_vals.append(cur._data)
            finally:
                for p, d in saved:
                    p._data = d
                _autograd.set_training(prev_train)
            out_leaves, out_tree = _flatten_nd(out)
            self_ref._out_trees[sig] = out_tree
            self_ref._aux_idx[sig] = tuple(mutated_idx)
            self_ref._n_out[sig] = len(out_leaves)
            return tuple(o._data for o in out_leaves) + tuple(mutated_vals)

        return jax.jit(jit_body, static_argnums=(2, 3, 4))

    def _call_cached(self, *args):
        if self._jit_fn is None:
            self._out_trees = {}
            self._aux_idx = {}
            self._n_out = {}
            self._jit_fn = self._build_jit()
        names, plist = self._param_list()
        param_arrays = [p.data()._data for p in plist]
        leaves_nd, tree = _flatten_nd(args)
        leaves = [l._data for l in leaves_nd]
        training = _autograd.is_training()
        sig = (tree, training,
               tuple((tuple(l.shape), str(l.dtype)) for l in leaves))
        # remember the call signature so export() can retrace for serving
        # (plain tuples: this is the hot path, avals are built in export)
        self._export_info = (tree, tuple(
            (tuple(l.shape), l.dtype) for l in leaves))
        key = _random.next_key()

        if _autograd.is_recording():
            # One tape node for the whole block: compiled forward + compiled
            # backward (ref: CachedOp::Backward).  The PRNG key must be a vjp
            # ARGUMENT, not a closure: closed-over concrete arrays become jaxpr
            # constants, so a fresh key per step would defeat the compile cache
            # (recompile every step).
            fn = self._jit_fn

            def diff_fn(pa, k, *lv):
                return fn(pa, k, training, tree, sig, *lv)

            outs, pull_k = jax.vjp(diff_fn, param_arrays, key, *leaves)

            def pull(cts, _p=pull_k):
                pg, _kg, *ig = _p(cts)
                return (pg, *ig)
            out_nds = tuple(NDArray(o) for o in outs)
            tape_inputs = [p.data() for p in plist] + list(leaves_nd)

            def pullback(cts, _pull=pull, _n=len(outs)):
                pg, *ig = _pull(tuple(cts[:_n]))
                return list(pg) + list(ig)

            node = _autograd.TapeNode(tape_inputs, list(out_nds), pullback,
                                      name=f"cachedop_{self.name}")
            _autograd.append_node(node)
        else:
            outs = self._jit_fn(param_arrays, key, training, tree, sig, *leaves)
            out_nds = tuple(NDArray(o) for o in outs)
        n = self._n_out[sig]
        for i, new_val in zip(self._aux_idx[sig], outs[n:]):
            plist[i]._data._data = new_val
        result = _unflatten_nd(self._out_trees[sig], out_nds[:n])
        return result

    # ---------------------------------------------------------------- export --
    def export(self, path, epoch=0):
        """ref: HybridBlock.export — graph json + params.

        The TPU-native graph artifact is a serialized StableHLO program
        (jax.export) of the block's inference forward with parameters as
        inputs, plus the structural-name param file.  The pair reloads into
        a servable callable WITHOUT the defining Python class via
        ``SymbolBlock.imports`` (ref: model-symbol.json / model-0000.params
        round-trip).  The block must have run at least one hybridized
        forward so input shapes are known — same precondition as the
        reference's export.
        """
        import json
        import os

        params_file = f"{path}-{epoch:04d}.params"
        self.save_parameters(params_file)
        # file references are BASENAMES resolved against the json's own
        # directory at import time, so the artifact directory is relocatable
        meta = {"framework": "mxnet_tpu", "block": type(self).__name__,
                "prefix": self._prefix,
                "params": os.path.basename(params_file)}
        if getattr(self, "_export_info", None) is not None:
            tree, leaf_sig = self._export_info
            names, plist = self._param_list()
            # param order in the graph is _param_list order; the .params
            # file keys are STRUCTURAL names — record the mapping so imports
            # can feed arrays in graph order whatever the name counters say
            by_id = {id(p): sn
                     for sn, p in self._collect_params_with_prefix().items()}
            try:
                struct_order = [by_id[id(p)] for p in plist]
            except KeyError:
                struct_order = None  # params outside the tree: graph skipped
            if struct_order is not None:
                param_avals = [jax.ShapeDtypeStruct(p.data().shape,
                                                    p.data()._data.dtype)
                               for p in plist]
                leaf_avals = [jax.ShapeDtypeStruct(s, d)
                              for s, d in leaf_sig]
                sig = (tree, False,
                       tuple((tuple(a.shape), str(a.dtype))
                             for a in leaf_avals))
                if self._jit_fn is None:
                    self._out_trees, self._aux_idx, self._n_out = {}, {}, {}
                    self._jit_fn = self._build_jit()
                jit_fn = self._jit_fn

                def serve(param_arrays, *leaves):
                    # inference mode: fixed key (dropout off), no aux writes
                    return jit_fn(param_arrays, jax.random.key(0), False,
                                  tree, sig, *leaves)

                exp = jax.export.export(jax.jit(serve),
                                        platforms=("cpu", "tpu"))(
                    param_avals, *leaf_avals)
                graph_file = f"{path}-graph.bin"
                # raw StableHLO bytes on disk + json-only metadata: the
                # artifact stays non-executable at load time (no pickle)
                with open(graph_file, "wb") as f:
                    f.write(exp.serialize())
                meta["graph"] = os.path.basename(graph_file)
                meta["out_tree"] = _tree_to_json(self._out_trees[sig])
                meta["n_out"] = self._n_out[sig]
                meta["param_order"] = struct_order
        with open(f"{path}-symbol.json", "w") as f:
            json.dump(meta, f, indent=2)
        return f"{path}-symbol.json", params_file


class SymbolBlock(HybridBlock):
    """Construct a Block from symbol outputs (ref: class SymbolBlock).

    Three accepted forms of ``outputs``:
      * an ``mx.sym.Symbol`` graph + ``inputs`` (Symbols or names) — the
        reference's original contract: remaining graph arguments become
        Parameters (aux states with grad_req null), and forward evaluates
        the DAG through ``nd.invoke`` so autograd/hybridize work normally;
      * any jax-traceable python callable + params (TPU-native form);
      * ``SymbolBlock.imports`` — class-free serving from
        ``HybridBlock.export``'s StableHLO artifact.
    """

    def __init__(self, outputs, inputs=None, params=None, prefix=None):
        super().__init__(prefix=prefix)
        from .. import symbol as _symbol

        self._sym = None
        if isinstance(outputs, _symbol.Symbol):
            self._init_from_symbol(outputs, inputs, params)
            return
        if not callable(outputs):
            raise TypeError("SymbolBlock(outputs): outputs must be a Symbol "
                            "or a callable built from framework ops")
        self._fn = outputs
        if params:
            for name, p in (params.items() if hasattr(params, "items") else
                            ((p.name, p) for p in params)):
                self._params._params[name] = p

    def _init_from_symbol(self, outputs, inputs, params):
        from .. import symbol as _symbol

        self._sym = outputs
        if inputs is None:
            inputs = ["data"]
        if isinstance(inputs, (str, _symbol.Symbol)):
            inputs = [inputs]
        for s in inputs:
            if isinstance(s, _symbol.Symbol) and s._node.op is not None:
                raise ValueError(
                    f"SymbolBlock: input {s.name!r} is an op output, not a "
                    f"variable; graph cutting is not supported — rebuild the "
                    f"subgraph from a Variable (or bind the full symbol)")
        self._sym_inputs = [s.name if isinstance(s, _symbol.Symbol) else s
                            for s in inputs]
        arg_names = self._sym.list_arguments()
        aux_names = self._sym.list_auxiliary_states()
        unknown = [n for n in self._sym_inputs
                   if n not in arg_names and n not in aux_names]
        if unknown:
            raise ValueError(
                f"SymbolBlock: inputs {unknown} are not variables of the "
                f"symbol (its variables: {arg_names})")
        # loss-head label variables are inputs, never weights: zeros are
        # fed at forward unless the caller wires them as inputs
        self._label_vars = _symbol.label_variables(self._sym) \
            - set(self._sym_inputs)
        self._label_shape_cache = {}
        given = {}
        if params:
            items = params.items() if hasattr(params, "items") else \
                ((p.name, p) for p in params)
            for name, p in items:
                # accept mx.model arg_params-style 'arg:'/'aux:' prefixes
                key = name.split(":", 1)[1] if name[:4] in ("arg:", "aux:") \
                    else name
                given[key] = p
        for n in arg_names + aux_names:
            if n in self._sym_inputs or n in self._label_vars:
                continue
            p = given.pop(n, None)
            if isinstance(p, Parameter):
                self._params._params[n] = p
                continue
            param = Parameter(n, shape=None, allow_deferred_init=True,
                              grad_req="null" if n in aux_names else "write")
            if p is not None:  # an NDArray/array from load_checkpoint
                param.set_data(p if isinstance(p, NDArray)
                               else NDArray(np.asarray(p)))
            self._params._params[n] = param
        if given:
            # a key mismatch must not silently yield a random-init model
            # (ref: SymbolBlock raises for params not found in the symbol)
            raise ValueError(
                f"SymbolBlock: params {sorted(given)} match no argument of "
                f"the symbol (its arguments: {arg_names + aux_names})")

    def forward(self, *args):
        if self._sym is None:
            return self._fn(*args)
        from ..executor import walk_graph
        from ..ndarray import invoke as _invoke

        if len(args) != len(self._sym_inputs):
            raise ValueError(f"SymbolBlock: expected {len(self._sym_inputs)} "
                             f"inputs {self._sym_inputs}, got {len(args)}")
        feed = dict(zip(self._sym_inputs, args))
        missing_labels = [n for n in self._label_vars if n not in feed]
        if missing_labels:
            ckey = tuple(tuple(feed[n].shape) for n in self._sym_inputs)
            if ckey not in self._label_shape_cache:
                from ..symbol import infer_arg_shapes
                self._label_shape_cache[ckey] = infer_arg_shapes(
                    self._sym, {n: tuple(feed[n].shape)
                                for n in self._sym_inputs})
            shp = self._label_shape_cache[ckey]
            for n in missing_labels:
                feed[n] = NDArray(jax.numpy.zeros(shp[n], jax.numpy.float32))
        pending = [p for p in self._params._params.values()
                   if p._data is None and p._deferred_init is not None]
        if pending:
            # first forward with known input shapes finishes deferred init
            # (ref: SymbolBlock parameter shape inference at first call)
            from ..symbol import infer_arg_shapes
            shapes = infer_arg_shapes(
                self._sym, {n: tuple(feed[n].shape)
                            for n in self._sym_inputs})
            for p in pending:
                p._finish_deferred_init(shapes.get(p.name))

        def leaf(node):
            if node.name in feed:
                return feed[node.name]
            return self._params._params[node.name].data()

        def apply_op(node, ins, attrs):
            # nd.invoke injects the training flag and tapes under autograd
            return _invoke(node.op, *ins, **attrs)

        def aux_update(name, v_new):
            if _autograd.is_training():
                # in place (set_data) so external aliases of the aux
                # NDArray see fresh stats, like the reference's mutation
                self._params._params[name].set_data(v_new)

        outs = walk_graph(self._sym, leaf, apply_op, aux_update)
        return outs[0] if len(outs) == 1 else tuple(outs)

    @staticmethod
    def imports(symbol_file, input_names=None, param_file=None, ctx=None):
        """Reconstruct a servable block from ``HybridBlock.export`` output
        WITHOUT the defining Python class (ref: SymbolBlock.imports over
        model-symbol.json + model-0000.params).

        The graph is the serialized StableHLO program export wrote next to
        the json descriptor; params load by structural name and feed the
        graph in its recorded order.  ``input_names`` is accepted for API
        compatibility (the graph's positional signature is authoritative).
        """
        import json
        import os

        from .. import ndarray as ndmod

        with open(symbol_file) as f:
            meta = json.load(f)
        graph_file = meta.get("graph")
        if not graph_file:
            raise ValueError(
                f"{symbol_file} has no serialized graph — it predates "
                "graph export; re-export the model after one hybridized "
                "forward (or rebuild the model class and use "
                "load_parameters)")
        base = os.path.dirname(os.path.abspath(symbol_file))
        with open(os.path.join(base, graph_file), "rb") as f:
            exported = jax.export.deserialize(f.read())
        params_path = param_file or os.path.join(base, meta["params"])
        loaded = ndmod.load(params_path)
        missing = [n for n in meta["param_order"] if n not in loaded]
        if missing:
            raise ValueError(
                f"params file {params_path} is missing graph inputs "
                f"{missing}")
        param_arrays = [loaded[n]._data for n in meta["param_order"]]
        out_tree = _tree_from_json(meta["out_tree"])
        n_out = meta["n_out"]

        def fn(*args):
            leaves_nd, _ = _flatten_nd(args)
            outs = exported.call(param_arrays,
                                 *[l._data for l in leaves_nd])
            out_nds = tuple(NDArray(o) for o in outs[:n_out])
            return _unflatten_nd(out_tree, out_nds)

        blk = SymbolBlock(fn)
        for name, arr in loaded.items():
            p = Parameter(name, shape=arr.shape, dtype=None)
            p._data = arr
            blk._params._params[name] = p
        return blk
