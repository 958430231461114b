"""Fused SPMD train step.

ref: the reference's training loop is CachedOp::Forward +
Imperative::Backward + kvstore push/pull + optimizer_op updates, each a
separate engine-scheduled stage (SURVEY.md §3.2/§3.3).  TPU-native, ALL of it
— forward, loss, backward, cross-device gradient reduction, optimizer update
— is one jitted XLA program over a sharded mesh: XLA inserts the ICI
collectives where the `dp` axis demands them (the KVStore allreduce), overlaps
them with compute, and fuses the whole optimizer (the reference's
`multi_sgd_update`/`multi_lamb` multi-tensor fusion, taken to 100%).

Usage:
    mesh = parallel.make_mesh(dp=8)
    step = parallel.TrainStep(net, loss_fn, optimizer, mesh=mesh)
    for data, label in loader:
        loss = step(data, label)          # sharded, async
    step.sync_params_to_net()             # reflect into Gluon Parameters
"""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .. import random as _random
from ..fault import fire as _fire
from ..elastic import NonFiniteAbortError
from .. import profiler as _profiler
from .. import telemetry as _telemetry
from ..profiler import scope as _pscope
from ..ndarray import NDArray
from ..gluon.block import (_flatten_nd, _unflatten_nd, infer_shapes,
                           publish_kept)
from ..gluon.parameter import _run_program, materialize
from .mesh import MeshScope, default_mesh
from .sharding import ShardingRules, batch_spec, param_sharding
from .functional import (FunctionalState, functional_call,
                         param_names_and_values, trainable_split)
from .functional_opt import pure_update, state_template
from . import quantize as _quantize
from .mesh import shard_map as _shard_map

__all__ = ["TrainStep", "EvalStep", "all_finite_rows", "add_transfer_hook",
           "remove_transfer_hook"]


def all_finite_rows(arrays):
    """Per-example all-finite verdict over batch-major arrays.

    The serving-side counterpart of ``TrainStep(skip_nonfinite=True)``'s
    fused guard: the same isfinite/and reduction, taken per ROW instead
    of over the whole update, so the InferenceServer can fail exactly the
    poisoned request in a batch while its neighbours (and the server)
    carry on.  ``arrays`` is one array or a list whose leading axis is
    the batch; returns a host bool mask of shape ``(batch,)`` — True
    where every element of that example's outputs is finite."""
    mask = None
    for a in arrays if isinstance(arrays, (list, tuple)) else (arrays,):
        x = a._data if isinstance(a, NDArray) else a
        if isinstance(x, np.ndarray):
            # already on host (the serving path lands here after its
            # outputs were pulled for splitting): a host reduction —
            # round-tripping through the device would ADD two transfers
            # plus a sync per batch
            m = np.isfinite(x.reshape((x.shape[0], -1))).all(axis=1)
        else:
            # still on device: reduce there, ship back one bool per row
            m = np.asarray(jnp.all(
                jnp.isfinite(jnp.reshape(x, (x.shape[0], -1))), axis=1))
        mask = m if mask is None else np.logical_and(mask, m)
    return np.asarray(mask)

# Observers of actual host→device batch transfers (called as fn(leaf,
# sharding) right before each real device_put in _put_batch — NOT for
# pre-placed batches, which skip the put).  Tests and the profiler use this
# to assert/see that the async feed does exactly one transfer per leaf.
_TRANSFER_HOOKS = []


def add_transfer_hook(fn):
    """Register ``fn(leaf, sharding)`` to run on every real batch H2D put."""
    _TRANSFER_HOOKS.append(fn)
    return fn


def remove_transfer_hook(fn):
    _TRANSFER_HOOKS.remove(fn)


def _leaves(args):
    nds, tree = _flatten_nd(args)
    return [a._data for a in nds], tree


# decorrelates the gradient-quantizer rounding stream from the forward
# pass's dropout stream (both fold from the step's one PRNG key)
_GRADQ_SALT = 0x6A5D


def _coerce_arrays(v):
    """Accept raw numpy / jax arrays as batch leaves (wrap into NDArray so
    they flatten as data, not as static tree structure).  numpy stays in
    host memory — placement happens once, in ``_put_batch``."""
    if isinstance(v, (tuple, list)):
        return tuple(_coerce_arrays(x) for x in v)
    if isinstance(v, (np.ndarray, jax.Array)):
        return NDArray(v)
    return v


def _put_batch(leaf, sharding):
    """Place one batch leaf on the mesh.

    Single-process: plain device_put (the leaf is the full global batch).
    Multi-process (``jax.distributed``): the leaf is this worker's LOCAL
    shard — the reference's per-worker data partition (each worker reads its
    own slice of the dataset; SURVEY §3.3) — so the global batch is assembled
    from per-process shards without any cross-host copy.  A leaf that is
    already a global (not fully addressable) jax.Array is already placed;
    hand it to device_put for a sharding-to-sharding transfer instead.

    A leaf that ALREADY carries the target sharding (a DevicePrefetcher
    placed it while the previous step ran) is returned as-is: no second
    device_put, no transfer-hook callback — the async feed's steady state
    costs zero extra HBM traffic."""
    if isinstance(leaf, jax.Array) \
            and getattr(leaf, "sharding", None) == sharding:
        return leaf
    if jax.process_count() > 1:
        if not (isinstance(leaf, jax.Array) and not leaf.is_fully_addressable):
            for fn in _TRANSFER_HOOKS:
                fn(leaf, sharding)
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(leaf))
    for fn in _TRANSFER_HOOKS:
        fn(leaf, sharding)
    return jax.device_put(leaf, sharding)


def _has_pending(net):
    return any(p._deferred_init is not None
               for p in net.collect_params().values())


# The set-up spans (``cat="setup"``: kept whatever ``telemetry.enable``'s
# ``sample``), ``<who>`` being ``TrainStep`` or ``EvalStep``:
# ``<who>.deferred_init`` (children ``.infer_shapes``, ``.materialize``),
# ``<who>.state_init`` and ``<who>.compile``.
def _materialize_net(net, sample_args, mesh, who):
    """Before a step first reads ``net``'s parameters: one abstract pass
    for the deferred shapes, then one program that makes every pending
    array (``program``: ``stored`` where its module was found beside the
    compile cache, ``lowered`` where it had to be made, absent where this
    process had run it before).  ``executables``: what jax created under
    the span, counted while ``config.watch_compiles`` listens.  Returns how
    many arrays it made."""
    with _pscope(f"{who}.deferred_init", cat="setup") as span, \
            MeshScope(mesh):
        before = _telemetry.compile_stats()["executables_created"]
        with _pscope(f"{who}.infer_shapes", cat="setup"):
            pending = infer_shapes(net, *sample_args)
        with _pscope(f"{who}.materialize", cat="setup"):
            made = materialize(pending)
        span.set(params=made, executables=_telemetry.compile_stats()[
            "executables_created"] - before)
    return made


def _compile_first(who, call):
    """The first call of a signature: trace, lower, compile or load from
    the persistent cache, and the first execution (waited for, so the span
    holds all of it).  The span's attributes are jax's own account of the
    call (``telemetry.compile_split``): ``trace_ms``, ``lower_ms``,
    ``backend_ms``, ``cache_retrieval_ms`` and ``cache_hit``."""
    with _pscope(f"{who}.compile", cat="setup") as span:
        before = _telemetry.compile_stats()
        out = jax.block_until_ready(call())
        span.set(**_telemetry.compile_split(before))
    return out


class TrainStep:
    """Compiled (params, states, batch) → (params', states', loss) on a mesh."""

    def __init__(self, net, loss_fn, optimizer, mesh=None, rules=None,
                 data_spec=None, loss_reduce="mean", donate_batch=False,
                 skip_nonfinite=False, nonfinite_budget=10,
                 grad_reduce="f32", heartbeat=None):
        self.net = net
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = mesh if mesh is not None else default_mesh()
        self.rules = rules or ShardingRules()
        self._data_pspec = data_spec if data_spec is not None \
            else batch_spec(self.mesh)
        self._loss_reduce = loss_reduce
        # donate_batch=True donates the batch buffers to the XLA program so
        # a prefetched feed costs no steady-state HBM beyond the in-flight
        # batches.  Only safe when every batch is consumed exactly once
        # (DevicePrefetcher feed) — NOT when the caller re-steps the same
        # arrays (bench-style loops).
        self._donate_batch = bool(donate_batch)
        # skip_nonfinite=True guards the update with a fused all-finite
        # check over loss+grads INSIDE the compiled program: a NaN/Inf
        # batch leaves params, optimizer state, aux state and the step
        # counter untouched (the update is a select, not a branch — no
        # retrace, no host round-trip beyond the verdict scalar).  After
        # ``nonfinite_budget`` CONSECUTIVE skips the step aborts with a
        # diagnostic instead of silently treading water while the run
        # diverges; ``nonfinite_budget=None`` disables the abort.
        self._skip_nonfinite = bool(skip_nonfinite)
        self._nonfinite_budget = nonfinite_budget
        # grad_reduce selects the cross-device gradient wire format:
        # "f32" keeps the implicit sharding-inserted full-precision
        # collective; "bf16"/"int8" route the backward pass through an
        # explicit shard_map reduction stage over the dp axis
        # (parallel.quantize.reduce_gradients) — same jitted program,
        # compressed collective payloads, stochastic rounding driven by
        # the step's PRNG key.  Quantized modes need a pure
        # data-parallel mesh: the explicit stage replicates params per
        # device, which a tp/fsdp-sharded layout would contradict.
        if grad_reduce not in _quantize.GRAD_REDUCE_MODES:
            raise ValueError(
                f"TrainStep: grad_reduce={grad_reduce!r} not in "
                f"{_quantize.GRAD_REDUCE_MODES}")
        if grad_reduce != "f32":
            if "dp" not in self.mesh.shape:
                raise ValueError(
                    f"TrainStep: grad_reduce={grad_reduce!r} needs a "
                    f"'dp' mesh axis to reduce over (mesh axes: "
                    f"{dict(self.mesh.shape)})")
            extra = {a: s for a, s in self.mesh.shape.items()
                     if a != "dp" and s > 1}
            if extra:
                raise ValueError(
                    f"TrainStep: grad_reduce={grad_reduce!r} supports "
                    f"pure data-parallel meshes only; model-parallel "
                    f"axes {extra} shard the params the explicit "
                    f"reduction stage would replicate")
        self._grad_reduce = grad_reduce
        # heartbeat: an elastic.Heartbeat stamped after every completed
        # step (host side, post-dispatch) — the supervised-training
        # liveness wire (docs/api.md "Elastic training")
        self._heartbeat = heartbeat
        self.skipped_steps = 0
        self.consecutive_skips = 0
        self._skip_counter = _profiler.Counter(
            None, "TrainStep::nonfinite_skips")
        self._built = False
        self._jit = None
        self._num_update = optimizer.begin_num_update

    @property
    def data_sharding(self):
        """The NamedSharding batch leaves are placed with — what a
        DevicePrefetcher needs to pre-place batches this step will accept
        without a second transfer."""
        return NamedSharding(self.mesh, self._data_pspec)

    # --------------------------------------------------------------- build --
    def _build(self, sample_args):
        if _has_pending(self.net):
            _materialize_net(self.net, sample_args, self.mesh, "TrainStep")
        names, plist, arrays = param_names_and_values(self.net)
        self._names, self._plist = names, plist
        self._train_idx, self._aux_idx = trainable_split(plist)
        shardings = param_sharding(names, [a.shape for a in arrays],
                                   self.mesh, self.rules)
        self._param_shardings = shardings
        train_sh = [shardings[i] for i in self._train_idx]
        aux_sh = [shardings[i] for i in self._aux_idx]
        opt = self.optimizer

        def own(arrays):
            # the step DONATES its arrays: copies, so the net's Parameters
            # stay readable, and the optimizer state, in one program
            train = [arrays[i] for i in self._train_idx]
            return ([jnp.copy(a) for a in train],
                    [jnp.copy(arrays[i]) for i in self._aux_idx],
                    tuple(tuple(state_template(opt, a)) for a in train))

        # ends when ``own`` is compiled (or loaded) and DISPATCHED: its
        # writes, 12 bytes a parameter under a multi-precision optimizer,
        # run on the device under what follows (``TrainStep.compile`` waits)
        with _pscope("TrainStep.state_init", cat="setup"):
            arrays = jax.device_put(arrays, shardings)
            self._train_arrays, self._aux_arrays, self._states = \
                _run_program(own, (arrays,),
                             (train_sh, aux_sh, tuple(train_sh)))
        # static per-param lr/wd multipliers (ref: Optimizer._get_lr/_get_wd)
        self._lr_mults = [plist[i].lr_mult for i in self._train_idx]
        self._wd_mults = [plist[i].wd_mult for i in self._train_idx]
        self._repl = NamedSharding(self.mesh, PartitionSpec())
        # device_put so t's aval carries the mesh like the jit outputs do —
        # otherwise step 2 retraces (t: i32[]({}) vs i32[]({Auto: (dp,)}))
        self._t = jax.device_put(np.int32(self._num_update), self._repl)
        self._built = True

    def _base_lr(self):
        # evaluated at the post-increment count, matching the eager path
        # (Optimizer._update_count runs before _get_lr)
        opt = self.optimizer
        if opt.lr_scheduler is not None:
            return float(opt.lr_scheduler(self._num_update + 1))
        return float(opt.lr)

    def _compile(self, data_tree, label_tree, n_data):
        net, opt = self.net, self.optimizer
        plist = self._plist
        train_idx, aux_idx = self._train_idx, self._aux_idx
        lr_mults, wd_mults = self._lr_mults, self._wd_mults
        loss_fn, reduce = self.loss_fn, self._loss_reduce
        state_holder = FunctionalState()

        def fn(train_arrays, aux_arrays, states, t, key, lr, *batch):
            data_leaves = list(batch[:n_data])
            label_leaves = list(batch[n_data:])

            def value_grad(ta_in, aux_in, key_in, dl, ll):
                def loss_of(ta):
                    pa = [None] * len(plist)
                    for i, a in zip(train_idx, ta):
                        pa[i] = a
                    for i, a in zip(aux_idx, aux_in):
                        pa[i] = a
                    # mesh visible to mesh-aware ops (ring/ulysses attn)
                    with MeshScope(self.mesh), jax.named_scope("forward"):
                        outs = functional_call(net, plist, pa, data_tree,
                                               dl, key_in, True,
                                               state_holder)
                    out_nd = _unflatten_nd(state_holder.out_tree,
                                           tuple(NDArray(o) for o in outs))
                    lab_nd = _unflatten_nd(label_tree,
                                           tuple(NDArray(l) for l in ll))
                    if isinstance(lab_nd, tuple) and len(lab_nd) == 1:
                        lab_nd = lab_nd[0]
                    with jax.named_scope("loss"):
                        loss = loss_fn(out_nd, lab_nd)
                        lv = loss._data if isinstance(loss, NDArray) else loss
                        lv = jnp.mean(lv) if reduce == "mean" else jnp.sum(lv)
                    mut = [m for _, m in state_holder.mutated]
                    return lv.astype(jnp.float32), mut

                # device-side names: jax writes the forward pass's ops as
                # ``jvp(forward)/...`` and their transposes, the backward
                # pass, as ``transpose(jvp(forward))/...``
                with publish_kept():
                    return jax.value_and_grad(loss_of, has_aux=True)(ta_in)

            if self._grad_reduce == "f32":
                # implicit path: grads of the sharded-batch loss — the
                # SPMD partitioner inserts the full-precision all-reduce
                (loss, mut), grads = value_grad(
                    train_arrays, aux_arrays, key, data_leaves,
                    label_leaves)
            else:
                # explicit path: per-device local grads inside shard_map,
                # reduced by parallel.quantize with a compressed wire
                # format.  The local loss is the mean/sum over the LOCAL
                # shard; pmean/psum restores the global reduction (equal
                # shard sizes — sharding already guarantees that).
                dp = self.mesh.shape["dp"]
                mode = self._grad_reduce

                def local_step(ta, aux, k, *leaves):
                    # per-device key: forward RNG (dropout) and the
                    # rounding streams decorrelate across replicas
                    dk = jax.random.fold_in(k, jax.lax.axis_index("dp"))
                    (lv, mu), gr = value_grad(ta, aux, dk,
                                              list(leaves[:n_data]),
                                              list(leaves[n_data:]))
                    gr = _quantize.reduce_gradients(
                        gr, "dp", dp, mode=mode,
                        key=jax.random.fold_in(dk, _GRADQ_SALT),
                        reduce=reduce)
                    lv = (jax.lax.pmean if reduce == "mean"
                          else jax.lax.psum)(lv, "dp")
                    # aux updates (BN running stats) are per-shard here:
                    # average the float ones; anything non-float is
                    # assumed replica-identical
                    # mxlint: disable=spmd-collective-in-loop -- deliberate
                    # per-leaf comprehension over the short aux-state
                    # list (BN running stats): leaves have heterogeneous
                    # shapes and only float ones reduce
                    mu = [jax.lax.pmean(m, "dp")
                          if jnp.issubdtype(m.dtype, jnp.floating) else m
                          for m in mu]
                    return lv, mu, gr

                repl = PartitionSpec()
                loss, mut, grads = _shard_map(
                    local_step, mesh=self.mesh,
                    in_specs=(repl, repl, repl)
                    + tuple([self._data_pspec] * len(batch)),
                    out_specs=(repl, repl, repl),
                    check_vma=False)(train_arrays, aux_arrays, key, *batch)
            t1 = t + 1
            new_train, new_states = [], []
            with jax.named_scope("optimizer"):
                for k, (w, g, s) in enumerate(zip(train_arrays, grads,
                                                  states)):
                    lr_k = lr * lr_mults[k]
                    wd_k = opt.wd * wd_mults[k]
                    nw, ns = pure_update(opt, w, g, s, t1, lr_k, wd_k)
                    new_train.append(nw)
                    new_states.append(ns)
            # aux-state writeback (BatchNorm running stats — the reference's
            # aux_states path in cached_op.cc)
            mut_map = {i: v for (i, _), v in zip(state_holder.mutated, mut)}
            new_aux = [mut_map.get(i, a) for i, a in zip(aux_idx, aux_arrays)]
            if not self._skip_nonfinite:
                return new_train, new_aux, tuple(new_states), t1, loss
            # fused all-finite guard: one reduction over loss+grads, then
            # every state transition becomes a select against it.  XLA
            # fuses the isfinite/and tree into the backward pass; a bad
            # batch costs the same step wall-clock as a good one.
            finite = jnp.all(jnp.isfinite(loss))   # scalar even when
            for g in grads:                        # loss_reduce="none"
                finite = jnp.logical_and(finite,
                                         jnp.all(jnp.isfinite(g)))
            keep = lambda new, old: jnp.where(finite, new, old)  # noqa: E731
            new_train = [keep(n, o) for n, o in zip(new_train, train_arrays)]
            new_states = [tuple(keep(n, o) for n, o in zip(ns, os))
                          for ns, os in zip(new_states, states)]
            new_aux = [keep(n, o) for n, o in zip(new_aux, aux_arrays)]
            t1 = jnp.where(finite, t1, t)
            return new_train, new_aux, tuple(new_states), t1, loss, finite

        train_sh = [self._param_shardings[i] for i in train_idx]
        aux_sh = [self._param_shardings[i] for i in aux_idx]
        state_sh = tuple(tuple(train_sh[k] for _ in s)
                         for k, s in enumerate(self._states))
        dat_sh = NamedSharding(self.mesh, self._data_pspec)
        in_sh = (train_sh, aux_sh, state_sh, self._repl, self._repl,
                 self._repl)
        out_sh = (train_sh, aux_sh, state_sh, self._repl, self._repl)
        if self._skip_nonfinite:
            out_sh = out_sh + (self._repl,)
        donate = (0, 1, 2)
        if self._donate_batch:
            # batch leaves sit after (train, aux, states, t, key, lr)
            donate += tuple(range(6, 6 + n_data + self._n_label))
        return jax.jit(
            fn,
            in_shardings=in_sh + tuple([dat_sh] * (n_data + self._n_label)),
            out_shardings=out_sh,
            donate_argnums=donate)

    # ---------------------------------------------------------------- call --
    def __call__(self, data, label):
        return self.step(data, label)

    def step(self, data, label):
        try:
            with _pscope("TrainStep.step", cat="step") as span:
                return self._step(data, label, span)
        except NonFiniteAbortError:
            # the numeric-abort flight trigger (ISSUE 15).  The scope has
            # closed with the error, so the dying step's spans are in the
            # ring BEFORE the post-mortem bundle lands; then the raise
            # unwinds
            _telemetry.flight_trip(
                "nonfinite-abort", step=int(self._num_update),
                consecutive_skips=self.consecutive_skips)
            try:
                # queued async snapshots commit before the abort unwinds
                # (ISSUE 17): the last GOOD state must be on disk when
                # the supervisor inspects the wreck
                from .checkpoint import flush_pending
                flush_pending(timeout=60.0)
            except Exception:  # noqa: BLE001 — the abort verdict must
                pass           # not be masked by a flush
            raise

    def _prepare(self, data, label):
        """Everything a step needs short of touching the device: coerce
        the batch args, run the deferred-init build on first use, and
        (re)compile the jit program when the signature changed.  Shared
        by ``_step`` (which then places the batch and executes) and the
        AOT costing path (``lower``/``cost_analysis``, which never
        executes).  Returns the flattened (data_leaves, label_leaves)."""
        data, label = _coerce_arrays(data), _coerce_arrays(label)
        data_args = data if isinstance(data, (tuple, list)) else (data,)
        data_args = tuple(data_args)
        if not self._built:
            self._build(data_args)
        data_leaves, data_tree = _leaves(data_args)
        label_args = label if isinstance(label, (tuple, list)) else (label,)
        label_leaves, label_tree = _leaves(tuple(label_args))
        sig = (data_tree, label_tree,
               tuple((l.shape, str(l.dtype)) for l in data_leaves),
               tuple((l.shape, str(l.dtype)) for l in label_leaves))
        if self._jit is None or sig != getattr(self, "_sig", None):
            self._n_label = len(label_leaves)
            self._jit = self._compile(data_tree, label_tree, len(data_leaves))
            self._sig = sig
            self._last_avals = None  # refresh lazily on the next step
            self._cost_cache = None
            self._compiled_cache = None
            self._fresh_jit = True
        return data_leaves, label_leaves

    def _invoke(self, args):
        """The one jit dispatch of a step (the donated first call
        suppresses XLA's expected "donated buffers were not usable"
        notice — for that compile only, not process-wide)."""
        if self._donate_batch and getattr(self, "_fresh_jit", False):
            import warnings
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                out = self._jit(*args)
            self._fresh_jit = False
            return out
        return self._jit(*args)

    def _run_guarded(self, args):
        """``_invoke`` through the compile-event chokepoint."""
        with _telemetry.compile_guard("TrainStep", self._jit, key="step"):
            return self._invoke(args)

    def _step(self, data, label, span):
        _fire("step")
        t_wall = time.perf_counter()
        data_leaves, label_leaves = self._prepare(data, label)
        # does this signature still owe its compile?  Stamped on the
        # heartbeat BEFORE the compiling call so the supervisor's
        # watchdog can tell a long first compile from a hung step
        # (ISSUE 15 — startup grace stops being a blind timer)
        fresh = self._jit._cache_size() == 0
        if self._heartbeat is not None and fresh:
            self._heartbeat.beat(self._num_update, phase="train",
                                 compile_in_progress=True)
        key = _random.next_key()
        lr = jnp.float32(self._base_lr())
        dat_sh = NamedSharding(self.mesh, self._data_pspec)
        # ends when the transfers are dispatched, not when they arrive;
        # empty for a batch a DevicePrefetcher placed already
        with _pscope("TrainStep.h2d", cat="step"):
            data_leaves = [_put_batch(l, dat_sh) for l in data_leaves]
            label_leaves = [_put_batch(l, dat_sh) for l in label_leaves]
        args = (self._train_arrays, self._aux_arrays, self._states,
                self._t, key, lr, *data_leaves, *label_leaves)
        if getattr(self, "_last_avals", None) is None:
            # once per signature: the aval snapshot cost_analysis() lowers
            # with (shapes are fixed until sig changes)
            self._last_avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        if fresh:
            out = _compile_first("TrainStep",
                                 lambda: self._run_guarded(args))
        else:
            # host time to hand the program to the device: the device
            # runs it after this span has closed
            with _pscope("TrainStep.dispatch", cat="step"):
                out = self._run_guarded(args)
        if self._skip_nonfinite:
            (self._train_arrays, self._aux_arrays, self._states, self._t,
             loss, finite) = out
            # the verdict is the one host round-trip the guard costs; the
            # arrays themselves stay async on the mesh
            if bool(finite):
                self._num_update += 1
                self.consecutive_skips = 0
            else:
                self.skipped_steps += 1
                self.consecutive_skips += 1
                self._skip_counter.increment()
                budget = self._nonfinite_budget
                if budget is not None and self.consecutive_skips >= budget:
                    try:
                        lv = float(np.asarray(loss))
                    except Exception:
                        lv = float("nan")
                    # step() trips the flight recorder once this raise has
                    # closed the step's scope
                    raise NonFiniteAbortError(
                        f"TrainStep: {self.consecutive_skips} consecutive "
                        f"non-finite updates (budget {budget}) at "
                        f"num_update={self._num_update}; last loss={lv}. "
                        f"Params and optimizer state are unchanged since the "
                        f"last finite step — check the input pipeline for "
                        f"corrupt batches or lower the learning rate "
                        f"(skipped {self.skipped_steps} steps total this "
                        f"run)")
        else:
            (self._train_arrays, self._aux_arrays, self._states, self._t,
             loss) = out
            self._num_update += 1
        self.optimizer.num_update = self._num_update
        span.set(num_update=int(self._num_update))
        if self._heartbeat is not None:
            self._heartbeat.beat(
                self._num_update,
                last_step_ms=(time.perf_counter() - t_wall) * 1e3)
        return NDArray(loss)

    # ------------------------------------------------------------- costing --
    def _synth_avals(self, data_leaves, label_leaves):
        """Abstract argument shapes for AOT lowering, built WITHOUT
        running a step: params/states exist after ``_build``; batch
        leaves are canonicalized the way ``device_put`` would (x64 off:
        int64→int32, float64→float32); the PRNG-key aval comes from a
        constant key so the costing path never consumes RNG state (a
        budget audit must not perturb a seeded training run)."""
        key_aval = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        lr_aval = jax.ShapeDtypeStruct((), jnp.float32)

        def leaf_aval(l):
            return jax.ShapeDtypeStruct(
                l.shape, jax.dtypes.canonicalize_dtype(l.dtype))

        args = (self._train_arrays, self._aux_arrays, self._states,
                self._t, key_aval, lr_aval,
                *[leaf_aval(l) for l in data_leaves],
                *[leaf_aval(l) for l in label_leaves])
        return jax.tree.map(
            lambda a: a if isinstance(a, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(a.shape, a.dtype), args)

    def lower(self, data=None, label=None):
        """AOT-lower the compiled step program WITHOUT executing a step.

        After a step has run, no arguments are needed (the live
        signature is reused).  Before any step, pass one sample
        ``(data, label)`` batch — host numpy zeros are enough; only
        shapes/dtypes matter — and the program is built and lowered from
        abstract values: nothing is placed on the device and no update
        runs (tools/costguard's budget audits drive this path in tier-1
        under ``JAX_PLATFORMS=cpu``)."""
        if data is not None:
            dl, ll = self._prepare(data, label)
            if getattr(self, "_last_avals", None) is None:
                self._last_avals = self._synth_avals(dl, ll)
        if self._jit is None or getattr(self, "_last_avals", None) is None:
            raise RuntimeError(
                "lower() needs one completed step, or a sample (data, "
                "label) batch to lower against")
        return self._jit.lower(*self._last_avals)

    def compiled(self, data=None, label=None):
        """The AOT-compiled step executable (cached per jit signature:
        the lower+compile is a second full XLA compile).  Accepts the same optional
        sample batch as ``lower`` — a sample with a NEW signature
        recompiles rather than serving the previous program's cache."""
        if data is not None:
            # _prepare resets _compiled_cache/_cost_cache on a signature
            # change, so the cache check below is always against the
            # sample's own program, never a stale one
            dl, ll = self._prepare(data, label)
            if getattr(self, "_last_avals", None) is None:
                self._last_avals = self._synth_avals(dl, ll)
        if getattr(self, "_compiled_cache", None) is None:
            self._compiled_cache = self.lower().compile()
        return self._compiled_cache

    def _require_program(self, what, data):
        if data is None and (self._jit is None
                             or getattr(self, "_last_avals", None) is None):
            raise RuntimeError(
                f"{what} needs one completed step or a sample "
                f"(data, label) batch")

    def cost_analysis(self, data=None, label=None):
        """XLA's cost model of the compiled step program: {'flops': ...,
        'bytes accessed': ...} — a static count of the program, not a
        device measurement.  Works after one completed step, or — the lower-only path — from a
        sample ``(data, label)`` batch without ever executing."""
        self._require_program("cost_analysis()", data)
        compiled = self.compiled(data, label)
        if getattr(self, "_cost_cache", None) is None:
            costs = compiled.cost_analysis()
            self._cost_cache = costs[0] if isinstance(costs, list) else costs
        return self._cost_cache

    def memory_analysis(self, data=None, label=None):
        """XLA's compiled-buffer accounting (argument/output/temp/alias
        bytes) of the step program — ``cost_analysis``'s memory-side
        sibling, same lower-only contract."""
        self._require_program("memory_analysis()", data)
        return self.compiled(data, label).memory_analysis()

    # ---------------------------------------------------------------- sync --
    def sync_params_to_net(self):
        """Write the step-owned arrays back into the Gluon Parameters.

        Arrays are gathered to the default device: eager Gluon execution is
        single-logical-device (placement-by-sharding belongs to the step), and
        mesh-committed params would collide with device-0 inputs in eager ops."""
        dev = jax.local_devices()[0]
        if not hasattr(self, "_gather"):
            # one jitted identity reused across params and calls (a fresh
            # lambda per param would retrace/recompile every sync)
            self._gather = jax.jit(lambda x: x, out_shardings=self._repl)

        def host(a):
            # Multi-process: a may be sharded over non-addressable devices;
            # all-gather to fully-replicated first (XLA collective), then the
            # local copy is readable on every rank.
            if jax.process_count() > 1:
                if not a.is_fully_replicated:
                    a = self._gather(a)
                return np.asarray(a)
            return a

        for i, a in zip(self._train_idx, self._train_arrays):
            self._plist[i].data()._data = jax.device_put(host(a), dev)
        for i, a in zip(self._aux_idx, self._aux_arrays):
            self._plist[i].data()._data = jax.device_put(host(a), dev)

    @property
    def params(self):
        full = [None] * len(self._plist)
        for i, a in zip(self._train_idx, self._train_arrays):
            full[i] = a
        for i, a in zip(self._aux_idx, self._aux_arrays):
            full[i] = a
        return dict(zip(self._names, full))


class EvalStep:
    """Compiled sharded inference: (params, batch) → outputs."""

    def __init__(self, net, mesh=None, rules=None, data_spec=None):
        self.net = net
        self.mesh = mesh if mesh is not None else default_mesh()
        self.rules = rules or ShardingRules()
        self._data_pspec = data_spec if data_spec is not None \
            else batch_spec(self.mesh)
        self._jit = None
        self._built = False

    @property
    def data_sharding(self):
        """See TrainStep.data_sharding."""
        return NamedSharding(self.mesh, self._data_pspec)

    def _build(self, sample_args):
        if _has_pending(self.net):
            _materialize_net(self.net, sample_args, self.mesh, "EvalStep")
        names, plist, arrays = param_names_and_values(self.net)
        self._names, self._plist = names, plist
        sh = param_sharding(names, [a.shape for a in arrays], self.mesh,
                            self.rules)
        with _pscope("EvalStep.state_init", cat="setup"):
            self._arrays = jax.device_put(arrays, sh)
        self._shardings = sh
        self._built = True

    def __call__(self, *data):
        data = tuple(_coerce_arrays(d) for d in data)
        if not self._built:
            self._build(data)
        data_leaves, data_tree = _leaves(tuple(data))
        sig = (data_tree, tuple((l.shape, str(l.dtype)) for l in data_leaves))
        if self._jit is None or sig != getattr(self, "_sig", None):
            net, plist = self.net, self._plist
            holder = FunctionalState()

            def fn(arrays, key, *leaves):
                with MeshScope(self.mesh):
                    outs = functional_call(net, plist, list(arrays), data_tree,
                                           list(leaves), key, False, holder)
                return tuple(outs)

            dat_sh = NamedSharding(self.mesh, self._data_pspec)
            self._jit = jax.jit(
                fn,
                in_shardings=(self._shardings,
                              NamedSharding(self.mesh, PartitionSpec()))
                + tuple([dat_sh] * len(data_leaves)))
            self._holder = holder
            self._sig = sig
        key = _random.next_key()
        dat_sh = NamedSharding(self.mesh, self._data_pspec)
        data_leaves = [_put_batch(l, dat_sh) for l in data_leaves]

        def run():
            with _telemetry.compile_guard("EvalStep", self._jit, key="eval"):
                return self._jit(self._arrays, key, *data_leaves)
        outs = _compile_first("EvalStep", run) \
            if self._jit._cache_size() == 0 else run()
        res = _unflatten_nd(self._holder.out_tree,
                            tuple(NDArray(o) for o in outs))
        if isinstance(res, tuple) and len(res) == 1:
            return res[0]
        return res
