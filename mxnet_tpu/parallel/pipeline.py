"""Pipeline parallelism (GPipe-style microbatch schedule over a ``pp`` axis).

The reference has NO pipeline parallelism (SURVEY.md §2.3: only symbolic
``group2ctx`` device groups with executor-inserted copies).  TPU-native
design: stage parameters are STACKED along a leading dim sharded over the
``pp`` mesh axis (stage i's slice lives on pp-rank i), and the schedule is a
``lax.scan`` over ticks inside shard_map — each tick every device applies its
stage to its current microbatch and ``ppermute``s the activation to the next
rank.  Warmup/cooldown bubbles are masked compute, the canonical GPipe cost
of (P-1)/(M+P-1).

Constraints (v1): every stage must map activations of one fixed shape to the
same shape (the transformer-block case); the incoming batch splits into
``microbatches`` equal microbatches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from .mesh import current_mesh, shard_map

__all__ = ["gpipe", "PipelineStack"]


def gpipe(stage_apply, stacked_params, x, mesh=None, axis="pp",
          batch_axis="dp", microbatches=None):
    """Run ``x`` through P pipelined stages.

    stage_apply(params_slice, act) -> act', shape-preserving.
    stacked_params: pytree whose leaves have leading dim P (sharded on axis).
    x: (B, ...) global batch; split into M microbatches along dim 0.
    Returns (B, ...).
    """
    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        raise ValueError("gpipe needs a mesh: pass mesh= or enter a MeshScope")
    P = mesh.shape[axis]
    for path, leaf in jax.tree_util.tree_flatten_with_path(stacked_params)[0]:
        if leaf.ndim < 1 or leaf.shape[0] != P:
            raise ValueError(
                f"gpipe: stacked param {jax.tree_util.keystr(path)} has "
                f"leading dim {leaf.shape[:1]} but mesh axis {axis!r} has "
                f"size {P}; every stacked leaf must have leading dim == "
                f"number of pipeline stages == mesh.shape[{axis!r}]")
    M = microbatches if microbatches is not None else P
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible into {M} microbatches")
    bm = B // M
    xm = x.reshape((M, bm) + x.shape[1:])

    p_spec = jax.tree_util.tree_map(lambda _: PartitionSpec(axis), stacked_params)
    bdim = batch_axis if batch_axis in mesh.shape else None
    x_spec = PartitionSpec(None, bdim)
    out_spec = PartitionSpec(None, bdim)  # stays (M, bm, ...); flatten outside

    import inspect
    takes_rng = len(inspect.signature(stage_apply).parameters) >= 3
    base_key = None
    if takes_rng:
        from .. import random as _random
        base_key = _random.next_key()

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(p_spec, x_spec, PartitionSpec()), out_specs=out_spec,
        check_vma=False)
    def _run(params_loc, xm_loc, key):
        # params_loc leaves: (1, ...) -> (...)
        params_me = jax.tree_util.tree_map(
            lambda a: jnp.squeeze(a, axis=0), params_loc)
        rank = jax.lax.axis_index(axis)
        T = M + P - 1
        act0 = jnp.zeros(xm_loc.shape[1:], xm_loc.dtype)
        out0 = jnp.zeros(xm_loc.shape, xm_loc.dtype)
        send = [(p, p + 1) for p in range(P - 1)]
        # distinct RNG stream per stage/dp-shard/tick (stacked dropout masks
        # must be independent across stages and microbatches)
        key_me = jax.random.fold_in(key, rank) if takes_rng else None
        if takes_rng and batch_axis in mesh.shape:
            key_me = jax.random.fold_in(
                key_me, jax.lax.axis_index(batch_axis))

        def tick(carry, t):
            recv, out = carry
            x_t = jax.lax.dynamic_index_in_dim(
                xm_loc, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            my_in = jnp.where(rank == 0, x_t, recv)
            if takes_rng:
                y = stage_apply(params_me, my_in,
                                jax.random.fold_in(key_me, t))
            else:
                y = stage_apply(params_me, my_in)
            y_next = jax.lax.ppermute(y, axis, send) if P > 1 else y
            widx = t - (P - 1)
            write = (widx >= 0) & (rank == P - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                out, y, jnp.clip(widx, 0, M - 1), 0)
            out = jnp.where(write, upd, out)
            return (y_next, out), None

        (_, out), _ = jax.lax.scan(tick, (act0, out0), jnp.arange(T))
        # only the last rank holds real outputs (others are zero) -> replicate
        mine = jnp.where(rank == P - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(mine, axis)   # (M, bm_local, ...)

    x_sh = NamedSharding(mesh, x_spec)
    p_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), p_spec)
    eager = not any(isinstance(l, jax.core.Tracer)
                    for l in jax.tree_util.tree_leaves((stacked_params, x)))
    if eager:
        stacked_params = jax.tree_util.tree_map(
            jax.device_put, stacked_params, p_sh)
        xm = jax.device_put(xm, x_sh)
    if base_key is None:
        base_key = jax.random.key(0)  # unused by 2-arg stage fns
    out = _run(stacked_params, xm, base_key)
    out = out.reshape((B,) + out.shape[2:])
    if eager:
        out = jax.device_put(out, jax.local_devices()[0])
    return out


def _make_pipeline_stack():
    """Deferred import cycle breaker: gluon imports parallel pieces lazily."""
    from ..gluon.block import Block
    from ..ndarray import NDArray
    from .functional import FunctionalState, functional_call
    from .sharding import ShardingRules
    from .. import initializer as init_mod
    from .. import random as _random
    from .. import autograd as _autograd
    import re

    class PipelineStack(Block):
        """Gluon pipeline of N identical-structure stages (GPipe over ``pp``).

        Built from a factory producing one HybridBlock stage; owns STACKED
        parameters (leading dim = num_stages) so each stage's slice lands on
        its pp rank — pass ``stack.sharding_rules()`` to TrainStep.
        """

        def __init__(self, stage_factory, num_stages, microbatches=None,
                     axis="pp", prefix=None, params=None):
            super().__init__(prefix=prefix, params=params)
            self.num_stages = num_stages
            self.microbatches = microbatches
            self.axis = axis
            with self.name_scope():
                self.template = stage_factory()
            self.template.initialize()
            stacked_names = []
            for name, p in sorted(self.template.collect_params().items()):
                if not p._shape_known():
                    raise ValueError(
                        f"pipeline stages need fully-specified shapes; "
                        f"parameter '{name}' has deferred init "
                        f"(pass in_units/in_channels)")
                draws = [p.data()._data]
                initializer = init_mod.create(
                    p.init if p.init is not None else "uniform")
                for _ in range(num_stages - 1):
                    draws.append(jnp.asarray(
                        initializer(p.name, p.shape, p.dtype)))
                arr = jnp.stack(draws)
                p._data = NDArray(arr)
                p.shape = tuple(arr.shape)
                if p._grad_req != "null":
                    p._data.attach_grad(p._grad_req)
                stacked_names.append(name)
            self._stacked_names = stacked_names

        def sharding_rules(self):
            """Leading stage dim of every stacked param -> the pp axis."""
            return ShardingRules(
                rules=[(re.escape(n), (self.axis,))
                       for n in self._stacked_names])

        def forward(self, x):
            names = self._stacked_names
            plist = [self.template.collect_params()[n] for n in names]
            stacked = [p.data()._data for p in plist]
            template = self.template
            state = FunctionalState()

            def stage_apply(params_slice, act, rng_key):
                arrays = [params_slice[n] for n in names]
                outs = functional_call(
                    template, plist, arrays, ("*",), [act],
                    rng_key, _autograd.is_training(), state)
                return outs[0]

            params_tree = dict(zip(names, stacked))
            xv = x._data if isinstance(x, NDArray) else x
            out = gpipe(stage_apply, params_tree, xv, axis=self.axis,
                        microbatches=self.microbatches)
            return NDArray(out) if isinstance(x, NDArray) else out

    return PipelineStack


PipelineStack = _make_pipeline_stack()