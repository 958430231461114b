"""Grouped matrix products over the first ``total`` rows of a sorted buffer.

A dropless expert layer (``parallel/moe.py``) sorts its N x k assignments by
held expert: rows ``offsets[g] .. offsets[g + 1]`` of the buffer belong to
expert ``g``, and rows past ``total = offsets[-1]`` to no held expert.  Three
kernels, all over the held rows alone:

- ``moe_grouped_fwd``: ``x[g] @ w[g]`` (M, N) from ``x`` (M, K), ``w`` (G, K,
  N);
- ``moe_grouped_dx``: ``dy[g] @ w[g].T`` (M, K): the forward's body, reading
  the weight block transposed;
- ``moe_grouped_dw``: ``x[g].T @ dy[g]`` (G, K, N), zeros for an expert with no
  row; no row past ``total`` is read.

Each walks the buffer in row tiles on a grid whose step axis follows a
**group map** (``group_map``): step ``s`` computes row tile ``tiles[s]`` for
expert ``groups[s]``, a tile that two experts share is visited once for each,
in order.  The map is built once a layer, from the loads on the device, and
every kernel of that layer takes it as scalar-prefetch operands.  Steps past
the last visit re-name its blocks (nothing is fetched or written) and skip
their body.  Accumulation is float32; results take the operands' type.  Rows
of ``fwd`` / ``dx`` past ``total`` are NEVER WRITTEN past the last visited
tile, and inside it hold whatever the tile's buffer held: whoever reads them
masks by ``total`` (``parallel/moe.py`` does).

Each kernel is one module-level ``jax.jit`` with static tiles chosen from the
shapes alone, over its jaxpr traced once a process (``once.bind``): a process
traces each distinct kernel once however many layers, passes and contexts
call it, and lowers it once (the forward ones twice: jax's partial evaluation
gives the recomputed forward its own copy of the jit's jaxpr); PR 37's
kernels, lowered at every call site, cost ``mellum2_12b_a2_5b.train_s8192``
3.2 s of ``setup_s`` (PERF.md 6, PR 38).  Inside a block the
contracted dimension is whole and a loop walks the result's columns in
chunks of 128 (``dw`` over its rows' block transposed once into VMEM): the
executable holds each kernel at every call site, and the loop keeps that
code to one chunk's product, a fifth to a seventh of the whole block's.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu
from .once import bind

# rows a tile: the map's unit, the same for all six calls of a layer
_ROW_TILE = 512
# the widest block of a result dimension (``moe_grouped_dw``'s first one:
# _TILE_K), and the columns of one product inside a block: a loop over
# them keeps a kernel's code to one chunk's product (PERF.md 6, PR 38)
_TILE_N = 2048
_TILE_K = 1024
_CHUNK = 128
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)
_PARAMS_DW = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


class GroupMap(NamedTuple):
    """Which row tile and expert each grid step computes (int32):
    ``offsets`` (G + 1,) the experts' first rows and ``total``; ``groups``
    and ``tiles`` (tiles_m + G - 1,), repeating the last visit past it;
    ``steps`` (1,) the visits."""
    offsets: jax.Array
    groups: jax.Array
    tiles: jax.Array
    steps: jax.Array


def row_tile(m):
    """Rows a tile, from the buffer's rows alone."""
    return min(_ROW_TILE, m)


def _tiles(m, n):
    """(rows, result columns, columns a product) of ``moe_grouped_fwd`` /
    ``_dx``'s blocks, from the shapes alone."""
    tn = _block(n, _TILE_N)
    return row_tile(m), tn, _block(tn, _CHUNK)


def _weight_tiles(m, k, n):
    """(rows, K, N, columns a product) of ``moe_grouped_dw``'s blocks."""
    tn = _block(n, _TILE_N)
    return row_tile(m), _block(k, _TILE_K), tn, _block(tn, _CHUNK)


def _block(width, cap):
    """The widest multiple of 128 that divides ``width`` and is at most
    ``cap``; the whole width where it is no multiple of 128."""
    if width % 128:
        return width
    return max(b for b in range(128, min(width, cap) + 1, 128)
               if width % b == 0)


@functools.partial(jax.jit, static_argnames=("m", "tile"))
def group_map(sizes, m, tile):
    """The map of experts with ``sizes`` (G,) int32 rows (a device value)
    over a buffer of ``m`` rows in tiles of ``tile`` rows.  A non-empty
    expert visits every tile its rows touch; an empty one visits the tile it
    would start in once, so that ``moe_grouped_dw`` writes its zeros.  A
    ``jax.jit``: its layers and passes share one trace (~7.5 ms a call on
    this host otherwise, eight calls in a four-layer step; PR 38)."""
    tiles_m = pl.cdiv(m, tile)
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    starts = ends - sizes
    first = jnp.minimum(starts // tile, tiles_m - 1)
    visits = jnp.where(sizes > 0, (ends + tile - 1) // tile - first, 1)
    last = jnp.cumsum(visits)
    steps = last[-1]
    s = jnp.minimum(jnp.arange(tiles_m + sizes.shape[0] - 1,
                               dtype=jnp.int32), steps - 1)
    # a dense comparison: the default search is a while loop, and a loop in
    # a layer moves XLA's schedule of the whole step (PERF.md 6, PR 34)
    groups = jnp.searchsorted(last, s, side="right",
                              method="compare_all").astype(jnp.int32)
    tiles = first[groups] + s - (last - visits)[groups]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return GroupMap(offsets, groups, tiles.astype(jnp.int32),
                    steps.reshape(1))


def tile_visits(sizes, tile):
    """Row tiles the products compute for experts of ``sizes`` (host
    integers) in tiles of ``tile`` rows: each non-empty expert's tiles, a
    shared tile once for each expert in it."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    return int(np.sum(np.where(sizes > 0, -(-ends // tile) - starts // tile,
                               0)))


def _rows(tiles_ref, offsets_ref, g, s, rows):
    """(the mask of the rows of this step's tile that belong to expert g,
    whether all of them do, the tile's rows)."""
    start = tiles_ref[s] * rows
    at = start + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    first, end = offsets_ref[g], offsets_ref[g + 1]
    whole = (start >= first) & (start + rows <= end)
    return (at >= first) & (at < end), whole, at


def _columns(width, chunk, body):
    """``body(start)`` for each ``chunk`` of ``width`` columns: a loop, so
    that the kernel's code holds one chunk's product, not the block's."""
    if width == chunk:
        body(0)
        return

    def step(j, carry):
        body(pl.multiple_of(j * chunk, chunk))
        return carry
    jax.lax.fori_loop(0, width // chunk, step, 0)


def _product_kernel(offsets_ref, groups_ref, tiles_ref, steps_ref, x_ref,
                    w_ref, out_ref, *, transpose, chunk):
    s = pl.program_id(1)
    g = groups_ref[s]

    @pl.when((s < steps_ref[0]) & (offsets_ref[g + 1] > offsets_ref[g]))
    def _():
        mine, whole, _ = _rows(tiles_ref, offsets_ref, g, s, out_ref.shape[0])

        def columns(c):
            if transpose:
                w, dims = w_ref[pl.ds(c, chunk), :], ((1,), (1,))
            else:
                w, dims = w_ref[:, pl.ds(c, chunk)], ((1,), (0,))
            got = jax.lax.dot_general(x_ref[...], w, (dims, ((), ())),
                                      preferred_element_type=jnp.float32)

            @pl.when(whole)
            def _():
                out_ref[:, pl.ds(c, chunk)] = got.astype(out_ref.dtype)

            @pl.when(jnp.logical_not(whole))
            def _():
                # a tile that another expert or the rows past ``total``
                # share: this expert's rows alone
                out_ref[:, pl.ds(c, chunk)] = jnp.where(
                    mine, got, out_ref[:, pl.ds(c, chunk)].astype(
                        jnp.float32)).astype(out_ref.dtype)
        _columns(out_ref.shape[1], chunk, columns)


def _weight_kernel(offsets_ref, groups_ref, tiles_ref, steps_ref, x_ref,
                   dy_ref, out_ref, xt_ref, acc_ref, *, chunk):
    s, last = pl.program_id(2), pl.num_programs(2) - 1
    steps = steps_ref[0]
    g = groups_ref[s]
    live = s < steps

    @pl.when(live & ((s == 0) | (groups_ref[jnp.maximum(s - 1, 0)] != g)))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live & (offsets_ref[g + 1] > offsets_ref[g]))
    def _():
        mine, whole, at = _rows(tiles_ref, offsets_ref, g, s, x_ref.shape[0])
        total = offsets_ref[offsets_ref.shape[0] - 1]

        # dy's rows past ``total`` may hold anything (NaN or Inf, which a
        # zero of x would not cancel): zero them in the block itself (a step
        # that names the block again keeps them zero, and no expert reads
        # them); below ``total`` every row is finite and the mask of x alone
        # keeps the other experts' rows out
        @pl.when(tiles_ref[s] * x_ref.shape[0] + x_ref.shape[0] > total)
        def _():
            dy_ref[...] = jnp.where(at < total, dy_ref[...].astype(
                jnp.float32), 0.0).astype(dy_ref.dtype)

        xt_ref[...] = jnp.where(mine, x_ref[...], 0).T

        def columns(c):
            acc_ref[:, pl.ds(c, chunk)] += jnp.dot(
                xt_ref[...], dy_ref[:, pl.ds(c, chunk)],
                preferred_element_type=jnp.float32)
        _columns(acc_ref.shape[1], chunk, columns)

    @pl.when(live & ((s == steps - 1)
                     | (groups_ref[jnp.minimum(s + 1, last)] != g)))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("transpose", "tiles",
                                             "interpret"))
def _product(gmap, x, w, *, transpose, tiles, interpret):
    """``moe_grouped_fwd`` (``x[g] @ w[g]``) or, ``transpose``,
    ``moe_grouped_dx`` (``x[g] @ w[g].T``): ``tiles`` = (rows, result
    columns) of a block and the columns of one product in it; the
    contracted dimension is whole."""
    return bind(_build_product, (*gmap, x, w), transpose=transpose,
                 tiles=tiles, interpret=interpret)[0]


def _build_product(*operands, transpose, tiles, interpret):
    gmap, (x, w) = GroupMap(*operands[:4]), operands[4:]
    m, k = x.shape
    n = w.shape[1] if transpose else w.shape[2]
    tm, tn, chunk = tiles

    def x_at(j, s, offsets, groups, tiles, steps):
        return tiles[s], 0

    def w_at(j, s, offsets, groups, tiles, steps):
        return (groups[s], j, 0) if transpose else (groups[s], 0, j)

    def out_at(j, s, offsets, groups, tiles, steps):
        return tiles[s], j
    w_block = (None, tn, k) if transpose else (None, k, tn)
    return pl.pallas_call(
        functools.partial(_product_kernel, transpose=transpose, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, gmap.groups.shape[0]),
            in_specs=[pl.BlockSpec((tm, k), x_at),
                      pl.BlockSpec(w_block, w_at)],
            out_specs=pl.BlockSpec((tm, tn), out_at)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=_PARAMS, interpret=interpret,
        name="moe_grouped_dx" if transpose else "moe_grouped_fwd",
    )(*gmap, x, w)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _weights(gmap, x, dy, *, tiles, interpret):
    """``moe_grouped_dw``: ``x[g].T @ dy[g]`` (G, K, N): ``tiles`` = (rows,
    K, N) of a block and the columns of ``x`` (rows of the result) in one
    product."""
    return bind(_build_weights, (*gmap, x, dy), tiles=tiles,
                 interpret=interpret)[0]


def _build_weights(*operands, tiles, interpret):
    gmap, (x, dy) = GroupMap(*operands[:4]), operands[4:]
    m, k = x.shape
    n = dy.shape[1]
    groups = gmap.offsets.shape[0] - 1
    tm, tk, tn, chunk = tiles

    def x_at(j, kk, s, offsets, groups, tiles, steps):
        return tiles[s], kk

    def dy_at(j, kk, s, offsets, groups, tiles, steps):
        return tiles[s], j

    def out_at(j, kk, s, offsets, groups, tiles, steps):
        return groups[s], kk, j
    return pl.pallas_call(
        functools.partial(_weight_kernel, chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, k // tk, gmap.groups.shape[0]),
            in_specs=[pl.BlockSpec((tm, tk), x_at),
                      pl.BlockSpec((tm, tn), dy_at)],
            out_specs=pl.BlockSpec((None, tk, tn), out_at),
            scratch_shapes=[pltpu.VMEM((tk, tm), x.dtype),
                            pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), x.dtype),
        compiler_params=_PARAMS_DW, interpret=interpret,
        name="moe_grouped_dw",
    )(*gmap, x, dy)


@jax.custom_vjp
def grouped_matmul(x, w, gmap):
    """``x[g] @ w[g]`` for the rows of each expert ``g`` of ``gmap`` (a
    ``GroupMap`` of ``x``'s rows): ``x`` (M, K), ``w`` (G, K, N) -> (M, N).
    Rows past the experts' sum hold anything, and the backward pass gives
    ``x``'s cotangent under the same rule; what ``dy``'s rows past the sum
    hold reaches neither a held row's cotangent nor ``w``'s."""
    return _product(gmap, x, w, transpose=False,
                    tiles=_tiles(x.shape[0], w.shape[2]),
                    interpret=not on_tpu())


def _grouped_matmul_fwd(x, w, gmap):
    return grouped_matmul(x, w, gmap), (x, w, gmap)


def _grouped_matmul_bwd(kept, dy):
    x, w, gmap = kept
    (m, k), n = x.shape, w.shape[2]
    interpret = not on_tpu()
    dx = _product(gmap, dy, w, transpose=True, tiles=_tiles(m, k),
                  interpret=interpret)
    dw = _weights(gmap, x, dy, tiles=_weight_tiles(m, k, n),
                  interpret=interpret)
    return dx, dw, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
