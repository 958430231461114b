"""Selective-scan Pallas kernels: Mamba's recurrence with the state in VMEM.

``h[t] = exp(dt[t] A) h[t-1] + (dt[t] x[t]) B[t]^T``, ``y[t] = h[t] C[t]``,
position by position in float32 (Gu & Dao, arXiv:2312.00752, eq. 2).  In XLA
every position is a handful of small fusions whose ``[N, D]`` state goes
through HBM between loop iterations; here a grid step holds ``SCAN_CHUNK``
positions of a tile of channels, the state stays in VMEM for the whole
sequence, and HBM sees ``dt``, ``x`` and ``y`` once.

Layout: the state of a tile is ``[N, bd]``, the states on the sublanes and
``bd`` channels on the lanes.  Grid ``(B, T / L, D / bd)``, the tiles of D
INNERMOST: ``b[t]`` and ``c[t]`` are columns over the sublanes, the same for
every channel, and spreading a column over 128 lanes (a ``[128, 128]``
transpose of a replicated row) is work for the transpose unit that one chunk
does once and all its tiles read.  The backward's ``db`` and ``dc`` are sums
over the channels: each tile adds its 128-lane partial into a scratch and the
chunk's last tile folds the lanes (the transpose again) into a compact row.
So ``b``, ``c`` and their gradients cross HBM as ``[T, N]``, never broadcast.
The states of all tiles are one scratch ``[D / bd, N, bd]`` (320 KB at D
5120, N 16); both loop axes are sequential.

The forward also writes the state at the start of every chunk, ``[T / L, N,
D]``, the only residual besides the inputs; the backward walks the chunks
from the last, rebuilds one chunk's states from its boundary into VMEM, then
walks its positions backwards carrying ``dL/dh``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu

_F32 = jnp.float32
_LANES = 128
# channels a grid step holds: the largest of these that divides the padded D
_TILES = (512, 256, 128)
# positions in the body of a sequential loop, so that one position's exp and
# loads overlap the next's multiply-add chain (a chunk is a multiple of 16)
_UNROLL = 4
# rows of a compact block (128 / N positions each) spread or gathered in one
# loop iteration
_ROWS = 8
# both loop axes are sequential: the state is carried over the chunks and the
# spread columns over the tiles.  The backward's scratch is 9.5 MB at a chunk
# of 128 and a tile of 512, blocks double-buffered beside it
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=48 * 1024 * 1024)


def _round_up(v, m):
    return -(-v // m) * m


def _rounder(state_dtype):
    """Identity for the float32 state; for a narrower one (the precision
    control of ``tests_tpu/test_sambay_tpu.py``) every state-typed value is
    rounded through it, as XLA rounds each op of a narrow-typed scan."""
    if jnp.dtype(state_dtype) == jnp.dtype(_F32):
        return lambda v: v
    return lambda v: v.astype(state_dtype).astype(_F32)


# ------------------------------------------------- pieces of both kernels --
def _each_row(rows, body):
    """``body(i)`` for i = 0 .. rows - 1, up to ``_ROWS`` of them an
    iteration: their transposes are in flight together."""
    group = math.gcd(rows, _ROWS)

    def some(i, _):
        for k in range(group):
            body(i * group + k)
    jax.lax.fori_loop(0, rows // group, some, None)


def _spread(src_ref, dst_ref, rows):
    """``src_ref`` (1, 1, rows, 128): a chunk's ``b`` (or ``c``) row-major,
    128 / N positions a row.  ``dst_ref`` (L * N, 128): row ``t * N + n``
    holds ``b[t, n]`` in every lane."""
    def one(i):
        row = src_ref[0, 0, pl.ds(i, 1), :]
        dst_ref[pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES), :] = \
            jnp.broadcast_to(row, (_LANES, _LANES)).T
    _each_row(rows, one)


def _gather(src_ref, dst_ref, rows):
    """The inverse, summing: ``dst_ref[0, 0, i, t * N + n]`` = the sum over
    the lanes of ``src_ref``'s row ``(i * 128 / N + t) * N + n``."""
    def one(i):
        part = src_ref[pl.ds(pl.multiple_of(i * _LANES, _LANES), _LANES), :]
        dst_ref[0, 0, pl.ds(i, 1), :] = jnp.sum(part.T, axis=0, keepdims=True)
    _each_row(rows, one)


def _over_lanes(tile, bd):
    """(N, 128) -> (N, bd): the same 128 lanes under every lane tile."""
    return tile if bd == _LANES else jnp.concatenate(
        [tile] * (bd // _LANES), axis=1)


def _fold_lanes(v):
    """(N, bd) -> (N, 128): the lane tiles added."""
    out = v[:, :_LANES]
    for i in range(1, v.shape[1] // _LANES):
        out = out + v[:, i * _LANES:(i + 1) * _LANES]
    return out


def _each_position(chunk, body, carry):
    """``carry = body(t, carry)`` for t = 0 .. chunk - 1, ``_UNROLL``
    positions an iteration (Mosaic unrolls a ``fori_loop`` fully or not at
    all)."""
    def some(i, carry):
        for k in range(_UNROLL):
            carry = body(i * _UNROLL + k, carry)
        return carry
    return jax.lax.fori_loop(0, chunk // _UNROLL, some, carry)


def _row(ref, t, n):
    """Row ``t`` of a (L, bd) block under all ``n`` sublanes."""
    row = ref[pl.ds(t, 1), :]
    return jnp.broadcast_to(row, (n, row.shape[1]))


def _tile(ref, t, n, bd):
    """Position ``t``'s (n, bd) of a spread ``b`` or ``c``."""
    return _over_lanes(ref[pl.ds(pl.multiple_of(t * n, n), n), :], bd)


# ------------------------------------------------------------- forward ------
def _fwd_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, y_ref, hb_ref,
                h_s, bb_s, cc_s, u_s, *, chunk, n, bd, rows, rnd):
    j, d = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _start():
        h_s[d] = jnp.zeros((n, bd), _F32)

    @pl.when(d == 0)
    def _columns():
        _spread(b_ref, bb_s, rows)
        _spread(c_ref, cc_s, rows)

    hb_ref[0, 0] = h_s[d]
    u_s[...] = rnd(dt_ref[0] * x_ref[0].astype(_F32))
    a = a_ref[...]

    def step(t, h):
        decay = rnd(jnp.exp(rnd(_row(dt_ref.at[0], t, n) * a)))
        h = rnd(rnd(decay * h) + rnd(_tile(bb_s, t, n, bd) * _row(u_s, t, n)))
        y_ref[0, pl.ds(t, 1), :] = rnd(jnp.sum(
            rnd(h * _tile(cc_s, t, n, bd)), axis=0, keepdims=True))
        return h

    h_s[d] = _each_position(chunk, step, h_s[d])


def _geometry(dt, a, chunk):
    """(batch, chunks, tiles, N, channels a tile, rows of a compact block)
    of padded ``dt`` (B, T, D) and ``a`` (N, D)."""
    bsz, t, dim = dt.shape
    n = a.shape[0]
    bd = next(w for w in _TILES if dim % w == 0)
    return bsz, t // chunk, dim // bd, n, bd, chunk * n // _LANES


def _compact_spec(rows, index_map):
    """BlockSpec of a chunk's compact ``b``, ``c``, ``db`` or ``dc``: the
    block's last two dims are the array's, whatever the chunk and N."""
    return pl.BlockSpec((1, 1, rows, _LANES), index_map)


# jitted, so that a program traces and lowers each kernel once however many
# layers (and recomputations) call it: six calls a step in the benchmark's cell
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _scan_fwd(dt, x, a, b2, c2, chunk, state_dtype, interpret):
    bsz, n_t, n_d, n, bd, rows = _geometry(dt, a, chunk)
    kernel = functools.partial(_fwd_kernel, chunk=chunk, n=n, bd=bd,
                               rows=rows, rnd=_rounder(state_dtype))
    block = pl.BlockSpec((1, chunk, bd), lambda i, j, d: (i, j, d))
    compact = _compact_spec(rows, lambda i, j, d: (i, j, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(bsz, n_t, n_d),
        in_specs=[block, block,
                  pl.BlockSpec((n, bd), lambda i, j, d: (0, d)),
                  compact, compact],
        out_specs=[block,
                   pl.BlockSpec((1, 1, n, bd), lambda i, j, d: (i, j, 0, d))],
        out_shape=[jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, n_t, n, dt.shape[2]), _F32)],
        scratch_shapes=[
            pltpu.VMEM((n_d, n, bd), _F32),
            pltpu.VMEM((chunk * n, _LANES), _F32),
            pltpu.VMEM((chunk * n, _LANES), _F32),
            pltpu.VMEM((chunk, bd), _F32),
        ],
        compiler_params=_PARAMS,
        name="selective_scan_fwd",
        interpret=interpret,
    )(dt, x, a, b2, c2)


# ------------------------------------------------------------ backward ------
def _bwd_kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, hb_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                g_s, hs_s, bb_s, cc_s, db_s, dc_s, u_s, dy_s, du_s, dda_s,
                *, chunk, n, bd, rows, rnd):
    j, d = pl.program_id(1), pl.program_id(2)     # j counts from the END

    @pl.when(j == 0)
    def _start():
        g_s[d] = jnp.zeros((n, bd), _F32)
        da_ref[0, d] = jnp.zeros((n, bd), _F32)

    @pl.when(d == 0)
    def _columns():
        _spread(b_ref, bb_s, rows)
        _spread(c_ref, cc_s, rows)
        db_s[...] = jnp.zeros_like(db_s)
        dc_s[...] = jnp.zeros_like(dc_s)

    dt_all = dt_ref[0]
    x_all = x_ref[0].astype(_F32)
    u_s[...] = rnd(dt_all * x_all)
    dy_s[...] = dy_ref[0].astype(_F32)
    a = a_ref[...]

    def state_at(t):        # h[t - 1] of the chunk; slot 0 is the boundary
        return hs_s[pl.ds(pl.multiple_of(t * n, n), n), :]

    def decay_of(dt):
        return rnd(jnp.exp(rnd(dt * a)))

    # the chunk's states, rebuilt from its boundary
    hs_s[pl.ds(0, n), :] = hb_ref[0, 0]

    def rebuild(t, h):
        h = rnd(rnd(decay_of(_row(dt_ref.at[0], t, n)) * h)
                + rnd(_tile(bb_s, t, n, bd) * _row(u_s, t, n)))
        hs_s[pl.ds(pl.multiple_of((t + 1) * n, n), n), :] = h
        return h

    _each_position(chunk, rebuild, hb_ref[0, 0])

    # backwards over the positions; g = dL/dh[t] through the later ones
    def step(i, carry):
        g, da = carry
        t = chunk - 1 - i
        at = pl.ds(pl.multiple_of(t * n, n), n)
        dt, dy = _row(dt_ref.at[0], t, n), _row(dy_s, t, n)
        decay = decay_of(dt)
        g = rnd(g + rnd(_tile(cc_s, t, n, bd) * dy))
        dc_s[at, :] += _fold_lanes(rnd(state_at(t + 1) * dy))
        db_s[at, :] += _fold_lanes(rnd(g * _row(u_s, t, n)))
        du_s[pl.ds(t, 1), :] = rnd(jnp.sum(
            rnd(g * _tile(bb_s, t, n, bd)), axis=0, keepdims=True))
        w = rnd(rnd(g * state_at(t)) * decay)      # dL/d(dt[t] A)
        dda_s[pl.ds(t, 1), :] = rnd(jnp.sum(rnd(w * a), axis=0,
                                            keepdims=True))
        da = rnd(da + rnd(w * dt))
        return rnd(g * decay), da

    g, da = _each_position(chunk, step,
                           (g_s[d], jnp.zeros((n, bd), _F32)))
    g_s[d] = g
    da_ref[0, d] = rnd(da_ref[0, d] + da)
    du = du_s[...]
    dx_ref[0] = (du * dt_all).astype(dx_ref.dtype)
    ddt_ref[0] = rnd(rnd(du * x_all) + dda_s[...])

    @pl.when(d == pl.num_programs(2) - 1)
    def _columns_out():
        _gather(db_s, db_ref, rows)
        _gather(dc_s, dc_ref, rows)


@functools.partial(jax.jit, static_argnums=(7, 8, 9))
def _scan_bwd(dt, x, a, b2, c2, hb, dy, chunk, state_dtype, interpret):
    bsz, n_t, n_d, n, bd, rows = _geometry(dt, a, chunk)
    kernel = functools.partial(_bwd_kernel, chunk=chunk, n=n, bd=bd,
                               rows=rows, rnd=_rounder(state_dtype))
    block = pl.BlockSpec((1, chunk, bd),
                         lambda i, j, d: (i, n_t - 1 - j, d))
    compact = _compact_spec(rows, lambda i, j, d: (i, n_t - 1 - j, 0, 0))
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, _F32)  # noqa: E731
    return pl.pallas_call(
        kernel,
        grid=(bsz, n_t, n_d),
        in_specs=[block, block,
                  pl.BlockSpec((n, bd), lambda i, j, d: (0, d)),
                  compact, compact,
                  pl.BlockSpec((1, 1, n, bd),
                               lambda i, j, d: (i, n_t - 1 - j, 0, d)),
                  block],
        out_specs=[block, block,
                   # every tile's dA, resident while a sequence runs
                   pl.BlockSpec((1, n_d, n, bd), lambda i, j, d: (i, 0, 0, 0)),
                   compact, compact],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   f32(*dt.shape), f32(bsz, n_d, n, bd),
                   f32(*b2.shape), f32(*c2.shape)],
        scratch_shapes=[
            pltpu.VMEM((n_d, n, bd), _F32),                 # g, per tile
            pltpu.VMEM(((chunk + 1) * n, bd), _F32),        # the states
            pltpu.VMEM((chunk * n, _LANES), _F32),          # b, c spread
            pltpu.VMEM((chunk * n, _LANES), _F32),
            pltpu.VMEM((chunk * n, _LANES), _F32),          # db, dc by lane
            pltpu.VMEM((chunk * n, _LANES), _F32),
            pltpu.VMEM((chunk, bd), _F32),                  # dt x
            pltpu.VMEM((chunk, bd), _F32),                  # dy
            pltpu.VMEM((chunk, bd), _F32),                  # dL/d(dt x)
            pltpu.VMEM((chunk, bd), _F32),                  # dL/d(dt), decay
        ],
        compiler_params=_PARAMS,
        name="selective_scan_bwd",
        interpret=interpret,
    )(dt, x, a, b2, c2, hb, dy)


# ------------------------------------------------------------ the op --------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(dt, x, a, b2, c2, chunk, state_dtype, interpret):
    return _scan_fwd(dt, x, a, b2, c2, chunk, state_dtype, interpret)[0]


def _scan_fwd_rule(dt, x, a, b2, c2, chunk, state_dtype, interpret):
    y, hb = _scan_fwd(dt, x, a, b2, c2, chunk, state_dtype, interpret)
    return y, (dt, x, a, b2, c2, hb)


def _scan_bwd_rule(chunk, state_dtype, interpret, residuals, dy):
    dx, ddt, da, db2, dc2 = _scan_bwd(*residuals, dy, chunk, state_dtype,
                                      interpret)
    n = da.shape[2]
    da = jnp.moveaxis(da.sum(axis=0), 1, 0).reshape(n, -1)
    return ddt, dx, da, db2, dc2


_scan.defvjp(_scan_fwd_rule, _scan_bwd_rule)


def selective_scan(x, dt, a, b, c, *, chunk, state_dtype=_F32,
                   interpret=None):
    """``y[t] = h[t] C[t]`` of the recurrence above, float32 (B, T, D).

    ``x``, ``dt`` (B, T, D); ``a`` (D, N); ``b``, ``c`` (B, T, N).  ``chunk``
    positions a grid step (rounded up to 16, and down to T's own 16); the
    state, ``dt`` and ``a`` in ``state_dtype``, float32 outside a precision
    control.  D is padded to a multiple of 128, T to a multiple of the chunk
    with steps of 0, which change nothing, and N to a power of two from 8 to
    128 with decay rates and columns of 0.  ``interpret=None`` takes the
    Pallas interpreter off the TPU."""
    if interpret is None:
        interpret = not on_tpu()
    bsz, t, dim = x.shape
    n = a.shape[1]
    if n > _LANES:
        raise ValueError(f"selective_scan holds at most {_LANES} states a "
                         f"channel, got a of shape {a.shape}")
    n_pad = max(8, 1 << (n - 1).bit_length())
    chunk = min(_round_up(chunk, 16), _round_up(t, 16))
    t_pad, d_pad = _round_up(t, chunk), _round_up(dim, _LANES)
    rnd = _rounder(state_dtype)

    def compact(v):         # (B, T, N) -> (B, chunks, chunk * N / 128, 128)
        v = jnp.pad(v.astype(_F32),
                    ((0, 0), (0, t_pad - t), (0, n_pad - n)))
        return v.reshape(bsz, t_pad // chunk, chunk * n_pad // _LANES, _LANES)

    grow = ((0, 0), (0, t_pad - t), (0, d_pad - dim))
    y = _scan(jnp.pad(rnd(dt.astype(_F32)), grow), jnp.pad(x, grow),
              jnp.pad(rnd(a.astype(_F32)).T,
                      ((0, n_pad - n), (0, d_pad - dim))),
              compact(rnd(b)), compact(rnd(c)), chunk, state_dtype,
              interpret)
    return y[:, :t, :dim]
