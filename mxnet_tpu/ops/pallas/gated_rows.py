"""``silu(gate) * up`` over the first ``total`` rows of a row buffer.

The stage between a dropless expert layer's two grouped products
(``parallel/moe.py``): ``gate_up`` (M, 2F) holds every assignment's gate and
up projections side by side, and only its first ``total`` rows belong to a
held expert; ``total`` is a value on the device.  Two kernels,
``moe_gated_fwd`` and ``moe_gated_bwd``, walk the buffer in blocks of rows
with ``total`` as a **scalar-prefetch** operand: a grid step past the last
block that holds a held row names that block again, so it fetches and
writes nothing, and its body is skipped.  Inside that last block the rows
past ``total`` are written as zeros; the blocks after it are NEVER WRITTEN
and hold whatever the buffer held: whoever reads the result masks by
``total`` (a grouped product over the same groups does not visit them).

As an XLA loop over chunks of rows (a ``dynamic_update_slice`` a trip into
a zero-filled buffer) the same stage read 25 ns a row against 16 for the
whole-buffer fusion on the chip, plus 0.75-1.5 ms of fills a call, and was
slower than the whole pass from a held share of ~0.5 (PERF.md 6, PR 34).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu
from .once import bind

# rows a grid step: 256 x (2F + F + 2F) x 2 bytes, double-buffered, and the
# float32 intermediates are 16.7 MB of VMEM backward at the widest cell's F
# of 1,792, over the 16 MB a kernel has unasked
_BLOCK = 256
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=48 * 1024 * 1024)


def _held(total_ref, x_ref):
    """(this step holds a held row, the mask of its held rows)."""
    block = x_ref.shape[0]
    start = pl.program_id(0) * block
    rows = start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    return start < total_ref[0], rows < total_ref[0]


def _fwd_kernel(total_ref, x_ref, out_ref):
    some, mine = _held(total_ref, x_ref)

    @pl.when(some)
    def _():
        f = out_ref.shape[1]
        gate = x_ref[:, :f].astype(jnp.float32)
        up = x_ref[:, f:].astype(jnp.float32)
        out_ref[...] = jnp.where(
            mine, gate * jax.nn.sigmoid(gate) * up, 0.0).astype(out_ref.dtype)


def _bwd_kernel(total_ref, x_ref, dy_ref, out_ref):
    some, mine = _held(total_ref, x_ref)

    @pl.when(some)
    def _():
        f = dy_ref.shape[1]
        gate = x_ref[:, :f].astype(jnp.float32)
        up = x_ref[:, f:].astype(jnp.float32)
        dy = dy_ref[...].astype(jnp.float32)
        s = jax.nn.sigmoid(gate)
        d_gate = dy * up * s * (1.0 + gate * (1.0 - s))
        out_ref[:, :f] = jnp.where(mine, d_gate, 0.0).astype(out_ref.dtype)
        out_ref[:, f:] = jnp.where(mine, dy * gate * s, 0.0
                                   ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("backward", "block",
                                             "interpret"))
def _call(total, *operands, backward, block, interpret):
    """One of the two kernels.  A ``jax.jit``, so that a model's layers
    share one lowering of each (they are traced three times a layer under
    recomputation; each lowering costs a warm ``setup_s`` ~0.15 s), over
    the kernel traced once a process (``once.bind``)."""
    return bind(_build, (total, *operands), backward=backward, block=block,
                interpret=interpret)[0]


def _build(total, *operands, backward, block, interpret):
    kernel, name = (_bwd_kernel, "moe_gated_bwd") if backward \
        else (_fwd_kernel, "moe_gated_fwd")
    m, width = operands[0].shape
    if not backward:
        width //= 2

    def at(i, total):
        # the last block with a held row, for every step past it
        return jnp.minimum(i, jnp.maximum(total[0] - 1, 0) // block), 0
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(m, block),),
            in_specs=[pl.BlockSpec((block, x.shape[1]), at)
                      for x in operands],
            out_specs=pl.BlockSpec((block, width), at)),
        out_shape=jax.ShapeDtypeStruct((m, width), operands[0].dtype),
        compiler_params=_PARAMS, name=name, interpret=interpret,
    )(total.reshape(1).astype(jnp.int32), *operands)


def _how(gate_up):
    """Blocks of rows; the Pallas interpreter off the TPU."""
    return dict(block=min(_BLOCK, gate_up.shape[0]), interpret=not on_tpu())


@jax.custom_vjp
def gated_rows(gate_up, total):
    """``silu(gate_up[:, :F]) * gate_up[:, F:]`` (M, F) for the rows below
    ``total`` (an int32 scalar, traced or not); zeros from ``total`` to the
    end of its block of rows, and past that block whatever the buffer held.
    The backward pass gives ``gate_up``'s cotangent under the same rule and
    reads ``dy`` below ``total`` alone."""
    return _call(jnp.asarray(total), gate_up, backward=False,
                 **_how(gate_up))


def _gated_rows_fwd(gate_up, total):
    return gated_rows(gate_up, total), (gate_up, total)


def _gated_rows_bwd(kept, dy):
    gate_up, total = kept
    return _call(jnp.asarray(total), gate_up, dy, backward=True,
                 **_how(gate_up)), None


gated_rows.defvjp(_gated_rows_fwd, _gated_rows_bwd)
