"""Fused norm→relu→conv Pallas kernel — the ResNet HBM-floor breaker.

PERF.md's round-3 accounting: the ResNet-50 step is HBM-bound at 44 GB,
of which ~12 GB is BN/relu/residual kLoop fusions.  XLA cannot fuse
elementwise producers INTO a convolution custom-call, so every
``relu(bn(y) [+res])`` materialises a full activation tensor that the next
conv immediately re-reads.  These kernels apply the normalize(+residual)
+relu prologue ON LOAD inside the conv itself — the normalized activation
never exists in HBM, in forward OR backward (both backward kernels
recompute the prologue from the raw input, flash-attention style).

Scope (the ResNet residual-block hot path, SURVEY §7.0.2):
  * NHWC, HWIO weights, kernel 1×1 or 3×3, stride 1 or 2, SAME
    padding, groups=1.  The 7×7 stem stays on the XLA conv.
  * ``scale``/``shift`` are per-channel affine terms ALREADY folded from
    BN statistics (gamma/sqrt(var+eps), beta-mean*scale).  They stay in
    the autograd graph, so the batch-statistics paths of BN gradients
    flow through d(scale)/d(shift) automatically.

Why block-INTERNAL fusion only (analysis, round 4): folding a block's
tail (bn3+residual+relu) into the NEXT block's 1×1 looks tempting, but
ResNet v1 reuses that tail output as the next block's residual — it must
materialise regardless, and the folded prologue would then read BOTH the
wide y3 (C channels) and the previous activation instead of one C/4
tensor, i.e. MORE traffic.  The winnable reads are exactly the two
block-internal ones (bn1+relu into the 3×3, bn2+relu into the closing
1×1), which is what this kernel family covers.

ref: src/operator/nn/convolution.cc + batch_norm.cc — the reference runs
these as separate cuDNN calls with the same materialisation; no
counterpart kernel exists there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...context import on_tpu

__all__ = ["norm_relu_conv", "norm_relu_conv_reference", "supports"]


def supports(kh, kw, stride, groups=1):
    """True when the fused kernel covers this conv configuration."""
    return (kh, kw) in ((1, 1), (3, 3)) and stride in (1, 2) and groups == 1


def _out_dim(n, stride):
    """SAME-padding output extent."""
    return -(-n // stride)


def _same_pads(n, k, stride):
    """(pad_lo, pad_hi) of SAME padding along one spatial dim."""
    total = max((_out_dim(n, stride) - 1) * stride + k - n, 0)
    return total // 2, total - total // 2


def _prologue(x, scale, shift, res, relu):
    """X = relu(x*scale + shift [+ res]) in f32 — shared by all 3 kernels."""
    pre = x.astype(jnp.float32) * scale + shift
    if res is not None:
        pre = pre + res.astype(jnp.float32)
    return jnp.maximum(pre, 0.0) if relu else pre


# ------------------------------------------------------------- forward ------
def _taps(Xp, h, w_dim, ci, k, stride):
    """Yield (ky, kx, patch) with patch = the (Ho, Wo, Ci) strided window
    of the padded input under tap (ky, kx) — the 9 shifted views whose
    matmuls sum to the convolution.

    Mosaic rejects strided vector slices (`vector.extract_strided_slice`
    requires unit strides), so for
    stride > 1 the decimation is a contiguous slice + reshape + static
    index, all of which lower to unit-stride ops.  Callers must pad Xp
    with `stride - 1` extra rows/cols (see ``_pad_guard``) so the
    contiguous slice extent ``stride * ho`` stays in bounds."""
    ho, wo = _out_dim(h, stride), _out_dim(w_dim, stride)
    for ky in range(k):
        for kx in range(k):
            if stride == 1:
                patch = lax.slice(Xp, (ky, kx, 0), (ky + ho, kx + wo, ci))
            else:
                full = lax.slice(Xp, (ky, kx, 0),
                                 (ky + stride * ho, kx + stride * wo, ci))
                patch = full.reshape(ho, stride, wo, stride,
                                     ci)[:, 0, :, 0, :]
            yield ky, kx, patch


def _pad_guard(stride):
    """Extra high-side padding so stride>1 taps can slice contiguously."""
    return stride - 1


def _fwd_kernel(x_ref, scale_ref, shift_ref, w_ref, *rest, k, stride, relu,
                has_res):
    if has_res:
        r_ref, o_ref = rest
    else:
        (o_ref,) = rest
        r_ref = None
    h, w_dim, ci = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    ho, wo = _out_dim(h, stride), _out_dim(w_dim, stride)
    X = _prologue(x_ref[0], scale_ref[0], shift_ref[0],
                  r_ref[0] if has_res else None, relu)
    if k == 1 and stride == 1:
        acc = X.reshape(h * w_dim, ci) @ w_ref[0, 0].astype(jnp.float32)
    else:
        py, _py2 = _same_pads(h, k, stride)
        px, _px2 = _same_pads(w_dim, k, stride)
        g = _pad_guard(stride)
        Xp = jnp.pad(X, ((py, _py2 + g), (px, _px2 + g), (0, 0)))
        acc = None
        for ky, kx, patch in _taps(Xp, h, w_dim, ci, k, stride):
            term = patch.reshape(ho * wo, ci) @ \
                w_ref[ky, kx].astype(jnp.float32)
            acc = term if acc is None else acc + term
    o_ref[0] = acc.reshape(ho, wo, -1).astype(o_ref.dtype)


def _pick_block_co(co, want):
    """Largest divisor of co that is <= want (grid tiles must cover co
    exactly — a non-dividing block would leave tail channels unwritten)
    and that Mosaic takes as the lane dim of the w/do/out blocks: a
    multiple of 128, else all of co.  co=192 at want=128 is one 192-wide
    tile, not two of 96.  The interpreter tiles by the same rule, so the
    parity tests run the grid the chip runs."""
    for d in range(min(want, co), 0, -1):
        if co % d == 0 and d % 128 == 0:
            return d
    return co


def _fwd(x, scale, shift, w, res, relu, stride, block_co, interpret):
    n, h, wd, ci = x.shape
    k, _, _, co = w.shape
    ho, wo = _out_dim(h, stride), _out_dim(wd, stride)
    block_co = _pick_block_co(co, block_co)
    inputs = [x, scale.reshape(1, ci), shift.reshape(1, ci), w]
    in_specs = [
        pl.BlockSpec((1, h, wd, ci), lambda nb, cb: (nb, 0, 0, 0)),
        pl.BlockSpec((1, ci), lambda nb, cb: (0, 0)),
        pl.BlockSpec((1, ci), lambda nb, cb: (0, 0)),
        pl.BlockSpec((k, k, ci, block_co), lambda nb, cb: (0, 0, 0, cb)),
    ]
    if res is not None:
        inputs.append(res)
        in_specs.append(
            pl.BlockSpec((1, h, wd, ci), lambda nb, cb: (nb, 0, 0, 0)))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, stride=stride, relu=relu,
                          has_res=res is not None),
        grid=(n, co // block_co),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, ho, wo, block_co),
                               lambda nb, cb: (nb, 0, 0, cb)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
        name="fused_conv_fwd",
        interpret=interpret,
    )(*inputs)


# ---------------------------------------------------------- backward dX -----
def _dx_kernel(x_ref, scale_ref, shift_ref, w_ref, do_ref, *rest, k, stride,
               relu, has_res):
    """dx (+dres) for one sample; also per-sample dscale/dshift partials.

    G = dO ⋆ flip(W) (the full correlation); the relu mask and the affine
    chain rule are the epilogue: dx = G·mask·scale, dres = G·mask,
    dscale_n = Σ G·mask·x, dshift_n = Σ G·mask.
    """
    if has_res:
        r_ref, dx_ref, dres_ref, dsc_ref, dsh_ref = rest
    else:
        dx_ref, dsc_ref, dsh_ref = rest
        r_ref = dres_ref = None
    h, wd, ci = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    co = do_ref.shape[3]
    ho, wo = _out_dim(h, stride), _out_dim(wd, stride)
    do = do_ref[0].astype(jnp.float32)
    if k == 1 and stride == 1:
        G = do.reshape(h * wd, co) @ \
            w_ref[0, 0].astype(jnp.float32).T
    else:
        if stride == 1:
            dod = do
        else:
            # transposed conv: dilate dO by the stride (zeros between
            # output positions), then full-correlate with flipped taps.
            # Strided scatter (`.at[::s, ::s]`) doesn't lower on Mosaic;
            # interleave zeros via pad + reshape (unit-stride ops), then
            # trim the trailing `stride - 1` zeros to the dilated extent.
            dod = jnp.pad(do.reshape(ho, 1, wo, 1, co),
                          ((0, 0), (0, stride - 1),
                           (0, 0), (0, stride - 1), (0, 0)))
            dod = dod.reshape(stride * ho, stride * wo, co)
            dod = lax.slice(dod, (0, 0, 0),
                            (stride * (ho - 1) + 1,
                             stride * (wo - 1) + 1, co))
        py, _ = _same_pads(h, k, stride)
        px, _ = _same_pads(wd, k, stride)
        ply = k - 1 - py
        plx = k - 1 - px
        pry = h + k - 1 - dod.shape[0] - ply
        prx = wd + k - 1 - dod.shape[1] - plx
        dop = jnp.pad(dod, ((ply, pry), (plx, prx), (0, 0)))
        G = None
        for ky in range(k):
            for kx in range(k):
                patch = lax.slice(dop, (ky, kx, 0), (ky + h, kx + wd, co))
                # correlate with the 180°-flipped tap
                term = patch.reshape(h * wd, co) @ \
                    w_ref[k - 1 - ky, k - 1 - kx].astype(jnp.float32).T
                G = term if G is None else G + term
    G = G.reshape(h, wd, ci)
    x = x_ref[0].astype(jnp.float32)
    scale = scale_ref[0]
    if relu:
        pre = x * scale + shift_ref[0]
        if has_res:
            pre = pre + r_ref[0].astype(jnp.float32)
        Gm = jnp.where(pre > 0.0, G, 0.0)
    else:
        Gm = G
    dx_ref[0] = (Gm * scale).astype(dx_ref.dtype)
    if has_res:
        dres_ref[0] = Gm.astype(dres_ref.dtype)
    # rank-3 (N, 1, Ci) partials: a (1, Ci) block over an (N, Ci) array
    # violates Mosaic's last-two-dims rule (1 ∤ 8 and 1 != N); the extra
    # unit axis makes the block's trailing dims equal the array's.
    dsc_ref[0, 0] = jnp.sum(Gm * x, axis=(0, 1))
    dsh_ref[0, 0] = jnp.sum(Gm, axis=(0, 1))


def _dx(x, scale, shift, w, res, do, relu, stride, interpret):
    n, h, wd, ci = x.shape
    k = w.shape[0]
    has_res = res is not None
    inputs = [x, scale.reshape(1, ci), shift.reshape(1, ci), w, do]
    in_specs = [
        pl.BlockSpec((1, h, wd, ci), lambda nb: (nb, 0, 0, 0)),
        pl.BlockSpec((1, ci), lambda nb: (0, 0)),
        pl.BlockSpec((1, ci), lambda nb: (0, 0)),
        pl.BlockSpec(w.shape, lambda nb: (0, 0, 0, 0)),
        pl.BlockSpec((1, do.shape[1], do.shape[2], do.shape[3]),
                     lambda nb: (nb, 0, 0, 0)),
    ]
    if has_res:
        inputs.append(res)
        in_specs.append(
            pl.BlockSpec((1, h, wd, ci), lambda nb: (nb, 0, 0, 0)))
    out_specs = [pl.BlockSpec((1, h, wd, ci), lambda nb: (nb, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if has_res:
        out_specs.append(
            pl.BlockSpec((1, h, wd, ci), lambda nb: (nb, 0, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(x.shape, res.dtype))
    out_specs += [pl.BlockSpec((1, 1, ci), lambda nb: (nb, 0, 0)),
                  pl.BlockSpec((1, 1, ci), lambda nb: (nb, 0, 0))]
    out_shape += [jax.ShapeDtypeStruct((n, 1, ci), jnp.float32),
                  jax.ShapeDtypeStruct((n, 1, ci), jnp.float32)]
    outs = pl.pallas_call(
        functools.partial(_dx_kernel, k=k, stride=stride, relu=relu,
                          has_res=has_res),
        grid=(n,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="fused_conv_bwd_dx",
        interpret=interpret,
    )(*inputs)
    if has_res:
        dx, dres, dsc, dsh = outs
    else:
        dx, dsc, dsh = outs
        dres = None
    # per-sample partials -> channel totals (tiny (N, 1, Ci) reduce in XLA)
    return dx, dres, dsc.sum(axis=(0, 1)), dsh.sum(axis=(0, 1))


# ---------------------------------------------------------- backward dW -----
def _dw_kernel(x_ref, scale_ref, shift_ref, do_ref, *rest, k, stride,
               relu, has_res, n):
    """dW accumulated over samples: grid (co_tiles, N), acc in VMEM."""
    if has_res:
        r_ref, dw_ref, acc_ref = rest
    else:
        dw_ref, acc_ref = rest
        r_ref = None
    nb = pl.program_id(1)

    @pl.when(nb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    h, wd, ci = x_ref.shape[1], x_ref.shape[2], x_ref.shape[3]
    tco = do_ref.shape[3]
    ho, wo = _out_dim(h, stride), _out_dim(wd, stride)
    X = _prologue(x_ref[0], scale_ref[0], shift_ref[0],
                  r_ref[0] if has_res else None, relu)
    do = do_ref[0].astype(jnp.float32).reshape(ho * wo, tco)
    if k == 1 and stride == 1:
        acc_ref[0, 0] += X.reshape(h * wd, ci).T @ do
    else:
        py, py2 = _same_pads(h, k, stride)
        px, px2 = _same_pads(wd, k, stride)
        g = _pad_guard(stride)
        Xp = jnp.pad(X, ((py, py2 + g), (px, px2 + g), (0, 0)))
        for ky, kx, patch in _taps(Xp, h, wd, ci, k, stride):
            acc_ref[ky, kx] += patch.reshape(ho * wo, ci).T @ do

    @pl.when(nb == n - 1)
    def _finish():
        dw_ref[...] = acc_ref[...].astype(dw_ref.dtype)


def _dw(x, scale, shift, res, do, k, co, relu, stride, block_co,
        interpret):
    n, h, wd, ci = x.shape
    block_co = _pick_block_co(co, block_co)
    has_res = res is not None
    inputs = [x, scale.reshape(1, ci), shift.reshape(1, ci), do]
    in_specs = [
        pl.BlockSpec((1, h, wd, ci), lambda cb, nb: (nb, 0, 0, 0)),
        pl.BlockSpec((1, ci), lambda cb, nb: (0, 0)),
        pl.BlockSpec((1, ci), lambda cb, nb: (0, 0)),
        pl.BlockSpec((1, do.shape[1], do.shape[2], block_co),
                     lambda cb, nb: (nb, 0, 0, cb)),
    ]
    if has_res:
        inputs.append(res)
        in_specs.append(
            pl.BlockSpec((1, h, wd, ci), lambda cb, nb: (nb, 0, 0, 0)))
    return pl.pallas_call(
        functools.partial(_dw_kernel, k=k, stride=stride, relu=relu,
                          has_res=has_res, n=n),
        grid=(co // block_co, n),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((k, k, ci, block_co),
                               lambda cb, nb: (0, 0, 0, cb)),
        out_shape=jax.ShapeDtypeStruct((k, k, ci, co), jnp.float32),
        scratch_shapes=[pltpu.VMEM((k, k, ci, block_co), jnp.float32)],
        name="fused_conv_bwd_dw",
        interpret=interpret,
    )(*inputs)


# ----------------------------------------------------------- public api -----
def norm_relu_conv_reference(x, scale, shift, w, residual=None, relu=True,
                             stride=1):
    """XLA twin of the fused kernel (test oracle + fallback path)."""
    pre = x.astype(jnp.float32) * scale + shift
    if residual is not None:
        pre = pre + residual.astype(jnp.float32)
    X = jnp.maximum(pre, 0.0) if relu else pre
    out = lax.conv_general_dilated(
        X.astype(x.dtype), w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _core(x, scale, shift, w, relu, stride, block_co, interpret):
    out, _ = _fwd_rule(x, scale, shift, w, relu, stride, block_co,
                       interpret)
    return out


def _fwd_rule(x, scale, shift, w, relu, stride, block_co, interpret):
    out = _fwd(x, scale.astype(jnp.float32), shift.astype(jnp.float32), w,
               None, relu, stride, block_co, interpret)
    return out, (x, scale, shift, w)


def _bwd_rule(relu, stride, block_co, interpret, resd, do):
    x, scale, shift, w = resd
    s32 = scale.astype(jnp.float32)
    h32 = shift.astype(jnp.float32)
    dx, _, dsc, dsh = _dx(x, s32, h32, w, None, do, relu, stride, interpret)
    dw = _dw(x, s32, h32, None, do, w.shape[0], w.shape[3], relu, stride,
             block_co, interpret)
    return (dx, dsc.astype(scale.dtype), dsh.astype(shift.dtype),
            dw.astype(w.dtype))


_core.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _core_res(x, scale, shift, w, residual, relu, stride, block_co,
              interpret):
    out, _ = _fwd_res_rule(x, scale, shift, w, residual, relu, stride,
                           block_co, interpret)
    return out


def _fwd_res_rule(x, scale, shift, w, residual, relu, stride, block_co,
                  interpret):
    out = _fwd(x, scale.astype(jnp.float32), shift.astype(jnp.float32), w,
               residual, relu, stride, block_co, interpret)
    return out, (x, scale, shift, w, residual)


def _bwd_res_rule(relu, stride, block_co, interpret, resd, do):
    x, scale, shift, w, residual = resd
    s32 = scale.astype(jnp.float32)
    h32 = shift.astype(jnp.float32)
    dx, dres, dsc, dsh = _dx(x, s32, h32, w, residual, do, relu, stride,
                             interpret)
    dw = _dw(x, s32, h32, residual, do, w.shape[0], w.shape[3], relu,
             stride, block_co, interpret)
    return (dx, dsc.astype(scale.dtype), dsh.astype(shift.dtype),
            dw.astype(w.dtype), dres)


_core_res.defvjp(_fwd_res_rule, _bwd_res_rule)


def norm_relu_conv(x, scale, shift, w, residual=None, relu=True, stride=1,
                   block_co=128, interpret=None):
    """conv(relu(x·scale + shift [+ residual]), w) without materialising
    the normalized activation (forward or backward).

    x: (N, H, W, Ci) raw pre-norm activations; scale/shift: (Ci,) affine
    folded from BN stats (keep them in the traced graph so stat gradients
    flow); w: (k, k, Ci, Co) HWIO with k in {1, 3}; stride 1 or 2, SAME.
    ``interpret=None`` auto-selects the Pallas interpreter off-TPU.
    """
    k = w.shape[0]
    if not supports(k, w.shape[1], stride):
        raise ValueError(f"fused kernel supports 1x1/3x3 stride 1/2; got "
                         f"{w.shape[:2]} stride {stride}")
    if interpret is None:
        interpret = not on_tpu()
    if residual is None:
        return _core(x, scale, shift, w, relu, stride, block_co, interpret)
    return _core_res(x, scale, shift, w, residual, relu, stride, block_co,
                     interpret)
