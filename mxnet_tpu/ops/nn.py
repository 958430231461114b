"""Neural-network core ops.

Re-emission of (ref: src/operator/nn/ — convolution-inl.h, fully_connected-inl.h,
batch_norm-inl.h, layer_norm-inl.h, pooling-inl.h, softmax-inl.h, dropout-inl.h,
activation-inl.h, ../leaky_relu-inl.h).  Convs lower to lax.conv_general_dilated
(MXU path, replacing cuDNN autotuned algos — XLA picks the tiling); pooling to
lax.reduce_window; normalisations are jnp expressions XLA fuses into one kernel.
Layout is NCHW/NCW/NCDHW to match the reference's default.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_op
from ..base import dtype_np
from .. import random as _random
from .. import autograd as _autograd


def _tup(v, n):
    if v is None or (isinstance(v, (tuple, list)) and len(v) == 0):
        return (1,) * n if n else ()
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


# -------------------------------------------------------------- linear ------
@register_op("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True,
                     out_dtype=None):
    """ref: src/operator/nn/fully_connected-inl.h — FCForward (cuBLAS gemm).
    Weight layout (num_hidden, in_units), reference convention.
    ``out_dtype`` asks the matmul for a wider result than its inputs' type
    (float32 logits from bf16 weights: the MXU accumulates in float32 anyway)."""
    x = data.reshape(data.shape[0], -1) if flatten else data
    out = jnp.matmul(x, weight.T, preferred_element_type=None
                     if out_dtype is None else dtype_np(out_dtype))
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------- conv ------
# channel-first (reference default) and channel-last (TPU-preferred: feature
# dim maps onto lanes without layout-change copies around every conv).
# Weight conventions follow the reference: O,I,*k channel-first; O,*k,I
# channel-last (src/operator/nn/convolution-inl.h layout table).
_CONV_LAYOUTS = {"NCW": ("NCW", "OIW", "NCW"), "NCHW": ("NCHW", "OIHW", "NCHW"),
                 "NCDHW": ("NCDHW", "OIDHW", "NCDHW"),
                 "NWC": ("NWC", "OWI", "NWC"), "NHWC": ("NHWC", "OHWI", "NHWC"),
                 "NDHWC": ("NDHWC", "ODHWI", "NDHWC")}
_DEFAULT_CONV_LAYOUT = {1: "NCW", 2: "NCHW", 3: "NCDHW"}


def _conv_layout(layout, nd):
    l = layout or _DEFAULT_CONV_LAYOUT[nd]
    if l not in _CONV_LAYOUTS:
        raise ValueError(f"unsupported conv layout {l!r}")
    return l, _CONV_LAYOUTS[l], l[-1] == "C"


@register_op("Convolution", aliases=("convolution",))
def _convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, workspace=1024,
                 no_bias=False, cudnn_tune=None, cudnn_off=False, layout=None):
    """ref: src/operator/nn/convolution-inl.h — ConvolutionOp::Forward.
    cuDNN algo selection is replaced by XLA's conv emitter onto the MXU."""
    nd = data.ndim - 2
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad, nd) if pad else (0,) * nd
    _, dnl, chan_last = _conv_layout(layout, nd)
    dn = jax.lax.conv_dimension_numbers(data.shape, weight.shape, dnl)
    out = jax.lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        precision=None,
    )
    if bias is not None and not no_bias:
        bshape = ((1,) * (nd + 1) + (-1,)) if chan_last \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(bshape)
    return out


@register_op("Deconvolution", aliases=("deconvolution",))
def _deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                   pad=None, adj=None, target_shape=None, num_filter=None,
                   num_group=1, workspace=512, no_bias=True, cudnn_tune=None,
                   cudnn_off=False, layout=None):
    """ref: src/operator/nn/deconvolution-inl.h — transposed conv via
    lax.conv_transpose; weight layout (in, out/group, *k) like the reference."""
    nd = data.ndim - 2
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad, nd) if pad else (0,) * nd
    adj = _tup(adj, nd) if adj else (0,) * nd
    # Gradient-of-conv formulation: with transpose_kernel=True jax itself
    # swaps the kernel's I/O axes, so the reference layout (in, out/group, *k)
    # is passed through as-is in the O-I slot order.  jax applies ``padding``
    # to the stride-dilated input, so the reference's output-size contract
    # out = (in-1)*stride - 2*pad + kernel (+adj) needs (ke-1-pad) here.
    _, (lhs, rhs, out_l), chan_last = _conv_layout(layout, nd)
    ke = [(k - 1) * d + 1 for k, d in zip(kernel, dilate)]
    out = jax.lax.conv_transpose(
        data, weight,
        strides=stride,
        padding=[(e - 1 - p, e - 1 - p) for e, p in zip(ke, pad)],
        rhs_dilation=dilate,
        dimension_numbers=(lhs, rhs, out_l),
        transpose_kernel=True,
    )
    if adj != (0,) * nd:
        pads = ([(0, 0)] + [(0, a) for a in adj] + [(0, 0)]) if chan_last \
            else ([(0, 0), (0, 0)] + [(0, a) for a in adj])
        out = jnp.pad(out, pads)
    if bias is not None and not no_bias:
        bshape = ((1,) * (nd + 1) + (-1,)) if chan_last \
            else ((1, -1) + (1,) * nd)
        out = out + bias.reshape(bshape)
    return out


# ------------------------------------------------------------- pooling ------
@register_op("Pooling", aliases=("pooling",))
def _pooling(data, kernel=None, pool_type="max", global_pool=False, cudnn_off=False,
             pooling_convention="valid", stride=None, pad=None, p_value=2,
             count_include_pad=True, layout=None):
    """ref: src/operator/nn/pooling-inl.h — PoolingOp; lax.reduce_window.
    ``layout`` accepts the channel-first defaults and the channel-last
    (NWC/NHWC/NDHWC) TPU-preferred variants."""
    nd = data.ndim - 2
    chan_last = _conv_layout(layout, nd)[2]
    sp0 = 1 if chan_last else 2  # first spatial axis
    if global_pool:
        axes = tuple(range(sp0, sp0 + nd))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type == "sum":
            return jnp.sum(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd) if stride else kernel
    pad = _tup(pad, nd) if pad else (0,) * nd
    if pooling_convention == "full":
        # ceil-mode output: extend padding on the right so the last window fits
        extra = []
        for i in range(nd):
            size = data.shape[sp0 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra.append((stride[i] - rem) % stride[i] if rem else 0)
        spads = tuple((p, p + e) for p, e in zip(pad, extra))
    else:
        spads = tuple((p, p) for p in pad)
    if chan_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
        pads = ((0, 0),) + spads + ((0, 0),)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
        pads = ((0, 0), (0, 0)) + spads
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return jax.lax.reduce_window(data, init, jax.lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        summed = jax.lax.reduce_window(data, 0.0, jax.lax.add, window, strides, pads)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = float(np.prod(kernel))
            return summed / denom
        ones = jnp.ones_like(data)
        counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides, pads)
        return summed / counts
    if pool_type == "lp":
        p = float(p_value)
        powed = jax.lax.reduce_window(jnp.abs(data) ** p, 0.0, jax.lax.add, window, strides, pads)
        return powed ** (1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------- normalisation ---
def _norm_axes(axes, ndim):
    axes = (axes,) if isinstance(axes, int) else tuple(axes)
    return tuple(a % ndim for a in axes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _moments(data, axes, keepdims=False):
    """Centred mean/variance with wide accumulators (f32; f64 for f64 in).

    Centred (not E[x²]−E[x]²) so large-mean/small-std data keeps precision;
    the custom VJP recomputes the centred values in the backward instead of
    letting jax store a widened full-activation residual — the norm ops sit
    on the HBM-bound hot path and must never materialise an f32 activation
    (that residual alone cost ~15% ResNet-50 step time; see PERF.md)."""
    ax = _norm_axes(axes, data.ndim)
    if data.dtype in (jnp.bfloat16, jnp.float16):
        # half-precision hot path: one fused pass, f32 accumulators.  The
        # E[x²]−E[x]² cancellation floor (eps_f32·mean²) sits far below the
        # input's own quantisation noise for any data bf16 can represent,
        # and a single pass keeps the HBM-bound step at one read of x.
        x = data.astype(jnp.float32)
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x), axis=axes, keepdims=True) \
            - jnp.square(mean)
        var = jnp.maximum(var, 0.0)
    else:
        # full-precision path: centred two-pass — immune to large-mean
        # cancellation (the custom VJP below still avoids storing any
        # widened residual for the backward).
        acc_dt = jnp.float64 if data.dtype == jnp.float64 else jnp.float32
        x = data.astype(acc_dt)
        mean = jnp.mean(x, axis=axes, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=axes, keepdims=True)
    if not keepdims:
        shape = [d for i, d in enumerate(data.shape) if i not in ax]
        mean = mean.reshape(shape)
        var = var.reshape(shape)
    return mean, var


def _moments_fwd(data, axes, keepdims):
    mean, var = _moments(data, axes, keepdims)
    return (mean, var), (data, mean)


def _moments_bwd(axes, keepdims, res, cts):
    data, mean = res
    dmean, dvar = cts
    ax = _norm_axes(axes, data.ndim)
    n = 1
    for a in ax:
        n *= data.shape[a]
    kshape = [1 if i in ax else s for i, s in enumerate(data.shape)]
    mean_k = mean.reshape(kshape)
    dmean_k = dmean.reshape(kshape).astype(mean.dtype)
    dvar_k = dvar.reshape(kshape).astype(mean.dtype)
    xm = data.astype(mean.dtype) - mean_k  # recomputed, fuses, not stored
    dx = dmean_k / n + xm * (2.0 * dvar_k / n)
    return (dx.astype(data.dtype),)


_moments.defvjp(_moments_fwd, _moments_bwd)


@register_op("BatchNorm", aliases=("batch_norm",))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                fix_gamma=True, use_global_stats=False, output_mean_var=False,
                axis=1, cudnn_off=False, training=None):
    """ref: src/operator/nn/batch_norm-inl.h — BatchNormForward.

    Functional form: returns (out, new_moving_mean, new_moving_var); the Gluon
    layer threads the aux state (the reference mutates aux in-place via the
    engine; under XLA state must be explicit).
    """
    if training is None:
        training = _autograd.is_training()
    axis = axis % data.ndim
    axes = tuple(i for i in range(data.ndim) if i != axis)
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    if training and not use_global_stats:
        mean, var = _moments(data, axes)
        new_mm = moving_mean * momentum + mean.astype(moving_mean.dtype) * (1 - momentum)
        new_mv = moving_var * momentum + var.astype(moving_var.dtype) * (1 - momentum)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
    # per-channel scale in wide precision (tiny), then one fused centred
    # multiply-add over the activation in ITS OWN dtype — the bf16 hot path
    # never materialises a widened activation (the step is HBM-bound), and
    # subtracting mean before scaling keeps large-mean data well-conditioned
    inv = jax.lax.rsqrt(var + eps)
    scale = (inv * g.astype(var.dtype)).astype(data.dtype)
    out = ((data - mean.astype(data.dtype).reshape(bshape))
           * scale.reshape(bshape) + beta.reshape(bshape))
    if output_mean_var:
        return out, mean.astype(data.dtype), inv.astype(data.dtype)
    return out, new_mm, new_mv


@register_op("FusedNormReluConv", aliases=("fused_norm_relu_conv",))
def _fused_norm_relu_conv(data, weight, gamma, beta, moving_mean,
                          moving_var, residual=None, eps=1e-5, momentum=0.9,
                          relu=True, stride=1, training=None):
    """BatchNorm(+residual)+ReLU folded into the following conv via the
    Pallas kernel (ops/pallas/fused_conv.py) — the normalized activation
    never reaches HBM.  NHWC data, HWIO weight, 1x1/3x3, stride 1 or 2.

    Functional like BatchNorm: returns (out, new_moving_mean,
    new_moving_var); the gluon NormReluConv2D layer threads the aux state.
    """
    from .pallas.fused_conv import norm_relu_conv

    if training is None:
        training = _autograd.is_training()
    axes = tuple(range(data.ndim - 1))  # NHWC: all but channels
    if training:
        mean, var = _moments(data, axes)
        new_mm = moving_mean * momentum + \
            jax.lax.stop_gradient(mean).astype(moving_mean.dtype) * (1 - momentum)
        new_mv = moving_var * momentum + \
            jax.lax.stop_gradient(var).astype(moving_var.dtype) * (1 - momentum)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
    inv = jax.lax.rsqrt(var + eps)
    scale = gamma.astype(jnp.float32) * inv
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    out = norm_relu_conv(data, scale, shift, weight, residual=residual,
                         relu=relu, stride=stride)
    return out, new_mm, new_mv


@register_op("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    """ref: src/operator/nn/layer_norm-inl.h — LayerNormCompute."""
    mean, var = _moments(data, axis, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = ((data - mean.astype(data.dtype)) * inv.astype(data.dtype)
           * gamma.reshape(shape) + beta.reshape(shape))
    if output_mean_var:
        return (out, jnp.squeeze(mean, axis).astype(data.dtype),
                jnp.squeeze(inv, axis).astype(data.dtype))
    return out


@register_op("RMSNorm", aliases=("rms_norm",))
def _rms_norm(data, gamma, axis=-1, eps=1e-6):
    """TPU-era extension (no reference analogue; standard in modern LMs)."""
    ms = jnp.mean(jnp.square(data.astype(jnp.float32)), axis=axis,
                  keepdims=True)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    return data * jax.lax.rsqrt(ms + eps).astype(data.dtype) * gamma.reshape(shape)


@register_op("GroupNorm", aliases=("group_norm",))
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    """ref: src/operator/nn/group_norm-inl.h."""
    n, c = data.shape[0], data.shape[1]
    rest = data.shape[2:]
    x = data.reshape(n, num_groups, c // num_groups, *rest)
    axes = tuple(range(2, x.ndim))
    mean, var = _moments(x, axes, keepdims=True)
    x = ((x - mean.astype(x.dtype))
         * jax.lax.rsqrt(var + eps).astype(x.dtype))
    x = x.reshape(data.shape)
    bshape = (1, c) + (1,) * len(rest)
    return x * gamma.reshape(bshape) + beta.reshape(bshape)


@register_op("InstanceNorm", aliases=("instance_norm",))
def _instance_norm(data, gamma, beta, eps=1e-3):
    """ref: src/operator/instance_norm-inl.h."""
    axes = tuple(range(2, data.ndim))
    mean, var = _moments(data, axes, keepdims=True)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return ((data - mean.astype(data.dtype))
            * jax.lax.rsqrt(var + eps).astype(data.dtype)
            * gamma.reshape(bshape) + beta.reshape(bshape))


# ------------------------------------------------------------ activation ----
@register_op("Activation", aliases=("activation",))
def _activation(data, act_type="relu"):
    """ref: src/operator/nn/activation-inl.h."""
    if act_type == "relu":
        return jax.nn.relu(data)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "gelu":
        # reference routes gelu via LeakyReLU(act_type='gelu'); accepted here
        # too so Dense(activation='gelu') works (the BERT FFN path)
        return jax.nn.gelu(data, approximate=False)
    if act_type == "silu" or act_type == "swish":
        return jax.nn.silu(data)
    raise ValueError(f"unknown act_type {act_type}")


@register_op("LeakyReLU", aliases=("leaky_relu",))
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334):
    """ref: src/operator/leaky_relu-inl.h (leaky/prelu/elu/selu/gelu/rrelu)."""
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim and g.ndim == 1:
            g = g.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, mid * data)
    raise ValueError(f"unknown act_type {act_type}")


@register_op("gelu_tanh")
def _gelu_tanh(data):
    return jax.nn.gelu(data, approximate=True)


@register_op("silu")
def _silu(data):
    return jax.nn.silu(data)


# --------------------------------------------------------------- softmax ----
@register_op("softmax")
def _softmax(data, axis=-1, temperature=None, length=None, use_length=False, dtype=None):
    """ref: src/operator/nn/softmax-inl.h — Softmax with optional length mask."""
    x = data / temperature if temperature else data
    if length is not None:
        pos = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        mask = pos.reshape(shape) < jnp.expand_dims(length.astype(jnp.int32), axis)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register_op("softmin")
def _softmin(data, axis=-1, temperature=None, dtype=None):
    return _softmax(-data, axis=axis, temperature=temperature)


# --------------------------------------------------------------- dropout ----
@register_op("Dropout", aliases=("dropout",), needs_rng=True)
def _dropout(data, p=0.5, mode="training", axes=(), cudnn_off=False, training=None):
    """ref: src/operator/nn/dropout-inl.h — DropoutOp (inverted dropout)."""
    if training is None:
        training = _autograd.is_training()
    if (not training and mode != "always") or p == 0:
        return data
    key = _random.next_key()
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1  # broadcast dropout over these axes
    keep = jax.random.bernoulli(key, 1.0 - p, shape=tuple(shape))
    return jnp.where(keep, data / (1.0 - p), jnp.zeros((), data.dtype))


# ------------------------------------------------------------- legacy fused -
@register_op("SoftmaxOutput", aliases=("softmax_output",))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    """ref: src/operator/softmax_output-inl.h — forward only returns softmax;
    the fused backward trick is replaced by SoftmaxCrossEntropyLoss + autograd."""
    return jax.nn.softmax(data, axis=1 if multi_output else -1)
