"""Neural-network ops: conv, FC, pooling, norms, softmax, dropout, RNN.

TPU-native counterpart of ``src/operator/nn/`` (SURVEY §2.4): where the
reference dispatches to cuDNN/mshadow kernels (``cudnn_convolution-inl.h``,
``batch_norm.cu``, ``cudnn_rnn-inl.h``), these lower to ``jax.lax`` ops that
XLA tiles onto the MXU (conv/matmul) and VPU (elementwise/norm), with fusion
replacing the reference's hand-written fused kernels.

Layouts follow MXNet: NCHW for 2-D conv (NCW / NCDHW for 1-D/3-D), weights
OIHW, time-major (T, N, C) for the fused RNN op.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .registry import Field, Schema, Shape, register_op

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _tup(v, n):
    if v is None:
        return (1,) * n
    if isinstance(v, int):
        return (v,) * n
    v = tuple(v)
    if len(v) == 1:
        return v * n
    return v


# ---------------------------------------------------------------------------
# FullyConnected (reference: fully_connected.cc — cuBLAS gemm → MXU)
# ---------------------------------------------------------------------------

@register_op("FullyConnected", aliases=("fully_connected",), schema=Schema(
    num_hidden=Field(int, None, "Number of hidden units (inferred from the "
                     "weight shape when omitted).", nullable=True),
    no_bias=Field(bool, False, "Whether to disable the bias term."),
    flatten=Field(bool, True, "Collapse all axes but the first before the "
                  "matmul (reference FullyConnectedParam::flatten)."),
))
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True):
    """Linear transform y = x·Wᵀ + b (reference:
    src/operator/nn/fully_connected.cc) — one MXU matmul."""
    if flatten:
        x = data.reshape(data.shape[0], -1)
    else:
        x = data
    out = jnp.matmul(x, weight.T)
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (reference: convolution.cc + cudnn wrappers)
# ---------------------------------------------------------------------------

_CONV_SPECS = {1: ("NCH", "OIH", "NCH"), 2: ("NCHW", "OIHW", "NCHW"), 3: ("NCDHW", "OIDHW", "NCDHW")}


def _conv_dims(kernel):
    return len(kernel) if not isinstance(kernel, int) else 1


@register_op("Convolution", aliases=("convolution",), schema=Schema(
    ignore=("cudnn_tune", "cudnn_off", "workspace"),
    kernel=Field(Shape, describe="Convolution kernel size, e.g. (3, 3)."),
    stride=Field(Shape, None, "Convolution stride; defaults to 1 per dim.",
                 nullable=True),
    dilate=Field(Shape, None, "Convolution dilation; defaults to 1 per dim.",
                 nullable=True),
    pad=Field(Shape, None, "Zero-padding per spatial dim; defaults to 0.",
              nullable=True),
    num_filter=Field(int, None, "Number of output channels (inferred from "
                     "the weight when omitted).", nullable=True, ge=1),
    num_group=Field(int, 1, "Grouped-convolution group count "
                    "(feature_group_count in the XLA lowering).", ge=1),
    no_bias=Field(bool, False, "Whether to disable the bias term."),
    layout=Field(str, None, "Data layout; only the reference default "
                 "NC(DHW) layouts are supported.", nullable=True,
                 choices=("NCW", "NCHW", "NCDHW")),
))
def convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                pad=None, num_filter=None, num_group=1, no_bias=False, layout=None):
    """N-d convolution over NC(DHW) via lax.conv_general_dilated (reference:
    src/operator/nn/convolution.cc + cudnn wrappers, subsumed by XLA)."""
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_SPECS[nd])
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        preferred_element_type=None,
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


_IM2COL_FIELDS = dict(
    kernel=Field(Shape, describe="Sliding-window size, e.g. (3, 3)."),
    stride=Field(Shape, None, "Window stride; defaults to 1 per dim.",
                 nullable=True),
    dilate=Field(Shape, None, "Window dilation; defaults to 1 per dim.",
                 nullable=True),
    pad=Field(Shape, None, "Zero-padding per spatial dim; defaults to 0.",
              nullable=True),
)


@register_op("im2col", schema=Schema(**_IM2COL_FIELDS))
def im2col(data, kernel=None, stride=None, dilate=None, pad=None):
    """Sliding-window patch extraction (reference: nn/im2col.cc): output
    (N, C·∏kernel, ∏out_spatial) with channel-major row order — exactly the
    layout lax.conv_general_dilated_patches produces."""
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd)
    dilate = _tup(dilate, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    patches = lax.conv_general_dilated_patches(
        data, filter_shape=tuple(kernel), window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate)
    return patches.reshape(patches.shape[0], patches.shape[1], -1)


@register_op("col2im", schema=Schema(
    output_size=Field(Shape, describe="Spatial shape of the output image."),
    **_IM2COL_FIELDS))
def col2im(data, output_size=None, kernel=None, stride=None, dilate=None,
           pad=None):
    """Patch scatter-accumulate, the linear transpose of :func:`im2col`
    (reference: nn/im2col.cc col2im) — derived via jax.linear_transpose from
    an abstract trace (no forward pass runs) so both ops stay consistent by
    construction; overlapping positions sum."""
    import math
    output_size = tuple(output_size)
    n, ckk, _ = data.shape
    kernel = _tup(kernel, len(output_size))
    channels = ckk // math.prod(kernel)
    img_shape = (n, channels) + output_size
    transpose = jax.linear_transpose(
        lambda img: im2col(img, kernel=kernel, stride=stride, dilate=dilate,
                           pad=pad),
        jax.ShapeDtypeStruct(img_shape, data.dtype))
    return transpose(data)[0]


@register_op("Deconvolution", aliases=("deconvolution",), schema=Schema(
    ignore=("cudnn_tune", "cudnn_off", "workspace"),
    kernel=Field(Shape, describe="Deconvolution kernel size."),
    stride=Field(Shape, None, "Stride (lhs_dilation in the XLA lowering).",
                 nullable=True),
    dilate=Field(Shape, None, "Dilation.", nullable=True),
    pad=Field(Shape, None, "Padding removed from the output.", nullable=True),
    adj=Field(Shape, None, "Output-size adjustment per spatial dim.",
              nullable=True),
    num_filter=Field(int, None, "Number of output channels.", nullable=True,
                     ge=1),
    num_group=Field(int, 1, "Group count.", ge=1),
    no_bias=Field(bool, False, "Whether to disable the bias term."),
    target_shape=Field(Shape, None, "Explicit output spatial shape.",
                       nullable=True),
    layout=Field(str, None, "Data layout.", nullable=True,
                 choices=("NCW", "NCHW", "NCDHW")),
))
def deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                  pad=None, adj=None, num_filter=None, num_group=1, no_bias=False,
                  target_shape=None, layout=None):
    nd = _conv_dims(kernel)
    stride = _tup(stride, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    adj = _tup(adj if adj is not None else 0, nd)
    # ConvTranspose = gradient of conv: lhs_dilation implements fractional stride.
    # weight layout for MXNet Deconvolution is (in, out/g, *k).
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_SPECS[nd])
    k = weight.shape[2:]
    padding = [(k[i] - 1 - pad[i], k[i] - 1 - pad[i] + adj[i]) for i in range(nd)]
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if num_group == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        ci, co = w.shape[0], w.shape[1]
        w = w.reshape(num_group, ci // num_group, co, *k)
        w = jnp.swapaxes(w, 1, 2).reshape(num_group * co, ci // num_group, *k)
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (reference: pooling.cc → lax.reduce_window)
# ---------------------------------------------------------------------------

@register_op("Pooling", aliases=("pooling",), schema=Schema(
    ignore=("cudnn_off", "p_value"),
    kernel=Field(Shape, None, "Pooling window size.", nullable=True),
    pool_type=Field(str, "max", "Pooling reduction.",
                    choices=("max", "avg", "sum", "lp")),
    global_pool=Field(bool, False, "Pool over the whole spatial extent."),
    stride=Field(Shape, None, "Window stride; defaults to 1 per dim.",
                 nullable=True),
    pad=Field(Shape, None, "Zero padding; defaults to 0.", nullable=True),
    pooling_convention=Field(str, "valid", "Output-size rounding rule.",
                             choices=("valid", "full", "same")),
    count_include_pad=Field(bool, True, "Average counts padded cells."),
    layout=Field(str, None, "Data layout.", nullable=True,
                 choices=("NCW", "NCHW", "NCDHW")),
))
def pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
            pad=None, pooling_convention="valid", count_include_pad=True, layout=None):
    nd = data.ndim - 2
    if global_pool:
        axes = tuple(range(2, 2 + nd))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        return jnp.mean(data, axis=axes, keepdims=True)
    kernel = _tup(kernel, nd)
    stride = _tup(stride, nd)
    pad = _tup(pad if pad is not None else 0, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pooling_convention == "full":
        # ceil-mode: add extra right-padding so the last window fits
        extra = []
        for i in range(nd):
            size = data.shape[2 + i] + 2 * pad[i]
            rem = (size - kernel[i]) % stride[i]
            extra.append(0 if rem == 0 else stride[i] - rem)
        padding = ((0, 0), (0, 0)) + tuple((p, p + e) for p, e in zip(pad, extra))
    elif pooling_convention == "same":
        # out = ceil(in/stride): distribute the needed pad low/high (extra on
        # the high side), on top of any explicit pad.
        pads = []
        for i in range(nd):
            size = data.shape[2 + i] + 2 * pad[i]
            out = -(-size // stride[i])
            total = max((out - 1) * stride[i] + kernel[i] - size, 0)
            pads.append((pad[i] + total // 2, pad[i] + total - total // 2))
        padding = ((0, 0), (0, 0)) + tuple(pads)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = 1.0
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return summed / counts
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add, window, strides, padding)
        return s ** (1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------------------------
# Normalization (reference: batch_norm.cc, layer_norm.cc, group_norm.cc)
# ---------------------------------------------------------------------------

@register_op("BatchNorm", aliases=("batch_norm",), schema=Schema(
    ignore=("cudnn_off",),
    eps=Field(float, 1e-5, "Epsilon added to the variance.", ge=0.0),
    momentum=Field(float, 0.9, "Moving-average momentum for running stats."),
    fix_gamma=Field(bool, True, "Treat gamma as constant 1 (reference "
                    "BatchNormParam::fix_gamma)."),
    use_global_stats=Field(bool, False, "Always normalize with the running "
                           "statistics, even in training."),
    output_mean_var=Field(bool, False, "Also return the batch mean/var."),
    axis=Field(int, 1, "Channel axis."),
    training=Field(bool, False, "Training mode (batch statistics)."),
))
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-5, momentum=0.9,
               fix_gamma=True, use_global_stats=False, output_mean_var=False,
               axis=1, training=False):
    """Returns (out, batch_mean, batch_var). The layer updates running stats
    functionally from the returned batch statistics (aux-state discipline —
    see gluon/nn BatchNorm; reference mutates aux states inside the op)."""
    # statistics and normalization in fp32 (AMP discipline: the layer keeps
    # gamma/beta/running stats fp32 under cast('bfloat16')); the output drops
    # back to the activation dtype so bf16 nets stay bf16 end-to-end
    x32 = data.astype(jnp.float32)
    axes = tuple(i for i in range(data.ndim) if i != axis)
    if training and not use_global_stats:
        m = jnp.mean(x32, axis=axes)
        v = jnp.var(x32, axis=axes)
    else:
        m = moving_mean.astype(jnp.float32)
        v = moving_var.astype(jnp.float32)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    out = ((x32 - m.reshape(shape)) * lax.rsqrt(v.reshape(shape) + eps)
           * g.reshape(shape).astype(jnp.float32)
           + beta.reshape(shape).astype(jnp.float32))
    return out.astype(data.dtype), m, v


@register_op("LayerNorm", aliases=("layer_norm",), schema=Schema(
    axis=Field(int, -1, "Axis to normalize over."),
    eps=Field(float, 1e-5, "Epsilon added to the variance.", ge=0.0),
    output_mean_var=Field(bool, False, "Also return mean/var."),
))
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    # statistics, scale and shift in fp32 regardless of activation dtype
    # (bf16 mean/var loses ~3 decimal digits; the reference computes fp32
    # throughout and XLA fuses the casts into the same kernel), then ONE
    # cast back: the layer keeps gamma/beta fp32 in a bf16 net, and a
    # bf16 * f32 product would hand every later op a float32 activation.
    # The scope names the norm's device operations (their HLO op_name)
    with jax.named_scope("layer_norm"):
        x32 = data.astype(jnp.float32)
        m = jnp.mean(x32, axis=axis, keepdims=True)
        v = jnp.var(x32, axis=axis, keepdims=True)
        shape = [1] * data.ndim
        shape[axis] = data.shape[axis]
        out = ((x32 - m) * lax.rsqrt(v + eps)
               * gamma.reshape(shape).astype(jnp.float32)
               + beta.reshape(shape).astype(jnp.float32)).astype(data.dtype)
        if output_mean_var:
            return out, jnp.squeeze(m, axis), jnp.squeeze(v, axis)
        return out


@register_op("GroupNorm")
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5, **_):
    n, c = data.shape[0], data.shape[1]
    rest = data.shape[2:]
    x = data.reshape(n, num_groups, c // num_groups, *rest)
    axes = tuple(range(2, x.ndim))
    m = jnp.mean(x, axis=axes, keepdims=True)
    v = jnp.var(x, axis=axes, keepdims=True)
    x = (x - m) * lax.rsqrt(v + eps)
    x = x.reshape(data.shape)
    shape = (1, c) + (1,) * len(rest)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register_op("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3, **_):
    axes = tuple(range(2, data.ndim))
    m = jnp.mean(data, axis=axes, keepdims=True)
    v = jnp.var(data, axis=axes, keepdims=True)
    x = (data - m) * lax.rsqrt(v + eps)
    shape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register_op("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance", **_):
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, data.ndim))
    nrm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / nrm


@register_op("RMSNorm", aliases=("rms_norm",))
def rms_norm(data, gamma, axis=-1, eps=1e-6, **_):
    """TPU-era extension (not in reference): RMSNorm for LLaMA-family models.
    Statistics and scale in fp32, one cast back, under a scope of its own
    (see layer_norm)."""
    with jax.named_scope("rms_norm"):
        x32 = data.astype(jnp.float32)
        v = jnp.mean(jnp.square(x32), axis=axis, keepdims=True)
        return (x32 * lax.rsqrt(v + eps)
                * gamma.astype(jnp.float32)).astype(data.dtype)


# ---------------------------------------------------------------------------
# Activations (reference: activation.cc, leaky_relu.cc)
# ---------------------------------------------------------------------------

_ACTS = {
    "relu": lambda x: jnp.maximum(x, 0),
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    # extended set (Gluon Activation accepts these in the TPU build; the
    # reference routes them through LeakyReLU/contrib ops instead)
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
}


@register_op("Activation", aliases=("activation",), schema=Schema(
    act_type=Field(str, describe="Activation function to apply.",
                   choices=("relu", "sigmoid", "tanh", "softrelu", "softsign",
                            "gelu", "gelu_tanh", "silu", "swish", "mish")),
))
def activation(data, act_type="relu"):
    return _ACTS[act_type](data)


@register_op("LeakyReLU", aliases=("leaky_relu",), schema=Schema(
    gamma=Field(object, None, "Learnable slope tensor (prelu).",
                nullable=True),
    act_type=Field(str, "leaky", "Leaky-family activation variant.",
                   choices=("leaky", "prelu", "elu", "selu", "gelu", "rrelu")),
    slope=Field(float, 0.25, "Negative slope (leaky/elu)."),
    lower_bound=Field(float, 0.125, "rrelu lower slope bound."),
    upper_bound=Field(float, 0.334, "rrelu upper slope bound."),
))
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25, lower_bound=0.125,
               upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma
        if g.ndim < data.ndim:
            shape = [1] * data.ndim
            if data.ndim > 1:
                shape[1] = g.size
            g = g.reshape(shape)
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
def gelu_tanh(data, **_):
    return jax.nn.gelu(data, approximate=True)


@register_op("silu", aliases=("swish",))
def silu(data, **_):
    return data * jax.nn.sigmoid(data)


# ---------------------------------------------------------------------------
# Softmax family (reference: softmax.cc incl. SoftmaxWithLength)
# ---------------------------------------------------------------------------

@register_op("softmax", schema=Schema(
    length=Field(object, None, "Per-row valid lengths (SoftmaxWithLength).",
                 nullable=True),
    axis=Field(int, -1, "Axis to normalize over."),
    temperature=Field(float, None, "Softmax temperature.", nullable=True),
    use_length=Field(bool, False, "Mask positions >= length along axis."),
    dtype=Field(str, None, "Accepted for parity; output follows input dtype.",
                nullable=True),
))
def softmax(data, length=None, axis=-1, temperature=None, use_length=False, dtype=None):
    x = data / temperature if temperature not in (None, 1.0) else data
    if use_length and length is not None:
        # mask positions >= length along `axis` (SoftmaxWithLength)
        T = data.shape[axis]
        steps = jnp.arange(T)
        shape = [1] * data.ndim
        shape[axis] = T
        lshape = list(data.shape)
        lshape[axis] = 1
        mask = steps.reshape(shape) < length.reshape(lshape).astype(jnp.int32)
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def log_softmax(data, axis=-1, temperature=None, **_):
    x = data / temperature if temperature not in (None, 1.0) else data
    return jax.nn.log_softmax(x, axis=axis)


@register_op("softmin")
def softmin(data, axis=-1, **_):
    return jax.nn.softmax(-data, axis=axis)


@register_op("SoftmaxActivation")
def softmax_activation(data, mode="instance", **_):
    """Deprecated-but-present reference op (softmax_activation-inl.h):
    ``instance`` normalizes each example over all remaining dims, ``channel``
    normalizes across axis 1 at every spatial position."""
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    flat = data.reshape(data.shape[0], -1)
    return jax.nn.softmax(flat, axis=-1).reshape(data.shape)


@register_op("masked_softmax")
def masked_softmax(data, mask=None, axis=-1, temperature=1.0, **_):
    x = data / temperature
    if mask is not None:
        x = jnp.where(mask != 0, x, -jnp.inf)
    out = jax.nn.softmax(x, axis=axis)
    if mask is not None:
        out = jnp.where(mask != 0, out, 0.0)
    return out


@register_op("SoftmaxOutput", aliases=("softmax_output",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1, multi_output=False,
                   use_ignore=False, preserve_shape=False, normalization="null",
                   out_grad=False, smooth_alpha=0.0, **_):
    """Forward = softmax; backward = (p - onehot(label)) * grad_scale,
    IGNORING the incoming head gradient (reference: softmax_output-inl.h —
    the op fuses the cross-entropy loss gradient; Module-era nets end in it
    and call backward() with no explicit loss)."""
    axis = 1 if multi_output else -1

    @jax.custom_vjp
    def _f(x, lab):
        return jax.nn.softmax(x, axis=axis)

    def _fwd(x, lab):
        p = jax.nn.softmax(x, axis=axis)
        return p, (p, lab)

    def _bwd(res, g):
        p, lab = res
        k = p.shape[axis]
        oh = jax.nn.one_hot(lab.astype(jnp.int32), k, axis=axis, dtype=p.dtype)
        if smooth_alpha:
            oh = oh * (1.0 - smooth_alpha) + smooth_alpha / k
        gx = p - oh
        if use_ignore:
            keep = (lab != ignore_label)
            gx = gx * jnp.expand_dims(keep.astype(p.dtype), axis)
            if normalization == "valid":
                gx = gx / jnp.maximum(jnp.sum(keep), 1.0)
        if normalization == "batch":
            gx = gx / p.shape[0]
        if out_grad:
            gx = gx * g
        return gx * grad_scale, jnp.zeros_like(lab)

    _f.defvjp(_fwd, _bwd)
    return _f(data, label)


@register_op("softmax_cross_entropy")
def softmax_cross_entropy(data, label, **_):
    logp = jax.nn.log_softmax(data, axis=-1)
    nll = -jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return jnp.sum(nll)


@register_op("smooth_l1")
def smooth_l1(data, scalar=1.0, **_):
    s2 = scalar * scalar
    a = jnp.abs(data)
    return jnp.where(a < 1.0 / s2, 0.5 * s2 * jnp.square(data), a - 0.5 / s2)


def _loss_output(fwd_fn, grad_fn):
    """Output-head factory (reference: regression_output-inl.h family):
    forward applies ``fwd_fn``; backward IGNORES the incoming head gradient
    and emits the fused loss gradient ``grad_fn(pred, label)`` — Module-era
    nets end in these and call backward() with no explicit loss."""

    @jax.custom_vjp
    def _f(x, lab):
        return fwd_fn(x)

    def _vfwd(x, lab):
        p = fwd_fn(x)
        return p, (p, lab)

    def _vbwd(res, g):
        p, lab = res
        return grad_fn(p, lab.astype(p.dtype)), jnp.zeros_like(lab)

    _f.defvjp(_vfwd, _vbwd)
    return _f


def _per_example_outputs(label) -> float:
    """num_output in the reference's regression heads: outputs per example
    (label.Size()/label.shape[0]); gradients are scaled by
    grad_scale/num_output so multi-output regression averages, not sums."""
    n = 1
    for d in label.shape[1:]:
        n *= int(d)
    return float(max(n, 1))


@register_op("LinearRegressionOutput", aliases=("linear_regression_output",))
def linear_regression_output(data, label, grad_scale=1.0, **_):
    """Identity forward; backward = (pred − label)·grad_scale/num_output
    (reference: src/operator/regression_output.cc LinearRegressionOutput)."""
    return _loss_output(
        lambda x: x,
        lambda p, l: (p - l) * (grad_scale / _per_example_outputs(l))
    )(data, label)


@register_op("LogisticRegressionOutput", aliases=("logistic_regression_output",))
def logistic_regression_output(data, label, grad_scale=1.0, **_):
    """Sigmoid forward; backward = (σ(x) − label)·grad_scale/num_output
    (reference: regression_output.cc LogisticRegressionOutput)."""
    return _loss_output(
        jax.nn.sigmoid,
        lambda p, l: (p - l) * (grad_scale / _per_example_outputs(l))
    )(data, label)


@register_op("MAERegressionOutput", aliases=("mae_regression_output",))
def mae_regression_output(data, label, grad_scale=1.0, **_):
    """Identity forward; backward = sign(pred − label)·grad_scale/num_output
    (reference: regression_output.cc MAERegressionOutput)."""
    return _loss_output(
        lambda x: x,
        lambda p, l: jnp.sign(p - l) * (grad_scale / _per_example_outputs(l))
    )(data, label)


@register_op("SVMOutput", aliases=("svm_output",))
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False, **_):
    """One-vs-all SVM output head (reference: src/operator/svm_output.cc):
    identity forward over class scores; backward is the hinge-loss gradient —
    L2-SVM by default, L1-SVM (linear) with ``use_linear``. Per class c the
    sign is +1 for the labeled class, −1 otherwise."""
    reg = regularization_coefficient

    def _grad(p, lab):
        k = p.shape[-1]
        y = 2.0 * jax.nn.one_hot(lab.astype(jnp.int32), k, dtype=p.dtype) - 1.0
        viol = margin - y * p          # >0 where the margin is violated
        active = (viol > 0).astype(p.dtype)
        if use_linear:
            return -reg * y * active
        return -2.0 * reg * y * viol * active

    return _loss_output(lambda x: x, _grad)(data, label)


@register_op("LRN", aliases=("lrn",), schema=Schema(
    alpha=Field(float, 1e-4, "Scale of the squared local sum."),
    beta=Field(float, 0.75, "Exponent of the normalizer."),
    knorm=Field(float, 2.0, "Additive constant."),
    nsize=Field(int, 5, "Channel window (normalization width).", ge=1),
))
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **_):
    """Across-channel local response normalization over NCHW (reference:
    src/operator/nn/lrn.cc — the AlexNet normalizer):
    ``out = x · (knorm + α/n · Σ_{local} x²)^{−β}``. The channel-window sum
    lowers to reduce_window, which XLA fuses with the pointwise tail."""
    sq = jnp.square(data).astype(jnp.float32)
    half = nsize // 2
    local = jax.lax.reduce_window(
        sq, 0.0, jax.lax.add,
        window_dimensions=(1, nsize, 1, 1), window_strides=(1, 1, 1, 1),
        padding=((0, 0), (half, nsize - 1 - half), (0, 0), (0, 0)))
    norm = jnp.power(knorm + (alpha / nsize) * local, -beta)
    return (data.astype(jnp.float32) * norm).astype(data.dtype)


# ---------------------------------------------------------------------------
# Dropout (reference: dropout.cc — cuDNN dropout state ≙ explicit key)
# ---------------------------------------------------------------------------

@register_op("Dropout", aliases=("dropout",), schema=Schema(
    ignore=("cudnn_off",),
    p=Field(float, 0.5, "Fraction of units to drop.", ge=0.0, le=1.0),
    mode=Field(str, "training", "When to apply dropout.",
               choices=("training", "always")),
    axes=Field(Shape, (), "Axes to broadcast the drop mask over."),
    training=Field(bool, False, "Training mode (apply the mask)."),
    key=Field(object, None, "PRNG key (threaded by the RNG trace scope).",
              nullable=True),
))
def dropout(data, p=0.5, mode="training", axes=(), training=False, key=None):
    if not training or p <= 0.0 or key is None:
        return data
    shape = list(data.shape)
    for a in axes:
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


# ---------------------------------------------------------------------------
# UpSampling / resize (reference: upsampling.cc, bilinear_resize.cc)
# ---------------------------------------------------------------------------

@register_op("UpSampling")
def upsampling(data, scale=1, sample_type="nearest", num_args=1, **_):
    n, c, h, w = data.shape
    if sample_type == "nearest":
        return jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


@register_op("contrib_BilinearResize2D", aliases=("bilinear_resize_2d",))
def bilinear_resize_2d(data, height=None, width=None, scale_height=None, scale_width=None, **_):
    n, c, h, w = data.shape
    oh = height if height else int(h * scale_height)
    ow = width if width else int(w * scale_width)
    return jax.image.resize(data, (n, c, oh, ow), method="bilinear")


# ---------------------------------------------------------------------------
# Fused RNN op (reference: rnn.cc / cudnn_rnn-inl.h → lax.scan)
# ---------------------------------------------------------------------------

def _lstm_cell(x, h, c, wx, wh, bx, bh):
    gates = jnp.matmul(x, wx.T) + jnp.matmul(h, wh.T) + bx + bh
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
    g = jnp.tanh(g)
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    return h2, c2


def _gru_cell(x, h, wx, wh, bx, bh):
    xr, xz, xn = jnp.split(jnp.matmul(x, wx.T) + bx, 3, axis=-1)
    hr, hz, hn = jnp.split(jnp.matmul(h, wh.T) + bh, 3, axis=-1)
    r = jax.nn.sigmoid(xr + hr)
    z = jax.nn.sigmoid(xz + hz)
    n = jnp.tanh(xn + r * hn)
    return (1 - z) * n + z * h


def _rnn_cell(x, h, wx, wh, bx, bh, act):
    return act(jnp.matmul(x, wx.T) + jnp.matmul(h, wh.T) + bx + bh)


def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "gru": 3, "lstm": 4}[mode]


def rnn_unpack_params(params, mode, num_layers, input_size, hidden, bidirectional):
    """Slice MXNet's flat fused-RNN parameter vector into per-layer weights.
    Layout (cuDNN order, reference rnn-inl.h): all Wx,Wh per layer/direction,
    then all bx,bh."""
    ngates = _gates(mode)
    dirs = 2 if bidirectional else 1
    shapes = []
    for layer in range(num_layers):
        isz = input_size if layer == 0 else hidden * dirs
        for _ in range(dirs):
            shapes.append((ngates * hidden, isz))   # wx
            shapes.append((ngates * hidden, hidden))  # wh
    bias_shapes = []
    for layer in range(num_layers):
        for _ in range(dirs):
            bias_shapes.append((ngates * hidden,))
            bias_shapes.append((ngates * hidden,))
    ws, off = [], 0
    for s in shapes:
        n = s[0] * (s[1] if len(s) > 1 else 1)
        ws.append(params[off:off + n].reshape(s))
        off += n
    bs = []
    for s in bias_shapes:
        bs.append(params[off:off + s[0]].reshape(s))
        off += s[0]
    return ws, bs


def rnn_param_size(mode, num_layers, input_size, hidden, bidirectional):
    ngates = _gates(mode)
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        isz = input_size if layer == 0 else hidden * dirs
        size += dirs * ngates * hidden * (isz + hidden + 2)
    return size


@register_op("RNN", schema=Schema(
    ignore=("lstm_state_clip_min", "lstm_state_clip_max",
            "lstm_state_clip_nan", "use_sequence_length"),
    state_size=Field(int, describe="Hidden state size.", ge=1),
    num_layers=Field(int, 1, "Number of stacked layers.", ge=1),
    mode=Field(str, "lstm", "Cell type.",
               choices=("rnn_relu", "rnn_tanh", "lstm", "gru")),
    bidirectional=Field(bool, False, "Run a reverse direction too."),
    p=Field(float, 0.0, "Inter-layer dropout (ignored at 0).", ge=0.0, le=1.0),
    state_outputs=Field(bool, False, "Also return the final states."),
    projection_size=Field(int, None, "LSTMP projection size.", nullable=True),
))
def rnn(data, parameters, state, state_cell=None, state_size=None, num_layers=1,
        mode="lstm", bidirectional=False, p=0.0, state_outputs=False,
        projection_size=None):
    """Fused multi-layer (bi)RNN. data: (T, N, C) time-major like the
    reference. Returns out or (out, h_n[, c_n]) per state_outputs."""
    T, N, C = data.shape
    hidden = state_size
    dirs = 2 if bidirectional else 1
    ws, bs = rnn_unpack_params(parameters, mode, num_layers, C, hidden, bidirectional)
    act = jnp.tanh if mode != "rnn_relu" else (lambda x: jnp.maximum(x, 0))

    x = data
    h_states, c_states = [], []
    for layer in range(num_layers):
        outs_dir = []
        for d in range(dirs):
            wi = ws[(layer * dirs + d) * 2]
            wh = ws[(layer * dirs + d) * 2 + 1]
            bi = bs[(layer * dirs + d) * 2]
            bh = bs[(layer * dirs + d) * 2 + 1]
            h0 = state[layer * dirs + d]
            seq = x if d == 0 else jnp.flip(x, axis=0)
            if mode == "lstm":
                c0 = state_cell[layer * dirs + d]

                def step(carry, xt):
                    h, c = carry
                    h2, c2 = _lstm_cell(xt, h, c, wi, wh, bi, bh)
                    return (h2, c2), h2

                (hT, cT), out = lax.scan(step, (h0, c0), seq)
                c_states.append(cT)
            elif mode == "gru":
                def step(h, xt):
                    h2 = _gru_cell(xt, h, wi, wh, bi, bh)
                    return h2, h2

                hT, out = lax.scan(step, h0, seq)
            else:
                def step(h, xt):
                    h2 = _rnn_cell(xt, h, wi, wh, bi, bh, act)
                    return h2, h2

                hT, out = lax.scan(step, h0, seq)
            h_states.append(hT)
            if d == 1:
                out = jnp.flip(out, axis=0)
            outs_dir.append(out)
        x = jnp.concatenate(outs_dir, axis=-1) if dirs == 2 else outs_dir[0]

    outs = [x, jnp.stack(h_states, axis=0)]
    if mode == "lstm":
        outs.append(jnp.stack(c_states, axis=0))
    if state_outputs:
        return tuple(outs)
    return x


# ---------------------------------------------------------------------------
# CTC loss (reference: ctc_loss.cc — forward-backward via scan in log space)
# ---------------------------------------------------------------------------

@register_op("CTCLoss", aliases=("ctc_loss",))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False, blank_label="first", **_):
    """data: (T, N, C) activations (pre-softmax); label: (N, L) padded with
    -1 (or 0s when blank_label='last'). Returns per-example loss (N,)."""
    T, N, C = data.shape
    logp = jax.nn.log_softmax(data, axis=-1)
    blank = 0 if blank_label == "first" else C - 1
    L = label.shape[1]
    lab = label.astype(jnp.int32)
    if use_label_lengths and label_lengths is not None:
        lab_len = label_lengths.astype(jnp.int32)
    elif blank_label == "first":
        # blank is class 0, real labels are 1..C-1, padding is 0 or -1
        # (reference semantics: ctc_loss label packing).
        lab_len = jnp.sum(lab > 0, axis=1).astype(jnp.int32)
    else:
        # blank is class C-1, real labels are 0..C-2, padding is -1.
        lab_len = jnp.sum(lab >= 0, axis=1).astype(jnp.int32)
    # Padded entries may be -1; clamp to blank so ext never holds a negative
    # class index (those positions sit beyond 2*lab_len and cannot influence
    # the left-to-right alpha recurrence).
    lab = jnp.where(lab >= 0, lab, blank)
    t_len = (data_lengths.astype(jnp.int32) if use_data_lengths and data_lengths is not None
             else jnp.full((N,), T, jnp.int32))

    S = 2 * L + 1
    # extended label: blank, l1, blank, l2, ... blank
    ext = jnp.full((N, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    neg_inf = -1e30

    def per_example(logp_n, ext_n, ll, tl):
        # alpha: (S,)
        alpha0 = jnp.full((S,), neg_inf)
        alpha0 = alpha0.at[0].set(logp_n[0, blank])
        alpha0 = alpha0.at[1].set(jnp.where(ll > 0, logp_n[0, ext_n[1]], neg_inf))

        allow_skip = jnp.concatenate([
            jnp.array([False, False]),
            (ext_n[2:] != blank) & (ext_n[2:] != ext_n[:-2]),
        ])

        def step(alpha, t):
            a_prev1 = jnp.concatenate([jnp.array([neg_inf]), alpha[:-1]])
            a_prev2 = jnp.concatenate([jnp.array([neg_inf, neg_inf]), alpha[:-2]])
            a_prev2 = jnp.where(allow_skip, a_prev2, neg_inf)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, a_prev1), a_prev2)
            new = merged + logp_n[t, ext_n]
            new = jnp.where(t < tl, new, alpha)
            return new, None

        alphaT, _ = lax.scan(step, alpha0, jnp.arange(1, T))
        end = 2 * ll
        p1 = alphaT[end]
        p2 = jnp.where(end - 1 >= 0, alphaT[jnp.maximum(end - 1, 0)], neg_inf)
        return -jnp.logaddexp(p1, p2)

    return jax.vmap(per_example)(jnp.transpose(logp, (1, 0, 2)), ext, lab_len, t_len)


# ---------------------------------------------------------------------------
# Gated short convolution (LFM2-style conv mixer; no reference counterpart)
# ---------------------------------------------------------------------------

def short_conv_gate_plain(bcx, w):
    """:func:`short_conv_gate` in plain ``jax.numpy``, differentiated by jax
    itself: what XLA makes of the op, and the oracle the fused op and its
    kernels are tested against. Products and the taps' sum in fp32."""
    C, K = w.shape
    b, c, x = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    L = bcx.shape[1]
    s = jnp.pad(b * x, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(w[:, k].astype(jnp.float32) * s[:, k:k + L] for k in range(K))
    return (c * conv).astype(bcx.dtype)


def _short_conv_kernels(bcx, w):
    """The Pallas kernel pair (its module) on a TPU where the shapes allow,
    ``None`` for the plain form elsewhere: by platform and shape, as
    ``dot_product_attention`` chooses its flash kernels."""
    from .pallas import short_conv
    on_chip = not short_conv._interpret_for(bcx) and short_conv.supported(bcx, w)
    return short_conv if on_chip else None


@jax.custom_vjp
def _short_conv_gate(bcx, w):
    kernels = _short_conv_kernels(bcx, w)
    return kernels.forward(bcx, w) if kernels else short_conv_gate_plain(bcx, w)


def _short_conv_gate_fwd(bcx, w):
    return _short_conv_gate(bcx, w), (bcx, w)


def _short_conv_gate_bwd(res, dy):
    bcx, w = res
    kernels = _short_conv_kernels(bcx, w)
    if kernels:
        return kernels.backward(bcx, w, dy)
    return jax.vjp(short_conv_gate_plain, bcx, w)[1](dy)      # rebuilt from the inputs


_short_conv_gate.defvjp(_short_conv_gate_fwd, _short_conv_gate_bwd)


@register_op()
def short_conv_gate(bcx, w, **_):
    """Gated causal depthwise short convolution, fused: ``bcx (B, L, 3C)``
    holds ``Bg | Cg | x`` side by side, ``w (C, K)`` one weight a channel and
    a tap; returns ``Cg * conv(Bg * x) (B, L, C)`` with ``conv(s)[t] = sum_k
    w[:, k] * s[t - (K - 1) + k]``, ``s`` zero before a row's first
    position (a state-space-style token mixer without attention: LFM2's
    ``conv`` layers, ``K = 3``).

    One op with its own backward: ``d bcx`` and ``d w`` come from ``bcx``,
    ``w`` and ``dy`` alone, so nothing but the inputs is held for the
    backward pass. On a TPU both ways are one Pallas kernel each
    (``ops/pallas/short_conv.py``: ``short_conv_fwd``, ``short_conv_bwd``),
    one pass over the operands; elsewhere :func:`short_conv_gate_plain` and
    jax's gradient of it, which is also what the kernels are tested against."""
    with jax.named_scope("short_conv"):
        return _short_conv_gate(bcx, w)


# ---------------------------------------------------------------------------
# Around a state-space scan: the causal convolution before it and the gated
# norm after it (Mamba-2-style mixer; no reference counterpart)
# ---------------------------------------------------------------------------

def causal_conv1d_plain(x, weight, bias, split=(), start=0):
    """:func:`causal_conv1d` in plain ``jax.numpy``, differentiated by jax
    itself: what XLA makes of the op, what a call the kernels do not take
    traces, and the oracle the kernels are tested against. Products and sums
    in fp32, one cast back."""
    C, K = weight.shape
    L = x.shape[1]
    x = lax.slice_in_dim(x, start, start + C, axis=-1)
    s = jnp.pad(x.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    y = sum(w[:, k] * s[:, k:k + L] for k in range(K)) + bias.astype(jnp.float32)
    y = jax.nn.silu(y).astype(x.dtype)
    return tuple(jnp.split(y, split, axis=-1)) if split else y


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def causal_conv_fused(x, weight, bias, split, start):
    """:func:`causal_conv1d` on the kernel pair whatever the platform
    (interpret mode off the TPU), with the op's own backward: ``dx``, ``d
    weight`` and ``d bias`` come from the inputs and the output's gradient
    alone, so nothing but the inputs is held for it."""
    return _causal_conv_fused_fwd(x, weight, bias, split, start)[0]


def _causal_conv_fused_fwd(x, weight, bias, split, start):
    from .pallas import causal_conv
    y = causal_conv.forward(x, weight, bias, split, start)
    return (y if split else y[0]), (x, weight, bias)


def _causal_conv_fused_bwd(split, start, res, dy):
    from .pallas import causal_conv
    x, weight, _ = res
    dx, dw, db = causal_conv.backward(*res, dy if split else (dy,), start)
    after = x.shape[-1] - start - weight.shape[0]
    if start or after:       # the channels the convolution does not read
        dx = jnp.pad(dx, ((0, 0), (0, 0), (start, after)))
    return dx, dw, db


causal_conv_fused.defvjp(_causal_conv_fused_fwd, _causal_conv_fused_bwd)


@register_op()
def causal_conv1d(x, weight, bias, split=(), start=0, **_):
    """Causal depthwise convolution over positions with bias, then SiLU:
    ``x (B, L, C)``, ``weight (C, K)`` one weight a channel and a tap,
    ``bias (C,)``; ``out[t] = silu(sum_k weight[:, k] * x[t - (K - 1) + k]
    + bias)`` with ``x`` zero before a row's first position (PyTorch's
    ``Conv1d`` with ``groups=C``, ``padding=K-1``, cut to ``L``). ``x`` may
    be wider, the convolution reading its ``C`` channels from ``start`` (a
    projection's whole output, read where it lies). With ``split``, the
    output's parts as ``jnp.split(out, split, axis=-1)`` gives them, and
    their gradients come back the same way.

    The call decides by what it can observe: bf16 or fp32, every part and
    ``start`` whole lane tiles and at most 8 taps on a TPU is one Pallas
    kernel each way (``ops/pallas/causal_conv.py``: ``causal_conv_fwd``,
    ``causal_conv_bwd``) behind a backward rule of the op's own, each part
    written where the next op reads it; anything else traces
    :func:`causal_conv1d_plain`. The gauge ``mxtpu_causal_conv_fused{kernel=}``
    says which way the last call of that width and tap count went."""
    from ..telemetry import metrics
    from .pallas import causal_conv
    C, K = weight.shape
    split = tuple(split)
    fused = (not causal_conv._interpret_for(x)
             and causal_conv.supported(x, weight, split, start))
    metrics.gauge("mxtpu_causal_conv_fused", "1 where the causal convolution's kernel pair "
                  "took the last call of this width and tap count, 0 where the plain form "
                  "did", kernel=f"causal_conv_c{C}_k{K}").set(int(fused))
    if fused:
        return causal_conv_fused(x, weight, bias, split, start)
    return causal_conv1d_plain(x, weight, bias, split, start)


@register_op()
def rms_norm_gated(data, gate, gamma, eps=1e-6, **_):
    """``gamma * g / rms(g)`` with ``g = data * silu(gate)``, the statistic
    over the last axis (one group), all in fp32, one cast back: the norm
    between a Mamba-2 scan and its out-projection."""
    g = data.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    v = jnp.mean(jnp.square(g), axis=-1, keepdims=True)
    return (g * lax.rsqrt(v + eps) * gamma.astype(jnp.float32)).astype(data.dtype)


# ---------------------------------------------------------------------------
# q and k between their projections and the attention kernels: per-head
# RMSNorm, rotary, the head-major layout (no reference counterpart)
# ---------------------------------------------------------------------------

def rotary(x, positions, theta: float, interleaved: bool = False):
    """Rotary position embedding of ``x (B, L, H, D)`` at ``positions (B,
    L)``, computed in fp32. A pair's two parts are the two halves of the
    head dimension (the ``rotate_half`` convention) or, ``interleaved``,
    the neighbours ``(2i, 2i + 1)``; a pair stays where it was."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    if interleaved:
        # every lane meets its pair's other lane through a 0/1 matrix on the
        # MXU (exact: one product by 1.0 a lane). On a TPU a strided slice of
        # the lanes (x[..., 0::2]) is a gather and a shift by one lane a
        # misaligned pass: 0.8 ms each over 32 heads of 64 at 8,192 tokens
        # (v5e, PR 31) against the matmul's few tens of microseconds
        angle = (positions.astype(jnp.float32)[:, :, None, None]
                 * jnp.repeat(inv_freq, 2))
        lane = jnp.arange(D)
        swap = (lane[:, None] == (lane ^ 1)[None, :]).astype(x.dtype)
        other = jnp.einsum("blhd,de->blhe", x, swap, precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
        return (x.astype(jnp.float32) * jnp.cos(angle)
                + other * jnp.where(lane % 2 == 0, -1.0, 1.0) * jnp.sin(angle)).astype(x.dtype)
    angle = positions.astype(jnp.float32)[:, :, None, None] * inv_freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def qk_norm_rope_plain(x, gamma, positions, theta, eps, heads):
    """:func:`qk_norm_rope` as three lines of ``jax.numpy``, differentiated by
    jax itself: what XLA makes of the op, what a call the kernels do not take
    traces, and the oracle the kernels are tested against."""
    B, L, width = x.shape
    y = rms_norm(x.reshape(B, L, heads, width // heads), gamma, eps=eps)
    if positions is not None:
        y = rotary(y, positions, theta)
    return y.transpose(0, 2, 1, 3)


def rotary_table(positions, theta: float, D: int):
    """``cos`` and signed ``sin`` ``(B, L, D)`` in fp32, as the fused kernels
    read them: :func:`rotary`'s angles on both halves of the head, ``sin``
    negated on the first, so that ``x * cos + roll(x, D/2) * sin`` is the
    rotation."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    angle = positions.astype(jnp.float32)[:, :, None] * jnp.tile(inv_freq, 2)
    return jnp.cos(angle), jnp.sin(angle) * jnp.where(jnp.arange(D) < D // 2, -1.0, 1.0)


def _qk_prologue_fused(x, heads) -> bool:
    """Do the Pallas kernels take this call: on a TPU, where its dtype and
    shapes allow? By platform and shape, as ``dot_product_attention``
    chooses its flash kernels."""
    from .pallas import qk_prologue
    return not qk_prologue._interpret_for(x) and qk_prologue.supported(x, heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _qk_prologue(x, gamma, table, eps, heads):
    from .pallas import qk_prologue
    return qk_prologue.forward(x, gamma, table, eps, heads)


def _qk_prologue_fwd(x, gamma, table, eps, heads):
    return _qk_prologue(x, gamma, table, eps, heads), (x, gamma, table)


def _qk_prologue_bwd(eps, heads, res, dy):
    from .pallas import qk_prologue
    x, gamma, table = res
    # rebuilt from the inputs; the tables come from integer positions
    return (*qk_prologue.backward(x, gamma, table, dy, eps, heads), None)


_qk_prologue.defvjp(_qk_prologue_fwd, _qk_prologue_bwd)


@register_op()
def qk_norm_rope(x, gamma, positions=None, theta=10000.0, eps=1e-6, heads=1, **_):
    """A q or k projection's result made ready for the attention kernels:
    ``x (B, L, heads * D)`` is RMS-normed per head with the learnt scale
    ``gamma (D,)``, rotated by ``positions (B, L)`` (:func:`rotary`'s halves
    convention; no rotation with ``None``) and returned head-major, ``(B,
    heads, L, D)``, as ``dot_product_attention`` takes it.

    The call decides by what it can observe: bf16 with ``D`` whole lane
    tiles and ``L`` whole row tiles on a TPU is one Pallas kernel each way
    (``ops/pallas/qk_prologue.py``: ``qk_prologue_fwd``, ``qk_prologue_bwd``)
    behind a backward rule of the op's own, one pass over the operands with
    the statistic, the scale and the rotation in fp32 registers, and
    nothing but the inputs held for the backward pass; anything else traces
    :func:`qk_norm_rope_plain` and nothing more. The gauge
    ``mxtpu_qk_prologue_fused{kernel=}`` says which way the last call of
    that head count and size went."""
    from ..telemetry import metrics
    D = x.shape[-1] // heads
    fused = _qk_prologue_fused(x, heads)
    metrics.gauge("mxtpu_qk_prologue_fused", "1 where the fused q/k prologue kernels took "
                  "the last call of this head count and size, 0 where the plain form did",
                  kernel=f"qk_prologue_h{heads}_d{D}").set(int(fused))
    if not fused:
        return qk_norm_rope_plain(x, gamma, positions, theta, eps, heads)
    table = None if positions is None else rotary_table(positions, theta, D)
    with jax.named_scope("qk_prologue"):
        return _qk_prologue(x, gamma, table, eps, heads)
