"""Neural-network layer ops.

TPU-native re-design of the reference's legacy ``OperatorProperty`` layers
(src/operator/*.cc — Convolution, FullyConnected, BatchNorm, Pooling, ...).
Where the reference dispatches to cuDNN/mshadow CUDA kernels, these lower to
lax convolutions / reduce_windows / dot_generals that XLA tiles onto the
MXU; loss layers reproduce the reference's custom backward semantics via
jax.custom_vjp; stateful aux (BatchNorm moving stats) is returned
functionally and written back by the executor.

Each layer carrying learnable parameters provides ``infer_shape`` so that
partial shape information propagates exactly like the reference's
InferShape (weights back-inferred from data shape + attrs).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register
from ..base import MXNetError


def _tuple(x, n=None):
    if isinstance(x, (list, tuple)):
        t = tuple(x)
    else:
        t = (x,)
    if n is not None and len(t) == 1 and n > 1:
        t = t * n
    return t


def _prod(xs):
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ---------------------------------------------------------------------------
# FullyConnected — src/operator/fully_connected-inl.h
# ---------------------------------------------------------------------------

def _fc_inputs(attrs):
    if attrs.get("no_bias", False):
        return ("data", "weight")
    return ("data", "weight", "bias")


def _fc_infer(attrs, in_shapes):
    num_hidden = int(attrs["num_hidden"])
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    in_dim = _prod(data[1:])
    shapes = [tuple(data), (num_hidden, in_dim)]
    if not attrs.get("no_bias", False):
        shapes.append((num_hidden,))
    return shapes, [(data[0], num_hidden)], []


@register("FullyConnected", input_names=_fc_inputs, infer_shape=_fc_infer)
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False):
    """y = x @ W.T + b with input flattened to 2D (reference
    src/operator/fully_connected-inl.h Forward).  Direct MXU matmul."""
    x = data.reshape((data.shape[0], -1))
    out = jnp.dot(x, weight.T)
    if bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution — src/operator/convolution-inl.h (cuDNN in the reference;
# here lax.conv_general_dilated → MXU)
# ---------------------------------------------------------------------------

_CONV_DIMNUMS = {1: ("NCH", "OIH", "NCH"),
                 2: ("NCHW", "OIHW", "NCHW"),
                 3: ("NCDHW", "OIDHW", "NCDHW")}


def _conv_infer(attrs, in_shapes):
    kernel = _tuple(attrs["kernel"])
    nd = len(kernel)
    num_filter = int(attrs["num_filter"])
    num_group = int(attrs.get("num_group", 1))
    no_bias = attrs.get("no_bias", False)
    stride = _tuple(attrs.get("stride", (1,) * nd), nd)
    pad = _tuple(attrs.get("pad", (0,) * nd), nd)
    dilate = _tuple(attrs.get("dilate", (1,) * nd), nd)
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    c_in = data[1]
    wshape = (num_filter, c_in // num_group) + kernel
    shapes = [tuple(data), wshape] + ([] if no_bias else [(num_filter,)])
    out_sp = tuple(
        (data[2 + i] + 2 * pad[i] - (dilate[i] * (kernel[i] - 1) + 1)) // stride[i] + 1
        for i in range(nd))
    return shapes, [(data[0], num_filter) + out_sp], []


@register("Convolution", input_names=_fc_inputs, infer_shape=_conv_infer,
          aliases=("Convolution_v1",))
def convolution(data, weight, bias=None, kernel=(), stride=None, dilate=None,
                pad=None, num_filter=0, num_group=1, no_bias=False,
                workspace=1024, cudnn_tune=None, cudnn_off=False, layout=None):
    kernel = _tuple(kernel)
    nd = len(kernel)
    stride = _tuple(stride or (1,) * nd, nd)
    dilate = _tuple(dilate or (1,) * nd, nd)
    pad = _tuple(pad if pad is not None else (0,) * nd, nd)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, _CONV_DIMNUMS[nd])
    if data.dtype != weight.dtype:
        # the weights' dtype is the layer's compute dtype: a bf16 serving
        # pool (serving/pool.py) feeds f32 requests into bf16 weights, and
        # lax.conv — unlike jnp.dot — refuses mixed operands
        data = data.astype(weight.dtype)
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad], rhs_dilation=dilate,
        feature_group_count=num_group, dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Deconvolution — src/operator/deconvolution-inl.h
# ---------------------------------------------------------------------------

def _deconv_infer(attrs, in_shapes):
    kernel = _tuple(attrs["kernel"])
    nd = len(kernel)
    num_filter = int(attrs["num_filter"])
    num_group = int(attrs.get("num_group", 1))
    no_bias = attrs.get("no_bias", True)
    stride = _tuple(attrs.get("stride", (1,) * nd), nd)
    pad = _tuple(attrs.get("pad", (0,) * nd), nd)
    adj = _tuple(attrs.get("adj", (0,) * nd), nd)
    dilate = _tuple(attrs.get("dilate", (1,) * nd), nd)
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    c_in = data[1]
    wshape = (c_in, num_filter // num_group) + kernel
    shapes = [tuple(data), wshape] + ([] if no_bias else [(num_filter,)])
    out_sp = tuple(
        stride[i] * (data[2 + i] - 1) + dilate[i] * (kernel[i] - 1) + 1
        - 2 * pad[i] + adj[i]
        for i in range(nd))
    return shapes, [(data[0], num_filter) + out_sp], []


@register("Deconvolution",
          input_names=lambda attrs: (("data", "weight") if attrs.get("no_bias", True)
                                     else ("data", "weight", "bias")),
          infer_shape=_deconv_infer)
def deconvolution(data, weight, bias=None, kernel=(), stride=None, pad=None,
                  adj=None, dilate=None, num_filter=0, num_group=1,
                  no_bias=True, workspace=512, target_shape=None,
                  cudnn_tune=None, cudnn_off=False, layout=None):
    """Transposed convolution = gradient of Convolution w.r.t. its input
    (reference implements it exactly that way via the conv backward kernel)."""
    kernel = _tuple(kernel)
    nd = len(kernel)
    stride = _tuple(stride or (1,) * nd, nd)
    pad = _tuple(pad if pad is not None else (0,) * nd, nd)
    adj = _tuple(adj if adj is not None else (0,) * nd, nd)
    dilate = _tuple(dilate if dilate is not None else (1,) * nd, nd)
    # lhs-dilated conv with flipped kernel implements conv-transpose;
    # effective kernel extent accounts for rhs dilation
    keff = [dilate[i] * (kernel[i] - 1) + 1 for i in range(nd)]
    padding = [(keff[i] - 1 - pad[i], keff[i] - 1 - pad[i] + adj[i])
               for i in range(nd)]
    flipped = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    # weight layout is (C_in, num_filter//group, k...) → swap to OIHW w.r.t.
    # the transposed conv
    if num_group == 1:
        w = jnp.swapaxes(flipped, 0, 1)
    else:
        ci, co_g = flipped.shape[0], flipped.shape[1]
        w = flipped.reshape((num_group, ci // num_group, co_g) + kernel)
        w = jnp.swapaxes(w, 1, 2).reshape((num_group * co_g, ci // num_group) + kernel)
    dn = lax.conv_dimension_numbers(data.shape, w.shape, _CONV_DIMNUMS[nd])
    if data.dtype != w.dtype:    # as in convolution: the weights decide
        data = data.astype(w.dtype)
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, rhs_dilation=dilate,
        feature_group_count=num_group, dimension_numbers=dn)
    if bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling — src/operator/pooling-inl.h (+ pooling_v1)
# ---------------------------------------------------------------------------

def _pool_out_dim(size, k, s, p, convention):
    if convention == "full":
        return int(np.ceil((size + 2 * p - k) / float(s))) + 1
    return (size + 2 * p - k) // s + 1


def _pool_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    if attrs.get("global_pool", False):
        return [tuple(data)], [tuple(data[:2]) + (1,) * (len(data) - 2)], []
    kernel = _tuple(attrs["kernel"])
    nd = len(kernel)
    stride = _tuple(attrs.get("stride", (1,) * nd), nd)
    pad = _tuple(attrs.get("pad", (0,) * nd), nd)
    conv = str(attrs.get("pooling_convention", "valid"))
    out_sp = tuple(_pool_out_dim(data[2 + i], kernel[i], stride[i], pad[i], conv)
                   for i in range(nd))
    return [tuple(data)], [tuple(data[:2]) + out_sp], []


@register("Pooling", infer_shape=_pool_infer, aliases=("Pooling_v1",))
def pooling(data, kernel=(), pool_type="max", stride=None, pad=None,
            global_pool=False, pooling_convention="valid", cudnn_off=False):
    nd = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = _tuple(kernel)
        stride = _tuple(stride or (1,) * nd, nd)
        pad = _tuple(pad if pad is not None else (0,) * nd, nd)
    window = (1, 1) + tuple(kernel)
    strides = (1, 1) + tuple(stride)
    if pooling_convention == "full" and not global_pool:
        # ceil-mode: extend right padding so the last window fits
        pads = [(0, 0), (0, 0)]
        for i in range(nd):
            out_d = _pool_out_dim(data.shape[2 + i], kernel[i], stride[i],
                                  pad[i], "full")
            needed = (out_d - 1) * stride[i] + kernel[i] - data.shape[2 + i] - pad[i]
            pads.append((pad[i], max(needed, pad[i])))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pool_type == "max":
        # init must stay a python/numpy scalar literal: the reduce_window
        # max-grad rule inspects it, and a jax-array constant becomes an
        # opaque tracer under jit, killing the VJP
        if jnp.issubdtype(data.dtype, jnp.floating):
            init = np.array(-np.inf, dtype=data.dtype)
        else:
            init = np.array(np.iinfo(data.dtype).min, dtype=data.dtype)
        return lax.reduce_window(data, init, lax.max, window, strides, pads)
    summed = lax.reduce_window(data, np.array(0, dtype=data.dtype), lax.add,
                               window, strides, pads)
    if pool_type == "sum":
        return summed
    if pool_type == "avg":
        # reference mshadow pool divides by the constant kernel size
        # (padding included) — pooling-inl.h
        return summed / _prod(kernel)
    raise MXNetError("unknown pool_type %r" % pool_type)


# ---------------------------------------------------------------------------
# Activation / LeakyReLU — src/operator/activation-inl.h, leaky_relu-inl.h
# ---------------------------------------------------------------------------

@register("Activation")
def activation(data, act_type="relu"):
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
    if act_type == "silu":
        return jax.nn.silu(data)
    raise MXNetError("unknown act_type %r" % act_type)


def _lrelu_inputs(attrs):
    if str(attrs.get("act_type", "leaky")) == "prelu":
        return ("data", "gamma")
    return ("data",)


def _lrelu_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    if str(attrs.get("act_type", "leaky")) == "prelu":
        return [tuple(data), (data[1],)], [tuple(data)], []
    return [tuple(data)], [tuple(data)], []


@register("LeakyReLU", input_names=_lrelu_inputs, infer_shape=_lrelu_infer,
          needs_is_train=True, needs_rng=True)
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334, is_train=False, rng=None):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        if is_train:
            s = jax.random.uniform(rng, data.shape, dtype=data.dtype,
                                   minval=lower_bound, maxval=upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise MXNetError("unknown act_type %r" % act_type)


# ---------------------------------------------------------------------------
# BatchNorm — src/operator/batch_norm-inl.h (aux: moving_mean, moving_var)
# ---------------------------------------------------------------------------

def _bn_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None, None, None], [None, None]
    c = (data[1],)
    return [tuple(data), c, c], [tuple(data), c, c], [c, c]


@register("BatchNorm", input_names=("data", "gamma", "beta"),
          aux_names=("moving_mean", "moving_var"),
          num_outputs=lambda attrs: 3 if attrs.get("output_mean_var", False) else 1,
          output_names=lambda attrs: (("output", "mean", "var")
                                      if attrs.get("output_mean_var", False)
                                      else ("output",)),
          infer_shape=_bn_infer, needs_is_train=True)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=0.001,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, is_train=False):
    """Batch normalization over the channel axis (axis 1, NCHW).

    Train mode computes batch statistics and returns updated moving stats as
    trailing outputs (the executor writes them back to aux storage — the
    functional equivalent of the reference mutating aux_states in-place).
    """
    axes = (0,) + tuple(range(2, data.ndim))
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    if is_train and not use_global_stats:
        mean = jnp.mean(data, axis=axes)
        var = jnp.var(data, axis=axes)
        new_moving_mean = moving_mean * momentum + mean * (1 - momentum)
        new_moving_var = moving_var * momentum + var * (1 - momentum)
    else:
        mean, var = moving_mean, moving_var
        new_moving_mean, new_moving_var = moving_mean, moving_var
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape)) * inv.reshape(bshape) * \
        gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, mean, lax.stop_gradient(inv), new_moving_mean, new_moving_var
    return out, new_moving_mean, new_moving_var


# ---------------------------------------------------------------------------
# InstanceNorm / L2Normalization — src/operator/instance_norm-inl.h,
# l2_normalization-inl.h
# ---------------------------------------------------------------------------

def _in_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    c = (data[1],)
    return [tuple(data), c, c], [tuple(data)], []


@register("InstanceNorm", input_names=("data", "gamma", "beta"),
          infer_shape=_in_infer)
def instance_norm(data, gamma, beta, eps=0.001):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + \
        beta.reshape(bshape)


@register("L2Normalization")
def l2_normalization(data, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, data.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, data.ndim))
    else:
        raise MXNetError("unknown mode %r" % mode)
    norm = jnp.sqrt(jnp.sum(jnp.square(data), axis=axes, keepdims=True) + eps)
    return data / norm


# ---------------------------------------------------------------------------
# LRN — src/operator/lrn-inl.h
# ---------------------------------------------------------------------------

@register("LRN", num_outputs=1)
def lrn(data, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    windows = sum(
        lax.slice_in_dim(padded, i, i + data.shape[1], axis=1)
        for i in range(nsize))
    norm = jnp.power(knorm + (alpha / nsize) * windows, -beta)
    return data * norm


# ---------------------------------------------------------------------------
# Layers of today's language models (no 2017 counterpart): RMSNorm, the
# gated feed-forward's SwiGLU, partial rotary embedding, the causal short
# convolution of linear-attention and latent mixers (depthwise or grouped),
# heads normalised to a fixed length
# ---------------------------------------------------------------------------

def _rmsnorm_inputs(attrs):
    return ("data", "gamma", "gate") if attrs.get("gated", False) \
        else ("data", "gamma")


def _rmsnorm_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    shapes = [tuple(data), (data[-1],)]
    if attrs.get("gated", False):
        shapes.append(tuple(data))
    return shapes, [tuple(data)], []


def rms_math(data, gamma, gate=None, eps=1e-6, zero_centered=False,
             gate_act="silu"):
    """``RMSNorm``'s arithmetic, inline: float32, the result in the data's
    dtype."""
    x = data.astype(jnp.float32)
    w = gamma.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + float(eps))
    y = y * (1.0 + w if zero_centered else w)
    if gate is not None:
        act = jax.nn.silu if gate_act == "silu" else jax.nn.sigmoid
        y = y * act(gate.astype(jnp.float32))
    return y.astype(data.dtype)


@register("RMSNorm", input_names=_rmsnorm_inputs, infer_shape=_rmsnorm_infer)
def rms_norm(data, gamma, gate=None, eps=1e-6, zero_centered=False,
             gated=False, gate_act="silu", rotary_dim=0, rope_base=10000.0):
    """Root-mean-square norm over the last axis, computed in float32:
    ``x * rsqrt(mean(x^2) + eps) * w``, with ``w = 1 + gamma`` when
    ``zero_centered`` (gamma initialised 0) and ``w = gamma`` otherwise.
    With ``gated`` the result is multiplied by ``gate_act(gate)``, ``silu``
    or ``sigmoid`` (the output norm of a gated linear-attention mixer).
    With ``rotary_dim`` (data of rank 4, (batch, positions, heads, width))
    the result in the data's dtype is then turned by
    ``RotaryEmbedding(rotary_dim=rotary_dim, base=rope_base)``: a query's or
    a key's norm and the rotary embedding after it in one node.

    Data of rank 4 goes through
    :func:`mxnet_tpu.kernels.head_norm.head_norm` (in a program lowered for
    a TPU the compiled kernels, for widths of 128, 256 or 512 and positions
    in multiples of 16; the lax tier on other platforms, for other operands
    and under a mesh the partitioner splits — the same arithmetic either
    way); other ranks are the inline arithmetic of :func:`rms_math`."""
    if gate_act not in ("silu", "sigmoid"):
        raise MXNetError("RMSNorm: gate_act %r is neither silu nor sigmoid"
                         % (gate_act,))
    if data.ndim == 4:
        from ..kernels.head_norm import head_norm
        return head_norm(data, gamma, gate, eps, zero_centered, gate_act,
                         int(rotary_dim), float(rope_base))
    if int(rotary_dim):
        raise MXNetError("RMSNorm: rotary_dim needs data of rank 4, not %d"
                         % data.ndim)
    return rms_math(data, gamma, gate, eps, zero_centered, gate_act)


@register("_contrib_SwiGLU", aliases=("SwiGLU",))
def swiglu(data):
    """``silu(a) * b`` for ``data = [a, b]`` along the last axis: the gate
    and up halves of a gated feed-forward's first projection."""
    a, b = jnp.split(data, 2, axis=-1)
    return jax.nn.silu(a) * b


@register("_contrib_RotaryEmbedding", aliases=("RotaryEmbedding",))
def rotary_embedding(data, rotary_dim=0, base=10000.0):
    """Rotary position embedding, rotate-half form, on the first
    ``rotary_dim`` features of every head (all of them when 0); the rest
    pass through.  data (batch, positions, heads, head_dim); position p
    turns the pair (x[i], x[i + rotary_dim/2]) by p * base^(-2i/rotary_dim).
    Angles are float32.

    For heads that are not normed (ZAYA1's).  A turn that follows a heads'
    ``RMSNorm`` belongs in that norm's ``rotary_dim`` / ``rope_base``: the
    same result, and on a TPU one pass of the compiled heads' norm where
    this node is a pass of its own."""
    d = int(rotary_dim) or data.shape[-1]
    half = d // 2
    inv_freq = float(base) ** (-jnp.arange(half, dtype=jnp.float32)
                               * 2.0 / d)
    angle = jnp.arange(data.shape[1], dtype=jnp.float32)[:, None] \
        * inv_freq[None, :]                             # (T, half)
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1 = data[..., :half].astype(jnp.float32)
    x2 = data[..., half:d].astype(jnp.float32)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                             axis=-1).astype(data.dtype)
    return jnp.concatenate([turned, data[..., d:]], axis=-1)


def _causal_conv_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    k, groups = int(attrs["kernel"]), int(attrs.get("num_group", 0))
    weight = (data[-1], data[-1] // groups, k) if groups else (data[-1], k)
    return [tuple(data), weight], [tuple(data)], []


@register("_contrib_CausalConv1D", aliases=("CausalConv1D",),
          input_names=("data", "weight"), infer_shape=_causal_conv_infer)
def causal_conv1d(data, weight, kernel=4, act_type=None, num_group=0):
    """Causal convolution along positions, no bias: data (batch,
    positions, channels).  Depthwise (``num_group`` 0, the default):
    weight (channels, kernel),
    ``y[t] = sum_j weight[:, j] * x[t - (kernel-1) + j]`` with zeros before
    a row's start; the taps are summed in float32
    (:func:`mxnet_tpu.kernels.causal_conv.causal_conv`: in a program lowered
    for a TPU the compiled kernels, for channels in multiples of 128,
    positions in multiples of 32, 2 to 8 taps, ``act_type`` None or
    ``silu`` and bfloat16 or float32 data; the lax tier on other platforms,
    for other operands and under a mesh the partitioner splits — the same
    arithmetic either way).  Grouped (``num_group``
    g > 0): weight (channels, channels / g, kernel), output channel ``o``
    mixing the channels of its own group over the taps,
    ``y[t][o] = sum_j sum_{i in group(o)} weight[o, i, j] * x[t - (kernel-1)
    + j][i]`` — ``lax.conv_general_dilated`` with ``feature_group_count``
    on operands of the data's dtype.  ``act_type`` as in ``Activation``,
    taken in float32."""
    k, g = int(kernel), int(num_group)
    if not g:
        from ..kernels.causal_conv import causal_conv
        return causal_conv(data, weight, k, act_type)
    y = lax.conv_general_dilated(
        data, weight.astype(data.dtype), window_strides=(1,),
        padding=[(k - 1, 0)], dimension_numbers=("NWC", "OIW", "NWC"),
        feature_group_count=g)
    if act_type:
        y = activation(y.astype(jnp.float32), act_type)
    return y.astype(data.dtype)


def _head_l2_inputs(attrs):
    return ("data", "log_scale") if attrs.get("scaled", False) \
        else ("data",)


def _head_l2_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    shapes = [tuple(data)]
    if attrs.get("scaled", False):
        shapes.append((data[-2],))
    return shapes, [tuple(data)], []


@register("_contrib_HeadL2Norm", aliases=("HeadL2Norm",),
          input_names=_head_l2_inputs, infer_shape=_head_l2_infer)
def head_l2_norm(data, log_scale=None, eps=1e-6, scaled=False):
    """Every head brought to the length ``sqrt(head_dim)``, in float32:
    data (batch, positions, heads, head_dim) ->
    ``sqrt(head_dim) * x * rsqrt(sum(x^2) + eps)``; with ``scaled`` and
    ``log_scale`` (heads,) each head is then multiplied by
    ``exp(log_scale[head])`` (a learned temperature on normalised keys)."""
    x = data.astype(jnp.float32)
    y = x * (lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + float(eps))
             * float(data.shape[-1]) ** 0.5)
    if log_scale is not None:
        y = y * jnp.exp(log_scale.astype(jnp.float32))[:, None]
    return y.astype(data.dtype)


# ---------------------------------------------------------------------------
# Dropout — src/operator/dropout-inl.h
# ---------------------------------------------------------------------------

@register("Dropout", needs_is_train=True, needs_rng=True,
          num_outputs=1)
def dropout(data, p=0.5, is_train=False, rng=None, mode=None):
    if not is_train or p <= 0:
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, jnp.zeros_like(data))


# ---------------------------------------------------------------------------
# Embedding — src/operator/tensor/indexing_op.h (EmbeddingOp)
# ---------------------------------------------------------------------------

def _embed_infer(attrs, in_shapes):
    input_dim = int(attrs["input_dim"])
    output_dim = int(attrs["output_dim"])
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    return [tuple(data), (input_dim, output_dim)], [tuple(data) + (output_dim,)], []


@register("Embedding", input_names=("data", "weight"), infer_shape=_embed_infer)
def embedding(data, weight, input_dim=0, output_dim=0, dtype="float32"):
    return jnp.take(weight, data.astype(jnp.int32), axis=0)


# ---------------------------------------------------------------------------
# Concat / SliceChannel — src/operator/concat-inl.h, slice_channel-inl.h
# ---------------------------------------------------------------------------

def _concat_inputs(attrs):
    n = int(attrs.get("num_args", 1))
    return tuple("arg%d" % i for i in range(n))


def _concat_infer(attrs, in_shapes):
    dim = int(attrs.get("dim", 1))
    known = [s for s in in_shapes if s is not None]
    if not known or any(s is None for s in in_shapes):
        return in_shapes, [None], []
    out = list(known[0])
    out[dim] = sum(s[dim] for s in in_shapes)
    return [tuple(s) for s in in_shapes], [tuple(out)], []


@register("Concat", input_names=_concat_inputs, variable_inputs=True,
          infer_shape=_concat_infer, aliases=("concat",))
def concat(*args, num_args=1, dim=1):
    return jnp.concatenate(args, axis=dim)


def _slice_channel_infer(attrs, in_shapes):
    n = int(attrs.get("num_outputs", 1))
    axis = int(attrs.get("axis", 1))
    squeeze = attrs.get("squeeze_axis", False)
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None] * n, []
    out = list(data)
    out[axis] //= n
    if squeeze and out[axis] == 1:
        out.pop(axis)
    return [tuple(data)], [tuple(out)] * n, []


@register("SliceChannel", aliases=("split",),
          num_outputs=lambda attrs: int(attrs.get("num_outputs", 1)),
          infer_shape=_slice_channel_infer)
def slice_channel(data, num_outputs=1, axis=1, squeeze_axis=False):
    parts = jnp.split(data, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts)


# ---------------------------------------------------------------------------
# Pad / Crop / UpSampling — src/operator/pad.cc, crop.cc, upsampling.cc
# ---------------------------------------------------------------------------

@register("Pad", aliases=("pad",))
def pad_op(data, pad_width=(), mode="constant", constant_value=0.0):
    pw = _tuple(pad_width)
    pads = [(pw[2 * i], pw[2 * i + 1]) for i in range(data.ndim)]
    if mode == "constant":
        return jnp.pad(data, pads, constant_values=constant_value)
    if mode == "edge":
        return jnp.pad(data, pads, mode="edge")
    if mode == "reflect":
        return jnp.pad(data, pads, mode="reflect")
    raise MXNetError("unknown pad mode %r" % mode)


def _crop_inputs(attrs):
    n = int(attrs.get("num_args", 1))
    return ("data",) if n == 1 else ("data", "crop_like")


def _crop_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    if int(attrs.get("num_args", 1)) == 2 and in_shapes[1] is not None:
        hw = in_shapes[1][2:]
    else:
        hw = _tuple(attrs.get("h_w", ()))
    out = tuple(data[:2]) + tuple(hw)
    return [tuple(s) if s else s for s in in_shapes], [out], []


@register("Crop", input_names=_crop_inputs, infer_shape=_crop_infer)
def crop(data, crop_like=None, num_args=1, offset=(0, 0), h_w=(0, 0),
         center_crop=False):
    if crop_like is not None:
        h, w = crop_like.shape[2], crop_like.shape[3]
    else:
        h, w = _tuple(h_w, 2)
    if center_crop:
        oy = (data.shape[2] - h) // 2
        ox = (data.shape[3] - w) // 2
    else:
        oy, ox = _tuple(offset, 2)
    return lax.dynamic_slice(data, (0, 0, oy, ox),
                             (data.shape[0], data.shape[1], h, w))


def _upsample_bilinear_filter(scale):
    k = 2 * scale - scale % 2
    center = (2 * scale - 1 - scale % 2) / (2.0 * scale)
    og = np.arange(k)
    f = (1 - np.abs(og / scale - center))
    return (f[:, None] * f[None, :]).astype(np.float32)


def _upsample_infer(attrs, in_shapes):
    scale = int(attrs.get("scale", 1))
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    out = (data[0], data[1], data[2] * scale, data[3] * scale)
    shapes = [tuple(s) if s else s for s in in_shapes]
    if str(attrs.get("sample_type", "nearest")) == "bilinear":
        k = 2 * scale - scale % 2
        nf = int(attrs.get("num_filter", data[1]) or data[1])
        shapes = [tuple(data), (nf, 1, k, k)]
        out = (data[0], nf, data[2] * scale, data[3] * scale)
    return shapes, [out], []


def _upsample_inputs(attrs):
    if str(attrs.get("sample_type", "nearest")) == "bilinear":
        return ("data", "weight")
    return _concat_inputs(attrs)


@register("UpSampling", variable_inputs=True, input_names=_upsample_inputs,
          infer_shape=_upsample_infer)
def upsampling(*args, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    """Nearest: integer repeat.  Bilinear: grouped transposed conv with the
    (learnable) weight input, kernel 2*scale-scale%2, stride scale — exactly
    the reference's UpSamplingBilinear (src/operator/upsampling-inl.h)."""
    if sample_type == "bilinear":
        data, weight = args[0], args[1]
        k = 2 * scale - scale % 2
        p = int(np.ceil((scale - 1) / 2.0))
        nf = num_filter or data.shape[1]
        # deconv weight layout is (C_in, nf/group, k, k); group == C
        w = jnp.reshape(weight, (data.shape[1], 1, k, k))
        return deconvolution(data, w, None, kernel=(k, k),
                             stride=(scale, scale), pad=(p, p),
                             num_filter=nf, num_group=data.shape[1],
                             no_bias=True)
    outs = []
    for data in args:
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        outs.append(out)
    if len(outs) == 1:
        return outs[0]
    if multi_input_mode == "sum":
        return sum(outs)
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# Loss layers with custom backward — softmax_output-inl.h,
# regression_output-inl.h, make_loss-inl.h, svm_output-inl.h
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _softmax_output(data, label, grad_scale, ignore_label, use_ignore,
                    multi_output, normalization, out_grad):
    return _softmax_fwd_only(data, multi_output)


def _softmax_fwd_only(data, multi_output):
    if multi_output and data.ndim > 2:
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization, out_grad):
    out = _softmax_fwd_only(data, multi_output)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, multi_output,
                        normalization, out_grad, res, g):
    out, label = res
    axis = 1 if (multi_output and out.ndim > 2) else out.ndim - 1
    if label.shape == out.shape:
        grad = out - label
        valid = jnp.asarray(out.shape[0], out.dtype)
    else:
        idx = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(idx, out.shape[axis], axis=axis, dtype=out.dtype)
        grad = out - onehot
        if use_ignore:
            mask = (idx != int(ignore_label)).astype(out.dtype)
            grad = grad * jnp.expand_dims(mask, axis)
            valid = jnp.maximum(jnp.sum(mask), 1.0)
        else:
            valid = jnp.asarray(float(np.prod(label.shape)), out.dtype)
    scale = grad_scale
    if normalization == "batch":
        grad = grad * (scale / out.shape[0])
    elif normalization == "valid":
        grad = grad * scale / valid
    else:
        grad = grad * scale
    if out_grad:
        # reference softmax_output-inl.h:127-129,220-224: with out_grad=True
        # the label-based gradient is modulated elementwise by the incoming
        # head gradient (policy-gradient / custom-loss escape hatch)
        grad = grad * g
    return grad, jnp.zeros_like(label)


_softmax_output.defvjp(_softmax_output_fwd, _softmax_output_bwd)


def _loss_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    label = in_shapes[1] if len(in_shapes) > 1 and in_shapes[1] is not None \
        else (data[0],)
    return [tuple(data), tuple(label)], [tuple(data)], []


def _softmax_label_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    if attrs.get("multi_output", False) and len(data) > 2:
        label = (data[0],) + tuple(data[2:])
    else:
        label = tuple(data[:-1])
    if len(in_shapes) > 1 and in_shapes[1] is not None:
        label = tuple(in_shapes[1])
    return [tuple(data), label], [tuple(data)], []


@register("SoftmaxOutput", input_names=("data", "label"),
          infer_shape=_softmax_label_infer, aliases=("Softmax_",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   multi_output=False, use_ignore=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Softmax forward; backward = (p - onehot(label)) * grad_scale, ignoring
    incoming head gradient — reference src/operator/softmax_output-inl.h."""
    return _softmax_output(data, label, float(grad_scale), float(ignore_label),
                           bool(use_ignore), bool(multi_output),
                           str(normalization), bool(out_grad))


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _make_regression(name, fwd_fn, bwd_fn):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def _core(data, label, grad_scale):
        return fwd_fn(data)

    def _fwd(data, label, grad_scale):
        out = fwd_fn(data)
        return out, (out, label)

    def _bwd(grad_scale, res, g):
        out, label = res
        num_output = _prod(label.shape[1:]) if label.ndim > 1 else 1
        grad = (grad_scale / num_output) * bwd_fn(out, label.reshape(out.shape))
        return grad, jnp.zeros_like(label)

    _core.defvjp(_fwd, _bwd)

    @register(name, input_names=("data", "label"), infer_shape=_loss_infer)
    def _op(data, label, grad_scale=1.0):
        return _core(data, label, float(grad_scale))
    _op.__name__ = name
    return _op


_make_regression("LinearRegressionOutput", lambda d: d, lambda o, l: o - l)
_make_regression("LogisticRegressionOutput", jax.nn.sigmoid, lambda o, l: o - l)
_make_regression("MAERegressionOutput", lambda d: d, lambda o, l: jnp.sign(o - l))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_output(data, label, margin, regularization_coefficient, use_linear):
    return data


def _svm_fwd(data, label, margin, regularization_coefficient, use_linear):
    return data, (data, label)


def _svm_bwd(margin, reg, use_linear, res, g):
    """One-vs-all hinge exactly as the reference kernels (svm_output.cc
    L1_SVM/L2_SVM): the true class's score is pushed above +margin, every
    other class's score below -margin; incoming head gradient is ignored
    (loss-layer convention)."""
    data, label = res
    idx = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(idx, data.shape[1], dtype=data.dtype)
    if use_linear:
        g_true = jnp.where(data < margin, -reg, 0.0)
        g_other = jnp.where(data > -margin, reg, 0.0)
    else:
        g_true = jnp.where(data < margin, -2.0 * reg * (margin - data), 0.0)
        g_other = jnp.where(data > -margin, 2.0 * reg * (margin + data), 0.0)
    grad = onehot * g_true + (1 - onehot) * g_other
    return grad.astype(data.dtype), jnp.zeros_like(label)


_svm_output.defvjp(_svm_fwd, _svm_bwd)


@register("SVMOutput", input_names=("data", "label"), infer_shape=_loss_infer)
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    return _svm_output(data, label, float(margin),
                       float(regularization_coefficient), bool(use_linear))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _make_loss_core(data, grad_scale, normalization):
    return data


def _make_loss_fwd(data, grad_scale, normalization):
    return data, data.shape


def _make_loss_bwd(grad_scale, normalization, shape, g):
    scale = grad_scale
    if normalization == "batch":
        scale = scale / shape[0]
    elif normalization == "valid":
        scale = scale / _prod(shape)
    return (jnp.full(shape, scale, dtype=g.dtype),)


_make_loss_core.defvjp(_make_loss_fwd, _make_loss_bwd)


@register("MakeLoss", aliases=("make_loss",))
def make_loss(data, grad_scale=1.0, valid_thresh=0.0, normalization="null"):
    """Forward identity; backward emits grad_scale (reference
    src/operator/make_loss-inl.h:92-98)."""
    return _make_loss_core(data, float(grad_scale), str(normalization))


@register("IdentityAttachKLSparseReg")
def identity_attach_kl_sparse_reg(data, sparseness_target=0.1, penalty=0.001,
                                  momentum=0.9):
    return data  # regularization gradient omitted (matches fwd semantics)


# ---------------------------------------------------------------------------
# Sequence ops — src/operator/sequence_{last,mask,reverse}-inl.h
# layouts: data is (seq_len, batch, ...) like the reference
# ---------------------------------------------------------------------------

def _seq_inputs(attrs):
    if attrs.get("use_sequence_length", False):
        return ("data", "sequence_length")
    return ("data",)


def _seq_last_infer(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None], []
    return [tuple(s) if s else s for s in in_shapes], [tuple(data[1:])], []


@register("SequenceLast", input_names=_seq_inputs, infer_shape=_seq_last_infer)
def sequence_last(data, sequence_length=None, use_sequence_length=False):
    if not use_sequence_length or sequence_length is None:
        return data[-1]
    idx = (sequence_length.astype(jnp.int32) - 1)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=0)[0]


@register("SequenceMask", input_names=_seq_inputs)
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0):
    if not use_sequence_length or sequence_length is None:
        return data
    T = data.shape[0]
    steps = jnp.arange(T).reshape((T,) + (1,) * (data.ndim - 1))
    lens = sequence_length.reshape((1, -1) + (1,) * (data.ndim - 2))
    return jnp.where(steps < lens, data, jnp.asarray(value, data.dtype))


@register("SequenceReverse", input_names=_seq_inputs)
def sequence_reverse(data, sequence_length=None, use_sequence_length=False):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=0)
    T = data.shape[0]
    steps = jnp.arange(T)[:, None]
    lens = sequence_length.astype(jnp.int32)[None, :]
    rev_idx = jnp.where(steps < lens, lens - 1 - steps, steps)
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ---------------------------------------------------------------------------
# Fused RNN — src/operator/rnn-inl.h / cudnn_rnn-inl.h.
# TPU-native: lax.scan over time with gates batched into single MXU matmuls.
# Weight layout matches the reference's fused vector format so
# rnn_cell pack/unpack round-trips (python/mxnet/rnn/rnn_cell.py:541-607):
# per layer, per direction: all i2h weights (gates stacked), all h2h weights,
# then per layer/direction all i2h biases, all h2h biases.
# Gate order: LSTM [i, f, c, o]; GRU [r, z, n].
# ---------------------------------------------------------------------------

_RNN_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(num_layers, input_size, state_size, bidirectional, mode):
    gates = _RNN_GATES[mode]
    dirs = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        size += dirs * gates * state_size * (in_sz + state_size + 2)
    return size


def _rnn_split_params(params, num_layers, input_size, state_size,
                      bidirectional, mode):
    """Split the fused 1-D parameter vector into per-layer weight matrices."""
    gates = _RNN_GATES[mode]
    dirs = 2 if bidirectional else 1
    ws, bs = [], []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * dirs
        layer_w = []
        for d in range(dirs):
            n_i2h = gates * state_size * in_sz
            w_i2h = params[off:off + n_i2h].reshape(gates * state_size, in_sz)
            off += n_i2h
            n_h2h = gates * state_size * state_size
            w_h2h = params[off:off + n_h2h].reshape(gates * state_size, state_size)
            off += n_h2h
            layer_w.append((w_i2h, w_h2h))
        ws.append(layer_w)
    for layer in range(num_layers):
        layer_b = []
        for d in range(dirs):
            b_i2h = params[off:off + gates * state_size]
            off += gates * state_size
            b_h2h = params[off:off + gates * state_size]
            off += gates * state_size
            layer_b.append((b_i2h, b_h2h))
        bs.append(layer_b)
    return ws, bs


def _rnn_cell_step(mode, state_size):
    # MXTPU_FUSED_KERNELS routing is resolved ONCE per trace (this
    # factory runs at trace time): the fused cell does all gate math in
    # one kernel pass (mxnet_tpu/kernels/lstm_cell.py — Pallas on TPU,
    # fused-lax elsewhere, bit-identical op order either way)
    fused_lstm = None
    if mode == "lstm":
        from ..kernels import fused_enabled
        if fused_enabled("lstm_cell"):
            from ..kernels.lstm_cell import lstm_cell as fused_lstm

    def step(carry, x_proj, w_h2h, b_h2h):
        if mode == "lstm":
            h, c = carry
            gates = x_proj + jnp.dot(h, w_h2h.T) + b_h2h
            if fused_lstm is not None:
                h, c = fused_lstm(gates, c)
                return (h, c), h
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            return (h, c), h
        if mode == "gru":
            h = carry[0]
            hp = jnp.dot(h, w_h2h.T) + b_h2h
            xr, xz, xn = jnp.split(x_proj, 3, axis=-1)
            hr, hz, hn = jnp.split(hp, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h = (1 - z) * n + z * h
            return (h,), h
        h = carry[0]
        pre = x_proj + jnp.dot(h, w_h2h.T) + b_h2h
        h = jax.nn.relu(pre) if mode == "rnn_relu" else jnp.tanh(pre)
        return (h,), h
    return step


def _rnn_inputs(attrs):
    mode = str(attrs.get("mode", "lstm"))
    if mode == "lstm":
        return ("data", "parameters", "state", "state_cell")
    return ("data", "parameters", "state")


def _rnn_num_outputs(attrs):
    if not attrs.get("state_outputs", False):
        return 1
    return 3 if str(attrs.get("mode", "lstm")) == "lstm" else 2


def _rnn_infer(attrs, in_shapes):
    mode = str(attrs.get("mode", "lstm"))
    num_layers = int(attrs.get("num_layers", 1))
    state_size = int(attrs.get("state_size"))
    bi = attrs.get("bidirectional", False)
    dirs = 2 if bi else 1
    data = in_shapes[0]
    if data is None:
        return in_shapes, [None] * _rnn_num_outputs(attrs), []
    T, N, I = data
    psize = rnn_param_size(num_layers, I, state_size, bi, mode)
    sshape = (num_layers * dirs, N, state_size)
    shapes = [tuple(data), (psize,), sshape]
    if mode == "lstm":
        shapes.append(sshape)
    outs = [(T, N, state_size * dirs)]
    if attrs.get("state_outputs", False):
        outs.append(sshape)
        if mode == "lstm":
            outs.append(sshape)
    return shapes, outs, []


@register("RNN", input_names=_rnn_inputs, num_outputs=_rnn_num_outputs,
          infer_shape=_rnn_infer, needs_is_train=True, needs_rng=True)
def rnn(data, parameters, state, state_cell=None, state_size=0, num_layers=1,
        bidirectional=False, mode="lstm", p=0.0, state_outputs=False,
        lstm_state_clip_min=None, lstm_state_clip_max=None, is_train=False,
        rng=None):
    """Fused multi-layer RNN (reference src/operator/cudnn_rnn-inl.h).

    lax.scan over time; all gate projections for a timestep are one MXU
    matmul.  The input projection for the whole sequence is hoisted out of
    the scan (a single (T*N, I) x (I, G*H) matmul) — the TPU-idiomatic
    version of cuDNN's fused RNN.
    """
    T, N, _ = data.shape
    dirs = 2 if bidirectional else 1
    gates = _RNN_GATES[mode]
    ws, bs = _rnn_split_params(parameters, num_layers, data.shape[2],
                               state_size, bidirectional, mode)
    step = _rnn_cell_step(mode, state_size)

    h0 = state.reshape(num_layers, dirs, N, state_size)
    c0 = state_cell.reshape(num_layers, dirs, N, state_size) \
        if state_cell is not None else None

    layer_in = data
    h_finals, c_finals = [], []
    for layer in range(num_layers):
        outs_dir = []
        for d in range(dirs):
            w_i2h, w_h2h = ws[layer][d]
            b_i2h, b_h2h = bs[layer][d]
            seq = layer_in if d == 0 else jnp.flip(layer_in, axis=0)
            x_proj = jnp.einsum("tni,gi->tng", seq, w_i2h) + b_i2h
            if mode == "lstm":
                carry0 = (h0[layer, d], c0[layer, d])
            else:
                carry0 = (h0[layer, d],)

            def scan_fn(carry, xp, _w=w_h2h, _b=b_h2h):
                return step(carry, xp, _w, _b)

            carry, hs = lax.scan(scan_fn, carry0, x_proj)
            if d == 1:
                hs = jnp.flip(hs, axis=0)
            outs_dir.append(hs)
            h_finals.append(carry[0])
            if mode == "lstm":
                c_finals.append(carry[1])
        layer_in = outs_dir[0] if dirs == 1 else jnp.concatenate(outs_dir, axis=-1)
        if is_train and p > 0 and layer < num_layers - 1:
            keep = 1.0 - p
            mask = jax.random.bernoulli(jax.random.fold_in(rng, layer), keep,
                                        layer_in.shape)
            layer_in = jnp.where(mask, layer_in / keep, 0.0)

    if not state_outputs:
        return layer_in
    h_out = jnp.stack(h_finals).reshape(num_layers * dirs, N, state_size)
    if mode == "lstm":
        c_out = jnp.stack(c_finals).reshape(num_layers * dirs, N, state_size)
        return layer_in, h_out, c_out
    return layer_in, h_out
