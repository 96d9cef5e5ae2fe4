"""pool_act — pooling/activation fusion + a faster pooling lowering.

Three rewrites (:func:`mxnet_tpu.mxfuse.pass_pool_act`):

- **act → max-pool reorder** (:func:`make_act_then_maxpool`): every
  registered activation type is monotone non-decreasing, so it commutes
  with max-pooling BITWISE — ``f(max(a, b)) == max(f(a), f(b))`` (the
  pooled maximum is one of the window values and a non-decreasing f
  keeps the argmax; ties pick equal values either way).  Pooling first
  shrinks the tensor the activation touches by the pool stride squared.
  Restricted to the ``valid`` pooling convention: ``full`` (ceil)
  windows can in principle cover only -inf padding, where the commute
  breaks.
- **pool → act collapse** (:func:`make_pool_then_act`): the identical
  composition emitted as ONE plan entry — one dispatch instead of two
  on the eager/no-jit paths.
- **shifted-slice pooling** (:func:`pooling_opt`, applied by every
  override here and to standalone Pooling entries): XLA CPU's
  ``reduce_window`` iterates windows scalar-ily (~2 GFLOP/s measured);
  the same pooling as k² strided slices combined by ``maximum``/``add``
  vectorizes (2.2-3.2x at inception shapes).  Gated to small spatial
  extents (big maps favor ``reduce_window`` — measured), to 2-D
  non-global ``valid`` windows, and for max pooling to the INFERENCE
  path only: the slice lowering's max backward breaks ties on a
  different window element than ``reduce_window``'s select-and-scatter
  (both valid subgradients, but training parity pins would see it).
  Avg/sum stay on for training — the backward is linear, so only
  addition order differs (the documented reassociation tolerance,
  ~1e-7).
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax

__all__ = ["make_act_then_maxpool", "make_pool_then_act",
           "make_pool_opt", "pooling_opt", "POOL_SLICE_MAX_SPATIAL"]

#: input spatial extent (H*W) above which the slice lowering loses to
#: reduce_window (measured on a CPU host, not on the chip: 48² wins
#: 2.5x, 112² loses) — bigger maps fall back
POOL_SLICE_MAX_SPATIAL = 3200


def _slice_pool(data, kernel, stride, pad, op, init):
    n, c, h, w = data.shape
    kh, kw = kernel
    sh, sw = stride
    ph, pw = pad
    xp = jnp.pad(data, ((0, 0), (0, 0), (ph, ph), (pw, pw)),
                 constant_values=init)
    hp, wp = h + 2 * ph, w + 2 * pw
    ho, wo = (hp - kh) // sh + 1, (wp - kw) // sw + 1
    out = None
    for di in range(kh):
        for dj in range(kw):
            v = lax.slice(xp, (0, 0, di, dj),
                          (n, c, di + (ho - 1) * sh + 1,
                           dj + (wo - 1) * sw + 1),
                          (1, 1, sh, sw))
            out = v if out is None else op(out, v)
    return out


def pooling_opt(data, pool_attrs, is_train=False):
    """The routed pooling lowering: the shifted-slice form when
    eligible (see module docstring), the registered ``Pooling`` op
    otherwise.  Decided at trace time from concrete shapes."""
    from ..ops.nn import pooling
    attrs = dict(pool_attrs)
    kernel = attrs.get("kernel") or ()
    stride = attrs.get("stride") or (1,) * len(kernel)
    pad = attrs.get("pad") or (0,) * len(kernel)
    pool_type = str(attrs.get("pool_type", "max"))
    eligible = (
        data.ndim == 4 and len(kernel) == 2
        and not attrs.get("global_pool", False)
        and str(attrs.get("pooling_convention", "valid")) == "valid"
        and pool_type in ("max", "avg", "sum")
        and int(data.shape[2]) * int(data.shape[3])
        <= POOL_SLICE_MAX_SPATIAL
        and not (pool_type == "max" and is_train))
    if not eligible:
        return pooling(data, **pool_attrs)
    kernel = tuple(int(k) for k in kernel)
    stride = tuple(int(s) for s in (stride if len(stride) == 2
                                    else (stride,) * 2))
    pad = tuple(int(p) for p in (pad if len(pad) == 2 else (pad,) * 2))
    if pool_type == "max":
        if jnp.issubdtype(data.dtype, jnp.floating):
            init = -np.inf
        else:
            init = np.iinfo(data.dtype).min
        return _slice_pool(data, kernel, stride, pad, jnp.maximum, init)
    out = _slice_pool(data, kernel, stride, pad, jnp.add, 0)
    if pool_type == "avg":
        out = out / float(kernel[0] * kernel[1])
    return out


def make_act_then_maxpool(act_type):
    """Override body for the Pooling node of an act→max-pool pair: pool
    the PRE-activation input (the act entry is a passthrough), then
    activate the pooled tensor.  Bitwise-equal to act-then-pool."""
    def fused(data, is_train=False, **pool_attrs):
        from ..ops.nn import activation
        return activation(pooling_opt(data, pool_attrs, is_train),
                          act_type=act_type)
    return fused


def make_pool_then_act(pool_attrs):
    """Override body for the Activation node of a pool→act pair: the
    pool entry is a passthrough; this entry runs the original
    pool-then-activate composition in one call."""
    def fused(data, is_train=False, **act_attrs):
        from ..ops.nn import activation
        return activation(pooling_opt(data, pool_attrs, is_train),
                          **act_attrs)
    return fused


def make_pool_opt():
    """Override body for a standalone Pooling entry: same math, the
    routed (possibly shifted-slice) lowering."""
    def fused(data, is_train=False, **pool_attrs):
        return pooling_opt(data, pool_attrs, is_train)
    return fused
