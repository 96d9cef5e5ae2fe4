"""Fused BatchNorm + activation, and BN-into-conv folding.

inception-bn spends its non-matmul time in dozens of BatchNorm ->
Activation pairs: at dispatch granularity that is five memory passes per
pair (normalize read+write, activate read+write, plus the stats pass).
Two fusions close the gap:

- **Training** (:func:`fused_bn_act`): normalize + scale/shift +
  activate in ONE pass over the data.  The batch statistics stay lax
  reductions (XLA's reduction codegen is already roofline-bound); the
  elementwise pass — the memory-bound part fusion actually buys — is the
  kernel.  The fused-lax reference literally composes the registered
  ``BatchNorm``/``Activation`` lowerings in one traced function, so it
  is bit-identical to the unfused graph; the Pallas tier runs the
  normalize+activate block as a ``pl.pallas_call`` pair behind
  ``jax.custom_vjp`` (backward recomputes the activation in-tile and
  accumulates per-image partial sums for the scale/shift gradients).
- **Inference** (:func:`fold_bn_into_conv`): with frozen moving stats,
  ``BN(conv(x, W) + b)`` is exactly ``conv(x, W * s) + (b - mean) * s +
  beta`` with ``s = gamma * rsqrt(var + eps)`` — the BN op vanishes from
  the graph for the price of one O(weights) rescale.  The executor's
  eval trace applies this when ``MXTPU_FUSED_KERNELS`` enables
  ``bn_fold`` (executor.py ``_fuse_bn_plan``); folding reassociates
  float math, so parity with the unfused graph is tolerance-checked,
  not bitwise (tests/test_kernels.py pins the tolerance).

The executor's BatchNorm aux-update path is preserved untouched: both
tiers return ``(out, new_moving_mean, new_moving_var)`` exactly like the
registered ``BatchNorm`` op, and the executor writes the trailing
outputs back to aux storage as before.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["fused_bn_act", "fused_bn_act_lax", "fused_bn_act_pallas",
           "fold_bn_into_conv"]


def fused_bn_act_lax(data, gamma, beta, moving_mean, moving_var,
                     act_type=None, eps=0.001, momentum=0.9,
                     fix_gamma=True, use_global_stats=False,
                     is_train=False):
    """Fused-lax reference: the registered BatchNorm lowering plus the
    registered Activation lowering in one traced function — the same
    per-element op sequence as the unfused graph (bit-identical), fused
    by XLA because it is one program."""
    from ..ops import nn as _nn
    out, new_mm, new_mv = _nn.batch_norm(
        data, gamma, beta, moving_mean, moving_var, eps=eps,
        momentum=momentum, fix_gamma=fix_gamma,
        use_global_stats=use_global_stats, output_mean_var=False,
        is_train=is_train)
    if act_type:
        out = _nn.activation(out, act_type=act_type)
    return out, new_mm, new_mv


# ---------------------------------------------------------------------------
# Pallas tier: the normalize+activate elementwise block as a kernel pair
# ---------------------------------------------------------------------------

#: activations the Pallas block supports (act' expressible from y alone);
#: anything else routes to the lax tier
_PALLAS_ACTS = ("relu", "sigmoid", "tanh")


def _apply_act(y, act_type):
    if act_type == "relu":
        return jax.nn.relu(y)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(y)
    if act_type == "tanh":
        return jnp.tanh(y)
    return y


def _act_grad_from_y(y, act_type):
    """act'(pre) expressed from the POST-activation value y."""
    if act_type == "relu":
        return (y > 0).astype(y.dtype)
    if act_type == "sigmoid":
        return y * (1.0 - y)
    if act_type == "tanh":
        return 1.0 - y * y
    return jnp.ones_like(y)


#: elements of one (C, tile) data block: 128K f32 elements = 512 KiB, so
#: the backward's three double-buffered data blocks plus its in-kernel
#: f32 temporaries stay well inside the 16 MiB scoped-VMEM default
_BLOCK_ELEMS = 128 * 1024


def _tile(C, M):
    """Lane tile of the (C, M) row: the largest multiple-of-128 divisor
    of M whose (C, tile) block fits ``_BLOCK_ELEMS``; a row that is not
    lane-aligned is one whole block (block dim == array dim is always a
    legal Mosaic block, and is what the interpret-mode tests present)."""
    if M % 128:
        return M
    lanes = M // 128
    fit = max(1, _BLOCK_ELEMS // (C * 128))
    return 128 * max(d for d in range(1, lanes + 1)
                     if lanes % d == 0 and d <= fit)


def _make_norm_act(act_type, interpret):
    """custom_vjp'd ``y = act(x * scale + shift)`` over an (N, C, M)
    array with per-channel f32 scale/shift shaped (1, C, 1).  Grid
    (N, M / tile): one (C, tile) block per program, math in f32 whatever
    the data dtype (v5e has no bf16 VPU)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def specs(x):
        _, C, M = x.shape
        t = _tile(C, M)
        row = pl.BlockSpec((None, C, t), lambda n, m: (n, 0, m),
                           memory_space=pltpu.VMEM)
        chan = pl.BlockSpec((None, C, 1), lambda n, m: (0, 0, 0),
                            memory_space=pltpu.VMEM)
        # per-image partial sums: the block index ignores m, so the
        # block stays resident and accumulates across the row's tiles
        part = pl.BlockSpec((None, C, 1), lambda n, m: (n, 0, 0),
                            memory_space=pltpu.VMEM)
        return (x.shape[0], M // t), row, chan, part

    def fwd_kernel(x_ref, s_ref, b_ref, y_ref):
        y = _apply_act(x_ref[...].astype(jnp.float32) * s_ref[...]
                       + b_ref[...], act_type)
        y_ref[...] = y.astype(y_ref.dtype)

    def bwd_kernel(x_ref, s_ref, b_ref, dy_ref, dx_ref, ds_ref, db_ref):
        # recompute y in-tile (nothing saved between passes), then the
        # pre-activation cotangent and this tile's partial reductions
        x = x_ref[...].astype(jnp.float32)
        y = _apply_act(x * s_ref[...] + b_ref[...], act_type)
        dpre = dy_ref[...].astype(jnp.float32) * _act_grad_from_y(y, act_type)
        dx_ref[...] = (dpre * s_ref[...]).astype(dx_ref.dtype)

        @pl.when(pl.program_id(1) == 0)
        def _():
            ds_ref[...] = jnp.zeros_like(ds_ref)
            db_ref[...] = jnp.zeros_like(db_ref)
        ds_ref[...] += jnp.sum(dpre * x, axis=-1, keepdims=True)
        db_ref[...] += jnp.sum(dpre, axis=-1, keepdims=True)

    def fwd_call(x, s, b):
        grid, row, chan, _ = specs(x)
        return pl.pallas_call(
            fwd_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=grid, in_specs=[row, chan, chan], out_specs=row,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            name="mxtpu_bn_act_fwd", interpret=interpret)(x, s, b)

    def bwd_call(x, s, b, dy):
        N, C, _ = x.shape
        grid, row, chan, part = specs(x)
        dx, ds_p, db_p = pl.pallas_call(
            bwd_kernel,
            out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((N, C, 1), jnp.float32),
                       jax.ShapeDtypeStruct((N, C, 1), jnp.float32)),
            grid=grid, in_specs=[row, chan, chan, row],
            out_specs=(row, part, part),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            name="mxtpu_bn_act_bwd", interpret=interpret)(x, s, b, dy)
        # fold the per-image partials in lax
        return (dx, jnp.sum(ds_p, axis=0, keepdims=True),
                jnp.sum(db_p, axis=0, keepdims=True))

    @jax.custom_vjp
    def norm_act(x, scale, shift):
        return fwd_call(x, scale, shift)

    def na_fwd(x, scale, shift):
        return fwd_call(x, scale, shift), (x, scale, shift)

    def na_bwd(res, dy):
        return bwd_call(*res, dy)

    norm_act.defvjp(na_fwd, na_bwd)
    return norm_act


_norm_act_cache = {}


def _norm_act(x3, scale3, shift3, act_type, interpret):
    key = (act_type or "", bool(interpret))
    fn = _norm_act_cache.get(key)
    if fn is None:
        fn = _norm_act_cache[key] = _make_norm_act(act_type, interpret)
    return fn(x3, scale3, shift3)


def fused_bn_act_pallas(data, gamma, beta, moving_mean, moving_var,
                        act_type=None, eps=0.001, momentum=0.9,
                        fix_gamma=True, use_global_stats=False,
                        is_train=False, interpret=False):
    """Pallas-tier fused BN(+act): lax batch statistics + one
    normalize+activate kernel pass (custom_vjp registered).  Semantics
    and return shape match the registered BatchNorm op exactly.
    ``interpret=True`` runs the same kernels in the Pallas interpreter
    (the CPU tests); the default compiles them with Mosaic."""
    if act_type and act_type not in _PALLAS_ACTS:
        return fused_bn_act_lax(
            data, gamma, beta, moving_mean, moving_var, act_type=act_type,
            eps=eps, momentum=momentum, fix_gamma=fix_gamma,
            use_global_stats=use_global_stats, is_train=is_train)
    axes = (0,) + tuple(range(2, data.ndim))
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    if is_train and not use_global_stats:
        mean = jnp.mean(data, axis=axes)
        var = jnp.var(data, axis=axes)
        new_mm = moving_mean * momentum + mean * (1 - momentum)
        new_mv = moving_var * momentum + var * (1 - momentum)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    inv = lax.rsqrt(var + eps)
    scale = (inv * gamma).astype(jnp.float32)
    shift = (beta - mean * inv * gamma).astype(jnp.float32)
    n, c = data.shape[0], data.shape[1]
    x3 = data.reshape(n, c, -1)
    out = _norm_act(x3, scale.reshape(1, c, 1), shift.reshape(1, c, 1),
                    act_type, interpret)
    return out.reshape(data.shape), new_mm, new_mv


def fused_bn_act(data, gamma, beta, moving_mean, moving_var, **kw):
    """Platform-routed fused BN(+activation): the compiled Pallas kernel
    in a program lowered for a TPU, fused-lax anywhere else (same
    signature/returns as the BatchNorm op, plus ``act_type``).  The
    compiled kernel engages only for (sublane, lane)-aligned (C, H*W)
    rows; unaligned shapes take the fused-lax path rather than paying
    Mosaic relayouts."""
    from . import by_platform
    spatial = 1
    for d in data.shape[2:]:
        spatial *= int(d)
    args = (data, gamma, beta, moving_mean, moving_var)
    if spatial % 128 == 0 and data.shape[1] % 8 == 0:
        return by_platform(functools.partial(fused_bn_act_pallas, **kw),
                           functools.partial(fused_bn_act_lax, **kw), *args)
    return fused_bn_act_lax(*args, **kw)


def fold_bn_into_conv(weight, bias, gamma, beta, moving_mean, moving_var,
                      eps=0.001, fix_gamma=True):
    """Fold frozen BN statistics into the preceding conv's parameters.

    ``weight``: (O, I/g, *k); ``bias``: (O,) or None.  Returns
    ``(weight', bias')`` such that ``conv(x, w') + b'`` equals
    ``BN(conv(x, w) + b)`` with the moving statistics (inference mode),
    up to float reassociation.
    """
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    scale = gamma * lax.rsqrt(moving_var + eps)
    w = weight * scale.reshape((-1,) + (1,) * (weight.ndim - 1)) \
        .astype(weight.dtype)
    b = bias if bias is not None else jnp.zeros_like(moving_mean)
    b = ((b - moving_mean) * scale + beta).astype(w.dtype)
    return w, b
