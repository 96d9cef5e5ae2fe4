"""Depthwise causal convolution along positions — ``CausalConv1D`` with
``num_group`` 0: ``y[t] = act(sum_j w[:, j] * x[t - (kernel-1) + j])``, zeros
before a row's start, products, sum and activation in float32.

Not arithmetic (``kernel`` x 2 FLOPs an element) but traffic: the op is worth
what it moves through HBM.  Two tiers (package docstring), chosen from what
the trace can see (:func:`_lax_reason`; each call records a ``kernel.route``
event: kernel ``causal_conv``, tier ``pallas`` / ``lax``, reason ``aligned`` /
``shapes`` / ``mesh``):

- :func:`causal_conv_lax` — the op as it always was: the stream padded and
  widened to float32, ``kernel`` shifted products, a Python ``sum``; its
  backward is what autodiff makes of that.  Any shape, any platform, a mesh,
  and the oracle of the compiled tier.
- :func:`causal_conv_pallas`, in a program lowered for a TPU:
  ``mxtpu_causal_conv_fwd`` and ``mxtpu_causal_conv_bwd`` under one
  ``jax.custom_vjp`` whose residuals are the inputs.  A grid step takes a
  (positions x channels) tile of one row in the data's dtype with the
  ``_HALO`` positions before it (after it too, on the way back) as a second
  block of the same operand, widens it in VMEM and walks it ``_ROWS``
  positions at a time: the taps multiplied and summed in float32 in the lax
  tier's order, the activation in float32, one store in the data's dtype.
  The backward recomputes the tile's pre-activation, takes ``g = dy *
  act'(pre)``, writes ``dx`` (the same convolution flipped, zeros past a
  row's end) and adds ``dw`` into a float32 block that stays in VMEM over
  every row and position of its channels.  HBM sees ``x`` and ``y`` once
  forward; ``x``, ``dy`` and ``dx`` once backward.

On the v5e (2 x 8,192 positions, bfloat16; forward / forward + backward, ms;
PERF.md, PR 36): 4,096 channels, 4 taps, ``silu``: lax tier 2.31 / 7.55,
kernels 0.50 / 1.40 (HBM allows 0.33 / 0.82); 8,192 channels: 4.97 / 14.92
and 0.96 / 2.72; 1,280 channels, 2 taps, no activation: 0.24 / 0.80 and
0.22 / 0.48.  The kernels' bfloat16 ``y`` is the lax tier's bit for bit.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["causal_conv", "causal_conv_lax", "causal_conv_pallas"]

#: positions of the neighbouring block a grid step fetches beside its tile:
#: a whole sublane tile of either dtype, and at least ``_MAX_TAPS - 1``
_HALO = 16
#: positions the kernels hold in registers at a time (at 16 the forward
#: reads 0.59 ms where 32 reads 0.50; at 64 the backward spills)
_ROWS = 32
_MAX_TAPS = 8
_ACTS = (None, "silu")
_VMEM = 32 * 1024 * 1024


def causal_conv_lax(data, weight, kernel, act_type=None):
    """data (batch, positions, channels), weight (channels, kernel)."""
    from ..ops.nn import activation
    k, t = int(kernel), data.shape[1]
    x = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0))).astype(jnp.float32)
    w = weight.astype(jnp.float32)
    y = sum(x[:, j:j + t] * w[:, j] for j in range(k))
    if act_type:
        y = activation(y.astype(jnp.float32), act_type)
    return y.astype(data.dtype)


def _tiles(T, C):
    """(positions, channels) of a grid step, or None: whole lane tiles of
    channels, positions in whole ``_ROWS``."""
    bt = next((b for b in (2048, 1024, 512, 256, 128, 64, 32)
               if T % b == 0), None)
    bc = next((b for b in (512, 256, 128) if C % b == 0), None)
    return None if bt is None or bc is None else (bt, bc)


def _shifted(buf, r, rows, shifts):
    """``buf[r + s : r + s + rows]`` for each ``s`` of ``shifts`` (-8 .. 8),
    ``r`` a multiple of 8: aligned loads, turned along the sublanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n = rows + 8
    back = buf[pl.ds(r - 8, n), :] if min(shifts) < 0 else None
    on = buf[pl.ds(r, n), :] if max(shifts) > 0 else None
    out = []
    for s in shifts:
        if s < 0:
            out.append(pltpu.roll(back, -s, 0)[8:])
        elif s > 0:
            out.append(pltpu.roll(on, n - s, 0)[:rows])
        else:
            out.append(buf[pl.ds(r, rows), :])
    return out


def _taps(rows, w):
    """``sum_j w[j] * rows[j]``, j = 0 .. K-1 in turn, in float32."""
    acc = rows[0] * w[0]
    for j in range(1, len(rows)):
        acc = acc + rows[j] * w[j]
    return acc


def _fwd_kernel(K, act, x_ref, before_ref, w_ref, y_ref, xbuf):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    bt = x_ref.shape[0]
    # [the _HALO positions before the tile | the tile], float32; zeros
    # before a row's start
    xbuf[0:_HALO, :] = jnp.where(pl.program_id(2) > 0,
                                 before_ref[...].astype(f32), 0.0)
    xbuf[_HALO:, :] = x_ref[...].astype(f32)
    w = [w_ref[j:j + 1, :].astype(f32) for j in range(K)]

    def chunk(i, carry):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        pre = _taps(_shifted(xbuf, r + _HALO, _ROWS, range(1 - K, 1)), w)
        if act:
            pre = pre * jax.nn.sigmoid(pre)
        y_ref[pl.ds(r, _ROWS), :] = pre.astype(y_ref.dtype)
        return carry
    lax.fori_loop(0, bt // _ROWS, chunk, 0)


def _bwd_kernel(K, act, x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                w_ref, dx_ref, dw_ref, xbuf, gbuf):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    bt = x_ref.shape[0]
    first = (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
    last = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(first)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
    # [_HALO before | the tile | _HALO after], float32
    xbuf[0:_HALO, :] = jnp.where(pl.program_id(2) > 0,
                                 before_ref[...].astype(f32), 0.0)
    xbuf[_HALO:_HALO + bt, :] = x_ref[...].astype(f32)
    xbuf[_HALO + bt:, :] = after_ref[...].astype(f32)
    w = [w_ref[j:j + 1, :].astype(f32) for j in range(K)]

    def cotangent(r, dy):
        """g of the positions from ``r`` on that ``dy`` is of (of the tile
        and the ``_HALO`` after it), into ``gbuf``; the shifted x it read."""
        rows = _shifted(xbuf, r + _HALO, len(dy), range(1 - K, 1))
        pre = _taps(rows, w)
        g = dy.astype(f32)
        if act:
            s = jax.nn.sigmoid(pre)
            g = g * (s * (1.0 + pre * (1.0 - s)))
        gbuf[pl.ds(r, len(dy)), :] = g
        return g, rows

    def chunk(i, dw):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        g, rows = cotangent(r, dy_ref[pl.ds(r, _ROWS), :])
        return tuple(d + jnp.sum((g * x).reshape(_ROWS // 8, 8, -1), axis=0)
                     for d, x in zip(dw, rows))
    zero = jnp.zeros((8, x_ref.shape[1]), f32)
    dw = lax.fori_loop(0, bt // _ROWS, chunk, (zero,) * K)
    # the positions after the tile reach back into it; nothing does from
    # past a row's end
    cotangent(bt, jnp.where(last, 0.0, dy_after_ref[...].astype(f32)))
    for j in range(K):
        dw_ref[j:j + 1, :] += jnp.sum(dw[j], axis=0, keepdims=True)

    def flipped(i, carry):
        r = pl.multiple_of(i * _ROWS, _ROWS)
        acc = _taps(_shifted(gbuf, r, _ROWS, range(K - 1, -1, -1)), w)
        dx_ref[pl.ds(r, _ROWS), :] = acc.astype(dx_ref.dtype)
        return carry
    lax.fori_loop(0, bt // _ROWS, flipped, 0)


@functools.lru_cache(maxsize=None)
def _pallas_conv(K, act, tiles, interpret):
    """The convolution as one ``custom_vjp`` over x (B, T, C) and w (C, K)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, bc = tiles
    halos = bt // _HALO
    shape = jax.ShapeDtypeStruct

    def call(kernel, name, grid, index, x, ins, outs, out_shape, scratch,
             semantics):
        """``index(*grid ids) -> (row, channel block, position block)``."""
        T = x.shape[1]

        def spec(block, at):
            return pl.BlockSpec(block, lambda *ids: at(*index(*ids)))
        specs = dict(
            tile=spec((None, bt, bc), lambda b, c, t: (b, t, c)),
            before=spec((None, _HALO, bc), lambda b, c, t:
                        (b, jnp.maximum(t * halos - 1, 0), c)),
            after=spec((None, _HALO, bc), lambda b, c, t:
                       (b, jnp.minimum((t + 1) * halos, T // _HALO - 1), c)),
            w=spec((K, bc), lambda b, c, t: (0, c)))
        return pl.pallas_call(
            functools.partial(kernel, K, act), grid=grid,
            in_specs=[specs[n] for n in ins],
            out_specs=tuple(specs[n] for n in outs), out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((rows, bc), jnp.float32)
                            for rows in scratch],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=_VMEM),
            name=name, interpret=interpret)

    # jitted, so that a model's layers of one shape trace and lower each
    # kernel once
    @jax.jit
    def run_forward(x, w):
        B, T, C = x.shape
        return call(
            _fwd_kernel, "mxtpu_causal_conv_fwd", (B, C // bc, T // bt),
            lambda b, c, t: (b, c, t), x, ("tile", "before", "w"), ("tile",),
            (shape(x.shape, x.dtype),), [_HALO + bt],
            ("parallel", "parallel", "parallel"))(x, x, w.T)[0]

    @jax.jit
    def run_backward(x, w, dy):
        B, T, C = x.shape
        # a channel block's dw stays in VMEM over every row and position
        dx, dw = call(
            _bwd_kernel, "mxtpu_causal_conv_bwd", (C // bc, B, T // bt),
            lambda c, b, t: (b, c, t), x,
            ("tile", "before", "after", "tile", "after", "w"), ("tile", "w"),
            (shape(x.shape, x.dtype), shape((K, C), jnp.float32)),
            [2 * _HALO + bt, _HALO + bt],
            ("parallel", "arbitrary", "arbitrary"))(x, x, x, dy, dy, w.T)
        return dx, dw.T.astype(w.dtype)

    @jax.custom_vjp
    def conv(x, w):
        return run_forward(x, w)

    def conv_fwd(x, w):
        return run_forward(x, w), (x, w)

    def conv_bwd(res, dy):
        return run_backward(*res, dy)

    conv.defvjp(conv_fwd, conv_bwd)
    return conv


def causal_conv_pallas(data, weight, kernel, act_type=None, tiles=None,
                       interpret=False):
    """The compiled tier of :func:`causal_conv` (same operands, same
    result): :func:`_lax_reason` says which operands it takes.  ``tiles`` =
    (positions, channels) of a grid step, from the shapes when not given."""
    tiles = tiles or _tiles(data.shape[1], data.shape[2])
    return _pallas_conv(int(kernel), act_type, tuple(tiles),
                        bool(interpret))(data, weight)


def _lax_reason(data, kernel, act_type):
    """Why these operands are not the compiled tier's, or None."""
    from . import partitioned
    if partitioned():
        return "mesh"
    if data.ndim != 3 or _tiles(*data.shape[1:]) is None \
            or not 2 <= int(kernel) <= _MAX_TAPS or act_type not in _ACTS \
            or data.dtype not in (jnp.bfloat16, jnp.float32):
        return "shapes"
    return None


@functools.partial(jax.jit, static_argnames=("kernel", "act_type"))
def _lax_branch(data, weight, kernel, act_type):
    """The lax tier as the other platforms' branch of a program that takes
    the kernels: jitted, so that a model's layers of one shape trace it
    once."""
    return causal_conv_lax(data, weight, kernel, act_type)


def causal_conv(data, weight, kernel, act_type=None):
    """Depthwise causal convolution: data (batch, positions, channels),
    weight (channels, kernel); the result in the data's dtype.

    Which tier runs follows from what the trace can see
    (:func:`_lax_reason`): the compiled kernels in a program lowered for a
    TPU, for channels in whole lane tiles (multiples of 128), positions in
    whole blocks of 32, 2 to 8 taps, ``act_type`` None or ``silu`` and
    bfloat16 or float32 data; the lax tier on other platforms, for other
    operands and in a program the SPMD partitioner will split.  Each call
    records one ``kernel.route`` event in the program's recorder (``kernel``
    = ``causal_conv``, the tier, the reason: ``aligned``, ``shapes``,
    ``mesh``) and counts ``kernel.causal_conv.<tier>``."""
    from .. import profiler
    from . import by_platform
    act_type = act_type or None
    reason = _lax_reason(data, kernel, act_type)
    tier = "lax" if reason else "pallas"
    now = time.perf_counter_ns()
    profiler.event("kernel.route", now, now, kernel="causal_conv", tier=tier,
                   reason=reason or "aligned")
    profiler.count("kernel.causal_conv." + tier)
    if reason:
        return causal_conv_lax(data, weight, kernel, act_type)
    return by_platform(
        functools.partial(causal_conv_pallas, kernel=int(kernel),
                          act_type=act_type),
        functools.partial(_lax_branch, kernel=int(kernel), act_type=act_type),
        data, weight)
