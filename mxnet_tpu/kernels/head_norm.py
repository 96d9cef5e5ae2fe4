"""RMSNorm over the heads of a (batch, positions, heads, width) tensor —
``RMSNorm`` of rank 4: ``y = x * rsqrt(mean(x^2) + eps) * w`` over each
head's ``width`` features in float32, ``w = gamma`` or ``1 + gamma``, times
``silu(gate)`` or ``sigmoid(gate)`` where the norm is gated (the output norm
of a linear-attention mixer), and turned by the rotary embedding that
follows it where the norm is a query's or a key's (``rotary_dim`` features of
each head, rotate-half form, angles ``p * base^(-2i / rotary_dim)``).

Not arithmetic but traffic: the op is worth what it moves through HBM.  Two
tiers (package docstring), chosen from what the trace can see
(:func:`_lax_reason`; each call records a ``kernel.route`` event: kernel
``head_norm``, tier ``pallas`` / ``lax``, reason ``aligned`` / ``shapes`` /
``mesh``):

- :func:`head_norm_lax` — the op as it always was: ``RMSNorm``'s float32
  arithmetic, its result in the data's dtype, then ``RotaryEmbedding``'s;
  its backward is what autodiff makes of that.  Any shape, any platform, a
  mesh, and the oracle of the compiled tier.
- :func:`head_norm_pallas`, in a program lowered for a TPU:
  ``mxtpu_head_norm_fwd`` and ``mxtpu_head_norm_bwd`` under one
  ``jax.custom_vjp`` whose residuals are the inputs.  The operands are read
  as (batch, positions, heads x width), the layout of the projection that
  made them; a grid step takes a block of whole rows of every head (each
  row one contiguous read) and walks it one head and ``_ROWS`` positions at
  a time: the norm, weight, gate and turn in float32, one store in the
  data's dtype.  The turn's cosines and signed sines are one (positions,
  width) float32 operand each, computed by XLA in the jitted wrapper as the
  lax tier computes them, and fetched once a position block (rows are the
  inner grid axis).  The backward recomputes the norm and writes ``dx``
  (and the gate's cotangent) in one pass over ``x``, the gate and ``dy``,
  and adds the weight's gradient into a float32 block that stays in VMEM
  over the whole grid.  HBM sees ``x`` (and the gate) and ``y`` once
  forward; ``x``, the gate, ``dy``, ``dx`` and the gate's cotangent once
  backward.

The kernels' factory is cached by (eps, gate, rotary width and base, tiles,
interpret) and its forward and backward are jitted: every norm of one shape
in a model's step shares one trace and one lowered function, and a kernel
body's traced size depends on none of heads, positions or the rotary width
(the grid and a ``fori_loop`` walk them).

On the v5e (2 x 8,192 positions, bfloat16, forward / backward, ms;
PERF.md): 32 heads of 128 turned over all 128, 0.59 / 0.94 (HBM allows
0.33 / 0.49); plain, 0.43 / 0.86; gated by ``silu``, 0.65 / 1.08 (0.49 /
0.82); 16 heads of 256, zero-centred, turned over 64, 0.48 / 0.91.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["head_norm", "head_norm_lax", "head_norm_pallas"]

_ACTS = (None, "silu", "sigmoid")
#: bytes of VMEM the blocks that stream through a grid step may take, each
#: double-buffered: the backward's x, dy and dx, and a gated norm's gate and
#: its cotangent besides (256 bfloat16 positions of 32 heads of 128 gated:
#: 2 MiB a block, 20 MiB in all).  Blocks are whole rows of every head, so
#: that each row is one contiguous read
_STREAMED = 20 * 1024 * 1024
#: positions a register chunk holds (on the v5e at 2 x 8,192 positions of
#: 32 heads of 128, forward / backward: 64 rows 1.06 / 1.98 ms, 128 rows
#: 0.58 / 1.17, 256 rows 0.43 / 0.86; a chunk's lane reductions are chains
#: of dependent steps, and only a wide chunk gives them company)
_ROWS = 256
_WIDTHS = (128, 256, 512)
_VMEM = 32 * 1024 * 1024


def head_norm_lax(data, gamma, gate=None, eps=1e-6, zero_centered=False,
                  gate_act="silu", rotary_dim=0, base=10000.0):
    """``RMSNorm`` then, where ``rotary_dim`` is given, ``RotaryEmbedding``:
    the two ops' own arithmetic, node after node."""
    from ..ops.nn import rms_math, rotary_embedding
    y = rms_math(data, gamma, gate, eps, zero_centered, gate_act)
    if rotary_dim:
        y = rotary_embedding(y, rotary_dim, base)
    return y


def _tiles(T, H, D, itemsize, gated):
    """(positions a grid step, rows a register chunk), or None: a width of
    whole lane tiles (128, 256 or 512), positions in whole blocks of 16
    whose streamed blocks fit ``_STREAMED``."""
    if D not in _WIDTHS:
        return None
    position = 2 * (5 if gated else 3) * H * D * itemsize
    bt = next((b for b in (2048, 1024, 512, 256, 128, 64, 32, 16)
               if b * position <= _STREAMED and T % b == 0), None)
    return None if bt is None else (bt, min(bt, _ROWS))


def _turns(T, D, rotary_dim, base):
    """Cosines and signed sines of the rotary turn, (T, D) float32 each:
    the lax tier's angles; 1 and 0 on the features it leaves alone."""
    f32 = jnp.float32
    half = rotary_dim // 2
    inv_freq = float(base) ** (-jnp.arange(half, dtype=f32) * 2.0
                               / rotary_dim)
    angle = jnp.arange(T, dtype=f32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    rest = [jnp.ones((T, D - rotary_dim), f32)] if D > rotary_dim else []
    return (jnp.concatenate([cos, cos] + rest, axis=-1),
            jnp.concatenate([-sin, sin] + [0.0 * r for r in rest], axis=-1))


def _swapped(x, half):
    """The rotate-half partner of each feature: ``x[j + half]`` below
    ``half``, ``x[j - half]`` from it on (past ``2 * half`` whatever the
    signed sine then multiplies by 0)."""
    from jax.experimental.pallas import tpu as pltpu
    D = x.shape[-1]
    up = pltpu.roll(x, half, 1)
    if 2 * half == D:
        return up
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < half, pltpu.roll(x, D - half, 1), up)


def _gated(act, g):
    return jax.nn.silu(g) if act == "silu" else jax.nn.sigmoid(g)


def _gated_grad(act, g):
    s = jax.nn.sigmoid(g)
    return s * (1.0 + g * (1.0 - s)) if act == "silu" else s * (1.0 - s)


def _operands(act, half, refs):
    """x, w, gate, cos, sin refs (gate, cos and sin None where the variant
    has none), and the rest."""
    x, w, *refs = refs
    g = refs.pop(0) if act else None
    cos, sin = (refs.pop(0), refs.pop(0)) if half else (None, None)
    return x, w, g, cos, sin, refs


def _walk(x_ref, w_ref, rows, body, carry):
    """``body(rows of positions, lanes of one head, carry)`` over every
    register chunk of a block of (positions, heads x width), a head's chunks
    in turn."""
    from jax.experimental import pallas as pl
    D = w_ref.shape[1]
    n = x_ref.shape[0] // rows

    def chunk(k, carry):
        at = pl.ds(pl.multiple_of((k % n) * rows, rows), rows)
        lanes = pl.ds(pl.multiple_of((k // n) * D, 128), D)
        return body(at, lanes, carry)
    return lax.fori_loop(0, n * (x_ref.shape[1] // D), chunk, carry)


def _fwd_kernel(eps, act, half, rows, *refs):
    f32 = jnp.float32
    x_ref, w_ref, g_ref, cos_ref, sin_ref, (y_ref,) = _operands(act, half,
                                                                refs)
    w = w_ref[...]

    def chunk(at, lanes, carry):
        x = x_ref[at, lanes].astype(f32)
        y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        y = y * w
        if act:
            y = y * _gated(act, g_ref[at, lanes].astype(f32))
        if half:
            # the norm's result in the data's dtype, as the next node reads
            y = y.astype(y_ref.dtype).astype(f32)
            y = y * cos_ref[at, :] + _swapped(y, half) * sin_ref[at, :]
        y_ref[at, lanes] = y.astype(y_ref.dtype)
        return carry
    _walk(x_ref, w_ref, rows, chunk, 0)


def _bwd_kernel(eps, act, half, rows, *refs):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    x_ref, w_ref, g_ref, cos_ref, sin_ref, refs = _operands(act, half, refs)
    dy_ref, dx_ref, *refs = refs
    dg_ref = refs.pop(0) if act else None
    dw_ref, = refs

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
    w = w_ref[...]

    def chunk(at, lanes, dw):
        x = x_ref[at, lanes].astype(f32)
        r = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        n = x * r
        db = dy_ref[at, lanes].astype(f32)
        if half:
            # the turn's transpose, rounded where the norm's result was
            db = db * cos_ref[at, :] - _swapped(db, half) * sin_ref[at, :]
            db = db.astype(dx_ref.dtype).astype(f32)
        if act:
            g = g_ref[at, lanes].astype(f32)
            dg_ref[at, lanes] = (db * (n * w) * _gated_grad(act, g)).astype(
                dg_ref.dtype)
            db = db * _gated(act, g)
        dw = dw + jnp.sum((db * n).reshape(rows // 8, 8, -1), axis=0)
        dn = db * w
        dx = r * (dn - n * jnp.mean(dn * n, axis=-1, keepdims=True))
        dx_ref[at, lanes] = dx.astype(dx_ref.dtype)
        return dw
    dw = _walk(x_ref, w_ref, rows, chunk, jnp.zeros((8, w.shape[1]), f32))
    dw_ref[...] += jnp.sum(dw, axis=0, keepdims=True)


@functools.lru_cache(maxsize=None)
def _pallas_norm(eps, act, rotary_dim, base, tiles, interpret):
    """The norm as one ``custom_vjp`` over x (B, T, H, D), w (1, D) float32
    and, where ``act`` is given, the gate (B, T, H, D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bt, rows = tiles
    half = rotary_dim // 2
    shape = jax.ShapeDtypeStruct

    def flat(a):
        return a.reshape(a.shape[:2] + (-1,))

    def call(kernel, name, x, g, ins, outs, out_shape, semantics):
        """The kernel over x, w, the gate ``g`` (empty or one), the turn's
        tables (where the variant has them) and ``ins``; rows innermost."""
        B, T, H, D = x.shape
        specs = dict(
            tile=pl.BlockSpec((None, bt, H * D), lambda t, b: (b, t, 0)),
            w=pl.BlockSpec((1, D), lambda t, b: (0, 0)),
            turn=pl.BlockSpec((bt, D), lambda t, b: (t, 0)))
        names = ["tile", "w"] + ["tile"] * len(g) + ["turn"] * (2 * bool(
            half)) + list(ins)
        kernel = pl.pallas_call(
            functools.partial(kernel, eps, act, half, rows),
            grid=(T // bt, B), in_specs=[specs[n] for n in names],
            out_specs=tuple(specs[n] for n in outs), out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=semantics, vmem_limit_bytes=_VMEM),
            name=name, interpret=interpret)
        turns = _turns(T, D, rotary_dim, base) if half else ()
        return lambda w, *rest: kernel(flat(x), w, *map(flat, g), *turns,
                                       *map(flat, rest))

    # jitted, so that a model's norms of one shape trace and lower each
    # kernel once
    @jax.jit
    def run_forward(x, w, *g):
        y, = call(_fwd_kernel, "mxtpu_head_norm_fwd", x, g, (), ("tile",),
                  (shape(flat(x).shape, x.dtype),),
                  ("parallel", "parallel"))(w)
        return y.reshape(x.shape)

    @jax.jit
    def run_backward(x, w, *rest):
        *g, dy = rest
        out_shape = (shape(flat(x).shape, x.dtype),) + tuple(
            shape(flat(a).shape, a.dtype) for a in g) \
            + (shape(w.shape, jnp.float32),)
        # the weight's gradient stays in VMEM over the whole grid
        grads = call(_bwd_kernel, "mxtpu_head_norm_bwd", x, g, ("tile",),
                     ("tile",) * len(out_shape[:-1]) + ("w",), out_shape,
                     ("arbitrary", "arbitrary"))(w, dy)
        return (grads[0].reshape(x.shape), grads[-1]) + tuple(
            d.reshape(x.shape) for d in grads[1:-1])

    @jax.custom_vjp
    def norm(x, w, *g):
        return run_forward(x, w, *g)

    def norm_fwd(x, w, *g):
        return run_forward(x, w, *g), (x, w) + g

    def norm_bwd(res, dy):
        return run_backward(*res, dy)

    norm.defvjp(norm_fwd, norm_bwd)
    return norm


def head_norm_pallas(data, gamma, gate=None, eps=1e-6, zero_centered=False,
                     gate_act="silu", rotary_dim=0, base=10000.0, tiles=None,
                     interpret=False):
    """The compiled tier of :func:`head_norm` (same operands, same result):
    :func:`_lax_reason` says which operands it takes.  ``tiles`` =
    (positions a grid step, rows a register chunk), from the shapes when
    not given."""
    tiles = tiles or _tiles(*data.shape[1:], jnp.dtype(data.dtype).itemsize,
                            gate is not None)
    w = gamma.astype(jnp.float32)
    w = (1.0 + w if zero_centered else w).reshape(1, -1)
    act = gate_act if gate is not None else None
    rotary_dim = int(rotary_dim)
    norm = _pallas_norm(float(eps), act, rotary_dim,
                        float(base) if rotary_dim else 0.0, tuple(tiles),
                        bool(interpret))
    return norm(data, w, *(() if gate is None else (gate,)))


def _lax_reason(data, gate_act, rotary_dim):
    """Why these operands are not the compiled tier's, or None."""
    from . import partitioned
    if partitioned():
        return "mesh"
    if data.dtype not in (jnp.bfloat16, jnp.float32) \
            or _tiles(*data.shape[1:], jnp.dtype(data.dtype).itemsize,
                      gate_act is not None) is None \
            or gate_act not in _ACTS or rotary_dim % 2 \
            or not 0 <= rotary_dim <= data.shape[3]:
        return "shapes"
    return None


@functools.partial(jax.jit, static_argnames=(
    "eps", "zero_centered", "gate_act", "rotary_dim", "base"))
def _lax_branch(data, gamma, gate=None, *, eps, zero_centered, gate_act,
                rotary_dim, base):
    """The lax tier as the other platforms' branch of a program that takes
    the kernels: jitted, so that a model's norms of one shape trace it
    once."""
    return head_norm_lax(data, gamma, gate, eps, zero_centered, gate_act,
                         rotary_dim, base)


def head_norm(data, gamma, gate=None, eps=1e-6, zero_centered=False,
              gate_act="silu", rotary_dim=0, base=10000.0):
    """RMSNorm over the last axis of data (batch, positions, heads, width),
    gated and turned as :func:`head_norm_lax`; the result in the data's
    dtype.

    Which tier runs follows from what the trace can see
    (:func:`_lax_reason`): the compiled kernels in a program lowered for a
    TPU, for a width of whole lane tiles (128, 256 or 512), positions in
    whole blocks of 16 whose streamed blocks fit ``_STREAMED`` and bfloat16
    or float32 data; the lax tier on other
    platforms, for other operands and in a program the SPMD partitioner
    will split.  Each call records one ``kernel.route`` event in the
    program's recorder (``kernel`` = ``head_norm``, the tier, the reason:
    ``aligned``, ``shapes``, ``mesh``) and counts ``kernel.head_norm.<tier>``."""
    from .. import profiler
    from . import by_platform
    rotary_dim = int(rotary_dim)
    gate_act = gate_act if gate is not None else None
    reason = _lax_reason(data, gate_act, rotary_dim)
    tier = "lax" if reason else "pallas"
    now = time.perf_counter_ns()
    profiler.event("kernel.route", now, now, kernel="head_norm", tier=tier,
                   reason=reason or "aligned")
    profiler.count("kernel.head_norm." + tier)
    attrs = dict(eps=float(eps), zero_centered=bool(zero_centered),
                 gate_act=gate_act or "silu", rotary_dim=rotary_dim,
                 base=float(base))
    args = (data, gamma) + (() if gate is None else (gate,))
    if reason:
        return head_norm_lax(*args, **attrs)
    return by_platform(functools.partial(head_norm_pallas, **attrs),
                       functools.partial(_lax_branch, **attrs), *args)
