"""Fused LSTM cell — all gate math in one kernel.

The unfused cell (ops/nn.py ``_rnn_cell_step``, rnn_cell.py ``LSTMCell``)
splits the (B, 4H) gate pre-activations into four tensors and chains
sigmoid/tanh/mul/add ops — at dispatch granularity that is ~10 memory
passes over (B, H) for ~10 flops/element, squarely memory-bound.  The
fused cell does the whole block in one pass:

    i, f, g, o = gates            # static slices, gate order [i, f, c, o]
    c = sigmoid(f) * c_prev + sigmoid(i) * tanh(g)
    h = sigmoid(o) * tanh(c)

Two tiers (package docstring):

- :func:`lstm_cell_lax` — the fused-lax reference.  The per-element
  operation sequence is IDENTICAL to the unfused composition, so forward
  values are bit-equal and autodiff gradients match the unfused graph's
  (tests/test_kernels.py pins both).  Differentiable by jax as-is.
- :func:`lstm_cell_pallas` — a ``pl.pallas_call`` kernel pair behind
  ``jax.custom_vjp`` (Pallas has no reverse-mode transpose — rtc.py
  contract).  The backward kernel RECOMPUTES the gate activations
  in-tile from the saved pre-activations instead of materializing them
  (the FlashAttention discipline), so residuals are just (gates, c_prev).

:func:`lstm_cell` routes by the platform the program is lowered for; the
symbolic graph consumes the ``_FusedLSTMCell`` op (``rnn_cell.LSTMCell`` emits it when
``MXTPU_FUSED_KERNELS`` enables ``lstm_cell``), and the fused RNN op's
``lax.scan`` (ops/nn.py ``rnn``) calls :func:`lstm_cell` directly.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["lstm_cell", "lstm_cell_lax", "lstm_cell_pallas"]


def lstm_cell_lax(gates, c_prev):
    """Fused-lax reference: one traced function, unfused op order.

    ``gates``: (B, 4H) pre-activations in gate order [i, f, c, o]
    (i2h + h2h + biases already summed); ``c_prev``: (B, H).
    Returns ``(h, c)``.
    """
    h = c_prev.shape[-1]
    i = jax.nn.sigmoid(gates[..., 0 * h:1 * h])
    f = jax.nn.sigmoid(gates[..., 1 * h:2 * h])
    g = jnp.tanh(gates[..., 2 * h:3 * h])
    o = jax.nn.sigmoid(gates[..., 3 * h:4 * h])
    c = f * c_prev + i * g
    new_h = o * jnp.tanh(c)
    return new_h, c


def _gates(g_ref, h):
    """The four activated gates of one (rows, 4H) block, in f32 (v5e has
    no bf16 VPU/EUP).  Static ref slices: lane-aligned when H % 128 == 0."""
    f32 = jnp.float32
    i = jax.nn.sigmoid(g_ref[:, 0 * h:1 * h].astype(f32))
    f = jax.nn.sigmoid(g_ref[:, 1 * h:2 * h].astype(f32))
    g = jnp.tanh(g_ref[:, 2 * h:3 * h].astype(f32))
    o = jax.nn.sigmoid(g_ref[:, 3 * h:4 * h].astype(f32))
    return i, f, g, o


def _fwd_kernel(g_ref, c_ref, h_out, c_out):
    """Pallas forward body: one row block's gate math in VMEM."""
    i, f, g, o = _gates(g_ref, c_ref.shape[-1])
    c = f * c_ref[...].astype(jnp.float32) + i * g
    c_out[...] = c.astype(c_out.dtype)
    h_out[...] = (o * jnp.tanh(c)).astype(h_out.dtype)


def _bwd_kernel(g_ref, c_ref, dh_ref, dc_ref, dg_out, dcp_out):
    """Pallas backward body: recompute activations in-tile, emit
    (dgates, dc_prev) from (dh, dc_next)."""
    h = c_ref.shape[-1]
    i, f, g, o = _gates(g_ref, h)
    c_prev = c_ref[...].astype(jnp.float32)
    tanh_c = jnp.tanh(f * c_prev + i * g)
    dh = dh_ref[...].astype(jnp.float32)
    # dc accumulates the explicit cotangent and the h = o * tanh(c) path
    dc = dc_ref[...].astype(jnp.float32) + dh * o * (1.0 - tanh_c * tanh_c)
    dt = dg_out.dtype
    dg_out[:, 0 * h:1 * h] = (dc * g * i * (1.0 - i)).astype(dt)
    dg_out[:, 1 * h:2 * h] = (dc * c_prev * f * (1.0 - f)).astype(dt)
    dg_out[:, 2 * h:3 * h] = (dc * i * (1.0 - g * g)).astype(dt)
    dg_out[:, 3 * h:4 * h] = (dh * tanh_c * o * (1.0 - o)).astype(dt)
    dcp_out[...] = (dc * f).astype(dcp_out.dtype)


#: elements of one (rows, 4H) gate block (128K f32 = 512 KiB): the
#: backward holds two such blocks and four (rows, H) ones, double-buffered
_BLOCK_ELEMS = 128 * 1024


def _row_tile(rows, H):
    """Rows per program: the largest multiple-of-8 divisor of ``rows``
    whose gate block fits ``_BLOCK_ELEMS``; rows that are not
    sublane-aligned go as one block (the interpret-mode tests)."""
    if rows % 8:
        return rows
    fit = max(1, _BLOCK_ELEMS // (4 * H * 8))
    return 8 * max(d for d in range(1, rows // 8 + 1)
                   if (rows // 8) % d == 0 and d <= fit)


def _pallas_call(kernel, name, out_widths, interpret):
    """``call(gates, *rest)`` over 2-D operands — ``gates`` 4H wide, the
    rest H wide — gridded over row blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def call(*arrays):
        rows, H = arrays[-1].shape
        t = _row_tile(rows, H)

        def spec(width):
            return pl.BlockSpec((t, width * H), lambda r: (r, 0),
                                memory_space=pltpu.VMEM)
        widths = [4] + [1] * (len(arrays) - 1)
        return pl.pallas_call(
            kernel,
            out_shape=tuple(
                jax.ShapeDtypeStruct(
                    (rows, w * H), arrays[0 if w == 4 else -1].dtype)
                for w in out_widths),
            grid=(rows // t,),
            in_specs=[spec(w) for w in widths],
            out_specs=tuple(spec(w) for w in out_widths),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)),
            name=name, interpret=interpret)(*arrays)
    return call


def _make_pallas(interpret):
    fwd_call = _pallas_call(_fwd_kernel, "mxtpu_lstm_cell_fwd", (1, 1),
                            interpret)
    bwd_call = _pallas_call(_bwd_kernel, "mxtpu_lstm_cell_bwd", (4, 1),
                            interpret)

    @jax.custom_vjp
    def cell(gates, c_prev):
        return fwd_call(gates, c_prev)

    def cell_fwd(gates, c_prev):
        # residuals are the INPUTS only; the backward kernel recomputes
        # every activation in-tile (nothing materialized between passes)
        return fwd_call(gates, c_prev), (gates, c_prev)

    def cell_bwd(res, cot):
        gates, c_prev = res
        dh, dc = cot
        return bwd_call(gates, c_prev, dh, dc)

    cell.defvjp(cell_fwd, cell_bwd)
    return cell


_pallas_cells = {}


def lstm_cell_pallas(gates, c_prev, interpret=False):
    """Pallas-tier fused cell (custom_vjp registered).  ``interpret=True``
    runs the same kernels in the Pallas interpreter (the CPU tests); the
    default compiles them with Mosaic."""
    cell = _pallas_cells.get(bool(interpret))
    if cell is None:
        cell = _pallas_cells[bool(interpret)] = _make_pallas(bool(interpret))
    H = c_prev.shape[-1]
    h, c = cell(gates.reshape(-1, 4 * H), c_prev.reshape(-1, H))
    return h.reshape(c_prev.shape), c.reshape(c_prev.shape)


def lstm_cell(gates, c_prev):
    """Platform-routed fused LSTM cell: the compiled Pallas kernel in a
    program lowered for a TPU, the fused-lax reference anywhere else.
    The compiled tier engages only for (sublane, lane)-aligned shapes —
    H a lane multiple, rows a sublane multiple — so tile-unaligned cells
    (H=200 etc.) take the fused-lax path instead of paying Mosaic
    relayouts."""
    from . import by_platform
    H = c_prev.shape[-1]
    rows = int(np.prod(c_prev.shape[:-1]))
    if H % 128 == 0 and rows % 8 == 0:
        return by_platform(lstm_cell_pallas, lstm_cell_lax, gates, c_prev)
    return lstm_cell_lax(gates, c_prev)


# ---------------------------------------------------------------------------
# symbolic surface: the op rnn_cell.LSTMCell emits when fusion is enabled
# ---------------------------------------------------------------------------

def _flc_infer(attrs, in_shapes):
    g = in_shapes[0]
    if g is None:
        if len(in_shapes) > 1 and in_shapes[1] is not None:
            c = tuple(in_shapes[1])
            return [(c[0], 4 * c[1]), c], [c, c], []
        return in_shapes, [None, None], []
    c = (g[0], g[1] // 4)
    return [tuple(g), c], [c, c], []


def _register_op():
    from ..ops.registry import OP_REGISTRY, register

    if "_FusedLSTMCell" in OP_REGISTRY:  # idempotent under re-import
        return

    @register("_FusedLSTMCell", input_names=("gates", "prev_c"),
              num_outputs=2, output_names=("h", "c"),
              infer_shape=_flc_infer, hidden=True)
    def _fused_lstm_cell(gates, prev_c):
        """Fused LSTM gate block (mxnet_tpu/kernels/lstm_cell.py):
        (B, 4H) pre-activations + previous cell -> (next_h, next_c)."""
        return lstm_cell(gates, prev_c)

    # late registration: the autogen nd/sym modules were populated at
    # package import — self-inject like rtc.register_kernel does
    from ..rtc import _inject
    _inject("_FusedLSTMCell")


_register_op()
