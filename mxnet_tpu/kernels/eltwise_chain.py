"""eltwise_chain — collapse private elementwise runs into one entry.

A run of elementwise ops at dispatch granularity is one memory
round-trip PER OP: each stage writes its full tensor and the next reads
it back.  Fused into one region the chain is one read and one write —
the canonical memory-bound fusion.  Under the whole-graph jit the composed
function traces the IDENTICAL op sequence, so the compiled program —
and therefore forward AND gradient values — are bit-identical to the
unfused plan; the win is real on the eager paths (no-jit graphs,
dispatch-granularity execution) and in plan/trace size.

:data:`ELTWISE_OPS` is the fusable catalog: plain, deterministic,
single-output elementwise math.  Ops with RNG (Dropout), train-mode
branches, custom VJPs (the loss layers), or host callbacks are
deliberately absent — their semantics are not position-free.
"""
from __future__ import annotations

__all__ = ["ELTWISE_OPS", "make_chain_fn"]

#: registered op names the chain pass may absorb (docs/how_to/kernels.md)
ELTWISE_OPS = frozenset((
    # unary math
    "Activation", "abs", "sign", "ceil", "floor", "round", "rint",
    "trunc", "fix", "square", "sqrt", "rsqrt", "cbrt", "rcbrt",
    "exp", "log", "log10", "log2", "log1p", "expm1", "clip",
    "smooth_l1", "sin", "cos", "tan", "sinh", "cosh", "tanh",
    "arcsin", "arccos", "arctan", "arcsinh", "arccosh", "arctanh",
    "relu", "sigmoid", "softsign", "negative", "reciprocal", "erf",
    # scalar-attr binary
    "_plus_scalar", "_minus_scalar", "_rminus_scalar", "_mul_scalar",
    "_div_scalar", "_rdiv_scalar", "_power_scalar", "_rpower_scalar",
    "_maximum_scalar", "_minimum_scalar",
    # tensor binary (the second operand rides as an extra ref)
    "elemwise_add", "elemwise_sub", "elemwise_mul", "elemwise_div",
    "_maximum", "_minimum",
    "broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_div",
    "broadcast_maximum", "broadcast_minimum",
))


def make_chain_fn(stages):
    """Compose a fused chain body from ``stages`` — a list of
    ``(op_fn, call_attrs, n_side_inputs)`` in chain order.

    The interpreter calls the override at the chain TAIL with the
    tail's own inputs first (the chain value slot plus the tail's side
    operands) followed by the extra refs: the side operands of every
    earlier stage, flattened in chain order.  The tail's ``call_attrs``
    arrive as keywords too; they are ignored in favor of the closed-over
    copy (same values — the interpreter contract passes them always).
    """
    head_to_last = stages[:-1]
    tail_fn, tail_attrs, tail_nside = stages[-1]

    def fused(*vals, **_tail_kw):
        x = vals[0]
        tail_sides = vals[1:1 + tail_nside]
        extras = vals[1 + tail_nside:]
        k = 0
        for fn, attrs, nside in head_to_last:
            sides = extras[k:k + nside]
            k += nside
            x = fn(x, *sides, **attrs)
        return tail_fn(x, *tail_sides, **tail_attrs)

    return fused
