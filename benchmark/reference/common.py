"""What every plain reference shares: the precision switch, the optimizer
rule, and the three-step drive.  Nothing here imports the program.

Precision names:

``f32``   the reference proper: float32 everywhere, contractions under
          ``jax.default_matmul_precision("highest")``.
``bf16``  contraction operands, and the gradient arriving at each
          contraction's output, rounded to bfloat16; float32 accumulation
          — the configuration's own precision, used when the reference
          stands in the program's place (fault readings, tests).
``fp8``   the control: the usual fp8 training recipe (Micikevicius et
          al., arXiv:2209.05433) — contraction operands rounded to float8
          e4m3 going forward and the gradient arriving at each
          contraction's output rounded to float8 e5m2 going backward, each
          with a per-tensor scale, float32 accumulation — the nearest
          precision below the bfloat16 the configurations state.
``int8``  the same places rounded to int8 with a per-tensor symmetric
          scale (127 steps to the largest magnitude): the lower precision
          the v5e has hardware for (393 TOP/s int8, no fp8 unit).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

_E4M3_MAX, _E5M2_MAX = 448.0, 57344.0


def _fp8(x, dtype, top):
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(x.dtype) / scale


def _rounded(x, precision, backward=False):
    if precision == "bf16":
        # not astype().astype(): XLA may drop that pair as excess precision
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    if precision == "fp8":
        return _fp8(x, jnp.float8_e5m2, _E5M2_MAX) if backward \
            else _fp8(x, jnp.float8_e4m3fn, _E4M3_MAX)
    if precision == "int8":
        scale = 127.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
        return jnp.round(x * scale) / scale
    raise ValueError("unknown precision %r" % (precision,))


def round_operand(x, precision):
    """``x`` as a contraction sees it under ``precision``; the gradient
    passes straight through the rounding."""
    if precision == "f32":
        return x
    return x + lax.stop_gradient(_rounded(x, precision) - x)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def round_gradient(y, precision):
    """``y`` unchanged; the gradient arriving at it is rounded as
    ``precision`` holds it going backward."""
    return y


round_gradient.defvjp(
    lambda y, precision: (y, None),
    lambda precision, _, g: (g if precision == "f32"
                             else _rounded(g, precision, backward=True),))


def matmul(a, b, precision):
    """a @ b with float32 accumulation."""
    return round_gradient(jnp.matmul(
        round_operand(a, precision), round_operand(b, precision),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), precision)


def conv2d(x, w, stride, pad, precision):
    """NCHW x OIHW convolution, float32 accumulation."""
    return round_gradient(lax.conv_general_dilated(
        round_operand(x, precision), round_operand(w, precision),
        window_strides=stride, padding=[(pad[0], pad[0]), (pad[1], pad[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), precision)


def softmax_ce_sum(logits, labels):
    """Sum over rows of -log softmax(logits)[label]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels.astype(jnp.int32)[:, None],
                                 axis=-1)
    return -jnp.sum(picked)


def decays(name):
    """MXNet's rule: weight decay on ``*_weight`` and ``*_gamma`` only."""
    return name.endswith("_weight") or name.endswith("_gamma")


def sgd_momentum(params, grads, mom, opt, rows):
    """MXNet's ``sgd_mom_update`` on every leaf: the gradient is the SUM
    over the batch, rescaled by 1/rows; returns (params, mom, g) where
    ``g`` is the gradient as the optimizer gets it (rescaled, decayed)."""
    lr, momentum, wd = opt["learning_rate"], opt["momentum"], opt["wd"]
    new_p, new_m, seen = {}, {}, {}
    for k, p in params.items():
        g = grads[k] * (1.0 / rows) + (wd if decays(k) else 0.0) * p
        m = momentum * mom[k] - lr * g
        new_p[k], new_m[k], seen[k] = p + m, m, g
    return new_p, new_m, seen


def make_step(loss_fn, opt, rows):
    """One jitted training step of the plain reference.
    ``loss_fn(params, aux, batch) -> (loss_sum, (new_aux, items))``."""

    @jax.jit
    def step(params, aux, mom, batch):
        (loss, (new_aux, items)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, aux, batch)
        new_p, new_m, seen = sgd_momentum(params, grads, mom, opt, rows)
        return new_p, new_aux, new_m, loss / items, seen, grads

    return step


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def norms_of_diff(a, b):
    """{leaf: ‖a - b‖} over the leaves of ``b``, in float32."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(a[k], jnp.float32) - b[k].astype(jnp.float32))))
        for k in b}


def follow(step, params, aux, batches):
    """Drive ``step`` through ``batches`` from zero momentum.  Returns the
    readings a training cell compares: each step's mean loss, per-leaf norm
    of the first gradient as the optimizer gets it, per-leaf norm of the
    raw first gradient, per-leaf norm of the parameters' change after all
    the steps — and, under ``"full"``, that first gradient and that change
    themselves (device arrays), for the norms of differences."""
    p0 = params
    mom = jax.tree.map(jnp.zeros_like, params)
    losses, first, raw, seen1 = [], None, None, None
    for i, batch in enumerate(batches):
        params, aux, mom, loss, seen, grads = step(params, aux, mom, batch)
        losses.append(loss)
        if i == 0:
            first, raw = jax.jit(leaf_norms)(seen), jax.jit(leaf_norms)(grads)
            seen1 = seen
        del seen, grads
    change = jax.jit(lambda a, b: {k: a[k] - b[k] for k in a})(params, p0)
    host = jax.device_get
    return {"loss": [float(x) for x in host(losses)],
            "grad1": {k: float(v) for k, v in host(first).items()},
            "grad1_raw": {k: float(v) for k, v in host(raw).items()},
            "change": {k: float(v) for k, v in
                       host(jax.jit(leaf_norms)(change)).items()},
            "full": {"grad1": seen1, "change": change}}


def differences(prog, ref):
    """``prog`` with ``grad1_diff`` and ``change_diff`` added: per leaf,
    the norm of the difference between its first gradient (its change) and
    the reference's.  Both sides' ``"full"`` arrays are dropped."""
    out = {k: v for k, v in prog.items() if k != "full"}
    for what in ("grad1", "change"):
        d = jax.device_get(norms_of_diff(prog["full"][what],
                                         ref["full"][what]))
        out[what + "_diff"] = {k: float(v) for k, v in d.items()}
    return out
