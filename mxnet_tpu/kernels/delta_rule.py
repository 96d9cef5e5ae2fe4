"""Gated delta rule in chunked form (Yang et al., "Gated Delta Networks",
arXiv:2412.06464; the WY / UT-transform of "Parallelizing Linear
Transformers with the Delta Rule", arXiv:2406.06484).

Per head, with a state ``S`` (dk x dv, zero at a row's start), position
``t`` does::

    S = exp(g_t) * S
    u = (v_t - S^T k_t) * beta_t
    S = S + k_t u^T
    o_t = S^T q_t

Position by position that is T dependent rank-one updates.  In chunks of
``C`` positions the updates of one chunk collapse into matrix products:
with ``G`` the running sum of ``g`` inside the chunk and
``L = strict_lower((diag(beta) K K^T) * exp(G_i - G_j))``, the chunk's
``u`` rows solve the unit-lower-triangular system ``(I + L) u = beta * (v
- exp(G) K S0)``, so ``u = U - W S0`` with ``U = (I + L)^-1 (beta * V)`` and
``W = (I + L)^-1 (beta * exp(G) * K)``; only ``S0`` — the state entering
the chunk — is carried from chunk to chunk, by ``lax.scan``.

Pure lax, differentiable by jax; the triangular inverse has its own
backward (two products) so that its doubling steps are not saved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gated_delta_rule"]

_HIGHEST = lax.Precision.HIGHEST


def _mm(a, b):
    """Chunk-local product in float32 at full precision: these are C x C
    and C x d tiles, a thousandth of the layer's FLOPs."""
    return jnp.matmul(a, b, precision=_HIGHEST)


def _unit_lower_inverse_impl(low):
    """(I + L)^-1 for strictly lower triangular ``L`` (..., C, C): L is
    nilpotent, so the inverse is the finite sum of (-L)^i, built by
    doubling: (I - L)(I + L^2)(I + L^4)..."""
    c = low.shape[-1]
    eye = jnp.eye(c, dtype=low.dtype)
    x, p = eye - low, _mm(low, low)
    span = 2
    while span < c:
        x = x + _mm(x, p)
        span *= 2
        if span < c:
            p = _mm(p, p)
    return x


@jax.custom_vjp
def _unit_lower_inverse(low):
    return _unit_lower_inverse_impl(low)


def _uli_fwd(low):
    inv = _unit_lower_inverse_impl(low)
    return inv, inv


def _uli_bwd(inv, g):
    inv_t = jnp.swapaxes(inv, -1, -2)
    return (jnp.tril(-_mm(_mm(inv_t, g), inv_t), -1),)


_unit_lower_inverse.defvjp(_uli_fwd, _uli_bwd)


@functools.partial(jax.jit, static_argnames=("chunk",))
def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """q, k (B, T, H, dk); v (B, T, H, dv); g (log-decay, <= 0) and beta
    (B, T, H); all heads already expanded to the value heads.  Returns o
    (B, T, H, dv) in float32.  ``T`` need not be a multiple of ``chunk``:
    the tail is padded with positions that leave the state as it is."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = int(chunk)
    n = -(-T // C)
    pad = n * C - T

    def chunks(x):                     # (B, T, H, ...) -> (B, H, n, C, ...)
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, n, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                         # (B, H, n, C)
    lower = jnp.tril(jnp.ones((C, C), bool))
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kb, vb = k * beta[..., None], v * beta[..., None]
    kt = jnp.swapaxes(k, -1, -2)
    inv = _unit_lower_inverse(jnp.tril(_mm(kb, kt) * decay, -1))
    w = _mm(inv, kb * jnp.exp(gc)[..., None])           # (B, H, n, C, dk)
    u = _mm(inv, vb)                                    # (B, H, n, C, dv)
    a = _mm(q, kt) * decay                              # (B, H, n, C, C)
    qg = q * jnp.exp(gc)[..., None]
    kg = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    g_last = jnp.exp(gc[..., -1])                       # (B, H, n)

    def step(s, xs):
        w_n, u_n, a_n, qg_n, kg_n, gl_n = xs
        v_new = u_n - jnp.matmul(w_n, s)
        o_n = jnp.matmul(qg_n, s) + jnp.matmul(a_n, v_new)
        s = s * gl_n[..., None, None] + jnp.matmul(
            jnp.swapaxes(kg_n, -1, -2), v_new)
        return s, o_n

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, u, a, qg, kg, g_last))
    _, o = lax.scan(step, jnp.zeros((B, H, dk, dv), f32), xs)
    o = jnp.moveaxis(o, 0, 2)                           # (B, H, n, C, dv)
    o = jnp.moveaxis(o, 1, 3).reshape(B, n * C, H, dv)
    return o[:, :T]
