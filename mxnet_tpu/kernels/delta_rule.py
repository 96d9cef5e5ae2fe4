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
the chunk — is carried from chunk to chunk.

Two tiers, one algorithm (package docstring), and two rules: the decay
``g`` one number a head (rank 3, Gated DeltaNet) or one a key channel
(rank 4, Kimi Delta Attention: ``S = diag(exp(g_t)) S``, ``G`` a vector
over dk).  Under the vector decay ``L_ij = beta_i sum_d k_i[d] k_j[d]
exp(G_i[d] - G_j[d])`` no longer factors as a product times a decay, so
both tiers form the chunk's tiles by sub-blocks of 16 positions (no
exponent of a positive log-decay) and invert without powers of L.

- :func:`gated_delta_rule` — pure lax, differentiable by jax, every
  platform lowers it; the numeric oracle of both rules.  The scalar decay:
  every chunk's tiles at once, then a ``lax.scan`` over the chunks; the
  triangular inverse has its own backward (two products) so that its
  doubling steps are not saved.  The vector decay: the tiles inside a
  rematerialised walk (:func:`_channel_tiles`), the inverse by
  substitution (:func:`_unit_lower_inverse_blocked_impl`).
- :func:`gated_delta_net_pallas` — two kernels a rule behind one
  ``jax.custom_vjp`` each: a grid over (row, stream of heads) and,
  innermost and sequential, the row's chunks, with a chunk's tiles and
  ``S`` in VMEM.  HBM sees q, k, v, g, beta and o, and for the backward the
  state that entered each chunk and the chunk's inverse.
  ``mxtpu_delta_rule_fwd`` / ``_bwd`` (scalar decay): a stream is a key
  head and the value heads it serves; :func:`chunk_forward` is the chunk's
  map on tiles and :func:`chunk_backward` its transpose by hand.
  ``mxtpu_delta_rule_channel_fwd`` / ``_bwd`` (vector decay): a stream is
  ``128 // C`` heads side by side, each with its own q, k and decays;
  :func:`channel_chunk_forward` / :func:`channel_chunk_backward`.  The
  kernels' bodies and the tests share the chunk maps.

:func:`gated_delta_net` routes between them from platform, mesh, the rank
of ``g`` and shapes, and records each lowering as a ``kernel.route`` event:
ids ``kernel``, ``tier`` (``pallas`` / ``lax``), ``reason`` (``aligned``,
``shapes``, ``mesh``; a vector decay is no reason for the lax tier any
more) and, on the vector rule's events, ``decay`` = ``channel``.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["gated_delta_rule", "gated_delta_net", "gated_delta_net_lax",
           "gated_delta_net_pallas", "chunk_forward", "chunk_backward",
           "channel_chunk_forward", "channel_chunk_backward"]

_HIGHEST = lax.Precision.HIGHEST


def _mm(a, b):
    """Chunk-local product in float32 at full precision: these are C x C
    and C x d tiles, a thousandth of the layer's FLOPs."""
    return jnp.matmul(a, b, precision=_HIGHEST)


def _unit_lower_inverse_impl(low, order=None):
    """(I + L)^-1 for strictly lower triangular ``L`` (..., C, C): L is
    nilpotent, so the inverse is the finite sum of (-L)^i, built by
    doubling: (I - L)(I + L^2)(I + L^4)...  ``order``: L^order = 0 (the
    block length of a block-diagonal L; default C)."""
    c = order or low.shape[-1]
    eye = jnp.eye(low.shape[-1], dtype=low.dtype)
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


#: positions of a sub-block of a chunk under a decay per key channel: of
#: its decayed products and of its triangular inverse
_SUB = 16


def _unit_lower_inverse_blocked_impl(low, sub=_SUB):
    """(I + L)^-1 for strictly lower triangular ``L`` (..., C, C), without
    the doubling's powers of L: the diagonal blocks of ``sub`` rows by
    forward substitution, row after row, then pairs of neighbours merged —
    ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1, D^-1]]`` — until one
    block is left.  :func:`_unit_lower_inverse_impl` sums (-L)^i, whose
    terms reach C(C - 2, C / 2 - 1) b^i where the inverse itself stays
    under 1 (L = b x ones: slowly decaying, alike keys): in float32 they
    cancel to garbage (160 at b = 0.5, 9e10 at 0.99, C = 64).  A slow decay
    a key channel meets that case; this form has no such terms."""
    C = low.shape[-1]
    m = C // sub if C % sub == 0 else 1
    if m & (m - 1):                    # pairs need a power of two
        m = 1
    size = C // m
    eye = jnp.eye(size, dtype=low.dtype)
    x = jnp.stack([low[..., i * size:(i + 1) * size,
                       i * size:(i + 1) * size] for i in range(m)], axis=-3)
    inv = jnp.zeros_like(x)
    for i in range(size):              # rows past i are still zero
        row = eye[i] - jnp.einsum("...j,...jk->...k", x[..., i, :], inv,
                                  precision=_HIGHEST)
        inv = inv.at[..., i, :].set(row)
    parts = [inv[..., i, :, :] for i in range(m)]
    while len(parts) > 1:
        merged = []
        for i in range(0, len(parts), 2):
            a, d = parts[i], parts[i + 1]
            at = i * size
            b = low[..., at + size:at + 2 * size, at:at + size]
            zeros = jnp.zeros_like(a)
            merged.append(jnp.concatenate([
                jnp.concatenate([a, zeros], axis=-1),
                jnp.concatenate([-_mm(_mm(d, b), a), d], axis=-1)], axis=-2))
        parts, size = merged, 2 * size
    return parts[0]


@jax.custom_vjp
def _unit_lower_inverse_blocked(low):
    return _unit_lower_inverse_blocked_impl(low)


def _ulib_fwd(low):
    inv = _unit_lower_inverse_blocked_impl(low)
    return inv, inv


_unit_lower_inverse_blocked.defvjp(_ulib_fwd, _uli_bwd)


def _channel_tiles(q, k, gc, sub):
    """The chunk-local tiles under a decay per key channel: for q, k and
    the running log-decay gc (..., C, dk) the lower triangles (diagonal
    included, zero above) of ``sum_d k_i[d] k_j[d] exp(gc_i[d] - gc_j[d])``
    and of the same with ``q_i`` — the decay sits inside the contraction,
    and ``(q e^gc)(k e^-gc)^T`` would overflow.  By sub-blocks of ``sub``
    positions: a sub-block against itself takes the differences directly
    (an elementwise product summed over dk); against an earlier one it is a
    matrix product of ``x_i exp(gc_i - gc_s)`` and ``k_j exp(gc_s - gc_j)``
    about the log-decay gc_s at its own first position s, both factors <=
    1.  No exponent of a positive number is formed."""
    lead, (C, dk) = q.shape[:-2], q.shape[-2:]
    m = C // sub

    def blocks(x):
        return x.reshape(lead + (m, sub, dk))
    qb, kb, gb = blocks(q), blocks(k), blocks(gc)
    below = jnp.tril(jnp.ones((sub, sub), bool), -1)[..., None]
    diff = gb[..., :, None, :] - gb[..., None, :, :]    # (.., m, sub, sub, dk)
    # a position against itself decays by exp(0): a constant, so that no
    # cotangent goes out to gc and comes back to cancel in round-off
    near = jnp.where(below, jnp.exp(jnp.where(below, diff, 0.0)),
                     jnp.eye(sub, dtype=q.dtype)[..., None])
    kj = kb[..., None, :, :] * near
    kk_near = jnp.sum(kb[..., :, None, :] * kj, axis=-1)
    qk_near = jnp.sum(qb[..., :, None, :] * kj, axis=-1)  # (.., m, sub, sub)
    start = gb[..., :1, :]                              # (.., m, 1, dk)
    into = jnp.exp(gb - start)                          # (.., m, sub, dk)
    # k_j about every later sub-block's start: (.., m, C, dk), j before it
    before = (jnp.arange(C)[None, :] < sub * jnp.arange(m)[:, None])[..., None]
    back = start - gc[..., None, :, :]
    kfar = jnp.where(before, jnp.exp(jnp.where(before, back, 0.0)), 0.0) \
        * k[..., None, :, :]
    kfar_t = jnp.swapaxes(kfar, -1, -2)
    own = jnp.eye(m, dtype=bool)[:, None, :, None]      # (m, 1, m, 1)

    def whole(far, near_blocks):
        far = far.reshape(lead + (m, sub, m, sub))
        return jnp.where(own, near_blocks[..., :, :, None, :],
                         far).reshape(lead + (C, C))
    return (whole(_mm(kb * into, kfar_t), kk_near),
            whole(_mm(qb * into, kfar_t), qk_near))


def _channel_chunk_local(q, k, v, g, beta, sub):
    """What the chunks (..., C, d) compute before they meet the state,
    under a decay per key channel: (w, u, a, qg, kg, g_last) of the module
    docstring with ``L = strict_lower(beta_i sum_d k_i k_j e^(G_i - G_j))``."""
    gc = jnp.cumsum(g, axis=-2)
    kk, qk = _channel_tiles(q, k, gc, sub)
    inv = _unit_lower_inverse_blocked(jnp.tril(beta[..., None] * kk, -1))
    eg = jnp.exp(gc)
    w = _mm(inv, k * beta[..., None] * eg)
    u = _mm(inv, v * beta[..., None])
    kg = k * jnp.exp(gc[..., -1:, :] - gc)
    return w, u, qk, q * eg, kg, jnp.exp(gc[..., -1, :])[..., None]


def _walk_step(s, xs):
    """One chunk of the walk: ``xs`` = (w, u, a, qg, kg, g_last) of the
    chunk, from the state ``s`` (B, H, dk, dv) -> (state after, o)."""
    w_n, u_n, a_n, qg_n, kg_n, gl_n = xs
    v_new = u_n - jnp.matmul(w_n, s)
    o_n = jnp.matmul(qg_n, s) + jnp.matmul(a_n, v_new)
    s = s * gl_n + jnp.matmul(jnp.swapaxes(kg_n, -1, -2), v_new)
    return s, o_n


@functools.partial(jax.jit, static_argnames=("chunk",))
def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """q, k (B, T, H, dk); v (B, T, H, dv); beta (B, T, H); g (log-decay,
    <= 0) either (B, T, H), one decay a head, or (B, T, H, dk), one a key
    channel (the state's rows decay each at its own rate); all heads
    already expanded to the value heads.  Returns o (B, T, H, dv) in
    float32.  ``T`` need not be a multiple of ``chunk``: the tail is padded
    with positions that leave the state as it is.

    The scalar decay factors out of the chunk-local products, which are
    formed for every chunk at once before the walk.  The vector decay does
    not (:func:`_channel_tiles`): each step of its walk forms its own
    chunk's tiles and is rematerialised going backward, so that neither
    pass holds more than a chunk's sub-block differences — a step's are
    gigabytes.  (Two and four chunks a step were slower on the chip: 141
    and 153 ms forward + backward against 112-116 at 2 x 8,192 positions
    of 32 heads of 128, PR 31.)"""
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
    s0 = jnp.zeros((B, H, dk, dv), f32)
    if g.ndim == q.ndim:
        sub = _SUB if C % _SUB == 0 else C

        @jax.checkpoint
        def chunk_step(s, xs):
            return _walk_step(s, _channel_chunk_local(*xs, sub))
        _, o = lax.scan(chunk_step, s0, tuple(
            jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)))
    else:
        gc = jnp.cumsum(g, axis=-1)                     # (B, H, n, C)
        lower = jnp.tril(jnp.ones((C, C), bool))
        diff = gc[..., :, None] - gc[..., None, :]
        decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        kb, vb = k * beta[..., None], v * beta[..., None]
        kt = jnp.swapaxes(k, -1, -2)
        inv = _unit_lower_inverse(jnp.tril(_mm(kb, kt) * decay, -1))
        w = _mm(inv, kb * jnp.exp(gc)[..., None])       # (B, H, n, C, dk)
        u = _mm(inv, vb)                                # (B, H, n, C, dv)
        a = _mm(q, kt) * decay                          # (B, H, n, C, C)
        qg = q * jnp.exp(gc)[..., None]
        kg = k * jnp.exp(gc[..., -1:] - gc)[..., None]
        g_last = jnp.exp(gc[..., -1])[..., None, None]  # (B, H, n, 1, 1)
        _, o = lax.scan(_walk_step, s0, tuple(
            jnp.moveaxis(x, 2, 0) for x in (w, u, a, qg, kg, g_last)))
    o = jnp.moveaxis(o, 0, 2)                           # (B, H, n, C, dv)
    o = jnp.moveaxis(o, 1, 3).reshape(B, n * C, H, dv)
    return o[:, :T]


# ---------------------------------------------------------------------------
# The compiled tier: one chunk's map on tiles, and the two kernels that walk
# a row's chunks with the state in VMEM.
#
# A grid step works on one key head and the ``rep`` value heads it serves.
# What meets the state is stacked along rows, head-major (v, v_new, o: R =
# rep * C rows); the heads' chunk-local (C x C) tiles lie side by side along
# lanes ((C, R)), and a product with them streams C rows through the heads'
# blocks on the diagonal of one (R x R) tile: with rep * C = 128 that is
# one full MXU tile.
# ---------------------------------------------------------------------------

def _nt(a, b, precision=None):
    """a @ b^T."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _tn(a, b, precision=None):
    """a^T @ b."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _heads(x, rep):
    """The ``rep`` row blocks of a stacked tile."""
    c = x.shape[0] // rep
    return [x[r * c:(r + 1) * c] for r in range(rep)]


def _stack(blocks):
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=0)


def _total(x):
    """The sum of a tile as a (1, 1) tile."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _down(g_last, like):
    """exp of a (1, 1) log-decay as a column for the rows of ``like``
    (Mosaic broadcasts along one of lanes and sublanes at a time: down
    the rows here, along the lanes in the product that follows)."""
    return jnp.exp(jnp.broadcast_to(g_last, (like.shape[0], 1)))


def _unit(x, eps):
    """Rows of ``x`` at unit L2 length, and the factor that made them so."""
    r = lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
    return x * r, r


def _unit_bwd(unit, r, d_unit):
    return r * (d_unit - unit * jnp.sum(unit * d_unit, axis=-1,
                                        keepdims=True))


def _masks(C, rep):
    """The index masks of a chunk's tiles, R = rep * C.  (R, R): ``own``
    the heads' diagonal blocks, ``eye`` the diagonal.  (C, R), the heads'
    (C, C) blocks side by side along lanes: ``head[r]`` head r's lanes,
    ``low`` / ``strict`` the blocks' lower triangles, ``eyes`` their
    diagonals.  ``row`` (R, 1) the row index."""
    R = rep * C
    i = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    j = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    own = functools.reduce(jnp.logical_or, [
        (i >= r * C) & (i < (r + 1) * C) & (j >= r * C) & (j < (r + 1) * C)
        for r in range(rep)])
    ic = lax.broadcasted_iota(jnp.int32, (C, R), 0)
    jc = lax.broadcasted_iota(jnp.int32, (C, R), 1)
    head = [(jc >= r * C) & (jc < (r + 1) * C) for r in range(rep)]
    any_of = functools.partial(functools.reduce, jnp.logical_or)
    return dict(
        own=own, eye=i == j, head=head,
        low=any_of([h & (ic + r * C >= jc) for r, h in enumerate(head)]),
        strict=any_of([h & (ic + r * C > jc) for r, h in enumerate(head)]),
        eyes=any_of([jc == ic + r * C for r in range(rep)]),
        row=lax.broadcasted_iota(jnp.int32, (R, 1), 0))


def _side_by_side(x, own, rep):
    """The diagonal blocks of (R, R) -> (C, R), side by side."""
    return sum(_heads(jnp.where(own, x, 0.0), rep))


def _blocks(x, own):
    """(C, R) side by side -> (R, R) with the blocks on the diagonal."""
    return jnp.where(own, _stack([x] * (x.shape[1] // x.shape[0])), 0.0)


def _along(cols, head):
    """The heads' (C, 1) columns, each along its head's lanes: (C, R)."""
    out = cols[-1]
    for col, lanes in zip(cols[-2::-1], head[-2::-1]):
        out = jnp.where(lanes, col, out)
    return out


def _down_heads(x, head):
    """(C, R) side by side -> (R, 1) stacked: each head's lanes summed."""
    return _stack([jnp.sum(jnp.where(lanes, x, 0.0), axis=1, keepdims=True)
                   for lanes in head])


def _chunk_tiles(q, k, gcol, grow, bcol, rep, eps, scale, m):
    """What a chunk computes before it meets the state.  q, k (C, dk) as
    the graph has them (float32 here); gcol, bcol (R, 1) the running
    log-decay and beta of the stacked heads down rows, grow (1, R) the
    former along lanes.  The (C, C) tiles of the heads lie side by side."""
    C = q.shape[0]
    qh, rq = _unit(q, eps)
    kh, rk = _unit(k, eps)
    qs = qh * scale
    k2 = _stack([kh] * rep)
    exponent = _along(_heads(gcol, rep), m["head"]) - grow
    decay = jnp.where(m["low"],
                      jnp.exp(jnp.where(m["low"], exponent, 0.0)), 0.0)
    last = [b[C - 1:C] for b in _heads(gcol, rep)]
    gend = _stack([jnp.broadcast_to(b, (C, 1)) for b in last])
    eg, er, ec = jnp.exp(gcol), jnp.exp(gend - gcol), jnp.exp(gend)
    # every head's block of k k^T (q k^T) is the same: C rows of product
    return dict(qh=qh, rq=rq, kh=kh, rk=rk, qs=qs, q2=_stack([qs] * rep),
                k2=k2, decay=decay, kk=_nt(kh, k2, _HIGHEST),
                qk=_nt(qs, k2, _HIGHEST),
                beta=_along(_heads(bcol, rep), m["head"]),
                eg=eg, er=er, ec=ec, last=last)


def _inverse_side_by_side(low, m):
    """:func:`_unit_lower_inverse_impl` for the heads' strictly lower (C,
    C) tiles side by side (C, R): each product streams C rows through the
    heads' blocks on the diagonal of one (R, R) tile."""
    C, own = low.shape[0], m["own"]
    x, p = jnp.where(m["eyes"], 1.0, 0.0) - low, _mm(low, _blocks(low, own))
    span = 2
    while span < C:
        x = x + _mm(x, _blocks(p, own))
        span *= 2
        if span < C:
            p = _mm(p, _blocks(p, own))
    return x


def _per_head(fn, rep, *stacked):
    """``fn`` over the heads' row blocks, stacked again."""
    return _stack([fn(r, *xs) for r, xs in enumerate(
        zip(*(_heads(x, rep) for x in stacked)))])


def chunk_forward(s0, q, k, v, gcol, grow, bcol, *, rep, eps, scale,
                  masks=None):
    """One chunk of the rule for one key head: the states entering it
    ``s0`` (a list of ``rep`` (dk, dv) tiles), q, k (C, dk), v (R, dv),
    gcol, bcol (R, 1), grow (1, R) -> (o (R, dv), the states leaving it,
    the heads' unit-lower inverses side by side (C, R)).  The module
    docstring's mathematics with ``v_new = T (beta * (V - (K e^G) S0))``;
    chunk-local products at full float32 precision, products with the
    state at the default one, as the lax tier has them."""
    m = masks or _masks(q.shape[0], rep)
    t = _chunk_tiles(q, k, gcol, grow, bcol, rep, eps, scale, m)
    inverse = _inverse_side_by_side(jnp.where(
        m["strict"], t["beta"] * t["kk"] * t["decay"], 0.0), m)
    kg, qg, kr = t["k2"] * t["eg"], t["q2"] * t["eg"], t["k2"] * t["er"]
    p = _per_head(lambda r, x: jnp.matmul(x, s0[r]), rep, kg)
    v_new = _mm(_blocks(inverse, m["own"]), bcol * (v - p))
    o = _per_head(lambda r, x: jnp.matmul(x, s0[r]), rep, qg) \
        + jnp.matmul(_blocks(t["qk"] * t["decay"], m["own"]), v_new)
    s1 = [_down(c, s) * s + _tn(x, y) for s, c, x, y in zip(
        s0, t["last"], _heads(kr, rep), _heads(v_new, rep))]
    return o, s1, inverse


def chunk_backward(s0, inverse, q, k, v, gcol, grow, bcol, do, ds1, *,
                   rep, eps, scale, masks=None):
    """The transpose of :func:`chunk_forward` at ``(do, ds1)``, by hand:
    (ds0, dq, dk (C, dk), dv (R, dv), dgcol (R, 1), dgrow (1, R), dbcol
    (R, 1)).  ``inverse`` is the forward's; its derivative needs no
    product with it twice over: with ``dR = T^T dv_new``, the cotangent of
    the strict-lower tile is ``-strict(dR v_new^T)``."""
    C = q.shape[0]
    m = masks or _masks(C, rep)
    own, head = m["own"], m["head"]
    t = _chunk_tiles(q, k, gcol, grow, bcol, rep, eps, scale, m)
    decay, kk, qk, k2, q2 = t["decay"], t["kk"], t["qk"], t["k2"], t["q2"]
    eg, er, beta = t["eg"], t["er"], t["beta"]
    kg, qg, kr = k2 * eg, q2 * eg, k2 * er
    p = _per_head(lambda r, x: jnp.matmul(x, s0[r]), rep, kg)
    vmp = v - p
    inverse = _blocks(inverse, own)
    v_new = _mm(inverse, bcol * vmp)
    # s1 = ec * s0 + kr^T v_new;  o = qg s0 + (qk * decay) v_new
    d_kr = _per_head(lambda r, x: _nt(x, ds1[r]), rep, v_new)
    d_qg = _per_head(lambda r, x: _nt(x, s0[r]), rep, do)
    d_a = _side_by_side(_nt(do, v_new), own, rep)
    d_vn = _per_head(lambda r, x: jnp.matmul(x, ds1[r]), rep, kr) \
        + _tn(_blocks(qk * decay, own), do)
    # v_new = T (bcol * (v - p))
    d_r = _tn(inverse, d_vn, _HIGHEST)
    d_low = jnp.where(m["strict"],
                      -_side_by_side(_nt(d_r, v_new, _HIGHEST), own, rep), 0.0)
    dv = bcol * d_r
    d_kg = _per_head(lambda r, x: -_nt(x, s0[r]), rep, dv)
    ds0 = [_down(c, d) * d + _tn(x, y) - _tn(z, w)
           for d, c, x, y, z, w in zip(
        ds1, t["last"], _heads(qg, rep), _heads(do, rep),
        _heads(kg, rep), _heads(dv, rep))]
    # low = strict(beta * kk * decay);  a = qk * decay
    dbcol = jnp.sum(d_r * vmp, axis=-1, keepdims=True) \
        + _down_heads(d_low * kk * decay, head)
    d_kk, d_qk = d_low * beta * decay, d_a * decay
    e = (d_low * beta * kk + d_a * qk) * decay
    x_kr = jnp.sum(d_kr * kr, axis=-1, keepdims=True)
    dgcol = _down_heads(e, head) \
        + jnp.sum(d_qg * qg + d_kg * kg, axis=-1, keepdims=True) - x_kr
    dgrow = -jnp.sum(e, axis=0, keepdims=True)
    # the chunk's last row carries gend: er's and ec's exponents
    ends = [jnp.sum(x, axis=0, keepdims=True) + c[:1] * _total(d * s)
            for x, c, d, s in zip(_heads(x_kr, rep), _heads(t["ec"], rep),
                                  ds1, s0)]
    dgcol = dgcol + _per_head(
        lambda r, row: jnp.where(row == (r + 1) * C - 1, ends[r], 0.0),
        rep, m["row"])
    # k2, q2 repeat kh, qh: the heads' blocks side by side sum over heads
    # in the product
    d_kh = _mm(d_kk, k2) + sum(_heads(
        _tn(d_kk, t["kh"], _HIGHEST) + _tn(d_qk, t["qs"], _HIGHEST)
        + d_kg * eg + d_kr * er, rep))
    d_qh = _mm(d_qk, k2) + sum(_heads(d_qg * eg, rep))
    dq = _unit_bwd(t["qh"], t["rq"], scale * d_qh)
    dk = _unit_bwd(t["kh"], t["rk"], d_kh)
    return ds0, dq, dk, dv, dgcol, dgrow, dbcol


#: bytes of one grid step's blocks (each double-buffered) that decide how
#: many chunks it walks: 8 at the heads of 128 the kernels are tuned for.
#: A v5e starts a grid step in ~0.35 us, and the walk is unrolled so that
#: the scheduler lays one chunk's products into the gaps of the next's: 8
#: chunks a step took 17.9 ms forward + backward where 2 took 19.3 and 16
#: do not fit the 16 MiB of scoped VMEM (chip, PR 30)
_BLOCK_BYTES = 4 << 20


def _chunks_per_step(n, C, rep, dk, dv):
    """The largest divisor of a row's ``n`` chunks whose blocks in the
    backward kernel (the larger: states, inverse, q, k, v, do and their
    cotangents, counted at 4 bytes) stay within ``_BLOCK_BYTES``."""
    chunk = 4 * (rep * dk * dv + C * rep * C + 4 * C * dk
                 + 3 * C * rep * dv)
    fit = max(1, _BLOCK_BYTES // chunk)
    return max(d for d in range(1, min(n, fit) + 1) if n % d == 0)


def _to_col(row, eye):
    """(1, R) along lanes -> (R, 1) down rows, exactly: a select against
    the identity and a sum of zeros."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _to_row(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _read_chunk(refs, c_here, c_row, C, rep, eye):
    """One chunk's tiles, in float32: q, k (C, dk), v (R, dv) stacked,
    gcol, bcol (R, 1), grow (1, R).  ``c_here`` counts chunks inside the
    grid step's block, ``c_row`` inside the row."""
    from jax.experimental import pallas as pl
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    f32 = jnp.float32
    rows = pl.ds(pl.multiple_of(c_here * C, C), C)
    q = q_ref[0, rows, :].astype(f32)
    k = k_ref[0, rows, :].astype(f32)
    v = _stacked(v_ref[0, rows, :], rep)
    grow = g_ref[0, 0, pl.ds(c_row, 1), :]
    bcol = _to_col(b_ref[0, 0, pl.ds(c_row, 1), :], eye)
    return rows, q, k, v, _to_col(grow, eye), grow, bcol


def _along_lanes(x, rep):
    """(R, n) stacked -> (C, rep * n): the heads' row blocks along lanes."""
    blocks = _heads(x, rep)
    return blocks[0] if rep == 1 else jnp.concatenate(blocks, axis=1)


def _stacked(x, rep):
    """(C, rep * n), the heads along lanes as the graph has them -> (R, n)
    stacked, in float32."""
    n = x.shape[1] // rep
    x = x.astype(jnp.float32)
    return _stack([x[:, r * n:(r + 1) * n] for r in range(rep)])


def _fwd_kernel(*refs, rep, C, nb, eps, scale, save):
    """Forward body: the grid step's ``nb`` chunks in order (unrolled: a
    chunk's tiles and inverse wait for no state, so the scheduler lays
    them into the gaps of the chunk before), the states in ``s_scr``.
    With ``save`` each chunk also writes the states that entered it and
    its inverse, for the backward."""
    from jax.experimental import pallas as pl
    o_ref = refs[5]
    s_scr = refs[-1]
    m = _masks(C, rep)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    def body(ci, carry):
        rows, q, k, v, gcol, grow, bcol = _read_chunk(
            refs[:5], ci, t * nb + ci, C, rep, m["eye"])
        s0 = [s_scr[r] for r in range(rep)]
        o, s1, inverse = chunk_forward(
            s0, q, k, v, gcol, grow, bcol, rep=rep, eps=eps, scale=scale,
            masks=m)
        o_ref[0, rows, :] = _along_lanes(o, rep).astype(o_ref.dtype)
        for r in range(rep):
            if save:
                refs[6][0, r, ci] = s0[r]
            s_scr[r] = s1[r]
        if save:
            refs[7][0, 0, ci] = inverse
        return carry
    lax.fori_loop(0, nb, body, 0, unroll=True)


def _bwd_kernel(*refs, rep, C, nb, eps, scale):
    """Backward body: the grid step's chunks in reverse, the states'
    cotangent in ``ds_scr``."""
    from jax.experimental import pallas as pl
    s_ref, t_ref, do_ref = refs[5:8]
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref = refs[8:13]
    ds_scr = refs[-1]
    m = _masks(C, rep)
    eye = m["eye"]
    t = pl.num_programs(2) - 1 - pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def body(step, carry):
        ci = nb - 1 - step
        c_row = t * nb + ci
        rows, q, k, v, gcol, grow, bcol = _read_chunk(
            refs[:5], ci, c_row, C, rep, eye)
        s0 = [s_ref[0, r, ci] for r in range(rep)]
        inverse = t_ref[0, 0, ci]
        do = _stacked(do_ref[0, rows, :], rep)
        ds1 = [ds_scr[r] for r in range(rep)]
        ds0, dq, dk, d_v, dgcol, dgrow, dbcol = chunk_backward(
            s0, inverse, q, k, v, gcol, grow, bcol, do, ds1,
            rep=rep, eps=eps, scale=scale, masks=m)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = _along_lanes(d_v, rep).astype(dv_ref.dtype)
        dg_ref[0, 0, pl.ds(c_row, 1), :] = dgrow + _to_row(dgcol, eye)
        db_ref[0, 0, pl.ds(c_row, 1), :] = _to_row(dbcol, eye)
        for r in range(rep):
            ds_scr[r] = ds0[r]
        return carry
    lax.fori_loop(0, nb, body, 0, unroll=True)


@functools.lru_cache(maxsize=None)
def _pallas_rule(C, eps, interpret):
    """The rule over row-major operands as one ``custom_vjp``: q, k (B, T,
    Hk * dk), v (B, T, Hv * dv), grow, brow (B, Hk, n, rep * C) -> o like
    v."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def calls(q, v, grow):
        B, T, _ = q.shape
        _, Hk, n, R = grow.shape
        rep = R // C
        dk, dv = q.shape[2] // Hk, v.shape[2] // (Hk * rep)
        nb = _chunks_per_step(n, C, rep, dk, dv)
        steps = n // nb

        def specs(when):
            """Block specs of a grid step at time block ``when(t)``."""
            def rowwise(width):
                return pl.BlockSpec((1, nb * C, width),
                                    lambda b, h, t: (b, when(t), h),
                                    memory_space=pltpu.VMEM)

            def chunkwise(lead, *tail):
                zeros = (0,) * len(tail)
                return pl.BlockSpec(
                    (1, lead, nb) + tail,
                    lambda b, h, t: (b, h, when(t)) + zeros,
                    memory_space=pltpu.VMEM)
            return dict(
                q=rowwise(dk), v=rowwise(rep * dv),
                # a row's decays and betas stay for all its grid steps
                row=pl.BlockSpec((1, 1, n, R), lambda b, h, t: (b, h, 0, 0),
                                 memory_space=pltpu.VMEM),
                states=chunkwise(rep, dk, dv), inverse=chunkwise(1, C, R))
        scratch = [pltpu.VMEM((rep, dk, dv), jnp.float32)]
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        kw = dict(rep=rep, C=C, nb=nb, eps=eps, scale=dk ** -0.5)
        f32 = jnp.float32
        shape = jax.ShapeDtypeStruct
        states = shape((B, Hk * rep, n, dk, dv), f32)
        inverse = shape((B, Hk, n, C, R), f32)

        def forward(save):
            s = specs(lambda t: t)
            return pl.pallas_call(
                functools.partial(_fwd_kernel, save=save, **kw),
                grid=(B, Hk, steps),
                in_specs=[s["q"], s["q"], s["v"], s["row"], s["row"]],
                out_specs=(s["v"], s["states"], s["inverse"]) if save
                else s["v"],
                out_shape=(shape(v.shape, v.dtype), states, inverse)
                if save else shape(v.shape, v.dtype),
                scratch_shapes=scratch, compiler_params=params,
                name="mxtpu_delta_rule_fwd", interpret=interpret)

        def backward(k):
            s = specs(lambda t: steps - 1 - t)
            return pl.pallas_call(
                functools.partial(_bwd_kernel, **kw),
                grid=(B, Hk, steps),
                in_specs=[s["q"], s["q"], s["v"], s["row"], s["row"],
                          s["states"], s["inverse"], s["v"]],
                out_specs=(s["q"], s["q"], s["v"], s["row"], s["row"]),
                out_shape=(shape(q.shape, q.dtype), shape(k.shape, k.dtype),
                           shape(v.shape, v.dtype), shape(grow.shape, f32),
                           shape(grow.shape, f32)),
                scratch_shapes=scratch, compiler_params=params,
                name="mxtpu_delta_rule_bwd", interpret=interpret)
        return forward, backward

    # jitted, so that a model's layers of one shape trace and lower each
    # kernel once
    @functools.partial(jax.jit, static_argnames=("save",))
    def run_forward(q, k, v, grow, brow, save):
        return calls(q, v, grow)[0](save)(q, k, v, grow, brow)

    @jax.jit
    def run_backward(q, k, v, grow, brow, states, inverse, do):
        return calls(q, v, grow)[1](k)(q, k, v, grow, brow, states, inverse,
                                       do)

    @jax.custom_vjp
    def rule(q, k, v, grow, brow):
        return run_forward(q, k, v, grow, brow, save=False)

    def rule_fwd(q, k, v, grow, brow):
        o, states, inverse = run_forward(q, k, v, grow, brow, save=True)
        return o, (q, k, v, grow, brow, states, inverse)

    def rule_bwd(res, do):
        return run_backward(*res, do)

    rule.defvjp(rule_fwd, rule_bwd)
    return rule


def gated_delta_net_pallas(query, key, value, g, beta, chunk=64, eps=1e-6,
                           interpret=False):
    """The compiled tier of :func:`gated_delta_net` (same operands, same
    result).  The kernels take q, k and v as they are; of g and beta —
    (B, T, Hv) float32, a thousandth of the bytes — they take g's running
    sum inside each chunk and beta with a key head's value heads side by
    side along lanes ((B, Hk, n, rep * C)), and jax differentiates that
    rearrangement.  A g of rank 4 is the other rule
    (:func:`_channel_pallas`)."""
    if g.ndim == 4:
        return _channel_pallas(query, key, value, g, beta, int(chunk), eps,
                               interpret)
    B, T, Hk, dk = query.shape
    Hv, dv = value.shape[2:]
    rep, C = Hv // Hk, int(chunk)
    n = T // C

    def rows(x):                       # (B, T, Hv) -> (B, Hv, n, C)
        return jnp.transpose(x.astype(jnp.float32).reshape(B, n, C, Hv),
                             (0, 3, 1, 2))

    def side_by_side(x):               # -> (B, Hk, n, rep * C)
        x = jnp.transpose(x.reshape(B, Hk, rep, n, C), (0, 1, 3, 2, 4))
        return x.reshape(B, Hk, n, rep * C)
    run = side_by_side(jnp.cumsum(rows(g), axis=-1))
    out = _pallas_rule(C, float(eps), bool(interpret))(
        query.reshape(B, T, Hk * dk), key.reshape(B, T, Hk * dk),
        value.reshape(B, T, Hv * dv), run, side_by_side(rows(beta)))
    return out.reshape(value.shape)


# ---------------------------------------------------------------------------
# The compiled tier for a decay per key channel (Kimi Delta Attention).
#
# A grid step works on ``P`` heads, each with its own q, k and decays (one
# key head a value head).  What is a row a position is stacked head-major
# (q, k, g, v, v_new, o: R = P * C rows); the heads' chunk-local (C, C) tiles
# lie side by side along lanes ((C, R)) exactly as the scalar rule's, and a
# product with them streams C rows through one (R, R) tile.  The decay sits
# inside the contraction over dk, so the tiles are formed by sub-blocks of
# ``_SUB`` positions: a sub-block against itself diagonal by diagonal from
# the differences of the running log-decays, on operands transposed to
# (dk, R) so that the partner is a lane roll away and the sum over dk runs
# down sublanes; against an earlier sub-block as one matrix product about
# the later one's first position.  No exponent of a positive number is
# formed.
# ---------------------------------------------------------------------------

def _channel_masks(C, P, dk):
    """:func:`_masks` and the index masks of the vector rule's tiles.  (C,
    R): ``dist`` how far a row lies behind its column inside a head.  (dk,
    R): ``pos`` a lane's position inside its sub-block.  (R, R): ``tri``
    the heads' lower triangles of ones (a chunk's running sum as a
    product).  (R, 1): ``local`` the row inside its head.  ``half[h]`` (C,
    R): the lower-left (h, h) blocks of the heads' diagonal (2h, 2h)
    blocks."""
    m = _masks(C, P)
    R = P * C
    i32 = jnp.int32
    js = lax.broadcasted_iota(i32, (_SUB, R), 1)
    ic = lax.broadcasted_iota(i32, (C, R), 0)
    jl = lax.broadcasted_iota(i32, (C, R), 1) & (C - 1)
    i = lax.broadcasted_iota(i32, (R, R), 0)
    j = lax.broadcasted_iota(i32, (R, R), 1)
    half, h = {}, 1
    while h < C:
        half[h] = ((ic & (2 * h - 1)) >= h) & ((jl & (2 * h - 1)) < h) \
            & ((ic & -(2 * h)) == (jl & -(2 * h)))
        h *= 2
    m.update(
        dist=ic - jl, half=half,
        sub_head=[(js >= r * C) & (js < (r + 1) * C) for r in range(P)],
        pos=lax.broadcasted_iota(i32, (dk, R), 1) & (_SUB - 1),
        lane=lax.broadcasted_iota(i32, (dk, R), 1),
        tri=jnp.where(m["own"] & (i >= j), 1.0, 0.0),
        local=m["row"] & (C - 1))
    return m


def _channel_inverse(low, m):
    """(I + L)^-1 for the heads' strictly lower (C, C) tiles side by side
    (C, R), without powers of L (:func:`_unit_lower_inverse_blocked_impl`
    says why): the diagonal blocks of one position are 1; pairs of
    neighbours are merged — ``[[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B
    A^-1, D^-1]]`` — until one block a head is left.  With ``X`` the
    inverse of the diagonal (h, h) blocks and ``B`` the lower-left blocks
    of the (2h, 2h) ones, all heads and pairs at once: ``X - X B X``."""
    C, own = low.shape[0], m["own"]
    x = jnp.where(m["eyes"], 1.0, 0.0) - jnp.where(m["half"][1], low, 0.0)
    h = 2
    while h < C:
        b = jnp.where(m["half"][h], low, 0.0)
        x = x - _mm(_mm(x, _blocks(b, own)), _blocks(x, own))
        h *= 2
    return x


def _rows_of(x, C, P, at, n=_SUB):
    """Rows ``at .. at + n`` of every head of a stacked (R, d) tile."""
    return [x[h * C + at:h * C + at + n] for h in range(P)]


def _from_head_rows(pieces, P, like):
    """A stacked (R, d) tile from the heads' row pieces: ``pieces[b][h]``
    is head h's sub-block b, ``None`` a sub-block of zeros."""
    zero = jnp.zeros((_SUB, like.shape[1]), like.dtype)
    return _stack([zero if piece is None else piece[h]
                   for h in range(P) for piece in pieces])


def _channel_chunk_tiles(q, k, g, bcol, P, eps, scale, m, roll):
    """What a chunk computes before it meets the state, under a decay per
    key channel.  q, k, g (R, dk) of the stacked heads (float32; q, k as
    the graph has them, g the log-decay a position), bcol (R, 1).  The
    tiles ``kk`` (strictly lower) and ``qk`` (lower) of ``sum_d x_i[d]
    k_j[d] exp(G_i[d] - G_j[d])`` come out (C, R), side by side."""
    R, dk = q.shape
    C = R // P
    subs = C // _SUB
    head = m["head"]
    qh, rq = _unit(q, eps)
    kh, rk = _unit(k, eps)
    qs = qh * scale
    gc = _mm(m["tri"], g)                              # the running sum
    g_t, k_t, q_t = gc.T, kh.T, qs.T                   # (dk, R)
    # a sub-block against itself: the diagonal ``d`` positions behind
    kk = jnp.zeros((C, R), jnp.float32)
    qk = jnp.where(m["dist"] == 0,
                   jnp.sum(q_t * k_t, axis=0, keepdims=True), 0.0)
    near = []
    for d in range(1, _SUB):
        valid = m["pos"] < _SUB - d
        e = jnp.where(valid, jnp.exp(jnp.where(
            valid, roll(g_t, R - d, 1) - g_t, 0.0)), 0.0)
        ke = k_t * e
        k_later, q_later = roll(k_t, R - d, 1), roll(q_t, R - d, 1)
        hit = m["dist"] == d
        kk = jnp.where(hit, jnp.sum(k_later * ke, axis=0, keepdims=True), kk)
        qk = jnp.where(hit, jnp.sum(q_later * ke, axis=0, keepdims=True), qk)
        near.append((d, e, ke, k_later, q_later, hit))
    # against an earlier sub-block: about the later one's first position
    start = [_rows_of(gc, C, P, b * _SUB, 1) for b in range(subs)]
    into = jnp.exp(gc - _stack([
        jnp.broadcast_to(start[b][h], (_SUB, dk))
        for h in range(P) for b in range(subs)]))
    ki, qi = kh * into, qs * into
    zero = jnp.zeros((_SUB, R), jnp.float32)
    far, far_kk, far_qk, lanes = [], [zero], [zero], m["sub_head"]
    for b in range(1, subs):
        before = m["local"] < b * _SUB
        eb = jnp.where(before, jnp.exp(jnp.where(before, _stack([
            jnp.broadcast_to(start[b][h], (C, dk)) for h in range(P)]) - gc,
            0.0)), 0.0)
        right = kh * eb
        left = _stack([x for pair in zip(_rows_of(ki, C, P, b * _SUB),
                                         _rows_of(qi, C, P, b * _SUB))
                       for x in pair])                 # (2 P SUB, dk)
        prod = _heads(_nt(left, right, _HIGHEST), 2 * P)   # (SUB, R) each
        far_kk.append(_along(prod[0::2], lanes))
        far_qk.append(_along(prod[1::2], lanes))
        far.append((b, eb, right, left))
    kk, qk = kk + _stack(far_kk), qk + _stack(far_qk)
    last = _rows_of(gc, C, P, C - 1, 1)
    gend = _stack([jnp.broadcast_to(x, (C, dk)) for x in last])
    eg, er = jnp.exp(gc), jnp.exp(gend - gc)
    # a head's decay over the whole chunk, down the state's rows
    ec = [jnp.exp(jnp.sum(jnp.where(m["lane"] == (h + 1) * C - 1, g_t, 0.0),
                          axis=1, keepdims=True)) for h in range(P)]
    return dict(qh=qh, rq=rq, kh=kh, rk=rk, qs=qs, gc=gc, k_t=k_t, q_t=q_t,
                kk=kk, qk=qk, near=near, far=far, into=into, ki=ki, qi=qi,
                eg=eg, er=er, ec=ec, beta=_along(_heads(bcol, P), head))


def channel_chunk_forward(s0, q, k, v, g, bcol, *, pair, eps, scale,
                          masks=None, roll=jnp.roll):
    """One chunk of the rule under a decay per key channel, for ``pair``
    heads: the states entering it ``s0`` (a list of ``pair`` (dk, dv)
    tiles), q, k, g (R, dk), v (R, dv), bcol (R, 1), all stacked head-major
    -> (o (R, dv), the states leaving it, the heads' unit-lower inverses
    side by side (C, R)).  :func:`_channel_chunk_local` and
    :func:`_walk_step` on tiles, with ``v_new = T (beta * (V - (K e^G)
    S0))``; chunk-local products at full float32 precision, products with
    the state at the default one, as the lax tier has them.  ``roll`` is
    ``jnp.roll`` or, inside a kernel, the TPU's."""
    P = pair
    m = masks or _channel_masks(q.shape[0] // P, P, q.shape[1])
    t = _channel_chunk_tiles(q, k, g, bcol, P, eps, scale, m, roll)
    inverse = _channel_inverse(
        jnp.where(m["strict"], t["beta"] * t["kk"], 0.0), m)
    kg, qg, kr = t["kh"] * t["eg"], t["qs"] * t["eg"], t["kh"] * t["er"]
    p = _per_head(lambda r, x: jnp.matmul(x, s0[r]), P, kg)
    v_new = _mm(_blocks(inverse, m["own"]), bcol * (v - p))
    o = _per_head(lambda r, x: jnp.matmul(x, s0[r]), P, qg) \
        + jnp.matmul(_blocks(t["qk"], m["own"]), v_new)
    s1 = [c * s + _tn(x, y) for s, c, x, y in zip(
        s0, t["ec"], _heads(kr, P), _heads(v_new, P))]
    return o, s1, inverse


def channel_chunk_backward(s0, inverse, q, k, v, g, bcol, do, ds1, *, pair,
                           eps, scale, masks=None, roll=jnp.roll):
    """The transpose of :func:`channel_chunk_forward` at ``(do, ds1)``, by
    hand: (ds0, dq, dk, dg (R, dk), dv (R, dv), dbcol (R, 1)).  ``inverse``
    is the forward's (:func:`chunk_backward` says how its derivative goes).
    The first position of a sub-block, about which the products with
    earlier sub-blocks are formed, gets no cotangent: the tiles do not
    depend on it."""
    P = pair
    R, dk = q.shape
    C = R // P
    m = masks or _channel_masks(C, P, dk)
    own, head = m["own"], m["head"]
    t = _channel_chunk_tiles(q, k, g, bcol, P, eps, scale, m, roll)
    kh, qs, kk, qk, eg, er = (t[n] for n in ("kh", "qs", "kk", "qk", "eg",
                                             "er"))
    kg, qg, kr = kh * eg, qs * eg, kh * er
    p = _per_head(lambda r, x: jnp.matmul(x, s0[r]), P, kg)
    vmp = v - p
    inverse = _blocks(inverse, own)
    v_new = _mm(inverse, bcol * vmp)
    # s1 = ec * s0 + kr^T v_new;  o = qg s0 + qk v_new
    d_kr = _per_head(lambda r, x: _nt(x, ds1[r]), P, v_new)
    d_qg = _per_head(lambda r, x: _nt(x, s0[r]), P, do)
    d_qk = jnp.where(m["low"], _side_by_side(_nt(do, v_new), own, P), 0.0)
    d_vn = _per_head(lambda r, x: jnp.matmul(x, ds1[r]), P, kr) \
        + _tn(_blocks(qk, own), do)
    # v_new = T (bcol * (v - p))
    d_r = _tn(inverse, d_vn, _HIGHEST)
    d_low = jnp.where(m["strict"],
                      -_side_by_side(_nt(d_r, v_new, _HIGHEST), own, P), 0.0)
    dv = bcol * d_r
    d_kg = _per_head(lambda r, x: -_nt(x, s0[r]), P, dv)
    ds0 = [c * d + _tn(x, y) - _tn(z, w) for d, c, x, y, z, w in zip(
        ds1, t["ec"], _heads(qg, P), _heads(do, P), _heads(kg, P),
        _heads(dv, P))]
    dbcol = jnp.sum(d_r * vmp, axis=-1, keepdims=True) \
        + _down_heads(d_low * kk, head)
    d_kk = d_low * t["beta"]
    # what enters by rows: kg, qg = (k, q) e^G;  kr = k e^(G_end - G)
    x_kr = d_kr * kr
    d_kh = d_kg * eg + d_kr * er
    d_qs = d_qg * eg
    d_gc = d_kg * kg + d_qg * qg - x_kr
    ends = [jnp.sum(x, axis=0, keepdims=True) for x in _heads(x_kr, P)]
    d_gc = d_gc + _per_head(
        lambda r, row: jnp.where(row == C - 1, ends[r], 0.0), P, m["local"])
    # the tiles against earlier sub-blocks
    rows_k, rows_q, rows_g = [None], [None], [None]
    for b, eb, right, left in t["far"]:
        at = b * _SUB
        d_prod = _stack([jnp.where(lanes, x[at:at + _SUB], 0.0)
                         for lanes in m["sub_head"] for x in (d_kk, d_qk)])
        d_left = _mm(d_prod, right)
        d_right = _tn(d_prod, left, _HIGHEST)
        d_kh = d_kh + d_right * eb
        d_gc = d_gc - d_right * right
        through = _heads(d_left * left, 2 * P)
        rows_g.append([x + y for x, y in zip(through[0::2], through[1::2])])
        d_left, into = _heads(d_left, 2 * P), _rows_of(t["into"], C, P, at)
        rows_k.append([x * i for x, i in zip(d_left[0::2], into)])
        rows_q.append([x * i for x, i in zip(d_left[1::2], into)])
    if len(rows_k) > 1:
        d_kh = d_kh + _from_head_rows(rows_k, P, kh)
        d_qs = d_qs + _from_head_rows(rows_q, P, kh)
        d_gc = d_gc + _from_head_rows(rows_g, P, kh)
    # a sub-block against itself, and the decay over the chunk: by lanes
    d_kt = jnp.zeros_like(t["k_t"])
    d_qt = jnp.zeros_like(d_kt)
    d_gt = jnp.zeros_like(d_kt)
    for h in range(P):
        end = t["ec"][h] * jnp.sum(ds1[h] * s0[h], axis=1, keepdims=True)
        d_gt = jnp.where(m["lane"] == (h + 1) * C - 1, d_gt + end, d_gt)
    x0 = jnp.sum(jnp.where(m["dist"] == 0, d_qk, 0.0), axis=0, keepdims=True)
    d_qt = d_qt + x0 * t["k_t"]
    d_kt = d_kt + x0 * t["q_t"]
    for d, e, ke, k_later, q_later, hit in t["near"]:
        xk = jnp.sum(jnp.where(hit, d_kk, 0.0), axis=0, keepdims=True)
        xq = jnp.sum(jnp.where(hit, d_qk, 0.0), axis=0, keepdims=True)
        a = xk * k_later + xq * q_later
        through = a * ke
        d_kt = d_kt + a * e + roll(xk * ke, d, 1)
        d_qt = d_qt + roll(xq * ke, d, 1)
        d_gt = d_gt - through + roll(through, d, 1)
    dg = _tn(m["tri"], d_gc + d_gt.T, _HIGHEST)
    dq = _unit_bwd(t["qh"], t["rq"], scale * (d_qs + d_qt.T))
    dk_ = _unit_bwd(t["kh"], t["rk"], d_kh + d_kt.T)
    return ds0, dq, dk_, dg, dv, dbcol


def _channel_heads_per_step(C):
    """Heads a grid step of the vector rule lays side by side: as many as
    make its (C, R) tiles a whole lane tile wide (two at chunks of 64)."""
    return max(1, 128 // C)


def _channel_chunks_per_step(n, C, P, dk, dv):
    """:func:`_chunks_per_step` for the vector rule's backward kernel:
    states, inverse, q, k, g, v, do and their cotangents, counted at 4
    bytes, within half of ``_BLOCK_BYTES``: a chunk's tiles — the running
    log-decays, their transposes and a sub-block's fifteen diagonals, (dk,
    R) float32 each — take VMEM beside the blocks.  2 chunks a step at
    two heads of 128: 16.0 ms forward and 41.1 forward + backward where 4
    took 15.4 / 41.1 and 1 took 17.2 / 42.2; 8 ask for 16.12 MiB of the 16
    of scoped VMEM (chip, PR 32)."""
    R = P * C
    chunk = 4 * (P * dk * dv + C * R + 6 * R * dk + 3 * R * dv)
    fit = max(1, (_BLOCK_BYTES // 2) // chunk)
    return max(d for d in range(1, min(n, fit) + 1) if n % d == 0)


def _read_channel_chunk(refs, c_here, c_row, C, P, eye):
    """One chunk's tiles, in float32, stacked head-major: q, k, g (R, dk),
    v (R, dv), bcol (R, 1)."""
    from jax.experimental import pallas as pl
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    rows = pl.ds(pl.multiple_of(c_here * C, C), C)
    bcol = _to_col(b_ref[0, 0, pl.ds(c_row, 1), :], eye)
    return (rows,) + tuple(_stacked(ref[0, rows, :], P)
                           for ref in (q_ref, k_ref, v_ref, g_ref)) + (bcol,)


def _channel_fwd_kernel(*refs, P, C, nb, eps, scale, save):
    """:func:`_fwd_kernel` for a decay per key channel: ``P`` heads a grid
    step, each with its own state in ``s_scr``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    o_ref = refs[5]
    s_scr = refs[-1]
    m = _channel_masks(C, P, refs[0].shape[2] // P)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    def body(ci, carry):
        rows, q, k, v, g, bcol = _read_channel_chunk(
            refs[:5], ci, t * nb + ci, C, P, m["eye"])
        s0 = [s_scr[r] for r in range(P)]
        o, s1, inverse = channel_chunk_forward(
            s0, q, k, v, g, bcol, pair=P, eps=eps, scale=scale, masks=m,
            roll=pltpu.roll)
        o_ref[0, rows, :] = _along_lanes(o, P).astype(o_ref.dtype)
        for r in range(P):
            if save:
                refs[6][0, r, ci] = s0[r]
            s_scr[r] = s1[r]
        if save:
            refs[7][0, 0, ci] = inverse
        return carry
    lax.fori_loop(0, nb, body, 0, unroll=True)


def _channel_bwd_kernel(*refs, P, C, nb, eps, scale):
    """:func:`_bwd_kernel` for a decay per key channel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s_ref, t_ref, do_ref = refs[5:8]
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref = refs[8:13]
    ds_scr = refs[-1]
    m = _channel_masks(C, P, refs[0].shape[2] // P)
    eye = m["eye"]
    t = pl.num_programs(2) - 1 - pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    def body(step, carry):
        ci = nb - 1 - step
        c_row = t * nb + ci
        rows, q, k, v, g, bcol = _read_channel_chunk(
            refs[:5], ci, c_row, C, P, eye)
        s0 = [s_ref[0, r, ci] for r in range(P)]
        do = _stacked(do_ref[0, rows, :], P)
        ds1 = [ds_scr[r] for r in range(P)]
        ds0, dq, dk, dg, d_v, dbcol = channel_chunk_backward(
            s0, t_ref[0, 0, ci], q, k, v, g, bcol, do, ds1, pair=P, eps=eps,
            scale=scale, masks=m, roll=pltpu.roll)
        for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, d_v),
                       (dg_ref, dg)):
            ref[0, rows, :] = _along_lanes(x, P).astype(ref.dtype)
        db_ref[0, 0, pl.ds(c_row, 1), :] = _to_row(dbcol, eye)
        for r in range(P):
            ds_scr[r] = ds0[r]
        return carry
    lax.fori_loop(0, nb, body, 0, unroll=True)


@functools.lru_cache(maxsize=None)
def _pallas_channel_rule(C, eps, interpret):
    """The rule under a decay per key channel over row-major operands as
    one ``custom_vjp``: q, k, g (B, T, H * dk), v (B, T, H * dv), brow (B,
    H / P, n, P * C) -> o like v."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    P = _channel_heads_per_step(C)

    def calls(q, v, brow):
        B, T, _ = q.shape
        _, streams, n, R = brow.shape
        H = streams * P
        dk, dv = q.shape[2] // H, v.shape[2] // H
        nb = _channel_chunks_per_step(n, C, P, dk, dv)
        steps = n // nb

        def specs(when):
            """Block specs of a grid step at time block ``when(t)``."""
            def rowwise(width):
                return pl.BlockSpec((1, nb * C, P * width),
                                    lambda b, h, t: (b, when(t), h),
                                    memory_space=pltpu.VMEM)

            def chunkwise(lead, *tail):
                zeros = (0,) * len(tail)
                return pl.BlockSpec(
                    (1, lead, nb) + tail,
                    lambda b, h, t: (b, h, when(t)) + zeros,
                    memory_space=pltpu.VMEM)
            return dict(
                q=rowwise(dk), v=rowwise(dv),
                # a row's betas stay for all its grid steps
                row=pl.BlockSpec((1, 1, n, R), lambda b, h, t: (b, h, 0, 0),
                                 memory_space=pltpu.VMEM),
                states=chunkwise(P, dk, dv), inverse=chunkwise(1, C, R))
        scratch = [pltpu.VMEM((P, dk, dv), jnp.float32)]
        params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
        kw = dict(P=P, C=C, nb=nb, eps=eps, scale=dk ** -0.5)
        f32 = jnp.float32
        shape = jax.ShapeDtypeStruct
        states = shape((B, H, n, dk, dv), f32)
        inverse = shape((B, streams, n, C, R), f32)

        def forward(save):
            s = specs(lambda t: t)
            return pl.pallas_call(
                functools.partial(_channel_fwd_kernel, save=save, **kw),
                grid=(B, streams, steps),
                in_specs=[s["q"], s["q"], s["v"], s["q"], s["row"]],
                out_specs=(s["v"], s["states"], s["inverse"]) if save
                else s["v"],
                out_shape=(shape(v.shape, v.dtype), states, inverse)
                if save else shape(v.shape, v.dtype),
                scratch_shapes=scratch, compiler_params=params,
                name="mxtpu_delta_rule_channel_fwd", interpret=interpret)

        def backward(k):
            s = specs(lambda t: steps - 1 - t)
            return pl.pallas_call(
                functools.partial(_channel_bwd_kernel, **kw),
                grid=(B, streams, steps),
                in_specs=[s["q"], s["q"], s["v"], s["q"], s["row"],
                          s["states"], s["inverse"], s["v"]],
                out_specs=(s["q"], s["q"], s["v"], s["q"], s["row"]),
                out_shape=(shape(q.shape, q.dtype), shape(k.shape, k.dtype),
                           shape(v.shape, v.dtype), shape(q.shape, f32),
                           shape(brow.shape, f32)),
                scratch_shapes=scratch, compiler_params=params,
                name="mxtpu_delta_rule_channel_bwd", interpret=interpret)
        return forward, backward

    @functools.partial(jax.jit, static_argnames=("save",))
    def run_forward(q, k, v, g, brow, save):
        return calls(q, v, brow)[0](save)(q, k, v, g, brow)

    @jax.jit
    def run_backward(q, k, v, g, brow, states, inverse, do):
        return calls(q, v, brow)[1](k)(q, k, v, g, brow, states, inverse, do)

    @jax.custom_vjp
    def rule(q, k, v, g, brow):
        return run_forward(q, k, v, g, brow, save=False)

    def rule_fwd(q, k, v, g, brow):
        o, states, inverse = run_forward(q, k, v, g, brow, save=True)
        return o, (q, k, v, g, brow, states, inverse)

    def rule_bwd(res, do):
        return run_backward(*res, do)

    rule.defvjp(rule_fwd, rule_bwd)
    return rule


def _channel_pallas(query, key, value, g, beta, C, eps, interpret):
    """:func:`gated_delta_net_pallas` for g (B, T, H, dk): the kernels take
    q, k, v and g as they are — the running sum inside a chunk is a product
    with a triangle of ones there, and so is its transpose — and beta with
    a grid step's heads side by side along lanes ((B, H / P, n, P * C))."""
    B, T, H, dk = query.shape
    dv = value.shape[-1]
    P = _channel_heads_per_step(C)
    n = T // C
    brow = jnp.transpose(
        beta.astype(jnp.float32).reshape(B, n, C, H // P, P),
        (0, 3, 1, 4, 2)).reshape(B, H // P, n, P * C)
    out = _pallas_channel_rule(C, float(eps), bool(interpret))(
        query.reshape(B, T, H * dk), key.reshape(B, T, H * dk),
        value.reshape(B, T, H * dv),
        g.astype(jnp.float32).reshape(B, T, H * dk), brow)
    return out.reshape(value.shape)


def gated_delta_net_lax(query, key, value, g, beta, chunk=64, eps=1e-6):
    """The lax tier of :func:`gated_delta_net`: q and k normalised and
    shared out to the value heads, then :func:`gated_delta_rule`."""
    f32 = jnp.float32
    rep = value.shape[2] // query.shape[2]

    def unit(x):
        x = x.astype(f32)
        x = x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                          + float(eps))
        return jnp.repeat(x, rep, axis=2) if rep > 1 else x

    q = unit(query) * (query.shape[-1] ** -0.5)
    out = gated_delta_rule(q, unit(key), value, g, beta, chunk=int(chunk))
    return out.astype(value.dtype)


def _lax_reason(query, value, g, chunk):
    """Why these operands are not the compiled tier's, or None."""
    from . import partitioned
    (_, T, Hk, dk), (Hv, dv) = query.shape, value.shape[2:]
    C = int(chunk)
    if partitioned():
        return "mesh"
    if dk % 128 or dv % 128 or Hv % Hk or T % C or C % 16:
        return "shapes"
    # a decay per key channel: one key head a value head, a power of two of
    # positions a chunk and whole grid steps of heads
    if g.ndim == 4 and (Hv != Hk or C & (C - 1) or C > 128
                        or Hv % _channel_heads_per_step(C)):
        return "shapes"
    return None


def gated_delta_net(query, key, value, g, beta, chunk=64, eps=1e-6):
    """The gated delta rule on operands as the graph has them: query, key
    (B, T, Hk, dk), value (B, T, Hv, dv), beta (B, T, Hv) and the log-decay
    g (<= 0), (B, T, Hv) or — a decay per key channel — (B, T, Hv, dk),
    each key head serving ``Hv // Hk`` consecutive value heads; query and
    key are L2-normalised per head (``eps``) and the query scaled by
    dk^-0.5.  Returns o like value.

    Which tier runs follows from what the trace can see: the compiled
    kernels in a program lowered for a TPU, for lane-aligned heads and
    whole chunks (and, under a decay per key channel, one key head a value
    head, chunks of 16 to 128 positions by halving and whole grid steps of
    heads: :func:`_lax_reason`); the lax tier on other platforms, for other
    shapes (it pads the tail) and in a program the SPMD partitioner will
    split.  Each call records one ``kernel.route`` event in the program's
    recorder with the kernel, the tier, the reason (``aligned``,
    ``mesh``, ``shapes``) and, for a decay per key channel, ``decay`` =
    ``channel`` (an event without it is the scalar rule's)."""
    from .. import profiler
    from . import by_platform
    reason = _lax_reason(query, value, g, chunk)
    tier = "lax" if reason else "pallas"
    now = time.perf_counter_ns()
    ids = dict(kernel="delta_rule", tier=tier, reason=reason or "aligned")
    if g.ndim == 4:
        ids["decay"] = "channel"
    profiler.event("kernel.route", now, now, **ids)
    profiler.count("kernel.delta_rule." + tier)
    lax_fn = functools.partial(gated_delta_net_lax, chunk=chunk, eps=eps)
    if reason:
        return lax_fn(query, key, value, g, beta)
    return by_platform(
        functools.partial(gated_delta_net_pallas, chunk=chunk, eps=eps),
        lax_fn, query, key, value, g, beta)
