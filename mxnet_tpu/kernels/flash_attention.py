"""Flash attention — tiled online-softmax attention.

Plain attention materializes the (T x T) score and probability matrices:
4 extra memory passes over B*H*T^2 elements that dwarf the useful q/k/v
traffic for long sequences.  The flash formulation (Dao et al., 2022)
streams over key blocks keeping a running (max, sum-of-exp, accumulator)
triple per query row — nothing quadratic ever exists.

Shared core: :func:`online_update` is ONE streaming-softmax accumulation
step.  The lax flash scan uses it per key block, and
``parallel/ring_attention.py`` composes with it per ring hop — ring
attention IS this kernel's accumulation run across devices, so the two
paths cannot drift numerically.

Tiers (package docstring):

- :func:`flash_attention_lax` — ``lax.scan`` over key blocks; pure lax,
  differentiable by jax (the scan transposes to the standard recompute
  backward), O(T) memory.
- :func:`gqa_attention` — causal grouped-query attention (each
  key/value head serves ``Hq // Hkv`` query heads, the value heads as
  wide as they like).  The language models route here, and it has two
  tiers of its own, chosen from what the trace can see
  (:func:`_gqa_lax_reason`; each call records a ``kernel.route`` event:
  kernel ``gqa_attention``, tier ``pallas`` / ``lax``, reason ``aligned``
  / ``shapes`` / ``mesh``):

  - compiled (:func:`gqa_attention_pallas`), in a program lowered for a
    TPU: ``mxtpu_gqa_attention_fwd`` and ``mxtpu_gqa_attention_bwd``
    under one ``jax.custom_vjp``.  A grid step meets a block of keys with
    a block of query rows of all the heads its key head serves; score
    tile, running softmax and — in the one backward kernel — dq of the
    row block and dk, dv of the whole (row, key head) stay in VMEM; only
    blocks at or below the diagonal are visited, only those it crosses
    masked.  For positions in whole blocks of 128, value heads of whole
    lane tiles and query / key heads that are (128, 256) or pad to one by
    a third at most (192 -> 256, zeros);
  - pure lax with its OWN backward (``jax.custom_vjp``: the saved
    residuals are q, k, v, the output and one log-sum-exp a row; the
    probabilities are recomputed a query block at a time), so neither
    pass ever holds more than one (block x prefix) tile of scores: any
    shape, any platform, a mesh, and the oracle of the compiled tier.

  On either the bf16 gradient is finite (masked scores are a large
  finite negative, never ``-inf``), contractions take the operands' dtype
  into float32 and the softmax is float32.  Either takes a ``window``
  (sliding-window attention: a row sees that many keys up to its own): the
  compiled schedule then skips the key blocks outside it, the lax tier
  slices them off, and unset it is the program it was.
- :func:`flash_attention_pallas` — a ``pl.pallas_call`` kernel (grid
  over batch x heads x query blocks x key blocks, the running triple in
  VMEM scratch across the key axis) behind ``jax.custom_vjp``; the
  registered backward recomputes through the fused-lax tier (O(T)
  memory, the FlashAttention recompute discipline) — Pallas has no
  reverse-mode transpose (rtc.py contract; mxlint ``graph-pallas-no-vjp``
  polices unprotected kernels).

Numerics: the streaming softmax reassociates the sum of exponentials, so
parity with :func:`~mxnet_tpu.parallel.ring_attention.full_attention` is
tolerance-checked (f32 ~1e-5 relative), not bitwise — the documented
tolerance in tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .delta_rule import _nt, _tn

__all__ = ["flash_attention", "flash_attention_lax",
           "flash_attention_pallas", "online_update", "default_block",
           "gqa_attention", "gqa_attention_pallas"]


def default_block():
    from ..base import get_env
    from . import ENV_FLASH_BLOCK
    try:
        return max(8, int(get_env(ENV_FLASH_BLOCK, 128)))
    except (TypeError, ValueError):
        return 128


def online_update(acc, m_run, s_run, q, k, v, scale, mask):
    """One streaming-softmax accumulation step.

    ``acc`` (B, Tq, H, D) f32, ``m_run``/``s_run`` (B, H, Tq); ``q``
    (B, Tq, H, D); ``k``/``v`` (B, Tk, H, D); ``mask`` broadcastable to
    (B, H, Tq, Tk), True = attend.  Returns the updated triple.  Shared
    verbatim by the flash scan (per key block) and ring attention (per
    ring hop) so the two compositions stay numerically identical.
    """
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = jnp.where(mask, scores, -jnp.inf)
    m_blk = jnp.max(scores, axis=-1)
    # guard fully-masked rows
    m_safe = jnp.where(jnp.isfinite(m_blk), m_blk, 0.0)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(mask, p, 0.0)
    s_blk = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    # rescale both running state and the new block to the common max; a
    # fully-masked block (s_blk == 0) must not move the running max
    m_new = jnp.maximum(m_run, jnp.where(s_blk > 0, m_safe, m_run))
    alpha = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_new), 0.0)
    beta = jnp.where(jnp.isfinite(m_blk) & (s_blk > 0),
                     jnp.exp(m_safe - m_new), 0.0)
    s_new = s_run * alpha + s_blk * beta
    acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + \
        out.astype(acc.dtype) * beta.transpose(0, 2, 1)[..., None]
    return acc_new, m_new, s_new


def _finalize(acc, s_run, dtype):
    s = jnp.maximum(s_run, 1e-20)
    return (acc / s.transpose(0, 2, 1)[..., None]).astype(dtype)


def flash_attention_lax(q, k, v, causal=False, scale=None, block_k=None):
    """Tiled online-softmax attention in pure lax: ``lax.scan`` over key
    blocks.  q/k/v (B, T, H, D) -> (B, Tq, H, D).  Memory O(B*T*H*D) —
    the (Tq x Tk) score matrix never materializes beyond one
    (Tq x block_k) tile."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale or (1.0 / np.sqrt(D))
    bk = min(block_k or default_block(), Tk)
    nk = -(-Tk // bk)
    pad = nk * bk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (nk, B, bk, H, D) blocks for the scan
    kb = jnp.moveaxis(k.reshape(B, nk, bk, H, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, bk, H, D), 1, 0)
    # absolute positions: q row i attends k col j iff j - i <= Tk - Tq
    # (the full_attention tril convention)
    q_pos = jnp.arange(Tq) + (Tk - Tq)

    acc0 = jnp.zeros((B, Tq, H, D), dtype=jnp.float32)
    m0 = jnp.full((B, H, Tq), -jnp.inf)
    s0 = jnp.zeros((B, H, Tq))

    def body(carry, blk):
        acc, m_run, s_run, idx = carry
        kblk, vblk = blk
        k_pos = idx * bk + jnp.arange(bk)
        valid = k_pos < Tk                                # padding tail
        if causal:
            mask = (q_pos[:, None] >= k_pos[None, :]) & valid[None, :]
        else:
            mask = jnp.broadcast_to(valid[None, :], (Tq, bk))
        acc, m_run, s_run = online_update(
            acc, m_run, s_run, q, kblk, vblk, scale, mask[None, None])
        return (acc, m_run, s_run, idx + 1), None

    (acc, _, s_run, _), _ = lax.scan(body, (acc0, m0, s0, 0), (kb, vb))
    return _finalize(acc, s_run, q.dtype)


# ---------------------------------------------------------------------------
# Pallas tier
# ---------------------------------------------------------------------------

#: score of a masked position: finite, so the running max never needs an
#: is-finite test (a fully masked row keeps s_run == 0 and outputs 0)
_MASKED = -1e30


def _flash_kernel(causal, scale, Tq, Tk, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, s_ref):
    """One (batch, head, q-block, k-block) program.  The k-block axis is
    the innermost grid axis: the running (acc, m, s) triple lives in VMEM
    scratch across it and the output block is written on its last step.
    ``Tq``/``Tk`` are the TRUE (unpadded) lengths — causal offsets must
    not see the block padding."""
    from jax.experimental import pallas as pl

    bq, bk = q_ref.shape[0], k_ref.shape[0]
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        s_ref[...] = jnp.zeros_like(s_ref)

    q = q_ref[...].astype(jnp.float32)                  # (bq, D)
    kb = k_ref[...].astype(jnp.float32)                 # (bk, D)
    vb = v_ref[...].astype(jnp.float32)
    k_pos = kj * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = k_pos < Tk
    if causal:
        q_pos = pl.program_id(2) * bq + (Tk - Tq) \
            + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        mask = mask & (q_pos >= k_pos)
    # q @ k^T as a transposed-rhs contraction (no in-kernel transpose)
    scores = lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask, scores, _MASKED)
    m_run = m_ref[...]
    m_new = jnp.maximum(m_run, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_run - m_new)
    s_ref[...] = s_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + \
        jnp.dot(p, vb, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / jnp.maximum(s_ref[...], 1e-20)) \
            .astype(o_ref.dtype)


def _flash_pallas_fwd(q, k, v, causal, scale, block, interpret):
    """pallas_call over a (B, H, nq, nk) grid in (B, H, T, D) layout:
    q/o blocks of ``block`` rows, K/V streamed in ``block``-row blocks."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    bq = min(block, Tq)
    nq = -(-Tq // bq)
    pad_q = nq * bq - Tq
    qt = jnp.moveaxis(q, 1, 2)                          # (B, H, Tq, D)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    bk = min(block, Tk)
    nk = -(-Tk // bk)
    pad_k = nk * bk - Tk
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))

    def spec(rows, index_map):
        return pl.BlockSpec((None, None, rows, D), index_map,
                            memory_space=pltpu.VMEM)
    q_spec = spec(bq, lambda b, h, i, j: (b, h, i, 0))
    kv_spec = spec(bk, lambda b, h, i, j: (b, h, j, 0))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal, scale, Tq, Tk),
        out_shape=jax.ShapeDtypeStruct((B, H, nq * bq, D), q.dtype),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="mxtpu_flash_attention_fwd",
        interpret=interpret)(qt, kt, vt)
    if pad_q:
        out = out[:, :, :Tq, :]
    return jnp.moveaxis(out, 2, 1)                      # (B, Tq, H, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_pallas(q, k, v, causal, scale, block, interpret):
    return _flash_pallas_fwd(q, k, v, causal, scale, block, interpret)


def _fp_fwd(q, k, v, causal, scale, block, interpret):
    return _flash_pallas_fwd(q, k, v, causal, scale, block, interpret), \
        (q, k, v)


def _fp_bwd(causal, scale, block, interpret, res, g):
    # registered backward: recompute through the fused-lax tier — O(T)
    # memory, no quadratic residuals (the FlashAttention recompute rule)
    q, k, v = res
    _, vjp_fn = jax.vjp(
        lambda a, b, c: flash_attention_lax(a, b, c, causal=causal,
                                            scale=scale, block_k=block),
        q, k, v)
    return vjp_fn(g)


_flash_pallas.defvjp(_fp_fwd, _fp_bwd)


def flash_attention_pallas(q, k, v, causal=False, scale=None, block=None,
                           interpret=False):
    """Pallas-tier flash attention (custom_vjp registered).
    ``interpret=True`` runs the same kernel in the Pallas interpreter
    (the CPU tests); the default compiles it with Mosaic."""
    D = q.shape[-1]
    scale = scale or (1.0 / np.sqrt(D))
    return _flash_pallas(q, k, v, bool(causal), float(scale),
                         int(block or default_block()), bool(interpret))


def flash_attention(q, k, v, causal=False, scale=None, block=None):
    """Platform-routed flash attention: the compiled Pallas kernel in a
    program lowered for a TPU, the lax scan anywhere else.  Same
    contract as :func:`~mxnet_tpu.parallel.ring_attention.full_attention`."""
    from . import by_platform
    return by_platform(
        functools.partial(flash_attention_pallas, causal=causal,
                          scale=scale, block=block),
        functools.partial(flash_attention_lax, causal=causal, scale=scale,
                          block_k=block),
        q, k, v)


# ---------------------------------------------------------------------------
# causal grouped-query attention, lax tier with its own backward
# ---------------------------------------------------------------------------

def _gqa_blocks(T, block_q):
    bq = min(int(block_q), T)
    return bq, -(-T // bq)


#: float32 score tiles of ALL query blocks together past which the blocks
#: are chained.  The blocks do not depend on one another, and the TPU
#: scheduler then holds every block's tile at once: at 32 heads x 2 rows of
#: 8,192 positions that is 8.6 GB of a 16 GB chip, and the step does not
#: compile (PR 31); at 16 heads it is 4.3 GB and fits.  Chained where it
#: need not be, a step holds 1.5 GB less and runs 1.2 % slower (the
#: scheduler can no longer start a block under the one before it: the
#: 16-head cell read 30,604 -> 30,244 tok/s in 2 of 2 pairs, PR 31), so
#: the blocks are chained only where they must be.
_CHAIN_BYTES = 6 << 30


def _gqa_chained(B, Hq, T):
    return 2 * B * Hq * T * T > _CHAIN_BYTES          # 4 bytes x T^2 / 2


def _after(x, done):
    """``x``, not to be computed before ``done`` is: one query block's
    operand held back until the block before it has finished."""
    return x if done is None else lax.optimization_barrier((x, done))[0]


def _gqa_window(window, T):
    """``window`` as the tiers take it: None where every row sees its
    whole causal prefix (0, None, or ``window`` keys and more than there
    are positions), else the count of keys a row sees, its own
    included."""
    window = int(window or 0)
    if window < 0:
        raise ValueError("gqa_attention: window %d" % window)
    return window if 0 < window < T else None


def _gqa_span(i, bq, T, window):
    """Rows [lo, hi) of query block ``i`` and the first key its first row
    sees: the block meets keys [first, hi)."""
    lo, hi = i * bq, min((i + 1) * bq, T)
    return lo, hi, 0 if window is None else max(0, lo - window + 1)


def _gqa_scores(qi, k, i, bq, scale, first=0, window=None):
    """Scores of query block ``i`` against the keys it meets — its causal
    prefix, from key ``first`` on —, f32: qi (B, bq, Hkv, G, D), k (B, Lk,
    Hkv, D) -> (B, Hkv, G, bq, Lk).  With a ``window`` the keys more than
    ``window - 1`` before a row are masked too, where the block's last row
    has any."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k,
                   preferred_element_type=jnp.float32) * scale
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    k_pos = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    if first:
        k_pos = k_pos + first
    seen = q_pos >= k_pos
    if window is not None and i * bq + s.shape[-2] > window:
        seen = seen & (k_pos > q_pos - window)
    return jnp.where(seen, s, _MASKED)


def _gqa_lax_steps(T, bq, window):
    """The (row block, key tile of ``bq`` keys) pairs the lax tier's
    slices touch: what its ``kernel.route`` event calls steps."""
    steps = 0
    for i in range(-(-T // bq)):
        _, hi, first = _gqa_span(i, bq, T, window)
        steps += -(-hi // bq) - first // bq
    return steps


def _gqa_fwd_blocks(q, k, v, scale, block_q, window=None):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    bq, nq = _gqa_blocks(T, block_q)
    q5 = q.reshape(B, T, Hkv, Hq // Hkv, D)
    chained = _gqa_chained(B, Hq, T)
    outs, lses = [], []
    for i in range(nq):
        lo, hi, first = _gqa_span(i, bq, T, window)
        qi = _after(q5[:, lo:hi], outs[-1] if chained and outs else None)
        s = _gqa_scores(qi, k[:, first:hi], i, bq, scale, first, window)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype),
                       v[:, first:hi], preferred_element_type=jnp.float32)
        outs.append(o / jnp.moveaxis(l, (1, 2, 3), (2, 3, 1)))
        lses.append((m + jnp.log(l))[..., 0])           # (B, Hkv, G, bq)
    out = jnp.concatenate(outs, axis=1).reshape(B, T, Hq, v.shape[-1])
    return out.astype(q.dtype), jnp.concatenate(lses, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gqa(q, k, v, scale, block_q, window=None):
    return _gqa_fwd_blocks(q, k, v, scale, block_q, window)[0]


def _gqa_fwd(q, k, v, scale, block_q, window=None):
    out, lse = _gqa_fwd_blocks(q, k, v, scale, block_q, window)
    return out, (q, k, v, out, lse)


def _gqa_bwd(scale, block_q, window, res, g):
    q, k, v, out, lse = res
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    bq, nq = _gqa_blocks(T, block_q)
    q5 = q.reshape(B, T, Hkv, G, D)
    g5 = g.reshape(B, T, Hkv, G, v.shape[-1])
    # rowsum(dO * O): what the softmax's backward subtracts in each row
    delta = jnp.sum(g5.astype(jnp.float32) * out.reshape(g5.shape)
                    .astype(jnp.float32), axis=-1)      # (B, T, Hkv, G)
    delta = jnp.moveaxis(delta, 1, 3)                   # (B, Hkv, G, T)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    chained = _gqa_chained(B, Hq, T)
    dqs = []
    for i in range(nq):
        lo, hi, first = _gqa_span(i, bq, T, window)
        qi, gi = q5[:, lo:hi], g5[:, lo:hi]
        qi = _after(qi, dqs[-1] if chained and dqs else None)
        s = _gqa_scores(qi, k[:, first:hi], i, bq, scale, first, window)
        p = jnp.exp(s - lse[..., lo:hi, None])
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", gi, v[:, first:hi],
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., lo:hi, None]) * scale).astype(q.dtype)
        pb = p.astype(q.dtype)
        dv = dv.at[:, first:hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", pb, gi,
            preferred_element_type=jnp.float32))
        dk = dk.at[:, first:hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", ds, qi,
            preferred_element_type=jnp.float32))
        dqs.append(jnp.einsum("bhgqk,bkhd->bqhgd", ds, k[:, first:hi],
                              preferred_element_type=jnp.float32))
    dq = jnp.concatenate(dqs, axis=1).reshape(q.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_gqa.defvjp(_gqa_fwd, _gqa_bwd)


# ---------------------------------------------------------------------------
# causal grouped-query attention, compiled tier
#
# A grid step meets one block of query rows with one block of keys, for one
# key / value head and the ``G`` query heads it serves, whose rows are
# stacked head-major into one (G * bq) tall tile: K and V are fetched once
# a group.  The steps of a (row, key head) walk the blocks at or below the
# diagonal and no others (two int32 tables in SMEM name each step's
# blocks; the index maps read them), so a block above the diagonal is
# neither computed nor fetched; only the blocks that the diagonal crosses
# take the mask.  Nothing quadratic leaves VMEM.
# ---------------------------------------------------------------------------

#: scores of a tile, (key rows) x (a group's query heads x a query block):
#: 4 MiB of float32.  On the v5e at 2 x 8,192 positions (PERF.md, PR 34)
#: forward + backward of the three language models' shapes ran within 8 %
#: of one another on tiles of a quarter to twice this and best at it; at
#: twice, eight query heads' backward took 40 % longer
_GQA_TILE = 1 << 20

#: VMEM a kernel may ask for: the backward holds a (row, key head)'s dk and
#: dv whole (float32 accumulators and the double-buffered blocks they
#: leave through); a v5e core has 128 MiB
_GQA_VMEM = 100 << 20


def _gqa_tiles(T, G):
    """(query rows a head, key rows) of a grid step, or None: powers of
    two that divide the positions — up to 1,024 query rows (a longer block
    wastes more above the diagonal), stacked over the group to 2,048 rows
    at most and whole lane tiles; then the key rows that fill the tile."""
    def largest(sizes, fits):
        return next((c for c in sizes if T % c == 0 and fits(c)), None)
    bq = largest((1024, 512, 256, 128, 64, 32, 16),
                 lambda c: G * c <= 2048 and G * c % 128 == 0)
    bk = largest((1024, 512, 256, 128),
                 lambda c: c == 128 or G * bq * c <= _GQA_TILE) if bq else None
    return (bq, bk) if bk else None


def _gqa_steps(T, bq, bk, window=None):
    """The (query block, key block) of each grid step: row blocks in turn,
    each with the key blocks that hold a key at or before its last row —
    and, with a ``window``, none wholly before the first key its first row
    sees: exactly the tiles that hold a pair that see each other."""
    qi, kj = [], []
    for i in range(T // bq):
        first = _gqa_span(i, bq, T, window)[2] // bk
        n = ((i + 1) * bq - 1) // bk + 1
        qi += [i] * (n - first)
        kj += range(first, n)
    return np.asarray(qi, np.int32), np.asarray(kj, np.int32)


def _stack(dst_ref, src_ref, G):
    """(bq, G * d) heads side by side -> (G * bq, d) head-major rows."""
    bq, d = src_ref.shape[0], src_ref.shape[1] // G
    for g in range(G):
        dst_ref[g * bq:(g + 1) * bq, :] = src_ref[:, g * d:(g + 1) * d]


def _unstack(dst_ref, x, G):
    """(G * bq, d) head-major rows -> (bq, G * d) heads side by side."""
    bq, d = x.shape[0] // G, x.shape[1]
    for g in range(G):
        dst_ref[:, g * d:(g + 1) * d] = \
            x[g * bq:(g + 1) * bq].astype(dst_ref.dtype)


def _gqa_first(i, j, bq, bk, window=None):
    """Whether key block ``j`` is the first that row block ``i`` meets."""
    if window is None:
        return j == 0
    return j == jnp.maximum(i * bq - (window - 1), 0) // bk


def _gqa_edges(i, j, bq, bk, window=None):
    """Of the tile that meets row block ``i`` with key block ``j``: whether
    it is the row block's last, and whether an edge crosses it — the
    diagonal (some key after the block's first row) or, with a ``window``,
    the lower edge (some key ``window`` or more before the block's last
    row).  Only a crossed tile takes the mask."""
    last, crossed = (j + 1) * bk >= (i + 1) * bq, (j + 1) * bk - 1 > i * bq
    if window is not None:
        crossed = crossed | (j * bk + window < (i + 1) * bq)
    return last, crossed


def _gqa_step(qi_ref, kj_ref, bq, bk, window=None):
    """This grid step's blocks and what :func:`_gqa_edges` says of them."""
    from jax.experimental import pallas as pl
    step = pl.program_id(2)
    i, j = qi_ref[step], kj_ref[step]
    return (i, j) + _gqa_edges(i, j, bq, bk, window)


def _gqa_causal(shape, i, j, bq, bk, window=None):
    """Which (key, stacked query row) pairs of a (bk, G * bq) tile see
    each other: the key at or before the row and, with a ``window``, fewer
    than ``window`` before it."""
    k_pos = j * bk + lax.broadcasted_iota(jnp.int32, shape, 0)
    q_pos = i * bq + (lax.broadcasted_iota(jnp.int32, shape, 1) & (bq - 1))
    if window is None:
        return q_pos >= k_pos
    return (q_pos >= k_pos) & (k_pos > q_pos - window)


def _either(cond, fn):
    """``fn(True)`` where ``cond``, ``fn(False)`` elsewhere."""
    from jax.experimental import pallas as pl
    pl.when(cond)(functools.partial(fn, True))
    pl.when(jnp.logical_not(cond))(functools.partial(fn, False))


def _gqa_fwd_kernel(G, scale, window, qi_ref, kj_ref, q_ref, k_ref, v_ref,
                    o_ref, lse_ref, acc_ref, m_ref, l_ref, *qs_ref):
    """q_ref (bq, G * D), k_ref (bk, D), v_ref (bk, Dv) -> o_ref (bq,
    G * Dv), lse_ref (1, G * bq).  The tiles are (bk, G * bq): keys down
    the rows, the group's stacked query rows along lanes, so a query row's
    running max and sum are one lane each of a (1, G * bq) row and their
    reductions run down the sublanes; the float32 accumulator is the
    output's transpose, (Dv, G * bq).  All three live in scratch over a
    row block's key blocks.  (Under a ``window`` a row may meet no key it
    sees in its row block's first tiles: its max stays ``_MASKED`` there,
    and the first key it does see rescales what those tiles left by
    exp(-1e30) = 0.)"""
    from jax.experimental import pallas as pl
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    i, j, last, crossed = _gqa_step(qi_ref, kj_ref, bq, bk, window)
    rows_ref = qs_ref[0] if qs_ref else q_ref

    @pl.when(_gqa_first(i, j, bq, bk, window))
    def _():
        if qs_ref:
            _stack(rows_ref, q_ref, G)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)

    def update(masked):
        v = v_ref[...]
        s = _nt(k_ref[...], rows_ref[...]) * scale          # (bk, M)
        if masked:
            s = jnp.where(_gqa_causal(s.shape, i, j, bq, bk, window), s,
                          _MASKED)
        m_run = m_ref[...]
        m_new = jnp.maximum(m_run, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _tn(v, p.astype(v.dtype))
        m_ref[...] = m_new
    _either(crossed, update)

    @pl.when(last)
    def _():
        l = l_ref[...]
        _unstack(o_ref, (acc_ref[...] / l).T, G)
        lse_ref[...] = m_ref[...] + jnp.log(l)


def _gqa_bwd_kernel(G, scale, window, qi_ref, kj_ref, q_ref, k_ref, v_ref,
                    do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
                    dq_acc, dk_acc, dv_acc, *stacked):
    """One kernel for all three gradients, its tiles (bk, G * bq): keys
    down the rows, the group's stacked query rows along lanes, where
    lse_ref and delta_ref (1, G * bq) broadcast.  dq of a row block
    accumulates over its key blocks; dk and dv of the whole (row, key
    head) accumulate in float32 scratch over every step — under a
    ``window`` still over every row block that sees the keys — and leave
    once, with the last."""
    from jax.experimental import pallas as pl
    bq, bk = q_ref.shape[0], k_ref.shape[0]
    step = pl.program_id(2)
    i, j, last, crossed = _gqa_step(qi_ref, kj_ref, bq, bk, window)
    if stacked:
        qs_ref, dos_ref = stacked
    else:
        qs_ref, dos_ref = q_ref, do_ref

    @pl.when(step == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_gqa_first(i, j, bq, bk, window))
    def _():
        if stacked:
            _stack(qs_ref, q_ref, G)
            _stack(dos_ref, do_ref, G)
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def update(masked):
        q, do, k = qs_ref[...], dos_ref[...], k_ref[...]
        s = _nt(k, q) * scale                               # (bk, M)
        if masked:
            s = jnp.where(_gqa_causal(s.shape, i, j, bq, bk, window), s,
                          _MASKED)
        p = jnp.exp(s - lse_ref[...])
        dp = _nt(v_ref[...], do)
        ds = (p * (dp - delta_ref[...]) * scale).astype(q.dtype)
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dv_acc[rows, :] += jnp.dot(p.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dk_acc[rows, :] += jnp.dot(ds, q,
                                   preferred_element_type=jnp.float32)
        dq_acc[...] += _tn(ds, k)
    _either(crossed, update)

    @pl.when(last)
    def _():
        _unstack(dq_ref, dq_acc[...], G)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _pallas_gqa(scale, tiles, interpret, window=None):
    """The attention as one ``custom_vjp`` over q (B, T, Hq, D), k (B, T,
    Hkv, D) and v (B, T, Hkv, Dv), D and Dv whole lane tiles.  The kernels
    read them row-major, (B, T, heads * width), a head's lanes a block.
    ``window``: None, or the keys a row sees (fewer than the positions)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    bq, bk = tiles
    shape = jax.ShapeDtypeStruct

    def dims(q, v):
        (B, T, Hq, D), (Hkv, Dv) = q.shape, v.shape[2:]
        return B, T, Hq, D, Hkv, Dv, Hq // Hkv, Hq // Hkv * bq

    def call(kernel, name, q, v, ins, outs, out_shape, scratch):
        """The kernel over the (row, key head, step) grid, as a function of
        its operands; ``ins`` and ``outs`` name block specs, ``scratch``
        lists (shape, dtype)."""
        B, T, _, D, Hkv, Dv, G, M = dims(q, v)

        def rows(width):                 # a query block of the G heads
            return pl.BlockSpec((None, bq, G * width),
                                lambda b, h, s, qi, kj: (b, qi[s], h))

        def keys(width):
            return pl.BlockSpec((None, bk, width),
                                lambda b, h, s, qi, kj: (b, kj[s], h))

        def whole(width):                # a (row, key head)'s every key
            return pl.BlockSpec((None, T, width),
                                lambda b, h, s, qi, kj: (b, 0, h))
        specs = dict(
            q=rows(D), o=rows(Dv), k=keys(D), v=keys(Dv), dk=whole(D),
            dv=whole(Dv), row=pl.BlockSpec(
                (None, None, None, 1, M),
                lambda b, h, s, qi, kj: (b, h, qi[s], 0, 0)))
        qi, kj = _gqa_steps(T, bq, bk, window)
        return functools.partial(pl.pallas_call(
            functools.partial(kernel, G, scale, window),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(B, Hkv, len(qi)),
                in_specs=[specs[n] for n in ins],
                out_specs=tuple(specs[n] for n in outs),
                scratch_shapes=[pltpu.VMEM(*x) for x in scratch]),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=_GQA_VMEM),
            name=name, interpret=interpret), qi, kj)

    def flat(x):
        return x.reshape(x.shape[:2] + (-1,))
    f32 = jnp.float32

    # jitted, so that a model's layers of one shape trace and lower each
    # kernel once
    @jax.jit
    def run_forward(q, k, v):
        B, T, Hq, D, Hkv, Dv, G, M = dims(q, v)
        o, lse = call(
            _gqa_fwd_kernel, "mxtpu_gqa_attention_fwd", q, v,
            "qkv", ("o", "row"),
            (shape((B, T, Hq * Dv), q.dtype),
             shape((B, Hkv, T // bq, 1, M), f32)),
            [((Dv, M), f32), ((1, M), f32), ((1, M), f32)]
            + [((M, D), q.dtype)] * (G > 1))(flat(q), flat(k), flat(v))
        return o.reshape(B, T, Hq, Dv), lse

    @jax.jit
    def run_backward(q, k, v, o, lse, do):
        B, T, _, D, Hkv, Dv, G, M = dims(q, v)
        # rowsum(dO * O), laid out as the log-sum-exp is
        delta = jnp.sum(do.astype(f32) * o.astype(f32), axis=-1)
        delta = jnp.transpose(delta.reshape(B, T // bq, bq, Hkv, G),
                              (0, 3, 1, 4, 2)).reshape(lse.shape)
        grads = call(
            _gqa_bwd_kernel, "mxtpu_gqa_attention_bwd", q, v,
            ("q", "k", "v", "o", "row", "row"), ("q", "dk", "dv"),
            tuple(shape(flat(x).shape, x.dtype) for x in (q, k, v)),
            [((M, D), f32), ((T, D), f32), ((T, Dv), f32)]
            + [((M, D), q.dtype), ((M, Dv), do.dtype)] * (G > 1))(
            flat(q), flat(k), flat(v), flat(do), lse, delta)
        return tuple(g.reshape(x.shape) for g, x in zip(grads, (q, k, v)))

    @jax.custom_vjp
    def attend(q, k, v):
        return run_forward(q, k, v)[0]

    def attend_fwd(q, k, v):
        o, lse = run_forward(q, k, v)
        return o, (q, k, v, o, lse)

    def attend_bwd(res, do):
        return run_backward(*res, do)

    attend.defvjp(attend_fwd, attend_bwd)
    return attend


def gqa_attention_pallas(q, k, v, scale=None, tiles=None, interpret=False,
                         window=None):
    """The compiled tier of :func:`gqa_attention` (same operands, same
    result): :func:`_gqa_lax_reason` says which operands it takes.  Query
    / key heads that are not whole lane tiles (latent attention's 192) are
    padded with zeros to the next one, which leaves every score as it was.
    ``tiles`` = (query rows a head, key rows) of a grid step, from the
    shapes when not given."""
    T, D = q.shape[1], q.shape[3]
    scale = float(scale or 1.0 / np.sqrt(D))
    if -D % 128:
        q, k = (jnp.pad(x, ((0, 0),) * 3 + ((0, -D % 128),)) for x in (q, k))
    tiles = tiles or _gqa_tiles(T, q.shape[2] // k.shape[2])
    return _pallas_gqa(scale, tuple(tiles), bool(interpret),
                       _gqa_window(window, T))(q, k, v)


def _gqa_lax_reason(q, v):
    """Why these operands are not the compiled tier's, or None."""
    from . import partitioned
    (_, T, Hq, D), (Hkv, Dv) = q.shape, v.shape[2:]
    if partitioned():
        return "mesh"
    # whole blocks of positions; value heads of whole lane tiles, query /
    # key heads that pad to one by a third of their width at most; a (row,
    # key head)'s dk and dv in VMEM, float32 accumulators and the blocks
    # they leave through — whole under a window too, which changes the
    # tiles a schedule visits and not what a step holds
    pad = -D % 128
    if _gqa_tiles(T, Hq // Hkv) is None or Dv % 128 or 3 * pad > D \
            or (4 + 2 * q.dtype.itemsize) * T * (D + pad + Dv) \
            > _GQA_VMEM // 2:
        return "shapes"
    return None


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "window"))
def _gqa_branch(q, k, v, scale, block_q, window=None):
    """The lax tier as the other platforms' branch of a program that takes
    the kernels: jitted, so that a model's layers of one shape trace its
    unrolled blocks, forward and backward, once and not once a layer."""
    return _gqa(q, k, v, scale, block_q, window)


def gqa_attention(q, k, v, scale=None, block_q=512, window=None):
    """Causal grouped-query attention.  q (B, T, Hq, D); k (B, T, Hkv, D)
    and v (B, T, Hkv, Dv) with ``Hq % Hkv == 0``: key/value head ``h``
    serves query heads ``h*G .. h*G+G-1``.  The value heads may be
    narrower (or wider) than the query / key heads: the result follows
    the value, (B, T, Hq, Dv), in q's dtype.

    Which tier runs follows from what the trace can see
    (:func:`_gqa_lax_reason`): the compiled kernels in a program lowered
    for a TPU, for whole blocks of positions and lane-aligned heads; the
    lax tier on other platforms, for other shapes and in a program the
    SPMD partitioner will split.  Each call records one ``kernel.route``
    event in the program's recorder (``kernel`` = ``gqa_attention``, the
    tier, the reason: ``aligned``, ``shapes``, ``mesh``) and counts
    ``kernel.gqa_attention.<tier>``.

    ``window``: position ``p`` sees the ``window`` keys ``p - window < j
    <= p``, itself included (sliding-window attention); None, 0, or a
    window of all the positions or more is plain causal attention and the
    very program it was.  Under a window the compiled schedule leaves out
    the key blocks wholly before a row block's first visible key and masks
    only the tiles that the diagonal or the window's lower edge crosses;
    the lax tier slices a row block's keys from the first its first row
    sees.  The event of a windowed call carries three more ids: ``window``,
    ``steps`` (grid steps a (row, key head) of the schedule it built; on
    the lax tier the (row block, tile of ``block_q`` keys) pairs its slices
    touch) and ``steps_causal`` (the same without the window).

    On the lax tier query rows go in blocks of ``block_q``; block ``i``
    meets only its causal prefix of keys (a static slice), so the work is
    the lower triangle plus half a block, and the largest tile either pass
    holds is (B, Hq, block_q, T) scores.  On either tier contractions take
    the operands' dtype with float32 accumulation; the softmax is
    float32."""
    from .. import profiler
    from . import by_platform
    D = q.shape[-1]
    if q.shape[2] % k.shape[2]:
        raise ValueError("gqa_attention: %d query heads over %d key/value "
                         "heads" % (q.shape[2], k.shape[2]))
    scale = float(scale or 1.0 / np.sqrt(D))
    T = q.shape[1]
    window = _gqa_window(window, T)
    reason = _gqa_lax_reason(q, v)
    tier = "lax" if reason else "pallas"
    ids = {}
    if window is not None:
        if reason:
            bq = _gqa_blocks(T, block_q)[0]
            steps = [_gqa_lax_steps(T, bq, w) for w in (window, None)]
        else:
            tiles = _gqa_tiles(T, q.shape[2] // k.shape[2])
            steps = [len(_gqa_steps(T, *tiles, w)[0]) for w in (window, None)]
        ids = dict(window=window, steps=steps[0], steps_causal=steps[1])
    now = time.perf_counter_ns()
    profiler.event("kernel.route", now, now, kernel="gqa_attention",
                   tier=tier, reason=reason or "aligned", **ids)
    profiler.count("kernel.gqa_attention." + tier)

    if reason:
        return _gqa(q, k, v, scale, int(block_q), window)
    return by_platform(
        functools.partial(gqa_attention_pallas, scale=scale, window=window),
        functools.partial(_gqa_branch, scale=scale, block_q=int(block_q),
                          window=window),
        q, k, v)
