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
  key/value head serves ``Hq // Hkv`` query heads, any head size) in
  pure lax with its OWN backward (``jax.custom_vjp``: the saved
  residuals are q, k, v, the output and one log-sum-exp a row; the
  probabilities are recomputed a query block at a time), so neither
  pass ever holds more than one (block x prefix) tile of scores and the
  bf16 gradient is finite (masked scores are a large finite negative,
  never ``-inf``).  The language models route here.
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

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["flash_attention", "flash_attention_lax",
           "flash_attention_pallas", "online_update", "default_block",
           "gqa_attention"]


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


def _gqa_scores(qi, k, i, bq, scale):
    """Scores of query block ``i`` against its causal key prefix, f32:
    qi (B, bq, Hkv, G, D), k (B, Lk, Hkv, D) -> (B, Hkv, G, bq, Lk)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k,
                   preferred_element_type=jnp.float32) * scale
    q_pos = i * bq + lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    k_pos = lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    return jnp.where(q_pos >= k_pos, s, _MASKED)


def _gqa_fwd_blocks(q, k, v, scale, block_q):
    B, T, Hq, D = q.shape
    Hkv = k.shape[2]
    bq, nq = _gqa_blocks(T, block_q)
    q5 = q.reshape(B, T, Hkv, Hq // Hkv, D)
    chained = _gqa_chained(B, Hq, T)
    outs, lses = [], []
    for i in range(nq):
        lo, hi = i * bq, min((i + 1) * bq, T)
        qi = _after(q5[:, lo:hi], outs[-1] if chained and outs else None)
        s = _gqa_scores(qi, k[:, :hi], i, bq, scale)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v[:, :hi],
                       preferred_element_type=jnp.float32)
        outs.append(o / jnp.moveaxis(l, (1, 2, 3), (2, 3, 1)))
        lses.append((m + jnp.log(l))[..., 0])           # (B, Hkv, G, bq)
    out = jnp.concatenate(outs, axis=1).reshape(B, T, Hq, v.shape[-1])
    return out.astype(q.dtype), jnp.concatenate(lses, axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gqa(q, k, v, scale, block_q):
    return _gqa_fwd_blocks(q, k, v, scale, block_q)[0]


def _gqa_fwd(q, k, v, scale, block_q):
    out, lse = _gqa_fwd_blocks(q, k, v, scale, block_q)
    return out, (q, k, v, out, lse)


def _gqa_bwd(scale, block_q, res, g):
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
        lo, hi = i * bq, min((i + 1) * bq, T)
        qi, gi = q5[:, lo:hi], g5[:, lo:hi]
        qi = _after(qi, dqs[-1] if chained and dqs else None)
        s = _gqa_scores(qi, k[:, :hi], i, bq, scale)
        p = jnp.exp(s - lse[..., lo:hi, None])
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", gi, v[:, :hi],
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[..., lo:hi, None]) * scale).astype(q.dtype)
        pb = p.astype(q.dtype)
        dv = dv.at[:, :hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", pb, gi,
            preferred_element_type=jnp.float32))
        dk = dk.at[:, :hi].add(jnp.einsum(
            "bhgqk,bqhgd->bkhd", ds, qi,
            preferred_element_type=jnp.float32))
        dqs.append(jnp.einsum("bhgqk,bkhd->bqhgd", ds, k[:, :hi],
                              preferred_element_type=jnp.float32))
    dq = jnp.concatenate(dqs, axis=1).reshape(q.shape)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_gqa.defvjp(_gqa_fwd, _gqa_bwd)


def gqa_attention(q, k, v, scale=None, block_q=512):
    """Causal grouped-query attention.  q (B, T, Hq, D); k (B, T, Hkv, D)
    and v (B, T, Hkv, Dv) with ``Hq % Hkv == 0``: key/value head ``h``
    serves query heads ``h*G .. h*G+G-1``.  The value heads may be
    narrower (or wider) than the query / key heads: the result follows
    the value, (B, T, Hq, Dv), in q's dtype.

    Query rows go in blocks of ``block_q``; block ``i`` meets only its
    causal prefix of keys (a static slice), so the work is the lower
    triangle plus half a block, and the largest tile either pass holds
    is (B, Hq, block_q, T) scores.  Contractions take the operands'
    dtype with float32 accumulation; the softmax is float32."""
    D = q.shape[-1]
    if q.shape[2] % k.shape[2]:
        raise ValueError("gqa_attention: %d query heads over %d key/value "
                         "heads" % (q.shape[2], k.shape[2]))
    return _gqa(q, k, v, float(scale or 1.0 / np.sqrt(D)), int(block_q))
