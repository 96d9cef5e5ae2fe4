"""Ring attention — sequence/context parallelism over the 'sp' mesh axis.

New capability beyond the reference (which only had bucketing for long
sequences, SURVEY §5.7).  Q/K/V are sharded along the sequence axis across
the 'sp' devices; each device holds one query block and streams the K/V
blocks around the ring with ``lax.ppermute`` (neighbor exchange over ICI),
accumulating attention with the numerically-stable streaming-softmax
(flash-attention style log-sum-exp rescaling).  Compute on each hop is a
full block matmul (MXU-sized); communication overlaps with compute across
hops.

Reference pattern: Ring Attention (Liu et al. 2023) / blockwise attention —
see PAPERS.md.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_attention", "full_attention", "ring_attention_sharded"]


def full_attention(q, k, v, causal=False, scale=None):
    """Single-device attention: q,k,v (B, T, H, D) -> (B, T, H, D).

    Long sequences route through the tiled online-softmax kernel
    (mxnet_tpu/kernels/flash_attention.py — Pallas on TPU, lax scan
    elsewhere) when ``MXTPU_FUSED_KERNELS`` enables it: the (Tq x Tk)
    score matrix then never materializes.  Short sequences (at most one
    key block) and ``MXTPU_FUSED_KERNELS=0`` use the exact-softmax
    reference below."""
    B, Tq, H, D = q.shape
    scale = scale or (1.0 / np.sqrt(D))
    Tk = k.shape[1]
    from ..kernels import fused_enabled
    if fused_enabled("flash_attention"):
        from ..kernels import flash_attention as fa
        if Tk > fa.default_block():
            return fa.flash_attention(q, k, v, causal=causal, scale=scale)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), dtype=bool), Tk - Tq)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _ring_body(axis_name, n_blocks, causal, scale, q, k0, v0, my_idx):
    """Streaming accumulation over ring hops inside shard_map."""
    B, Tq, H, D = q.shape
    Tk = k0.shape[1]

    acc = jnp.zeros((B, Tq, H, D), dtype=jnp.float32)
    m_run = jnp.full((B, H, Tq), -jnp.inf)
    s_run = jnp.zeros((B, H, Tq))

    # each hop is ONE streaming-softmax accumulation step — the same
    # online_update the flash-attention kernel runs per key block
    # (mxnet_tpu/kernels/flash_attention.py), so ring attention IS the
    # flash accumulation composed across devices and the two paths
    # cannot drift numerically
    from ..kernels.flash_attention import online_update

    def hop(carry, hop_idx):
        acc, m_run, s_run, k, v = carry
        # block owner of the K/V currently held: after h hops of the
        # i -> i+1 ring, device i holds block (i - h) mod n
        kv_idx = (my_idx - hop_idx) % n_blocks
        if causal:
            q_pos = my_idx * Tq + jnp.arange(Tq)
            k_pos = kv_idx * Tk + jnp.arange(Tk)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((Tq, Tk), dtype=bool)
        acc, m_run, s_run = online_update(
            acc, m_run, s_run, q, k, v, scale, mask[None, None])
        # pass K/V to the next device on the ring (ICI neighbor exchange)
        perm = [(i, (i + 1) % n_blocks) for i in range(n_blocks)]
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return (acc, m_run, s_run, k, v), None

    (acc, m_run, s_run, _, _), _ = lax.scan(
        hop, (acc, m_run, s_run, k0, v0), jnp.arange(n_blocks))
    s_run = jnp.maximum(s_run, 1e-20)
    return (acc / s_run.transpose(0, 2, 1)[..., None]).astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                           scale=None):
    """Ring attention with q/k/v sharded on the sequence axis (axis 1) over
    ``axis_name`` of ``mesh``.  q,k,v: (B, T, H, D) global shapes."""
    from jax.sharding import PartitionSpec as P

    n_blocks = mesh.shape[axis_name]
    D = q.shape[-1]
    scale = scale or (1.0 / np.sqrt(D))
    spec = P(None, axis_name, None, None)

    def local_fn(q_blk, k_blk, v_blk):
        my_idx = lax.axis_index(axis_name)
        return _ring_body(axis_name, n_blocks, causal, scale, q_blk, k_blk,
                          v_blk, my_idx)

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None):
    """Entry point: ring attention when a mesh with ``axis_name`` is given,
    plain (still flash-style-stable) attention otherwise."""
    if mesh is not None and axis_name in mesh.shape and \
            mesh.shape[axis_name] > 1:
        return ring_attention_sharded(q, k, v, mesh, axis_name, causal, scale)
    if mesh is not None and mesh.size > 1:
        # the caller's program is partitioned over the mesh's other axes:
        # no Mosaic kernel there (kernels.auto_partitioned)
        from ..kernels import auto_partitioned
        with auto_partitioned():
            return full_attention(q, k, v, causal=causal, scale=scale)
    return full_attention(q, k, v, causal=causal, scale=scale)
