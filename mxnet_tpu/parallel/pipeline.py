"""Pipeline parallelism over the 'pp' mesh axis — GPipe-style microbatch
schedule with neighbor exchange.

New capability beyond the reference (SURVEY §2.3: "Pipeline parallelism:
NO").  The idiomatic TPU formulation (scaling-book recipe): S homogeneous
stages hold their parameters stacked on a leading axis sharded over 'pp';
inside ``shard_map`` every device runs the same program, processes one
microbatch per tick, and passes activations to its ring neighbor with
``lax.ppermute`` (ICI).  A batch of M microbatches drains in M + S - 1
ticks — the classic pipeline bubble.

The reference's closest analog was manual layer placement across GPUs
(example/model-parallel-lstm); that overlapping-by-luck scheme becomes a
deterministic compiled schedule here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] -> one tree with a leading stage
    axis (shard it with PartitionSpec('pp', ...) on the mesh)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_apply(fn, stage_params, x, mesh, axis_name="pp",
                   n_microbatch=None):
    """Run ``x`` through S pipelined stages of ``fn``.

    fn(params_of_one_stage, act) -> act         (shape-preserving)
    stage_params: pytree, leaves (S, ...), sharded P('pp', ...) over mesh
    x: (B, ...) replicated batch; B must divide by n_microbatch
    returns: (B, ...) replicated result of stage S-1 ∘ ... ∘ stage 0
    """
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis_name]
    n_given = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    if n_given != n_stages:
        raise ValueError(
            "stage_params stack %d stages but mesh axis %r has %d devices "
            "(one stage per device; for more layers than devices, fold "
            "several layers into one stage fn)"
            % (n_given, axis_name, n_stages))
    M = n_microbatch or n_stages
    B = x.shape[0]
    if B % M != 0:
        raise ValueError(
            "n_microbatch %d must divide the batch %d" % (M, B))
    mb = B // M
    micro = x.reshape((M, mb) + x.shape[1:])

    param_specs = jax.tree_util.tree_map(
        lambda l: P(axis_name, *([None] * (l.ndim - 1))), stage_params)

    def local_fn(params, micro_local):
        # params leaves: (1, ...) — this device's stage
        params = jax.tree_util.tree_map(lambda l: l[0], params)
        idx = lax.axis_index(axis_name)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        state = jnp.zeros((mb,) + x.shape[1:], x.dtype)   # held activation
        out = jnp.zeros_like(micro_local)

        def tick(carry, t):
            state, out = carry
            # stage 0 ingests microbatch t (all devices compute the slice;
            # only device 0 uses it)
            feed = lax.dynamic_index_in_dim(
                micro_local, jnp.minimum(t, M - 1), axis=0, keepdims=False)
            x_in = jnp.where(idx == 0, feed, state)
            y = fn(params, x_in)
            # last stage finishes microbatch t - (S-1) at this tick
            done_idx = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (done_idx >= 0)
            out = lax.cond(
                write,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(done_idx, 0), axis=0),
                lambda o: o, out)
            state = lax.ppermute(y, axis_name, fwd_perm)
            return (state, out), None

        (state, out), _ = lax.scan(tick, (state, out),
                                   jnp.arange(M + n_stages - 1))
        # replicate the last stage's collected outputs to every device
        out = lax.psum(jnp.where(idx == n_stages - 1, out,
                                 jnp.zeros_like(out)), axis_name)
        return out

    fn_sharded = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(), check_vma=False)
    out = fn_sharded(stage_params, micro)
    return out.reshape((B,) + x.shape[1:])
