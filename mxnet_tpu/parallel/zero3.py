"""ZeRO-3 machinery: layer-grouped parameter gathers for the fused step.

``grad_sync='zero3'`` goes the rest of the way from 'zero' (sharded
master params + optimizer state, one gather block at step start): the
step all-gathers each PARAMETER GROUP on demand inside the jitted
program, the backward RE-GATHERS instead of keeping the replicated
copies alive between the passes, and gradients leave the backward as
reduce-scatter.  Nothing replicated persists between steps — per-device
parameter residency is ~1/world
(``tests/test_parallel.py::test_zero3_param_residency_is_one_over_world``).

Two tiers, the kernels-package discipline (Pallas/lax):

- **manual** (pure-dp mesh): the whole step body
  runs under ``shard_map`` over the dp axis.  Gathers are explicit
  ``lax.all_gather`` calls — several same-group shards flatten into ONE
  bucketed collective — and their autodiff transpose IS
  ``psum_scatter``, so the gradient reduce-scatter is guaranteed by
  construction on every backend (XLA CPU never synthesizes
  reduce-scatter from GSPMD partial sums; proven by
  tests/test_analysis.py's schedule-rule tests).
- **gspmd** (multi-axis meshes — dp×tp/ep/pp composition): grouped
  ``with_sharding_constraint`` re-shardings under the same remat
  policy; GSPMD inserts the collectives.  XLA's ReduceScatterCreator
  rewrites the gradient all-reduce+slice into reduce-scatter on
  TPU/GPU pipelines; CPU keeps the all-reduce form, which the schedule
  lint reports as a documented tier note rather than a violation.

Group boundaries are keyed by the executor plan's TOPOLOGICAL order
(executor._node_plan): each parameter belongs to the plan position of
its first consuming node.  Under the MXTPU_ZERO3_GATHER_GROUP=auto
default the PLANNER (parallel/planner.py) merges consecutive consumer
nodes ("layers") toward a target bucket size; a numeric value is the
manual N-layers-per-group override (plan_gather_groups below).
Separate per-group gathers — not one monolithic gather — are what
XLA's latency-hiding scheduler can pipeline against early forward
compute.

The backward re-gather is expressed with ``jax.checkpoint`` +
``checkpoint_name``: every gathered (replicated) value is tagged
``zero3_gather`` and the step's loss closure runs under the
``save_anything_except_these_names`` policy, so activations checkpoint
as usual while gathered parameters are dropped after the forward and
re-gathered (recomputed from the shards) inside the backward.
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError, register_env

__all__ = ["ENV_ZERO3_GATHER_GROUP", "GATHER_TAG", "first_consumer_order",
           "plan_gather_groups", "remat_policy", "make_manual_gather",
           "make_gspmd_gather"]

#: checkpoint_name tag on every gathered (replicated) parameter value;
#: the step's remat policy drops exactly these between forward and
#: backward so the backward re-gathers from the shards
GATHER_TAG = "zero3_gather"

ENV_ZERO3_GATHER_GROUP = register_env(
    "MXTPU_ZERO3_GATHER_GROUP", default="auto",
    doc="grad_sync='zero3': gather grouping.  'auto' (default) derives "
        "the groups from the executor plan's first-consumer order, "
        "merged toward MXTPU_PLAN_GATHER_BUCKET bytes per collective "
        "(parallel/planner.py).  A numeric value is the manual "
        "override — N consecutive plan-order layers per group (1 = "
        "per-layer gathers; larger values fuse more parameters into "
        "fewer, bigger collectives — less dispatch overhead, less "
        "overlap) — and warns when it loses to the planned grouping "
        "on the memory model")


def first_consumer_order(symbol, param_names):
    """``{param_name: topological position of its first consumer}``.

    Positions come from the executor plan (executor._node_plan slot 5):
    a pure function of the graph, identical across processes — the same
    property the RNG fold constants rely on, so group boundaries are
    reproducible anywhere the program is.  Params never consumed by an
    op (possible in hand-built graphs) sort last, after every real
    consumer.
    """
    from ..executor import _node_plan
    wanted = set(param_names)
    order = {}
    last = 0
    for entry in _node_plan(symbol):
        node, ix = entry[0], entry[4]
        if node.is_variable:
            continue
        last = max(last, ix)
        for src, _ in node.inputs:
            if src.is_variable and src.name in wanted \
                    and src.name not in order:
                order[src.name] = ix
    for name in param_names:
        order.setdefault(name, last + 1)
    return order


def plan_gather_groups(symbol, param_names, group_layers=1):
    """Chunk ``param_names`` into gather groups of ``group_layers``
    consecutive consuming nodes each, ordered by the plan's topological
    order.  Returns a list of name-lists; every input name appears in
    exactly one group."""
    group_layers = max(1, int(group_layers))
    order = first_consumer_order(symbol, param_names)
    by_node = {}
    for name in param_names:
        by_node.setdefault(order[name], []).append(name)
    groups, current, nlayers = [], [], 0
    for ix in sorted(by_node):
        current.extend(sorted(by_node[ix]))
        nlayers += 1
        if nlayers >= group_layers:
            groups.append(current)
            current, nlayers = [], 0
    if current:
        groups.append(current)
    return groups


def remat_policy():
    """The zero3 checkpoint policy: save every residual EXCEPT gathered
    parameters (tag ``GATHER_TAG``) — activations behave as in a plain
    step, replicated parameters are re-gathered in the backward."""
    import jax
    return jax.checkpoint_policies.save_anything_except_these_names(
        GATHER_TAG)


def make_manual_gather(groups, shard_dim, shapes, world, axis_name):
    """Build ``gather(shards) -> {name: full}`` for the manual tier.

    Per group, every dim-0-sharded member flattens into ONE bucketed
    ``all_gather`` (the ZeRO gather bucket: one collective per layer
    group; its autodiff transpose is ONE ``psum_scatter`` carrying the
    whole group's gradients).  Members sharded on another dimension
    gather individually (their flattened shards would interleave
    wrongly in a dim-0 bucket).  Every replicated full value is tagged
    ``GATHER_TAG`` so the remat policy re-gathers it in the backward.

    ``shard_dim``: {name: int} — which dimension the dp axis shards.
    ``shapes``: {name: full shape}.  ``world``: dp axis size.
    """
    import jax
    from jax.ad_checkpoint import checkpoint_name

    def _tag(v):
        return checkpoint_name(v, GATHER_TAG)

    def gather(p):
        full = {}
        for g in groups:
            bucket = [n for n in g if shard_dim[n] == 0]
            singles = [n for n in g if shard_dim[n] != 0]
            if len(bucket) < 2:
                singles = bucket + singles
                bucket = []
            if bucket:
                flat = jax.numpy.concatenate(
                    [p[n].reshape(-1) for n in bucket])
                gathered = _tag(jax.lax.all_gather(
                    flat, axis_name, axis=0, tiled=True))
                # [world * bucket_elems] -> (world, bucket_elems); each
                # param's full value is its column strip re-stacked over
                # the world rows (dim-0 shards are contiguous row blocks)
                mat = gathered.reshape(world, -1)
                off = 0
                for n in bucket:
                    size = int(np.prod(shapes[n])) // world
                    strip = mat[:, off:off + size]
                    full[n] = _tag(strip.reshape(shapes[n]))
                    off += size
            for n in singles:
                full[n] = _tag(jax.lax.all_gather(
                    p[n], axis_name, axis=shard_dim[n], tiled=True))
        return full

    return gather


def make_gspmd_gather(groups, sharding_of, replicated):
    """Build ``gather(params) -> {name: full}`` for the gspmd tier:
    per-group ``with_sharding_constraint`` pairs (pin to the shard so
    the partitioner cannot hoist the gather above the compute-dtype
    cast, then demand replicated), tagged for the backward re-gather.
    GSPMD turns each replication demand into an all-gather; grouping
    here is emission ORDER (the latency-hiding scheduler keys on the
    dependency structure, one gather per parameter group member)."""
    import jax
    from jax.ad_checkpoint import checkpoint_name

    def gather(p):
        full = {}
        for g in groups:
            for n in g:
                v = jax.lax.with_sharding_constraint(p[n], sharding_of(n))
                full[n] = checkpoint_name(
                    jax.lax.with_sharding_constraint(v, replicated),
                    GATHER_TAG)
        return full

    return gather
