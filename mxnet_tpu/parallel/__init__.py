"""Parallelism over device meshes — the TPU-native replacement for the
reference's multi-executor + parameter-server stack.

The reference scales by running one executor per GPU and reducing gradients
through KVStore/ps-lite (SURVEY §2.3).  Here the unit of scaling is a
``jax.sharding.Mesh`` with named axes (dp/tp/sp/pp/ep): one jit-compiled
training step is annotated with shardings and GSPMD partitions it across
the mesh, inserting AllReduce/AllGather/ReduceScatter over ICI — the
collectives the reference hand-wires through NCCL/ZMQ fall out of the
compiler.

Components:
- mesh.py: mesh construction helpers
- planner.py: mxplan — the automatic sharding planner (mesh shape,
  replicate/dp-shard/zero3 strategy under an HBM budget, derived zero3
  gather groups) and the serializable ShardingPlan artifact checkpoints
  persist for elastic world-size resume
- trainer.py: SPMDTrainer — fused fwd+bwd+optimizer-update step, sharded
  over the mesh (the kvstore='tpu' fast path; what every benchmark
  cell trains through)
- spmd_module.py: SPMDModule — Module-API adapter over SPMDTrainer
- ring_attention.py: ring attention over the 'sp' axis (sequence/context
  parallelism — capability beyond the reference, SURVEY §5.7)
- pipeline.py: GPipe-style microbatch pipeline over the 'pp' axis
  (shard_map + ppermute neighbor exchange)
- moe.py: GShard-style top-2 mixture-of-experts over the 'ep' axis
  (dispatch/combine einsums -> all_to_all under GSPMD)
"""
from .mesh import build_mesh, default_mesh, local_mesh
from .trainer import SPMDTrainer
from . import zero3  # noqa: F401 — EAGER env registration (MXTPU_ZERO3_*)
from . import planner  # noqa: F401 — EAGER env registration (MXTPU_PLAN_*)
from .planner import ShardingPlan
from .spmd_module import SPMDModule
from . import ring_attention
from .ring_attention import ring_attention as ring_attention_fn
from . import pipeline
from .pipeline import pipeline_apply, stack_stage_params
from . import moe
from .moe import moe_ffn, moe_init, moe_shardings
