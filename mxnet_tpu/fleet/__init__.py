"""mxfleet: a multi-replica serving fleet on the mxserve stack
(docs/how_to/fleet.md).

mxserve (``mxnet_tpu/serving/``) is ONE daemon: one process, one warm
``ModelPool``, one public port — its QPS ceiling is one Python
dispatcher and one device client.  This package composes N of those
daemons into one serving system:

- :mod:`.manifest` — the fleet manifest (models, replica count, device
  placement) + each model's stable HOME replica.
- :mod:`.controller` — replica lifecycle: spawns N real
  ``tools/serve.py`` processes, each pinned to its own device subset
  (``JAX_PLATFORMS``/visible-chip env, CPU-core affinity on the CPU
  tier), supervised by the ``tools/supervise.py`` exit-code discipline
  (85/87 relaunch with ``MXTPU_RESUME=1``; any other death respawns
  within a streak budget; drains relaunch nothing).
- :mod:`.router` — the routing front end that owns the public port:
  route-by-model to the home replica, SPILL to the least-loaded
  replica when the home's queue/SLO signal crosses the bar (the
  ``/stats`` surface PR 6 built is the routing input), heartbeat-age
  eviction off ``/healthz``, exactly-once keyed retry on a dead
  replica (one resend to a different healthy replica, same request
  id — replica dedup + bucket bit-stability make it safe),
  SIGTERM drain that fences new work then drains every replica, and
  fleet-level p50/p99/shed aggregation on ``/stats``.
- :mod:`.warm` — the AOT warm store: pre-compile every (model, bucket)
  forward into ``JAX_COMPILATION_CACHE_DIR`` so a fresh or respawned replica
  warms from disk instead of from XLA.
- :mod:`.view` — the shared fleet view that shards the front end: ONE
  controller-side prober publishes manifest + health + the fenced set
  into an atomic JSON snapshot with a generation counter; N
  ``FleetRouter`` worker processes (:class:`~.view.RouterWorkerSet`)
  accept on the SAME public port via SO_REUSEPORT and route off the
  snapshot — workers never probe and never coordinate.
- :mod:`.autoscale` — the loop that ACTS on the aggregated
  ``est_wait_ms`` signal: hysteresis + cooldown, scale-up through
  :meth:`~.controller.ReplicaController.add_replica` (warm AOT
  bring-up), scale-down through the mxswap fence -> drain -> stop
  path (never below the capacity floor).

``tools/fleet.py`` is the CLI (``serve`` + ``warmup`` +
``router-worker`` subcommands); ``tests/test_chaos.py``'s fleet drills
drive the real daemons.  Every
``MXTPU_FLEET_*`` knob is registered EAGERLY at its owner module
below (the PR-7 lazy-registration lesson); this package never imports
jax — the router and controller are pure-host processes by design.
"""
from .manifest import (FleetManifest, parse_shape_specs,
                       replica_device_env, default_serve_py,
                       ENV_FLEET_REPLICAS)
from .controller import Replica, ReplicaController
from .router import (FleetRouter, NoHealthyReplica, ReplicaDead,
                     ENV_FLEET_SPILL_QUEUE, ENV_FLEET_HEARTBEAT_S,
                     ENV_FLEET_EVICT_S)
from .warm import build_warm_store, warm_store_manifest
from .deploy import RollingSwap
from .view import (FleetViewPublisher, FleetViewReader, RouterWorkerSet,
                   reserve_port, ENV_FLEET_WORKERS,
                   ENV_FLEET_VIEW_REFRESH_S)
from .autoscale import (Autoscaler, ENV_FLEET_SCALE_HIGH_MS,
                        ENV_FLEET_SCALE_LOW_MS,
                        ENV_FLEET_SCALE_COOLDOWN_S,
                        ENV_FLEET_MIN_REPLICAS, ENV_FLEET_MAX_REPLICAS)

__all__ = ["FleetManifest", "parse_shape_specs", "replica_device_env",
           "default_serve_py", "Replica", "ReplicaController",
           "FleetRouter", "NoHealthyReplica", "ReplicaDead",
           "build_warm_store", "warm_store_manifest", "RollingSwap",
           "FleetViewPublisher", "FleetViewReader", "RouterWorkerSet",
           "reserve_port", "Autoscaler",
           "ENV_FLEET_REPLICAS", "ENV_FLEET_SPILL_QUEUE",
           "ENV_FLEET_HEARTBEAT_S", "ENV_FLEET_EVICT_S",
           "ENV_FLEET_WORKERS", "ENV_FLEET_VIEW_REFRESH_S",
           "ENV_FLEET_SCALE_HIGH_MS", "ENV_FLEET_SCALE_LOW_MS",
           "ENV_FLEET_SCALE_COOLDOWN_S", "ENV_FLEET_MIN_REPLICAS",
           "ENV_FLEET_MAX_REPLICAS"]
