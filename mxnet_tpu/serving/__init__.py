"""mxserve: production inference serving on the predict/resilience stack
(docs/how_to/serving.md).

The reference stopped at a predict-only ABI (``c_predict_api.h`` ->
``mxnet_tpu/predict.py``); this package turns a trained checkpoint into
a traffic-serving daemon:

- :mod:`.batcher` — continuous request batching into padded power-of-two
  **bucket** shapes, one cached compiled forward per bucket.
- :mod:`.pool` — warm multi-model pool, device-resident weights,
  optional bf16 weight-cast, checkpoint-directory loading.
- :mod:`.frontend` — HTTP admission control: bounded queues, SLO-aware
  load shedding (429), ``/healthz`` + ``/stats``, graceful SIGTERM
  drain, StepWatchdog coverage of wedged forwards (exit 87 ->
  ``tools/supervise.py`` relaunch), weighted-fair tenant queueing.
- :mod:`.sequence` — bucketed SEQUENCE serving (``/predict_seq``):
  variable-length token streams length-bucketed at the front door, one
  batcher per (model, length) pair, answers trimmed to true length.

``tools/serve.py`` is the CLI daemon.
"""
from .batcher import (BucketBatcher, DeadlineExpired, Draining, QueueFull,
                      TenantQuotaExceeded, parse_buckets, pick_bucket,
                      pad_to_bucket, parse_tenant_weights)
from .pool import ModelPool, PooledModel
from .frontend import ServeClient, ServingFrontend, Stats
# deploy's MXTPU_SWAP_* knobs register EAGERLY here (the PR-7 lesson),
# and sequence's MXTPU_SERVE_SEQ_BUCKETS rides the same rule
from .deploy import CheckpointWatcher
from .sequence import (SequenceEntry, parse_seq_buckets, pick_seq_bucket,
                       seq_batcher_name)

__all__ = ["BucketBatcher", "DeadlineExpired", "Draining", "QueueFull",
           "TenantQuotaExceeded", "parse_buckets", "pick_bucket",
           "pad_to_bucket", "parse_tenant_weights", "ModelPool",
           "PooledModel", "ServeClient", "ServingFrontend", "Stats",
           "CheckpointWatcher", "SequenceEntry", "parse_seq_buckets",
           "pick_seq_bucket", "seq_batcher_name"]
