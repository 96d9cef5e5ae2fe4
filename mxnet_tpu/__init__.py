"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
MXNet v0.9.5 (NNVM era), re-designed on JAX/XLA/pjit/Pallas.

Usage mirrors the reference's ``import mxnet as mx``::

    import mxnet_tpu as mx
    a = mx.nd.ones((2, 3), ctx=mx.tpu())
    net = mx.sym.FullyConnected(mx.sym.Variable("data"), num_hidden=10)
    mod = mx.mod.Module(net, ...)
"""

__version__ = "0.1.0"

from .base import MXNetError
from . import resilience
from .resilience import CheckpointManager, PreemptionHandler, StepWatchdog

# Persistent XLA compilation cache.  JAX_COMPILATION_CACHE_DIR places it
# from outside: jax reads that variable itself and nothing here touches
# the setting.  Unset, the cache is ONE fixed directory in the checkout —
# the path is part of the cache key, so a directory that moves never
# hits.  Configured BEFORE anything can trigger a compile; jax's own
# thresholds decide which programs are worth an entry.
import os as _os
if "JAX_COMPILATION_CACHE_DIR" not in _os.environ:
    import jax as _jax
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
    del _jax
del _os

# Join the process group BEFORE anything can touch a JAX backend: under
# tools/launch.py the MXTPU_* envs are set, and jax.distributed.initialize
# must precede backend creation (it also pins the worker platform).  This is
# the analog of the reference consulting DMLC_ROLE at import
# (python/mxnet/kvstore_server.py:58-68); a no-op when unlaunched.
from . import distributed
distributed.initialize()
from .context import Context, cpu, gpu, tpu, current_context, num_gpus, num_tpus
from . import ops
from . import operator
from . import ndarray
from . import ndarray as nd
from . import random
from . import random as rnd

ndarray._init_ndarray_module()

from .ndarray import NDArray
from . import name
from . import attribute
from .attribute import AttrScope
from . import symbol
from . import symbol as sym
from .symbol import Symbol

symbol._init_symbol_module()

from . import executor
from .executor import Executor
from . import engine
from . import recordio
from . import image
from . import io
# reference parity: the C++ record iterators register as mx.io.* iterators
# (src/io/iter_image_recordio.cc:319, iter_image_det_recordio.cc:563); ours
# live in image.py / image_det.py
from . import image_det
io.ImageRecordIter = image.ImageRecordIter
io.ImageRecordUInt8Iter = image.ImageRecordUInt8Iter
io.ImageDetRecordIter = image_det.ImageDetRecordIter
from . import dataflow
from .dataflow import DevicePrefetchIter
io.DevicePrefetchIter = DevicePrefetchIter
from . import initializer
from .initializer import init_registry
from . import optimizer
from .optimizer import Optimizer
from . import lr_scheduler
from . import metric
from . import callback
from . import kvstore
from . import kvstore as kv
from . import executor_manager
from . import parallel
from . import autograd
from . import contrib
from . import rtc
from . import torch_bridge
from .torch_bridge import th
# both addressing styles work: mx.contrib.symbol.X (the reference's v0.9.5
# layout) and mx.sym.contrib.X / mx.nd.contrib.X (later-API convenience)
symbol.contrib = contrib.symbol
ndarray.contrib = contrib.ndarray
from . import monitor
from . import monitor as mon
from .monitor import Monitor
from . import profiler
from .profiler import profiler_set_config, profiler_set_state, dump_profile
from . import visualization
from . import visualization as viz
from . import models
from . import rnn
from . import model
from . import libinfo
from .model import FeedForward
from . import module
from . import module as mod
from . import predict
from . import serving
# multi-replica serving fleet (jax-free package; imported for env
# registry completeness, like serving)
from . import fleet
from . import test_utils
from . import analysis
# fused Pallas/lax kernels (registers the _FusedLSTMCell op and the
# MXTPU_FLASH_BLOCK knob — imported at package init for registry
# completeness, like serving)
from . import kernels
