"""Device context.

Mirrors the reference's ``Context`` (python/mxnet/context.py) with a TPU-first
mapping: ``mx.tpu(i)`` is the native device; ``mx.gpu(i)`` is accepted as an
alias for the i-th accelerator so reference scripts run unmodified
(BASELINE.json north star); ``mx.cpu(i)`` maps to the i-th XLA host-platform
device, which is how multi-device semantics are tested without hardware
(reference tests/python/unittest/test_model_parallel.py:30-31 uses cpu(0)/cpu(1)
the same way).
"""
from __future__ import annotations

import os
import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus", "num_tpus"]


class Context(object):
    """A device context. devtype ids follow the reference
    (include/mxnet/base.h Context::kCPU=1, kGPU=2, kCPUPinned=3) with kTPU=4
    appended."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    # -- JAX mapping ------------------------------------------------------
    @property
    def jax_device(self):
        """The jax.Device this context denotes.

        Always a device addressable by THIS process: under the multi-process
        runtime (distributed.py) ``jax.devices()`` also lists peers' devices,
        but a worker's ``tpu(i)`` means its own i-th chip, exactly as a
        reference worker's ``gpu(i)`` is its local GPU i.
        """
        import jax
        if self.device_typeid in (1, 3):
            devs = (jax.local_devices(backend="cpu") if _has_platform("cpu")
                    else jax.local_devices())
        else:
            # tpu/gpu name the default backend's devices: the chips on a
            # TPU host, the host-platform CPU devices where there is none
            # (the test mesh).  Code that must not run off-chip checks
            # ``jax_device.platform`` itself (chip_smoke.py).
            devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "%s: device_id %d out of range (%d %s devices visible)"
                % (self, self.device_id, len(devs), devs[0].platform if devs else "?"))
        return devs[self.device_id]

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx


def _has_platform(name):
    import jax
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _default_device_type():
    """tpu if an accelerator backend is present, else cpu."""
    import jax
    plat = jax.default_backend()
    return "cpu" if plat == "cpu" else "tpu"


def cpu(device_id=0):
    """Return a CPU context (host-platform XLA device)."""
    return Context("cpu", device_id)


def gpu(device_id=0):
    """Accelerator alias so reference scripts using mx.gpu() run on TPU."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    """Return a TPU context — the native device of this framework."""
    return Context("tpu", device_id)


def num_gpus():
    import jax
    return 0 if jax.default_backend() == "cpu" else len(jax.local_devices())


def num_tpus():
    return num_gpus()


def current_context():
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context(_default_device_type(), 0)
    return Context._default_ctx.value
