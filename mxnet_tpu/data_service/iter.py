"""``DataIter`` facade over a data service (local OR network tier).

Split out of :mod:`.service` so the coordinator itself stays jax-free
(``tools/data_server.py`` runs it on remote CPU hosts through the
synthetic-package stub); this module pulls in :mod:`..io`, which sits
on the jax side of the fence.

The facade works over anything with the service collector surface
(``next_batch``/``reset``/``seek``/``stats``/``close`` plus the
``_bs``/``_lw``/``_dtype``/``_ring_shape`` layout attrs) — today that
is :class:`.service.DataService` (shared-memory rings on this host)
and :class:`.net.NetDataService` (TCP frames from a remote server
fleet), so every consumer-side contract is written once.
"""
from __future__ import annotations

import numpy as np

from ..io import DataBatch, DataDesc, DataIter

__all__ = ["DataServiceIter"]


class DataServiceIter(DataIter):
    """`DataIter` facade over :class:`.service.DataService` (or
    :class:`.net.NetDataService`): host numpy batches (the
    ``host_batches`` analog of the in-process native pipe).

    ``copy=True`` (the safe default) hands each consumer a private
    array.  ``copy=False`` hands the transport-owned VIEW itself (a
    shared-memory ring slot locally, a receive buffer on the network
    tier) — fastest, but only for strictly serial consumers: the array
    is valid until ``batch.release()`` or the next pull, and anything
    "uploading" it must truly copy (on the CPU backend
    ``jax.device_put`` ALIASES numpy memory; use
    ``jnp.array(view, copy=True)``).  ``ImageRecordIter``'s
    ``host_batches`` service mode uses ``copy=False``; wrapping either
    flavor in
    ``dataflow.DevicePrefetchIter(stage=trainer)`` is safe — the
    prefetcher snapshots slot-backed batches on its background thread
    and releases the slot before running ahead."""

    def __init__(self, service=None, data_name="data",
                 label_name="softmax_label", copy=True, **kwargs):
        if service is None:
            from .service import DataService
            service = DataService(**kwargs)
        self._service = service
        super().__init__(self._service._bs)
        self._copy = bool(copy)
        self.data_name = data_name
        self.label_name = label_name
        self.current_batch = None

    @property
    def provide_data(self):
        svc = self._service
        dt = np.dtype("float32" if svc._dtype == "bfloat16" else svc._dtype)
        return [DataDesc(self.data_name, (svc._bs,) + svc._ring_shape,
                         dtype=dt)]

    @property
    def provide_label(self):
        svc = self._service
        shape = (svc._bs, svc._lw) if svc._lw > 1 else (svc._bs,)
        return [DataDesc(self.label_name, shape)]

    def next(self):
        data, labels, pad, release = self._service.next_batch()
        batch = DataBatch([data], [labels], pad=pad,
                          provide_data=self.provide_data,
                          provide_label=self.provide_label)
        # the device-side augmentation seam reads these: the per-batch
        # chunk seed (same value any worker/server count) and validity
        batch.aug_seed = self._service.last_aug_seed
        if self._copy:
            # already private: copy now, recycle the slot, and do NOT
            # attach the instance-level release — its presence is the
            # "transport-owned buffers" signal DevicePrefetchIter keys
            # its snapshot on, which would re-copy every batch
            batch.data = [np.array(data)]
            release()
        else:
            batch.release = release
        self.current_batch = batch
        return batch

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getpad(self):
        return self.current_batch.pad

    def reset(self):
        self._service.reset()

    def stats(self):
        return self._service.stats()

    def close(self):
        self.current_batch = None   # drop the last zero-copy view
        self._service.close()
