"""Device-side input pipelining.

The reference overlapped host IO with compute through the dependency
engine: ThreadedIter staged decoded batches while executors ran
(src/io/iter_prefetcher.h).  On TPU the equivalent critical-path hazard
is the host->device transfer itself: a synchronous per-step
``jax.device_put`` of the batch serializes the upload of batch N+1
behind the execution of batch N.  :class:`DevicePrefetchIter` closes
that gap — a background thread pulls batches from any ``DataIter``
(including a ``PrefetchingIter`` doing the decode-side overlap) and
STAGES them onto the mesh ahead of time: sharded ``device_put``, compute
dtype cast, and the multihost global-array conversion, exactly as
``SPMDTrainer._shard_batch`` would do per-step.  The consumer then feeds
:class:`~mxnet_tpu.io.StagedBatch` objects straight into
``SPMDTrainer.step`` / ``Module.forward_backward``, which skip the
transfer entirely.

Resilience: source pulls go through the shared
:func:`~mxnet_tpu.resilience.retrying_next` ladder (MXTPU_DATA_RETRIES),
errors surface on the consuming thread (never a silent hang), and
``reset()``/``close()`` shut the worker down cleanly mid-epoch.
"""
from __future__ import annotations

import logging
import queue
import threading

from . import profiler
from .base import MXNetError
from .io import DataBatch, DataIter, StagedBatch

__all__ = ["DevicePrefetchIter"]

_LOG = logging.getLogger(__name__)

#: queue sentinel: the source raised StopIteration (epoch end)
_END = object()


class _WorkerError(object):
    def __init__(self, exc):
        self.exc = exc


def _resolve_stage(stage):
    """Accept a callable, an SPMDTrainer, or a Module-like object owning a
    trainer; None means 'prefetch only, no device staging'."""
    if stage is None or callable(stage):
        return stage
    for attr in ("stage_batch",):
        fn = getattr(stage, attr, None)
        if callable(fn):
            return fn
    for attr in ("_fused", "_trainer"):
        owner = getattr(stage, attr, None)
        fn = getattr(owner, "stage_batch", None)
        if callable(fn):
            return fn
    raise MXNetError(
        "DevicePrefetchIter: stage must be a callable, an SPMDTrainer, or "
        "a module with a fused trainer (got %r)" % (stage,))


class DevicePrefetchIter(DataIter):
    """Stage the NEXT batch onto the mesh while the current step executes.

    Parameters
    ----------
    data_iter : DataIter
        Source iterator (wrap a ``PrefetchingIter`` to also overlap the
        decode side).
    stage : callable | SPMDTrainer | Module, optional
        ``stage(*arrays) -> {name: device_array}`` — normally
        ``SPMDTrainer.stage_batch`` (pass the trainer or the module and
        it is resolved).  None yields un-staged batches (pure prefetch).
    depth : int
        Number of batches staged ahead (default 2).  ``depth=0`` stages
        synchronously on the consuming thread — same batches, no
        overlap.

    Semantics: batches come out byte-identical and in order vs the
    source; a source error (after the retry ladder is exhausted) is
    raised from ``next()`` on the consuming thread, after which
    ``reset()`` realigns and restarts the worker.
    """

    def __init__(self, data_iter, stage=None, depth=2):
        super().__init__(getattr(data_iter, "batch_size", 0))
        self._iter = data_iter
        self._stage = _resolve_stage(stage)
        self.depth = max(0, int(depth))
        self._gen = 0
        self._staged = 0    # batches numbered so far: the spans' `batch`
        self._done = False
        self._stop = threading.Event()
        self._thread = None
        if self.depth > 0:
            self._queue = queue.Queue(maxsize=self.depth)
            self._start()
        else:
            self._queue = None
        self.current_batch = None

    # -- worker ------------------------------------------------------------
    def _start(self):
        # each worker owns its OWN stop event: if a stuck worker outlives
        # its join timeout in _shutdown(), its (set) event stays set and
        # it exits whenever the blocked source call returns — it can
        # never race a successor worker for the source iterator
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._worker, args=(self._gen, self._stop),
            name="DevicePrefetchIter", daemon=True)
        self._thread.start()

    def _next_staged(self):
        """Pull the source's next batch and stage it, under the feed's
        number for it (``batch_no``), which its spans carry as ``batch``."""
        from .resilience import retrying_next
        n, self._staged = self._staged, self._staged + 1
        with profiler.span("feed.source_next", batch=n):
            batch = retrying_next(self._iter, name="device_prefetch.next")
        with profiler.span("feed.stage", batch=n):
            item = self._stage_one(batch)
        item.batch_no = n
        return item

    def _worker(self, gen, stop):
        while not stop.is_set():
            try:
                item = self._next_staged()
            except StopIteration:
                item = _END
            except Exception as e:  # noqa: BLE001 — surfaced to consumer
                item = _WorkerError(e)
            self._put(gen, item, stop)
            if item is _END or isinstance(item, _WorkerError):
                return

    def _put(self, gen, item, stop):
        """Bounded put that aborts promptly on shutdown (a plain blocking
        put would deadlock close() when the consumer is gone)."""
        with profiler.span("feed.put_wait",
                           batch=getattr(item, "batch_no", None)):
            while not stop.is_set():
                try:
                    self._queue.put((gen, item), timeout=0.05)
                    return
                except queue.Full:
                    continue

    def _stage_one(self, batch):
        # deterministic fault points for the staging path: "stage_batch"
        # raises (surfaced to the consumer like a failed device_put),
        # "hang_stage" stalls the worker — the consumer then blocks in
        # next() exactly like a wedged host->device transfer, which is
        # what the fit() watchdog window is armed to catch
        from .resilience import faults
        faults.maybe_hang("hang_stage")
        faults.maybe_fail("stage_batch")
        # Transport-owned buffers (shared-memory data-service ring
        # slots override release() per instance): this worker runs
        # AHEAD of the consumer, so by the time a queued batch is
        # consumed its slot views may have been recycled — and a CPU
        # backend device_put can ALIAS numpy memory rather than copy
        # it, so even the staged arrays aren't safe.  Snapshot on this
        # background thread (off the step's critical path) and hand the
        # slot back to the producer immediately.
        release = batch.__dict__.get("release")
        if release is not None:
            import numpy as _np
            batch = DataBatch(
                [_np.array(d) for d in batch.data],
                [_np.array(l) for l in batch.label]
                if batch.label is not None else None,
                pad=batch.pad, index=batch.index,
                provide_data=batch.provide_data,
                provide_label=batch.provide_label)
            release()
        if self._stage is None:
            return batch
        arrays = list(batch.data) + list(batch.label or [])
        staged = self._stage(*arrays)
        return StagedBatch(staged, data=batch.data, label=batch.label,
                           pad=batch.pad, index=batch.index,
                           provide_data=batch.provide_data,
                           provide_label=batch.provide_label)

    # -- DataIter protocol -------------------------------------------------
    @property
    def provide_data(self):
        return self._iter.provide_data

    @property
    def provide_label(self):
        return self._iter.provide_label

    def reset(self):
        """Realign with the source: stop the worker (dropping in-flight
        staged batches), reset the source, and restart — safe mid-epoch
        and after a surfaced error."""
        self._shutdown()
        self._gen += 1
        self._done = False
        self._iter.reset()
        if self.depth > 0:
            self._queue = queue.Queue(maxsize=self.depth)
            self._start()

    def next(self):
        if self._done:
            raise StopIteration
        if self.depth == 0:
            try:
                self.current_batch = self._next_staged()
            except StopIteration:
                self._done = True
                raise
            return self.current_batch
        profiler.count("feed.gets")
        if self._queue.empty():
            profiler.count("feed.empty_gets")
        with profiler.span("feed.get_wait") as waited:
            item = self._get()
            waited.note(batch=getattr(item, "batch_no", None))
        if item is _END:
            self._done = True
            raise StopIteration
        if isinstance(item, _WorkerError):
            # the worker stopped after the error; reset() restarts it
            self._done = True
            raise item.exc
        self.current_batch = item
        return item

    def _get(self):
        """The worker's next item of this generation (blocks)."""
        while True:
            try:
                gen, item = self._queue.get(timeout=1.0)
            except queue.Empty:
                if self._thread is not None and not self._thread.is_alive():
                    raise MXNetError(
                        "DevicePrefetchIter: worker thread died without "
                        "reporting a result")
                continue
            if gen == self._gen:    # else stale, from before a reset()
                return item
    # NOTE: no `__next__ = next` here — DataIter.__next__ dispatches to
    # self.next() dynamically, so subclass overrides stay reachable from
    # for-loops (the io.py DataIter contract)

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

    # -- lifecycle ---------------------------------------------------------
    def _shutdown(self):
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            # unblock a worker stuck in put(): drain one slot
            try:
                while True:
                    self._queue.get_nowait()
            except (queue.Empty, AttributeError):
                pass
            t.join(timeout=5.0)
            if t.is_alive():  # pragma: no cover — diagnostics only
                _LOG.warning("DevicePrefetchIter: worker did not stop "
                             "within 5s")

    def close(self):
        """Stop the background worker and release queued device batches.
        Safe to call twice; the iterator raises StopIteration afterwards
        until reset()."""
        self._shutdown()
        self._done = True
        self._queue = queue.Queue(maxsize=max(1, self.depth)) \
            if self.depth > 0 else None

    def __del__(self):
        try:
            self._stop.set()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
