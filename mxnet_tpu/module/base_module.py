"""BaseModule — the high-level train/predict lifecycle (reference
python/mxnet/module/base_module.py: bind → init_params → init_optimizer →
fit/forward_backward/update/score/predict)."""
from __future__ import annotations

import logging
import time

import numpy as np

from .. import metric as metric_mod
from ..base import MXNetError
from ..io import DataBatch
from ..model import BatchEndParam
from ..ndarray import NDArray, concatenate
from ..initializer import Uniform

__all__ = ["BaseModule"]


def _as_list(obj):
    if obj is None:
        return []
    if isinstance(obj, (list, tuple)):
        return list(obj)
    return [obj]


def _check_input_names(symbol, names, typename, throw):
    args = symbol.list_arguments()
    for name in names:
        if name not in args:
            msg = "You created Module with Module(..., %s_names=%s) but " \
                  "input with name '%s' is not found in symbol.list_arguments(). " \
                  "Did you mean one of:\n\t%s\n" % (
                      typename, str(names), name, "\n\t".join(args))
            if throw:
                raise ValueError(msg)
            logging.warning(msg)


class BaseModule(object):
    """reference base_module.py:BaseModule."""

    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None
        self._total_exec_bytes = 0

    # -- high level API ----------------------------------------------------
    def forward_backward(self, data_batch):
        self.forward(data_batch, is_train=True)
        self.backward()

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None, reset=True,
              epoch=0):
        """Run prediction on eval_data and evaluate (base_module.py:score)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                       eval_metric=eval_metric, locals=locals())
                for callback in _as_list(batch_end_callback):
                    callback(params)
            actual_num_batch += 1
        if score_end_callback:
            params = BatchEndParam(epoch=epoch, nbatch=actual_num_batch,
                                   eval_metric=eval_metric, locals=locals())
            for callback in _as_list(score_end_callback):
                callback(params)
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad] for out in self.get_outputs()]
            yield (outputs, nbatch, eval_batch)

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False):
        """Collect predictions (base_module.py:predict)."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                assert len(out) == num_outputs, \
                    "Cannot merge batches, as num of outputs is not the same " \
                    "in mini-batches. Maybe bucketing is used?"
            output_list2 = [concatenate([out[i] for out in output_list])
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=Uniform(0.01), arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, checkpoint=None, resume=False,
            preemption_safe=None, watchdog=None):
        """The canonical training loop (reference base_module.py:368-520).

        ``checkpoint`` (a :class:`~mxnet_tpu.resilience.CheckpointManager`
        or a directory path) turns on managed epoch-end checkpointing:
        params + optimizer state land atomically after every epoch, with
        retention handled by the manager.  ``resume=True`` (or the
        ``MXTPU_RESUME=1`` env set by ``tools/supervise.py`` relaunches)
        restores the newest checkpoint before training — params,
        optimizer state and epoch — so a preempted run relaunched with
        the same arguments continues where it stopped.  A MID-EPOCH
        checkpoint (saved by graceful preemption, below) additionally
        carries step + RNG state: the resumed run fast-forwards the data
        iterator past the consumed batches and restores the random
        stream, making the relaunch bit-identical to the uninterrupted
        run (the iterator must be deterministic across ``reset()``, which
        every built-in iterator is).

        ``preemption_safe=True`` (or ``MXTPU_ON_PREEMPT=save``) installs
        a SIGTERM/SIGINT handler: the signal sets a flag, the next step
        boundary saves a mid-epoch checkpoint and exits with
        ``resilience.PREEMPT_EXIT_CODE`` — preemption costs at most one
        step of work, not an epoch.  Needs ``checkpoint=``.

        ``watchdog`` arms a hung-step monitor around every batch:
        ``True`` / a :class:`~mxnet_tpu.resilience.StepWatchdog`
        instance, or None to follow the ``MXTPU_STEP_TIMEOUT`` env
        (seconds, or ``auto`` to calibrate from the first steps'
        median).  An overrunning step dumps all thread stacks + device
        state (stderr and ``MXTPU_DEBUG_DIR``) and aborts with
        ``resilience.WATCHDOG_EXIT_CODE`` so a supervisor relaunches
        with resume instead of burning a pod on a wedged collective.

        Async pipeline: ``train_data`` may yield
        :class:`~mxnet_tpu.io.StagedBatch` objects (wrap it in
        ``dataflow.DevicePrefetchIter`` after ``init_optimizer``) to
        overlap the host->device transfer with the running step; on fused
        modules the train metric is accumulated in-graph (deferred — see
        MXTPU_METRIC_INTERVAL / MXTPU_METRIC_BLOCKING) or, where it stays
        on the host, updated for step N once step N+1 is dispatched
        (reads through ``get()`` stay exact; its fields alone lag), and
        MXTPU_PROFILE_DIR captures a ``jax.profiler`` trace of steps
        10-15 of the first epoch.  See docs/how_to/performance.md."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..base import get_env
        from .. import resilience
        from ..resilience import (CheckpointManager, PreemptionHandler,
                                  StepWatchdog, faults, preempted_exit)

        if checkpoint is not None and not hasattr(checkpoint, "restore"):
            checkpoint = CheckpointManager(checkpoint)
        if not resume and str(get_env(resilience.ENV_RESUME, "0")) == "1":
            # a supervise.py relaunch: same command line, resume forced
            resume = checkpoint is not None
        restored_states = None
        resume_step_state = None
        if resume:
            assert checkpoint is not None, "fit(resume=True) needs checkpoint="
            if checkpoint.latest() is not None:
                _, arg_restored, aux_restored, restored_states, ck_epoch = \
                    checkpoint.restore()
                arg_params, aux_params = arg_restored, aux_restored
                entry = checkpoint.entry(ck_epoch) or {}
                resume_step_state = entry.get("step_state")
                if resume_step_state is not None:
                    # partial (preemption) checkpoint: re-enter the
                    # interrupted epoch, not the one after it
                    begin_epoch = max(begin_epoch,
                                      int(resume_step_state["epoch"]))
                else:
                    begin_epoch = max(begin_epoch, ck_epoch)
                force_init = True
                self.logger.info("fit(resume=True): restored checkpoint "
                                 "epoch %d%s from %s", ck_epoch,
                                 " (mid-epoch, step %d)"
                                 % resume_step_state["step"]
                                 if resume_step_state else "",
                                 checkpoint.directory)

        if preemption_safe is None:
            preemption_safe = checkpoint is not None and str(
                get_env(resilience.ENV_ON_PREEMPT, "")).lower() in \
                ("save", "1")
        if preemption_safe and checkpoint is None:
            raise MXNetError("fit(preemption_safe=True) needs checkpoint= "
                             "(there is nowhere to save the mid-epoch "
                             "state)")

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)
        if restored_states is not None:
            try:
                self.set_optimizer_states(restored_states)
            except NotImplementedError:
                # module can't carry optimizer state (mirrors the save
                # side): resume params + epoch only
                self.logger.warning(
                    "fit(resume=True): %s has no optimizer-state support; "
                    "resuming params and epoch only",
                    type(self).__name__)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            eval_metric = metric_mod.create(eval_metric)
        # deferred metrics: fused modules fold the train metric's
        # (sum, count) INTO the step program so update_metric never forces
        # a per-step device->host sync (installed before the first step,
        # so the one compile already includes the accumulators; no-op on
        # the executor path / unsupported metrics / MXTPU_METRIC_BLOCKING)
        self._install_deferred_metric(eval_metric)

        # mid-epoch resume: restore the RNG stream the interrupted run
        # saved at the preemption boundary (AFTER init — the restore must
        # win over anything initialization consumed) and remember how many
        # batches of begin_epoch to fast-forward past
        fast_forward = 0
        if resume_step_state is not None:
            fast_forward = int(resume_step_state.get("step", 0))
            if resume_step_state.get("rng") is not None:
                from .. import random as _random
                _random.set_state(resume_step_state["rng"])

        from contextlib import nullcontext

        # graceful preemption + hung-step watchdog + profiler trace are
        # all set up INSIDE the try so a failure anywhere in bring-up
        # still runs the finally — a leaked signal handler would swallow
        # the process's next Ctrl-C, a leaked monitor thread its memory,
        # a leaked running trace the next fit()'s start_trace
        preempt = None
        wd = None
        own_watchdog = False
        fused_trainer = self._deferred_metric_trainer()
        trace = None
        try:
            # a metric that stays on the host is settled one step behind
            # the device, which then never waits for it
            # (_update_step_metric)
            self._lag_step_metric(eval_metric, True)
            if preemption_safe:
                # flag set by SIGTERM/SIGINT, consumed at the step
                # boundaries below.  Multi-process runs AGREE on the flag
                # at each boundary (distributed.agree_flag) so every rank
                # checkpoints at the same step instead of deadlocking in
                # mismatched collectives.
                preempt = PreemptionHandler(logger=self.logger).install()
            import jax as _jax
            preempt_sync = preempt is not None and _jax.process_count() > 1

            # fit owns the watchdog's monitor thread; the fused trainer
            # (when present) is armed too so its per-step context lands
            # in the hang report
            if watchdog is None:
                watchdog = resilience.step_timeout_configured()
            if isinstance(watchdog, StepWatchdog):
                wd = watchdog
            elif watchdog:
                wd = StepWatchdog(logger=self.logger)
                own_watchdog = True
            if wd is not None:
                wd.start()
                if fused_trainer is not None:
                    fused_trainer.install_watchdog(wd)

            # MXTPU_PROFILE_DIR: capture a jax.profiler trace of steps
            # 10-15 of the first epoch (None when the env is unset)
            from .. import profiler as _profiler
            trace = _profiler.StepTraceCapture.from_env(fused_trainer)
            _span = _profiler.span
            nstep = 0   # batches this fit() has taken: the spans' `step`

            ############################################################
            # training loop
            ############################################################
            for epoch in range(begin_epoch, num_epoch):
                tic = time.time()
                eval_metric.reset()
                data_stream = iter(train_data)
                nbatch = -1
                if epoch == begin_epoch and fast_forward > 0:
                    # fast-forward past the batches the interrupted run
                    # already trained on (deterministic iterators replay
                    # the same order after reset)
                    for _ in range(fast_forward):
                        try:
                            next(data_stream)
                        except StopIteration:
                            break
                        nbatch += 1
                    self.logger.info(
                        "fit(resume=True): fast-forwarded %d batches of "
                        "epoch %d", nbatch + 1, epoch)
                while True:
                    # the armed window covers the data fetch too — a
                    # wedged staging thread hangs the consumer in next()
                    with wd.armed("epoch %d batch %d"
                                  % (epoch, nbatch + 1)) \
                            if wd is not None else nullcontext():
                        with _span("fit.next", step=nstep) as sp:
                            try:
                                data_batch = next(data_stream)
                            except StopIteration:
                                break
                            # the feed's number of this batch, which its
                            # worker's spans carry too
                            sp.note(batch=getattr(data_batch, "batch_no",
                                                  None))
                        nbatch += 1
                        if trace is not None:
                            trace.on_batch(nbatch)
                        if monitor is not None:
                            monitor.tic()
                        with _span("fit.step", step=nstep):
                            self.forward_backward(data_batch)
                            self.update()
                        with _span("fit.metric", step=nstep):
                            self.update_metric(eval_metric, data_batch.label)
                        if monitor is not None:
                            monitor.toc_print()
                        if batch_end_callback is not None:
                            with _span("fit.callback", step=nstep):
                                batch_end_params = BatchEndParam(
                                    epoch=epoch, nbatch=nbatch,
                                    eval_metric=eval_metric,
                                    locals=locals())
                                for callback in _as_list(batch_end_callback):
                                    callback(batch_end_params)
                        nstep += 1
                    # step boundary: consume a pending preemption —
                    # checkpoint mid-epoch and exit cleanly for the
                    # supervisor to relaunch with resume
                    if preempt is not None:
                        if faults.consume("preempt"):
                            # in-band drill: deliver a REAL signal so the
                            # whole handler path is what gets tested
                            import os as _os
                            import signal as _signal
                            _os.kill(_os.getpid(), _signal.SIGTERM)
                            time.sleep(0.05)  # let the handler run
                        triggered = preempt.triggered
                        if preempt_sync:
                            # all ranks take the same branch at the same
                            # boundary (any rank signaled => all save)
                            from .. import distributed as _dist
                            triggered = _dist.agree_flag(triggered)
                        if triggered:
                            self._save_preemption_checkpoint(
                                checkpoint, epoch, nbatch + 1)
                            preempted_exit()
                if trace is not None:
                    trace.stop()  # epoch shorter than the window: close
                    trace = None  # first epoch only

                # one epoch of training is finished
                self._settle_metric()
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name,
                                     val)
                toc = time.time()
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch,
                                 (toc - tic))

                # sync aux params across devices
                arg_params_, aux_params_ = self.get_params()
                self.set_params(arg_params_, aux_params_)

                if epoch_end_callback is not None:
                    for callback in _as_list(epoch_end_callback):
                        callback(epoch, self.symbol, arg_params_,
                                 aux_params_)

                if checkpoint is not None:
                    # gather happens above on EVERY rank (collective under
                    # sharded params); the manager then writes on rank 0
                    # only
                    try:
                        states = self.get_optimizer_states()
                    except NotImplementedError:
                        states = None
                    checkpoint.save(epoch + 1, self.symbol, arg_params_,
                                    aux_params_, optimizer_states=states)

                # ----------------------------------------
                # evaluation on validation set
                if eval_data:
                    res = self.score(
                        eval_data, validation_metric,
                        score_end_callback=eval_end_callback,
                        batch_end_callback=eval_batch_end_callback,
                        epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f",
                                         epoch, name, val)

                # end of 1 epoch, reset the data-iter for another epoch
                train_data.reset()
            # drain the async checkpoint writers so every epoch's save is
            # durable (and any background write failure surfaces here)
            # before fit() reports success — the manager's own writer AND
            # the shared default writer behind prefix-based saves
            # (epoch_end_callback=do_checkpoint(prefix) queues there; the
            # writer thread is a daemon, so an undrained write could be
            # killed mid-flight at interpreter exit)
            if checkpoint is not None and hasattr(checkpoint, "wait"):
                checkpoint.wait()
            from ..resilience import wait_checkpoints
            wait_checkpoints()
        finally:
            self._lag_step_metric(eval_metric, False)
            if trace is not None:
                trace.stop()
            if preempt is not None:
                preempt.uninstall()
            if wd is not None:
                if fused_trainer is not None:
                    fused_trainer.install_watchdog(None)
                if own_watchdog:
                    wd.stop()

    def _save_preemption_checkpoint(self, checkpoint, epoch, step):
        """Mid-epoch checkpoint at a step boundary: params + optimizer
        state under the SAME epoch number the epoch-end save will use
        (epoch + 1), plus a ``step_state`` manifest record — epoch index,
        batches consumed, RNG stream — that ``fit(resume=True)`` uses to
        fast-forward.  The later epoch-end save of the same number
        replaces the partial entry.

        The exit-85 contract requires the checkpoint to be ON DISK when
        the process exits: any in-flight async save is drained first
        (best-effort — this blocking save supersedes whatever the failed
        write would have published) and the preemption save itself is
        always blocking, MXTPU_CKPT_ASYNC notwithstanding."""
        from .. import random as _random
        from ..resilience import CheckpointManager, wait_checkpoints
        # BOUNDED drain of the shared default writer (prefix-based async
        # saves): a wedged — not failed — background write must not eat
        # the whole preemption grace period; a timeout surfaces as the
        # same MXNetError a failed write would.  The manager's own
        # writer is drained inside save(blocking=True) below, equally
        # bounded; the blocking save supersedes whatever was in flight.
        try:
            wait_checkpoints(timeout=CheckpointManager.DRAIN_TIMEOUT / 2)
        except Exception as e:  # noqa: BLE001 — superseded below
            self.logger.warning(
                "preemption: in-flight async checkpoint write failed "
                "(%s: %s) — the blocking preemption save below "
                "supersedes it", type(e).__name__, e)
        arg_params_, aux_params_ = self.get_params()
        try:
            states = self.get_optimizer_states()
        except NotImplementedError:
            states = None
        checkpoint.save(epoch + 1, self.symbol, arg_params_, aux_params_,
                        optimizer_states=states, blocking=True,
                        step_state={"epoch": int(epoch), "step": int(step),
                                    "rng": _random.get_state()})
        from ..resilience import PREEMPT_EXIT_CODE
        self.logger.warning(
            "preemption: saved mid-epoch checkpoint (epoch %d, step %d) "
            "to %s; exiting with code %d — relaunch with resume to "
            "continue", epoch, step, checkpoint.directory,
            PREEMPT_EXIT_CODE)

    # -- symbol / params ---------------------------------------------------
    @property
    def symbol(self):
        return self._symbol

    def get_params(self):
        raise NotImplementedError

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        raise NotImplementedError

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {("arg:%s" % k): v for k, v in arg_params.items()}
        save_dict.update({("aux:%s" % k): v for k, v in aux_params.items()})
        from .. import ndarray as nd
        from ..resilience import atomic_path
        with atomic_path(fname) as tmp:
            nd.save(tmp, save_dict)

    def load_params(self, fname):
        from .. import ndarray as nd
        save_dict = nd.load(fname)
        arg_params = {}
        aux_params = {}
        for k, value in save_dict.items():
            arg_type, name = k.split(":", 1)
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError("Invalid param file " + fname)
        self.set_params(arg_params, aux_params)

    def _deferred_metric_trainer(self):
        """The fused SPMDTrainer that can carry in-graph metrics, or None
        — the base has none, so every module type stays on the classic
        blocking path unless it overrides this."""
        return None

    def _install_deferred_metric(self, eval_metric):
        """fit() hook: move the train metric's accumulation into the
        fused step program (metric.try_install_deferred).  Detaches any
        previously installed metric and uninstalls a stale in-graph rule
        when the new metric cannot defer, so a second fit() never leaks
        the first run's accumulators or steals its deltas."""
        from .. import metric as metric_mod
        prev = getattr(self, "_deferred_metric", None)
        if prev is not None:
            prev.detach_deferred_source()
        self._deferred_metric = None
        self._deferred_interval = 0
        self._deferred_calls = 0
        trainer = self._deferred_metric_trainer()
        if trainer is None:
            return
        interval = metric_mod.try_install_deferred(trainer, eval_metric)
        if interval is None:
            if getattr(trainer, "_metric_fn", None) is not None:
                trainer.install_metric(None)
            return
        self._deferred_metric = eval_metric
        self._deferred_interval = interval

    def _deferred_metric_update(self, eval_metric):
        """True when ``eval_metric`` is accumulated in-graph for train
        steps (the per-step host update must be skipped); folds the
        device totals every ``_deferred_interval`` calls."""
        if getattr(self, "_deferred_metric", None) is not eval_metric:
            return False
        self._deferred_calls += 1
        if self._deferred_interval > 0 and \
                self._deferred_calls % self._deferred_interval == 0:
            eval_metric.fold_deferred()
        return True

    # -- a fused train step's host-side metric, one step behind -----------
    _metric_lags = False    # True inside fit()'s loop on a fused module
    _owed_metric = None     # (eval_metric, labels, outputs, guard copy)

    def _update_step_metric(self, eval_metric, labels):
        """``eval_metric.update`` from the outputs of the fused train step
        just dispatched, unless the guard skipped it (its outputs are
        non-finite: one NaN into a summing metric would poison the whole
        epoch's Train-* rows).

        Called by hand this waits for the step: the metric is current
        when the call returns.  Inside ``fit`` the step's labels, outputs
        and guard counters are only noted, and settled once the NEXT step
        is in the device's queue (or when something reads or resets the
        metric), so neither the guard's answer nor an ``asnumpy`` in
        ``update`` makes the device wait for the host.  The iterator's
        label arrays must stay as they are until then, which a batch
        staged ahead by ``DevicePrefetchIter`` needs of them anyway.
        Step N's record goes before step N+2 is dispatched, so two
        steps' outputs are alive at a dispatch, as when the metric was
        updated at once; an ``update`` that launches device work on the
        outputs (and fetches nothing) keeps them until that work has
        run, behind step N+1: a third step's outputs are then alive."""
        outputs = self.get_outputs()
        trainer = self._deferred_metric_trainer()
        if self._metric_lags:
            self._settle_metric()
            self._owed_metric = (eval_metric, labels, outputs,
                                 trainer.guard_snapshot())
            return
        trainer.flush_step_guard()
        if not trainer.last_step_skipped:
            eval_metric.update(labels, outputs)

    def _settle_metric(self):
        """Pay the metric the step that is owed to it; returns the
        nothing a metric's deferred source has left to add."""
        owed, self._owed_metric = self._owed_metric, None
        if owed is not None:
            eval_metric, labels, outputs, snap = owed
            if not self._deferred_metric_trainer().step_skipped(snap):
                eval_metric.update(labels, outputs)
        return 0.0, 0.0

    def _drop_owed_metric(self):
        self._owed_metric = None

    def _lag_step_metric(self, eval_metric, on):
        """fit()'s switch, on for its loop and off in its ``finally``.
        While on, every read of ``eval_metric`` (``get``,
        ``get_name_value``, a child of a composite) settles the owed step
        first and ``reset`` drops it, through the hook a metric has for a
        source that lags (``attach_deferred_source``): a callback sees
        what it saw when the metric was updated at once.  Off, nothing is
        owed any more: no step's outputs outlive ``fit`` here."""
        self._owed_metric = None
        if self._deferred_metric_trainer() is None:
            return   # the executor path: nothing is fused, nothing lags
        self._metric_lags = on
        if getattr(self, "_deferred_metric", None) is eval_metric:
            return   # accumulated in-graph: the hook is the trainer's
        nodes = [eval_metric]
        for m in nodes:
            nodes.extend(getattr(m, "metrics", ()))
            if on:
                m.attach_deferred_source(self._settle_metric,
                                         self._drop_owed_metric)
            else:
                m.detach_deferred_source()

    def get_optimizer_states(self):
        """Serialized optimizer state (bytes), for managed checkpointing.
        Subclasses with an optimizer implement this; the base raises so
        ``fit(checkpoint=...)`` degrades to params-only checkpoints."""
        raise NotImplementedError

    def set_optimizer_states(self, states):
        raise NotImplementedError

    # -- abstract interface ------------------------------------------------
    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels):
        raise NotImplementedError

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError

    def install_monitor(self, mon):
        raise NotImplementedError
