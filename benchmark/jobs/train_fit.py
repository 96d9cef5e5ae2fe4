"""Job kind ``train_fit``: one ``SPMDModule.fit`` call on the cell's chips,
fed by the cell's traffic, measured from outside.

One ``fit`` call carries everything, on one module and one compiled step:

    check steps (3)  the first steps from the seeded weights, whose losses,
                     first gradient and parameter change ``compare()``
                     sets against the plain reference;
    warm-up steps    until the feed's queues are full;
    the window       ``--seconds`` of steps, from the dispatch of the first
                     to a ``block_until_ready`` on the parameters after
                     the last — the only part ``train_throughput`` sees;
    traced steps     with ``--trace 1`` a few more under the profiler.

The benchmark owns the iterator handed to ``fit`` (:class:`Feed`), the
metric (:class:`DeviceLoss`), the device-side input transform and the
weights; the program owns everything between: ``ImageRecordIter`` /
``NDArrayIter`` -> ``DevicePrefetchIter`` -> ``SPMDModule`` ->
``SPMDTrainer``'s fused step.
"""
import importlib
import json
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import compare, datagen

CHECK_STEPS = 3


def _resolve(path):
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)


class Job(object):
    def __init__(self, cell, config, traffic, limits, seed, meter):
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.meter = seed, meter
        self.chips = int(cell["chips"])
        self.ref = importlib.import_module(
            "benchmark.reference." + config["reference"])
        self.batch = int(traffic["batch_per_chip"]) * self.chips
        self.model = dict(config["model"], batch=self.batch)
        self.opt = dict(config["optimizer"]["params"])
        self.work = None
        self.faults = {}           # tests plant faults here

    # -- set-up ------------------------------------------------------------
    def setup(self):
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu.parallel import SPMDModule, default_mesh

        self.work = tempfile.mkdtemp(prefix="bench_")
        self.raw_inputs = []
        source = getattr(self, "_feed_" + self.traffic["feed"])(mx)
        sym = _resolve(self.config["program"]["symbol"])(
            *self.config["program"].get("args", []),
            **self.config["program"].get("kwargs", {}))
        sym = sym[0] if isinstance(sym, tuple) else sym
        devices = jax.devices()[:self.chips]
        self.mod = SPMDModule(
            sym, compute_dtype=self.config["program"]["compute_dtype"],
            grad_sync=self.traffic.get("grad_sync"),
            mesh=default_mesh(devices=devices))

        # the weights: one jitted call from the seed; the program gets
        # copies, since its step donates what it is given
        init = jax.jit(lambda k: self.ref.init(k, self.model),
                       out_shardings=self._replicated())
        self.w0, aux0 = init(datagen.jax_key(self.seed, 3))
        args, auxs = self.ref.to_program(
            *jax.jit(lambda t: jax.tree.map(lambda x: x + 0, t))(
                (self.w0, aux0)), self.model)
        wrap = mx.nd.NDArray._from_jax
        self.fit_args = dict(
            num_epoch=1, kvstore="tpu",
            optimizer=self.config["optimizer"]["name"],
            optimizer_params=dict(self.opt), initializer=None,
            arg_params={k: wrap(v) for k, v in args.items()},
            aux_params={k: wrap(v) for k, v in auxs.items()})
        # fit() binds, places the weights and builds the fused trainer;
        # the Feed then stages through that trainer (DevicePrefetchIter)
        self.source, self.fed, self.trainer = source, None, None

    def _mesh(self):
        """The benchmark's own mesh over the cell's chips (rows over
        ``rows``), for what it computes itself: the seeded weights, the
        norms of the program's state, the reference."""
        import jax
        return jax.sharding.Mesh(np.array(jax.devices()[:self.chips]),
                                 ("rows",))

    def _replicated(self):
        import jax
        return jax.sharding.NamedSharding(self._mesh(),
                                          jax.sharding.PartitionSpec())

    def _start_feed(self):
        """Once fit() has built its trainer: the program's prefetcher over
        the program's iterator, staging through that trainer."""
        import mxnet_tpu as mx
        from mxnet_tpu.parallel import SPMDTrainer
        self.trainer = self.mod._deferred_metric_trainer()
        assert isinstance(self.trainer, SPMDTrainer), \
            "the fused step did not engage"
        if "trainer" in self.faults:
            self.faults["trainer"](self.trainer)
        self.fed = mx.dataflow.DevicePrefetchIter(
            self.source, stage=self.trainer,
            depth=int(self.traffic["prefetch_depth"]))

    def _feed_recordio(self, mx):
        t = self.traffic
        prefix = datagen.make_recordio(
            self.work, self.seed, int(t["images"]), int(t["side"]),
            int(self.model["num_classes"]), int(t["label_ids"]),
            epoch_images=t.get("epoch_images"))
        it = mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
            data_shape=tuple(self.model["image_shape"]),
            batch_size=self.batch, shuffle=True,
            rand_crop=bool(t["rand_crop"]), rand_mirror=bool(t["rand_mirror"]),
            preprocess_threads=int(t["decode_threads"]),
            prefetch_buffer=int(t["prefetch_buffer"]), dtype="uint8",
            layout="NHWC", device_transform=self._device_transform(),
            seed=self.seed & datagen.SEED_MASK)
        name = type(it._pipeline).__name__
        assert name == "_NativePipeline", \
            "the batches come from %s, not the native decoder" % name
        return it

    def _device_transform(self):
        """uint8 NHWC -> normalised compute-dtype NCHW on the device (the
        caller's part of the input path, copied from ``chip_smoke``).  It
        keeps the raw batches of the check steps for the reference."""
        import jax
        import jax.numpy as jnp
        mean = jnp.asarray(self.model["pixel_mean"], jnp.float32)
        std = jnp.asarray(self.model["pixel_std"], jnp.float32)
        dtype = self.config["program"]["compute_dtype"]
        f = jax.jit(lambda x: jnp.transpose(
            (x.astype(jnp.float32) - mean) / std, (0, 3, 1, 2)).astype(dtype))

        def transform(x):
            if not isinstance(x, jax.core.Tracer) \
                    and len(self.raw_inputs) < CHECK_STEPS:
                self.raw_inputs.append(x)
            return f(x)
        return transform

    def _feed_tokens(self, mx):
        t = self.traffic
        data, label = datagen.make_tokens(
            self.seed, int(t["batches"]) * self.batch,
            int(self.model["seq_len"]), int(self.model["vocab_size"]))
        return mx.io.NDArrayIter(data, label, batch_size=self.batch)

    # -- the one fit call ----------------------------------------------------
    def run(self, seconds, trace_dir=None):
        """Returns the window's facts; ``self.program`` holds the check
        steps' readings afterwards."""
        import jax

        feed = Feed(self, seconds, trace_dir)
        loss = DeviceLoss()
        self.feed, self.loss = feed, loss
        self.mod.fit(feed, eval_metric=loss, batch_end_callback=feed.on_step,
                     **self.fit_args)
        assert feed.phase == "done", feed.phase
        losses = np.asarray(jax.device_get(loss.stacked()), np.float64)
        self.program["loss"] = [float(x) for x in losses]
        # a step fails if the trainer's guard skipped it, or — every step
        # of the window then — if the window left a parameter non-finite
        skipped = int(self.trainer.skipped_steps or 0)
        return {
            "steps": feed.window_steps, "seconds": feed.t_end - feed.t_start,
            "end_to_end": {"train_throughput": (
                feed.window_steps * self.items_per_step()
                / (feed.t_end - feed.t_start))},
            "failed": skipped if feed.finite else feed.window_steps,
            "waits": feed.waits, "t_start": feed.t_start,
            "traced_steps": feed.traced_steps,
            "meter_at_start": feed._before,
            "compile": feed.compile_in_window,
        }

    def items_per_step(self):
        per_row = self.traffic.get("items_per_row", 1)
        if isinstance(per_row, str):
            per_row = self.model[per_row]
        return self.batch * int(per_row)

    # -- readings of the program's first steps -------------------------------
    def after_check_step(self, k):
        """Called once step ``k`` (1-based) is dispatched: the first
        gradient as the optimizer got it (its momentum after one step,
        over minus the learning rate) and the parameters' change after the
        last check step, with their per-leaf norms."""
        import jax
        import jax.numpy as jnp
        from benchmark.reference.common import leaf_norms

        if k == 1:
            lr = float(self.opt["learning_rate"])
            mom = self.ref.from_program(
                {n: s[0] for n, s in self.trainer.opt_state.items()},
                self.model)
            grad1 = jax.jit(lambda m: {n: v / -lr for n, v in m.items()})(
                mom)
            self._grad1 = (jax.jit(leaf_norms)(grad1), grad1)
        if k == CHECK_STEPS:
            now = self.ref.from_program(dict(self.trainer.params), self.model)
            change = jax.jit(lambda a, b: {
                n: a[n] - b[n].astype(jnp.float32) for n in b})(now, self.w0)
            # the gradient and the change themselves go to the host (the
            # check steps wait for them; the window does not), so that
            # they cost the device nothing while it is measured
            norms, full = jax.device_get(
                ((self._grad1[0], jax.jit(leaf_norms)(change)),
                 {"grad1": self._grad1[1], "change": change}))
            self.program = {
                "grad1": {n: float(v) for n, v in norms[0].items()},
                "change": {n: float(v) for n, v in norms[1].items()},
                "full": full}
            self.w0 = self._grad1 = None

    # -- after the window ----------------------------------------------------
    def step_program(self):
        """(HLO text, memory analysis) of the compiled step, for a traced
        run: the trace names device events by HLO instruction, and the
        instruction's ``op_name`` — the graph node's scope — is only in the
        program's text.  Costs one more trace + lowering of the step."""
        args = self.trainer._example_args(self.feed.last_batch)
        compiled = self.trainer._step_fn.lower(*args).compile()
        mem = compiled.memory_analysis()
        return compiled.as_text(), {
            k: getattr(mem, k) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes")
            if hasattr(mem, k)}

    def release(self):
        """Free the program's state and the feed; what the reference needs
        (the check steps' inputs) stays."""
        self.fed.close()
        if hasattr(self.source, "close"):
            self.source.close()
        self.trainer.close()
        self.mod = self.trainer = self.fed = self.source = None
        self.fit_args = self.loss = None
        shutil.rmtree(self.work, ignore_errors=True)

    def check_batches(self):
        """The check steps' inputs as the reference takes them: the raw
        batch as it reached the device-side transform (or the token ids),
        and the labels the feed delivered."""
        batches = []
        for i, (data, label) in enumerate(self.feed.check_inputs):
            if self.raw_inputs:
                data = self.raw_inputs[i]
            batches.append({"data": data, "softmax_label": label})
        return batches

    def _reference_step(self, precision):
        return _cached_step(self.ref, self.model, self.opt, self.batch,
                            precision)

    def compare(self, precision="f32", batches=None, wrap=None):
        """Run the plain reference over the check steps and judge.
        Returns (correct, shown).  ``precision`` other than ``f32`` puts
        the reference in the program's place (controls, fault readings)
        and returns its readings instead (``common.differences`` sets
        them against ``self.reference``); ``wrap`` plants a fault in its
        step."""
        import jax
        from benchmark.reference import common

        batches = self.check_batches() if batches is None else batches
        if self.chips > 1:
            # a global batch of four chips does not fit one: the rows go
            # over the chips (XLA keeps the arithmetic that of one device)
            rows = jax.sharding.NamedSharding(
                self._mesh(), jax.sharding.PartitionSpec("rows"))
            batches = [jax.device_put(b, rows) for b in batches]
        params, aux = jax.jit(lambda k: self.ref.init(k, self.model),
                              out_shardings=self._replicated())(
                                  datagen.jax_key(self.seed, 3))
        step = self._reference_step(precision)
        readings = common.follow(wrap(step) if wrap else step, params, aux,
                                 batches)
        if precision != "f32":
            return readings
        self.reference = readings
        self.program = common.differences(self.program, readings)
        gaps = compare.training_gaps(self.program, readings)
        return compare.judge(gaps, self.limits)


_STEPS = {}


def _cached_step(ref, model, opt, rows, precision):
    """One jitted reference step per (configuration, precision) and
    process: the limit-setting tool follows many seeds in one process."""
    from benchmark.reference import common
    key = (ref.__name__, json.dumps(model, sort_keys=True),
           json.dumps(opt, sort_keys=True), rows, precision)
    if key not in _STEPS:
        _STEPS[key] = common.make_step(ref.loss_fn(model, precision), opt,
                                       rows)
    return _STEPS[key]


class DeviceLoss(object):
    """Built lazily as an ``EvalMetric``: mean cross-entropy of each check
    step from the step's own softmax output, reduced on the device and
    fetched when the run asks; later steps cost the device nothing (a pass
    over the LM's 35,840 x 10,000 probabilities is 3 ms of a 105 ms step)."""

    def __new__(cls):
        import jax
        import jax.numpy as jnp

        import mxnet_tpu as mx

        @jax.jit
        def ce(prob, label):
            if label.ndim == 2:                      # (B, T) -> time-major
                label = jnp.swapaxes(label, 0, 1).reshape(-1)
            p = jnp.take_along_axis(
                prob.astype(jnp.float32),
                label.astype(jnp.int32)[:, None], axis=-1)
            return -jnp.mean(jnp.log(jnp.maximum(p, 1e-30)))

        class _DeviceLoss(mx.metric.EvalMetric):
            def __init__(self):
                super().__init__("device_ce")
                self.per_step = []

            def update(self, labels, preds):
                # the label stays where it is: fetching it would make the
                # host wait on the device in every step
                if len(self.per_step) < CHECK_STEPS:
                    label = getattr(labels[0], "_data", labels[0])
                    self.per_step.append(ce(preds[0]._data, label))
                self.num_inst += 1

            def stacked(self):
                return jnp.stack(self.per_step)

        return _DeviceLoss()


def Feed(job, seconds, trace_dir):
    """The iterator ``fit`` is handed: it passes the program's own feed
    through, clocks every ``next()``, and turns the phases."""
    import jax

    import mxnet_tpu as mx

    @jax.jit
    def all_finite(tree):
        import jax.numpy as jnp
        return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x))
                                  for x in jax.tree.leaves(tree)]))

    class _Feed(mx.io.DataIter):
        def __init__(self):
            super().__init__(job.batch)
            self.phase, self.left = "check", CHECK_STEPS
            self.check_inputs, self.waits = [], []
            self.window_steps = self.traced_steps = 0
            self.done_steps = 0
            self.t_start = self.t_end = None
            self.compile_in_window = None

        provide_data = property(lambda s: job.source.provide_data)
        provide_label = property(lambda s: job.source.provide_label)

        def reset(self):
            pass

        def _pull(self):
            if job.fed is None:
                job._start_feed()
            try:
                return job.fed.next()
            except StopIteration:       # the data set's end is not fit's
                job.fed.reset()
                return job.fed.next()

        def _drain(self):
            jax.block_until_ready(job.trainer.params)
            return time.perf_counter()

        def _turn(self):
            """Phase changes, decided before a batch is pulled."""
            if self.phase == "check" and self.left == 0:
                self.phase, self.left = "warm", int(
                    job.traffic["warmup_steps"])
            if self.phase == "warm" and self.left == 0:
                self.phase = "window"
                self._before = job.meter.snapshot()
                self.t_start = self._drain()
            elif self.phase == "window" and \
                    time.perf_counter() - self.t_start >= seconds:
                self.t_end = self._drain()
                self.compile_in_window = job.meter.delta(
                    job.meter.snapshot(), self._before)
                self.finite = bool(all_finite(job.trainer.params))
                if trace_dir:
                    self.phase, self.left = "trace", int(
                        job.traffic["trace_steps"])
                    os.makedirs(trace_dir, exist_ok=True)
                    # no host TraceMe events: with them the transfer
                    # threads write a million events for one batch's
                    # layout change and stall the feed they record (1.6 s
                    # for 40 ms of work); the Python tracer stays on and
                    # names what the host was doing in each idle gap
                    options = jax.profiler.ProfileOptions()
                    options.host_tracer_level = 0
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=options)
                else:
                    self.phase = "done"
            elif self.phase == "trace" and self.left == 0:
                self._drain()
                jax.profiler.stop_trace()
                self.phase = "done"

        def next(self):
            self._turn()
            if self.phase == "done":
                raise StopIteration
            tic = time.perf_counter()
            batch = self._pull()
            wait = time.perf_counter() - tic
            if self.phase == "check":
                data, label = [
                    None if x is None else
                    x.asnumpy() if hasattr(x, "asnumpy") else np.array(x)
                    for x in (None if job.raw_inputs else batch.data[0],
                              batch.label[0])]
                self.check_inputs.append((data, label))
            elif self.phase == "window":
                self.waits.append(wait)
                self.window_steps += 1
            elif self.phase == "trace":
                self.traced_steps += 1
            if self.phase != "window":
                self.left -= 1
            self.last_batch = batch
            return job.faults["batch"](batch) if "batch" in job.faults \
                else batch

        def on_step(self, param):
            """``fit``'s batch-end callback: the step is dispatched."""
            self.done_steps += 1
            if self.done_steps <= CHECK_STEPS:
                job.after_check_step(self.done_steps)

    return _Feed()
