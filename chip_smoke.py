#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments, run from the root of a copy of the repo:

    python chip_smoke.py

It drives the main path once, through the entry points a user calls, at the
full width of ResNet-50 (1000 classes, 224x224, bf16 compute): a seeded
RecordIO file -> ``ImageRecordIter`` (uint8 NHWC, native libjpeg decode) ->
``DevicePrefetchIter`` -> ``SPMDModule.fit`` for a few dozen steps with
managed checkpoints; then the checkpoint is loaded into a ``ModelPool``
behind a ``ServingFrontend`` in a thread of this process and answers
``ServeClient`` requests at buckets 1 and 32, compared with an f32
``Predictor``; a ``jax.profiler`` trace of the last training steps must hold
a TPU plane with events; a calibration phase settles whether
``block_until_ready`` is a completion barrier here; with four devices the
training phase repeats on a dp=4 mesh (64 a chip) under allreduce and zero3.

It exits non-zero — before compiling anything — unless JAX's default
device is a TPU, and whenever a phase fails: nothing is caught to carry on
with another device, kernel tier or pipeline.  The last line of its
standard output is one JSON object with the device as JAX reports it.

The phases are importable functions with their sizes as arguments, so the
tier-1 tests drive them at toy sizes on the CPU mesh; only :func:`main`
checks for the chip.
"""
import glob
import json
import os
import shutil
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

import numpy as np

# -- the sizes main() runs at -------------------------------------------------
MODEL = "resnet-50"
NUM_CLASSES = 1000          # the head stays 1000 wide ...
LABEL_CLASSES = 8           # ... labels come from a few classes (a divisor
#                             of 16: make_dataset), so that thirty steps
#                             can show the optimizer acting
IMAGE = 224
BATCH = 256                 # fits 16 GB
STEPS_PER_EPOCH = 8
EPOCHS = 4                  # 32 steps
SERVE_BUCKETS = (1, 32)
MULTICHIP_BATCH = 64        # per chip: the zero3 shard_map step keeps 4.6x
#                             the activations of the allreduce step and
#                             wants 25.7 GB at 256 a chip (PERF.md)
MULTICHIP_EPOCHS = 1        # 8 steps of 4 x 64 a strategy

_MEAN = (123.68, 116.28, 103.53)
_STD = (58.395, 57.12, 57.375)


def log(msg):
    print(msg, flush=True)


def mark():
    """(perf-counter seconds, the program's counters) now, for
    :func:`since`."""
    from mxnet_tpu import profiler
    return time.perf_counter(), profiler.counters()


def since(snap):
    """{wall_s, compile_s, run_s, cache_hits, cache_misses} since the
    :func:`mark` ``snap``, from the program's own recorder: ``compile.*``
    events (trace + lowering + backend compile, a cache hit's retrieval
    included) and the ``compile.cache_*`` counters; run_s is the rest of
    the wall."""
    from mxnet_tpu import profiler
    now, counted = mark()
    wall = now - snap[0]
    comp = sum(r["end"] - r["start"] for r in profiler.spans(since=snap[0])
               if r["name"].startswith("compile."))
    return {"wall_s": round(wall, 2), "compile_s": round(comp, 2),
            "run_s": round(max(0.0, wall - comp), 2),
            **{key: counted.get("compile." + key, 0)
               - snap[1].get("compile." + key, 0)
               for key in ("cache_hits", "cache_misses")}}


def device_report():
    """What JAX reports, printed before any work."""
    import jax
    devs = jax.devices()
    rep = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("jax %s  platform=%s  device_kind=%r  devices=%d"
        % (jax.__version__, rep["platform"], rep["kind"], rep["count"]))
    return rep


def result_line(dev):
    """The last line of stdout, parsed by the driver: exactly the keys "ok"
    and "device" (platform, kind, count) — anything else goes on the
    ``summary:`` line before it."""
    return json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": int(dev["count"])}})


def cache_entries():
    """(compile-cache directory, number of cached programs in it)."""
    import jax
    d = jax.config.jax_compilation_cache_dir
    n = len([f for f in glob.glob(os.path.join(d, "*"))
             if os.path.isfile(f) and not f.endswith("-atime")]) if d else 0
    return d, n


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# calibration: is block_until_ready a completion barrier on this machine?
# ---------------------------------------------------------------------------

def phase_calibration(n=8192, chain=32, peak_tflops=None):
    """Time a dependent chain of ``chain`` (n x n) bf16 matmuls twice: ended
    by ``block_until_ready`` and ended by fetching a scalar that depends on
    the result.  Both rates must sit inside the chip's peak (the row of
    this ``device_kind`` in ``benchmark/peaks.json``; an unknown kind
    raises)."""
    import jax
    import jax.numpy as jnp

    kind = jax.devices()[0].device_kind
    if peak_tflops is None:
        from benchmark.flops import peaks
        peak_tflops = peaks(kind)["bf16_flops"] / 1e12
    a = jnp.full((n, n), 0.01, jnp.bfloat16)

    @jax.jit
    def run(x):
        return jax.lax.fori_loop(
            0, chain, lambda _, y: (y @ a).astype(jnp.bfloat16), x)

    x = jnp.ones((n, n), jnp.bfloat16)
    run(x).block_until_ready()                      # compile + warm
    flops = 2.0 * n ** 3 * chain

    def rate(sync):
        best = 0.0
        for _ in range(2):
            tic = time.perf_counter()
            sync(run(x))
            best = max(best, flops / (time.perf_counter() - tic) / 1e12)
        return best

    out = {
        "device_kind": kind, "peak_tflops": peak_tflops,
        "block_until_ready_tflops": rate(lambda y: y.block_until_ready()),
        "scalar_fetch_tflops": rate(
            lambda y: float(jnp.sum(y[:1, :8].astype(jnp.float32)))),
    }
    log("calibration: %d x (%d x %d) bf16 matmul on %r: %.1f TFLOP/s under "
        "block_until_ready, %.1f TFLOP/s under a dependent scalar fetch, "
        "peak %.1f"
        % (chain, n, n, kind, out["block_until_ready_tflops"],
           out["scalar_fetch_tflops"], peak_tflops))
    for key in ("block_until_ready_tflops", "scalar_fetch_tflops"):
        if out[key] > peak_tflops:
            raise RuntimeError(
                "calibration: %s = %.1f TFLOP/s exceeds the %.1f TFLOP/s "
                "peak of %r — that barrier returns before the device is "
                "done" % (key, out[key], peak_tflops, kind))
    return out


# ---------------------------------------------------------------------------
# training through fit(), fed from RecordIO
# ---------------------------------------------------------------------------

def make_dataset(n_img, side=256, classes=1000, directory=None):
    """A seeded RecordIO file of JPEGs with the statistics of photographs
    (smooth gradients plus low-frequency texture, ~13 KB an image at q90;
    white noise carries ~4x the entropy and decodes several times slower
    than any photo).  Image i carries texture i % 16 and label i % classes,
    so where ``classes`` divides 16 the label can be learnt from the
    pixels: that is why the smoke may assert that its loss falls, and why
    it does not take ``benchmark.datagen.make_recordio``, whose labels are
    drawn from the seed.  ``classes`` must not exceed the head: a label out
    of range one-hots to a zero row under SoftmaxOutput and the loss
    diverges.  Written under ``directory`` (default: a fresh temporary
    one); returns the prefix of the ``.rec`` / ``.idx`` pair."""
    import cv2

    from mxnet_tpu import recordio

    prefix = os.path.join(
        directory or tempfile.mkdtemp(prefix="chip_smoke_rec_"), "smoke")
    rs = np.random.RandomState(0)
    xs = np.linspace(0, 1, side)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    tex_bank = [
        cv2.GaussianBlur(rs.randn(side, side, 3).astype(np.float32) * 40,
                         (7, 7), 0) for _ in range(16)]
    for i in range(n_img):
        base = (np.outer(xs, np.roll(xs, (i * 37) % side))[..., None]
                * np.array([255, 180, 120])).astype(np.float32)
        img = np.clip(base + tex_bank[i % 16], 0, 255).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % classes), i, 0)
        rec.write_idx(i, recordio.pack_img(header, img, quality=90))
    rec.close()
    return prefix


def _device_transform():
    """uint8 NHWC -> normalized bf16 NCHW, on the device."""
    import jax
    import jax.numpy as jnp
    mean = jnp.array(_MEAN, jnp.float32)
    std = jnp.array(_STD, jnp.float32)
    return jax.jit(lambda x: jnp.transpose(
        (x.astype(jnp.float32) - mean) / std, (0, 3, 1, 2))
        .astype(jnp.bfloat16))


def _staged_step_ms(trainer, batch, steps):
    """Steady wall ms of one fused step on an already staged batch."""
    import jax
    trainer.step(batch)
    jax.block_until_ready(trainer.params)
    tic = time.perf_counter()
    for _ in range(steps):
        trainer.step(batch)
    jax.block_until_ready(trainer.params)
    return (time.perf_counter() - tic) / steps * 1e3


def phase_train(symbol, rec_prefix, image, batch, epochs, ckpt_dir=None,
                devices=None, grad_sync=None, trace_dir=None,
                lr=0.05, decode_threads=8, timed_steps=5):
    """``SPMDModule(compute_dtype='bfloat16').fit`` over the RecordIO file
    at ``rec_prefix``: ImageRecordIter (uint8 NHWC, native decode) ->
    DevicePrefetchIter -> fused step, ``epochs`` epochs, with managed
    checkpoints into ``ckpt_dir`` and a profiler trace of the last three
    steps into ``trace_dir`` (each skipped when None).  ``devices`` bounds
    the dp mesh (default: the first device only).

    Asserts what must hold on the chip path: the native pipeline served the
    batches, the fused trainer exists, the loss is finite at every step.
    Returns the facts main() prints — the per-step losses, and what
    the lint of the compiled step found in it."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import native
    from mxnet_tpu.parallel import SPMDModule, SPMDTrainer, default_mesh
    from mxnet_tpu.profiler import StepTraceCapture

    devices = list(devices or jax.devices()[:1])
    train_iter = mx.io.ImageRecordIter(
        path_imgrec=rec_prefix + ".rec", path_imgidx=rec_prefix + ".idx",
        data_shape=(3, image, image), batch_size=batch, shuffle=True,
        rand_crop=True, rand_mirror=True, preprocess_threads=decode_threads,
        prefetch_buffer=4, dtype="uint8", layout="NHWC",
        device_transform=_device_transform(), seed=0)
    pipeline = type(train_iter._pipeline).__name__
    assert pipeline == "_NativePipeline", (
        "the batches come from %s, not the native libjpeg pipeline"
        % pipeline)

    mod = SPMDModule(symbol, compute_dtype="bfloat16", grad_sync=grad_sync,
                     mesh=default_mesh(devices=devices))
    opt = {"learning_rate": lr, "momentum": 0.9, "wd": 1e-4}
    init = mx.initializer.Xavier(rnd_type="gaussian", factor_type="in",
                                 magnitude=2)
    mod.bind(train_iter.provide_data, train_iter.provide_label)
    mod.init_params(init)
    mod.init_optimizer(kvstore="tpu", optimizer="sgd", optimizer_params=opt)
    trainer = mod._deferred_metric_trainer()
    assert isinstance(trainer, SPMDTrainer), "the fused step did not engage"
    fed = mx.dataflow.DevicePrefetchIter(train_iter, stage=mod, depth=2)

    with open(rec_prefix + ".idx") as f:
        total = epochs * (sum(1 for _ in f) // batch)
    losses, stamps = [], [time.perf_counter()]
    seen = [0.0, 0]
    # the window opens once step total-3 has completed: the last three steps
    trace = StepTraceCapture(trace_dir, total - 3, total, trainer=trainer) \
        if trace_dir else None

    def on_batch(param):
        # the metric accumulates over the epoch; the step's own loss is
        # the difference to what it held a step ago.  get() first: fit
        # settles a step's metric one step behind the device unless the
        # metric is read, and the fields alone are no read
        m = param.eval_metric
        m.get()
        if param.nbatch == 0:
            seen[0], seen[1] = 0.0, 0
        losses.append(float(m.sum_metric - seen[0])
                      / (m.num_inst - seen[1]))
        seen[0], seen[1] = m.sum_metric, m.num_inst
        stamps.append(time.perf_counter())
        if trace is not None:
            trace.on_batch(len(losses))

    try:
        mod.fit(fed, num_epoch=epochs, eval_metric=mx.metric.CrossEntropy(),
                kvstore="tpu", optimizer="sgd", optimizer_params=opt,
                initializer=init, batch_end_callback=on_batch,
                checkpoint=ckpt_dir)
        if trace is not None:
            trace.stop()
        assert len(losses) == total, (len(losses), total)
        assert losses and all(np.isfinite(losses)), \
            "non-finite loss: %r" % (losses,)

        # one more pass over a single staged batch: where its shards sit,
        # what the compiled step contains, and its steady step time with
        # the input path out of the picture
        fed.reset()
        staged = next(fed)
        shard_devices = sorted(
            str(s.device) for s in
            staged.staged[trainer.input_names[0]].addressable_shards)
        report = trainer.analyze(staged)
        step_ms = _staged_step_ms(trainer, staged, timed_steps)
    finally:
        fed.close()
        train_iter.close()
        trainer.close()

    lib = native.get_lib()
    gaps = np.diff(stamps)
    return {
        "steps": len(losses), "first_loss": losses[0],
        "last_loss": losses[-1], "losses": losses,
        "first_step_s": float(gaps[0]),
        "fed_step_ms": float(np.median(gaps[1:]) * 1e3),
        "staged_step_ms": step_ms,
        "pipeline": pipeline, "native_lib": lib is not None,
        "native_imagedec": hasattr(lib, "MXTPUImgPipeDecodeBatch"),
        "grad_sync": trainer.grad_sync, "shard_devices": shard_devices,
        "pallas_kernels": report.stats["pallas_kernels"],
        "collectives": {k: v["count"] for k, v in
                        report.stats["collectives"].items() if v["count"]},
        "step_memory": report.stats["memory"],
    }


def phase_second_trainer(symbol, image, batch, steps=5):
    """A second trainer of the same program, built after the first one was
    closed, stepping on a staged random batch: its steady step time, to set
    beside the first trainer's (ROADMAP Speed 6)."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer, default_mesh
    import jax

    trainer = SPMDTrainer(
        symbol, "sgd", {"learning_rate": 0.05, "momentum": 0.9, "wd": 1e-4,
                        "rescale_grad": 1.0 / batch},
        mesh=default_mesh(devices=jax.devices()[:1]),
        compute_dtype="bfloat16")
    try:
        trainer.bind([("data", (batch, 3, image, image))],
                     [("softmax_label", (batch,))])
        trainer.init_params(mx.initializer.Xavier(
            rnd_type="gaussian", factor_type="in", magnitude=2))
        rs = np.random.RandomState(1)
        staged = mx.io.StagedBatch(trainer.stage_batch(
            rs.rand(batch, 3, image, image).astype("f"),
            rs.randint(0, LABEL_CLASSES, batch).astype("f")))
        return {"staged_step_ms": _staged_step_ms(trainer, staged, steps)}
    finally:
        trainer.close()


def check_trace(trace_dir, plane_prefix):
    """The newest trace under ``trace_dir`` must hold a plane whose name
    starts with ``plane_prefix`` and has events; returns {plane: events}."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, "no .xplane.pb under %s" % trace_dir
    data = jax.profiler.ProfileData.from_file(
        max(paths, key=os.path.getmtime))
    planes = {p.name: sum(len(list(line.events)) for line in p.lines)
              for p in data.planes}
    assert any(name.startswith(plane_prefix) and n > 0
               for name, n in planes.items()), (
        "no %s* plane with events in the trace: %r" % (plane_prefix, planes))
    return {k: v for k, v in planes.items() if v}


# ---------------------------------------------------------------------------
# serving the checkpoint
# ---------------------------------------------------------------------------

def sample_inputs(rec_prefix, n, image):
    """The first ``n`` images of the dataset as the net receives them:
    centre crop, normalized, float32 NCHW."""
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(rec_prefix + ".idx",
                                     rec_prefix + ".rec", "r")
    try:
        xs = []
        for i in range(n):
            _, img = recordio.unpack_img(rec.read_idx(i))
            off = (img.shape[0] - image) // 2
            crop = img[off:off + image, off:off + image, ::-1]   # BGR->RGB
            xs.append(((crop.astype(np.float32) - _MEAN) / _STD)
                      .transpose(2, 0, 1))
    finally:
        rec.close()
    return np.stack(xs).astype(np.float32)


def phase_serve(ckpt_dir, xs, buckets=SERVE_BUCKETS):
    """Load the newest checkpoint of ``ckpt_dir`` into a bf16 ``ModelPool``,
    serve it from a ``ServingFrontend`` thread of this process, and answer
    one ``ServeClient`` request per row of ``xs``: the first alone (the
    smallest bucket), the rest at once (padded to a larger one).  Outputs
    must be finite and agree with an f32 ``Predictor`` on the same inputs
    within the repo's bf16 serving tolerance (tests/test_serving.py)."""
    import mxnet_tpu as mx
    from mxnet_tpu.serving import ModelPool, ServeClient, ServingFrontend
    from mxnet_tpu.serving import aot

    shape = tuple(xs.shape[1:])
    pool = ModelPool(dtype="bfloat16")
    entry = pool.load_dir("smoke", ckpt_dir, sample_shapes={"data": shape})
    # the daemon's warm-up (tools/serve.py): executables found in the AOT
    # store load, the rest compile
    loaded = entry.load_aot(aot.aot_dir(), buckets)
    entry.warmup([b for b in buckets if b not in entry._aot])

    front = ServingFrontend(pool, port=0, max_wait_ms=200,
                            buckets=",".join(str(b) for b in buckets))
    front.serve_in_background()
    got = [None] * len(xs)

    def ask(i):
        cli = ServeClient("127.0.0.1", front.port, timeout=120)
        try:
            status, payload = cli.predict("smoke", xs[i], npy=True)
            assert status == 200, (status, payload)
            got[i] = np.asarray(payload["outputs"][0], dtype=np.float32)
        finally:
            cli.close()

    try:
        ask(0)                                   # alone: smallest bucket
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(1, len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads), "a request hung"
        stats = front.stats_payload()
    finally:
        front.drain_and_stop()
        front.wait_stopped(timeout=60)
    assert all(g is not None for g in got), "a request failed"
    # one request alone ran at the smallest bucket; more rows than batches
    # means a batch of several ran at a larger one
    assert stats["batches"]["rows"] > stats["batches"]["count"], \
        stats["batches"]

    blob = {"arg:%s" % k: v.astype("float32")
            for k, v in entry.arg_params.items()}
    blob.update({"aux:%s" % k: v.astype("float32")
                 for k, v in entry.aux_params.items()})
    pred = mx.predict.Predictor(entry.symbol, blob,
                                {"data": (len(xs),) + shape})
    ref = pred.forward(data=xs).get_output(0)
    got = np.stack(got)
    assert got.shape == ref.shape and np.isfinite(got).all(), got.shape
    np.testing.assert_allclose(got, ref, rtol=0.1, atol=0.05)
    return {"requests": len(xs), "loaded_epoch": entry.loaded_epoch,
            "aot_loaded": loaded, "compiled": len(buckets) - loaded,
            "batches": stats["batches"],
            "max_abs_diff_vs_f32_predictor": float(np.abs(got - ref).max())}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_multichip(symbol, rec_prefix, image, batch, epochs, n=4):
    """The training phase again on a dp=``n`` mesh at a global batch of
    ``n`` x ``batch``, once per gradient-sync strategy.  Batch shards must
    sit on ``n`` distinct devices and the compiled step must hold the
    strategy's collectives."""
    import jax
    out = {}
    for sync, wanted in (("allreduce", ("all-reduce",)),
                         ("zero3", ("all-gather", "reduce-scatter"))):
        res = phase_train(symbol, rec_prefix, image, n * batch, epochs,
                          devices=jax.devices()[:n], grad_sync=sync)
        assert len(set(res["shard_devices"])) == n, (
            "batch shards on %r, not on %d distinct devices"
            % (res["shard_devices"], n))
        missing = [c for c in wanted if not res["collectives"].get(c)]
        assert not missing, ("%s step compiled without %s: %r"
                             % (sync, missing, res["collectives"]))
        log(_fmt_train("multichip dp=%d %s" % (n, sync), res, ""))
        out[sync] = res
    return out


# ---------------------------------------------------------------------------

def _fmt_train(tag, res, took):
    return ("%s: %d steps through fit(), loss first %r last %r | pipeline "
            "%s native_lib=%s imagedec=%s | grad_sync=%s shards on %s | "
            "kernels compiled by Mosaic in the step: %r | collectives %r | "
            "first step %.1fs, fed step %.1f ms (median), staged step "
            "%.1f ms | XLA's per-device accounting of the step, GiB: %s | %r"
            % (tag, res["steps"], res["first_loss"], res["last_loss"],
               res["pipeline"], res["native_lib"], res["native_imagedec"],
               res["grad_sync"], res["shard_devices"],
               res["pallas_kernels"] or "none", res["collectives"] or "none",
               res["first_step_s"], res["fed_step_ms"],
               res["staged_step_ms"],
               {k: round(v / 2 ** 30, 2)
                for k, v in res["step_memory"].items()}, took))


def main():
    dev = device_report()
    if dev["platform"] != "tpu":
        log("chip_smoke: JAX found no TPU (platform %r) — this script "
            "only proves anything on the chip" % dev["platform"])
        return 2

    from mxnet_tpu import models
    start = mark()
    cache_dir, entries_before = cache_entries()
    log("compile cache: %s (JAX_COMPILATION_CACHE_DIR %s), %d entries"
        % (cache_dir,
           "set" if "JAX_COMPILATION_CACHE_DIR" in os.environ else "unset",
           entries_before))
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        snap = mark()
        phase_calibration()
        log("calibration: %r" % since(snap))

        snap = mark()
        rec = make_dataset(STEPS_PER_EPOCH * BATCH, 256, LABEL_CLASSES,
                           directory=work)
        log("dataset: %d JPEGs in %.1fs"
            % (STEPS_PER_EPOCH * BATCH, since(snap)["wall_s"]))

        sym = models.get_symbol(MODEL, num_classes=NUM_CLASSES)
        ckpt_dir = os.path.join(work, "ckpt")
        trace_dir = os.path.join(work, "trace")
        snap = mark()
        train = phase_train(sym, rec, IMAGE, BATCH, EPOCHS,
                            ckpt_dir=ckpt_dir, trace_dir=trace_dir)
        log(_fmt_train("train", train, since(snap)))
        assert train["last_loss"] < train["first_loss"], (
            "the loss did not fall over %d steps" % train["steps"])
        assert train["pallas_kernels"], (
            "no Pallas kernel in the compiled ResNet-50 step: bn0 should "
            "have taken the compiled bn_act tier")

        planes = check_trace(trace_dir, "/device:TPU")
        log("trace: planes with events %r" % planes)

        snap = mark()
        second = phase_second_trainer(sym, IMAGE, BATCH)
        log("second trainer (built after the first was closed): staged "
            "step %.1f ms against the first trainer's %.1f ms | %r"
            % (second["staged_step_ms"], train["staged_step_ms"],
               since(snap)))

        snap = mark()
        serve = phase_serve(ckpt_dir, sample_inputs(rec, 9, IMAGE))
        log("serve: %r | %r" % (serve, since(snap)))

        if dev["count"] >= 4:
            snap = mark()
            phase_multichip(sym, rec, IMAGE, MULTICHIP_BATCH,
                            MULTICHIP_EPOCHS)
            log("multichip: %r" % since(snap))
        else:
            log("multichip: %d device, phase not run" % dev["count"])
    finally:
        shutil.rmtree(work, ignore_errors=True)

    _, entries_after = cache_entries()
    peak = peak_bytes()
    whole = since(start)
    log("compile cache: %d entries before, %d after; %d hits, %d misses, "
        "%.1fs compiling in all" % (entries_before, entries_after,
                                    whole["cache_hits"],
                                    whole["cache_misses"],
                                    whole["compile_s"]))
    log("device 0 peak_bytes_in_use: %s"
        % ("not reported" if peak is None else "%.2f GiB" % (peak / 2 ** 30)))
    log("losses: %s" % json.dumps(train["losses"]))
    log("summary: %s" % json.dumps(
        {"steps": train["steps"], "first_loss": train["first_loss"],
         "last_loss": train["last_loss"], "claim": None}))
    print(result_line(dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
