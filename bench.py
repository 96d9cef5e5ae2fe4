#!/usr/bin/env python
"""Benchmark: training throughput on one TPU chip.

Prints ONE JSON line.  Primary metric: ResNet-50 batch-32 training fed by
the RecordIO input pipeline end-to-end (decode + augment + H2D + fused
train step) — the number a user actually gets.  Baseline: the reference's
published ResNet-50 batch-32 training throughput, 109 images/sec on 1x K80
(BASELINE.md row 1, reference example/image-classification/README.md:154).

Secondary metrics in the same JSON object:
  - compute_img_s: steady-state fused-step throughput on pre-staged
    device batches (input pipeline excluded), the r01/r02 headline.
  - pipeline_decode_img_s: iterator-only decode+augment throughput —
    comparable to the reference's "RecordIO pipeline ~3,000 img/s" row
    (BASELINE.md; reference docs imagenet_full.md:37).
  - inception_bn_img_s / resnet152_img_s: train throughput for the other
    BASELINE.md model rows (152 and 57 img/s on K80).
  - lstm_tok_s: 2-layer LSTM LM tokens/sec (BASELINE config #3 workload;
    the reference publishes no tokens/s number, so no vs_baseline).

Feed path design (TPU-first): the native libjpeg pipeline emits raw uint8
NHWC batches (4x fewer host-link bytes than f32), and
normalize/transpose/cast run on-device inside the fused step where XLA
folds them into the first convolution.  Each metric runs in its own
subprocess (see _collect): the parent stays off JAX, because a chip
belongs to one process.

The chip modes (CHIP_MODES) refuse to run on anything but a TPU, so a CPU
timing can never be printed under a device metric's name, and a
``device_kind`` missing from PEAK_TFLOPS is an error, not a silently
dropped ``mfu``.

Timing: every on-chip metric times S1 and S2 steps, each ended by a
scalar fetch of the updated parameters, and takes the slope
(work-scaling).  On the v5e the chip tool provides, ``block_until_ready``
IS a completion barrier (chip_smoke.py's calibration phase: a dependent
bf16 matmul chain reads the same TFLOP/s under either barrier, inside the
peak), so the slope method is no longer needed for correctness; replacing
it is the benchmark PR's job (ROADMAP Speed 1).  Each model metric carries
{flops_per_img, tflops, mfu} from analytic model FLOPs (contrib/flops.py,
1 MAC = 2 FLOPs, training = 3x forward) against the chip's nominal peak,
and the run fails loudly if any MFU exceeds 1.0.
"""
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _env_int(name, default):
    return int(os.environ.get(name, str(default)))


#: sentinel for _scoped_env: "don't touch the value on entry" (the body
#: sets its own values; only the exit-time restore is wanted)
_KEEP = object()


@contextlib.contextmanager
def _scoped_env(name, value=_KEEP):
    """Scoped RAW save/restore of one environment variable.

    Deliberately raw (not get_env): the restore must distinguish "the
    operator never set it" (pop) from an explicit value, and get_env
    cannot — it substitutes the registered default, so a round-trip
    through it would leave later modes measuring under the default
    instead of the operator's (absent) setting.  ``value`` is applied
    on entry (``None`` unsets for the scope; the ``_KEEP`` default
    leaves the current value alone — for bodies that steer the
    variable themselves and only need the exit-time restore)."""
    prev = os.environ.get(name)
    if value is None:
        os.environ.pop(name, None)
    elif value is not _KEEP:
        os.environ[name] = value
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prev


def _make_trainer(sym_name, batch, input_transforms=None, shapes=None):
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer

    sym = models.get_symbol(sym_name, num_classes=1000)
    trainer = SPMDTrainer(
        sym, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4,
         "rescale_grad": 1.0 / batch},
        mesh=None, compute_dtype="bfloat16",
        input_transforms=input_transforms)
    trainer.bind(shapes or [("data", (batch, 3, 224, 224))],
                 [("softmax_label", (batch,))])
    trainer.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                              factor_type="in", magnitude=2))
    return trainer


def _staged_batches(batch, n_staged, dtype="bfloat16", shape=(3, 224, 224)):
    import mxnet_tpu as mx
    rs = np.random.RandomState(0)
    staged = []
    for _ in range(n_staged):
        d = mx.nd.array(rs.rand(batch, *shape).astype("f")).astype(dtype)
        l = mx.nd.array(rs.randint(0, 1000, size=batch).astype("f"))
        d.wait_to_read()
        l.wait_to_read()
        staged.append((d, l))
    return staged


def _best_of(fn, trials):
    best = 0.0
    for _ in range(max(1, trials)):
        best = max(best, fn())
    return best


#: nominal dense bf16 peak by device_kind, TFLOP/s.  Values are the
#: published per-chip numbers; 'cpu' has no meaningful MXU peak.
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


#: the modes that time the chip; everything else pins the CPU backend
CHIP_MODES = ("fed", "compute", "compute-large", "inception-bn",
              "resnet-152", "lstm")


def _require_tpu():
    """Chip modes fail — before compiling anything — unless JAX's default
    device is a TPU: a CPU number must never carry a device metric's
    name (``mx.tpu(i)`` alone would resolve to a CPU device here)."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(
            "this bench mode measures the chip and jax reports platform "
            "%r (%s) — refusing to print a device metric from it"
            % (d.platform, d.device_kind))


def _device_peak():
    """(device_kind, nominal bf16 TFLOP/s); an unknown kind is an error."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_TFLOPS:
        raise KeyError("no PEAK_TFLOPS row for device_kind %r — add the "
                       "published peak before reporting MFU on it" % kind)
    return kind, PEAK_TFLOPS[kind]


def _fetch_sync(trainer):
    """Completion barrier: fetch a scalar that data-depends on the
    freshest parameters (see the module docstring on why this outlived
    the backend it was written for)."""
    import jax.numpy as jnp
    name = min(trainer.params, key=lambda k: trainer.params[k].size)
    return float(jnp.sum(trainer.params[name].astype(jnp.float32)))


def _slope_rate(run_steps, sync, s1, s2, trials):
    """Work-scaling rate for an arbitrary step driver: time s1 and s2
    steps, each ended by ``sync`` (a dependent-scalar fetch); the slope
    cancels the fixed cost of the fetch.  Raises instead of returning a
    bogus 0 when no
    trial yields a positive slope (clock anomaly): the metric then comes
    back missing from the artifact, not silently zero."""
    def timed(nsteps):
        tic = time.perf_counter()
        run_steps(nsteps)
        sync()
        return time.perf_counter() - tic

    best = 0.0
    for _ in range(max(1, trials)):
        t1 = timed(s1)
        t2 = timed(s2)
        if t2 > t1:
            best = max(best, (s2 - s1) / (t2 - t1))
    if best <= 0.0:
        raise RuntimeError(
            "work-scaling slope non-positive across %d trials "
            "(s1=%d, s2=%d) — timing anomaly, refusing to report" %
            (trials, s1, s2))
    return best


def _steps_per_sec(trainer, staged, s1, s2, trials):
    return _slope_rate(
        lambda n: [trainer.step(*staged[i % len(staged)])
                   for i in range(n)],
        lambda: _fetch_sync(trainer), s1, s2, trials)


def _roofline(per_item_rate, flops_per_item):
    """{tflops, mfu, ...} block for one model metric."""
    kind, peak = _device_peak()
    tflops = per_item_rate * flops_per_item / 1e12
    return {"flops_per_item": int(flops_per_item),
            "tflops": round(tflops, 2), "device_kind": kind,
            "mfu": round(tflops / peak, 4)}


def _compute_bench(trainer, batch, steps, warmup, trials,
                   staged=None):
    """Steady-state fused-step throughput on pre-staged device batches,
    measured by fetch-synced work-scaling."""
    staged = staged or _staged_batches(batch, 8)
    for i in range(warmup):
        trainer.step(*staged[i % len(staged)])
    _fetch_sync(trainer)
    s1 = max(4, steps // 4)
    return batch * _steps_per_sec(trainer, staged, s1, s1 + steps, trials)


def _make_dataset(n_img, side=256, classes=1000, directory=None):
    """Synthetic RecordIO dataset with natural-image-like JPEG statistics
    (smooth gradients + low-frequency texture; ~13 KB/img at q90, in line
    with 256x256 photographic JPEGs — NOT white noise, which carries ~4x
    the entropy and decodes several times slower than any real photo).
    ``classes`` bounds the labels: a consumer training a small head must
    ask for a matching range — out-of-range labels under SoftmaxOutput
    one-hot to a ZERO row, so every such example pushes all logits down
    and the fed loop diverges (the fed-cpu guard abort on this host).
    Image i carries texture i % 16 and label i % classes: where classes
    divides 16 the label can be learnt from the pixels (chip_smoke.py).
    Written under ``directory`` (default: a fresh temporary one)."""
    import tempfile

    import cv2

    from mxnet_tpu import recordio

    prefix = os.path.join(
        directory or tempfile.mkdtemp(prefix="bench_rec_"), "bench")
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


def _fed_bench(batch, steps, warmup, trials):
    """End-to-end: RecordIO pipeline -> uint8 NHWC batches -> device-side
    normalize/transpose/cast in the pipeline's upload stage (overlapped
    across in-flight batches) -> the plain bf16 fused train step."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx

    mean = jnp.array([123.68, 116.28, 103.53], jnp.float32)
    std = jnp.array([58.395, 57.12, 57.375], jnp.float32)
    pre = jax.jit(lambda x: jnp.transpose(
        (x.astype(jnp.float32) - mean) / std, (0, 3, 1, 2))
        .astype(jnp.bfloat16))

    variant = os.environ.get("BENCH_FED_VARIANT", "instep")
    if variant == "instep":
        def data_tf(x):
            x = (x.astype(jnp.float32) - mean) / std
            return jnp.transpose(x, (0, 3, 1, 2)).astype(jnp.bfloat16)
        trainer = _make_trainer("resnet-50", batch,
                                input_transforms={"data": data_tf})
        pre = None
    else:
        trainer = _make_trainer("resnet-50", batch)

    prefix = _make_dataset(max(batch * 8, 1024))
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
        data_shape=(3, 224, 224), batch_size=batch, shuffle=True,
        rand_crop=True, rand_mirror=True,
        preprocess_threads=_env_int("BENCH_DECODE_THREADS", 8),
        prefetch_buffer=6, dtype="uint8", layout="NHWC",
        device_transform=pre, seed=0)

    def batches():
        while True:
            it.reset()
            for b in it:
                yield b

    gen = batches()

    def run_steps(n):
        for _ in range(n):
            b = next(gen)
            trainer.step(b.data[0], b.label[0])

    run_steps(warmup + 8)
    _fetch_sync(trainer)
    s1 = max(4, steps // 4)
    fed = batch * _slope_rate(run_steps, lambda: _fetch_sync(trainer),
                              s1, s1 + steps, trials)
    it.close()
    trainer.close()  # release HBM (params/momentum/exe) before the next bench
    return fed


def _decode_bench(batch=128, n_img=1024, trials=3):
    """Pure host-side decode+augment throughput with ZERO device
    involvement: the iterator runs in host_batches mode (numpy output, the
    exact product the reference's C++ parser hands out) on the CPU
    platform, in this metric's own subprocess.  Reports total img/s per
    thread count (1/2/4/8) plus the 1-thread per-core number — on a
    single-core host the scaling rows are flat by construction and the
    per-core number IS the capability claim.

    Reference anchor: "~3,000 images/sec decode+augment" for the whole
    2017 multi-core host (docs/tutorials/computer_vision/imagenet_full.md:37,
    C++ parser src/io/iter_image_recordio_2.cc:27-80)."""
    import mxnet_tpu as mx

    prefix = _make_dataset(n_img)
    scaling = {}
    for threads in (1, 2, 4, 8):
        it = mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
            data_shape=(3, 224, 224), batch_size=batch, shuffle=True,
            rand_crop=True, rand_mirror=True, preprocess_threads=threads,
            prefetch_buffer=4, dtype="uint8", layout="NHWC", seed=0,
            host_batches=True, data_service=False)  # this metric IS the
        # in-process pipe — an ambient MXTPU_DATA_WORKERS must not
        # silently remeasure the service under the pipe's key
        for b in it:   # warm epoch (thread pools, buffers, page cache)
            pass

        def it_trial():
            it.reset()
            n = 0
            tic = time.time()
            for b in it:
                n += b.data[0].shape[0]
            return n / (time.time() - tic)

        scaling[threads] = round(_best_of(it_trial, trials), 2)
        it.close()
    out = {
        "decode": max(scaling.values()),
        "decode_per_core": scaling[1],
        "decode_scaling": scaling,
        "decode_scaling_x": round(max(scaling.values()) / scaling[1], 3),
        "ncores": os.cpu_count(),
    }
    if (os.cpu_count() or 1) == 1:
        # honesty note: with one core the 1/2/4/8 rows are flat BY
        # CONSTRUCTION — the gate skips scaling-shape comparisons on
        # such hosts so a 1-core CI box can neither mask nor fake a
        # real scaling regression (see gate())
        out["decode_scaling_note"] = "flat_by_construction_1core"
    return out


def _data_service_bench(batch=128, n_img=1024, trials=2):
    """The multi-process shared-memory data service
    (mxnet_tpu/data_service/, docs/how_to/performance.md "Scaling the
    input pipeline") against the in-process pipe, pure host work:

      - data_service_transport_overhead: service at workers=1 vs the raw
        in-process native pipe at preprocess_threads=1 — the cost of the
        process hop + ring (decode lands directly in shared memory, the
        collector hands zero-copy views, so this should be < 10% and is
        typically NEGATIVE: the consumer stops stealing decode cycles).
      - data_service_scaling: img/s per worker-process count; with >1
        core this must scale near-linearly where the in-process pipe is
        flat (decode_scaling).  data_service_scaling_x is the ratio at
        min(4, ncores) workers vs 1; linear would equal that worker
        count (data_service_linear_frac = x / workers >= 0.7 is the
        acceptance bar).  On a 1-core host every row is flat by
        construction and the note tells the gate to skip the shape.
      - per-stage counters from the service's stats() surface
        (producer/consumer stall %, mean ring occupancy).
    """
    import mxnet_tpu as mx

    prefix = _make_dataset(n_img)
    ncores = os.cpu_count() or 1
    kw = dict(path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
              data_shape=(3, 224, 224), batch_size=batch, shuffle=True,
              rand_crop=True, rand_mirror=True, prefetch_buffer=4,
              dtype="uint8", layout="NHWC", seed=0, host_batches=True)

    def measure(it):
        """(best img/s, stats-delta of the best trial) after one warm
        epoch."""
        for b in it:
            pass
        best, best_stats = 0.0, None
        for _ in range(max(1, trials)):
            before = it.stats()
            it.reset()
            n = 0
            tic = time.time()
            for b in it:
                n += b.data[0].shape[0]
            dt = time.time() - tic
            rate = n / dt
            if rate > best:
                best = rate
                after = it.stats()
                if after is not None:
                    best_stats = {
                        "elapsed_s": dt,
                        "workers": after["num_workers"],
                        "producer_stall_s":
                            after["producer_stall_s"]
                            - (before or after)["producer_stall_s"],
                        "consumer_stall_s":
                            after["consumer_stall_s"]
                            - (before or after)["consumer_stall_s"],
                        "ring_occupancy": after["ring_occupancy"],
                    }
        it.close()
        return best, best_stats

    # data_service=False pins the baseline to the in-process pipe even
    # when an ambient MXTPU_DATA_WORKERS would route it (a service-vs-
    # service "overhead" of ~0 would be a lie)
    inproc, _ = measure(mx.io.ImageRecordIter(
        preprocess_threads=1, data_service=False, **kw))

    scaling, stats_at = {}, {}
    for w in (1, 2, 4, 8):
        svc, st = measure(mx.io.ImageRecordIter(
            preprocess_threads=w, data_service=True, **kw))
        scaling[w] = round(svc, 2)
        if st is not None:
            stats_at[w] = st

    # the recordio readahead satellite: the same w=1 service with the
    # posix_fadvise window off — the before/after of
    # MXTPU_DATA_READAHEAD (page-cache-warm hosts show ~0; cold/remote
    # storage is where the window pays); workers inherit the env
    with _scoped_env("MXTPU_DATA_READAHEAD", "0"):
        ra_off, _ = measure(mx.io.ImageRecordIter(
            preprocess_threads=1, data_service=True, **kw))

    # largest MEASURED worker count within min(4, ncores) — ncores==3
    # must pick row 2, not KeyError on a row that was never measured
    w_target = max((w for w in scaling if w <= min(4, ncores)),
                   default=1) if ncores > 1 else 1
    sx = round(scaling[w_target] / scaling[1], 3) if scaling[1] else 0.0
    out = {
        "data_service_img_s": max(scaling.values()),
        "data_service_scaling": scaling,
        "data_service_scaling_x": sx,
        "data_service_scaling_workers": w_target,
        "data_service_linear_frac": round(sx / max(1, w_target), 3),
        "data_service_inproc_img_s": round(inproc, 2),
        "data_service_transport_overhead": round(
            1.0 - scaling[1] / inproc, 3) if inproc else None,
        "data_service_readahead_img_s": scaling[1],
        "data_service_readahead_off_img_s": round(ra_off, 2),
        "data_service_readahead_x": round(scaling[1] / ra_off, 3)
        if ra_off else None,
        "data_service_ncores": ncores,
    }
    st = stats_at.get(w_target)
    if st is not None and st["elapsed_s"] > 0:
        out["data_service_producer_stall_pct"] = round(
            100.0 * st["producer_stall_s"]
            / (st["workers"] * st["elapsed_s"]), 1)
        out["data_service_consumer_stall_pct"] = round(
            100.0 * st["consumer_stall_s"] / st["elapsed_s"], 1)
        out["data_service_ring_occupancy"] = st["ring_occupancy"]
    if ncores == 1:
        out["data_service_scaling_note"] = "flat_by_construction_1core"
    return out


def _spawn_data_servers(count, port_dir):
    """``count`` loopback ``tools/data_server.py`` processes (jax-free —
    each holds ONE python interpreter + its decode workers, the real
    remote-host footprint).  Returns (procs, 'host:port,host:port').

    Deliberately standalone from tests/conftest.spawn_data_server: this
    runs inside bench metric subprocesses, which must not import
    pytest/jax-side conftest machinery.  On ANY bring-up failure the
    already-spawned servers are killed before raising — the caller's
    finally block only sees fully-built fleets."""
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    procs, addrs = [], []
    try:
        for n in range(count):
            pf = os.path.join(port_dir, "ds-port-%d" % n)
            if os.path.exists(pf):
                os.remove(pf)
            procs.append(subprocess.Popen(
                [sys.executable,
                 os.path.join(here, "tools", "data_server.py"),
                 "--port", "0", "--port-file", pf],
                stderr=subprocess.DEVNULL))
            deadline = time.monotonic() + 30
            while not os.path.exists(pf):
                if procs[-1].poll() is not None:
                    raise RuntimeError(
                        "data server %d died at startup (rc=%s)"
                        % (n, procs[-1].returncode))
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        "data server %d did not come up" % n)
                time.sleep(0.05)
            with open(pf) as f:
                addrs.append(f.read().strip())
    except BaseException:
        for p in procs:
            p.kill()
        raise
    return procs, ",".join(addrs)


def _data_net_bench(batch=128, n_img=1024, trials=2):
    """The NETWORK tier of the data service (mxnet_tpu/data_service/net.py
    + tools/data_server.py; docs/how_to/performance.md) against the
    in-process service, loopback sockets, pure host work:

      - data_net_transport_overhead: ONE loopback server (1 decode
        worker) vs the in-process service at workers=1 — the cost of
        the TCP hop + frame crc on top of PR 7's process hop
        (acceptance: <= 15%).
      - data_net_scaling: img/s per SERVER-process count (1/2/4, one
        decode worker each); server processes are what a real
        deployment adds per CPU host, so this is the disaggregation
        curve the tier exists for.  data_net_scaling_x is the ratio at
        the largest measured count the host's cores can actually run
        concurrently (consumer + S servers + S workers); hosts with
        < 4 cores emit data_net_scaling_note and the gate skips the
        SHAPE key (absolute throughput still gates).
    """
    import shutil
    import tempfile

    import mxnet_tpu as mx

    prefix = _make_dataset(n_img)
    ncores = os.cpu_count() or 1
    kw = dict(path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
              data_shape=(3, 224, 224), batch_size=batch, shuffle=True,
              rand_crop=True, rand_mirror=True, prefetch_buffer=4,
              dtype="uint8", layout="NHWC", seed=0, host_batches=True)

    def measure(it):
        for b in it:
            pass
        best = 0.0
        for _ in range(max(1, trials)):
            it.reset()
            n = 0
            tic = time.time()
            for b in it:
                n += b.data[0].shape[0]
            best = max(best, n / (time.time() - tic))
        it.close()
        return best

    inproc = measure(mx.io.ImageRecordIter(
        preprocess_threads=1, data_service=True, **kw))

    port_dir = tempfile.mkdtemp(prefix="bench_data_net_")
    scaling = {}
    try:
        for nserv in (1, 2, 4):
            procs, addrs = _spawn_data_servers(nserv, port_dir)
            try:
                scaling[nserv] = round(measure(mx.io.ImageRecordIter(
                    preprocess_threads=1, data_service=addrs, **kw)), 2)
            finally:
                for p in procs:
                    p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except Exception:  # noqa: BLE001 — bounded teardown
                        p.kill()
    finally:
        shutil.rmtree(port_dir, ignore_errors=True)

    # largest measured server count whose decode workers + the consumer
    # fit the host's cores (the server streamer threads are I/O-bound)
    s_target = max((s for s in scaling
                    if s <= min(4, max(1, ncores - 1))), default=1)
    sx = round(scaling[s_target] / scaling[1], 3) if scaling[1] else 0.0
    overhead = round(1.0 - scaling[1] / inproc, 3) if inproc else None
    out = {
        "data_net_img_s": max(scaling.values()),
        "data_net_scaling": scaling,
        "data_net_scaling_x": sx,
        "data_net_scaling_servers": s_target,
        "data_net_inproc_img_s": round(inproc, 2),
        "data_net_transport_overhead": overhead,
        "data_net_transport_ok": overhead is not None and overhead <= 0.15,
        "data_net_ncores": ncores,
    }
    if ncores < 4:
        # consumer + S servers + S decode workers structurally cannot
        # run concurrently on this host: the scaling SHAPE is
        # meaningless here (the SCALING_SHAPE_KEYS honesty contract);
        # absolute throughput and transport overhead still gate
        out["data_net_scaling_note"] = \
            "flat_by_construction_%dcore" % ncores
    return out


def _fed_cpu_bench(batch=64, steps=40, warmup=8, trials=3):
    """Overlap proof on the CPU backend: pipeline ->
    device_put -> fused step.  Computes decode-only rate D, staged
    step-only rate S, and the fed rate F.  The feed machinery hides its
    latency when F reaches the host's ceiling: min(D, S) when decode and
    compute can run on different cores, else the single-core serial bound
    1/(1/D + 1/S) — one core cannot decode and matmul at once, so on a
    1-core host the demonstrable property is that the pipeline adds no
    extra serialization on top of the CPU-bound work."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer

    # labels bounded to THIS net's 10-class head (see _make_dataset)
    prefix = _make_dataset(512, side=96, classes=10)
    shape = (3, 64, 64)

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=16, kernel=(3, 3),
                             pad=(1, 1), name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Convolution(net, num_filter=32, kernel=(3, 3),
                             pad=(1, 1), name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    def make_it(host):
        # mean/std normalization: raw 0-255 pixels into an SGD step at
        # lr 0.01 diverge to non-finite weights within the warmup on
        # this host (the step guard then aborts the bench) — normalized
        # inputs keep the measured work identical and the loop stable
        return mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
            data_shape=shape, batch_size=batch, shuffle=True,
            rand_crop=True, rand_mirror=True, preprocess_threads=2,
            mean_r=127.0, mean_g=127.0, mean_b=127.0,
            std_r=60.0, std_g=60.0, std_b=60.0,
            prefetch_buffer=4, dtype="float32", seed=0, host_batches=host)

    trainer = SPMDTrainer(
        net, "sgd", {"learning_rate": 0.01, "momentum": 0.9,
                     "rescale_grad": 1.0 / batch},
        mesh=None, compute_dtype="float32")
    trainer.bind([("data", (batch,) + shape)],
                 [("softmax_label", (batch,))])
    trainer.init_params(mx.initializer.Xavier())

    # D: decode-only
    it = make_it(host=True)
    for b in it:
        pass

    def d_trial():
        it.reset()
        n = 0
        tic = time.time()
        for b in it:
            n += b.data[0].shape[0]
        return n / (time.time() - tic)

    D = _best_of(d_trial, trials)
    it.close()

    # S: step-only on staged device batches
    rs = np.random.RandomState(0)
    staged = []
    for _ in range(4):
        d = mx.nd.array(rs.rand(batch, *shape).astype("f"))
        l = mx.nd.array(rs.randint(0, 10, (batch,)).astype("f"))
        d.wait_to_read()
        staged.append((d, l))
    for i in range(warmup):
        trainer.step(*staged[i % 4])
    jax.block_until_ready(trainer.params)

    def s_trial():
        tic = time.time()
        for i in range(steps):
            trainer.step(*staged[i % 4])
        jax.block_until_ready(trainer.params)
        return batch * steps / (time.time() - tic)

    S = _best_of(s_trial, trials)

    # F: fed end-to-end
    it = make_it(host=False)

    def batches():
        while True:
            it.reset()
            for b in it:
                yield b

    gen = batches()
    for _ in range(warmup):
        b = next(gen)
        trainer.step(b.data[0], b.label[0])
    jax.block_until_ready(trainer.params)

    def f_trial():
        tic = time.time()
        for _ in range(steps):
            b = next(gen)
            trainer.step(b.data[0], b.label[0])
        jax.block_until_ready(trainer.params)
        return batch * steps / (time.time() - tic)

    F = _best_of(f_trial, trials)
    it.close()

    ncores = os.cpu_count() or 1
    ceiling = min(D, S) if ncores > 1 else 1.0 / (1.0 / D + 1.0 / S)
    return {
        "fed_cpu": round(F, 2),
        "fed_cpu_decode": round(D, 2),
        "fed_cpu_step": round(S, 2),
        "fed_cpu_ceiling": round(ceiling, 2),
        "fed_cpu_overlap": round(F / ceiling, 3),
    }


def _pipeline_bench(batch=64, steps=40, warmup=6, trials=3):
    """Async input-pipeline overlap proof on the CPU backend: fused-step
    steps/sec against a DELIBERATELY SLOW host iterator (a per-batch
    sleep calibrated to ~1.5x the staged step time), with prefetch depth
    0 (synchronous staging on the consuming thread) vs depth 2
    (DevicePrefetchIter staging on a background thread).  The serial
    bound is 1/(delay+step); full overlap reaches 1/max(delay, step) —
    with delay = 1.5x step that is a ~1.67x ceiling, so the reported
    speedup demonstrates real overlap, not noise."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.dataflow import DevicePrefetchIter
    from mxnet_tpu.parallel import SPMDTrainer

    dim, classes = 256, 10
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=1024, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=1024, name="fc2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc3")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    trainer = SPMDTrainer(
        net, "sgd", {"learning_rate": 0.1, "momentum": 0.9,
                     "rescale_grad": 1.0 / batch},
        mesh=None)
    trainer.bind([("data", (batch, dim))], [("softmax_label", (batch,))])
    trainer.init_params(mx.initializer.Xavier())

    rs = np.random.RandomState(0)
    X = rs.randn(batch * 16, dim).astype("f")
    y = rs.randint(0, classes, batch * 16).astype("f")

    # calibrate: staged step-only time (batches pre-placed, warm program)
    staged = [trainer.stage_batch(X[i:i + batch], y[i:i + batch])
              for i in range(0, batch * 4, batch)]
    from mxnet_tpu.io import StagedBatch
    staged = [StagedBatch(s, data=[], label=[]) for s in staged]
    for i in range(warmup):
        trainer.step(staged[i % len(staged)])
    jax.block_until_ready(trainer.params)
    tic = time.perf_counter()
    for i in range(steps):
        trainer.step(staged[i % len(staged)])
    jax.block_until_ready(trainer.params)
    step_s = (time.perf_counter() - tic) / steps
    delay = max(1.5 * step_s, 0.002)

    class SlowIter(mx.io.NDArrayIter):
        """Host iterator with a fixed per-batch stall (sleep releases the
        GIL, like real decode/storage waits do)."""

        def next(self):
            time.sleep(delay)
            return super().next()

        __next__ = next

    def run(depth):
        src = SlowIter(X, y, batch_size=batch)
        it = DevicePrefetchIter(src, stage=trainer, depth=depth)
        gen = iter(self_repeat(it))
        for _ in range(warmup):
            trainer.step(next(gen))
        jax.block_until_ready(trainer.params)

        def trial():
            tic = time.perf_counter()
            for _ in range(steps):
                trainer.step(next(gen))
            jax.block_until_ready(trainer.params)
            return steps / (time.perf_counter() - tic)

        best = _best_of(trial, trials)
        it.close()
        return best

    def self_repeat(it):
        while True:
            it.reset()
            for b in it:
                yield b

    d0 = run(0)
    d2 = run(2)
    trainer.close()
    return {
        "pipeline_steps_s_depth0": round(d0, 2),
        "pipeline_steps_s_depth2": round(d2, 2),
        "pipeline_speedup": round(d2 / d0, 3),
        "pipeline_step_ms": round(step_s * 1e3, 3),
        "pipeline_iter_delay_ms": round(delay * 1e3, 3),
    }


def _compile_probe():
    """Bring-up time: trainer construction + bind + first step, the part
    the persistent compile cache amortizes.  Run twice in fresh subprocesses with
    the same cache dir: run 1 = cold (compiles + populates), run 2 = warm
    (loads compiled programs from disk)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer

    batch, side = 32, 32
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=32, kernel=(3, 3),
                             pad=(1, 1), name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Convolution(net, num_filter=64, kernel=(3, 3),
                             pad=(1, 1), name="c2")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    rs = np.random.RandomState(0)
    X = rs.rand(batch, 3, side, side).astype("f")
    y = rs.randint(0, 10, batch).astype("f")

    tic = time.perf_counter()
    trainer = SPMDTrainer(
        net, "sgd", {"learning_rate": 0.1, "rescale_grad": 1.0 / batch},
        mesh=None)
    trainer.bind([("data", (batch, 3, side, side))],
                 [("softmax_label", (batch,))])
    trainer.init_params(mx.initializer.Xavier())
    trainer.step(X, y)
    jax.block_until_ready(trainer.params)
    bringup = time.perf_counter() - tic
    trainer.close()
    return {"compile_bringup_s": round(bringup, 3)}


def _resume_bench(steps=60, batch=64):
    """resume_overhead: the wall-clock price of surviving a preemption —
    mid-run checkpoint save + fresh-trainer restore + refit of the
    remaining steps to parity — against an uninterrupted run of the same
    total step budget (CPU backend: this measures the framework's
    save/restore/recompile machinery, not the chip).  The refit finishes
    BIT-identical to the baseline (asserted), so "refit-to-parity" is
    exactly the second half's steps; the overhead is save + restore +
    the relaunch recompile (the part the persistent compile cache
    amortizes)."""
    import shutil
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer
    from mxnet_tpu.resilience import CheckpointManager

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=128, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    rs = np.random.RandomState(0)
    X = rs.rand(batch, 64).astype("f")
    y = rs.randint(0, 10, batch).astype("f")

    def make():
        t = SPMDTrainer(net, "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9,
                         "rescale_grad": 1.0 / batch}, mesh=None)
        t.bind([("data", (batch, 64))], [("softmax_label", (batch,))])
        mx.random.seed(11)
        t.init_params(mx.initializer.Xavier())
        return t

    def run(t, n):
        for _ in range(n):
            t.step(X, y)
        t.flush_step_guard()

    # uninterrupted baseline (includes its one compile, like any run)
    base = make()
    tic = time.perf_counter()
    run(base, steps)
    baseline_s = time.perf_counter() - tic
    base_params, _ = base.get_params()
    base.close()

    half = steps // 2
    tmp = tempfile.mkdtemp(prefix="bench_resume_")
    try:
        man = CheckpointManager(tmp)
        a = make()
        run(a, half)
        tic = time.perf_counter()
        a.save_checkpoint(man, half)
        save_s = time.perf_counter() - tic
        a.close()

        # the relaunch: a FRESH trainer (new process in real life —
        # restore + recompile both count)
        b = make()
        tic = time.perf_counter()
        b.restore(man)
        restore_s = time.perf_counter() - tic
        tic = time.perf_counter()
        run(b, steps - half)
        refit_s = time.perf_counter() - tic
        res_params, _ = b.get_params()
        b.close()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    parity = all(
        np.array_equal(base_params[k].asnumpy(), res_params[k].asnumpy())
        for k in base_params)
    total = save_s + restore_s + refit_s
    out = {
        "resume_save_s": round(save_s, 4),
        "resume_restore_s": round(restore_s, 4),
        "resume_refit_s": round(refit_s, 4),
        "resume_baseline_s": round(baseline_s, 4),
        # the preempted run re-trains NO steps (bit-identical resume), so
        # its extra cost over the uninterrupted run is save + restore +
        # the second compile hiding inside refit's first step
        "resume_overhead_s": round(total + baseline_s * half / steps
                                   - baseline_s, 4),
        "resume_parity": parity,
    }
    if not parity:
        out["resume_parity_note"] = ("restored run diverged from the "
                                     "uninterrupted baseline — resume is "
                                     "broken, numbers above are invalid")
    return out


def _checkpoint_bench(saves=5, steps_between=3, batch=64, hidden=1024):
    """The price of a checkpoint, measured where it hurts: the STEP-LOOP
    STALL per save — how long ``save_checkpoint`` blocks the training
    loop — for the blocking path (serialize + atomic write + fsync +
    checksum + manifest, all inline) vs the async path (host snapshot
    only; the CheckpointWriter does the rest off-thread).  Also measures
    the integrity tax: a verified restore vs the file read alone, and a
    full ``tools/ckpt_fsck.py`` audit of the directory.  The async run's
    restored params are asserted byte-identical to the blocking run's
    (``ckpt_parity``) — a fast save that loses bits is not a feature.
    CPU/host work only."""
    import shutil
    import subprocess as _sp
    import tempfile

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer
    from mxnet_tpu.resilience import CheckpointManager, checksum_file

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    X = rs.rand(batch, 1024).astype("f")
    y = rs.randint(0, 10, batch).astype("f")

    def run(blocking):
        tmp = tempfile.mkdtemp(prefix="bench_ckpt_")
        man = CheckpointManager(tmp, keep_last=saves + 1)
        t = SPMDTrainer(net, "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9,
                         "rescale_grad": 1.0 / batch}, mesh=None)
        t.bind([("data", (batch, 1024))], [("softmax_label", (batch,))])
        mx.random.seed(11)
        t.init_params(mx.initializer.Xavier())
        stalls = []
        for i in range(1, saves + 1):
            for _ in range(steps_between):
                t.step(X, y)
            t.flush_step_guard()
            # production checkpoints are minutes apart — by the next save
            # the writer is long idle.  This bench's saves are a few fast
            # CPU steps apart, so drain OUTSIDE the timed window; without
            # this the measured "stall" is mostly the previous write's
            # back-pressure, a regime no sane checkpoint cadence hits.
            man.wait()
            tic = time.perf_counter()
            t.save_checkpoint(man, i, blocking=blocking)
            stalls.append(time.perf_counter() - tic)
        man.wait()
        t.close()
        stalls.sort()
        return stalls[len(stalls) // 2], man, tmp

    out = {}
    try:
        block_stall, man_b, dir_b = run(blocking=True)
        async_stall, man_a, dir_a = run(blocking=False)
        out["ckpt_stall_blocking_s"] = round(block_stall, 5)
        out["ckpt_stall_async_s"] = round(async_stall, 5)
        out["ckpt_stall_ratio"] = round(block_stall / max(async_stall,
                                                          1e-9), 1)
        # identical training streams => the two directories' newest
        # checkpoints must restore byte-identically
        _, pa, _, sa, _ = man_b.restore()
        _, pb, _, sb, _ = man_a.restore()
        out["ckpt_parity"] = bool(
            sa == sb and set(pa) == set(pb) and all(
                np.array_equal(pa[k].asnumpy(), pb[k].asnumpy())
                for k in pa))
        # integrity tax: verified restore vs raw params read, plus the
        # offline fsck audit of the whole directory
        params_path = man_b.params_path(man_b.latest())
        tic = time.perf_counter()
        man_b.restore()
        out["ckpt_restore_verified_s"] = round(time.perf_counter() - tic,
                                               5)
        tic = time.perf_counter()
        checksum_file(params_path, "sha256")
        out["ckpt_verify_s"] = round(time.perf_counter() - tic, 5)
        here = os.path.dirname(os.path.abspath(__file__))
        tic = time.perf_counter()
        res = _sp.run([sys.executable,
                       os.path.join(here, "tools", "ckpt_fsck.py"),
                       dir_b, "-q"], capture_output=True, text=True,
                      timeout=120)
        out["ckpt_fsck_s"] = round(time.perf_counter() - tic, 3)
        out["ckpt_fsck_rc"] = res.returncode
    finally:
        for d in (locals().get("dir_b"), locals().get("dir_a")):
            if d:
                shutil.rmtree(d, ignore_errors=True)
    return out


def _ckpt_sharded_bench(saves=3, steps_between=2, batch=32, hidden=1024):
    """``bench.py ckpt`` — sharded-native vs gathered checkpoints on a
    real zero3 trainer (docs/how_to/fault_tolerance.md "Sharded-native
    checkpoints").  The gathered path pulls every shard into one full
    host copy before the write; the sharded path
    (``save_checkpoint_sharded`` / ``MXTPU_CKPT_SHARDED=1``) writes one
    verified blob per dp shard with peak host residency of a single
    blob.  Gate keys: ``ckpt_save_ms`` (sharded save wall time, lower
    is better) and ``ckpt_peak_host_frac`` (peak single-blob bytes /
    total blob bytes — the whole point of the feature; it rises back
    toward 1.0 if a host-side gather sneaks into the save path).
    ``ckpt_sharded_parity`` asserts the sharded directory restores
    bit-identically to the gathered one — a smaller host copy that
    loses bits is not a feature.  8-virtual-device CPU mesh."""
    import shutil
    import tempfile

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import SPMDTrainer, local_mesh
    from mxnet_tpu.resilience import CheckpointManager

    world = len(jax.devices())
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=hidden, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    X = rs.randn(batch, 512).astype("f")
    y = rs.randint(0, 8, batch).astype("f")

    t = SPMDTrainer(net, "sgd",
                    {"learning_rate": 0.05, "momentum": 0.9,
                     "rescale_grad": 1.0 / batch},
                    mesh=local_mesh("dp"), grad_sync="zero3")
    t.bind([("data", (batch, 512))], [("softmax_label", (batch,))])
    mx.random.seed(7)
    t.init_params(mx.initializer.Xavier())

    dir_g = tempfile.mkdtemp(prefix="bench_ckpt_gathered_")
    dir_s = tempfile.mkdtemp(prefix="bench_ckpt_sharded_")
    out = {"ckpt_world": world}
    try:
        man_g = CheckpointManager(dir_g, keep_last=None)
        man_s = CheckpointManager(dir_s, keep_last=None)
        gathered, sharded = [], []
        for i in range(1, saves + 1):
            for _ in range(steps_between):
                t.step(X, y)
            t.flush_step_guard()
            # identical trainer state goes to BOTH directories each
            # epoch, so the parity check below compares like with like
            tic = time.perf_counter()
            t.save_checkpoint(man_g, i, blocking=True)
            gathered.append(time.perf_counter() - tic)
            tic = time.perf_counter()
            t.save_checkpoint_sharded(man_s, i)
            sharded.append(time.perf_counter() - tic)
        gathered.sort()
        sharded.sort()
        out["ckpt_gathered_save_ms"] = round(
            gathered[len(gathered) // 2] * 1e3, 2)
        out["ckpt_save_ms"] = round(sharded[len(sharded) // 2] * 1e3, 2)
        stats = man_s.last_save_stats or {}
        if stats.get("total_blob_bytes"):
            out["ckpt_peak_host_bytes"] = stats["peak_blob_bytes"]
            out["ckpt_total_blob_bytes"] = stats["total_blob_bytes"]
            out["ckpt_peak_host_frac"] = round(
                stats["peak_blob_bytes"] / stats["total_blob_bytes"], 4)
        # verified assembly from per-shard blobs, timed where a resuming
        # trainer pays it
        tic = time.perf_counter()
        _, ps, _, ss, _ = man_s.restore()
        out["ckpt_restore_ms"] = round((time.perf_counter() - tic) * 1e3,
                                       2)
        _, pg, _, sg, _ = man_g.restore()
        # content equality, not pickle-byte equality: the two save paths
        # serialize the same state in different dict orders
        import pickle
        oa, ob = pickle.loads(ss), pickle.loads(sg)
        opt_ok = (oa["num_update"] == ob["num_update"] and
                  set(oa["states"]) == set(ob["states"]) and all(
                      len(oa["states"][k]) == len(ob["states"][k]) and
                      all(np.array_equal(x, z) for x, z in
                          zip(oa["states"][k], ob["states"][k]))
                      for k in oa["states"]))
        out["ckpt_sharded_parity"] = bool(
            opt_ok and set(ps) == set(pg) and all(
                np.array_equal(ps[k].asnumpy(), pg[k].asnumpy())
                for k in ps))
        t.close()
    finally:
        shutil.rmtree(dir_g, ignore_errors=True)
        shutil.rmtree(dir_s, ignore_errors=True)
    return out


def _roofline_bench(preset=None, trials=None):
    """``bench.py roofline`` — per-op proof for the fused kernels
    (mxnet_tpu/kernels/, docs/how_to/kernels.md).

    For each kernel the mode times (a) the FUSED implementation (the
    routed tier as one jitted program — fused-lax on the CPU tier,
    Pallas on TPU) and (b) the UNFUSED composition at dispatch
    granularity: every primitive its own compiled call, the execution
    model the pre-fusion graphs (and the reference's per-op engine) pay.
    Each fused time is also compared against an analytic bytes/FLOPs
    roofline (kernels/roofline.py) using the machine's MEASURED matmul
    rate and copy bandwidth (calibrated here, not nominal), so the
    artifact shows how close each kernel runs to the hardware and which
    side binds it.

    Self-gating: every kernel must beat its unfused composition
    (``roofline_<op>_win``); the ``roofline_<op>_speedup`` keys are in
    GATE_KEYS so later rounds cannot silently regress them.
    """
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.kernels import bn_act as BA
    from mxnet_tpu.kernels import flash_attention as FA
    from mxnet_tpu.kernels import lstm_cell as LC
    from mxnet_tpu.kernels import roofline as RL
    from mxnet_tpu.ops import nn as NN

    preset = preset or os.environ.get("BENCH_ROOFLINE_PRESET", "full")
    trials = trials or _env_int("BENCH_TRIALS", 3)
    small = preset == "small"
    reps = 3 if small else 10

    def timeit(fn, *args):
        """Best-of-trials seconds for one call of fn (block-synced)."""
        jax.block_until_ready(fn(*args))           # warm/compile
        best = float("inf")
        for _ in range(max(1, trials)):
            tic = time.perf_counter()
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
            best = min(best, (time.perf_counter() - tic) / reps)
        return best

    # -- machine calibration: achieved matmul rate + copy bandwidth ----
    n = 256 if small else 1024
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    t_mm = timeit(mm, a)
    peak_flops = 2.0 * n * n * n / t_mm
    buf = jnp.ones((1 << 20,) if small else (1 << 24,), jnp.float32)
    scale_pass = jax.jit(lambda x: x * 1.0000001)   # one read + one write
    t_cp = timeit(scale_pass, buf)
    mem_bw = 2.0 * buf.size * 4 / t_cp

    rs = np.random.RandomState(0)
    out = {
        "roofline_peak_gflops": round(peak_flops / 1e9, 1),
        "roofline_mem_gbs": round(mem_bw / 1e9, 2),
        "roofline_preset": preset,
    }

    def record(name, fused_s, unfused_s, work):
        bound_s = RL.roofline_seconds(work["flops"], work["fused_bytes"],
                                      peak_flops, mem_bw)
        out["roofline_%s_fused_us" % name] = round(fused_s * 1e6, 2)
        out["roofline_%s_unfused_us" % name] = round(unfused_s * 1e6, 2)
        out["roofline_%s_speedup" % name] = round(unfused_s / fused_s, 3)
        out["roofline_%s_bound_us" % name] = round(bound_s * 1e6, 2)
        out["roofline_%s_bound" % name] = RL.bound_side(
            work["flops"], work["fused_bytes"], peak_flops, mem_bw)
        out["roofline_%s_of_roofline" % name] = round(
            bound_s / fused_s, 3) if fused_s else None
        out["roofline_%s_win" % name] = bool(unfused_s >= fused_s)

    # -- bn_act: the inception-bn inner loop shape --------------------
    N, C, HW = (8, 32, 28 * 28) if small else (32, 64, 56 * 56)
    x = jnp.asarray(rs.rand(N, C, HW).astype("f").reshape(N, C, HW))
    gam = jnp.asarray(rs.rand(C).astype("f") + 0.5)
    bet = jnp.asarray(rs.rand(C).astype("f"))
    mmean = jnp.zeros(C)
    mvar = jnp.ones(C)

    fused_bn = jax.jit(lambda x, g, b, m, v: BA.fused_bn_act_lax(
        x, g, b, m, v, act_type="relu", fix_gamma=False, is_train=True))
    bn_stage = jax.jit(lambda x, g, b, m, v: NN.batch_norm(
        x, g, b, m, v, fix_gamma=False, is_train=True))
    act_stage = jax.jit(lambda x: NN.activation(x, act_type="relu"))

    def unfused_bn(x, g, b, m, v):
        o, nm, nv = bn_stage(x, g, b, m, v)
        return act_stage(o), nm, nv

    record("bn_act",
           timeit(fused_bn, x, gam, bet, mmean, mvar),
           timeit(unfused_bn, x, gam, bet, mmean, mvar),
           RL.workload("bn_act", n=N, c=C, hw=HW))

    # -- lstm_cell: the lstm_tok_s bench's cell shape -----------------
    B, H = (16, 64) if small else (32, 200)
    gates = jnp.asarray(rs.randn(B, 4 * H).astype("f"))
    cprev = jnp.asarray(rs.randn(B, H).astype("f"))

    fused_cell = jax.jit(LC.lstm_cell_lax)
    sig = jax.jit(jax.nn.sigmoid)
    tnh = jax.jit(jnp.tanh)
    mul = jax.jit(jnp.multiply)
    add = jax.jit(jnp.add)
    split4 = jax.jit(lambda g: tuple(jnp.split(g, 4, axis=-1)))

    def unfused_cell(g, c):
        i, f, gg, o = split4(g)
        c2 = add(mul(sig(f), c), mul(sig(i), tnh(gg)))
        return mul(sig(o), tnh(c2)), c2

    record("lstm_cell",
           timeit(fused_cell, gates, cprev),
           timeit(unfused_cell, gates, cprev),
           RL.workload("lstm_cell", b=B, h=H))

    # -- flash_attention ----------------------------------------------
    Bq, T, Hh, D = (2, 128, 2, 64) if small else (4, 512, 8, 64)
    q = jnp.asarray(rs.randn(Bq, T, Hh, D).astype("f"))
    k = jnp.asarray(rs.randn(Bq, T, Hh, D).astype("f"))
    v = jnp.asarray(rs.randn(Bq, T, Hh, D).astype("f"))

    fused_fa = jax.jit(lambda q, k, v: FA.flash_attention_lax(
        q, k, v, causal=True))
    scores_stage = jax.jit(
        lambda q, k: jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D))
    mask_soft = jax.jit(lambda s: jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf),
        axis=-1))
    out_stage = jax.jit(lambda p, v: jnp.einsum("bhqk,bkhd->bqhd", p, v))

    def unfused_fa(q, k, v):
        return out_stage(mask_soft(scores_stage(q, k)), v)

    record("flash_attention",
           timeit(fused_fa, q, k, v),
           timeit(unfused_fa, q, k, v),
           RL.workload("flash_attention", b=Bq, t=T, heads=Hh, d=D))

    # -- eltwise_chain: relu -> scale -> add -> tanh run --------------
    Ne, Ce, HWe = (4, 16, 28 * 28) if small else (16, 32, 56 * 56)
    xe = jnp.asarray(rs.rand(Ne, Ce, HWe).astype("f"))
    ye = jnp.asarray(rs.rand(Ne, Ce, HWe).astype("f"))
    relu_j = jax.jit(jax.nn.relu)
    scale_j = jax.jit(lambda v: v * 0.125)
    add_j = jax.jit(jnp.add)
    tanh_j = jax.jit(jnp.tanh)

    def unfused_chain(x, y):
        return tanh_j(add_j(scale_j(relu_j(x)), y))

    fused_chain = jax.jit(
        lambda x, y: jnp.tanh(jax.nn.relu(x) * 0.125 + y))
    record("eltwise_chain",
           timeit(fused_chain, xe, ye),
           timeit(unfused_chain, xe, ye),
           RL.workload("eltwise_chain", n=Ne, c=Ce, hw=HWe, depth=4))

    # -- concat_fuse: sibling 1x1 tower heads as ONE GEMM -------------
    Nc, Cc, Hc = (2, 64, 14) if small else (8, 192, 28)
    widths = (16, 16, 24) if small else (64, 64, 96)
    xc = jnp.asarray(rs.randn(Nc, Cc, Hc, Hc).astype("f"))
    wsc = [jnp.asarray(rs.randn(w, Cc, 1, 1).astype("f") * 0.1)
           for w in widths]
    conv1 = jax.jit(lambda x, w: jax.nn.relu(
        jax.lax.conv_general_dilated(x, w, (1, 1), "VALID")))

    def unfused_cc(x, w1, w2, w3):
        return conv1(x, w1), conv1(x, w2), conv1(x, w3)

    o1, o2 = widths[0], widths[0] + widths[1]

    @jax.jit
    def fused_cc(x, w1, w2, w3):
        m = jax.nn.relu(jax.lax.conv_general_dilated(
            x, jnp.concatenate([w1, w2, w3], axis=0), (1, 1), "VALID"))
        return m[:, :o1], m[:, o1:o2], m[:, o2:]

    record("concat_fuse",
           timeit(fused_cc, xc, *wsc),
           timeit(unfused_cc, xc, *wsc),
           RL.workload("concat_fuse", n=Nc, c=Cc, hw=Hc * Hc,
                       widths=list(widths)))

    # -- pool_act: act->max-pool reordered to pool-first --------------
    Np, Cp, Hp = (4, 16, 56) if small else (16, 64, 112)
    xp = jnp.asarray(rs.randn(Np, Cp, Hp, Hp).astype("f"))
    pool_j = jax.jit(lambda v: NN.pooling(
        v, kernel=(3, 3), stride=(2, 2), pad=(1, 1), pool_type="max"))

    def unfused_pa(x):
        return pool_j(relu_j(x))

    fused_pa = jax.jit(lambda v: NN.activation(NN.pooling(
        v, kernel=(3, 3), stride=(2, 2), pad=(1, 1), pool_type="max"),
        act_type="relu"))
    record("pool_act",
           timeit(fused_pa, xp),
           timeit(unfused_pa, xp),
           RL.workload("pool_act", n=Np, c=Cp, hw=Hp * Hp, stride=2))

    out["roofline_all_win"] = all(
        out["roofline_%s_win" % op]
        for op in ("bn_act", "lstm_cell", "flash_attention",
                   "eltwise_chain", "concat_fuse", "pool_act"))

    # -- whole-model proof: inception-bn forward, new passes on vs off
    out.update(_roofline_inception(small, trials))
    return out


#: the pre-mxfuse kernel set — the "new passes off" baseline the
#: inception stanza (and the headline inception-gap claim) compares
#: against; bn_act/bn_fold stay ON both sides
_PRE_MXFUSE_KERNELS = "bn_act,bn_fold,lstm_cell,flash_attention,augment"


def _small_inception():
    """A trimmed inception-bn (stem + one A tower + one B tower) for
    the small roofline preset — the same patterns every pass matches
    (merge trio, grouped 3x3 siblings, act→pool stem, avg-pool
    branch) at test-tier compile cost."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.inception_bn import (ConvFactory,
                                               InceptionFactoryA,
                                               InceptionFactoryB)
    data = mx.sym.Variable("data")
    c1 = ConvFactory(data, 16, (3, 3), pad=(1, 1), name="conv1")
    p1 = mx.sym.Pooling(c1, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                        pool_type="max", name="pool1")
    a = InceptionFactoryA(p1, 16, 16, 24, 16, 24, "avg", 16, "3a")
    b = InceptionFactoryB(a, 16, 24, 16, 24, "3c")
    flat = mx.sym.Flatten(mx.sym.Pooling(
        b, global_pool=True, kernel=(1, 1), pool_type="avg"))
    fc = mx.sym.FullyConnected(flat, num_hidden=10, name="fc1")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


def _roofline_inception(small, trials):
    """The mxfuse headline measurement (ISSUE 15 / ROADMAP item 5):
    inception-bn FORWARD throughput through the real executor with the
    plan-optimizer passes ON (default env) vs OFF (the pre-mxfuse
    kernel set — bn_act/bn_fold still on, so the delta is the NEW
    passes only), plus the infer_trace satellite: eval-trace build
    time with dead-node elimination on vs off (the pruned plan skips
    tracing every conv a fold replaced).

    Both executors are bound first and the timing windows INTERLEAVE
    on/off (best-of): sequential measurement on this host drifts by
    more than the effect under test (page cache, frequency ramp), and
    interleaving cancels it.  The small preset measures a trimmed
    inception (same patterns, test-tier compile cost)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.executor import _build_eval
    from mxnet_tpu.kernels import KNOWN_KERNELS
    from mxnet_tpu.models import inception_bn

    shape = (2, 3, 32, 32) if small else (8, 3, 96, 96)
    steps = 2 if small else 5
    windows = 2 if small else 7
    sym = _small_inception() if small \
        else inception_bn.get_symbol(num_classes=100)

    def bind(env):
        os.environ["MXTPU_FUSED_KERNELS"] = env
        ex = sym.simple_bind(mx.cpu(), grad_req="null", data=shape)
        rs_i = np.random.RandomState(0)
        for name in sorted(ex.arg_dict):
            if name in ("data", "softmax_label"):
                continue
            arr = ex.arg_dict[name]
            arr[:] = (rs_i.rand(*arr.shape).astype("f") - 0.5) * 0.2
        for name in ex.aux_dict:
            ex.aux_dict[name][:] = 1.0 if name.endswith("var") else 0.0
        ex.arg_dict["data"][:] = rs_i.rand(*shape).astype("f")
        return ex

    def window(ex):
        tic = time.perf_counter()
        for _ in range(steps):
            outs = ex.forward()
        outs[0].asnumpy()                          # completion barrier
        return (time.perf_counter() - tic) / steps

    out = {}
    # bind()/trace_once() steer MXTPU_FUSED_KERNELS themselves; the
    # scope restores the operator's value (or its absence) on exit
    with _scoped_env("MXTPU_FUSED_KERNELS"):
        ex_on = bind("1")
        ex_off = bind(_PRE_MXFUSE_KERNELS)
        ex_on.forward()[0].asnumpy()               # compile + warm
        ex_off.forward()[0].asnumpy()
        best_on = best_off = float("inf")
        for _ in range(max(1, windows)):
            best_on = min(best_on, window(ex_on))
            best_off = min(best_off, window(ex_off))
        ex_on.close()
        ex_off.close()
        on_rate, off_rate = shape[0] / best_on, shape[0] / best_off
        out["roofline_inception_fwd_on_img_s"] = round(on_rate, 2)
        out["roofline_inception_fwd_off_img_s"] = round(off_rate, 2)
        out["roofline_inception_fwd_x"] = round(on_rate / off_rate, 3)
        out["roofline_inception_fwd_win"] = bool(on_rate >= off_rate)

        # infer_trace: eval-trace build time (plan interpretation +
        # jaxpr trace) with the pruned plan vs the full fused plan
        args = {n: np.zeros(s, np.float32) for n, s in zip(
            sym.list_arguments(),
            sym.infer_shape(data=shape)[0])}
        auxs = {n: np.zeros(s, np.float32) for n, s in zip(
            sym.list_auxiliary_states(),
            sym.infer_shape(data=shape)[2])}
        rng = jax.random.PRNGKey(0)

        def trace_once(env):
            os.environ["MXTPU_FUSED_KERNELS"] = env
            tic = time.perf_counter()
            eval_fn = _build_eval(sym)
            jax.make_jaxpr(
                lambda a, x, r: eval_fn(a, x, r, False))(args, auxs,
                                                         rng)
            return time.perf_counter() - tic

        no_prune = ",".join(k for k in KNOWN_KERNELS
                            if k != "infer_trace")
        # same discipline as the forward stanza: warm BOTH paths once
        # untimed (the first trace pays jax tracing-machinery warmup
        # for this program size), then INTERLEAVE best-of windows —
        # sequential on-then-off measurement drifts by more than the
        # ~10-20% effect on a ~0.2s quantity (the r06 dry run measured
        # the on path first-and-cold and "lost" for exactly that
        # reason)
        trace_once("1")
        trace_once(no_prune)
        on_s = off_s = float("inf")
        for _ in range(3 if small else 5):
            off_s = min(off_s, trace_once(no_prune))
            on_s = min(on_s, trace_once("1"))
        out["roofline_infer_trace_on_s"] = round(on_s, 3)
        out["roofline_infer_trace_off_s"] = round(off_s, 3)
        out["roofline_infer_trace_x"] = round(off_s / on_s, 3) \
            if on_s else None
        out["roofline_infer_trace_win"] = bool(off_s >= on_s)
    return out


def _lstm_bench(batch, seq_len, steps, warmup, trials):
    """2-layer LSTM LM (lstm_bucketing workload, one bucket) tokens/sec."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.models import lstm_lm
    from mxnet_tpu.parallel import SPMDTrainer

    vocab = 10000
    sym, data_names, label_names = lstm_lm.lstm_lm_sym(
        seq_len, vocab, num_embed=200, num_hidden=200, num_layers=2)
    trainer = SPMDTrainer(
        sym, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.0,
         "rescale_grad": 1.0 / batch},
        mesh=None, compute_dtype="bfloat16")
    shapes = {"data": (batch, seq_len), "softmax_label": (batch, seq_len)}
    trainer.bind([(n, shapes[n]) for n in data_names],
                 [(n, shapes[n]) for n in label_names])
    trainer.init_params(mx.initializer.Xavier())

    rs = np.random.RandomState(0)
    staged = []
    for _ in range(8):
        d = mx.nd.array(rs.randint(0, vocab, (batch, seq_len)).astype("f"))
        l = mx.nd.array(rs.randint(0, vocab, (batch, seq_len)).astype("f"))
        d.wait_to_read()
        l.wait_to_read()
        staged.append((d, l))
    for i in range(warmup):
        trainer.step(*staged[i % 8])
    _fetch_sync(trainer)
    s1 = max(4, steps // 4)
    return batch * seq_len * _steps_per_sec(trainer, staged, s1,
                                            s1 + steps, trials)


def _save_serving_models(tmp, deep=False):
    """Write the two bench serving checkpoints: the standard MLP
    (models/mlp.py shape) and a resnet-shaped small-image net (cifar
    branch of models/resnet.py) -> {name: (prefix, epoch, sample_shape)}.
    ``deep=True`` swaps resnet-20 for resnet-56 (the fleet mode: a
    graph deep enough that bring-up is compile-dominated and a forward
    heavy enough that replica compute, not HTTP plumbing, is the
    scaling bottleneck)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.model import save_checkpoint

    rs = np.random.RandomState(7)
    out = {}
    for name, sym, sample in (
            ("mlp", models.get_symbol("mlp", num_classes=10), (784,)),
            ("resnet", models.get_symbol("resnet", num_classes=10,
                                         num_layers=56 if deep else 20,
                                         image_shape=(3, 32, 32)),
             (3, 32, 32))):
        shapes = {"data": (1,) + sample}
        arg_shapes, _, aux_shapes = sym.infer_shape(**shapes)
        args = {n: mx.nd.array(rs.uniform(-0.1, 0.1, s).astype("f"))
                for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in ("data", "softmax_label")}
        auxs = {}
        for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
            # BN moving stats: mean 0, var 1 — a forward through random
            # weights stays finite
            auxs[n] = mx.nd.array(
                (np.ones(s) if n.endswith("var")
                 else np.zeros(s)).astype("f"))
        prefix = os.path.join(tmp, name)
        save_checkpoint(prefix, 1, sym, args, auxs, blocking=True)
        out[name] = (prefix, 1, sample)
    return out


def _serve_load(port, model, sample, concurrency, seconds, warmup_s=0.5,
                npy=False):
    """Closed-loop load: ``concurrency`` threads, each its own keep-alive
    client, firing back-to-back requests for ``seconds`` after a warmup
    window.  ``npy=True`` sends x-npy bodies (C-speed serialization —
    the fleet rows use it so the CLIENT's JSON encode cost cannot mask
    replica scaling).  Returns (qps, p50_ms, p99_ms, shed, errors)."""
    import threading

    from mxnet_tpu.serving import ServeClient

    rs = np.random.RandomState(0)
    stop = threading.Event()
    lats, shed, errors = [], [0], [0]
    lock = threading.Lock()

    def worker(i):
        cli = ServeClient("127.0.0.1", port)
        x = rs.rand(*sample).astype("f") + i  # distinct payloads
        mine = []
        try:
            while not stop.is_set():
                tic = time.perf_counter()
                try:
                    status, _ = cli.predict(model, x, npy=npy)
                except Exception:  # noqa: BLE001 — connection-level loss
                    status = -1
                dt = (time.perf_counter() - tic) * 1e3
                if status == 200:
                    mine.append((tic, dt))
                elif status == 429:
                    with lock:
                        shed[0] += 1
                else:
                    with lock:
                        errors[0] += 1
        finally:
            cli.close()
        with lock:
            lats.extend(mine)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(warmup_s + seconds)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    cut = t0 + warmup_s
    window = sorted(d for (tic, d) in lats if tic >= cut)
    if not window:
        return 0.0, None, None, shed[0], errors[0]
    # the ONE nearest-rank percentile rule — same math /stats reports
    from mxnet_tpu.serving.frontend import _percentile
    return (round(len(window) / seconds, 2),
            round(_percentile(window, 50), 3),
            round(_percentile(window, 99), 3), shed[0], errors[0])


def _serve_open_loop(port, model, sample, rate_qps, seconds, workers=32):
    """Open-loop load: a paced worker pool fires at a fixed AGGREGATE
    arrival rate on a schedule independent of completions (a worker
    that falls behind its slots fires immediately — the standard
    bounded-worker approximation of open-loop arrivals, without the
    thread-per-request storm that would just fill the kernel's accept
    backlog instead of the daemon's bounded queue).  Returns (ok, shed,
    errors, p99_ms_of_successes)."""
    import threading

    from mxnet_tpu.serving import ServeClient

    rs = np.random.RandomState(1)
    x = rs.rand(*sample).astype("f")
    results = []
    lock = threading.Lock()
    interval = workers / float(rate_qps)
    t0 = time.perf_counter() + 0.05
    end = t0 + seconds

    def worker(i):
        cli = ServeClient("127.0.0.1", port, timeout=30)
        nxt = t0 + i * (1.0 / rate_qps)
        try:
            while nxt < end:
                pause = nxt - time.perf_counter()
                if pause > 0:
                    time.sleep(pause)
                tic = time.perf_counter()
                try:
                    status, _ = cli.predict(model, x)
                except Exception:  # noqa: BLE001 — refused/dropped conn
                    status = -1
                with lock:
                    results.append(
                        (status, (time.perf_counter() - tic) * 1e3))
                nxt += interval
        finally:
            cli.close()

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    ok = sum(1 for s, _ in results if s == 200)
    shed = sum(1 for s, _ in results if s in (429, 503))
    errors = len(results) - ok - shed
    from mxnet_tpu.serving.frontend import _percentile
    p99 = _percentile(sorted(d for s, d in results if s == 200), 99)
    return ok, shed, errors, round(p99, 3) if p99 is not None else None


def _serve_bench(seconds=2.5):
    """The ``bench.py serve`` mode: spin up the real daemon
    (tools/serve.py) on the CPU backend, drive closed-loop load at
    1/8/32 concurrency for the standard MLP and a resnet-shaped model,
    verify serving output is bit-identical to the unbatched Predictor
    forward, then overdrive it open-loop and record the shed rate.

    Headline: ``serve_batch_speedup`` = QPS at concurrency 32 / QPS at
    concurrency 1 for the MLP — continuous batching must buy >= 2x on
    the CPU tier (acceptance criterion)."""
    import shutil
    import signal as _signal
    import subprocess
    import tempfile

    from mxnet_tpu.serving import ServeClient

    tmp = tempfile.mkdtemp(prefix="bench_serve_")
    out = {}
    proc = None
    try:
        specs = _save_serving_models(tmp)
        here = os.path.dirname(os.path.abspath(__file__))
        port_file = os.path.join(tmp, "port")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        cmd = [sys.executable, os.path.join(here, "tools", "serve.py"),
               "--port", "0", "--port-file", port_file,
               "--buckets", "1,2,4,8,16,32", "--max-wait-ms", "2",
               "--max-queue", "64", "--warmup"]
        for name, (prefix, epoch, sample) in specs.items():
            cmd += ["--model", "%s=%s:%d" % (name, prefix, epoch),
                    "--input-shape",
                    "%s:data=%s" % (name, ",".join(map(str, sample)))]
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                                stderr=subprocess.PIPE, text=True)
        deadline = time.monotonic() + 300
        while not os.path.exists(port_file):
            if proc.poll() is not None:
                raise RuntimeError("serve daemon died: %s"
                                   % proc.stderr.read()[-2000:])
            if time.monotonic() > deadline:
                raise RuntimeError("serve daemon never wrote its port")
            time.sleep(0.1)
        port = int(open(port_file).read().split(":")[1])
        ServeClient("127.0.0.1", port).wait_ready(60)

        # bit-parity: one quiet request == the unbatched (bucket-1)
        # Predictor forward, bitwise
        out["serve_parity"] = _serve_parity(port, specs)

        for name, (_, _, sample) in specs.items():
            for conc in (1, 8, 32):
                qps, p50, p99, shed, errs = _serve_load(
                    port, name, sample, conc, seconds)
                key = "serve_%s_c%d" % (name, conc)
                out[key + "_qps"] = qps
                out[key + "_p50_ms"] = p50
                out[key + "_p99_ms"] = p99
                if shed:
                    out[key + "_shed"] = shed
                if errs:
                    out[key + "_errors"] = errs
        if out.get("serve_mlp_c1_qps"):
            out["serve_batch_speedup"] = round(
                out["serve_mlp_c32_qps"] / out["serve_mlp_c1_qps"], 2)

        # open-loop: paced arrivals at a fixed rate just under the MLP's
        # measured capacity — the sustained-QPS-within-SLO row
        rate = min(400.0, max(50.0,
                              0.8 * (out.get("serve_mlp_c8_qps") or 50.0)))
        ok, shed, errors, p99 = _serve_open_loop(
            port, "mlp", specs["mlp"][2], rate, 1.5)
        out["serve_openloop_rate_qps"] = round(rate, 1)
        out["serve_openloop_ok"] = ok
        out["serve_openloop_shed"] = shed
        out["serve_openloop_errors"] = errors
        if p99 is not None:
            out["serve_openloop_p99_ms"] = p99

        # overload: closed-loop concurrency far past the queue bound —
        # admission control must shed (429) the excess rather than
        # queue it without bound, while the admitted work completes
        _, _, p99o, shed_o, errs_o = _serve_load(
            port, "resnet", specs["resnet"][2], 96, seconds)
        out["serve_overload_shed"] = shed_o
        out["serve_overload_errors"] = errs_o
        if p99o is not None:
            out["serve_overload_p99_ms"] = p99o
        status, stats = ServeClient("127.0.0.1", port).stats()
        if status == 200:
            out["serve_batch_fill"] = stats["batches"].get("fill_ratio")
            out["serve_sheds_counted"] = (
                stats["counters"]["shed_queue"]
                + stats["counters"]["shed_slo"])

        proc.send_signal(_signal.SIGTERM)
        out["serve_drain_rc"] = proc.wait(timeout=60)
        proc = None
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _serve_parity(port, specs):
    """True iff a request served through the daemon (bucket 1, quiet
    daemon) is BIT-identical to the local unbatched Predictor forward
    for every model."""
    from mxnet_tpu import predict
    from mxnet_tpu.model import load_checkpoint
    from mxnet_tpu.serving import ServeClient

    rs = np.random.RandomState(3)
    cli = ServeClient("127.0.0.1", port)
    try:
        for name, (prefix, epoch, sample) in specs.items():
            x = rs.rand(*sample).astype("f")
            status, payload = cli.predict(name, x)
            if status != 200:
                return False
            got = np.asarray(payload["outputs"][0], dtype=np.float32)
            sym, args, auxs = load_checkpoint(prefix, epoch)
            pred = predict.Predictor(
                sym, {**{"arg:%s" % k: v for k, v in args.items()},
                      **{"aux:%s" % k: v for k, v in auxs.items()}},
                {"data": (1,) + tuple(sample)})
            ref = pred.forward(data=x[None]).get_output(0)[0]
            if not np.array_equal(got, ref):
                return False
    finally:
        cli.close()
    return True


def _hotswap_bench(seconds=2.0):
    """The ``bench.py hotswap`` mode (docs/how_to/serving.md,
    "Continuous deployment"): a LIVE ``tools/serve.py --watch`` daemon
    under closed-loop load while this process streams new verified
    epochs into its checkpoint directory — the train-to-serve seam,
    measured, not assumed.

    - ``hotswap_swap_ms`` — mean dispatch-boundary critical section per
      swap (wait for the in-flight batch + install + probe), as the
      daemon itself measures it.  LOWER is better: the gate treats it
      through ``LOWER_IS_BETTER_KEYS``.
    - ``hotswap_drop_free`` — 1.0 iff ZERO requests were dropped or
      errored across every swap (the zero-dropped-requests contract;
      429 sheds are admission control, not drops, and are counted
      separately).
    - ``hotswap_promote_ms`` — publish-to-served latency (includes the
      MXTPU_SWAP_POLL_S poll; recorded alongside, not gated).
    - ``hotswap_qps_dip_frac`` — completion rate in the worst 250ms
      window around a swap vs the steady-state median (1.0 = no dip).
    """
    import shutil
    import signal as _signal
    import subprocess
    import tempfile
    import threading

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.resilience import CheckpointManager
    from mxnet_tpu.serving import ServeClient

    tmp = tempfile.mkdtemp(prefix="bench_hotswap_")
    out = {}
    proc = None
    try:
        sym = models.get_symbol("mlp", num_classes=10)
        arg_shapes, _, _ = sym.infer_shape(data=(1, 784))

        def params(seed):
            rs = np.random.RandomState(seed)
            return {n: mx.nd.array(rs.uniform(-0.1, 0.1, s).astype("f"))
                    for n, s in zip(sym.list_arguments(), arg_shapes)
                    if n not in ("data", "softmax_label")}

        ckpt_dir = os.path.join(tmp, "ckpts")
        man = CheckpointManager(ckpt_dir)
        man.save(1, symbol=sym, arg_params=params(1), aux_params={},
                 blocking=True)

        here = os.path.dirname(os.path.abspath(__file__))
        port_file = os.path.join(tmp, "port")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   MXTPU_SWAP_POLL_S="0.1")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(here, "tools", "serve.py"),
             "--model", "mlp=%s" % ckpt_dir,
             "--input-shape", "mlp:data=784",
             "--port", "0", "--port-file", port_file,
             "--buckets", "1,2,4,8", "--max-wait-ms", "2",
             "--warmup", "--watch"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True)
        deadline = time.monotonic() + 300
        while not os.path.exists(port_file):
            if proc.poll() is not None:
                raise RuntimeError("hotswap daemon died: %s"
                                   % proc.stderr.read()[-2000:])
            if time.monotonic() > deadline:
                raise RuntimeError("hotswap daemon never wrote its port")
            time.sleep(0.1)
        port = int(open(port_file).read().split(":")[1])
        ServeClient("127.0.0.1", port).wait_ready(60)

        # -- closed-loop load for the whole run ---------------------------
        rs = np.random.RandomState(0)
        stop = threading.Event()
        lock = threading.Lock()
        events = []                 # (t_done, status) per request
        drops = [0]                 # connection-level losses

        def worker(i):
            cli = ServeClient("127.0.0.1", port, timeout=30)
            x = rs.rand(784).astype("f") + i
            try:
                while not stop.is_set():
                    try:
                        status, _ = cli.predict("mlp", x, npy=True)
                    except Exception:  # noqa: BLE001 — dropped response
                        with lock:
                            drops[0] += 1
                        continue
                    with lock:
                        events.append((time.monotonic(), status))
            finally:
                cli.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        time.sleep(max(1.0, seconds / 2.0))   # steady-state baseline

        # -- stream new epochs under load ---------------------------------
        stat_cli = ServeClient("127.0.0.1", port)
        swap_ms, promote_ms, swap_at = [], [], []
        for epoch in (2, 3):
            man.save(epoch, symbol=sym, arg_params=params(epoch),
                     aux_params={}, blocking=True)
            t_pub = time.monotonic()
            lim = time.monotonic() + 60
            while time.monotonic() < lim:
                status, stats = stat_cli.stats()
                if status == 200 and \
                        (stats.get("epochs") or {}).get("mlp") == epoch:
                    break
                time.sleep(0.02)
            else:
                raise RuntimeError("epoch %d never went live" % epoch)
            t_live = time.monotonic()
            swap_at.append(t_live)
            promote_ms.append((t_live - t_pub) * 1e3)
            dep = (stats.get("deploy") or {}).get("mlp") or {}
            if dep.get("last_swap_ms") is not None:
                swap_ms.append(float(dep["last_swap_ms"]))
            time.sleep(max(0.5, seconds / 4.0))
        time.sleep(max(0.5, seconds / 4.0))
        stop.set()
        for t in threads:
            t.join(timeout=30)

        status, stats = stat_cli.stats()
        dep = (stats.get("deploy") or {}).get("mlp") or {}
        stat_cli.close()

        # -- the ledger ---------------------------------------------------
        with lock:
            done = list(events)
        errors = sum(1 for _, s in done if s not in (200, 429))
        sheds = sum(1 for _, s in done if s == 429)
        ok = [t for t, s in done if s == 200]
        out["hotswap_swaps"] = int(dep.get("promoted") or len(swap_at))
        out["hotswap_requests"] = len(done)
        out["hotswap_errors"] = errors
        out["hotswap_dropped_conns"] = drops[0]
        if sheds:
            out["hotswap_sheds"] = sheds
        out["hotswap_drop_free"] = \
            1.0 if errors == 0 and drops[0] == 0 else 0.0
        if swap_ms:
            out["hotswap_swap_ms"] = round(sum(swap_ms) / len(swap_ms), 3)
        out["hotswap_promote_ms"] = round(
            sum(promote_ms) / len(promote_ms), 1)
        # QPS dip: completions per 250ms bucket, worst swap-adjacent
        # bucket vs the steady-state median
        if ok:
            t0 = min(ok)
            buckets = {}
            for t in ok:
                buckets[int((t - t0) / 0.25)] = \
                    buckets.get(int((t - t0) / 0.25), 0) + 1
            hot = set()
            for ts in swap_at:
                base_i = int((ts - t0) / 0.25)
                hot.update((base_i - 1, base_i, base_i + 1))
            steady = sorted(v for k, v in buckets.items()
                            if k not in hot and k != max(buckets))
            inside = [buckets.get(i, 0) for i in sorted(hot)
                      if 0 <= i <= max(buckets)]
            if steady and inside:
                med = steady[len(steady) // 2]
                if med > 0:
                    out["hotswap_qps_dip_frac"] = round(
                        min(inside) / float(med), 3)
        proc.send_signal(_signal.SIGTERM)
        out["hotswap_drain_rc"] = proc.wait(timeout=60)
        proc = None
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _region_bench(timeout=420):
    """The composed region drill as a metric (docs/how_to/region.md):
    one ``tools/region.py smoke`` run — data plane -> supervised elastic
    trainer -> rolling fleet -> closed-loop clients, with a rot-injected
    publish — measured at the region's own seams:

    - ``region_drop_free`` — 1.0 iff ZERO client requests were dropped
      or errored across the drill (the storm-grade contract).
    - ``region_goodput_chaos_frac`` — fraction of client requests that
      succeeded on the FIRST client attempt.  With exactly-once
      routing the router absorbs dead replicas by keyed resend, so a
      client-side retry (a 502 that leaked through) counts against
      goodput AND should be zero — the storm report carries
      ``client_retries`` as its own top-level number.
    - ``region_freshness_ms`` — end-to-end publish->served freshness:
      wall-clock from the trainer's manifest publish to the watcher's
      committed swap, fleet-wide worst case (lower is better).
    """
    import shutil
    import subprocess
    import tempfile

    region = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "region.py")
    tmp = tempfile.mkdtemp(prefix="bench_region_")
    out = {}
    try:
        report_path = os.path.join(tmp, "report.json")
        res = subprocess.run(
            [sys.executable, region, "smoke", "--run-dir",
             os.path.join(tmp, "run"), "--report", report_path],
            capture_output=True, text=True, timeout=timeout)
        if res.returncode != 0:
            raise RuntimeError("region smoke drill failed (rc %d):\n%s"
                               % (res.returncode, res.stderr[-2000:]))
        with open(report_path) as f:
            doc = json.load(f)
        stats = doc["stats"]
        clients = stats["clients"]
        requests = clients["requests"]
        dropped = clients["dropped"]
        out["region_requests"] = requests
        out["region_dropped"] = dropped
        out["region_retried"] = clients["retried"]
        out["region_drop_free"] = \
            1.0 if dropped == 0 and doc["ok"] else 0.0
        if requests:
            out["region_goodput_chaos_frac"] = round(
                (requests - clients["retried"] - dropped)
                / float(requests), 4)
        if stats.get("freshness_ms") is not None:
            out["region_freshness_ms"] = round(
                float(stats["freshness_ms"]), 3)
        out["region_served_epoch"] = doc["spec"]["epochs"]
        out["region_publish_rejected"] = \
            stats["events"].get("publish_rejected", 0)
        out["region_elapsed_s"] = doc["elapsed_s"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _fleet_manifest(specs, buckets, replicas=1):
    """The bench models as a real :class:`FleetManifest` (the same
    object the CLI builds — no parallel spec format to drift)."""
    from mxnet_tpu.fleet import FleetManifest
    return FleetManifest(
        {name: {"target": "%s:%d" % (prefix, epoch),
                "shapes": {"data": list(sample)}}
         for name, (prefix, epoch, sample) in specs.items()},
        replicas=replicas, buckets=buckets, device_sets="cpu")


def _fleet_warm_run(specs, buckets, cache_dir, timeout=600):
    """One ``tools/serve.py --warmup-only`` bring-up over every bench
    model with ``JAX_COMPILATION_CACHE_DIR=cache_dir``; returns the parsed
    ``warmup_s`` (trace+compile — or, against a built AOT store,
    executable-load — time only; process imports excluded, so the
    number is exactly what the warm store removes)."""
    import subprocess

    from mxnet_tpu.fleet.warm import WARMUP_RE

    argv = _fleet_manifest(specs, buckets).serve_argv(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "serve.py"),
        port=0, warmup_only=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache_dir)
    res = subprocess.run(argv, env=env, capture_output=True, text=True,
                         timeout=timeout)
    if res.returncode != 0:
        raise RuntimeError("warmup-only run failed (rc %d):\n%s"
                           % (res.returncode, res.stderr[-2000:]))
    m = WARMUP_RE.search(res.stderr)
    if not m:
        raise RuntimeError("warmup-only run printed no warmup_s:\n%s"
                           % res.stderr[-2000:])
    return float(m.group(1))


def _fleet_up(specs, buckets, store, run_dir, replicas, extra_env=None,
              timeout=600, workers=None, autoscale=False,
              replica_env=None):
    """Boot a fleet (router + ``replicas`` daemons) on an ephemeral
    port; returns ``(proc, port)`` once the port file appears.
    ``workers`` > 1 shards the front end into reuseport router workers;
    ``autoscale`` closes the replica-count loop (both: the overdrive
    mode); ``replica_env`` is a list of ``RID:NAME=VALUE`` overrides
    for single replicas (the tail mode arms ONE gray replica with it)."""
    import subprocess

    here = os.path.dirname(os.path.abspath(__file__))
    port_file = os.path.join(run_dir, "router.port")
    cmd = [sys.executable, os.path.join(here, "tools", "fleet.py"),
           "serve", "--replicas", str(replicas), "--device-sets", "cpu",
           "--buckets", buckets, "--warm-store", store,
           "--run-dir", run_dir, "--port", "0",
           "--port-file", port_file]
    if workers is not None:
        cmd += ["--workers", str(workers)]
    if autoscale:
        cmd += ["--autoscale"]
    for spec in (replica_env or ()):
        cmd += ["--replica-env", spec]
    for name, (prefix, epoch, sample) in specs.items():
        cmd += ["--model", "%s=%s:%d" % (name, prefix, epoch),
                "--input-shape",
                "%s:data=%s" % (name, ",".join(map(str, sample)))]
    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                            stderr=subprocess.PIPE, text=True)
    deadline = time.monotonic() + timeout
    while not os.path.exists(port_file):
        if proc.poll() is not None:
            raise RuntimeError("fleet died during bring-up: %s"
                               % proc.stderr.read()[-2000:])
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError("fleet never wrote its port file")
        time.sleep(0.1)
    return proc, int(open(port_file).read().split(":")[1])


def _fleet_bench(seconds=2.5):
    """The ``bench.py fleet`` mode (docs/how_to/fleet.md): the three
    fleet claims, measured, not assumed.

    - ``fleet_warm_start_x`` = cold-compile bring-up / AOT-warm
      bring-up: the cold run traces and XLA-compiles every (model,
      bucket) forward against an EMPTY cache; the warm run is a fresh
      process warming from the built AOT executable store
      (deserialized compiled programs — no trace, no compile; exactly
      a respawned replica's warmup).  Bar: >= 3x (``fleet_warm_ok``).
    - ``fleet_qps_x`` = 2-replica fleet QPS / 1-replica fleet QPS on
      the compute-heavy resnet model (npy bodies so client
      serialization cannot mask it; a low spill bar so the second
      replica actually takes overflow — the spill policy IS what is
      being scaled; best-of-2 over 4s windows for gate-grade
      stability).  Bar: >= 1.6x on a host with enough cores to run
      clients + router + two replicas concurrently; smaller hosts emit
      ``fleet_scaling_note`` (the mxdata 1-core honesty rule: the gate
      skips the SHAPE key via SCALING_SHAPE_KEYS, absolute keys still
      gate).
    - ``fleet_route_overhead_ms`` = router p50 - direct-to-replica p50
      at concurrency 1 on the resnet-shaped model (compute-heavy enough
      that the hop is measurable against a stable base).  Bar:
      overhead < 15% of the direct p50 (``fleet_route_ok``).  The GATE
      key is the monotone ratio ``fleet_route_eff`` = direct/router p50
      (higher is better, like every gate key; it collapses when the
      router hop bloats).
    """
    import shutil
    import signal as _signal
    import tempfile

    from mxnet_tpu.serving import ServeClient

    buckets = "1,2,4,8"
    tmp = tempfile.mkdtemp(prefix="bench_fleet_")
    out = {}
    proc = None
    try:
        specs = _save_serving_models(tmp, deep=True)
        store = os.path.join(tmp, "warm_store")
        cold_dir = os.path.join(tmp, "cold_cache")
        os.makedirs(store)
        os.makedirs(cold_dir)

        # --- AOT warm store: cold vs warm bring-up -----------------------
        from mxnet_tpu.fleet import build_warm_store
        built = build_warm_store(_fleet_manifest(specs, buckets), store)
        out["fleet_warm_build_s"] = built["warmup_s"]
        # cold replica: empty cache, no store — trace + XLA compile all
        cold_s = _fleet_warm_run(specs, buckets, cold_dir)
        # warm replica: fresh process against the built store —
        # deserialize the compiled executables
        warm_s = _fleet_warm_run(specs, buckets, store)
        out["fleet_warm_cold_s"] = round(cold_s, 3)
        out["fleet_warm_warm_s"] = round(warm_s, 3)
        out["fleet_warm_start_x"] = round(cold_s / max(warm_s, 1e-6), 2)
        out["fleet_warm_ok"] = bool(out["fleet_warm_start_x"] >= 3.0)

        fleet_env = {
            # spill early so the second replica takes real overflow
            "MXTPU_FLEET_SPILL_QUEUE": "4",
            "MXTPU_FLEET_HEARTBEAT_S": "0.25",
            "MXTPU_SERVE_MAX_WAIT_MS": "2",
        }

        # --- 1-replica fleet: baseline QPS + route overhead --------------
        # the scaling rows drive the resnet-shaped model: its forward
        # is compute-heavy enough that replica COMPUTE, not the python
        # HTTP plumbing (client encode, router hop), is what saturates
        # — the scaling number then measures replicas, not the proxy.
        # (The converse is real and measured: the router is ONE python
        # process, so sub-ms dispatch-bound models cap at its ~1.2k/s
        # proxy ceiling regardless of replica count — scale-out buys
        # throughput for compute-bound work, the docs say so.)
        # Best-of-2 over 4s windows: single short windows put ±15%
        # scheduler noise on a gate key with a 10% tolerance.
        def _scaling_row(port):
            return max(_serve_load(port, "resnet", specs["resnet"][2],
                                   32, 4.0, npy=True)
                       for _ in range(2))

        run1 = os.path.join(tmp, "run1")
        proc, port = _fleet_up(specs, buckets, store, run1, 1,
                               extra_env=fleet_env)
        qps1, _, _, _, _ = _scaling_row(port)
        out["fleet_qps_1"] = qps1
        _, router_p50, _, _, _ = _serve_load(
            port, "resnet", specs["resnet"][2], 1, seconds, npy=True)
        status, stats = ServeClient("127.0.0.1", port).stats()
        direct_port = None
        if status == 200:
            for rep in stats.get("replicas", {}).values():
                direct_port = rep.get("port")
        if direct_port:
            _, direct_p50, _, _, _ = _serve_load(
                direct_port, "resnet", specs["resnet"][2], 1, seconds,
                npy=True)
            if router_p50 and direct_p50:
                out["fleet_route_p50_ms"] = router_p50
                out["fleet_direct_p50_ms"] = direct_p50
                out["fleet_route_overhead_ms"] = round(
                    router_p50 - direct_p50, 3)
                out["fleet_route_eff"] = round(direct_p50 / router_p50,
                                               3)
                out["fleet_route_ok"] = bool(
                    router_p50 - direct_p50 < 0.15 * direct_p50)
        proc.send_signal(_signal.SIGTERM)
        out["fleet_drain_rc_1"] = proc.wait(timeout=90)
        proc = None

        # --- 2-replica fleet: the scale-out claim ------------------------
        run2 = os.path.join(tmp, "run2")
        proc, port = _fleet_up(specs, buckets, store, run2, 2,
                               extra_env=fleet_env)
        qps2, _, p99_2, shed2, err2 = _scaling_row(port)
        out["fleet_qps_2"] = qps2
        if p99_2 is not None:
            out["fleet_qps_2_p99_ms"] = p99_2
        if shed2:
            out["fleet_qps_2_shed"] = shed2
        if err2:
            out["fleet_qps_2_errors"] = err2
        status, stats = ServeClient("127.0.0.1", port).stats()
        if status == 200:
            out["fleet_spilled"] = stats["router"]["counters"].get(
                "spilled", 0)
            out["fleet_routed"] = stats["router"]["counters"].get(
                "routed", 0)
        if qps1:
            out["fleet_qps_x"] = round(qps2 / qps1, 2)
        ncores = os.cpu_count() or 1
        out["fleet_ncores"] = ncores
        if ncores < 4:
            # clients + router + 2 replicas are 4 concurrent python
            # processes: with fewer cores the scaling row is flat by
            # construction — the gate skips the SHAPE key, a capable
            # host still gates it (tests/test_bench_harness.py)
            out["fleet_scaling_note"] = \
                "flat_by_construction_%dcore" % ncores
        elif "fleet_qps_x" in out:
            out["fleet_qps_ok"] = bool(out["fleet_qps_x"] >= 1.6)
        proc.send_signal(_signal.SIGTERM)
        out["fleet_drain_rc"] = proc.wait(timeout=90)
        proc = None
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _tail_bench(requests=60):
    """The ``bench.py tail`` mode (docs/how_to/fleet.md): hedged tail
    latency against a GRAY replica, measured, not assumed.

    Two 3-replica fleets, replica 0 armed with the ``slow_replica``
    fault (every request it serves stalls ~250 ms — a sick host whose
    probes stay fast).  A single sequential client routes to the
    least-loaded replica with the lowest-rid tie-break, so on an idle
    fleet EVERY request primary-routes to the gray replica — the worst
    case hedging exists for:

    - ``tail_unhedged_p99_ms`` — hedging off: the client eats the
      stall (the fail-once baseline this PR retires).
    - ``tail_p99_ms`` — hedging on (``MXTPU_FLEET_HEDGE_PCT=95``,
      floor 25 ms): the backup to the next-least-loaded replica
      answers first; the stalled primary is the race's counted loser
      (``hedge_wasted``).  GATE key, lower is better.
    - ``tail_drop_free`` — 1.0 iff ZERO non-200s across both windows
      and both fleets drained to rc 0: hedging must never trade
      correctness for latency.
    """
    import shutil
    import signal as _signal
    import tempfile

    from mxnet_tpu.serving import ServeClient

    buckets = "1,2,4,8"
    tmp = tempfile.mkdtemp(prefix="bench_tail_")
    out = {}
    try:
        specs = _save_serving_models(tmp)
        specs = {"mlp": specs["mlp"]}       # cheap model: the stall,
        store = os.path.join(tmp, "warm_store")  # not compute, is the tail
        os.makedirs(store)
        from mxnet_tpu.fleet import build_warm_store
        build_warm_store(_fleet_manifest(specs, buckets), store)
        rs = np.random.RandomState(11)
        x = rs.rand(*specs["mlp"][2]).astype("f")

        def window(run_dir, hedge):
            env = {
                "MXTPU_FLEET_HEARTBEAT_S": "0.25",
                "MXTPU_SERVE_MAX_WAIT_MS": "1",
                "MXTPU_FLEET_HEDGE_PCT": "95" if hedge else "0",
                "MXTPU_FLEET_HEDGE_MIN_MS": "25",
            }
            # arm far more stalls than the window sends: replica 0
            # stays gray for the WHOLE window, never exhausts mid-run
            fproc, port = _fleet_up(
                specs, buckets, store, run_dir, 3, extra_env=env,
                replica_env=["0:MXTPU_FAULTS=slow_replica:%d"
                             % (requests * 10)])
            try:
                lats, errors = [], 0
                cli = ServeClient("127.0.0.1", port, timeout=30)
                try:
                    # unmeasured warmup: first-touch costs (backup
                    # replica's batcher spin-up, conn setup, hedge
                    # thread machinery) would otherwise BE the p99 of
                    # a sequential window
                    for _ in range(3):
                        cli.predict("mlp", x, npy=True)
                    for _ in range(requests):
                        tic = time.perf_counter()
                        try:
                            status, _ = cli.predict("mlp", x, npy=True)
                        except Exception:  # noqa: BLE001 — dropped
                            status = -1
                        dt = (time.perf_counter() - tic) * 1e3
                        if status == 200:
                            lats.append(dt)
                        else:
                            errors += 1
                    status, stats = cli.stats()
                    counters = (stats["router"]["counters"]
                                if status == 200 else {})
                finally:
                    cli.close()
                fproc.send_signal(_signal.SIGTERM)
                rc = fproc.wait(timeout=90)
            finally:
                if fproc.poll() is None:
                    fproc.kill()
                    fproc.wait(timeout=30)
            return lats, errors, counters, rc

        from mxnet_tpu.serving.frontend import _percentile
        cold, errs_u, _, rc_u = window(os.path.join(tmp, "run_u"),
                                       hedge=False)
        hedged, errs_h, counters, rc_h = window(
            os.path.join(tmp, "run_h"), hedge=True)
        if cold:
            out["tail_unhedged_p99_ms"] = round(
                _percentile(sorted(cold), 99), 3)
        if hedged:
            out["tail_p99_ms"] = round(
                _percentile(sorted(hedged), 99), 3)
        if cold and hedged:
            out["tail_hedge_won"] = bool(
                out["tail_p99_ms"] < out["tail_unhedged_p99_ms"])
        out["tail_hedges"] = counters.get("hedges", 0)
        out["tail_hedge_wasted"] = counters.get("hedge_wasted", 0)
        out["tail_errors"] = errs_u + errs_h
        out["tail_drop_free"] = 1.0 if (
            errs_u == 0 and errs_h == 0 and rc_u == 0 and rc_h == 0
            and len(cold) == requests and len(hedged) == requests
        ) else 0.0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _tenant_load(port, model, sample, tenants, seconds, warmup_s=0.5):
    """Closed-loop load with per-TENANT client pools: ``tenants`` is a
    list of ``(name, nthreads, pause_s)`` rows (``pause_s`` > 0 makes a
    pool well-behaved — it yields between requests instead of hammering
    back-to-back).  Returns {tenant: {"p50", "p99", "ok", "shed",
    "errors"}} from the CLIENT side — the flood's damage, if any, shows
    up in the quiet tenants' p99, not in a server-side counter."""
    import threading

    from mxnet_tpu.serving import ServeClient
    from mxnet_tpu.serving.frontend import _percentile

    rs = np.random.RandomState(3)
    stop = threading.Event()
    lock = threading.Lock()
    acc = {name: {"lat": [], "shed": 0, "errors": 0}
           for name, _, _ in tenants}

    def worker(tenant, pause_s, i):
        cli = ServeClient("127.0.0.1", port)
        x = rs.rand(*sample).astype("f") + i
        mine, shed, errors = [], 0, 0
        try:
            while not stop.is_set():
                tic = time.perf_counter()
                try:
                    status, _ = cli.predict(model, x, npy=True,
                                            tenant=tenant)
                except Exception:  # noqa: BLE001 — connection loss
                    status = -1
                dt = (time.perf_counter() - tic) * 1e3
                if status == 200:
                    mine.append((tic, dt))
                elif status == 429:
                    shed += 1
                else:
                    errors += 1
                if pause_s:
                    time.sleep(pause_s)
        finally:
            cli.close()
        with lock:
            acc[tenant]["lat"].extend(mine)
            acc[tenant]["shed"] += shed
            acc[tenant]["errors"] += errors

    threads = []
    for name, nthreads, pause_s in tenants:
        threads += [threading.Thread(target=worker,
                                     args=(name, pause_s, i))
                    for i in range(nthreads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(warmup_s + seconds)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    cut = t0 + warmup_s
    out = {}
    for name, row in acc.items():
        window = sorted(d for (tic, d) in row["lat"] if tic >= cut)
        out[name] = {
            "ok": len(window),
            "p50": round(_percentile(window, 50), 3) if window else None,
            "p99": round(_percentile(window, 99), 3) if window else None,
            "shed": row["shed"], "errors": row["errors"]}
    return out


def _view_healthy_count(view_path):
    """Healthy-replica count straight from the published fleet-view
    snapshot (the same doc every router worker routes off) — None when
    the file is missing/torn (the reader's last-good rule; the bench
    just polls again)."""
    try:
        with open(view_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    reps = doc.get("replicas") or {}
    return sum(1 for rep in reps.values() if rep.get("healthy"))


def _overdrive_bench(seconds=2.5):
    """The ``bench.py overdrive`` mode (docs/how_to/fleet.md "Sharding
    the front end"): the sharded front end's three claims, measured on
    the dispatch-bound tiny MLP — the opposite regime from ``fleet``'s
    compute-heavy resnet, and exactly the one where a single router
    process IS the fleet's QPS ceiling.

    - ``overdrive_qps`` / ``overdrive_qps_x`` = closed-loop QPS through
      4 SO_REUSEPORT router workers, and its ratio over the measured
      1-worker ceiling, with ONE identical replica behind both — the
      delta is pure front-end dispatch, nothing else changes.  Bar:
      >= 4x on a host with cores for clients + 4 workers + replica;
      smaller hosts emit ``overdrive_note`` and only the SHAPE key is
      gate-exempt (the SCALING_SHAPE_KEYS honesty rule — the absolute
      ``overdrive_qps`` still gates round over round).
    - ``overdrive_tenant_p99_ms`` (LOWER is better) = the worst
      WELL-BEHAVED tenant's client-side p99 while one tenant floods
      back-to-back at ~10x its queued-request quota through the same
      sharded front end.  The flood gets quota-shed
      (``overdrive_tenant_flood_shed`` > 0 proves the quota engaged);
      the quiet tenants must hold inside ``overdrive_tenant_slo_ms``.
    - ``overdrive_drop_free`` = 1.0 iff client-visible errors were ZERO
      across one autoscale-up (watermark breach -> warm AOT
      ``add_replica``) and one fenced scale-down (fence -> publish ->
      drain -> stop) under continuous traffic — capacity moved both
      ways and no request was dropped in either direction.
    """
    import shutil
    import signal as _signal
    import tempfile

    buckets = "1,2,4,8"
    tmp = tempfile.mkdtemp(prefix="bench_overdrive_")
    out = {}
    proc = None
    try:
        specs = _save_serving_models(tmp)
        specs = {"mlp": specs["mlp"]}
        sample = specs["mlp"][2]
        store = os.path.join(tmp, "warm_store")
        os.makedirs(store)
        from mxnet_tpu.fleet import build_warm_store
        build_warm_store(_fleet_manifest(specs, buckets), store)

        base_env = {
            "MXTPU_FLEET_HEARTBEAT_S": "0.25",
            "MXTPU_FLEET_VIEW_REFRESH_S": "0.2",
            "MXTPU_SERVE_MAX_WAIT_MS": "2",
        }

        def _qps_row(port):
            # best-of-2: scheduler noise on a shared box is larger
            # than the gate tolerance on a single short window
            return max(_serve_load(port, "mlp", sample, 8, seconds,
                                   npy=True)[0] for _ in range(2))

        # --- 1-worker ceiling vs 4 reuseport workers ---------------------
        run1 = os.path.join(tmp, "run1w")
        proc, port = _fleet_up(specs, buckets, store, run1, 1,
                               extra_env=base_env, workers=1)
        out["overdrive_qps_1w"] = _qps_row(port)
        proc.send_signal(_signal.SIGTERM)
        out["overdrive_drain_rc_1w"] = proc.wait(timeout=90)
        proc = None

        run4 = os.path.join(tmp, "run4w")
        proc, port = _fleet_up(specs, buckets, store, run4, 1,
                               extra_env=base_env, workers=4)
        out["overdrive_workers"] = 4
        out["overdrive_qps"] = _qps_row(port)
        proc.send_signal(_signal.SIGTERM)
        out["overdrive_drain_rc_4w"] = proc.wait(timeout=90)
        proc = None
        if out["overdrive_qps_1w"]:
            out["overdrive_qps_x"] = round(
                out["overdrive_qps"] / out["overdrive_qps_1w"], 2)
        ncores = os.cpu_count() or 1
        out["overdrive_ncores"] = ncores
        if ncores < 6:
            # clients + 4 workers + replica + publisher want >= 6
            # cores; with fewer, the kernel balances connections across
            # workers that all share one core — flat by construction,
            # the gate skips the SHAPE key only
            out["overdrive_note"] = \
                "flat_by_construction_%dcore" % ncores
        elif "overdrive_qps_x" in out:
            out["overdrive_qps_ok"] = bool(out["overdrive_qps_x"] >= 4.0)

        # --- tenant flood through the sharded front end ------------------
        # quota 2 queued; the flood pool runs 8 back-to-back threads
        # (~10x the share a 2-slot quota represents under 3 pools),
        # each quiet pool is 1 paced thread
        runt = os.path.join(tmp, "runt")
        tenant_env = dict(base_env, MXTPU_SERVE_TENANT_QUOTA="2")
        proc, port = _fleet_up(specs, buckets, store, runt, 1,
                               extra_env=tenant_env, workers=4)
        rows = _tenant_load(port, "mlp", sample,
                            [("flood", 8, 0.0),
                             ("quiet-a", 1, 0.005),
                             ("quiet-b", 1, 0.005)], 2.0 + seconds)
        proc.send_signal(_signal.SIGTERM)
        out["overdrive_drain_rc_tenant"] = proc.wait(timeout=90)
        proc = None
        quiet_p99 = [rows[t]["p99"] for t in ("quiet-a", "quiet-b")
                     if rows[t]["p99"] is not None]
        if quiet_p99:
            out["overdrive_tenant_p99_ms"] = max(quiet_p99)
        if rows["flood"]["p99"] is not None:
            out["overdrive_tenant_flood_p99_ms"] = rows["flood"]["p99"]
        out["overdrive_tenant_flood_shed"] = rows["flood"]["shed"]
        out["overdrive_tenant_errors"] = sum(
            r["errors"] for r in rows.values())
        out["overdrive_tenant_slo_ms"] = 500.0
        out["overdrive_tenant_ok"] = bool(
            quiet_p99 and max(quiet_p99) <= 500.0
            and rows["flood"]["shed"] > 0
            and out["overdrive_tenant_errors"] == 0)

        # --- the autoscale round trip, drop-free -------------------------
        # watermarks scaled to the MLP's ms-scale waits; cooldown short
        # so the drill finishes inside the mode budget
        runa = os.path.join(tmp, "runa")
        scale_env = dict(base_env,
                         MXTPU_FLEET_SCALE_HIGH_MS="1.0",
                         MXTPU_FLEET_SCALE_LOW_MS="0.25",
                         MXTPU_FLEET_SCALE_COOLDOWN_S="2",
                         MXTPU_FLEET_MIN_REPLICAS="1",
                         MXTPU_FLEET_MAX_REPLICAS="2")
        proc, port = _fleet_up(specs, buckets, store, runa, 1,
                               extra_env=scale_env, workers=2,
                               autoscale=True)
        view_path = os.path.join(runa, "fleet-view.json")
        import threading

        from mxnet_tpu.serving import ServeClient

        stop_flood = threading.Event()
        stop_all = threading.Event()
        errors = [0]
        sheds = [0]
        requests = [0]
        lock = threading.Lock()
        rs = np.random.RandomState(11)

        def drill_worker(i, flood):
            cli = ServeClient("127.0.0.1", port)
            x = rs.rand(*sample).astype("f") + i
            mine_err = mine_shed = mine_n = 0
            gate = stop_flood if flood else stop_all
            try:
                while not gate.is_set():
                    try:
                        status, _ = cli.predict("mlp", x, npy=True)
                    except Exception:  # noqa: BLE001 — conn loss
                        status = -1
                    mine_n += 1
                    if status == 429:
                        mine_shed += 1
                    elif status != 200:
                        mine_err += 1
                    if not flood:
                        time.sleep(0.05)  # the trickle keeps the
                        # signal under the LOW watermark
            finally:
                cli.close()
            with lock:
                errors[0] += mine_err
                sheds[0] += mine_shed
                requests[0] += mine_n

        threads = [threading.Thread(target=drill_worker,
                                    args=(i, True)) for i in range(8)]
        threads.append(threading.Thread(target=drill_worker,
                                        args=(99, False)))
        for t in threads:
            t.start()

        def _wait_healthy(n, deadline_s, what):
            deadline = time.monotonic() + deadline_s
            tic = time.monotonic()
            while _view_healthy_count(view_path) != n:
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        "overdrive autoscale drill: %s never happened "
                        "(healthy=%s)" % (what,
                                          _view_healthy_count(view_path)))
                time.sleep(0.1)
            return time.monotonic() - tic

        out["overdrive_scale_up_s"] = round(
            _wait_healthy(2, 120, "scale-up to 2 replicas"), 2)
        stop_flood.set()    # trickle only -> signal under LOW
        out["overdrive_scale_down_s"] = round(
            _wait_healthy(1, 120, "fenced scale-down to 1 replica"), 2)
        time.sleep(2.0)     # traffic across the post-fence drain too
        stop_all.set()
        for t in threads:
            t.join(timeout=30)
        out["overdrive_drill_requests"] = requests[0]
        out["overdrive_drill_errors"] = errors[0]
        if sheds[0]:
            out["overdrive_drill_shed"] = sheds[0]
        out["overdrive_drop_free"] = \
            1.0 if errors[0] == 0 and requests[0] > 0 else 0.0
        proc.send_signal(_signal.SIGTERM)
        out["overdrive_drain_rc"] = proc.wait(timeout=90)
        proc = None
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def _train_flops(sym_name):
    """Analytic training FLOPs per image (3x forward; contrib/flops.py)."""
    from mxnet_tpu import models
    from mxnet_tpu.contrib.flops import model_flops
    sym = models.get_symbol(sym_name, num_classes=1000)
    return 3 * model_flops(sym, data=(1, 3, 224, 224))


def _analyze_bench():
    """Static-analysis metrics (docs/how_to/static_analysis.md):
    per-step collective count + bytes from the mxlint graph audit for
    the standard MLP (dp 'allreduce' — expect all-reduce only) and the
    same model under grad_sync='zero' (expect all-gather +
    reduce-scatter by design), plus mxlint wall time over the full
    default scope (package + tools + bench, ALL levels including the
    whole-repo race/contract passes) against its < 5 s budget —
    ``lint_wall_ms`` is gate-guarded LOWER-is-better so a quadratic
    blow-up in a new repo-wide pass cannot land silently.  All host/CPU
    work."""
    import subprocess as _sp
    import time as _time

    out = {}
    here = os.path.dirname(os.path.abspath(__file__))
    t0 = _time.monotonic()
    res = _sp.run([sys.executable, os.path.join(here, "tools",
                                                "mxlint.py"), "-q"],
                  capture_output=True, text=True, timeout=120)
    out["mxlint_wall_s"] = round(_time.monotonic() - t0, 2)
    out["lint_wall_ms"] = round(out["mxlint_wall_s"] * 1000.0, 1)
    out["mxlint_rc"] = res.returncode
    out["mxlint_budget_ok"] = bool(
        res.returncode == 0 and out["mxlint_wall_s"] < 5.0)

    from mxnet_tpu.analysis import fixtures

    X, y = fixtures.standard_mlp_batch()
    findings = 0
    for key, grad_sync in (("analyze_mlp", "allreduce"),
                           ("analyze_zero", "zero")):
        trainer = fixtures.standard_mlp_trainer(grad_sync=grad_sync)
        try:
            rep = trainer.analyze(X, y)
            findings += len(rep.findings)
            out[key + "_collectives"] = rep.stats.get("collectives", {})
        finally:
            trainer.close()
    out["analyze_findings"] = findings
    return out


def _zero3_bench(preset=None):
    """Fully-sharded training sweep (docs/how_to/sharded_training.md):
    allreduce vs zero vs zero3 on the standard MLP and a deliberately
    WIDE model (params dominate activations — the regime zero3 exists
    for), on the 8-virtual-device CPU mesh.

    Self-proof keys: ``zero3_param_bytes_frac`` must show ~1/world
    per-device parameter residency (plus the indivisible-param
    residue), ``zero3_vs_zero_frac`` prices the on-demand gathers
    against zero's monolithic gather block (acceptance: within 10%),
    and ``zero3_schedule_ok`` runs trainer.analyze() so the artifact
    records the PROVEN collective schedule, not an assumption.  Gate
    keys: ``zero3_steps_s`` (throughput), ``zero3_param_shard_x``
    (residency leverage — drops to ~1 if sharding silently breaks),
    ``zero3_wide_mem_x`` (compiled peak-memory leverage on the wide
    model from ``compiled.memory_analysis()``).
    """
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.analysis import fixtures
    from mxnet_tpu.parallel import SPMDTrainer, local_mesh

    small = preset == "small"
    steps = 10 if small else 30
    warmup = 3 if small else 8
    world = len(jax.devices())
    out = {"zero3_world": world}

    def _wide_sym(nh=2048, nc=8):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=nc, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    def _measure(make_trainer, X, y):
        res = {}
        for sync in ("allreduce", "zero", "zero3"):
            trainer = make_trainer(sync)
            full = sum(int(np.prod(v.shape)) *
                       np.dtype(v.dtype).itemsize
                       for v in trainer.params.values())
            resident = sum(v.addressable_shards[0].data.nbytes
                           for v in trainer.params.values())
            opt_res = sum(x.addressable_shards[0].data.nbytes
                          for s in trainer.opt_state.values() for x in s)
            args = trainer._example_args(X, y)
            compiled = trainer._step_fn.lower(*args).compile()
            try:
                ma = compiled.memory_analysis()
                peak = int(getattr(ma, "argument_size_in_bytes", 0) +
                           getattr(ma, "temp_size_in_bytes", 0))
            except Exception:  # noqa: BLE001 — backend without the API
                peak = None
            for _ in range(warmup):
                trainer.step(X, y)
            small_p = min(trainer.params,
                          key=lambda k: trainer.params[k].size)

            def sync_dev():
                np.asarray(
                    trainer.params[small_p].addressable_shards[0].data)

            sync_dev()
            tic = time.perf_counter()
            for _ in range(steps):
                trainer.step(X, y)
            sync_dev()
            elapsed = time.perf_counter() - tic
            entry = {"steps_s": round(steps / elapsed, 2),
                     "param_bytes": full,
                     "param_resident_bytes": resident,
                     "param_bytes_frac": round(resident / full, 4),
                     "opt_resident_bytes": opt_res}
            if peak:
                entry["peak_bytes"] = peak
            if sync == "zero3":
                entry["tier"] = trainer.zero3_tier
                rep = trainer.analyze(X, y)
                coll = rep.stats.get("collectives", {})
                entry["collectives"] = coll
                entry["schedule_ok"] = bool(
                    rep.ok and coll.get("reduce-scatter", {}).get("count")
                    and coll.get("all-gather", {}).get("count"))
            trainer.close()
            res[sync] = entry
        return res

    # standard MLP — the fixture every analyze/lint consumer pins
    X, y = fixtures.standard_mlp_batch()
    std = _measure(
        lambda sync: fixtures.standard_mlp_trainer(grad_sync=sync), X, y)
    out["zero3_steps_s"] = std["zero3"]["steps_s"]
    out["zero3_zero_steps_s"] = std["zero"]["steps_s"]
    out["zero3_allreduce_steps_s"] = std["allreduce"]["steps_s"]
    out["zero3_vs_zero_frac"] = round(
        std["zero3"]["steps_s"] / std["zero"]["steps_s"], 3)
    out["zero3_param_bytes_frac"] = std["zero3"]["param_bytes_frac"]
    out["zero3_param_shard_x"] = round(
        1.0 / max(std["zero3"]["param_bytes_frac"], 1e-9), 2)
    out["zero3_frac_ok"] = bool(
        std["zero3"]["param_bytes_frac"] <= 1.0 / world + 0.05)
    out["zero3_tier"] = std["zero3"].get("tier")
    out["zero3_collectives"] = std["zero3"].get("collectives")
    out["zero3_schedule_ok"] = std["zero3"].get("schedule_ok")

    # deliberately wide model: params >> activations, batch small
    nh = 512 if small else 2048
    din = 128 if small else 512
    rs = np.random.RandomState(0)
    Xw = rs.randn(32, din).astype("f")
    yw = rs.randint(0, 8, 32).astype("f")
    sym = _wide_sym(nh=nh)

    def _wide_trainer(sync):
        t = SPMDTrainer(sym, "sgd",
                        {"learning_rate": 0.05, "momentum": 0.9,
                         "rescale_grad": 1.0 / 32},
                        mesh=local_mesh("dp"), grad_sync=sync)
        t.bind([("data", (32, din))], [("softmax_label", (32,))])
        mx.random.seed(7)
        t.init_params(mx.initializer.Xavier())
        return t

    wide = _measure(_wide_trainer, Xw, yw)
    out["zero3_wide_steps_s"] = wide["zero3"]["steps_s"]
    out["zero3_wide_param_bytes_frac"] = \
        wide["zero3"]["param_bytes_frac"]
    if wide["zero3"].get("peak_bytes") and \
            wide["allreduce"].get("peak_bytes"):
        out["zero3_wide_peak_mb"] = round(
            wide["zero3"]["peak_bytes"] / 1e6, 2)
        out["zero3_allreduce_wide_peak_mb"] = round(
            wide["allreduce"]["peak_bytes"] / 1e6, 2)
        out["zero3_wide_mem_x"] = round(
            wide["allreduce"]["peak_bytes"] /
            wide["zero3"]["peak_bytes"], 2)
    else:
        # a backend without compiled.memory_analysis() cannot measure
        # the key at all — mark it structurally unmeasurable so the
        # self-gate SKIPS the comparison instead of reporting a
        # vanished metric (same contract as the 1-core scaling notes)
        out["zero3_mem_note"] = "unavailable_memory_analysis"
    return out


def _plan_bench(preset=None):
    """mxplan self-proof (docs/how_to/planner.md): planner decision
    time and the planned-vs-manual gather grouping on the zero3 bench
    model, on the 8-virtual-device CPU mesh.

    Gate keys (both LOWER is better): ``plan_decide_ms`` — one full
    prescriptive ``planner.plan()`` pass over the wide model (strategy
    ladder + per-param rules + gather groups; planning must stay a
    bind-time rounding error, never a bring-up tax) — and
    ``plan_step_ms`` — the zero3 step under the planned (=auto)
    grouping.  ``plan_vs_manual_frac`` prices the planned grouping
    against the retired manual default (MXTPU_ZERO3_GATHER_GROUP=1,
    per-layer gathers): < 1.0 means the planner's bucket-merged groups
    beat per-layer dispatch on this host.  Self-proof keys:
    ``plan_roundtrip_ok`` (serialize -> parse -> identical digest, the
    manifest-persistence contract) and ``plan_budget_ladder_ok`` (a
    shrinking HBM budget walks allreduce -> zero -> zero3).
    """
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.parallel import ShardingPlan, SPMDTrainer, local_mesh
    from mxnet_tpu.parallel import planner

    small = preset == "small"
    steps = 10 if small else 30
    warmup = 3 if small else 8
    world = len(jax.devices())
    out = {"plan_world": world}

    nh = 512 if small else 2048
    din = 128 if small else 512

    def _wide_sym():
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=8, name="fc2")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    data_shapes = [("data", (32, din))]
    label_shapes = [("softmax_label", (32,))]
    # the small preset's whole model fits one default bucket, which
    # would collapse the zero3 byte model into zero's — scale the
    # bucket so the ladder has three distinct rungs on both presets
    bucket = (1 << 16) if small else None

    # 1) decision time: a full prescriptive pass, budget engaged so the
    # strategy ladder actually walks (best-of to shed scheduler noise)
    probe = planner.plan(_wide_sym(), data_shapes, label_shapes,
                         world=world, optimizer="sgd",
                         optimizer_params={"momentum": 0.9},
                         gather_bucket=bucket)
    model = probe.doc["bytes"]["per_device"]
    budget = int((model["zero"] + model["zero3"]) / 2)  # forces zero3
    best = None
    for _ in range(3 if small else 5):
        tic = time.perf_counter()
        chosen = planner.plan(_wide_sym(), data_shapes, label_shapes,
                              world=world, hbm_budget=budget,
                              optimizer="sgd",
                              optimizer_params={"momentum": 0.9},
                              gather_bucket=bucket)
        dt = time.perf_counter() - tic
        best = dt if best is None else min(best, dt)
    out["plan_decide_ms"] = round(best * 1000, 3)
    out["plan_grad_sync"] = chosen.grad_sync
    out["plan_groups"] = len(chosen.gather_groups)

    # self-proof: the budget ladder picks each strategy in turn, and a
    # serialized plan parses back bit-identical (the manifest contract)
    ladder = []
    for b in (model["allreduce"] + 1, model["zero"] + 1,
              model["zero3"] + 1):
        ladder.append(planner.plan(
            _wide_sym(), data_shapes, label_shapes, world=world,
            hbm_budget=int(b), optimizer="sgd",
            optimizer_params={"momentum": 0.9},
            gather_bucket=bucket).grad_sync)
    out["plan_budget_ladder"] = ladder
    out["plan_budget_ladder_ok"] = ladder == ["allreduce", "zero",
                                              "zero3"]
    try:
        planner.plan(_wide_sym(), data_shapes, label_shapes, world=world,
                     hbm_budget=1, optimizer="sgd")
        out["plan_overflow_raises"] = False
    except MXNetError:
        out["plan_overflow_raises"] = True
    rt = ShardingPlan.from_doc(json.loads(chosen.to_json()))
    out["plan_roundtrip_ok"] = bool(rt.digest() == chosen.digest())

    # 2) planned (=auto) vs the retired manual default (=1, per-layer)
    # on a DEEP stack — the regime where the groupings actually differ:
    # per-layer gathers dispatch one collective per fc, the planner's
    # bucket merge fuses consecutive small layers into few collectives
    depth = 4 if small else 10
    dnh = 128 if small else 512

    def _deep_sym():
        net = mx.sym.Variable("data")
        for i in range(depth):
            net = mx.sym.FullyConnected(net, num_hidden=dnh,
                                        name="fc%d" % i)
            net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=8, name="fc_out")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    deep_data = [("data", (32, dnh))]
    rs = np.random.RandomState(0)
    Xw = rs.randn(32, dnh).astype("f")
    yw = rs.randint(0, 8, 32).astype("f")

    def _measure(group_env):
        from mxnet_tpu.parallel.zero3 import ENV_ZERO3_GATHER_GROUP
        # steering the OPERATOR'S variable around one measurement, not
        # reading config — _scoped_env round-trips "unset" faithfully
        with _scoped_env(ENV_ZERO3_GATHER_GROUP, group_env):
            t = SPMDTrainer(_deep_sym(), "sgd",
                            {"learning_rate": 0.001, "momentum": 0.9,
                             "rescale_grad": 1.0 / 32},
                            mesh=local_mesh("dp"), grad_sync="zero3")
            t.bind(deep_data, label_shapes)
            mx.random.seed(7)
            t.init_params(mx.initializer.Xavier())
            ngroups = len(t._zero3_groups)
            for _ in range(warmup):
                t.step(Xw, yw)
            small_p = min(t.params, key=lambda k: t.params[k].size)

            def sync_dev():
                np.asarray(t.params[small_p].addressable_shards[0].data)

            sync_dev()
            tic = time.perf_counter()
            for _ in range(steps):
                t.step(Xw, yw)
            sync_dev()
            elapsed = time.perf_counter() - tic
            t.close()
            return (elapsed / steps) * 1000, ngroups

    # best-of-2, interleaved: host scheduler drift on a shared box is
    # larger than the grouping delta, so each variant keeps its best run
    auto_ms, auto_groups = _measure("auto")
    manual_ms, manual_groups = _measure("1")
    if not small:
        auto_ms = min(auto_ms, _measure("auto")[0])
        manual_ms = min(manual_ms, _measure("1")[0])
    out["plan_step_ms"] = round(auto_ms, 3)
    out["plan_manual_step_ms"] = round(manual_ms, 3)
    out["plan_vs_manual_frac"] = round(auto_ms / manual_ms, 3)
    out["plan_auto_groups"] = auto_groups
    out["plan_manual_groups"] = manual_groups
    return out


def _run_mode(mode):
    """One metric, current process.  Prints a partial-JSON line."""
    batch = _env_int("BENCH_BATCH", 32)
    steps = _env_int("BENCH_STEPS", 30)
    warmup = _env_int("BENCH_WARMUP", 10)
    trials = _env_int("BENCH_TRIALS", 2)
    sweep_steps = _env_int("BENCH_SWEEP_STEPS", 25)
    out = {}
    if mode == "_hang-grandchild":
        # harness self-test fixture (tests/test_bench_harness.py): hang
        # with a grandchild holding the inherited stdout pipe — the
        # BENCH_r05 failure shape.  Never in a real artifact.
        import subprocess as _sp
        _sp.Popen([sys.executable, "-c", "import time; time.sleep(600)"])
        time.sleep(600)
        return
    if mode in ("data_service", "data-service"):
        mode = "data-service"
    if mode in ("data_net", "data-net"):
        mode = "data-net"
    if mode in ("decode", "fed-cpu", "pipeline", "compile-probe",
                "resume", "checkpoint", "ckpt", "analyze", "serve",
                "fleet", "tail", "overdrive", "hotswap", "data-service",
                "data-net", "roofline", "zero3", "plan"):
        # host-side metrics: pin the CPU backend BEFORE any jax client
        # exists, whatever the host has
        if mode in ("analyze", "zero3", "plan", "ckpt"):
            # these lint/shard the dp=8 fused step on a virtual mesh
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
        import jax
        jax.config.update("jax_platforms", "cpu")
    elif mode in CHIP_MODES:
        _require_tpu()
    if mode == "analyze":
        out.update(_analyze_bench())
    elif mode == "zero3":
        out.update(_zero3_bench())
    elif mode == "plan":
        out.update(_plan_bench())
    elif mode == "roofline":
        out.update(_roofline_bench())
    elif mode == "serve":
        out.update(_serve_bench())
    elif mode == "fleet":
        out.update(_fleet_bench())
    elif mode == "tail":
        out.update(_tail_bench())
    elif mode == "overdrive":
        out.update(_overdrive_bench())
    elif mode == "region":
        out.update(_region_bench())
    elif mode == "hotswap":
        out.update(_hotswap_bench())
    elif mode == "decode":
        out.update(_decode_bench())
    elif mode == "data-service":
        out.update(_data_service_bench())
    elif mode == "data-net":
        out.update(_data_net_bench())
    elif mode == "fed-cpu":
        out.update(_fed_cpu_bench())
    elif mode == "pipeline":
        out.update(_pipeline_bench())
    elif mode == "compile-probe":
        out.update(_compile_probe())
    elif mode == "resume":
        out.update(_resume_bench())
    elif mode == "checkpoint":
        out.update(_checkpoint_bench())
    elif mode == "ckpt":
        out.update(_ckpt_sharded_bench())
    elif mode == "fed":
        out["fed"] = round(_fed_bench(batch, steps, warmup, trials), 2)
        out["fed_roofline"] = _roofline(out["fed"],
                                        _train_flops("resnet-50"))
        out["device_kind"] = _device_peak()[0]
    elif mode == "compute":
        tr = _make_trainer("resnet-50", batch)
        out["compute"] = round(
            _compute_bench(tr, batch, steps, warmup, trials), 2)
        out["compute_roofline"] = _roofline(out["compute"],
                                            _train_flops("resnet-50"))
        out["device_kind"] = _device_peak()[0]
    elif mode == "compute-large":
        # MFU headroom row: the baseline config is batch 32 (the
        # reference's table row); larger per-chip batches raise
        # arithmetic intensity and show the utilization ceiling
        big = _env_int("BENCH_LARGE_BATCH", 256)
        tr = _make_trainer("resnet-50", big)
        out["compute-large"] = round(
            _compute_bench(tr, big, max(8, steps // 3), 4, 1,
                           staged=_staged_batches(big, 2)), 2)
        out["compute-large_roofline"] = _roofline(
            out["compute-large"], _train_flops("resnet-50"))
        out["compute_large_batch"] = big
    elif mode in ("inception-bn", "resnet-152"):
        tr = _make_trainer(mode, batch)
        out[mode] = round(
            _compute_bench(tr, batch, sweep_steps, warmup, 1), 2)
        out[mode + "_roofline"] = _roofline(out[mode], _train_flops(mode))
    elif mode == "lstm":
        out["lstm"] = round(
            _lstm_bench(batch, 32, sweep_steps, warmup, 1), 2)
        from mxnet_tpu.contrib.flops import model_flops
        from mxnet_tpu.models import lstm_lm
        sym, _, _ = lstm_lm.lstm_lm_sym(32, 10000, num_embed=200,
                                        num_hidden=200, num_layers=2)
        # per-token training flops at the bench seq_len
        out["lstm_roofline"] = _roofline(
            out["lstm"], 3 * model_flops(sym, data=(1, 32)) / 32.0)
    else:
        # an unknown mode must fail loudly (-> a "failed" status record
        # in the artifact), not ship an empty part that looks like a
        # metric quietly measuring nothing
        sys.stderr.write("unknown BENCH_MODE %r\n" % mode)
        sys.exit(2)
    print("BENCH_PART " + json.dumps(out))


#: modes the positional CLI form (`python bench.py <mode>`) accepts —
#: the same names BENCH_MODE understands (aliases included)
KNOWN_MODES = frozenset((
    "decode", "data-service", "data_service", "data-net", "data_net",
    "fed-cpu", "pipeline", "compile-probe", "resume", "checkpoint",
    "ckpt", "analyze", "serve", "fleet", "tail", "overdrive", "hotswap",
    "region",
    "roofline", "zero3",
    "plan", "fed", "compute",
    "compute-large", "inception-bn", "resnet-152", "lstm",
))


def _collect(mode, timeout=480, extra_env=None):
    """Run one metric in a FRESH subprocess, with HARD timeout isolation.

    Each metric gets its own process, and this parent never imports JAX
    or ``mxnet_tpu`` (whose ``__init__`` imports JAX): a chip belongs to
    one process, so a parent that touched the backend would starve every
    chip-mode child.  (The old reason — a second trainer in one process
    stepping ~12x slower — did not reproduce on the v5e: chip_smoke.py
    builds a second trainer after closing the first and prints both step
    times.)  ``extra_env`` overlays the child environment (the
    compile-cache probes point both runs at one cache directory this
    way).

    Isolation (the BENCH_r05 regression, ROADMAP item 5): a metric that
    hits its budget must cost THAT metric, never the run.  The child is
    its own session/process group and an overrun SIGKILLs the whole
    group — ``subprocess.run``'s own timeout path kills only the direct
    child and then blocks in ``communicate()`` for as long as any
    grandchild (XLA compile workers, decode pools) holds the inherited
    stdout pipe open, which is how one 480s model kill turned into rc=1
    for the whole r05 run.  The final pipe scavenge is bounded too, so
    even an unkillable (D-state) descendant cannot wedge the harness.
    """
    import signal as _signal
    import subprocess
    env = dict(os.environ)
    env["BENCH_MODE"] = mode
    env.update(extra_env or {})
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env, start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, _signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            proc.kill()
        try:
            proc.communicate(timeout=15)
        except (subprocess.TimeoutExpired, ValueError, OSError):
            pass
        sys.stderr.write("bench mode %s timed out after %ds — partial "
                         "artifact continues\n" % (mode, timeout))
        return {mode: {"status": "timeout", "timeout_s": timeout}}
    for line in stdout.splitlines():
        if line.startswith("BENCH_PART "):
            return json.loads(line[len("BENCH_PART "):])
    sys.stderr.write("bench mode %s failed (rc=%s):\n%s\n"
                     % (mode, proc.returncode, (stderr or stdout)[-800:]))
    return {mode: {"status": "failed", "rc": proc.returncode}}


# ---------------------------------------------------------------------------
# regression gate (ROADMAP item 5): compare a fresh artifact against the
# most recent BENCH_*.json and fail on >10% drops in the named keys
# ---------------------------------------------------------------------------

#: higher-is-better keys the gate guards (except the members of
#: LOWER_IS_BETTER_KEYS below).  Entries ending in ``*`` are prefixes
#: (every matching key is compared).
GATE_KEYS = ("value", "compute_img_s", "compute_large_img_s",
             "inception_bn_img_s", "resnet152_img_s", "lstm_tok_s",
             "pipeline_decode_img_s", "fed_cpu", "pipeline_speedup",
             "ckpt_stall_ratio", "ckpt_save_ms", "ckpt_peak_host_frac",
             "serve_*_qps", "serve_batch_speedup",
             "data_service_img_s", "data_service_scaling_x",
             "data_net_img_s", "data_net_scaling_x",
             "pipeline_decode_scaling_x", "roofline_*_speedup",
             "roofline_inception_fwd_x", "roofline_infer_trace_x",
             "inception_gap_frac",
             "zero3_steps_s", "zero3_param_shard_x", "zero3_wide_mem_x",
             "fleet_qps_x", "fleet_warm_start_x", "fleet_route_eff",
             "tail_p99_ms", "tail_drop_free",
             "overdrive_qps", "overdrive_qps_x",
             "overdrive_tenant_p99_ms", "overdrive_drop_free",
             "hotswap_drop_free", "hotswap_swap_ms",
             "region_drop_free", "region_goodput_chaos_frac",
             "region_freshness_ms",
             "plan_decide_ms", "plan_step_ms", "lint_wall_ms")

#: GATE_KEYS members where LOWER is better (latencies): the gate flags
#: a RISE past tolerance instead of a drop — gating a latency with the
#: higher-is-better rule would fail every improvement and bless every
#: regression
LOWER_IS_BETTER_KEYS = frozenset(("hotswap_swap_ms", "plan_decide_ms",
                                  "tail_p99_ms",
                                  "plan_step_ms", "region_freshness_ms",
                                  "overdrive_tenant_p99_ms",
                                  "ckpt_save_ms", "ckpt_peak_host_frac",
                                  "lint_wall_ms"))

#: structurally-unmeasurable keys: each maps to a NOTE key whose
#: presence (``flat_by_construction*`` on 1-core hosts — the decode
#: threads/worker processes have nowhere to scale TO — or
#: ``unavailable*`` when the backend lacks the measurement API) makes
#: the gate SKIP that one comparison; a host that CAN measure still
#: gates, so the note can neither mask nor fake a regression.  The
#: absolute-throughput keys above always gate.
SCALING_SHAPE_KEYS = {
    "pipeline_decode_scaling_x": "decode_scaling_note",
    "data_service_scaling_x": "data_service_scaling_note",
    "data_net_scaling_x": "data_net_scaling_note",
    "zero3_wide_mem_x": "zero3_mem_note",
    # clients + router + 2 replicas need >= 4 cores to scale; smaller
    # hosts note it and only the SHAPE key is exempted
    "fleet_qps_x": "fleet_scaling_note",
    # clients + 4 reuseport workers + replica need >= 6 cores; the
    # absolute overdrive_qps always gates
    "overdrive_qps_x": "overdrive_note",
}

#: keys whose absolute value is a property of the ACCELERATOR tier the
#: round ran on (the fed/compute/model-sweep throughputs).  The gate
#: compares them only when baseline and new artifact ran the SAME
#: device tier (``device_kind``): a CPU round "regressing" a TPU
#: round's img/s is a hardware swap, not a code regression — and
#: blessing it would be just as wrong as blocking it.  Skipped keys
#: are listed LOUDLY in the report (``skipped_device_tier_change``);
#: same-tier rounds always gate, so the rule can neither mask nor fake
#: a regression within a tier.  Ratio/host-side keys always gate.
DEVICE_TIER_KEYS = frozenset((
    "value", "compute_img_s", "compute_large_img_s",
    "inception_bn_img_s", "resnet152_img_s", "lstm_tok_s"))


def _gate_payload(path):
    """An artifact file -> the result dict.  Accepts both the raw
    ``bench.py`` stdout object and the driver's ``{n, cmd, rc, parsed,
    tail}`` wrapper; returns None when the file holds no usable run
    (e.g. the r05 rc=1 wrapper with ``parsed: null``)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "parsed" in doc and "cmd" in doc:
        doc = doc["parsed"]
    if not isinstance(doc, dict) or not doc:
        return None
    return doc


def _latest_artifact(directory, exclude=None):
    """Newest usable ``BENCH_*.json`` by round number (``BENCH_r05`` >
    ``BENCH_r04``), skipping files with no payload AND ``exclude``."""
    import re
    best = None
    exclude = os.path.abspath(exclude) if exclude else None
    for name in os.listdir(directory):
        m = re.match(r"BENCH_r?(\d+)\.json$", name)
        if not m:
            continue
        path = os.path.join(directory, name)
        if exclude and os.path.abspath(path) == exclude:
            continue
        try:
            payload = _gate_payload(path)
        except (OSError, ValueError):
            continue
        if payload is None:
            continue
        if best is None or int(m.group(1)) > best[0]:
            best = (int(m.group(1)), path, payload)
    return best


def _match_gate_keys(payload):
    keys = set()
    for pat in GATE_KEYS:
        if "*" in pat:
            head, _, tail = pat.partition("*")
            keys.update(k for k in payload
                        if k.startswith(head) and k.endswith(tail)
                        and isinstance(payload[k], (int, float)))
        elif isinstance(payload.get(pat), (int, float)):
            keys.add(pat)
    return keys


def gate(new_path, against=None, tolerance=0.10):
    """Compare ``new_path`` (an artifact path, or an already-parsed
    result dict — the self-gate in ``main()`` passes its own result)
    against a baseline artifact; returns the report dict (``pass``
    False on any guarded key dropping more than ``tolerance``, going
    missing, or timing out)."""
    if isinstance(new_path, dict):
        new, new_path = new_path, None
    else:
        try:
            new = _gate_payload(new_path)
        except (OSError, ValueError) as e:
            return {"pass": False, "error": "cannot read artifact %s: %s"
                    % (new_path, e)}
        if new is None:
            return {"pass": False, "error": "artifact %s holds no parsed "
                    "result" % new_path}
    if against:
        try:
            base_path, base = against, _gate_payload(against)
        except (OSError, ValueError) as e:
            return {"pass": False, "error": "cannot read baseline %s: %s"
                    % (against, e)}
    else:
        found = _latest_artifact(
            os.path.dirname(os.path.abspath(__file__)), exclude=new_path)
        if found is None:
            return {"pass": True, "baseline": None,
                    "note": "no prior BENCH_*.json — nothing to gate "
                            "against"}
        _, base_path, base = found
    if base is None:
        return {"pass": False, "error": "baseline %s holds no parsed "
                "result" % base_path}
    regressions, checked, skipped = [], [], []
    tier_skipped = []
    base_tier = base.get("device_kind")
    new_tier = new.get("device_kind")
    tier_changed = base_tier != new_tier
    structural = ("flat_by_construction", "unavailable")
    for key in sorted(_match_gate_keys(base)):
        if key in DEVICE_TIER_KEYS and tier_changed:
            # accelerator-tier throughputs are only comparable within
            # one device tier — a changed tier is recorded, not gated
            tier_skipped.append(key)
            continue
        note = SCALING_SHAPE_KEYS.get(key)
        if note is not None and (
                str(base.get(note, "")).startswith(structural)
                or str(new.get(note, "")).startswith(structural)):
            skipped.append(key)
            continue
        old_v = base[key]
        new_v = new.get(key)
        if not isinstance(new_v, (int, float)):
            # a guarded metric that vanished IS a regression — that is
            # precisely how a timed-out model (r05's inception-bn)
            # surfaces in a partial artifact
            regressions.append({"key": key, "baseline": old_v,
                                "status": "missing"})
            continue
        checked.append(key)
        if key in LOWER_IS_BETTER_KEYS:
            if old_v > 0 and new_v > old_v * (1.0 + tolerance):
                regressions.append(
                    {"key": key, "baseline": old_v, "value": new_v,
                     "rise": round(new_v / old_v - 1.0, 3)})
        elif old_v > 0 and new_v < old_v * (1.0 - tolerance):
            regressions.append(
                {"key": key, "baseline": old_v, "value": new_v,
                 "drop": round(1.0 - new_v / old_v, 3)})
    report = {"pass": not regressions, "baseline": base_path,
              "tolerance": tolerance, "checked": checked,
              "regressions": regressions}
    if skipped:
        report["skipped_flat_by_construction"] = skipped
    if tier_skipped:
        report["skipped_device_tier_change"] = {
            "keys": tier_skipped,
            "baseline_device": base_tier, "new_device": new_tier}
    if new.get("incomplete"):
        report["incomplete_modes"] = sorted(new["incomplete"])
    return report


def _gate_main(argv):
    import argparse
    parser = argparse.ArgumentParser(
        prog="bench.py --gate",
        description="fail (rc 1) on >tolerance drops vs the most recent "
                    "BENCH_*.json")
    parser.add_argument("--gate", required=True, metavar="NEW.json",
                        help="the fresh artifact to check")
    parser.add_argument("--against", default=None, metavar="OLD.json",
                        help="explicit baseline (default: newest usable "
                             "BENCH_*.json next to bench.py)")
    parser.add_argument("--gate-tolerance", type=float, default=0.10)
    args = parser.parse_args(argv)
    report = gate(args.gate, against=args.against,
                  tolerance=args.gate_tolerance)
    print(json.dumps(report))
    return 0 if report["pass"] else 1


def main():
    if any(a.startswith("--gate") for a in sys.argv[1:]):
        sys.exit(_gate_main(sys.argv[1:]))
    mode = os.environ.get("BENCH_MODE")
    if mode is None and len(sys.argv) > 1 and sys.argv[1] in KNOWN_MODES:
        # positional single-mode form, e.g. `python bench.py roofline`
        # (docs/how_to/kernels.md) — same path as BENCH_MODE=<mode>.
        # Restricted to the known-mode set: main() is also called
        # IN-PROCESS (tests monkeypatch _collect), where argv belongs
        # to the embedding program, not to bench.
        mode = sys.argv[1]
    if mode:
        _run_mode(mode)
        return

    batch = _env_int("BENCH_BATCH", 32)
    result = {}
    parts = {}
    if os.environ.get("BENCH_PIPELINE", "1") != "0":
        parts.update(_collect("decode"))
        parts.update(_collect("data-service"))
        parts.update(_collect("data-net"))
        parts.update(_collect("fed-cpu"))
        parts.update(_collect("pipeline"))
        # cold vs warm bring-up through the persistent compile cache: two
        # fresh processes sharing one cache dir — the first compiles and
        # populates, the second loads from disk.  A FIXED path, emptied
        # first: the path is part of the cache key, so a directory that
        # moves never hits.  The probe's net compiles in well under jax's
        # one-second caching threshold, hence the zeroed thresholds.
        import shutil
        cache_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 ".jax_cache", "compile-probe")
        shutil.rmtree(cache_dir, ignore_errors=True)
        cache_env = {"JAX_COMPILATION_CACHE_DIR": cache_dir,
                     "JAX_ENABLE_COMPILATION_CACHE": "true",
                     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
                     "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
        cold = _collect("compile-probe", extra_env=cache_env)
        warm = _collect("compile-probe", extra_env=cache_env)
        if "compile_bringup_s" in cold:
            parts["compile_cold_s"] = cold["compile_bringup_s"]
        if "compile_bringup_s" in warm:
            parts["compile_warm_s"] = warm["compile_bringup_s"]
        parts.update(_collect("resume"))
        parts.update(_collect("checkpoint"))
        # sharded-native vs gathered checkpoints on the dp=8 zero3 mesh
        parts.update(_collect("ckpt"))
        parts.update(_collect("serve"))
        parts.update(_collect("hotswap"))
        parts.update(_collect("fleet", timeout=600))
        parts.update(_collect("tail", timeout=600))
        # the sharded front end: reuseport worker scaling, tenant
        # isolation under flood, the drop-free autoscale round trip
        parts.update(_collect("overdrive", timeout=600))
        # the composed region drill (tools/region.py smoke): trainer
        # bring-up + fleet bring-up + the settled storm window
        parts.update(_collect("region", timeout=600))
        # the mxfuse whole-model stanza compiles inception twice
        parts.update(_collect("roofline", timeout=600))
        parts.update(_collect("zero3"))
        parts.update(_collect("plan"))
        parts.update(_collect("fed", timeout=1800))
    parts.update(_collect("analyze", timeout=240))
    # the budgets assume COLD compiles: the children share the persistent
    # compile cache (JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache),
    # but the chip tool's machine starts every call with it empty
    parts.update(_collect("compute", timeout=1800))
    if os.environ.get("BENCH_SWEEP", "1") != "0":
        parts.update(_collect("compute-large", timeout=2400))
        parts.update(_collect("inception-bn", timeout=1800))
        # the deepest compile of the sweep
        parts.update(_collect("resnet-152", timeout=3600))
        parts.update(_collect("lstm"))

    # pull timed-out/failed models aside so the numeric consumers below
    # see only real measurements; the statuses ship in the artifact
    statuses = {k: v for k, v in parts.items()
                if isinstance(v, dict) and v.get("status")}
    for k in statuses:
        parts.pop(k)
    if statuses:
        result["incomplete"] = statuses

    baseline = 109.0  # reference: ResNet-50 batch 32 on 1x K80
    fed = parts.get("fed")
    compute = parts.get("compute")
    if fed is not None:
        result.update({
            "metric": "resnet50_train_throughput_fed_batch%d" % batch,
            "value": fed,
            "unit": "images/sec",
            "vs_baseline": round(fed / baseline, 3),
        })
    if "decode" in parts:
        # reference RecordIO pipeline row: ~3,000 img/s decode+augment
        # (imagenet_full.md:37) — measured here with zero device
        # involvement, per-thread-count scaling rows included
        result["pipeline_decode_img_s"] = parts["decode"]
        result["pipeline_decode_vs_baseline"] = round(
            parts["decode"] / 3000.0, 3)
        result["pipeline_decode_per_core_img_s"] = parts["decode_per_core"]
        result["pipeline_decode_scaling"] = parts["decode_scaling"]
        result["pipeline_decode_scaling_x"] = parts.get("decode_scaling_x")
        result["pipeline_ncores"] = parts["ncores"]
        if "decode_scaling_note" in parts:
            result["decode_scaling_note"] = parts["decode_scaling_note"]
    for k in sorted(parts):
        if k.startswith("data_service_") or k.startswith("data_net_"):
            result[k] = parts[k]
    for k in ("fed_cpu", "fed_cpu_decode", "fed_cpu_step",
              "fed_cpu_ceiling", "fed_cpu_overlap",
              "pipeline_steps_s_depth0", "pipeline_steps_s_depth2",
              "pipeline_speedup", "pipeline_step_ms",
              "pipeline_iter_delay_ms",
              "compile_cold_s", "compile_warm_s",
              "resume_save_s", "resume_restore_s", "resume_refit_s",
              "resume_baseline_s", "resume_overhead_s", "resume_parity",
              "resume_parity_note",
              "ckpt_stall_blocking_s", "ckpt_stall_async_s",
              "ckpt_stall_ratio", "ckpt_parity",
              "ckpt_restore_verified_s", "ckpt_verify_s",
              "ckpt_fsck_s", "ckpt_fsck_rc",
              "ckpt_world", "ckpt_save_ms", "ckpt_gathered_save_ms",
              "ckpt_restore_ms", "ckpt_peak_host_frac",
              "ckpt_peak_host_bytes", "ckpt_total_blob_bytes",
              "ckpt_sharded_parity",
              "mxlint_wall_s", "lint_wall_ms", "mxlint_rc",
              "mxlint_budget_ok",
              "analyze_mlp_collectives", "analyze_zero_collectives",
              "analyze_findings"):
        if k in parts:
            result[k] = parts[k]
    for k in sorted(parts):
        if k.startswith("serve_") or k.startswith("roofline_") \
                or k.startswith("zero3_") or k.startswith("fleet_") \
                or k.startswith("hotswap_") or k.startswith("plan_"):
            result[k] = parts[k]
    if compute is not None:
        if fed is None:
            result.update({
                "metric": "resnet50_train_throughput_batch%d" % batch,
                "value": compute,
                "unit": "images/sec",
                "vs_baseline": round(compute / baseline, 3),
            })
        else:
            result["compute_img_s"] = compute
            result["compute_vs_baseline"] = round(compute / baseline, 3)
            result["pipeline_frac_of_compute"] = round(fed / compute, 3)
    if "inception-bn" in parts:
        result["inception_bn_img_s"] = parts["inception-bn"]
        result["inception_bn_vs_baseline"] = round(
            parts["inception-bn"] / 152.0, 3)
        if compute:
            # the mxfuse headline metric (ROADMAP item 5): inception's
            # speedup-over-its-K80-baseline as a fraction of resnet50's
            # (r04: 61.4x / 137.1x = 0.448) — the plan-optimizer passes
            # exist to narrow this gap, and the gate holds the ratio
            result["inception_gap_frac"] = round(
                (parts["inception-bn"] / 152.0) / (compute / 109.0), 3)
    if "resnet-152" in parts:
        result["resnet152_img_s"] = parts["resnet-152"]
        result["resnet152_vs_baseline"] = round(
            parts["resnet-152"] / 57.0, 3)
    if "lstm" in parts:
        result["lstm_tok_s"] = parts["lstm"]

    # roofline accounting: every on-chip rate carries analytic FLOPs and
    # MFU against the chip's nominal peak; >100% is physically impossible
    # and fails the run loudly instead of shipping a bogus artifact
    if "device_kind" in parts:
        result["device_kind"] = parts["device_kind"]
        result["device_peak_tflops"] = PEAK_TFLOPS.get(parts["device_kind"])
    if "compute-large" in parts:
        result["compute_large_img_s"] = parts["compute-large"]
        result["compute_large_batch"] = parts.get("compute_large_batch")
    violations = []
    for key in ("fed", "compute", "compute-large", "inception-bn",
                "resnet-152", "lstm"):
        roof = parts.get(key + "_roofline")
        if roof:
            # key style matches the sibling *_img_s keys: resnet-152 ->
            # resnet152_img_s, compute-large -> compute_large_img_s
            name = ("resnet152" if key == "resnet-152"
                    else key.replace("-", "_"))
            result[name + "_roofline"] = roof
            if roof.get("mfu", 0) > 1.0:
                violations.append("%s: mfu=%.2f" % (key, roof["mfu"]))
    result["sync_method"] = "dependent-scalar fetch + work-scaling slope"
    if violations:
        result["mfu_implausible"] = violations
        sys.stderr.write("ROOFLINE VIOLATION (>100%% MFU — measurement "
                         "invalid): %s\n" % "; ".join(violations))

    # self-enforcing regression gate (ROADMAP item 5, final step): a full
    # run compares itself against the newest usable BENCH_*.json on disk
    # and FAILS THE PROCESS on >10% drops or vanished keys, so the
    # driver/CI rc blocks regressions instead of accumulating them.
    # BENCH_GATE=0 opts out; partial runs (BENCH_PIPELINE/BENCH_SWEEP
    # off) never self-gate — they are missing keys by design.
    gate_report = None
    full_run = (os.environ.get("BENCH_PIPELINE", "1") != "0"
                and os.environ.get("BENCH_SWEEP", "1") != "0")
    if os.environ.get("BENCH_GATE", "1") != "0" and full_run:
        gate_report = gate(result)
        result["gate"] = gate_report
        if not gate_report.get("pass", True):
            sys.stderr.write("BENCH GATE FAILED: %s\n"
                             % json.dumps(gate_report))

    print(json.dumps(result))
    if gate_report is not None and not gate_report.get("pass", True):
        sys.exit(1)


if __name__ == "__main__":
    main()
