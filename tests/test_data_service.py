"""Data-service tests (mxnet_tpu/data_service/ — the multi-process
shared-memory input pipeline; docs/how_to/performance.md "Scaling the
input pipeline").

The load-bearing contracts proved here:

1. ORDERING/DETERMINISM: for a given seed the delivered record stream
   (data bytes, labels, pads) is identical for ANY worker count, across
   epochs, and — on hosts with the native decoder — BIT-IDENTICAL to
   the in-process pipe for both the no-augment and the seeded
   rand_crop/rand_mirror paths (the worker derives the same
   per-global-batch chunk seed the in-process pipeline uses).
2. ZERO-COPY SLOT LIFETIME: views alias ring slots and are recycled on
   release/next-pull; the device upload path makes a true copy (a CPU
   backend device_put ALIASES numpy memory — the regression that
   test_service_device_arrays_do_not_alias_slots pins).
3. ROBUSTNESS: a crashed worker (injected fault or real SIGKILL — the
   latter in tests/test_chaos.py) is respawned, its shard resumes at
   the last consumed record, and a worker that keeps dying exhausts a
   budget instead of looping forever.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.data_service import common
from mxnet_tpu.data_service.ring import Ring

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gradient_img(h=64, w=64, seed=0):
    rs = np.random.RandomState(seed)
    yy, xx = np.mgrid[0:h, 0:w]
    img = np.stack([(yy * 3) % 256, (xx * 2) % 256,
                    ((yy + xx) * 2) % 256], -1).astype(np.uint8)
    img += rs.randint(0, 10, img.shape).astype(np.uint8)
    return img


@pytest.fixture(scope="module")
def rec_dataset(tmp_path_factory):
    """A 37-image .rec/.idx (odd count: exercises the padded final
    batch) with scalar labels."""
    import cv2
    td = tmp_path_factory.mktemp("dsrec")
    path = str(td / "data.rec")
    idx = str(td / "data.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    for i in range(37):
        ok, buf = cv2.imencode(".jpg", _gradient_img(seed=i))
        assert ok
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i % 5), i, 0), buf.tobytes()))
    w.close()
    return path, idx


def _stream(it, epochs=1):
    """Materialize (data, label, pad) per batch, copying out of any
    transport views."""
    out = []
    for e in range(epochs):
        if e:
            it.reset()
        for b in it:
            d = b.data[0]
            d = d.asnumpy() if hasattr(d, "asnumpy") else np.array(d)
            lab = b.label[0]
            lab = lab.asnumpy() if hasattr(lab, "asnumpy") else np.array(lab)
            out.append((d.copy(), lab.copy(), b.pad))
    return out


def _assert_streams_equal(a, b, what):
    assert len(a) == len(b), (what, len(a), len(b))
    for i, ((d1, l1, p1), (d2, l2, p2)) in enumerate(zip(a, b)):
        assert p1 == p2, (what, i, "pad", p1, p2)
        np.testing.assert_array_equal(l1, l2, err_msg="%s batch %d labels"
                                      % (what, i))
        np.testing.assert_array_equal(d1, d2, err_msg="%s batch %d data"
                                      % (what, i))


def _kw(path, idx, **over):
    kw = dict(path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
              batch_size=8, shuffle=True, seed=11, dtype="float32",
              host_batches=True, prefetch_buffer=2)
    kw.update(over)
    return kw


def _native_decoder_available():
    from mxnet_tpu import native
    lib = native.get_lib()
    return lib is not None


# ---------------------------------------------------------------------------
# common: seeds, order, shards
# ---------------------------------------------------------------------------

def test_chunk_seed_shared_with_image_py():
    from mxnet_tpu import image
    assert image._chunk_seed is common.chunk_seed
    assert common.chunk_seed(3, 5, epoch=2) == common.chunk_seed(3, 5, 2)
    assert common.chunk_seed(3, 5, 1) != common.chunk_seed(3, 5, 2)


def test_epoch_order_matches_imageiter_shuffle():
    """The service's per-epoch permutation IS ImageIter's: a stateful
    Random(seed) shuffling the (partitioned) key list once per epoch."""
    import random as pyrandom
    keys = list(range(23))
    ref_rng = pyrandom.Random(7)
    ref = list(keys)
    orders = []
    for _ in range(3):
        ref_rng.shuffle(ref)
        orders.append(list(ref))
    o = common.EpochOrder(keys, 7, True)
    for e in range(3):
        assert o.advance() == orders[e]
    # seek replays from scratch — a respawned worker lands mid-run
    o2 = common.EpochOrder(keys, 7, True)
    assert o2.seek(3) == orders[2]
    assert o2.seek(2) == orders[1]   # backwards seek replays too


def test_worker_batches_partition_is_exact():
    order = list(range(37))
    per = [common.worker_batches(order, 8, r, 3) for r in range(3)]
    seen = {}
    for shard in per:
        for gi, keys in shard:
            assert gi not in seen
            seen[gi] = keys
    assert sorted(seen) == list(range(common.num_batches(37, 8)))
    flat = [k for gi in sorted(seen) for k in seen[gi]]
    assert flat == order   # union in global order IS the epoch stream
    assert len(seen[4]) == 5   # padded final batch holds the remainder


def test_worker_batches_strided_partition_is_exact():
    """The network tier's two-level shard: server s of S owns global
    batches i % S == s, its local workers subdivide — the union over
    (server, worker) is exactly the epoch stream for ANY (S, W)."""
    order = list(range(100))
    nb = common.num_batches(100, 8)
    for S, W in ((1, 1), (2, 2), (3, 2), (4, 3)):
        seen = {}
        for s in range(S):
            count = 0
            for w in range(W):
                for gi, keys in common.worker_batches(
                        order, 8, w, W, stream_offset=s,
                        stream_stride=S):
                    assert gi % S == s        # the outer shard
                    assert gi not in seen
                    seen[gi] = keys
                    count += 1
            assert count == common.stream_batches(nb, s, S)
        assert sorted(seen) == list(range(nb)), (S, W)
        flat = [k for gi in sorted(seen) for k in seen[gi]]
        assert flat == order, (S, W)
    # defaults are the single-host assignment, entry for entry
    assert common.worker_batches(order, 8, 1, 3) == \
        common.worker_batches(order, 8, 1, 3, 0, 1)


def test_read_index_matches_indexed_recordio(rec_dataset):
    path, idx = rec_dataset
    pairs = recordio.read_index(idx)
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    assert [k for k, _ in pairs] == r.keys
    assert dict(pairs) == r.idx
    r.close()


def test_read_index_tolerates_extra_columns(tmp_path):
    """Some external im2rec variants append a size column; the parser
    keeps the historical split-based tolerance."""
    p = tmp_path / "wide.idx"
    p.write_text("0\t0\t1234\n1\t640\t999\n\n2\t1280\n")
    assert recordio.read_index(str(p)) == [(0, 0), (1, 640), (2, 1280)]


# ---------------------------------------------------------------------------
# ring
# ---------------------------------------------------------------------------

def test_ring_seqlock_rejects_unpublished_and_stale_slots():
    ring = Ring("mxds-test-%d" % os.getpid(), slots=2, batch_size=2,
                data_shape=(3, 4, 4), label_width=1, itemsize=4,
                create=True)
    try:
        assert not ring.ready(0)
        s = ring.acquire()
        ring.begin_write(s, 0)
        assert not ring.ready(0)   # odd seq: write in progress
        ring.data_view(s, np.float32)[:] = 1.5
        ring.label_view(s)[:] = 7.0
        ring.commit(s, 0, 2, 1)
        assert ring.ready(0) and not ring.ready(1)
        hdr, lab, dat = ring.peek(np.float32)
        assert int(hdr[common.HDR_NVALID]) == 2
        assert float(dat[0, 0, 0, 0]) == 1.5 and float(lab[0, 0]) == 7.0
        ring.release()
        assert not ring.ready(0)   # consumed: same seq is now stale
        # fill the ring: producer must block (acquire via on_wait abort)
        for i in (1, 2):
            s = ring.acquire()
            ring.begin_write(s, i)
            ring.commit(s, i, 2, 1)
        assert ring.occupancy() == 2
        assert ring.acquire(on_wait=lambda: True) is None   # full
    finally:
        ring.close()


def test_ring_stop_and_stall_accounting():
    ring = Ring("mxds-test2-%d" % os.getpid(), slots=2, batch_size=1,
                data_shape=(1,), label_width=1, itemsize=1, create=True)
    try:
        ring.request_stop()
        assert ring.acquire() is None
        assert ring.heartbeat_age_s() < 5.0
    finally:
        ring.close()


# ---------------------------------------------------------------------------
# the service: determinism + parity
# ---------------------------------------------------------------------------

def test_service_stream_identical_any_worker_count(rec_dataset):
    """ORDERING CONTRACT: same seed => the same delivered per-epoch
    record stream for workers=1 vs workers=4, across two epochs."""
    path, idx = rec_dataset
    kw = _kw(path, idx, rand_crop=True, rand_mirror=True)
    it1 = mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                                **kw)
    s1 = _stream(it1, epochs=2)
    it1.close()
    it4 = mx.io.ImageRecordIter(preprocess_threads=4, data_service=True,
                                **kw)
    s4 = _stream(it4, epochs=2)
    it4.close()
    _assert_streams_equal(s1, s4, "w1-vs-w4")


@pytest.mark.skipif(not _native_decoder_available(),
                    reason="needs the native libjpeg decoder on both sides")
def test_service_bit_identical_to_inprocess_pipe_no_augment(rec_dataset):
    """host_batches service output is bit-identical to the in-process
    native pipe for the no-augment path (and the padded final batch
    matches too)."""
    path, idx = rec_dataset
    kw = _kw(path, idx)
    ref_it = mx.io.ImageRecordIter(preprocess_threads=1, **kw)
    ref = _stream(ref_it, epochs=2)
    ref_it.close()
    svc_it = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                   **kw)
    svc = _stream(svc_it, epochs=2)
    svc_it.close()
    _assert_streams_equal(ref, svc, "inproc-vs-service")
    assert ref[-1][2] == 8 - 37 % 8   # padded final batch (5 real rows)


@pytest.mark.skipif(not _native_decoder_available(),
                    reason="needs the native libjpeg decoder on both sides")
def test_service_bit_identical_to_inprocess_pipe_seeded_augment(
        rec_dataset):
    """Augmented parity: the per-global-batch chunk-seed derivation is
    shared, so even rand_crop+rand_mirror output matches the in-process
    pipe bit-for-bit."""
    path, idx = rec_dataset
    kw = _kw(path, idx, rand_crop=True, rand_mirror=True, seed=3)
    ref_it = mx.io.ImageRecordIter(preprocess_threads=1, **kw)
    ref = _stream(ref_it)
    ref_it.close()
    svc_it = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                   **kw)
    svc = _stream(svc_it)
    svc_it.close()
    _assert_streams_equal(ref, svc, "inproc-vs-service-augmented")


def test_service_device_mode_matches_host_mode(rec_dataset):
    """The transparent (device-array) route delivers the same bytes as
    host_batches, and the labels/pads survive the upload."""
    path, idx = rec_dataset
    kw = _kw(path, idx)
    host = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                 **kw)
    hs = _stream(host)
    host.close()
    kw.pop("host_batches")
    dev = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                host_batches=False, **kw)
    ds = _stream(dev)
    dev.close()
    _assert_streams_equal(hs, ds, "host-vs-device")


def test_service_device_arrays_do_not_alias_slots(rec_dataset):
    """REGRESSION: on the CPU backend a plain device_put ALIASES numpy
    memory; if the upload path did that, releasing the ring slot would
    rewrite 'device' arrays of earlier batches once the ring wraps."""
    path, idx = rec_dataset
    kw = _kw(path, idx, shuffle=False)
    kw.pop("host_batches")
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                               host_batches=False, **kw)
    first = it.next()
    snap = first.data[0].asnumpy().copy()
    for _ in range(4):   # > ring slots with default 4: wraps for sure
        try:
            it.next()
        except StopIteration:
            it.reset()
    np.testing.assert_array_equal(first.data[0].asnumpy(), snap)
    it.close()


def test_service_host_views_are_recycled_on_next_pull(rec_dataset):
    """The documented copy=False lifetime contract: a held view is
    rewritten once its slot is recycled (that is WHY it is zero-copy);
    DataServiceIter's default copy=True hands out private arrays."""
    from mxnet_tpu.data_service import DataServiceIter
    path, idx = rec_dataset
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                               **_kw(path, idx, shuffle=False))
    b0 = it.next()
    view = b0.data[0]
    before = view.copy()
    changed = False
    for _ in range(4):
        it.next()
        if not np.array_equal(view, before):
            changed = True
            break
    assert changed, "zero-copy view was never recycled — is the ring " \
                    "copying?"
    it.close()
    # the safe default on the public iterator: private arrays
    svc = DataServiceIter(path_imgrec=path, path_imgidx=idx,
                          data_shape=(3, 32, 32), batch_size=8,
                          num_workers=1, dtype="float32")
    b0 = svc.next()
    keep = b0.data[0]
    snap = keep.copy()
    for _ in range(4):
        svc.next()
    np.testing.assert_array_equal(keep, snap)
    svc.close()


def test_service_uint8_nhwc_layout(rec_dataset):
    path, idx = rec_dataset
    it = mx.io.ImageRecordIter(
        preprocess_threads=2, data_service=True,
        **_kw(path, idx, dtype="uint8", layout="NHWC"))
    b = it.next()
    assert b.data[0].dtype == np.uint8
    assert b.data[0].shape == (8, 32, 32, 3)
    assert it.provide_data[0].shape == (8, 32, 32, 3)
    it.close()


def test_service_stats_surface(rec_dataset):
    path, idx = rec_dataset
    it = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                               **_kw(path, idx))
    _stream(it)
    st = it.stats()
    assert st["num_workers"] == 2
    assert st["batches_produced"] == 5
    assert set(st["workers"]) == {0, 1}
    for w in st["workers"].values():
        assert w["alive"] and w["respawns"] == 0
        assert w["producer_stall_s"] >= 0.0
    it.close()
    # in-process pipelines have no stats surface
    it = mx.io.ImageRecordIter(preprocess_threads=1, **_kw(path, idx))
    assert it.stats() is None
    it.close()


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_env_var_routes_through_service(rec_dataset, monkeypatch):
    path, idx = rec_dataset
    monkeypatch.setenv("MXTPU_DATA_WORKERS", "2")
    it = mx.io.ImageRecordIter(preprocess_threads=1, **_kw(path, idx))
    assert it._service is not None
    assert it._service.num_workers == 2
    it.close()
    # explicit opt-out wins over the env
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=False,
                               **_kw(path, idx))
    assert it._service is None
    it.close()
    # an EXPLICIT data_service=True sizes from the call, not the env
    it = mx.io.ImageRecordIter(preprocess_threads=3, data_service=True,
                               **_kw(path, idx))
    assert it._service.num_workers == 3
    it.close()


def test_env_routing_falls_back_when_ineligible(rec_dataset, monkeypatch,
                                                caplog):
    """MXTPU_DATA_WORKERS on an ineligible config (no .idx) quietly uses
    the in-process pipeline; an EXPLICIT data_service=True raises."""
    path, idx = rec_dataset
    monkeypatch.setenv("MXTPU_DATA_WORKERS", "2")
    kw = _kw(path, idx)
    kw.pop("path_imgidx")
    it = mx.io.ImageRecordIter(preprocess_threads=1, **kw)
    assert it._service is None
    it.close()
    with pytest.raises(mx.MXNetError, match="path_imgidx"):
        mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                              **kw)


def test_non_jpeg_rec_is_ineligible(tmp_path, monkeypatch):
    """A PNG-payload .rec crash-loops libjpeg worker pipes; eligibility
    must catch it up front — env routing falls back to the cv2
    pipelines, explicit data_service=True gets a clear config error."""
    import cv2
    rec = str(tmp_path / "png.rec")
    idx = str(tmp_path / "png.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(9):
        ok, buf = cv2.imencode(".png", _gradient_img(seed=i))
        assert ok
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.tobytes()))
    w.close()
    with pytest.raises(mx.MXNetError, match="JPEG"):
        mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                              **_kw(rec, idx))
    monkeypatch.setenv("MXTPU_DATA_WORKERS", "2")
    kw = _kw(rec, idx)
    kw.pop("host_batches")   # host_batches itself needs the native pipe
    it = mx.io.ImageRecordIter(preprocess_threads=1, **kw)
    assert it._service is None   # fell back, still serves the data
    b = it.next()
    assert b.data[0].shape == (8, 3, 32, 32)
    it.close()


def test_explicit_service_rejects_unsupported_augs(rec_dataset):
    path, idx = rec_dataset
    with pytest.raises(mx.MXNetError, match="augmentations"):
        mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                              brightness=0.4, **_kw(path, idx))


# ---------------------------------------------------------------------------
# robustness (signal-level drills live in tests/test_chaos.py)
# ---------------------------------------------------------------------------

def test_worker_fault_point_respawns_and_stream_intact(rec_dataset,
                                                       clean_faults,
                                                       monkeypatch):
    """MXTPU_FAULTS=data_worker:1 crashes one worker's first batch; the
    respawn (with the fault STRIPPED from the child env) resumes the
    shard and the delivered stream equals the uninterrupted one."""
    path, idx = rec_dataset
    kw = _kw(path, idx, rand_crop=True, rand_mirror=True)
    it = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                               **kw)
    ref = _stream(it)
    it.close()
    monkeypatch.setenv("MXTPU_FAULTS", "data_worker:1")
    it = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                               **kw)
    got = _stream(it)
    st = it.stats()
    it.close()
    assert sum(w["respawns"] for w in st["workers"].values()) >= 1, st
    _assert_streams_equal(ref, got, "fault-respawn")


def test_worker_respawn_budget_exhausts(rec_dataset, clean_faults,
                                        monkeypatch, tmp_path):
    """A worker that dies on EVERY attempt (fault armed for more firings
    than the budget, so stripping doesn't save it... it would — so use a
    dataset-level poison instead: truncate the .rec) surfaces as an
    MXNetError naming the worker, instead of respawning forever."""
    import shutil
    path, idx = rec_dataset
    bad_rec = str(tmp_path / "bad.rec")
    bad_idx = str(tmp_path / "bad.idx")
    shutil.copy(idx, bad_idx)
    with open(path, "rb") as f:
        blob = f.read()
    with open(bad_rec, "wb") as f:   # truncated: reads past EOF fail
        f.write(blob[:200])
    with pytest.raises(mx.MXNetError, match="respawn budget"):
        it = mx.io.ImageRecordIter(
            preprocess_threads=1, data_service=True,
            **_kw(bad_rec, bad_idx))
        _stream(it)


def test_strip_faults_env():
    from mxnet_tpu.resilience import strip_faults_env
    assert strip_faults_env("data_worker:1,ckpt_write:2@1",
                            ("data_worker", "hang_data_worker")) \
        == "ckpt_write:2@1"
    assert strip_faults_env("hang_data_worker:1", ("hang_data_worker",)) \
        == ""
    assert strip_faults_env(None, ("x",)) == ""
    assert strip_faults_env(" a:1 , b:2 ", ("c",)) == "a:1,b:2"


# ---------------------------------------------------------------------------
# composition with DevicePrefetchIter (the device-staging path)
# ---------------------------------------------------------------------------

def test_service_composes_with_device_prefetch(rec_dataset):
    """DataServiceIter(copy=False) -> DevicePrefetchIter round-trips the
    stream UNCORRUPTED: the prefetcher runs ahead of the consumer, so
    it must SNAPSHOT slot-backed batches on its background thread and
    release the slot — queued batches referencing live ring views would
    be rewritten once the (deliberately tiny, slots=2) ring wraps."""
    from mxnet_tpu.data_service import DataServiceIter
    from mxnet_tpu.dataflow import DevicePrefetchIter
    path, idx = rec_dataset
    svc = DataServiceIter(path_imgrec=path, path_imgidx=idx,
                          data_shape=(3, 32, 32), batch_size=8,
                          num_workers=2, shuffle=True, seed=11,
                          dtype="float32", copy=False, slots=2)
    direct = DataServiceIter(path_imgrec=path, path_imgidx=idx,
                             data_shape=(3, 32, 32), batch_size=8,
                             num_workers=1, shuffle=True, seed=11,
                             dtype="float32")
    pf = DevicePrefetchIter(svc, stage=None, depth=2)
    batches = list(pf)           # pull everything: max pull-ahead churn
    got = [(np.array(b.data[0]).copy(), np.array(b.label[0]).copy(),
            b.pad) for b in batches]
    ref = _stream(direct)
    _assert_streams_equal(ref, got, "prefetch-composition")
    pf.close()
    svc.close()
    direct.close()


def test_databatch_release_default_noop_and_dataiter_close():
    b = mx.io.DataBatch([np.zeros(3)])
    b.release()
    b.release()   # idempotent no-op
    it = mx.io.NDArrayIter(np.zeros((4, 2)), batch_size=2)
    it.close()    # base-class no-op exists for generic consumers


# ---------------------------------------------------------------------------
# chunk_seed / EpochOrder stability across process boundaries — the
# contract the network tier rides on: the epoch permutation, the batch
# ownership and the augmentation seeds are pure functions of
# (keys, seed, epoch), so a server process on ANOTHER host computes
# byte-identical plans from nothing but the config.
# ---------------------------------------------------------------------------

_XPROC_PROG = """
import json, sys
sys.path.insert(0, %r)
from mxnet_tpu.data_service import common
cfg = json.loads(sys.stdin.read())
keys = cfg["keys"]
out = {"orders": {}, "shards": {}, "seeds": {}}
o = common.EpochOrder(keys, cfg["seed"], True)
for epoch in (1, 2, 3):
    order = o.seek(epoch)
    out["orders"][str(epoch)] = list(order)
    shard = {}
    for s in range(cfg["S"]):
        for w in range(cfg["W"]):
            for g, ks in common.worker_batches(
                    order, cfg["bs"], w, cfg["W"], s, cfg["S"]):
                shard[str(g)] = {"server": s, "worker": w, "keys": ks}
    out["shards"][str(epoch)] = shard
    out["seeds"][str(epoch)] = [
        common.chunk_seed(cfg["seed"], g, epoch=epoch)
        for g in range(len(shard))]
print(json.dumps(out, sort_keys=True))
"""


def test_epoch_order_and_chunk_seeds_identical_across_processes():
    """Serialize nothing but the CONFIG to another "host" (a fresh
    python process importing only the jax-free common module) and
    replay: epoch orders, per-(server, worker) batch ownership and
    per-batch augmentation seeds must be byte-identical to this
    process's — the determinism theorem the network tier's exactly-once
    reconnect resume depends on."""
    cfg = {"keys": list(range(53)), "seed": 17, "bs": 8, "S": 3, "W": 2}
    # silence the synthetic path difference: run the SAME program here
    # and there, compare the JSON byte-for-byte
    prog = _XPROC_PROG % (REPO,)
    res = subprocess.run([sys.executable, "-c", prog],
                         input=json.dumps(cfg), capture_output=True,
                         text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    remote = res.stdout.strip()

    out = {"orders": {}, "shards": {}, "seeds": {}}
    o = common.EpochOrder(cfg["keys"], cfg["seed"], True)
    for epoch in (1, 2, 3):
        order = o.seek(epoch)
        out["orders"][str(epoch)] = list(order)
        shard = {}
        for s in range(cfg["S"]):
            for w in range(cfg["W"]):
                for g, ks in common.worker_batches(
                        order, cfg["bs"], w, cfg["W"], s, cfg["S"]):
                    shard[str(g)] = {"server": s, "worker": w, "keys": ks}
        out["shards"][str(epoch)] = shard
        out["seeds"][str(epoch)] = [
            common.chunk_seed(cfg["seed"], g, epoch=epoch)
            for g in range(len(shard))]
    local = json.dumps(out, sort_keys=True)
    assert local == remote


# ---------------------------------------------------------------------------
# recordio readahead (the io_uring-style posix_fadvise window)
# ---------------------------------------------------------------------------

def test_read_plan_readahead_advises_and_reads_correctly(rec_dataset):
    path, idx = rec_dataset
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    plain = {k: r.read_idx(k) for k in r.keys}
    r.close()
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    order = list(reversed(r.keys))          # a shuffled-ish plan
    r.set_read_plan(order, window=8)
    got = {k: r.read_idx(k) for k in order}
    if hasattr(os, "posix_fadvise"):
        assert r.readahead_advised > 0
    r.close()
    assert got == plain                     # advice never changes bytes


def test_read_plan_off_plan_reads_resync(rec_dataset):
    """A read that deviates from the plan (respawn resume, random
    access) must stay correct — the plan resynchronizes or quietly
    disables, never misreads."""
    path, idx = rec_dataset
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    r.set_read_plan(r.keys, window=4)
    a = r.read_idx(r.keys[0])
    b = r.read_idx(r.keys[10])   # skipped 9 plan entries
    c = r.read_idx(r.keys[11])
    r2 = recordio.MXIndexedRecordIO(idx, path, "r")
    assert a == r2.read_idx(r2.keys[0])
    assert b == r2.read_idx(r2.keys[10])
    assert c == r2.read_idx(r2.keys[11])
    r.close()
    r2.close()


def test_read_plan_survives_reset(rec_dataset):
    """reset() (close + open) while a plan is live must not leave the
    plan advising through a closed fd — the next planned read stays a
    plain correct read."""
    path, idx = rec_dataset
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    r.set_read_plan(r.keys, window=4)
    a = r.read_idx(r.keys[0])
    r.reset()
    b = r.read_idx(r.keys[0])    # plan cleared with its fd: plain read
    assert a == b
    r.close()


def test_read_plan_window_zero_disables(rec_dataset, monkeypatch):
    monkeypatch.setenv("MXTPU_DATA_READAHEAD", "0")
    path, idx = rec_dataset
    r = recordio.MXIndexedRecordIO(idx, path, "r")
    r.set_read_plan(r.keys)      # window from env: 0 = off
    r.read_idx(r.keys[0])
    assert r.readahead_advised == 0
    r.close()


# ---------------------------------------------------------------------------
# the network tier (data_service/net.py + tools/data_server.py)
# ---------------------------------------------------------------------------

from conftest import spawn_data_server as _spawn_data_server  # noqa: E402


@pytest.fixture()
def data_servers(rec_dataset, tmp_path):
    """Two loopback tools/data_server.py processes."""
    procs, addrs = [], []
    for n in range(2):
        p, a = _spawn_data_server(tmp_path, n)
        procs.append(p)
        addrs.append(a)
    yield ",".join(addrs)
    for p in procs:
        p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


def test_net_tier_bit_identical_to_local_service(rec_dataset,
                                                 data_servers):
    """THE network-tier contract: a 2-server stream (1 decode worker
    each) is bit-identical to the local in-process service — augmented,
    across two epochs, padded final batch included.  (The local service
    is itself pinned bit-identical to the in-process pipe above, so
    transitively all three transports agree.)"""
    path, idx = rec_dataset
    kw = _kw(path, idx, rand_crop=True, rand_mirror=True)
    loc = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                **kw)
    ref = _stream(loc, epochs=2)
    loc.close()
    net = mx.io.ImageRecordIter(preprocess_threads=1,
                                data_service=data_servers, **kw)
    got = _stream(net, epochs=2)
    st = net.stats()
    net.close()
    _assert_streams_equal(ref, got, "local-vs-net")
    assert ref[-1][2] == 8 - 37 % 8   # padded final batch survived TCP
    assert st["num_servers"] == 2
    assert all(s["alive"] for s in st["servers"].values())
    assert all(s["reconnects"] == 0 for s in st["servers"].values())


def test_net_tier_single_server_and_device_mode(rec_dataset, tmp_path):
    """A 1-server stream matches the 2-server stream (any-server-count
    identity), and the transparent device-array route delivers the
    same bytes as host_batches over the network."""
    path, idx = rec_dataset
    proc, addr = _spawn_data_server(tmp_path, 9)
    try:
        kw = _kw(path, idx)
        host = mx.io.ImageRecordIter(preprocess_threads=2,
                                     data_service=addr, **kw)
        hs = _stream(host)
        host.close()
        kw2 = _kw(path, idx)
        kw2.pop("host_batches")
        dev = mx.io.ImageRecordIter(preprocess_threads=1,
                                    data_service=addr,
                                    host_batches=False, **kw2)
        ds = _stream(dev)
        dev.close()
        _assert_streams_equal(hs, ds, "net-host-vs-device")
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_net_seek_resumes_mid_epoch_bit_identical(rec_dataset,
                                                  data_servers):
    """`NetDataService.seek(epoch, consumed)` honors the DataService
    collector surface: a fresh consumer seeking to (epoch, K) streams
    exactly the reference's tail — the same machinery a reconnect uses,
    exposed for resume-at-batch consumers."""
    from mxnet_tpu.data_service import DataServiceIter
    from mxnet_tpu.data_service.net import NetDataService

    def svc():
        return NetDataService(data_servers, *rec_dataset, (3, 32, 32), 8,
                              shuffle=True, seed=11)

    ref_it = DataServiceIter(svc())
    ref = [(np.array(b.data[0]).copy(), np.array(b.label[0]).copy(),
            b.pad) for b in ref_it]
    ref_it.close()

    resumed = svc()
    resumed.seek(1, 2)              # first 2 global batches consumed
    it = DataServiceIter(resumed)
    got = [(np.array(b.data[0]).copy(), np.array(b.label[0]).copy(),
            b.pad) for b in it]
    st = resumed.stats()
    it.close()
    _assert_streams_equal(ref[2:], got, "seek-resume")
    # the resume is WARM: pre-seek frames already in flight are
    # discarded in-band (same-epoch, behind the cursor), never treated
    # as a protocol violation that evicts the connection
    assert all(s["reconnects"] == 0 for s in st["servers"].values()), st


def test_net_env_var_routes_and_false_opts_out(rec_dataset, data_servers,
                                               monkeypatch):
    from mxnet_tpu.data_service.net import NetDataService
    path, idx = rec_dataset
    monkeypatch.setenv("MXTPU_DATA_SERVERS", data_servers)
    it = mx.io.ImageRecordIter(preprocess_threads=1, **_kw(path, idx))
    assert isinstance(it._service, NetDataService)
    it.close()
    # explicit opt-out wins over the env
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=False,
                               **_kw(path, idx))
    assert it._service is None
    it.close()
    # explicit data_service=True keeps the LOCAL service even when the
    # env names servers (a call site that opted into local stays local)
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                               **_kw(path, idx))
    assert not isinstance(it._service, NetDataService)
    assert it._service is not None
    it.close()


def test_data_service_truthy_and_list_forms_route(rec_dataset,
                                                  data_servers):
    """Routing accepts the historical truthy form (data_service=1 ==
    the local service — it must not silently fall through to the
    in-process pipeline) and a list of addresses for the net tier."""
    from mxnet_tpu.data_service.net import NetDataService
    path, idx = rec_dataset
    it = mx.io.ImageRecordIter(preprocess_threads=2, data_service=1,
                               **_kw(path, idx))
    assert it._service is not None
    assert not isinstance(it._service, NetDataService)
    assert it._service.num_workers == 2
    it.close()
    it = mx.io.ImageRecordIter(preprocess_threads=1,
                               data_service=data_servers.split(","),
                               **_kw(path, idx))
    assert isinstance(it._service, NetDataService)
    it.close()


def test_net_tier_rejects_bad_server_and_bad_config(rec_dataset,
                                                    tmp_path):
    """An unreachable server exhausts the reconnect budget with a clear
    error; a server-side dataset problem surfaces as the handshake
    rejection, not a crash loop."""
    from mxnet_tpu.data_service.net import NetDataService
    path, idx = rec_dataset
    with pytest.raises(mx.MXNetError, match="unreachable"):
        NetDataService("127.0.0.1:1", path, idx, (3, 32, 32), 8,
                       retries=2, reconnect_s=0.05)
    proc, addr = _spawn_data_server(tmp_path, 8)
    try:
        with pytest.raises(mx.MXNetError, match="rejected"):
            NetDataService(addr, "/nonexistent/x.rec",
                           "/nonexistent/x.idx", (3, 32, 32), 8)
    finally:
        proc.terminate()
        proc.wait(timeout=10)


def test_data_server_cli_never_imports_jax(rec_dataset, tmp_path):
    """The server process (and its decode workers) must stay jax-free —
    an XLA client on every decode host would burn seconds + hundreds of
    MB per server and fight a co-tenant trainer for the chip.  Poisoned-
    jax proof, the mxlint/fleet CLI idiom: the server decodes and
    streams a REAL epoch with `import jax` booby-trapped, which would
    crash it (and its workers) on the spot."""
    poison = tmp_path / "jax"
    poison.mkdir()
    (poison / "__init__.py").write_text(
        "raise ImportError('data server must not import jax')")
    env = {"PYTHONPATH": str(tmp_path) + os.pathsep
           + os.environ.get("PYTHONPATH", "")}
    proc, addr = _spawn_data_server(tmp_path, 7, extra_env=env)
    try:
        from mxnet_tpu.data_service import DataServiceIter
        from mxnet_tpu.data_service.net import NetDataService
        path, idx = rec_dataset
        svc = NetDataService(addr, path, idx, (3, 32, 32), 8,
                             shuffle=True, seed=11, retries=2)
        it = DataServiceIter(svc)
        n = sum(1 for _ in it)
        it.close()
        assert n == 5                   # full epoch streamed jax-free
        assert proc.poll() is None      # server survived the epoch
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# in-graph (device) augmentation — kernels/augment.py behind the
# MXTPU_FUSED_KERNELS 'augment' seam
# ---------------------------------------------------------------------------

def test_augment_kernel_registered_in_router():
    from mxnet_tpu import kernels
    assert "augment" in kernels.KNOWN_KERNELS
    assert kernels.fused_enabled("augment")   # default "1" = all on


def test_device_augment_reproducible_across_worker_counts(rec_dataset):
    """The acceptance contract: the device-augmented pipeline is a pure
    function of (seed, epoch, batch) — identical streams for w=1 vs
    w=4 across two epochs, final shapes/dtype as requested, pad rows
    exact zeros."""
    path, idx = rec_dataset
    kw = dict(path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
              batch_size=8, shuffle=True, seed=11, dtype="float32",
              rand_crop=True, rand_mirror=True, mean=True, std=True)

    def dev_stream(workers):
        it = mx.io.ImageRecordIter(preprocess_threads=workers,
                                   data_service=True,
                                   device_augment=True, **kw)
        assert it.provide_data[0].shape == (8, 3, 32, 32)
        out = _stream(it, epochs=2)
        it.close()
        return out

    s1 = dev_stream(1)
    s4 = dev_stream(4)
    _assert_streams_equal(s1, s4, "device-aug w1-vs-w4")
    pad = s1[4][2]
    assert pad == 8 - 37 % 8
    np.testing.assert_array_equal(s1[4][0][-pad:], 0)   # pad rows zeroed


def test_device_augment_seam_off_restores_exact_host_path(rec_dataset,
                                                          monkeypatch):
    """MXTPU_FUSED_KERNELS=0 + device_augment falls back to the EXACT
    host-augmented graph (bitwise equal to a plain service run), and
    with the seam ON the device product provably differs (the kernel
    actually engaged)."""
    path, idx = rec_dataset
    kw = dict(path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
              batch_size=8, shuffle=True, seed=11, dtype="float32",
              rand_crop=True, rand_mirror=True)
    host = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                 **kw)
    ref = _stream(host)
    host.close()
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    off = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                                device_augment=True, **kw)
    assert off._dev_aug is None
    got = _stream(off)
    off.close()
    _assert_streams_equal(ref, got, "seam-off-vs-host")
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    on = mx.io.ImageRecordIter(preprocess_threads=2, data_service=True,
                               device_augment=True, **kw)
    dev = _stream(on)
    on.close()
    assert any(not np.array_equal(a[0], b[0])
               for a, b in zip(ref, dev))   # provably engaged


def test_device_augment_requires_service_and_rejects_host_batches(
        rec_dataset):
    path, idx = rec_dataset
    with pytest.raises(mx.MXNetError, match="device_augment"):
        mx.io.ImageRecordIter(preprocess_threads=1, device_augment=True,
                              **_kw(path, idx))
    with pytest.raises(mx.MXNetError, match="host_batches"):
        mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                              device_augment=True, **_kw(path, idx))


def test_device_augment_zero_margin_engages_and_false_opts_out(
        rec_dataset):
    """device_augment=0 is a REAL margin (center crop + on-device
    mirror/normalize), not a falsy 'off' — only None/False disable."""
    path, idx = rec_dataset
    kw = dict(path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
              batch_size=8, shuffle=False, seed=11, dtype="float32",
              rand_mirror=True)
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                               device_augment=0, **kw)
    assert it._dev_aug is not None and it._dev_aug.margin == 0
    b = it.next()
    assert b.data[0].shape == (8, 3, 32, 32)
    it.close()
    it = mx.io.ImageRecordIter(preprocess_threads=1, data_service=True,
                               device_augment=False, **kw)
    assert it._dev_aug is None
    it.close()


def test_device_augment_kernel_unit_geometry():
    """The traced op itself: center crop with margin 0 passes pixels
    through; a mismatched canvas goes through the jax.image resize
    path; per-image RNG makes rows differ under rand_crop."""
    from mxnet_tpu.kernels.augment import DeviceAugment
    rs = np.random.RandomState(0)
    # identity: margin 0, no aug, float pass-through
    aug = DeviceAugment((3, 8, 8), margin=0, layout="NCHW")
    x = rs.randint(0, 255, (4, 3, 8, 8)).astype(np.uint8)
    y = np.asarray(aug(x, cseed=7, nvalid=4))
    np.testing.assert_array_equal(y, x.astype(np.float32))
    # resize path: canvas 16x16 -> (8+0)x(8+0) via jax.image
    y2 = np.asarray(aug(rs.randint(0, 255, (4, 3, 16, 16))
                        .astype(np.uint8), cseed=7, nvalid=4))
    assert y2.shape == (4, 3, 8, 8)
    # random crop: same cseed reproduces, different cseed differs
    aug_rc = DeviceAugment((3, 8, 8), margin=4, rand_crop=True,
                           rand_mirror=True, layout="NCHW")
    big = rs.randint(0, 255, (4, 3, 12, 12)).astype(np.uint8)
    a = np.asarray(aug_rc(big, cseed=5, nvalid=4))
    b = np.asarray(aug_rc(big, cseed=5, nvalid=4))
    c = np.asarray(aug_rc(big, cseed=6, nvalid=4))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # NHWC layout round-trips shapes
    aug_nhwc = DeviceAugment((3, 8, 8), margin=4, rand_crop=True,
                             layout="NHWC")
    z = np.asarray(aug_nhwc(rs.randint(0, 255, (2, 12, 12, 3))
                            .astype(np.uint8), cseed=1, nvalid=2))
    assert z.shape == (2, 8, 8, 3)
