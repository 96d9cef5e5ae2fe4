"""Fault-tolerant training runtime: atomic CheckpointManager + auto-resume,
the fused step's NaN/Inf guard, retry/backoff bring-up, and the
deterministic fault-injection points that exercise all of it on CPU."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import (CheckpointManager, FaultInjector,
                                  TransientError, atomic_write, retry)

pytestmark = pytest.mark.resilience


def make_blobs(n, d, c, seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.randn(c, d) * 3
    X = np.concatenate([centers[i] + rs.randn(n // c, d)
                        for i in range(c)]).astype("f")
    y = np.concatenate([np.full(n // c, i) for i in range(c)]).astype("f")
    perm = rs.permutation(len(X))
    return X[perm], y[perm]


def mlp_sym(num_classes=3, nh=16):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


# ---------------------------------------------------------------------------
# retry helper (fake clock — zero real sleeping)
# ---------------------------------------------------------------------------

def test_retry_succeeds_after_transient_failures():
    sleeps = []
    attempts = []

    def flaky():
        attempts.append(1)
        if len(attempts) < 3:
            raise TransientError("not yet")
        return 42

    assert retry(flaky, attempts=5, backoff=0.5,
                 sleep=sleeps.append, clock=lambda: 0.0) == 42
    assert sleeps == [0.5, 1.0]  # exponential backoff, no real sleep


def test_retry_exhaustion_raises_mxnet_error():
    def always(): raise TransientError("down")
    with pytest.raises(MXNetError, match="all 2 attempts"):
        retry(always, attempts=2, backoff=0.1,
              sleep=lambda s: None, clock=lambda: 0.0)


def test_retry_timeout_bounds_total_wall_time():
    now = {"t": 0.0}
    sleeps = []

    def sleep(s):
        sleeps.append(s)
        now["t"] += s

    calls = []

    def always():
        calls.append(1)
        raise TransientError("down")

    with pytest.raises(MXNetError):
        retry(always, attempts=10, backoff=4.0, timeout=10.0,
              sleep=sleep, clock=lambda: now["t"])
    # deadline cuts the ladder well short of 10 attempts, and the final
    # wait is clamped to the time remaining
    assert calls == [1, 1, 1]
    assert sleeps == [4.0, 6.0]


def test_retry_does_not_catch_unlisted_exceptions():
    def bug(): raise ValueError("programming error")
    with pytest.raises(ValueError):
        retry(bug, attempts=5, sleep=lambda s: None)


# ---------------------------------------------------------------------------
# fault injection
# ---------------------------------------------------------------------------

def test_fault_injector_env_arming(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULTS", "iter_next:2, checkpoint_write")
    fi = FaultInjector()
    assert fi.is_armed("iter_next") and fi.is_armed("checkpoint_write")
    with pytest.raises(TransientError):
        fi.maybe_fail("checkpoint_write")
    assert not fi.is_armed("checkpoint_write")
    assert fi.consume("iter_next") and fi.consume("iter_next")
    assert not fi.consume("iter_next")


# ---------------------------------------------------------------------------
# atomic writes + CheckpointManager
# ---------------------------------------------------------------------------

def test_atomic_write_replaces_not_tears(tmp_path, clean_faults):
    target = tmp_path / "f.json"
    atomic_write(str(target), "old")
    clean_faults.arm("checkpoint_write")
    with pytest.raises(TransientError):
        atomic_write(str(target), "new")
    assert target.read_text() == "old"
    assert list(tmp_path.iterdir()) == [target]  # temp cleaned up


def test_checkpoint_crash_mid_write_keeps_previous(tmp_path, clean_faults):
    man = CheckpointManager(str(tmp_path), keep_last=3)
    man.save(1, mlp_sym(), {"w": mx.nd.array(np.ones((3, 2), "f"))}, {})
    assert man.latest() == 1
    old_bytes = (tmp_path / "checkpoint-0001.params").read_bytes()

    clean_faults.arm("checkpoint_write")
    with pytest.raises(TransientError):
        man.save(2, None, {"w": mx.nd.array(np.full((3, 2), 7, "f"))}, {})
    # the kill-during-checkpoint run: previous checkpoint byte-for-byte
    # intact, still discoverable, still loadable
    assert (tmp_path / "checkpoint-0001.params").read_bytes() == old_bytes
    assert not (tmp_path / "checkpoint-0002.params").exists()
    assert man.latest() == 1
    sym, args, auxs, states, epoch = man.restore()
    assert epoch == 1 and sym is not None and states is None
    assert np.allclose(args["w"].asnumpy(), 1.0)

    # the relaunched run saves the same epoch cleanly
    man.save(2, None, {"w": mx.nd.array(np.full((3, 2), 7, "f"))}, {})
    assert man.latest() == 2
    _, args2, _, _, _ = man.restore()
    assert np.allclose(args2["w"].asnumpy(), 7.0)


def test_checkpoint_retention_keep_last(tmp_path):
    man = CheckpointManager(str(tmp_path), keep_last=2)
    for epoch in range(1, 5):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {},
                 optimizer_states=b"state-%d" % epoch)
    assert man.checkpoints() == [3, 4]
    assert not (tmp_path / "checkpoint-0001.params").exists()
    assert not (tmp_path / "checkpoint-0002.states").exists()
    _, args, _, states, epoch = man.restore()
    assert epoch == 4 and states == b"state-4"
    assert np.allclose(args["w"].asnumpy(), 4.0)


def test_manifest_corruption_falls_back_to_directory_scan(tmp_path):
    """A corrupt manifest.json (torn by a dying disk / non-atomic copy)
    must not make the directory look empty: latest() recovers the intact
    params files by scanning."""
    man = CheckpointManager(str(tmp_path), keep_last=5)
    for epoch in (1, 2):
        man.save(epoch, mlp_sym(),
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {},
                 optimizer_states=b"state-%d" % epoch)
    # truncate the newest manifest mid-JSON
    mpath = tmp_path / "manifest.json"
    mpath.write_bytes(mpath.read_bytes()[: len(mpath.read_bytes()) // 2])
    man2 = CheckpointManager(str(tmp_path))
    assert man2.checkpoints() == [1, 2]
    # the first fallback read repaired the manifest in place (atomic),
    # so later reads don't rescan-and-warn forever
    assert [e["epoch"] for e in
            json.loads(mpath.read_text())["checkpoints"]] == [1, 2]
    assert man2.latest() == 2
    _, args, _, states, epoch = man2.restore()
    assert epoch == 2 and states == b"state-2"
    assert np.allclose(args["w"].asnumpy(), 2.0)
    # the next save rewrites a healthy manifest
    man2.save(3, None, {"w": mx.nd.array(np.full((2,), 3, "f"))}, {})
    assert json.loads(mpath.read_text())["checkpoints"][-1]["epoch"] == 3


def test_restore_walks_back_past_corrupt_params(tmp_path):
    """Bit rot in the NEWEST checkpoint's params file degrades restore()
    by one epoch (with a warning) instead of killing the resume."""
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2, 3):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {})
    # truncate epoch 3's params to half its bytes
    p3 = tmp_path / "checkpoint-0003.params"
    p3.write_bytes(p3.read_bytes()[: len(p3.read_bytes()) // 2])
    _, args, _, _, epoch = man.restore()
    assert epoch == 2
    assert np.allclose(args["w"].asnumpy(), 2.0)
    # an explicitly requested corrupt epoch still raises (the caller
    # asked for THAT checkpoint; silently substituting would be worse)
    with pytest.raises(Exception):
        man.restore(3)


def test_restore_raises_when_everything_is_corrupt(tmp_path):
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {})
    p1 = tmp_path / "checkpoint-0001.params"
    p1.write_bytes(b"\x00" * 16)
    with pytest.raises(MXNetError, match="unreadable"):
        man.restore()


def test_step_state_round_trip_and_replacement(tmp_path):
    """step_state (mid-epoch metadata) rides the manifest entry and is
    dropped when the complete epoch-end save of the same number lands."""
    man = CheckpointManager(str(tmp_path))
    st = {"epoch": 1, "step": 3, "rng": {"key": [0, 7], "seed": 21}}
    man.save(2, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {},
             step_state=st)
    entry = man.latest_entry()
    assert entry["epoch"] == 2 and entry["step_state"] == st
    man.save(2, None, {"w": mx.nd.array(np.full((2,), 5, "f"))}, {})
    entry = man.latest_entry()
    assert entry["epoch"] == 2 and "step_state" not in entry


def test_do_checkpoint_accepts_manager(tmp_path):
    man = CheckpointManager(str(tmp_path), keep_last=2)
    cb = mx.callback.do_checkpoint(man, period=2)
    sym = mlp_sym()
    for iter_no in range(4):
        cb(iter_no, sym, {"w": mx.nd.array(np.full((2,), iter_no, "f"))}, {})
    assert man.checkpoints() == [2, 4]


def test_kvstore_optimizer_states_atomic(tmp_path, clean_faults):
    kv = mx.kv.create("local")
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.1,
                                         momentum=0.9))
    w = mx.nd.array(np.ones((4, 3), "f"))
    kv.init(0, w)
    kv.push(0, [mx.nd.array(np.full((4, 3), 0.5, "f"))])
    fname = str(tmp_path / "opt.states")
    kv.save_optimizer_states(fname)
    old_bytes = (tmp_path / "opt.states").read_bytes()

    kv.push(0, [mx.nd.array(np.full((4, 3), 0.25, "f"))])
    clean_faults.arm("checkpoint_write")
    with pytest.raises(TransientError):
        kv.save_optimizer_states(fname)
    # a torn/partial write is impossible: the old file survives whole
    assert (tmp_path / "opt.states").read_bytes() == old_bytes
    kv.load_optimizer_states(fname)  # and still loads


# ---------------------------------------------------------------------------
# NaN/Inf step guard
# ---------------------------------------------------------------------------

def _fused_module(X, y, batch=32, seed=11):
    it = mx.io.NDArrayIter(X, y, batch_size=batch)
    mod = mx.mod.Module(mlp_sym())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(seed)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "momentum": 0.9})
    assert mod._fused is not None, "fused path did not engage"
    return mod, it


def test_step_guard_skips_poisoned_batch_params_unchanged(clean_faults):
    X, y = make_blobs(128, 10, 3)
    mod, it = _fused_module(X, y)
    batch = next(iter(it))
    before = {k: v.asnumpy().copy() for k, v in mod.get_params()[0].items()}

    clean_faults.arm("poison_grad")
    mod.forward_backward(batch)
    mod.update()
    after = mod.get_params()[0]
    for name, old in before.items():
        assert np.array_equal(old, after[name].asnumpy()), \
            "guard leaked a non-finite update into %s" % name
    assert mod.skipped_update_count == 1
    assert mod._fused.consecutive_bad_steps == 1

    # the very next (clean) batch trains normally
    mod.forward_backward(batch)
    mod.update()
    newer = mod.get_params()[0]
    assert any(not np.array_equal(before[k], newer[k].asnumpy())
               for k in before)
    assert mod.skipped_update_count == 1
    assert mod._fused.consecutive_bad_steps == 0


def test_training_converges_after_poisoned_batch(clean_faults):
    mx.random.seed(106)
    X, y = make_blobs(512, 10, 3)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(mlp_sym())
    clean_faults.arm("poison_grad")  # poisons the first step's batch
    mod.fit(it, num_epoch=6, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod.skipped_update_count == 1
    acc = dict(mod.score(mx.io.NDArrayIter(X, y, batch_size=64), "acc"))
    assert acc["accuracy"] > 0.9, acc


def test_step_guard_aborts_after_max_consecutive_bad_steps(clean_faults):
    from mxnet_tpu.parallel import SPMDTrainer
    trainer = SPMDTrainer(mlp_sym(), "sgd",
                          {"learning_rate": 0.1, "rescale_grad": 1.0 / 16},
                          max_consecutive_bad_steps=2)
    trainer.bind([("data", (16, 10))], [("softmax_label", (16,))])
    mx.random.seed(3)
    trainer.init_params(mx.initializer.Xavier())
    X = np.random.RandomState(0).randn(16, 10).astype("f")
    y = np.zeros((16,), "f")

    clean_faults.arm("poison_grad", times=2)
    trainer.step(X, y)  # skip 1: guarded
    assert trainer.skipped_steps == 1  # counter read flushes the flag
    trainer.step(X, y)  # skip 2: flag read is pipelined one step late ...
    with pytest.raises(MXNetError, match="consecutive"):
        trainer.flush_step_guard()  # ... and aborts when accounted
    assert trainer._skipped_steps == 2


def test_step_guard_counter_surfaces_in_metric_and_monitor(clean_faults):
    X, y = make_blobs(64, 10, 3)
    mod, it = _fused_module(X, y)
    skipped = mx.metric.SkippedSteps(mod)
    assert skipped.get() == ("skipped_steps", 0.0)

    clean_faults.arm("poison_grad")
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    assert skipped.get() == ("skipped_steps", 1.0)

    mon = mx.mon.Monitor(1)
    mon.install_step_guard(mod)
    mon.tic()
    rows = {k: v for _, k, v in mon.toc()}
    assert rows["step_guard_skipped"] == str(1.0)
    assert rows["step_guard_consecutive_bad"] == str(1.0)


def test_poisoned_step_does_not_contaminate_metric(clean_faults):
    X, y = make_blobs(64, 10, 3)
    mod, it = _fused_module(X, y)
    batch = next(iter(it))
    metric = mx.metric.CrossEntropy()

    clean_faults.arm("poison_grad")
    mod.forward_backward(batch)
    mod.update()
    mod.update_metric(metric, batch.label)
    # the skipped step's NaN outputs contributed nothing to the sum
    assert metric.num_inst == 0

    mod.forward_backward(batch)
    mod.update()
    mod.update_metric(metric, batch.label)
    assert metric.num_inst > 0
    assert np.isfinite(metric.get()[1]), metric.get()


def test_step_guard_can_be_disabled():
    from mxnet_tpu.parallel import SPMDTrainer
    trainer = SPMDTrainer(mlp_sym(), "sgd",
                          {"learning_rate": 0.1, "rescale_grad": 1.0 / 16},
                          step_guard=False)
    trainer.bind([("data", (16, 10))], [("softmax_label", (16,))])
    mx.random.seed(3)
    trainer.init_params(mx.initializer.Xavier())
    X = np.random.RandomState(0).randn(16, 10).astype("f")
    trainer.step(X, np.zeros((16,), "f"))
    assert trainer.skipped_steps == 0


# ---------------------------------------------------------------------------
# auto-resume
# ---------------------------------------------------------------------------

def _fit_params(tmp_dir, kvstore, epochs, resume=False, seed=21):
    X, y = make_blobs(256, 10, 3, seed=4)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(mlp_sym())
    mx.random.seed(seed)
    mod.fit(it, num_epoch=epochs, kvstore=kvstore, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            initializer=mx.initializer.Xavier(),
            checkpoint=tmp_dir, resume=resume)
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


@pytest.mark.parametrize("kvstore", ["local", "tpu"])
def test_fit_resume_matches_uninterrupted_run(tmp_path, kvstore):
    full = _fit_params(str(tmp_path / "full"), kvstore, epochs=4)
    # "preempted" run: 2 epochs, then a fresh module resumes to 4
    _fit_params(str(tmp_path / "cut"), kvstore, epochs=2)
    man = CheckpointManager(str(tmp_path / "cut"))
    assert man.latest() == 2
    resumed = _fit_params(str(tmp_path / "cut"), kvstore, epochs=4,
                          resume=True)
    for name in full:
        np.testing.assert_allclose(resumed[name], full[name], rtol=2e-5,
                                   atol=2e-6, err_msg=name)
    # resumed run checkpointed epochs 3 and 4 on top
    assert man.latest() == 4


def test_fit_resume_with_empty_dir_starts_fresh(tmp_path):
    params = _fit_params(str(tmp_path / "fresh"), "local", epochs=2,
                         resume=True)
    assert params  # no checkpoint existed: trains from scratch, no error
    assert CheckpointManager(str(tmp_path / "fresh")).latest() == 2


def test_spmd_module_fit_resume_restores_optimizer_state(tmp_path):
    from mxnet_tpu.parallel import SPMDModule

    def run(d, epochs, resume=False):
        X, y = make_blobs(256, 10, 3, seed=9)
        it = mx.io.NDArrayIter(X, y, batch_size=64)
        mod = SPMDModule(mlp_sym())
        mx.random.seed(31)
        mod.fit(it, num_epoch=epochs, optimizer="sgd",
                optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
                initializer=mx.initializer.Xavier(),
                checkpoint=d, resume=resume)
        return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    full = run(str(tmp_path / "full"), 4)
    run(str(tmp_path / "cut"), 2)
    # the cut run saved optimizer state too (momentum must survive)
    assert os.path.exists(str(tmp_path / "cut" / "checkpoint-0002.states"))
    resumed = run(str(tmp_path / "cut"), 4, resume=True)
    for name in full:
        np.testing.assert_allclose(resumed[name], full[name], rtol=2e-5,
                                   atol=2e-6, err_msg=name)


def test_spmd_trainer_checkpoint_roundtrip(tmp_path):
    from mxnet_tpu.parallel import SPMDTrainer
    X = np.random.RandomState(1).randn(16, 10).astype("f")
    y = np.zeros((16,), "f")

    def make():
        t = SPMDTrainer(mlp_sym(), "sgd",
                        {"learning_rate": 0.1, "momentum": 0.9,
                         "rescale_grad": 1.0 / 16})
        t.bind([("data", (16, 10))], [("softmax_label", (16,))])
        mx.random.seed(5)
        t.init_params(mx.initializer.Xavier())
        return t

    man = CheckpointManager(str(tmp_path))
    a = make()
    for _ in range(3):
        a.step(X, y)
    a.save_checkpoint(man, 3)

    b = make()
    assert b.restore(man) == 3
    assert b._num_update == a._num_update  # momentum schedule continues
    a.step(X, y)
    b.step(X, y)
    pa, _ = a.get_params()
    pb, _ = b.get_params()
    for name in pa:
        np.testing.assert_allclose(pb[name].asnumpy(), pa[name].asnumpy(),
                                   rtol=1e-6, err_msg=name)


# ---------------------------------------------------------------------------
# retryable bring-up + prefetcher
# ---------------------------------------------------------------------------

def test_distributed_initialize_retries_transient_failure(monkeypatch):
    from mxnet_tpu import distributed as dist
    calls = []

    def fake_join(addr, n, pid, timeout):
        calls.append((addr, n, pid, timeout))
        if len(calls) == 1:
            raise RuntimeError("injected transient coordinator failure")

    monkeypatch.setattr(dist, "_join", fake_join)
    monkeypatch.setattr(dist, "_check_backend_untouched", lambda: None)
    monkeypatch.delenv("MXTPU_PLATFORM", raising=False)
    monkeypatch.setenv("MXTPU_INIT_RETRIES", "3")
    monkeypatch.setenv("MXTPU_INIT_BACKOFF", "0")
    monkeypatch.setenv("MXTPU_INIT_TIMEOUT", "7")
    assert not dist.is_initialized()
    try:
        dist.initialize(coordinator_address="127.0.0.1:1", num_processes=2,
                        process_id=0)
        assert dist.is_initialized()
    finally:
        dist._INITIALIZED = False
    assert len(calls) == 2  # failed once, joined on the retry
    assert calls[0] == ("127.0.0.1:1", 2, 0, "7")


def test_distributed_initialize_retry_exhaustion(monkeypatch):
    from mxnet_tpu import distributed as dist

    def always_fail(addr, n, pid, timeout):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(dist, "_join", always_fail)
    monkeypatch.setattr(dist, "_check_backend_untouched", lambda: None)
    monkeypatch.delenv("MXTPU_PLATFORM", raising=False)
    monkeypatch.setenv("MXTPU_INIT_RETRIES", "2")
    monkeypatch.setenv("MXTPU_INIT_BACKOFF", "0")
    with pytest.raises(MXNetError, match="all 2 attempts"):
        dist.initialize(coordinator_address="127.0.0.1:1", num_processes=2,
                        process_id=0)
    assert not dist.is_initialized()


def test_prefetcher_retries_transient_iterator_error(monkeypatch,
                                                     clean_faults):
    monkeypatch.setenv("MXTPU_DATA_RETRY_BACKOFF", "0")
    X = np.arange(64, dtype="f").reshape(16, 4)
    base = mx.io.NDArrayIter(X, np.zeros(16, "f"), batch_size=4)
    clean_faults.arm("iter_next", times=2)  # both absorbed by one next()
    it = mx.io.PrefetchingIter(base)
    seen = [b.data[0].asnumpy().copy() for b in it]
    assert len(seen) == 4
    np.testing.assert_allclose(seen[0], X[:4])  # no batch lost or reordered
    np.testing.assert_allclose(seen[-1], X[12:])


def test_prefetcher_surfaces_exhausted_retries(monkeypatch, clean_faults):
    monkeypatch.setenv("MXTPU_DATA_RETRY_BACKOFF", "0")
    monkeypatch.setenv("MXTPU_DATA_RETRIES", "2")
    X = np.arange(64, dtype="f").reshape(16, 4)
    base = mx.io.NDArrayIter(X, np.zeros(16, "f"), batch_size=4)
    clean_faults.arm("iter_next", times=2)  # beats the 2-attempt budget
    it = mx.io.PrefetchingIter(base)
    # the error reaches the consuming thread (no silent hang) ...
    with pytest.raises(MXNetError, match="all 2 attempts"):
        next(it)
    # ... and iteration continues past the failed fetch
    assert next(it) is not None


# ---------------------------------------------------------------------------
# checksummed manifests + verified restore
# ---------------------------------------------------------------------------

def _flip_payload_byte(path, value):
    """Flip one mantissa bit inside the serialized float32 payload for
    ``value`` — the file still parses cleanly (valid format, wrong
    numbers): the bit rot only checksums can catch."""
    import struct
    pat = struct.pack("<f", float(value)) * 2
    blob = bytearray(open(path, "rb").read())
    i = bytes(blob).find(pat)
    assert i >= 0, "float payload %r not found in %s" % (value, path)
    blob[i] ^= 0x01
    with open(path, "wb") as f:
        f.write(bytes(blob))


def test_manifest_records_file_checksums(tmp_path):
    man = CheckpointManager(str(tmp_path))
    man.save(1, mlp_sym(), {"w": mx.nd.array(np.ones((3, 2), "f"))}, {},
             optimizer_states=b"state-1")
    entry = man.latest_entry()
    assert entry["checksum"] == "sha256"
    files = entry["files"]
    assert set(files) == {"checkpoint-0001.params",
                          "checkpoint-0001.states",
                          "checkpoint-symbol.json"}
    for name, rec in files.items():
        size, digest = mx.resilience.checksum_file(
            str(tmp_path / name), "sha256")
        assert (size, digest) == (rec["size"], rec["digest"]), name


def test_restore_detects_bitflip_that_parses_cleanly(tmp_path):
    """A flipped payload byte leaves the params file loadable — the old
    walk-back (unpickle errors only) restored it silently.  The checksum
    verify must catch it and degrade to the previous epoch."""
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {})
    _flip_payload_byte(str(tmp_path / "checkpoint-0002.params"), 2)
    # the rotted file still parses — only the checksum knows
    assert mx.nd.load(str(tmp_path / "checkpoint-0002.params"))
    _, args, _, _, epoch = man.restore()
    assert epoch == 1
    assert np.allclose(args["w"].asnumpy(), 1.0)
    # explicitly requesting the rotten epoch still raises
    with pytest.raises(MXNetError, match="verification"):
        man.restore(2)


def test_corrupt_symbol_never_restored_silently(tmp_path):
    """The shared symbol file only carries a checksum record on the
    NEWEST manifest entry (each save rewrites the file and moves the
    record forward), so every epoch's restore must verify it against
    that newest record — the walk-back previously landed on an older
    entry with no record and returned the rotted symbol silently."""
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2):
        man.save(epoch, mlp_sym(),
                 {"w": mx.nd.array(np.ones((2,), "f"))}, {})
    path = tmp_path / "checkpoint-symbol.json"
    # flip one letter inside a node-name string: still valid JSON
    path.write_bytes(path.read_bytes().replace(b"fc1", b"fc9", 1))
    json.loads(path.read_text())  # parses cleanly — only the checksum knows
    with pytest.raises(MXNetError, match="verification"):
        man.restore()  # the walk-back must NOT reach an unverified epoch
    with pytest.raises(MXNetError, match="verification"):
        man.restore(1)


def test_checksum_algos(monkeypatch, tmp_path):
    from mxnet_tpu.resilience import checksum_bytes
    # known vectors: CRC32C("hello") = 0x9a71bb4c, zlib CRC32 = 0x3610a686
    assert checksum_bytes(b"hello", "crc32c") == (5, "9a71bb4c")
    assert checksum_bytes(b"hello", "crc32") == (5, "3610a686")
    assert checksum_bytes(b"hello", "off") == (5, None)
    assert len(checksum_bytes(b"hello", "sha256")[1]) == 64
    # the selector routes through the manifest
    monkeypatch.setenv("MXTPU_CKPT_CHECKSUM", "crc32c")
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {})
    entry = man.latest_entry()
    assert entry["checksum"] == "crc32c"
    assert len(entry["files"]["checkpoint-0001.params"]["digest"]) == 8
    man.restore()  # verifies under crc32c
    # an operator typo degrades to sha256, never to no-integrity
    monkeypatch.setenv("MXTPU_CKPT_CHECKSUM", "md5oops")
    man.save(2, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {})
    assert man.latest_entry()["checksum"] == "sha256"


# ---------------------------------------------------------------------------
# async saves (the zero-stall path)
# ---------------------------------------------------------------------------

def test_async_save_parity_and_wait(tmp_path):
    """blocking=False returns after the snapshot; wait() drains; the
    written checkpoint is byte-equivalent to a blocking save of the same
    values."""
    w = np.random.RandomState(0).randn(8, 4).astype("f")
    mb = CheckpointManager(str(tmp_path / "block"))
    ma = CheckpointManager(str(tmp_path / "async"))
    mb.save(1, mlp_sym(), {"w": mx.nd.array(w)}, {},
            optimizer_states=b"st")
    ma.save(1, mlp_sym(), {"w": mx.nd.array(w)}, {},
            optimizer_states=b"st", blocking=False)
    res = ma.wait()
    assert res["error"] is None and res["label"] == "epoch 1"
    assert ma.last_result()["error"] is None
    assert (tmp_path / "block" / "checkpoint-0001.params").read_bytes() \
        == (tmp_path / "async" / "checkpoint-0001.params").read_bytes()
    _, args, _, states, epoch = ma.restore()
    assert epoch == 1 and states == b"st"
    assert np.array_equal(args["w"].asnumpy(), w)


def test_async_snapshot_isolated_from_mutation(tmp_path):
    """The values handed to an async save are frozen at the call: the
    caller mutating its (host) params afterwards — exactly what the
    executor path's in-place epoch sync does — must not tear the write."""
    from mxnet_tpu.resilience import faults as fi
    w = mx.nd.array(np.zeros((4, 4), "f"))
    man = CheckpointManager(str(tmp_path))
    fi.arm_hang("ckpt_write", seconds=0.2)  # hold the writer mid-save
    try:
        man.save(1, None, {"w": w}, {}, blocking=False)
        w[:] = 7.0  # the next epoch trains on
        _ = w.asnumpy()
        man.wait()
    finally:
        fi.disarm()
    _, args, _, _, _ = man.restore()
    assert np.array_equal(args["w"].asnumpy(), np.zeros((4, 4), "f"))


def test_async_save_failure_surfaces_at_next_call(tmp_path, clean_faults):
    """A failed background write re-raises at the next save/wait — one
    epoch late, exactly where the blocking save would have raised — and
    the previous checkpoint stays restorable."""
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {})
    clean_faults.arm("ckpt_write")
    man.save(2, None, {"w": mx.nd.array(np.full((2,), 2, "f"))}, {},
             blocking=False)
    with pytest.raises(MXNetError, match="background write"):
        man.wait()
    assert man.latest() == 1  # epoch 2 never published
    assert man.last_result()["error"] is not None
    # the writer recovers: the next save lands
    man.save(3, None, {"w": mx.nd.array(np.full((2,), 3, "f"))}, {},
             blocking=False)
    man.wait()
    assert man.latest() == 3


@pytest.mark.parametrize("kvstore", ["local", "tpu"])
def test_async_fit_resume_bit_identical(tmp_path, monkeypatch, kvstore):
    """MXTPU_CKPT_ASYNC=1 routes fit's epoch-end saves through the
    writer; a resumed run restores from an async+verified checkpoint and
    finishes BIT-identical to the uninterrupted run — fused 'tpu' and
    executor 'local' paths both."""
    monkeypatch.setenv("MXTPU_CKPT_ASYNC", "1")
    full = _fit_params(str(tmp_path / "full"), kvstore, epochs=4)
    _fit_params(str(tmp_path / "cut"), kvstore, epochs=2)
    man = CheckpointManager(str(tmp_path / "cut"))
    assert man.latest() == 2  # fit drained the writer before returning
    assert man.latest_entry()["files"]  # checksummed
    resumed = _fit_params(str(tmp_path / "cut"), kvstore, epochs=4,
                          resume=True)
    for name in full:
        assert np.array_equal(resumed[name], full[name]), name


def test_module_save_checkpoint_async_prefix_path(tmp_path, monkeypatch):
    """The manager-less prefix surface (Module.save_checkpoint /
    callback.do_checkpoint with a plain prefix) honors MXTPU_CKPT_ASYNC
    through the shared default writer."""
    monkeypatch.setenv("MXTPU_CKPT_ASYNC", "1")
    X, y = make_blobs(64, 10, 3)
    mod, it = _fused_module(X, y)
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    want = {k: v.asnumpy().copy() for k, v in mod.get_params()[0].items()}
    prefix = str(tmp_path / "mod")
    mod.save_checkpoint(prefix, 3, save_optimizer_states=True)
    mx.resilience.wait_checkpoints()
    sym, args, auxs = mx.model.load_checkpoint(prefix, 3)
    assert os.path.exists(prefix + "-0003.states")
    for name in want:
        assert np.array_equal(want[name], args[name].asnumpy()), name


def test_module_async_save_submits_one_job(tmp_path, monkeypatch):
    """params + optimizer states land via ONE writer job: a second
    submit on the single-slot writer would block the caller for the
    first job's entire serialize+write+fsync — exactly the stall the
    async path exists to remove."""
    monkeypatch.setenv("MXTPU_CKPT_ASYNC", "1")
    calls = []
    real = mx.resilience.submit_checkpoint

    def counting(fn, label="checkpoint"):
        calls.append(label)
        return real(fn, label)

    monkeypatch.setattr(mx.resilience, "submit_checkpoint", counting)
    X, y = make_blobs(64, 10, 3)
    mod, it = _fused_module(X, y)
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    prefix = str(tmp_path / "mod")
    mod.save_checkpoint(prefix, 1, save_optimizer_states=True)
    mx.resilience.wait_checkpoints()
    assert len(calls) == 1, calls
    assert os.path.exists(prefix + "-0001.params")
    assert os.path.exists(prefix + "-0001.states")


def test_blocking_save_drains_inflight_async_write(tmp_path):
    """save(blocking=True) with an async write still in flight must
    drain it first: both run _update_manifest (read-modify-write of
    manifest.json), so racing them can silently drop one epoch's entry
    — and racing prunes could delete files the other just recorded."""
    import time as _time
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {},
             blocking=False)
    man.wait()
    done = []

    def slow():
        _time.sleep(0.3)
        done.append(1)

    man._writer.submit(slow, "in-flight")
    man.save(2, None, {"w": mx.nd.array(np.full((2,), 2, "f"))}, {},
             blocking=True)
    assert done, "blocking save did not wait for the in-flight write"
    assert man.checkpoints() == [1, 2]


def test_preempt_drain_is_bounded(tmp_path, monkeypatch):
    """A WEDGED (not failed) background write must not eat the whole
    preemption grace period: the drain times out after a bounded budget
    and the blocking exit-85 save still lands."""
    import time as _time
    monkeypatch.setattr(CheckpointManager, "DRAIN_TIMEOUT", 0.4)
    X, y = make_blobs(64, 10, 3)
    mod, it = _fused_module(X, y)
    batch = next(iter(it))
    mod.forward_backward(batch)
    mod.update()
    mx.resilience.submit_checkpoint(lambda: _time.sleep(1.2), "wedged")
    man = CheckpointManager(str(tmp_path))
    t0 = _time.monotonic()
    mod._save_preemption_checkpoint(man, 0, 4)
    assert _time.monotonic() - t0 < 1.0, \
        "preemption drain waited out the wedged write"
    entry = man.latest_entry()
    assert entry["epoch"] == 1 and entry["step_state"]["step"] == 4
    mx.resilience.wait_checkpoints()  # clean up the sleeper


def test_replicas_typo_degrades_not_crashes(tmp_path, monkeypatch):
    """A non-numeric MXTPU_CKPT_REPLICAS disables replication with a
    warning (like the checksum selector's fallback) instead of raising
    inside every epoch-end save."""
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "one")
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {},
             rank=0, world=3)
    assert man.latest() == 1
    assert "shards" not in man.latest_entry()


def test_fit_drains_default_writer_for_prefix_callbacks(tmp_path,
                                                        monkeypatch):
    """fit() must drain the SHARED default writer too: prefix-based
    epoch_end_callback saves (callback.do_checkpoint(prefix)) queue
    there, not on a manager, and the writer thread is a daemon — an
    undrained final save could be killed mid-write at interpreter
    exit.  The writer is slowed so a missing drain fails, not races."""
    import time as _time
    monkeypatch.setenv("MXTPU_CKPT_ASYNC", "1")
    real = mx.resilience.submit_checkpoint

    def slow_submit(fn, label="checkpoint"):
        def slow():
            _time.sleep(0.3)
            fn()
        return real(slow, label)

    monkeypatch.setattr(mx.resilience, "submit_checkpoint", slow_submit)
    X, y = make_blobs(64, 10, 3)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    prefix = str(tmp_path / "mod")
    mod = mx.mod.Module(mlp_sym())
    mx.random.seed(11)
    mod.fit(it, num_epoch=2, kvstore="tpu", optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            epoch_end_callback=mx.callback.do_checkpoint(prefix))
    # no explicit wait_checkpoints() here: fit itself must have drained
    assert os.path.exists(prefix + "-0002.params")


# ---------------------------------------------------------------------------
# hardened retention
# ---------------------------------------------------------------------------

def test_prune_crash_cannot_resurrect_pruned_epoch(tmp_path, clean_faults):
    """A crash between the (already pruned) manifest write and the file
    deletion leaves tombstones: neither the manifest nor the
    corrupt-manifest directory scan may resurrect the pruned epoch, and
    the next save completes the interrupted prune."""
    man = CheckpointManager(str(tmp_path), keep_last=2)
    for epoch in (1, 2):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {})
    clean_faults.arm("ckpt_prune")
    with pytest.raises(TransientError):
        man.save(3, None, {"w": mx.nd.array(np.full((2,), 3, "f"))}, {})
    # the prune committed (manifest) but the files outlived the crash
    assert (tmp_path / "checkpoint-0001.params").exists()
    assert (tmp_path / "checkpoint-0001.pruning").exists()
    assert man.checkpoints() == [2, 3]
    # even with the manifest torn, the scan skips the tombstoned epoch
    (tmp_path / "manifest.json").write_text("{torn")
    assert CheckpointManager(str(tmp_path)).checkpoints() == [2, 3]
    # the next save finishes the job: files and tombstone gone, fsync'd
    man2 = CheckpointManager(str(tmp_path), keep_last=2)
    man2.save(4, None, {"w": mx.nd.array(np.full((2,), 4, "f"))}, {})
    assert not (tmp_path / "checkpoint-0001.params").exists()
    assert not any(p.name.endswith(".pruning")
                   for p in tmp_path.iterdir())
    assert man2.checkpoints() == [3, 4]


def test_prune_deletes_shard_files_too(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    man = CheckpointManager(str(tmp_path), keep_last=1)
    args = {"w": mx.nd.array(np.ones((2,), "f"))}
    for epoch in (1, 2):
        for r in range(2):
            man.save(epoch, None, args, {}, rank=r, world=2)
    names = {p.name for p in tmp_path.iterdir()}
    assert "checkpoint-0002.shard000" in names
    assert not any(n.startswith("checkpoint-0001.shard") for n in names)
    assert not (tmp_path / "checkpoint-0001.params").exists()


# ---------------------------------------------------------------------------
# ring-replicated shards (single-process simulation; the multi-process
# drill lives in tests/dist/dist_ckpt_replica.py)
# ---------------------------------------------------------------------------

def _simulated_ring_save(tmp_path, world=3, epoch=1):
    args = {"w%d" % i: mx.nd.array(np.full((4, 3), i + 1, "f"))
            for i in range(5)}
    man = CheckpointManager(str(tmp_path))
    for r in range(world):
        man.save(epoch, None, args, {}, optimizer_states=b"ABCDEFGHIJKL",
                 rank=r, world=world)
    return man, args


def test_replication_writes_ring_shards(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    man, _ = _simulated_ring_save(tmp_path)
    names = {p.name for p in tmp_path.iterdir()}
    for p in range(3):
        assert "checkpoint-0001.shard%03d" % p in names
        assert "checkpoint-0001.shard%03d.rep1" % p in names
    meta = man.latest_entry()["shards"]
    assert meta["world"] == 3 and meta["replicas"] == 1
    # rank 0 recorded every shard's digest without reading peer files
    for part in meta["parts"]:
        size, digest = mx.resilience.checksum_file(
            str(tmp_path / part["file"]), "sha256")
        assert (size, digest) == (part["size"], part["digest"])


def test_shard_parts_need_subset_is_byte_identical(tmp_path):
    """Non-zero ranks build only their own + neighbor partitions
    (pickling all ``world`` parts there is O(world) redundant CPU per
    save); the limited build must stay byte-identical to the full one —
    rank 0's manifest digests vouch for bytes peers produce
    independently."""
    man = CheckpointManager(str(tmp_path))
    args = {"w%d" % i: mx.nd.array(np.full((4, 3), i + 1, "f"))
            for i in range(5)}
    full = man._shard_parts(1, args, {}, b"ABCDEFGHIJKL", 3)
    assert sorted(full) == [0, 1, 2]
    subset = man._shard_parts(1, args, {}, b"ABCDEFGHIJKL", 3,
                              need={1, 2})
    assert sorted(subset) == [1, 2]
    for p in subset:
        assert subset[p] == full[p]


def test_replication_recovers_from_peer_replica(tmp_path, monkeypatch):
    """Primary params file corrupt AND one shard's primary corrupt (both
    valid-format, flipped bytes): restore rebuilds the full state from
    the intact shards + the peer-written replica, bit-identical."""
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    man, args = _simulated_ring_save(tmp_path)
    _flip_payload_byte(str(tmp_path / "checkpoint-0001.params"), 3)
    # shard 1 holds keys w1 (=2.0) and w4 (=5.0): rot its primary copy
    _flip_payload_byte(str(tmp_path / "checkpoint-0001.shard001"), 2)
    _, restored, _, states, epoch = man.restore()
    assert epoch == 1 and states == b"ABCDEFGHIJKL"
    for name in args:
        assert np.array_equal(args[name].asnumpy(),
                              restored[name].asnumpy()), name


def test_replication_walks_back_when_all_copies_dead(tmp_path,
                                                     monkeypatch):
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    man, args = _simulated_ring_save(tmp_path)
    _simulated_ring_save(tmp_path, epoch=2)
    for name in ("checkpoint-0002.params", "checkpoint-0002.shard001",
                 "checkpoint-0002.shard001.rep1"):
        _flip_payload_byte(str(tmp_path / name), 2)
    _, restored, _, _, epoch = man.restore()
    assert epoch == 1  # every copy of shard 1 dead: degrade one epoch
    with pytest.raises(MXNetError, match="no intact copy"):
        man.restore(2)


def test_replication_recovers_with_checksums_off(tmp_path, monkeypatch):
    """With MXTPU_CKPT_CHECKSUM=off there is no digest to flag a rotted
    shard primary before deserializing — a truncated copy surfaces at
    pickle.loads, which must fall through to the intact peer replica
    instead of failing the epoch."""
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    monkeypatch.setenv("MXTPU_CKPT_CHECKSUM", "off")
    man, args = _simulated_ring_save(tmp_path)
    (tmp_path / "checkpoint-0001.params").write_bytes(b"torn")
    shard = tmp_path / "checkpoint-0001.shard001"
    shard.write_bytes(shard.read_bytes()[:len(shard.read_bytes()) // 2])
    _, restored, _, states, epoch = man.restore()
    assert epoch == 1 and states == b"ABCDEFGHIJKL"
    for name in args:
        assert np.array_equal(args[name].asnumpy(),
                              restored[name].asnumpy()), name


def test_shard_writer_ranks_prune_their_own_files(tmp_path, monkeypatch):
    """keep_last retention on a rank that writes only shard files: on
    per-host disks rank 0's manifest-driven pruning never reaches this
    host's directory, so the shard writer prunes its own view."""
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    man = CheckpointManager(str(tmp_path), keep_last=2)
    args = {"w": mx.nd.array(np.ones((2, 2), "f"))}
    for epoch in (1, 2, 3):
        man.save(epoch, None, args, {}, rank=1, world=3)
    names = {p.name for p in tmp_path.iterdir()}
    assert "checkpoint-0002.shard001" in names
    assert "checkpoint-0003.shard001" in names
    assert not any(n.startswith("checkpoint-0001.shard")
                   for n in names), names


# ---------------------------------------------------------------------------
# tools/ckpt_fsck.py (offline audit)
# ---------------------------------------------------------------------------

FSCK = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools", "ckpt_fsck.py")


def _run_fsck(directory, *args):
    import subprocess
    import sys
    return subprocess.run([sys.executable, FSCK, str(directory), *args],
                          capture_output=True, text=True, timeout=120)


def test_fsck_clean_directory_exits_zero(tmp_path):
    import json as _json
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2):
        man.save(epoch, mlp_sym(),
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {},
                 optimizer_states=b"s")
    res = _run_fsck(tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    report = _json.loads(res.stdout)
    assert report["ok"] and len(report["checkpoints"]) == 2
    assert all(e["ok"] for e in report["checkpoints"])


def test_fsck_flags_corruption_and_exits_one(tmp_path):
    import json as _json
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {})
    _flip_payload_byte(str(tmp_path / "checkpoint-0002.params"), 2)
    out = tmp_path / "report.json"
    res = _run_fsck(tmp_path, "--json", str(out), "-q")
    assert res.returncode == 1
    assert "mismatch" in res.stderr
    report = _json.loads(out.read_text())
    assert not report["ok"]
    by_epoch = {e["epoch"]: e for e in report["checkpoints"]}
    assert by_epoch[1]["ok"] and not by_epoch[2]["ok"]
    assert "checkpoint-0002.params" in by_epoch[2]["problems"][0]


def test_fsck_degraded_replica_reports_but_exits_zero(tmp_path,
                                                      monkeypatch):
    """A lost replica behind an intact primary is fully restorable:
    the audit surfaces it under ``degraded`` without failing."""
    import json as _json
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    _simulated_ring_save(tmp_path)
    os.remove(str(tmp_path / "checkpoint-0001.shard001.rep1"))
    res = _run_fsck(tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    entry = _json.loads(res.stdout)["checkpoints"][0]
    assert entry["ok"] and entry["degraded"], entry


def test_fsck_dead_shard_primary_exits_one(tmp_path, monkeypatch):
    """A dead shard primary leaning on its last replica is one fault
    from data loss — the audit must fail it."""
    import json as _json
    monkeypatch.setenv("MXTPU_CKPT_REPLICAS", "1")
    _simulated_ring_save(tmp_path)
    _flip_payload_byte(str(tmp_path / "checkpoint-0001.shard001"), 2)
    res = _run_fsck(tmp_path)
    assert res.returncode == 1
    entry = _json.loads(res.stdout)["checkpoints"][0]
    assert not entry["ok"]
    assert any("primary dead" in p for p in entry["problems"]), entry


def test_fsck_checksums_lockstep_with_resilience(tmp_path):
    """ckpt_fsck duplicates the checksum code (it must stay import-light
    — no jax); the two implementations must agree byte-for-byte."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("ckpt_fsck_t", FSCK)
    fsck = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fsck)
    sample = tmp_path / "sample.bin"
    sample.write_bytes(bytes(range(256)) * 41)
    for algo in ("sha256", "crc32", "crc32c"):
        assert fsck.checksum_file(str(sample), algo) == \
            mx.resilience.checksum_file(str(sample), algo), algo


# ---------------------------------------------------------------------------
# CheckpointManager recovery corners, directly on the manager (ISSUE 13
# satellite — these paths were only exercised through the pool before)
# ---------------------------------------------------------------------------

def test_manager_manifest_lists_deleted_epoch(tmp_path):
    """An epoch the manifest still lists but whose params file is gone
    (operator rm, partial restore of a backup) silently drops out of
    checkpoints()/latest() — it is not restorable and must not be
    advertised; restore() lands on the newest epoch that exists."""
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2, 3):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {})
    os.remove(str(tmp_path / "checkpoint-0003.params"))
    man2 = CheckpointManager(str(tmp_path))
    assert man2.checkpoints() == [1, 2]
    assert man2.latest() == 2
    _, args, _, _, epoch = man2.restore()
    assert epoch == 2 and np.allclose(args["w"].asnumpy(), 2.0)
    # the deleted epoch is still in the manifest (nothing rewrote it)
    # but entry() exposes it for forensics without latest() lying
    assert man2.entry(3) is not None


def test_manager_digest_mismatch_entry_walked_past(tmp_path):
    """Same-size bit rot (the flavor only digests catch): latest()
    still names the rotted epoch — existence is its contract — but the
    default restore() walks back past it, and verify_promotion refuses
    it outright (the promote path never walks anywhere)."""
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    for epoch in (1, 2):
        man.save(epoch, None,
                 {"w": mx.nd.array(np.full((2,), epoch, "f"))}, {})
    _flip_payload_byte(str(tmp_path / "checkpoint-0002.params"), 2)
    assert man.latest() == 2
    _, args, _, _, epoch = man.restore()
    assert epoch == 1 and np.allclose(args["w"].asnumpy(), 1.0)
    got_epoch, problems = verify_promotion(str(tmp_path))
    assert got_epoch == 2 and problems, problems
    assert "fails verification" in problems[0]


def test_manager_scan_rebuild_entries_not_promotable(tmp_path):
    """A manifest rebuilt by the corrupt-manifest directory scan has no
    integrity records: restore() tolerates that (legacy stance), the
    promote gate must NOT — unverifiable bytes never ride a hot swap."""
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((2,), "f"))}, {})
    (tmp_path / "manifest.json").write_text("{ torn")
    man2 = CheckpointManager(str(tmp_path))
    assert man2.checkpoints() == [1]          # the scan recovered it
    epoch, problems = verify_promotion(str(tmp_path))
    assert epoch == 1 and problems
    assert "no integrity record" in problems[0]


# ---------------------------------------------------------------------------
# the promote-path verifier + rot/truncate fault points (ISSUE 13)
# ---------------------------------------------------------------------------

def test_verify_promotion_clean_and_damaged(tmp_path):
    from mxnet_tpu.resilience import verify_promotion
    assert verify_promotion(str(tmp_path / "nope"))[0] is None
    man = CheckpointManager(str(tmp_path))
    assert verify_promotion(str(tmp_path))[0] is None   # empty dir
    man.save(1, mlp_sym(), {"w": mx.nd.array(np.ones((2,), "f"))}, {},
             optimizer_states=b"opt")
    epoch, problems = verify_promotion(str(tmp_path))
    assert (epoch, problems) == (1, [])
    epoch, problems = verify_promotion(str(tmp_path), epoch=9)
    assert epoch == 9 and "not in the manifest" in problems[0]
    # states rot is caught too — the verifier covers every recorded file
    sp = tmp_path / "checkpoint-0001.states"
    sp.write_bytes(b"opX")
    epoch, problems = verify_promotion(str(tmp_path))
    assert epoch == 1 and problems
    # ...and symbol rot (shared file, newest entry vouches)
    sp.write_bytes(b"opt")
    assert verify_promotion(str(tmp_path)) == (1, [])
    sym_path = tmp_path / "checkpoint-symbol.json"
    sym_path.write_text(sym_path.read_text() + " ")
    epoch, problems = verify_promotion(str(tmp_path))
    assert epoch == 1 and problems


def test_rot_and_truncate_fault_points_fire_after_manifest(
        tmp_path, clean_faults):
    """The promote-path fault points damage the params file AFTER its
    manifest entry is published: the manifest looks healthy, the bytes
    are not — exactly what the digest layer must catch."""
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((4,), "f"))}, {})
    clean_faults.arm("rot_checkpoint")
    man.save(2, None, {"w": mx.nd.array(np.full((4,), 2.0, "f"))}, {})
    # the manifest LISTS epoch 2 (published before the damage) ...
    assert man.latest() == 2
    # ... same size on disk (a flip, not a truncation) ...
    rec = man.entry(2)["files"]["checkpoint-0002.params"]
    assert os.path.getsize(str(tmp_path / "checkpoint-0002.params")) \
        == rec["size"]
    # ... and the digest refuses it
    _, problems = verify_promotion(str(tmp_path))
    assert problems and "fails verification" in problems[0]

    clean_faults.arm("truncate_checkpoint")
    man.save(3, None, {"w": mx.nd.array(np.full((4,), 3.0, "f"))}, {})
    assert os.path.getsize(str(tmp_path / "checkpoint-0003.params")) \
        < man.entry(3)["files"]["checkpoint-0003.params"]["size"]
    _, problems = verify_promotion(str(tmp_path))
    assert problems
    # restore() still works: it walks back to the intact epoch 1
    _, args, _, _, epoch = man.restore()
    assert epoch == 1


def test_fsck_promote_gate_and_watch_share_the_verifier(tmp_path,
                                                        clean_faults):
    """tools/ckpt_fsck.py --promote-gate/--watch run resilience.
    verify_promotion itself (imported through the synthetic-package
    stub): clean epoch -> rc 0 / PROMOTABLE, rot-injected epoch ->
    rc 1 / REJECTED — byte-for-byte the watcher's verdict."""
    import json as _json
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    man.save(1, None, {"w": mx.nd.array(np.ones((4,), "f"))}, {})
    res = _run_fsck(tmp_path, "--promote-gate")
    assert res.returncode == 0, res.stdout + res.stderr
    doc = _json.loads(res.stdout)
    assert doc["promotable"] and doc["epoch"] == 1

    clean_faults.arm("rot_checkpoint")
    man.save(2, None, {"w": mx.nd.array(np.full((4,), 2.0, "f"))}, {})
    res = _run_fsck(tmp_path, "--promote-gate")
    assert res.returncode == 1
    doc = _json.loads(res.stdout)
    assert not doc["promotable"] and doc["epoch"] == 2
    # the CLI's problems are the in-process verifier's, verbatim
    _, problems = verify_promotion(str(tmp_path))
    assert doc["problems"] == problems
    # --epoch targets a specific (here: still-intact) epoch
    res = _run_fsck(tmp_path, "--promote-gate", "--epoch", "1")
    assert res.returncode == 0

    res = _run_fsck(tmp_path, "--watch", "--watch-count", "1",
                    "--poll", "0.05")
    assert res.returncode == 1
    assert "epoch 2 REJECTED" in res.stdout


# ---------------------------------------------------------------------------
# sharded-native checkpoints: per-shard blobs, shard-level verification,
# elastic-ready assembly (ISSUE 18)
# ---------------------------------------------------------------------------

def _sharded_payloads(epoch, world, base=1.0, rows=2):
    """Synthetic shard payloads in the trainer's blob contract: shard k
    carries its slice of "w" (dim 0) + one momentum slot; shard 0 also
    carries the replicated "bias", aux state and the update counter."""
    import pickle

    def payload(k):
        w = np.full((rows, 3), base + k, "f")
        out = {"epoch": int(epoch), "shard": k, "world": int(world),
               "args": {"w": w}, "opt": {"w": (w * 0.5,)},
               "dims": {"w": 0}}
        if k == 0:
            out["args"]["bias"] = np.full((3,), base, "f")
            out["dims"]["bias"] = None
            out["aux"] = {"mov": np.full((2,), base, "f")}
            out["num_update"] = int(epoch) * 10
        return pickle.dumps(out, protocol=4)
    return payload


def _expected_w(world, base=1.0, rows=2):
    return np.concatenate(
        [np.full((rows, 3), base + k, "f") for k in range(world)], axis=0)


def test_save_sharded_roundtrip_and_format2_manifest(tmp_path):
    """The tentpole roundtrip: one verified blob per shard, a format-2
    manifest entry whose shard_set records every blob's index/size/
    digest (and whose files map covers them for the generic
    verifiers), and restore() assembling the full arrays — params
    along the recorded dim, replicated/aux/num_update from blob 0."""
    import pickle
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    world = 4
    man.save_sharded(1, mlp_sym(), _sharded_payloads(1, world),
                     world=world)
    entry = man.entry(1)
    assert entry["format"] == CheckpointManager.SHARDED_FORMAT
    assert entry["params"] is None and entry["states"] is None
    ss = entry["shard_set"]
    assert ss["world"] == world
    assert [r["shard"] for r in ss["files"]] == list(range(world))
    for rec in ss["files"]:
        assert rec["file"].startswith("checkpoint-0001.params.s")
        assert os.path.exists(str(tmp_path / rec["file"]))
        # the same record rides the generic files map (size + digest),
        # so every existing verifier covers blobs with no new code
        assert entry["files"][rec["file"]]["digest"] == rec["digest"]
    assert man.checkpoints() == [1]
    assert verify_promotion(str(tmp_path)) == (1, [])
    # peak host residency is ONE blob, not the gather
    st = man.last_save_stats
    assert st["peak_blob_bytes"] < st["total_blob_bytes"]

    symbol, args, auxs, states, epoch = man.restore()
    assert epoch == 1 and symbol is not None
    assert np.array_equal(args["w"].asnumpy(), _expected_w(world))
    assert np.array_equal(args["bias"].asnumpy(), np.full((3,), 1.0, "f"))
    assert np.array_equal(auxs["mov"].asnumpy(), np.full((2,), 1.0, "f"))
    st = pickle.loads(states)
    assert st["num_update"] == 10
    assert np.array_equal(st["states"]["w"][0], _expected_w(world) * 0.5)


@pytest.mark.parametrize("point", ["rot_shard", "truncate_shard",
                                   "drop_shard"])
def test_shard_loss_matrix_every_single_shard(tmp_path, clean_faults,
                                              point):
    """The shard-loss matrix: EACH single shard rotted / truncated /
    deleted (arm(point, times=1, after=k) damages exactly blob k after
    its manifest publish) is caught by verify_promotion before any
    deserialization, and restore() walks back to the last COMPLETE
    verified epoch — never a partial or mixed assembly."""
    from mxnet_tpu.resilience import verify_promotion
    world = 3
    for k in range(world):
        d = tmp_path / ("%s_%d" % (point, k))
        man = CheckpointManager(str(d))
        man.save_sharded(1, mlp_sym(), _sharded_payloads(1, world),
                         world=world)
        clean_faults.arm(point, times=1, after=k)
        man.save_sharded(2, None, _sharded_payloads(2, world, base=5.0),
                         world=world)
        # the manifest vouches for epoch 2 (damage landed post-publish)
        assert man.latest() == 2
        blob_k = d / man.shard_blob_name(2, k, world)
        if point == "drop_shard":
            assert not blob_k.exists()
        else:
            assert blob_k.exists()
        epoch, problems = verify_promotion(str(d))
        assert epoch == 2 and problems, (point, k)
        # walk-back to the intact epoch, bit-exact
        _, args, _, _, epoch = man.restore()
        assert epoch == 1, (point, k)
        assert np.array_equal(args["w"].asnumpy(), _expected_w(world))


def test_sharded_scan_rebuild_restorable_not_promotable(tmp_path):
    """Corrupt-manifest recovery recognizes shard blob filenames: a
    COMPLETE shard set is reassembled (restorable), an incomplete one
    is skipped, and — PR 13 semantics — a rebuilt entry has no digests
    so the promote gate refuses it."""
    from mxnet_tpu.resilience import atomic_write, verify_promotion
    man = CheckpointManager(str(tmp_path))
    world = 2
    man.save_sharded(1, mlp_sym(), _sharded_payloads(1, world),
                     world=world)
    # a second epoch missing one blob: the scan must NOT resurrect it
    pay = _sharded_payloads(3, world, base=9.0)
    atomic_write(str(tmp_path / man.shard_blob_name(3, 0, world)),
                 pay(0))
    (tmp_path / "manifest.json").write_text("{ torn")
    man2 = CheckpointManager(str(tmp_path))
    assert man2.checkpoints() == [1]
    _, args, _, _, epoch = man2.restore()
    assert epoch == 1
    assert np.array_equal(args["w"].asnumpy(), _expected_w(world))
    epoch, problems = verify_promotion(str(tmp_path))
    assert epoch == 1 and problems
    assert "no integrity record" in problems[0]


def test_sharded_mixed_epoch_refusal_without_digests(tmp_path):
    """Blobs self-identify (epoch/shard/world in the payload), so even
    a digest-less scan-rebuilt entry can never assemble a Frankenstein
    state from two epochs' blobs — the mixed epoch fails and restore
    walks back to a coherent one."""
    import shutil as _sh
    man = CheckpointManager(str(tmp_path))
    world = 2
    man.save_sharded(1, mlp_sym(), _sharded_payloads(1, world),
                     world=world)
    man.save_sharded(2, None, _sharded_payloads(2, world, base=5.0),
                     world=world)
    # lose the manifest -> rebuilt entries carry no digests ...
    (tmp_path / "manifest.json").write_text("{ torn")
    # ... then splice epoch 1's blob into epoch 2's shard set
    _sh.copyfile(str(tmp_path / man.shard_blob_name(1, 1, world)),
                 str(tmp_path / man.shard_blob_name(2, 1, world)))
    man2 = CheckpointManager(str(tmp_path))
    assert man2.checkpoints() == [1, 2]
    _, args, _, _, epoch = man2.restore()
    assert epoch == 1   # epoch 2 refused as a mixed assembly
    assert np.array_equal(args["w"].asnumpy(), _expected_w(world))


def test_verify_promotion_shard_set_completeness(tmp_path):
    """An entry whose shard_set lost a record (manifest damage that
    keeps valid JSON) is reported as incomplete — not promotable, no
    deserialization attempted."""
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    world = 3
    man.save_sharded(1, mlp_sym(), _sharded_payloads(1, world),
                     world=world)
    mpath = tmp_path / "manifest.json"
    doc = json.loads(mpath.read_text())
    entry = doc["checkpoints"][-1]
    dropped = entry["shard_set"]["files"].pop(1)
    entry["files"].pop(dropped["file"])
    mpath.write_text(json.dumps(doc))
    epoch, problems = verify_promotion(str(tmp_path))
    assert epoch == 1 and problems
    assert "incomplete" in problems[0]


def test_sharded_and_gathered_epochs_coexist(tmp_path, clean_faults):
    """Backward compat both ways in ONE directory: a legacy gathered
    epoch and a sharded epoch restore and promote side by side, and a
    damaged sharded epoch walks back onto the gathered one."""
    from mxnet_tpu.resilience import verify_promotion
    man = CheckpointManager(str(tmp_path))
    world = 2
    man.save(1, mlp_sym(), {"w": mx.nd.array(_expected_w(world))}, {},
             optimizer_states=b"opt")
    man.save_sharded(2, None, _sharded_payloads(2, world, base=5.0),
                     world=world)
    assert man.checkpoints() == [1, 2]
    assert verify_promotion(str(tmp_path)) == (2, [])
    _, args, _, _, epoch = man.restore()
    assert epoch == 2
    assert np.array_equal(args["w"].asnumpy(),
                          _expected_w(world, base=5.0))
    # damage one shard blob -> promote refuses, restore lands on the
    # legacy gathered epoch
    blob = tmp_path / man.shard_blob_name(2, 0, world)
    raw = bytearray(blob.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    blob.write_bytes(bytes(raw))
    epoch, problems = verify_promotion(str(tmp_path))
    assert epoch == 2 and problems
    _, args, _, states, epoch = man.restore()
    assert epoch == 1 and states == b"opt"
    assert np.array_equal(args["w"].asnumpy(), _expected_w(world))


def test_sharded_prune_deletes_blobs_and_tombstones(tmp_path):
    """Retention covers the sharded layout: pruning a format-2 epoch
    removes every blob (manifest-listed AND stray same-epoch blobs via
    the tombstone sweep)."""
    man = CheckpointManager(str(tmp_path), keep_last=1)
    world = 2
    for epoch in (1, 2):
        man.save_sharded(epoch, mlp_sym(),
                         _sharded_payloads(epoch, world), world=world)
    assert man.checkpoints() == [2]
    assert not (tmp_path / man.shard_blob_name(1, 0, world)).exists()
    assert not (tmp_path / man.shard_blob_name(1, 1, world)).exists()
    assert (tmp_path / man.shard_blob_name(2, 0, world)).exists()


def test_parse_fault_schedule_rot_grammar():
    """STORM grammar: '<at_s> rot <role> shard#<k>' parses to a counted
    rot event; malformed args fail loudly (a silently skipped event
    would pass its drill without testing anything)."""
    from mxnet_tpu.resilience import parse_fault_schedule
    evs = parse_fault_schedule("9 rot trainer shard#1\n")
    assert len(evs) == 1
    ev = evs[0]
    assert (ev.at_s, ev.action, ev.target, ev.arg) == \
        (9.0, "rot", "trainer", "shard#1")
    assert ev.label == "rot:trainer:shard#1"
    for bad in ("9 rot trainer", "9 rot trainer shard1",
                "9 rot trainer shard#", "9 rot trainer shard#1 extra"):
        with pytest.raises(MXNetError):
            parse_fault_schedule(bad)


def test_fsck_sharded_clean_damaged_and_incomplete(tmp_path):
    """tools/ckpt_fsck.py speaks the sharded layout: a clean shard set
    passes, a rotted blob fails the audit AND the promote gate, and an
    entry whose shard_set lost a record is reported incomplete."""
    import json as _json
    man = CheckpointManager(str(tmp_path))
    world = 3
    man.save_sharded(1, mlp_sym(), _sharded_payloads(1, world),
                     world=world)
    res = _run_fsck(tmp_path, "-q")
    assert res.returncode == 0, res.stdout + res.stderr
    res = _run_fsck(tmp_path, "--promote-gate")
    assert res.returncode == 0
    assert _json.loads(res.stdout)["promotable"]

    blob = tmp_path / man.shard_blob_name(1, 1, world)
    raw = bytearray(blob.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    blob.write_bytes(bytes(raw))
    res = _run_fsck(tmp_path, "-q")
    assert res.returncode == 1
    res = _run_fsck(tmp_path, "--promote-gate")
    assert res.returncode == 1
    assert not _json.loads(res.stdout)["promotable"]

    mpath = tmp_path / "manifest.json"
    doc = _json.loads(mpath.read_text())
    entry = doc["checkpoints"][-1]
    dropped = entry["shard_set"]["files"].pop(0)
    entry["files"].pop(dropped["file"])
    mpath.write_text(_json.dumps(doc))
    res = _run_fsck(tmp_path)
    assert res.returncode == 1
    assert "incomplete" in res.stdout
