"""Preemption-and-hang survival layer: StepWatchdog (calibration, stack
dump, abort code), graceful SIGTERM preemption (mid-epoch checkpoint +
bit-identical resume), tools/supervise.py relaunch policy, and the
end-to-end chaos drill — a run killed mid-epoch, relaunched through the
supervisor, finishing with params bit-identical to an uninterrupted run.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.resilience import (CheckpointManager, FaultInjector,
                                  PreemptionHandler, StepWatchdog,
                                  TransientError, PREEMPT_EXIT_CODE,
                                  WATCHDOG_EXIT_CODE, faults)

pytestmark = pytest.mark.resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUPERVISE = os.path.join(REPO, "tools", "supervise.py")


def make_blobs(n, d, c, seed=4):
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


def test_exit_codes_match_supervisor():
    """supervise.py hardcodes the codes (it must not import jax); they
    must stay in lockstep with resilience's."""
    import importlib.util
    spec = importlib.util.spec_from_file_location("supervise_t", SUPERVISE)
    sup = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sup)
    assert sup.PREEMPT_EXIT_CODE == PREEMPT_EXIT_CODE
    assert sup.WATCHDOG_EXIT_CODE == WATCHDOG_EXIT_CODE
    assert PREEMPT_EXIT_CODE != WATCHDOG_EXIT_CODE


# ---------------------------------------------------------------------------
# fault injector: delayed firing + hang points
# ---------------------------------------------------------------------------

def test_fault_injector_after_delay():
    fi = FaultInjector()
    fi.arm("preempt", times=1, after=3)
    assert [fi.consume("preempt") for _ in range(5)] == \
        [False, False, False, True, False]


def test_fault_injector_env_after_syntax(monkeypatch):
    monkeypatch.setenv("MXTPU_FAULTS", "hang_step:1@2, iter_next:3")
    fi = FaultInjector()
    assert [fi.consume("hang_step") for _ in range(4)] == \
        [False, False, True, False]
    assert fi.is_armed("iter_next")


def test_maybe_hang_stalls_for_armed_duration(clean_faults):
    clean_faults.arm_hang("hang_step", seconds=0.2)
    t0 = time.monotonic()
    faults.maybe_hang("hang_step")
    assert time.monotonic() - t0 >= 0.2
    # disarmed after firing: second call returns immediately
    t0 = time.monotonic()
    faults.maybe_hang("hang_step")
    assert time.monotonic() - t0 < 0.1


# ---------------------------------------------------------------------------
# StepWatchdog (fake clock + injected abort: full fire path, no process
# death, no real sleeping)
# ---------------------------------------------------------------------------

def _fake_watchdog(**kw):
    now = {"t": 0.0}
    fired = []
    wd = StepWatchdog(clock=lambda: now["t"], abort=fired.append,
                      debug_dir=kw.pop("debug_dir", None), **kw)
    return wd, now, fired


def test_watchdog_calibrates_from_median():
    wd, now, _ = _fake_watchdog(calibrate_steps=3, multiplier=10.0,
                                min_timeout=0.5)
    assert wd.calibrated_timeout is None
    for dur in (5.0, 0.1, 0.2):  # first step = XLA compile: 25x the rest
        with wd.armed("step"):
            now["t"] += dur
    # median (0.2) x 10, NOT mean — one compile-dominated step must not
    # inflate the budget 25x
    assert wd.calibrated_timeout == pytest.approx(2.0)


def test_watchdog_min_timeout_floor():
    wd, now, _ = _fake_watchdog(calibrate_steps=2, multiplier=10.0,
                                min_timeout=60.0)
    for _ in range(2):
        with wd.armed("step"):
            now["t"] += 0.001
    assert wd.calibrated_timeout == 60.0


def test_watchdog_env_fixed_timeout(monkeypatch):
    monkeypatch.setenv("MXTPU_STEP_TIMEOUT", "7.5")
    wd = StepWatchdog(clock=lambda: 0.0, abort=lambda c: None)
    assert wd.calibrated_timeout == 7.5
    monkeypatch.setenv("MXTPU_STEP_TIMEOUT", "auto")
    wd = StepWatchdog(clock=lambda: 0.0, abort=lambda c: None)
    assert wd.calibrated_timeout is None  # auto = calibrate


def test_step_timeout_zero_means_disabled(monkeypatch):
    """MXTPU_STEP_TIMEOUT=0 is the natural 'off' spelling: it must not
    enable a watchdog (let alone a zero-second budget)."""
    from mxnet_tpu.resilience import step_timeout_configured
    for value, expect in (("0", False), ("-1", False), ("", False),
                          ("nonsense", False), ("auto", True),
                          ("2.5", True)):
        monkeypatch.setenv("MXTPU_STEP_TIMEOUT", value)
        assert step_timeout_configured() is expect, value
    monkeypatch.delenv("MXTPU_STEP_TIMEOUT")
    assert step_timeout_configured() is False
    # and the constructor never arms a <=0 budget from the env
    monkeypatch.setenv("MXTPU_STEP_TIMEOUT", "0")
    wd = StepWatchdog(clock=lambda: 0.0, abort=lambda c: None)
    assert wd.calibrated_timeout is None


def test_agree_flag_single_process_passthrough():
    from mxnet_tpu.distributed import agree_flag
    assert agree_flag(True) is True
    assert agree_flag(False) is False


def test_install_watchdog_detach_clears_info():
    from mxnet_tpu.parallel import SPMDTrainer
    trainer = SPMDTrainer(mlp_sym(), "sgd",
                          {"learning_rate": 0.1, "rescale_grad": 1.0 / 16})
    wd = StepWatchdog(timeout=1.0, clock=lambda: 0.0,
                      abort=lambda c: None)
    trainer.install_watchdog(wd)
    assert wd.info is not None and "grad_sync" in wd.info()
    trainer.install_watchdog(None)
    assert wd.info is None and trainer.watchdog is None


def test_watchdog_fires_on_overrun_and_dumps(tmp_path, capsys):
    wd, now, fired = _fake_watchdog(timeout=1.0, debug_dir=str(tmp_path))
    wd.info = lambda: "trainer: step 3, mesh={'dp': 8}"
    with wd.armed("epoch 0 batch 3"):
        now["t"] += 0.5
        assert not wd.poll()        # within budget
        now["t"] += 1.0
        assert wd.poll()            # 1.5s > 1.0s budget
    assert fired == [WATCHDOG_EXIT_CODE]
    err = capsys.readouterr().err
    assert "epoch 0 batch 3" in err
    assert "mesh={'dp': 8}" in err
    assert "MainThread" in err      # the stack dump reached stderr
    dumps = list(tmp_path.iterdir())
    assert len(dumps) == 1 and dumps[0].name.startswith("watchdog-")
    report = dumps[0].read_text()
    assert "exceeded its 1.0s budget" in report
    assert "--- thread" in report


def test_watchdog_does_not_fire_disarmed_or_in_budget():
    wd, now, fired = _fake_watchdog(timeout=1.0)
    now["t"] += 100.0
    assert not wd.poll()            # not armed: no deadline
    with wd.armed("step"):
        now["t"] += 0.9
        assert not wd.poll()
    assert fired == []


def test_watchdog_reentrant_arming_keeps_outer_deadline():
    wd, now, fired = _fake_watchdog(timeout=1.0)
    with wd.armed("outer"):
        now["t"] += 0.8
        with wd.armed("inner"):     # fit() wraps trainer.step's own arm
            now["t"] += 0.4
            assert wd.poll()        # 1.2s from the OUTER arm
    assert fired == [WATCHDOG_EXIT_CODE]


def test_watchdog_monitor_thread_fires_for_real():
    fired = []
    wd = StepWatchdog(timeout=0.2, check_interval=0.05,
                      abort=fired.append)
    wd.start()
    try:
        with wd.armed("stalled step"):
            deadline = time.monotonic() + 5.0
            while not fired and time.monotonic() < deadline:
                time.sleep(0.05)
    finally:
        wd.stop()
    assert fired == [WATCHDOG_EXIT_CODE]


# ---------------------------------------------------------------------------
# preemption handler + mid-epoch checkpoint/resume (in-process)
# ---------------------------------------------------------------------------

def test_preemption_handler_flag_and_uninstall():
    before = signal.getsignal(signal.SIGTERM)
    h = PreemptionHandler().install()
    try:
        assert not h.triggered
        os.kill(os.getpid(), signal.SIGTERM)
        for _ in range(100):
            if h.triggered:
                break
            time.sleep(0.01)
        assert h.triggered
    finally:
        h.uninstall()
    assert signal.getsignal(signal.SIGTERM) is before


def _fit_kwargs(ckpt_dir, epochs, **kw):
    kw.setdefault("kvstore", "tpu")
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("optimizer_params", {"learning_rate": 0.1,
                                       "momentum": 0.9})
    kw.setdefault("initializer", mx.initializer.Xavier())
    return dict(num_epoch=epochs, checkpoint=ckpt_dir, **kw)


def _run_fit(ckpt_dir, epochs, preempt_after=None, resume=False, seed=21,
             kvstore="tpu"):
    """One fit() over the blob MLP; returns host params, or None when the
    run exited via graceful preemption."""
    X, y = make_blobs(256, 10, 3)
    it = mx.io.NDArrayIter(X, y, batch_size=64)
    mod = mx.mod.Module(mlp_sym())
    mx.random.seed(seed)
    if preempt_after is not None:
        faults.arm("preempt", times=1, after=preempt_after)
    try:
        mod.fit(it, **_fit_kwargs(ckpt_dir, epochs, resume=resume,
                                  kvstore=kvstore,
                                  preemption_safe=preempt_after
                                  is not None))
    except SystemExit as e:
        assert e.code == PREEMPT_EXIT_CODE
        return None
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


@pytest.mark.parametrize("kvstore", ["tpu", "local"])
def test_preemption_saves_mid_epoch_and_resume_is_bit_identical(
        tmp_path, clean_faults, kvstore):
    """SIGTERM (in-band, delivered for real) mid-epoch -> checkpoint with
    step_state -> fit(resume=True) fast-forwards and finishes with params
    BIT-identical to the uninterrupted run — on both the fused-SPMD and
    the executor/kvstore paths."""
    full = _run_fit(str(tmp_path / "full"), 3, kvstore=kvstore)

    # preempted at the 6th step boundary of a 4-steps/epoch run: mid
    # epoch 1
    cut_dir = str(tmp_path / "cut")
    assert _run_fit(cut_dir, 3, preempt_after=5, kvstore=kvstore) is None
    entry = CheckpointManager(cut_dir).latest_entry()
    assert entry["step_state"]["epoch"] == 1
    assert entry["step_state"]["step"] == 2
    assert entry["step_state"]["rng"] is not None

    resumed = _run_fit(cut_dir, 3, resume=True, kvstore=kvstore)
    for name in full:
        assert np.array_equal(full[name], resumed[name]), name
    # the finished run's epoch-end saves replaced the partial entry
    final = CheckpointManager(cut_dir).latest_entry()
    assert final["epoch"] == 3 and "step_state" not in final


def test_epoch_end_save_replaces_partial_entry(tmp_path, clean_faults):
    cut_dir = str(tmp_path / "cut")
    assert _run_fit(cut_dir, 2, preempt_after=2) is None
    man = CheckpointManager(cut_dir)
    assert "step_state" in man.latest_entry()
    resumed = _run_fit(cut_dir, 2, resume=True)
    assert resumed is not None
    for e in man._read_manifest()["checkpoints"]:
        assert "step_state" not in e  # every survivor is a complete epoch


def test_resume_env_var_forces_resume(tmp_path, clean_faults, monkeypatch):
    """MXTPU_RESUME=1 (what supervise.py sets on relaunch) == passing
    resume=True."""
    cut_dir = str(tmp_path / "cut")
    full = _run_fit(str(tmp_path / "full"), 2)
    assert _run_fit(cut_dir, 2, preempt_after=1) is None
    monkeypatch.setenv("MXTPU_RESUME", "1")
    resumed = _run_fit(cut_dir, 2)      # no explicit resume=
    for name in full:
        assert np.array_equal(full[name], resumed[name]), name


def test_preemption_checkpoint_callback_for_custom_loops(tmp_path):
    """Custom training loops get the same SIGTERM-to-checkpoint exit via
    mx.callback.PreemptionCheckpoint."""
    from mxnet_tpu.model import BatchEndParam
    X, y = make_blobs(128, 10, 3)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(mlp_sym())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mx.random.seed(7)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})
    man = CheckpointManager(str(tmp_path))
    before = signal.getsignal(signal.SIGTERM)
    with mx.callback.PreemptionCheckpoint(mod, man) as cb:
        with pytest.raises(SystemExit) as exc:
            for nbatch, batch in enumerate(it):
                mod.forward_backward(batch)
                mod.update()
                if nbatch == 1:
                    cb.handler.trigger()     # "SIGTERM arrived here"
                cb(BatchEndParam(epoch=0, nbatch=nbatch, eval_metric=None,
                                 locals=None))
        assert exc.value.code == PREEMPT_EXIT_CODE
    # context exit restored the original disposition
    assert signal.getsignal(signal.SIGTERM) is before
    entry = man.latest_entry()
    assert entry["step_state"] == {"epoch": 0, "step": 2,
                                   "rng": entry["step_state"]["rng"]}


def test_preemption_safe_requires_checkpoint():
    X, y = make_blobs(64, 10, 3)
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod = mx.mod.Module(mlp_sym())
    with pytest.raises(MXNetError, match="needs checkpoint"):
        mod.fit(it, num_epoch=1, preemption_safe=True)


# ---------------------------------------------------------------------------
# async checkpoint writer under fire (ckpt_write fault/hang point)
# ---------------------------------------------------------------------------

def test_fault_maybe_trip_hang_vs_fail(clean_faults):
    """One point, both flavors: arm() makes maybe_trip raise (failing
    disk), arm_hang() makes it stall (the SIGKILL-mid-save window)."""
    clean_faults.arm("ckpt_write")
    with pytest.raises(TransientError):
        faults.maybe_trip("ckpt_write")
    clean_faults.arm_hang("ckpt_write", seconds=0.15)
    t0 = time.monotonic()
    faults.maybe_trip("ckpt_write")
    assert time.monotonic() - t0 >= 0.15


def test_preemption_drains_async_writer_before_exit85(tmp_path,
                                                      clean_faults,
                                                      monkeypatch):
    """MXTPU_CKPT_ASYNC=1 + SIGTERM: the preemption save drains any
    in-flight background write and lands BLOCKING, so the exit-85
    contract ('checkpoint is on disk') is unchanged — proved by the
    resumed run being bit-identical."""
    monkeypatch.setenv("MXTPU_CKPT_ASYNC", "1")
    full = _run_fit(str(tmp_path / "full"), 3)
    cut_dir = str(tmp_path / "cut")
    assert _run_fit(cut_dir, 3, preempt_after=5) is None
    man = CheckpointManager(cut_dir)
    # on disk and discoverable at exit time — no pending writer state
    entry = man.latest_entry()
    assert entry["step_state"]["epoch"] == 1
    assert entry["files"]  # checksummed like any save
    resumed = _run_fit(cut_dir, 3, resume=True)
    for name in full:
        assert np.array_equal(full[name], resumed[name]), name


# ---------------------------------------------------------------------------
# staging / collective fault points (the watchdog's production targets,
# reproducible on CPU)
# ---------------------------------------------------------------------------

def test_stage_fault_surfaces_to_consumer(clean_faults):
    from mxnet_tpu.dataflow import DevicePrefetchIter
    X = np.arange(64, dtype="f").reshape(16, 4)
    base = mx.io.NDArrayIter(X, np.zeros(16, "f"), batch_size=4)
    clean_faults.arm("stage_batch")
    it = DevicePrefetchIter(base, stage=None, depth=2)
    try:
        with pytest.raises(TransientError, match="stage_batch"):
            for _ in it:
                pass
    finally:
        it.close()


def test_stage_hang_then_recovers(clean_faults):
    """A short injected staging stall delays but does not lose the batch
    (the long-stall variant is what the watchdog drill kills)."""
    from mxnet_tpu.dataflow import DevicePrefetchIter
    X = np.arange(64, dtype="f").reshape(16, 4)
    base = mx.io.NDArrayIter(X, np.zeros(16, "f"), batch_size=4)
    clean_faults.arm_hang("hang_stage", seconds=0.3)
    it = DevicePrefetchIter(base, stage=None, depth=2)
    try:
        seen = [b.data[0].asnumpy().copy() for b in it]
    finally:
        it.close()
    assert len(seen) == 4
    np.testing.assert_allclose(seen[0], X[:4])


def test_collective_fault_point(clean_faults):
    from mxnet_tpu.distributed import Collective
    coll = Collective()
    x = np.ones((3,), "f")
    np.testing.assert_allclose(coll.allreduce_sum(x), x)  # clean pass
    clean_faults.arm("collective")
    with pytest.raises(TransientError, match="peer is gone"):
        coll.allreduce_sum(x)
    clean_faults.arm_hang("hang_collective", seconds=0.2)
    t0 = time.monotonic()
    np.testing.assert_allclose(coll.broadcast(x), x)
    assert time.monotonic() - t0 >= 0.2


# ---------------------------------------------------------------------------
# supervise.py policy (plain-python children: fast, no jax)
# ---------------------------------------------------------------------------

def _run_supervise(tmp_path, script_body, *args):
    script = tmp_path / "child.py"
    script.write_text(textwrap.dedent(script_body))
    cmd = [sys.executable, SUPERVISE, "--backoff", "0",
           *args, "--", sys.executable, str(script)]
    return subprocess.run(cmd, capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))


def test_supervise_relaunches_on_preempt_code_with_resume_env(tmp_path):
    res = _run_supervise(tmp_path, """
        import json, os, sys
        runs = []
        if os.path.exists("runs.json"):
            runs = json.load(open("runs.json"))
        runs.append(os.environ.get("MXTPU_RESUME"))
        json.dump(runs, open("runs.json", "w"))
        sys.exit(85 if len(runs) == 1 else 0)
    """, "--max-restarts", "2")
    assert res.returncode == 0, res.stderr
    runs = json.load(open(tmp_path / "runs.json"))
    # first launch: no resume env; relaunch: MXTPU_RESUME=1
    assert runs == [None, "1"]
    assert "graceful preemption" in res.stderr


def test_supervise_relaunches_on_watchdog_code(tmp_path):
    res = _run_supervise(tmp_path, """
        import os, sys
        if os.environ.get("MXTPU_RESUME") == "1":
            sys.exit(0)
        sys.exit(87)
    """, "--max-restarts", "1")
    assert res.returncode == 0, res.stderr
    assert "watchdog abort" in res.stderr


def test_supervise_propagates_ordinary_failure(tmp_path):
    res = _run_supervise(tmp_path, "import sys; sys.exit(3)\n",
                         "--max-restarts", "5")
    assert res.returncode == 3
    assert "not a preempt/watchdog code" in res.stderr


def test_supervise_restart_budget_exhaustion(tmp_path):
    res = _run_supervise(tmp_path, """
        import json, os, sys
        n = 0
        if os.path.exists("n.json"):
            n = json.load(open("n.json"))
        json.dump(n + 1, open("n.json", "w"))
        sys.exit(85)
    """, "--max-restarts", "2")
    assert res.returncode == 85
    assert json.load(open(tmp_path / "n.json")) == 3  # 1 launch + 2 retries
    assert "budget (2) exhausted" in res.stderr


def test_supervise_retry_any_spends_budget_on_other_codes(tmp_path):
    res = _run_supervise(tmp_path, """
        import os, sys
        sys.exit(0 if os.environ.get("MXTPU_RESUME") == "1" else 9)
    """, "--max-restarts", "1", "--retry-any")
    assert res.returncode == 0, res.stderr


# ---------------------------------------------------------------------------
# the end-to-end chaos drill (subprocesses, real signals, real exits)
# ---------------------------------------------------------------------------

DRILL_SCRIPT = """
import os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")  # the env may pin a TPU plugin
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.resilience import faults

def make_blobs(n, d, c, seed=4):
    rs = np.random.RandomState(seed)
    centers = rs.randn(c, d) * 3
    X = np.concatenate([centers[i] + rs.randn(n // c, d)
                        for i in range(c)]).astype("f")
    y = np.concatenate([np.full(n // c, i) for i in range(c)]).astype("f")
    perm = rs.permutation(len(X))
    return X[perm], y[perm]

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
sym = mx.sym.SoftmaxOutput(net, name="softmax")

X, y = make_blobs(256, 10, 3)
it = mx.io.NDArrayIter(X, y, batch_size=64)
mod = mx.mod.Module(sym)
mx.random.seed(21)

resuming = os.environ.get("MXTPU_RESUME") == "1"
preempt_at = os.environ.get("CHAOS_PREEMPT_AT")
if preempt_at and not resuming:
    # in-band preemption: a REAL SIGTERM to ourselves at step boundary N
    # (fit's "preempt" fault point) — deterministic, signal path included
    faults.arm("preempt", times=1, after=int(preempt_at))

mod.fit(it, num_epoch=3, kvstore="tpu", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        initializer=mx.initializer.Xavier(),
        checkpoint=os.environ["CHAOS_DIR"],
        preemption_safe=bool(preempt_at))
mod.save_params(os.environ["CHAOS_OUT"])
"""


def _drill_env(tmp_path, name, preempt_at=None):
    env = dict(os.environ)
    env["CHAOS_DIR"] = str(tmp_path / name)
    env["CHAOS_OUT"] = str(tmp_path / (name + ".params"))
    env.pop("MXTPU_RESUME", None)
    env.pop("MXTPU_FAULTS", None)
    if preempt_at is not None:
        env["CHAOS_PREEMPT_AT"] = str(preempt_at)
    else:
        env.pop("CHAOS_PREEMPT_AT", None)
    return env


def _load_params(path):
    return {k: v.asnumpy() for k, v in mx.nd.load(str(path)).items()}


@pytest.mark.chaos
def test_chaos_drill_kill_and_resume_bit_identical(tmp_path):
    """THE drill: train, SIGTERM mid-epoch, relaunch via supervise.py,
    and the finished run's params are bit-identical to an uninterrupted
    run's."""
    script = tmp_path / "train.py"
    script.write_text(DRILL_SCRIPT % {"repo": REPO})

    # uninterrupted baseline
    res = subprocess.run([sys.executable, str(script)],
                         env=_drill_env(tmp_path, "full"),
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]

    # supervised run, killed at the 6th step boundary (mid-epoch 1 of 3
    # x 4 steps), relaunched by the supervisor with MXTPU_RESUME=1
    res = subprocess.run(
        [sys.executable, SUPERVISE, "--max-restarts", "2", "--backoff",
         "0", "--", sys.executable, str(script)],
        env=_drill_env(tmp_path, "cut", preempt_at=5),
        capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "graceful preemption — relaunch 1/2" in res.stderr

    # the interruption really happened mid-epoch (a partial checkpoint
    # was written and later replaced by the complete epoch-end save)
    assert "saved mid-epoch checkpoint (epoch 1, step 2)" in res.stderr
    man = CheckpointManager(str(tmp_path / "cut"))
    assert man.latest() == 3
    assert "step_state" not in man.latest_entry()

    full = _load_params(tmp_path / "full.params")
    cut = _load_params(tmp_path / "cut.params")
    assert set(full) == set(cut)
    for name in full:
        assert np.array_equal(full[name], cut[name]), name


CKPT_DRILL_SCRIPT = """
import os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.resilience import faults

def make_blobs(n, d, c, seed=4):
    rs = np.random.RandomState(seed)
    centers = rs.randn(c, d) * 3
    X = np.concatenate([centers[i] + rs.randn(n // c, d)
                        for i in range(c)]).astype("f")
    y = np.concatenate([np.full(n // c, i) for i in range(c)]).astype("f")
    perm = rs.permutation(len(X))
    return X[perm], y[perm]

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
sym = mx.sym.SoftmaxOutput(net, name="softmax")

X, y = make_blobs(256, 10, 3)
it = mx.io.NDArrayIter(X, y, batch_size=64)
mod = mx.mod.Module(sym)
mx.random.seed(21)

if os.environ.get("CHAOS_CKPT_HANG") and \\
        os.environ.get("MXTPU_RESUME") != "1":
    # wedge the background writer mid-save of epoch 2: its data files
    # are on disk, the manifest is not — then the parent SIGKILLs us
    faults.arm_hang("ckpt_write", seconds=3600, after=1)

mod.fit(it, num_epoch=3, kvstore="tpu", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
        initializer=mx.initializer.Xavier(),
        checkpoint=os.environ["CHAOS_DIR"])
mod.save_params(os.environ["CHAOS_OUT"])
"""


@pytest.mark.chaos
def test_chaos_sigkill_mid_async_save_resumes_previous_epoch(tmp_path):
    """SIGKILL delivered while the async writer is mid-save of epoch 2
    (epoch-2 files written, manifest not yet published): the torn save
    must never be restorable — the relaunch resumes from epoch 1 and
    finishes bit-identical to an uninterrupted run."""
    script = tmp_path / "train.py"
    script.write_text(CKPT_DRILL_SCRIPT % {"repo": REPO})
    env = _drill_env(tmp_path, "full")
    env["MXTPU_CKPT_ASYNC"] = "1"
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]

    cut_dir = tmp_path / "cut"
    env = _drill_env(tmp_path, "cut")
    env["MXTPU_CKPT_ASYNC"] = "1"
    env["CHAOS_CKPT_HANG"] = "1"
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        # the wedged writer has already landed epoch 2's data files
        # (states is written last before the hang point) — kill inside
        # the hang, before the manifest could ever be published
        deadline = time.monotonic() + 120
        states2 = cut_dir / "checkpoint-0002.states"
        while time.monotonic() < deadline and not states2.exists():
            assert proc.poll() is None, "drill process died early"
            time.sleep(0.05)
        assert states2.exists(), "epoch-2 save never started"
        time.sleep(0.5)  # let the writer reach the armed hang
        proc.kill()      # SIGKILL: no cleanup, no atexit, no finally
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    # the torn epoch-2 save is not restorable: manifest still ends at 1
    man = CheckpointManager(str(cut_dir))
    assert man.latest() == 1
    entry = man.latest_entry()
    assert entry["epoch"] == 1 and entry["files"]

    # relaunch-and-resume lands on epoch 1 and retrains to parity
    env = _drill_env(tmp_path, "cut")
    env["MXTPU_CKPT_ASYNC"] = "1"
    env["MXTPU_RESUME"] = "1"
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    # the resumed run re-saved epochs 2 and 3 (replacing the torn files)
    assert man.latest() == 3

    full = _load_params(tmp_path / "full.params")
    cut = _load_params(tmp_path / "cut.params")
    assert set(full) == set(cut)
    for name in full:
        assert np.array_equal(full[name], cut[name]), name


SHARDED_DRILL_SCRIPT = """
import os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.parallel import SPMDTrainer, build_mesh
from mxnet_tpu.resilience import CheckpointManager, faults

def make_blobs(n, d, c, seed=4):
    rs = np.random.RandomState(seed)
    centers = rs.randn(c, d) * 3
    X = np.concatenate([centers[i] + rs.randn(n // c, d)
                        for i in range(c)]).astype("f")
    y = np.concatenate([np.full(n // c, i) for i in range(c)]).astype("f")
    perm = rs.permutation(len(X))
    return X[perm], y[perm]

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
sym = mx.sym.SoftmaxOutput(net, name="softmax")

world = int(os.environ["CHAOS_WORLD"])
trainer = SPMDTrainer(sym, "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9},
                      mesh=build_mesh({"dp": world},
                                      jax.devices()[:world]),
                      grad_sync="zero3")
trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
mx.random.seed(21)
trainer.init_params(mx.initializer.Xavier())
mgr = CheckpointManager(os.environ["CHAOS_DIR"])

start = 0
resuming = os.environ.get("MXTPU_RESUME") == "1"
if resuming:
    start = trainer.restore(mgr)
    if os.environ.get("CHAOS_RESTORE_OUT"):
        # the restored-state probe: what the walk-back + elastic
        # assembly actually put on THIS world's mesh, dumped before a
        # single new step can touch it
        arg, _ = trainer.get_params()
        mx.nd.save(os.environ["CHAOS_RESTORE_OUT"], dict(arg))

X, y = make_blobs(192, 10, 3)  # 192 = 3 full 64-batches, no ragged tail
for epoch in range(start, 3):
    for i in range(0, 192, 64):
        trainer.step(X[i:i + 64], y[i:i + 64])
    if os.environ.get("CHAOS_SHARD_HANG") and not resuming \\
            and epoch == 1:
        # wedge the epoch-2 sharded save BETWEEN blob writes: shards
        # 0 and 1 land on disk, the hang holds before shard 2, the
        # manifest is never published — then the parent SIGKILLs us.
        # each blob passes the point TWICE (pre-write trip + the
        # atomic publish check), so blobs 0+1 burn 4 'after' hits
        faults.arm_hang("shard_write", seconds=3600, after=4)
    trainer.save_checkpoint(mgr, epoch + 1)
    if os.environ.get("CHAOS_E1_OUT") and epoch == 0:
        arg, _ = trainer.get_params()
        mx.nd.save(os.environ["CHAOS_E1_OUT"], dict(arg))
trainer.close()
"""


@pytest.mark.chaos
def test_chaos_sigkill_mid_shard_write_elastic_resume(tmp_path):
    """THE sharded drill: a world=4 zero3 trainer is SIGKILLed inside
    a sharded-native save with 2 of 4 shard blobs on disk and the
    manifest unpublished.  The torn shard set must never be
    restorable — the directory walks back to epoch 1 — and the resume
    is ELASTIC: relaunches at world=2 AND world=8 both restore the
    epoch-1 state bit-identical to the world=4 run's, then train on
    to completion publishing their own shard sets."""
    import shutil
    script = tmp_path / "train.py"
    script.write_text(SHARDED_DRILL_SCRIPT % {"repo": REPO})

    def env_for(name, world, **extra):
        env = _drill_env(tmp_path, name)
        env["MXTPU_CKPT_SHARDED"] = "1"
        env["CHAOS_WORLD"] = str(world)
        env.update({k: str(v) for k, v in extra.items()})
        return env

    # the cut run: it publishes epoch 1 cleanly (dumping its params as
    # the bit-parity reference), then gets wedged between blob 1 and
    # blob 2 of epoch 2's save and SIGKILLed — no cleanup, no atexit
    e1 = tmp_path / "e1.params"
    cut_dir = tmp_path / "cut"
    proc = subprocess.Popen(
        [sys.executable, str(script)],
        env=env_for("cut", 4, CHAOS_SHARD_HANG=1, CHAOS_E1_OUT=e1),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        sentinel = cut_dir / "checkpoint-0002.params.s001-of-004"
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not sentinel.exists():
            assert proc.poll() is None, "drill process died early"
            time.sleep(0.05)
        assert sentinel.exists(), "epoch-2 sharded save never started"
        time.sleep(0.5)  # let the writer reach the armed hang
        proc.kill()
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    # mid-shard-write forensics: a PARTIAL shard set (blobs 0 and 1,
    # no blob 2) with the manifest still ending at epoch 1 — the torn
    # epoch is invisible to restore
    man = CheckpointManager(str(cut_dir))
    assert man.latest() == 1
    assert man.latest_entry()["shard_set"]["world"] == 4
    assert (cut_dir / "checkpoint-0002.params.s000-of-004").exists()
    assert not (cut_dir / "checkpoint-0002.params.s002-of-004").exists()

    # elastic resume from the torn directory at world=2 AND world=8
    # (8 needs its own copy: the first resume re-publishes 2 and 3)
    cut8 = tmp_path / "cut8"
    shutil.copytree(cut_dir, cut8)
    restored = {}
    for world, name in ((2, "cut"), (8, "cut8")):
        probe = tmp_path / ("restored-w%d.params" % world)
        env = env_for(name, world, CHAOS_RESTORE_OUT=probe)
        env["MXTPU_RESUME"] = "1"
        res = subprocess.run([sys.executable, str(script)], env=env,
                             capture_output=True, text=True,
                             timeout=300)
        assert res.returncode == 0, (world, res.stderr[-2000:])
        restored[world] = _load_params(probe)
        m = CheckpointManager(str(tmp_path / name))
        assert m.latest() == 3
        assert m.latest_entry()["shard_set"]["world"] == world

    # both restores are bit-identical to the world=4 epoch-1 state:
    # shard-count-mismatched assembly changed NOTHING
    want = _load_params(e1)
    for world in (2, 8):
        assert set(restored[world]) == set(want)
        for k in want:
            assert np.array_equal(restored[world][k], want[k]), \
                (world, k)


# ---------------------------------------------------------------------------
# serving drills: SIGTERM drain + wedged-forward watchdog relaunch
# (docs/how_to/serving.md — the daemon side of the survival story)
# ---------------------------------------------------------------------------

SERVE = os.path.join(REPO, "tools", "serve.py")

#: relaunch-aware daemon wrapper: identical to running tools/serve.py,
#: except a supervised RELAUNCH (MXTPU_RESUME=1) strips the armed fault
#: so the second life serves clean — the drill's "fault strikes once"
#: determinism, same pattern as CKPT_DRILL_SCRIPT
SERVE_DRILL_SCRIPT = """
import os, runpy, sys
sys.path.insert(0, %(repo)r)
if os.environ.get("MXTPU_RESUME") == "1":
    os.environ.pop("MXTPU_FAULTS", None)
import jax
jax.config.update("jax_platforms", "cpu")
sys.argv = ["serve.py",
            "--model", "mlp=" + os.environ["SERVE_PREFIX"] + ":1",
            "--input-shape", "data=32", "--port", "0",
            "--port-file", os.environ["SERVE_PORT_FILE"],
            "--buckets", "1,2,4,8", "--max-wait-ms", "5"]
runpy.run_path(%(serve)r, run_name="__main__")
"""


def _save_serve_mlp(tmp_path):
    from mxnet_tpu.model import save_checkpoint
    sym = mlp_sym(num_classes=10, nh=32)
    rs = np.random.RandomState(0)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 32))
    args = {n: mx.nd.array(rs.uniform(-0.3, 0.3, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    prefix = str(tmp_path / "mlp")
    save_checkpoint(prefix, 1, sym, args, {}, blocking=True)
    return prefix


def _serve_request(port, timeout=10.0):
    """One POST /predict/mlp; returns (status, payload) or (None, err)."""
    from mxnet_tpu.serving import ServeClient
    cli = ServeClient("127.0.0.1", port, timeout=timeout)
    try:
        return cli.predict("mlp", np.zeros((32,), "f"))
    except Exception as e:  # noqa: BLE001 — daemon down/wedged
        return None, {"error": str(e)}
    finally:
        cli.close()


def _wait_port_file(path, proc, deadline_s=120):
    deadline = time.monotonic() + deadline_s
    while not os.path.exists(path):
        assert proc.poll() is None, "daemon died before listening"
        assert time.monotonic() < deadline, "daemon never listened"
        time.sleep(0.05)
    return int(open(path).read().split(":")[1])


@pytest.mark.chaos
def test_serving_drill_sigterm_drains_in_flight_requests(tmp_path):
    """SIGTERM lands while requests are queued in an open batch window:
    every ACCEPTED request still gets its 200 (no 5xx for accepted
    work), post-drain arrivals are refused, and the daemon exits 0."""
    import threading

    prefix = _save_serve_mlp(tmp_path)
    port_file = str(tmp_path / "port")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, SERVE, "--model", "mlp=%s:1" % prefix,
         "--input-shape", "data=32", "--port", "0",
         "--port-file", port_file, "--buckets", "32",
         "--max-wait-ms", "60000"],  # a wide-open batch window: the 12
        # requests below stay QUEUED until SIGTERM lands (the drain
        # flushes the window immediately, so the test is still fast).
        # The window must comfortably outlast the accepted-count poll
        # below — a 1500ms window used to dispatch the batch BEFORE the
        # SIGTERM whenever full-suite load stretched a 0.4s sleep past
        # it, failing the done_at >= sigterm_at assertion ~4/5 runs.
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    try:
        port = _wait_port_file(port_file, proc)
        from mxnet_tpu.serving import ServeClient
        ServeClient("127.0.0.1", port).wait_ready(60)

        results = [None] * 12
        done_at = [None] * 12

        def fire(i):
            results[i] = _serve_request(port, timeout=90)
            done_at[i] = time.monotonic()

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        # deterministic arming: poll /stats until ALL 12 requests are
        # verifiably accepted AND still queued (none dispatched, none
        # refused) — a fixed sleep raced both edges under load: too
        # short and a late arrival got the post-drain 503, too long
        # and the batch window dispatched before the signal
        cli = ServeClient("127.0.0.1", port)
        try:
            deadline = time.monotonic() + 60
            while True:
                _, stats = cli.stats()
                if stats.get("counters", {}).get("accepted", 0) == 12 \
                        and stats.get("queue_depth", {}) \
                                 .get("mlp", 0) == 12:
                    break
                assert time.monotonic() < deadline, \
                    "12 requests never all queued: %s" % (stats,)
                time.sleep(0.02)
        finally:
            cli.close()
        proc.send_signal(signal.SIGTERM)
        sigterm_at = time.monotonic()
        for t in threads:
            t.join(timeout=120)

        # every accepted request completed 200 with a real result —
        # and completed AFTER the SIGTERM (they really were in flight:
        # the 1500ms batch window was still holding them queued)
        for i, (status, payload) in enumerate(results):
            assert status == 200, (i, payload)
            assert len(payload["outputs"][0]) == 10
            assert done_at[i] >= sigterm_at, (
                "request %d completed before SIGTERM — nothing was in "
                "flight, the drill proved nothing" % i)
        rc = proc.wait(timeout=60)
        assert rc == 0, proc.stderr.read()[-2000:]
        err = proc.stderr.read()
        assert "drained" in err
        # post-drain arrival: refused (503/conn error), never a 5xx==500
        status, _ = _serve_request(port, timeout=5)
        assert status in (None, 503)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.chaos
def test_serving_drill_wedged_forward_watchdog_supervise_relaunch(
        tmp_path):
    """The serving half of the watchdog story: a wedged batch forward
    (MXTPU_FAULTS=hang_serve_forward:1, the env plumbing a pod drill
    would use) trips the StepWatchdog inside its 4s budget -> stack
    dump + exit 87 -> supervise.py relaunches the daemon
    (MXTPU_RESUME=1 strips the fault) -> traffic is served again, warm
    via the shared compile cache.  A supervisor SIGTERM then drains the
    relaunched daemon to rc 0."""
    prefix = _save_serve_mlp(tmp_path)
    script = tmp_path / "serve_drill.py"
    script.write_text(SERVE_DRILL_SCRIPT
                      % {"repo": REPO, "serve": SERVE})
    port_file = str(tmp_path / "port")
    debug_dir = tmp_path / "debug"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_RESUME", None)
    env.update(SERVE_PREFIX=prefix, SERVE_PORT_FILE=port_file,
               MXTPU_FAULTS="hang_serve_forward:1",
               MXTPU_STEP_TIMEOUT="4",
               MXTPU_DEBUG_DIR=str(debug_dir),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla_cache"))
    proc = subprocess.Popen(
        [sys.executable, SUPERVISE, "--max-restarts", "1", "--backoff",
         "0", "--", sys.executable, str(script)],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    try:
        port = _wait_port_file(port_file, proc)
        # this request hits the armed hang: the dispatch wedges, the
        # watchdog fires exit 87, supervise relaunches — keep knocking
        # (re-reading the port file: the relaunch binds a new port)
        # until the reborn daemon answers 200
        deadline = time.monotonic() + 180
        served = False
        while time.monotonic() < deadline:
            try:
                port = int(open(port_file).read().split(":")[1])
            except (OSError, ValueError, IndexError):
                pass
            status, payload = _serve_request(port, timeout=5)
            if status == 200:
                served = True
                break
            assert proc.poll() is None, \
                "supervisor gave up: %s" % proc.stderr.read()[-3000:]
            time.sleep(0.2)
        assert served, "daemon never served after the watchdog relaunch"

        # shut the relaunched daemon down through the supervisor
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        err = proc.stderr.read()
        assert rc == 0, err[-3000:]
        assert "watchdog abort (hung step)" in err     # supervise's log
        assert "StepWatchdog" in err                   # the dump itself
        assert "exceeded its 4.0s budget" in err
        dumps = list(debug_dir.iterdir())
        assert len(dumps) == 1 and \
            dumps[0].name.startswith("watchdog-")
        assert "serve mlp batch" in dumps[0].read_text()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.chaos
def test_watchdog_drill_stalled_step_dumps_and_aborts(tmp_path):
    """A deliberately stalled fused step (MXTPU_FAULTS hang injection)
    trips the watchdog within the budget: thread stacks land in
    MXTPU_DEBUG_DIR and the process exits WATCHDOG_EXIT_CODE."""
    script = tmp_path / "train.py"
    script.write_text(DRILL_SCRIPT % {"repo": REPO})
    debug_dir = tmp_path / "debug"
    env = _drill_env(tmp_path, "hang")
    # stall step 3 for 60s against a 3s fixed budget; hang via the env
    # syntax so the injection rides the same MXTPU_FAULTS plumbing a
    # pod-level drill would use
    env["MXTPU_FAULTS"] = "hang_step:1@2"
    env["MXTPU_STEP_TIMEOUT"] = "5"
    env["MXTPU_DEBUG_DIR"] = str(debug_dir)
    t0 = time.monotonic()
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=240)
    elapsed = time.monotonic() - t0
    assert res.returncode == WATCHDOG_EXIT_CODE, \
        (res.returncode, res.stderr[-2000:])
    assert "StepWatchdog" in res.stderr
    assert "exceeded its 5.0s budget" in res.stderr
    dumps = list(debug_dir.iterdir())
    assert len(dumps) == 1
    report = dumps[0].read_text()
    assert "--- thread" in report          # stack dump
    assert "maybe_hang" in report          # names the wedged frame
    assert "jax backend: cpu" in report    # device/mesh state
    # fired within the timeout, not at the 60s hang's natural end
    assert elapsed < 120


# ---------------------------------------------------------------------------
# data-service worker drills (mxnet_tpu/data_service/): a decode worker
# is a real OS process — kill it with a real SIGKILL / wedge it with a
# real injected hang and prove the epoch still delivers every record
# exactly once, bit-identical to an undisturbed run.
# ---------------------------------------------------------------------------

def _ds_rec_dataset(tmp_path, n=41):
    import cv2
    from mxnet_tpu import recordio
    path = str(tmp_path / "chaos.rec")
    idx = str(tmp_path / "chaos.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    rs = np.random.RandomState(0)
    for i in range(n):
        img = rs.randint(0, 255, (48, 48, 3)).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img)
        assert ok
        w.write_idx(i, recordio.pack(
            mx.recordio.IRHeader(0, float(i % 7), i, 0), buf.tobytes()))
    w.close()
    return path, idx


def _ds_iter(path, idx, workers, **over):
    kw = dict(path_imgrec=path, path_imgidx=idx, data_shape=(3, 32, 32),
              batch_size=8, shuffle=True, rand_crop=True, rand_mirror=True,
              seed=5, dtype="float32", host_batches=True,
              data_service=True, preprocess_threads=workers)
    kw.update(over)
    return mx.io.ImageRecordIter(**kw)


def _ds_stream(it):
    return [(np.array(b.data[0]).copy(), np.array(b.label[0]).copy(),
             b.pad) for b in it]


@pytest.mark.chaos
def test_data_service_drill_sigkill_worker_mid_epoch(tmp_path):
    """SIGKILL one decode worker after the first delivered batch: the
    service respawns it, the epoch completes with no duplicated or
    dropped records, and the delivered batch stream is bit-identical to
    an uninterrupted seeded run — including the NEXT epoch."""
    path, idx = _ds_rec_dataset(tmp_path)
    it = _ds_iter(path, idx, workers=2)
    ref_e1 = _ds_stream(it)
    it.reset()
    ref_e2 = _ds_stream(it)
    it.close()

    it = _ds_iter(path, idx, workers=2)
    got = []
    for n, b in enumerate(it):
        got.append((np.array(b.data[0]).copy(),
                    np.array(b.label[0]).copy(), b.pad))
        if n == 0:
            victims = it._service.worker_pids()
            assert len(victims) == 2
            os.kill(victims[0], signal.SIGKILL)
    # the collector brings a dead worker back while it waits for a batch
    # that worker still owes; a victim that had already put its whole
    # share of this short epoch into its ring (a consumer slowed by the
    # whole suite's load) owes nothing more, and is brought back when the
    # next epoch first waits for it.  The service has no monitor of its
    # own in between, so the count is taken after the next epoch: one
    # respawn either way
    it.reset()
    got_e2 = _ds_stream(it)
    st = it.stats()
    assert sum(w["respawns"] for w in st["workers"].values()) == 1, st
    it.close()

    assert len(got) == len(ref_e1)
    for i, (a, b) in enumerate(zip(ref_e1, got)):
        assert a[2] == b[2], ("pad", i)
        np.testing.assert_array_equal(a[1], b[1], err_msg="labels %d" % i)
        np.testing.assert_array_equal(a[0], b[0], err_msg="data %d" % i)
    for i, (a, b) in enumerate(zip(ref_e2, got_e2)):
        np.testing.assert_array_equal(a[0], b[0],
                                      err_msg="epoch2 data %d" % i)


@pytest.mark.chaos
def test_data_service_drill_hung_worker_heartbeat_respawn(
        tmp_path, monkeypatch, clean_faults):
    """A WEDGED (not dead) worker: MXTPU_FAULTS=hang_data_worker:1
    stalls one worker's decode loop for an hour.  Its heartbeat goes
    stale, the collector kills + respawns it (fault stripped from the
    child env), and the stream still matches the undisturbed run."""
    path, idx = _ds_rec_dataset(tmp_path)
    it = _ds_iter(path, idx, workers=2)
    ref = _ds_stream(it)
    it.close()

    monkeypatch.setenv("MXTPU_FAULTS", "hang_data_worker:1")
    monkeypatch.setenv("MXTPU_DATA_HEARTBEAT_S", "2")
    t0 = time.monotonic()
    it = _ds_iter(path, idx, workers=2)
    got = _ds_stream(it)
    st = it.stats()
    it.close()
    assert sum(w["respawns"] for w in st["workers"].values()) >= 1, st
    assert time.monotonic() - t0 < 120   # heartbeat fired, not the hang
    assert len(got) == len(ref)
    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(a[0], b[0], err_msg="data %d" % i)
        np.testing.assert_array_equal(a[1], b[1], err_msg="labels %d" % i)


# ---------------------------------------------------------------------------
# network data-plane drill (mxnet_tpu/data_service/net.py +
# tools/data_server.py): the PR-7 SIGKILL drill one layer up — kill a
# REAL remote server process mid-epoch on a loopback 2-server run and
# prove connection eviction, reconnect-resume at the last consumed
# batch, and a stream bit-identical to the undisturbed run including
# the next epoch.
# ---------------------------------------------------------------------------

from conftest import spawn_data_server as _spawn_data_server  # noqa: E402


@pytest.mark.chaos
def test_data_net_drill_sigkill_server_mid_epoch(tmp_path, monkeypatch):
    """SIGKILL data server 0 (a real tools/data_server.py process)
    after the second delivered batch; the host's "supervisor" (this
    test) respawns it on the same port.  The consumer's heartbeat/
    reconnect machinery evicts the dead connection, the handshake
    resumes at the last consumed batch, the epoch completes, and the
    whole 2-epoch stream is bit-identical to an undisturbed run —
    exactly-once delivery across a server kill."""
    monkeypatch.setenv("MXTPU_DATA_NET_TIMEOUT_S", "5")
    monkeypatch.setenv("MXTPU_DATA_NET_RECONNECT_S", "0.25")
    monkeypatch.setenv("MXTPU_DATA_NET_RETRIES", "60")
    path, idx = _ds_rec_dataset(tmp_path)
    p0, addr0 = _spawn_data_server(tmp_path, 0)
    p1, addr1 = _spawn_data_server(tmp_path, 1)
    port0 = int(addr0.rsplit(":", 1)[1])
    servers = "%s,%s" % (addr0, addr1)
    procs = [p0, p1]
    try:
        it = _ds_iter(path, idx, workers=1, data_service=servers)
        ref_e1 = _ds_stream(it)
        it.reset()
        ref_e2 = _ds_stream(it)
        it.close()

        it = _ds_iter(path, idx, workers=1, data_service=servers)
        got = []
        for n, b in enumerate(it):
            got.append((np.array(b.data[0]).copy(),
                        np.array(b.label[0]).copy(), b.pad))
            if n == 1:
                os.kill(p0.pid, signal.SIGKILL)
                p0.wait()
                # the remote host's supervisor brings the server back
                # on its well-known port; the consumer reconnects
                procs[0], new_addr = _spawn_data_server(
                    tmp_path, 0, port=port0)
                assert new_addr == addr0
        st = it.stats()
        it.reset()
        got_e2 = _ds_stream(it)
        it.close()

        reconnects = sum(s["reconnects"]
                         for s in st["servers"].values())
        assert reconnects >= 1, st
        assert len(got) == len(ref_e1)
        for i, (a, b) in enumerate(zip(ref_e1, got)):
            assert a[2] == b[2], ("pad", i)
            np.testing.assert_array_equal(a[1], b[1],
                                          err_msg="labels %d" % i)
            np.testing.assert_array_equal(a[0], b[0],
                                          err_msg="data %d" % i)
        for i, (a, b) in enumerate(zip(ref_e2, got_e2)):
            np.testing.assert_array_equal(a[0], b[0],
                                          err_msg="epoch2 data %d" % i)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# ---------------------------------------------------------------------------
# fleet drills (mxnet_tpu/fleet/): replicas are real serve.py daemons
# behind the real router — SIGKILL one mid-traffic and prove eviction,
# fail-once-never-retry, warm rejoin from the AOT store, and a clean
# fleet-wide SIGTERM drain (docs/how_to/fleet.md).
# ---------------------------------------------------------------------------

FLEET = os.path.join(REPO, "tools", "fleet.py")


@pytest.mark.chaos
def test_fleet_drill_sigkill_replica_evict_reroute_rejoin_drain(
        tmp_path):
    """The ISSUE-11 drill, upgraded to the ISSUE-20 exactly-once
    contract, end to end on real daemons:

    1. a 2-replica fleet serves traffic (the warm store is built on
       the way up);
    2. SIGKILL the model's HOME replica mid-traffic — requests in
       flight to it are resent ONCE to the survivor with the same
       idempotency key: their clients see 200/``retried: true``,
       NEVER a 502 (the old fail-once stance is gone);
    3. the router evicts the dead replica on heartbeat age and new
       traffic reroutes to the survivor (200s continue);
    4. the controller respawns the victim, which rejoins WARM — its
       relaunch log shows the AOT-store load, not a compile;
    5. fleet-wide SIGTERM drains every replica to rc 0 and the fleet
       exits 0.  No request ever goes unanswered (zero client-level
       hangs/exceptions).
    """
    import threading

    from mxnet_tpu.serving import ServeClient

    prefix = _save_serve_mlp(tmp_path)
    store = str(tmp_path / "store")
    run_dir = str(tmp_path / "run")
    port_file = str(tmp_path / "port")
    env = dict(os.environ,
               MXTPU_FLEET_HEARTBEAT_S="0.3",
               MXTPU_FLEET_EVICT_S="1.2",
               MXTPU_SERVE_MAX_WAIT_MS="1")
    proc = subprocess.Popen(
        [sys.executable, FLEET, "serve",
         "--model", "mlp=%s:1" % prefix,
         "--input-shape", "mlp:data=32", "--replicas", "2",
         "--device-sets", "cpu", "--buckets", "1,2,4",
         "--warm-store", store, "--run-dir", run_dir,
         "--port", "0", "--port-file", port_file],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    try:
        port = _wait_port_file(port_file, proc, deadline_s=300)
        results = []                 # (status, payload) per request
        exceptions = []
        stop = threading.Event()

        def traffic():
            cli = ServeClient("127.0.0.1", port, timeout=30)
            x = np.zeros(32, "f")
            try:
                while not stop.is_set():
                    try:
                        results.append(cli.predict("mlp", x, npy=True))
                    except Exception as e:  # noqa: BLE001 — a DROPPED
                        exceptions.append(e)  # response, contract-fatal
                    time.sleep(0.01)
            finally:
                cli.close()

        threads = [threading.Thread(target=traffic) for _ in range(2)]
        for t in threads:
            t.start()

        def _ok_count():
            return sum(1 for s, _ in results if s == 200)

        deadline = time.monotonic() + 60
        while _ok_count() < 20 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _ok_count() >= 20, "fleet never served baseline traffic"

        # -- kill the HOME replica (one model -> home is replica 0) --
        cli = ServeClient("127.0.0.1", port, timeout=30)
        status, stats = cli.stats()
        assert status == 200
        victim = stats["replicas"]["0"]
        assert victim["pid"], stats
        os.kill(victim["pid"], signal.SIGKILL)

        # eviction: within heartbeat+evict the fleet reports 1 healthy
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            status, h = cli.healthz()
            if status == 200 and h["replicas_healthy"] == 1:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("dead replica was never evicted")

        # traffic keeps flowing (rerouted to the survivor)
        base = _ok_count()
        deadline = time.monotonic() + 30
        while _ok_count() < base + 20 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _ok_count() >= base + 20, "traffic did not reroute"

        # respawn + WARM rejoin: healthy goes back to 2 and the
        # victim's relaunch warmed from the AOT store, not a compile
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            status, h = cli.healthz()
            if status == 200 and h["replicas_healthy"] == 2:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("respawned replica never rejoined")
        status, stats = cli.stats()
        assert stats["replicas"]["0"]["restarts"] >= 1
        log0 = open(os.path.join(run_dir, "replica-0.log")).read()
        assert "from the AOT store" in log0.split(
            "warmup-only")[-1], "respawn did not warm from the store"

        # the rejoined home serves again
        base = _ok_count()
        deadline = time.monotonic() + 30
        while _ok_count() < base + 10 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _ok_count() >= base + 10

        stop.set()
        for t in threads:
            t.join(timeout=30)
        cli.close()

        # -- the exactly-once ledger ---------------------------------
        # every request got exactly one answer, and the SIGKILL was
        # fully absorbed by the keyed resend: ZERO client-visible 502s;
        # every absorbed death surfaces as a 200 with retried:true and
        # reconciles against the router's retry counters — and
        # replica_errors (FINAL failures only) matches the 502 count,
        # i.e. stays zero
        assert not exceptions, "dropped responses: %r" % exceptions[:3]
        failed = [(s, p) for s, p in results if s != 200]
        n502 = sum(1 for s, _ in failed if s == 502)
        assert n502 == 0, "client-visible 502s: %r" % failed[:3]
        for s, p in failed:
            assert s == 503, (s, p)     # brief no-replica windows only
        retried_ok = sum(1 for s, p in results
                         if s == 200 and p.get("retried") is True)
        status, stats = ServeClient("127.0.0.1", port).stats()
        counters = stats["router"]["counters"]
        assert counters.get("replica_errors", 0) == n502 == 0
        assert counters.get("retry_ok", 0) >= retried_ok
        assert counters.get("retries", 0) >= counters.get("retry_ok", 0)
        # the kill happened mid-traffic: at least one request must have
        # actually ridden the resend path
        assert retried_ok >= 1, "the SIGKILL was never client-visible"

        # -- fleet-wide SIGTERM: every replica drains to rc 0 --------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        stderr = proc.stderr.read()
        assert rc == 0, stderr[-3000:]
        assert "replica exit codes {0: 0, 1: 0}" in stderr
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.chaos
def test_fleet_drill_gray_failure_eject_sigkill_exactly_once(tmp_path):
    """The ISSUE-20 drill: a real 3-replica fleet with one replica
    armed ``slow_replica`` and one SIGKILLed mid-traffic serves a
    mixed-tenant closed loop with ZERO client-visible 502s.

    1. replica 0 (home of the one model) is armed
       ``slow_replica`` via ``--replica-env`` — gray failure: fast
       /healthz, crawling predicts; hedging bounds the tail while the
       outlier detector watches its reported ``p99_recent``;
    2. the detector EJECTS it (``ejected: true`` on /stats, out of
       ``healthy()``) without ever violating the routable floor;
    3. replica 2 is SIGKILLed mid-traffic — the keyed resend absorbs
       every in-flight death: zero 502s in the closed loop;
    4. once the armed fault exhausts, the slow replica's window washes
       clean and it REJOINS via the half-open probe
       (``eject_rejoins`` counts it);
    5. the ``dup_request`` fault (armed fleet-wide, consumed router-
       side) re-sends delivered requests — the replica dedup cache
       collapses them (``dedup_hits`` > 0 end to end over HTTP);
    6. duplicate executions stay bounded: extra executions beyond
       client sends are covered by hedges + retries + dup_requests.
    """
    import threading

    from mxnet_tpu.serving import ServeClient

    prefix = _save_serve_mlp(tmp_path)
    store = str(tmp_path / "store")
    run_dir = str(tmp_path / "run")
    port_file = str(tmp_path / "port")
    env = dict(os.environ,
               MXTPU_FLEET_HEARTBEAT_S="0.3",
               MXTPU_FLEET_EVICT_S="1.2",
               MXTPU_FLEET_EJECT_X="3",
               MXTPU_FLEET_HEDGE_PCT="95",
               MXTPU_FLEET_HEDGE_MIN_MS="120",
               MXTPU_FAULTS="dup_request:5",
               MXTPU_SERVE_MAX_WAIT_MS="1")
    proc = subprocess.Popen(
        [sys.executable, FLEET, "serve",
         "--model", "mlp=%s:1" % prefix,
         "--input-shape", "mlp:data=32", "--replicas", "3",
         "--device-sets", "cpu", "--buckets", "1,2,4",
         "--warm-store", store, "--run-dir", run_dir,
         # ~30 gray predicts at the 0.25s default stall, replica 0 only
         "--replica-env", "0:MXTPU_FAULTS=slow_replica:30",
         "--port", "0", "--port-file", port_file],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    try:
        port = _wait_port_file(port_file, proc, deadline_s=300)
        results = []
        exceptions = []
        stop = threading.Event()

        def traffic(i):
            cli = ServeClient("127.0.0.1", port, timeout=30)
            x = np.zeros(32, "f")
            try:
                while not stop.is_set():
                    try:
                        results.append(cli.predict(
                            "mlp", x, npy=True,
                            tenant="t%d" % (i % 2), priority=i % 2))
                    except Exception as e:  # noqa: BLE001 — dropped
                        exceptions.append(e)  # answer: contract-fatal
                    time.sleep(0.01)
            finally:
                cli.close()

        threads = [threading.Thread(target=traffic, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()

        def _ok_count():
            return sum(1 for s, _ in results if s == 200)

        cli = ServeClient("127.0.0.1", port, timeout=30)
        deadline = time.monotonic() + 60
        while _ok_count() < 20 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _ok_count() >= 20, "fleet never served baseline traffic"

        # -- gray failure: the slow replica is EJECTED ----------------
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status, stats = cli.stats()
            if status == 200 and \
                    stats["replicas"]["0"].get("ejected"):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("slow replica was never ejected")
        assert stats["router"]["counters"].get("ejects", 0) >= 1
        # floor respected: ejection never took out more than one
        healthy_n = stats["fleet"]["replicas_healthy"]
        assert healthy_n >= 2, stats["fleet"]

        # -- SIGKILL a healthy non-home replica mid-traffic -----------
        victim = stats["replicas"]["2"]
        assert victim["pid"], stats
        os.kill(victim["pid"], signal.SIGKILL)
        base = _ok_count()
        deadline = time.monotonic() + 30
        while _ok_count() < base + 20 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert _ok_count() >= base + 20, "traffic stalled after kill"

        # -- the fault exhausts; half-open probation REJOINS it -------
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            status, stats = cli.stats()
            if status == 200 \
                    and stats["router"]["counters"].get(
                        "eject_rejoins", 0) >= 1 \
                    and not stats["replicas"]["0"].get("ejected"):
                break
            time.sleep(0.2)
        else:
            raise AssertionError("ejected replica never rejoined")

        stop.set()
        for t in threads:
            t.join(timeout=30)

        # -- the exactly-once ledger ---------------------------------
        assert not exceptions, "dropped responses: %r" % exceptions[:3]
        n502 = sum(1 for s, _ in results if s == 502)
        assert n502 == 0, "client-visible 502s under gray+kill chaos"
        status, stats = cli.stats()
        rc = stats["router"]["counters"]
        fc = stats["fleet"]["counters"]
        assert rc.get("replica_errors", 0) == 0
        # hedging engaged on the gray tail, and the race's losers are
        # accounted — never more losers than hedges
        assert rc.get("hedges", 0) >= 1
        assert rc.get("hedge_wasted", 0) <= rc.get("hedges", 0)
        # the armed dup_request resends were collapsed by replica-side
        # dedup, proving the id rides client -> router -> replica
        assert rc.get("dup_requests", 0) >= 1
        assert fc.get("dedup_hits", 0) >= 1
        # duplicate executions bounded: every execution beyond the
        # client's sends is covered by a counted hedge/retry/dup
        sends = len(results)
        extra = rc.get("hedges", 0) + rc.get("retries", 0) \
            + rc.get("dup_requests", 0)
        assert fc.get("accepted", 0) <= sends + extra

        # -- wait for the relaunched victim before draining -----------
        # the controller relaunched replica 2 after the SIGKILL; a
        # SIGTERM that lands while it is still booting (before
        # serve.py installs its drain handler) kills it rc=-15 and
        # fails the drain — wait until it serves health first
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status, stats = cli.stats()
            if status == 200 and \
                    stats["replicas"]["2"].get("healthy"):
                break
            time.sleep(0.2)
        else:
            raise AssertionError("relaunched replica never came back")
        cli.close()

        proc.send_signal(signal.SIGTERM)
        rc_exit = proc.wait(timeout=120)
        stderr = proc.stderr.read()
        assert rc_exit == 0, stderr[-3000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# train-to-serve hot-swap drills (ISSUE 13): a REAL trainer process
# streams checkpoints into a LIVE serving daemon/fleet under traffic
# ---------------------------------------------------------------------------

HOTSWAP_TRAINER_SCRIPT = """
import os, sys, time
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.resilience import faults

def make_blobs(n, d, c, seed=4):
    rs = np.random.RandomState(seed)
    centers = rs.randn(c, d) * 3
    X = np.concatenate([centers[i] + rs.randn(n // c, d)
                        for i in range(c)]).astype("f")
    y = np.concatenate([np.full(n // c, i) for i in range(c)]).astype("f")
    perm = rs.permutation(len(X))
    return X[perm], y[perm]

data = mx.sym.Variable("data")
net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
net = mx.sym.Activation(net, act_type="relu")
net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2")
sym = mx.sym.SoftmaxOutput(net, name="softmax")

X, y = make_blobs(240, 32, 10)
it = mx.io.NDArrayIter(X, y, batch_size=60)
mod = mx.mod.Module(sym)
mx.random.seed(7)

resuming = os.environ.get("MXTPU_RESUME") == "1"
hang_at = os.environ.get("STREAM_HANG_AT")
if hang_at and not resuming:
    # wedge the Nth checkpoint save AFTER its files are written but
    # BEFORE the manifest publishes — the SIGKILL-mid-write window
    faults.arm_hang("ckpt_write", 3600.0, after=int(hang_at))

gap = float(os.environ.get("STREAM_GAP_S", "0"))

def epoch_cb(epoch, sym_, args, auxs):
    if gap:
        time.sleep(gap)     # let the watcher see each epoch land

mod.fit(it, num_epoch=int(os.environ.get("STREAM_EPOCHS", "4")),
        kvstore="tpu", optimizer="sgd",
        optimizer_params={"learning_rate": 0.05},
        initializer=mx.initializer.Xavier(),
        epoch_end_callback=epoch_cb,
        checkpoint=os.environ["CKPT_DIR"])
"""


def _wait_until(cond, deadline_s, what):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.05)
    raise AssertionError("timed out waiting for %s" % what)


def _daemon_stats(port):
    from mxnet_tpu.serving import ServeClient
    cli = ServeClient("127.0.0.1", port, timeout=10)
    try:
        status, payload = cli.stats()
        return payload if status == 200 else {}
    except Exception:  # noqa: BLE001 — daemon busy/binding
        return {}
    finally:
        cli.close()


@pytest.mark.chaos
def test_hotswap_drill_trainer_streams_rot_and_sigkill(tmp_path):
    """Drills (a)+(b)+(c) of the ISSUE-13 acceptance matrix, end to
    end on real processes:

    (a) a REAL trainer process streams checkpoints into a LIVE
        ``tools/serve.py --watch`` daemon under concurrent traffic —
        every landed swap is drop-free and the served epoch advances;
    (b) a ROT-INJECTED checkpoint mid-stream (rot_checkpoint: byte
        flipped after the manifest published) is rejected by digest and
        the pool keeps serving the previous epoch (counter asserted —
        never a walk-forward onto bad bytes);
    (c) the trainer is SIGKILLed MID-WRITE (wedged in the
        files-on-disk/no-manifest window): the daemon keeps serving,
        the watcher stays alive, and a respawned trainer resumes the
        stream to completion.
    """
    import threading

    from mxnet_tpu.resilience import CheckpointManager

    script = tmp_path / "trainer.py"
    script.write_text(HOTSWAP_TRAINER_SCRIPT % {"repo": REPO})
    ckpt_dir = str(tmp_path / "stream")
    env = dict(os.environ, CKPT_DIR=ckpt_dir, STREAM_EPOCHS="4",
               STREAM_GAP_S="1.0", STREAM_HANG_AT="2",
               MXTPU_FAULTS="rot_checkpoint:1@1")
    env.pop("MXTPU_RESUME", None)
    trainer = subprocess.Popen([sys.executable, str(script)], env=env,
                               stdout=subprocess.DEVNULL,
                               stderr=subprocess.PIPE, text=True)
    daemon = None
    try:
        man = CheckpointManager(ckpt_dir)
        _wait_until(lambda: man.latest() is not None, 120,
                    "the trainer's first epoch")

        port_file = str(tmp_path / "port")
        denv = dict(os.environ, JAX_PLATFORMS="cpu",
                    MXTPU_SWAP_POLL_S="0.15")
        denv.pop("MXTPU_FAULTS", None)
        daemon = subprocess.Popen(
            [sys.executable, SERVE, "--model", "mlp=%s" % ckpt_dir,
             "--input-shape", "data=32", "--port", "0",
             "--port-file", port_file, "--buckets", "1,2,4",
             "--max-wait-ms", "1", "--watch"],
            env=denv, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True)
        port = _wait_port_file(port_file, daemon)
        from mxnet_tpu.serving import ServeClient
        ServeClient("127.0.0.1", port).wait_ready(60)

        results, exceptions = [], []
        stop = threading.Event()

        def traffic():
            cli = ServeClient("127.0.0.1", port, timeout=30)
            x = np.zeros(32, "f")
            try:
                while not stop.is_set():
                    try:
                        results.append(cli.predict("mlp", x, npy=True))
                    except Exception as e:  # noqa: BLE001 — a DROP
                        exceptions.append(e)
                    time.sleep(0.01)
            finally:
                cli.close()

        threads = [threading.Thread(target=traffic) for _ in range(2)]
        for t in threads:
            t.start()
        _wait_until(
            lambda: sum(1 for s, _ in results if s == 200) >= 10, 60,
            "baseline traffic")

        # (b) the rotted epoch 2 is published and REJECTED by digest;
        # serving stays on epoch 1 — no walk-forward onto bad bytes
        _wait_until(lambda: (man.latest() or 0) >= 2, 90,
                    "the rotted epoch's publish")

        def _rejected():
            dep = (_daemon_stats(port).get("deploy") or {}).get("mlp")
            return dep and dep["rejected"] >= 1 and dep["epoch"] == 1
        _wait_until(_rejected, 60, "the digest rejection")

        # (c) the trainer is wedged MID-WRITE of epoch 3 (params file
        # on disk, manifest not published) — SIGKILL it there
        _wait_until(
            lambda: os.path.exists(
                os.path.join(ckpt_dir, "checkpoint-0003.params")), 90,
            "the wedged epoch-3 write")
        assert man.latest() == 2        # never published
        assert trainer.poll() is None
        trainer.kill()
        trainer.wait(timeout=30)

        # the pool keeps serving and the watcher stays alive
        base = sum(1 for s, _ in results if s == 200)
        _wait_until(
            lambda: sum(1 for s, _ in results if s == 200) >= base + 10,
            30, "serving to continue after the trainer died")
        dep = (_daemon_stats(port).get("deploy") or {}).get("mlp")
        assert dep and dep["watching"], dep

        # respawn the trainer (faults stripped, resume): it walks back
        # past the rotted epoch 2, retrains 2..4, republishes cleanly
        renv = dict(env, MXTPU_RESUME="1")
        renv.pop("MXTPU_FAULTS", None)
        renv.pop("STREAM_HANG_AT", None)
        trainer = subprocess.Popen([sys.executable, str(script)],
                                   env=renv,
                                   stdout=subprocess.DEVNULL,
                                   stderr=subprocess.PIPE, text=True)

        # (a) the stream completes and the served epoch ADVANCES to 4
        _wait_until(
            lambda: _daemon_stats(port).get("epochs", {}).get("mlp")
            == 4, 180, "the served epoch to reach 4")
        rc = trainer.wait(timeout=60)
        assert rc == 0, trainer.stderr.read()[-2000:]

        stop.set()
        for t in threads:
            t.join(timeout=30)

        # ZERO dropped/errored requests across every swap, rejection,
        # trainer death and respawn
        assert not exceptions, "dropped responses: %r" % exceptions[:3]
        bad = [(s, p) for s, p in results if s != 200]
        assert not bad, "non-200 responses during the stream: %r" \
            % bad[:3]
        dep = (_daemon_stats(port).get("deploy") or {}).get("mlp")
        assert dep["promoted"] >= 1          # swaps really landed
        assert dep["rejected"] >= 1          # the rot really rejected
        assert dep["epoch"] == 4
    finally:
        for proc in (trainer, daemon):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)


@pytest.mark.chaos
def test_hotswap_drill_fleet_rolling_swap(tmp_path):
    """Drill (d): a rolling swap across 2 REAL replicas keeps >= 1
    replica serving at every instant (the fence takes one replica at a
    time), the router's /stats shows per-replica epochs advancing, and
    a BAD epoch (NaN weights — digest-clean, validation-fatal) halts
    the rollout with every replica still on the old epoch."""
    import threading

    from mxnet_tpu.resilience import CheckpointManager
    from mxnet_tpu.serving import ServeClient

    sym = mlp_sym(num_classes=10, nh=32)
    arg_shapes, _, _ = sym.infer_shape(data=(1, 32))

    def params(seed, poison=False):
        rs = np.random.RandomState(seed)
        out = {}
        for n, s in zip(sym.list_arguments(), arg_shapes):
            if n in ("data", "softmax_label"):
                continue
            v = rs.uniform(-0.3, 0.3, s).astype("f")
            out[n] = mx.nd.array(v)
        if poison:
            out["fc2_weight"] = mx.nd.array(
                np.full(out["fc2_weight"].shape, np.nan, "f"))
        return out

    ckpt_dir = str(tmp_path / "stream")
    man = CheckpointManager(ckpt_dir)
    man.save(1, symbol=sym, arg_params=params(1), aux_params={},
             blocking=True)

    run_dir = str(tmp_path / "run")
    port_file = str(tmp_path / "port")
    env = dict(os.environ,
               MXTPU_FLEET_HEARTBEAT_S="0.3",
               MXTPU_FLEET_EVICT_S="1.5",
               MXTPU_SERVE_MAX_WAIT_MS="1",
               MXTPU_SWAP_POLL_S="0.2")
    proc = subprocess.Popen(
        [sys.executable, FLEET, "serve",
         "--model", "mlp=%s" % ckpt_dir,
         "--input-shape", "mlp:data=32", "--replicas", "2",
         "--device-sets", "cpu", "--buckets", "1,2,4",
         "--run-dir", run_dir, "--port", "0",
         "--port-file", port_file, "--watch"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    try:
        port = _wait_port_file(port_file, proc, deadline_s=300)
        results, exceptions = [], []
        min_healthy = [99]
        stop = threading.Event()

        def traffic():
            cli = ServeClient("127.0.0.1", port, timeout=30)
            x = np.zeros(32, "f")
            try:
                while not stop.is_set():
                    try:
                        results.append(cli.predict("mlp", x, npy=True))
                    except Exception as e:  # noqa: BLE001 — a DROP
                        exceptions.append(e)
                    time.sleep(0.01)
            finally:
                cli.close()

        def capacity_sampler():
            cli = ServeClient("127.0.0.1", port, timeout=10)
            try:
                while not stop.is_set():
                    try:
                        status, h = cli.healthz()
                        if status == 200:
                            min_healthy[0] = min(
                                min_healthy[0],
                                h["replicas_healthy"])
                    except Exception:  # noqa: BLE001 — poll only
                        cli.close()
                    time.sleep(0.05)
            finally:
                cli.close()

        threads = [threading.Thread(target=traffic) for _ in range(2)]
        threads.append(threading.Thread(target=capacity_sampler))
        for t in threads:
            t.start()
        _wait_until(
            lambda: sum(1 for s, _ in results if s == 200) >= 20, 60,
            "fleet baseline traffic")

        cli = ServeClient("127.0.0.1", port, timeout=10)

        def _replica_epochs():
            try:
                status, stats = cli.stats()
            except Exception:  # noqa: BLE001 — poll only
                return {}
            if status != 200:
                return {}
            return {rid: (rep.get("epochs") or {}).get("mlp")
                    for rid, rep in (stats.get("replicas")
                                     or {}).items()}

        # -- the rolling swap: both replicas advance, one at a time --
        man.save(2, symbol=sym, arg_params=params(2), aux_params={},
                 blocking=True)
        _wait_until(
            lambda: set(_replica_epochs().values()) == {2}, 120,
            "both replicas to serve epoch 2")
        status, stats = cli.stats()
        assert stats["rollout"]["state"]["state"] == "complete"
        assert stats["rollout"]["state"]["epoch"] == 2

        # -- the BAD epoch: digest-clean NaN weights; every replica's
        # own validation refuses it and the rollout HALTS
        man.save(3, symbol=sym,
                 arg_params=params(3, poison=True), aux_params={},
                 blocking=True)

        def _halted():
            try:
                status, stats = cli.stats()
            except Exception:  # noqa: BLE001 — poll only
                return None
            if status != 200:
                return None
            roll = stats.get("rollout") or {}
            return roll.get("state", {}).get("state") == "halted" \
                and stats
        stats = _wait_until(_halted, 120, "the rollout to halt")
        # every replica is UNTOUCHED on the old epoch
        assert set(_replica_epochs().values()) == {2}, \
            _replica_epochs()
        assert stats["rollout"]["halted"] >= 1

        stop.set()
        for t in threads:
            t.join(timeout=30)
        cli.close()

        # capacity never dropped below N-1 = 1, and no request was
        # dropped or errored across both rollouts
        assert min_healthy[0] >= 1, min_healthy
        assert not exceptions, "dropped responses: %r" % exceptions[:3]
        bad = [(s, p) for s, p in results if s != 200]
        assert not bad, "non-200s during the rolling swap: %r" % bad[:3]

        # -- fleet-wide SIGTERM: clean drain ------------------------
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        stderr = proc.stderr.read()
        assert rc == 0, stderr[-3000:]
        assert "replica exit codes {0: 0, 1: 0}" in stderr
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


# ---------------------------------------------------------------------------
# the elastic-resume chaos drill (ISSUE 14 / ROADMAP item 2 capstone):
# a zero3 run SIGKILLed at world=4 resumes at world=2 AND world=8 from
# the SAME checkpoint — restored params bit-identical, loss trajectory
# matching an unbroken run
# ---------------------------------------------------------------------------

ELASTIC_SCRIPT = """
import os, re, sys, json, signal, hashlib
world = int(os.environ["ELASTIC_WORLD"])
flags = re.sub(r"--xla_force_host_platform_device_count=\\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=%%d" %% world).strip()
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.parallel import SPMDTrainer, local_mesh
from mxnet_tpu.resilience import CheckpointManager

TOTAL, SAVE_AT = 6, 3

def build():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    t = SPMDTrainer(sym, "sgd",
                    {"learning_rate": 0.3, "momentum": 0.9,
                     "rescale_grad": 1.0 / 64},
                    mesh=local_mesh("dp"), grad_sync="zero3")
    t.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(33)
    t.init_params(mx.initializer.Xavier())
    return t

rs = np.random.RandomState(0)
X = rs.randn(TOTAL * 64, 10).astype("f")
y = rs.randint(0, 4, TOTAL * 64).astype("f")

def one_step(t, i):
    b = slice(i * 64, (i + 1) * 64)
    outs = t.step(X[b], y[b])
    p = np.asarray(outs[0])
    picked = p[np.arange(64), y[b].astype(int)]
    return float(-np.log(np.maximum(picked, 1e-12)).mean())

def digest(t):
    arg, aux = t.get_params()
    h = hashlib.sha256()
    for name in sorted(arg):
        h.update(arg[name].asnumpy().tobytes())
    for name in sorted(aux):
        h.update(aux[name].asnumpy().tobytes())
    return h.hexdigest()

phase = os.environ["ELASTIC_PHASE"]
mgr = CheckpointManager(os.environ["ELASTIC_DIR"])
t = build()
report = {"phase": phase, "world": world, "losses": []}

if phase == "train":
    for i in range(SAVE_AT):
        one_step(t, i)
    t.save_checkpoint(mgr, SAVE_AT, blocking=True)
    print("ELASTIC SAVED", flush=True)
    one_step(t, SAVE_AT)  # step 4 runs; its result must be lost
    os.kill(os.getpid(), signal.SIGKILL)

if phase == "unbroken":
    for i in range(TOTAL):
        loss = one_step(t, i)
        if i >= SAVE_AT:
            report["losses"].append(loss)
    report["digest"] = digest(t)

if phase == "resume":
    mx.random.seed(99)  # resume must not depend on ambient RNG state
    restored = t.restore(mgr)
    assert restored == SAVE_AT, restored
    report["restored_digest"] = digest(t)
    for i in range(SAVE_AT, TOTAL):
        report["losses"].append(one_step(t, i))
    report["digest"] = digest(t)

print("ELASTIC_REPORT " + json.dumps(report), flush=True)
"""


def _spawn_elastic(script, tmp_path, phase, world):
    env = dict(os.environ)
    env["ELASTIC_PHASE"] = phase
    env["ELASTIC_WORLD"] = str(world)
    env["ELASTIC_DIR"] = str(tmp_path / "ckpt")
    env.pop("MXTPU_FAULTS", None)
    env.pop("MXTPU_ZERO3_GATHER_GROUP", None)  # the auto default
    return subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _elastic_report(res):
    for line in res.stdout.splitlines():
        if line.startswith("ELASTIC_REPORT "):
            return json.loads(line[len("ELASTIC_REPORT "):])
    raise AssertionError("no report in:\n%s\n%s"
                         % (res.stdout[-2000:], res.stderr[-2000:]))


@pytest.mark.chaos
def test_chaos_elastic_resume_across_world_sizes(tmp_path):
    """THE elastic drill: a zero3 run on world=4 is SIGKILLed mid-step-4
    (checkpoint at step 3 on disk, with its sharding plan in the
    manifest), then resumes at world=2 AND world=8 from that same
    checkpoint.  Restored params are BIT-identical to the checkpoint on
    both worlds (gather-on-save + set_params re-sharding), and both
    post-resume loss trajectories match the unbroken world=4 run —
    same-world continuation is bitwise (tests/dist/dist_zero3.py);
    across world sizes the psum tree re-associates, so parity is to
    reduction order (~1e-7 here; asserted at rtol 1e-5).  The
    planner-chosen (auto) gather groups are in force throughout, and
    the pre-resume gates see the plan: plan_explain --check FITS both
    resume worlds and rejects an indivisible one."""
    script = tmp_path / "elastic.py"
    script.write_text(ELASTIC_SCRIPT % {"repo": REPO})

    # the unbroken world=4 baseline and the run that dies are
    # independent (the baseline never touches the checkpoint dir) —
    # run them concurrently to keep the drill inside the tier-1 budget
    p_unbroken = _spawn_elastic(script, tmp_path, "unbroken", 4)
    p_train = _spawn_elastic(script, tmp_path, "train", 4)
    out_t, err_t = p_train.communicate(timeout=300)
    out_u, err_u = p_unbroken.communicate(timeout=300)
    assert p_unbroken.returncode == 0, err_u[-2000:]
    unbroken = _elastic_report(subprocess.CompletedProcess(
        p_unbroken.args, 0, out_u, err_u))

    # the dying run: SIGKILL mid-step-4, checkpoint at step 3
    assert p_train.returncode == -signal.SIGKILL, (p_train.returncode,
                                                   err_t[-2000:])
    assert "ELASTIC SAVED" in out_t

    # the manifest carries the writing run's plan: world=4 zero3 with
    # planner-derived gather groups
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    plan = mgr.plan(3)
    assert plan is not None
    assert plan["world"] == 4 and plan["grad_sync"] == "zero3"
    assert plan["gather_groups"], plan

    # pre-resume gate: the plan FITS the resume worlds (elastic note),
    # rejects an indivisible inventory
    cli = os.path.join(REPO, "tools", "plan_explain.py")
    for ndev, rc in ((2, 0), (8, 0), (7, 1)):
        res = subprocess.run(
            [sys.executable, cli, str(tmp_path / "ckpt"), "--check",
             "--devices", str(ndev), "-q"],
            capture_output=True, text=True, timeout=120)
        assert res.returncode == rc, (ndev, res.stdout, res.stderr)

    # the checkpoint's own content digest (what a bit-identical restore
    # must reproduce): hash the saved arg+aux exactly like the drill
    import hashlib
    loaded = mx.nd.load(str(tmp_path / "ckpt" / "checkpoint-0003.params"))
    arg = {k[4:]: v for k, v in loaded.items() if k.startswith("arg:")}
    aux = {k[4:]: v for k, v in loaded.items() if k.startswith("aux:")}
    h = hashlib.sha256()
    for name in sorted(arg):
        h.update(arg[name].asnumpy().tobytes())
    for name in sorted(aux):
        h.update(aux[name].asnumpy().tobytes())
    ckpt_digest = h.hexdigest()

    # resume at HALF and DOUBLE the writing world, same checkpoint
    # (read-only consumers of it — concurrent for the same reason)
    procs = {w: _spawn_elastic(script, tmp_path, "resume", w)
             for w in (2, 8)}
    reports = {}
    for world, proc in procs.items():
        stdout, stderr = proc.communicate(timeout=300)
        assert proc.returncode == 0, (world, stderr[-2000:])
        reports[world] = _elastic_report(subprocess.CompletedProcess(
            proc.args, 0, stdout, stderr))

    for world, rep in reports.items():
        # bit-identical restore on BOTH worlds
        assert rep["restored_digest"] == ckpt_digest, \
            "world=%d restore is not bit-identical" % world
        # loss trajectory matches the unbroken run (reduction-order
        # parity across different psum tree shapes)
        np.testing.assert_allclose(
            rep["losses"], unbroken["losses"], rtol=1e-5, atol=1e-7,
            err_msg="world=%d post-resume trajectory diverged" % world)
    # and the two resumes agree with each other the same way
    np.testing.assert_allclose(reports[2]["losses"], reports[8]["losses"],
                               rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# the composed region drill (tools/region.py): data plane -> elastic
# trainer -> rolling fleet -> clients under ONE supervision tree, with
# scheduled chaos and a live /region/stats endpoint
# (docs/how_to/region.md)
# ---------------------------------------------------------------------------

REGION = os.path.join(REPO, "tools", "region.py")


def _run_region(mode, tmp_path, timeout):
    """Run ``tools/region.py <mode>``, poll /region/stats while it is
    live (the endpoint is part of the contract), return (report, one
    mid-run stats payload)."""
    import http.client

    run_dir = str(tmp_path / "region")
    report = str(tmp_path / "report.json")
    proc = subprocess.Popen(
        [sys.executable, REGION, mode, "--run-dir", run_dir,
         "--report", report],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    deadline = time.monotonic() + timeout
    try:
        port_file = os.path.join(run_dir, "region.port")
        addr = None
        while time.monotonic() < deadline and proc.poll() is None:
            if os.path.exists(port_file):
                addr = open(port_file).read().strip()
                if addr:
                    break
            time.sleep(0.2)
        live = None
        if addr and proc.poll() is None:
            host, port = addr.rsplit(":", 1)
            while time.monotonic() < deadline and proc.poll() is None:
                try:
                    conn = http.client.HTTPConnection(host, int(port),
                                                      timeout=5)
                    conn.request("GET", "/region/stats")
                    resp = conn.getresponse()
                    body = resp.read()
                    conn.close()
                    if resp.status == 200:
                        live = json.loads(body.decode())
                        break
                except OSError:
                    time.sleep(0.2)
        out, err = proc.communicate(
            timeout=max(5.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        proc.kill()
        out, err = proc.communicate()
        raise AssertionError("region %s hung:\n%s" % (mode, err[-4000:]))
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
    assert proc.returncode == 0, \
        "region %s failed rc=%s:\n%s" % (mode, proc.returncode,
                                         err[-4000:])
    assert "REGION_REPORT " in out, out[-2000:]
    assert live is not None, "stats endpoint never answered mid-run"
    assert "events" in live and "roles" in live and "clients" in live
    with open(report) as f:
        return json.load(f), live


@pytest.mark.chaos
def test_region_smoke_drill(tmp_path):
    """The tier-1-sized composed drill: 1 data server -> supervised
    trainer -> 1-replica fleet -> closed-loop clients, with one
    rot-injected publish.  Zero dropped requests, the rot rejected at
    the rollout gate, and the served epoch advances bit-verified."""
    doc, live = _run_region("smoke", tmp_path, timeout=300)
    assert doc["ok"], doc["checks"]
    stats = doc["stats"]
    assert stats["clients"]["dropped"] == 0
    # every request resolved OK (at most one per client thread may be
    # in flight at the instant the report is cut)
    assert stats["clients"]["requests"] - stats["clients"]["ok"] \
        <= doc["spec"]["clients"]
    assert stats["events"].get("publish_rejected", 0) >= 1
    # the supervision tree's exit-code discipline is visible: the
    # trainer completed (rc 0) as a counted named event
    assert stats["events"].get("exit:trainer:rc=0") == 1
    assert stats["served_epochs"] == {"0": doc["spec"]["epochs"]}
    assert stats["freshness_ms"] is not None


@pytest.mark.slow
@pytest.mark.chaos
def test_region_storm_drill(tmp_path):
    """The full STORM: data-server SIGKILL, a mid-run world-size
    change (SIGKILL + respawn at different --devices), a rot-injected
    publish, and a replica SIGKILL — all in one window.  Zero dropped
    or errored client requests, a bit-verified served-epoch advance
    across the whole storm, every scheduled fault a counted named
    event on /region/stats."""
    doc, live = _run_region("storm", tmp_path, timeout=480)
    assert doc["ok"], doc["checks"]
    events = doc["stats"]["events"]
    for label in ("kill:data#0", "resize:trainer",
                  "arm:trainer:rot_checkpoint", "kill:replica#1"):
        assert events.get(label) == 1, events
    assert doc["stats"]["clients"]["dropped"] == 0
    # exactly-once routing: the router absorbs the replica SIGKILL by
    # keyed resend, so no client ever saw a 502 it had to retry.  503
    # retries stay allowed — they are the backstop for the no-routable
    # window when the kill overlaps the rolling swap's fence
    assert events.get("client_retry:502", 0) == 0, events
    assert doc["checks"]["no_502_leak"], events
    epochs = doc["spec"]["epochs"]
    assert doc["stats"]["served_epochs"] == {"0": epochs, "1": epochs}
    assert doc["stats"]["trainer"]["world"] == 4    # the resize landed
    assert events.get("data_reconnect", 0) >= 1     # the data plane
    assert events.get("publish_rejected", 0) >= 1   # the rot
    assert doc["stats"]["freshness_ms"] is not None
