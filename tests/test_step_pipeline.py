"""The one-deep step pipeline: ``SPMDTrainer.step`` and ``fit`` settle step
N's guard counters and host-side metric only after step N+1 is dispatched,
and every read of the counters or the metric is as exact as when they were
settled at once."""
import gc
import weakref

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import SPMDModule, SPMDTrainer

BATCH, BATCHES, DIM, CLASSES = 16, 8, 10, 3
OPT = {"learning_rate": 0.1, "momentum": 0.9}


def blobs(seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.randn(CLASSES, DIM) * 3
    y = rs.randint(0, CLASSES, BATCH * BATCHES)
    X = centers[y] + rs.randn(len(y), DIM)
    return X.astype("f"), y.astype("f")


def mlp():
    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def module(kind):
    return SPMDModule(mlp()) if kind == "spmd" else mx.mod.Module(mlp())


def feed():
    X, y = blobs()
    return mx.io.NDArrayIter(X, y, batch_size=BATCH)


def fit(mod, metric, callback=None, opt=OPT):
    mx.random.seed(11)
    mod.fit(feed(), num_epoch=1, eval_metric=metric, kvstore="tpu",
            optimizer="sgd", optimizer_params=opt,
            initializer=mx.initializer.Xavier(),
            batch_end_callback=callback)
    return mod


def by_hand(mod, metric, poison_at=None, opt=OPT, faults=None):
    """The same run with every step's metric settled at once: the loop
    ``fit`` makes, driven through the public calls."""
    mx.random.seed(11)
    it = feed()
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="sgd", optimizer_params=opt)
    metric.reset()
    for k, batch in enumerate(it):
        if k == poison_at:
            faults.arm("poison_grad")
        mod.forward_backward(batch)
        mod.update()
        mod.update_metric(metric, batch.label)
    return mod


def trainer_of(mod):
    return mod._deferred_metric_trainer()


def params_of(mod):
    return {k: v.asnumpy() for k, v in mod.get_params()[0].items()}


def host_metric(name, monkeypatch):
    """A metric ``fit`` keeps on the host: cross-entropy has no in-graph
    rule; accuracy has one, which the blocking switch turns off."""
    if name == "acc":
        monkeypatch.setenv("MXTPU_METRIC_BLOCKING", "1")
    return mx.metric.create(name)


# -- (a) the order of things ------------------------------------------------

@pytest.mark.parametrize("kind", ["module", "spmd"])
def test_fit_waits_for_a_step_only_after_the_next_is_dispatched(kind):
    before = profiler.counters()
    since = profiler._now() / 1e9
    mod = fit(module(kind), mx.metric.CrossEntropy())
    assert trainer_of(mod).flush_interval == 1
    records = profiler.spans(since=since)
    after = profiler.counters()
    dispatch = [r for r in records if r["name"] == "step.dispatch"]
    assert [r["ids"]["queued"] for r in dispatch] == [0] + [1] * (BATCHES - 1)
    assert after["step.overlapped"] - before.get("step.overlapped", 0) \
        == BATCHES - 1
    assert after["step.drained"] - before.get("step.drained", 0) == 1
    # step k's counters are read inside step k+1's call, after its
    # dispatch; the last step's when the epoch's metric is read
    waits = [r for r in records if r["name"] == "step.guard_wait"]
    assert len(waits) == BATCHES
    for k, wait in enumerate(waits[:-1]):
        assert wait["ids"]["step"] == k + 1
        assert dispatch[k + 1]["end"] <= wait["start"]
    assert "step" not in waits[-1]["ids"]
    assert dispatch[-1]["end"] <= waits[-1]["start"]


def test_a_raw_step_loop_overlaps_and_a_counter_read_drains(clean_faults):
    tr = SPMDTrainer(mlp(), "sgd", dict(OPT, rescale_grad=1.0 / BATCH))
    tr.bind([("data", (BATCH, DIM))], [("softmax_label", (BATCH,))])
    tr.init_params(mx.initializer.Xavier())
    X, y = blobs()
    X, y = X[:BATCH], y[:BATCH]
    since = profiler._now() / 1e9
    try:
        tr.step(X, y)
        first = tr.guard_snapshot()
        clean_faults.arm("poison_grad")
        tr.step(X, y)
        second = tr.guard_snapshot()
        tr.step(X, y)
        # each step's copy of the counters outlives the carry's donation
        assert tr._guard_acc is not first and not first.is_deleted()
        assert [tr.step_skipped(s) for s in (first, second)] == [False, True]
        assert tr.step_skipped(None) is False
        # step() itself is one step behind; the property is exact
        assert tr._skipped_steps == 1 and tr._consecutive_bad_steps == 1
        assert tr.consecutive_bad_steps == 0 and tr.skipped_steps == 1
        tr.step(X, y)
        queued = [r["ids"]["queued"] for r in profiler.spans(since=since)
                  if r["name"] == "step.dispatch"]
        assert queued == [0, 1, 1, 0]
        assert tr.analyze(X, y).ok
    finally:
        tr.close()


# -- (b) parity with the metric settled at once -----------------------------

@pytest.mark.parametrize("poison_at", [None, 3])
@pytest.mark.parametrize("name", ["acc", "ce"])
@pytest.mark.parametrize("kind", ["module", "spmd"])
def test_fit_equals_the_loop_that_settles_every_step_at_once(
        kind, name, poison_at, monkeypatch, clean_faults):
    def arm(param):
        if param.nbatch + 1 == poison_at:
            clean_faults.arm("poison_grad")

    metric = host_metric(name, monkeypatch)
    mod = fit(module(kind), metric, arm)
    assert trainer_of(mod).flush_interval == 1     # no in-graph metric
    want_metric = host_metric(name, monkeypatch)
    want = by_hand(module(kind), want_metric, poison_at, faults=clean_faults)

    assert mod.skipped_update_count == want.skipped_update_count \
        == (0 if poison_at is None else 1)
    assert metric.get() == want_metric.get()
    assert metric.num_inst == want_metric.num_inst \
        == BATCH * (BATCHES - mod.skipped_update_count)
    assert trainer_of(mod)._num_update == trainer_of(want)._num_update
    got, ref = params_of(mod), params_of(want)
    for k in ref:
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)


class Schedule(mx.lr_scheduler.LRScheduler):
    def __init__(self):
        super().__init__()
        self.asked = []

    def __call__(self, num_update):
        self.asked.append(num_update)
        return 0.1 * 0.9 ** num_update


def test_under_a_schedule_the_roll_back_comes_one_step_later(clean_faults):
    """The one thing that lags: after a skipped step, ``_num_update`` is
    rolled back once the NEXT step is dispatched, so that step asks the
    schedule one update too far; the one after asks it right."""
    def arm(param):
        if param.nbatch + 1 == 3:
            clean_faults.arm("poison_grad")

    lagging, at_once = Schedule(), Schedule()
    mod = fit(module("spmd"), mx.metric.CrossEntropy(), arm,
              dict(OPT, lr_scheduler=lagging))
    want = by_hand(module("spmd"), mx.metric.CrossEntropy(), 3,
                   dict(OPT, lr_scheduler=at_once), clean_faults)
    assert at_once.asked == [1, 2, 3, 4, 4, 5, 6, 7]
    assert lagging.asked == [1, 2, 3, 4, 5, 5, 6, 7]
    assert trainer_of(mod)._num_update == trainer_of(want)._num_update == 7
    assert mod.skipped_update_count == want.skipped_update_count == 1


# -- (c) what a callback sees ------------------------------------------------

@pytest.mark.parametrize("name", ["acc", "ce", "composite"])
@pytest.mark.parametrize("kind", ["module", "spmd"])
def test_a_callback_sees_every_batch_so_far(kind, name, monkeypatch):
    if name == "composite":
        metric = mx.metric.create(["ce", "mse"])
        inst = lambda: metric.get_metric(0).num_inst     # noqa: E731
    else:
        metric = host_metric(name, monkeypatch)
        inst = lambda: metric.num_inst                   # noqa: E731
    mod = module(kind)
    seen = []

    def read(param):
        param.eval_metric.get_name_value()
        seen.append((trainer_of(mod)._num_update, inst()))

    fit(mod, metric, read)
    assert seen == [(k, k * BATCH) for k in range(1, BATCHES + 1)]


def test_a_callback_that_reads_every_other_batch_drains_every_other_step():
    before = profiler.counters()

    def read(param):
        if param.nbatch % 2:
            param.eval_metric.get()

    fit(module("spmd"), mx.metric.CrossEntropy(), read)
    after = profiler.counters()
    assert after["step.drained"] - before.get("step.drained", 0) \
        == BATCHES // 2
    assert after["step.overlapped"] - before.get("step.overlapped", 0) \
        == BATCHES // 2


def test_a_reset_inside_the_epoch_drops_the_owed_step(clean_faults):
    metric = mx.metric.CrossEntropy()

    def reset(param):
        if param.nbatch == 4:
            param.eval_metric.reset()

    fit(module("spmd"), metric, reset)
    assert metric.num_inst == BATCH * (BATCHES - 5)


# -- (d) the abort -----------------------------------------------------------

@pytest.mark.parametrize("kind", ["module", "spmd"])
def test_fit_aborts_at_most_one_step_after_the_second_bad_step(
        kind, monkeypatch, clean_faults):
    monkeypatch.setenv("MXTPU_MAX_BAD_STEPS", "2")
    mod, metric, ended = module(kind), mx.metric.CrossEntropy(), []

    def arm(param):
        ended.append(param.nbatch)
        if param.nbatch == 1:
            clean_faults.arm("poison_grad", times=2)    # batches 2 and 3

    with pytest.raises(MXNetError, match="consecutive"):
        fit(mod, metric, arm)
    # batch 3 is the second bad one; the abort comes from batch 4's step
    assert ended == [0, 1, 2, 3]
    assert mod._owed_metric is None and not mod._metric_lags
    assert metric._deferred_fetch is None
    assert metric.num_inst == 2 * BATCH


# -- (e) what outlives fit ---------------------------------------------------

@pytest.mark.parametrize("kind", ["module", "spmd"])
def test_no_step_but_the_last_outlives_fit(kind):
    mod, outputs = module(kind), []

    def note(param):
        outputs.append(weakref.ref(mod.get_outputs()[0]._data))

    metric = mx.metric.CrossEntropy()
    fit(mod, metric, note)
    assert mod._owed_metric is None and not mod._metric_lags
    assert metric._deferred_fetch is None
    gc.collect()
    assert [r() is not None for r in outputs] == \
        [False] * (BATCHES - 1) + [True]
