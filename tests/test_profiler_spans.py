"""The program's span-and-counter recorder (mxnet_tpu/profiler.py): what a
span records, the ring's bound, threads, the spans a ``fit`` over a
``DevicePrefetchIter`` makes, the Chrome dumps, and the one clock it
shares with a ``jax.profiler`` trace.  No duration is asserted tighter
than a factor of ten."""
import glob
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.base import MXNetError


def _names(records):
    return [r["name"] for r in records]


# -- one span -----------------------------------------------------------------

@pytest.fixture(scope="module")
def nested():
    """outer(step=3) > [inner > innermost, event], then a sibling."""
    t0 = time.perf_counter()
    with profiler.span("t.outer", step=3) as outer:
        with profiler.span("t.inner", batch=7) as inner:
            with profiler.span("t.innermost"):
                pass
            inner.note(images=5)
        now = time.perf_counter_ns()
        profiler.event("t.event", now - 1000, now, kind="x")
        outer.note(late=True)
    with profiler.span("t.sibling"):
        pass
    return {r["name"]: r for r in profiler.spans(since=t0)
            if r["name"].startswith("t.")}


@pytest.mark.parametrize("child,parent", [
    ("t.outer", None), ("t.inner", "t.outer"), ("t.innermost", "t.inner"),
    ("t.event", "t.outer"), ("t.sibling", None)])
def test_parent_is_the_enclosing_span_of_the_thread(nested, child, parent):
    want = None if parent is None else nested[parent]["serial"]
    assert nested[child]["parent"] == want


@pytest.mark.parametrize("name,ids", [
    ("t.outer", {"step": 3, "late": True}),
    ("t.inner", {"step": 3, "batch": 7, "images": 5}),
    ("t.innermost", {"step": 3, "batch": 7}),     # inherited, both levels
    ("t.event", {"step": 3, "kind": "x"}),
    ("t.sibling", {})])
def test_ids_own_inherited_and_noted(nested, name, ids):
    assert nested[name]["ids"] == ids


def test_times_nest_and_map_to_unix_time(nested):
    outer, inner = nested["t.outer"], nested["t.inner"]
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]
    assert outer["thread"] == threading.get_ident()
    assert outer["thread_name"] == threading.current_thread().name
    # the anchor pair puts a perf-counter stamp on the Unix clock
    assert abs(outer["unix_ns"] - time.time_ns()) < 60e9
    assert nested["t.event"]["end"] - nested["t.event"]["start"] == \
        pytest.approx(1e-6, rel=1e-3)


def test_spans_are_sorted_by_start_and_cut_by_since_and_until():
    with profiler.span("t.a"):
        pass
    cut = time.perf_counter()
    with profiler.span("t.b"):
        pass
    later = profiler.spans(since=cut)
    assert "t.b" in _names(later) and "t.a" not in _names(later)
    assert "t.a" in _names(profiler.spans(until=cut))
    assert "t.b" not in _names(profiler.spans(until=cut))
    starts = [r["start"] for r in profiler.spans()]
    assert starts == sorted(starts)


def test_an_exception_still_records_the_span_and_unwinds_the_stack():
    t0 = time.perf_counter()
    with pytest.raises(ValueError):
        with profiler.span("t.raises"):
            raise ValueError("x")
    with profiler.span("t.after"):
        pass
    got = {r["name"]: r for r in profiler.spans(since=t0)}
    assert "t.raises" in got and got["t.after"]["parent"] is None


def test_counters_accumulate():
    before = profiler.counters().get("t.count", 0)
    profiler.count("t.count")
    profiler.count("t.count", 4)
    assert profiler.counters()["t.count"] == before + 5


# -- the ring, threads --------------------------------------------------------

def test_the_ring_is_bounded_and_keeps_the_newest():
    for i in range(profiler.RING + 10):
        with profiler.span("t.fill", i=i):
            pass
    held = profiler.spans()
    assert len(held) == profiler.RING
    assert held[-1]["ids"] == {"i": profiler.RING + 9}
    assert all(r["name"] == "t.fill" for r in held)


def test_appends_from_four_threads_lose_nothing():
    each, t0 = 4000, time.perf_counter()     # 4 x 4000 < RING
    start = threading.Barrier(4)

    def work(k):
        start.wait(timeout=30)
        for i in range(each):
            with profiler.span("t.thread%d" % k, i=i):
                with profiler.span("t.child"):
                    pass
            profiler.count("t.threads")

    before = profiler.counters().get("t.threads", 0)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(k,)) for k in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(switch)
    held = profiler.spans(since=t0)
    by_serial = {r["serial"]: r for r in held}
    for k in range(4):
        mine = [r for r in held if r["name"] == "t.thread%d" % k]
        assert [r["ids"]["i"] for r in mine] == list(range(each))
        assert len({r["thread"] for r in mine}) == 1
    children = [r for r in held if r["name"] == "t.child"]
    assert len(children) == 4 * each
    # a child's parent is its own thread's open span, never another's
    assert all(by_serial[c["parent"]]["thread"] == c["thread"]
               for c in children)
    assert profiler.counters()["t.threads"] == before + 4 * each


# -- fit over a DevicePrefetchIter ----------------------------------------------

STEPS = 6


@pytest.fixture(scope="module")
def fit_records():
    """One epoch of a toy ``SPMDModule.fit`` over a ``DevicePrefetchIter``
    (a fresh symbol, so its first step compiles here)."""
    from mxnet_tpu.parallel import SPMDModule

    net = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(net, num_hidden=23, name="spanfc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=5, name="spanfc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rs.rand(8 * STEPS, 11).astype("f"),
                           rs.randint(0, 5, 8 * STEPS).astype("f"),
                           batch_size=8)
    opt = {"learning_rate": 0.1}
    mod = SPMDModule(net)
    t0 = time.perf_counter()
    mod.bind(it.provide_data, it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params=opt)
    fed = mx.dataflow.DevicePrefetchIter(it, stage=mod, depth=2)
    seen = []
    try:
        mod.fit(fed, num_epoch=1, eval_metric="acc", optimizer="sgd",
                optimizer_params=opt,
                batch_end_callback=lambda p: seen.append(p.nbatch))
    finally:
        fed.close()
    assert seen == list(range(STEPS))
    return profiler.spans(since=t0), profiler.counters()


@pytest.mark.parametrize("name", [
    "fit.step", "fit.metric", "fit.callback", "step.prepare",
    "step.dispatch", "step.localize"])
def test_fit_makes_one_span_of_each_kind_a_step(fit_records, name):
    mine = [r for r in fit_records[0] if r["name"] == name]
    assert [r["ids"].get("step") for r in mine] == list(range(STEPS))


def test_fit_next_carries_the_step_and_the_feeds_batch(fit_records):
    nexts = [r for r in fit_records[0] if r["name"] == "fit.next"]
    # one a step, and the one that met the epoch's end
    assert [r["ids"]["step"] for r in nexts] == list(range(STEPS + 1))
    assert "batch" not in nexts[-1]["ids"]
    batches = [r["ids"]["batch"] for r in nexts[:-1]]
    assert batches == list(range(batches[0], batches[0] + STEPS))
    waits = {r["ids"]["batch"]: r for r in fit_records[0]
             if r["name"] == "feed.get_wait"}
    for r in nexts[:-1]:
        assert waits[r["ids"]["batch"]]["parent"] == r["serial"]


@pytest.mark.parametrize("name", ["feed.source_next", "feed.stage",
                                  "feed.put_wait"])
def test_the_feeds_worker_numbers_what_fit_receives(fit_records, name):
    records, _ = fit_records
    received = {r["ids"]["batch"]: r for r in records
                if r["name"] == "fit.next" and "batch" in r["ids"]}
    mine = {r["ids"]["batch"]: r for r in records if r["name"] == name}
    assert set(received) <= set(mine)
    for batch, r in received.items():
        # staged on another thread, before fit received it
        assert mine[batch]["thread"] != r["thread"]
        assert mine[batch]["thread_name"] == "DevicePrefetchIter"
        assert mine[batch]["start"] <= r["end"]


def test_feed_counters_count_every_get(fit_records):
    counted = fit_records[1]
    assert counted["feed.gets"] >= STEPS + 1
    assert 0 <= counted.get("feed.empty_gets", 0) <= counted["feed.gets"]


@pytest.mark.parametrize("kind", ["compile.trace", "compile.lower",
                                  "compile.backend"])
def test_only_the_first_dispatch_compiles(fit_records, kind):
    records, _ = fit_records
    dispatch = [r for r in records if r["name"] == "step.dispatch"]
    inside = [[c for c in records if c["name"] == kind
               and d["start"] <= c["start"] and c["end"] <= d["end"]]
              for d in dispatch]
    assert inside[0], "no %s event inside the first step.dispatch" % kind
    assert all(c["parent"] == dispatch[0]["serial"] for c in inside[0])
    assert not any(inside[1:])
    assert dispatch[0]["end"] - dispatch[0]["start"] > \
        sum(d["end"] - d["start"] for d in dispatch[1:]) / (STEPS - 1)


@pytest.mark.parametrize("name,parent", [
    ("setup.init_optimizer", None), ("setup.bind", "setup.init_optimizer"),
    ("setup.build_step", "setup.bind"),
    ("setup.init_params", "setup.init_optimizer")])
def test_set_up_is_spanned_piece_by_piece(fit_records, name, parent):
    records, _ = fit_records
    by_serial = {r["serial"]: r for r in records}
    mine = [r for r in records if r["name"] == name]
    assert mine
    first = mine[0]
    got = by_serial[first["parent"]]["name"] if first["parent"] is not None \
        else None
    assert got == parent


def test_guard_and_metric_waits_are_spanned(fit_records):
    names = set(_names(fit_records[0]))
    # the accuracy metric is accumulated in the step: fetched at the end
    assert "step.metric_wait" in names and "step.guard_wait" in names


# -- the dumps ----------------------------------------------------------------

def test_dump_profile_holds_engine_operations_and_spans(tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler_set_config(mode="all", filename=fname)
    mx.profiler_set_state("run")
    eng = mx.engine.get()
    with profiler.span("t.dumped", step=1):
        var = eng.new_variable()
        eng.push(lambda: None, const_vars=(), mutable_vars=(var,),
                 name="dumped_op")
        eng.wait_for_all()
    mx.profiler_set_state("stop")
    assert mx.dump_profile() == fname
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    op = [e for e in events if e["name"] == "dumped_op" and e["ph"] == "B"]
    mine = [e for e in events if e["name"] == "t.dumped"]
    assert op and len(mine) == 1
    assert mine[0]["ph"] == "X" and mine[0]["args"] == {"step": 1}
    assert mine[0]["tid"] == threading.get_ident()
    # one clock: the operation ran inside the span
    assert mine[0]["ts"] - 1e4 <= op[0]["ts"] <= \
        mine[0]["ts"] + mine[0]["dur"] + 1e4
    threads = [e for e in events if e["ph"] == "M"
               and e["tid"] == mine[0]["tid"]]
    assert threads[0]["args"]["name"] == threading.current_thread().name


class _FakeTrace(object):
    """Stands in for jax.profiler's start_trace / stop_trace."""

    def __init__(self, monkeypatch):
        import jax
        self.calls = []
        monkeypatch.setattr(jax.profiler, "start_trace", self.start)
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: self.calls.append("stop"))

    def start(self, directory, profiler_options=None):
        self.calls.append((directory, profiler_options))


def test_step_trace_capture_traces_the_device_only_and_writes_its_spans(
        tmp_path, monkeypatch):
    fake = _FakeTrace(monkeypatch)

    class Trainer(object):
        def step_text(self):
            return 'HloModule jit_step\n %f.1 = ... op_name="jit(step)/a/b"'

    capture = profiler.StepTraceCapture(str(tmp_path / "tr"), 2, 3,
                                        trainer=Trainer())
    with profiler.span("t.before_window"):
        pass
    for nbatch in range(6):
        capture.on_batch(nbatch)
        with profiler.span("t.traced", step=nbatch):
            pass
    capture.stop()
    (directory, options), stop = fake.calls
    assert directory == str(tmp_path / "tr") and stop == "stop"
    assert options.host_tracer_level == 0
    assert options.python_tracer_level == 0
    with open(os.path.join(directory, capture.SPANS_FILE)) as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    steps = [e["args"]["step"] for e in events if e["name"] == "t.traced"]
    assert steps == [2, 3]          # opened at batch 2, closed at batch 4
    assert "t.before_window" not in [e["name"] for e in events]
    # Unix microseconds
    assert abs(events[0]["ts"] * 1e3 - time.time_ns()) < 60e9
    # the compiled step's text beside them, for the op table's paths
    with open(os.path.join(directory, capture.STEP_FILE)) as f:
        assert f.read() == Trainer().step_text()
    assert capture.STEP_FILE == "mxnet_tpu_step.hlo.txt"


def test_step_trace_capture_writes_no_step_text_without_a_trainer(
        tmp_path, monkeypatch):
    """Nobody handed it a trainer, or the trainer has stepped no batch."""
    _FakeTrace(monkeypatch)

    class Unstepped(object):
        def step_text(self):
            return None

    for trainer in (None, Unstepped()):
        capture = profiler.StepTraceCapture(str(tmp_path), 0, 0,
                                            trainer=trainer)
        capture.on_batch(0)
        capture.stop()
        assert os.path.exists(tmp_path / capture.SPANS_FILE)
        assert not os.path.exists(tmp_path / capture.STEP_FILE)


# -- one clock with the device trace --------------------------------------------

def test_a_span_lands_on_a_real_traces_clock(tmp_path):
    """A jax.profiler trace on the CPU with the Python tracer on: the
    span's Unix start less the trace's profile_start_time is where the
    Python tracer put the same call."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()

    def spanned_call_for_the_tracer():
        return f(x).block_until_ready()

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        t0 = time.perf_counter()
        with profiler.span("t.on_the_trace"):
            spanned_call_for_the_tracer()
    finally:
        jax.profiler.stop_trace()
    mine = [r for r in profiler.spans(since=t0)
            if r["name"] == "t.on_the_trace"][0]
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[0]
    data = jax.profiler.ProfileData.from_file(path)
    start, traced = None, []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        for line in plane.lines:
            traced += [e.start_ns for e in line.events
                       if "spanned_call_for_the_tracer" in e.name]
    assert start is not None and traced
    assert abs((mine["unix_ns"] - start) - traced[0]) < 1e6     # 1 ms


def _device(ops):
    return {"/device:TPU:0": {"XLA Ops": [(s, d, "op") for s, d in ops],
                              "XLA Modules": []}}


def _record(name, unix_ns, seconds, thread=1):
    return {"name": name, "thread": thread, "unix_ns": unix_ns,
            "start": 0.0, "end": seconds}


def test_idle_gaps_puts_a_gap_down_to_the_innermost_span():
    base = 1_700_000_000_000_000_000
    # busy 0-10 ms, idle 10-14 ms, busy 14-20 ms, idle 20-21 ms, busy to 30
    devices = _device([(0, 10e6), (14e6, 6e6), (21e6, 9e6)])
    devices["/device:TPU:1"] = {"XLA Ops": [(0, 1e6, "op")]}   # less busy
    records = [
        _record("fit.step", base + 9_000_000, 0.0045),       # 9-13.5 ms
        _record("step.guard_wait", base + 9_500_000, 0.001),     # to 10.5
        _record("step.dispatch", base + 12_000_000, 0.0015),     # 12-13.5
        _record("feed.stage", base + 10_000_000, 0.004, thread=2),
    ]
    got = profiler._attribute_gaps(base, devices, records, top=1)
    assert got["device"] == "/device:TPU:0"
    assert got["window_s"] == pytest.approx(0.030)
    assert got["idle_s"] == pytest.approx(0.005)
    # 10-10.5 guard_wait, 10.5-12 fit.step, 12-13.5 dispatch, 13.5-14 and
    # the whole second gap nobody's; the feed's thread is not asked
    assert got["by_span"] == pytest.approx({
        "step.guard_wait": 0.0005, "fit.step": 0.0015,
        "step.dispatch": 0.0015, "unattributed": 0.0015})
    assert len(got["gaps"]) == 1
    assert got["gaps"][0]["seconds"] == pytest.approx(0.004)
    assert got["gaps"][0]["start_unix_ns"] == base + 10_000_000
    assert "feed.stage" not in got["by_span"]


def test_idle_gaps_asks_every_thread_where_nothing_dispatched():
    base = 1_700_000_000_000_000_000
    records = [_record("feed.stage", base + 10_000_000, 0.004, thread=2)]
    got = profiler._attribute_gaps(
        base, _device([(0, 10e6), (14e6, 6e6)]), records)
    assert got["by_span"] == pytest.approx({"feed.stage": 0.004})


def test_idle_gaps_raises_without_profile_start_time():
    with pytest.raises(MXNetError, match="profile_start_time"):
        profiler._attribute_gaps(None, _device([(0, 1e6), (2e6, 1e6)]), [])


def test_idle_gaps_reads_a_traces_newest_xplane_and_the_spans_file(tmp_path):
    """A CPU trace holds no TPU plane: the reading gets as far as saying
    so, past the profile_start_time and the spans file."""
    import jax
    capture = profiler.StepTraceCapture(str(tmp_path), 0, 0)
    capture.on_batch(0)
    with profiler.span("t.cpu_traced"):
        jax.jit(lambda x: x + 1)(1.0).block_until_ready()
    capture.stop()
    assert os.path.exists(tmp_path / capture.SPANS_FILE)
    start, devices = profiler._device_lines(str(tmp_path))
    assert abs(start - time.time_ns()) < 60e9 and devices == {}
    with pytest.raises(MXNetError, match="no device plane"):
        profiler.idle_gaps(str(tmp_path))
    with pytest.raises(MXNetError, match="xplane"):
        profiler.idle_gaps(str(tmp_path / "nowhere"))
