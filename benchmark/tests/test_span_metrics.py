"""The seven per-layer metrics that read the program's span recorder, each on
a hand-written span list: the value, and nothing (None) where the spans it
needs are absent or the program keeps none."""
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from mxnet_tpu import profiler  # noqa: E402

T0 = 100.0                       # the window: [100 s, 101 s], 4 steps
FACTS = {"window": {"t_start": T0, "seconds": 1.0, "steps": 4}}
MAIN, FEED, DECODER = 1, 2, 3


def rec(name, start_ms, ms, thread=MAIN, **ids):
    """A span ``start_ms`` after the window's start, ``ms`` long."""
    start = T0 + start_ms / 1e3
    return {"name": name, "start": start, "end": start + ms / 1e3,
            "unix_ns": 0, "thread": thread, "serial": 0, "parent": None,
            "thread_name": {MAIN: "MainThread", FEED: "DevicePrefetchIter",
                            DECODER: "decode"}[thread], "ids": ids}


def steps():
    """Four steps: dispatches end at 12, 112, 212 and 512 ms (the last
    period holds a 200 ms stall in the feed)."""
    out = []
    for k, at in enumerate((0, 100, 200, 500)):
        out += [rec("step.guard_wait", at, 4, step=k),
                rec("step.prepare", at + 4, 5, step=k),
                rec("step.dispatch", at + 9, 3, step=k),
                rec("step.localize", at + 12, 1, step=k),
                rec("feed.get_wait", at + 20, 200 if k == 2 else 2, step=k),
                rec("feed.source_next", at + 1, 30, FEED, batch=k),
                rec("feed.stage", at + 31, 10, FEED, batch=k),
                rec("feed.put_wait", at + 41, 50, FEED, batch=k),
                rec("decode.read", at, 5, DECODER),
                rec("decode.batch", at + 5, 50, DECODER, images=250 + k)]
    # a fetch of the in-step metric that the end of step 1's dispatch
    # (112 ms) cuts in two: each period is less the part inside it
    out.append(rec("step.metric_wait", 110, 6))
    # a dispatch on another thread (a second trainer) is not this loop's
    out.append(rec("step.dispatch", 50, 1, thread=FEED))
    # set-up's compilation, before the window; one trace nested in another
    out += [rec("compile.trace", -5000, 2000), rec("compile.trace", -4500, 500),
            rec("compile.lower", -3000, 1000),
            rec("compile.backend", -2000, 1500),
            rec("compile.trace", 600, 100)]       # inside the window: not set-up
    return out


@pytest.fixture
def recorder(monkeypatch):
    """Puts a span list in the recorder's place."""
    def install(records):
        def spans(since=None, until=None):
            lo = -float("inf") if since is None else since
            hi = float("inf") if until is None else until
            return sorted((r for r in records if lo <= r["start"] <= hi),
                          key=lambda r: r["start"])
        monkeypatch.setattr(profiler, "spans", spans, raising=False)
    return install


def read(name):
    return importlib.import_module("benchmark.metrics." + name).read(FACTS)


WANT = {
    # periods 100, 100, 300 ms; less the guard waits (4 each) and the
    # metric fetch, 2 ms before step 1's dispatch ended and 4 ms after
    "host_turnaround_ms": (94 + 92 + 296) / 3,
    "step_dispatch_ms": 9.0,
    "step_period_max_ms": 300.0,
    "feed_wait_ms": (2 + 2 + 200 + 2) / 4,
    "feed_busy_share": 100.0 * 4 * 0.040 / 1.0,
    "decode_rate": (250 + 251 + 252 + 253) / 0.2,
    "setup_trace_lower_s": 3.5,
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_written_span_list(recorder, name):
    recorder(steps())
    assert read(name) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_an_empty_recorder(recorder, name):
    recorder([])
    assert read(name) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_returns_nothing_from_a_program_without_a_recorder(
        monkeypatch, name):
    monkeypatch.delattr(profiler, "spans", raising=False)
    assert read(name) is None


@pytest.mark.parametrize("name,kept", [
    ("host_turnaround_ms", ("step.dispatch",)),       # one dispatch: no period
    ("step_period_max_ms", ("step.dispatch",)),
    ("step_dispatch_ms", ("step.prepare",)),          # no dispatch: no step
    ("feed_wait_ms", ("feed.stage",)),
    ("feed_busy_share", ("feed.get_wait",)),
    ("decode_rate", ("decode.read",)),
    ("setup_trace_lower_s", ("compile.backend",))])
def test_reader_finds_nothing_without_the_spans_it_needs(recorder, name,
                                                         kept):
    records = [r for r in steps() if r["name"] in kept]
    if kept == ("step.dispatch",):
        records = records[:1]
    recorder(records)
    assert read(name) is None


def test_the_longest_period_names_the_spans_that_fill_it(recorder, capsys):
    recorder(steps())
    read("step_period_max_ms")
    err = capsys.readouterr().err
    assert "between the dispatches of steps 2 and 3" in err
    first = err.splitlines()[1].split()
    assert first[:2] == ["feed.get_wait", "MainThread"]
    assert float(first[2]) == pytest.approx(200.0)


def test_setup_trace_lower_reports_the_wall_time_beside_the_sum(recorder,
                                                                capsys):
    recorder(steps())
    read("setup_trace_lower_s")
    # 2 s of tracing (the nested 0.5 s inside it) + 1 s of lowering
    assert "3 events, 3.500 s summed, 3.000 s of wall time" in \
        capsys.readouterr().err


def test_every_span_metric_is_in_the_spec_with_its_cells():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mine = {m["name"]: m for m in spec["per_layer"] if m["name"] in WANT}
    assert set(mine) == set(WANT)
    both = ["resnet50.fed", "lstm_ptb_large.train"]
    for name, m in mine.items():
        assert m["source"] == "program_counter"
        assert m["workloads"] == (["resnet50.fed"] if name == "decode_rate"
                                  else both)
        assert m["moves"] == ("setup_s" if name == "setup_trace_lower_s"
                              else "train_throughput")
