"""The reduction from a profiler trace to numbers, on the CPU: interval
arithmetic and the scope rule on hand-made events, and the whole reduction
on small traces recorded on the chip (``data/trace_*.json.gz``, cut by
``tools/cut_trace.py`` from this PR's first traced runs: the tail of one
step of the cell, the idle stretch between, the head of the next).  Each
figure is checked against a second, slower way of getting it, and pinned.
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import trace_reduce as T  # noqa: E402

RECORDED = {
    "resnet50.fed": "trace_resnet50_fed.json.gz",
    "lstm_ptb_large.train": "trace_lstm_ptb_large_train.json.gz",
}


def ev(name, start, dur, **stats):
    return [name, float(start), float(dur), stats]


def plane(ops=(), asyncs=(), modules=()):
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": list(ops)},
        {"name": "Async XLA Ops", "events": list(asyncs)},
        {"name": "XLA Modules", "events": list(modules)}]}


def covered(intervals):
    """Length of the union, by walking the sorted endpoints."""
    points = sorted({p for s, e in intervals for p in (s, e)})
    return sum(b - a for a, b in zip(points, points[1:])
               if any(s <= a and b <= e for s, e in intervals))


def test_interval_arithmetic():
    merged = T.union([(0, 4), (2, 6), (10, 12), (11, 11.5), (12, 13)])
    assert merged == [[0, 6], [10, 13]]
    assert T.total(merged) == 9
    assert T.subtract([[0, 10]], [[2, 3], [5, 7], [9, 20]]) == 6
    assert T.subtract([[0, 2], [4, 6]], []) == 4
    assert T.subtract([[5, 6]], [[0, 10]]) == 0
    assert T.gaps(merged, 0, 20) == [(6, 10), (13, 20)]
    assert T.gaps([], 3, 5) == [(3, 5)]


def test_a_scope_gets_self_time_not_its_childrens():
    ops = [ev("%while.1 = while(...)", 0, 100),
           ev("%fusion.1 = fusion(...)", 10, 20),
           ev("%fusion.2 = fusion(...)", 40, 30),
           ev("%copy.3 = copy(...)", 45, 5),          # inside fusion.2
           ev("%fusion.9 = fusion(...)", 120, 10)]
    got = {T.instruction_of(e): t for e, t in T.self_times(ops)}
    assert got == {"while.1": 50, "fusion.1": 20, "fusion.2": 25,
                   "copy.3": 5, "fusion.9": 10}
    assert sum(got.values()) == covered([(e[1], e[1] + e[2]) for e in ops])


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jvp(conv0)/conv_general_dilated", "conv0"),
    ("jit(step)/transpose(jvp(stage1_unit1_bn1))/mul", "_backward_stage1_unit1_bn1"),
    ("jit(step)/jvp(lstm)/while/body/dot_general", "lstm"),
    ("jit(step)/transpose(jvp(lstm))/while/body/closed_call/mul",
     "_backward_lstm"),
    ("jit(step)/sgd/mul", "sgd"),
    ("jit(step)/add", "add"),
])
def test_scope_rule(path, scope):
    e = ev("%fusion.5 = bf16[8]{0} fusion(...)", 0, 1)
    assert T.scope_of(e, {"fusion.5": path}) == scope
    assert T.scope_of(ev("%x = f32[] add(...)", 0, 1, tf_op=path)) == scope


def test_an_event_without_a_path_keeps_its_instructions_name():
    assert T.scope_of(ev("%copy-done.37 = bf16[4]{0} copy-done(...)", 0, 1)) \
        == "hlo:copy-done"
    assert T.scope_of(ev("%all-reduce.12 = f32[4]{0} all-reduce(...)", 0, 1),
                      {"fusion.1": "jit(step)/jvp(a)/b"}) == "hlo:all-reduce"


def test_instruction_scopes_reads_op_names_from_hlo_text():
    text = '''
  %fusion.7 = bf16[2]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/jvp(conv0)/conv" source_file="x.py"}
  ROOT %tuple.1 = (bf16[2]{0}) tuple(%fusion.7)
  all-reduce.3 = f32[4]{0} all-reduce(%g), metadata={op_name="jit(step)/transpose(jvp(fc1))/dot_general"}
'''
    assert T.instruction_scopes(text) == {
        "fusion.7": "jit(step)/jvp(conv0)/conv",
        "all-reduce.3": "jit(step)/transpose(jvp(fc1))/dot_general"}


def test_collective_time_and_its_exposed_part():
    """Compute runs 0-10 and 30-40; an all-reduce is in flight 5-25 (async
    line) and a synchronous all-gather runs 40-45: 25 of collective time,
    of which 5-10 hides behind compute."""
    p = plane(ops=[ev("%fusion.1 = fusion()", 0, 10),
                   ev("%fusion.2 = fusion()", 30, 10),
                   ev("%all-gather.1 = all-gather()", 40, 5)],
              asyncs=[ev("%all-reduce-start.1 = all-reduce-start()", 5, 20)],
              modules=[ev("jit_step(1)", 0, 45)])
    r = T.reduce_device(p)
    assert r["collective_s"] * 1e9 == pytest.approx(25)
    assert r["collective_exposed_s"] * 1e9 == pytest.approx(20)
    assert r["busy_s"] * 1e9 == pytest.approx(25)
    assert r["window_s"] * 1e9 == pytest.approx(45)
    assert r["step_module"] == "jit_step"
    assert r["step_module_s"] * 1e9 == pytest.approx(45)
    # several chips: busy and window are averaged, the collective figures
    # are the worst device's
    q = plane(ops=[ev("%fusion.1 = fusion()", 0, 45)],
              modules=[ev("jit_step(1)", 0, 45)])
    q["name"] = "/device:TPU:1"
    both = T.reduce({"planes": [p, q]}, chips=2)
    assert both["busy_s"] * 1e9 == pytest.approx(35)
    assert both["busy_s_fullest"] * 1e9 == pytest.approx(45)
    assert both["collective_exposed_s"] * 1e9 == pytest.approx(20)
    assert both["devices"] == 2


def test_idle_gaps_are_labelled_by_what_the_host_was_doing():
    p = plane(ops=[ev("%fusion.1 = fusion()", 0, 10),
                   ev("%fusion.2 = fusion()", 60, 10),
                   ev("%fusion.3 = fusion()", 75, 5)])
    host = {"name": "/host:CPU", "lines": [
        {"name": "main", "events": [
            ev("$base_module.py:1 fit", 0, 100),
            ev("$train_fit.py:5 next", 8, 35), ev("$queue.py:1 get", 12, 30),
            ev("$trainer.py:9 step", 70, 6)]},
        {"name": "decoder", "events": [ev("$image.py:630 _put", 5, 38)]}]}
    r = T.reduce({"planes": [p, host]}, host_marker="train_fit.py:")
    assert [g[1] * 1e9 for g in r["idle_gaps"]] == pytest.approx([50, 5])
    # frames of the harness's own thread that are about the gap, outermost
    # first; the loop around the whole run is not
    assert r["idle_gaps"][0][0] == "train_fit.py:5 next > queue.py:1 get"
    assert r["idle_gaps"][1][0] == "trainer.py:9 step"
    # without the marker every thread's frames count
    r = T.reduce({"planes": [p, host]})
    assert r["idle_gaps"][0][0] == \
        "image.py:630 _put > train_fit.py:5 next > queue.py:1 get"
    b = T.breakdown(r)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_device_events_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_trace(cell):
    path = os.path.join(HERE, "data", RECORDED[cell])
    recorded = T.load(path)
    paths = recorded["paths"]
    r = T.reduce(recorded, 1, paths)
    dev = [p for p in recorded["planes"] if p["name"] == r["plane"]][0]
    ops = [e for line in dev["lines"] if line["name"] == "XLA Ops"
           for e in line["events"]]
    assert 30 < len(ops) < 3000
    spans = [(e[1], e[1] + e[2]) for e in ops]

    # busy union and window, a second way
    assert r["busy_s"] * 1e9 == pytest.approx(covered(spans), rel=1e-9)
    assert r["window_s"] * 1e9 == pytest.approx(
        max(e for _, e in spans) - min(s for s, _ in spans))
    assert 0 < r["busy_s"] < r["window_s"]

    # per-scope sums: every nanosecond of the union belongs to one scope
    assert sum(r["scopes_s"].values()) == pytest.approx(r["busy_s"],
                                                        rel=1e-9)
    named = {k: v for k, v in r["scopes_s"].items()
             if not k.startswith("hlo:")}
    assert named, "no event was given a graph node's scope"

    # the step program is the module that took most device time
    assert r["step_module"] == "jit_step"

    # idle gaps: sorted, inside the window, labelled
    gaps = r["idle_gaps"]
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(g[1] for g in gaps) <= r["window_s"] - r["busy_s"] + 1e-12
    assert all(isinstance(g[0], str) and g[0] for g in gaps)
    assert r["collective_s"] == 0 and r["collective_exposed_s"] == 0
