"""``moe_compact_share`` on hand-made ``step.counters`` records: the share
of the window's routed-expert calls whose held pairs fit the compact
capacity, and nothing from a program that counts neither calls nor
compact calls (the parent of the PR that brought them)."""
import json
import os

import pytest

from benchmark.tests.test_qwen3_next import _facts, counters  # noqa: F401
from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import moe_compact_share


def step(calls, compact):
    return {"moe.assignments": 655360.0, "moe.assignments_here": 40100.0,
            "moe.load_max": 1700.0, "moe.load_mean": 320.0,
            "moe.calls": float(calls), "moe.compact_calls": float(compact)}


@pytest.mark.parametrize("steps,want", [
    ([step(4, 4)] * 3, 100.0),
    ([step(4, 4), step(4, 3), step(4, 1)], 100.0 * 8 / 12),
    ([step(4, 0)] * 2, 0.0),
])
def test_share_of_the_windows_calls_that_were_compact(counters, steps, want):
    assert moe_compact_share.read(_facts({}, steps)) == pytest.approx(want)


def test_a_step_without_the_two_counts_is_left_out(counters):
    old = {k: v for k, v in step(4, 4).items()
           if k not in ("moe.calls", "moe.compact_calls")}
    assert moe_compact_share.read(_facts({}, [old, step(4, 2)])) == 50.0


@pytest.mark.parametrize("steps", [
    [], [{"moe.load_max": 9.0, "moe.load_mean": 3.0}], [step(0, 0)]])
def test_nothing_from_a_program_without_the_counters(counters, steps):
    assert moe_compact_share.read(_facts({}, steps)) is None


def test_nothing_where_no_record_starts_in_the_window():
    facts = _facts({})
    facts["window"]["t_start"] = 1e12
    assert moe_compact_share.read(facts) is None


def test_the_metric_is_in_the_spec_with_its_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = [m for m in spec["per_layer"]
             if m["name"] == "moe_compact_share"]
    assert entry == [{
        "name": "moe_compact_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput",
        "workloads": ["qwen3_next_80b_a3b.train_8k"]}]
