"""``step_overlap_share`` on hand-written span lists: the share of the
loop's dispatches noted ``queued=1``, and nothing where no dispatch
carries the note (the parent of the PR that brought it), where the
recorder is empty, or where the program keeps none."""
import json
import os

import pytest

from benchmark.tests.test_span_metrics import (FACTS, FEED, ROOT, profiler,
                                               rec, recorder, steps)  # noqa: F401
from benchmark.metrics import step_overlap_share


def noted(queued):
    """The four steps of ``test_span_metrics``, their dispatches noted."""
    records, left = steps(), list(queued)
    for r in records:
        if r["name"] == "step.dispatch" and r["thread"] != FEED:
            r["ids"]["queued"] = left.pop(0)
    return records


@pytest.mark.parametrize("queued,want", [
    ((0, 1, 1, 1), 75.0), ((1, 1, 1, 1), 100.0), ((0, 0, 0, 0), 0.0)])
def test_share_of_the_dispatches_noted_queued(recorder, queued, want):
    recorder(noted(queued))
    assert step_overlap_share.read(FACTS) == pytest.approx(want)


def test_another_threads_dispatch_is_not_this_loops(recorder):
    records = noted((1, 1, 1, 1))
    records.append(rec("step.dispatch", 60, 1, thread=FEED, queued=0))
    recorder(records)
    assert step_overlap_share.read(FACTS) == 100.0


def test_nothing_without_the_note(recorder):
    recorder(steps())
    assert step_overlap_share.read(FACTS) is None


def test_nothing_in_an_empty_recorder(recorder):
    recorder([])
    assert step_overlap_share.read(FACTS) is None


def test_nothing_from_a_program_without_a_recorder(monkeypatch):
    monkeypatch.delattr(profiler, "spans", raising=False)
    assert step_overlap_share.read(FACTS) is None


def test_the_metric_is_in_the_spec_with_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert spec["per_layer"][-1] == {
        "name": "step_overlap_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "step",
        "moves": "train_throughput",
        "workloads": ["resnet50.fed", "lstm_ptb_large.train"]}
