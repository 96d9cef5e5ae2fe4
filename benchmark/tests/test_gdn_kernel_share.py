"""``gdn_kernel_share`` on recorded ``kernel.route`` events: the share of
set-up's ``GatedDeltaRule`` lowerings that took the compiled kernels, and
nothing from a program that records no such event (the parent of the PR
that brought them)."""
import json
import os

import pytest

from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import gdn_kernel_share


def route(tier, reason, kernel="delta_rule", end=1.0):
    return {"name": "kernel.route", "start": end, "end": end,
            "ids": {"kernel": kernel, "tier": tier, "reason": reason}}


PALLAS, SHAPES, MESH = (route("pallas", "aligned"), route("lax", "shapes"),
                        route("lax", "mesh"))


@pytest.fixture
def recorded(monkeypatch):
    """Hand the reader these records in place of the program's recorder."""
    from mxnet_tpu import profiler

    def give(records):
        monkeypatch.setattr(
            profiler, "spans", lambda since=None, until=None: [
                r for r in records if until is None or r["start"] <= until])
        return {"window": {"t_start": 10.0}}
    return give


@pytest.mark.parametrize("records,want", [
    ([PALLAS] * 6, 100.0),
    ([PALLAS, SHAPES, PALLAS, MESH], 50.0),
    ([SHAPES, MESH], 0.0),
    # another kernel's events, a step's span and an event inside the
    # window are not this kernel's set-up
    ([PALLAS, route("lax", "shapes", kernel="flash_attention"),
      {"name": "step.dispatch", "start": 2.0, "end": 2.1, "ids": {}},
      route("lax", "shapes", end=11.0)], 100.0),
], ids=["all_compiled", "mixed", "none_compiled", "others_left_out"])
def test_share_of_the_lowerings_routed_to_the_kernels(recorded, records,
                                                      want):
    assert gdn_kernel_share.read(recorded(records)) == pytest.approx(want)


@pytest.mark.parametrize("records", [
    [], [{"name": "compile.trace", "start": 1.0, "end": 2.0, "ids": {}}],
    [route("lax", "shapes", kernel="flash_attention")]],
    ids=["empty", "no_route", "other_kernel"])
def test_nothing_from_a_program_that_records_no_route(recorded, records):
    assert gdn_kernel_share.read(recorded(records)) is None


def test_the_reader_reads_what_the_op_records():
    """The op's own event, through the real recorder."""
    import time
    import numpy as np
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    x = np.ones((1, 8, 1, 4), "f")
    h = np.ones((1, 8, 1), "f")
    gated_delta_rule_op(x, x, x, h, h, np.zeros(1, "f"), np.ones(1, "f"),
                        chunk=4)
    facts = {"window": {"t_start": time.perf_counter()}}
    assert gdn_kernel_share.routes(facts)[-1] == {
        "kernel": "delta_rule", "tier": "lax", "reason": "shapes"}
    assert gdn_kernel_share.read(facts) is not None


def test_the_metric_is_in_the_spec_with_its_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = [m for m in spec["per_layer"]
             if m["name"] == "gdn_kernel_share"]
    assert entry == [{
        "name": "gdn_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput",
        "workloads": ["qwen3_next_80b_a3b.train_8k"]}]
