"""``kda_kernel_share`` on recorded ``kernel.route`` events: the share of
set-up's ``GatedDeltaRule`` lowerings of the Kimi Linear step that took
the compiled kernels — 0 from the parent of the PR that gave the vector
decay its kernels (four lowerings, all ``lax``), 100 with it — and nothing
from a program that records no such event."""
import json
import os

import pytest

from benchmark.tests.test_gdn_kernel_share import recorded, route  # noqa: F401
from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import kda_kernel_share

CELL = "kimi_linear_48b_a3b.train_8k"


def channel(tier, reason):
    r = route(tier, reason)
    r["ids"]["decay"] = "channel"
    return r


@pytest.mark.parametrize("records,want", [
    ([route("lax", "channel_decay")] * 4, 0.0),
    ([channel("pallas", "aligned")] * 4, 100.0),
    ([channel("pallas", "aligned"), channel("lax", "shapes"),
      channel("pallas", "aligned"), channel("lax", "mesh")], 50.0),
    # another kernel's events, a step's span and an event inside the
    # window are not this kernel's set-up
    ([channel("pallas", "aligned"),
      route("lax", "shapes", kernel="flash_attention"),
      {"name": "step.dispatch", "start": 2.0, "end": 2.1, "ids": {}},
      route("lax", "channel_decay", end=11.0)], 100.0),
], ids=["parent_all_lax", "all_compiled", "mixed", "others_left_out"])
def test_share_of_the_lowerings_routed_to_the_kernels(recorded, records,
                                                      want):
    assert kda_kernel_share.read(recorded(records)) == pytest.approx(want)


@pytest.mark.parametrize("records", [
    [], [{"name": "compile.trace", "start": 1.0, "end": 2.0, "ids": {}}],
    [route("lax", "shapes", kernel="flash_attention")]],
    ids=["empty", "no_route", "other_kernel"])
def test_nothing_from_a_program_that_records_no_route(recorded, records):
    assert kda_kernel_share.read(recorded(records)) is None


def test_the_reader_reads_what_the_vector_decay_records():
    """The op's own event for a decay per key channel, through the real
    recorder: told apart from a scalar decay's by its ``decay`` id."""
    import time
    import numpy as np
    from benchmark.metrics import gdn_kernel_share
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    x = np.ones((1, 8, 1, 4), "f")
    h = np.ones((1, 8, 1), "f")
    gated_delta_rule_op(x, x, x, x, h, np.zeros(1, "f"), np.ones(4, "f"),
                        chunk=4)
    facts = {"window": {"t_start": time.perf_counter()}}
    assert gdn_kernel_share.routes(facts)[-1] == {
        "kernel": "delta_rule", "tier": "lax", "reason": "shapes",
        "decay": "channel"}
    assert kda_kernel_share.read(facts) is not None


def test_the_metric_is_in_the_spec_and_the_cell_is_among_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "kda_kernel_share"]
    assert CELL in entry.pop("workloads")
    assert entry == {
        "name": "kda_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput"}
    assert CELL in {w["name"] for w in spec["workloads"]}
