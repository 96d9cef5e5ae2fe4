"""``attn_kernel_share`` on recorded ``kernel.route`` events: the share of
set-up's ``GQAttention`` lowerings that took the compiled kernels — 100
where every lowering did, and nothing from a program that records no such
event (the parent of the PR that brought them: its ``gqa_attention`` had
one tier and said nothing)."""
import json
import os

import pytest

from benchmark.tests.test_gdn_kernel_share import recorded, route  # noqa: F401
from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import attn_kernel_share

CELLS = {"qwen3_next_80b_a3b.train_8k", "kimi_linear_48b_a3b.train_8k",
         "zaya1_8b.train_8k"}


def attn(tier, reason, end=1.0):
    return route(tier, reason, kernel="gqa_attention", end=end)


@pytest.mark.parametrize("records,want", [
    ([attn("pallas", "aligned")] * 5, 100.0),
    ([attn("pallas", "aligned"), attn("lax", "shapes"),
      attn("pallas", "aligned"), attn("lax", "mesh")], 50.0),
    ([attn("lax", "shapes")], 0.0),
    # the delta rule's events, a step's span and an event inside the
    # window are not this kernel's set-up
    ([attn("pallas", "aligned"), route("lax", "shapes"),
      {"name": "step.dispatch", "start": 2.0, "end": 2.1, "ids": {}},
      attn("lax", "shapes", end=11.0)], 100.0),
], ids=["all_compiled", "mixed", "none_compiled", "others_left_out"])
def test_share_of_the_lowerings_routed_to_the_kernels(recorded, records,
                                                      want):
    assert attn_kernel_share.read(recorded(records)) == pytest.approx(want)


@pytest.mark.parametrize("records", [
    [], [{"name": "compile.trace", "start": 1.0, "end": 2.0, "ids": {}}],
    [route("pallas", "aligned")]],
    ids=["empty", "no_route", "other_kernel"])
def test_nothing_from_a_program_that_records_no_route(recorded, records):
    assert attn_kernel_share.read(recorded(records)) is None


def test_the_reader_reads_what_the_op_records():
    """The op's own event, through the real recorder."""
    import time
    import numpy as np
    from mxnet_tpu.ops.contrib import gq_attention
    x = np.ones((1, 8, 2, 4), "f")
    gq_attention(x, x, x)
    facts = {"window": {"t_start": time.perf_counter()}}
    assert attn_kernel_share.routes(facts)[-1] == {
        "kernel": "gqa_attention", "tier": "lax", "reason": "shapes"}
    assert attn_kernel_share.read(facts) is not None


def test_the_metric_is_in_the_spec_and_the_cells_are_among_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "attn_kernel_share"]
    assert CELLS <= set(entry.pop("workloads"))
    assert entry == {
        "name": "attn_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput"}
    assert CELLS <= {w["name"] for w in spec["workloads"]}
