"""``causal_conv_kernel_share`` on recorded ``kernel.route`` events: the
share of set-up's depthwise ``CausalConv1D`` lowerings that took the
compiled kernels — 100 where every lowering did, and nothing from a program
that records no such event (the parent of the PR that brought them: its
convolution had one tier and said nothing)."""
import json
import os

import pytest

from benchmark.tests.test_gdn_kernel_share import recorded, route  # noqa: F401
from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import causal_conv_kernel_share

CELLS = {"qwen3_next_80b_a3b.train_8k", "kimi_linear_48b_a3b.train_8k",
         "zaya1_8b.train_8k"}


def conv(tier, reason, end=1.0):
    return route(tier, reason, kernel="causal_conv", end=end)


@pytest.mark.parametrize("records,want", [
    ([conv("pallas", "aligned")] * 12, 100.0),
    ([conv("pallas", "aligned"), conv("lax", "shapes"),
      conv("pallas", "aligned"), conv("lax", "mesh")], 50.0),
    ([conv("lax", "shapes")], 0.0),
    # the delta rule's and the attention's events, a step's span and an
    # event inside the window are not this kernel's set-up
    ([conv("pallas", "aligned"), route("lax", "shapes"),
      route("lax", "shapes", kernel="gqa_attention"),
      {"name": "step.dispatch", "start": 2.0, "end": 2.1, "ids": {}},
      conv("lax", "shapes", end=11.0)], 100.0),
], ids=["all_compiled", "mixed", "none_compiled", "others_left_out"])
def test_share_of_the_lowerings_routed_to_the_kernels(recorded, records,
                                                      want):
    assert causal_conv_kernel_share.read(recorded(records)) \
        == pytest.approx(want)


@pytest.mark.parametrize("records", [
    [], [{"name": "compile.trace", "start": 1.0, "end": 2.0, "ids": {}}],
    [route("pallas", "aligned")]],
    ids=["empty", "no_route", "other_kernel"])
def test_nothing_from_a_program_that_records_no_route(recorded, records):
    assert causal_conv_kernel_share.read(recorded(records)) is None


@pytest.mark.parametrize("attrs,want", [
    (dict(kernel=4, act_type="silu"),
     {"kernel": "causal_conv", "tier": "lax", "reason": "shapes"}),
    (dict(kernel=2, num_group=2), None),
], ids=["depthwise", "grouped"])
def test_the_reader_reads_what_the_op_records(attrs, want):
    """The op's own event, through the real recorder: one a depthwise
    lowering, none from the grouped branch."""
    import time
    import numpy as np
    from mxnet_tpu.ops.nn import causal_conv1d
    since = {"window": {"t_start": time.perf_counter()}}
    before = len(causal_conv_kernel_share.routes(since))
    groups = attrs.get("num_group", 0)
    weight = np.ones((8, 8 // groups, 2) if groups else (8, 4), "f")
    causal_conv1d(np.ones((1, 8, 8), "f"), weight, **attrs)
    facts = {"window": {"t_start": time.perf_counter()}}
    found = causal_conv_kernel_share.routes(facts)
    if want is None:
        assert len(found) == before
        return
    assert len(found) == before + 1 and found[-1] == want
    assert causal_conv_kernel_share.read(facts) is not None


def test_the_metric_is_in_the_spec_and_the_cells_are_among_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "causal_conv_kernel_share"]
    assert CELLS <= set(entry.pop("workloads"))
    assert entry == {
        "name": "causal_conv_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput"}
    assert CELLS <= {w["name"] for w in spec["workloads"]}
