"""``head_norm_kernel_share`` on recorded ``kernel.route`` events: the share
of set-up's heads' ``RMSNorm`` lowerings that took the compiled kernels —
100 where every lowering did, and nothing from a program that records no
such event (the parent of the PR that brought them: its norm had one tier
and said nothing)."""
import json
import os

import pytest

from benchmark.tests.test_gdn_kernel_share import recorded, route  # noqa: F401
from benchmark.tests.test_span_metrics import ROOT
from benchmark.metrics import head_norm_kernel_share

CELLS = {"qwen3_next_80b_a3b.train_8k", "kimi_linear_48b_a3b.train_8k",
         "trinity_mini.train_8k"}


def norm(tier, reason, end=1.0):
    return route(tier, reason, kernel="head_norm", end=end)


@pytest.mark.parametrize("records,want", [
    ([norm("pallas", "aligned")] * 12, 100.0),
    ([norm("pallas", "aligned"), norm("lax", "shapes"),
      norm("pallas", "aligned"), norm("lax", "mesh")], 50.0),
    ([norm("lax", "shapes")], 0.0),
    # the other kernels' events, a step's span and an event inside the
    # window are not this kernel's set-up
    ([norm("pallas", "aligned"), route("lax", "shapes"),
      route("lax", "shapes", kernel="causal_conv"),
      {"name": "step.dispatch", "start": 2.0, "end": 2.1, "ids": {}},
      norm("lax", "shapes", end=11.0)], 100.0),
], ids=["all_compiled", "mixed", "none_compiled", "others_left_out"])
def test_share_of_the_lowerings_routed_to_the_kernels(recorded, records,
                                                      want):
    assert head_norm_kernel_share.read(recorded(records)) \
        == pytest.approx(want)


@pytest.mark.parametrize("records", [
    [], [{"name": "compile.trace", "start": 1.0, "end": 2.0, "ids": {}}],
    [route("pallas", "aligned", kernel="causal_conv")]],
    ids=["empty", "no_route", "other_kernel"])
def test_nothing_from_a_program_that_records_no_route(recorded, records):
    assert head_norm_kernel_share.read(recorded(records)) is None


@pytest.mark.parametrize("shape,attrs,want", [
    ((1, 16, 2, 128), dict(rotary_dim=128),
     {"kernel": "head_norm", "tier": "pallas", "reason": "aligned"}),
    ((1, 16, 2, 8), dict(gated=True),
     {"kernel": "head_norm", "tier": "lax", "reason": "shapes"}),
    ((16, 128), {}, None),
    ((1, 16, 256), {}, None),
], ids=["heads", "narrow_heads", "stream", "rows"])
def test_the_reader_reads_what_the_op_records(shape, attrs, want):
    """The op's own event, through the real recorder: one a lowering of
    rank 4 (``pallas`` where the kernels take it, whichever platform the
    program is then lowered for), none from a norm of rank 2 or 3."""
    import time
    import numpy as np
    from mxnet_tpu.ops.nn import rms_norm
    since = {"window": {"t_start": time.perf_counter()}}
    before = len(head_norm_kernel_share.routes(since))
    x = np.ones(shape, "f")
    gate = x if attrs.pop("gated", False) else None
    rms_norm(x, np.ones(shape[-1:], "f"), gate, **attrs)
    facts = {"window": {"t_start": time.perf_counter()}}
    found = head_norm_kernel_share.routes(facts)
    if want is None:
        assert len(found) == before
        return
    assert len(found) == before + 1 and found[-1] == want
    assert head_norm_kernel_share.read(facts) is not None


def test_the_metric_is_in_the_spec_and_the_cells_are_among_its_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry, = [m for m in spec["per_layer"]
              if m["name"] == "head_norm_kernel_share"]
    assert CELLS <= set(entry.pop("workloads"))
    assert entry == {
        "name": "head_norm_kernel_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput"}
    assert CELLS <= {w["name"] for w in spec["workloads"]}
