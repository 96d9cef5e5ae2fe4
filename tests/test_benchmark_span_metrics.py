"""``benchmark/tests/test_span_metrics.py``, collected by tier-1."""
from benchmark.tests.test_span_metrics import *  # noqa: F401,F403

# pins BENCHMARK.json's lists to PR 26's (PERF.md section 7 (6)): a
# `benchmark` issue's to repair, outside tier-1 until then
del test_every_span_metric_is_in_the_spec_with_its_cells  # noqa: F821
