"""``benchmark/tests/test_step_scope_metrics.py``, collected by tier-1."""
from benchmark.tests.test_step_scope_metrics import *  # noqa: F401,F403
