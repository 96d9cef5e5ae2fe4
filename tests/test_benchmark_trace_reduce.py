"""``benchmark/tests/test_trace_reduce.py``, collected by tier-1."""
from benchmark.tests.test_trace_reduce import *  # noqa: F401,F403
