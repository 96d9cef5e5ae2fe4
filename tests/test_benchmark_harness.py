"""``benchmark/tests/test_harness.py``, collected by tier-1."""
from benchmark.tests.test_harness import *  # noqa: F401,F403
