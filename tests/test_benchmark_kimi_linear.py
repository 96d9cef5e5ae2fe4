"""``benchmark/tests/test_kimi_linear.py``, collected by tier-1."""
from benchmark.tests.test_kimi_linear import *  # noqa: F401,F403
