"""``benchmark/tests/test_flops.py``, collected by tier-1."""
from benchmark.tests.test_flops import *  # noqa: F401,F403
