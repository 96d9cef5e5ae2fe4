"""``benchmark/tests/test_zaya1.py``, collected by tier-1."""
from benchmark.tests.test_zaya1 import *  # noqa: F401,F403
