"""``benchmark/tests/test_trinity.py``, collected by tier-1."""
from benchmark.tests.test_trinity import *  # noqa: F401,F403
