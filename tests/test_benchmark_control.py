"""``benchmark/tests/test_control.py``, collected by tier-1."""
from benchmark.tests.test_control import *  # noqa: F401,F403
