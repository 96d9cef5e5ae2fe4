"""``benchmark/tests/test_qwen3_next.py``, collected by tier-1."""
from benchmark.tests.test_qwen3_next import *  # noqa: F401,F403
