"""``benchmark/tests/test_moe_compact_share.py``, collected by tier-1."""
from benchmark.tests.test_moe_compact_share import *  # noqa: F401,F403
