"""``benchmark/tests/test_head_norm_kernel_share.py``, collected by tier-1."""
from benchmark.tests.test_head_norm_kernel_share import *  # noqa: F401,F403
