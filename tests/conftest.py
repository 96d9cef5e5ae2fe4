"""Test configuration.

Two platforms (select with MXTPU_TEST_PLATFORM):

- ``cpu`` (default): an 8-device virtual CPU mesh — the reference tests
  multi-device semantics the same way, with cpu(0)/cpu(1) fake devices
  (tests/python/unittest/test_model_parallel.py:30-31).  The platform is
  pinned through jax.config before the backend initializes, whatever
  JAX_PLATFORMS says; XLA_FLAGS must be set before that too.

- ``tpu``: JAX's default device is the chip, in THIS pytest process, so
  ``mx.current_context()`` is the chip, ``check_consistency`` compares
  CPU-reference vs TPU execution per op (SURVEY §4 implication (b); the
  reference's tests/python/gpu/test_operator_gpu.py axis) and
  tests/test_kernels.py's ``compiled`` cases compile the Pallas kernels
  with Mosaic.  Send it through the chip tool as
  ``MXTPU_TEST_PLATFORM=tpu python -m pytest tests/test_kernels.py
  tests/test_operator.py -q``.  Matmul precision is pinned to "highest"
  so the oracle checks op semantics at f32 like the reference's fp32 GPU
  suite (TPU bf16-pass matmul defaults would need ~1e-2 tolerances and
  mask real bugs; bf16 training numerics are covered by the dedicated
  bfloat16 convergence tests).  A chip belongs to one process and this
  one holds it: suites that spawn children which open the backend
  themselves are skipped, as are the suites that need the 8-device mesh.

The persistent compile cache is off for the test processes and every
child they spawn (tier-1 would fill the in-checkout ``.jax_cache`` with
thousands of CPU programs, and the chip tool copies the tree as it
stands) — unless JAX_COMPILATION_CACHE_DIR places it from outside.
"""
import os

import pytest

_PLATFORM = os.environ.get("MXTPU_TEST_PLATFORM", "cpu")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402

# tests/test_benchmark_<name>.py re-export the harness's tests: have their
# asserts explained like any collected module's
pytest.register_assert_rewrite("benchmark.tests")

if _PLATFORM == "cpu":
    jax.config.update("jax_platforms", "cpu")
else:
    jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # registered here (no pytest.ini in-repo) so `-m 'not slow'` and the
    # resilience suite produce no unknown-marker warnings
    config.addinivalue_line(
        "markers", "slow: long-running test excluded from the tier-1 run")
    config.addinivalue_line(
        "markers", "resilience: fault-injection / recovery test")
    config.addinivalue_line(
        "markers", "chaos: kill-and-resume drill (spawns subprocesses, "
        "sends real signals; runs in tier-1, combinable with slow for "
        "pod-scale variants)")
    config.addinivalue_line(
        "markers", "serve: inference-serving runtime test (batcher/"
        "pool/frontend units run in tier-1; daemon drills spawn "
        "tools/serve.py subprocesses)")


@pytest.fixture
def clean_faults():
    """Disarm every injected fault point after the test, even on failure."""
    from mxnet_tpu.resilience import faults
    faults.disarm()
    yield faults
    faults.disarm()


@pytest.fixture
def compiled_tier(monkeypatch):
    """``kernels.by_platform`` as a program lowered for a TPU resolves it,
    with the Pallas kernels run by the interpreter: ``gated_delta_rule_op``
    then takes the compiled tier of either rule on the CPU."""
    import functools
    import mxnet_tpu.kernels as kernels
    monkeypatch.setattr(
        kernels, "by_platform", lambda pallas_fn, lax_fn, *args:
        functools.partial(pallas_fn, interpret=True)(*args))


def spawn_data_server(tmp_path, n, port=0, extra_env=None):
    """Spawn one real ``tools/data_server.py`` on a loopback port and
    wait for its port file: ``(proc, 'host:port')``.  ONE helper shared
    by the data-service tests and the chaos drills — the spawn/poll
    protocol must not drift between them."""
    import subprocess
    import sys
    import time
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pf = str(tmp_path / ("dsport%d-%d" % (n, port)))
    if os.path.exists(pf):
        os.remove(pf)
    env = dict(os.environ)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(repo, "tools", "data_server.py"),
         "--port", str(port), "--port-file", pf],
        stderr=subprocess.DEVNULL, env=env)
    deadline = time.monotonic() + 30
    while not os.path.exists(pf):
        assert proc.poll() is None, \
            "data server died at startup (rc=%s)" % proc.returncode
        assert time.monotonic() < deadline, "data server did not come up"
        time.sleep(0.05)
    with open(pf) as f:
        return proc, f.read().strip()


def pytest_collection_modifyitems(config, items):
    if _PLATFORM == "cpu":
        return
    if len(jax.devices()) >= 8:
        return
    skip = pytest.mark.skip(
        reason="needs the 8-device virtual CPU mesh "
               "(MXTPU_TEST_PLATFORM=cpu): sharded dp/tp/pp/sp/ep "
               "execution over a Mesh")
    needs_mesh = ("test_parallel", "test_pp_ep")
    # This pytest process imported JAX and holds the chip.  These suites
    # work through child processes (launcher ranks, serve daemons, fleet
    # replicas, trainer drills): the CPU-pinned ones add no chip
    # coverage, and one that opened the default backend could not get
    # the chip — it would fail or hang.
    skip_children = pytest.mark.skip(
        reason="drives child processes; the pytest parent holds the chip "
               "(one process per chip), so they could only run CPU-pinned "
               "— covered by the MXTPU_TEST_PLATFORM=cpu tier")
    spawns_children = ("test_dist", "test_serving", "test_fleet",
                       "test_chaos")
    # Host-loop example trainings exercise no op the unit suites do not
    # already run on the chip, and take minutes each.
    skip_examples = pytest.mark.skip(
        reason="host-loop example training; no op coverage beyond the "
               "unit suites — covered by the MXTPU_TEST_PLATFORM=cpu tier")
    examples = ("test_rl_examples", "test_example_tail",
                "test_dec_example", "test_speech_demo_example",
                "test_stochdepth_example", "test_rcnn_example")
    for item in items:
        path = str(item.fspath)
        if any(k in path for k in needs_mesh):
            item.add_marker(skip)
        elif any(k in path for k in spawns_children):
            item.add_marker(skip_children)
        elif any(k in path for k in examples):
            item.add_marker(skip_examples)
        # test_kvstore runs everywhere: multi-device aggregation semantics
        # are tested with value LISTS on one device, the reference's own
        # trick (tests/python/unittest/test_kvstore.py on CPU)
