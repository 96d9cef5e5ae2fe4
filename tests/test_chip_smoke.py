"""The chip bring-up contract, as far as a CPU host can hold it:

- ``chip_smoke.py``'s phases run at toy sizes on the CPU mesh through
  import (the same functions ``main()`` runs at ResNet-50's width on the
  chip), and ``main()`` itself refuses a host without a TPU;
- nothing on that path hides the device: ``rtc.on_tpu`` lets backend
  errors surface, the calibration refuses a ``device_kind`` that
  ``benchmark/peaks.json`` has no row for, ``tools/launch.py`` refuses to
  start several ranks on one host's accelerators;
- the compile cache is placed from outside by ``JAX_COMPILATION_CACHE_DIR``
  and is one fixed in-checkout directory otherwise.
"""
import json
import os
import re
import subprocess
import sys

import pytest

import jax

import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

import chip_smoke  # noqa: E402


def _toy_net(classes=16):
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                             stride=(2, 2), pad=(1, 1), name="c1")
    net = mx.sym.BatchNorm(net, name="bn1", fix_gamma=False)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=classes, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    work = tmp_path_factory.mktemp("chip_smoke")
    rec = chip_smoke.make_dataset(64, side=40, classes=4,
                                  directory=str(work))
    return work, rec, _toy_net()


def test_train_serve_trace_phases_at_toy_size(toy):
    """RecordIO -> native decode -> DevicePrefetchIter -> fit() ->
    managed checkpoint -> ModelPool behind a ServingFrontend thread ->
    ServeClient, plus the trace check — the smoke's main path."""
    work, rec, sym = toy
    ckpt, trace = str(work / "ckpt"), str(work / "trace")
    res = chip_smoke.phase_train(sym, rec, 32, 16, 3, ckpt_dir=ckpt,
                                 trace_dir=trace, decode_threads=2)
    assert res["steps"] == 12 and len(res["losses"]) == 12
    assert res["last_loss"] < res["first_loss"]
    assert res["pipeline"] == "_NativePipeline"
    assert res["native_lib"] and res["native_imagedec"]
    assert len(res["shard_devices"]) == 1
    # a program lowered for the CPU holds no Mosaic kernel
    assert res["pallas_kernels"] == {}
    # the capture records the device's planes only (none on the CPU) and
    # writes the program's spans of its window beside the trace
    with pytest.raises(AssertionError, match="no /device:TPU"):
        chip_smoke.check_trace(trace, "/device:TPU")
    with open(os.path.join(trace, "mxnet_tpu_spans.trace.json")) as f:
        spans = json.load(f)["traceEvents"]
    assert sum(e["name"] == "step.dispatch" for e in spans) == 3

    serve = chip_smoke.phase_serve(
        ckpt, chip_smoke.sample_inputs(rec, 4, 32), buckets=(1, 4))
    assert serve["requests"] == 4 and serve["loaded_epoch"] == 3
    assert serve["aot_loaded"] + serve["compiled"] == 2

    second = chip_smoke.phase_second_trainer(sym, 32, 16, steps=2)
    assert second["staged_step_ms"] > 0


def test_multichip_phase_on_the_cpu_mesh(toy):
    """dp=4: batch shards on four distinct devices, and the compiled
    step holds each strategy's collectives (asserted inside)."""
    _, rec, sym = toy
    out = chip_smoke.phase_multichip(sym, rec, 32, 8, 1, n=4)
    assert set(out) == {"allreduce", "zero3"}
    assert out["zero3"]["grad_sync"] == "zero3"
    assert len(set(out["allreduce"]["shard_devices"])) == 4


def test_phases_read_their_compile_seconds_from_the_programs_recorder():
    """``since(mark())``: the compile.* events and the compile.cache_*
    counters of ``mxnet_tpu.profiler`` (the smoke keeps no meter)."""
    import jax.numpy as jnp
    snap = chip_smoke.mark()
    jax.jit(lambda x: sum(jnp.tanh(x * k) for k in range(40)))(
        jnp.ones(7)).block_until_ready()
    took = chip_smoke.since(snap)
    assert set(took) == {"wall_s", "compile_s", "run_s", "cache_hits",
                         "cache_misses"}
    assert 0 < took["compile_s"] <= took["wall_s"] + 0.01
    assert took["cache_hits"] == 0


def test_calibration_fails_on_a_rate_above_the_peak():
    ok = chip_smoke.phase_calibration(n=128, chain=2, peak_tflops=1e9)
    assert 0 < ok["block_until_ready_tflops"] < 1e9
    with pytest.raises(RuntimeError, match="exceeds"):
        chip_smoke.phase_calibration(n=128, chain=2, peak_tflops=1e-9)
    # without a given peak the device_kind must be in benchmark/peaks.json
    with pytest.raises(KeyError):
        chip_smoke.phase_calibration(n=128, chain=2)


def test_main_exits_nonzero_without_a_chip():
    res = subprocess.run([sys.executable,
                          os.path.join(REPO, "chip_smoke.py")],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert "no TPU" in res.stdout and "platform 'cpu'" in res.stdout
    assert '"ok"' not in res.stdout


def test_result_line_has_exactly_the_keys_the_driver_parses():
    import json
    rep = chip_smoke.device_report()
    out = json.loads(chip_smoke.result_line(rep))
    assert list(out) == ["ok", "device"] and out["ok"] is True
    assert list(out["device"]) == ["platform", "kind", "count"]
    assert isinstance(out["device"]["count"], int)
    assert out["device"]["kind"] == jax.devices()[0].device_kind


def test_on_tpu_lets_backend_errors_surface(monkeypatch):
    assert mx.rtc.on_tpu() is (jax.default_backend() == "tpu")

    def broken():
        raise RuntimeError("backend failed to initialize")
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        mx.rtc.on_tpu()


def test_launch_refuses_several_ranks_on_one_hosts_accelerators():
    import launch
    with pytest.raises(ValueError, match="one process"):
        launch.launch(2, [sys.executable, "-c", "pass"], env={})
    with pytest.raises(ValueError, match="one process"):
        launch.launch(2, [sys.executable, "-c", "pass"], platform="tpu",
                      env={})
    # one rank, or the CPU platform, is fine
    assert launch.launch(1, [sys.executable, "-c", "pass"], env={},
                         quiet=True) == [0]


def _cache_dir_after_import(env):
    code = ("import mxnet_tpu, jax; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = dict(env, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr
    return res.stdout.strip().splitlines()[-1]


def test_compile_cache_is_placed_from_outside_or_fixed(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    assert _cache_dir_after_import(env) == os.path.join(REPO, ".jax_cache")
    outside = str(tmp_path / "cache")
    assert _cache_dir_after_import(
        dict(env, JAX_COMPILATION_CACHE_DIR=outside)) == outside


def test_only_the_package_root_sets_the_cache_dir():
    """With the variable set nothing may override it: the one
    ``config.update`` of the setting sits behind the package root's
    ``not in os.environ`` guard, and no path is made up at run time."""
    hits = []
    for root in ("mxnet_tpu", "tools", "example"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(REPO, f) for f in ("chip_smoke.py",
                                             "__graft_entry__.py")]
    setters = []
    for path in hits:
        with open(path) as f:
            src = f.read()
        if re.search(r"update\(\s*[\"']jax_compilation_cache_dir", src):
            setters.append(os.path.relpath(path, REPO))
    assert setters == [os.path.join("mxnet_tpu", "__init__.py")]
    with open(os.path.join(REPO, "mxnet_tpu", "__init__.py")) as f:
        src = f.read()
    assert '"JAX_COMPILATION_CACHE_DIR" not in' in src
    assert "mkdtemp" not in src and "getpid" not in src
