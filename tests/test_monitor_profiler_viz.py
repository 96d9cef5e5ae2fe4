"""Monitor, profiler, visualization tests (reference test_profiler.py,
test_viz.py, monitor usage in examples)."""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx


def _mlp():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def test_monitor_collects_stats():
    net = _mlp()
    x = np.random.uniform(-1, 1, (8, 10)).astype(np.float32)
    ex = net.simple_bind(mx.current_context(), data=(8, 10),
                         softmax_label=(8,))
    for k, v in ex.arg_dict.items():
        if k != "data" and not k.endswith("label"):
            v[:] = np.random.uniform(-0.1, 0.1, v.shape)
    mon = mx.Monitor(interval=1, pattern=".*fc.*")
    mon.install(ex)
    mon.tic()
    ex.forward(is_train=True, data=x)
    res = mon.toc()
    names = [k for _n, k, _v in res]
    assert any("fc1" in n for n in names)
    assert any("fc2" in n for n in names)
    assert not any("relu" in n for n in names)  # pattern filtered
    # interval: second batch not sampled with interval=2
    mon2 = mx.Monitor(interval=2)
    mon2.install(ex)
    mon2.tic(); ex.forward(is_train=False); first = mon2.toc()
    mon2.tic(); ex.forward(is_train=False); second = mon2.toc()
    assert first and not second


def test_monitor_in_module_fit():
    net = _mlp()
    x = np.random.uniform(-1, 1, (40, 10)).astype(np.float32)
    y = np.random.randint(0, 4, (40,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=20, label_name="softmax_label")
    mod = mx.mod.Module(net, label_names=["softmax_label"],
                        context=mx.current_context())
    mon = mx.Monitor(interval=1)
    mod.fit(it, num_epoch=1, monitor=mon)


def test_profiler_dump(tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler_set_config(mode="all", filename=fname)
    mx.profiler_set_state("run")
    eng = mx.engine.get()
    done = []
    for i in range(4):
        v = eng.new_variable()
        eng.push(lambda i=i: done.append(i), const_vars=(), mutable_vars=(v,),
                 name="testop%d" % i)
    eng.wait_for_all()
    mx.profiler_set_state("stop")
    out = mx.dump_profile()
    assert out == fname and os.path.exists(fname)
    data = json.load(open(fname))
    assert "traceEvents" in data
    names = {e["name"] for e in data["traceEvents"]}
    assert any("testop" in n for n in names)


def test_print_summary(capsys):
    net = _mlp()
    total = mx.viz.print_summary(net, shape={"data": (8, 10)})
    out = capsys.readouterr().out
    assert "fc1" in out and "fc2" in out
    # fc1: 10*16+16, fc2: 16*4+4
    assert total == 10 * 16 + 16 + 16 * 4 + 4


def test_plot_network():
    pytest.importorskip("graphviz")
    net = _mlp()
    dot = mx.viz.plot_network(net, shape={"data": (8, 10)})
    src = dot.source
    assert "fc1" in src and "softmax" in src


def test_per_op_stats_over_fused_program(tmp_path):
    """Per-op device times from a FUSED (jit) training step: HLO op_name
    metadata (stamped by the executor's named_scope per symbol node) maps
    device events back to graph node names — the reference's per-op
    profile (src/engine/profiler.cc:134-216) over an XLA program.  The
    chip's trace names events by HLO instruction, so the names come from
    the compiled step's text (``SPMDTrainer.step_text``).
    Device-side HLO events only exist on a real accelerator backend."""
    import jax
    if jax.default_backend() == "cpu":
        pytest.skip("XLA device-op trace events need a TPU backend")
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import SPMDTrainer
    import numpy as np

    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                             pad=(1, 1), name="conv1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=10,
                                name="fc1")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    trainer = SPMDTrainer(net, "sgd", {"learning_rate": 0.1,
                                       "rescale_grad": 1.0 / 32})
    trainer.bind([("data", (32, 3, 16, 16))], [("softmax_label", (32,))])
    trainer.init_params(mx.initializer.Xavier())
    batch = (np.random.rand(32, 3, 16, 16).astype("f"),
             np.random.randint(0, 10, 32).astype("f"))
    profiler.profiler_set_config(
        mode="all_xla", filename=str(tmp_path / "prof.json"),
        trace_dir=str(tmp_path / "xla"))
    trainer.step(*batch)          # compile outside the trace
    profiler.profiler_set_state("run")
    for _ in range(3):
        outs = trainer.step(*batch)
    jax.block_until_ready(outs)
    profiler.profiler_set_state("stop")

    text = trainer.step_text()
    stats = profiler.get_op_stats(str(tmp_path / "xla"), hlo_text=text)
    names = set(stats)
    # forward and backward of named layers appear with device times
    assert any(n.startswith("conv1") or n == "conv1" for n in names), names
    assert "_backward_conv1" in names, names
    assert "step.update" in names, names
    assert stats["_backward_conv1"]["total_us"] > 0
    table = profiler.dumps(trace_dir=str(tmp_path / "xla"), hlo_text=text)
    assert "Profile Statistics" in table and "_backward_conv1" in table
