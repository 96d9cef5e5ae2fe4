"""Everything the fused step adds to the graph is traced under a scope:
the ``op_name`` path of every instruction of the compiled step holds a
graph node, a ``mirror_stage`` or a ``step.*`` scope of the trainer
(``parallel/trainer.py``), whichever way the gradients are synchronised —
and the scopes the trainer adds change no node's or stage's name, which is
what every reader of a device trace keys on."""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.parallel import SPMDTrainer, local_mesh

STEP_SCOPES = {"step.input", "step.cast", "step.guard", "step.update",
               "step.metric", "step.counters", "step.seed", "step.sync"}
# instructions jax or XLA makes where the program traced nothing: a
# checkpoint's and a shard_map's own call, XLA's clones of a broadcast
JAX_MADE = re.compile(r"^(remat2|shard_map|broadcast\.\d+)$")


def net():
    """Two unstaged layers around one ``mirror_stage``, a softmax head and
    a ``__step_counters__`` head."""
    data = mx.sym.Variable("data")
    x = mx.sym.FullyConnected(data, num_hidden=32, name="fc0")
    with mx.AttrScope(mirror_stage="s1"):
        h = mx.sym.FullyConnected(x, num_hidden=32, name="s1_fc")
        h = mx.sym.Activation(h, act_type="relu", name="s1_act")
    x = mx.sym.elemwise_add(x, h, name="join")
    out = mx.sym.FullyConnected(x, num_hidden=4, name="fc2")
    loss = mx.sym.SoftmaxOutput(out, name="softmax")
    seen = mx.sym.sum(mx.sym.BlockGrad(h, name="cut"), name="seen_sum")
    seen = mx.sym.Reshape(seen, shape=(1,), name="seen",
                          attr={"__step_counters__": "t.seen"})
    return mx.sym.Group([loss, seen])


def scopes_of(path):
    """The scopes somebody wrote in one ``op_name`` path of the step, out
    of their ``jvp(...)`` / ``transpose(jvp(...))`` wrapping."""
    return profiler._parse_path(path)[0]


@pytest.fixture(scope="module", params=["allreduce", "zero", "zero3"])
def step_paths(request):
    """(the graph, the ``jit(step)`` paths of the compiled step's text) of
    a bf16 trainer with its guard on, an in-graph metric installed and a
    counter head, after one step."""
    import jax.numpy as jnp
    sym = net()
    trainer = SPMDTrainer(
        sym, "sgd", {"learning_rate": 0.1, "momentum": 0.9,
                     "rescale_grad": 1.0 / 64},
        mesh=local_mesh("dp"), grad_sync=request.param,
        compute_dtype="bfloat16")
    assert trainer.step_guard
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    trainer.init_params(mx.initializer.Xavier())
    trainer.install_metric(
        lambda outs, data: (jnp.sum(outs[0][:, 0]), jnp.asarray(64.0)))
    assert trainer.step_text() is None        # no batch stepped yet
    trainer.step(np.random.rand(64, 10).astype("f"), np.zeros(64, "f"))
    text = trainer.step_text()
    trainer.close()
    paths = sorted(set(re.findall(r'op_name="(jit\(step\)/[^"]+)"', text)))
    assert len(paths) > 40
    return sym, paths


def test_every_instruction_of_the_step_sits_under_a_scope(step_paths):
    sym, paths = step_paths
    nodes = {n.name for n in sym._nodes() if n.op is not None} | {"s1"}
    bare = []
    for path in paths:
        names = scopes_of(path)
        if not any(n in nodes or n.startswith("step.") for n in names) \
                and not JAX_MADE.match(path.rsplit("/", 1)[1]):
            bare.append(path)
    assert not bare


def test_the_scopes_change_no_node_or_stage_name(step_paths):
    """The names the text holds beside the trainer's own are the graph's:
    every one is a node or the stage, and every layer, the stage and the
    nodes inside it are there, forward and backward."""
    sym, paths = step_paths
    nodes = {n.name for n in sym._nodes() if n.op is not None} | {"s1"}
    found = {names[0] for names in map(scopes_of, paths)
             if not JAX_MADE.match(names[0])}
    inside = {names[1] for names in map(scopes_of, paths)
              if names[:1] == ["s1"] and len(names) > 1}
    assert found - nodes <= STEP_SCOPES
    assert {"step.update", "step.guard", "step.metric",
            "step.cast"} <= found
    assert {"fc0", "s1", "fc2", "softmax", "seen_sum"} <= found
    assert {"s1_fc", "s1_act"} <= inside
    # autodiff wraps the outermost scope: what a trace's reader keys on
    # (zero3 checkpoints the whole loss, and its backward reads jvp())
    wrapped = ["jvp(fc0)", "jvp(s1)"]
    if not any("shard_map" in p for p in paths):
        wrapped += ["transpose(jvp(fc0))", "transpose(jvp(s1))"]
    for part in wrapped:
        assert any("/%s/" % part in p for p in paths), part


def test_fit_hands_the_capture_its_trainer(monkeypatch, tmp_path):
    """``fit`` under MXTPU_PROFILE_DIR: the compiled step's text lands
    beside the trace and the spans, with the trainer's scopes in it."""
    from mxnet_tpu.parallel import SPMDModule
    from mxnet_tpu.profiler import ENV_PROFILE_DIR, StepTraceCapture
    monkeypatch.setenv(ENV_PROFILE_DIR, str(tmp_path))
    data = mx.sym.Variable("data")
    out = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    mod = SPMDModule(mx.sym.SoftmaxOutput(out, name="softmax"),
                     mesh=local_mesh("dp"))
    it = mx.io.NDArrayIter(np.random.rand(288, 8).astype("f"),
                           np.zeros(288, "f"), batch_size=16)
    mod.fit(it, num_epoch=1, optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier())
    assert (tmp_path / StepTraceCapture.SPANS_FILE).exists()
    text = (tmp_path / StepTraceCapture.STEP_FILE).read_text()
    assert 'op_name="jit(step)/step.update/' in text
    assert 'op_name="jit(step)/jvp(fc)/' in text
