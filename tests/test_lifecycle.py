"""Trainer/Executor lifecycle: deterministic release of device memory and
compiled programs, so several models can live sequentially in ONE process
(guards the 12x step-time degradation seen in round 3 when a prior
trainer's state lingered; reference analog: ~GraphExecutor frees
its memory pool)."""
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.parallel import SPMDTrainer


def _small_net(seed_name=""):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=64, name="fc1" + seed_name)
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc2" + seed_name)
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _train_steps(trainer, batch, steps):
    import jax
    rs = np.random.RandomState(0)
    d = mx.nd.array(rs.rand(batch, 32).astype("f"))
    l = mx.nd.array(rs.randint(0, 10, (batch,)).astype("f"))
    for _ in range(3):
        trainer.step(d, l)
    jax.block_until_ready(trainer.params)
    best = float("inf")
    for _ in range(3):
        tic = time.time()
        for _ in range(steps):
            trainer.step(d, l)
        jax.block_until_ready(trainer.params)
        best = min(best, (time.time() - tic) / steps)
    return best


def _make_trainer():
    t = SPMDTrainer(_small_net(), "sgd", {"learning_rate": 0.1},
                    mesh=None, compute_dtype="float32")
    t.bind([("data", (32, 32))], [("softmax_label", (32,))])
    t.init_params(mx.initializer.Xavier())
    return t


def test_two_trainers_sequential_same_speed():
    """After close(), a second model trains at the first one's speed
    (within noise) — no lingering buffers/compiled state tax it."""
    t1 = _make_trainer()
    dt1 = _train_steps(t1, 32, 20)
    t1.close()
    assert t1.params is None and t1._step_fn is None
    t2 = _make_trainer()
    dt2 = _train_steps(t2, 32, 20)
    t2.close()
    # best-of timing; 1.5x bound per the round-3 verdict, with a small
    # absolute floor so micro-jitter on sub-ms steps can't flake
    assert dt2 <= max(1.5 * dt1, dt1 + 2e-3), (dt1, dt2)


def test_trainer_close_releases_buffers():
    import jax
    t = _make_trainer()
    leaves = [v for v in jax.tree_util.tree_leaves(t.params)
              if isinstance(v, jax.Array)]
    assert leaves
    t.close()
    assert all(leaf.is_deleted() for leaf in leaves)
    t.close()   # idempotent


def test_trainer_context_manager():
    with _make_trainer() as t:
        _train_steps(t, 32, 2)
    assert t.params is None


def test_executor_close_releases_own_buffers_only():
    """close() frees the executor's outputs and compiled programs but must
    NOT delete the bound arrays — those are caller-owned and may be shared
    (shared_exec bucketing, the caller's own parameter NDArrays)."""
    net = _small_net()
    ex = net.simple_bind(mx.cpu(), data=(8, 32), grad_req="write")
    ex.arg_dict["data"][:] = np.random.rand(8, 32).astype("f")
    caller_arrays = list(ex.arg_dict.values())
    outs = ex.forward(is_train=True)
    ex.backward()
    out_bufs = [o._data for o in outs]
    ex.close()
    assert all(b.is_deleted() for b in out_bufs)
    assert ex.arg_dict == {} and ex._outputs is None
    # caller arrays survive and stay usable
    for a in caller_arrays:
        assert not a._data.is_deleted()
        a.asnumpy()
    ex.close()  # idempotent


def test_module_sequential_lifecycle():
    """Two Modules back-to-back in one process train fine and the first
    one's executor can be explicitly closed."""
    X = np.random.RandomState(0).randn(128, 32).astype("f")
    y = (X.sum(1) > 0).astype("f")
    for _ in range(2):
        it = mx.io.NDArrayIter(X, y, batch_size=32)
        mod = mx.mod.Module(_small_net())
        mod.fit(it, num_epoch=1, optimizer="sgd",
                initializer=mx.initializer.Xavier())
        exe = getattr(mod, "_exec", None)
