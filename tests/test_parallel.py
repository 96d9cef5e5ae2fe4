"""Parallelism tests on the 8-device virtual CPU mesh (the reference tests
multi-device semantics on fake devices the same way, SURVEY §4)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel import (SPMDModule, SPMDTrainer, build_mesh,
                                default_mesh, local_mesh)
from mxnet_tpu.parallel.ring_attention import (full_attention,
                                               ring_attention_sharded)


def mlp_sym(num_classes=3, nh=32):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def make_blobs(n, d, c, seed=0):
    rs = np.random.RandomState(seed)
    centers = rs.randn(c, d) * 3
    X = np.concatenate([centers[i] + rs.randn(n // c, d)
                        for i in range(c)]).astype("f")
    y = np.concatenate([np.full(n // c, i) for i in range(c)]).astype("f")
    perm = rs.permutation(len(X))
    return X[perm], y[perm]


def test_build_mesh():
    import jax
    assert len(jax.devices()) == 8, "tests need the 8-device CPU platform"
    mesh = build_mesh({"dp": 4, "tp": 2})
    assert mesh.shape == {"dp": 4, "tp": 2}
    mesh2 = default_mesh(tensor_parallel=2)
    assert mesh2.shape["dp"] == 4 and mesh2.shape["tp"] == 2
    with pytest.raises(mx.MXNetError):
        build_mesh({"dp": 3})


def test_spmd_trainer_dp():
    """Fused sharded step over dp=8 converges (the kvstore='tpu' fast path:
    grads psum over dp via GSPMD, optimizer in-graph)."""
    X, y = make_blobs(512, 10, 4)
    mesh = local_mesh("dp")
    trainer = SPMDTrainer(mlp_sym(num_classes=4), "sgd",
                          {"learning_rate": 0.5, "rescale_grad": 1.0 / 64,
                           "momentum": 0.9},
                          mesh=mesh)
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(21)  # deterministic init regardless of suite order
    trainer.init_params(mx.initializer.Xavier())
    for epoch in range(6):
        correct = 0
        for i in range(0, 512, 64):
            outs = trainer.step(X[i:i + 64], y[i:i + 64])
            p = np.asarray(outs[0])
            correct += (p.argmax(1) == y[i:i + 64]).sum()
    assert correct / 512 > 0.95
    # sharding really happened: data batch is split over 8 devices
    arg_params, _ = trainer.get_params()
    assert arg_params["fc1_weight"].shape == (32, 10)


def test_spmd_trainer_zero_matches_allreduce():
    """grad_sync='zero' (dp-sharded master params + reduce-scattered
    grads + sharded optimizer update) is numerically identical to the
    allreduce path, while actually sharding params and optimizer state
    over dp."""
    X, y = make_blobs(256, 10, 4)
    mesh = local_mesh("dp")
    results = {}
    for sync in ("allreduce", "zero"):
        trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                              {"learning_rate": 0.3,
                               "rescale_grad": 1.0 / 64,
                               "momentum": 0.9},
                              mesh=mesh, grad_sync=sync)
        trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
        mx.random.seed(33)
        trainer.init_params(mx.initializer.Xavier())
        if sync == "zero":
            # master weights and momentum really live sharded: each
            # device holds 1/8 of fc1_weight (64 x 10 -> dim0 8-way)
            w = trainer.params["fc1_weight"]
            assert w.sharding.spec == ("dp", None), w.sharding
            local = w.addressable_shards[0].data.shape
            assert local == (8, 10), local
            m = trainer.opt_state["fc1_weight"][0]
            assert m.addressable_shards[0].data.shape == (8, 10)
        for i in range(0, 256, 64):
            trainer.step(X[i:i + 64], y[i:i + 64])
        arg_params, _ = trainer.get_params()
        results[sync] = {k: v.asnumpy() for k, v in arg_params.items()}
        trainer.close()
    for name in results["allreduce"]:
        np.testing.assert_allclose(
            results["zero"][name], results["allreduce"][name],
            rtol=2e-5, atol=2e-6, err_msg=name)


def test_spmd_trainer_zero_collectives_in_hlo():
    """The compiled zero step contains the weight-sharded-DP collective
    signature: params all-gather in, grads reduce-scatter out (GSPMD may
    express RS as reduce-scatter or all-reduce+dynamic-slice depending on
    backend passes)."""
    mesh = local_mesh("dp")
    trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                          {"learning_rate": 0.3, "rescale_grad": 1.0 / 64,
                           "momentum": 0.9},
                          mesh=mesh, grad_sync="zero")
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(33)
    trainer.init_params(mx.initializer.Xavier())
    import jax.numpy as jnp
    from mxnet_tpu import random as _random
    X, y = make_blobs(64, 10, 4)
    data = trainer._shard_batch((X, y))
    import numpy as _np
    # the step's guard carry: one stacked i32[3] (total, consec, trips)
    extras = {"guard": trainer._scalar_acc(_np.zeros(3, _np.int32),
                                           _np.int32)}
    lowered = trainer._step_fn.lower(
        trainer.params, trainer.aux, trainer.opt_state, extras, data,
        _random.peek_key(), jnp.asarray(0.3, jnp.float32),
        jnp.asarray(0.0, jnp.float32), 1)
    hlo = lowered.compile().as_text()
    assert "all-gather" in hlo, "no param all-gather in compiled step"
    assert ("reduce-scatter" in hlo
            or ("all-reduce" in hlo and "dynamic-slice" in hlo)), \
        "no gradient reduce-scatter signature in compiled step"
    trainer.close()


def test_spmd_trainer_dp_tp():
    """dp×tp mesh: FC weights sharded over tp, batch over dp — GSPMD
    inserts the tp collectives (beyond-reference capability)."""
    X, y = make_blobs(256, 16, 4, seed=2)
    mesh = default_mesh(tensor_parallel=2)  # dp=4, tp=2
    trainer = SPMDTrainer(
        mlp_sym(num_classes=4, nh=64), "sgd",
        {"learning_rate": 0.5, "rescale_grad": 1.0 / 64},
        mesh=mesh,
        param_shardings={r"fc1_weight": ("tp", None),
                         r"fc2_weight": (None, "tp")})
    trainer.bind([("data", (64, 16))], [("softmax_label", (64,))])
    trainer.init_params(mx.initializer.Xavier())
    for _ in range(12):
        for i in range(0, 256, 64):
            trainer.step(X[i:i + 64], y[i:i + 64])
    outs = trainer.eval_step(X[:64], y[:64])
    acc = (np.asarray(outs[0]).argmax(1) == y[:64]).mean()
    assert acc > 0.9
    # the fc1 weight is physically sharded over tp
    import jax
    w = trainer.params["fc1_weight"]
    assert len(w.sharding.device_set) == 8


def test_spmd_module_fit():
    """SPMDModule drives BaseModule.fit unchanged (API parity)."""
    X, y = make_blobs(512, 10, 3, seed=1)
    train = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True)
    mod = SPMDModule(mlp_sym(), mesh=local_mesh("dp"))
    mod.fit(train, num_epoch=5, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.initializer.Xavier(), kvstore="tpu")
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=64), "acc")
    assert score[0][1] > 0.95, score


def test_spmd_matches_single_device():
    """SPMD dp-sharded step is numerically equivalent to the single-device
    Module path (same seed, same updates) — the engine-vs-serial oracle of
    the reference (threaded_engine_test.cc) transplanted to sharding."""
    X, y = make_blobs(64, 8, 2, seed=7)
    sym = mlp_sym(num_classes=2, nh=8)

    arg_shapes, _, _ = sym.infer_shape(data=(32, 8))
    init = {}
    rs = np.random.RandomState(3)
    for name, shape in zip(sym.list_arguments(), arg_shapes):
        if name not in ("data", "softmax_label"):
            init[name] = mx.nd.array(rs.uniform(-0.1, 0.1, shape))

    # single device module
    mod = mx.mod.Module(sym, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(arg_params={k: v.copy() for k, v in init.items()},
                    aux_params={}, initializer=None)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1,
                                         "rescale_grad": 1.0 / 32,
                                         "wd": 0.0})
    for batch in it:
        mod.forward_backward(batch)
        mod.update()
    w_single = mod.get_params()[0]["fc1_weight"].asnumpy()

    # SPMD dp=8
    trainer = SPMDTrainer(sym, "sgd",
                          {"learning_rate": 0.1, "rescale_grad": 1.0 / 32,
                           "wd": 0.0},
                          mesh=local_mesh("dp"))
    trainer.bind([("data", (32, 8))], [("softmax_label", (32,))])
    trainer.init_params(None, arg_params=init)
    for i in range(0, 64, 32):
        trainer.step(X[i:i + 32], y[i:i + 32])
    w_spmd = trainer.get_params()[0]["fc1_weight"].asnumpy()
    np.testing.assert_allclose(w_single, w_spmd, rtol=1e-4, atol=1e-5)


def test_ring_attention_matches_full():
    """Ring attention over sp=4 == full attention, causal and not."""
    import jax
    mesh = build_mesh({"sp": 4}, jax.devices()[:4])
    rs = np.random.RandomState(0)
    B, T, H, D = 2, 16, 2, 8
    q = rs.randn(B, T, H, D).astype("f")
    k = rs.randn(B, T, H, D).astype("f")
    v = rs.randn(B, T, H, D).astype("f")
    for causal in (False, True):
        ref = np.asarray(full_attention(q, k, v, causal=causal))
        ring = np.asarray(ring_attention_sharded(q, k, v, mesh, "sp",
                                                 causal=causal))
        np.testing.assert_allclose(ref, ring, rtol=2e-4, atol=2e-5)


def test_kvstore_tpu_in_module():
    """Module.fit(kvstore='tpu') single-process path works."""
    mx.random.seed(42)
    X, y = make_blobs(128, 8, 2)
    train = mx.io.NDArrayIter(X, y, batch_size=16)
    mod = mx.mod.Module(mlp_sym(num_classes=2, nh=8), context=mx.cpu())
    mod.fit(train, num_epoch=3, kvstore="tpu",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.initializer.Xavier())
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=16), "acc")
    assert score[0][1] > 0.9


def test_spmd_trainer_bfloat16_converges():
    """bf16 compute / f32 master weights training converges (the reference
    tests/python/train/test_dtype.py fp16-cifar axis, TPU-native: MXU-rate
    bfloat16 matmuls with full-precision accumulation + updates)."""
    rs = np.random.RandomState(0)
    N, D, C = 512, 16, 3
    X = rs.randn(N, D).astype("f")
    w = rs.randn(D, C).astype("f")
    y = X.dot(w).argmax(axis=1).astype("f")

    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=C, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    trainer = SPMDTrainer(net, "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9,
                           "rescale_grad": 1.0 / 64},
                          mesh=None, compute_dtype="bfloat16")
    trainer.bind([("data", (64, D))], [("softmax_label", (64,))])
    mx.random.seed(0)
    trainer.init_params(mx.initializer.Xavier())
    # master weights stay f32
    assert all(np.dtype(v.dtype) == np.float32
               for v in trainer.params.values())
    for epoch in range(6):
        for i in range(0, N, 64):
            trainer.step(X[i:i + 64], y[i:i + 64])
    outs = trainer.eval_step(X[:64], y[:64])
    pred = np.asarray(outs[0]).argmax(axis=1)
    acc = (pred == y[:64]).mean()
    assert acc > 0.9, acc


def test_spmd_trainer_remat_matches():
    """SPMDTrainer(remat=True) steps produce the same weights as without
    remat (jax.checkpoint only changes the memory/compute schedule)."""
    rs = np.random.RandomState(3)
    X = rs.randn(64, 8).astype("f")
    y = rs.randint(0, 3, 64).astype("f")

    def run(remat):
        data = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        t = SPMDTrainer(net, "sgd", {"learning_rate": 0.1,
                                     "rescale_grad": 1.0 / 32},
                        remat=remat)
        t.bind([("data", (32, 8))], [("softmax_label", (32,))])
        mx.random.seed(11)
        t.init_params(mx.initializer.Xavier())
        for i in range(4):
            t.step(X[i % 2 * 32:(i % 2 + 1) * 32],
                   y[i % 2 * 32:(i % 2 + 1) * 32])
        return {k: np.asarray(v) for k, v in t.params.items()}

    a, b = run(False), run(True)
    for k in a:
        np.testing.assert_allclose(b[k], a[k], rtol=1e-6, err_msg=k)


def test_spmd_trainer_input_transforms():
    """On-device input preprocessing compiled into the fused step: feeding
    raw uint8 NHWC batches through a normalize/transpose transform gives
    the same training trajectory as feeding host-preprocessed f32 NCHW
    (the TPU-first raw-pixel feed path; reference normalizes on the host
    in its C++ iterator, src/io/iter_normalize.h)."""
    import jax.numpy as jnp

    def conv_sym():
        data = mx.sym.Variable("data")
        net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                                 name="c1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.Flatten(net)
        net = mx.sym.FullyConnected(net, num_hidden=3, name="fc")
        return mx.sym.SoftmaxOutput(net, name="softmax")

    rs = np.random.RandomState(3)
    raw = rs.randint(0, 255, (4, 8, 8, 3)).astype(np.uint8)  # NHWC u8
    labels = rs.randint(0, 3, 4).astype("f")
    mean = jnp.array([120.0, 115.0, 100.0], jnp.float32)
    std = jnp.array([58.0, 57.0, 56.0], jnp.float32)

    def tf(x):
        return jnp.transpose((x.astype(jnp.float32) - mean) / std,
                             (0, 3, 1, 2))

    tr_a = SPMDTrainer(conv_sym(), "sgd", {"learning_rate": 0.1},
                       mesh=None, input_transforms={"data": tf})
    tr_a.bind([("data", (4, 3, 8, 8))], [("softmax_label", (4,))])
    mx.random.seed(5)
    tr_a.init_params(mx.initializer.Xavier())

    tr_b = SPMDTrainer(conv_sym(), "sgd", {"learning_rate": 0.1},
                       mesh=None)
    tr_b.bind([("data", (4, 3, 8, 8))], [("softmax_label", (4,))])
    mx.random.seed(5)
    tr_b.init_params(mx.initializer.Xavier())

    host = ((raw.astype(np.float32) - np.array([120, 115, 100], np.float32))
            / np.array([58, 57, 56], np.float32)).transpose(0, 3, 1, 2)
    for _ in range(3):
        oa = tr_a.step(mx.nd.array(raw, dtype="uint8"),
                       mx.nd.array(labels))
        ob = tr_b.step(mx.nd.array(host), mx.nd.array(labels))
    np.testing.assert_allclose(np.asarray(oa[0]), np.asarray(ob[0]),
                               rtol=1e-5, atol=1e-5)
    pa, _ = tr_a.get_params()
    pb, _ = tr_b.get_params()
    for k in pa:
        np.testing.assert_allclose(pa[k].asnumpy(), pb[k].asnumpy(),
                                   rtol=1e-5, atol=1e-5)
    # eval path applies the same transform
    ea = tr_a.eval_step(mx.nd.array(raw, dtype="uint8"),
                        mx.nd.array(labels))
    eb = tr_b.eval_step(mx.nd.array(host), mx.nd.array(labels))
    np.testing.assert_allclose(np.asarray(ea[0]), np.asarray(eb[0]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# grad_sync='zero3' — fully sharded training (docs/how_to/sharded_training.md)
# ---------------------------------------------------------------------------

def test_spmd_trainer_zero3_matches_allreduce_bitwise():
    """zero3 (manual tier: on-demand bucketed gathers, backward
    re-gather, reduce-scatter grads, sharded optimizer update) is
    BIT-identical to the allreduce path on the pure-dp mesh — the
    reduce-scatter sums each element in the same device order the
    all-reduce does, and the sharded momentum update is elementwise."""
    X, y = make_blobs(256, 10, 4)
    mesh = local_mesh("dp")
    results = {}
    for sync in ("allreduce", "zero3"):
        trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                              {"learning_rate": 0.3,
                               "rescale_grad": 1.0 / 64,
                               "momentum": 0.9},
                              mesh=mesh, grad_sync=sync)
        trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
        mx.random.seed(33)
        trainer.init_params(mx.initializer.Xavier())
        if sync == "zero3":
            assert trainer.zero3_tier == "manual"
            # master weights AND momentum really live sharded 1/8
            w = trainer.params["fc1_weight"]
            assert w.sharding.spec == ("dp", None), w.sharding
            assert w.addressable_shards[0].data.shape == (8, 10)
            m = trainer.opt_state["fc1_weight"][0]
            assert m.addressable_shards[0].data.shape == (8, 10)
        for i in range(0, 256, 64):
            trainer.step(X[i:i + 64], y[i:i + 64])
        arg_params, _ = trainer.get_params()
        results[sync] = {k: v.asnumpy() for k, v in arg_params.items()}
        trainer.close()
    for name in results["allreduce"]:
        np.testing.assert_array_equal(
            results["zero3"][name], results["allreduce"][name],
            err_msg=name)


def test_zero3_param_residency_is_one_over_world():
    """Per-device parameter residency under zero3 is ~1/world: each
    device holds only its shard of every dp-divisible parameter (the
    indivisible residue — fc2_bias here — stays replicated)."""
    import jax
    world = len(jax.devices())
    trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                          {"learning_rate": 0.1},
                          mesh=local_mesh("dp"), grad_sync="zero3")
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(1)
    trainer.init_params(mx.initializer.Xavier())
    full = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in trainer.params.values())
    resident = sum(v.addressable_shards[0].data.nbytes
                   for v in trainer.params.values())
    assert resident / full <= 1.0 / world + 0.05, (resident, full)
    trainer.close()


def test_zero3_schedule_proven_by_analyze():
    """trainer.analyze() under zero3 PROVES the collective schedule:
    param-scale all-gathers, reduce-scatter gradients, and no
    full-parameter all-reduce (the graph-collective-schedule rule
    would flag it; the residual all-reduces are the indivisible
    fc2_bias + the guard scalar, orders of magnitude below)."""
    X, y = make_blobs(64, 10, 4)
    trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                          {"learning_rate": 0.1},
                          mesh=local_mesh("dp"), grad_sync="zero3")
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(1)
    trainer.init_params(mx.initializer.Xavier())
    rep = trainer.analyze(X, y)
    assert rep.ok, rep.format_text()
    coll = rep.stats["collectives"]
    expect = trainer._zero3_expected_gather_bytes()
    assert expect > 0
    assert coll["all-gather"]["bytes"] >= 0.75 * expect, coll
    assert coll["reduce-scatter"]["count"] >= 1, coll
    ar = coll.get("all-reduce", {"bytes": 0})
    assert ar["bytes"] < 0.5 * expect, coll
    assert rep.stats["schedule"]["declared"] == "zero3-manual"
    trainer.close()


def test_zero3_gather_groups_follow_plan_order(monkeypatch):
    """Gather groups are keyed by the executor plan's topological order
    (fc1's params before fc2's): MXTPU_ZERO3_GATHER_GROUP=1 gives one
    group per consuming layer, =2 fuses two layers per group, and the
    'auto' default hands the grouping to the planner (which merges this
    tiny model's layers into ONE bucket — its bytes are far below the
    MXTPU_PLAN_GATHER_BUCKET target)."""
    def build():
        trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                              {"learning_rate": 0.1},
                              mesh=local_mesh("dp"), grad_sync="zero3")
        trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
        return trainer

    monkeypatch.setenv("MXTPU_ZERO3_GATHER_GROUP", "1")
    t = build()
    groups = [sorted(g) for g in t._zero3_groups]
    # fc1's layer group strictly precedes fc2's in plan order
    assert any("fc1_weight" in g for g in groups)
    ix1 = next(i for i, g in enumerate(groups) if "fc1_weight" in g)
    ix2 = next(i for i, g in enumerate(groups) if "fc2_weight" in g)
    assert ix1 < ix2, groups
    n_per_layer = len(groups)
    t.close()
    monkeypatch.setenv("MXTPU_ZERO3_GATHER_GROUP", "2")
    t = build()
    assert len(t._zero3_groups) < n_per_layer or n_per_layer == 1
    t.close()
    # the auto default: planner-derived groups (bucket-merged, same
    # name set, same plan order)
    monkeypatch.delenv("MXTPU_ZERO3_GATHER_GROUP", raising=False)
    t = build()
    from mxnet_tpu.parallel import planner
    want = planner.derive_gather_groups(
        t.symbol, sorted(t._zero3_dims),
        {n: tuple(t.arg_shapes[n]) for n in t._zero3_dims})
    assert t._zero3_groups == want
    assert sorted(n for g in t._zero3_groups for n in g) == \
        sorted(t._zero3_dims)
    t.close()


def test_zero3_composes_with_tp():
    """One trainer config expresses dp x tp: explicit tp rules keep
    their sharding (GSPMD tier engages on the multi-axis mesh), the
    otherwise-replicated params still shard over dp, and the model
    converges."""
    X, y = make_blobs(256, 16, 4, seed=2)
    mesh = default_mesh(tensor_parallel=2)  # dp=4, tp=2
    trainer = SPMDTrainer(
        mlp_sym(num_classes=4, nh=64), "sgd",
        {"learning_rate": 0.5, "rescale_grad": 1.0 / 64},
        mesh=mesh, grad_sync="zero3",
        param_shardings={r"fc1_weight": ("tp", None)})
    trainer.bind([("data", (64, 16))], [("softmax_label", (64,))])
    mx.random.seed(4)
    trainer.init_params(mx.initializer.Xavier())
    assert trainer.zero3_tier == "gspmd"
    # tp rule wins for fc1_weight; fc1_bias (64) dp-shards over dp=4
    assert trainer.params["fc1_weight"].sharding.spec == ("tp", None)
    assert "fc1_bias" in trainer._zero3_dims
    for _ in range(12):
        for i in range(0, 256, 64):
            trainer.step(X[i:i + 64], y[i:i + 64])
    outs = trainer.eval_step(X[:64], y[:64])
    acc = (np.asarray(outs[0]).argmax(1) == y[:64]).mean()
    assert acc > 0.9, acc
    rep = trainer.analyze(X[:64], y[:64])
    assert rep.ok, rep.format_text()
    trainer.close()


def test_zero3_guard_skips_poisoned_step():
    """The in-graph NaN guard composes with zero3: a poisoned batch
    applies NO update to the sharded params/opt state, and the skip
    counters agree across shards (psum'd finite flag)."""
    from mxnet_tpu.resilience import faults
    X, y = make_blobs(128, 10, 4)
    trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                          {"learning_rate": 0.3, "momentum": 0.9},
                          mesh=local_mesh("dp"), grad_sync="zero3")
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(2)
    trainer.init_params(mx.initializer.Xavier())
    trainer.step(X[:64], y[:64])
    before = {k: v.asnumpy()
              for k, v in trainer.get_params()[0].items()}
    faults.arm("poison_grad", 1)
    trainer.step(X[64:128], y[64:128])
    assert trainer.skipped_steps == 1
    after = {k: v.asnumpy() for k, v in trainer.get_params()[0].items()}
    for k in before:
        np.testing.assert_array_equal(before[k], after[k], err_msg=k)
    trainer.close()


def test_zero3_checkpoint_roundtrip_bit_identical(tmp_path):
    """Gather-on-save checkpointing under zero3: save_checkpoint
    gathers per parameter into host snapshots, restore re-shards, and
    continued training is bit-identical to the uninterrupted run."""
    from mxnet_tpu.resilience import CheckpointManager
    X, y = make_blobs(192, 10, 4)

    def build():
        t = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                        {"learning_rate": 0.3, "momentum": 0.9},
                        mesh=local_mesh("dp"), grad_sync="zero3")
        t.bind([("data", (64, 10))], [("softmax_label", (64,))])
        mx.random.seed(6)
        t.init_params(mx.initializer.Xavier())
        return t

    mgr = CheckpointManager(str(tmp_path))
    a = build()
    a.step(X[:64], y[:64])
    a.step(X[64:128], y[64:128])
    a.save_checkpoint(mgr, 1)
    a.step(X[128:], y[128:])
    want = {k: v.asnumpy() for k, v in a.get_params()[0].items()}
    a.close()

    b = build()  # different init values get fully replaced by restore
    mx.random.seed(99)
    b.restore(mgr)
    assert b.params["fc1_weight"].sharding.spec == ("dp", None)
    b.step(X[128:], y[128:])
    got = {k: v.asnumpy() for k, v in b.get_params()[0].items()}
    for k in want:
        np.testing.assert_array_equal(want[k], got[k], err_msg=k)
    b.close()


def test_zero3_snapshot_params_adopted_without_copy():
    """SPMDTrainer.snapshot_params feeds the checkpoint path directly:
    resilience.snapshot_params ADOPTS the per-parameter host snapshots
    instead of deep-copying the whole model a second time."""
    from mxnet_tpu import resilience
    trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                          {"learning_rate": 0.1},
                          mesh=local_mesh("dp"), grad_sync="zero3")
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(1)
    trainer.init_params(mx.initializer.Xavier())
    arg, aux = trainer.snapshot_params()
    again = resilience.snapshot_params(arg)
    for k in arg:
        assert again[k] is arg[k], k  # adopted, not re-copied
    # values match the NDArray gather path bit-for-bit
    nd_arg, _ = trainer.get_params()
    for k in arg:
        np.testing.assert_array_equal(arg[k].asnumpy(),
                                      nd_arg[k].asnumpy(), err_msg=k)
    trainer.close()


def test_zero3_indivisible_batch_raises():
    """The manual tier shard_maps the step, so a batch that does not
    divide the dp axis must fail LOUDLY with guidance, not crash in
    the partitioner (iterators pad the final batch by default)."""
    trainer = SPMDTrainer(mlp_sym(num_classes=4, nh=64), "sgd",
                          {"learning_rate": 0.1},
                          mesh=local_mesh("dp"), grad_sync="zero3")
    trainer.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(1)
    trainer.init_params(mx.initializer.Xavier())
    X, y = make_blobs(60, 10, 4)
    with pytest.raises(mx.MXNetError, match="zero3"):
        trainer.step(X[:60], y[:60])
    trainer.close()


def test_spmd_module_fit_zero3():
    """SPMDModule(grad_sync='zero3') drives BaseModule.fit unchanged."""
    X, y = make_blobs(512, 10, 3, seed=1)
    train = mx.io.NDArrayIter(X, y, batch_size=64, shuffle=True)
    mod = SPMDModule(mlp_sym(), mesh=local_mesh("dp"), grad_sync="zero3")
    mod.fit(train, num_epoch=5, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5},
            initializer=mx.initializer.Xavier(), kvstore="tpu")
    score = mod.score(mx.io.NDArrayIter(X, y, batch_size=64), "acc")
    assert score[0][1] > 0.95, score


def test_zero_keeps_explicit_rule_spec_and_records_decision():
    """The silent-widening fix: under grad_sync='zero' an explicitly
    rule-sharded param (tp) KEEPS its spec through the step — it is
    never quietly widened to replicated — and the kept spec is a
    recorded plan decision.  Numerics still match allreduce."""
    X, y = make_blobs(256, 16, 4, seed=2)
    results = {}
    for sync in ("allreduce", "zero"):
        trainer = SPMDTrainer(
            mlp_sym(num_classes=4, nh=64), "sgd",
            {"learning_rate": 0.3, "rescale_grad": 1.0 / 64,
             "momentum": 0.9},
            mesh=default_mesh(tensor_parallel=2),  # dp=4, tp=2
            grad_sync=sync,
            param_shardings={r"fc1_weight": ("tp", None)})
        trainer.bind([("data", (64, 16))], [("softmax_label", (64,))])
        mx.random.seed(11)
        trainer.init_params(mx.initializer.Xavier())
        for i in range(0, 256, 64):
            trainer.step(X[i:i + 64], y[i:i + 64])
        # the live param still carries the tp rule AFTER stepping — a
        # widened "gathered view" would leave it replicated here
        assert trainer.params["fc1_weight"].sharding.spec[0] == "tp", \
            (sync, trainer.params["fc1_weight"].sharding)
        if sync == "zero":
            decs = trainer.sharding_plan.decisions
            assert any("fc1_weight: explicit shard spec" in d
                       and "kept" in d and "'zero'" in d
                       for d in decs), decs
        arg_params, _ = trainer.get_params()
        results[sync] = {k: v.asnumpy() for k, v in arg_params.items()}
        trainer.close()
    for name in results["allreduce"]:
        np.testing.assert_allclose(
            results["zero"][name], results["allreduce"][name],
            rtol=2e-6, atol=1e-7, err_msg=name)


def _zero3_trainer(world, seed, nh=64):
    import jax
    t = SPMDTrainer(mlp_sym(num_classes=4, nh=nh), "sgd",
                    {"learning_rate": 0.3, "momentum": 0.9},
                    mesh=build_mesh({"dp": world},
                                    jax.devices()[:world]),
                    grad_sync="zero3")
    t.bind([("data", (64, 10))], [("softmax_label", (64,))])
    mx.random.seed(seed)
    t.init_params(mx.initializer.Xavier())
    return t


def test_zero3_sharded_native_checkpoint_roundtrip_and_elastic(
        tmp_path, monkeypatch):
    """MXTPU_CKPT_SHARDED=1 reroutes save_checkpoint to the sharded-
    native writer: one blob per dp shard, a format-2 manifest entry,
    restore + continued training bit-identical to the uninterrupted
    run — and the restore is ELASTIC: the same 4-blob checkpoint
    restores bit-identically (params, momentum, update counter) onto
    world=2 AND world=8 meshes whose shard counts don't match the
    blobs."""
    import os as _os
    import pickle
    from mxnet_tpu.resilience import CheckpointManager
    monkeypatch.setenv("MXTPU_CKPT_SHARDED", "1")
    X, y = make_blobs(192, 10, 4)
    mgr = CheckpointManager(str(tmp_path))
    a = _zero3_trainer(4, seed=6)
    a.step(X[:64], y[:64])
    a.step(X[64:128], y[64:128])
    a.save_checkpoint(mgr, 1)
    entry = mgr.entry(1)
    assert entry["format"] == 2 and entry["params"] is None
    assert entry["shard_set"]["world"] == 4
    for rec in entry["shard_set"]["files"]:
        assert _os.path.exists(_os.path.join(str(tmp_path),
                                             rec["file"]))
    want_saved = {k: v.asnumpy() for k, v in a.get_params()[0].items()}
    want_states = pickle.loads(a.get_states())
    a.step(X[128:], y[128:])
    want_after = {k: v.asnumpy() for k, v in a.get_params()[0].items()}
    a.close()

    # same-world roundtrip: restore fully replaces a different init
    # and continued training is bit-identical to the uninterrupted run
    b = _zero3_trainer(4, seed=99)
    assert b.restore(mgr) == 1
    assert b.params["fc1_weight"].sharding.spec == ("dp", None)
    b.step(X[128:], y[128:])
    got = {k: v.asnumpy() for k, v in b.get_params()[0].items()}
    for k in want_after:
        np.testing.assert_array_equal(want_after[k], got[k], err_msg=k)
    b.close()

    # elastic: 4 blobs assemble + re-shard onto world=2 and world=8
    for world in (2, 8):
        c = _zero3_trainer(world, seed=99)
        assert c.restore(mgr) == 1
        got = {k: v.asnumpy() for k, v in c.get_params()[0].items()}
        for k in want_saved:
            np.testing.assert_array_equal(
                want_saved[k], got[k], err_msg="%d:%s" % (world, k))
        gs = pickle.loads(c.get_states())
        assert gs["num_update"] == want_states["num_update"]
        assert set(gs["states"]) == set(want_states["states"])
        for name, slots in want_states["states"].items():
            for i, s in enumerate(slots):
                np.testing.assert_array_equal(
                    np.asarray(gs["states"][name][i]), np.asarray(s),
                    err_msg="%d:%s[%d]" % (world, name, i))
        c.close()
