"""mxserve: bucket batching correctness (the bit-identity contract),
the warm model pool, admission control/shedding, and the HTTP daemon
(docs/how_to/serving.md).

THE correctness claim, proved both ways here: a request's result
depends only on its own bytes and the bucket shape it ran at — never on
batch fill, row position, or co-batched requests.  The converse is also
pinned: XLA re-tiles reductions per batch shape, so results between
DIFFERENT batch shapes are close but NOT bit-identical — which is
exactly why the batcher serves canonical bucket shapes instead of
arrival-sized batches.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import predict
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serving import (BucketBatcher, Draining, ModelPool,
                               QueueFull, ServeClient, ServingFrontend,
                               TenantQuotaExceeded, parse_buckets,
                               parse_seq_buckets, parse_tenant_weights,
                               pad_to_bucket, pick_bucket,
                               pick_seq_bucket)

pytestmark = pytest.mark.serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVE = os.path.join(REPO, "tools", "serve.py")


def mlp_sym(nh=64, num_classes=10):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=num_classes, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def conv_sym():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3),
                             pad=(1, 1), name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2),
                         pool_type="max")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def init_params(sym, data_shape, seed=0):
    """Random args (+ sane BN aux: mean 0 / var 1) for ``sym``."""
    rs = np.random.RandomState(seed)
    arg_shapes, _, aux_shapes = sym.infer_shape(data=data_shape)
    args = {n: mx.nd.array(rs.uniform(-0.3, 0.3, s).astype("f"))
            for n, s in zip(sym.list_arguments(), arg_shapes)
            if n not in ("data", "softmax_label")}
    auxs = {}
    for n, s in zip(sym.list_auxiliary_states(), aux_shapes):
        auxs[n] = mx.nd.array((np.ones(s) if n.endswith("var")
                               else np.zeros(s)).astype("f"))
    return args, auxs


def make_pool(sym=None, sample=(32,), name="m", **kw):
    sym = sym if sym is not None else mlp_sym()
    args, auxs = init_params(sym, (1,) + tuple(sample))
    pool = ModelPool()
    pool.add(name, sym, args, auxs, sample_shapes={"data": sample}, **kw)
    return pool, sym, args, auxs


def ref_predictor(sym, args, auxs, shape):
    blob = {("arg:%s" % k): v for k, v in args.items()}
    blob.update({("aux:%s" % k): v for k, v in auxs.items()})
    return predict.Predictor(sym, blob, {"data": shape})


# ---------------------------------------------------------------------------
# buckets: selection, padding, truncation-impossibility
# ---------------------------------------------------------------------------

def test_parse_buckets_env_and_validation(monkeypatch):
    assert parse_buckets("1,2,4,8") == (1, 2, 4, 8)
    assert parse_buckets((3, 5)) == (3, 5)
    monkeypatch.setenv("MXTPU_SERVE_BUCKETS", "2, 4,16")
    assert parse_buckets() == (2, 4, 16)
    for bad in ("8,4", "0,1", "1,1,2", "", "a,b"):
        with pytest.raises(MXNetError):
            parse_buckets(bad)


def test_pick_bucket_never_truncates():
    buckets = (1, 2, 4, 8)
    for n in range(1, 9):
        assert pick_bucket(n, buckets) >= n
    assert [pick_bucket(n, buckets) for n in (1, 2, 3, 5, 8)] == \
        [1, 2, 4, 8, 8]
    with pytest.raises(MXNetError):
        pick_bucket(9, buckets)


def test_pad_to_bucket_edge_pads_last_row():
    rows = [np.full((3,), i, "f") for i in range(3)]
    out = pad_to_bucket(rows, 8)
    assert out.shape == (8, 3)
    np.testing.assert_array_equal(out[:3], np.stack(rows))
    for i in range(3, 8):
        np.testing.assert_array_equal(out[i], rows[-1])


# ---------------------------------------------------------------------------
# THE bit-identity contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sym_fn,sample", [(mlp_sym, (32,)),
                                           (conv_sym, (3, 8, 8))])
def test_batched_rows_bit_identical_to_unbatched(sym_fn, sample):
    """A request served in a shared padded bucket == the same request
    served ALONE (the unbatched forward, padded to the bucket shape),
    bit for bit — including the partial-final-batch (padding) path."""
    pool, sym, args, auxs = make_pool(sym_fn(), sample)
    entry = pool.get("m")
    rs = np.random.RandomState(1)
    n, bucket = 5, 8          # partial fill: 3 padding rows
    X = rs.randn(n, *sample).astype("f")

    batched = entry.forward(
        {"data": pad_to_bucket(list(X), bucket)})[0]

    ref = ref_predictor(sym, args, auxs, (bucket,) + tuple(sample))
    for i in range(n):
        alone = ref.forward(
            data=pad_to_bucket([X[i]], bucket)).get_output(0)
        assert np.array_equal(batched[i], alone[0]), \
            "row %d differs between shared and solo service" % i


def test_full_bucket_is_literally_the_hand_batched_forward():
    """When n requests exactly fill a bucket there is NO padding: the
    serving batch is byte-for-byte the batch a user would have built by
    hand, so every row must equal the plain Predictor.forward rows."""
    pool, sym, args, auxs = make_pool()
    entry = pool.get("m")
    rs = np.random.RandomState(2)
    X = rs.randn(8, 32).astype("f")
    batched = entry.forward({"data": X.copy()})[0]
    ref = ref_predictor(sym, args, auxs, (8, 32))
    hand = ref.forward(data=X).get_output(0)
    assert np.array_equal(batched, hand)


def test_cross_shape_forwards_differ_why_buckets_exist():
    """The negative control: the SAME row through batch-1 vs batch-8
    programs is NOT bit-identical (XLA tiles reductions per shape).
    If this ever starts passing as equal, buckets stopped mattering
    numerically and the contract can be widened."""
    pool, sym, args, auxs = make_pool()
    rs = np.random.RandomState(3)
    x = rs.randn(32).astype("f")
    p1 = ref_predictor(sym, args, auxs, (1, 32))
    p8 = ref_predictor(sym, args, auxs, (8, 32))
    r1 = p1.forward(data=x[None]).get_output(0)[0]
    r8 = p8.forward(data=pad_to_bucket([x], 8)).get_output(0)[0]
    np.testing.assert_allclose(r1, r8, rtol=1e-4, atol=1e-6)  # close...
    # ...but not guaranteed identical; assert only closeness above.


def test_batcher_end_to_end_bit_identity_with_partial_final_batch():
    """11 concurrent requests through the real batcher (max bucket 8):
    a full 8-batch plus a padded 3->4 final batch.  Every result must
    be bit-identical to the per-request solo reference, and no request
    may be truncated or lost."""
    pool, sym, args, auxs = make_pool()
    entry = pool.get("m")
    batcher = BucketBatcher(entry.forward, buckets=(1, 2, 4, 8),
                            max_wait_ms=50.0, name="m")
    rs = np.random.RandomState(4)
    X = rs.randn(11, 32).astype("f")
    try:
        futures = [batcher.submit({"data": X[i]}) for i in range(11)]
        results = [f.result(timeout=60) for f in futures]
    finally:
        batcher.close()
    refs = {}
    for i in range(11):
        got = results[i][0]
        assert got.shape == (10,)
        found = False
        for bucket in (1, 2, 4, 8):
            if bucket not in refs:
                refs[bucket] = ref_predictor(sym, args, auxs, (bucket, 32))
            alone = refs[bucket].forward(
                data=pad_to_bucket([X[i]], bucket)).get_output(0)[0]
            if np.array_equal(got, alone):
                found = True
                break
        assert found, ("request %d matches no bucket's solo forward "
                       "bitwise" % i)


def test_batcher_never_truncates_above_max_bucket():
    """2x max bucket + 3 queued requests: every one completes, every
    dispatched batch is <= the largest bucket."""
    calls = []

    def runner(inputs, n):
        calls.append((inputs["data"].shape[0], n))
        return [inputs["data"] * 2.0]

    batcher = BucketBatcher(runner, buckets=(1, 2, 4), max_wait_ms=30.0)
    try:
        futures = [batcher.submit({"data": np.full((2,), i, "f")})
                   for i in range(11)]
        outs = [f.result(timeout=30) for f in futures]
    finally:
        batcher.close()
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o[0], np.full((2,), 2.0 * i))
    assert sum(n for _, n in calls) == 11
    assert all(shape <= 4 and n <= shape for shape, n in calls)


# ---------------------------------------------------------------------------
# batcher dispatch policy
# ---------------------------------------------------------------------------

def test_full_bucket_dispatches_without_waiting_out_the_timer():
    done = threading.Event()

    def runner(inputs, n):
        done.set()
        return [inputs["data"]]

    batcher = BucketBatcher(runner, buckets=(1, 2), max_wait_ms=5000.0)
    try:
        batcher.submit({"data": np.zeros((1,), "f")})
        batcher.submit({"data": np.zeros((1,), "f")})
        assert done.wait(5.0), \
            "a full bucket sat on the max-wait timer"
    finally:
        batcher.close()


def test_single_request_dispatches_after_max_wait():
    def runner(inputs, n):
        return [inputs["data"]]

    batcher = BucketBatcher(runner, buckets=(4,), max_wait_ms=40.0)
    try:
        tic = time.monotonic()
        fut = batcher.submit({"data": np.zeros((1,), "f")})
        fut.result(timeout=10)
        elapsed = time.monotonic() - tic
        assert elapsed >= 0.03, "dispatched before the wait window"
        assert elapsed < 5.0
    finally:
        batcher.close()


def test_batcher_queue_bound_and_draining():
    release = threading.Event()

    def runner(inputs, n):
        release.wait(30)
        return [inputs["data"]]

    batcher = BucketBatcher(runner, buckets=(1,), max_wait_ms=0.0,
                            max_queue=2)
    try:
        futures = [batcher.submit({"data": np.zeros((1,), "f")})]
        deadline = time.monotonic() + 10
        while batcher._qtotal_locked() and time.monotonic() < deadline:
            time.sleep(0.005)   # let the dispatcher take req 1 in flight
        futures += [batcher.submit({"data": np.zeros((1,), "f")})
                    for _ in range(2)]  # 1 in flight + 2 queued
        with pytest.raises(QueueFull):
            batcher.submit({"data": np.zeros((1,), "f")})
        release.set()
        for f in futures:
            f.result(timeout=30)
    finally:
        release.set()
        batcher.close()
    with pytest.raises(Draining):
        batcher.submit({"data": np.zeros((1,), "f")})


def test_batcher_model_error_reaches_every_waiter():
    def runner(inputs, n):
        raise RuntimeError("model exploded")

    batcher = BucketBatcher(runner, buckets=(1, 2), max_wait_ms=20.0)
    try:
        futures = [batcher.submit({"data": np.zeros((1,), "f")})
                   for _ in range(2)]
        for f in futures:
            with pytest.raises(RuntimeError, match="model exploded"):
                f.result(timeout=30)
    finally:
        batcher.close()


def test_batcher_shape_mismatch_rejected():
    batcher = BucketBatcher(lambda i, n: [i["data"]], buckets=(1,))
    try:
        batcher.submit({"data": np.zeros((4,), "f")})
        with pytest.raises(MXNetError, match="do not match"):
            batcher.submit({"data": np.zeros((5,), "f")})
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# model pool
# ---------------------------------------------------------------------------

def test_pool_load_checkpoint_pair(tmp_path):
    from mxnet_tpu.model import save_checkpoint
    sym = mlp_sym()
    args, _ = init_params(sym, (1, 32))
    prefix = str(tmp_path / "m")
    save_checkpoint(prefix, 7, sym, args, {}, blocking=True)
    pool = ModelPool()
    pool.load("mlp", prefix, 7, sample_shapes={"data": (32,)})
    x = np.random.RandomState(0).randn(2, 32).astype("f")
    out = pool.get("mlp").forward({"data": x})[0]
    ref = ref_predictor(sym, args, {}, (2, 32)).forward(
        data=x).get_output(0)
    assert np.array_equal(out, ref)


def test_pool_load_dir_picks_newest_intact_epoch(tmp_path):
    """A CheckpointManager directory with a corrupted newest epoch:
    serving must come up on the previous INTACT epoch (the restore
    walk-back), not crash and not serve rotten weights."""
    from mxnet_tpu.resilience import CheckpointManager
    sym = mlp_sym()
    man = CheckpointManager(str(tmp_path))
    args1, _ = init_params(sym, (1, 32), seed=1)
    args2, _ = init_params(sym, (1, 32), seed=2)
    man.save(1, symbol=sym, arg_params=args1, aux_params={})
    man.save(2, symbol=sym, arg_params=args2, aux_params={})
    # rot epoch 2's params (valid length, flipped bytes)
    p2 = man.params_path(2)
    blob = bytearray(open(p2, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(p2, "wb") as f:
        f.write(blob)
    pool = ModelPool()
    entry = pool.load_dir("mlp", str(tmp_path),
                          sample_shapes={"data": (32,)})
    assert entry.loaded_epoch == 1
    x = np.zeros((1, 32), "f")
    ref = ref_predictor(sym, args1, {}, (1, 32)).forward(
        data=x).get_output(0)
    assert np.array_equal(entry.forward({"data": x})[0], ref)


def test_pool_bf16_weight_cast():
    pool, sym, args, auxs = make_pool(dtype="bfloat16")
    entry = pool.get("m")
    assert all(np.dtype(v.dtype).name == "bfloat16"
               for v in entry.arg_params.values())
    x = np.random.RandomState(0).randn(2, 32).astype("f")
    out = entry.forward({"data": x})[0]
    assert np.isfinite(out).all()
    f32 = ref_predictor(sym, args, auxs, (2, 32)).forward(
        data=x).get_output(0)
    np.testing.assert_allclose(out, f32, rtol=0.1, atol=0.05)


def test_pool_bn_fold_is_the_serving_default_with_tolerance_parity(
        monkeypatch):
    """Inference-trace conv-BN folding (`bn_fold`) is the SERVING
    default: the default MXTPU_FUSED_KERNELS set includes it, the
    pooled conv/BN forward's plan structurally carries the fold (the
    BN entry holds the conv's inputs as extra refs), and the served
    outputs are tolerance-equal to a fold-off pool — the ONE
    documented non-bitwise fusion (docs/how_to/serving.md, next to the
    bf16/int8 accuracy rows)."""
    from mxnet_tpu import kernels
    from mxnet_tpu.executor import _fuse_bn_plan, _node_plan
    monkeypatch.delenv("MXTPU_FUSED_KERNELS", raising=False)
    assert "bn_fold" in kernels.enabled_kernels()   # default = on
    sym = conv_sym()
    # structural proof on the very graph the pool serves: under the
    # DEFAULT env the fusion pass folds bn1 into c1 (3 conv extra refs)
    plan = _node_plan(sym)
    refs = [(id(n), i) for n, i in sym._outputs]
    fused = _fuse_bn_plan(plan, refs)
    bn_entry = next(e for e in fused if e[0].name == "bn1")
    assert bn_entry[5] is not None and len(bn_entry[5][1]) == 3

    x = np.random.RandomState(3).randn(4, 3, 8, 8).astype("f")
    pool_on, _, args, auxs = make_pool(sym=sym, sample=(3, 8, 8))
    folded = pool_on.get("m").forward({"data": x})[0]
    # a fresh pool with the fold disabled (everything else fused as
    # before): tolerance-equal, per the documented contract
    monkeypatch.setenv("MXTPU_FUSED_KERNELS",
                       "bn_act,lstm_cell,flash_attention,augment")
    assert "bn_fold" not in kernels.enabled_kernels()
    pool_off = ModelPool()
    pool_off.add("m", sym, args, auxs, sample_shapes={"data": (3, 8, 8)})
    unfolded = pool_off.get("m").forward({"data": x})[0]
    np.testing.assert_allclose(folded, unfolded, rtol=1e-5, atol=1e-6)


def test_pool_inference_trace_passes_stay_in_fold_contract(monkeypatch):
    """The mxfuse inference-trace pass set (infer_trace DCE +
    concat/pool rewrites) is part of the serving default: a pool
    serving with everything on stays within the SAME rtol 1e-5
    contract bn_fold established vs a pre-mxfuse pool, and the
    infer_trace pruning alone changes NOTHING bitwise."""
    from mxnet_tpu import kernels
    monkeypatch.delenv("MXTPU_FUSED_KERNELS", raising=False)
    for name in ("concat_fuse", "pool_act", "eltwise_chain",
                 "infer_trace"):
        assert name in kernels.enabled_kernels()   # serving default
    sym = conv_sym()
    x = np.random.RandomState(7).randn(4, 3, 8, 8).astype("f")
    pool_on, _, args, auxs = make_pool(sym=sym, sample=(3, 8, 8))
    on = pool_on.get("m").forward({"data": x})[0]
    # pre-mxfuse kernel set (bn_act/bn_fold still on)
    monkeypatch.setenv("MXTPU_FUSED_KERNELS",
                       "bn_act,bn_fold,lstm_cell,flash_attention,"
                       "augment")
    pool_pre = ModelPool()
    pool_pre.add("m", sym, args, auxs, sample_shapes={"data": (3, 8, 8)})
    pre = pool_pre.get("m").forward({"data": x})[0]
    np.testing.assert_allclose(on, pre, rtol=1e-5, atol=1e-6)
    # DCE alone is bit-identical: all passes on vs all-but-infer_trace
    monkeypatch.setenv(
        "MXTPU_FUSED_KERNELS",
        ",".join(k for k in kernels.KNOWN_KERNELS
                 if k != "infer_trace"))
    pool_np = ModelPool()
    pool_np.add("m", sym, args, auxs, sample_shapes={"data": (3, 8, 8)})
    assert np.array_equal(on, pool_np.get("m").forward({"data": x})[0])
    # the served graph's plan-fusion-parity audit rides analyze()
    monkeypatch.delenv("MXTPU_FUSED_KERNELS", raising=False)
    rep = pool_on.get("m").analyze(bucket=2)
    assert rep.ok, rep.format_text()
    assert "plan_fusion" in rep.stats


def test_pool_unknown_model_and_names():
    pool, _, _, _ = make_pool()
    assert pool.names() == ["m"]
    assert "m" in pool and "nope" not in pool
    with pytest.raises(MXNetError, match="no model"):
        pool.get("nope")


def test_env_analyze_gates_serving_compiles(monkeypatch, caplog):
    """MXTPU_ANALYZE=1 lints each newly compiled bucket (warn mode);
    strict mode refuses a violating forward STICKILY — a retry of the
    same signature must not slip the bad program into service."""
    import logging

    monkeypatch.setenv("MXTPU_ANALYZE", "1")
    pool, _, _, _ = make_pool()
    entry = pool.get("m")
    x = np.zeros((2, 32), "f")
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.serving.pool"):
        entry.forward({"data": x})
    assert any("MXTPU_ANALYZE" in r.message for r in caplog.records)

    class FakeReport:
        ok = False

        @staticmethod
        def format_text():
            return "graph-callback: seeded"

    monkeypatch.setenv("MXTPU_ANALYZE", "strict")
    pool2, _, _, _ = make_pool()
    entry2 = pool2.get("m")
    monkeypatch.setattr(entry2, "analyze", lambda bucket: FakeReport)
    for _ in range(2):      # the second hit must refuse WITHOUT relint
        with pytest.raises(MXNetError, match="strict"):
            entry2.forward({"data": x})
    assert tuple(entry2._refused)  # the refusal is recorded


def test_frontend_rejects_wrong_sample_shape_with_400():
    """A client sending the wrong per-sample shape is a 400 — and must
    never pin the model's shapes or surface as a 500 from the model."""
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0)
    status, payload = fe.handle_predict(
        "m", {"data": np.zeros((16,), "f")})
    assert status == 400 and "shapes" in payload["error"]
    # the right shape still serves
    status, _ = fe.handle_predict("m", {"data": np.zeros((32,), "f")})
    assert status == 200


def test_malformed_first_request_does_not_brick_undeclared_model():
    """A daemon started WITHOUT declared input shapes: the first
    request is malformed (wrong input dim).  It must fail alone (5xx
    for that client) — a correct request afterwards must serve, not be
    rejected against shapes the bad request pinned."""
    sym = mlp_sym()
    args, auxs = init_params(sym, (1, 32))
    pool = ModelPool()
    pool.add("m", sym, args, auxs)          # sample_shapes undeclared
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0)
    status, _ = fe.handle_predict("m", {"data": np.zeros((33,), "f")})
    assert status == 500                    # the bad request itself
    assert pool.get("m").sample_shapes is None   # nothing pinned
    status, payload = fe.handle_predict(
        "m", {"data": np.zeros((32,), "f")})
    assert status == 200, payload           # the model is NOT bricked
    assert pool.get("m").sample_shapes == {"data": (32,)}


def test_serving_forward_graph_lint_clean():
    """Donation/dtype/callback/collective rules apply to inference
    graphs too: the pooled MLP *and* conv forward lint clean, and a
    single-device forward shows zero collectives."""
    for sym_fn, sample in ((mlp_sym, (32,)), (conv_sym, (3, 8, 8))):
        pool, _, _, _ = make_pool(sym_fn(), sample)
        report = pool.get("m").analyze(bucket=4)
        assert report.ok, report.format_text()
        assert report.stats["collectives"] == {}


# ---------------------------------------------------------------------------
# frontend: admission control + stats (no HTTP server needed)
# ---------------------------------------------------------------------------

def test_frontend_handle_predict_and_stats():
    pool, sym, args, auxs = make_pool()
    fe = ServingFrontend(pool, buckets=(1, 2, 4), max_wait_ms=1)
    x = np.random.RandomState(0).randn(32).astype("f")
    status, payload = fe.handle_predict("m", {"data": x})
    assert status == 200
    ref = ref_predictor(sym, args, auxs, (1, 32)).forward(
        data=x[None]).get_output(0)[0]
    assert np.array_equal(
        np.asarray(payload["outputs"][0], np.float32), ref)
    stats = fe.stats_payload()
    assert stats["counters"]["accepted"] == 1
    assert stats["counters"]["completed"] == 1
    assert stats["batches"]["count"] == 1
    assert stats["batches"]["fill_ratio"] == 1.0
    assert stats["latency_ms"]["p50"] is not None


def test_frontend_sheds_on_queue_bound():
    release = threading.Event()
    pool, _, _, _ = make_pool()
    entry = pool.get("m")
    real_forward = entry.forward

    def slow_forward(inputs, n=None):
        release.wait(30)
        return real_forward(inputs, n)

    entry.forward = slow_forward
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0, max_queue=1)
    x = np.zeros((32,), "f")
    codes = []
    threads = [threading.Thread(
        target=lambda: codes.append(fe.handle_predict("m",
                                                      {"data": x})[0]))
        for _ in range(4)]
    for t in threads:
        t.start()
        time.sleep(0.05)   # deterministic arrival order
    release.set()
    for t in threads:
        t.join(timeout=30)
    assert codes.count(429) >= 1
    assert fe.stats.snapshot()["counters"]["shed_queue"] >= 1
    # the admitted ones all completed
    assert codes.count(200) == 4 - codes.count(429)


def test_frontend_slo_shed_uses_wait_estimate():
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,), slo_ms=5.0, max_queue=100)
    b = fe.batcher("m")
    b._ema_batch_s = 1.0          # pretend forwards take 1s
    with b._cv:
        b._inflight = 1           # and one is running now
    ok, status, reason = fe.admit("m")
    assert not ok and status == 429 and "SLO" in reason
    assert fe.stats.snapshot()["counters"]["shed_slo"] == 1
    with b._cv:
        b._inflight = 0
    assert fe.admit("m")[0]


def test_frontend_draining_rejects_with_503():
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,))
    fe.draining = True
    status, payload = fe.handle_predict(
        "m", {"data": np.zeros((32,), "f")})
    assert status == 503 and "draining" in payload["error"]


def test_each_model_batcher_gets_its_own_watchdog():
    """Watchdog coverage in a MULTI-model daemon: armed()'s nesting
    bookkeeping is single-thread, and every model's batcher dispatches
    on its own thread — sharing one StepWatchdog would mis-track
    overlapping arms (a wedged forward could go unmonitored and the
    depth could latch above zero, disarming the watchdog for good).
    Each batcher must therefore own a distinct watchdog, all stopped by
    the drain."""
    from mxnet_tpu.resilience import StepWatchdog
    sym = mlp_sym()
    args, auxs = init_params(sym, (1, 32))
    pool = ModelPool()
    for name in ("a", "b"):
        pool.add(name, sym, args, auxs, sample_shapes={"data": (32,)})
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0,
                         watchdog=StepWatchdog(timeout=30))
    ba, bb = fe.batcher("a"), fe.batcher("b")
    assert ba.watchdog is not None and bb.watchdog is not None
    assert ba.watchdog is not bb.watchdog
    # overlapping arms on the two dispatcher threads stay independent:
    # each watchdog sees exactly its own model's deadline
    with ba.watchdog.armed("a"), bb.watchdog.armed("b"):
        assert ba.watchdog._armed_at is not None
        assert bb.watchdog._armed_at is not None
    assert ba.watchdog._depth == 0 and bb.watchdog._depth == 0
    fe.drain_and_stop(timeout=5)
    assert fe._watchdogs == []
    assert ba.watchdog._thread is None and bb.watchdog._thread is None


def test_drain_racing_serve_forever_still_stops():
    """The SIGTERM-during-warmup window: the drain may start BEFORE
    serve_forever (handlers are installed before warmup).  shutdown()
    then blocks until the accept loop starts — which must notice the
    pending request and return immediately instead of serving a
    draining daemon forever."""
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0).start()
    drainer = threading.Thread(target=fe.drain_and_stop, daemon=True)
    drainer.start()
    time.sleep(0.2)              # drain is parked inside shutdown()
    server = threading.Thread(target=fe.serve_forever, daemon=True)
    server.start()
    server.join(timeout=10)
    assert not server.is_alive(), \
        "serve_forever kept accepting on a draining daemon"
    drainer.join(timeout=10)
    assert not drainer.is_alive()
    assert fe.wait_stopped(1)


def test_stats_percentiles():
    from mxnet_tpu.serving import Stats
    s = Stats()
    for v in range(1, 101):
        s.record_latency(float(v))
    snap = s.snapshot()
    assert snap["latency_ms"]["p50"] == pytest.approx(50, abs=2)
    assert snap["latency_ms"]["p99"] == pytest.approx(99, abs=2)


# ---------------------------------------------------------------------------
# the HTTP daemon (tools/serve.py) end to end
# ---------------------------------------------------------------------------

def _save_mlp(tmp_path):
    from mxnet_tpu.model import save_checkpoint
    sym = mlp_sym()
    args, _ = init_params(sym, (1, 32))
    prefix = str(tmp_path / "mlp")
    save_checkpoint(prefix, 1, sym, args, {}, blocking=True)
    return sym, args, prefix


def _spawn_daemon(tmp_path, prefix, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    port_file = str(tmp_path / "port")
    proc = subprocess.Popen(
        [sys.executable, SERVE, "--model", "mlp=%s:1" % prefix,
         "--input-shape", "data=32", "--port", "0",
         "--port-file", port_file, "--buckets", "1,2,4,8", *extra],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        text=True)
    deadline = time.monotonic() + 120
    while not os.path.exists(port_file):
        if proc.poll() is not None:
            raise AssertionError("daemon died: %s"
                                 % proc.stderr.read()[-3000:])
        if time.monotonic() > deadline:
            proc.kill()
            raise AssertionError("daemon never wrote its port file")
        time.sleep(0.05)
    port = int(open(port_file).read().split(":")[1])
    return proc, port


def test_daemon_end_to_end(tmp_path):
    """The full lifecycle: load a checkpoint pair, /healthz, bit-exact
    /predict (JSON and npy bodies), live /stats, 404/400 paths, then a
    SIGTERM drain to exit 0."""
    sym, args, prefix = _save_mlp(tmp_path)
    proc, port = _spawn_daemon(tmp_path, prefix)
    try:
        cli = ServeClient("127.0.0.1", port)
        health = cli.wait_ready(60)
        assert health["status"] == "ok" and health["models"] == ["mlp"]

        x = np.random.RandomState(0).randn(32).astype("f")
        ref = ref_predictor(sym, args, {}, (1, 32)).forward(
            data=x[None]).get_output(0)[0]
        for npy in (False, True):
            status, payload = cli.predict("mlp", x, npy=npy)
            assert status == 200, payload
            assert np.array_equal(
                np.asarray(payload["outputs"][0], np.float32), ref)

        status, stats = cli.stats()
        assert status == 200
        assert stats["counters"]["completed"] == 2
        assert stats["queue_depth"] == {"mlp": 0}

        status, _ = cli.predict("nope", x)
        assert status == 404
        status, payload = cli._request("POST", "/predict/mlp",
                                       body=b"{}")
        assert status == 400
        cli.close()
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
        assert "drained" in proc.stderr.read()


def test_daemon_drains_past_idle_keepalive_connection(tmp_path):
    """An IDLE keep-alive connection (a client that made a request and
    then just held the socket open) must not wedge the SIGTERM drain:
    its handler thread sits in a socket read, and shutdown joins
    handler threads — without the handler's socket timeout the daemon
    would never exit.  The drain must still finish with exit 0."""
    _, _, prefix = _save_mlp(tmp_path)
    proc, port = _spawn_daemon(tmp_path, prefix)
    cli = ServeClient("127.0.0.1", port)
    try:
        cli.wait_ready(60)
        status, _ = cli.predict("mlp", np.zeros((32,), "f"))
        assert status == 200
        # do NOT close cli: the keep-alive socket stays open and idle
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0, \
            "drain wedged behind an idle keep-alive connection"
    finally:
        cli.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


def test_daemon_healthz_reports_draining(tmp_path):
    _, _, prefix = _save_mlp(tmp_path)
    proc, port = _spawn_daemon(tmp_path, prefix)
    try:
        cli = ServeClient("127.0.0.1", port)
        cli.wait_ready(60)
        proc.send_signal(signal.SIGTERM)
        # between SIGTERM and exit the daemon reports draining (or is
        # already gone — both are legal; only a non-zero exit is not)
        try:
            status, health = cli.healthz()
            if status == 200:
                assert health["status"] in ("draining", "ok")
        except Exception:  # noqa: BLE001 — already exited
            pass
        cli.close()
    finally:
        assert proc.wait(timeout=60) == 0


def test_bucket_shape_stats_expose_batching(tmp_path):
    """Concurrent clients against the daemon produce multi-row batches
    (fill ratio recorded) and every response is bit-exact vs its bucket
    reference — continuous batching changes THROUGHPUT, not bytes."""
    sym, args, prefix = _save_mlp(tmp_path)
    proc, port = _spawn_daemon(tmp_path, prefix, "--max-wait-ms", "20",
                               "--warmup")
    try:
        ServeClient("127.0.0.1", port).wait_ready(60)
        rs = np.random.RandomState(1)
        X = rs.randn(12, 32).astype("f")
        results = [None] * 12

        def worker(i):
            c = ServeClient("127.0.0.1", port)
            try:
                results[i] = c.predict("mlp", X[i])
            finally:
                c.close()

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        refs = {b: ref_predictor(sym, args, {}, (b, 32))
                for b in (1, 2, 4, 8)}
        for i in range(12):
            status, payload = results[i]
            assert status == 200
            got = np.asarray(payload["outputs"][0], np.float32)
            assert any(np.array_equal(
                got, refs[b].forward(
                    data=pad_to_bucket([X[i]], b)).get_output(0)[0])
                for b in refs), "request %d matches no bucket" % i
        status, stats = ServeClient("127.0.0.1", port).stats()
        assert status == 200
        assert stats["batches"]["rows"] == 12
        assert 0.0 < stats["batches"]["fill_ratio"] <= 1.0
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0


# ---------------------------------------------------------------------------
# two checkpoints of different shape behind one pool
# ---------------------------------------------------------------------------

def _save_serving_models(tmp):
    """Write two serving checkpoints — the standard MLP (models/mlp.py)
    and a small-image ResNet-20 (the cifar branch of models/resnet.py) —
    and return {name: (prefix, epoch, sample_shape)}."""
    from mxnet_tpu import models
    from mxnet_tpu.model import save_checkpoint

    rs = np.random.RandomState(7)
    out = {}
    for name, sym, sample in (
            ("mlp", models.get_symbol("mlp", num_classes=10), (784,)),
            ("resnet", models.get_symbol("resnet", num_classes=10,
                                         num_layers=20,
                                         image_shape=(3, 32, 32)),
             (3, 32, 32))):
        arg_shapes, _, aux_shapes = sym.infer_shape(data=(1,) + sample)
        args = {n: mx.nd.array(rs.uniform(-0.1, 0.1, s).astype("f"))
                for n, s in zip(sym.list_arguments(), arg_shapes)
                if n not in ("data", "softmax_label")}
        # BN moving stats: mean 0, var 1 — a forward through random
        # weights stays finite
        auxs = {n: mx.nd.array((np.ones(s) if n.endswith("var")
                                else np.zeros(s)).astype("f"))
                for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
        prefix = os.path.join(tmp, name)
        save_checkpoint(prefix, 1, sym, args, auxs, blocking=True)
        out[name] = (prefix, 1, sample)
    return out


def test_serving_models_save_and_load(tmp_path):
    specs = _save_serving_models(str(tmp_path))
    assert set(specs) == {"mlp", "resnet"}
    pool = ModelPool()
    for name, (prefix, epoch, sample) in specs.items():
        pool.load(name, prefix, epoch, sample_shapes={"data": sample})
        out = pool.get(name).forward(
            {"data": np.random.RandomState(0).rand(1, *sample)
             .astype("f")})[0]
        assert out.shape == (1, 10) and np.isfinite(out).all()


# ---------------------------------------------------------------------------
# priority + deadline dispatch (PR 11 satellite)
# ---------------------------------------------------------------------------

def _tagged_batcher(order, buckets=(1,), **kw):
    """A batcher whose runner records each dispatched row's tag and a
    gate that holds the FIRST dispatch open so a queue can build."""
    from mxnet_tpu.serving.batcher import BucketBatcher
    gate = threading.Event()
    first = threading.Event()

    def runner(inputs, n):
        vals = np.asarray(inputs["data"])
        if not first.is_set():
            first.set()
            assert gate.wait(10), "test gate never released"
        order.extend(vals[:n, 0].tolist())
        return [vals]

    b = BucketBatcher(runner, buckets=buckets, max_wait_ms=0, **kw)
    return b, gate, first


def test_priority_dispatches_highest_first_fifo_within_level():
    order = []
    b, gate, first = _tagged_batcher(order)
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)           # queue builds behind this one
        for tag, pri in ((1.0, 0), (2.0, 5), (3.0, 1), (4.0, 5)):
            futs.append(b.submit({"data": np.full((2,), tag, "f")},
                                 priority=pri))
        gate.set()
        for f in futs:
            f.result(timeout=10)
        # priority desc; FIFO within the two p=5 entries (2 before 4)
        assert order == [0.0, 2.0, 4.0, 3.0, 1.0]
    finally:
        b.close()


def test_equal_priority_keeps_exact_fifo_order():
    """The regression pin: all-default-priority traffic must keep the
    historical strict-FIFO dispatch order bit for bit."""
    order = []
    b, gate, first = _tagged_batcher(order)
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)
        for tag in (1.0, 2.0, 3.0, 4.0):
            futs.append(b.submit({"data": np.full((2,), tag, "f")}))
        gate.set()
        for f in futs:
            f.result(timeout=10)
        assert order == [0.0, 1.0, 2.0, 3.0, 4.0]
    finally:
        b.close()


def test_priority_traffic_keeps_bit_exactness_contract():
    """Reordering changes WHEN a request runs, never WHAT it returns:
    mixed-priority traffic is bit-identical to the unbatched reference
    forward at the same bucket shape (bucket pinned to 1 here — the
    contract is per bucket SHAPE, and cross-shape deltas are the
    documented reason buckets exist)."""
    pool, sym, args, auxs = make_pool()
    entry = pool.get("m")
    from mxnet_tpu.serving.batcher import BucketBatcher
    b = BucketBatcher(entry.forward, buckets=(1,), max_wait_ms=1)
    try:
        rs = np.random.RandomState(3)
        xs = [rs.rand(32).astype("f") for _ in range(6)]
        futs = [b.submit({"data": x, }, priority=i % 3)
                for i, x in enumerate(xs)]
        got = [f.result(timeout=30)[0] for f in futs]
        ref = ref_predictor(sym, args, auxs, (1, 32))
        for x, out in zip(xs, got):
            expected = ref.forward(data=x[None]).get_output(0)[0]
            assert np.array_equal(out, expected)
    finally:
        b.close()


def test_deadline_expires_queued_entries_as_shed_deadline():
    from mxnet_tpu.serving import DeadlineExpired, Stats
    order = []
    stats = Stats()
    b, gate, first = _tagged_batcher(order, stats=stats)
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)
        doomed = b.submit({"data": np.full((2,), 1.0, "f")},
                          deadline_ms=30)
        kept = b.submit({"data": np.full((2,), 2.0, "f")})
        time.sleep(0.15)                # the deadline passes queued
        gate.set()
        futs[0].result(timeout=10)
        kept.result(timeout=10)
        with pytest.raises(DeadlineExpired):
            doomed.result(timeout=10)
        assert 1.0 not in order         # dead work never dispatched
        assert stats.snapshot()["counters"]["shed_deadline"] == 1
    finally:
        b.close()


def test_deadline_already_spent_sheds_at_submit():
    from mxnet_tpu.serving import DeadlineExpired, Stats
    stats = Stats()
    order = []
    b, gate, first = _tagged_batcher(order, stats=stats)
    gate.set()
    try:
        with pytest.raises(DeadlineExpired):
            b.submit({"data": np.zeros(2, "f")}, deadline_ms=0)
        assert stats.snapshot()["counters"]["shed_deadline"] == 1
    finally:
        b.close()


def test_frontend_deadline_is_429_and_stats_expose_est_wait():
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1, 2), max_wait_ms=1)
    status, payload = fe.handle_predict(
        "m", {"data": np.zeros(32, "f")}, deadline_ms=-1.0)
    assert status == 429
    assert payload["reason"] == "shed_deadline"
    # a served request keeps working with qos args
    status, payload = fe.handle_predict(
        "m", {"data": np.zeros(32, "f")}, priority=3, deadline_ms=5000)
    assert status == 200
    stats = fe.stats_payload()
    assert stats["counters"]["shed_deadline"] == 1
    assert "est_wait_ms" in stats and "m" in stats["est_wait_ms"]
    fe.drain_and_stop()


def test_http_qos_headers_reach_the_batcher():
    """priority/deadline ride X-MXTPU-* headers (and JSON body fields)
    through the HTTP layer; an already-spent deadline answers 429 with
    shed_deadline end to end."""
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, port=0, buckets=(1, 2), max_wait_ms=1)
    fe.serve_in_background()
    try:
        cli = ServeClient("127.0.0.1", fe.port, timeout=30)
        status, payload = cli.predict("m", np.zeros(32, "f"),
                                      priority=2, deadline_ms=8000)
        assert status == 200
        status, payload = cli.predict("m", np.zeros(32, "f"),
                                      deadline_ms=-5)
        assert status == 429 and payload["reason"] == "shed_deadline"
        # body fields override headers (JSON route)
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", fe.port,
                                          timeout=30)
        body = json.dumps({"inputs": {"data": [0.0] * 32},
                           "deadline_ms": -1}).encode()
        conn.request("POST", "/predict/m", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 429
        assert json.loads(resp.read())["reason"] == "shed_deadline"
        conn.close()
        cli.close()
    finally:
        fe.drain_and_stop()


# ---------------------------------------------------------------------------
# int8 weight quantization (PR 11 satellite)
# ---------------------------------------------------------------------------

def test_quantize_int8_per_channel_properties():
    from mxnet_tpu.serving.pool import quantize_int8
    rs = np.random.RandomState(0)
    w = rs.uniform(-2.0, 2.0, (8, 16)).astype("f")
    w[3] *= 0.01                        # a tiny channel gets its own scale
    w[5] = 0.0                          # an all-zero channel
    q, s = quantize_int8(w)
    assert q.dtype == np.int8 and s.shape == (8, 1)
    assert np.abs(q).max() <= 127
    # symmetric: no zero point — w ~ q * s within half a step per channel
    assert np.all(np.abs(w - q * s) <= s / 2 + 1e-8)
    assert s[5, 0] == 1.0 and np.all(q[5] == 0)
    # per-channel: the tiny channel's scale is ~100x finer
    assert s[3, 0] < s[0, 0] / 10
    # conv layout: scale broadcasts over (O, I, kH, kW)
    wc = rs.uniform(-1, 1, (4, 3, 3, 3)).astype("f")
    qc, sc = quantize_int8(wc)
    assert sc.shape == (4, 1, 1, 1)
    assert np.all(np.abs(wc - qc * sc) <= sc / 2 + 1e-8)


@pytest.mark.parametrize("sym_fn,sample",
                         [(mlp_sym, (32,)), (conv_sym, (3, 8, 8))])
def test_pool_int8_parity_within_tolerance(sym_fn, sample):
    """The accuracy contract (docs/how_to/serving.md): int8 weight-only
    serving tracks f32 within a small tolerance — and is NOT bit-equal
    (the quantization actually engaged)."""
    sym = sym_fn()
    args, auxs = init_params(sym, (1,) + sample)
    p32 = ModelPool()
    p32.add("m", sym, dict(args), dict(auxs),
            sample_shapes={"data": sample})
    p8 = ModelPool(dtype="int8")
    e8 = p8.add("m", sym, dict(args), dict(auxs),
                sample_shapes={"data": sample})
    assert e8._wt_scales, "no weight was quantized"
    x = np.random.RandomState(5).rand(8, *sample).astype("f")
    o32 = p32.get("m").forward({"data": x})[0]
    o8 = e8.forward({"data": x})[0]
    assert not np.array_equal(o32, o8)
    np.testing.assert_allclose(o8, o32, atol=2e-2, rtol=5e-2)


def test_pool_int8_device_bytes_are_quarter_f32():
    pool, _, _, _ = make_pool(dtype="int8")
    entry = pool.get("m")
    entry.forward({"data": np.zeros((1, 32), "f")})
    f32_bytes = sum(
        int(np.prod(np.shape(v))) * 4
        for k, v in entry.arg_params.items() if k in entry._wt_scales)
    resident = entry._int8.resident_weight_bytes()
    # int8 payload + f32 per-channel scales: ~1/4 + epsilon
    assert resident < 0.3 * f32_bytes


def test_pool_int8_keeps_bucket_bit_stability_contract():
    """One program per bucket shape holds for the int8 path too: a
    row's result is independent of fill and co-batched rows."""
    pool, _, _, _ = make_pool(dtype="int8")
    entry = pool.get("m")
    rs = np.random.RandomState(2)
    x = rs.rand(8, 32).astype("f")
    alone = entry.forward(
        {"data": np.concatenate([x[:1]] * 8)})[0][0]
    cohort = entry.forward({"data": x})[0][0]
    assert np.array_equal(alone, cohort)


def test_pool_int8_composes_with_batcher_and_analyze():
    from mxnet_tpu.serving.batcher import BucketBatcher
    pool, _, _, _ = make_pool(dtype="int8")
    entry = pool.get("m")
    b = BucketBatcher(entry.forward, buckets=(1, 2, 4), max_wait_ms=1)
    try:
        rs = np.random.RandomState(1)
        xs = [rs.rand(32).astype("f") for _ in range(3)]
        futs = [b.submit({"data": x}) for x in xs]
        got = [f.result(timeout=30)[0] for f in futs]
        for x, out in zip(xs, got):
            direct = entry.forward(
                {"data": np.stack([x])})[0][0]
            assert out.shape == direct.shape
        # the inference lint runs on the math actually served
        # (dequantized weights)
        assert entry.analyze(bucket=2).ok
    finally:
        b.close()


# ---------------------------------------------------------------------------
# AOT executable store (PR 11 tentpole; serving/aot.py)
# ---------------------------------------------------------------------------

def test_aot_export_load_bit_parity_with_predictor(tmp_path):
    """THE warm-store correctness claim: a replica that warms by
    deserializing stored executables serves bit-identically to one
    that traced and compiled its own."""
    pool, sym, args, auxs = make_pool()
    entry = pool.get("m")
    entry.export_aot([1, 2, 4], str(tmp_path / "aot"))
    fresh = ModelPool()
    loaded = fresh.add("m", sym, dict(args), dict(auxs),
                       sample_shapes={"data": (32,)})
    assert loaded.load_aot(str(tmp_path / "aot")) == 3
    rs = np.random.RandomState(4)
    for n in (1, 2, 4):
        x = rs.rand(n, 32).astype("f")
        out_aot = loaded.forward({"data": x})[0]
        out_pred = entry.forward({"data": x})[0]
        assert np.array_equal(out_aot, out_pred), "bucket %d" % n
    # a non-bucket shape transparently falls back to the Predictor path
    x = rs.rand(3, 32).astype("f")
    assert loaded.forward({"data": x})[0].shape == (3, 10)


def test_aot_store_meta_mismatch_falls_back(tmp_path, caplog):
    import logging
    pool, sym, args, auxs = make_pool()
    pool.get("m").export_aot([1], str(tmp_path / "aot"))
    other = ModelPool()
    entry = other.add("m", sym, dict(args), dict(auxs),
                      sample_shapes={"data": (16,)})   # different shape
    with caplog.at_level(logging.WARNING):
        assert entry.load_aot(str(tmp_path / "aot")) == 0
    assert "meta mismatch" in caplog.text
    # absent store: quiet zero
    assert entry.load_aot(str(tmp_path / "nowhere")) == 0


def test_aot_store_corrupt_artifact_falls_back(tmp_path, caplog):
    import logging
    pool, sym, args, auxs = make_pool()
    entry = pool.get("m")
    store = entry.export_aot([1], str(tmp_path / "aot"))
    # rot the executable bytes; load must warn and refuse, not serve it
    path = str(tmp_path / "aot" / "m-b1.exec")
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    blob = blob[:len(blob) // 2]
    with open(path, "wb") as f:
        f.write(blob)
    fresh = ModelPool()
    loaded = fresh.add("m", sym, dict(args), dict(auxs),
                       sample_shapes={"data": (32,)})
    with caplog.at_level(logging.WARNING):
        assert loaded.load_aot(str(tmp_path / "aot")) == 0
    assert not loaded._aot
    # serving still works — through the classic path
    assert loaded.forward(
        {"data": np.zeros((1, 32), "f")})[0].shape == (1, 10)


def test_aot_int8_pool_refuses_export_and_load(tmp_path):
    pool, sym, args, auxs = make_pool()
    pool.get("m").export_aot([1], str(tmp_path / "aot"))
    p8 = ModelPool(dtype="int8")
    e8 = p8.add("m", sym, dict(args), dict(auxs),
                sample_shapes={"data": (32,)})
    with pytest.raises(MXNetError, match="int8"):
        e8.export_aot([1], str(tmp_path / "aot2"))
    assert e8.load_aot(str(tmp_path / "aot")) == 0


def test_serve_daemon_warms_from_aot_store(tmp_path):
    """End to end through tools/serve.py: build the store with
    --warmup-only --export-aot, then a daemon launched against the same
    cache warms by LOADING and serves bit-identically to a storeless
    daemon."""
    sym, args, prefix = _save_mlp(tmp_path)
    store = str(tmp_path / "cache")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=store)
    res = subprocess.run(
        [sys.executable, SERVE, "--model", "mlp=%s:1" % prefix,
         "--input-shape", "data=32", "--port", "0",
         "--buckets", "1,2,4", "--warmup-only", "--export-aot"],
        capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr
    assert "exported AOT executables" in res.stderr
    assert os.path.isdir(os.path.join(store, "aot"))
    proc, port = _spawn_daemon(tmp_path, prefix, "--warmup",
                               "--buckets", "1,2,4",
                               env_extra={
                                   "JAX_COMPILATION_CACHE_DIR": store})
    try:
        # the daemon's stderr says it warmed from the store
        x = np.random.RandomState(6).rand(32).astype("f")
        cli = ServeClient("127.0.0.1", port, timeout=30)
        status, payload = cli.predict("mlp", x)
        assert status == 200
        got = np.asarray(payload["outputs"][0], dtype=np.float32)
        blob = {("arg:%s" % k): v for k, v in args.items()}
        pred = predict.Predictor(sym, blob, {"data": (1, 32)})
        expected = pred.forward(data=x[None]).get_output(0)[0]
        assert np.array_equal(got, expected)
        cli.close()
    finally:
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=60) == 0
    assert "from the AOT store" in proc.stderr.read()


def test_sustained_high_priority_cannot_starve_low():
    """The anti-starvation bound (review finding): under a continuous
    self-refilling stream of priority-9 arrivals, a priority-0 request
    older than the starvation bound claims a batch slot and completes
    WHILE the flood is still running — not after it ends."""
    from mxnet_tpu.serving.batcher import BucketBatcher
    state = {"refills": 0, "low_seen_at": None, "b": None}

    def runner(inputs, n):
        vals = np.asarray(inputs["data"])
        if 1.0 in vals[:n, 0]:
            state["low_seen_at"] = state["refills"]
        elif state["refills"] < 200 and state["low_seen_at"] is None:
            state["refills"] += 1
            state["b"].submit({"data": np.full((2,), 9.0, "f")},
                              priority=9)
        time.sleep(0.02)        # keep the queue permanently non-empty
        return [vals]

    b = state["b"] = BucketBatcher(runner, buckets=(1,), max_wait_ms=0)
    try:
        b.submit({"data": np.full((2,), 9.0, "f")}, priority=9)
        low = b.submit({"data": np.full((2,), 1.0, "f")}, priority=0)
        low.result(timeout=30)
        # served DURING the flood (which only stops once low is seen):
        # a few batches in — after the ~0.25s starvation bound — but
        # long before the 200-refill flood would have drained
        assert state["low_seen_at"] is not None
        assert 3 <= state["low_seen_at"] < 150, state
    finally:
        b.close()


# ---------------------------------------------------------------------------
# train-to-serve hot swap (serving/deploy.py — ISSUE 13)
# ---------------------------------------------------------------------------

def _ckpt_stream(tmp_path, sym=None, sample=(32,), seed0=1):
    """A CheckpointManager dir + an epoch-writer: save(epoch) writes
    params seeded per epoch (so every epoch's weights differ)."""
    from mxnet_tpu.resilience import CheckpointManager
    sym = sym if sym is not None else mlp_sym()
    man = CheckpointManager(str(tmp_path / "stream"))

    def save(epoch, args=None, auxs=None):
        if args is None:
            args, auxs = init_params(sym, (1,) + tuple(sample),
                                     seed=seed0 + epoch)
        man.save(epoch, symbol=sym, arg_params=args,
                 aux_params=auxs or {}, blocking=True)
        return man

    save(1)
    return man, sym, save


def _watched_pool(tmp_path, **kw):
    from mxnet_tpu.serving.deploy import CheckpointWatcher
    man, sym, save = _ckpt_stream(tmp_path)
    pool = ModelPool()
    entry = pool.load_dir("m", man.directory,
                          sample_shapes={"data": (32,)}, **kw)
    watcher = CheckpointWatcher(pool, "m")
    return man, sym, save, pool, entry, watcher


def test_hot_swap_bit_exactness_unchanged_and_swapped(tmp_path):
    """THE bit-exactness contract: (1) a model whose weights did NOT
    change serves bitwise-identical outputs across another model's
    swap; (2) the swapped model serves outputs bitwise equal to a
    fresh pool loaded directly from the new checkpoint — the swap
    installs the new epoch's exact bytes."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    # a second, UNTOUCHED model in the same pool
    args_b, auxs_b = init_params(mlp_sym(nh=24), (1, 32), seed=77)
    pool.add("bystander", mlp_sym(nh=24), args_b, auxs_b,
             sample_shapes={"data": (32,)})
    x = {"data": np.random.RandomState(3).rand(4, 32).astype("f")}
    before_b = pool.get("bystander").forward(dict(x))
    assert watcher.check_once()["action"] == "current"

    save(2)
    out = watcher.check_once()
    assert out["ok"] and out["action"] == "promoted", out
    assert entry.loaded_epoch == 2

    after_b = pool.get("bystander").forward(dict(x))
    for a, b in zip(before_b, after_b):
        assert np.array_equal(a, b), "bystander's bytes moved"

    swapped = entry.forward(dict(x))
    fresh_pool = ModelPool()
    fresh = fresh_pool.load_dir("m", man.directory,
                                sample_shapes={"data": (32,)})
    assert fresh.loaded_epoch == 2
    fresh_out = fresh.forward(dict(x))
    for a, b in zip(swapped, fresh_out):
        assert np.array_equal(a, b), "swap != fresh load of the epoch"


def test_hot_swap_rejects_rot_keeps_serving_then_walks_past(
        tmp_path, clean_faults):
    """A rot-injected epoch (byte flipped AFTER the manifest vouched —
    the rot_checkpoint fault point) is rejected by digest BEFORE any
    read: the counter moves, serving stays bitwise on the old epoch,
    the same bad publish is not re-counted every poll, and a later
    clean epoch promotes right past it."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    x = {"data": np.random.RandomState(5).rand(2, 32).astype("f")}
    before = entry.forward(dict(x))

    clean_faults.arm("rot_checkpoint")
    save(2)
    out = watcher.check_once()
    assert not out["ok"] and out["action"] == "rejected", out
    assert out["target"] == 2 and out["epoch"] == 1
    assert watcher.counters["rejected"] == 1
    assert entry.loaded_epoch == 1
    after = entry.forward(dict(x))
    for a, b in zip(before, after):
        assert np.array_equal(a, b), "a rejected epoch changed serving"
    # an unchanged bad publish is one rejection, not one per poll
    out = watcher.check_once()
    assert out["action"] == "rejected" and out.get("already_counted")
    assert watcher.counters["rejected"] == 1

    save(3)
    out = watcher.check_once()
    assert out["ok"] and out["action"] == "promoted" and \
        out["epoch"] == 3, out


def test_hot_swap_truncate_fault_rejected(tmp_path, clean_faults):
    """The truncate_checkpoint flavor: a half-length params file under
    an intact manifest entry is a size+digest mismatch, same verdict."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    clean_faults.arm("truncate_checkpoint")
    save(2)
    out = watcher.check_once()
    assert not out["ok"] and out["action"] == "rejected"
    assert entry.loaded_epoch == 1


def test_hot_swap_validation_rejects_nan_and_wrong_graph(tmp_path):
    """Digest-clean but BROKEN epochs die in staged validation, off the
    serving path: NaN weights (non-finite validation forward) and a
    different graph (param-set digest mismatch) both leave serving
    untouched."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    # NaN weights: digests verify (the manifest recorded the NaN bytes)
    args, auxs = init_params(sym, (1, 32), seed=9)
    name = next(iter(args))
    args[name] = mx.nd.array(np.full(args[name].shape, np.nan, "f"))
    save(2, args=args, auxs=auxs)
    out = watcher.check_once()
    assert not out["ok"] and out["action"] == "validation_failed", out
    assert watcher.counters["validation_failures"] == 1
    assert entry.loaded_epoch == 1
    # a failed publish is HELD, not re-staged every poll
    out = watcher.check_once()
    assert out["action"] == "held" and \
        watcher.counters["validation_failures"] == 1

    # different graph: the param set no longer matches the program
    other = mlp_sym(nh=48)
    o_args, o_auxs = init_params(other, (1, 32), seed=10)
    from mxnet_tpu.resilience import CheckpointManager
    man2 = CheckpointManager(man.directory)
    man2.save(3, symbol=other, arg_params=o_args, aux_params={},
              blocking=True)
    out = watcher.check_once()
    assert not out["ok"] and out["action"] == "validation_failed", out
    assert entry.loaded_epoch == 1


def test_hot_swap_probe_failure_rolls_back_bitwise(tmp_path,
                                                   clean_faults):
    """A post-swap probe failure (swap_probe fault point) restores the
    PREVIOUS weights before any request can see the new ones — and the
    restore is bitwise, not approximate."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    x = {"data": np.random.RandomState(7).rand(2, 32).astype("f")}
    before = entry.forward(dict(x))
    clean_faults.arm("swap_probe")
    save(2)
    out = watcher.check_once()
    assert not out["ok"] and out["action"] == "rolled_back", out
    assert watcher.counters["rolled_back"] == 1
    assert entry.loaded_epoch == 1
    after = entry.forward(dict(x))
    for a, b in zip(before, after):
        assert np.array_equal(a, b), "rollback is not bitwise"
    # the failed publish is HELD by the poll loop...
    out = watcher.check_once()
    assert out["action"] == "held", out
    # ...but an explicit retry (what POST /swap sends: force=True, no
    # epoch needed) re-attempts it — and the fault is spent, so it
    # promotes
    out = watcher.check_once(force=True)
    assert out["ok"] and out["action"] == "promoted", out


def test_hot_swap_at_dispatch_boundary_under_traffic(tmp_path):
    """run_exclusive IS the dispatch boundary: a batch in flight when
    the swap lands finishes on the OLD weights, the next batch runs on
    the NEW ones, and no queued request is dropped or errored."""
    import threading

    sym = mlp_sym()
    args1, auxs1 = init_params(sym, (1, 32), seed=1)
    args2, auxs2 = init_params(sym, (1, 32), seed=2)
    pool = ModelPool()
    entry = pool.add("m", sym, args1, auxs1,
                     sample_shapes={"data": (32,)})
    entered = threading.Event()
    release = threading.Event()

    def runner(inputs, n):
        entered.set()
        assert release.wait(30)
        entered.clear()
        release.clear()
        return entry.forward(inputs, n)

    b = BucketBatcher(runner, buckets=(1, 2), max_wait_ms=0, name="m")
    try:
        x = np.random.RandomState(0).rand(32).astype("f")
        ref1 = ref_predictor(sym, args1, auxs1, (1, 32)).forward(
            data=x[None]).get_output(0)[0]
        ref2 = ref_predictor(sym, args2, auxs2, (1, 32)).forward(
            data=x[None]).get_output(0)[0]

        fut1 = b.submit({"data": x})
        assert entered.wait(10)          # batch 1 is IN FLIGHT
        swapped = threading.Event()

        def do_swap():
            b.run_exclusive(lambda: entry.swap_params(args2, auxs2))
            swapped.set()

        t = threading.Thread(target=do_swap)
        t.start()
        fut2 = b.submit({"data": x})     # queued behind the swap
        time.sleep(0.2)
        assert not swapped.is_set(), "swap jumped the in-flight batch"
        release.set()                    # let batch 1 finish
        t.join(timeout=30)
        assert swapped.is_set()
        out1 = fut1.result(timeout=30)[0]
        assert entered.wait(10)
        release.set()
        out2 = fut2.result(timeout=30)[0]
        assert np.array_equal(out1, ref1), \
            "in-flight batch did not finish on the old weights"
        assert np.array_equal(out2, ref2), \
            "post-swap batch did not run on the new weights"
    finally:
        release.set()
        b.close(drain=False, timeout=5)


def test_hot_swap_int8_and_bf16_pools(tmp_path):
    """The swap composes with the cast/quantized serving paths: the
    new epoch's weights go through the SAME cast the load path applies,
    and the swapped pool equals a fresh pool loaded from the new
    checkpoint — bitwise, per dtype path."""
    for dtype in ("bfloat16", "int8"):
        man, sym, save = _ckpt_stream(tmp_path / dtype)
        pool = ModelPool(dtype=dtype)
        entry = pool.load_dir("m", man.directory,
                              sample_shapes={"data": (32,)})
        x = {"data": np.random.RandomState(11).rand(2, 32).astype("f")}
        entry.forward(dict(x))           # compile the serving path
        save(2)
        from mxnet_tpu.serving.deploy import CheckpointWatcher
        out = CheckpointWatcher(pool, "m").check_once()
        assert out["ok"], (dtype, out)
        swapped = entry.forward(dict(x))
        fresh = ModelPool(dtype=dtype).load_dir(
            "m", man.directory, sample_shapes={"data": (32,)})
        fresh_out = fresh.forward(dict(x))
        for a, c in zip(swapped, fresh_out):
            assert np.array_equal(a, c), dtype


def test_hot_swap_frontend_endpoint_and_epoch_reporting(tmp_path):
    """The /swap admin surface + epoch observability, in process: 404
    unknown model, 409 for a non-directory model, 200 current/promoted,
    409 rejected; /stats carries epochs + the deploy block."""
    man, sym, save = _ckpt_stream(tmp_path)
    pool = ModelPool()
    pool.load_dir("m", man.directory, sample_shapes={"data": (32,)})
    args, auxs = init_params(sym, (1, 32), seed=50)
    pool.add("inmem", sym, args, auxs, sample_shapes={"data": (32,)})
    fe = ServingFrontend(pool, buckets=(1, 2))

    status, _ = fe.handle_swap("nope")
    assert status == 404
    status, out = fe.handle_swap("inmem")
    assert status == 409, out            # no checkpoint dir to watch
    status, out = fe.handle_swap("m")
    assert status == 200 and out["action"] == "current"
    save(2)
    status, out = fe.handle_swap("m")
    assert status == 200 and out["action"] == "promoted", out
    payload = fe.stats_payload()
    assert payload["epochs"]["m"] == 2
    assert payload["deploy"]["m"]["promoted"] == 1
    from mxnet_tpu.resilience import faults
    try:
        faults.arm("rot_checkpoint")
        save(3)
        status, out = fe.handle_swap("m")
        assert status == 409 and out["action"] == "rejected"
        assert fe.stats_payload()["epochs"]["m"] == 2
    finally:
        faults.disarm()


def test_hot_swap_watcher_thread_promotes_and_backs_off(tmp_path):
    """The poll thread: a new epoch published while the watcher tails
    the directory is promoted without any explicit call; stop() ends
    the tail."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    watcher.poll_s = 0.05
    watcher.start()
    try:
        assert watcher.watching()
        save(2)
        deadline = time.monotonic() + 20
        while entry.loaded_epoch != 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert entry.loaded_epoch == 2, watcher.stats()
    finally:
        watcher.stop()
    assert not watcher.watching()


def test_swap_params_refuses_program_change():
    """swap_params is weights-only by contract: a parameter set with
    different shapes raises and leaves serving untouched."""
    pool, sym, args, auxs = make_pool()
    entry = pool.get("m")
    x = {"data": np.random.RandomState(1).rand(1, 32).astype("f")}
    before = entry.forward(dict(x))
    other = mlp_sym(nh=48)
    o_args, o_auxs = init_params(other, (1, 32), seed=3)
    with pytest.raises(MXNetError):
        entry.swap_params(o_args, o_auxs)
    after = entry.forward(dict(x))
    for a, b in zip(before, after):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# weighted-fair tenant queueing (serving/batcher.py WFQ)
# ---------------------------------------------------------------------------

def test_parse_tenant_weights_spec_and_validation():
    assert parse_tenant_weights("gold:4,free:1") == {"gold": 4.0,
                                                     "free": 1.0}
    assert parse_tenant_weights({"a": 2}) == {"a": 2.0}
    assert parse_tenant_weights("") == {}
    with pytest.raises(MXNetError):
        parse_tenant_weights("gold:0")          # ban via quota, not weight
    with pytest.raises(MXNetError):
        parse_tenant_weights("gold")


def test_wfq_flood_tenant_cannot_starve_an_equal():
    """THE fairness bound: while one tenant floods, an equal-weight
    tenant's requests are served at least every other dispatch slot —
    its whole backlog clears within 2*k slots, never behind the flood."""
    order = []
    b, gate, first = _tagged_batcher(order)
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)           # queue builds behind this one
        for i in range(12):             # the flood: tags 100..111
            futs.append(b.submit({"data": np.full((2,), 100.0 + i, "f")},
                                 tenant="flood"))
        for i in range(3):              # the victim: tags 1, 2, 3
            futs.append(b.submit({"data": np.full((2,), 1.0 + i, "f")},
                                 tenant="quiet"))
        gate.set()
        for f in futs:
            f.result(timeout=10)
        served = order[1:]              # drop the gate-holder
        quiet_pos = [i for i, tag in enumerate(served) if tag < 100.0]
        # every quiet request inside the first 2*k slots (k=3), and
        # FIFO within the tenant
        assert quiet_pos, served
        assert max(quiet_pos) <= 6, (quiet_pos, served)
        assert [served[i] for i in quiet_pos] == [1.0, 2.0, 3.0]
        # the flood still gets everything it queued, in its own order
        assert [t for t in served if t >= 100.0] == \
            [100.0 + i for i in range(12)]
    finally:
        b.close()


def test_wfq_weights_bias_service_proportionally():
    """gold:3 vs free:1 — over the first 8 slots gold takes ~3/4."""
    order = []
    b, gate, first = _tagged_batcher(
        order, tenant_weights="gold:3,free:1")
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)
        for i in range(8):
            futs.append(b.submit({"data": np.full((2,), 100.0 + i, "f")},
                                 tenant="gold"))
            futs.append(b.submit({"data": np.full((2,), 200.0 + i, "f")},
                                 tenant="free"))
        gate.set()
        for f in futs:
            f.result(timeout=10)
        first8 = order[1:9]
        gold = sum(1 for t in first8 if 100.0 <= t < 200.0)
        assert gold >= 5, (gold, order)
    finally:
        b.close()


def test_tenant_quota_sheds_only_the_flooder():
    order = []
    b, gate, first = _tagged_batcher(order, tenant_quota=3)
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)
        for i in range(3):              # exactly at quota: accepted
            futs.append(b.submit({"data": np.full((2,), 100.0 + i, "f")},
                                 tenant="flood"))
        with pytest.raises(TenantQuotaExceeded):
            b.submit({"data": np.full((2,), 199.0, "f")}, tenant="flood")
        # the OTHER tenant is untouched by the flooder's quota
        futs.append(b.submit({"data": np.full((2,), 1.0, "f")},
                             tenant="quiet"))
        gate.set()
        for f in futs:
            f.result(timeout=10)
        assert 199.0 not in order
        assert 1.0 in order
    finally:
        b.close()


def test_wfq_priority_still_wins_within_a_tenant():
    order = []
    b, gate, first = _tagged_batcher(order)
    try:
        futs = [b.submit({"data": np.full((2,), 0.0, "f")})]
        assert first.wait(10)
        futs.append(b.submit({"data": np.full((2,), 1.0, "f")},
                             tenant="t", priority=0))
        futs.append(b.submit({"data": np.full((2,), 2.0, "f")},
                             tenant="t", priority=5))
        gate.set()
        for f in futs:
            f.result(timeout=10)
        assert order[1:] == [2.0, 1.0]
    finally:
        b.close()


def test_frontend_tenant_header_reaches_batcher_and_stats():
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets="1,2", max_wait_ms=1,
                         tenant_quota=64)
    x = np.random.RandomState(0).rand(32).astype("f")
    st, out = fe.handle_predict("m", {"data": x}, tenant="gold")
    assert st == 200, out
    payload = fe.stats_payload()
    # nothing queued anymore -> no tenants table; the latency ledger
    # still attributes the served request to its tenant
    assert payload.get("tenants", {}) == {}
    assert "gold" in payload["tenant_latency_ms"]


# ---------------------------------------------------------------------------
# bucketed sequence serving (serving/sequence.py + /predict_seq)
# ---------------------------------------------------------------------------

def test_parse_seq_buckets_and_pick():
    assert parse_seq_buckets("8,16,32") == (8, 16, 32)
    assert pick_seq_bucket(5, (8, 16)) == 8
    assert pick_seq_bucket(8, (8, 16)) == 8
    assert pick_seq_bucket(9, (8, 16)) == 16
    with pytest.raises(MXNetError):
        pick_seq_bucket(17, (8, 16))            # never truncates
    with pytest.raises(MXNetError):
        pick_seq_bucket(0, (8, 16))
    with pytest.raises(MXNetError):
        parse_seq_buckets("8,-1")


def _lstm_pool(vocab=50, hidden=8, layers=2):
    """A tiny LSTM LM registered WITHOUT its init states in the params
    — the Predictor zero-fills them at the back-inferred (layers, B, H)
    shape per batch bucket, which is the training-side zero state."""
    from mxnet_tpu.models import lstm_lm
    sym, _, _ = lstm_lm.lstm_lm_sym(8, vocab, num_embed=8,
                                    num_hidden=hidden, num_layers=layers)
    ex = sym.simple_bind(mx.cpu(), data=(2, 8), softmax_label=(2, 8))
    skip = ("data", "softmax_label", "lstm_init_h", "lstm_init_c")
    for name in sorted(ex.arg_dict):
        if name in skip:
            continue
        r = np.random.RandomState(abs(hash(name)) % (2 ** 31))
        ex.arg_dict[name][:] = \
            (r.rand(*ex.arg_dict[name].shape).astype("f") - 0.5) * 0.4
    args = {k: v.asnumpy() for k, v in ex.arg_dict.items()
            if k not in skip}
    pool = ModelPool()
    pool.add("lm", sym, args)
    return pool, vocab


def test_predict_seq_bit_stable_across_bucket_boundaries():
    """THE sequence-serving contract: the scan is causal, so the same
    prefix answers BIT-IDENTICALLY whether the request padded into the
    small bucket or rode a longer sequence into the next one — bucket
    boundaries are invisible in the answers."""
    pool, vocab = _lstm_pool()
    fe = ServingFrontend(pool, buckets="1,2,4", max_wait_ms=1,
                         seq_buckets="4,8,16")
    toks = [3, 7, 11, 19, 2]
    st, out = fe.handle_predict_seq("lm", toks)
    assert st == 200, out
    assert out["bucket"] == 8 and out["len"] == 5
    o = np.asarray(out["outputs"][0])
    assert o.shape == (5, vocab)
    # per-step softmax rows: the time-major relay really un-interleaved
    assert np.allclose(o.sum(axis=1), 1.0, atol=1e-5)

    st2, out2 = fe.handle_predict_seq("lm", toks + [23, 29, 31, 5, 13])
    assert st2 == 200 and out2["bucket"] == 16
    o2 = np.asarray(out2["outputs"][0])
    assert o2.shape == (10, vocab)
    assert np.array_equal(o, o2[:5])            # bit-stable prefix

    # same bucket, repeated: bitwise deterministic
    st3, out3 = fe.handle_predict_seq("lm", toks)
    assert np.array_equal(np.asarray(out3["outputs"][0]), o)

    # longer than every bucket: honest 400, never a silent truncation
    st4, out4 = fe.handle_predict_seq("lm", list(range(99)))
    assert st4 == 400 and "exceeds" in out4["error"]


def test_predict_seq_http_roundtrip_and_per_bucket_batchers():
    pool, vocab = _lstm_pool()
    fe = ServingFrontend(pool, buckets="1,2,4", max_wait_ms=1,
                         seq_buckets="4,8")
    fe.serve_in_background()
    try:
        cli = ServeClient("127.0.0.1", fe.port, timeout=30)
        st, out = cli.predict_seq("lm", [1, 2, 3], tenant="gold")
        assert st == 200, out
        assert out["bucket"] == 4 and out["len"] == 3
        assert np.asarray(out["outputs"][0]).shape == (3, vocab)
        st2, out2 = cli.predict_seq("lm", list(range(1, 8)))
        assert st2 == 200 and out2["bucket"] == 8
        # each (model, length) pair batches on its own queue
        payload = fe.stats_payload()
        assert "lm@seq4" in payload["est_wait_ms"]
        assert "lm@seq8" in payload["est_wait_ms"]
        st3, out3 = cli.predict_seq("lm", list(range(99)))
        assert st3 == 400
        st4, _ = cli.predict_seq("nope", [1, 2])
        assert st4 == 404
        cli.close()
    finally:
        fe.drain_and_stop(timeout=10)


def _sharded_publish(man, sym, epoch, args, auxs, world=2):
    """Publish ``epoch`` sharded-native (format 2): fc1_weight split
    along dim 0 across ``world`` blobs, everything else (+ aux) riding
    blob 0 — the serving side must assemble before it can promote."""
    import pickle
    np_args = {k: v.asnumpy() for k, v in args.items()}
    w = np_args.pop("fc1_weight")
    per = w.shape[0] // world

    def payload(k):
        out = {"epoch": int(epoch), "shard": k, "world": world,
               "args": {"fc1_weight": w[k * per:(k + 1) * per]},
               "opt": {}, "dims": {"fc1_weight": 0}}
        if k == 0:
            out["args"].update(np_args)
            out["aux"] = {n: v.asnumpy() for n, v in auxs.items()}
            out["num_update"] = int(epoch)
        return pickle.dumps(out, protocol=4)

    man.save_sharded(epoch, sym, payload, world=world)


def test_watcher_promotes_sharded_publish_bit_exact(tmp_path):
    """A sharded-native publish (ISSUE 18) rides the same watcher
    pipeline: verified (shard-set completeness + per-blob digests)
    before a byte deserializes, assembled from the blobs, and the
    swapped weights are bitwise equal to a fresh load of the epoch."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    assert watcher.check_once()["action"] == "current"
    args2, auxs2 = init_params(sym, (1, 32), seed=202)
    _sharded_publish(man, sym, 2, args2, auxs2)
    out = watcher.check_once()
    assert out["ok"] and out["action"] == "promoted", out
    assert entry.loaded_epoch == 2
    x = {"data": np.random.RandomState(5).rand(4, 32).astype("f")}
    swapped = entry.forward(dict(x))
    fresh = ModelPool().load_dir("m2", man.directory,
                                 sample_shapes={"data": (32,)})
    assert fresh.loaded_epoch == 2
    for a, b in zip(swapped, fresh.forward(dict(x))):
        assert np.array_equal(a, b), "swap != fresh load of the epoch"


def test_watcher_rejects_damaged_shard_exactly_once(tmp_path):
    """One damaged blob of a sharded publish = ONE rejection counted
    (per publish mark, not per poll), the served epoch unchanged — the
    shard-loss matrix's serving-tier row."""
    man, sym, save, pool, entry, watcher = _watched_pool(tmp_path)
    args2, auxs2 = init_params(sym, (1, 32), seed=202)
    _sharded_publish(man, sym, 2, args2, auxs2)
    assert watcher.check_once()["action"] == "promoted"
    args3, auxs3 = init_params(sym, (1, 32), seed=303)
    _sharded_publish(man, sym, 3, args3, auxs3)
    blob = os.path.join(man.directory, man.shard_blob_name(3, 1, 2))
    raw = bytearray(open(blob, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    open(blob, "wb").write(bytes(raw))
    out = watcher.check_once()
    assert not out["ok"] and out["action"] == "rejected"
    assert watcher.counters["rejected"] == 1
    out = watcher.check_once()
    assert out["action"] == "rejected" and out.get("already_counted")
    assert watcher.counters["rejected"] == 1
    assert entry.loaded_epoch == 2


# ---------------------------------------------------------------------------
# exactly-once: the replica-side idempotency cache + client request ids
# ---------------------------------------------------------------------------

def test_dedup_completed_replay_is_bit_identical_without_reexecution():
    """A duplicate of a COMPLETED request replays the cached response
    bytes — bit-identical payload, batcher never re-entered (accepted
    counter unchanged)."""
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0)
    x = np.random.RandomState(0).randn(32).astype("f")
    status1, p1 = fe.handle_predict("m", {"data": x}, request_id="r1")
    assert status1 == 200
    assert fe.stats.snapshot()["counters"]["accepted"] == 1
    status2, p2 = fe.handle_predict("m", {"data": x}, request_id="r1")
    assert status2 == 200
    assert json.dumps(p2).encode() == json.dumps(p1).encode()
    counters = fe.stats.snapshot()["counters"]
    assert counters["accepted"] == 1        # no second execution
    assert counters["dedup_hits"] == 1
    assert fe.stats_payload()["dedup"]["entries"] == 1


def test_dedup_keys_scope_by_tenant_and_request_id():
    """(tenant, request id) is the key: the same id from two tenants is
    two executions; two different ids are two executions."""
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0)
    x = np.zeros((32,), "f")
    fe.handle_predict("m", {"data": x}, request_id="r", tenant="t1")
    fe.handle_predict("m", {"data": x}, request_id="r", tenant="t2")
    fe.handle_predict("m", {"data": x}, request_id="r2", tenant="t1")
    counters = fe.stats.snapshot()["counters"]
    assert counters["accepted"] == 3
    assert counters.get("dedup_hits", 0) == 0


def test_dedup_inflight_duplicate_joins_the_one_execution():
    """A duplicate arriving while the original is still executing
    BLOCKS on the original's completion and shares its answer — one
    execution, two identical responses."""
    release = threading.Event()
    pool, _, _, _ = make_pool()
    entry = pool.get("m")
    real_forward = entry.forward

    def slow_forward(inputs, n=None):
        release.wait(30)
        return real_forward(inputs, n)

    entry.forward = slow_forward
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0)
    x = np.random.RandomState(1).randn(32).astype("f")
    out = [None, None]

    def call(i):
        out[i] = fe.handle_predict("m", {"data": x}, request_id="dup")

    t1 = threading.Thread(target=call, args=(0,))
    t1.start()
    deadline = time.monotonic() + 5
    while not fe.dedup._inflight:       # original claimed its slot
        assert time.monotonic() < deadline
        time.sleep(0.01)
    t2 = threading.Thread(target=call, args=(1,))
    t2.start()
    time.sleep(0.1)
    assert out[1] is None, "duplicate must block, not double-execute"
    release.set()
    t1.join(10)
    t2.join(10)
    assert out[0][0] == 200 and out[1][0] == 200
    assert json.dumps(out[0][1]) == json.dumps(out[1][1])
    counters = fe.stats.snapshot()["counters"]
    assert counters["accepted"] == 1
    assert counters["dedup_joined"] == 1


def test_dedup_ttl_and_size_eviction(monkeypatch):
    """Bounds hold: an entry past MXTPU_SERVE_DEDUP_TTL_S re-executes
    (dedup_evicted_ttl), and the cap evicts oldest-first
    (dedup_evicted_size)."""
    monkeypatch.setenv("MXTPU_SERVE_DEDUP_TTL_S", "0.05")
    monkeypatch.setenv("MXTPU_SERVE_DEDUP_CAP", "2")
    pool, _, _, _ = make_pool()
    fe = ServingFrontend(pool, buckets=(1,), max_wait_ms=0)
    x = np.zeros((32,), "f")
    fe.handle_predict("m", {"data": x}, request_id="r1")
    time.sleep(0.12)
    fe.handle_predict("m", {"data": x}, request_id="r1")
    counters = fe.stats.snapshot()["counters"]
    assert counters["accepted"] == 2            # TTL expired: re-ran
    assert counters["dedup_evicted_ttl"] >= 1
    # cap=2: r2, r3 push the refreshed r1 out oldest-first
    fe.handle_predict("m", {"data": x}, request_id="r2")
    fe.handle_predict("m", {"data": x}, request_id="r3")
    counters = fe.stats.snapshot()["counters"]
    assert counters["dedup_evicted_size"] >= 1
    assert fe.stats_payload()["dedup"]["entries"] <= 2


class _HeaderEcho(object):
    """Tiny HTTP server echoing the request-id header + client port —
    enough to observe what ServeClient actually puts on the wire."""

    def __init__(self):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        echo = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                echo.seen.append(
                    (self.headers.get("X-MXTPU-Request-Id"),
                     self.client_address[1]))
                body = json.dumps({"ok": True}).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.seen = []
        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        threading.Thread(target=self.server.serve_forever,
                         daemon=True).start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def test_serve_client_stamps_request_ids_and_retires_idle_conn():
    """Every ServeClient.predict carries an auto-generated
    X-MXTPU-Request-Id (distinct per call, caller-overridable), and an
    idle keep-alive connection is proactively retired after
    CONN_IDLE_S — the next request opens a FRESH socket instead of
    racing the server's idle close (PR 11's router-side bug class)."""
    echo = _HeaderEcho()
    try:
        client = ServeClient("127.0.0.1", echo.port)
        client.CONN_IDLE_S = 0.1        # instance override for the test
        client.predict("m", np.zeros((4,), "f"))
        client.predict("m", np.zeros((4,), "f"))
        client.predict("m", np.zeros((4,), "f"),
                       request_id="caller-chosen")
        assert len(echo.seen) == 3
        ids = [rid for rid, _ in echo.seen]
        assert all(ids) and ids[0] != ids[1]
        assert ids[2] == "caller-chosen"
        # back-to-back requests reuse the keep-alive socket
        assert echo.seen[0][1] == echo.seen[1][1] == echo.seen[2][1]
        time.sleep(0.25)                # > CONN_IDLE_S: retire it
        client.predict("m", np.zeros((4,), "f"))
        assert echo.seen[3][1] != echo.seen[0][1], \
            "post-idle request must ride a fresh connection"
        client.close()
    finally:
        echo.close()
