"""Pipeline ('pp') and expert ('ep') parallelism tests on the virtual
8-device mesh: sharded execution must match the plain sequential / dense
per-token reference computation."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.parallel import (build_mesh, moe_ffn, moe_init,
                                moe_shardings, pipeline_apply,
                                stack_stage_params)


def _devices(n):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip("needs %d devices" % n)
    return devs[:n]


def test_pipeline_matches_sequential():
    S = 4
    devs = _devices(S)
    mesh = build_mesh({"pp": S}, devs)
    d = 16
    rs = np.random.RandomState(0)
    per_stage = [{"w": jnp.asarray(rs.randn(d, d).astype("f") * 0.3),
                  "b": jnp.asarray(rs.randn(d).astype("f") * 0.1)}
                 for _ in range(S)]
    stacked = stack_stage_params(per_stage)

    def stage(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    x = jnp.asarray(rs.randn(8, d).astype("f"))
    out = pipeline_apply(stage, stacked, x, mesh, n_microbatch=4)

    ref = x
    for p in per_stage:
        ref = stage(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_microbatch_counts():
    S = 2
    devs = _devices(S)
    mesh = build_mesh({"pp": S}, devs)
    d = 8
    rs = np.random.RandomState(1)
    per_stage = [{"w": jnp.asarray(rs.randn(d, d).astype("f") * 0.3)}
                 for _ in range(S)]
    stacked = stack_stage_params(per_stage)

    def stage(params, x):
        return x @ params["w"]

    x = jnp.asarray(rs.randn(12, d).astype("f"))
    for M in (2, 3, 6):
        out = pipeline_apply(stage, stacked, x, mesh, n_microbatch=M)
        ref = x @ per_stage[0]["w"] @ per_stage[1]["w"]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def _dense_moe_reference(params, x):
    """Per-token top-2 expert mix (no capacity drops)."""
    B, S, d = x.shape
    tokens = np.asarray(x).reshape(-1, d)
    gate = np.asarray(params["gate"])
    w1, b1 = np.asarray(params["w1"]), np.asarray(params["b1"])
    w2, b2 = np.asarray(params["w2"]), np.asarray(params["b2"])
    logits = tokens @ gate
    e_x = np.exp(logits - logits.max(axis=1, keepdims=True))
    gates = e_x / e_x.sum(axis=1, keepdims=True)
    out = np.zeros_like(tokens)
    for t in range(tokens.shape[0]):
        order = np.argsort(-gates[t])
        e1, e2 = order[0], order[1]
        g1, g2 = gates[t][e1], gates[t][e2]
        norm = g1 + g2
        for e, g in ((e1, g1 / norm), (e2, g2 / norm)):
            h = np.maximum(tokens[t] @ w1[e] + b1[e], 0)
            out[t] += g * (h @ w2[e] + b2[e])
    return out.reshape(B, S, d)


def test_moe_matches_dense_reference():
    E = 4
    params = moe_init(jax.random.PRNGKey(0), d_model=8, d_hidden=16,
                      num_experts=E)
    x = jnp.asarray(np.random.RandomState(2).randn(2, 6, 8).astype("f"))
    # generous capacity: nothing drops, exact match with the dense mix
    out = moe_ffn(params, x, capacity_factor=E)
    ref = _dense_moe_reference(params, x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_moe_sharded_over_ep():
    E = 8
    devs = _devices(8)
    mesh = build_mesh({"ep": 8}, devs)
    from jax.sharding import NamedSharding, PartitionSpec as P
    params = moe_init(jax.random.PRNGKey(1), d_model=8, d_hidden=16,
                      num_experts=E)
    specs = moe_shardings("ep")
    placed = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in params.items()}
    x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 8).astype("f"))

    fitted = jax.jit(lambda p, x: moe_ffn(p, x, capacity_factor=E))
    out = fitted(placed, x)
    ref = _dense_moe_reference(params, x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-5)


def test_moe_capacity_drops_tokens():
    """With tight capacity some tokens lose an expert — output is the
    partial mix, never NaN (the GShard drop contract)."""
    E = 2
    params = moe_init(jax.random.PRNGKey(2), d_model=4, d_hidden=8,
                      num_experts=E)
    x = jnp.asarray(np.random.RandomState(4).randn(1, 16, 4).astype("f"))
    out = moe_ffn(params, x, capacity_factor=0.25)
    assert np.isfinite(np.asarray(out)).all()
    dense = _dense_moe_reference(params, x)
    assert not np.allclose(np.asarray(out), dense)


def test_pipeline_rejects_stage_count_mismatch():
    devs = _devices(2)
    mesh = build_mesh({"pp": 2}, devs)
    d = 4
    per_stage = [{"w": jnp.eye(d)} for _ in range(4)]  # 4 stages, 2 devices
    with pytest.raises(ValueError, match="4 stages.*2 devices"):
        pipeline_apply(lambda p, x: x @ p["w"],
                       stack_stage_params(per_stage),
                       jnp.ones((4, d)), mesh, n_microbatch=2)
