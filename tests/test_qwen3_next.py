"""The ops, the cut and the data path of the hybrid language-model family
(``models/qwen3_next.py``), at tiny sizes on the CPU: each new op against a
recurrence, a dense loop or explicit scores, the whole model against the
plain reference (``benchmark/reference/qwen3_next.py``) through
``SPMDModule.fit``, the share of the experts tied to the whole layer, and
an id a bfloat16 cannot hold carried to ``Embedding`` and ``SoftmaxOutput``
unchanged in a bfloat16 job."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.test_utils import (check_numeric_gradient,
                                  check_symbolic_forward)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RS = np.random.RandomState

TOY = dict(hidden_size=32, num_hidden_layers=4, full_attention_interval=4,
           num_attention_heads=4, num_key_value_heads=2, head_dim=16,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=8,
           linear_conv_kernel_dim=4, num_experts_per_tok=4,
           moe_intermediate_size=16, shared_expert_intermediate_size=16,
           norm_topk_prob=True, partial_rotary_factor=0.25,
           rope_theta=1e7, rms_norm_eps=1e-6, vocab_size=300)


def _n(shape, seed, scale=1.0):
    return (RS(seed).randn(*shape) * scale).astype("f")


# -- RMSNorm ---------------------------------------------------------------

@pytest.mark.parametrize("zero_centered", [True, False])
def test_rmsnorm_forward_and_numeric_gradient(zero_centered):
    x, w = _n((3, 5, 8), 0), _n((8,), 1, 0.3)
    s = mx.sym.RMSNorm(mx.sym.Variable("x"), gamma=mx.sym.Variable("w"),
                       eps=1e-6, zero_centered=zero_centered)
    y = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    y = y * (1 + w if zero_centered else w)
    check_symbolic_forward(s, {"x": x, "w": w}, [y], rtol=1e-5)
    check_numeric_gradient(s, {"x": x, "w": w}, rtol=2e-2, atol=2e-3)


def test_rmsnorm_gated_multiplies_by_silu_of_the_gate_in_float32():
    x, w, z = _n((4, 6), 2), _n((6,), 3), _n((4, 6), 4)
    s = mx.sym.RMSNorm(mx.sym.Variable("x"), gamma=mx.sym.Variable("w"),
                       gate=mx.sym.Variable("z"), gated=True)
    y = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * w \
        * (z / (1 + np.exp(-z)))
    check_symbolic_forward(s, {"x": x, "w": w, "z": z}, [y], rtol=1e-5)
    out = mx.nd.RMSNorm(mx.nd.array(x).astype("bfloat16"),
                        mx.nd.array(w).astype("bfloat16"))
    assert out.dtype == np.dtype("bfloat16")


def test_silu_and_swiglu():
    x = _n((5, 8), 5)
    silu = x / (1 + np.exp(-x))
    check_symbolic_forward(mx.sym.Activation(mx.sym.Variable("x"),
                                             act_type="silu"),
                           {"x": x}, [silu], rtol=1e-5)
    s = mx.sym.SwiGLU(mx.sym.Variable("x"))
    check_symbolic_forward(s, {"x": x}, [silu[:, :4] * x[:, 4:]], rtol=1e-5)
    check_numeric_gradient(s, {"x": x}, rtol=2e-2, atol=2e-3)


# -- partial rotary embedding -----------------------------------------------

def test_partial_rotary_turns_pairs_and_passes_the_rest():
    x = _n((2, 7, 3, 16), 6)
    s = mx.sym.RotaryEmbedding(mx.sym.Variable("x"), rotary_dim=8,
                               base=100.0)
    out = check_symbolic_forward(s, {"x": x}, [], rtol=1e-5)[0]
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(out[:, 0], x[:, 0], rtol=1e-6)   # position 0
    # pair (i, i + 4) is turned by p * base^(-2i/8): a complex product
    for i in range(4):
        z = (x[..., i] + 1j * x[..., i + 4]) * np.exp(
            1j * np.arange(7)[None, :, None] * 100.0 ** (-2.0 * i / 8))
        np.testing.assert_allclose(out[..., i], z.real, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(out[..., i + 4], z.imag, rtol=1e-4,
                                   atol=1e-5)
    check_numeric_gradient(s, {"x": x[:1, :3, :1]}, rtol=2e-2, atol=2e-3)


# -- causal short convolution -------------------------------------------------

def test_causal_conv_is_a_loop_over_taps_and_sees_no_future():
    x, w = _n((2, 9, 5), 7), _n((5, 4), 8)
    s = mx.sym.CausalConv1D(mx.sym.Variable("x"),
                            weight=mx.sym.Variable("w"), kernel=4)
    y = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                y[:, t] += w[:, j] * x[:, t - 3 + j]
    check_symbolic_forward(s, {"x": x, "w": w}, [y], rtol=1e-5)
    later = x.copy()
    later[:, 5:] += 1.0
    out = check_symbolic_forward(s, {"x": later, "w": w}, [], rtol=1e-5)[0]
    np.testing.assert_allclose(out[:, :5], y[:, :5], rtol=1e-5)
    act = mx.sym.CausalConv1D(mx.sym.Variable("x"),
                              weight=mx.sym.Variable("w"), kernel=4,
                              act_type="silu")
    check_symbolic_forward(act, {"x": x, "w": w}, [y / (1 + np.exp(-y))],
                           rtol=1e-5)
    check_numeric_gradient(act, {"x": x, "w": w}, rtol=2e-2, atol=2e-3)


# -- gated grouped-query attention ------------------------------------------

def _explicit_attention(q, k, v, gate=None):
    g = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(q.shape[-1])
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv)
    return out if gate is None else out * jax.nn.sigmoid(gate)


@pytest.mark.parametrize("t,block", [(37, 16), (64, 16), (16, 512)])
def test_gq_attention_matches_explicit_scores(t, block):
    from mxnet_tpu.ops.contrib import gq_attention
    q, k, v, gate = (jnp.asarray(_n((2, t, h, 8), i))
                     for i, h in enumerate((4, 2, 2, 4)))
    out = gq_attention(q, k, v, gate, block_q=block, gated=True)
    np.testing.assert_allclose(out, _explicit_attention(q, k, v, gate),
                               rtol=1e-4, atol=1e-5)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3))(q, k, v, gate)
    for a, b in zip(grads(lambda *a: gq_attention(*a, block_q=block,
                                                  gated=True)),
                    grads(_explicit_attention)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_gq_attention_bf16_gradient_is_finite_and_close():
    from mxnet_tpu.kernels.flash_attention import gqa_attention
    q, k, v = (jnp.asarray(_n((1, 48, h, 16), 10 + i))
               for i, h in enumerate((4, 2, 2)))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss(fn, *a):
        return jnp.sum(fn(*a).astype(jnp.float32) ** 2)
    gb = jax.grad(lambda *a: loss(lambda *b: gqa_attention(*b, block_q=16),
                                  *a), argnums=(0, 1, 2))(qb, kb, vb)
    gf = jax.grad(lambda *a: loss(_explicit_attention, *a),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gb, gf):
        a = np.asarray(a.astype(jnp.float32))
        assert np.isfinite(a).all()
        assert np.linalg.norm(a - b) < 0.05 * np.linalg.norm(b)


def test_gq_attention_symbol_infers_and_runs():
    s = mx.sym.GQAttention(query=mx.sym.Variable("q"),
                           key=mx.sym.Variable("k"),
                           value=mx.sym.Variable("v"))
    _, out, _ = s.infer_shape(q=(1, 6, 4, 8), k=(1, 6, 2, 8), v=(1, 6, 2, 8))
    assert out == [(1, 6, 4, 8)]
    q, k, v = _n((1, 6, 4, 8), 0), _n((1, 6, 2, 8), 1), _n((1, 6, 2, 8), 2)
    check_symbolic_forward(s, {"q": q, "k": k, "v": v},
                           [np.asarray(_explicit_attention(q, k, v))],
                           rtol=1e-4)


# -- the gated delta rule ------------------------------------------------------

def _delta_recurrence(q, k, v, g, beta):
    """Position by position: q, k (B, T, H, dk), v (B, T, H, dv)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)) * b_t[..., None]
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
    b, _, h, dk = q.shape
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _rule_inputs(t, seed=20, dk=8, dv=6, hk=2, a_shift=0.0):
    q, k = _n((2, t, hk, dk), seed), _n((2, t, hk, dk), seed + 1)
    v = _n((2, t, 2 * hk, dv), seed + 2)
    a = _n((2, t, 2 * hk), seed + 3) + a_shift
    b = _n((2, t, 2 * hk), seed + 4)
    a_log = np.log(RS(seed + 5).uniform(0.1, 4, 2 * hk)).astype("f")
    return tuple(jnp.asarray(x) for x in
                 (q, k, v, a, b, a_log, np.ones(2 * hk, "f")))


def _rule_oracle(q, k, v, a, b, a_log, dt_bias):
    def unit(x):
        x = x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
        return jnp.repeat(x, 2, axis=2)
    g = -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias)
    return _delta_recurrence(unit(q) * q.shape[-1] ** -0.5, unit(k), v, g,
                             jax.nn.sigmoid(b))


@pytest.mark.parametrize("t,chunk", [(150, 64), (64, 64), (40, 16), (5, 64)])
def test_chunked_delta_rule_matches_the_recurrence(t, chunk):
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(t)
    out = gated_delta_rule_op(*args, chunk=chunk)
    ref = _rule_oracle(*args)
    assert out.shape == ref.shape == (2, t, 4, 6)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-6)


def test_chunked_delta_rule_gradient_matches_the_recurrences():
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(150, seed=30)       # 150 = 2 x 64 + 22

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3 * fn(*a))),
                        argnums=tuple(range(7)))(*args)
    for a, b in zip(grads(lambda *a: gated_delta_rule_op(*a, chunk=64)),
                    grads(_rule_oracle)):
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


def test_delta_rule_symbol_numeric_gradient():
    names = ("query", "key", "value", "a", "b", "A_log", "dt_bias")
    s = mx.sym.GatedDeltaRule(chunk=4, **{n: mx.sym.Variable(n)
                                          for n in names})
    loc = {"query": _n((1, 6, 1, 3), 0), "key": _n((1, 6, 1, 3), 1),
           "value": _n((1, 6, 2, 2), 2), "a": _n((1, 6, 2), 3),
           "b": _n((1, 6, 2), 4), "A_log": _n((2,), 5, 0.3),
           "dt_bias": np.ones(2, "f")}
    _, out, _ = s.infer_shape(**{k: v.shape for k, v in loc.items()})
    assert out == [(1, 6, 2, 2)]
    check_numeric_gradient(s, loc, rtol=3e-2, atol=3e-3)


@pytest.mark.parametrize("rep", [1, 2])
def test_chunk_backward_is_the_transpose_of_chunk_forward(rep):
    """The kernels' bodies call ``chunk_forward`` and the hand-written
    ``chunk_backward``: the second is jax's transpose of the first."""
    from mxnet_tpu.kernels.delta_rule import chunk_backward, chunk_forward
    c, dk, dv = 8, 16, 12
    r = rep * c
    s0 = [jnp.asarray(_n((dk, dv), 80 + i, 0.3)) for i in range(rep)]
    q, k, v = (jnp.asarray(_n(shape, 83 + i)) for i, shape in
               enumerate([(c, dk), (c, dk), (r, dv)]))
    run = jnp.concatenate([jnp.cumsum(-jnp.abs(x)) for x in jnp.split(
        jnp.asarray(_n((r,), 86, 0.5)), rep)])
    bcol = jax.nn.sigmoid(jnp.asarray(_n((r, 1), 87)))
    kw = dict(rep=rep, eps=1e-6, scale=dk ** -0.5)
    primals = (s0, q, k, v, run[:, None], run[None, :], bcol)
    (_, _, inverse), pull = jax.vjp(
        lambda *a: chunk_forward(*a, **kw), *primals)
    do = jnp.asarray(_n((r, dv), 88))
    ds1 = [jnp.asarray(_n((dk, dv), 89 + i)) for i in range(rep)]
    want = pull((do, ds1, jnp.zeros_like(inverse)))
    got = chunk_backward(s0, inverse, *primals[1:], do, ds1, **kw)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-6 * float(jnp.max(jnp.abs(b))))


# the compiled tier, through the op, in the Pallas interpreter

def _routes(since):
    return [r["ids"] for r in profiler.spans(since=since)
            if r["name"] == "kernel.route"
            and r["ids"]["kernel"] == "delta_rule"]


@pytest.mark.parametrize("t,chunk,a_shift", [
    (64, 64, 0.0),                  # a stream of one chunk
    (96, 16, 0.0),                  # six chunks, two grid steps a row
    (48, 16, 200.0),                # decays that underflow to 0
], ids=["one_chunk", "several_chunks", "strong_decay"])
def test_compiled_delta_rule_matches_recurrence_and_lax_tier(
        compiled_tier, t, chunk, a_shift):
    """Two rows (the state is zero at each row's start), two value heads
    to a key head, heads of 128."""
    import time
    from mxnet_tpu.kernels.delta_rule import gated_delta_net_lax
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(t, seed=50, dk=128, dv=128, hk=1, a_shift=a_shift)
    since = time.perf_counter()
    out = gated_delta_rule_op(*args, chunk=chunk)
    assert _routes(since) == [
        {"kernel": "delta_rule", "tier": "pallas", "reason": "aligned"}]
    ref = _rule_oracle(*args)
    assert out.shape == ref.shape == (2, t, 2, 128)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-6)
    q, k, v, a, b, a_log, dt_bias = args
    lax_tier = gated_delta_net_lax(
        q, k, v, -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias),
        jax.nn.sigmoid(b), chunk=chunk)
    np.testing.assert_allclose(out, lax_tier, rtol=1e-4, atol=2e-6)


@pytest.mark.parametrize("t,chunk,a_shift", [
    (64, 64, 0.0), (96, 16, 0.0), (48, 16, 200.0),
], ids=["one_chunk", "several_chunks", "strong_decay"])
def test_compiled_delta_rule_gradient_matches_the_recurrences(
        compiled_tier, t, chunk, a_shift):
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(t, seed=60, dk=128, dv=128, hk=1, a_shift=a_shift)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3 * fn(*a))),
                        argnums=tuple(range(7)))(*args)
    got = grads(lambda *a: gated_delta_rule_op(*a, chunk=chunk))
    for a, b in zip(got, grads(_rule_oracle)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b, rtol=2e-3,
                                   atol=1e-5 * float(jnp.max(jnp.abs(b))) + 1e-7)


def test_compiled_delta_rule_on_bfloat16_operands(compiled_tier):
    """q, k, v arrive in bfloat16 and leave so; inside, the rule is the
    float32 one on those values: forward and the cotangents agree with the
    lax tier's to a bfloat16's last place."""
    from mxnet_tpu.kernels.delta_rule import gated_delta_net_lax
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(96, seed=70, dk=128, dv=128, hk=1)
    args = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]

    def lax_op(q, k, v, a, b, a_log, dt_bias):
        return gated_delta_net_lax(
            q, k, v, -jnp.exp(a_log) * jax.nn.softplus(a + dt_bias),
            jax.nn.sigmoid(b), chunk=16)

    def both(fn):
        out, pull = jax.vjp(fn, *args)
        return (out,) + pull(jnp.ones_like(out))
    got = both(lambda *a: gated_delta_rule_op(*a, chunk=16))
    want = both(lax_op)
    assert got[0].dtype == got[1].dtype == got[3].dtype == jnp.bfloat16
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, "f"), np.asarray(b, "f")
        np.testing.assert_allclose(a, b, rtol=2 ** -7,
                                   atol=2 ** -8 * np.abs(b).max())


def test_delta_rule_takes_the_lax_tier_for_other_shapes_and_meshes():
    """Heads that are not lane multiples, a tail that is no whole chunk,
    and a trace the SPMD partitioner will split: the lax tier, with the
    reason on the ``kernel.route`` record."""
    import time
    from mxnet_tpu.kernels import auto_partitioned
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    before = profiler.counters()
    since = time.perf_counter()
    gated_delta_rule_op(*_rule_inputs(150, dk=6), chunk=64)
    gated_delta_rule_op(*_rule_inputs(150, dk=128, dv=128, hk=1), chunk=64)
    aligned = _rule_inputs(64, dk=128, dv=128, hk=1)
    with auto_partitioned():
        jaxpr = jax.make_jaxpr(
            lambda *a: gated_delta_rule_op(*a, chunk=64))(*aligned)
    assert "pallas_call" not in str(jaxpr)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: gated_delta_rule_op(*a, chunk=64))(*aligned))
    assert _routes(since) == [
        {"kernel": "delta_rule", "tier": "lax", "reason": "shapes"},
        {"kernel": "delta_rule", "tier": "lax", "reason": "shapes"},
        {"kernel": "delta_rule", "tier": "lax", "reason": "mesh"},
        {"kernel": "delta_rule", "tier": "pallas", "reason": "aligned"}]
    after = profiler.counters()
    assert after["kernel.delta_rule.lax"] \
        - before.get("kernel.delta_rule.lax", 0) == 3
    assert after["kernel.delta_rule.pallas"] \
        - before.get("kernel.delta_rule.pallas", 0) == 1


def test_mxlint_finds_the_delta_rule_kernels_behind_their_vjp():
    """``graph-pallas-no-vjp`` on a graph that holds the op with both
    tiers traced (``lax.platform_dependent``): the kernel is there, and
    it is behind its ``custom_vjp``."""
    from mxnet_tpu.analysis import graph_lint
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(64, dk=128, dv=128, hk=1)

    def graph(*a):
        return gated_delta_rule_op(*a, chunk=64)
    report = graph_lint.lint_jit(graph, *args, expect_allgather=False,
                                 min_donate_bytes=0)
    assert "pallas_call" in str(jax.make_jaxpr(graph)(*args))
    assert "graph-pallas-no-vjp" not in {f.rule for f in report.findings}, \
        report.format_text()


# -- the routed-expert layer ---------------------------------------------------

def _dense_experts(x, wr, wgu, wd, k, offset):
    prob = jax.nn.softmax(x @ wr.T, -1)
    w, e = lax.top_k(prob, k)
    w = w / w.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(wgu.shape[0]):
        mine = jnp.sum(jnp.where(e == j + offset, w, 0.0), -1)
        gate, up = jnp.split(x @ wgu[j], 2, -1)
        y = y + mine[:, None] * ((jax.nn.silu(gate) * up) @ wd[j])
    return y, e


def _expert_inputs():
    """40 tokens, 16 experts, top-4, experts 4..7 held.  Token 0's four
    experts are 0..3 (none held); held expert 5 gets no token."""
    x = np.abs(_n((40, 16), 40)) + 0.1
    x[:, 0] = 0.0
    x[0, 0] = 50.0
    wr = _n((16, 16), 41)
    wr[:, 0] = 0.0
    wr[:4, 0] = 1.0
    wr[5] = -1.0
    return tuple(jnp.asarray(a) for a in (
        x, wr, _n((4, 16, 16), 42, 0.3), _n((4, 8, 16), 43, 0.3)))


def test_routed_experts_match_a_dense_loop_with_absent_and_idle_experts():
    from mxnet_tpu.ops.contrib import routed_experts
    x, wr, wgu, wd = _expert_inputs()
    out, stats = routed_experts(x, wr, wgu, wd, top_k=4, expert_offset=4)
    ref, chosen = _dense_experts(x, wr, wgu, wd, 4, 4)
    chosen = np.asarray(chosen)
    assert set(chosen[0]) == {0, 1, 2, 3}            # all absent
    assert not (chosen == 5).any()                   # an idle held expert
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)
    load = [(chosen == e).sum() for e in range(4, 8)]
    # 160 pairs are fewer than a block's 1,024: the full path
    np.testing.assert_allclose(stats, [160, sum(load), max(load),
                                       np.mean(load), 1, 0])

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3))(x, wr, wgu, wd)
    for a, b in zip(
            grads(lambda *a: routed_experts(*a, top_k=4,
                                            expert_offset=4)[0]),
            grads(lambda *a: _dense_experts(*a, 4, 4)[0])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_routed_experts_symbol_has_two_outputs_and_a_combiner():
    r = mx.sym.RoutedExperts(
        data=mx.sym.Variable("x"), top_k=2, expert_offset=1,
        router_weight=mx.sym.Variable("wr", shape=(6, 8)),
        gate_up_weight=mx.sym.Variable("wgu", shape=(2, 8, 8)),
        down_weight=mx.sym.Variable("wd", shape=(2, 4, 8)))
    assert r.list_outputs() == ["routedexperts0_output",
                                "routedexperts0_stats"] or \
        len(r.list_outputs()) == 2
    _, out, _ = r.infer_shape(x=(10, 8))
    assert out == [(10, 8), (6,)]
    both = mx.sym.RoutedExpertsStats(r[1], r[1])
    loc = {"x": _n((10, 8), 0), "wr": _n((6, 8), 1), "wgu": _n((2, 8, 8), 2),
           "wd": _n((2, 4, 8), 3)}
    one = check_symbolic_forward(r[1], loc, [], rtol=1e-6)[0]
    two = check_symbolic_forward(both, loc, [], rtol=1e-6)[0]
    np.testing.assert_allclose(two, [2 * one[0], 2 * one[1], one[2], one[3],
                                     2, 0])
    assert one[0] == 20 and one[4] == 1


# -- the blocked path: the rows of the held experts, a block at a time --------

def _blocked_inputs(case):
    """40 tokens, 16 experts, top-4, experts 4 and 5 held: 160 pairs of
    which about 20 land here.  ``idle``: expert 5 gets no token; ``none``:
    neither held expert gets one."""
    wr = _n((16, 16), 61)
    x = np.abs(_n((40, 16), 60)) + 0.1
    if case in ("idle", "none"):
        wr[5] = -1.0                     # x > 0: the lowest logit of all
    if case == "none":
        wr[4] = -1.0
    return tuple(jnp.asarray(a) for a in (
        x, wr, _n((2, 16, 16), 62, 0.3), _n((2, 8, 16), 63, 0.3)))


def _capacity_of(case, landed):
    """A block's rows for a case, and whether one block holds the step."""
    return {"under": (64, True), "exact": (landed, True),
            "overflow": (landed - 1, False), "blocks": (8, False),
            "idle": (64, True), "none": (8, True)}[case]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ["under", "exact", "overflow", "blocks",
                                  "idle", "none"])
def test_blocked_path_equals_the_full_path_and_the_dense_loop(case, dtype):
    """Output and the four gradients of ``_routed`` with a block the
    step's landed pairs fit, fill exactly, overflow by one (a second block
    of one pair) or fill three times over (``blocks``; no pair landed: no
    block runs): equal to the full path and to the dense loop; the
    gradients under ``jax.jit`` and ``jax.checkpoint``, as a
    ``mirror_stage`` runs the op."""
    from mxnet_tpu.ops.contrib import _routed, routed_experts_stats
    args = tuple(a.astype(dtype) for a in _blocked_inputs(case))
    wide = tuple(a.astype("float32") for a in args)

    def routed(capacity):
        return lambda *a: _routed(*a, 4, 4, True, capacity)
    full, counts = routed(160)(*args)
    landed = int(counts[1])
    capacity, compact = _capacity_of(case, landed)
    chosen = np.asarray(_dense_experts(*wide, 4, 4)[1])
    assert landed == ((chosen == 4) | (chosen == 5)).sum()
    if case == "none":
        assert landed == 0
    elif case == "idle":
        assert landed > 0 and not (chosen == 5).any()
    else:
        assert 16 < landed < 64 and (chosen == 4).any() \
            and (chosen == 5).any()

    out, stats = routed(capacity)(*args)
    assert out.dtype == args[0].dtype
    np.testing.assert_allclose(stats, [160, landed, counts[2], counts[3], 1,
                                       float(compact)])
    np.testing.assert_array_equal(counts[4:], [1, 0])    # the full path
    both = routed_experts_stats(stats, counts, num_args=2)
    np.testing.assert_allclose(both, [320, 2 * landed, counts[2], counts[3],
                                      2, float(compact)])
    exact = dict(rtol=1e-5, atol=1e-6) if dtype == "float32" else \
        dict(rtol=2e-2, atol=2e-3)       # a sum of bf16 rows, reordered
    loose = dict(rtol=1e-4, atol=1e-6) if dtype == "float32" else \
        dict(rtol=5e-2, atol=2e-2)
    f = lambda a: np.asarray(a, np.float32)
    np.testing.assert_allclose(f(out), f(full), **exact)
    np.testing.assert_allclose(f(out), f(_dense_experts(*wide, 4, 4)[0]),
                               **loose)
    if case == "none":
        np.testing.assert_array_equal(f(out), 0.0)

    def grads(fn, at, staged=False):
        fn = jax.checkpoint(fn) if staged else fn
        g = jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a).astype("float32"))),
                     argnums=(0, 1, 2, 3))
        return (jax.jit(g) if staged else g)(*at)
    mine = grads(lambda *a: routed(capacity)(*a)[0], args, staged=True)
    alone = grads(lambda *a: routed(160)(*a)[0], args)
    dense = grads(lambda *a: _dense_experts(*a, 4, 4)[0], wide)
    for name, a, b, c in zip(("data", "router", "gate_up", "down"), mine,
                             alone, dense):
        assert a.dtype == b.dtype and a.shape == c.shape, name
        np.testing.assert_allclose(f(a), f(b), err_msg=name, **exact)
        np.testing.assert_allclose(f(a), f(c), err_msg=name, **loose)
        if case == "none":
            np.testing.assert_array_equal(f(a), 0.0, err_msg=name)


def _routed_grad_jaxpr(tokens, k, held, experts, hidden=32, width=16):
    from mxnet_tpu.ops.contrib import routed_experts
    shapes = ((tokens, hidden), (experts, hidden), (held, hidden, 2 * width),
              (held, width, hidden))

    def loss(*a):
        out = jax.checkpoint(lambda *b: routed_experts(*b, top_k=k)[0])(*a)
        return jnp.sum(out.astype(jnp.float32) ** 2)
    return jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3)))(
        *(jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)).jaxpr


def _eqns(jaxpr):
    from mxnet_tpu.analysis.graph_lint import iter_eqns
    return iter_eqns(jaxpr)


def test_blocked_path_holds_nothing_as_wide_as_all_the_pairs():
    """1,024 tokens, top-10, 4 of 64 experts held: forward and backward
    are one loop each, no conditional, and in the whole op nothing
    floating has tokens * k rows but the flat (tokens, k) router weights,
    nor more elements than a block's rows of the widest activation."""
    from mxnet_tpu.ops.contrib import _capacity
    tokens, k, held, experts, hidden, width = 1024, 10, 4, 64, 32, 16
    pairs = tokens * k
    capacity = _capacity(pairs, held, experts)
    assert capacity == 2048
    eqns = list(_eqns(_routed_grad_jaxpr(tokens, k, held, experts)))
    names = [e.primitive.name for e in eqns]
    assert names.count("while") == 2 and "cond" not in names
    most = capacity * max(hidden, 2 * width)
    seen = 0
    for eqn in eqns:
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = var.aval
            if not jnp.issubdtype(aval.dtype, jnp.floating):
                continue
            seen += 1
            rows = aval.shape[0] if aval.shape else 1
            if rows == pairs or rows == tokens and aval.shape[1:2] == (k,):
                assert aval.size == pairs, (eqn.primitive.name, aval)
            assert aval.size <= most, (eqn.primitive.name, aval)
    assert seen > 100
    # the full path is what carries such rows
    full = _eqns(_routed_grad_jaxpr(tokens, k, 32, experts))
    assert any(getattr(v.aval, "shape", ())[:1] == (pairs,)
               and len(v.aval.shape) == 2 and v.aval.shape[1] >= hidden
               for e in full for v in e.outvars)


@pytest.mark.parametrize("held", [32, 48, 64])
def test_half_or_more_of_the_experts_held_builds_no_loop(held):
    names = {e.primitive.name
             for e in _eqns(_routed_grad_jaxpr(1024, 10, held, 64))}
    assert not names & {"cond", "while"} and "ragged_dot_general" in names


@pytest.mark.parametrize("pairs,held,experts,want", [
    (163840, 32, 512, 20480), (10240, 4, 64, 2048), (10240, 32, 64, 10240),
    (160, 2, 16, 160), (2048, 2, 16, 1024), (4096, 1, 512, 1024),
    (163840, 33, 512, 21504)])
def test_capacity_is_twice_the_uniform_share_in_whole_1024s(pairs, held,
                                                            experts, want):
    from mxnet_tpu.ops.contrib import _capacity
    assert _capacity(pairs, held, experts) == want


def test_the_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """16 experts, top-4, four shares of 4: the routed parts of all shares
    summed, plus the shared expert counted once, are the uncut reference's
    expert layer."""
    from benchmark.reference import qwen3_next as ref
    from mxnet_tpu.ops.contrib import routed_experts
    cfg = dict(TOY, num_experts=16, num_routed_experts=16, expert_offset=0,
               seq_len=24)
    params, _ = ref.init(jax.random.PRNGKey(3), cfg)
    m = "l0_moe_"
    p = {k: v * 5 for k, v in params.items() if k.startswith(m)}
    x = jnp.asarray(_n((24, 32), 50))
    whole = ref.expert_layer(p, m, x, cfg)
    gate, up = jnp.split(x @ p[m + "shared_gate_up_weight"].T, 2, -1)
    shared = jax.nn.sigmoid(x @ p[m + "shared_gate_weight"].T) * (
        (jax.nn.silu(gate) * up) @ p[m + "shared_down_weight"].T)

    def share(s):
        held = slice(4 * s, 4 * s + 4)
        return routed_experts(
            x, p[m + "router_weight"], p[m + "experts_gate_up_weight"][held],
            p[m + "experts_down_weight"][held], top_k=4, expert_offset=4 * s)
    parts = [share(s) for s in range(4)]
    assert sum(float(stats[1]) for _, stats in parts) == 24 * 4
    np.testing.assert_allclose(shared + sum(part for part, _ in parts),
                               whole, rtol=1e-4, atol=1e-6)
    # and the reference cut to one share gives that share's part
    cut = dict(cfg, num_experts=4, expert_offset=8)
    pc = dict(p)
    for name in ("experts_gate_up_weight", "experts_down_weight"):
        pc[m + name] = p[m + name][8:12]
    np.testing.assert_allclose(ref.expert_layer(pc, m, x, cut),
                               shared + parts[2][0], rtol=1e-4, atol=1e-6)


# -- the whole model against the plain reference, through fit ----------------

def _toy_model(seq_len=80, held=4, offset=4, **widths):
    from benchmark.reference import qwen3_next as ref
    from mxnet_tpu.models.qwen3_next import qwen3_next_sym
    toy = dict(TOY, **widths)
    sym = qwen3_next_sym(seq_len, num_experts=16, num_experts_held=held,
                         expert_offset=offset, **toy)[0]
    cfg = dict(toy, num_experts=held, num_routed_experts=16,
               expert_offset=offset, seq_len=seq_len)
    params, _ = ref.init(jax.random.PRNGKey(0), cfg)
    # larger than the family's 0.02 so that every nonlinearity is exercised
    params = {k: (v * 5 if k.endswith("_weight") else v)
              for k, v in params.items()}
    return sym, cfg, params


def test_symbol_has_the_reference_leaves_and_named_stages():
    from benchmark.reference import qwen3_next as ref
    sym, cfg, params = _toy_model()
    args = [a for a in sym.list_arguments()
            if a not in ("data", "softmax_label")]
    assert sorted(args) == sorted(params)
    shapes, out, _ = sym.infer_shape(data=(2, 80), softmax_label=(2, 80))
    assert out == [(160, 300), (6,)]
    want = ref.shapes(cfg)[0]
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in want:
            assert tuple(shape) == tuple(want[name]), name
    stages = {n.attrs.get("mirror_stage") for n in sym._nodes()
              if n.op is not None}
    assert stages == {None, "l0_gdn", "l1_gdn", "l2_gdn", "l3_attn",
                      "l0_moe", "l1_moe", "l2_moe", "l3_moe"}


@pytest.mark.parametrize("seq_len,held", [(80, 4), (256, 2)])
def test_model_matches_the_reference_through_fit(seq_len, held):
    """Loss of each of three steps, the first gradient as the optimizer
    got it and the change after three steps, float32, through
    ``SPMDModule.fit`` from int32 rows.  Rows of 256 tokens with 2 of 16
    experts held run the expert layers' blocked path."""
    from benchmark.reference import common, qwen3_next as ref
    from mxnet_tpu.parallel import SPMDModule, default_mesh
    sym, cfg, params = _toy_model(seq_len=seq_len, held=held)
    opt = {"learning_rate": 0.01, "momentum": 0.9, "wd": 0.0}
    rs = RS(1)
    data = rs.randint(0, 300, (6, seq_len)).astype(np.int32)
    label = rs.randint(0, 300, (6, seq_len)).astype(np.int32)
    mod = SPMDModule(sym, mesh=default_mesh(devices=jax.devices()[:1]))
    seen = {"loss": []}

    def on_step(param):
        trainer = mod._deferred_metric_trainer()
        prob = np.asarray(trainer.outputs[0].asnumpy(), np.float64)
        lab = label[2 * param.nbatch:2 * param.nbatch + 2].T.reshape(-1)
        seen["loss"].append(-np.mean(np.log(prob[np.arange(2 * seq_len), lab])))
        if param.nbatch == 0:
            seen["grad1"] = {k: np.asarray(v[0]) / -0.01
                             for k, v in trainer.opt_state.items()}
    mod.fit(mx.io.NDArrayIter(data, label, batch_size=2), num_epoch=1,
            optimizer="sgd", optimizer_params=dict(opt), initializer=None,
            arg_params={k: mx.nd.NDArray._from_jax(v + 0)
                        for k, v in params.items()},
            batch_end_callback=on_step,
            eval_metric=mx.metric.Perplexity(None))
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    batches = [{"data": data[i:i + 2], "softmax_label": label[i:i + 2]}
               for i in (0, 2, 4)]
    got = common.follow(common.make_step(ref.loss_fn(cfg), opt, 2), params,
                        {}, batches)
    np.testing.assert_allclose(seen["loss"], got["loss"], rtol=1e-5)
    for k, g in got["full"]["grad1"].items():
        g = np.asarray(g)
        assert np.linalg.norm(seen["grad1"][k] - g) <= \
            1e-3 * np.linalg.norm(g) + 1e-7, k
    for k, d in got["full"]["change"].items():
        d = np.asarray(d)
        mine = after[k] - np.asarray(params[k])
        assert np.linalg.norm(mine - d) <= 2e-3 * np.linalg.norm(d) + 1e-7, k


def test_staged_remat_gives_the_same_gradients_and_names_the_stages():
    """The same graph with and without its ``mirror_stage`` attributes:
    equal outputs and gradients; the staged program's op names carry the
    stage, forward and backward."""
    from mxnet_tpu.executor import _build_eval
    sym, cfg, params = _toy_model(seq_len=16)
    plain = mx.sym.load_json(sym.tojson())
    for node in plain._nodes():
        node.attrs.pop("mirror_stage", None)
    rs = RS(2)
    inputs = dict(params, data=rs.randint(0, 300, (2, 16)),
                  softmax_label=rs.randint(0, 300, (2, 16)))

    def grads_of(symbol):
        fn = _build_eval(symbol)

        def loss(p):
            outs, _ = fn(dict(inputs, **p), {}, jax.random.PRNGKey(0), True)
            return tuple(outs)
        return loss, jax.jit(lambda p: jax.vjp(loss, p)[1](
            tuple(jnp.ones_like(o) for o in loss(p)))[0])
    loss_s, staged = grads_of(sym)
    _, unstaged = grads_of(plain)
    a, b = staged(params), unstaged(params)
    for k in params:
        np.testing.assert_allclose(a[k], b[k], rtol=2e-4, atol=1e-7,
                                   err_msg=k)
    text = staged.lower(params).as_text(debug_info=True)
    assert "jvp(l0_gdn)" in text and "transpose(jvp(l3_attn))" in text
    assert "transpose(jvp(l2_moe))" in text


def test_staged_model_through_the_compiled_delta_rule(compiled_tier):
    """DeltaNet heads of 128 and rows of whole chunks: inside each
    rematerialised stage the op takes the compiled tier (the interpreter
    here), forward, again in the stage's backward, and its kernel
    backward; outputs and every gradient equal the lax tier's."""
    import time
    from mxnet_tpu import kernels
    from mxnet_tpu.executor import _build_eval
    sym, cfg, params = _toy_model(
        seq_len=64, linear_num_key_heads=1, linear_num_value_heads=2,
        linear_key_head_dim=128, linear_value_head_dim=128)
    rs = RS(4)
    inputs = dict(params, data=rs.randint(0, 300, (2, 64)),
                  softmax_label=rs.randint(0, 300, (2, 64)))
    fn = _build_eval(sym)

    def loss(p):
        outs, _ = fn(dict(inputs, **p), {}, jax.random.PRNGKey(0), True)
        return tuple(outs)

    def grads(p):
        return jax.vjp(loss, p)[1](tuple(jnp.ones_like(o)
                                         for o in loss(p)))[0]
    since = time.perf_counter()
    compiled = jax.jit(grads)(params)
    assert {r["tier"] for r in _routes(since)} == {"pallas"}
    with kernels.auto_partitioned():         # the lax tier, by its rule
        since = time.perf_counter()
        plain = jax.jit(lambda p: grads(p))(params)
        assert {r["reason"] for r in _routes(since)} == {"mesh"}
    for k in params:
        np.testing.assert_allclose(compiled[k], plain[k], rtol=2e-4,
                                   atol=1e-6, err_msg=k)


# -- counters the graph computes, settled one step late -----------------------

@pytest.mark.parametrize("seq_len,held,compact", [(16, 4, 0), (256, 2, 4)])
def test_step_counters_leave_the_outputs_and_settle_one_step_late(
        seq_len, held, compact):
    """Rows of 16 tokens: 128 pairs a layer, the full path.  Rows of 256
    with 2 of 16 experts held: 2,048 pairs in blocks of 1,024 and ~256
    landed, so each of the four layers' calls is one block."""
    from mxnet_tpu.parallel import SPMDTrainer
    sym, cfg, params = _toy_model(seq_len=seq_len, held=held)
    tr = SPMDTrainer(sym, "sgd", {"learning_rate": 0.01,
                                  "rescale_grad": 0.5})
    tr.bind([("data", (2, seq_len))], [("softmax_label", (2, seq_len))])
    tr.init_params(None, {k: mx.nd.NDArray._from_jax(v + 0)
                          for k, v in params.items()}, {})
    assert tr.out_shapes == [(2 * seq_len, 300)]
    rs = RS(3)
    batch = (rs.randint(0, 300, (2, seq_len)).astype(np.int32),
             rs.randint(0, 300, (2, seq_len)).astype(np.int32))
    before = profiler.counters().get("moe.assignments", 0)
    calls = profiler.counters().get("moe.calls", 0)
    compacts = profiler.counters().get("moe.compact_calls", 0)
    pairs = 2 * seq_len * 4 * 4                  # tokens x top-k x layers
    assert len(tr.step(*batch)) == 1
    assert profiler.counters().get("moe.assignments", 0) == before
    tr.step(*batch)
    assert profiler.counters()["moe.assignments"] == before + pairs
    tr.flush_step_guard()
    c = profiler.counters()
    assert c["moe.assignments"] == before + 2 * pairs
    assert 0 < c["moe.assignments_here"] < c["moe.assignments"]
    assert c["moe.load_max"] >= c["moe.load_mean"] > 0
    records = [r for r in profiler.spans() if r["name"] == "step.counters"]
    assert records[-1]["ids"]["moe.assignments"] == pairs
    assert records[-1]["ids"]["moe.calls"] == 4
    assert records[-1]["ids"]["moe.compact_calls"] == compact
    assert c["moe.calls"] - calls == 8 and \
        c["moe.compact_calls"] - compacts == 2 * compact
    assert len(tr.eval_step(*batch)) == 1
    assert c["step.overlapped"] >= 1
    tr.close()


# -- ids and labels stay integer from the iterator to the step ---------------

def test_ndarray_iter_keeps_wide_integers():
    it = mx.io.NDArrayIter(np.arange(8, dtype=np.int64).reshape(4, 2) + 18990,
                           np.array([18991, 3, 4, 5], np.int32),
                           batch_size=2)
    batch = it.next()
    assert batch.data[0].dtype == np.int32
    assert batch.label[0].dtype == np.int32
    np.testing.assert_array_equal(batch.data[0].asnumpy(),
                                  [[18990, 18991], [18992, 18993]])
    for dtype in (np.uint8, np.float64, np.float32):
        it = mx.io.NDArrayIter(np.zeros((4, 2), dtype), np.zeros(4, dtype),
                               batch_size=2)
        batch = it.next()
        assert batch.data[0].dtype == np.float32 == batch.label[0].dtype


def test_an_id_bf16_cannot_hold_reaches_embedding_and_softmax_in_a_bf16_job():
    """18,991 rounds to 18,944 in bfloat16.  Through NDArrayIter ->
    DevicePrefetchIter -> SPMDTrainer with compute_dtype bfloat16, the id
    and its label arrive as they were: only row 18,991 of the embedding
    gets a gradient, and the head's bias gradient is negative at the
    label, 18,990, alone."""
    from mxnet_tpu.parallel import SPMDTrainer
    assert int(jnp.asarray(18991, jnp.bfloat16)) != 18991
    vocab = 18992
    net = mx.sym.Embedding(mx.sym.Variable("data"), input_dim=vocab,
                           output_dim=8, name="embed")
    net = mx.sym.FullyConnected(net, num_hidden=vocab, name="head")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    it = mx.io.NDArrayIter(np.full((4,), 18991, np.int32),
                           np.full((4,), 18990, np.int32), batch_size=4)
    tr = SPMDTrainer(net, "sgd", {"learning_rate": 0.1, "momentum": 0.9,
                                  "rescale_grad": 0.25},
                     compute_dtype="bfloat16")
    tr.bind(it.provide_data, it.provide_label)
    tr.init_params(mx.initializer.Normal(0.1))
    fed = mx.dataflow.DevicePrefetchIter(it, stage=tr, depth=2)
    batch = fed.next()
    assert {str(v.dtype) for v in batch.staged.values()} == {"int32"}
    np.testing.assert_array_equal(np.asarray(batch.staged["data"]), 18991)
    np.testing.assert_array_equal(np.asarray(batch.staged["softmax_label"]),
                                  18990)
    tr.step(batch)
    rows = np.flatnonzero(np.abs(np.asarray(
        tr.opt_state["embed_weight"][0])).sum(axis=1))
    np.testing.assert_array_equal(rows, [18991])
    bias = np.asarray(tr.opt_state["head_bias"][0])      # -lr * gradient
    np.testing.assert_array_equal(np.flatnonzero(bias > 0), [18990])
    fed.close()
    tr.close()


def test_log_uniform_initializer_and_variable_init_reach_the_trainer():
    from mxnet_tpu.parallel import SPMDTrainer
    sym = _toy_model(seq_len=16)[0]
    tr = SPMDTrainer(sym, "sgd", {"learning_rate": 0.01})
    tr.bind([("data", (2, 16))], [("softmax_label", (2, 16))])
    tr.init_params(mx.initializer.Normal(0.02))
    p = {k: np.asarray(v) for k, v in tr.params.items()}
    assert (p["l0_gdn_norm_gamma"] == 0).all()          # zero-centred
    assert (p["l0_gdn_out_norm_gamma"] == 1).all()
    assert (p["l1_gdn_dt_bias"] == 1).all()
    a = np.exp(p["l2_gdn_A_log"])
    assert (a > 0).all() and (a < 16).all() and a.std() > 0
    assert 0.01 < p["l3_attn_q_proj_weight"].std() < 0.03
    tr.close()
