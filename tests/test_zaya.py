"""The ops and the cut of the ``zaya`` family (``models/zaya.py``), at tiny
sizes on the CPU: the grouped causal convolution against an explicit loop,
the pieces of Compressed Convolutional Attention against the plain
reference (``benchmark/reference/zaya1.py``) step by step, the expert layer
handed the scores a graph computed, the router state handed from layer to
layer, the tied head, the whole model against the reference through
``SPMDModule.fit``, and the 2 shares of the expert layer tied to the whole
layer."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RS = np.random.RandomState

# 8 query heads over 2 key-value heads (G = 4), as the 8B has them
TOY = dict(hidden_size=32, num_hidden_layers=3, num_attention_heads=8,
           num_key_value_heads=2, head_dim=8, cca_time0=2, cca_time1=2,
           partial_rotary_factor=0.5,
           rope_parameters={"hybrid": {"rope_theta": 5000000},
                            "hybrid_sliding": {"rope_theta": 10000}},
           router_hidden_size=12, num_experts_per_tok=1,
           moe_intermediate_size=16, rms_norm_eps=1e-5, vocab_size=300,
           router_balance_rate=0.05)


def _n(shape, seed, scale=1.0):
    return (RS(seed).randn(*shape) * scale).astype("f")


def _cfg(held=8, offset=0, seq_len=48, **more):
    return dict(TOY, num_experts=held, num_routed_experts=8,
                expert_offset=offset, seq_len=seq_len, **more)


def _nd(arrays):
    return {k: mx.nd.array(v) if isinstance(v, np.ndarray)
            else mx.nd.NDArray._from_jax(v) for k, v in arrays.items()}


def _bound(sym, arrays):
    return sym.bind(mx.cpu(), _nd(arrays))


# -- the grouped causal convolution ------------------------------------------

def _conv_loop(x, w, groups):
    """y[b, t, o] = sum_j sum_{i in group(o)} w[o, i, j] x[b, t-(k-1)+j, i],
    entry by entry."""
    b, t, c = x.shape
    n, k = c // groups, w.shape[2]
    y = np.zeros((b, t, c), np.float64)
    for o in range(c):
        first = (o // n) * n
        for j in range(k):
            for s in range(t):
                src = s - (k - 1) + j
                if src >= 0:
                    y[:, s, o] += x[:, src, first:first + n] @ w[o, :, j]
    return y


@pytest.mark.parametrize("t,c,groups,k", [(9, 12, 3, 2), (5, 8, 1, 3),
                                          (7, 6, 6, 2), (1, 12, 3, 2)])
def test_grouped_causal_convolution_is_the_explicit_loop(t, c, groups, k):
    from mxnet_tpu.ops.nn import causal_conv1d
    x, w = _n((2, t, c), 1), _n((c, c // groups, k), 2)
    out = causal_conv1d(jnp.asarray(x), jnp.asarray(w), kernel=k,
                        num_group=groups)
    np.testing.assert_allclose(out, _conv_loop(x, w, groups), rtol=1e-5,
                               atol=1e-6)
    # the gradient: of a linear map, the loop's transpose
    g = _n((2, t, c), 3)
    dx, dw = jax.grad(lambda x, w: jnp.sum(causal_conv1d(
        x, w, kernel=k, num_group=groups) * g), argnums=(0, 1))(
            jnp.asarray(x), jnp.asarray(w))
    eye_x = np.zeros_like(x)
    for idx in [(0, 0, 0), (1, t - 1, c - 1), (0, t // 2, c // 2)]:
        eye_x[:] = 0
        eye_x[idx] = 1
        assert abs(float(dx[idx]) - np.sum(_conv_loop(eye_x, w, groups) * g)) \
            < 1e-5
    eye_w = np.zeros_like(w)
    for idx in [(0, 0, 0), (c - 1, c // groups - 1, k - 1)]:
        eye_w[:] = 0
        eye_w[idx] = 1
        assert abs(float(dw[idx]) - np.sum(_conv_loop(x, eye_w, groups) * g)) \
            < 1e-5
    # with one channel a group it is the depthwise form's arithmetic
    if groups == c:
        np.testing.assert_allclose(
            out, causal_conv1d(jnp.asarray(x), jnp.asarray(w[:, 0]),
                               kernel=k), rtol=1e-5, atol=1e-6)


def test_grouped_convolution_symbol_infers_its_weight_and_depthwise_keeps_its():
    x = mx.sym.Variable("x")
    grouped = mx.sym.CausalConv1D(data=x, kernel=2, num_group=10, name="c")
    assert grouped.infer_shape(x=(2, 16, 1280))[0] == [(2, 16, 1280),
                                                       (1280, 128, 2)]
    depthwise = mx.sym.CausalConv1D(data=x, kernel=4, name="c")
    assert depthwise.infer_shape(x=(2, 16, 1280))[0] == [(2, 16, 1280),
                                                         (1280, 4)]
    from mxnet_tpu.ops.nn import causal_conv1d
    out = causal_conv1d(jnp.ones((1, 4, 6), jnp.bfloat16),
                        jnp.ones((6, 3, 2), jnp.bfloat16), kernel=2,
                        num_group=2, act_type="silu")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, "f")[0, :, 0],
                               [3 / (1 + np.exp(-3.0)),
                                6 / (1 + np.exp(-6.0))] + [6] * 2,
                               rtol=1e-2)


# -- Compressed Convolutional Attention, piece by piece ----------------------

def test_value_shift_takes_the_second_half_from_the_position_before():
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu.models.zaya import _shifted
    x = _n((2, 7, 6), 4)
    ex = _bound(_shifted(mx.sym.Variable("x"), 7), {"x": x})
    got = ex.forward()[0].asnumpy()
    np.testing.assert_array_equal(got[:, 0], 0)
    np.testing.assert_array_equal(got[:, 1:], x[:, :-1])
    v12 = jnp.asarray(_n((7, 8), 5))
    v = np.asarray(ref.values(v12))
    np.testing.assert_array_equal(v[:, :4], v12[:, :4])
    np.testing.assert_array_equal(v[0, 4:], 0)
    np.testing.assert_array_equal(v[1:, 4:], v12[:-1, 4:])


def test_query_key_mean_with_four_query_heads_a_key_head():
    """To query head h: half of itself plus half of its key head; to key
    head j: half of the mean of its four query heads plus half of itself."""
    from benchmark.reference import zaya1 as ref
    cfg = _cfg()
    latent = _n((5, 80), 6)
    mq, mk = ref.qk_mean(jnp.asarray(latent), cfg)
    q = latent[:, :64].reshape(5, 8, 8)
    k = latent[:, 64:].reshape(5, 2, 8)
    for h in range(8):
        np.testing.assert_allclose(np.asarray(mq).reshape(5, 8, 8)[:, h],
                                   (q[:, h] + k[:, h // 4]) / 2, rtol=1e-6)
    for j in range(2):
        np.testing.assert_allclose(
            np.asarray(mk)[:, j, 0],
            (q[:, 4 * j:4 * j + 4].mean(axis=1) + k[:, j]) / 2, rtol=1e-6,
            atol=1e-7)


def test_heads_are_normalised_to_root_d_and_keys_carry_a_temperature():
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu.ops.nn import head_l2_norm
    x, temp = _n((2, 5, 3, 8), 7, 3.0), _n((3,), 8, 0.5)
    out = head_l2_norm(jnp.asarray(x), jnp.asarray(temp), scaled=True)
    want = x / np.linalg.norm(x, axis=-1, keepdims=True) * np.sqrt(8) \
        * np.exp(temp)[:, None]
    np.testing.assert_allclose(out, want, rtol=1e-5)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(head_l2_norm(jnp.asarray(x))), axis=-1),
        np.sqrt(8), rtol=1e-5)
    np.testing.assert_allclose(ref.unit_heads(jnp.asarray(x[0]),
                                              jnp.asarray(temp)), out[0],
                               rtol=1e-6)
    # float32 inside, the data's dtype outside; the temperature is learnt
    low = head_l2_norm(jnp.asarray(x, jnp.bfloat16),
                       jnp.asarray(temp, jnp.bfloat16), scaled=True)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, "f"), want, rtol=2e-2)
    dt = jax.grad(lambda t: jnp.sum(head_l2_norm(
        jnp.asarray(x), t, scaled=True) * x))(jnp.asarray(temp))
    np.testing.assert_allclose(dt, np.sum(want * x, axis=(0, 1, 3)),
                               rtol=1e-4)
    s = mx.sym.HeadL2Norm(mx.sym.Variable("x"), scaled=True,
                          log_scale=mx.sym.Variable("t"))
    assert s.infer_shape(x=x.shape)[0] == [x.shape, (3,)]
    assert mx.sym.HeadL2Norm(mx.sym.Variable("x")).list_arguments() == ["x"]


def test_the_whole_mixer_is_the_plain_form():
    """The CCA stage of the graph against the reference's explicit scores,
    two rows (zeros before each row's start: the value shift and both
    convolutions stop at the row)."""
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu.models.zaya import _cca
    cfg = _cfg(seq_len=48)
    a = "l1_cca_"
    params = {k: jnp.asarray(v * (5 if k.endswith("_weight") else 1))
              for k, v in ref.init(jax.random.PRNGKey(1), cfg)[0].items()
              if k.startswith(a) and "norm" not in k and "_res_" not in k
              and "_out_" not in k}
    params[a + "temp"] = jnp.asarray([0.3, -0.2], jnp.float32)
    x = _n((2 * 48, 32), 9)
    ex = _bound(_cca(mx.sym.Variable("x"), "l1_cca", 48, TOY),
                dict(params, x=x))
    got = ex.forward()[0].asnumpy().reshape(2, 48, 32)
    for row in range(2):
        want = ref.cca(params, a, jnp.asarray(x[48 * row:48 * row + 48]),
                       cfg)
        np.testing.assert_allclose(got[row], want, rtol=1e-4, atol=1e-6)
    assert float(np.abs(got).max()) > 1e-3
    # the reference's attention against explicit scores in one piece
    q, k, v = (jnp.asarray(_n((48, h, 8), 10 + i))
               for i, h in enumerate((8, 2, 2)))
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, 4, 1)) * 8 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((48, 48), bool)), s, -jnp.inf)
    np.testing.assert_allclose(
        ref.attention(q, k, v),
        jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                   jnp.repeat(v, 4, 1)), rtol=1e-5, atol=1e-6)


# -- the expert layer handed its scores --------------------------------------

def _expert_weights(held=4, hidden=16, width=8, experts=8, seed=40):
    return (jnp.asarray(_n((experts, hidden), seed, 0.5)),
            jnp.asarray(_n((held, hidden, 2 * width), seed + 1, 0.3)),
            jnp.asarray(_n((held, width, hidden), seed + 2, 0.3)))


@pytest.mark.parametrize("tokens,held", [(40, 4), (2048, 2)],
                         ids=["full_path", "blocked_path"])
def test_scores_given_is_the_router_weight_form_handed_its_softmax(tokens,
                                                                   held):
    """``softmax(x @ router_weight.T)`` computed outside and handed in gives
    what the op computes inside, output, counts and the gradients of data
    and expert weights, on either path."""
    from mxnet_tpu.ops.contrib import routed_experts
    x = jnp.asarray(_n((tokens, 16), 44))
    wr, wgu, wd = _expert_weights(held)
    kw = dict(top_k=2, expert_offset=2)
    inside, stats = routed_experts(x, wr, wgu, wd, **kw)
    scores = jax.nn.softmax(x @ wr.T, axis=-1)
    outside, stats2 = routed_experts(x, scores, wgu, wd, scores_given=True,
                                     **kw)
    np.testing.assert_allclose(outside, inside, rtol=1e-5, atol=1e-7)
    np.testing.assert_array_equal(stats2, stats)
    assert float(stats[5]) == (0.0 if held == 4 else 1.0)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3))(x, wr, wgu, wd)
    mine = grads(lambda x, wr, wgu, wd: routed_experts(
        x, jax.nn.softmax(x @ wr.T, axis=-1), wgu, wd, scores_given=True,
        **kw)[0])
    for a, b in zip(mine, grads(lambda *a: routed_experts(*a, **kw)[0])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-6)
    # the router's gradient came back through the scores
    assert float(jnp.max(jnp.abs(mine[1]))) > 1e-4


def test_top_1_unrenormalised_weighs_by_the_chosen_probability():
    """``top_k=1, norm_topk_prob=False`` with a bias: the token's expert is
    ``argmax(p + b)``, its weight ``p`` of that expert as it is — with
    renormalisation it would be 1 —, and the scores' gradient is the
    chosen expert's output, nothing elsewhere."""
    from mxnet_tpu.ops.contrib import routed_experts
    x = jnp.asarray(_n((40, 16), 50))
    _, wgu, wd = _expert_weights(held=8)
    scores = jax.nn.softmax(jnp.asarray(_n((40, 8), 51)), axis=-1)
    bias = jnp.zeros(8).at[5].set(0.2).at[1].set(-1.0)
    kw = dict(top_k=1, norm_topk_prob=False, use_select_bias=True,
              scores_given=True)
    out, stats = routed_experts(x, scores, wgu, wd, bias, **kw)
    chosen = np.argmax(np.asarray(scores + bias), axis=-1)
    assert not np.array_equal(chosen, np.argmax(np.asarray(scores), -1))
    assert not (chosen == 1).any()

    def expert(e, rows):
        gate, up = jnp.split(rows @ wgu[e], 2, -1)
        return (jax.nn.silu(gate) * up) @ wd[e]
    plain = np.stack([np.asarray(expert(e, x[i:i + 1])[0])
                      for i, e in enumerate(chosen)])
    p_e = np.asarray(scores)[np.arange(40), chosen]
    np.testing.assert_allclose(out, p_e[:, None] * plain, rtol=1e-4,
                               atol=1e-6)
    assert list(np.asarray(stats[:2])) == [40.0, 40.0]
    one = routed_experts(x, scores, wgu, wd, bias, **dict(
        kw, norm_topk_prob=True))[0]
    np.testing.assert_allclose(one, plain, rtol=1e-4, atol=1e-6)
    ds, db = jax.grad(lambda s, b: jnp.sum(routed_experts(
        x, s, wgu, wd, b, **kw)[0]), argnums=(0, 1))(scores, bias)
    want = np.zeros((40, 8), "f")
    want[np.arange(40), chosen] = plain.sum(axis=1)
    np.testing.assert_allclose(ds, want, rtol=1e-4, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(db), 0.0)


@pytest.mark.parametrize("held,top_k", [(8, 1), (4, 1), (2, 2)],
                         ids=["held_all", "held_half", "blocked_top_2"])
def test_the_load_moves_the_balancing_bias(held, top_k):
    """With ``balance_rate`` the bias's gradient is the rate times (pairs
    that chose the expert - pairs / experts) over ALL the experts, whatever
    the cotangent and whichever experts are held; output, counts and every
    other gradient are the form's without it, and two rows' gradients add
    up to the batch's."""
    from mxnet_tpu.ops.contrib import routed_experts
    tokens = 2048 if held == 2 else 40
    x = jnp.asarray(_n((tokens, 16), 52))
    _, wgu, wd = _expert_weights(held=held)
    scores = jax.nn.softmax(jnp.asarray(_n((tokens, 8), 53)), axis=-1)
    bias = jnp.asarray(_n((8,), 54, 0.1))
    kw = dict(top_k=top_k, norm_topk_prob=False, use_select_bias=True,
              scores_given=True, expert_offset=2)

    def grads(x, scores, rate):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(routed_experts(
            a[0], a[1], a[2], a[3], a[4], balance_rate=rate, **kw)[0])),
            argnums=(0, 1, 2, 3, 4))(x, scores, wgu, wd, bias)
    still, moved = grads(x, scores, 0.0), grads(x, scores, 0.25)
    for a, b in zip(still[:4], moved[:4]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(np.asarray(still[4]), 0.0)
    chosen = np.argsort(-np.asarray(scores + bias), axis=-1)[:, :top_k]
    count = np.bincount(chosen.reshape(-1), minlength=8)
    assert count.max() > count.min()
    np.testing.assert_allclose(
        moved[4], 0.25 * (count - tokens * top_k / 8), rtol=1e-6)
    assert abs(float(jnp.sum(moved[4]))) < 1e-4
    for rate in (0.0, 0.25):
        out, stats = routed_experts(x, scores, wgu, wd, bias,
                                    balance_rate=rate, **kw)
        if rate:
            np.testing.assert_array_equal(out, first[0])
            np.testing.assert_array_equal(stats, first[1])
        first = (out, stats)
    half = tokens // 2
    rows = [grads(x[r * half:(r + 1) * half],
                  scores[r * half:(r + 1) * half], 0.25)[4] for r in (0, 1)]
    np.testing.assert_allclose(rows[0] + rows[1], moved[4], rtol=1e-6,
                               atol=1e-6)
    with pytest.raises(mx.base.MXNetError, match="use_select_bias"):
        routed_experts(x, scores, wgu, wd, balance_rate=0.25, top_k=top_k,
                       scores_given=True)


def test_expert_layer_symbol_takes_scores_in_place_of_the_router_weight():
    def build(**kw):
        return mx.sym.RoutedExperts(
            data=mx.sym.Variable("x"), top_k=1, name="r",
            gate_up_weight=mx.sym.Variable("wgu"),
            down_weight=mx.sym.Variable("wd"), **kw)
    assert build(router_weight=mx.sym.Variable("wr")).list_arguments() == [
        "x", "wr", "wgu", "wd"]
    given = build(scores=mx.sym.Variable("p"), scores_given=True,
                  select_bias=mx.sym.Variable("b"), use_select_bias=True,
                  norm_topk_prob=False)
    assert given.list_arguments() == ["x", "p", "wgu", "wd", "b"]
    shapes = dict(x=(6, 8), p=(6, 16), wgu=(8, 8, 6), wd=(8, 3, 8), b=(16,))
    assert given.infer_shape(**shapes)[1] == [(6, 8), (6,)]


def test_eight_of_sixteen_experts_take_the_full_path_at_the_cells_size():
    """The shapes choose: half of the experts held is no blocked path, and
    the counters say so (``moe.compact_calls`` 0)."""
    from mxnet_tpu.ops.contrib import _capacity
    assert _capacity(16384, 8, 16) == 16384
    assert _capacity(16384, 7, 16) < 16384


# -- the router --------------------------------------------------------------

def _router_weights(hidden=32, r=12, experts=8, seed=60):
    names = ("down_weight", "norm_gamma", "fc1_weight", "fc2_weight",
             "fc3_weight")
    shapes = ((r, hidden), (r,), (r, r), (r, r), (experts, r))
    return {n: jnp.asarray(_n(s, seed + i, 0.5) + (1 if n == "norm_gamma"
                                                   else 0))
            for i, (n, s) in enumerate(zip(names, shapes))}


def _plain_router(x, w, state=None, carry=None, eps=1e-6):
    from scipy.special import erf

    def gelu(y):
        return 0.5 * y * (1 + erf(y / np.sqrt(2)))
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    r = np.asarray(x, np.float64) @ w["down_weight"].T
    if state is not None:
        r = r + np.asarray(carry, np.float64) * np.asarray(state, np.float64)
    y = r / np.sqrt((r * r).mean(-1, keepdims=True) + eps) * w["norm_gamma"]
    s = gelu(gelu(y @ w["fc1_weight"].T) @ w["fc2_weight"].T) \
        @ w["fc3_weight"].T
    e = np.exp(s - s.max(-1, keepdims=True))
    return r, e / e.sum(-1, keepdims=True)


def test_depth_router_is_the_plain_mlp_over_the_carried_state():
    from mxnet_tpu.ops.contrib import depth_router
    x, w = jnp.asarray(_n((20, 32), 70)), _router_weights()
    state, carry = jnp.asarray(_n((20, 12), 71)), jnp.asarray(_n((12,), 72))
    r, p = depth_router(x, *w.values())
    want_r, want_p = _plain_router(x, w)
    np.testing.assert_allclose(r, want_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(p, want_p, rtol=1e-5)
    r2, p2 = depth_router(x, *w.values(), state, carry, carried=True,
                          eps=1e-5)
    want_r, want_p = _plain_router(x, w, state, carry, eps=1e-5)
    np.testing.assert_allclose(r2, want_r, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(p2, want_p, rtol=1e-5)
    assert not np.allclose(p2, p)
    # float32 out of bfloat16 data and weights: the state is not rounded on
    # its way from layer to layer
    low = depth_router(x.astype(jnp.bfloat16),
                       *(v.astype(jnp.bfloat16) for v in w.values()),
                       state, carry.astype(jnp.bfloat16), carried=True)
    assert low[0].dtype == low[1].dtype == jnp.float32
    s = mx.sym.DepthRouter(data=mx.sym.Variable("x"), name="r",
                           **{k: mx.sym.Variable(k) for k in w})
    assert s.list_arguments() == ["x"] + list(w)
    shapes = {k: v.shape for k, v in w.items()}
    assert s.infer_shape(x=(20, 32), **shapes)[1] == [(20, 12), (20, 8)]
    carried = mx.sym.DepthRouter(
        data=mx.sym.Variable("x"), carried=True,
        state=mx.sym.Variable("s"), carry=mx.sym.Variable("c"),
        **{k: mx.sym.Variable(k) for k in w})
    assert carried.list_arguments() == ["x"] + list(w) + ["s", "c"]


def test_router_state_reaches_the_next_layer_and_its_gradient_comes_back():
    """Two expert layers under their own ``mirror_stage`` (the executor's
    segmented, rematerialising evaluation), the second's router carrying
    the first's state: perturbing the first layer's down-projection moves
    the second layer's scores, and the second layer's loss has a gradient
    in the first layer's router that the unsegmented graph agrees with."""
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu.attribute import AttrScope
    from mxnet_tpu.models.zaya import _experts
    cfg = _cfg(held=8)
    params = {k: jnp.asarray(v * (5 if k.endswith("_weight") else 1))
              for k, v in ref.init(jax.random.PRNGKey(2), cfg)[0].items()
              if k.startswith(("l0_moe_", "l1_moe_")) and "norm_g" not in k
              and "_res_" not in k and "_out_" not in k
              or k.endswith("router_norm_gamma") and k[:2] in ("l0", "l1")}
    x = _n((48, 32), 80)

    def graph(staged):
        x0, x1, state, outs = (mx.sym.Variable("x0"), mx.sym.Variable("x1"),
                               None, [])
        for i, xi in enumerate((x0, x1)):
            p = "l%d_moe" % i
            with AttrScope(**({"mirror_stage": p} if staged else {})):
                out, _, state = _experts(xi, state, p, dict(TOY,
                                                            num_experts=8),
                                         8, 0)
            outs.append(out)
        return mx.sym.MakeLoss(mx.sym.sum(outs[1] * outs[1]))

    def run(staged, arrays):
        sym = graph(staged)
        args = _nd(arrays)
        grads = {k: mx.nd.zeros(v.shape) for k, v in args.items()}
        ex = sym.bind(mx.cpu(), args, args_grad=grads)
        loss = ex.forward(is_train=True)[0].asnumpy()
        ex.backward()
        return float(loss), {k: v.asnumpy() for k, v in grads.items()}
    arrays = dict(params, x0=x, x1=_n((48, 32), 81))
    loss, grads = run(True, arrays)
    plain_loss, plain = run(False, arrays)
    assert abs(loss - plain_loss) <= 1e-5 * abs(plain_loss)
    first = "l0_moe_router_down_weight"
    assert float(np.abs(grads[first]).max()) > 0
    for k in grads:
        np.testing.assert_allclose(grads[k], plain[k], rtol=1e-4,
                                   atol=1e-6 * np.abs(plain[k]).max() + 1e-12)
    # layer 0's experts do not enter layer 1's loss; its router does
    np.testing.assert_array_equal(grads["l0_moe_experts_down_weight"], 0)
    moved = dict(arrays)
    moved[first] = arrays[first] + 0.5
    assert abs(run(True, moved)[0] - loss) > 1e-6 * abs(loss)
    # and the reference hands the same state on
    p = {k: v for k, v in params.items()}
    w0, c0, r0 = ref.route(p, "l0_moe_", jnp.asarray(x), None, cfg)
    w1, c1, r1 = ref.route(p, "l1_moe_", jnp.asarray(arrays["x1"]), r0, cfg)
    np.testing.assert_allclose(
        r1, jnp.asarray(arrays["x1"]) @ p["l1_moe_router_down_weight"].T
        + p["l1_moe_router_carry"] * r0, rtol=1e-5, atol=1e-6)


def test_the_2_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """16 experts, top-1, 2 shares of 8 as in the cell's deployment: the
    routed parts of both shares summed are the uncut reference's expert
    layer, and each share is the reference cut to it."""
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu.ops.contrib import depth_router, routed_experts
    cfg = dict(TOY, num_experts=16, num_routed_experts=16, expert_offset=0,
               seq_len=64)
    m = "l1_moe_"
    p = {k: v * (5 if k.endswith("_weight") else 1) for k, v in
         ref.init(jax.random.PRNGKey(3), cfg)[0].items() if k.startswith(m)}
    # a bias small enough that the scores share the choice with it
    p[m + "router_balance_bias"] = jnp.asarray(_n((16,), 92, 0.002))
    x, state = jnp.asarray(_n((64, 32), 90)), jnp.asarray(_n((64, 12), 91))
    whole, r = ref.expert_layer(p, m, x, state, cfg)
    mine, scores = depth_router(
        x, *(p[m + "router_" + n] for n in (
            "down_weight", "norm_gamma", "fc1_weight", "fc2_weight",
            "fc3_weight")), state, p[m + "router_carry"], carried=True,
        eps=1e-5)
    np.testing.assert_allclose(mine, r, rtol=1e-5, atol=1e-6)

    def share(s):
        held = slice(8 * s, 8 * s + 8)
        return routed_experts(
            x, scores, p[m + "experts_gate_up_weight"][held],
            p[m + "experts_down_weight"][held],
            p[m + "router_balance_bias"], top_k=1, expert_offset=8 * s,
            norm_topk_prob=False, use_select_bias=True, scores_given=True)
    parts = [share(s) for s in range(2)]
    landed = [float(stats[1]) for _, stats in parts]
    assert sum(landed) == 64 and min(landed) > 0
    np.testing.assert_allclose(parts[0][0] + parts[1][0], whole, rtol=1e-4,
                               atol=1e-6)
    for s in range(2):
        cut = dict(cfg, num_experts=8, expert_offset=8 * s)
        pc = dict(p)
        for name in ("experts_gate_up_weight", "experts_down_weight"):
            pc[m + name] = p[m + name][8 * s:8 * s + 8]
        np.testing.assert_allclose(ref.expert_layer(pc, m, x, state, cut)[0],
                                   parts[s][0], rtol=1e-4, atol=1e-6)


# -- the tied head -----------------------------------------------------------

def test_tied_weights_gradient_is_the_embeddings_plus_the_heads():
    """One variable read by ``Embedding`` and by the head's
    ``FullyConnected``: its gradient is the sum of what each would get
    with a weight of its own."""
    from mxnet_tpu.models.qwen3_next import _linear
    rs = RS(5)
    ids = rs.randint(0, 11, (6,)).astype("f")
    label = rs.randint(0, 11, (6,)).astype("f")
    w = _n((11, 4), 6)

    def net(tied):
        e = mx.sym.Variable("embed_weight")
        h = mx.sym.Embedding(mx.sym.Variable("data"), weight=e, input_dim=11,
                             output_dim=4, name="embed")
        out = _linear(h * h, "head", 11, e if tied else None)
        return mx.sym.SoftmaxOutput(out, mx.sym.Variable("softmax_label"),
                                    name="softmax")

    def grads(tied):
        sym = net(tied)
        args = {"data": mx.nd.array(ids), "softmax_label": mx.nd.array(label),
                "embed_weight": mx.nd.array(w)}
        if not tied:
            args["head_weight"] = mx.nd.array(w)
        out = {k: mx.nd.zeros(v.shape) for k, v in args.items()}
        ex = sym.bind(mx.cpu(), args, args_grad=out)
        ex.forward(is_train=True)
        ex.backward()
        return {k: v.asnumpy() for k, v in out.items()}
    assert net(True).list_arguments() == ["data", "embed_weight",
                                          "softmax_label"]
    tied, apart = grads(True), grads(False)
    assert np.abs(apart["embed_weight"]).max() > 0
    assert np.abs(apart["head_weight"]).max() > 0
    np.testing.assert_allclose(
        tied["embed_weight"], apart["embed_weight"] + apart["head_weight"],
        rtol=1e-5, atol=1e-7)


# -- the whole model against the plain reference, through fit ----------------

def _toy_model(seq_len=80, held=4, offset=4):
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu.models import zaya_sym
    sym = zaya_sym(seq_len, num_experts=8, num_experts_held=held,
                   expert_offset=offset, **TOY)[0]
    cfg = _cfg(held, offset, seq_len)
    params, _ = ref.init(jax.random.PRNGKey(0), cfg)
    # larger than the family's 0.02 so that every nonlinearity is exercised
    params = {k: (v * 5 if k.endswith("_weight") else v)
              for k, v in params.items()}
    return sym, cfg, params


def test_symbol_has_the_reference_leaves_and_named_stages():
    from benchmark.reference import zaya1 as ref
    from mxnet_tpu import models
    assert models.zaya_sym is models.zaya.zaya_sym
    sym, cfg, params = _toy_model()
    args = [a for a in sym.list_arguments()
            if a not in ("data", "softmax_label")]
    assert sorted(args) == sorted(params)
    assert "head_weight" not in args and args.count("embed_weight") == 1
    # layer 0's router carries nothing
    assert "l0_moe_router_carry" not in args and "l1_moe_router_carry" in args
    shapes, out, _ = sym.infer_shape(data=(2, 80), softmax_label=(2, 80))
    assert out == [(160, 300), (6,)]
    want = ref.shapes(cfg)[0]
    for name, shape in zip(sym.list_arguments(), shapes):
        if name in want:
            assert tuple(shape) == tuple(want[name]), name
    stages = {n.attrs.get("mirror_stage") for n in sym._nodes()
              if n.op is not None}
    assert stages == {None, "l0_cca", "l1_cca", "l2_cca", "l0_moe", "l1_moe",
                      "l2_moe"}
    # Variable(init=...) seeds what the reference seeds the same way
    inits = {n.name: n.attrs.get("__init__") for n in sym._nodes()
             if n.op is None}
    assert "0.5" in inits["l1_moe_router_carry"]
    assert "zero" in inits["l0_cca_temp"].lower()


@pytest.mark.parametrize("seq_len,held,offset,dtype", [
    (80, 8, 0, None), (256, 4, 4, None), (80, 4, 0, "bfloat16")],
    ids=["held_all", "held_half", "held_half_bf16"])
def test_model_matches_the_reference_through_fit(seq_len, held, offset,
                                                 dtype):
    """Loss of each of three steps, the first gradient as the optimizer
    got it and the change after three steps, through ``SPMDModule.fit``
    (``SPMDTrainer``'s fused step) from int32 rows; the step's counters
    settle in the recorder.  In float32 leaf by leaf; with ``compute_dtype``
    bfloat16 — the tied weight through the trainer's cast, its donation and
    ``from_program`` — by the harness's own numbers, the median leaf within
    a bfloat16's rounding and the master weights still float32."""
    from benchmark import compare
    from benchmark.reference import common, zaya1 as ref
    from mxnet_tpu.parallel import SPMDModule, default_mesh
    sym, cfg, params = _toy_model(seq_len, held, offset)
    opt = {"learning_rate": 0.01, "momentum": 0.9, "wd": 0.0}
    rs = RS(1)
    data = rs.randint(0, 300, (6, seq_len)).astype(np.int32)
    label = rs.randint(0, 300, (6, seq_len)).astype(np.int32)
    mod = SPMDModule(sym, mesh=default_mesh(devices=jax.devices()[:1]),
                     compute_dtype=dtype)
    seen = {"loss": []}
    before = dict(profiler.counters())

    def on_step(param):
        trainer = mod._deferred_metric_trainer()
        prob = np.asarray(trainer.outputs[0].asnumpy(), np.float64)
        lab = label[2 * param.nbatch:2 * param.nbatch + 2].T.reshape(-1)
        seen["loss"].append(-np.mean(np.log(prob[np.arange(2 * seq_len), lab])))
        if param.nbatch == 0:
            assert {str(v.dtype) for v in trainer.params.values()} == {
                "float32"}
            seen["grad1"] = ref.from_program(
                {k: np.asarray(v[0]) / -0.01
                 for k, v in trainer.opt_state.items()}, cfg)
    mod.fit(mx.io.NDArrayIter(data, label, batch_size=2), num_epoch=1,
            optimizer="sgd", optimizer_params=dict(opt), initializer=None,
            arg_params={k: mx.nd.NDArray._from_jax(v + 0)
                        for k, v in params.items()},
            batch_end_callback=on_step,
            eval_metric=mx.metric.Perplexity(None))
    after = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}
    now = profiler.counters()
    # three expert layers a step, every one on the full path, a token one
    # pair
    assert now["moe.calls"] - before.get("moe.calls", 0) >= 2 * 3
    assert now.get("moe.compact_calls", 0) == before.get(
        "moe.compact_calls", 0)
    routed = now["moe.assignments"] - before.get("moe.assignments", 0)
    here = now["moe.assignments_here"] - before.get("moe.assignments_here", 0)
    assert routed % (3 * 2 * seq_len) == 0
    assert here == routed if held == 8 else 0 < here < routed

    batches = [{"data": data[i:i + 2], "softmax_label": label[i:i + 2]}
               for i in (0, 2, 4)]
    got = common.follow(common.make_step(ref.loss_fn(cfg), opt, 2), params,
                        {}, batches)
    # the load moved every layer's balancing bias, by the optimizer's rule:
    # its first gradient is the rate times the error of the experts' shares
    # of the step's tokens (it sums to nothing), the reference's leaf for leaf
    bias = [k for k in params if k.endswith("_router_balance_bias")]
    assert len(bias) == 3
    for k in bias:
        push = seen["grad1"][k] / 0.05 + 1.0 / 8
        assert abs(push.sum() - 1) < 1e-3 and push.min() > -1e-3, (k, push)
        assert np.abs(after[k]).max() > 1e-5
        np.testing.assert_allclose(
            seen["grad1"][k], np.asarray(got["full"]["grad1"][k]),
            atol=0.05 * (3 if dtype else 0.01) / (2 * seq_len) + 1e-7)
    if dtype is None:
        np.testing.assert_allclose(seen["loss"], got["loss"], rtol=1e-5)
        for k, g in got["full"]["grad1"].items():
            g = np.asarray(g)
            assert np.linalg.norm(seen["grad1"][k] - g) <= \
                1e-3 * np.linalg.norm(g) + 1e-7, k
        for k, d in got["full"]["change"].items():
            d = np.asarray(d)
            mine = after[k] - np.asarray(params[k])
            assert np.linalg.norm(mine - d) <= \
                2e-3 * np.linalg.norm(d) + 1e-7, k
        return
    change = {k: after[k] - np.asarray(params[k]) for k in params}
    prog = common.differences({
        "loss": seen["loss"],
        "grad1": {k: float(np.linalg.norm(v))
                  for k, v in seen["grad1"].items()},
        "change": {k: float(np.linalg.norm(v)) for k, v in change.items()},
        "full": {"grad1": seen["grad1"], "change": change}}, got)
    gaps = {k: v[0] for k, v in compare.training_gaps(prog, got).items()}
    assert gaps["loss_gap"] < 5e-3, gaps
    assert gaps["grad1_mid_diff"] < 0.05 and gaps["change_mid_diff"] < 0.05, \
        gaps
    # the tied leaf, summed from two readers in bfloat16
    tied = np.asarray(got["full"]["grad1"]["embed_weight"])
    assert np.linalg.norm(seen["grad1"]["embed_weight"] - tied) <= \
        0.05 * np.linalg.norm(tied)
