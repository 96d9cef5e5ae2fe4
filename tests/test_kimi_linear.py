"""The ops and the cut of the ``kimi_linear`` family
(``models/kimi_linear.py``), at tiny sizes on the CPU: the vector-decay
delta rule against the position-by-position recurrence, attention with
value heads narrower than the keys against explicit scores, the sigmoid
router against a dense loop, the sigmoid-gated norm, the whole model
against the plain reference (``benchmark/reference/kimi_linear.py``)
through ``SPMDModule.fit``, and the 32 shares of the expert layer tied to
the whole layer."""
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import mxnet_tpu as mx
from mxnet_tpu import profiler

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RS = np.random.RandomState

LINEAR = dict(full_attn_layers=[4, 8], kda_layers=[1, 2, 3, 5, 6, 7],
              num_heads=4, head_dim=8, short_conv_kernel_size=4)
TOY = dict(hidden_size=32, num_hidden_layers=5, linear_attn_config=LINEAR,
           num_attention_heads=4, kv_lora_rank=16, qk_nope_head_dim=8,
           qk_rope_head_dim=4, v_head_dim=8, first_k_dense_replace=1,
           intermediate_size=64, num_experts_per_token=4,
           moe_intermediate_size=16, num_shared_experts=1,
           moe_router_activation_func="sigmoid", moe_renormalize=True,
           routed_scaling_factor=2.446, rms_norm_eps=1e-5, vocab_size=300)


def _n(shape, seed, scale=1.0):
    return (RS(seed).randn(*shape) * scale).astype("f")


# -- the delta rule with a decay per key channel -----------------------------

def _recurrence(q, k, v, g, beta):
    """Position by position: q, k, g (B, T, H, dk), v (B, T, H, dv)."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        u = (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)) * b_t[..., None]
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)
    b, _, h, dk = q.shape
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def _rule_oracle(q, k, v, a, b, a_log, dt_bias):
    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        a + dt_bias.reshape(a.shape[2:]))
    return _recurrence(unit(q) * q.shape[-1] ** -0.5, unit(k), v, g,
                       jax.nn.sigmoid(b))


def _rule_inputs(t, seed=20, h=3, dk=8, dv=6, a_shift=0.0, rate=None):
    """``rate``: every head's ``exp(A_log)`` (default: U(1, 16))."""
    q, k = _n((2, t, h, dk), seed), _n((2, t, h, dk), seed + 1)
    v, b = _n((2, t, h, dv), seed + 2), _n((2, t, h), seed + 4)
    a = _n((2, t, h, dk), seed + 3) + a_shift
    a_log = np.log(RS(seed + 5).uniform(1, 16, h) if rate is None
                   else np.full(h, rate)).astype("f")
    return tuple(jnp.asarray(x) for x in
                 (q, k, v, a, b, a_log, _n((h * dk,), seed + 6, 0.1)))


def _routes(since):
    return [r["ids"] for r in profiler.spans(since=since)
            if r["name"] == "kernel.route"
            and r["ids"]["kernel"] == "delta_rule"]


# the last three: the fastest decay the initialisation allows (exp(A_log)
# = 16) under ordinary, large and very large ``a``: a chunk of 64
# positions accumulates a log-decay of -1,000 to -8,000 a channel, where
# exp(-G) of the textbook factoring is infinite in float32
@pytest.mark.parametrize("t,chunk,a_shift,rate", [
    (150, 64, 0.0, None), (64, 64, 0.0, None), (40, 16, 0.0, None),
    (5, 64, 0.0, None), (70, 24, 0.0, None), (150, 64, 0.0, 16.0),
    (150, 64, 2.0, 16.0), (150, 64, 8.0, 16.0)])
def test_vector_decay_rule_matches_the_recurrence(t, chunk, a_shift, rate):
    """Forward and gradient, ``T`` not a multiple of the chunk, chunks that
    are and are not whole sub-blocks of 16: finite, and equal to the
    recurrence within float32 round-off of chunk-local products (1e-4 of
    the largest entry, and 2e-6 where the recurrence's gradient underflows
    to zero: the running sums of O(1) cotangents round at 1e-7 each)."""
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(t, a_shift=a_shift, rate=rate)
    since = time.perf_counter()
    out = gated_delta_rule_op(*args, chunk=chunk)
    # heads of 8 are no lane multiple: the lax tier, and the event says
    # which of the two rules it walked
    assert _routes(since) == [{"kernel": "delta_rule", "tier": "lax",
                               "reason": "shapes", "decay": "channel"}]
    ref = _rule_oracle(*args)
    assert out.shape == ref.shape == (2, t, 3, 6)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-6)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3 * fn(*a))),
                        argnums=tuple(range(7)))(*args)
    for a, b in zip(grads(lambda *a: gated_delta_rule_op(*a, chunk=chunk)),
                    grads(_rule_oracle)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=1e-4 * float(jnp.max(jnp.abs(b))) + 2e-6)


def test_textbook_factoring_would_overflow_where_the_rule_stays_finite():
    """What the sub-blocks are for: at the strongest decays ``exp(-G)``
    over one chunk is infinite in float32."""
    q, k, v, a, b, a_log, dt_bias = _rule_inputs(64, a_shift=2.0, rate=16.0)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        a + dt_bias.reshape(a.shape[2:]))
    assert float(jnp.min(jnp.sum(g, axis=1))) < -1000
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-jnp.cumsum(g, axis=1)))))


def test_blocked_inverse_is_exact_where_the_doubling_cancels_to_garbage():
    """Alike keys under a slow decay make ``L`` nearly ``b x ones``: the
    inverse stays under 1 while the doubling's powers of L reach 1e17 and
    cancel in float32.  The vector rule's inverse (forward substitution in
    16-blocks, merged pairwise) has no such terms."""
    from mxnet_tpu.kernels.delta_rule import (
        _unit_lower_inverse_blocked, _unit_lower_inverse_impl)
    ones = np.tril(np.ones((64, 64), "f"), -1)
    for b in (0.5, 0.99):
        exact = np.linalg.inv(np.eye(64) + b * ones.astype(np.float64))
        assert np.abs(exact).max() <= 1.0
        got = np.asarray(_unit_lower_inverse_blocked(jnp.asarray(b * ones)))
        assert np.abs(got - exact).max() < 1e-5
        assert np.abs(np.asarray(_unit_lower_inverse_impl(
            jnp.asarray(b * ones))) - exact).max() > 100
    low = jnp.asarray(np.tril(_n((3, 48, 48), 4, 0.3), -1))   # 3 blocks of 16
    exact = np.linalg.inv(np.eye(48) + np.asarray(low, np.float64))
    np.testing.assert_allclose(_unit_lower_inverse_blocked(low), exact,
                               rtol=1e-4, atol=1e-5 * np.abs(exact).max())


def test_vector_decay_rule_with_alike_keys_and_slow_decays():
    """A token repeated for a whole chunk (Zipf ids: the commonest id is a
    tenth of the stream) under the decays the initialisation draws (dt
    down to 0.001): equal to the recurrence, forward and gradient."""
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    q, k, v, a, b, a_log, dt_bias = _rule_inputs(150, seed=40)
    k = jnp.broadcast_to(k[:, :1], k.shape) + 0.01 * k
    args = (q, k, v, a - 7.0, b + 3.0, a_log, dt_bias)   # beta ~ 0.95
    out, ref = gated_delta_rule_op(*args, chunk=64), _rule_oracle(*args)
    np.testing.assert_allclose(out, ref, rtol=1e-3, atol=1e-5)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3 * fn(*a))),
                        argnums=tuple(range(7)))(*args)
    for x, y in zip(grads(lambda *a: gated_delta_rule_op(*a, chunk=64)),
                    grads(_rule_oracle)):
        np.testing.assert_allclose(
            x, y, rtol=5e-3, atol=1e-3 * float(jnp.max(jnp.abs(y))) + 2e-6)


def test_one_op_two_ranks_a_vector_of_equal_decays_is_the_scalar_rule():
    """``a`` with a trailing dk axis whose channels are all equal gives
    what the rank-3 ``a`` gives (the code the scalar decay ran before),
    and both routes are told apart on their ``kernel.route`` records."""
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    q, k, v, a, b, a_log, _ = _rule_inputs(150, seed=33)
    a3, dt3 = a[..., 0], jnp.asarray(_n((3,), 9, 0.1))
    a4 = jnp.broadcast_to(a3[..., None], a.shape)
    dt4 = jnp.repeat(dt3, a.shape[-1])
    since = time.perf_counter()
    scalar = gated_delta_rule_op(q, k, v, a3, b, a_log, dt3, chunk=64)
    vector = gated_delta_rule_op(q, k, v, a4, b, a_log, dt4, chunk=64)
    assert [(r["reason"], r.get("decay")) for r in _routes(since)] == [
        ("shapes", None), ("shapes", "channel")]
    np.testing.assert_allclose(vector, scalar, rtol=1e-4, atol=2e-6)
    g = -jnp.exp(a_log) * jax.nn.softplus(a3 + dt3)

    def unit(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    ref = _recurrence(unit(q) * 8 ** -0.5, unit(k), v,
                      jnp.broadcast_to(g[..., None], a.shape),
                      jax.nn.sigmoid(b))
    np.testing.assert_allclose(scalar, ref, rtol=1e-4, atol=2e-6)


def _kernel_names(fn, *args):
    import re
    return re.findall(r"mxtpu_[a-z_]+", str(jax.make_jaxpr(fn)(*args)))


def test_vector_decay_symbol_infers_and_a_tpu_lowering_takes_its_kernels():
    names = ("query", "key", "value", "a", "b", "A_log", "dt_bias")
    s = mx.sym.GatedDeltaRule(chunk=64, **{n: mx.sym.Variable(n)
                                           for n in names})
    shapes = {"query": (1, 128, 2, 128), "key": (1, 128, 2, 128),
              "value": (1, 128, 2, 128), "a": (1, 128, 2, 128),
              "b": (1, 128, 2), "A_log": (2,), "dt_bias": (256,)}
    assert s.infer_shape(**shapes)[1] == [(1, 128, 2, 128)]
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = [jnp.zeros(shapes[n], jnp.float32) for n in names]

    def grads(*a):
        return jax.grad(lambda *x: jnp.sum(gated_delta_rule_op(*x, chunk=64)),
                        argnums=tuple(range(7)))(*a)
    # lane-aligned heads, whole chunks, a pair of heads: each rule takes
    # its own two kernels, told from the rank of ``a`` alone
    since = time.perf_counter()
    assert _kernel_names(grads, *args) == [
        "mxtpu_delta_rule_channel_fwd", "mxtpu_delta_rule_channel_bwd"]
    assert _routes(since) == [{"kernel": "delta_rule", "tier": "pallas",
                               "reason": "aligned", "decay": "channel"}]
    scalar = list(args)
    scalar[3], scalar[6] = args[3][..., 0], args[6][:2]
    since = time.perf_counter()
    assert _kernel_names(grads, *scalar) == ["mxtpu_delta_rule_fwd",
                                             "mxtpu_delta_rule_bwd"]
    assert _routes(since) == [{"kernel": "delta_rule", "tier": "pallas",
                               "reason": "aligned"}]


@pytest.mark.parametrize("heads,t,chunk,why", [
    (3, 128, 64, "a grid step lays two heads side by side"),
    (2, 96, 48, "a chunk of 48 positions does not halve down to one"),
    (2, 100, 64, "a tail that is no whole chunk"),
], ids=["odd_heads", "chunk_48", "tail"])
def test_vector_decay_takes_the_lax_tier_for_other_shapes(heads, t, chunk,
                                                          why):
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    z = jnp.zeros
    args = (z((1, t, heads, 128)), z((1, t, heads, 128)),
            z((1, t, heads, 128)), z((1, t, heads, 128)), z((1, t, heads)),
            z((heads,)), z((heads * 128,)))
    since = time.perf_counter()
    assert not _kernel_names(
        lambda *a: gated_delta_rule_op(*a, chunk=chunk), *args), why
    assert _routes(since) == [{"kernel": "delta_rule", "tier": "lax",
                               "reason": "shapes", "decay": "channel"}]


def test_vector_decay_takes_the_lax_tier_under_a_mesh():
    from mxnet_tpu.kernels import auto_partitioned
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    z = jnp.zeros
    args = (z((1, 128, 2, 128)),) * 4 + (z((1, 128, 2)), z((2,)), z((256,)))
    since = time.perf_counter()
    with auto_partitioned():
        assert not _kernel_names(
            lambda *a: gated_delta_rule_op(*a, chunk=64), *args)
    assert _routes(since) == [{"kernel": "delta_rule", "tier": "lax",
                               "reason": "mesh", "decay": "channel"}]


# -- the vector rule's compiled tier: its chunk map, and the op through the
# -- Pallas interpreter

def _chunk_operands(c, pair, dk, dv, rate, seed=80):
    r = pair * c
    s0 = [jnp.asarray(_n((dk, dv), seed + i, 0.3)) for i in range(pair)]
    q, k, v, a = (jnp.asarray(_n(shape, seed + 3 + i)) for i, shape in
                  enumerate([(r, dk), (r, dk), (r, dv), (r, dk)]))
    bcol = jax.nn.sigmoid(jnp.asarray(_n((r, 1), seed + 7)))
    return s0, q, k, v, -rate * jax.nn.softplus(a), bcol


@pytest.mark.parametrize("c,pair,rate", [
    (32, 2, 0.05), (16, 1, 1.0), (64, 2, 16.0), (32, 4, 0.001)],
    ids=["two_heads_slow", "one_sub_block", "four_sub_blocks_fast",
         "four_heads_slowest"])
def test_channel_chunk_map_is_the_lax_tiers_and_its_backward_the_transpose(
        c, pair, rate):
    """The kernels' bodies call ``channel_chunk_forward`` and the
    hand-written ``channel_chunk_backward``: the first is the lax tier's
    ``_channel_chunk_local`` + ``_walk_step`` head by head, the second
    jax's transpose of the first."""
    from mxnet_tpu.kernels import delta_rule as dr
    dk, dv = 16, 12
    primals = _chunk_operands(c, pair, dk, dv, rate)
    kw = dict(pair=pair, eps=1e-6, scale=dk ** -0.5)

    def lax_tier(s0, q, k, v, g, bcol):
        out, states = [], []
        for h in range(pair):
            at = slice(h * c, (h + 1) * c)
            s, o = dr._walk_step(s0[h], dr._channel_chunk_local(
                dr._unit(q[at], 1e-6)[0] * dk ** -0.5,
                dr._unit(k[at], 1e-6)[0], v[at], g[at], bcol[at, 0], 16))
            out.append(o)
            states.append(s)
        return jnp.concatenate(out), states
    (o, s1, inverse), pull = jax.vjp(
        lambda *a: dr.channel_chunk_forward(*a, **kw), *primals)
    for a, b in zip(jax.tree.leaves((o, s1)),
                    jax.tree.leaves(lax_tier(*primals))):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=2e-6 * float(jnp.max(jnp.abs(b))))
    do = jnp.asarray(_n((pair * c, dv), 90))
    ds1 = [jnp.asarray(_n((dk, dv), 91 + i)) for i in range(pair)]
    want = pull((do, ds1, jnp.zeros_like(inverse)))
    ds0, dq, dk_, dg, d_v, dbcol = dr.channel_chunk_backward(
        primals[0], inverse, *primals[1:], do, ds1, **kw)
    for a, b in zip(jax.tree.leaves((ds0, dq, dk_, d_v, dg, dbcol)),
                    jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=2e-6 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("pair", [1, 2])
def test_the_kernels_inverse_is_exact_where_the_doubling_cancels_to_garbage(
        pair):
    """The bar of the lax tier's blocked inverse, on the tiles the kernels
    invert: the heads' (64, 64) ``b x ones`` side by side."""
    from mxnet_tpu.kernels.delta_rule import (
        _channel_inverse, _channel_masks, _inverse_side_by_side)
    ones = np.tril(np.ones((64, 64), "f"), -1)
    m = _channel_masks(64, pair, 128)
    for b in (0.5, 0.99):
        exact = np.linalg.inv(np.eye(64) + b * ones.astype(np.float64))
        low = jnp.asarray(np.tile(b * ones, (1, pair)))
        got = np.asarray(_channel_inverse(low, m))
        assert np.abs(got - np.tile(exact, (1, pair))).max() < 1e-5
        assert np.abs(np.asarray(_inverse_side_by_side(low, m))
                      - np.tile(exact, (1, pair))).max() > 100
    low = jnp.asarray(np.tile(np.tril(_n((64, 64), 4, 0.3), -1), (1, pair)))
    exact = np.linalg.inv(np.eye(64) + np.asarray(low[:, :64], np.float64))
    np.testing.assert_allclose(_channel_inverse(low, m),
                               np.tile(exact, (1, pair)), rtol=1e-4,
                               atol=1e-5 * np.abs(exact).max())


def _lax_tier(q, k, v, a, b, a_log, dt_bias, chunk):
    from mxnet_tpu.kernels.delta_rule import gated_delta_net_lax
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        a + dt_bias.reshape(a.shape[2:]))
    return gated_delta_net_lax(q, k, v, g, jax.nn.sigmoid(b), chunk=chunk)


# decays: slow is the initialisation's slowest step (softplus(-7) = 0.0009
# under exp(A_log) = 1), fast its fastest rate under a large ``a`` (a
# chunk's log-decay passes -1,000)
@pytest.mark.parametrize("t,chunk,heads,a_shift,rate", [
    (64, 64, 2, -7.0, 1.0),         # one chunk, one pair of heads
    (256, 64, 4, 0.0, None),        # two chunks a grid step, two steps a
                                    # row, an even and an odd pair
    (256, 64, 4, 2.0, 16.0),
    (96, 32, 4, -7.0, 1.0),         # four heads a step, one chunk a step
], ids=["one_chunk_slow", "two_pairs", "two_pairs_fast", "four_heads_slow"])
def test_compiled_vector_rule_matches_recurrence_and_lax_tier(
        compiled_tier, t, chunk, heads, a_shift, rate):
    """Two rows (the state is zero at each row's start), heads of 128:
    forward and all seven gradients of the op against the position by
    position recurrence, and the forward against the lax tier."""
    from mxnet_tpu.kernels.delta_rule import _channel_chunks_per_step
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(t, seed=50, h=heads, dk=128, dv=128,
                        a_shift=a_shift, rate=rate)
    pair = 128 // chunk
    assert _channel_chunks_per_step(t // chunk, chunk, pair, 128, 128) == \
        {64: 1, 256: 2, 96: 1}[t]
    since = time.perf_counter()
    out = gated_delta_rule_op(*args, chunk=chunk)
    assert _routes(since) == [{"kernel": "delta_rule", "tier": "pallas",
                               "reason": "aligned", "decay": "channel"}]
    ref = _rule_oracle(*args)
    assert out.shape == ref.shape == (2, t, heads, 128)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-6)
    np.testing.assert_allclose(out, _lax_tier(*args, chunk), rtol=1e-4,
                               atol=2e-6)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(3 * fn(*a))),
                        argnums=tuple(range(7)))(*args)
    for a, b in zip(grads(lambda *a: gated_delta_rule_op(*a, chunk=chunk)),
                    grads(_rule_oracle)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(
            a, b, rtol=2e-3, atol=1e-4 * float(jnp.max(jnp.abs(b))) + 2e-6)


def test_compiled_vector_rule_on_bfloat16_operands(compiled_tier):
    """q, k, v arrive in bfloat16 and leave so, the log-decay stays
    float32; inside, the rule is the float32 one on those values."""
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    args = _rule_inputs(128, seed=70, h=2, dk=128, dv=128)
    args = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]

    def both(fn):
        out, pull = jax.vjp(fn, *args)
        return (out,) + pull(jnp.ones_like(out))
    got = both(lambda *a: gated_delta_rule_op(*a, chunk=64))
    want = both(lambda *a: _lax_tier(*a, 64))
    assert got[0].dtype == got[1].dtype == got[3].dtype == jnp.bfloat16
    assert got[4].dtype == jnp.float32
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, "f"), np.asarray(b, "f")
        np.testing.assert_allclose(a, b, rtol=2 ** -7,
                                   atol=2 ** -8 * np.abs(b).max())


def test_scalar_decay_still_lowers_to_its_two_kernels_and_no_other():
    """A rank-3 ``a`` lowers to exactly what it lowered to before the
    vector rule had kernels: ``mxtpu_delta_rule_fwd`` and ``_bwd``, one
    each, forward and gradient."""
    from mxnet_tpu.ops.contrib import gated_delta_rule_op
    z = jnp.zeros
    args = (z((2, 128, 2, 128)), z((2, 128, 2, 128)), z((2, 128, 4, 128)),
            z((2, 128, 4)), z((2, 128, 4)), z((4,)), z((4,)))

    def op(*a):
        return gated_delta_rule_op(*a, chunk=64)
    assert _kernel_names(op, *args) == ["mxtpu_delta_rule_fwd"]
    assert _kernel_names(
        jax.grad(lambda *a: jnp.sum(op(*a)), argnums=tuple(range(7))),
        *args) == ["mxtpu_delta_rule_fwd", "mxtpu_delta_rule_bwd"]


# -- attention whose values are narrower than its keys -----------------------

def _explicit_attention(q, k, v):
    """Per head, explicit (T, T) scores: q (B, T, H, d), k (B, T, Hkv, d),
    v (B, T, Hkv, dv)."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("t,block,hkv,d,dv", [
    (37, 16, 4, 12, 8), (64, 16, 2, 12, 8), (16, 512, 4, 8, 12)])
def test_attention_follows_the_values_head_size(t, block, hkv, d, dv):
    from mxnet_tpu.ops.contrib import gq_attention
    q, k = jnp.asarray(_n((2, t, 4, d), 1)), jnp.asarray(_n((2, t, hkv, d), 2))
    v = jnp.asarray(_n((2, t, hkv, dv), 3))
    out = gq_attention(q, k, v, block_q=block)
    assert out.shape == (2, t, 4, dv)
    np.testing.assert_allclose(out, _explicit_attention(q, k, v), rtol=1e-4,
                               atol=1e-6)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads(lambda *a: gq_attention(*a, block_q=block)),
                    grads(_explicit_attention)):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
    s = mx.sym.GQAttention(query=mx.sym.Variable("q"),
                           key=mx.sym.Variable("k"),
                           value=mx.sym.Variable("v"))
    assert s.infer_shape(q=q.shape, k=k.shape, v=v.shape)[1] == [out.shape]


def test_attention_chained_is_the_attention_unchained(monkeypatch):
    """Past ``_CHAIN_BYTES`` of score tiles every query block but the first
    waits for the block before it (an ``optimization_barrier`` on its
    queries, forward and backward): the path the 32-head step takes on the
    chip, where no CPU test's shapes reach the constant.  Same numbers as
    unchained, value heads narrower than the keys'."""
    from mxnet_tpu.kernels import flash_attention as fa
    q, k, v = (jnp.asarray(_n((1, 37, h, d), 20 + i))
               for i, (h, d) in enumerate(((4, 12), (2, 12), (2, 8))))

    def grads(*a):
        return jax.grad(lambda *b: jnp.sum(jnp.sin(
            fa.gqa_attention(*b, block_q=16))), argnums=(0, 1, 2))(*a)

    def barriers(fn):
        return str(jax.make_jaxpr(fn)(q, k, v)).count("optimization_barrier")
    blocks = 3                                           # ceil(37 / 16)
    plain = (fa.gqa_attention(q, k, v, block_q=16),) + grads(q, k, v)
    assert barriers(lambda *a: grads(*a)) == 0
    monkeypatch.setattr(fa, "_CHAIN_BYTES", 0)
    assert barriers(lambda *a: fa.gqa_attention(*a, block_q=16)) == blocks - 1
    assert barriers(lambda *a: grads(*a)) == 2 * (blocks - 1)
    assert barriers(lambda *a: fa.gqa_attention(*a, block_q=64)) == 0
    chained = (fa.gqa_attention(q, k, v, block_q=16),) + grads(q, k, v)
    for a, b in zip(chained, plain):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(chained[0], _explicit_attention(q, k, v),
                               rtol=1e-4, atol=1e-6)


def test_latent_attention_is_the_plain_form():
    """The MLA stage of the graph against the reference's explicit scores:
    the one ``k_pe`` of a position shared by every head, no rotary
    embedding, scale (128 + 64)^-0.5 of the toy's (8 + 4)."""
    from benchmark.reference import kimi_linear as ref
    from mxnet_tpu.models.kimi_linear import _mla
    cfg = dict(TOY, num_experts=4, num_routed_experts=16, expert_offset=0,
               seq_len=48)
    a = "l3_mla_"
    params = {k: jnp.asarray(v * 5) for k, v in
              ref.init(jax.random.PRNGKey(1), cfg)[0].items()
              if k.startswith(a)}
    x = _n((2 * 48, 32), 7)
    sym = _mla(mx.sym.Variable("x"), "l3_mla", 48, TOY)
    ex = sym.bind(mx.cpu(), dict({"x": mx.nd.array(x)}, **{
        k: mx.nd.NDArray._from_jax(v) for k, v in params.items()}))
    got = ex.forward()[0].asnumpy().reshape(2, 48, 32)
    for row in range(2):
        rows = jnp.asarray(x[48 * row:48 * row + 48])
        want = ref._attention(params, a, rows, cfg, "f32")
        np.testing.assert_allclose(got[row], want, rtol=1e-4, atol=1e-6)


# -- the router --------------------------------------------------------------

def _dense_sigmoid_experts(x, wr, wgu, wd, bias, k, offset, scaling):
    score = jax.nn.sigmoid(x @ wr.T)
    _, e = lax.top_k(score + bias, k)
    w = jnp.take_along_axis(score, e, -1)
    w = w / w.sum(-1, keepdims=True) * scaling
    y = jnp.zeros_like(x)
    for j in range(wgu.shape[0]):
        mine = jnp.sum(jnp.where(e == j + offset, w, 0.0), -1)
        gate, up = jnp.split(x @ wgu[j], 2, -1)
        y = y + mine[:, None] * ((jax.nn.silu(gate) * up) @ wd[j])
    return y, e


def test_router_chooses_by_score_plus_bias_and_weighs_by_score():
    from mxnet_tpu.ops.contrib import routed_experts
    x = jnp.asarray(_n((40, 16), 40))
    wr = jnp.asarray(_n((16, 16), 41, 0.5))
    wgu, wd = jnp.asarray(_n((4, 16, 16), 42, 0.3)), \
        jnp.asarray(_n((4, 8, 16), 43, 0.3))
    # a bias that lifts expert 6 into every token's choice and sinks 5
    bias = jnp.zeros(16).at[6].set(2.0).at[5].set(-2.0)
    kw = dict(top_k=4, expert_offset=4, score_func="sigmoid",
              routed_scaling_factor=2.446, use_select_bias=True)
    out, stats = routed_experts(x, wr, wgu, wd, bias, **kw)
    ref, chosen = _dense_sigmoid_experts(x, wr, wgu, wd, bias, 4, 4, 2.446)
    assert (np.asarray(chosen) == 6).any(axis=1).all()
    assert not (np.asarray(chosen) == 5).any()
    plain = lax.top_k(jax.nn.sigmoid(x @ wr.T), 4)[1]
    assert not np.array_equal(np.sort(plain, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-6)
    assert float(stats[1]) == float(np.isin(chosen, [4, 5, 6, 7]).sum())

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                        argnums=(0, 1, 2, 3, 4))(x, wr, wgu, wd, bias)
    mine = grads(lambda *a: routed_experts(*a, **kw)[0])
    for a, b in zip(mine, grads(lambda *a: _dense_sigmoid_experts(
            *a, 4, 4, 2.446)[0])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)
    # the bias enters the choice alone: no gradient, SGD leaves it as seeded
    np.testing.assert_array_equal(np.asarray(mine[4]), 0.0)
    # the defaults are the softmax router's, bias or no bias argument
    soft = routed_experts(x, wr, wgu, wd, top_k=4, expert_offset=4)[0]
    assert float(jnp.max(jnp.abs(soft))) > 0 and not np.allclose(soft, out)
    with pytest.raises(mx.MXNetError):
        routed_experts(x, wr, wgu, wd, top_k=4, score_func="tanh")


def test_router_symbol_takes_the_bias_as_an_optional_input():
    def build(**kw):
        return mx.sym.RoutedExperts(
            data=mx.sym.Variable("x"), top_k=2, name="r",
            router_weight=mx.sym.Variable("wr"),
            gate_up_weight=mx.sym.Variable("wgu"),
            down_weight=mx.sym.Variable("wd"), **kw)
    assert build().list_arguments() == ["x", "wr", "wgu", "wd"]
    biased = build(select_bias=mx.sym.Variable("b"), use_select_bias=True,
                   score_func="sigmoid", routed_scaling_factor=2.0)
    assert biased.list_arguments() == ["x", "wr", "wgu", "wd", "b"]
    shapes = dict(x=(6, 8), wr=(4, 8), wgu=(4, 8, 6), wd=(4, 3, 8), b=(4,))
    assert biased.infer_shape(**shapes)[1] == [(6, 8), (6,)]


def test_the_32_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """256 experts, top-8, 32 shares of 8 as in the cell's deployment: the
    routed parts of all shares summed, plus the shared expert counted
    once, are the uncut reference's expert layer."""
    from benchmark.reference import kimi_linear as ref
    from mxnet_tpu.ops.contrib import routed_experts
    cfg = dict(TOY, num_experts=256, num_routed_experts=256,
               num_experts_per_token=8, expert_offset=0, seq_len=24)
    m = "l1_moe_"
    p = {k: v * (1 if k.endswith("_bias") else 5) for k, v in
         ref.init(jax.random.PRNGKey(3), cfg)[0].items() if k.startswith(m)}
    x = jnp.asarray(_n((24, 32), 50))
    whole = ref.expert_layer(p, m, x, cfg)
    gate, up = jnp.split(x @ p[m + "shared_gate_up_weight"].T, 2, -1)
    shared = (jax.nn.silu(gate) * up) @ p[m + "shared_down_weight"].T

    def share(s):
        held = slice(8 * s, 8 * s + 8)
        return routed_experts(
            x, p[m + "router_weight"], p[m + "experts_gate_up_weight"][held],
            p[m + "experts_down_weight"][held],
            p[m + "e_score_correction_bias"], top_k=8, expert_offset=8 * s,
            score_func="sigmoid", routed_scaling_factor=2.446,
            use_select_bias=True)
    parts = [share(s) for s in range(32)]
    assert sum(float(stats[1]) for _, stats in parts) == 24 * 8
    np.testing.assert_allclose(shared + sum(part for part, _ in parts),
                               whole, rtol=1e-4, atol=1e-6)
    # and the reference cut to one share gives that share's part
    busiest = int(np.argmax([float(stats[1]) for _, stats in parts]))
    cut = dict(cfg, num_experts=8, expert_offset=8 * busiest)
    pc = dict(p)
    for name in ("experts_gate_up_weight", "experts_down_weight"):
        pc[m + name] = p[m + name][8 * busiest:8 * busiest + 8]
    np.testing.assert_allclose(ref.expert_layer(pc, m, x, cut),
                               shared + parts[busiest][0], rtol=1e-4,
                               atol=1e-6)


# -- the gated norm and the initialiser --------------------------------------

def test_rmsnorm_gate_activation_is_an_attribute():
    x, gate, gamma = _n((3, 5, 8), 1), _n((3, 5, 8), 2), _n((8,), 3)
    y = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * gamma

    def run(**kw):
        s = mx.sym.RMSNorm(data=mx.sym.Variable("x"), eps=1e-5, gated=True,
                           gamma=mx.sym.Variable("g"),
                           gate=mx.sym.Variable("z"), **kw)
        args = {"x": mx.nd.array(x), "g": mx.nd.array(gamma),
                "z": mx.nd.array(gate)}
        return s.bind(mx.cpu(), args).forward()[0].asnumpy()
    sig = 1 / (1 + np.exp(-gate))
    np.testing.assert_allclose(run(gate_act="sigmoid"), y * sig, rtol=1e-5)
    np.testing.assert_allclose(run(), y * gate * sig, rtol=1e-5)
    with pytest.raises(Exception):
        run(gate_act="tanh")


def test_step_size_bias_initializer_is_softplus_inverse_of_a_log_uniform():
    arr = mx.nd.zeros((4096,))
    mx.initializer.StepSizeBias()._init_weight("l0_kda_dt_bias", arr)
    dt = np.log1p(np.exp(arr.asnumpy()))
    assert 0.9e-3 < dt.min() < 2e-3 and 0.05 < dt.max() < 0.11
    assert abs(np.log(dt).mean() - np.log(1e-2)) < 0.1


# -- the whole model against the plain reference, through fit ----------------

def _toy_model(seq_len=80, held=4, offset=4):
    from benchmark.reference import kimi_linear as ref
    from mxnet_tpu.models.kimi_linear import kimi_linear_sym
    sym = kimi_linear_sym(seq_len, num_experts=16, num_experts_held=held,
                          expert_offset=offset, **TOY)[0]
    cfg = dict(TOY, num_experts=held, num_routed_experts=16,
               expert_offset=offset, seq_len=seq_len)
    params, _ = ref.init(jax.random.PRNGKey(0), cfg)
    # larger than the family's 0.02 so that every nonlinearity is exercised
    params = {k: (v * 5 if k.endswith("_weight") else v)
              for k, v in params.items()}
    return sym, cfg, params


def test_symbol_has_the_reference_leaves_and_named_stages():
    from benchmark.reference import kimi_linear as ref
    from mxnet_tpu import models
    assert models.kimi_linear.kimi_linear_sym
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
    assert stages == {None, "l0_kda", "l1_kda", "l2_kda", "l3_mla", "l4_kda",
                      "l0_mlp", "l1_moe", "l2_moe", "l3_moe", "l4_moe"}


@pytest.mark.parametrize("seq_len,held", [(80, 4), (256, 2)])
def test_model_matches_the_reference_through_fit(seq_len, held):
    """Loss of each of three steps, the first gradient as the optimizer
    got it and the change after three steps, float32, through
    ``SPMDModule.fit`` from int32 rows; the step's counters settle in the
    recorder.  Rows of 256 tokens with 2 of 16 experts held run the expert
    layers' blocked path; rows of 80 leave a tail of 16 in the rule's
    chunks of 64."""
    from benchmark.reference import common, kimi_linear as ref
    from mxnet_tpu.parallel import SPMDModule, default_mesh
    sym, cfg, params = _toy_model(seq_len=seq_len, held=held)
    opt = {"learning_rate": 0.01, "momentum": 0.9, "wd": 0.0}
    rs = RS(1)
    data = rs.randint(0, 300, (6, seq_len)).astype(np.int32)
    label = rs.randint(0, 300, (6, seq_len)).astype(np.int32)
    mod = SPMDModule(sym, mesh=default_mesh(devices=jax.devices()[:1]))
    seen = {"loss": []}
    calls = profiler.counters().get("moe.calls", 0)
    since = time.perf_counter()

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
    # four KDA layers traced: each told apart from a scalar decay's route
    # (the toy's heads of 8 keep them on the lax tier)
    routes = _routes(since)
    assert routes and all(r["decay"] == "channel" and r["tier"] == "lax"
                          and r["reason"] == "shapes" for r in routes)
    # four expert layers a step (the dense first layer has none)
    assert profiler.counters()["moe.calls"] - calls >= 2 * 4

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
    bias = [k for k in params if k.endswith("_e_score_correction_bias")]
    assert len(bias) == 4
    for k in bias:
        np.testing.assert_array_equal(after[k], np.asarray(params[k]))
