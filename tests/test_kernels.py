"""Fused-kernel (mxnet_tpu/kernels/) bit-parity matrix and routing.

Every fused kernel is checked against its unfused lax composition:

- fused-lax tier: BITWISE equal in forward AND gradient (the fused
  reference runs the identical per-element op sequence, so XLA computes
  identical values) — at f32 and bf16, on odd/partial-tile shapes.
- Pallas tier (``interpret=True`` on this CPU tier — the same kernel
  code a TPU compiles): equal within the DOCUMENTED tolerances below.
  The interpreter evaluates the same math but through pallas' own
  load/store path, so exact bit equality is not guaranteed; observed
  deviations are ~1e-7 (f32).
- BN-into-conv folding reassociates float math by construction
  (``conv(x, w*s)`` vs ``s * conv(x, w)``), so the eval-path fold is
  tolerance-checked, never bitwise — the one documented exception.

Plus: ``MXTPU_FUSED_KERNELS=0`` restores the exact pre-fusion graphs
(symbol structure and executor plan), and the executor-level BN fusion
trains bit-identically to the unfused composition.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.kernels import (bn_act as BA, causal_conv as CC,
                               flash_attention as FA, lstm_cell as LC,
                               enabled_kernels, fused_enabled)
from mxnet_tpu.ops import nn as NN

#: documented Pallas-interpret tolerances per dtype (forward; gradients
#: get 10x the atol — the backward kernels recompute activations, one
#: extra rounding step)
TOL = {"float32": dict(rtol=1e-5, atol=1e-5),
       "bfloat16": dict(rtol=5e-2, atol=5e-2)}


def _xprog_close(a, b, msg=""):
    """Cross-PROGRAM comparator (documented tolerance): fused and
    unfused whole graphs are two different XLA programs, and CPU
    dot-general partitioning can differ between them in the final bits
    (observed only under full-suite load).  The kernel math itself is
    bitwise-identical (the eager op-level tests above); whole-graph
    forward/gradient parity is asserted to ~2 ULP of f32 instead."""
    np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7, err_msg=msg)


def _close(a, b, dtype, grad=False):
    tol = dict(TOL[dtype])
    if grad:
        tol["atol"] *= 10
    np.testing.assert_allclose(
        np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32),
        **tol)


# ---------------------------------------------------------------------------
# LSTM cell
# ---------------------------------------------------------------------------

def _unfused_lstm(gates, c_prev):
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(g)
    return jax.nn.sigmoid(o) * jnp.tanh(c), c


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(5, 7), (16, 128), (3, 50)])
def test_lstm_cell_lax_bitwise(dtype, shape):
    """Fused-lax forward AND gradient are bit-equal to the unfused
    composition — f32 and bf16, odd/partial-tile shapes included."""
    B, H = shape
    rs = np.random.RandomState(0)
    g = jnp.asarray(rs.randn(B, 4 * H)).astype(dtype)
    c = jnp.asarray(rs.randn(B, H)).astype(dtype)
    h1, c1 = _unfused_lstm(g, c)
    h2, c2 = LC.lstm_cell_lax(g, c)
    assert np.array_equal(np.asarray(h1), np.asarray(h2))
    assert np.array_equal(np.asarray(c1), np.asarray(c2))

    def loss(fn):
        def run(g, c):
            h, cc = fn(g, c)
            return (h.astype(jnp.float32) ** 2).sum() \
                + (cc.astype(jnp.float32) * 3).sum()
        return jax.grad(run, argnums=(0, 1))(g, c)

    for a, b in zip(loss(_unfused_lstm), loss(LC.lstm_cell_lax)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(5, 7), (16, 128)])
def test_lstm_cell_pallas_interpret_parity(dtype, shape):
    """The Pallas kernel pair (interpret=True — the code a TPU compiles)
    matches the unfused composition in forward and vjp within the
    documented tolerance."""
    B, H = shape
    rs = np.random.RandomState(1)
    g = jnp.asarray(rs.randn(B, 4 * H)).astype(dtype)
    c = jnp.asarray(rs.randn(B, H)).astype(dtype)
    h1, c1 = _unfused_lstm(g, c)
    h2, c2 = LC.lstm_cell_pallas(g, c, interpret=True)
    _close(h1, h2, dtype)
    _close(c1, c2, dtype)

    def loss(fn):
        def run(g, c):
            h, cc = fn(g, c)
            return (h.astype(jnp.float32) ** 2).sum() \
                + (cc.astype(jnp.float32) * 3).sum()
        return jax.grad(run, argnums=(0, 1))(g, c)

    ref = loss(_unfused_lstm)
    got = loss(lambda g, c: LC.lstm_cell_pallas(g, c, interpret=True))
    for a, b in zip(ref, got):
        _close(a, b, dtype, grad=True)


# ---------------------------------------------------------------------------
# BatchNorm + activation
# ---------------------------------------------------------------------------

def _bn_inputs(dtype, shape=(4, 6, 5, 5)):
    rs = np.random.RandomState(2)
    x = jnp.asarray(rs.randn(*shape)).astype(dtype)
    c = shape[1]
    gam = jnp.asarray(rs.rand(c) + 0.5).astype(dtype)
    bet = jnp.asarray(rs.randn(c)).astype(dtype)
    mm = jnp.zeros(c, jnp.float32)
    mv = jnp.ones(c, jnp.float32)
    return x, gam, bet, mm, mv


@pytest.mark.parametrize("act", ["relu", "tanh", None])
@pytest.mark.parametrize("is_train", [True, False])
def test_bn_act_lax_bitwise(act, is_train):
    x, gam, bet, mm, mv = _bn_inputs("float32")
    o1, m1, v1 = NN.batch_norm(x, gam, bet, mm, mv, fix_gamma=False,
                               is_train=is_train)
    if act:
        o1 = NN.activation(o1, act_type=act)
    o2, m2, v2 = BA.fused_bn_act_lax(x, gam, bet, mm, mv, act_type=act,
                                     fix_gamma=False, is_train=is_train)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(m1), np.asarray(m2))
    assert np.array_equal(np.asarray(v1), np.asarray(v2))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", ["relu", "sigmoid", "tanh"])
def test_bn_act_pallas_interpret_parity(dtype, act):
    """Pallas normalize+activate kernel pair vs the unfused graph:
    forward and all three input gradients (the backward kernel's
    per-block partial reductions included) — odd channel/row counts."""
    x, gam, bet, mm, mv = _bn_inputs(dtype, shape=(3, 5, 7, 3))

    def ref(x, gam, bet):
        o, _, _ = NN.batch_norm(x, gam, bet, mm, mv, fix_gamma=False,
                                is_train=True)
        return NN.activation(o, act_type=act)

    def pal(x, gam, bet):
        o, _, _ = BA.fused_bn_act_pallas(
            x, gam, bet, mm, mv, act_type=act, fix_gamma=False,
            is_train=True, interpret=True)
        return o

    _close(ref(x, gam, bet), pal(x, gam, bet), dtype)
    g1 = jax.grad(lambda *a: (ref(*a).astype(jnp.float32) ** 2).sum(),
                  argnums=(0, 1, 2))(x, gam, bet)
    g2 = jax.grad(lambda *a: (pal(*a).astype(jnp.float32) ** 2).sum(),
                  argnums=(0, 1, 2))(x, gam, bet)
    for a, b in zip(g1, g2):
        _close(a, b, dtype, grad=True)


def test_bn_fold_matches_unfused_eval():
    """conv -> BN(+relu) inference with folded weights equals the
    unfused graph within the DOCUMENTED fold tolerance (float
    reassociation: w*s convolved vs conv then scaled)."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 3, 8, 8).astype("f"))
    w = jnp.asarray(rs.randn(6, 3, 3, 3).astype("f") * 0.2)
    b = jnp.asarray(rs.randn(6).astype("f") * 0.1)
    gam = jnp.asarray(rs.rand(6).astype("f") + 0.5)
    bet = jnp.asarray(rs.randn(6).astype("f"))
    mm = jnp.asarray(rs.randn(6).astype("f") * 0.1)
    mv = jnp.asarray(rs.rand(6).astype("f") + 0.5)

    conv = NN.convolution(x, w, b, kernel=(3, 3), pad=(1, 1), num_filter=6)
    ref, _, _ = NN.batch_norm(conv, gam, bet, mm, mv, fix_gamma=False,
                              is_train=False)
    ref = NN.activation(ref, act_type="relu")
    w2, b2 = BA.fold_bn_into_conv(w, b, gam, bet, mm, mv, fix_gamma=False)
    got = NN.activation(
        NN.convolution(x, w2, b2, kernel=(3, 3), pad=(1, 1), num_filter=6),
        act_type="relu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _exact_attention(q, k, v, causal):
    Tq, Tk = q.shape[1], k.shape[1]
    # scale as a reciprocal MULTIPLY — the exact op full_attention uses,
    # so the =0 route can be compared bitwise
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
        * (1.0 / np.sqrt(q.shape[-1]))
    if causal:
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(scores, axis=-1), v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T", [16, 37])
def test_flash_attention_parity(dtype, causal, T):
    """Tiled online-softmax (lax scan AND the Pallas kernel in
    interpret mode) vs exact attention — non-block-aligned T included;
    forward + gradient.  The streaming softmax reassociates the exp
    sums, so this is the documented-tolerance comparison."""
    rs = np.random.RandomState(4)
    mk = lambda: jnp.asarray(rs.randn(2, T, 3, 8)).astype(dtype)
    q, k, v = mk(), mk(), mk()
    ref = _exact_attention(q, k, v, causal)
    fl = FA.flash_attention_lax(q, k, v, causal=causal, block_k=16)
    fp = FA.flash_attention_pallas(q, k, v, causal=causal, block=16,
                                   interpret=True)
    _close(ref, fl, dtype)
    _close(ref, fp, dtype)
    if dtype == "float32":
        gr = jax.grad(lambda q: (_exact_attention(q, k, v, causal)
                                 ** 2).sum())(q)
        gl = jax.grad(lambda q: (FA.flash_attention_lax(
            q, k, v, causal=causal, block_k=16) ** 2).sum())(q)
        gp = jax.grad(lambda q: (FA.flash_attention_pallas(
            q, k, v, causal=causal, block=16, interpret=True)
            ** 2).sum())(q)
        _close(gr, gl, dtype, grad=True)
        _close(gr, gp, dtype, grad=True)


def test_full_attention_routes_to_flash(monkeypatch):
    """ring_attention.full_attention composes with the flash kernel for
    long sequences when enabled, and restores the exact-softmax graph
    under MXTPU_FUSED_KERNELS=0."""
    from mxnet_tpu.parallel import ring_attention as RA
    rs = np.random.RandomState(5)
    mk = lambda: jnp.asarray(rs.randn(2, 40, 2, 8).astype("f"))
    q, k, v = mk(), mk(), mk()
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    off = RA.full_attention(q, k, v, causal=True)
    assert np.array_equal(np.asarray(off),
                          np.asarray(_exact_attention(q, k, v, True)))
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    monkeypatch.setenv("MXTPU_FLASH_BLOCK", "16")
    on = RA.full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# causal grouped-query attention: the compiled tier against the lax tier
# ---------------------------------------------------------------------------

def _gqa_operands(hq, hkv, d, dv, t, dtype, seed=6):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(2, t, h, w).astype("f")).astype(dtype)
                 for h, w in ((hq, d), (hkv, d), (hkv, dv)))


def _gqa_both(fn, q, k, v):
    """Output and the three gradients of a weighted sum of it."""
    w = jnp.asarray(np.random.RandomState(7).randn(*q.shape[:3],
                                                   v.shape[-1]), "f")
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                     argnums=(0, 1, 2))(q, k, v)
    return (fn(q, k, v),) + grads


def _gqa_routes(since, kernel="gqa_attention"):
    import time
    from mxnet_tpu import profiler
    return [r["ids"] for r in profiler.spans(since, time.perf_counter())
            if r["name"] == "kernel.route"
            and r["ids"].get("kernel") == kernel]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("hq,hkv,d,dv,t,tiles", [
    (4, 4, 192, 128, 384, None),          # latent attention: keys padded
    (8, 2, 128, 128, 384, None),          # four query heads a key head
    (8, 1, 256, 256, 384, None),          # eight, 256 wide
    (8, 2, 128, 128, 512, (256, 128)),    # the diagonal crosses two blocks
    (4, 2, 128, 128, 512, (64, 256)),     # a key block over four row blocks
], ids=["mla_192_128", "g4_128", "g8_256", "tall_rows", "wide_keys"])
def test_gqa_attention_compiled_tier_matches_the_lax_tier(
        hq, hkv, d, dv, t, tiles, dtype):
    """The kernels in the interpreter against ``gqa_attention``'s lax body,
    output and all three gradients, over more than one query and key block
    (blocks on the diagonal, below it and never visited above it): float32
    at the documented tolerance; bfloat16 finite and, by norm, as close as
    two bfloat16 programs are."""
    q, k, v = _gqa_operands(hq, hkv, d, dv, t, dtype)
    scale = float(d ** -0.5)
    want = _gqa_both(lambda *a: FA._gqa(*a, scale, 128), q, k, v)
    got = _gqa_both(lambda *a: FA.gqa_attention_pallas(
        *a, tiles=tiles, interpret=True), q, k, v)
    for i, (a, b) in enumerate(zip(got, want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        if dtype == "float32":
            _close(a, b, dtype, grad=i > 0)
            continue
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert np.isfinite(a).all()
        assert np.linalg.norm(a - b) < 0.01 * np.linalg.norm(b)


def test_gqa_attention_takes_the_compiled_tier_for_aligned_operands(
        compiled_tier):
    """Through ``gqa_attention`` as a program lowered for a TPU resolves
    it: the kernels' result, one ``kernel.route`` event and the counter."""
    import time
    from mxnet_tpu import profiler
    q, k, v = _gqa_operands(4, 2, 128, 128, 256, "float32")
    before = profiler.counters().get("kernel.gqa_attention.pallas", 0)
    since = time.perf_counter()
    out = FA.gqa_attention(q, k, v)
    assert _gqa_routes(since) == [{"kernel": "gqa_attention",
                                   "tier": "pallas", "reason": "aligned"}]
    assert profiler.counters()["kernel.gqa_attention.pallas"] == before + 1
    _close(out, FA._gqa(q, k, v, 128 ** -0.5, 512), "float32")
    assert "mxtpu_gqa_attention_fwd" in str(jax.make_jaxpr(
        lambda *a: FA.gqa_attention(*a))(q, k, v))


@pytest.mark.parametrize("hq,hkv,d,dv,t,mesh,reason", [
    (4, 2, 128, 128, 200, False, "shapes"),   # positions: no whole block
    (4, 2, 128, 64, 256, False, "shapes"),    # value heads of half a tile
    (4, 2, 16, 128, 256, False, "shapes"),    # keys that pad eightfold
    (4, 2, 128, 128, 256, True, "mesh"),
], ids=["tail", "narrow_values", "narrow_keys", "mesh"])
def test_gqa_attention_falls_back_to_the_lax_tier(compiled_tier, hq, hkv, d,
                                                  dv, t, mesh, reason):
    """What the kernels do not take keeps to the lax tier even where the
    platform would take them, bit for bit, and says why."""
    import contextlib
    import time
    from mxnet_tpu import profiler
    from mxnet_tpu.kernels import auto_partitioned
    q, k, v = _gqa_operands(hq, hkv, d, dv, t, "float32")
    before = profiler.counters().get("kernel.gqa_attention.lax", 0)
    since = time.perf_counter()
    with auto_partitioned() if mesh else contextlib.nullcontext():
        got = _gqa_both(FA.gqa_attention, q, k, v)
        # (a fresh function: a trace is cached by the function traced)
        text = str(jax.make_jaxpr(lambda *a: FA.gqa_attention(*a))(q, k, v))
    assert "pallas_call" not in text
    assert _gqa_routes(since) == [{"kernel": "gqa_attention", "tier": "lax",
                                   "reason": reason}] * 3
    assert profiler.counters()["kernel.gqa_attention.lax"] == before + 3
    want = _gqa_both(lambda *a: FA._gqa(*a, float(d ** -0.5), 512), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_mxlint_finds_the_gqa_attention_kernels_behind_their_vjp():
    """``graph-pallas-no-vjp`` on a graph that holds the op with both
    tiers traced (``lax.platform_dependent``): the kernels are there, and
    they are behind their ``custom_vjp``."""
    from mxnet_tpu.analysis import graph_lint
    from mxnet_tpu.ops.contrib import gq_attention
    args = _gqa_operands(4, 2, 128, 128, 256, "float32")

    def graph(*a):
        return gq_attention(*a)
    report = graph_lint.lint_jit(graph, *args, expect_allgather=False,
                                 min_donate_bytes=0)
    assert "pallas_call" in str(jax.make_jaxpr(graph)(*args))
    assert "graph-pallas-no-vjp" not in {f.rule for f in report.findings}, \
        report.format_text()


# ---------------------------------------------------------------------------
# depthwise causal convolution: the compiled tier against the lax tier
# ---------------------------------------------------------------------------

def _conv_operands(t, c, k, dtype, rows=2, seed=8):
    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(rows, t, c).astype("f")).astype(dtype),
            jnp.asarray(0.5 * rs.randn(c, k).astype("f")).astype(dtype),
            jnp.asarray(rs.randn(rows, t, c).astype("f")).astype(dtype))


def _conv_both(fn, x, w, dy):
    """y, dx and dw under the cotangent ``dy``."""
    y, vjp = jax.vjp(fn, x, w)
    return (y,) + vjp(dy)


def _conv_routes(since):
    return _gqa_routes(since, kernel="causal_conv")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("act", [None, "silu"])
@pytest.mark.parametrize("taps", [2, 4])
def test_causal_conv_compiled_tier_matches_the_lax_tier(taps, act, dtype):
    """The kernels in the interpreter against the lax tier, y, dx and dw,
    over three position blocks and two channel blocks of two rows (the
    taps reach across a block's boundary, both ways): float32 to 1e-5 of
    the largest entry, bfloat16 within the rounding of one store."""
    x, w, dy = _conv_operands(96, 256, taps, dtype)
    want = _conv_both(lambda *a: CC.causal_conv_lax(*a, taps, act), x, w, dy)
    got = _conv_both(lambda *a: CC.causal_conv_pallas(
        *a, taps, act, tiles=(32, 128), interpret=True), x, w, dy)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = (np.asarray(v.astype(jnp.float32)) for v in (a, b))
        tol = 1e-5 if dtype == "float32" else 2.0 ** -8
        np.testing.assert_allclose(a, b, rtol=tol,
                                   atol=tol * np.abs(b).max())


@pytest.mark.parametrize("act", [None, "silu"])
def test_causal_conv_kernels_keep_a_row_to_itself(act):
    """Zeros before a row's start for y and past its end for dx, whatever
    the rows beside it hold: the second row of a batch of two reads as it
    does alone, and a position hears of nothing after it."""
    x, w, dy = _conv_operands(64, 128, 4, "float32")

    def run(x, dy):
        return _conv_both(lambda *a: CC.causal_conv_pallas(
            *a, 4, act, tiles=(32, 128), interpret=True), x, w, dy)
    y, dx, _ = run(x, dy)
    loud = x.at[0].set(1e4), dy.at[0].set(1e4)
    for got, alone in zip(run(*loud)[:2], run(x[1:], dy[1:])[:2]):
        np.testing.assert_array_equal(got[1:], alone)
    # y[t] reads x[t-3 .. t] and dx[t] reads dy[t .. t+3], of its own row
    later = run(x.at[:, 40:].set(7.0), dy)[0]
    np.testing.assert_array_equal(later[:, :40], y[:, :40])
    earlier = run(x, dy.at[:, :40].set(7.0))[1]
    np.testing.assert_array_equal(earlier[:, 40:], dx[:, 40:])
    np.testing.assert_array_equal(
        y[:, 0], np.asarray(CC.causal_conv_lax(x[:, :1], w, 4, act))[:, 0])


def test_causal_conv_takes_the_compiled_tier_for_aligned_operands(
        compiled_tier):
    """Through the op as a program lowered for a TPU resolves it: the
    kernels' result, one ``kernel.route`` event and the counter."""
    import time
    from mxnet_tpu import profiler
    x, w, _ = _conv_operands(64, 256, 4, "float32")
    before = profiler.counters().get("kernel.causal_conv.pallas", 0)
    since = time.perf_counter()
    out = NN.causal_conv1d(x, w, kernel=4, act_type="silu")
    assert _conv_routes(since) == [{"kernel": "causal_conv",
                                    "tier": "pallas", "reason": "aligned"}]
    assert profiler.counters()["kernel.causal_conv.pallas"] == before + 1
    _close(out, CC.causal_conv_lax(x, w, 4, "silu"), "float32")
    assert "mxtpu_causal_conv_fwd" in str(jax.make_jaxpr(
        lambda *a: NN.causal_conv1d(*a, kernel=4, act_type="silu"))(x, w))


@pytest.mark.parametrize("t,c,taps,act,mesh,reason", [
    (64, 100, 4, "silu", False, "shapes"),    # channels: no whole lane tile
    (70, 128, 4, "silu", False, "shapes"),    # a ragged row
    (64, 128, 4, "tanh", False, "shapes"),    # an activation not the kernels'
    (64, 128, 9, None, False, "shapes"),      # more taps than a halo holds
    (64, 128, 4, "silu", True, "mesh"),
], ids=["channels", "ragged", "tanh", "taps", "mesh"])
def test_causal_conv_falls_back_to_the_lax_tier(compiled_tier, t, c, taps,
                                                act, mesh, reason):
    """What the kernels do not take keeps to the lax tier even where the
    platform would take them, bit for bit, and says why."""
    import contextlib
    import time
    from mxnet_tpu import profiler
    from mxnet_tpu.kernels import auto_partitioned
    x, w, dy = _conv_operands(t, c, taps, "float32")

    def op(x, w):
        return NN.causal_conv1d(x, w, kernel=taps, act_type=act)
    before = profiler.counters().get("kernel.causal_conv.lax", 0)
    since = time.perf_counter()
    with auto_partitioned() if mesh else contextlib.nullcontext():
        got = _conv_both(op, x, w, dy)
        # (a fresh function: a trace is cached by the function traced)
        text = str(jax.make_jaxpr(lambda *a: op(*a))(x, w))
    assert "pallas_call" not in text
    assert _conv_routes(since) == [{"kernel": "causal_conv", "tier": "lax",
                                    "reason": reason}] * 2
    assert profiler.counters()["kernel.causal_conv.lax"] == before + 2
    want = _conv_both(lambda *a: CC.causal_conv_lax(*a, taps, act), x, w, dy)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_causal_conv_on_the_cpu_is_the_lax_tier_bit_for_bit():
    """Routed to the kernels, lowered for the CPU: ``platform_dependent``
    takes the lax branch, which is the op as it was."""
    x, w, dy = _conv_operands(64, 256, 4, "bfloat16")
    got = _conv_both(lambda *a: NN.causal_conv1d(
        *a, kernel=4, act_type="silu"), x, w, dy)
    want = _conv_both(lambda *a: CC.causal_conv_lax(*a, 4, "silu"), x, w, dy)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))


def test_mxlint_finds_the_causal_conv_kernels_behind_their_vjp():
    """``graph-pallas-no-vjp`` on a graph that holds the op with both tiers
    traced: the kernels are there, and they are behind their
    ``custom_vjp``."""
    from mxnet_tpu.analysis import graph_lint
    x, w, _ = _conv_operands(64, 128, 4, "float32")

    def graph(*a):
        return NN.causal_conv1d(*a, kernel=4, act_type="silu")
    report = graph_lint.lint_jit(graph, x, w, expect_allgather=False,
                                 min_donate_bytes=0)
    assert "pallas_call" in str(jax.make_jaxpr(graph)(x, w))
    assert "graph-pallas-no-vjp" not in {f.rule for f in report.findings}, \
        report.format_text()


# ---------------------------------------------------------------------------
# routing / registry
# ---------------------------------------------------------------------------

def test_env_routing(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    assert enabled_kernels() == frozenset()
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    assert fused_enabled("lstm_cell") and fused_enabled("bn_act")
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "lstm_cell, bn_act")
    assert enabled_kernels() == frozenset({"lstm_cell", "bn_act"})
    assert not fused_enabled("flash_attention")
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "lstm_cell,bogus_kernel")
    assert enabled_kernels() == frozenset({"lstm_cell"})


# ---------------------------------------------------------------------------
# executor integration: BN fusion / folding, fused plans, parity with off
# ---------------------------------------------------------------------------

def _bn_net():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="c1")
    net = mx.sym.BatchNorm(net, fix_gamma=False, name="bn1")
    net = mx.sym.Activation(net, act_type="relu", name="r1")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _named_init(ex, skip=("data", "softmax_label")):
    for name in sorted(ex.arg_dict):
        if name in skip:
            continue
        r = np.random.RandomState(abs(hash(name)) % (2 ** 31))
        ex.arg_dict[name][:] = \
            (r.rand(*ex.arg_dict[name].shape).astype("f") - 0.5) * 0.4
    for name in ex.aux_dict:
        ex.aux_dict[name][:] = 1.0 if name.endswith("var") else 0.0


def _run_bn_net(train):
    rs = np.random.RandomState(0)
    net = _bn_net()
    ex = net.simple_bind(mx.cpu(), data=(4, 3, 8, 8))
    _named_init(ex)
    ex.arg_dict["data"][:] = rs.rand(4, 3, 8, 8).astype("f")
    ex.arg_dict["softmax_label"][:] = rs.randint(0, 10, 4).astype("f")
    out = ex.forward(is_train=train)[0].asnumpy()
    grads, aux = {}, {}
    if train:
        ex.backward()
        grads = {k: v.asnumpy() for k, v in ex.grad_dict.items()
                 if v is not None}
        aux = {k: v.asnumpy() for k, v in ex.aux_dict.items()}
    return out, grads, aux


def test_executor_bn_fusion_train_parity(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    o_on, g_on, a_on = _run_bn_net(train=True)
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    o_off, g_off, a_off = _run_bn_net(train=True)
    _xprog_close(o_on, o_off, "forward")
    for k in g_off:
        _xprog_close(g_on[k], g_off[k], k)
    for k in a_off:
        _xprog_close(a_on[k], a_off[k], k)


def test_executor_bn_fold_eval_tolerance(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    o_on, _, _ = _run_bn_net(train=False)
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    o_off, _, _ = _run_bn_net(train=False)
    np.testing.assert_allclose(o_on, o_off, rtol=1e-5, atol=1e-6)


def test_fused_plan_overrides_and_off_restores_plain(monkeypatch):
    """Plan introspection: the fusion pass installs exactly one fused
    BN entry + one passthrough Activation entry per pair, and
    MXTPU_FUSED_KERNELS=0 leaves the plan untouched (the exact pre-PR
    program)."""
    from mxnet_tpu.executor import _fuse_bn_plan, _node_plan
    net = _bn_net()
    plan = _node_plan(net)
    refs = [(id(n), i) for n, i in net._outputs]
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    fused = _fuse_bn_plan(plan, refs)
    overridden = [e for e in fused if e[5] is not None]
    assert len(overridden) == 2
    names = sorted(e[0].name for e in overridden)
    assert names == ["bn1", "r1"]
    # the BN entry carries the conv's inputs as extra refs (fold path)
    bn_entry = next(e for e in fused if e[0].name == "bn1")
    assert len(bn_entry[5][1]) == 3          # conv data, weight, bias
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    assert _fuse_bn_plan(plan, refs) is plan
    # bn_act alone (no fold): fused entries but no extra conv refs
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "bn_act")
    act_only = _fuse_bn_plan(plan, refs)
    bn_entry = next(e for e in act_only if e[0].name == "bn1")
    assert bn_entry[5] is not None and len(bn_entry[5][1]) == 0


def test_bn_output_consumed_twice_not_fused(monkeypatch):
    """A BatchNorm whose output feeds anything besides its Activation
    must stay unfused — the fusion is only sound for a private pair."""
    from mxnet_tpu.executor import _fuse_bn_plan, _node_plan
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "bn_act")
    data = mx.sym.Variable("data")
    bn = mx.sym.BatchNorm(data, name="bnx")
    act = mx.sym.Activation(bn, act_type="relu", name="rx")
    net = mx.sym.Group([mx.sym.sum(act), mx.sym.sum(bn)])
    plan = _node_plan(net)
    refs = [(id(n), i) for n, i in net._outputs]
    assert _fuse_bn_plan(plan, refs) is plan


# ---------------------------------------------------------------------------
# LSTM consumers: the fused RNN scan and the symbolic LSTMCell
# ---------------------------------------------------------------------------

def _run_lstm_lm():
    from mxnet_tpu.models import lstm_lm
    rs = np.random.RandomState(6)
    sym, _, _ = lstm_lm.lstm_lm_sym(6, 50, num_embed=8, num_hidden=8,
                                    num_layers=2)
    ex = sym.simple_bind(mx.cpu(), data=(3, 6), softmax_label=(3, 6))
    _named_init(ex)
    ex.arg_dict["data"][:] = rs.randint(0, 50, (3, 6)).astype("f")
    ex.arg_dict["softmax_label"][:] = rs.randint(0, 50, (3, 6)).astype("f")
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    return out, {k: v.asnumpy() for k, v in ex.grad_dict.items()
                 if v is not None}


def test_rnn_op_fused_scan_parity(monkeypatch):
    """The fused RNN op's lax.scan with the fused cell matches the
    unfused scan — forward and every gradient (cross-program
    comparator: see _xprog_close)."""
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    o1, g1 = _run_lstm_lm()
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    o2, g2 = _run_lstm_lm()
    _xprog_close(o1, o2, "forward")
    for k in g2:
        _xprog_close(g1[k], g2[k], k)


def _run_lstm_cell_sym():
    from mxnet_tpu.rnn import rnn_cell as RC
    rs = np.random.RandomState(7)
    cell = RC.LSTMCell(16, prefix="l_")
    outs, _ = cell.unroll(5, inputs=mx.sym.Variable("data"),
                          merge_outputs=True)
    net = mx.sym.sum(outs)
    ex = net.simple_bind(mx.cpu(), data=(2, 5, 8))
    _named_init(ex, skip=("data",))
    ex.arg_dict["data"][:] = rs.rand(2, 5, 8).astype("f")
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    grads = {k: v.asnumpy() for k, v in ex.grad_dict.items()
             if v is not None}
    return out, grads, net.get_internals().list_outputs()


def test_lstm_cell_symbolic_parity_and_graph_shape(monkeypatch):
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "1")
    o1, g1, internals_on = _run_lstm_cell_sym()
    monkeypatch.setenv("MXTPU_FUSED_KERNELS", "0")
    o2, g2, internals_off = _run_lstm_cell_sym()
    _xprog_close(o1, o2, "forward")
    for k in g2:
        _xprog_close(g1[k], g2[k], k)
    # graph structure: fused op present when on; =0 restores the exact
    # pre-PR slice/activation graph
    assert any("fused" in n for n in internals_on)
    assert not any("fused" in n for n in internals_off)
    assert any("slice" in n for n in internals_off)


# ---------------------------------------------------------------------------
# trainer guard carry (the single-fetch change riding with this PR)
# ---------------------------------------------------------------------------

def test_trainer_guard_counters_are_one_stacked_carry():
    """The in-graph skip counters travel as ONE i32[3] array so each
    flush costs a single device->host fetch (three scalar fetches were
    per-step host work on the dispatch-bound LSTM path)."""
    from mxnet_tpu.parallel import SPMDTrainer
    rs = np.random.RandomState(8)
    data = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(data, num_hidden=8, name="fc1"),
        name="softmax")
    tr = SPMDTrainer(net, "sgd", {"learning_rate": 0.1,
                                  "rescale_grad": 0.25}, mesh=None)
    tr.bind([("data", (4, 6))], [("softmax_label", (4,))])
    tr.init_params(mx.initializer.Xavier())
    X = rs.rand(4, 6).astype("f")
    y = rs.randint(0, 8, 4).astype("f")
    try:
        tr.step(X, y)
        assert tuple(tr._guard_acc.shape) == (3,)
        assert tr.skipped_steps == 0
        tr.step(np.full_like(X, np.nan), y)
        tr.flush_step_guard()
        assert tr.skipped_steps == 1
        assert tr.consecutive_bad_steps == 1
        tr.step(X, y)
        tr.flush_step_guard()
        assert tr.consecutive_bad_steps == 0
    finally:
        tr.close()


# ---------------------------------------------------------------------------
# compiled tier: the router picks it by the platform a program is LOWERED
# for; on a chip (MXTPU_TEST_PLATFORM=tpu) Mosaic compiles each kernel at
# the shapes the supported models present and it must match its lax tier
# ---------------------------------------------------------------------------

needs_chip = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="compiles the Pallas kernels with Mosaic: send through the "
           "chip tool under MXTPU_TEST_PLATFORM=tpu")


def _sq(x):
    return (x.astype(jnp.float32) ** 2).sum()


def _compiled_case(name):
    """(routed fn, lax fn, argument shapes, kernel names) per kernel,
    both fns returning ``(loss, outputs)`` for value_and_grad."""
    if name == "bn_act":        # ResNet-50 / Inception-BN bn0
        args = ((256, 64, 112, 112), (64,), (64,))
        mm, mv = jnp.zeros(64, jnp.float32), jnp.ones(64, jnp.float32)

        def wrap(fn):
            def f(x, g, b):
                o, m, v = fn(x, g, b, mm, mv, act_type="relu",
                             fix_gamma=False, is_train=True)
                return (o.astype(jnp.float32) ** 2).mean(), (o, m, v)
            return f
        return wrap(BA.fused_bn_act), wrap(BA.fused_bn_act_lax), args, \
            ("mxtpu_bn_act_fwd", "mxtpu_bn_act_bwd")
    if name == "lstm_cell":     # an aligned cell: H=256, rows % 8 == 0
        args = ((64, 1024), (64, 256))

        def wrap(fn):
            def f(g, c):
                h, c2 = fn(g, c)
                return _sq(h) + c2.astype(jnp.float32).sum(), (h, c2)
            return f
        return wrap(LC.lstm_cell), wrap(LC.lstm_cell_lax), args, \
            ("mxtpu_lstm_cell_fwd", "mxtpu_lstm_cell_bwd")
    # example/long-context's attention: B=4, T=512, 4 heads of 16
    args = ((4, 512, 4, 16),) * 3

    def wrap(fn):
        def f(q, k, v):
            o = fn(q, k, v, causal=True)
            return _sq(o), o
        return f
    return wrap(FA.flash_attention), wrap(FA.flash_attention_lax), args, \
        ("mxtpu_flash_attention_fwd",)


_KERNELS = ["bn_act", "lstm_cell", "flash_attention"]


@pytest.mark.parametrize("name", _KERNELS)
def test_router_picks_tier_by_lowering_platform(name):
    """The same routed call lowers to the Mosaic kernels in a TPU
    program and to plain lax in a CPU one — decided by where the
    computation is placed, not by which backends the host happens to
    have (``kernels.by_platform``)."""
    from mxnet_tpu.kernels import compiled_kernels
    routed, _, shapes, names = _compiled_case(name)
    specs = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    traced = jax.jit(jax.value_and_grad(routed, has_aux=True)).trace(*specs)
    tpu = traced.lower(lowering_platforms=("tpu",)).as_text()
    cpu = traced.lower(lowering_platforms=("cpu",)).as_text()
    assert set(compiled_kernels(tpu)) == set(names)
    assert compiled_kernels(cpu) == {}


@needs_chip
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", _KERNELS)
def test_compiled_tier_matches_lax_on_the_chip(name, dtype):
    """Forward and backward of the Mosaic-compiled kernel against the
    fused-lax tier, at the model's shape, within the documented
    tolerances.  (flash attention's bf16 gradient is compared in f32
    only: the lax tier's own bf16 gradient is not finite on the chip.)"""
    from mxnet_tpu.kernels import compiled_kernels
    routed, lax_fn, shapes, names = _compiled_case(name)
    rs = np.random.RandomState(9)
    args = [jnp.asarray(rs.randn(*s).astype("f")).astype(dtype)
            for s in shapes]
    argnums = tuple(range(len(args)))
    fp = jax.jit(jax.value_and_grad(routed, argnums, has_aux=True))
    fl = jax.jit(jax.value_and_grad(lax_fn, argnums, has_aux=True))
    assert set(compiled_kernels(
        fp.lower(*args).compile().as_text())) == set(names)
    (_, outs_p), grads_p = fp(*args)
    (_, outs_l), grads_l = fl(*args)
    for a, b in zip(jax.tree.leaves(outs_p), jax.tree.leaves(outs_l)):
        _close(a, b, dtype)
    if name == "flash_attention" and dtype == "bfloat16":
        return
    for a, b in zip(grads_p, grads_l):
        _close(a, b, dtype, grad=True)


@pytest.mark.parametrize("sync,ndev,want", [
    ("allreduce", 1, True), ("allreduce", 4, False), ("zero3", 4, True)])
def test_trainer_step_lowers_for_tpu_at_every_placement(sync, ndev, want):
    """jax refuses to lower a Mosaic kernel in a program the SPMD
    partitioner splits over devices — the first ResNet-50 step on four
    real chips died of it.  A GSPMD-tier step on a multi-device mesh must
    keep to the lax tier (``kernels.auto_partitioned``); a single-device
    step and the zero3 ``shard_map`` body keep the compiled kernel.
    Lowering for the TPU platform from the CPU mesh shows all three."""
    from mxnet_tpu.kernels import compiled_kernels
    from mxnet_tpu.parallel import SPMDTrainer, default_mesh
    if len(jax.devices()) < ndev:
        pytest.skip("needs %d devices" % ndev)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), pad=(1, 1),
                             name="c1")
    net = mx.sym.Activation(mx.sym.BatchNorm(net, name="bn1",
                                             fix_gamma=False),
                            act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(
        mx.sym.Flatten(net), num_hidden=4, name="fc"), name="softmax")
    tr = SPMDTrainer(net, "sgd", {"learning_rate": 0.1,
                                  "rescale_grad": 0.125},
                     mesh=default_mesh(devices=jax.devices()[:ndev]),
                     grad_sync=sync)
    try:
        # bn1 sees (8, 8, 16, 8): C % 8 == 0 and H*W == 128 -> aligned
        tr.bind([("data", (8, 3, 16, 8))], [("softmax_label", (8,))])
        tr.init_params(mx.initializer.Xavier())
        args = tr._example_args(np.zeros((8, 3, 16, 8), "f"),
                                np.zeros(8, "f"))
        text = tr._step_fn.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    finally:
        tr.close()
    assert bool(compiled_kernels(text)) is want
