"""The window of ``gqa_attention`` and the cut of the ``afmoe`` family
(``models/trinity.py``), at tiny sizes on the CPU: sliding-window attention
on the lax tier against a naive masked softmax and on the compiled tier (in
the interpreter) against the lax tier, the windowed schedule against the
tiles that hold a visible pair, the whole model against the plain reference
(``benchmark/reference/trinity.py``) through ``SPMDModule.fit``, the 16
shares of the expert layer tied to the whole layer, the configuration's
parameter count, and three planted faults that the toy cell's limits
catch."""
import json
import os
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import profiler
from mxnet_tpu.kernels import flash_attention as FA

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

RS = np.random.RandomState

TYPES = (["sliding_attention"] * 3 + ["full_attention"]) * 2
TOY = dict(hidden_size=32, num_hidden_layers=6, layer_types=TYPES,
           sliding_window=24, num_attention_heads=4, num_key_value_heads=2,
           head_dim=8, rope_theta=10000, num_dense_layers=2,
           intermediate_size=64, num_experts_per_tok=4,
           moe_intermediate_size=16, num_shared_experts=1,
           score_func="sigmoid", route_norm=True, route_scale=2.826,
           mup_enabled=True, rms_norm_eps=1e-5, vocab_size=300)


# -- the window in gqa_attention ----------------------------------------------

def _naive(q, k, v, scale, window):
    """Masked softmax over explicit (T x T) scores, float32."""
    f32 = jnp.float32
    t, group = q.shape[1], q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x.astype(f32), group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), k) * scale
    p, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = (j <= p) & (j > p - window) if window else j <= p
    prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", prob, v).astype(q.dtype)


def _operands(hq, hkv, d, t, dtype, seed=6):
    rs = RS(seed)
    return tuple(jnp.asarray(rs.randn(2, t, h, d).astype("f")).astype(dtype)
                 for h in (hq, hkv, hkv))


def _both(fn, q, k, v):
    """Output and the three gradients of a weighted sum of it."""
    w = jnp.asarray(RS(7).randn(*q.shape[:3], v.shape[-1]), "f")
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                     argnums=(0, 1, 2))(q, k, v)
    return (fn(q, k, v),) + grads


def _agree(got, want, dtype):
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        assert np.isfinite(a).all()
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-4,
                                       atol=2e-5 * max(np.abs(b).max(), 1.0))
        else:                  # as close as two bfloat16 programs are
            assert np.linalg.norm(a - b) <= 0.02 * np.linalg.norm(b) \
                + 1e-4 * np.sqrt(a.size)


#: windows against rows in blocks of 64: smaller than a block, one block,
#: several blocks, not a multiple of the block, all the positions and more
WINDOWS = [1, 16, 64, 100, 128, 200, 256, 300]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_lax_tier_matches_a_naive_masked_softmax(window, dtype):
    """Forward and all three gradients, two query heads a key head, rows
    in blocks of 64 of 256 positions; a window of all the positions or
    more is today's causal attention to the bit."""
    q, k, v = _operands(4, 2, 16, 256, dtype)
    scale = 0.25
    since = time.perf_counter()
    got = _both(lambda *a: FA.gqa_attention(*a, block_q=64, window=window),
                q, k, v)
    _agree(got, _both(lambda *a: _naive(*a, scale, window), q, k, v), dtype)
    events = [r["ids"] for r in profiler.spans(since, time.perf_counter())
              if r["name"] == "kernel.route"]
    if window >= 256:
        for a, b in zip(got, _both(lambda *a: FA._gqa(*a, scale, 64),
                                   q, k, v)):
            np.testing.assert_array_equal(a, b)
        # the event of an unwindowed call carries no window
        assert events[0] == {"kernel": "gqa_attention", "tier": "lax",
                             "reason": "shapes"}
        return
    # four row blocks: each meets the 64-key tiles from its first row's
    # first visible key to the diagonal
    tiles = sum(i - max(0, 64 * i - window + 1) // 64 + 1 for i in range(4))
    assert events[0] == {"kernel": "gqa_attention", "tier": "lax",
                         "reason": "shapes", "window": window,
                         "steps": tiles, "steps_causal": 10}


def test_a_window_leaves_keys_outside_it_without_weight_or_gradient():
    """Moving a key or a value that no row sees through the window changes
    nothing; one inside it does."""
    q, k, v = _operands(2, 1, 16, 96, "float32")
    fn = jax.jit(lambda *a: FA.gqa_attention(*a, block_q=32, window=10))
    out = fn(q, k, v)
    moved = fn(q, k.at[:, 20].add(3.0), v.at[:, 20].add(3.0))
    changed = np.abs(np.asarray(moved - out)).max(axis=(0, 2, 3)) > 0
    assert changed[20:30].all() and not changed[:20].any() \
        and not changed[30:].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("window", WINDOWS)
def test_windowed_compiled_tier_matches_the_lax_tier(window, dtype):
    """The kernels in the interpreter against the lax tier for the same
    windows, output and all three gradients, over four row blocks and two
    key blocks: tiles skipped, tiles the lower edge crosses, tiles whose
    first rows see none of their keys."""
    q, k, v = _operands(4, 2, 128, 256, dtype)
    scale = float(128 ** -0.5)
    want = _both(lambda *a: FA._gqa(*a, scale, 64,
                                    FA._gqa_window(window, 256)), q, k, v)
    got = _both(lambda *a: FA.gqa_attention_pallas(
        *a, tiles=(64, 128), interpret=True, window=window), q, k, v)
    _agree(got, want, dtype)


def test_windowed_call_takes_the_compiled_tier_and_says_its_schedule(
        compiled_tier):
    """Through ``gqa_attention`` as a program lowered for a TPU resolves
    it: the kernels' result, and the route event with the window and both
    schedules' steps."""
    q, k, v = _operands(8, 1, 128, 1024, "float32")
    since = time.perf_counter()
    out = FA.gqa_attention(q, k, v, window=192)
    events = [r["ids"] for r in profiler.spans(since, time.perf_counter())
              if r["name"] == "kernel.route"]
    bq, bk = FA._gqa_tiles(1024, 8)
    assert events == [{
        "kernel": "gqa_attention", "tier": "pallas", "reason": "aligned",
        "window": 192, "steps": len(FA._gqa_steps(1024, bq, bk, 192)[0]),
        "steps_causal": len(FA._gqa_steps(1024, bq, bk)[0])}]
    assert events[0]["steps"] < events[0]["steps_causal"]
    _agree([out], [_naive(q, k, v, 128 ** -0.5, 192)], "float32")
    assert "mxtpu_gqa_attention_fwd" in str(jax.make_jaxpr(
        lambda *a: FA.gqa_attention(*a, window=192))(q, k, v))


@pytest.mark.parametrize("t,bq,bk,window", [
    (8192, 256, 512, 2048), (8192, 256, 128, 2048), (1024, 64, 128, 100),
    (1024, 128, 64, 300), (512, 64, 256, 64), (512, 32, 32, 1),
    (768, 128, 256, 767), (512, 128, 128, 129), (512, 64, 64, None)])
def test_windowed_schedule_visits_the_tiles_that_hold_a_visible_pair(
        t, bq, bk, window):
    """Exactly the (row block, key block) tiles in which some row sees
    some key, each row block's in rising order; of those, the mask goes to
    the ones an edge crosses — some pair of the tile is not visible — and
    to no other; first and last flag each row block's ends."""
    p, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= p) & ((j > p - window) if window else True)
    tiles = seen.reshape(t // bq, bq, t // bk, bk)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    qi, kj = FA._gqa_steps(t, bq, bk, window)
    visited = list(zip(qi.tolist(), kj.tolist()))
    assert visited == [(i, k) for i in range(t // bq)
                       for k in range(t // bk) if some[i, k]]
    for n, (i, k) in enumerate(visited):
        last, crossed = FA._gqa_edges(i, k, bq, bk, window)
        first = bool(FA._gqa_first(i, k, bq, bk, window))
        assert crossed == (not every[i, k]), (i, k)
        assert first == (n == 0 or visited[n - 1][0] != i), (i, k)
        assert last == (n + 1 == len(visited) or visited[n + 1][0] != i)
    if window == 2048:
        # the cell's stages: the band is 43.75 % of the triangle
        assert seen.sum() == 14681088 and np.tril(np.ones((t, t))).sum() \
            == 33558528
        causal = len(FA._gqa_steps(t, bq, bk)[0])
        assert (len(visited), causal) == {512: (140, 272),
                                          128: (504, 1056)}[bk]


def test_a_window_of_all_the_positions_is_the_program_it_was():
    """``window`` 0, None, the positions and more lower to the unwindowed
    jaxpr, on the lax tier and through the op."""
    from mxnet_tpu.ops.contrib import gq_attention
    q, k, v = _operands(4, 2, 16, 128, "float32")

    def text(**kw):
        return str(jax.make_jaxpr(lambda *a: FA.gqa_attention(
            *a, block_q=32, **kw))(q, k, v))
    plain = text()
    for window in (0, None, 128, 5000):
        assert text(window=window) == plain
    assert text(window=127) != plain
    np.testing.assert_array_equal(gq_attention(q, k, v, window=0),
                                  gq_attention(q, k, v))
    _agree([gq_attention(q, k, v, window=40, block_q=32)],
           [_naive(q, k, v, 0.25, 40)], "float32")
    with pytest.raises(ValueError):
        FA.gqa_attention(q, k, v, window=-3)


def test_symbol_infers_shapes_with_a_window():
    q = mx.sym.Variable("q")
    o = mx.sym.GQAttention(query=q, key=mx.sym.Variable("k"),
                           value=mx.sym.Variable("v"), window=24, name="a")
    _, out, _ = o.infer_shape(q=(2, 80, 4, 8), k=(2, 80, 2, 8),
                              v=(2, 80, 2, 8))
    assert out == [(2, 80, 4, 8)]


# -- the model ------------------------------------------------------------------

def _toy_model(seq_len=80, held=4, offset=4, **over):
    from benchmark.reference import trinity as ref
    from mxnet_tpu.models.trinity import trinity_sym
    toy = dict(TOY, **over)
    sym = trinity_sym(seq_len, num_experts=16, num_experts_held=held,
                      expert_offset=offset, **toy)[0]
    cfg = dict(toy, num_experts=held, num_routed_experts=16,
               expert_offset=offset, seq_len=seq_len)
    params, _ = ref.init(jax.random.PRNGKey(0), cfg)
    # larger than the family's 0.02 so that every nonlinearity is exercised
    params = {k: (v * 5 if k.endswith("_weight") else v)
              for k, v in params.items()}
    return sym, cfg, params


def test_symbol_has_the_reference_leaves_and_named_stages():
    from benchmark.reference import trinity as ref
    from mxnet_tpu import models
    assert models.trinity.trinity_sym is models.trinity_sym
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
    nodes = [n for n in sym._nodes() if n.op is not None]
    stages = {n.attrs.get("mirror_stage") for n in nodes}
    assert stages == {None, "l0_swa", "l1_swa", "l2_swa", "l3_attn", "l4_swa",
                      "l5_swa", "l0_mlp", "l1_mlp", "l2_moe", "l3_moe",
                      "l4_moe", "l5_moe"}
    assert [k for k, _ in ref._stages(cfg)] == [
        "swa", "mlp", "swa", "mlp", "swa", "moe", "attn", "moe", "swa", "moe",
        "swa", "moe"]
    # the op table reads these names; a stage holds both of its norms
    names = {n.name: n.attrs.get("mirror_stage") for n in nodes}
    for name in ("l0_swa_core", "l0_swa_q_proj", "l0_swa_gate_proj",
                 "l0_swa_norm", "l0_swa_post_norm", "l0_swa_q_norm",
                 "l0_swa_q_rope", "l0_swa_k_rope"):
        assert names[name] == "l0_swa", name
    assert names["l3_attn_core"] == names["l3_attn_post_norm"] == "l3_attn"
    assert names["l1_mlp_post_norm"] == "l1_mlp"
    assert names["l2_moe_post_norm"] == names["l2_moe_routed"] == "l2_moe"
    # the window and the rotary embedding go with the layer's type
    cores = {n.name: n.attrs for n in nodes if n.name.endswith("_core")}
    assert {k: int(v.get("window", 0)) for k, v in cores.items()} == {
        "l0_swa_core": 24, "l1_swa_core": 24, "l2_swa_core": 24,
        "l3_attn_core": 0, "l4_swa_core": 24, "l5_swa_core": 24}
    rotary = {n.attrs.get("mirror_stage") for n in nodes
              if "RotaryEmbedding" in n.op.name}
    assert rotary == {"l0_swa", "l1_swa", "l2_swa", "l4_swa", "l5_swa"}
    # listed layers past num_hidden_layers are ignored; an unknown type is
    # an error
    with pytest.raises(ValueError):
        _toy_model(layer_types=["sliding_attention", "chunked"] * 3)


@pytest.mark.parametrize("seq_len,held", [(80, 4), (256, 2)])
def test_model_matches_the_reference_through_fit(seq_len, held):
    """Loss of each of three steps, the first gradient as the optimizer
    got it and the change after three steps, float32, through
    ``SPMDModule.fit`` from int32 rows; the step's counters settle in the
    recorder.  Rows of 256 tokens with 2 of 16 experts held run the expert
    layers' blocked path; a window of 24 crosses the lax tier's row
    blocks."""
    from benchmark.reference import common, trinity as ref
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
    # six attention lowerings a trace of the step: five say their window
    routes = [r["ids"] for r in profiler.spans(since=since)
              if r["name"] == "kernel.route"
              and r["ids"]["kernel"] == "gqa_attention"]
    assert len(routes) % 6 == 0 and routes
    assert [r.get("window", 0) for r in routes[:6]] == [24, 24, 24, 0, 24, 24]
    assert all(r["steps"] <= r["steps_causal"] for r in routes
               if "window" in r)
    # four expert layers a step (the two dense layers have none)
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
    bias = [k for k in params if k.endswith("_expert_bias")]
    assert len(bias) == 4
    for k in bias:
        np.testing.assert_array_equal(after[k], np.asarray(params[k]))


def test_the_16_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """128 experts, top-8, 16 shares of 8 as in the cell's deployment: the
    routed parts of all shares summed, plus the shared expert counted
    once, are the uncut reference's expert layer."""
    from benchmark.reference import trinity as ref
    from mxnet_tpu.ops.contrib import routed_experts
    cfg = dict(TOY, num_experts=128, num_routed_experts=128,
               num_experts_per_tok=8, expert_offset=0, seq_len=24)
    m = "l2_moe_"
    p = {k: v * (1 if k.endswith("_bias") else 5) for k, v in
         ref.init(jax.random.PRNGKey(3), cfg)[0].items() if k.startswith(m)}
    x = jnp.asarray(RS(50).randn(24, 32).astype("f"))
    whole = ref.expert_layer(p, m, x, cfg)
    gate, up = jnp.split(x @ p[m + "shared_gate_up_weight"].T, 2, -1)
    shared = (jax.nn.silu(gate) * up) @ p[m + "shared_down_weight"].T

    def share(s):
        held = slice(8 * s, 8 * s + 8)
        return routed_experts(
            x, p[m + "router_weight"], p[m + "experts_gate_up_weight"][held],
            p[m + "experts_down_weight"][held], p[m + "expert_bias"],
            top_k=8, expert_offset=8 * s, score_func="sigmoid",
            routed_scaling_factor=2.826, use_select_bias=True)
    parts = [share(s) for s in range(16)]
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


def test_the_configuration_counts_its_parameters():
    """From the configuration file's program: the symbol's own leaves at
    the published widths, six layers, 8 experts held, 25,024 ids:
    569,167,872.  (Issue 37's 569,169,408 counts an attention stage at
    27,263,488, which holds the two 128-wide head norms twice: its own
    parts add up to 27,263,232.)"""
    from mxnet_tpu.models.trinity import trinity_sym
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "trinity_mini.json")) as f:
        config = json.load(f)
    kwargs = config["program"]["kwargs"]
    sym = trinity_sym(**kwargs)[0]
    rows = (2, kwargs["seq_len"])
    shapes, _, _ = sym.infer_shape(data=rows, softmax_label=rows)
    count = {}
    for name, shape in zip(sym.list_arguments(), shapes):
        if name not in ("data", "softmax_label"):
            count[name] = int(np.prod(shape))
    assert sum(count.values()) == 569167872 == 569169408 - 6 * 256
    # a stage: the issue's part plus the stage's two of the four norms
    assert sum(v for k, v in count.items() if k.startswith("l3_attn_")) \
        == 3 * 8388608 + 2 * 1048576 + 2 * 128 + 2 * 2048
    assert sum(v for k, v in count.items() if k.startswith("l0_mlp_")) \
        == 37748736 + 2 * 2048
    assert sum(v for k, v in count.items() if k.startswith("l2_moe_")) \
        == 56885376 + 2 * 2048
    assert count["embed_weight"] + count["head_weight"] \
        + count["head_norm_gamma"] == 102500352
    # as published: the router's width, its bias, the window, both dense
    # layers
    assert count["l2_moe_router_weight"] == 128 * 2048
    assert count["l2_moe_expert_bias"] == 128
    assert kwargs["sliding_window"] == 2048 and kwargs["route_scale"] == 2.826
    assert kwargs["num_experts_per_tok"] == 8 and kwargs["num_dense_layers"] == 2


# -- planted faults: the toy cell's limits catch each -----------------------------

def _no_window(trinity, monkeypatch):
    """The window ignored: every sliding layer sees its whole prefix."""
    real = trinity._attention
    monkeypatch.setattr(
        trinity, "_attention", lambda x, p, seq_len, c, sliding: real(
            x, p, seq_len, dict(c, sliding_window=0), sliding))


def _rotary_everywhere(trinity, monkeypatch):
    """The rotary embedding applied to the full layer too."""
    real = trinity._attention

    def turned(x, p, seq_len, c, sliding):
        return real(x, p, seq_len,
                    c if sliding else dict(c, sliding_window=0), True)
    monkeypatch.setattr(trinity, "_attention", turned)


def _no_output_norms(trinity, monkeypatch):
    """The branches' outputs added as they are (the norms' weights stay
    leaves, with no gradient)."""
    real = trinity._norm

    def norm(x, name, *args, **kw):
        y = real(x, name, *args, **kw)
        return x + 0 * y if name.endswith("_post_norm") else y
    monkeypatch.setattr(trinity, "_norm", norm)


@pytest.mark.parametrize("fault", [None, _no_window, _rotary_everywhere,
                                   _no_output_norms],
                         ids=["as_published", "window_ignored",
                              "rotary_on_the_full_layer", "no_output_norms"])
def test_a_planted_fault_fails_the_toy_cells_limits(fault, monkeypatch, capfd):
    """The toy cell through the harness (``benchmark/run.py``): correct as
    published, not correct with the window ignored, with the rotary
    embedding on the full layer, or with the output norms left out."""
    from benchmark import run
    from mxnet_tpu.models import trinity
    if fault is not None:
        fault(trinity, monkeypatch)
    spec = os.path.join(ROOT, "benchmark", "tests", "data",
                        "toy_spec_trinity.json")
    rc = run.main(["--workload", "trinity_toy.train_toy_lm", "--seed",
                   str(2 ** 31 + 77), "--seconds", "0.2", "--trace", "0"],
                  devices=jax.devices()[:1], spec_path=spec)
    assert rc == 0
    line = json.loads(capfd.readouterr()[0].strip().splitlines()[-1])
    assert line["failed"] == 0
    assert line["correct"] is (fault is None), line["compared"]
    if fault is not None:
        over = [k for k, row in line["compared"].items()
                if row["limit"] and row["value"] > 3 * row["limit"]]
        assert over, line["compared"]
