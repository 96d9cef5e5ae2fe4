"""Kernels of the main path compiled for a TPU v5e that is described, not
attached (``jax.experimental.topologies``): what the Pallas interpreter
cannot show — a slice that is not aligned to the tiling, more VMEM than a
kernel may use — costs no chip time here.  The topology is described
inside a fixture, never at import: only the worker that runs this file may
load the TPU's library."""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever the plugin raises
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows,t,hk,hv,dk,dv,chunk,dtype,decay", [
    (2, 8192, 16, 32, 128, 128, 64, "bfloat16", "head"),    # Qwen3-Next's
    (1, 1024, 2, 2, 256, 128, 32, "float32", "head"),   # one value head a key's
    (2, 8192, 32, 32, 128, 128, 64, "bfloat16", "channel"),  # Kimi Linear's
    (1, 512, 4, 4, 256, 128, 32, "float32", "channel"),    # four heads a step
], ids=["qwen3_next_8k", "wide_keys_f32", "kimi_linear_8k",
        "channel_wide_keys_f32"])
def test_delta_rule_kernels_compile_for_the_v5e(one_chip, rows, t, hk, hv, dk,
                                                dv, chunk, dtype, decay):
    """Forward and backward of each rule: a decay a head (g of rank 3) and
    a decay a key channel (rank 4, its own two kernels)."""
    from mxnet_tpu.kernels import compiled_kernels
    from mxnet_tpu.kernels.delta_rule import gated_delta_net_pallas

    def spec(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    g_shape = (rows, t, hv) + ((dk,) if decay == "channel" else ())
    specs = (spec(rows, t, hk, dk), spec(rows, t, hk, dk),
             spec(rows, t, hv, dv), spec(*g_shape, dt="float32"),
             spec(rows, t, hv, dt="float32"))

    def grads(*a):
        return jax.grad(lambda *x: jnp.sum(gated_delta_net_pallas(
            *x, chunk=chunk).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4))(*a)
    text = jax.jit(grads).lower(*specs).compile().as_text()
    stem = "mxtpu_delta_rule_channel_" if decay == "channel" \
        else "mxtpu_delta_rule_"
    assert compiled_kernels(text) == {stem + "fwd": 1, stem + "bwd": 1}


@pytest.mark.parametrize("hq,hkv,d,dv,window", [
    (32, 32, 192, 128, None),   # Kimi Linear's latent attention: keys padded
    (16, 2, 256, 256, None),    # Qwen3-Next's: eight query heads a key head
    (8, 2, 128, 128, None),     # ZAYA1's latent: four
    (32, 4, 128, 128, 2048),    # Trinity-Mini's sliding layers: eight
    (32, 4, 128, 128, None),    # and its full layer
], ids=["kimi_linear_8k", "qwen3_next_8k", "zaya1_8k", "trinity_mini_8k_swa",
        "trinity_mini_8k_full"])
def test_gqa_attention_kernels_compile_for_the_v5e(one_chip, hq, hkv, d, dv,
                                                   window):
    """Forward and gradient of the compiled causal grouped-query attention
    at the four language-model cells' shapes (2 x 8,192 positions,
    bfloat16), under a window of 2,048 keys where the cell has one: one
    forward kernel, one backward kernel for dq, dk and dv, within the VMEM
    they ask for."""
    from mxnet_tpu.kernels import compiled_kernels
    from mxnet_tpu.kernels.flash_attention import (_gqa_lax_reason,
                                                   gqa_attention_pallas)
    specs = tuple(jax.ShapeDtypeStruct((2, 8192, h, w), jnp.bfloat16,
                                       sharding=one_chip)
                  for h, w in ((hq, d), (hkv, d), (hkv, dv)))
    assert _gqa_lax_reason(specs[0], specs[2]) is None

    def grads(*a):
        return jax.grad(lambda *x: jnp.sum(gqa_attention_pallas(
            *x, window=window).astype(jnp.float32)), argnums=(0, 1, 2))(*a)
    text = jax.jit(grads).lower(*specs).compile().as_text()
    assert compiled_kernels(text) == {"mxtpu_gqa_attention_fwd": 1,
                                      "mxtpu_gqa_attention_bwd": 1}


def _attention_stage(model, x, seq_len):
    """One attention stage of a language model at its published head
    widths and grouping, few heads, a narrow stream."""
    if model == "kimi_linear":          # 192-wide keys, 128-wide values
        from mxnet_tpu.models.kimi_linear import _mla
        return _mla(x, "l3_mla", seq_len, dict(
            hidden_size=64, num_attention_heads=2, kv_lora_rank=32,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rms_norm_eps=1e-5))
    if model.startswith("trinity"):     # eight, 128 wide; a window or none
        from mxnet_tpu.models.trinity import _attention
        return _attention(x, "l0_swa", seq_len, dict(
            hidden_size=64, num_attention_heads=8, num_key_value_heads=1,
            head_dim=128, rope_theta=10000, sliding_window=128,
            rms_norm_eps=1e-5), model == "trinity_swa")
    rope = dict(partial_rotary_factor=0.25, rope_theta=1e7,
                rope_parameters={"hybrid": {"rope_theta": 5e6}})
    if model == "qwen3_next":           # eight query heads a key head
        from mxnet_tpu.models.qwen3_next import _gated_attention
        return _gated_attention(x, "l3_attn", seq_len, dict(
            rope, hidden_size=64, num_attention_heads=8,
            num_key_value_heads=1, head_dim=256, rms_norm_eps=1e-6))
    from mxnet_tpu.models.zaya import _cca  # four, 128 wide
    return _cca(x, "l0_cca", seq_len, dict(
        rope, hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
        head_dim=128, cca_time0=2, cca_time1=2, rms_norm_eps=1e-5))


def _lower_stage(stage):
    """A stage, rematerialised as the models build theirs, in a bfloat16
    training step over (2 x 256 positions, 64 wide): the ``kernel.route``
    events of its trace and the step's text lowered for a TPU and for the
    CPU."""
    import time
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import profiler
    from mxnet_tpu.parallel import SPMDTrainer, default_mesh
    seq_len, rows = 256, 2
    with mx.AttrScope(mirror_stage="stage"):
        stage = stage(mx.sym.Variable("data"), seq_len)
    net = mx.sym.LinearRegressionOutput(stage, mx.sym.Variable("label"),
                                        name="out")
    tr = SPMDTrainer(net, "sgd", {"learning_rate": 0.1, "rescale_grad": 1.0},
                     mesh=default_mesh(devices=jax.devices()[:1]),
                     compute_dtype="bfloat16")
    try:
        tr.bind([("data", (rows * seq_len, 64))],
                [("label", (rows * seq_len, 64))])
        tr.init_params(mx.initializer.Xavier())
        args = tr._example_args(np.zeros((rows * seq_len, 64), "f"),
                                np.zeros((rows * seq_len, 64), "f"))
        since = time.perf_counter()
        traced = tr._step_fn.trace(*args)
        routes = [r["ids"] for r in profiler.spans(since, time.perf_counter())
                  if r["name"] == "kernel.route"]
        return (routes, traced.lower(lowering_platforms=("tpu",)).as_text(),
                traced.lower(lowering_platforms=("cpu",)).as_text())
    finally:
        tr.close()


@pytest.mark.parametrize("model", ["kimi_linear", "qwen3_next", "zaya",
                                   "trinity_swa", "trinity_full"])
def test_a_tpu_lowering_of_each_attention_stage_takes_the_kernels(model):
    """The models' own attention stages in a bfloat16 training step:
    lowered for a TPU the step holds the attention's two kernels, lowered
    for the CPU none; the one ``GQAttention`` lowering says so in the
    recorder — a sliding-window stage's with its window and the steps of
    its schedule (256 positions in tiles of (256, 256): nothing to skip)."""
    from mxnet_tpu.kernels import compiled_kernels
    routes, tpu, cpu = _lower_stage(
        lambda x, seq_len: _attention_stage(model, x, seq_len))
    routes = [r for r in routes if r["kernel"] == "gqa_attention"]
    want = {"kernel": "gqa_attention", "tier": "pallas", "reason": "aligned"}
    if model == "trinity_swa":
        want.update(window=128, steps=1, steps_causal=1)
    assert routes and all(r == want for r in routes), routes
    assert {k for k in compiled_kernels(tpu) if "gqa" in k} == {
        "mxtpu_gqa_attention_fwd", "mxtpu_gqa_attention_bwd"}
    assert compiled_kernels(cpu) == {}


@pytest.mark.parametrize("c,taps,act", [
    (4096, 4, "silu"),      # a Kimi Delta Attention stage's q, k and v
    (8192, 4, "silu"),      # Qwen3-Next's q, k and v in one stream
    (1280, 2, None),        # ZAYA1's latent queries and keys
], ids=["kimi_linear_8k", "qwen3_next_8k", "zaya1_8k"])
def test_causal_conv_kernels_compile_for_the_v5e(one_chip, c, taps, act):
    """Forward and both gradients of the compiled depthwise causal
    convolution at the three language-model cells' shapes (2 x 8,192
    positions, bfloat16): one kernel each way, within the VMEM they ask
    for."""
    from mxnet_tpu.kernels import compiled_kernels
    from mxnet_tpu.kernels.causal_conv import (_lax_reason,
                                               causal_conv_pallas)
    x = jax.ShapeDtypeStruct((2, 8192, c), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((c, taps), jnp.bfloat16, sharding=one_chip)
    assert _lax_reason(x, taps, act) is None

    def both(x, w, dy):         # (the gradients alone need no forward)
        y, vjp = jax.vjp(lambda *a: causal_conv_pallas(*a, taps, act), x, w)
        return (y,) + vjp(dy)
    text = jax.jit(both).lower(x, w, x).compile().as_text()
    assert compiled_kernels(text) == {"mxtpu_causal_conv_fwd": 1,
                                      "mxtpu_causal_conv_bwd": 1}


def _mixer_stage(model, x, seq_len):
    """The stage of a language model that holds its short convolutions, at
    its published head widths and taps, few heads, a narrow stream."""
    if model == "kimi_linear":          # three convolutions, 4 taps, silu
        from mxnet_tpu.models.kimi_linear import _kda
        return _kda(x, "l0_kda", seq_len, dict(
            hidden_size=64, rms_norm_eps=1e-5, linear_attn_config=dict(
                num_heads=2, head_dim=128, short_conv_kernel_size=4)))
    if model == "qwen3_next":           # one, over q, k and v
        from mxnet_tpu.models.qwen3_next import _gated_delta_net
        return _gated_delta_net(x, "l0_gdn", seq_len, dict(
            hidden_size=64, linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128,
            linear_conv_kernel_dim=4, rms_norm_eps=1e-6))
    return _attention_stage(model, x, seq_len)  # one, 2 taps, plain


@pytest.mark.parametrize("model,convs", [("kimi_linear", 3),
                                         ("qwen3_next", 1), ("zaya", 1)])
def test_a_tpu_lowering_of_each_mixer_stage_takes_the_convolution_kernels(
        model, convs):
    """A KDA, a DeltaNet and a CCA stage in a bfloat16 training step:
    every depthwise ``CausalConv1D`` of the stage says ``pallas`` in the
    recorder, and lowered for a TPU the step holds the convolution's two
    kernels, lowered for the CPU none."""
    from mxnet_tpu.kernels import compiled_kernels
    routes, tpu, cpu = _lower_stage(
        lambda x, seq_len: _mixer_stage(model, x, seq_len))
    routes = [r for r in routes if r["kernel"] == "causal_conv"]
    assert routes and len(routes) % convs == 0 and all(
        r == {"kernel": "causal_conv", "tier": "pallas", "reason": "aligned"}
        for r in routes), routes
    assert {"mxtpu_causal_conv_fwd", "mxtpu_causal_conv_bwd"} <= set(
        compiled_kernels(tpu))
    assert compiled_kernels(cpu) == {}
