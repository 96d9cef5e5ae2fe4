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
