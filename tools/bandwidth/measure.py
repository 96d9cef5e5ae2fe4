#!/usr/bin/env python
"""Communication micro-benchmark (the reference tools/bandwidth/measure.py
analog): times device-side AllReduce across a size sweep.

Two modes:
- single-process (default): jitted psum over a mesh of all visible devices
  (the GSPMD collective the fused train step uses).  On a multi-chip host
  this measures ICI; on the virtual CPU mesh it validates the harness.
- multi-process (under tools/launch.py): the distributed Collective's
  cross-process AllReduce (gloo on CPU, ICI/DCN on pods).

Usage::

    python tools/bandwidth/measure.py --sizes 1KB,1MB,16MB --iters 20
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python tools/bandwidth/measure.py
    python tools/launch.py -n 4 --platform cpu \
        python tools/bandwidth/measure.py --dist
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))


def parse_size(s):
    s = s.strip().upper()
    mult = 1
    for suffix, m in (("KB", 1 << 10), ("MB", 1 << 20), ("GB", 1 << 30),
                      ("B", 1)):
        if s.endswith(suffix):
            return int(float(s[:-len(suffix)]) * m)
    return int(s)


def bench_single(sizes, iters):
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    mesh = Mesh(np.asarray(devs), ("dp",))
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, P("dp"))

    @jax.jit
    def allreduce(x):
        # dp-sharded input, replicated output: GSPMD emits AllReduce/
        # AllGather over the mesh — the fused trainer's gradient pattern
        return jax.lax.with_sharding_constraint(x, rep)

    results = []
    for size in sizes:
        n = max(len(devs), size // 4 // len(devs) * len(devs))
        x = jax.device_put(jnp.arange(n, dtype=jnp.float32), shard)
        allreduce(x).block_until_ready()      # compile + warm
        tic = time.time()
        for _ in range(iters):
            out = allreduce(x)
        out.block_until_ready()
        dt = (time.time() - tic) / iters
        results.append({"size_bytes": n * 4, "num_devices": len(devs),
                        "time_ms": round(dt * 1e3, 4),
                        "gbytes_per_s": round(n * 4 / dt / 1e9, 3)})
    return results


def bench_dist(sizes, iters):
    import numpy as np
    from mxnet_tpu import distributed
    distributed.initialize()
    coll = distributed.Collective()
    results = []
    for size in sizes:
        n = max(1, size // 4)
        x = np.ones(n, np.float32)
        coll.allreduce_sum(x)                 # warm
        tic = time.time()
        for _ in range(iters):
            out = coll.allreduce_sum(x)
        np.asarray(out)
        dt = (time.time() - tic) / iters
        results.append({"size_bytes": n * 4,
                        "num_workers": coll.num_workers,
                        "time_ms": round(dt * 1e3, 4),
                        "gbytes_per_s": round(n * 4 / dt / 1e9, 3)})
    return results


def main():
    parser = argparse.ArgumentParser(description="allreduce bandwidth sweep")
    parser.add_argument("--sizes", default="4KB,64KB,1MB,16MB,64MB",
                        help="comma-separated message sizes")
    parser.add_argument("--iters", type=int, default=20)
    parser.add_argument("--dist", action="store_true",
                        help="cross-process mode (run under tools/launch.py)")
    parser.add_argument("--virtual-devices", type=int, default=0,
                        help="provision an N-device virtual CPU mesh before "
                             "JAX init (for harness validation on 1-chip "
                             "hosts)")
    args = parser.parse_args()
    if args.virtual_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_force_host_platform_device_count=%d"
            % args.virtual_devices)
        import jax
        jax.config.update("jax_platforms", "cpu")
    sizes = [parse_size(s) for s in args.sizes.split(",")]
    rows = bench_dist(sizes, args.iters) if args.dist else \
        bench_single(sizes, args.iters)
    for r in rows:
        print(json.dumps(r))


if __name__ == "__main__":
    main()
