#!/usr/bin/env python
"""mxserve daemon: serve trained checkpoints over HTTP
(docs/how_to/serving.md).

::

    python tools/serve.py --model mlp=/ckpts/mlp:3 \\
        --model resnet=/ckpts/resnet-dir \\
        --input-shape mlp:data=784 --input-shape resnet:data=3,32,32 \\
        --port 8100 [--buckets 1,2,4,8,16,32] [--dtype bfloat16] \\
        [--warmup] [--port-file /run/mxserve.port]

Model specs: ``name=prefix:epoch`` loads the ``prefix-symbol.json`` +
``prefix-%04d.params`` pair; ``name=directory`` (a path holding a
``CheckpointManager`` manifest) loads the newest intact epoch with
checksum verification.

Lifecycle: SIGTERM/SIGINT drain (finish accepted requests, then exit 0);
a wedged forward is killed by the StepWatchdog (``MXTPU_STEP_TIMEOUT``,
exit 87) so ``tools/supervise.py`` can relaunch the daemon — warm:
compiled bucket programs reload from the persistent compile cache
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).  Serving knobs: ``MXTPU_SERVE_*`` (docs/env_vars.md) or the
equivalent flags below.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _parse_shape_specs(specs):
    """``["mlp:data=784", "data=3,32,32"]`` -> {model_or_None: {input:
    shape}} (no model prefix = applies to every model)."""
    out = {}
    for spec in specs or ():
        model = None
        head, _, tail = spec.partition("=")
        if ":" in head:
            model, _, head = head.partition(":")
        shape = tuple(int(x) for x in tail.split(",") if x)
        out.setdefault(model, {})[head] = shape
    return out


def _load_models(pool, specs, shape_specs):
    for spec in specs:
        name, _, target = spec.partition("=")
        if not name or not target:
            raise SystemExit("bad --model spec %r (want name=prefix:epoch "
                             "or name=ckpt-dir)" % spec)
        shapes = shape_specs.get(name, shape_specs.get(None))
        if os.path.isdir(target):
            entry = pool.load_dir(name, target, sample_shapes=shapes)
            src = "%s (epoch %d)" % (target, entry.loaded_epoch)
        else:
            prefix, _, epoch = target.rpartition(":")
            if not prefix or not epoch.isdigit():
                raise SystemExit("bad --model target %r (want "
                                 "prefix:epoch or a checkpoint dir)"
                                 % target)
            pool.load(name, prefix, int(epoch), sample_shapes=shapes)
            src = "%s:%s" % (prefix, epoch)
        sys.stderr.write("mxserve: loaded model %r from %s\n" % (name, src))


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="inference serving daemon (docs/how_to/serving.md)")
    parser.add_argument("--model", action="append", default=[],
                        metavar="NAME=PREFIX:EPOCH|NAME=DIR",
                        help="model to serve (repeatable)")
    parser.add_argument("--input-shape", action="append", default=[],
                        metavar="[MODEL:]INPUT=D1,D2,...",
                        help="per-sample input shape, enables --warmup "
                             "and load-time analysis (repeatable)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8100,
                        help="0 = ephemeral (see --port-file)")
    parser.add_argument("--port-file", default=None,
                        help="write 'host:port' here once listening")
    parser.add_argument("--buckets", default=None,
                        help="override MXTPU_SERVE_BUCKETS")
    parser.add_argument("--max-wait-ms", type=float, default=None,
                        help="override MXTPU_SERVE_MAX_WAIT_MS")
    parser.add_argument("--max-queue", type=int, default=None,
                        help="override MXTPU_SERVE_MAX_QUEUE")
    parser.add_argument("--seq-buckets", default=None,
                        help="sequence-LENGTH buckets for "
                             "/predict_seq, e.g. '8,16,32' (default: "
                             "MXTPU_SERVE_SEQ_BUCKETS)")
    parser.add_argument("--tenant-weights", default=None,
                        help="weighted-fair tenant shares, e.g. "
                             "'gold:4,free:1' (default: "
                             "MXTPU_SERVE_TENANT_WEIGHTS)")
    parser.add_argument("--tenant-quota", type=int, default=None,
                        help="per-tenant queued-request quota; beyond "
                             "it a tenant is shed 429 (default: "
                             "MXTPU_SERVE_TENANT_QUOTA; 0 disables)")
    parser.add_argument("--slo-ms", type=float, default=None,
                        help="override MXTPU_SERVE_SLO_MS")
    parser.add_argument("--dtype", default=None,
                        help="override MXTPU_SERVE_DTYPE (e.g. bfloat16)")
    parser.add_argument("--warmup", action="store_true",
                        help="compile every bucket per model before "
                             "accepting traffic (needs --input-shape)")
    parser.add_argument("--watch", action="store_true",
                        help="tail each checkpoint-DIRECTORY model for "
                             "new epochs and hot-swap verified ones in "
                             "with zero dropped requests (MXTPU_SWAP_* "
                             "knobs; docs/how_to/serving.md "
                             "'Continuous deployment')")
    parser.add_argument("--warmup-only", action="store_true",
                        help="warm every (model, bucket) forward, print "
                             "`mxserve: warmup_s=<s>`, exit 0 WITHOUT "
                             "serving (the fleet bring-up measurement; "
                             "docs/how_to/fleet.md)")
    parser.add_argument("--export-aot", action="store_true",
                        help="BUILD the AOT executable store: compile "
                             "every (model, bucket) forward and "
                             "serialize the executables under "
                             "JAX_COMPILATION_CACHE_DIR/aot (pair with "
                             "--warmup-only; replicas launched with "
                             "the same cache dir then warm by LOADING "
                             "instead of compiling)")
    args = parser.parse_args(argv)
    if not args.model:
        parser.error("at least one --model is required")

    from mxnet_tpu.resilience import StepWatchdog, step_timeout_configured
    from mxnet_tpu.serving import ModelPool, ServingFrontend, parse_buckets

    pool = ModelPool(dtype=args.dtype)
    _load_models(pool, args.model, _parse_shape_specs(args.input_shape))

    watchdog = None
    if step_timeout_configured():
        watchdog = StepWatchdog()

    frontend = ServingFrontend(
        pool, host=args.host, port=args.port, buckets=args.buckets,
        max_wait_ms=args.max_wait_ms, max_queue=args.max_queue,
        slo_ms=args.slo_ms, watchdog=watchdog,
        tenant_weights=args.tenant_weights,
        tenant_quota=args.tenant_quota,
        seq_buckets=args.seq_buckets)

    # handlers + bind BEFORE the (possibly minutes-long) warmup: a
    # SIGTERM during warmup must drain to exit 0, not die rc 143 on the
    # default handler.  The port file is only written after warmup, so
    # no client connects early.
    frontend.install_signal_handlers()
    frontend.start()

    if args.warmup or args.warmup_only or args.export_aot:
        import time as _time

        from mxnet_tpu.serving import aot

        aot_dir = aot.aot_dir()
        tic = _time.monotonic()
        buckets = parse_buckets(args.buckets)
        for name in pool.names():
            if frontend.draining:     # SIGTERM mid-warmup: stop compiling
                break
            entry = pool.get(name)
            if entry.sample_shapes is None:
                sys.stderr.write("mxserve: cannot warm %r — no "
                                 "--input-shape declared\n" % name)
                continue
            if args.export_aot:
                # the store BUILDER: compile + serialize each bucket's
                # executable (no Predictor warmup — this process never
                # serves)
                entry.export_aot(buckets, aot_dir)
                sys.stderr.write("mxserve: exported AOT executables "
                                 "for %r over buckets %s\n"
                                 % (name, list(buckets)))
                continue
            loaded = entry.load_aot(aot_dir, buckets)
            if loaded:
                sys.stderr.write("mxserve: warmed %r from the AOT "
                                 "store (%d/%d buckets)\n"
                                 % (name, loaded, len(buckets)))
            if loaded < len(buckets):
                # no store / partial store / meta mismatch: classic
                # trace-and-compile warmup for what is missing
                entry.warmup([b for b in buckets
                              if b not in entry._aot])
                sys.stderr.write("mxserve: warmed %r over buckets %s\n"
                                 % (name, [b for b in buckets
                                           if b not in entry._aot]))
        # the bring-up number (process start/imports excluded — this is
        # the compile cost the warm store removes)
        sys.stderr.write("mxserve: warmup_s=%.3f\n"
                         % (_time.monotonic() - tic))
    if args.warmup_only:
        # no serve_forever ran, so there is nothing to drain — the
        # bound (never-advertised) socket dies with the process
        sys.stderr.write("mxserve: warmup-only — exiting 0\n")
        sys.stderr.flush()
        return 0
    if args.watch:
        for name in pool.names():
            entry = pool.get(name)
            if entry.source_dir:
                frontend.watcher(name, start=True)
                sys.stderr.write(
                    "mxserve: watching %s (epoch %s) for new epochs of "
                    "%r\n" % (entry.source_dir, entry.loaded_epoch, name))
            else:
                sys.stderr.write(
                    "mxserve: --watch: model %r was loaded from a "
                    "prefix:epoch pair, not a checkpoint directory — "
                    "not watchable\n" % name)
    sys.stderr.write("mxserve: listening on %s:%d (models: %s)\n"
                     % (frontend.host, frontend.port, pool.names()))
    sys.stderr.flush()
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write("%s:%d" % (frontend.host, frontend.port))
        os.replace(tmp, args.port_file)
    frontend.serve_forever()
    sys.stderr.write("mxserve: drained — exiting 0\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
