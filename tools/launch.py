#!/usr/bin/env python
"""Launch a distributed job (the reference tools/launch.py analog).

The reference forks scheduler/server/worker roles with ``DMLC_*`` envs via
the dmlc-core tracker (reference ``tools/launch.py:46-70``,
``dmlc_tracker/local.py``).  The TPU-native cluster has one symmetric role:
N JAX processes that join a global device topology through
``jax.distributed.initialize`` (see ``mxnet_tpu/distributed.py``); this
launcher spawns them with the ``MXTPU_*`` envs the workers read.

Local mode (default) runs all N workers on this host — the exact analog of
the reference's ``--launcher local`` used by its nightly dist tests — and
only on the CPU platform (``--platform cpu``, the virtual cluster): a TPU
chip belongs to one process, and N ranks started here would each open
every chip of the host, so the launcher refuses.  On a TPU host ONE
process drives all its chips (``SPMDTrainer`` / ``kvstore='tpu'``); for
real multi-host pods, use the cluster scheduler (GKE/slurm) to start one
process per host with the same envs; there is no ssh fan-out here by
design (pods are provisioned, not ssh'd into).

Usage::

    python tools/launch.py -n 4 --platform cpu python train.py --kv-store dist_sync
    python tools/launch.py -n 2 --platform cpu python tests/dist/dist_sync_kvstore.py
"""
import argparse
import os
import signal
import socket
import subprocess
import sys
import threading


def _free_port():
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pump(stream, prefix, out):
    for line in iter(stream.readline, b""):
        out.write(("%s %s" % (prefix, line.decode("utf-8", "replace"))))
        out.flush()
    stream.close()


def launch(num_workers, command, platform=None, port=None, env=None,
           quiet=False):
    """Spawn ``num_workers`` local worker processes running ``command``.

    Returns the list of exit codes (in rank order).  The first failing
    worker triggers termination of the rest, like the reference tracker's
    local mode killing the job on a dead role.
    """
    base = dict(os.environ if env is None else env)
    plat = platform or base.get("MXTPU_PLATFORM") or base.get("JAX_PLATFORMS")
    if num_workers > 1 and plat != "cpu":
        raise ValueError(
            "local mode would start %d processes on this host with "
            "platform %r: each would open every accelerator chip of the "
            "host, and a chip belongs to one process.  Pass --platform cpu "
            "for the virtual cluster; on a TPU host run ONE process over "
            "all its chips" % (num_workers, plat))
    port = port or _free_port()
    base["MXTPU_COORDINATOR"] = "127.0.0.1:%d" % port
    base["MXTPU_NUM_WORKERS"] = str(num_workers)
    if platform:
        base["MXTPU_PLATFORM"] = platform
    procs, pumps = [], []
    for r in range(num_workers):
        wenv = dict(base)
        wenv["MXTPU_WORKER_RANK"] = str(r)
        p = subprocess.Popen(command, env=wenv,
                             stdout=subprocess.PIPE,
                             stderr=subprocess.STDOUT)
        procs.append(p)
        if not quiet:
            t = threading.Thread(target=_pump,
                                 args=(p.stdout, "[worker %d]" % r,
                                       sys.stdout),
                                 daemon=True)
            t.start()
            pumps.append(t)
    codes = [None] * num_workers
    try:
        for r, p in enumerate(procs):
            codes[r] = p.wait()
            if codes[r] != 0:  # fail fast: tear the job down
                for q in procs:
                    if q.poll() is None:
                        q.terminate()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for t in pumps:
            t.join(timeout=5)
    return [c if c is not None else -signal.SIGKILL for c in codes]


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job")
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes to launch")
    parser.add_argument("--launcher", default="local", choices=["local"],
                        help="only 'local' spawns here; multi-host pods are "
                             "started by the cluster scheduler (see module "
                             "docstring)")
    parser.add_argument("--platform", default=None,
                        help="force a JAX platform in workers (e.g. 'cpu' "
                             "for the virtual cluster used in tests)")
    parser.add_argument("--port", type=int, default=None,
                        help="coordinator port (default: pick a free one)")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to run in each worker")
    args = parser.parse_args(argv)
    if not args.command:
        parser.error("no command given")
    command = args.command[1:] if args.command[0] == "--" else args.command
    codes = launch(args.num_workers, command, platform=args.platform,
                   port=args.port)
    bad = [(r, c) for r, c in enumerate(codes) if c != 0]
    if bad:
        sys.stderr.write("workers failed: %s\n" % bad)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
