#!/usr/bin/env python
"""mxdata network-tier server: decode batches on THIS host's cores and
stream them to a remote consumer (docs/how_to/performance.md, "Scaling
the input pipeline" — the network tier).

::

    # on each CPU decode host (the .rec/.idx live on THIS host)
    python tools/data_server.py --host 0.0.0.0 --port 9410

    # on the TPU host
    it = mx.io.ImageRecordIter(..., data_service='cpu1:9410,cpu2:9410')
    # or fleet-wide: export MXTPU_DATA_SERVERS=cpu1:9410,cpu2:9410

The server is stateless across connections: every consumer connection
carries its full stream config (dataset paths AS SEEN FROM THIS HOST,
shapes, seed, shard offset/stride, local decode-worker count) in the
handshake, and the server builds a fresh sharded-reader/decode-worker
service for it — so one server process serves any number of jobs, and
a SIGKILLed server respawned by the host's supervisor (systemd,
supervise.py, k8s) needs no state handoff: the consumer's reconnect
handshake re-requests its stream at the last consumed batch.

IMPORT DISCIPLINE: this process NEVER imports jax — a decode host that
spun up an XLA client would burn seconds of startup and hundreds of MB
per server, and on a mixed host would fight the trainer for the chip
(the ``tools/supervise.py`` lesson).  The data_service package's
server half is jax-free by design; it is imported through the
synthetic-package stub below (the ``tools/mxlint.py`` idiom) so
``mxnet_tpu/__init__`` never executes.
"""
import argparse
import importlib.machinery
import os
import signal
import sys
import types

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)


def _bootstrap():
    """Install the package-path stub and import the jax-free leaves."""
    if "mxnet_tpu" not in sys.modules:
        pkg = types.ModuleType("mxnet_tpu")
        pkg.__path__ = [os.path.join(_ROOT, "mxnet_tpu")]
        pkg.__spec__ = importlib.machinery.ModuleSpec(
            "mxnet_tpu", None, is_package=True)
        pkg.__spec__.submodule_search_locations = pkg.__path__
        sys.modules["mxnet_tpu"] = pkg
    from mxnet_tpu.data_service import net
    return net


def _log(msg):
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="data-service network-tier server (jax-free; "
                    "docs/how_to/performance.md)")
    parser.add_argument("--host", default="127.0.0.1",
                        help="bind address (0.0.0.0 for remote "
                             "consumers)")
    parser.add_argument("--port", type=int, default=9410,
                        help="TCP port (0 = ephemeral; see --port-file)")
    parser.add_argument("--port-file", default=None,
                        help="write 'host:port' here once listening "
                             "(tests discover ephemeral ports)")
    args = parser.parse_args(argv)

    net = _bootstrap()
    server = net.BatchServer(host=args.host, port=args.port, log=_log)

    def _on_signal(signum, frame):
        _log("data_server: signal %d — shutting down" % signum)
        server.shutdown()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_signal)

    _log("data_server: listening on %s:%d (pid %d)"
         % (server.host, server.port, os.getpid()))
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write("%s:%d" % (server.host, server.port))
        os.replace(tmp, args.port_file)
    return server.serve_forever()


if __name__ == "__main__":
    sys.exit(main())
