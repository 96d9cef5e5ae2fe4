#!/usr/bin/env python
"""mxregion: the composed region drill — one command that stands up the
WHOLE production story and (in storm mode) runs it under scheduled
chaos (docs/how_to/region.md).

A "region" is every tier this repo has grown, wired together the way a
real deployment wires them:

::

    data#0..N   tools/data_server.py hosts (the remote batch plane)
        |            .. feeding, over TCP with reconnect-resume ..
    trainer     a supervised elastic zero3 SPMDTrainer (its own process,
        |       resumed with MXTPU_RESUME=1 on every respawn)
        |            .. publishing verified checkpoints into ..
    ckpt dir    watched by the fleet's RollingSwap (--watch)
        |            .. rolled replica-by-replica across ..
    fleet       tools/fleet.py serve: N replicas behind the router
        |            .. serving closed-loop clients ..
    clients     in-process threads POSTing /predict (drop = contract bug)

Every process runs under a :class:`supervise.Supervisor` — the
exit-code discipline of ``tools/supervise.py`` (0 done / 85 preempt /
87 watchdog / retry-any for chaos roles) extended across heterogeneous
roles, each death a counted named event on ``/region/stats``.

::

    python tools/region.py smoke                  # ~a minute, 1x1, rot only
    python tools/region.py storm                  # the full STORM drill
    python tools/region.py run   --spec region.json
    python tools/region.py storm --report out.json --run-dir /tmp/r

STORM mode drives a declarative fault schedule (one event per line,
``mxnet_tpu.resilience.parse_fault_schedule``) through the tree::

    2  kill   data#0                  # SIGKILL a data host mid-epoch
    5  resize trainer 4               # SIGKILL + respawn at --devices 4
    8  arm    trainer rot_checkpoint:1  # next publish is bit-rotted
    9  rot    trainer shard#1         # next publish: shard blob 1 rotted
    11 kill   replica#1               # SIGKILL a serving replica

Acceptance (checked, reported, exit code): ZERO dropped/errored client
requests, a bit-verified served-epoch advance across the whole storm
(``verify_promotion`` on the epoch every replica ends on), and every
scheduled fault observed as a counted named event on ``/region/stats``
— a seam failure must surface as a number, never a hang.

IMPORT DISCIPLINE: this orchestrator NEVER imports jax (the
``tools/fleet.py`` lesson — a control plane that spun up an XLA client
would steal devices from the children).  The jax-free leaves are
imported through the synthetic-package stub below; the trainer and
dataset builder run from generated scripts in their own processes.
"""
import argparse
import http.client
import importlib.machinery
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import types
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

from supervise import Supervisor  # noqa: E402 — tools/ sibling


def _bootstrap():
    """Install the package-path stub and import the jax-free leaves."""
    if "mxnet_tpu" not in sys.modules:
        pkg = types.ModuleType("mxnet_tpu")
        pkg.__path__ = [os.path.join(_ROOT, "mxnet_tpu")]
        pkg.__spec__ = importlib.machinery.ModuleSpec(
            "mxnet_tpu", None, is_package=True)
        pkg.__spec__.submodule_search_locations = pkg.__path__
        sys.modules["mxnet_tpu"] = pkg
    from mxnet_tpu import resilience
    return resilience


def _log(msg):
    sys.stderr.write("region: " + msg + "\n")
    sys.stderr.flush()


# ---------------------------------------------------------------------------
# generated one-shot scripts: the dataset builder (jax-free) and the
# elastic trainer (the only jax process the region owns directly)
# ---------------------------------------------------------------------------

_DATASET_SCRIPT = """\
# generated by tools/region.py — build the region's .rec/.idx dataset
import importlib.machinery, os, sys, types
repo = os.environ["REGION_REPO"]
sys.path.insert(0, repo)
pkg = types.ModuleType("mxnet_tpu")
pkg.__path__ = [os.path.join(repo, "mxnet_tpu")]
pkg.__spec__ = importlib.machinery.ModuleSpec(
    "mxnet_tpu", None, is_package=True)
pkg.__spec__.submodule_search_locations = pkg.__path__
sys.modules["mxnet_tpu"] = pkg
import cv2
import numpy as np
from mxnet_tpu import recordio

rec, idx, n = sys.argv[1], sys.argv[2], int(sys.argv[3])
w = recordio.MXIndexedRecordIO(idx, rec, "w")
rs = np.random.RandomState(0)
for i in range(n):
    img = rs.randint(0, 255, (16, 16, 3)).astype(np.uint8)
    ok, buf = cv2.imencode(".jpg", img)
    assert ok
    w.write_idx(i, recordio.pack(
        recordio.IRHeader(0, float(i % 7), i, 0), buf.tobytes()))
w.close()
print("REGION_DATASET records=%d" % n, flush=True)
"""

_TRAINER_SCRIPT = """\
# generated by tools/region.py — the region's elastic zero3 trainer.
# World size comes from REGION_WORLD (set per spawn by the supervisor's
# env_fn, so a `resize` event is just SIGKILL + respawn); data arrives
# over the network from the region's data_server.py hosts; every epoch
# publishes a verified checkpoint the fleet's RollingSwap picks up.
import os, re, sys, json, time
world = int(os.environ["REGION_WORLD"])
flags = re.sub(r"--xla_force_host_platform_device_count=\\d+", "",
               os.environ.get("XLA_FLAGS", ""))
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=%d" % world).strip()
sys.path.insert(0, os.environ["REGION_REPO"])
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.parallel import SPMDTrainer, local_mesh
from mxnet_tpu.resilience import CheckpointManager, faults

BATCH = int(os.environ["REGION_BATCH"])
EPOCHS = int(os.environ["REGION_EPOCHS"])
GAP_S = float(os.environ["REGION_EPOCH_GAP_S"])
STATUS = os.environ["REGION_STATUS_FILE"]
CMD = os.environ["REGION_CMD_FILE"]
FEATS = 3 * 8 * 8


def build():
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=7, name="fc2")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    t = SPMDTrainer(sym, "sgd",
                    {"learning_rate": 0.05, "momentum": 0.9,
                     "rescale_grad": 1.0 / BATCH},
                    mesh=local_mesh("dp"), grad_sync="zero3")
    t.bind([("data", (BATCH, FEATS))], [("softmax_label", (BATCH,))])
    mx.random.seed(7)
    t.init_params(mx.initializer.Xavier())
    return t


def data_iter():
    return mx.io.ImageRecordIter(
        path_imgrec=os.environ["REGION_REC"],
        path_imgidx=os.environ["REGION_IDX"],
        data_shape=(3, 8, 8), batch_size=BATCH, shuffle=True,
        rand_crop=True, rand_mirror=False, seed=11, dtype="float32",
        host_batches=True,
        data_service=os.environ["REGION_DATA_SERVERS"],
        preprocess_threads=1)


_applied = set()


def wait_for_go():
    # the orchestrator appends "go" once the fleet and the clients are up:
    # whatever the bring-up took, every publish after the first goes to a
    # LIVE region, so a scheduled rot can land neither after the last
    # publish nor on it (the fleet would then never serve the last epoch)
    while True:
        if os.path.exists(CMD):
            with open(CMD) as f:
                if "go" in f.read().split():
                    return
        time.sleep(0.05)


def poll_commands():
    # the orchestrator's storm scheduler appends "arm point:times[@after]"
    # lines; each is applied exactly once, in-process, BEFORE the next
    # save — so an armed rot_checkpoint hits the very next publish
    if not os.path.exists(CMD):
        return
    with open(CMD) as f:
        lines = f.read().splitlines()
    for n, line in enumerate(lines):
        line = line.strip()
        if not line or n in _applied:
            continue
        _applied.add(n)
        verb, _, spec = line.partition(" ")
        if verb != "arm" or not spec:
            continue
        name, _, rest = spec.partition(":")
        times_s, _, after_s = rest.partition("@")
        faults.arm(name, times=int(times_s or 1), after=int(after_s or 0))
        print("REGION_TRAINER armed %s" % spec, flush=True)


def write_status(epoch, it, batches, t0):
    st = it.stats() if it is not None else {}
    reconnects = sum(s.get("reconnects", 0)
                     for s in (st.get("servers") or {}).values())
    doc = {"epoch": epoch, "world": world, "pid": os.getpid(),
           "reconnects": reconnects, "batches": batches,
           "time": time.time(), "uptime_s": round(time.time() - t0, 3)}
    tmp = STATUS + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, STATUS)


mgr = CheckpointManager(os.environ["REGION_CKPT_DIR"], keep_last=None)
t = build()
start_epoch = 0
if os.environ.get("MXTPU_RESUME") == "1" and mgr.latest() is not None:
    start_epoch = t.restore(mgr)
    print("REGION_TRAINER resumed epoch=%d world=%d"
          % (start_epoch, world), flush=True)
it = data_iter()
t0 = time.time()
batches = 0
for epoch in range(start_epoch + 1, EPOCHS + 1):
    for b in it:
        x = np.array(b.data[0], dtype="f").reshape(BATCH, -1)
        y = np.array(b.label[0], dtype="f")
        t.step(x, y)
        batches += 1
    it.reset()
    poll_commands()
    t.save_checkpoint(mgr, epoch, blocking=True)
    write_status(epoch, it, batches, t0)
    print("REGION_TRAINER epoch=%d world=%d" % (epoch, world), flush=True)
    wait_for_go()
    time.sleep(GAP_S)
it.close()
print("REGION_TRAINER done epochs=%d" % EPOCHS, flush=True)
"""


# ---------------------------------------------------------------------------
# region spec: the declarative inventory (processes, ports, schedule)
# ---------------------------------------------------------------------------

STORM_SCHEDULE = """\
2  kill   data#0
5  resize trainer 4
8  arm    trainer rot_checkpoint:1
9  rot    trainer shard#1
11 kill   replica#1
"""


class RegionSpec(object):
    """The declarative region: process inventory + fault schedule.

    ``mode`` presets: ``run`` (full topology, no schedule), ``storm``
    (full topology + :data:`STORM_SCHEDULE`), ``smoke`` (1 data server,
    1 replica, 1 device, rot-publish only — the tier-1-sized variant).
    A ``--spec`` JSON file overrides any field.
    """

    FIELDS = ("data_servers", "replicas", "router_workers", "devices",
              "epochs", "epoch_gap_s", "batch", "records", "clients",
              "client_pause_s", "schedule", "faults",
              "bringup_timeout_s", "settle_timeout_s")

    def __init__(self, mode="run", overrides=None):
        self.mode = mode
        self.data_servers = 2
        self.replicas = 2
        # >1 shards the fleet front end into SO_REUSEPORT router
        # workers (docs/how_to/fleet.md "Sharding the front end")
        self.router_workers = 1
        self.devices = 2
        self.epochs = 16
        self.epoch_gap_s = 0.8
        self.batch = 8
        self.records = 48
        self.clients = 4
        self.client_pause_s = 0.02
        self.schedule = STORM_SCHEDULE if mode == "storm" else ""
        if mode == "storm":
            # the storm rides the sharded front end: two reuseport
            # router workers between the clients and the replicas
            self.router_workers = 2
        self.faults = {}          # role -> MXTPU_FAULTS spec at spawn 0
        self.bringup_timeout_s = 420.0
        self.settle_timeout_s = 420.0
        if mode == "smoke":
            self.data_servers = 1
            self.replicas = 1
            self.devices = 1
            # enough epochs x gap that the trainer is still publishing
            # when the storm's rot lands (it must rot a LIVE publish)
            self.epochs = 8
            self.epoch_gap_s = 0.8
            self.clients = 2
            self.schedule = "1 arm trainer rot_checkpoint:1\n"
        for key, value in (overrides or {}).items():
            if key not in self.FIELDS:
                raise SystemExit("unknown region spec field: %r" % key)
            setattr(self, key, value)

    @classmethod
    def for_mode(cls, mode, spec_file=None):
        overrides = None
        if spec_file:
            with open(spec_file) as f:
                overrides = json.load(f)
        return cls(mode, overrides)

    def as_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


# ---------------------------------------------------------------------------
# the orchestrator
# ---------------------------------------------------------------------------

class Region(object):
    """Bring-up, supervision, chaos, observation, and drain for one
    region.  Single-threaded control flow plus three service threads
    (fleet poller, storm scheduler, stats HTTP server) and the client
    pool; all shared counters sit behind one lock."""

    MODEL = "mlp"
    FEATS = 3 * 8 * 8

    def __init__(self, spec, run_dir, res, stats_port=0):
        self.spec = spec
        self.run_dir = run_dir
        self.res = res
        self.stats_port = stats_port
        self.ckpt_dir = os.path.join(run_dir, "ckpt")
        self.fleet_dir = os.path.join(run_dir, "fleet")
        self.status_file = os.path.join(run_dir, "trainer-status.json")
        self.cmd_file = os.path.join(run_dir, "trainer-commands")
        self.rec = os.path.join(run_dir, "region.rec")
        self.idx = os.path.join(run_dir, "region.idx")
        self.schedule = res.parse_fault_schedule(spec.schedule)
        self.sups = {}
        self._lock = threading.Lock()
        self._events = {}
        self._clients = {"requests": 0, "ok": 0, "retried": 0,
                         "dropped": 0, "retries": 0}
        self._world = spec.devices
        self._data_addrs = [None] * spec.data_servers
        self._fleet_port = None
        self._fleet_stats = {}
        self._counters = {}       # monotonic sources already credited
        self._recon = {"pid": None, "base": 0, "last": 0}
        self._first_served = None
        self._storm_fired = 0
        self._storm_t0 = None
        self._stop = threading.Event()
        self._threads = []
        self._logs = []
        self._httpd = None
        self.stats_addr = None

    # -- named events ------------------------------------------------------
    def _count(self, name, n=1):
        with self._lock:
            self._events[name] = self._events.get(name, 0) + n

    def _credit(self, name, value):
        """Turn a monotonic source counter (rollout.rejected, replica
        restarts, ...) into a named event by crediting only the delta."""
        with self._lock:
            seen = self._counters.get(name, 0)
            if value > seen:
                self._events[name] = self._events.get(name, 0) \
                    + (value - seen)
                self._counters[name] = value

    def _on_exit(self, role, rc, relaunching):
        self._count("exit:%s:rc=%s" % (role, rc))
        if not relaunching and not self._stop.is_set():
            _log("%s exited rc=%s (not relaunching)" % (role, rc))

    # -- helpers -----------------------------------------------------------
    def _logfile(self, name):
        f = open(os.path.join(self.run_dir, name + ".log"), "ab")
        self._logs.append(f)
        return f

    def _tail(self, name, n=2000):
        path = os.path.join(self.run_dir, name + ".log")
        try:
            with open(path, "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except OSError:
            return "<no %s.log>" % name

    def _wait(self, cond, timeout, what):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            value = cond()
            if value:
                return value
            time.sleep(0.1)
        snaps = {r: s.snapshot() for r, s in self.sups.items()}
        raise RuntimeError(
            "region: timed out waiting for %s\nroles: %s\n"
            "trainer log tail:\n%s\nfleet log tail:\n%s"
            % (what, json.dumps(snaps), self._tail("trainer"),
               self._tail("fleet")))

    def _read_port_file(self, path):
        try:
            with open(path) as f:
                txt = f.read().strip()
            return txt or None
        except OSError:
            return None

    def _base_env(self, arm=()):
        env = self.res.region_faults_env(os.environ, arm=arm)
        env["JAX_PLATFORMS"] = "cpu"
        return env

    # -- bring-up ----------------------------------------------------------
    def up(self):
        os.makedirs(self.ckpt_dir, exist_ok=True)
        os.makedirs(self.fleet_dir, exist_ok=True)
        open(self.cmd_file, "w").close()
        self._build_dataset()
        self._start_data_servers()
        self._start_trainer()
        self._wait_first_publish()
        self._start_fleet()
        self._start_stats_server()
        self._start_poller()
        self._start_clients()
        with open(self.cmd_file, "a") as f:
            f.write("go\n")
        _log("region is up: %d data server(s), trainer(world=%d), "
             "%d replica(s), %d client(s); stats at http://%s/region/stats"
             % (self.spec.data_servers, self._world, self.spec.replicas,
                self.spec.clients, self.stats_addr))

    def _build_dataset(self):
        script = os.path.join(self.run_dir, "dataset.py")
        with open(script, "w") as f:
            f.write(_DATASET_SCRIPT)
        env = self._base_env()
        env["REGION_REPO"] = _ROOT
        res = subprocess.run(
            [sys.executable, script, self.rec, self.idx,
             str(self.spec.records)],
            env=env, capture_output=True, text=True)
        if res.returncode != 0:
            raise RuntimeError("dataset build failed:\n%s" % res.stderr)
        _log("dataset: %d records at %s" % (self.spec.records, self.rec))

    def _start_data_servers(self):
        for i in range(self.spec.data_servers):
            role = "data#%d" % i
            port_file = os.path.join(self.run_dir, "data-%d.port" % i)

            def command(restarts, i=i, port_file=port_file):
                # first spawn takes an ephemeral port; respawns PIN the
                # recorded one so the trainer's reconnect machinery
                # redials the same well-known address
                port = "0"
                if restarts > 0 and self._data_addrs[i]:
                    port = self._data_addrs[i].rsplit(":", 1)[1]
                return [sys.executable,
                        os.path.join(_HERE, "data_server.py"),
                        "--port", port, "--port-file", port_file]

            def env(restarts, role=role):
                arm = self.spec.faults.get(role, "") if restarts == 0 \
                    else ""
                return self._base_env(arm=arm)

            sup = Supervisor(role, command, env=env, retry_any=True,
                             max_restarts=8, backoff=0.3, log=_log,
                             on_exit=self._on_exit,
                             stdout=self._logfile(role.replace("#", "-")),
                             stderr=subprocess.STDOUT)
            with self._lock:
                self.sups[role] = sup.start()
            addr = self._wait(
                lambda pf=port_file: self._read_port_file(pf),
                60, "%s port file" % role)
            self._data_addrs[i] = addr
            _log("%s listening at %s" % (role, addr))

    def _start_trainer(self):
        script = os.path.join(self.run_dir, "trainer.py")
        with open(script, "w") as f:
            f.write(_TRAINER_SCRIPT)

        def env(restarts):
            arm = self.spec.faults.get("trainer", "") if restarts == 0 \
                else ""
            e = self._base_env(arm=arm)
            e.update({
                "REGION_REPO": _ROOT,
                "REGION_WORLD": str(self._world),
                "REGION_CKPT_DIR": self.ckpt_dir,
                "REGION_STATUS_FILE": self.status_file,
                "REGION_CMD_FILE": self.cmd_file,
                "REGION_DATA_SERVERS": ",".join(self._data_addrs),
                "REGION_REC": self.rec,
                "REGION_IDX": self.idx,
                "REGION_EPOCHS": str(self.spec.epochs),
                "REGION_EPOCH_GAP_S": str(self.spec.epoch_gap_s),
                "REGION_BATCH": str(self.spec.batch),
                # the region trainer publishes sharded-native: every
                # zero3 shard its own verified blob, so the storm's
                # ``rot shard#k`` events have blobs to damage and the
                # resize event proves elastic resume FROM shard blobs
                "MXTPU_CKPT_SHARDED": "1",
                # a dead data host must be noticed in seconds, not the
                # production default 30
                "MXTPU_DATA_NET_TIMEOUT_S": "3",
                "MXTPU_DATA_NET_RECONNECT_S": "0.3",
                "MXTPU_DATA_NET_RETRIES": "60",
            })
            return e

        sup = Supervisor("trainer", [sys.executable, script], env=env,
                         retry_any=True, max_restarts=8, backoff=0.5,
                         log=_log, on_exit=self._on_exit,
                         stdout=self._logfile("trainer"),
                         stderr=subprocess.STDOUT)
        with self._lock:
            self.sups["trainer"] = sup.start()

    def _wait_first_publish(self):
        def published():
            epoch, problems = self.res.verify_promotion(self.ckpt_dir)
            return epoch if epoch and not problems else None

        epoch = self._wait(published, self.spec.bringup_timeout_s,
                           "first verified checkpoint publish")
        _log("trainer published verified epoch %d" % epoch)

    def _start_fleet(self):
        port_file = os.path.join(self.run_dir, "fleet.port")
        command = [
            sys.executable, os.path.join(_HERE, "fleet.py"), "serve",
            "--model", "%s=%s" % (self.MODEL, self.ckpt_dir),
            "--input-shape", "%s:data=%d" % (self.MODEL, self.FEATS),
            "--replicas", str(self.spec.replicas),
            "--workers", str(self.spec.router_workers),
            "--device-sets", "cpu", "--buckets", "1,2,4",
            "--run-dir", self.fleet_dir, "--port", "0",
            "--port-file", port_file, "--max-restarts", "6", "--watch",
        ]

        def env(restarts):
            e = self._base_env(arm=self.spec.faults.get("fleet", "")
                               if restarts == 0 else "")
            e.update({"MXTPU_FLEET_HEARTBEAT_S": "0.3",
                      "MXTPU_FLEET_EVICT_S": "1.5",
                      "MXTPU_SERVE_MAX_WAIT_MS": "1",
                      "MXTPU_SWAP_POLL_S": "0.2"})
            return e

        # the fleet is its own supervisor for its replicas; the region
        # does not relaunch the router (a dead router is a failed drill)
        sup = Supervisor("fleet", command, env=env, retry_any=False,
                         max_restarts=0, log=_log, on_exit=self._on_exit,
                         stdout=self._logfile("fleet"),
                         stderr=subprocess.STDOUT)
        with self._lock:
            self.sups["fleet"] = sup.start()
        port = self._wait(lambda: self._read_port_file(port_file),
                          self.spec.bringup_timeout_s, "fleet port file")
        self._fleet_port = int(port.rsplit(":", 1)[1]) if ":" in port \
            else int(port)
        _log("fleet router on port %d" % self._fleet_port)

    # -- observation -------------------------------------------------------
    def _http_json(self, port, path, timeout=10):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                return None
            return json.loads(body.decode("utf-8"))
        except (OSError, ValueError):
            return None
        finally:
            conn.close()

    def _trainer_status(self):
        try:
            with open(self.status_file) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}

    def _replica_epochs(self, stats=None):
        stats = self._fleet_stats if stats is None else stats
        out = {}
        for rid, entry in (stats.get("replicas") or {}).items():
            epoch = (entry.get("epochs") or {}).get(self.MODEL)
            if epoch is not None:
                out[rid] = epoch
        return out

    def _poll_once(self):
        if self._fleet_port is None:
            return
        stats = self._http_json(self._fleet_port, "/stats", timeout=5)
        if not stats:
            return
        self._fleet_stats = stats
        epochs = self._replica_epochs(stats)
        if self._first_served is None and \
                len(epochs) == self.spec.replicas:
            self._first_served = min(epochs.values())
        # monotonic sources -> counted named events
        rollout = (stats.get("rollout") or {})
        self._credit("publish_rejected", rollout.get("rejected", 0))
        self._credit("rollout_halted", rollout.get("halted", 0))
        self._credit("rollouts", rollout.get("rollouts", 0))
        for rid, entry in (stats.get("replicas") or {}).items():
            self._credit("replica_respawn:%s" % rid,
                         entry.get("restarts", 0) or 0)
        status = self._trainer_status()
        if status:
            self._reconnect_total(status)

    def _reconnect_total(self, status):
        """Accumulate data-plane reconnects across trainer generations
        (each respawned trainer restarts its iterator counters)."""
        pid, cur = status.get("pid"), status.get("reconnects", 0)
        r = self._recon
        with self._lock:
            if r["pid"] is None:
                r["pid"] = pid
            if pid != r["pid"]:
                r["base"] += r["last"]
                r["pid"], r["last"] = pid, 0
            if cur > r["last"]:
                self._events["data_reconnect"] = \
                    self._events.get("data_reconnect", 0) \
                    + (cur - r["last"])
                r["last"] = cur
            return r["base"] + r["last"]

    def _start_poller(self):
        def loop():
            while not self._stop.is_set():
                try:
                    self._poll_once()
                except Exception as e:  # noqa: BLE001 — observer must
                    _log("poll error: %r" % e)       # outlive any seam
                self._stop.wait(0.4)

        t = threading.Thread(target=loop, name="region-poll", daemon=True)
        t.start()
        self._threads.append(t)
        self._wait(lambda: self._replica_epochs() or None,
                   self.spec.bringup_timeout_s,
                   "replicas to serve the first epoch")

    # -- the stats endpoint ------------------------------------------------
    def stats_payload(self):
        with self._lock:
            events = dict(self._events)
            clients = dict(self._clients)
            recon = self._recon["base"] + self._recon["last"]
        fleet = self._fleet_stats or {}
        rollout = fleet.get("rollout") or {}
        status = self._trainer_status()
        epoch, problems = self.res.verify_promotion(self.ckpt_dir)
        published = epoch if epoch and not problems else None
        epochs = self._replica_epochs(fleet)
        return {
            "roles": {r: s.snapshot() for r, s in self.sups.items()},
            "trainer": {"epoch": status.get("epoch"),
                        "world": status.get("world"),
                        "batches": status.get("batches")},
            "published_epoch": published,
            "served_epochs": epochs,
            "first_served_epoch": self._first_served,
            "data_reconnects": recon,
            "freshness_ms": (fleet.get("fleet") or {}).get("freshness_ms"),
            "fleet": {
                "healthy": (fleet.get("fleet") or {}).get(
                    "replicas_healthy"),
                "freshness_ms": (fleet.get("fleet")
                                 or {}).get("freshness_ms"),
                "rollout": {k: rollout.get(k) for k in
                            ("polls", "rollouts", "rejected", "halted")},
            },
            "clients": clients,
            "events": events,
            "storm": {"scheduled": len(self.schedule),
                      "fired": self._storm_fired,
                      "labels": [e.label for e in self.schedule],
                      "window_s": None if self._storm_t0 is None else
                      round(time.monotonic() - self._storm_t0, 1)},
        }

    def _start_stats_server(self):
        region = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                if self.path.startswith("/region/stats"):
                    body = json.dumps(region.stats_payload(),
                                      sort_keys=True).encode("utf-8")
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                else:
                    self.send_error(404)

            def log_message(self, *a):  # quiet
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", self.stats_port),
                                          Handler)
        self._httpd.daemon_threads = True
        t = threading.Thread(target=self._httpd.serve_forever,
                             name="region-stats", daemon=True)
        t.start()
        self._threads.append(t)
        self.stats_addr = "127.0.0.1:%d" % self._httpd.server_address[1]
        with open(os.path.join(self.run_dir, "region.port"), "w") as f:
            f.write(self.stats_addr)

    # -- clients -----------------------------------------------------------
    def _start_clients(self):
        body = json.dumps(
            {"inputs": {"data": [0.5] * self.FEATS}}).encode("utf-8")
        headers = {"Content-Type": "application/json"}

        def one_request():
            with self._lock:
                self._clients["requests"] += 1
            retried = False
            for _attempt in range(40):
                if self._stop.is_set() and _attempt > 0:
                    break
                try:
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", self._fleet_port, timeout=60)
                    conn.request("POST", "/predict/%s" % self.MODEL,
                                 body=body, headers=headers)
                    resp = conn.getresponse()
                    resp.read()
                    code = resp.status
                    conn.close()
                except OSError as e:
                    self._count("client_drop:%s" % type(e).__name__)
                    break
                if code == 200:
                    with self._lock:
                        self._clients["ok"] += 1
                        if retried:
                            self._clients["retried"] += 1
                    return
                if code in (502, 503):
                    # with the exactly-once layer the ROUTER absorbs a
                    # dead replica (one keyed resend elsewhere), so no
                    # storm should ever produce a client_retry:502 —
                    # that event means the fleet leaked a dead-replica
                    # failure to its clients (the no_502_leak check).
                    # 503 retries are different: a kill overlapping a
                    # rolling swap's fence on a small fleet leaves a
                    # legitimate no-routable-replica window, and this
                    # loop is the backstop that rides it out
                    retried = True
                    with self._lock:
                        self._clients["retries"] += 1
                    self._count("client_retry:%d" % code)
                    time.sleep(0.15)
                    continue
                self._count("client_drop:http%d" % code)
                break
            with self._lock:
                self._clients["dropped"] += 1

        def loop():
            while not self._stop.is_set():
                one_request()
                self._stop.wait(self.spec.client_pause_s)

        for i in range(self.spec.clients):
            t = threading.Thread(target=loop, name="region-client-%d" % i,
                                 daemon=True)
            t.start()
            self._threads.append(t)

    # -- STORM -------------------------------------------------------------
    def storm(self):
        """Run the fault schedule (threaded), then settle the region:
        trainer to its final epoch, every replica serving it."""
        storm_thread = None
        if self.schedule:
            storm_thread = threading.Thread(target=self._run_schedule,
                                            name="region-storm",
                                            daemon=True)
            storm_thread.start()
        self._settle(storm_thread)

    def _run_schedule(self):
        self._storm_t0 = time.monotonic()
        _log("STORM window opens: %d scheduled event(s)"
             % len(self.schedule))
        for ev in self.schedule:
            wait = ev.at_s - (time.monotonic() - self._storm_t0)
            if wait > 0 and self._stop.wait(wait):
                return
            try:
                self._fire(ev)
                self._count(ev.label)
                with self._lock:
                    self._storm_fired += 1
                _log("STORM fired %s (t=%.1fs)"
                     % (ev.label,
                        time.monotonic() - self._storm_t0))
            except Exception as e:  # noqa: BLE001 — a missed event is a
                self._count(ev.label + ":missed")    # counted failure,
                _log("STORM event %s failed: %r" % (ev.label, e))

    def _fire(self, ev):
        if ev.action == "kill" and ev.target.startswith("data#"):
            pid = self.sups[ev.target].kill()
            if pid is None:
                raise RuntimeError("%s had no live child" % ev.target)
        elif ev.action == "kill" and ev.target.startswith("replica#"):
            rid = ev.target.split("#", 1)[1]
            pid = None
            deadline = time.monotonic() + 5
            while pid is None and time.monotonic() < deadline:
                entry = (self._fleet_stats.get("replicas")
                         or {}).get(rid) or {}
                pid = entry.get("pid")
                if pid is None:
                    time.sleep(0.2)
            if pid is None:
                raise RuntimeError("no pid for %s in fleet stats"
                                   % ev.target)
            os.kill(pid, signal.SIGKILL)
        elif ev.action == "resize" and ev.target == "trainer":
            # the elastic story: SIGKILL + respawn at a different world
            # size; env_fn reads self._world on the respawn, and
            # MXTPU_RESUME=1 makes it a checkpoint resume
            self._world = int(ev.arg)
            if self.sups["trainer"].kill() is None:
                raise RuntimeError("trainer had no live child to resize")
        elif ev.action == "arm":
            if ev.target != "trainer":
                raise RuntimeError("arm targets the trainer (got %r)"
                                   % ev.target)
            with open(self.cmd_file, "a") as f:
                f.write("arm %s\n" % ev.arg)
        elif ev.action == "rot":
            # sugar over arm: ``rot trainer shard#k`` arms rot_shard so
            # the k-th post-publish consume (index order) damages
            # exactly blob k of the next sharded publish
            if ev.target != "trainer":
                raise RuntimeError("rot targets the trainer (got %r)"
                                   % ev.target)
            k = int(ev.arg.split("#", 1)[1])
            with open(self.cmd_file, "a") as f:
                f.write("arm rot_shard:1@%d\n" % k)
        else:
            raise RuntimeError("unhandled event %r" % ev)

    def _settle(self, storm_thread):
        deadline = self.spec.settle_timeout_s

        def storm_done():
            return storm_thread is None or not storm_thread.is_alive()

        self._wait(storm_done, deadline, "storm schedule to finish")

        def trainer_done():
            return self.sups["trainer"].state == "done" and \
                self._trainer_status().get("epoch") == self.spec.epochs

        self._wait(trainer_done, deadline,
                   "trainer to reach epoch %d" % self.spec.epochs)

        def converged():
            epochs = self._replica_epochs()
            return len(epochs) == self.spec.replicas and \
                set(epochs.values()) == {self.spec.epochs}

        self._wait(converged, deadline,
                   "every replica to serve epoch %d" % self.spec.epochs)
        _log("settled: all %d replica(s) serve epoch %d"
             % (self.spec.replicas, self.spec.epochs))

    # -- acceptance --------------------------------------------------------
    def checks(self):
        with self._lock:
            events = dict(self._events)
            clients = dict(self._clients)
        epochs = self._replica_epochs()
        final = min(epochs.values()) if epochs else None
        _epoch, problems = self.res.verify_promotion(
            self.ckpt_dir, epoch=final) if final else (None, ["unserved"])
        drops = clients["dropped"] + sum(
            n for name, n in events.items()
            if name.startswith("client_drop:"))
        out = {
            "drop_free": drops == 0 and clients["requests"] > 0,
            # the exactly-once contract's client-visible face: the
            # router absorbs dead replicas by keyed resend, so no
            # client ever retried a 502.  (503 no-routable windows —
            # a kill overlapping a rolling swap's fence on a small
            # fleet — stay the backstop loop's business and are not a
            # leak.)
            "no_502_leak": events.get("client_retry:502", 0) == 0,
            "storm_fired": self._storm_fired == len(self.schedule),
            "events_counted": all(ev.label in events
                                  for ev in self.schedule),
            "served_epoch_advance": final is not None and
            self._first_served is not None and final > self._first_served,
            "served_epoch_verified": not problems,
            "trainer_completed":
                self.sups["trainer"].state == "done",
        }
        if any((ev.action == "arm" and "rot" in (ev.arg or ""))
               or ev.action == "rot" for ev in self.schedule):
            out["rot_publish_rejected"] = \
                events.get("publish_rejected", 0) >= 1
        return out

    def report(self, elapsed_s):
        checks = self.checks()
        with self._lock:
            client_retries = self._clients["retries"]
        return {"mode": self.spec.mode, "ok": all(checks.values()),
                "elapsed_s": round(elapsed_s, 1), "checks": checks,
                # total backstop retries, 503 churn windows included —
                # the exactly-once signal itself is the no_502_leak
                # check (zero client_retry:502 events)
                "client_retries": client_retries,
                "spec": self.spec.as_dict(),
                "stats": self.stats_payload()}

    # -- drain -------------------------------------------------------------
    def down(self):
        self._stop.set()
        for t in self._threads:
            if t is not threading.current_thread():
                t.join(timeout=10)
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
        rcs = {}
        with self._lock:
            fleet_sup = self.sups.get("fleet")
            trainer_sup = self.sups.get("trainer")
            data_sups = [(role, sup) for role, sup in self.sups.items()
                         if role.startswith("data#")]
        if fleet_sup is not None:
            rcs["fleet"] = fleet_sup.drain(timeout=90)
        if trainer_sup is not None:
            rcs["trainer"] = trainer_sup.drain(timeout=30)
        for role, sup in data_sups:
            rcs[role] = sup.drain(timeout=15)
        for f in self._logs:
            try:
                f.close()
            except OSError:
                pass
        _log("drained: %s" % json.dumps(rcs, sort_keys=True))
        return rcs


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(
        description="the composed region drill "
                    "(docs/how_to/region.md)")
    parser.add_argument("mode", choices=("run", "smoke", "storm"),
                        help="run: no chaos; smoke: 1x1 rot-only "
                             "(~a minute); storm: the full drill")
    parser.add_argument("--spec", default=None,
                        help="JSON file overriding RegionSpec fields")
    parser.add_argument("--run-dir", default=None,
                        help="working directory (default: a temp dir, "
                             "removed on success)")
    parser.add_argument("--stats-port", type=int, default=0,
                        help="/region/stats port (default ephemeral; "
                             "written to <run-dir>/region.port)")
    parser.add_argument("--report", default=None,
                        help="also write the JSON report here")
    parser.add_argument("--keep", action="store_true",
                        help="keep the run dir even on success")
    args = parser.parse_args(argv)

    res = _bootstrap()
    spec = RegionSpec.for_mode(args.mode, args.spec)
    run_dir = args.run_dir or tempfile.mkdtemp(prefix="mxregion-")
    os.makedirs(run_dir, exist_ok=True)
    keep = args.keep or args.run_dir is not None
    region = Region(spec, run_dir, res, stats_port=args.stats_port)
    t0 = time.monotonic()
    report = None
    try:
        region.up()
        region.storm()
        report = region.report(time.monotonic() - t0)
    finally:
        try:
            region.down()
        except Exception as e:  # noqa: BLE001 — drain best-effort on a
            _log("drain error: %r" % e)           # failed bring-up
    print("REGION_REPORT " + json.dumps(report, sort_keys=True))
    for name, ok in sorted(report["checks"].items()):
        _log("check %-24s %s" % (name, "PASS" if ok else "FAIL"))
    stats = report["stats"]
    _log("clients: %s" % json.dumps(stats["clients"], sort_keys=True))
    _log("events:  %s" % json.dumps(stats["events"], sort_keys=True))
    _log("freshness_ms=%s served=%s->%s"
         % (stats["freshness_ms"], stats["first_served_epoch"],
            stats["served_epochs"]))
    if args.report:
        with open(args.report, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
    if report["ok"] and not keep:
        shutil.rmtree(run_dir, ignore_errors=True)
    elif not report["ok"]:
        _log("run dir kept for post-mortem: %s" % run_dir)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
