"""mxfleet unit tests: manifest geometry, device pinning, the router's
routing/spill/eviction/idempotency policies against FAKE replicas
(stdlib HTTP servers — no jax, no daemons), the controller's relaunch
discipline against dummy children, and the warm-store build against a
stub serve binary.  The real-daemon composition lives in
tests/test_chaos.py (the SIGKILL, gray-failure and rolling-swap drills).
"""
import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.fleet import (  # noqa: E402
    Autoscaler, FleetManifest, FleetRouter, FleetViewPublisher,
    FleetViewReader, ReplicaController, build_warm_store,
    replica_device_env, reserve_port, warm_store_manifest)

pytestmark = pytest.mark.serve


# ---------------------------------------------------------------------------
# manifest + device pinning
# ---------------------------------------------------------------------------

def test_manifest_from_flags_and_file_roundtrip(tmp_path):
    man = FleetManifest.from_flags(
        ["mlp=/ckpts/mlp:3", "resnet=/ckpts/rdir"],
        ["mlp:data=784", "resnet:data=3,32,32"],
        replicas=2, buckets="1,2,4", device_sets="cpu")
    assert man.names() == ["mlp", "resnet"]
    assert man.models["mlp"]["target"] == "/ckpts/mlp:3"
    assert man.models["resnet"]["shapes"] == {"data": (3, 32, 32)}
    path = man.save(str(tmp_path / "fleet.json"))
    back = FleetManifest.from_file(path)
    assert back.to_doc() == man.to_doc()
    assert back.replicas == 2 and back.buckets == "1,2,4"


def test_manifest_home_is_stable_mod_replicas():
    man = FleetManifest.from_flags(
        ["a=/x:1", "b=/x:1", "c=/x:1"], ["data=4"], replicas=2)
    assert [man.home(m) for m in ("a", "b", "c")] == [0, 1, 0]
    with pytest.raises(MXNetError):
        man.home("nope")


def test_manifest_validation():
    with pytest.raises(MXNetError):
        FleetManifest({})                       # no models
    with pytest.raises(MXNetError):
        FleetManifest({"m": "/x:1"}, replicas=0)
    with pytest.raises(MXNetError):
        FleetManifest.from_flags(["justaname"], [])


def test_manifest_serve_argv_is_the_serve_py_contract():
    man = FleetManifest.from_flags(
        ["mlp=/ckpts/mlp:3"], ["mlp:data=784"], replicas=1,
        buckets="1,2")
    argv = man.serve_argv("/repo/tools/serve.py", port_file="/run/p")
    s = " ".join(argv)
    assert "--model mlp=/ckpts/mlp:3" in s
    assert "--input-shape mlp:data=784" in s
    assert "--buckets 1,2" in s and "--port-file /run/p" in s
    assert "--warmup" in s and "--warmup-only" not in s
    only = man.serve_argv("/repo/tools/serve.py", warmup_only=True)
    assert "--warmup-only" in " ".join(only)


def test_replica_device_env_specs():
    assert replica_device_env(None, 0) == {}
    assert replica_device_env("cpu", 3) == {"JAX_PLATFORMS": "cpu"}
    env0 = replica_device_env("tpu:0,1;2,3", 0)
    env1 = replica_device_env("tpu:0,1;2,3", 1)
    assert env0["TPU_VISIBLE_CHIPS"] == "0,1"
    assert env1["TPU_VISIBLE_CHIPS"] == "2,3"
    assert env0["JAX_PLATFORMS"] == "tpu"
    # a chip belongs to one process: more replicas than chip sets is an
    # error, at the replica and already at the manifest
    with pytest.raises(MXNetError, match="one process"):
        replica_device_env("tpu:0;1", 2)
    with pytest.raises(MXNetError, match="one process"):
        FleetManifest({"m": "/x:1"}, replicas=3, device_sets="tpu:0;1")
    assert FleetManifest({"m": "/x:1"}, replicas=2,
                         device_sets="tpu:0;1").device_sets == "tpu:0;1"
    # single-chip sets pin the 1x1x1 process topology too
    single = replica_device_env("tpu:0;1", 1)
    assert single["TPU_PROCESS_BOUNDS"] == "1,1,1"
    with pytest.raises(MXNetError):
        replica_device_env("gpu:0", 0)


# ---------------------------------------------------------------------------
# the router, against fake replicas
# ---------------------------------------------------------------------------

class _FakeReplica(object):
    """A stdlib HTTP server speaking the mxserve surface: /healthz,
    /stats (scriptable queue depths / est waits), /predict/<m> (records
    and answers).  ``die()`` closes the listener (connection-refused
    from then on); ``revive()`` rebinds the SAME port."""

    def __init__(self):
        self.received = []
        self.depths = {}
        self.est_wait = {}
        self.counters = {"completed": 0, "shed_queue": 0}
        self.draining = False
        #: scriptable gray-failure shape: per-predict latency, a
        #: reported recent-p99, and per-model tenant queue depths
        self.predict_delay_s = 0.0
        self.p99_recent = None
        self.tenants = {}
        #: drop (no response, closed socket) the next N /healthz
        #: probes — the single-dropped-packet shape the probe retry
        #: exists for
        self.fail_healthz = 0
        #: {model: epoch} reported on /healthz + /stats; /swap/<model>
        #: advances it (or refuses when swap_refuse is set)
        self.epochs = {}
        self.swap_refuse = False
        #: seam hooks for /swap/<model> while the POST is IN FLIGHT:
        #: ``on_swap(model, epoch)`` runs before the reply (the rollout
        #: race tests land a concurrent publish there); ``swap_drop``
        #: then kills the response — no status line, dead socket (the
        #: replica died mid-swap)
        self.on_swap = None
        self.swap_drop = False
        self._lock = threading.Lock()
        self._server = None
        self._thread = None
        self.port = None
        self._bind(0)

    def _bind(self, port):
        fake = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *args):
                pass

            def _reply(self, status, payload):
                body = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    with fake._lock:
                        drop = fake.fail_healthz > 0
                        if drop:
                            fake.fail_healthz -= 1
                    if drop:
                        # a dropped packet: no status line, dead socket
                        self.close_connection = True
                        return
                    self._reply(200, {
                        "status": "draining" if fake.draining else "ok",
                        "epochs": dict(fake.epochs)})
                elif self.path == "/stats":
                    with fake._lock:
                        payload = {
                            "queue_depth": dict(fake.depths),
                            "est_wait_ms": dict(fake.est_wait),
                            "epochs": dict(fake.epochs),
                            "counters": dict(fake.counters)}
                        if fake.p99_recent is not None:
                            payload["latency_ms"] = {
                                "p99_recent": fake.p99_recent}
                        if fake.tenants:
                            payload["tenants"] = {
                                m: dict(t)
                                for m, t in fake.tenants.items()}
                        self._reply(200, payload)
                else:
                    self._reply(404, {})

            def do_POST(self):
                length = int(self.headers.get("Content-Length", 0))
                body = self.rfile.read(length)
                with fake._lock:
                    fake.received.append((self.path, body))
                if self.path.startswith("/swap/"):
                    model = self.path[len("/swap/"):]
                    if fake.swap_refuse:
                        self._reply(409, {"ok": False,
                                          "action": "rejected",
                                          "problems": ["refused"]})
                        return
                    epoch = json.loads(body.decode()).get("epoch")
                    hook = fake.on_swap
                    if hook is not None:
                        hook(model, epoch)
                    if fake.swap_drop:
                        # died mid-swap: no status line, dead socket
                        self.close_connection = True
                        return
                    with fake._lock:
                        fake.epochs[model] = epoch
                    self._reply(200, {"ok": True, "action": "promoted",
                                      "epoch": epoch})
                    return
                if fake.predict_delay_s:
                    time.sleep(fake.predict_delay_s)
                with fake._lock:
                    fake.counters["completed"] += 1
                self._reply(200, {"fake": fake.port,
                                  "path": self.path})

        self._server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, daemon=True)
        self._thread.start()

    def die(self):
        self._server.shutdown()
        self._server.server_close()

    def revive(self):
        self._bind(self.port)

    def close(self):
        try:
            self.die()
        except Exception:  # noqa: BLE001 — already dead
            pass


def _mk_router(fakes, models=("a", "b"), **kw):
    man = FleetManifest.from_flags(
        ["%s=/x:1" % m for m in models], ["data=4"],
        replicas=len(fakes))
    endpoints = {i: ("127.0.0.1", f.port) for i, f in enumerate(fakes)}
    kw.setdefault("heartbeat_s", 0.15)
    kw.setdefault("evict_s", 0.6)
    kw.setdefault("spill_queue", 4)
    router = FleetRouter(endpoints, man, port=0, **kw)
    return router


@pytest.fixture
def two_fakes():
    fakes = [_FakeReplica(), _FakeReplica()]
    yield fakes
    for f in fakes:
        f.close()


def _predict(router, model, n=1):
    out = []
    for _ in range(n):
        out.append(router.proxy_predict(
            model, json.dumps({"inputs": {"data": [0, 0, 0, 0]}})
            .encode(), {"Content-Type": "application/json"}))
    return out


def test_router_routes_each_model_to_its_home(two_fakes):
    router = _mk_router(two_fakes)
    assert router.probe() == [0, 1]
    _predict(router, "a", 3)        # home: replica 0
    _predict(router, "b", 2)        # home: replica 1
    assert len(two_fakes[0].received) == 3
    assert len(two_fakes[1].received) == 2
    assert all(p == "/predict/a" for p, _ in two_fakes[0].received)
    assert all(p == "/predict/b" for p, _ in two_fakes[1].received)
    assert router.stats.snapshot()["counters"]["routed"] == 5
    assert router.stats.snapshot()["counters"].get("spilled", 0) == 0


def test_router_spills_when_home_queue_crosses_the_bar(two_fakes):
    two_fakes[0].depths = {"a": 10}         # home of "a" is saturated
    router = _mk_router(two_fakes, spill_queue=4)
    router.probe()
    _predict(router, "a", 3)
    assert len(two_fakes[1].received) == 3  # spilled to the idle one
    assert len(two_fakes[0].received) == 0
    assert router.stats.snapshot()["counters"]["spilled"] == 3


def test_router_spills_on_slo_estimate(two_fakes):
    two_fakes[0].est_wait = {"a": 500.0}    # deep estimated wait
    router = _mk_router(two_fakes, slo_ms=100.0)
    router.probe()
    _predict(router, "a", 2)
    assert len(two_fakes[1].received) == 2
    assert router.stats.snapshot()["counters"]["spilled"] == 2


def test_router_evicts_on_heartbeat_age_then_rejoins(two_fakes):
    router = _mk_router(two_fakes)
    router.serve_in_background()
    try:
        assert sorted(router.healthy()) == [0, 1]
        two_fakes[0].die()
        deadline = time.monotonic() + 5
        while 0 in router.healthy():
            assert time.monotonic() < deadline, "never evicted"
            time.sleep(0.05)
        # new traffic for replica-0-homed "a" reroutes to the survivor
        # — counted as FAILOVER (rerouted), not as load spill
        (status, _, _), = _predict(router, "a")
        assert status == 200
        assert len(two_fakes[1].received) == 1
        counters = router.stats.snapshot()["counters"]
        assert counters["rerouted"] == 1
        assert counters.get("spilled", 0) == 0
        # the respawned replica rejoins on the next successful probe
        two_fakes[0].revive()
        deadline = time.monotonic() + 5
        while 0 not in router.healthy():
            assert time.monotonic() < deadline, "never rejoined"
            time.sleep(0.05)
        _predict(router, "a")
        assert len(two_fakes[0].received) == 1      # home again
    finally:
        router.drain_and_stop(timeout=5)


def test_router_dead_replica_retried_once_elsewhere(two_fakes):
    """The exactly-once stance: a transport-failed forward is resent
    ONCE to a different healthy replica with the same request id — the
    client gets a 200 carrying ``retried: true`` instead of the old
    fail-once 502."""
    # evict_s far past what die() takes (a listener's shutdown waits out
    # its 0.5 s poll): the router must still BELIEVE the dead replica
    # healthy, or it answers 503 from the heartbeat age and never
    # forwards — same in the three tests below
    router = _mk_router(two_fakes, evict_s=60.0)
    router.probe()
    two_fakes[0].die()              # dies AFTER probing healthy
    status, body, _ = _predict(router, "a")[0]
    assert status == 200
    payload = json.loads(body.decode())
    assert payload["retried"] is True
    assert len(two_fakes[1].received) == 1      # the resend landed
    counters = router.stats.snapshot()["counters"]
    assert counters["retries"] == 1
    assert counters["retry_ok"] == 1
    # replica_errors counts FINAL client-visible failures only
    assert counters.get("replica_errors", 0) == 0


def test_router_retry_is_once_then_final_502(two_fakes):
    """The resend happens at most ONCE: with every candidate dead the
    client sees a single 502 with ``retried: true`` (the resend was
    attempted) and replica_errors counts exactly that final failure."""
    router = _mk_router(two_fakes, evict_s=60.0)
    router.probe()
    two_fakes[0].die()
    two_fakes[1].die()
    status, body, _ = _predict(router, "a")[0]
    assert status == 502
    payload = json.loads(body.decode())
    assert payload["retried"] is True
    counters = router.stats.snapshot()["counters"]
    assert counters["retries"] == 1
    assert counters.get("retry_ok", 0) == 0
    assert counters["replica_errors"] == 1


def test_router_no_resend_target_keeps_fail_once_surface():
    """A single-replica fleet has nowhere to resend: the old fail-once
    surface remains (one 502, ``retried: false``)."""
    fake = _FakeReplica()
    try:
        router = _mk_router([fake], evict_s=60.0)
        router.probe()
        fake.die()
        status, body, _ = _predict(router, "a")[0]
        assert status == 502
        payload = json.loads(body.decode())
        assert payload["retried"] is False
        assert "no other healthy replica" in payload["error"]
        counters = router.stats.snapshot()["counters"]
        assert counters.get("retries", 0) == 0
        assert counters["replica_errors"] == 1
    finally:
        fake.close()


def test_router_hedges_slow_primary_first_answer_wins(
        two_fakes, monkeypatch):
    """Tail defense: a request older than the hedge threshold gets a
    backup attempt on the other replica; the fast answer wins and the
    late primary is accounted ``hedge_wasted``."""
    monkeypatch.setenv("MXTPU_FLEET_HEDGE_PCT", "95")
    monkeypatch.setenv("MXTPU_FLEET_HEDGE_MIN_MS", "40")
    two_fakes[0].predict_delay_s = 0.6      # gray-slow home of "a"
    router = _mk_router(two_fakes)
    router.probe()
    tic = time.monotonic()
    status, body, _ = _predict(router, "a")[0]
    took_s = time.monotonic() - tic
    assert status == 200
    payload = json.loads(body.decode())
    assert payload["fake"] == two_fakes[1].port     # backup won
    assert payload.get("retried") is None           # hedge, not retry
    assert took_s < 0.5, "hedge should beat the slow primary"
    counters = router.stats.snapshot()["counters"]
    assert counters["hedges"] == 1
    # the slow primary eventually lands and is counted as waste
    deadline = time.monotonic() + 5
    while router.stats.snapshot()["counters"].get("hedge_wasted", 0) < 1:
        assert time.monotonic() < deadline, "loser never accounted"
        time.sleep(0.05)
    assert len(two_fakes[0].received) == 1
    assert len(two_fakes[1].received) == 1


def test_router_hedged_path_still_absorbs_dead_replica(
        two_fakes, monkeypatch):
    """With hedging on, a transport failure is still absorbed: the
    in-flight hedge doubles as the retry, or an explicit resend goes
    out — either way the client never sees the 502."""
    monkeypatch.setenv("MXTPU_FLEET_HEDGE_PCT", "95")
    monkeypatch.setenv("MXTPU_FLEET_HEDGE_MIN_MS", "40")
    router = _mk_router(two_fakes, evict_s=60.0)
    router.probe()
    two_fakes[0].die()
    status, body, _ = _predict(router, "a")[0]
    assert status == 200
    assert json.loads(body.decode())["retried"] is True
    assert router.stats.snapshot()["counters"].get(
        "replica_errors", 0) == 0


def test_router_brownout_sheds_low_priority_and_flooder_first(
        two_fakes, monkeypatch):
    """Brownout admission control: past the pressure SLO the router
    sheds un-prioritized work and the flooder tenant's work with a
    Retry-After 429 BEFORE it queues; prioritized well-behaved tenants
    still land."""
    monkeypatch.setenv("MXTPU_FLEET_BROWNOUT_MS", "100")
    for f in two_fakes:
        f.est_wait = {"a": 500.0, "b": 500.0}
    two_fakes[0].tenants = {"a": {"noisy": 9}}
    router = _mk_router(two_fakes, spill_queue=4)
    router.probe()
    body = json.dumps({"inputs": {"data": [0, 0, 0, 0]}}).encode()
    # priority 0 (default): shed
    status, data, _ = router.proxy_predict(
        "a", body, {"Content-Type": "application/json"})
    assert status == 429
    payload = json.loads(data.decode())
    assert payload["reason"] == "brownout"
    assert payload["retry_after_s"] > 0
    # flooder tenant: shed even at priority
    status, _, _ = router.proxy_predict(
        "a", body, {"Content-Type": "application/json",
                    "X-MXTPU-Priority": "5",
                    "X-MXTPU-Tenant": "noisy"})
    assert status == 429
    # prioritized well-behaved tenant: admitted
    status, _, _ = router.proxy_predict(
        "a", body, {"Content-Type": "application/json",
                    "X-MXTPU-Priority": "5",
                    "X-MXTPU-Tenant": "quiet"})
    assert status == 200
    counters = router.stats.snapshot()["counters"]
    assert counters["brownout_shed"] == 2
    assert counters["brownout_shed:-"] == 1
    assert counters["brownout_shed:noisy"] == 1
    assert router.stats_payload()["brownout"]["active"] is True


def test_outlier_detector_ejects_then_half_open_rejoin():
    """Unit shape of the detector: the replica whose recent p99 sits
    k-x above the fleet median is ejected (never below the N-1 floor),
    then rejoins via half-open probation once its samples come back
    clean."""
    from mxnet_tpu.fleet.view import OutlierDetector
    det = OutlierDetector(eject_x=3.0, min_samples=3, hold_s=5.0)
    assert det.enabled
    routable = {0, 1, 2}
    lat = {0: 10.0, 1: 12.0, 2: 400.0}
    t = 100.0
    for _ in range(4):
        events = det.update(routable, lat, {}, now=t)
        t += 1.0
    assert det.counters["ejects"] == 1
    assert det.ejected(now=t) == {2}
    # held out for hold_s, then promoted to half-open (routable again)
    t += 10.0
    assert det.ejected(now=t) == set()
    export = det.export(now=t)
    assert export[2]["half_open"] is True
    # clean samples on probation: reinstated for good
    det.update(routable, {0: 10.0, 1: 12.0, 2: 11.0}, {}, now=t)
    assert det.counters["eject_rejoins"] == 1
    assert det.export(now=t)[2]["half_open"] is False


def test_outlier_detector_respects_routable_floor():
    """max-eject / N-1 floor: of a two-replica fleet the detector may
    eject at most zero replicas (int(0.5*2)=1, n-1=1 -> 1; but a
    two-way split keeps the upper median at the outlier so latency
    never trips) — error streaks CAN trip it, and the second streak is
    refused on the floor."""
    from mxnet_tpu.fleet.view import OutlierDetector
    det = OutlierDetector(eject_x=3.0, min_samples=3, hold_s=60.0,
                          error_streak=2)
    errs = {0: 0, 1: 0}
    t = 100.0
    det.update({0, 1}, {}, dict(errs), now=t)
    for _ in range(3):      # both replicas grow error streaks together
        t += 1.0
        errs = {r: errs[r] + 1 for r in errs}
        det.update({0, 1}, {}, dict(errs), now=t)
    # one ejected, the other refused on the N-1 floor
    assert det.counters["ejects"] == 1
    assert det.counters["eject_blocked_floor"] >= 1
    assert len(det.ejected(now=t)) == 1


def test_router_folds_ejection_into_healthy_and_stats(
        two_fakes, monkeypatch):
    """Router integration: with MXTPU_FLEET_EJECT_X armed, a
    gray-slow replica (fast /healthz, huge reported p99) drops out of
    ``healthy()`` after enough probe passes and surfaces as
    ``ejected`` in /stats; traffic reroutes around it."""
    monkeypatch.setenv("MXTPU_FLEET_EJECT_X", "3")
    third = _FakeReplica()
    fakes = two_fakes + [third]
    try:
        fakes[0].p99_recent = 900.0     # gray: healthz fine, p99 awful
        fakes[1].p99_recent = 10.0
        fakes[2].p99_recent = 12.0
        router = _mk_router(fakes)
        for _ in range(4):
            router.probe()
        assert 0 not in router.healthy()
        assert sorted(router.healthy()) == [1, 2]
        payload = router.stats_payload()
        assert payload["replicas"][0]["ejected"] is True
        assert payload["replicas"][1]["ejected"] is False
        assert payload["ejection"][0]["ejected"] is True
        counters = router.stats.snapshot()["counters"]
        assert counters["ejects"] == 1
        # predicts route around the ejected outlier
        _predict(router, "a", 3)
        assert len(fakes[0].received) == 0
    finally:
        third.close()


def test_router_no_healthy_replica_is_503(two_fakes):
    router = _mk_router(two_fakes)  # never probed -> nothing routable
    status, body, _ = _predict(router, "a")[0]
    assert status == 503
    assert router.stats.snapshot()["counters"]["no_replica"] == 1


def test_router_unknown_model_is_404(two_fakes):
    router = _mk_router(two_fakes)
    router.probe()
    status, _, _ = router.proxy_predict("nope", b"{}", {})
    assert status == 404


def test_router_drain_fences_new_work(two_fakes):
    router = _mk_router(two_fakes)
    router.probe()
    router.draining = True
    status, _, _ = _predict(router, "a")[0]
    assert status == 503
    assert len(two_fakes[0].received) == 0


def test_router_stats_aggregates_replica_counters(two_fakes):
    two_fakes[0].counters = {"completed": 5, "shed_queue": 2}
    two_fakes[1].counters = {"completed": 7, "shed_queue": 1}
    router = _mk_router(two_fakes)
    router.probe()
    payload = router.stats_payload()
    assert payload["fleet"]["counters"]["completed"] == 12
    assert payload["fleet"]["counters"]["shed_queue"] == 3
    assert payload["fleet"]["replicas_healthy"] == 2
    assert set(payload["replicas"]) == {0, 1}
    assert payload["replicas"][0]["healthy"] is True
    # fleet p50/p99 is the router-measured end-to-end window
    assert payload["fleet"]["latency_ms"] == \
        payload["router"]["latency_ms"]


def test_router_http_surface_end_to_end(two_fakes):
    """The public port speaks the mxserve client protocol: /healthz,
    /stats, /predict/<m> proxied with headers intact."""
    from mxnet_tpu.serving import ServeClient
    router = _mk_router(two_fakes)
    router.serve_in_background()
    try:
        cli = ServeClient("127.0.0.1", router.port, timeout=10)
        status, payload = cli.healthz()
        assert status == 200 and payload["status"] == "ok"
        status, payload = cli.predict(
            "a", np.zeros(4, "f"), npy=True, priority=1,
            deadline_ms=4000)
        assert status == 200 and payload["fake"] == two_fakes[0].port
        status, stats = cli.stats()
        assert status == 200
        assert stats["router"]["counters"]["routed"] == 1
        cli.close()
        # QoS headers crossed the proxy to the replica? the fake can't
        # see headers in its reply, but the forward path is shared with
        # the body — assert the body arrived bit-intact
        path, body = two_fakes[0].received[0]
        assert path == "/predict/a"
        arr = np.load(__import__("io").BytesIO(body),
                      allow_pickle=False)
        assert arr.shape == (4,)
    finally:
        router.drain_and_stop(timeout=5)


def test_router_draining_replica_is_not_routable(two_fakes):
    two_fakes[0].draining = True
    router = _mk_router(two_fakes)
    router.probe()
    assert router.healthy() == [1]


# ---------------------------------------------------------------------------
# the controller, against dummy children
# ---------------------------------------------------------------------------

_CHILD = r"""
import json, os, signal, sys, time
port_file, state_file = sys.argv[1], sys.argv[2]
runs = 0
if os.path.exists(state_file):
    with open(state_file) as f:
        runs = json.load(f)["runs"]
with open(state_file, "w") as f:
    json.dump({"runs": runs + 1,
               "resume": os.environ.get("MXTPU_RESUME")}, f)
codes = json.loads(os.environ.get("CHILD_EXIT_PLAN", "[]"))
if runs < len(codes):
    sys.exit(codes[runs])
with open(port_file + ".tmp", "w") as f:
    f.write("127.0.0.1:1234")
os.replace(port_file + ".tmp", port_file)
def _term(sig, frame):
    sys.exit(0)
signal.signal(signal.SIGTERM, _term)
time.sleep(600)
"""


def _mk_controller(tmp_path, n=1, exit_plan=(), **kw):
    child = tmp_path / "child.py"
    child.write_text(_CHILD)
    man = FleetManifest.from_flags(["m=/x:1"], ["data=4"], replicas=n)
    kw.setdefault("backoff", 0.05)
    ctl = ReplicaController(man, str(tmp_path / "run"),
                            serve_py=str(child),
                            extra_env={"CHILD_EXIT_PLAN":
                                       json.dumps(list(exit_plan))},
                            **kw)
    # dummy children take (port_file, state_file) positionally instead
    # of the serve.py flag soup
    for rep in ctl.replicas:
        rep.argv = [sys.executable, str(child), rep.port_file,
                    str(tmp_path / ("state-%d.json" % rep.id))]
    return ctl


def _wait(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not pred():
        assert time.monotonic() < deadline, "timed out: %s" % msg
        time.sleep(0.05)


def test_controller_spawns_reads_ports_and_drains(tmp_path):
    ctl = _mk_controller(tmp_path, n=2)
    ctl.start()
    try:
        ports = ctl.wait_ready(timeout=20)
        assert set(ports) == {0, 1}
        assert all(p == 1234 for p in ports.values())
        snap = {r["id"]: r for r in ctl.snapshot()}
        assert snap[0]["state"] == "serving"
        assert snap[0]["pid"] is not None
        rcs = ctl.drain(timeout=10)
        assert rcs == {0: 0, 1: 0}
        assert all(r.state == "drained" for r in ctl.replicas)
    finally:
        ctl.kill()


def test_controller_relaunches_watchdog_exit_with_resume_env(tmp_path):
    """Exit 87 (watchdog) is the supervise.py discipline: relaunch with
    MXTPU_RESUME=1 in the child env."""
    ctl = _mk_controller(tmp_path, exit_plan=[87])
    ctl.start()
    try:
        ctl.wait_ready(timeout=20)
        assert ctl.replicas[0].restarts == 1
        state = json.loads(
            (tmp_path / "state-0.json").read_text())
        assert state["runs"] == 2
        assert state["resume"] == "1"
    finally:
        ctl.kill()


def test_controller_respawns_plain_death_without_resume(tmp_path):
    """A SIGKILL-style death (arbitrary rc) respawns too — capacity
    loss, not job failure — but WITHOUT the resume env."""
    ctl = _mk_controller(tmp_path, exit_plan=[1])
    ctl.start()
    try:
        ctl.wait_ready(timeout=20)
        state = json.loads((tmp_path / "state-0.json").read_text())
        assert state["runs"] == 2
        assert state["resume"] is None
    finally:
        ctl.kill()


def test_controller_restart_budget_exhausts_to_failed(tmp_path):
    ctl = _mk_controller(tmp_path, exit_plan=[1, 1, 1, 1, 1, 1],
                         max_restarts=2)
    ctl.start()
    try:
        _wait(lambda: ctl.replicas[0].state == "failed",
              msg="budget exhaustion")
        state = json.loads((tmp_path / "state-0.json").read_text())
        # initial + 2 relaunches, then the budget stops the bleeding
        assert state["runs"] == 3
    finally:
        ctl.kill()


_SERVE_FLAGS_CHILD = """
import os, signal, sys, time
port_file = sys.argv[sys.argv.index("--port-file") + 1]
with open(port_file + ".tmp", "w") as f:
    f.write("127.0.0.1:1234")
os.replace(port_file + ".tmp", port_file)
signal.signal(signal.SIGTERM, lambda sig, frame: sys.exit(0))
time.sleep(600)
"""


def test_controller_add_replica_then_stop_replica_stays_down(tmp_path):
    """The autoscaler's two endpoints on real child processes:
    ``add_replica`` spawns the next free id from the controller's own
    serve.py command line and supervises it like the rest;
    ``stop_replica`` retires one through SIGTERM (rc 0), takes its port
    out of routing, and its death is never answered with a relaunch."""
    child = tmp_path / "child.py"
    child.write_text(_SERVE_FLAGS_CHILD)
    man = FleetManifest.from_flags(["m=/x:1"], ["data=4"], replicas=1)
    ctl = ReplicaController(man, str(tmp_path / "run"),
                            serve_py=str(child), backoff=0.05)
    ctl.start()
    try:
        assert ctl.wait_ready(timeout=20) == {0: 1234}
        rep = ctl.add_replica()
        assert rep.id == 1
        assert ctl.wait_ready(timeout=20) == {0: 1234, 1: 1234}
        pid = rep.proc.pid

        assert ctl.stop_replica(1) == 0
        # replica 1's supervisor returns; replica 0's keeps waiting
        _wait(lambda: not ctl._threads[1].is_alive(),
              msg="the retired replica's supervisor to return")
        assert ctl._threads[0].is_alive()
        snap = {r["id"]: r for r in ctl.snapshot()}
        assert snap[1]["state"] == "scaled_down"
        assert snap[1]["pid"] == pid and snap[1]["restarts"] == 0
        assert not os.path.exists(rep.port_file)
        assert ctl.ports() == {0: 1234}
        with pytest.raises(MXNetError, match="no replica"):
            ctl.stop_replica(7)

        assert ctl.drain(timeout=10)[0] == 0
        with pytest.raises(MXNetError, match="draining"):
            ctl.add_replica()
    finally:
        ctl.kill()


def test_controller_affinity_partitions_cores():
    sets = ReplicaController._affinity_sets(2)
    cores = sorted(os.sched_getaffinity(0))
    if len(cores) < 4:
        assert sets == [None, None]     # nothing to partition
    else:
        assert len(sets) == 2
        assert sets[0] and sets[1]
        assert not (sets[0] & sets[1])
        assert sets[0] | sets[1] == set(cores)


# ---------------------------------------------------------------------------
# the AOT warm store, against a stub serve binary
# ---------------------------------------------------------------------------

_STUB_SERVE = r"""
import os, sys
assert "--warmup-only" in sys.argv
cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
assert cache, "warm store build must set JAX_COMPILATION_CACHE_DIR"
with open(os.path.join(cache, "compiled.bin"), "w") as f:
    f.write("programs")
sys.stderr.write("mxserve: warmup_s=1.234\n")
"""


def test_build_warm_store_runs_serve_and_writes_marker(tmp_path):
    stub = tmp_path / "stub_serve.py"
    stub.write_text(_STUB_SERVE)
    man = FleetManifest.from_flags(["m=/x:1"], ["m:data=4"],
                                   replicas=1, buckets="1,2")
    store = str(tmp_path / "store")
    doc = build_warm_store(man, store, serve_py=str(stub))
    assert doc["warmup_s"] == 1.234
    assert doc["models"] == ["m"]
    assert os.path.exists(os.path.join(store, "compiled.bin"))
    assert warm_store_manifest(store)["buckets"] == "1,2"
    # idempotent: a second build is a no-op returning the marker
    os.unlink(os.path.join(store, "compiled.bin"))
    doc2 = build_warm_store(man, store, serve_py=str(stub))
    assert doc2["warmup_s"] == 1.234
    assert not os.path.exists(os.path.join(store, "compiled.bin"))
    # force rebuilds
    doc3 = build_warm_store(man, store, serve_py=str(stub), force=True)
    assert os.path.exists(os.path.join(store, "compiled.bin"))


def test_build_warm_store_failure_surfaces(tmp_path):
    stub = tmp_path / "bad_serve.py"
    stub.write_text("import sys; sys.stderr.write('boom'); sys.exit(3)")
    man = FleetManifest.from_flags(["m=/x:1"], ["m:data=4"], replicas=1)
    with pytest.raises(MXNetError, match="boom"):
        build_warm_store(man, str(tmp_path / "store2"),
                         serve_py=str(stub))


# ---------------------------------------------------------------------------
# tools/fleet.py is jax-free (the supervise.py import discipline)
# ---------------------------------------------------------------------------

def test_fleet_cli_never_imports_jax(tmp_path):
    """The router/controller process must not spin up an XLA client (it
    would steal the device from its replicas) — poisoned-jax proof, the
    mxlint CLI idiom."""
    poison = tmp_path / "jax"
    poison.mkdir()
    (poison / "__init__.py").write_text(
        "raise ImportError('fleet CLI must not import jax')")
    stub = tmp_path / "stub_serve.py"
    stub.write_text(_STUB_SERVE)
    env = dict(os.environ,
               PYTHONPATH=str(tmp_path) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "fleet.py"),
         "warmup", "--model", "m=/x:1", "--input-shape", "m:data=4",
         "--warm-store", str(tmp_path / "store")],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path))
    # the warm store build execs tools/serve.py (which DOES import
    # mxnet_tpu -> jax in the CHILD) — with poisoned jax the child
    # fails, but the PARENT must have gotten that far jax-free: the
    # failure surfaces as the parent's clean wrap of the child's
    # poisoned-import error, not as the parent's own ImportError
    assert res.returncode == 1
    assert "fleet CLI must not import jax" in res.stderr
    assert "fleet: error: warm-store build failed" in res.stderr


# ---------------------------------------------------------------------------
# health-probe retry + rolling-swap fencing (ISSUE 13)
# ---------------------------------------------------------------------------

def test_probe_retry_heals_single_dropped_healthz(two_fakes):
    """One dropped /healthz on a loaded replica must not advance the
    heartbeat-age clock toward eviction: the probe retries ONCE (with
    jitter) inside the same pass and the replica stays routable.  The
    retry is for idempotent probe GETs only — no POST was ever sent."""
    router = _mk_router(two_fakes, evict_s=10.0)
    router.probe()
    assert router.healthy() == [0, 1]
    posts_before = len([p for p, _ in two_fakes[0].received
                        if p.startswith("/predict")])
    two_fakes[0].fail_healthz = 1
    router.probe()
    # the retry healed it in the SAME pass: still routable, fresh clock
    assert router.healthy() == [0, 1]
    assert router._views[0].probe_retries == 1
    assert router._views[0].last_ok is not None
    assert time.monotonic() - router._views[0].last_ok < 1.0
    # ...and nothing non-idempotent was replayed
    posts_after = len([p for p, _ in two_fakes[0].received
                       if p.startswith("/predict")])
    assert posts_after == posts_before
    # a replica that is REALLY down fails both tries and ages out
    two_fakes[0].fail_healthz = 99
    last_ok = router._views[0].last_ok
    router.probe()
    assert router._views[0].last_ok == last_ok  # clock did not advance
    assert router._views[0].probe_retries == 2


def test_probe_retry_does_not_resurrect_draining_replica(two_fakes):
    """'draining' is a deliberate self-fence, not a dropped packet: no
    retry, immediate eviction (the rolling-restart stance)."""
    router = _mk_router(two_fakes)
    router.probe()
    retries_before = router._views[0].probe_retries
    two_fakes[0].draining = True
    router.probe()
    assert router.healthy() == [1]
    assert router._views[0].probe_retries == retries_before


def test_fence_unfence_and_capacity_floor(two_fakes):
    """fence() holds a replica out of routing (its model's traffic
    reroutes), unfence() rejoins it — and fencing can never take the
    LAST routable replica (the N-1 capacity floor)."""
    router = _mk_router(two_fakes)
    router.probe()
    home_a = router.manifest.home("a") % 2
    router.fence(home_a)
    assert router.healthy() == [1 - home_a]
    rid, reason = router.route("a")
    assert rid == 1 - home_a and reason == "rerouted"
    with pytest.raises(MXNetError, match="no routable"):
        router.fence(1 - home_a)
    router.unfence(home_a)
    assert router.healthy() == [0, 1]
    assert router.route("a") == (home_a, None)
    # the per-replica table shows the fence while it holds
    router.fence(0)
    assert router.stats_payload()["replicas"][0]["fenced"]
    router.unfence(0)


def _publish_epoch(directory, epoch, payload):
    """A manifest entry with REAL digests, no jax: exactly the files
    verify_promotion checks (RollingSwap never deserializes weights —
    the replicas do, each behind its own watcher)."""
    from mxnet_tpu.resilience import atomic_write, checksum_file
    os.makedirs(directory, exist_ok=True)
    name = "checkpoint-%04d.params" % epoch
    path = os.path.join(directory, name)
    atomic_write(path, payload)
    size, digest = checksum_file(path)
    mpath = os.path.join(directory, "manifest.json")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        manifest = {"prefix": "checkpoint", "checkpoints": []}
    entries = [e for e in manifest["checkpoints"]
               if e["epoch"] != epoch]
    entries.append({"epoch": epoch, "params": name, "states": None,
                    "checksum": "sha256", "time": time.time(),
                    "files": {name: {"size": size, "digest": digest}}})
    manifest["checkpoints"] = sorted(entries,
                                     key=lambda e: e["epoch"])
    atomic_write(mpath, json.dumps(manifest))


def test_rolling_swap_rolls_one_replica_at_a_time(two_fakes, tmp_path):
    """The fleet tier: a verified new epoch rolls fence -> swap ->
    probe -> rejoin across the replicas; when done every replica
    serves it, nothing stays fenced, and /stats shows the rollout."""
    from mxnet_tpu.fleet import RollingSwap
    ckpt = str(tmp_path / "ckpts")
    _publish_epoch(ckpt, 1, b"epoch-one-bytes")
    for f in two_fakes:
        f.epochs["a"] = 1
    router = _mk_router(two_fakes)
    router.probe()
    roll = RollingSwap(router, {"a": ckpt}, poll_s=0.05,
                       log=lambda m: None)
    assert router.deploy is roll
    assert roll.check_once() == {"a": "current"}

    _publish_epoch(ckpt, 2, b"epoch-two-bytes")
    assert roll.check_once() == {"a": "complete"}
    assert two_fakes[0].epochs["a"] == 2
    assert two_fakes[1].epochs["a"] == 2
    assert router.fenced() == []
    stats = router.stats_payload()
    assert stats["rollout"]["state"]["state"] == "complete"
    assert stats["rollout"]["state"]["epoch"] == 2
    # each replica got exactly ONE /swap POST
    for f in two_fakes:
        swaps = [p for p, _ in f.received if p.startswith("/swap/")]
        assert swaps == ["/swap/a"]


def test_rolling_swap_rejects_damaged_epoch_before_any_replica(
        two_fakes, tmp_path):
    """A publish the verifier refuses never even starts a rollout: no
    replica sees a /swap, the fleet stays on the old epoch, and the
    same bad publish is counted once."""
    from mxnet_tpu.fleet import RollingSwap
    ckpt = str(tmp_path / "ckpts")
    _publish_epoch(ckpt, 1, b"epoch-one")
    for f in two_fakes:
        f.epochs["a"] = 1
    router = _mk_router(two_fakes)
    router.probe()
    roll = RollingSwap(router, {"a": ckpt}, log=lambda m: None)
    _publish_epoch(ckpt, 2, b"epoch-two")
    # rot AFTER publish: flip a byte under the recorded digest
    p2 = os.path.join(ckpt, "checkpoint-0002.params")
    blob = bytearray(open(p2, "rb").read())
    blob[3] ^= 0xFF
    open(p2, "wb").write(bytes(blob))
    assert roll.check_once() == {"a": "rejected"}
    assert roll.check_once() == {"a": "rejected"}
    assert roll.counters["rejected"] == 1      # counted once
    for f in two_fakes:
        assert not [p for p, _ in f.received
                    if p.startswith("/swap/")]
        assert f.epochs["a"] == 1


def test_rolling_swap_halts_when_a_replica_refuses(two_fakes,
                                                   tmp_path):
    """A replica that refuses the epoch (its own verify/validate/probe
    said no) HALTS the rollout right there: later replicas are never
    asked, keep the old epoch, and the fleet keeps serving — most of
    the fleet is untouched by a bad epoch."""
    from mxnet_tpu.fleet import RollingSwap
    ckpt = str(tmp_path / "ckpts")
    _publish_epoch(ckpt, 1, b"epoch-one")
    for f in two_fakes:
        f.epochs["a"] = 1
    router = _mk_router(two_fakes)
    router.probe()
    roll = RollingSwap(router, {"a": ckpt}, log=lambda m: None)
    two_fakes[0].swap_refuse = True
    _publish_epoch(ckpt, 2, b"epoch-two")
    assert roll.check_once() == {"a": "halted"}
    assert roll.counters["halted"] == 1
    # replica 0 refused and stayed put; replica 1 was NEVER asked
    assert two_fakes[0].epochs["a"] == 1
    assert two_fakes[1].epochs["a"] == 1
    assert not [p for p, _ in two_fakes[1].received
                if p.startswith("/swap/")]
    # nothing left fenced; the fleet still routes
    assert router.fenced() == []
    assert router.healthy() == [0, 1]
    st = router.stats_payload()["rollout"]["state"]
    assert st["state"] == "halted" and st["epoch"] == 2
    # the failed publish is held, not retried forever...
    assert roll.check_once() == {"a": "rejected"}
    assert roll.counters["halted"] == 1
    # ...but a REWRITTEN epoch re-enters and completes
    two_fakes[0].swap_refuse = False
    _publish_epoch(ckpt, 2, b"epoch-two-rewritten")
    assert roll.check_once() == {"a": "complete"}
    assert two_fakes[0].epochs["a"] == 2
    assert two_fakes[1].epochs["a"] == 2


# ---------------------------------------------------------------------------
# seam: a rollout racing the elastic trainer's resume (the mxregion
# composition — a world-size-changed trainer respawns with
# MXTPU_RESUME=1 and republishes while RollingSwap is mid-rollout)
# ---------------------------------------------------------------------------

def test_rolling_swap_races_elastic_resume_publish(two_fakes, tmp_path):
    """While replica 1's swap to epoch 2 is IN FLIGHT, the resumed
    trainer (respawned at a different world size) rewrites epoch 2's
    files AND publishes epoch 3.  The in-flight rollout must settle
    cleanly: complete on the epoch it started (every replica
    consistent, nothing left fenced), and the racing publish rolls on
    the NEXT poll — never a mixed-epoch fleet or a wedged fence."""
    from mxnet_tpu.fleet import RollingSwap
    ckpt = str(tmp_path / "ckpts")
    _publish_epoch(ckpt, 1, b"epoch-one")
    for f in two_fakes:
        f.epochs["a"] = 1
    router = _mk_router(two_fakes)
    router.probe()
    roll = RollingSwap(router, {"a": ckpt}, log=lambda m: None)
    _publish_epoch(ckpt, 2, b"epoch-two")

    fired = []

    def resume_lands(model, epoch):
        if fired:
            return
        fired.append(epoch)
        # the elastic resume republishes from its reloaded state...
        _publish_epoch(ckpt, 2, b"epoch-two-resume-rewrite")
        # ...and its next epoch lands while the rollout is in flight
        _publish_epoch(ckpt, 3, b"epoch-three-from-new-world")

    two_fakes[1].on_swap = resume_lands
    assert roll.check_once() == {"a": "complete"}
    assert fired == [2], "the race never fired"
    assert two_fakes[0].epochs["a"] == 2
    assert two_fakes[1].epochs["a"] == 2
    assert router.fenced() == []
    # the racing publish is not lost: the next poll rolls epoch 3
    two_fakes[1].on_swap = None
    assert roll.check_once() == {"a": "complete"}
    assert all(f.epochs["a"] == 3 for f in two_fakes)
    assert router.fenced() == []
    st = router.stats_payload()["rollout"]["state"]
    assert st["state"] == "complete" and st["epoch"] == 3


def test_rolling_swap_halts_cleanly_when_resume_races_a_dying_replica(
        two_fakes, tmp_path):
    """The ugly corner of the same seam: the trainer's resume publish
    lands just as the replica being swapped DIES mid-swap (no response
    on the wire).  The rollout must halt cleanly — nothing fenced, the
    survivor keeps serving its consistent epoch — and once the replica
    is back the next poll completes on the resume's newest epoch."""
    from mxnet_tpu.fleet import RollingSwap
    ckpt = str(tmp_path / "ckpts")
    _publish_epoch(ckpt, 1, b"epoch-one")
    for f in two_fakes:
        f.epochs["a"] = 1
    router = _mk_router(two_fakes)
    router.probe()
    roll = RollingSwap(router, {"a": ckpt}, log=lambda m: None)
    _publish_epoch(ckpt, 2, b"epoch-two")

    def die_mid_swap(model, epoch):
        _publish_epoch(ckpt, 3, b"epoch-three-resumed")
        two_fakes[1].swap_drop = True

    two_fakes[1].on_swap = die_mid_swap
    assert roll.check_once() == {"a": "halted"}
    assert roll.counters["halted"] == 1
    # clean halt: no fence held, the survivor serves epoch 2, the dead
    # replica was never marked swapped
    assert router.fenced() == []
    assert two_fakes[0].epochs["a"] == 2
    assert two_fakes[1].epochs["a"] == 1
    # the replica's supervisor brings it back; the next poll resumes
    # the rollout on the NEWEST publish (the resume's epoch 3)
    two_fakes[1].swap_drop = False
    two_fakes[1].on_swap = None
    router.probe()
    assert roll.check_once() == {"a": "complete"}
    assert all(f.epochs["a"] == 3 for f in two_fakes)
    assert router.fenced() == []


# ---------------------------------------------------------------------------
# seam: spill pressure racing a rollout's fence (the router must never
# spill onto a fenced replica, and the N-1 floor holds under load)
# ---------------------------------------------------------------------------

def test_spill_under_rollout_fence_never_targets_fenced_replica():
    """A home past its spill bar sheds load while a RollingSwap fence
    holds one replica out: under concurrent spill traffic the fenced
    replica is NEVER chosen, every request still lands somewhere, and
    fencing can never cross the N-1 capacity floor."""
    fakes = [_FakeReplica() for _ in range(3)]
    try:
        router = _mk_router(fakes, models=("a",))
        router.probe()
        home = router.manifest.home("a") % 3
        others = [r for r in range(3) if r != home]
        fenced_rid, spill_rid = others
        # script the home past the spill bar (spill_queue=4)
        fakes[home].depths["a"] = 10
        router.probe()
        router.fence(fenced_rid)       # a rollout holds this one

        hits, errs = [], []

        def worker():
            for _ in range(25):
                try:
                    hits.append(router.route("a"))
                except MXNetError as e:  # noqa: PERF203 — seam assert
                    errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs[:3]
        assert len(hits) == 100
        # no request ever landed on the fenced replica...
        assert all(rid != fenced_rid for rid, _ in hits), hits[:5]
        # ...and the overloaded home spilled to the unfenced sibling
        assert {rid for rid, _ in hits} == {spill_rid}
        assert all(reason == "spilled" for _, reason in hits)

        # N-1 floor under the same pressure: fencing the spill target
        # leaves only the (overloaded) home — allowed, traffic falls
        # back to it — but fencing the LAST routable replica is refused
        router.fence(spill_rid)
        rid, reason = router.route("a")
        assert rid == home and reason is None
        with pytest.raises(MXNetError, match="no routable"):
            router.fence(home)
        router.unfence(spill_rid)
        router.unfence(fenced_rid)
        assert router.healthy() == [0, 1, 2]
    finally:
        for f in fakes:
            f.close()


# ---------------------------------------------------------------------------
# sharded front end: the published fleet view + SO_REUSEPORT workers
# ---------------------------------------------------------------------------

def _mk_manifest(fakes, models=("a", "b")):
    return FleetManifest.from_flags(
        ["%s=/x:1" % m for m in models], ["data=4"],
        replicas=len(fakes))


def test_view_publisher_generation_and_reader_last_good(tmp_path,
                                                        two_fakes):
    prober = _mk_router(two_fakes)
    path = str(tmp_path / "fleet-view.json")
    pub = FleetViewPublisher(prober, path)
    pub.publish_once()
    reader = FleetViewReader(path, refresh_s=0.0)
    doc = reader.doc()
    assert reader.generation == 1
    assert sorted(int(r) for r in doc["replicas"]) == [0, 1]
    assert all(r["healthy"] for r in doc["replicas"].values())

    prober.fence(1)
    pub.publish_once()
    assert reader.generation == 2
    assert reader.fenced() == [1]
    # fencing folds into the worker-visible health bit (replicas() maps
    # back to the ORIGINAL int ids JSON stringified)
    assert not reader.replicas()[1]["healthy"]

    # a corrupt snapshot mid-write: the reader KEEPS the last good doc
    # and counts the error — it never goes blind or backward
    with open(path, "w") as f:
        f.write("{half a json docum")
    doc2 = reader.doc(force=True)
    assert doc2["generation"] == 2
    assert reader.read_errors >= 1
    prober.unfence(1)


def test_view_worker_routes_follows_fence_and_counts_stale(tmp_path,
                                                           two_fakes):
    """A worker routing over a STALE snapshot stays safe: it keeps
    routing on the last-good view (fail-once 502s cover a dead addr)
    and counts `stale_view_routes` so the operator sees the dead
    publisher."""
    prober = _mk_router(two_fakes)
    prober.probe()
    path = str(tmp_path / "fleet-view.json")
    pub = FleetViewPublisher(prober, path)
    pub.publish_once()

    man = _mk_manifest(two_fakes)
    worker = FleetRouter(FleetViewReader(path, refresh_s=0.0), man,
                         port=0, evict_s=0.4, spill_queue=4)
    sts = _predict(worker, "a", 2)          # home of "a" = replica 0
    assert all(s == 200 for s, _, _ in sts)
    assert len(two_fakes[0].received) == 2

    # controller-side fence propagates through ONE publish, no worker
    # coordination: new "a" traffic avoids replica 0
    prober.fence(0)
    pub.publish_once()
    before = len(two_fakes[1].received)
    sts = _predict(worker, "a", 2)
    assert all(s == 200 for s, _, _ in sts)
    assert len(two_fakes[0].received) == 2          # nothing new
    assert len(two_fakes[1].received) == before + 2
    prober.unfence(0)
    pub.publish_once()

    # no publisher for longer than evict_s: routing still works, the
    # staleness is COUNTED rather than fatal
    time.sleep(0.5)
    sts = _predict(worker, "a", 1)
    assert all(s == 200 for s, _, _ in sts)
    assert worker.stats.snapshot()["counters"]["stale_view_routes"] >= 1


def test_router_workers_share_reuseport_and_merge_stats(tmp_path,
                                                        two_fakes):
    """Two in-process view-mode workers bound to ONE kernel-balanced
    port: every request answers, and ANY worker's /stats merges the
    sibling dumps into one shard-wide payload."""
    import socket as socket_mod
    if not hasattr(socket_mod, "SO_REUSEPORT"):
        pytest.skip("no SO_REUSEPORT on this platform")
    import http.client

    prober = _mk_router(two_fakes)
    prober.probe()
    path = str(tmp_path / "fleet-view.json")
    FleetViewPublisher(prober, path).publish_once()

    sock, port = reserve_port("127.0.0.1", 0)
    man = _mk_manifest(two_fakes)
    workers = []
    try:
        for i in range(2):
            w = FleetRouter(FleetViewReader(path, refresh_s=0.05), man,
                            host="127.0.0.1", port=port, reuse_port=True,
                            worker_id=i, run_dir=str(tmp_path),
                            spill_queue=8, evict_s=60.0)
            w.serve_in_background()
            workers.append(w)

        body = json.dumps({"inputs": {"data": [0, 0, 0, 0]}}).encode()
        for _ in range(20):             # fresh connection per request:
            conn = http.client.HTTPConnection(      # the kernel picks
                "127.0.0.1", port, timeout=10)      # the worker
            conn.request("POST", "/predict/a", body=body,
                         headers={"Content-Type": "application/json"})
            assert conn.getresponse().status == 200
            conn.close()

        for w in workers:               # deterministic merge input
            w.dump_worker_stats()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        conn.request("GET", "/stats")
        resp = conn.getresponse()
        payload = json.loads(resp.read().decode())
        conn.close()
        assert resp.status == 200
        assert set(payload["workers"]) == {"0", "1"}
        assert payload["router"]["merged_from"] == 2
        # the shard-wide ledger: every request counted exactly once
        assert payload["router"]["counters"]["routed"] == 20
        assert payload["view"]["generation"] == 1
    finally:
        for w in workers:
            w.drain_and_stop(timeout=5)
        sock.close()


def test_worker_stats_dump_from_two_threads_never_tears(tmp_path,
                                                        two_fakes):
    """The dump loop and a caller's dump (the drain's last one, a
    test's) run in one process and share a temp path: no dump may
    raise, and a reader never meets a torn file."""
    prober = _mk_router(two_fakes)
    prober.probe()
    path = str(tmp_path / "fleet-view.json")
    FleetViewPublisher(prober, path).publish_once()
    w = FleetRouter(FleetViewReader(path, refresh_s=0.05),
                    _mk_manifest(two_fakes), port=0, worker_id=0,
                    run_dir=str(tmp_path), evict_s=60.0)
    dump_path = w.dump_worker_stats()
    stop = threading.Event()
    failed = []

    def dumper():
        while not stop.is_set():
            try:
                w.dump_worker_stats()
            except Exception as e:  # noqa: BLE001 — the seam assert
                failed.append(e)
                return

    threads = [threading.Thread(target=dumper) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(2000):
            with open(dump_path) as f:
                assert json.load(f)["worker"] == 0
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert not failed, failed[:1]


class _SupervisedFakes(object):
    """Controller duck over fake replicas: enough surface
    (``ports``/``replicas``/``snapshot``) for a prober-side
    FleetRouter, with distinct supervision fields per replica."""

    def __init__(self, fakes):
        self.replicas = list(range(len(fakes)))
        self._ports = {i: f.port for i, f in enumerate(fakes)}

    def ports(self):
        return dict(self._ports)

    def snapshot(self):
        return [{"id": i, "state": "serving", "port": p,
                 "pid": 40000 + i, "restarts": i, "last_rc": None}
                for i, p in sorted(self._ports.items())]


def test_worker_stats_carry_supervision_fields_through_view(tmp_path,
                                                            two_fakes):
    """Sharded front end: the controller lives in the prober's
    process, but kill-replica drills and respawn crediting read
    pid/restarts off whatever worker answers /stats — so those fields
    must ride the published view to every worker."""
    man = _mk_manifest(two_fakes)
    prober = FleetRouter(_SupervisedFakes(two_fakes), man, port=0,
                         heartbeat_s=0.15, evict_s=0.6, spill_queue=4)
    prober.probe()
    path = str(tmp_path / "fleet-view.json")
    FleetViewPublisher(prober, path).publish_once()

    worker = FleetRouter(FleetViewReader(path, refresh_s=0.0), man,
                         port=0, evict_s=0.4, spill_queue=4)
    reps = worker.stats_payload()["replicas"]
    for rid in (0, 1):
        assert reps[rid]["pid"] == 40000 + rid
        assert reps[rid]["restarts"] == rid
        assert reps[rid]["state"] == "serving"
    # the controller-side table says the same thing (one source of
    # truth, two serving paths)
    ctrl_reps = prober.stats_payload()["replicas"]
    for rid in (0, 1):
        assert ctrl_reps[rid]["pid"] == reps[rid]["pid"]


# ---------------------------------------------------------------------------
# autoscaler policy (fleet/autoscale.py) — synthetic signal, duck fleet
# ---------------------------------------------------------------------------

class _DuckRep(object):
    def __init__(self, rid):
        self.id, self.state = rid, "running"


class _DuckController(object):
    def __init__(self, n):
        self.replicas = [_DuckRep(i) for i in range(n)]
        self.log = []

    def add_replica(self):
        rep = _DuckRep(max(r.id for r in self.replicas) + 1)
        self.replicas.append(rep)
        self.log.append(("add", rep.id))
        return rep

    def stop_replica(self, rid, timeout=30.0):
        self.log.append(("stop", rid))
        for r in self.replicas:
            if r.id == rid:
                r.state = "scaled_down"
        return 0


class _DuckView(object):
    def __init__(self):
        self.stats = {"queue_depth": {}, "est_wait_ms": {}}
        self.inflight = 0


class _DuckRouter(object):
    def __init__(self, rids):
        self._lock = threading.Lock()
        self._views = {r: _DuckView() for r in rids}
        self._fenced = set()
        self.log = []

    def healthy(self):
        return sorted(set(self._views) - self._fenced)

    def fence(self, rid):
        if len(self.healthy()) <= 1:
            raise MXNetError("fencing replica %d would leave no "
                             "routable replica" % rid)
        self._fenced.add(rid)
        self.log.append(("fence", rid))

    def unfence(self, rid):
        self._fenced.discard(rid)
        self.log.append(("unfence", rid))


def _mk_scaler(n=2, signal=None, **kw):
    ctrl = _DuckController(n)
    router = _DuckRouter(range(n))
    sig = {"v": 0.0}
    kw.setdefault("high_ms", 50.0)
    kw.setdefault("low_ms", 5.0)
    kw.setdefault("up_after", 2)
    kw.setdefault("down_after", 2)
    kw.setdefault("cooldown_s", 0.0)
    kw.setdefault("settle_s", 0.0)
    kw.setdefault("drain_wait_s", 0.5)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    scaler = Autoscaler(ctrl, router, signal_fn=lambda: sig["v"], **kw)
    return scaler, ctrl, router, sig


def test_autoscaler_square_wave_never_flaps():
    """THE hysteresis pin: a signal bouncing across both watermarks
    faster than either streak fills takes NO action, ever."""
    scaler, ctrl, router, sig = _mk_scaler(up_after=2, down_after=2)
    for i in range(20):
        sig["v"] = 100.0 if i % 2 == 0 else 0.0
        assert scaler.tick() is None
    assert ctrl.log == [] and router.log == []
    assert scaler.counters["scale_ups"] == 0
    assert scaler.counters["scale_downs"] == 0


def test_autoscaler_scales_up_after_streak_then_cooldown_blocks():
    scaler, ctrl, router, sig = _mk_scaler(cooldown_s=60.0)
    sig["v"] = 100.0
    assert scaler.tick() is None            # streak 1 of 2
    assert scaler.tick() == "up"
    assert ctrl.log == [("add", 2)]
    # pressure persists: the cooldown absorbs it instead of stacking a
    # second scale-up onto capacity that has not warmed yet
    assert scaler.tick() is None
    assert scaler.tick() is None
    assert scaler.counters["blocked_cooldown"] >= 1
    assert len(ctrl.replicas) == 3


def test_autoscaler_ceiling_blocks_scale_up():
    scaler, ctrl, router, sig = _mk_scaler(n=4, max_replicas=4)
    sig["v"] = 100.0
    scaler.tick()
    assert scaler.tick() is None
    assert scaler.counters["blocked_max"] == 1
    assert ctrl.log == []


def test_autoscaler_fenced_scale_down_order_and_min_floor():
    """Scale-down is the mxswap dance in ONE tick: fence the victim,
    drain, stop, unfence the retired id — and the min-replica floor
    blocks the next one."""
    scaler, ctrl, router, sig = _mk_scaler(n=2, min_replicas=1)
    sig["v"] = 0.0
    assert scaler.tick() is None
    assert scaler.tick() == "down"
    # victim = highest id; fence BEFORE stop, unfence after
    assert router.log == [("fence", 1), ("unfence", 1)]
    assert ctrl.log == [("stop", 1)]
    assert [r.state for r in ctrl.replicas] == ["running", "scaled_down"]
    # the retired id no longer counts as live: the floor blocks
    router._views.pop(1)
    assert scaler.tick() is None
    assert scaler.tick() is None
    assert scaler.counters["blocked_min"] >= 1
    assert scaler.counters["scale_downs"] == 1


def test_autoscaler_n1_fence_floor_outranks_low_watermark():
    """Even above min_replicas, the router's own N-1 routable floor
    refuses the fence and the scale-down backs off cleanly."""
    scaler, ctrl, router, sig = _mk_scaler(n=2, min_replicas=1)
    router._fenced.add(0)               # sibling already fenced (swap)
    router.log = []
    sig["v"] = 0.0
    scaler.tick()
    assert scaler.tick() is None
    assert scaler.counters["blocked_floor"] == 1
    assert ctrl.log == []               # nothing stopped
    assert router.log == []             # fence refused, nothing leaked


def test_autoscaler_scale_down_failure_unwinds_fence():
    scaler, ctrl, router, sig = _mk_scaler(n=2)

    def boom(rid, timeout=30.0):
        raise RuntimeError("stop failed")

    ctrl.stop_replica = boom
    sig["v"] = 0.0
    scaler.tick()
    assert scaler.tick() is None
    assert scaler.counters["errors"] == 1
    # the half-retired replica is unfenced and keeps serving
    assert router._fenced == set()
    assert router.log == [("fence", 1), ("unfence", 1)]


def _publish_sharded_epoch(directory, epoch, world=2, damage=None):
    """A format-2 (sharded-native) manifest entry with REAL per-blob
    digests, no jax: params=None, every blob recorded in both `files`
    and `shard_set`.  `damage=(k, "rot"|"drop")` hurts blob k AFTER
    the digests are recorded — rot under the digest, or delete."""
    from mxnet_tpu.resilience import atomic_write, checksum_file
    os.makedirs(directory, exist_ok=True)
    files, records = {}, []
    for k in range(world):
        name = "checkpoint-%04d.params.s%03d-of-%03d" % (epoch, k,
                                                         world)
        path = os.path.join(directory, name)
        atomic_write(path, b"epoch-%d-shard-%d-bytes" % (epoch, k))
        size, digest = checksum_file(path)
        files[name] = {"size": size, "digest": digest}
        records.append({"shard": k, "file": name, "size": size,
                        "digest": digest})
    if damage is not None:
        k, how = damage
        path = os.path.join(
            directory, "checkpoint-%04d.params.s%03d-of-%03d"
            % (epoch, k, world))
        if how == "drop":
            os.remove(path)
        else:
            blob = bytearray(open(path, "rb").read())
            blob[len(blob) // 2] ^= 0xFF
            open(path, "wb").write(bytes(blob))
    mpath = os.path.join(directory, "manifest.json")
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        manifest = {"prefix": "checkpoint", "checkpoints": []}
    entries = [e for e in manifest["checkpoints"]
               if e["epoch"] != epoch]
    entries.append({"epoch": epoch, "format": 2, "params": None,
                    "states": None, "checksum": "sha256",
                    "time": time.time(), "files": files,
                    "shard_set": {"world": world, "files": records}})
    manifest["checkpoints"] = sorted(entries,
                                     key=lambda e: e["epoch"])
    atomic_write(mpath, json.dumps(manifest))


def test_rolling_swap_sharded_publish_rolls_and_gates(two_fakes,
                                                      tmp_path):
    """The fleet tier of the shard-loss matrix: a clean sharded-native
    publish rolls fence -> swap -> rejoin like any other epoch, a
    shard-damaged one (rot under digest OR missing blob) never starts
    a rollout — counted once per publish, fleet stays put."""
    from mxnet_tpu.fleet import RollingSwap
    ckpt = str(tmp_path / "ckpts")
    _publish_sharded_epoch(ckpt, 1)
    for f in two_fakes:
        f.epochs["a"] = 1
    router = _mk_router(two_fakes)
    router.probe()
    roll = RollingSwap(router, {"a": ckpt}, poll_s=0.05,
                       log=lambda m: None)
    assert roll.check_once() == {"a": "current"}

    # clean sharded epoch 2: full rollout, one /swap per replica
    _publish_sharded_epoch(ckpt, 2)
    assert roll.check_once() == {"a": "complete"}
    for f in two_fakes:
        assert f.epochs["a"] == 2
        swaps = [p for p, _ in f.received if p.startswith("/swap/")]
        assert swaps == ["/swap/a"]
    assert router.fenced() == []

    # epoch 3 loses blob 1 entirely: incomplete shard set, no rollout
    _publish_sharded_epoch(ckpt, 3, damage=(1, "drop"))
    assert roll.check_once() == {"a": "rejected"}
    assert roll.check_once() == {"a": "rejected"}
    assert roll.counters["rejected"] == 1      # counted once

    # epoch 4 bit-rots blob 0 under its recorded digest: rejected too,
    # and the NEW publish mark is counted separately
    _publish_sharded_epoch(ckpt, 4, damage=(0, "rot"))
    assert roll.check_once() == {"a": "rejected"}
    assert roll.counters["rejected"] == 2
    for f in two_fakes:
        assert f.epochs["a"] == 2
        swaps = [p for p, _ in f.received if p.startswith("/swap/")]
        assert swaps == ["/swap/a"]            # still just epoch 2's
