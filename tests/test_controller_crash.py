"""Control-plane crash safety (ISSUE 15): the fleet must survive the
controller.

Units: the durable crash-safety tables round-trip; the restart policy
persists budget consumption and serves out carried backoff deadlines;
the rejoin quarantine observes-but-never-acts; the event watcher's
dedup state rebuilds from the durable sink; `ktpu top` falls back to
direct pod polling when the controller is unreachable; a ws-flap chaos
draw severs the controller WS and the pod reconnects with the resync
full-snapshot handshake.

The acceptance e2e kills a real controller subprocess mid-serving and
asserts: the in-flight channel stream completes byte-identical with
execution count one (data plane untouched), the restarted controller
rebuilds correct gang health within the quarantine plus two sweep
intervals with ZERO spurious gang restarts, restart budgets and
runtime-registered SLO objectives carry over, and fleet rollup rates
stay non-negative across the gap.
"""

import asyncio
import hashlib
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import httpx
import pytest

REPO_ROOT = Path(__file__).resolve().parents[1]
SUMMER = Path(__file__).parent / "assets" / "summer"


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_http(url: str, proc=None, attempts: int = 300):
    for _ in range(attempts):
        if proc is not None and proc.poll() is not None:
            raise RuntimeError(
                f"server exited rc={proc.returncode} before {url} answered")
        try:
            if httpx.get(url, timeout=2.0).status_code < 500:
                return
        except httpx.HTTPError:
            pass
        time.sleep(0.1)
    raise RuntimeError(f"{url} never answered")


# ---------------------------------------------------------------- units
@pytest.mark.level("unit")
def test_db_crash_safety_tables_roundtrip(tmp_path):
    """The durable tables behind ISSUE 15: liveness rows upsert on
    transitions and delete per pod/service; restart state carries
    attempts + backoff deadlines (reset deletes unless a last-detect
    record keeps the row); SLO specs round-trip; the meta counter
    survives reopen."""
    from kubetorch_tpu.controller.db import Database

    path = str(tmp_path / "ctl.db")
    db = Database(path)
    db.save_liveness("svc", "p0", "alive")
    db.save_liveness("svc", "p0", "suspect")
    db.save_liveness("svc", "p1", "dead")
    db.save_liveness("other", "q0", "alive")
    rows = {(r["service"], r["pod"]): r["state"]
            for r in db.load_liveness()}
    assert rows == {("svc", "p0"): "suspect", ("svc", "p1"): "dead",
                    ("other", "q0"): "alive"}
    db.delete_liveness("svc", "p1")
    assert ("svc", "p1") not in {(r["service"], r["pod"])
                                 for r in db.load_liveness()}
    db.delete_liveness("svc")
    assert {r["service"] for r in db.load_liveness()} == {"other"}

    db.save_restart_state("svc", 2, backoff_until=123.0)
    db.save_last_detect("svc", {"pod": "p0", "detect_s": 0.4})
    states = db.load_restart_states()
    assert states["svc"]["attempts"] == 2
    assert states["svc"]["backoff_until"] == 123.0
    assert states["svc"]["last_detect"]["pod"] == "p0"
    # reset with a last-detect record zeroes attempts, keeps history
    db.save_restart_state("svc", 0, backoff_until=None)
    states = db.load_restart_states()
    assert states["svc"]["attempts"] == 0
    assert states["svc"]["last_detect"]["pod"] == "p0"
    # reset without history leaves no row at all
    db.save_restart_state("bare", 1, backoff_until=None)
    db.save_restart_state("bare", 0, backoff_until=None)
    assert "bare" not in db.load_restart_states()
    db.clear_restart_state("svc")
    assert db.load_restart_states() == {}

    spec = {"service": "svc", "name": "ttft", "kind": "latency",
            "metric": "engine_ttft_seconds", "threshold_ms": 500,
            "objective": 0.99}
    db.save_slo("svc", "ttft", spec)
    db.save_slo("svc", "shed", {"service": "svc", "name": "shed"})
    assert len(db.load_slos()) == 2
    db.delete_slos("svc", "shed")
    assert [s["name"] for s in db.load_slos()] == ["ttft"]
    db.delete_slos("svc")
    assert db.load_slos() == []

    assert db.bump_meta_counter("controller_rejoins_total") == 1
    # a REOPEN (the restart) sees every table
    db2 = Database(path)
    assert db2.bump_meta_counter("controller_rejoins_total") == 2
    assert db2.get_meta("controller_rejoins_total") == "2"


@pytest.mark.level("unit")
def test_restart_policy_persists_and_carries_backoff():
    """Budget consumption writes through the persist callback; a
    rebuilt policy resumes at the carried attempt count and serves out
    the previous incarnation's backoff deadline instead of restarting
    at its own crash cadence."""
    from kubetorch_tpu.resilience.restart import RestartPolicy

    saved = {}

    def persist(service, attempts, backoff_until):
        saved[service] = {"attempts": attempts,
                          "backoff_until": backoff_until}

    p1 = RestartPolicy(max_restarts_n=3, backoff_s=30.0, persist=persist)
    assert p1.next_delay("svc") == 0.0
    delay2 = p1.next_delay("svc")
    assert delay2 == 30.0
    assert saved["svc"]["attempts"] == 2
    assert saved["svc"]["backoff_until"] > time.time() + 25.0

    # the crash: a new policy restores from what was persisted
    p2 = RestartPolicy(max_restarts_n=3, backoff_s=30.0, persist=persist)
    assert p2.restore(dict(saved)) == 1
    assert p2.attempts("svc") == 2
    # third attempt must wait out the REMAINING ~30 s deadline, not
    # fire immediately because this process never slept it
    delay3 = p2.next_delay("svc")
    assert delay3 >= 25.0
    assert p2.next_delay("svc") is None          # budget exhausted
    assert p2.exhausted_once("svc") is True
    # reset clears the persisted row too
    p2.reset("svc")
    assert saved["svc"] == {"attempts": 0, "backoff_until": None}
    # expired deadlines are dropped at restore, attempts are not
    p3 = RestartPolicy(max_restarts_n=3, backoff_s=0.01, persist=persist)
    assert p3.restore({"svc": {"attempts": 1,
                               "backoff_until": time.time() - 5}}) == 1
    assert p3.attempts("svc") == 1
    assert p3.next_delay("svc") == pytest.approx(0.01, abs=0.01)
    # refund undoes the deadline with the attempt: a skipped restart
    # (gang revived during the backoff sleep) must not delay the next
    # legitimate restart — in memory or in the durable row
    saved.clear()
    p4 = RestartPolicy(max_restarts_n=3, backoff_s=30.0, persist=persist)
    assert p4.next_delay("svc") == 0.0
    assert p4.next_delay("svc") == 30.0
    p4.refund("svc")
    assert saved["svc"]["backoff_until"] is None
    p4.refund("svc")
    assert p4.next_delay("svc") == 0.0


@pytest.mark.level("minimal")
def test_rejoin_quarantine_observes_but_never_acts(tmp_path, monkeypatch):
    """A rebuilt controller inside KT_REJOIN_GRACE_S must not age
    restored pods toward dead (the restored last-seen stamps are its
    own start, not real silence); after the grace, truly-silent pods
    age out normally. Runtime SLOs and restart budgets are back too."""
    from kubetorch_tpu.controller.server import ControllerServer
    from kubetorch_tpu.observability.slo import Objective

    hb = 0.05
    monkeypatch.setenv("KT_HEARTBEAT_S", str(hb))
    monkeypatch.setenv("KT_DEAD_AFTER_MISSES", "2")
    monkeypatch.setenv("KT_AUTO_RESTART", "0")
    db = str(tmp_path / "ctl.db")

    s1 = ControllerServer(db, enable_reaper=False,
                          enable_resilience=False)
    assert s1._rejoined is False and s1.rejoin_grace_remaining() == 0.0
    s1.liveness.beat("svc", "p0")
    s1.liveness.beat("svc", "p1")
    s1.restart_policy.next_delay("svc")     # one attempt burned
    s1.slo.register(Objective(service="svc", name="ttft",
                              kind="latency",
                              metric="engine_ttft_seconds",
                              threshold_ms=500.0))
    s1.db.save_slo("svc", "ttft", {
        "service": "svc", "name": "ttft", "kind": "latency",
        "metric": "engine_ttft_seconds", "threshold_ms": 500.0})
    # a bare in-process server never runs the aiohttp shutdown hook —
    # release the log-persist executor thread before the "crash" (the
    # durable state under test lives in SQLite, not the log segments)
    if s1.log_sink.persist is not None:
        s1.log_sink.persist.close()
    del s1                                   # the crash

    grace = 6 * hb
    s2 = ControllerServer(db, enable_reaper=False,
                          enable_resilience=False, rejoin_grace_s=grace)
    assert s2._rejoined is True
    assert s2.rejoin_grace_remaining() > 0
    assert s2.restart_policy.attempts("svc") == 1
    assert [o.name for o in s2.slo.objectives("svc")] == ["ttft"]
    assert s2.liveness.pod_state("svc", "p0") == "alive"

    # deep into the dead window but still inside the grace: the tick
    # must NOT declare anything (p0/p1 never beat this incarnation)
    time.sleep(3 * hb)
    asyncio.run(s2._resilience_tick())
    health = s2.liveness.gang_health("svc")
    assert health["status"] == "healthy", health
    # ... and /health would have shown the window
    assert s2.rejoin_grace_remaining() > 0

    # after the grace the same silence is REAL silence
    deadline = time.time() + 40 * hb
    while time.time() < deadline:
        asyncio.run(s2._resilience_tick())
        if s2.liveness.gang_health("svc")["status"] == "dead":
            break
        time.sleep(hb / 2)
    assert s2.liveness.gang_health("svc")["status"] == "dead"
    # the dead transitions were persisted — a THIRD incarnation would
    # restore them as dead, not healthy
    states = {(r["service"], r["pod"]): r["state"]
              for r in s2.db.load_liveness()}
    assert states[("svc", "p0")] == "dead"
    if s2.log_sink.persist is not None:
        s2.log_sink.persist.close()   # thread-leak guard: see s1 above


@pytest.mark.level("minimal")
def test_event_watcher_dedup_rebuild_across_restart(tmp_path):
    """The docstring's durability claim, pinned: a watcher rebuilt on a
    fresh LogSink over the SAME persistence directory (the controller
    restart) re-seeds its dedup state from the sink and re-pushes
    nothing; a genuinely new/bumped event still lands."""
    from kubetorch_tpu.controller.event_watcher import (
        EVENTS_JOB,
        EventWatcher,
    )
    from kubetorch_tpu.observability.log_sink import LogSink
    from kubetorch_tpu.observability.persist import LogPersistence

    def event(uid, count=1, reason="Scheduled"):
        return {"metadata": {"uid": uid, "resourceVersion": str(100),
                             "namespace": "default"},
                "involvedObject": {"kind": "Pod", "name": "svc-0"},
                "type": "Normal", "reason": reason,
                "message": f"event {uid}", "count": count}

    class FakeK8s:
        def __init__(self, events):
            self.events = events

        def list(self, kind, namespace=None):
            return list(self.events)

    logs_dir = tmp_path / "obs"
    persist1 = LogPersistence(logs_dir)
    sink1 = LogSink(persist=persist1)
    k8s = FakeK8s([event("u1"), event("u2")])
    w1 = EventWatcher(sink1, k8s_client=k8s, list_services=lambda: [])
    assert w1.poll_once() == 2
    assert len(sink1.query({"job": EVENTS_JOB})) == 2
    persist1.close()                       # the controller goes down

    persist2 = LogPersistence(logs_dir)
    sink2 = LogSink(persist=persist2)      # replays segments
    w2 = EventWatcher(sink2, k8s_client=k8s, list_services=lambda: [])
    # dedup state rebuilt from the durable sink: nothing re-pushes
    assert w2.poll_once() == 0
    assert len(sink2.query({"job": EVENTS_JOB})) == 2
    # a bumped count (same uid, new marker) and a new uid still land
    k8s.events = [event("u1", count=2), event("u3")]
    assert w2.poll_once() == 2
    persist2.close()


@pytest.mark.level("minimal")
def test_ws_flap_reconnect_and_resync(tmp_path):
    """The ws-flap chaos kind severs the pod↔controller WS at a beat;
    the pod reconnects (full-jitter backoff), re-registers
    idempotently, counts ws_reconnects_total, and — because the
    controller's fleet store has never heard of it — ships the resync
    FULL telemetry snapshot that the registration ack requested."""
    from kubetorch_tpu.resilience import chaos as chaos_mod
    from kubetorch_tpu.serving.controller_ws import ControllerWebSocket

    port = _free_port()
    env = {**os.environ, "KT_HEARTBEAT_S": "0.2", "KT_AUTO_RESTART": "0",
           "KT_WS_RECONNECT_MAX_S": "0.5"}
    env.pop("KT_CHAOS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.controller.server",
         "--host", "127.0.0.1", "--port", str(port), "--db", ":memory:"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    url = f"http://127.0.0.1:{port}"

    class StubPodServer:
        metadata = {"service_name": "flapsvc"}
        ready = True
        setup_error = None
        launch_id = "gen1"

        def __init__(self):
            self.metrics = {}
            self.full_requests = 0

        def request_full_telemetry(self):
            self.full_requests += 1
            return {"ts": time.time(), "full": True,
                    "m": {"engine_tokens_total": 42.0}}

    async def drive():
        os.environ["KT_WS_RECONNECT_MAX_S"] = "0.5"
        os.environ["KT_POD_NAME"] = "flap-0"
        stub = StubPodServer()
        ws = ControllerWebSocket(stub, url)
        ws.start()
        try:
            deadline = time.time() + 10
            while not ws.connected and time.time() < deadline:
                await asyncio.sleep(0.05)
            assert ws.connected, "pod WS never connected"
            # seeded flap: the next beat is LOST with the connection
            chaos_mod.install(chaos_mod.ChaosPolicy(
                seed=3, ws_flap=1.0, max_events=1))
            ws.notify_heartbeat()
            deadline = time.time() + 10
            while time.time() < deadline and (
                    ws.connects < 2 or not ws.connected):
                await asyncio.sleep(0.05)
            assert ws.connects >= 2, "flap did not force a reconnect"
            assert stub.metrics.get("ws_reconnects_total", 0) >= 1
            # both registrations triggered the resync full snapshot
            # (new store each time it sees the pod… only the first
            # connect + the re-register after the flap)
            deadline = time.time() + 5
            while time.time() < deadline and stub.full_requests < 1:
                await asyncio.sleep(0.05)
            assert stub.full_requests >= 1
            # the snapshot actually landed in the fleet store
            deadline = time.time() + 5
            while time.time() < deadline:
                fleet = httpx.get(f"{url}/metrics/fleet/flapsvc",
                                  params={"window": 60},
                                  timeout=5.0)
                if fleet.status_code == 200 and \
                        "flap-0" in fleet.json().get("pods", {}):
                    break
                await asyncio.sleep(0.1)
            assert "flap-0" in fleet.json()["pods"]
        finally:
            chaos_mod.install(None)
            await ws.stop()

    old_env = {k: os.environ.get(k)
               for k in ("KT_POD_NAME", "KT_WS_RECONNECT_MAX_S")}
    try:
        asyncio.run(drive())
    finally:
        for key, old in old_env.items():
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
        proc.terminate()
        proc.wait(5)


# ------------------------------------------- ISSUE 20: scale-churn e2e
@pytest.mark.level("minimal")
def test_scale_churn_survives_controller_kill(tmp_path, monkeypatch):
    """The autoscaling half of the crash-safety story: a seeded
    scale-storm chaos draw ramps demand, the scaler actuates through
    the backend, the controller dies mid-ramp, and the restarted
    scaler resumes from its durable decisions — quarantined during the
    rejoin grace, then holding steady-state demand with ZERO spurious
    scale events before continuing the ramp."""
    from kubetorch_tpu.controller.server import ControllerServer
    from kubetorch_tpu.resilience.chaos import SCALE_STORM, ChaosPolicy

    svc = "churn-svc"
    monkeypatch.setenv("KT_SCALE_ENABLE", "1")
    monkeypatch.setenv("KT_SCALE_COOLDOWN_S", "0.5")
    monkeypatch.setenv("KT_SCALE_COLD_START_BUDGET_S", "1.0")
    monkeypatch.setenv("KT_AUTO_RESTART", "0")
    db_path = str(tmp_path / "ctl.db")

    calls = []

    class FakeBackend:
        name = "fake"

        def scale(self, service, replicas):
            calls.append((service, int(replicas)))
            return {"replicas": int(replicas)}

    def wire(server):
        server.scaler._backend_for = lambda name: FakeBackend()
        server.scaler.actuate_in_thread = False   # deterministic

    def feed(server, pods, active, free, queue):
        for name in pods:
            server.fleet.ingest(svc, name, {"ts": time.time(), "m": {
                "engine_phase": 2, "engine_active_rows": active,
                "engine_free_rows": free, "engine_queue_depth": queue,
            }, "full": True})

    s1 = ControllerServer(db_path, enable_reaper=False,
                          enable_resilience=False)
    assert s1.scale_enable is True
    wire(s1)
    s1.db.upsert_pool(svc, namespace="default", backend="fake",
                      compute={"autoscaling": {
                          "min_scale": 0, "max_scale": 6,
                          "metric": "concurrency"}})
    # the seeded scale-storm chaos kind drives the ramp: a hit triples
    # the offered queue depth exactly as in tests/fleet_sim.py's trace
    storm = ChaosPolicy(seed=5, scale_storm=1.0, pod_lag=1.0)
    queue = 4 * (3 if storm.decide(SCALE_STORM, "block-0") else 1)
    feed(s1, ["p0"], active=4, free=4, queue=queue)
    asyncio.run(s1._resilience_tick())
    # 16 demand rows over 8 rows/pod at 0.75 occupancy → 3 replicas
    assert calls == [(svc, 3)]
    assert len(s1.db.load_scale_decisions(svc)) == 1
    assert s1.scaler.flaps_total == 0
    if s1.log_sink.persist is not None:
        s1.log_sink.persist.close()
    del s1                                        # the mid-ramp crash

    s2 = ControllerServer(db_path, enable_reaper=False,
                          enable_resilience=False, rejoin_grace_s=0.3)
    wire(s2)
    # restored scaler state alone makes this a REJOIN: desired count is
    # back, and the quarantine gates the scale loop
    assert s2._rejoined is True
    assert s2.scaler.status(svc)[svc]["desired"] == 3
    feed(s2, ["p0", "p1", "p2"], active=5, free=3, queue=0)
    asyncio.run(s2._resilience_tick())            # inside the grace
    assert len(s2.db.load_scale_decisions(svc)) == 1, \
        "scaler acted inside the rejoin quarantine"

    time.sleep(0.35)                              # grace expires
    # steady state at the restored count: 15 demand rows over 24
    # capacity wants exactly the 3 replicas the old controller chose
    feed(s2, ["p0", "p1", "p2"], active=5, free=3, queue=0)
    asyncio.run(s2._resilience_tick())
    assert len(s2.db.load_scale_decisions(svc)) == 1, \
        "restarted scaler minted a spurious decision at steady state"
    assert calls == [(svc, 3)]

    # the next storm block resumes the ramp on the NEW controller
    queue = 4 * (3 if storm.decide(SCALE_STORM, "block-1") else 1)
    feed(s2, ["p0", "p1", "p2"], active=5, free=3, queue=queue)
    asyncio.run(s2._resilience_tick())
    rows = s2.db.load_scale_decisions(svc)
    assert len(rows) == 2 and rows[0]["to_replicas"] == 6  # max_scale
    assert calls[-1] == (svc, 6)
    assert s2.scaler.flaps_total == 0
    if s2.log_sink.persist is not None:
        s2.log_sink.persist.close()


@pytest.mark.level("minimal")
def test_scale_endpoints_and_cli_override(tmp_path, monkeypatch):
    """`ktpu scale <svc> <n>` routes through the controller's durable
    override row when one is reachable; `ktpu scale <svc> --auto`
    clears it; GET /scale answers the desired/actual view `ktpu top`
    renders."""
    from click.testing import CliRunner

    from kubetorch_tpu.cli import main as cli_main

    port = _free_port()
    url = f"http://127.0.0.1:{port}"
    env = {**os.environ, "KT_AUTO_RESTART": "0"}
    env.pop("KT_CHAOS", None)
    env.pop("KT_SCALE_ENABLE", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "kubetorch_tpu.controller.server",
         "--host", "127.0.0.1", "--port", str(port), "--db",
         str(tmp_path / "ctl.db")],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    try:
        _wait_http(f"{url}/health", proc)
        httpx.post(f"{url}/pool", json={
            "service_name": "pinsvc", "backend": "local",
            "module_meta": {"name": "pinsvc"}, "broadcast": False,
        }, timeout=5.0).raise_for_status()
        monkeypatch.setenv("KT_CONTROLLER_URL", url)

        result = CliRunner().invoke(cli_main, ["scale", "pinsvc", "2"])
        assert result.exit_code == 0, result.output
        assert "durable override" in result.output
        status = httpx.get(f"{url}/scale/pinsvc", timeout=5.0).json()
        assert status["enabled"] is False          # loop off, pin on
        assert status["services"]["pinsvc"]["override"] == 2
        assert status["decisions"][0]["kind"] == "override"
        # an unknown service 404s instead of minting rows
        bad = httpx.post(f"{url}/scale/no-such",
                         json={"replicas": 1}, timeout=5.0)
        assert bad.status_code == 404
        # a bad body 400s
        bad = httpx.post(f"{url}/scale/pinsvc",
                         json={"replicas": "many"}, timeout=5.0)
        assert bad.status_code == 400

        result = CliRunner().invoke(cli_main,
                                    ["scale", "pinsvc", "--auto"])
        assert result.exit_code == 0, result.output
        assert "override cleared" in result.output
        status = httpx.get(f"{url}/scale/pinsvc", timeout=5.0).json()
        assert status["services"]["pinsvc"]["override"] is None
        # clearing twice is a no-op, not an error
        result = CliRunner().invoke(cli_main,
                                    ["scale", "pinsvc", "--auto"])
        assert result.exit_code == 0, result.output
        assert "no override was set" in result.output
    finally:
        proc.terminate()
        try:
            proc.wait(5)
        except subprocess.TimeoutExpired:
            proc.kill()


@pytest.mark.level("unit")
def test_top_render_replica_column():
    """`ktpu top` shows the scaler's desired/actual/pin view on the
    service header line (ISSUE 20 satellite)."""
    from kubetorch_tpu.cli import _top_render

    snapshot = {"svc": {
        "fleet": {"pods": {}}, "slo": [],
        "scale": {"desired": 4, "actual": 2, "override": 4,
                  "cooldown_remaining_s": 12.0},
    }}
    out = _top_render(snapshot, 60.0)
    assert "replicas: 2/4 desired" in out
    assert "(pinned 4)" in out
    assert "(cooldown 12s)" in out
    # no scaler view (older controller): header renders without it
    bare = _top_render({"svc": {"fleet": {"pods": {}}, "slo": [],
                                "scale": None}}, 60.0)
    assert "replicas" not in bare


# ------------------------------------------------------------------ e2e
@pytest.fixture()
def local_state(tmp_path_factory):
    state = tmp_path_factory.mktemp("ktlocal-crash")
    old = os.environ.get("KT_LOCAL_STATE")
    os.environ["KT_LOCAL_STATE"] = str(state)
    import kubetorch_tpu.provisioning.backend as backend

    old_root = backend._LOCAL_ROOT
    backend._LOCAL_ROOT = state
    yield state
    for record in backend.LocalBackend().list_services():
        backend.LocalBackend().teardown(record["service_name"],
                                        quiet=True)
    backend._LOCAL_ROOT = old_root
    if old is None:
        os.environ.pop("KT_LOCAL_STATE", None)
    else:
        os.environ["KT_LOCAL_STATE"] = old


def _expected_tokens(tag, n):
    return [hashlib.sha256(f"{tag}:{i}".encode()).hexdigest()[:8]
            for i in range(n)]


@pytest.mark.level("minimal")
def test_controller_kill_e2e(tmp_path, local_state, monkeypatch):
    """ISSUE 15 acceptance: controller SIGKILLed mid-serving.

    Phase A seeds a ghost service whose restart budget is exhausted
    (the carried-budget witness). Phase B deploys a real pod and opens
    a channel stream; the controller dies mid-stream; the stream
    completes byte-identical with execution count one and `ktpu top`
    answers via the direct pod poll. Phase C restarts the controller on
    the same durable DB: budgets and the runtime SLO objective are
    back immediately, gang health rebuilds within the quarantine plus
    two sweep intervals, zero dead verdicts and zero gang restarts
    land, fleet rates stay non-negative, and the pod's reconnect is
    countable."""
    import kubetorch_tpu as kt
    from kubetorch_tpu.resources.callables.cls import Cls

    hb = 0.3
    grace = 1.0
    port = _free_port()
    url = f"http://127.0.0.1:{port}"
    db = str(tmp_path / "controller.db")
    ctl_env = {**os.environ,
               "KT_HEARTBEAT_S": str(hb),
               "KT_DEAD_AFTER_MISSES": "2",
               "KT_AUTO_RESTART": "1",
               "KT_MAX_RESTARTS": "1",
               "KT_REJOIN_GRACE_S": str(grace),
               "KT_LOCAL_STATE": str(local_state)}
    ctl_env.pop("KT_CHAOS", None)

    def start_controller():
        proc = subprocess.Popen(
            [sys.executable, "-m", "kubetorch_tpu.controller.server",
             "--host", "127.0.0.1", "--port", str(port), "--db", db],
            env=ctl_env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT)
        _wait_http(f"{url}/health", proc)
        return proc

    # pods inherit these (subprocesses of this test process)
    monkeypatch.setenv("KT_CONTROLLER_URL", url)
    monkeypatch.setenv("KT_HEARTBEAT_S", str(hb))
    monkeypatch.setenv("KT_WS_RECONNECT_MAX_S", "1.0")
    monkeypatch.setenv("KT_TELEMETRY_EVERY", "1")
    monkeypatch.delenv("KT_CHAOS", raising=False)

    proc = start_controller()
    remote = None
    try:
        # ---- phase A: ghost service exhausts its restart budget -----
        httpx.post(f"{url}/pool", json={
            "service_name": "ghost-svc", "backend": "local",
            "module_meta": {"name": "ghost-svc"}, "broadcast": False,
        }, timeout=5.0).raise_for_status()
        httpx.post(f"{url}/heartbeat", json={
            "service": "ghost-svc", "pod": "ghost-0"},
            timeout=5.0).raise_for_status()
        # ghost-0 never beats again → dead → auto-restart attempt fails
        # (no local service record) → budget (max 1) exhausted
        deadline = time.time() + 30
        ghost = None
        while time.time() < deadline:
            ghost = httpx.get(f"{url}/health/ghost-svc",
                              timeout=5.0).json()
            if ghost.get("restart_attempts", 0) >= 1:
                break
            time.sleep(hb / 2)
        assert ghost and ghost["restart_attempts"] == 1, ghost
        assert ghost["max_restarts"] == 1

        # ---- phase B: real pod + runtime SLO + mid-stream kill ------
        remote = Cls(root_path=str(SUMMER), import_path="summer",
                     callable_name="ChunkEngine", name="crashsvc")
        remote.to(kt.Compute(cpus="0.1"))
        svc = remote.service_name   # may carry a username prefix
        deadline = time.time() + 20
        while time.time() < deadline:
            health = httpx.get(f"{url}/health/{svc}", timeout=5.0)
            if health.status_code == 200 and \
                    health.json()["status"] == "healthy":
                break
            time.sleep(hb / 2)
        assert health.json()["status"] == "healthy", health.text
        pod_names = list(health.json()["pods"])
        httpx.post(f"{url}/slo", json={
            "service": svc, "name": "ttft", "kind": "latency",
            "metric": "engine_ttft_seconds", "threshold_ms": 500,
            "objective": 0.99}, timeout=5.0).raise_for_status()
        # give the telemetry piggyback a couple of beats to land
        deadline = time.time() + 10
        while time.time() < deadline:
            fleet = httpx.get(f"{url}/metrics/fleet/{svc}",
                              params={"window": 30}, timeout=5.0)
            if fleet.status_code == 200 and fleet.json()["pods"]:
                break
            time.sleep(0.2)
        assert fleet.json()["pods"], "no telemetry before the kill"

        n, delay = 60, 0.05
        expected = _expected_tokens("crash", n)
        with remote.channel(depth=2) as chan:
            stream = chan.submit("crash", method="decode",
                                 kwargs={"n": n, "delay": delay},
                                 stream=True).result(timeout=60)
            it = iter(stream)
            got = [next(it) for _ in range(10)]
            # ---- the crash: SIGKILL, mid-stream ---------------------
            proc.send_signal(signal.SIGKILL)
            proc.wait(10)
            got.extend(it)                  # the stream MUST complete
            assert [t["tok"] for t in got] == expected, \
                "stream not byte-identical through the controller kill"
            assert chan.call("crash", method="exec_count") == 1
            # data plane fully alive with the control plane dead
            assert chan.call("post-kill", method="exec_count") == 0

            # ---- satellite: ktpu top falls back to direct poll ------
            from click.testing import CliRunner

            from kubetorch_tpu.cli import main as cli_main

            result = CliRunner().invoke(
                cli_main, ["top", svc, "--once"])
            assert result.exit_code == 0, result.output
            assert "controller unreachable — direct poll" in result.output
            result = CliRunner().invoke(
                cli_main, ["top", svc, "--once", "--json"])
            assert result.exit_code == 0, result.output
            snapshot = json.loads(result.output)
            assert snapshot[svc]["fleet"]["source"] == \
                "direct-poll"
            assert snapshot[svc]["fleet"]["pods"], snapshot

            # ---- phase C: restart on the same durable DB ------------
            proc = start_controller()
            t_up = time.time()   # grace runs from the subprocess's
            # init, slightly BEFORE this stamp — the budget below is
            # measured from "controller answers /health"
            # budgets + SLOs are back IMMEDIATELY (inside the grace)
            ghost = httpx.get(f"{url}/health/ghost-svc",
                              timeout=5.0).json()
            assert ghost["restart_attempts"] == 1, \
                "restart budget did not carry over"
            slo = httpx.get(f"{url}/slo/{svc}", timeout=5.0).json()
            assert [o["name"] for o in slo["objectives"]] == ["ttft"], \
                "runtime SLO objective lost in the restart"
            # health rebuilds within the grace + 2 sweep intervals
            rebuild_budget = grace + 2 * (hb / 2) + 2.0  # + CI slack
            healthy_at = None
            while time.time() < t_up + rebuild_budget + 10:
                health = httpx.get(f"{url}/health/{svc}",
                                   timeout=5.0)
                if health.status_code == 200:
                    body = health.json()
                    if body["status"] == "healthy" and body["pods"]:
                        healthy_at = time.time()
                        break
                time.sleep(0.1)
            assert healthy_at is not None, health.text
            assert healthy_at - t_up <= rebuild_budget, (
                f"health took {healthy_at - t_up:.1f}s, "
                f"budget {rebuild_budget:.1f}s")
            assert set(health.json()["pods"]) == set(pod_names)

            # zero spurious verdicts or restarts on the new controller
            metrics = httpx.get(
                f"{url}/metrics", timeout=5.0,
                headers={"Accept": "text/plain"}).text
            assert "resilience_gang_restarts_total 0" in metrics
            assert "resilience_dead_transitions_total 0" in metrics
            assert "kubetorch_controller_rejoins_total 1" in metrics
            logs = httpx.get(f"{url}/logs/query",
                             params={"service": svc},
                             timeout=5.0).json()["entries"]
            assert not any(
                (e.get("labels") or {}).get("reason")
                in ("PodDead", "GangRestarted") for e in logs), logs

            # fleet rates non-negative across the gap; the resync full
            # snapshot re-seeds the store without waiting for the
            # KT_TELEMETRY_FULL_EVERY cadence
            deadline = time.time() + 15
            fleet = None
            while time.time() < deadline:
                resp = httpx.get(f"{url}/metrics/fleet/{svc}",
                                 params={"window": 30}, timeout=5.0)
                if resp.status_code == 200 and resp.json()["pods"]:
                    fleet = resp.json()
                    break
                time.sleep(0.2)
            assert fleet, "no telemetry reached the new controller"
            for name, entry in fleet["counters"].items():
                assert entry["rate"] >= 0, (name, entry)
                for pod, rate in entry["by_pod"].items():
                    assert rate >= 0, (name, pod, rate)
            assert not any(p["stale"] for p in fleet["pods"].values())

            # the stream path still works against the SAME channel
            out = chan.call(7777, method="step")
            assert out["i"] == 7777

        # the pod reconnected (countable) — the controller WS re-dials
        # on its jittered backoff (capped at KT_WS_RECONNECT_MAX_S=1 s
        # here), so give it a bounded window after the restart
        from kubetorch_tpu.provisioning.backend import get_backend

        pod_url = get_backend().pod_urls(svc)[0]
        deadline = time.time() + 15
        pod_metrics = ""
        while time.time() < deadline:
            pod_metrics = httpx.get(
                f"{pod_url}/metrics", timeout=5.0,
                headers={"Accept": "text/plain"}).text
            if "ws_reconnects_total" in pod_metrics:
                break
            time.sleep(0.3)
        assert "ws_reconnects_total" in pod_metrics
        # the outage itself was observed and countable pod-side too
        assert "heartbeat_send_errors_total" in pod_metrics
    finally:
        if remote is not None:
            try:
                remote.teardown()
            except Exception:
                pass
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(5)
            except subprocess.TimeoutExpired:
                proc.kill()
