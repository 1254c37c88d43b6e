"""Autoscaler battery (``pytest -m fleet``): the decision loop on a
virtual clock (hysteresis, cooldown anti-flap, min/max clamps, spawn
retry/give-up) and the chaos path end to end — a real replica subprocess
``kill -9``'d mid-load, the ring failing over with zero surfaced errors,
and a warm replacement admitted within ``replace_deadline_s``.

The unit half injects a fake router/launcher/scrape so every decision is
a pure function of the burn trace; the chaos half launches stdlib-only
stub replicas through :class:`SubprocessLauncher` so startup costs
milliseconds, not a jax import."""

import json
import os
import sys
import threading
import time

import pytest

from deepdfa_tpu.config import AutoscaleConfig
from deepdfa_tpu.resilience import faults
from deepdfa_tpu.resilience.journal import RunJournal
from deepdfa_tpu.serve import FleetRouter, SubprocessLauncher
from deepdfa_tpu.serve.autoscaler import Autoscaler, max_fast_burn

pytestmark = pytest.mark.fleet


# ---------------------------------------------------------------------------
# fakes: deterministic decision-loop harness (no sockets, virtual clock)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, seconds):
        self.t += seconds


class _FakeHandle:
    def __init__(self, name, join_cold_compiles=0):
        self.host, port = name.rsplit(":", 1)
        self.port = int(port)
        self.name = name
        self.join_cold_compiles = join_cold_compiles
        self.exit_code = None
        self.drained = False
        self.killed = False

    def poll(self):
        return self.exit_code

    def drain(self):
        self.drained = True

    def kill(self):
        self.killed = True
        self.exit_code = 137


class _FakeRouter:
    """Membership book-keeping only: a backend is ready the instant it is
    added (the stub 'probe' always finds it warm)."""

    def __init__(self):
        self.states = {}
        self.added = []
        self.removed = []

    def add_backend(self, spec):
        name = str(spec)
        self.states[name] = "ready"
        self.added.append(name)

    def remove_backend(self, name):
        self.removed.append(name)
        return self.states.pop(name, None) is not None

    def probe_once(self):
        return dict(self.states)


class _FakeLauncher:
    def __init__(self):
        self.count = 0
        self.handles = []

    def spawn(self):
        self.count += 1
        h = _FakeHandle(f"127.0.0.1:{9000 + self.count}")
        self.handles.append(h)
        return h


def _harness(burn, tmp_path=None, **cfg_kw):
    """An Autoscaler whose burn signal is ``burn['v']`` and whose clock
    only advances through injected sleeps."""
    defaults = dict(min_replicas=1, max_replicas=3, poll_interval_s=1.0,
                    burn_high=2.0, burn_low=0.5, up_consecutive=2,
                    down_consecutive=3, cooldown_s=10.0,
                    replace_deadline_s=30.0, spawn_attempts=3,
                    spawn_backoff_s=0.5)
    defaults.update(cfg_kw)
    cfg = AutoscaleConfig(enabled=True, **defaults)
    clock = _Clock()
    router = _FakeRouter()
    launcher = _FakeLauncher()
    journal = (RunJournal(tmp_path / "autoscaler.json")
               if tmp_path is not None else None)
    scaler = Autoscaler(cfg, router, launcher, journal=journal,
                        scrape=lambda handle: burn["v"],
                        clock=clock, sleep=clock.sleep)
    return scaler, router, launcher, clock


def _tick(scaler, clock, n=1, dt=1.0):
    made = []
    for _ in range(n):
        clock.t += dt
        made += scaler.poll_once()
    return made


# ---------------------------------------------------------------- decisions


def test_ensure_min_spawns_to_floor_and_registers():
    burn = {"v": 1.0}
    scaler, router, launcher, clock = _harness(burn, min_replicas=2)
    made = scaler.ensure_min()
    assert [d["action"] for d in made] == ["scale_up", "scale_up"]
    assert all(d["reason"] == "min_replicas" for d in made)
    assert launcher.count == 2
    assert sorted(router.states) == sorted(h.name for h in launcher.handles)


def test_hysteresis_dead_band_never_acts():
    burn = {"v": 1.0}  # between burn_low=0.5 and burn_high=2.0
    scaler, router, launcher, clock = _harness(burn)
    scaler.ensure_min()
    assert _tick(scaler, clock, n=20) == []
    assert launcher.count == 1  # only the min-replica spawn


def test_scale_up_needs_consecutive_high_polls():
    burn = {"v": 3.0}
    scaler, router, launcher, clock = _harness(burn, up_consecutive=3)
    scaler.ensure_min()
    assert _tick(scaler, clock, n=2) == []  # streak not yet met
    made = _tick(scaler, clock)
    assert [d["action"] for d in made] == ["scale_up"]
    assert made[0]["reason"] == "burn_high"
    assert launcher.count == 2


def test_dip_into_dead_band_resets_the_streak():
    burn = {"v": 3.0}
    scaler, router, launcher, clock = _harness(burn, up_consecutive=3)
    scaler.ensure_min()
    _tick(scaler, clock, n=2)
    burn["v"] = 1.0  # hysteresis: one in-band poll clears the streak
    _tick(scaler, clock)
    burn["v"] = 3.0
    assert _tick(scaler, clock, n=2) == []
    assert _tick(scaler, clock)[0]["action"] == "scale_up"


def test_flapping_burn_never_oscillates_the_fleet():
    """Alternating high/low polls keep resetting both streaks — the
    anti-flap property the watermarks + streaks exist for."""
    burn = {"v": 3.0}
    scaler, router, launcher, clock = _harness(burn, up_consecutive=2,
                                               down_consecutive=2)
    scaler.ensure_min()
    for _ in range(10):
        burn["v"] = 3.0
        _tick(scaler, clock)
        burn["v"] = 0.1
        _tick(scaler, clock)
    assert launcher.count == 1
    assert scaler.summary()["scale_decisions"] == 1  # the min spawn only


def test_cooldown_blocks_back_to_back_actions():
    burn = {"v": 3.0}
    scaler, router, launcher, clock = _harness(burn, up_consecutive=2,
                                               cooldown_s=10.0,
                                               max_replicas=5)
    scaler.ensure_min()
    _tick(scaler, clock, n=2)
    assert launcher.count == 2  # first scale-up landed
    # streak re-arms immediately but the cooldown gates actuation
    assert _tick(scaler, clock, n=5) == []
    assert launcher.count == 2
    clock.t += 10.0  # cooldown expires; the standing streak may act
    assert _tick(scaler, clock)[0]["action"] == "scale_up"
    assert launcher.count == 3


def test_max_clamp_holds_and_journals_the_hold(tmp_path):
    burn = {"v": 3.0}
    scaler, router, launcher, clock = _harness(
        burn, tmp_path=tmp_path, max_replicas=2, up_consecutive=2,
        cooldown_s=1.0)
    scaler.ensure_min()
    _tick(scaler, clock, n=2)  # 1 -> 2 (max)
    clock.t += 2.0
    made = _tick(scaler, clock, n=2)
    holds = [d for d in made if d["action"] == "hold"]
    assert holds and holds[0]["reason"] == "max_replicas"
    assert launcher.count == 2  # clamped
    rec = RunJournal(tmp_path / "autoscaler.json").read()
    assert rec["event"] == "autoscale_transition"


def test_min_clamp_never_drains_below_floor():
    burn = {"v": 0.1}
    scaler, router, launcher, clock = _harness(burn, min_replicas=1,
                                               down_consecutive=2)
    scaler.ensure_min()
    made = _tick(scaler, clock, n=4)
    holds = [d for d in made if d["action"] == "hold"]
    assert holds and holds[0]["reason"] == "min_replicas"
    assert not launcher.handles[0].drained
    assert router.states  # the floor replica is still registered


def test_scale_down_exits_ring_then_drains_flag_only():
    burn = {"v": 3.0}
    scaler, router, launcher, clock = _harness(
        burn, up_consecutive=1, down_consecutive=2, cooldown_s=1.0)
    scaler.ensure_min()
    _tick(scaler, clock)  # 1 -> 2
    clock.t += 2.0
    burn["v"] = 0.1
    made = _tick(scaler, clock, n=2)
    downs = [d for d in made if d["action"] == "scale_down"]
    assert downs and downs[0]["reason"] == "burn_low"
    victim = launcher.handles[-1]  # LIFO: the newest replica leaves
    assert downs[0]["backend"] == victim.name
    assert victim.name in router.removed
    # invariant 22: drained, never hard-killed
    assert victim.drained and not victim.killed
    assert launcher.handles[0].name in router.states


def test_dead_replica_replaced_outside_cooldown(tmp_path):
    burn = {"v": 1.0}
    scaler, router, launcher, clock = _harness(burn, tmp_path=tmp_path,
                                               cooldown_s=1000.0)
    scaler.ensure_min()
    dead = launcher.handles[0]
    dead.exit_code = 137  # the process vanished between polls
    made = _tick(scaler, clock)
    replaces = [d for d in made if d["action"] == "replace"]
    assert len(replaces) == 1
    r = replaces[0]
    assert r["backend"] == dead.name and r["exit_code"] == 137
    assert r["replacement"] == launcher.handles[-1].name
    assert r["replace_latency_s"] <= scaler._cfg.replace_deadline_s
    assert r["join_cold_compiles"] == 0
    assert dead.name in router.removed
    summary = scaler.summary()
    assert summary["replacements"] == 1
    assert summary["join_cold_compiles"] == 0


@pytest.mark.faults
def test_spawn_fault_retries_with_backoff_then_succeeds():
    burn = {"v": 1.0}
    scaler, router, launcher, clock = _harness(burn, spawn_attempts=3,
                                               spawn_backoff_s=0.5)
    with faults.installed("autoscale.spawn_fail@1,2"):
        made = scaler.ensure_min()
    assert [d["action"] for d in made] == ["scale_up"]
    assert launcher.count == 1  # third attempt reached the launcher
    assert clock.t >= 0.5  # the retry backoff actually slept
    assert scaler.summary()["spawn_give_ups"] == 0


@pytest.mark.faults
def test_spawn_fault_exhaustion_journals_give_up(tmp_path):
    burn = {"v": 1.0}
    scaler, router, launcher, clock = _harness(
        burn, tmp_path=tmp_path, spawn_attempts=3, spawn_backoff_s=0.1)
    with faults.installed("autoscale.spawn_fail"):  # every attempt fails
        made = scaler.ensure_min()
    assert made == []  # no replica admitted
    assert launcher.count == 0
    summary = scaler.summary()
    assert summary["spawn_give_ups"] == 1
    give_up = summary["decisions"][-1]
    assert give_up["action"] == "spawn_give_up"
    assert give_up["attempts"] == 3
    assert give_up["reason"] == "min_replicas"
    rec = RunJournal(tmp_path / "autoscaler.json").read()
    assert rec["event"] == "autoscale_transition"
    assert rec["action"] == "spawn_give_up"
    # next tick (fault cleared) retries the floor — give-ups are
    # per-tick, not terminal
    assert [d["action"] for d in _tick(scaler, clock)] == ["scale_up"]


@pytest.mark.faults
def test_crash_fault_kills_newest_and_heals_same_tick():
    burn = {"v": 1.0}
    scaler, router, launcher, clock = _harness(burn, min_replicas=2)
    scaler.ensure_min()
    victim = launcher.handles[-1]
    with faults.installed("autoscale.replica_crash@1"):
        made = _tick(scaler, clock)
    actions = [d["action"] for d in made]
    assert actions == ["replica_crash_injected", "replace"]
    assert victim.killed
    assert made[1]["backend"] == victim.name
    assert made[1]["replacement"] == launcher.handles[-1].name
    assert len(scaler.summary()["replicas"]) == 2


def test_stop_drains_every_managed_replica():
    burn = {"v": 1.0}
    scaler, router, launcher, clock = _harness(burn, min_replicas=2)
    scaler.ensure_min()
    summary = scaler.stop(drain=True)
    assert summary["replicas"] == []
    assert all(h.drained and not h.killed for h in launcher.handles)
    assert router.states == {}


def test_max_fast_burn_picks_worst_fast_window():
    text = ('deepdfa_serve_slo_burn_rate{slo="latency_p99",window="fast"} 1.5\n'
            'deepdfa_serve_slo_burn_rate{slo="latency_p99",window="slow"} 9.0\n'
            'deepdfa_serve_slo_burn_rate{slo="availability",window="fast"} 2.5\n'
            'deepdfa_serve_slo_burn_rate{slo="errors",window="fast"} NaN\n')
    assert max_fast_burn(text) == 2.5
    assert max_fast_burn("") is None
    assert max_fast_burn('x_burn_rate{window="slow"} 3.0') is None


# ---------------------------------------------------------------------------
# chaos: real subprocess replicas behind a real router, kill -9 mid-load

_STUB = r'''
import json, os, signal, threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

BURN = os.environ.get("STUB_BURN", "1.0")
draining = threading.Event()


class H(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _send(self, code, body, ctype="application/json"):
        data = (body if isinstance(body, str) else json.dumps(body)).encode()
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        if self.path == "/healthz":
            code = 503 if draining.is_set() else 200
            self._send(code, {"status": "draining" if draining.is_set()
                              else "ok", "draining": draining.is_set(),
                              "warm": True, "replica_id": "stub"})
        elif self.path == "/slo":
            text = ('deepdfa_serve_slo_burn_rate{slo="latency_p99",'
                    'window="fast"} %s\n' % BURN)
            self._send(200, text, ctype="text/plain; version=0.0.4")
        elif self.path == "/metrics":
            self._send(200, "stub_up 1\n", ctype="text/plain; version=0.0.4")
        else:
            self._send(404, {"error": "no route"})

    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(n)
        if draining.is_set():
            self._send(503, {"error": "draining"})
        else:
            self._send(200, {"results": [{"score": 0.5, "cached": False}],
                             "bytes": len(raw)})


httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
httpd.daemon_threads = True


def _term(*_):
    draining.set()
    threading.Thread(target=httpd.shutdown, daemon=True).start()


signal.signal(signal.SIGTERM, _term)
print(json.dumps({"status": "serving", "host": "127.0.0.1",
                  "port": httpd.server_address[1], "replica_id": "stub",
                  "warm_store": {"buckets": 3, "hits": 3, "misses": 0,
                                 "compile_seconds_saved": 2.5}}),
      flush=True)
httpd.serve_forever()
'''


def _write_stub(tmp_path):
    path = tmp_path / "stub_replica.py"
    path.write_text(_STUB)
    return path


def _launcher_for(tmp_path):
    stub = _write_stub(tmp_path)
    return SubprocessLauncher([sys.executable, str(stub)],
                              env={**os.environ, "STUB_BURN": "1.0"},
                              startup_timeout_s=30.0)


def _post(port, path, payload, timeout=10):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        body = json.dumps(payload).encode()
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _get(port, path, timeout=10):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def test_launcher_parses_serving_line_and_join_report(tmp_path):
    launcher = _launcher_for(tmp_path)
    h = launcher.spawn()
    try:
        assert h.poll() is None
        assert h.port > 0 and h.name == f"127.0.0.1:{h.port}"
        assert h.join_cold_compiles == 0  # invariant 11 via the stub report
        code, body = _get(h.port, "/healthz")
        assert code == 200 and body["warm"] is True
    finally:
        h.kill()


_CHIP_STUB = r'''
import json, os, time
print(json.dumps({"status": "serving", "host": "127.0.0.1", "port": 1,
                  "replica_id": os.environ.get("TPU_VISIBLE_CHIPS"),
                  "bounds": os.environ.get("TPU_CHIPS_PER_PROCESS_BOUNDS")}),
      flush=True)
time.sleep(60)
'''


def test_launcher_gives_each_live_child_its_own_chip(tmp_path):
    """One process per chip: with ``chips=2`` two live children name
    different chips in their environment, a third spawn is refused, and a
    dead child's chip goes back to the pool. ``chips=0`` assigns nothing."""
    from deepdfa_tpu.serve.autoscaler import SpawnError

    stub = tmp_path / "chip_stub.py"
    stub.write_text(_CHIP_STUB)
    launcher = SubprocessLauncher([sys.executable, str(stub)],
                                  startup_timeout_s=30.0, chips=2)
    a = launcher.spawn()
    b = launcher.spawn()
    try:
        assert {a.serving["replica_id"], b.serving["replica_id"]} == {"0", "1"}
        assert a.serving["bounds"] == b.serving["bounds"] == "1,1,1"
        with pytest.raises(SpawnError, match="refusing"):
            launcher.spawn()
        a.kill()
        a.wait(timeout=10)
        c = launcher.spawn()
        try:
            assert c.serving["replica_id"] == a.serving["replica_id"]
        finally:
            c.kill()
    finally:
        a.kill()
        b.kill()
    unmanaged = SubprocessLauncher(
        [sys.executable, str(stub)], startup_timeout_s=30.0,
        env={k: v for k, v in os.environ.items()
             if k != "TPU_VISIBLE_CHIPS"})
    d = unmanaged.spawn()
    try:
        assert d.serving["replica_id"] is None
    finally:
        d.kill()


def test_router_admin_endpoint_add_list_remove(tmp_path):
    launcher = _launcher_for(tmp_path)
    h = launcher.spawn()
    router = FleetRouter([], port=0, probe_interval_s=60.0,
                         allow_empty=True).start(probe=False)
    try:
        code, body = _post(router.port, "/admin/backends",
                           {"action": "add", "backend": h.name})
        assert code == 200 and body["state"] == "ready"
        code, body = _get(router.port, "/admin/backends")
        assert h.name in body["ready"]
        assert body["backends"][h.name]["state"] == "ready"
        # scoring routes through the registered backend
        code, body = _post(router.port, "/score", {"source": "int f();"})
        assert code == 200
        code, body = _post(router.port, "/admin/backends",
                           {"action": "remove", "backend": h.name})
        assert code == 200 and body["removed"] is True
        code, body = _get(router.port, "/admin/backends")
        assert body["ready"] == [] and body["backends"] == {}
        # malformed admin requests are 400s, never crashes
        assert _post(router.port, "/admin/backends", {"action": "add"})[0] == 400
        assert _post(router.port, "/admin/backends",
                     {"action": "add", "backend": "noport"})[0] == 400
    finally:
        h.kill()
        router.shutdown()


class _RecordingLauncher(SubprocessLauncher):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.handles = []

    def spawn(self):
        h = super().spawn()
        self.handles.append(h)
        return h


@pytest.mark.faults
def test_kill9_mid_load_fails_over_and_replaces_within_deadline(tmp_path):
    """The PR's acceptance chaos case: a replica is kill -9'd while load
    is flowing. The ring must fail the keyspace over with zero 5xx
    surfaced to clients, and the autoscaler must admit a warm
    replacement (join_cold_compiles == 0) within replace_deadline_s."""
    stub = _write_stub(tmp_path)
    launcher = _RecordingLauncher([sys.executable, str(stub)],
                                  env={**os.environ, "STUB_BURN": "1.0"},
                                  startup_timeout_s=30.0)
    router = FleetRouter([], port=0, probe_interval_s=0.1,
                         allow_empty=True).start(probe=True)
    cfg = AutoscaleConfig(enabled=True, min_replicas=2, max_replicas=3,
                          poll_interval_s=0.1, burn_high=2.0, burn_low=0.5,
                          up_consecutive=2, down_consecutive=3,
                          cooldown_s=1.0, replace_deadline_s=20.0,
                          spawn_attempts=3, spawn_backoff_s=0.1)
    journal = RunJournal(tmp_path / "autoscaler.json")
    scaler = Autoscaler(cfg, router, launcher, journal=journal)
    errors = []
    stop = threading.Event()

    def load():
        i = 0
        while not stop.is_set():
            i += 1
            try:
                code, _ = _post(router.port, "/score",
                                {"source": f"int f{i}() {{ return {i}; }}"})
                if code != 200:
                    errors.append(code)
            except OSError:
                errors.append("conn")  # the ROUTER itself must stay up
            time.sleep(0.01)

    workers = [threading.Thread(target=load, daemon=True) for _ in range(2)]
    try:
        scaler.ensure_min()
        assert len(launcher.handles) == 2
        for w in workers:
            w.start()
        time.sleep(0.4)  # load is flowing through both replicas
        with faults.installed("autoscale.replica_crash@1"):
            made = scaler.poll_once()  # kill -9 + heal in one tick
        time.sleep(0.4)  # failover window: load keeps flowing
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=10)
        summary = scaler.stop(drain=True)
        rsnap = router.shutdown()
        for h in launcher.handles:
            h.kill()

    actions = [d["action"] for d in made]
    assert actions == ["replica_crash_injected", "replace"]
    replace = made[1]
    assert replace["replace_latency_s"] <= cfg.replace_deadline_s
    assert replace["join_cold_compiles"] == 0
    assert summary["replacements"] == 1
    assert summary["join_cold_compiles"] == 0
    assert summary["spawn_give_ups"] == 0
    # zero errors surfaced beyond the failover window: the ring retried
    # every request that raced the kill onto the surviving replica
    assert errors == [], errors[:10]
    assert rsnap["no_backend_total"] == 0
    rec = journal.read()
    assert rec["event"] == "autoscale_transition"


def test_subprocess_scale_down_is_sigterm_drain(tmp_path):
    """Invariant 22 against a real process: the drained replica flips to
    draining (503 healthz, refuses new scores) and exits on its own —
    no SIGKILL involved."""
    launcher = _launcher_for(tmp_path)
    router = FleetRouter([], port=0, probe_interval_s=0.1,
                         allow_empty=True).start(probe=False)
    cfg = AutoscaleConfig(enabled=True, min_replicas=1, max_replicas=2,
                          poll_interval_s=0.1, burn_high=2.0, burn_low=0.5,
                          up_consecutive=1, down_consecutive=1,
                          cooldown_s=0.1, replace_deadline_s=20.0,
                          spawn_attempts=2, spawn_backoff_s=0.1)
    burn = {"v": 3.0}
    scaler = Autoscaler(cfg, router, launcher,
                        scrape=lambda handle: burn["v"])
    try:
        scaler.ensure_min()
        scaler.poll_once()  # burn high -> scale up to 2
        assert len(scaler.summary()["replicas"]) == 2
        time.sleep(0.2)  # clear the cooldown with the real clock
        burn["v"] = 0.1
        made = scaler.poll_once()  # burn low -> drain the newest
        downs = [d for d in made if d["action"] == "scale_down"]
        assert len(downs) == 1
        victim_name = downs[0]["backend"]
        victim = next(h for h in [scaler._drained[-1]]
                      if h.name == victim_name)
        assert victim.wait(timeout=10) == 0  # clean exit, not a kill
    finally:
        summary = scaler.stop(drain=True)
        router.shutdown()
        # belt and braces: reap anything still alive
        for h in list(scaler._drained):
            h.kill()
    assert summary["replicas"] == []
