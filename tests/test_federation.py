"""Multi-cell federation: spillover routing, cell-level drain, and
cell-kill survival (serve/federation.py).

Pins the ISSUE 20 contract / invariant candidate 32 — losing any single
cell loses no request:

- sticky routing is consistent-hash on ``source_key`` (cache capital
  lives in exactly one cell) and yields only under pressure;
- saturation (``/healthz`` brownout, queue-wait p99, ``/slo`` burn — no
  new probes) demotes a cell to fallback, never evicts it;
- one cell shedding 429 is spillover's cue, not the client's problem:
  the client sees 200 off a sibling; only a FLEET-WIDE shed surfaces,
  as 429 + the max Retry-After any cell advertised, never a 5xx;
- a cell dying at the socket fails over with zero 5xx;
- cell drain is flag-only and ring-exit-FIRST (invariant 6 one level
  up), undrain readmits through the readiness gate;
- the three ``federation.*`` chaos points are armed here (faultcov);
- the PromotionController's brownout gate (ROADMAP direction 1
  residual): refuses to start and pauses mid-roll while any target cell
  reports ``brownout_level > 0``, resumes when clear, every decision
  journaled as ``promotion_transition`` and flight-mirrored
  (invariant 20).

The e2e layer drives REAL ScoreServers (stub-engine idiom of
test_serve.py) behind real FleetRouters behind a live FederationRouter —
probes are manual (``probe_interval_s=60`` + ``probe_once()``) so every
membership transition is deterministic.
"""

import json
import time

import numpy as np
import pytest

pytestmark = pytest.mark.federation


class _StubEngine:
    """Real ScoringEngine over a stub score_fn (test_serve.py idiom)."""

    def __new__(cls, vocabs=(), max_batch=4, prob=0.5):
        from deepdfa_tpu.serve import ScoringEngine, serve_buckets

        def score_fn(batch):
            return np.full(batch.max_graphs, prob, np.float32)

        return ScoringEngine(score_fn, serve_buckets(max_batch),
                             feat_keys=tuple(vocabs))


class _Journal:
    def __init__(self, fail=False):
        self.fail = fail
        self.events: list[dict] = []

    def write(self, **kw):
        if self.fail:
            raise OSError("journal sink down")
        self.events.append(kw)


class _Flight:
    def __init__(self):
        self.events: list[tuple[str, dict]] = []

    def record(self, kind, **kw):
        self.events.append((kind, kw))


@pytest.fixture(scope="module")
def demo():
    """(vocabs, sources) from a tiny hermetic corpus (test_serve.py
    idiom — real frontend + real vocabularies, no training)."""
    from deepdfa_tpu.config import FeatureConfig
    from deepdfa_tpu.cpg.features import add_dependence_edges
    from deepdfa_tpu.cpg.frontend import parse_source
    from deepdfa_tpu.data.codegen import demo_corpus
    from deepdfa_tpu.data.materialize import CorpusBuilder

    rows = demo_corpus(6, seed=0).to_dict("records")
    cpgs = {int(r["id"]): add_dependence_edges(parse_source(r["before"]))
            for r in rows}
    labels = {int(r["id"]): int(r["vul"]) for r in rows}
    _, vocabs = CorpusBuilder(FeatureConfig()).build(
        cpgs, list(cpgs), graph_labels=labels)
    return vocabs, [r["before"] for r in rows]


def _req(port, method, path, body=None, timeout=30):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _post_score(port, source, klass=None, timeout=30):
    payload = {"source": source}
    if klass is not None:
        payload["class"] = klass
    status, headers, data = _req(port, "POST", "/score",
                                 json.dumps(payload), timeout)
    return status, headers, json.loads(data)


def _uniq(base: str, i: int) -> str:
    return f"{base}\nint fed_uniq_{i}(int a) {{\n  return a + {i};\n}}\n"


# ---------------------------------------------------------------------------
# config


def test_federation_config_validation():
    from deepdfa_tpu.config import FederationConfig

    with pytest.raises(ValueError, match="cells"):
        FederationConfig(cells=("nocolon",))
    with pytest.raises(ValueError, match="vnodes"):
        FederationConfig(vnodes=0)
    with pytest.raises(ValueError, match="probe_interval_s"):
        FederationConfig(probe_interval_s=0.0)
    with pytest.raises(ValueError, match="spill_brownout_level"):
        FederationConfig(spill_brownout_level=0)
    with pytest.raises(ValueError, match="spill_brownout_level"):
        FederationConfig(spill_brownout_level=4)
    with pytest.raises(ValueError, match="spill_queue_wait_p99_ms"):
        FederationConfig(spill_queue_wait_p99_ms=0.0)
    with pytest.raises(ValueError, match="spill_burn_high"):
        FederationConfig(spill_burn_high=-1.0)
    with pytest.raises(ValueError, match="drain_deadline_s"):
        FederationConfig(drain_deadline_s=0.0)
    with pytest.raises(ValueError, match="retry_after_floor_s"):
        FederationConfig(retry_after_floor_s=0)


def test_federation_config_dotted_overrides_and_roundtrip(tmp_path):
    from deepdfa_tpu.config import FederationConfig, load_config, to_json

    cfg = load_config(overrides={
        "serve.federation.enabled": True,
        "serve.federation.vnodes": 8,
        "serve.federation.spill_brownout_level": 2,
        "serve.federation.spill_burn_high": 3.0,
        "serve.federation.drain_deadline_s": 5.0})
    fc = cfg.serve.federation
    assert isinstance(fc, FederationConfig)
    assert (fc.enabled, fc.vnodes, fc.spill_brownout_level,
            fc.spill_burn_high, fc.drain_deadline_s) == (True, 8, 2, 3.0,
                                                         5.0)
    path = tmp_path / "cfg.json"
    path.write_text(to_json(cfg))
    assert load_config(path).serve.federation == fc
    with pytest.raises(ValueError, match="vnodes"):
        load_config(overrides={"serve.federation.vnodes": 0})


def test_federation_config_cells_tuple_coercion_survives_json(tmp_path):
    """JSON round-trips tuples as lists; __post_init__ re-coerces so
    equality (and hashing of the frozen config) holds."""
    from deepdfa_tpu.config import FederationConfig, load_config, to_json

    cfg = load_config(overrides={})
    object.__setattr__(cfg.serve, "federation",
                       FederationConfig(cells=("127.0.0.1:9001",
                                               "127.0.0.1:9002")))
    path = tmp_path / "cfg.json"
    path.write_text(to_json(cfg))
    back = load_config(path).serve.federation
    assert back.cells == ("127.0.0.1:9001", "127.0.0.1:9002")
    assert isinstance(back.cells, tuple)


# ---------------------------------------------------------------------------
# ledger directions + SLO specs (satellite 5 wiring)


def test_ledger_federation_series_lower_is_better():
    from deepdfa_tpu.obs.ledger import EXPLICIT_SERIES

    for series in ("cell_kill_recovery_s", "spillover_errors",
                   "fleetwide_5xx"):
        assert EXPLICIT_SERIES[("federation", series)] is True, series


def test_federation_slo_specs():
    from deepdfa_tpu.obs import federation_specs

    specs = {s.name: s for s in federation_specs(p99_ms=1500.0)}
    assert specs["availability"].kind == "ratio"
    assert specs["availability"].bad == "fleetwide_5xx_total"
    assert specs["latency_p99"].target == 1500.0
    assert specs["spillover_errors"].target == 0.0


# ---------------------------------------------------------------------------
# routing plan (no sockets: cells injected, states set by hand)


def _offline_fed(n=3, **cfg_kw):
    """A FederationRouter that never starts its HTTP server thread or
    probes — pure routing-table unit surface."""
    from deepdfa_tpu.config import FederationConfig
    from deepdfa_tpu.serve import FederationRouter

    fed = FederationRouter(
        cells=[f"127.0.0.1:{9400 + i}" for i in range(n)],
        cfg=FederationConfig(**cfg_kw))
    for c in fed.cells.values():
        fed._mark(c, "ready", {})
    return fed


def test_plan_route_is_sticky_and_consistent():
    from deepdfa_tpu.pipeline import source_key

    fed = _offline_fed(3)
    try:
        keys = [source_key(f"int f{i}(int x) {{ return {i}; }}")
                for i in range(32)]
        first = {k: fed.plan_route(k)[0] for k in keys}
        for _ in range(3):
            assert {k: fed.plan_route(k)[0] for k in keys} == first
        # the keyspace actually spreads over the cells
        assert len(set(first.values())) == 3
        # every plan tries every ready cell exactly once
        for k in keys:
            assert sorted(fed.plan_route(k)) == sorted(fed.cells)
    finally:
        fed.httpd.server_close()


def test_plan_route_demotes_saturated_sticky_owner():
    """Saturation spillover is a preference, not a refusal: the saturated
    owner drops to fallback (still in the plan), and the least-burned
    healthy cell leads."""
    fed = _offline_fed(3, spill_brownout_level=1)
    try:
        names = sorted(fed.cells)
        key = next(k for k in (f"k{i}" for i in range(200))
                   if fed.ring.route(k) == names[0])
        owner, others = names[0], [n for n in names if n != names[0]]
        fed.cells[owner].health = {"brownout_level": 2}
        fed.cells[others[0]].burn = 0.9
        fed.cells[others[1]].burn = 0.1
        plan = fed.plan_route(key)
        assert plan[0] == others[1]          # least burned leads
        assert plan[-1] == owner             # owner demoted, never dropped
        assert fed.saturated(fed.cells[owner])
        # recovery: the owner's next clean probe restores stickiness
        fed.cells[owner].health = {"brownout_level": 0}
        assert fed.plan_route(key)[0] == owner
    finally:
        fed.httpd.server_close()


def test_saturation_signals_are_the_probed_truth():
    """All three saturation cues come from signals the cell already
    exposes — brownout level, frontend queue-wait p99, SLO burn."""
    fed = _offline_fed(1, spill_brownout_level=2,
                       spill_queue_wait_p99_ms=100.0, spill_burn_high=1.5)
    try:
        (c,) = fed.cells.values()
        assert not fed.saturated(c)
        c.health = {"brownout_level": 1}
        assert not fed.saturated(c)          # below the watermark
        c.health = {"brownout_level": 2}
        assert fed.saturated(c)
        c.health = {"frontend_queue_wait_p99_ms": 250.0}
        assert fed.saturated(c)
        c.health = {}
        c.burn = 1.6
        assert fed.saturated(c)
    finally:
        fed.httpd.server_close()


def test_cell_parse():
    from deepdfa_tpu.serve import Cell

    c = Cell.parse("10.0.0.7:8900")
    assert (c.host, c.port, c.name, c.state) == ("10.0.0.7", 8900,
                                                 "10.0.0.7:8900", "pending")


# ---------------------------------------------------------------------------
# FleetRouter cell-facing hooks (PR 20 router.py satellites)


def _cell_server(demo, **adm_kw):
    from deepdfa_tpu.config import AdmissionConfig, ServeConfig
    from deepdfa_tpu.serve import ScoreServer

    vocabs, _ = demo
    admission = None
    if adm_kw:
        defaults = dict(enabled=True, poll_interval_s=60.0)
        defaults.update(adm_kw)
        admission = AdmissionConfig(**defaults)
    kw = {"admission": admission} if admission else {}
    return ScoreServer(_StubEngine(vocabs), vocabs,
                       ServeConfig(port=0, max_wait_ms=2.0, **kw))


def _cell(demo, **adm_kw):
    """One complete cell: a replica behind its own FleetRouter, probes
    manual."""
    from deepdfa_tpu.serve import FleetRouter

    srv = _cell_server(demo, **adm_kw)
    srv.warmup()  # FleetRouter's readiness gate only admits warm replicas
    srv.start()
    router = FleetRouter([f"127.0.0.1:{srv.port}"], port=0,
                         probe_interval_s=60.0)
    router.probe_once()
    router.start(probe=False)
    return srv, router


def test_cell_router_healthz_aggregates_brownout_and_queue_wait(demo):
    from deepdfa_tpu.resilience import faults

    srv, router = _cell(demo, brownout=True)
    try:
        _, _, data = _req(router.port, "GET", "/healthz")
        body = json.loads(data)
        assert body["warm"] is True
        assert body["brownout_level"] == 0
        assert "frontend_queue_wait_p99_ms" in body
        with faults.installed("admission.brownout_force@1"):
            srv.brownout.poll_once()
        router.probe_once()
        _, _, data = _req(router.port, "GET", "/healthz")
        assert json.loads(data)["brownout_level"] == 1
    finally:
        router.shutdown()
        srv.shutdown()


def test_cell_router_propagates_retry_after_header(demo):
    """A shed crossing the cell router keeps its deterministic
    Retry-After — the federation's fleet-wide 429 depends on it."""
    vocabs, sources = demo
    srv, router = _cell(demo, batch_rate=0.25, batch_burst=1.0)
    try:
        assert _post_score(router.port, _uniq(sources[0], 0),
                           klass="batch")[0] == 200
        status, headers, body = _post_score(router.port,
                                            _uniq(sources[1], 1),
                                            klass="batch")
        assert status == 429
        assert headers["Retry-After"] == str(int(body["retry_after_s"]))
    finally:
        router.shutdown()
        srv.shutdown()


def test_cell_router_admin_drain_roundtrip(demo):
    """POST /admin/drain is the federation's cell-drain back door:
    flag-only, reversible via undrain (invariant 6/22 — SIGTERM stop is
    the irreversible cousin)."""
    srv, router = _cell(demo)
    try:
        status, _, data = _req(router.port, "POST", "/admin/drain",
                               json.dumps({"action": "drain"}))
        assert status == 200 and json.loads(data)["draining"] is True
        code, _, data = _req(router.port, "GET", "/healthz")
        assert code == 503 and json.loads(data)["draining"] is True
        status, _, data = _req(router.port, "POST", "/admin/drain",
                               json.dumps({"action": "undrain"}))
        assert status == 200 and json.loads(data)["draining"] is False
        code, _, _ = _req(router.port, "GET", "/healthz")
        assert code == 200
    finally:
        router.shutdown()
        srv.shutdown()


# ---------------------------------------------------------------------------
# e2e: real ScoreServers behind real FleetRouters behind the federation


class _Fed:
    """Two live cells + a FederationRouter, all probes manual."""

    def __init__(self, demo, cell_kwargs=({}, {}), **cfg_kw):
        from deepdfa_tpu.config import FederationConfig
        from deepdfa_tpu.serve import FederationRouter

        self.cells = [_cell(demo, **kw) for kw in cell_kwargs]
        self._salt = 0
        cfg_kw.setdefault("probe_interval_s", 60.0)
        self.fed = FederationRouter(
            cells=[f"127.0.0.1:{r.port}" for _, r in self.cells],
            cfg=FederationConfig(**cfg_kw))
        self.fed.probe_once()
        self.fed.start(probe=False)

    def name(self, i):
        return f"127.0.0.1:{self.cells[i][1].port}"

    def sticky_source(self, sources, cell_index):
        """A FRESH source whose ring owner is cell ``cell_index`` —
        fresh so repeat calls never alias into a replica cache hit."""
        from deepdfa_tpu.pipeline import source_key

        want = self.name(cell_index)
        for _ in range(500):
            self._salt += 1
            src = _uniq(sources[self._salt % len(sources)],
                        10_000 + self._salt)
            if self.fed.ring.route(source_key(src)) == want:
                return src
        raise AssertionError(f"no source sticky to {want}")

    def close(self):
        self.fed.shutdown()
        for srv, router in self.cells:
            router.shutdown()
            srv.shutdown()


def test_e2e_sticky_serving_and_cell_header(demo):
    _, sources = demo
    f = _Fed(demo)
    try:
        assert sorted(f.fed.ring.nodes) == sorted([f.name(0), f.name(1)])
        src = f.sticky_source(sources, 0)
        for _ in range(3):
            status, headers, body = _post_score(f.fed.port, src)
            assert status == 200 and "results" in body
            assert headers["X-DeepDFA-Cell"] == f.name(0)
            assert headers["X-DeepDFA-Spillover"] == "false"
    finally:
        f.close()


def test_e2e_single_cell_shed_spills_to_sibling(demo):
    """Cross-cell shed semantics, half 1: ONE cell shedding 429 is the
    federation's cue to spill — the client sees 200 off the sibling,
    marked as spillover."""
    _, sources = demo
    # cell 0 has a starved batch budget; cell 1 is generous
    f = _Fed(demo, cell_kwargs=({"batch_rate": 0.01, "batch_burst": 1.0},
                                {"batch_rate": 100.0,
                                 "batch_burst": 100.0}))
    try:
        # burn cell 0's only batch token with a request sticky to it
        s0 = f.sticky_source(sources, 0)
        assert _post_score(f.fed.port, s0, klass="batch")[0] == 200
        # next sticky-to-0 batch request: 0 sheds, 1 serves -> client 200
        s1 = f.sticky_source(sources, 0)
        status, headers, _ = _post_score(f.fed.port, s1, klass="batch")
        assert status == 200
        assert headers["X-DeepDFA-Cell"] == f.name(1)
        assert headers["X-DeepDFA-Spillover"] == "true"
        snap = f.fed.metrics.snapshot()
        assert snap["spillover_total"] >= 1
        assert snap["fleetwide_shed_total"] == 0
        assert snap["fleetwide_5xx_total"] == 0
    finally:
        f.close()


def test_e2e_fleetwide_shed_is_429_with_max_retry_after(demo):
    """Cross-cell shed semantics, half 2: only a FLEET-WIDE shed reaches
    the client — 429 + the max Retry-After any cell advertised, and
    NEVER a 5xx (invariant 30 one level up)."""
    _, sources = demo
    f = _Fed(demo, cell_kwargs=({"batch_rate": 0.01, "batch_burst": 1.0},
                                {"batch_rate": 0.01, "batch_burst": 1.0}))
    try:
        # spend both cells' single batch token
        assert _post_score(f.fed.port, f.sticky_source(sources, 0),
                           klass="batch")[0] == 200
        assert _post_score(f.fed.port, f.sticky_source(sources, 1),
                           klass="batch")[0] == 200
        status, headers, body = _post_score(
            f.fed.port, f.sticky_source(sources, 0), klass="batch")
        assert status == 429
        assert int(headers["Retry-After"]) == int(body["retry_after_s"])
        assert int(headers["Retry-After"]) >= 1
        snap = f.fed.metrics.snapshot()
        assert snap["fleetwide_shed_total"] == 1
        assert snap["fleetwide_5xx_total"] == 0
    finally:
        f.close()


def test_e2e_cell_death_fails_over_without_5xx(demo):
    """Invariant candidate 32: a cell dying at the socket mid-traffic
    costs its cache shard, never a request."""
    _, sources = demo
    f = _Fed(demo)
    try:
        victim = 0
        src = f.sticky_source(sources, victim)
        assert _post_score(f.fed.port, src)[0] == 200
        # kill the whole cell: replica AND its router
        srv, router = f.cells[victim]
        srv.httpd.shutdown()
        srv.httpd.server_close()
        router.httpd.shutdown()
        router.httpd.server_close()
        # the NEXT request for its keyspace fails over in-line (the probe
        # has not run: the dead cell is still in the ring)
        status, headers, _ = _post_score(f.fed.port, src)
        assert status == 200
        assert headers["X-DeepDFA-Cell"] == f.name(1)
        assert f.fed.cells[f.name(victim)].state == "down"
        snap = f.fed.metrics.snapshot()
        assert snap["fleetwide_5xx_total"] == 0
        # after the probe confirms death the keyspace is reassigned
        f.fed.probe_once()
        assert f.name(victim) not in f.fed.ring.nodes
        assert _post_score(f.fed.port, src)[0] == 200
    finally:
        f.close()


def test_e2e_cell_drain_is_flag_only_and_reversible(demo):
    """Cell-level drain through POST /admin/cells: ring exit FIRST, the
    cell's own router gets the flag, in-flight forwards finish; undrain
    readmits through the readiness gate."""
    _, sources = demo
    f = _Fed(demo, drain_deadline_s=2.0)
    try:
        target = f.name(0)
        src = f.sticky_source(sources, 0)  # owned by the soon-drained cell
        status, _, data = _req(f.fed.port, "POST", "/admin/cells",
                               json.dumps({"action": "drain",
                                           "cell": target}))
        assert status == 200
        out = json.loads(data)
        assert out["inflight_at_flag"] == 0
        assert target not in f.fed.ring.nodes
        assert f.fed.cells[target].state == "draining"
        # the cell's own router took the flag (503 + draining healthz)
        code, _, data = _req(f.cells[0][1].port, "GET", "/healthz")
        assert code == 503 and json.loads(data)["draining"] is True
        # traffic sticky to the drained cell is served by the sibling
        status, headers, _ = _post_score(f.fed.port, src)
        assert status == 200 and headers["X-DeepDFA-Cell"] == f.name(1)
        # undrain readmits via the same readiness gate as a new member
        status, _, _ = _req(f.fed.port, "POST", "/admin/cells",
                            json.dumps({"action": "undrain",
                                        "cell": target}))
        assert status == 200
        assert f.fed.cells[target].state == "ready"
        assert target in f.fed.ring.nodes
    finally:
        f.close()


def test_e2e_add_remove_cell_membership_is_readiness_gated(demo):
    f = _Fed(demo)
    try:
        # an unreachable cell registers but never enters the ring
        ghost = f.fed.add_cell("127.0.0.1:1")
        assert ghost.state == "down"
        assert "127.0.0.1:1" not in f.fed.ring.nodes
        _, _, data = _req(f.fed.port, "GET", "/admin/cells")
        table = json.loads(data)
        assert table["cells"]["127.0.0.1:1"]["state"] == "down"
        assert f.fed.remove_cell("127.0.0.1:1") is True
        assert f.fed.remove_cell("127.0.0.1:1") is False
    finally:
        f.close()


def test_e2e_federation_drain_is_explicit_backpressure(demo):
    _, sources = demo
    f = _Fed(demo)
    try:
        f.fed.request_stop()
        status, headers, body = _post_score(f.fed.port,
                                            _uniq(sources[0], 77))
        assert status == 429
        assert headers["Retry-After"] == str(int(body["retry_after_s"]))
    finally:
        f.close()


def test_e2e_bad_request_is_400_not_routed(demo):
    f = _Fed(demo)
    try:
        assert _req(f.fed.port, "POST", "/score", "{not json")[0] == 400
        assert _req(f.fed.port, "POST", "/score",
                    json.dumps({"nope": 1}))[0] == 400
        assert f.fed.metrics.snapshot()["forwarded_total"] == {}
    finally:
        f.close()


# ---------------------------------------------------------------------------
# chaos: the three federation.* points (faultcov arms them here)


@pytest.mark.faults
def test_chaos_cell_kill_fires_kill_hook_and_survivors_serve(demo):
    """``federation.cell_kill``: the probe loop SIGKILLs one whole cell
    through the installed kill_hook; the survivors absorb its keyspace
    with zero client-visible 5xx."""
    from deepdfa_tpu.config import FederationConfig
    from deepdfa_tpu.resilience import faults
    from deepdfa_tpu.serve import FederationRouter

    _, sources = demo
    cells = [_cell(demo) for _ in range(2)]
    killed = []

    def kill_hook(name):
        killed.append(name)
        for srv, router in cells:
            if f"127.0.0.1:{router.port}" == name:
                srv.httpd.shutdown()
                srv.httpd.server_close()
                router.httpd.shutdown()
                router.httpd.server_close()

    fed = FederationRouter(
        cells=[f"127.0.0.1:{r.port}" for _, r in cells],
        cfg=FederationConfig(probe_interval_s=60.0), kill_hook=kill_hook)
    fed.probe_once()
    fed.start(probe=False)
    try:
        with faults.installed("federation.cell_kill@1"):
            fed.probe_once()
        assert len(killed) == 1
        assert fed.cells[killed[0]].state == "down"
        for i in range(6):
            status, headers, _ = _post_score(fed.port,
                                             _uniq(sources[i % 6], i))
            assert status == 200
            assert headers["X-DeepDFA-Cell"] != killed[0]
        assert fed.metrics.snapshot()["fleetwide_5xx_total"] == 0
    finally:
        fed.shutdown()
        for srv, router in cells:
            try:
                router.shutdown()
                srv.shutdown()
            except Exception:  # noqa: BLE001 — the killed cell is gone
                pass


@pytest.mark.faults
def test_chaos_probe_partition_marks_down_then_heals(demo):
    """``federation.probe_partition``: one partitioned probe reads as a
    socket failure — the cell leaves the ring, and the next CLEAN probe
    readmits it (no operator action)."""
    from deepdfa_tpu.resilience import faults

    f = _Fed(demo)
    try:
        target = f.name(0)
        with faults.installed("federation.probe_partition@1"):
            f.fed.probe_once()
        # @1 fires on the first probed cell; exactly one cell went down
        down = [c.name for c in f.fed.cells.values() if c.state == "down"]
        assert len(down) == 1
        assert down[0] not in f.fed.ring.nodes
        f.fed.probe_once()  # clean probe: rejoins through readiness
        assert f.fed.cells[down[0]].state == "ready"
        assert down[0] in f.fed.ring.nodes
        assert target in f.fed.ring.nodes
    finally:
        f.close()


@pytest.mark.faults
def test_chaos_spillover_drop_is_counted_and_retried(demo):
    """``federation.spillover_drop``: a spilled forward dies on the wire
    — counted as a spillover error, retried on the remaining plan, and
    the client NEVER sees a 5xx."""
    from deepdfa_tpu.resilience import faults

    _, sources = demo
    f = _Fed(demo, cell_kwargs=({"batch_rate": 0.01, "batch_burst": 1.0},
                                {"batch_rate": 100.0,
                                 "batch_burst": 100.0}))
    try:
        s0 = f.sticky_source(sources, 0)
        assert _post_score(f.fed.port, s0, klass="batch")[0] == 200
        with faults.installed("federation.spillover_drop@1"):
            status, _, _ = _post_score(f.fed.port,
                                       f.sticky_source(sources, 0),
                                       klass="batch")
        # the only remaining cell after the dropped spill is the shedding
        # owner -> honest 429; never a 5xx either way
        assert status in (200, 429)
        snap = f.fed.metrics.snapshot()
        assert snap["spillover_errors_total"] == 1
        assert snap["fleetwide_5xx_total"] == 0
    finally:
        f.close()


# ---------------------------------------------------------------------------
# promotion brownout gate (satellite 1 — fakes idiom of test_continual.py)


class _Ring:
    def __init__(self):
        self.states: dict[str, str] = {}
        self.revs: dict[str, str] = {}
        self.sizes: list[int] = []

    def add_backend(self, spec):
        self.states[str(spec)] = "ready"
        self.sizes.append(len(self.states))

    def remove_backend(self, name):
        ok = self.states.pop(name, None) is not None
        self.sizes.append(len(self.states))
        return ok

    def probe_once(self):
        return dict(self.states)


class _RevLauncher:
    def __init__(self, ring, rev, base_port):
        self.ring = ring
        self.rev = rev
        self.base = base_port
        self.count = 0
        self.handles = []

    def spawn(self):
        self.count += 1

        class _H:
            pass

        h = _H()
        h.name = f"127.0.0.1:{self.base + self.count}"
        h.join_cold_compiles = 0
        h.drain = lambda: None
        self.ring.revs[h.name] = self.rev
        self.handles.append(h)
        return h


def _brownout_controller(tmp_path, levels, *, targets=("cellA:1",),
                         pause_timeout_s=60.0, journal=None, flight=None,
                         n_prior=1):
    """A PromotionController over fakes whose brownout probe replays the
    scripted ``levels`` sequence (then 0 forever)."""
    from deepdfa_tpu.continual import PromotionController
    from deepdfa_tpu.obs.slo import write_alerts_artifact

    ring = _Ring()
    prior = _RevLauncher(ring, "revA", 9100)
    cand = _RevLauncher(ring, "revB", 9200)
    for _ in range(n_prior):
        ring.add_backend(prior.spawn().name)
    ring.sizes.clear()  # membership changes from here on are the roll's
    seq = list(levels)

    def probe(name):
        return seq.pop(0) if seq else 0

    alerts = write_alerts_artifact(tmp_path / "alerts.json", [])
    t = [0.0]  # fake clock: sleep advances it, so every poll is scripted
    pc = PromotionController(
        ring, cand, prior, candidate_rev="revB", prior_rev="revA",
        alerts_path=alerts, journal=journal, flight=flight,
        rev_probe=ring.revs.get, drift_probe=lambda name: "",
        brownout_probe=probe, brownout_targets=targets,
        brownout_pause_timeout_s=pause_timeout_s,
        drift_settle_polls=2, poll_interval_s=0.01, join_timeout_s=5.0,
        clock=lambda: t[0],
        sleep=lambda s: t.__setitem__(0, t[0] + s))
    return pc, ring, cand, prior


_OK_SHADOW = {"schema": 1, "pass": True}


def test_promotion_refused_while_target_cell_browned_out(tmp_path):
    """The gate refuses to START a roll into any target cell reporting
    brownout_level > 0 — journaled as promotion_transition and
    flight-mirrored (invariant 20)."""
    journal, flight = _Journal(), _Flight()
    pc, ring, cand, _ = _brownout_controller(
        tmp_path, levels=[2], targets=("cellA:1",), journal=journal,
        flight=flight)
    out = pc.promote(_OK_SHADOW)
    assert out["completed"] is False
    refusal = out["decisions"][0]
    assert refusal["action"] == "refused" and refusal["gate"] == "brownout"
    assert refusal["brownout_level"] == 2
    assert refusal["target"] == "cellA:1"
    assert cand.count == 0 and ring.sizes == []  # nothing moved
    assert any(e.get("event") == "promotion_transition"
               and e.get("action") == "refused" for e in journal.events)
    assert any(k == "promotion.refused" for k, _ in flight.events)


def test_promotion_gate_order_brownout_before_shadow(tmp_path):
    """Veto → brownout → shadow: a browned-out target refuses even when
    the shadow report would also fail (capacity first, correctness
    second)."""
    pc, *_ = _brownout_controller(tmp_path, levels=[1])
    refusal = pc.check_gates({"schema": 1, "pass": False})
    assert refusal["gate"] == "brownout"
    pc2, *_ = _brownout_controller(tmp_path, levels=[0])
    refusal2 = pc2.check_gates({"schema": 1, "pass": False})
    assert refusal2["gate"] == "shadow"


def test_promotion_pauses_midroll_and_resumes_when_clear(tmp_path):
    """Mid-roll brownout: the roll HOLDS before the next membership
    change, resumes when the cells recover, and completes — both
    transitions journaled."""
    journal, flight = _Journal(), _Flight()
    # gate pass (0), first hold-point clear (0), second hold-point
    # browned out twice (3, 1) then clear -> resume and finish
    pc, ring, cand, prior = _brownout_controller(
        tmp_path, levels=[0, 0, 3, 1, 0], n_prior=2, journal=journal,
        flight=flight)
    out = pc.promote(_OK_SHADOW)
    assert out["completed"] is True
    actions = [d["action"] for d in out["decisions"]]
    assert "paused" in actions and "resumed" in actions
    assert actions.index("paused") < actions.index("resumed")
    paused = next(d for d in out["decisions"] if d["action"] == "paused")
    assert paused["gate"] == "brownout" and paused["brownout_level"] == 3
    assert min(ring.sizes) >= 2  # the pause never shrank the ring
    assert any(k == "promotion.paused" for k, _ in flight.events)
    assert any(k == "promotion.resumed" for k, _ in flight.events)


def test_promotion_pause_timeout_rolls_back(tmp_path):
    """A pause that outlives brownout_pause_timeout_s fails the roll —
    which rolls BACK (restoring known-good capacity during a brownout is
    correct; deploying into it is not). The rollback itself does not
    pause."""
    pc, ring, cand, prior = _brownout_controller(
        tmp_path, levels=[0] + [3] * 10_000, n_prior=1,
        pause_timeout_s=0.02)
    out = pc.promote(_OK_SHADOW)
    assert out["completed"] is False and out["rolled_back"] is True
    actions = [d["action"] for d in out["decisions"]]
    assert "paused" in actions and "rollout_failed" in actions
    assert "resumed" not in actions
    assert out["ring_by_rev"] == {"revA": [prior.handles[-1].name]}


def test_promotion_brownout_gate_off_without_targets(tmp_path):
    """No targets configured -> the gate is off (pre-federation deploys
    keep their exact behaviour); a callable target list is re-read every
    check."""
    from deepdfa_tpu.continual import PromotionController
    from deepdfa_tpu.obs.slo import write_alerts_artifact

    ring = _Ring()
    prior = _RevLauncher(ring, "revA", 9100)
    cand = _RevLauncher(ring, "revB", 9200)
    ring.add_backend(prior.spawn().name)
    alerts = write_alerts_artifact(tmp_path / "alerts.json", [])
    pc = PromotionController(
        ring, cand, prior, candidate_rev="revB", prior_rev="revA",
        alerts_path=alerts, rev_probe=ring.revs.get,
        drift_probe=lambda name: "", brownout_probe=lambda name: 3,
        brownout_targets=None, drift_settle_polls=1,
        poll_interval_s=0.01, join_timeout_s=5.0, sleep=lambda s: None)
    assert pc.check_gates(_OK_SHADOW) is None  # level 3 yet no gate

    calls = []
    pc2, *_ = _brownout_controller(tmp_path, levels=[0])
    pc2._brownout_targets = lambda: calls.append(1) or ("cellA:1",)
    assert pc2.check_gates(_OK_SHADOW) is None
    assert calls  # the callable was consulted


# ---------------------------------------------------------------------------
# staleness honesty: the burn signal an idle replica reports (the
# federation's saturation deadlock regression test)


def test_idle_replica_burn_decays_not_freezes(demo):
    """A replica that served slow traffic and then went IDLE must stop
    reporting the stale latency p99 as live burn — otherwise a saturated
    cell demoted by spillover can never read healthy again and the
    federation deadlocks (the heal cell of the --federation bench)."""
    from deepdfa_tpu.config import ObsConfig, ServeConfig
    from deepdfa_tpu.serve import ScoreServer

    vocabs, _ = demo
    srv = ScoreServer(
        _StubEngine(vocabs), vocabs,
        ServeConfig(port=0, max_wait_ms=2.0,
                    obs=ObsConfig(slo_p99_ms=0.000001,
                                  slo_fast_window_s=0.2,
                                  slo_slow_window_s=0.4)))
    srv.start()
    try:
        assert _post_score(srv.port, "int f(int x) { return x; }")[0] == 200
        # the handler counts a response after it has sent it: the client
        # can be here first, so look until the count is in
        deadline = time.monotonic() + 5.0
        burn_hot = srv.slo.worst_fast_burn() or srv._observe_fast_burn()
        while not burn_hot and time.monotonic() < deadline:
            time.sleep(0.01)
            burn_hot = srv._observe_fast_burn()
        assert burn_hot is not None and burn_hot > 1.0  # absurd target
        time.sleep(0.5)  # a full fast window with zero traffic
        burn_idle = srv._observe_fast_burn()
        assert (burn_idle or 0.0) < 1.0  # decayed, not frozen
    finally:
        srv.shutdown()


def test_slo_gauge_burn_zero_when_window_empties():
    from deepdfa_tpu.obs import SLOEngine, SLOSpec

    t = [1000.0]
    eng = SLOEngine((SLOSpec("latency_p99", "max", 100.0, value="p99"),),
                    fast_window_s=2.0, slow_window_s=10.0,
                    clock=lambda: t[0])
    eng.observe({"p99": 500.0})
    assert eng.worst_fast_burn() == pytest.approx(5.0)
    t[0] += 5.0  # sample ages past the fast window; none replaces it
    eng.observe({"p99": None})
    statuses = {s["slo"]: s for s in eng.statuses()}
    assert statuses["latency_p99"]["burn_fast"] == 0.0  # no traffic,
    # no violation — never the frozen last reading
