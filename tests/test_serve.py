"""Online inference service: micro-batching, content-addressed caching,
metrics, and the HTTP surface's failure domains. Everything here runs on
a STUB engine (the live-model and artifact paths are covered by
test_serving.py and scripts/bench_serving.py) — these tests pin the
serving *machinery*: batch formation, backpressure, per-request failure
isolation, and graceful drain."""

import json
import signal
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

pytestmark = pytest.mark.serve


def _chain(n, keys=("_ABS_DATAFLOW",)):
    from deepdfa_tpu.data.graphs import Graph

    feats = {k: np.zeros(n, np.int32) for k in keys}
    return Graph(senders=np.arange(n - 1, dtype=np.int32),
                 receivers=np.arange(1, n, dtype=np.int32),
                 node_feats=feats).with_self_loops()


class _StubEngine:
    """Real ScoringEngine over a recording stub score_fn."""

    def __new__(cls, vocabs=(), max_batch=4, prob=0.25, delay_s=0.0,
                fail_first=False):
        from deepdfa_tpu.serve import ScoringEngine, serve_buckets

        record = []
        state = {"fail": fail_first}

        def score_fn(batch):
            if state["fail"]:
                state["fail"] = False
                raise RuntimeError("stub engine failure")
            if delay_s:
                time.sleep(delay_s)
            record.append(int(np.sum(np.asarray(batch.graph_mask))))
            return np.full(batch.max_graphs, prob, np.float32)

        eng = ScoringEngine(score_fn, serve_buckets(max_batch),
                            feat_keys=tuple(vocabs))
        eng.record = record
        return eng


@pytest.fixture(scope="module")
def demo():
    """(vocabs, sources) from a tiny hermetic corpus — real frontend +
    real vocabularies, no training."""
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


# ---------------------------------------------------------------------------
# cache


def test_cache_hit_counters_and_two_layers():
    from deepdfa_tpu.serve import ScanCache

    c = ScanCache(capacity=8)
    assert c.lookup("k") is None  # miss
    c.store("k", encoded=["enc"])
    e = c.lookup("k")  # encode-level hit: frontend skipped, scoring re-runs
    assert e.encoded == ["enc"] and e.results is None
    c.store("k", results=[{"p": 1}])
    e = c.lookup("k")  # full hit
    assert e.results == [{"p": 1}] and e.encoded == ["enc"]
    s = c.stats()
    assert (s["hits"], s["encode_hits"], s["misses"]) == (1, 1, 1)
    assert s["hit_rate"] == pytest.approx(1 / 3)


def test_cache_lru_eviction_order():
    from deepdfa_tpu.serve import ScanCache

    c = ScanCache(capacity=2)
    c.store("a", results=[1])
    c.store("b", results=[2])
    assert c.lookup("a") is not None  # touch a → b is now LRU
    c.store("c", results=[3])
    assert c.lookup("b") is None and c.lookup("a") is not None
    assert c.stats()["evictions"] == 1


def test_cache_capacity_zero_disables():
    from deepdfa_tpu.serve import ScanCache

    c = ScanCache(capacity=0)
    c.store("k", results=[1])
    assert c.lookup("k") is None and len(c) == 0


def test_source_key_whitespace_invariant():
    from deepdfa_tpu.pipeline import source_key

    a = "int f(int x) {\n  return x;\n}\n"
    b = "int f(int x) {   \r\n\n  return x;\n}"  # CRLF, trailing WS, blank
    assert source_key(a) == source_key(b)
    assert source_key(a) != source_key(a.replace("x", "y"))


# ---------------------------------------------------------------------------
# engine routing


def test_bucket_ladder_routing_and_oversize():
    from deepdfa_tpu.serve import OversizeGraphError

    eng = _StubEngine(max_batch=8)
    assert [b.graph_nodes for b in eng.buckets] == [126, 1022, 4094]
    assert eng.assign_bucket(_chain(10)).graph_nodes == 126
    assert eng.assign_bucket(_chain(500)).graph_nodes == 1022
    assert eng.assign_bucket(_chain(2000)).graph_nodes == 4094
    with pytest.raises(OversizeGraphError, match="exceeds the largest"):
        eng.assign_bucket(_chain(5000))


def test_engine_warmup_compiles_every_bucket():
    eng = _StubEngine(max_batch=4)
    report = eng.warmup()
    assert report["buckets"] == 3
    assert (report["hits"], report["misses"]) == (0, 3)  # no store: all cold
    assert len(eng.record) == 3  # one compile call per bucket shape
    assert eng.warm_buckets == [126, 1022, 4094]


@pytest.mark.faults
def test_engine_warmup_does_not_consume_armed_fault():
    """serve.engine_raises@1 must poison the first CLIENT request, not
    kill the server during startup warmup (found by driving the CLI with
    the chaos spec armed)."""
    from deepdfa_tpu.resilience import faults

    eng = _StubEngine(max_batch=4)
    with faults.installed("serve.engine_raises@1"):
        assert eng.warmup()["buckets"] == 3  # no InjectedFault
        with pytest.raises(faults.InjectedFault):
            eng.score([_chain(5)], eng.buckets[0])


# ---------------------------------------------------------------------------
# micro-batcher


def test_batcher_coalesces_window_into_one_dispatch():
    from deepdfa_tpu.serve import MicroBatcher

    eng = _StubEngine(max_batch=4)
    b = MicroBatcher(eng, max_batch=4, max_wait_ms=200.0).start()
    futs = [b.submit(_chain(5)) for _ in range(4)]
    assert [f.result(timeout=10) for f in futs] == [0.25] * 4
    # size trigger fired before the 200ms deadline: ONE padded dispatch
    assert eng.n_dispatches == 1 and eng.record == [4]
    b.stop()


def test_batcher_deadline_flushes_partial_window():
    from deepdfa_tpu.serve import MicroBatcher

    eng = _StubEngine(max_batch=16)
    b = MicroBatcher(eng, max_batch=16, max_wait_ms=20.0).start()
    fut = b.submit(_chain(5))
    assert fut.result(timeout=10) == 0.25  # dispatched alone at deadline
    assert eng.record == [1]
    b.stop()


def test_batcher_backpressure_bounded_queue():
    from deepdfa_tpu.serve import MicroBatcher, QueueFullError

    eng = _StubEngine()
    b = MicroBatcher(eng, max_queue=2)  # never started: queue can't drain
    b.submit(_chain(5))
    b.submit(_chain(5))
    with pytest.raises(QueueFullError, match="at capacity"):
        b.submit(_chain(5))


def test_batcher_engine_failure_is_per_batch_not_fatal():
    from deepdfa_tpu.serve import MicroBatcher

    eng = _StubEngine(fail_first=True)
    b = MicroBatcher(eng, max_batch=1, max_wait_ms=1.0).start()
    with pytest.raises(RuntimeError, match="stub engine failure"):
        b.submit(_chain(5)).result(timeout=10)
    # the dispatcher survived the poisoned batch and keeps serving
    assert b.submit(_chain(5)).result(timeout=10) == 0.25
    b.stop()


def test_batcher_stop_without_drain_fails_pending():
    from deepdfa_tpu.serve import MicroBatcher

    eng = _StubEngine()
    b = MicroBatcher(eng, max_queue=8)  # not started: items stay pending
    fut = b.submit(_chain(5))
    b.stop(drain=False)
    with pytest.raises(RuntimeError, match="shutting down"):
        fut.result(timeout=1)
    with pytest.raises(RuntimeError, match="draining"):
        b.submit(_chain(5))


def test_batcher_packs_within_bucket_budgets():
    """More requests than one batch admits → several dispatches, none over
    the bucket's graph capacity."""
    from deepdfa_tpu.serve import MicroBatcher

    eng = _StubEngine(max_batch=2)
    b = MicroBatcher(eng, max_batch=8, max_wait_ms=100.0)
    futs = [b.submit(_chain(5)) for _ in range(5)]
    b.start()
    assert [f.result(timeout=10) for f in futs] == [0.25] * 5
    assert max(eng.record) <= 2 and sum(eng.record) == 5


# ---------------------------------------------------------------------------
# config surface


def test_serve_config_overrides_and_validation():
    from deepdfa_tpu.config import ServeConfig, load_config

    cfg = load_config(overrides={"serve.max_batch": 4,
                                 "serve.max_wait_ms": 2.5,
                                 "serve.cache_entries": 0})
    assert (cfg.serve.max_batch, cfg.serve.max_wait_ms,
            cfg.serve.cache_entries) == (4, 2.5, 0)
    with pytest.raises(ValueError, match="max_batch"):
        ServeConfig(max_batch=0)
    with pytest.raises(ValueError, match="max_queue"):
        ServeConfig(max_queue=0)


# ---------------------------------------------------------------------------
# HTTP server


def _req(port, method, path, body=None, timeout=30):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _post_score(port, source, timeout=30):
    status, data = _req(port, "POST", "/score",
                        json.dumps({"source": source}), timeout)
    return status, json.loads(data)


@pytest.fixture()
def server(demo):
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                      ServeConfig(port=0, max_wait_ms=2.0)).start()
    try:
        yield srv, sources
    finally:
        srv.shutdown()


def test_server_scores_then_serves_from_cache(server):
    srv, sources = server
    status, body = _post_score(srv.port, sources[0])
    assert status == 200 and body["cached"] is False
    assert body["results"][0]["vulnerable_probability"] == 0.25
    dispatches_before = srv.engine.n_dispatches
    status, body = _post_score(srv.port, sources[0] + "   \n")  # WS-only edit
    assert status == 200 and body["cached"] is True
    assert srv.engine.n_dispatches == dispatches_before  # nothing re-scored
    assert srv.cache.stats()["hits"] == 1


def test_server_rejects_bad_requests_and_stays_up(server):
    srv, sources = server
    assert _req(srv.port, "POST", "/score", b"{nope")[0] == 400
    assert _post_score(srv.port, "")[0] == 400
    assert _post_score(srv.port, "this is not C {{{")[0] == 422
    assert _req(srv.port, "GET", "/nope")[0] == 404
    status, body = _req(srv.port, "GET", "/healthz")
    assert status == 200 and json.loads(body)["status"] == "ok"
    assert _post_score(srv.port, sources[0])[0] == 200


def test_server_metrics_endpoint_renders_counters(server):
    srv, sources = server
    _post_score(srv.port, sources[0])
    _post_score(srv.port, sources[0])
    status, data = _req(srv.port, "GET", "/metrics")
    text = data.decode()
    assert status == 200
    for field in ("deepdfa_serve_requests_total", "deepdfa_serve_queue_depth",
                  "deepdfa_serve_batch_occupancy_mean",
                  'deepdfa_serve_latency_ms{quantile="0.99"}',
                  "deepdfa_serve_cache_hits_total",
                  "deepdfa_serve_cache_hit_rate"):
        assert field in text, field
    assert "deepdfa_serve_cache_hits_total 1" in text


@pytest.mark.faults
def test_drop_request_fault_is_503_and_healthz_stays_green(server):
    from deepdfa_tpu.resilience import faults

    srv, sources = server
    with faults.installed("serve.drop_request@1"):
        status, body = _post_score(srv.port, sources[0])
        assert status == 503 and "drop" in body["error"]
        assert json.loads(_req(srv.port, "GET", "/healthz")[1])["status"] == "ok"
        assert _post_score(srv.port, sources[0])[0] == 200
    assert srv.metrics.snapshot()["dropped_total"] == 1


@pytest.mark.faults
def test_engine_fault_poisons_request_not_server(server):
    """DEEPDFA_FAULTS=serve.engine_raises@1 semantics: the poisoned
    request gets a 500, the server keeps serving, and the retry skips the
    frontend via the encode-layer cache entry the failed request left."""
    from deepdfa_tpu.resilience import faults

    srv, sources = server
    with faults.installed("serve.engine_raises@1"):
        status, body = _post_score(srv.port, sources[1])
        assert status == 500 and "serve.engine_raises" in body["error"]
        assert json.loads(_req(srv.port, "GET", "/healthz")[1])["status"] == "ok"
        status, body = _post_score(srv.port, sources[1])  # retry scores fine
        assert status == 200 and body["cached"] is False
    assert srv.cache.stats()["encode_hits"] == 1  # frontend ran ONCE


def test_sigterm_drains_inflight_requests_before_exit(demo):
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, delay_s=0.3), vocabs,
                      ServeConfig(port=0, max_wait_ms=1.0,
                                  drain_timeout_s=10.0)).start()
    prev = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        srv.install_signal_handlers()
        got = {}

        def client():
            got["resp"] = _post_score(srv.port, sources[0])

        t = threading.Thread(target=client, daemon=True)
        t.start()
        time.sleep(0.1)  # request admitted, batch in flight
        signal.raise_signal(signal.SIGTERM)
        snap = srv.wait()  # the drain path the foreground service runs
        t.join(timeout=10)
        status, body = got["resp"]
        assert status == 200  # in-flight request answered, not abandoned
        assert body["results"][0]["vulnerable_probability"] == 0.25
        assert snap["responses_total"].get("200") or snap["responses_total"].get(200)
        # listener is closed: new connections are refused
        with pytest.raises(OSError):
            _req(srv.port, "GET", "/healthz", timeout=2)
    finally:
        for s, h in prev.items():
            signal.signal(s, h)


def test_draining_server_refuses_new_scores(demo):
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs), vocabs,
                      ServeConfig(port=0, max_wait_ms=1.0)).start()
    try:
        # pre-drain baseline: healthz green
        status, body = _req(srv.port, "GET", "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"
        # the instant SIGTERM lands (flag set, drain not yet started) the
        # replica must advertise "draining" with a 503 so LBs stop routing
        srv._stop_requested.set()
        status, body = _req(srv.port, "GET", "/healthz")
        health = json.loads(body)
        assert status == 503
        assert health["status"] == "draining" and health["draining"] is True
        status, body = _post_score(srv.port, sources[0])
        assert status == 503 and "draining" in body["error"]
        srv._draining.set()  # mid-drain: same answer
        status, body = _post_score(srv.port, sources[0])
        assert status == 503 and "draining" in body["error"]
        assert json.loads(_req(srv.port, "GET", "/healthz")[1])["status"] == "draining"
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# bench contract


def test_serve_bench_schema_and_gates():
    from bench import assemble_serve_result

    good = dict(backend="cpu", device_kind="cpu", requests_per_sec=50.0,
                p50_ms=10.0, p99_ms=90.0, mean_batch_occupancy=0.7,
                cache_hit_rate=0.5, cache_hits=32, requests_total=64,
                errors_total=0)
    r = assemble_serve_result(**good)
    for key in ("metric", "value", "unit", "vs_baseline", "backend",
                "p50_ms", "p99_ms", "mean_batch_occupancy", "cache_hit_rate",
                "cache_hits", "requests_total", "errors_total", "ok"):
        assert key in r, key
    assert r["metric"] == "serve_requests_per_sec" and r["unit"] == "req/s"
    assert r["ok"] is True
    json.dumps(r)  # artifact must be JSON-serializable as-is

    # every acceptance gate flips ok independently
    assert assemble_serve_result(**{**good, "mean_batch_occupancy": 0.4})["ok"] is False
    assert assemble_serve_result(**{**good, "cache_hits": 0})["ok"] is False
    assert assemble_serve_result(**{**good, "errors_total": 1})["ok"] is False


def test_bench_serving_uniq_sources_have_distinct_keys():
    """The cold phase's uniqueness trick must actually produce distinct
    content addresses AND parseable C."""
    import bench_serving

    from deepdfa_tpu.cpg.frontend import parse_functions
    from deepdfa_tpu.pipeline import source_key

    base = "int f(int x) {\n  return x;\n}\n"
    srcs = [bench_serving._uniq_source(base, i) for i in range(3)]
    assert len({source_key(s) for s in srcs}) == 3
    names = [fn for fn, _ in parse_functions(srcs[0])]
    assert names == ["f", "bench_uniq_0"]


# ---------------------------------------------------------------------------
# latency mode + precision gate (live-model engines)


@pytest.fixture(scope="module")
def live_model():
    """Tiny segment-layout GGNN + fresh params over one feature column —
    the smallest real model the live-engine constructors accept."""
    import jax
    import jax.numpy as jnp

    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.graphs import batch_np
    from deepdfa_tpu.models import make_model

    cfg = GGNNConfig(hidden_dim=8, n_steps=2, num_output_layers=2,
                     concat_all_absdf=False)
    keys = ("_ABS_DATAFLOW",)
    model = make_model(cfg, input_dim=40)
    example = jax.tree.map(jnp.asarray, batch_np([_chain(6, keys)], 2, 16, 64))
    params = model.init(jax.random.key(0), example)["params"]
    return model, params, cfg.label_style, keys


def _live_engine(live_model, **kw):
    from deepdfa_tpu.serve import ScoringEngine

    model, params, label_style, keys = live_model
    return ScoringEngine.from_model(model, params, label_style,
                                    feat_keys=keys, max_batch=4, **kw)


def test_latency_mode_submit_matches_strict_and_donates(live_model):
    """submit().result() must equal the strict score() path, and the device
    batch must be DONATED to the warm callable. A GGNN batch is all
    int32/bool while the probs output is f32, so XLA has no aliasing
    target and reports every donation unusable — that compile-time
    UserWarning is the observable proof the argument is marked donated
    (this jax emits no donor marker in lowering text, and unusable donated
    buffers stay alive, so ``.is_deleted()`` can't witness it here; the
    aliasable in-place-consumption case is covered by
    ``test_dp_train_step_donates_state_and_metrics``)."""
    eng = _live_engine(live_model, latency_mode=True)
    assert eng.latency_mode
    keys = eng.feat_keys
    gs = [_chain(10, keys), _chain(25, keys)]
    bucket = eng.buckets[0]
    with pytest.warns(UserWarning, match="donated buffers were not usable"):
        pending = eng.submit(gs, bucket)
    got = pending.result()

    eng.latency_mode = False
    want = eng.score(gs, bucket)
    np.testing.assert_allclose(got, want, atol=1e-6)

    # warm resubmission: the donated-arg path must be reusable per request
    eng.latency_mode = True
    again = eng.submit(gs, bucket).result()
    np.testing.assert_allclose(again, want, atol=1e-6)
    assert eng.n_dispatches >= 3


def test_latency_mode_without_device_fn_warns_and_disables():
    """Artifact-style engines (host-side reductions, no jittable callable)
    cannot pipeline: latency_mode must downgrade loudly, not explode on
    the first request."""
    from deepdfa_tpu.serve import ScoringEngine, serve_buckets

    with pytest.warns(UserWarning, match="latency_mode requires"):
        eng = ScoringEngine(lambda b: np.zeros(4, np.float32),
                            serve_buckets(4), feat_keys=("_ABS_DATAFLOW",),
                            latency_mode=True)
    assert eng.latency_mode is False
    with pytest.raises(RuntimeError, match="device_fn"):
        eng.submit([_chain(5)], eng.buckets[0])


def test_int8_gate_accepts_and_scores_track_f32(live_model):
    """With a sane bound the int8 path must pass its own gate, record the
    measured delta, and serve scores within that bound of f32."""
    eng8 = _live_engine(live_model, precision="int8",
                        int8_max_score_delta=0.05)
    assert eng8.precision == "int8"
    assert eng8.int8_score_delta is not None
    assert eng8.int8_score_delta <= 0.05

    eng32 = _live_engine(live_model)
    gs = [_chain(12, eng8.feat_keys)]
    p8 = eng8.score(gs, eng8.buckets[0])
    p32 = eng32.score(gs, eng32.buckets[0])
    assert float(np.max(np.abs(p8 - p32))) <= 0.05
    assert np.all((p8 >= 0.0) & (p8 <= 1.0))


def test_int8_gate_refusal_falls_back_to_f32_and_journals(live_model, tmp_path):
    """An impossible bound forces the accuracy gate to refuse: the engine
    must warn, journal the refusal (reason + measured delta), and serve
    f32 — never silently ship the failing int8 path."""
    from deepdfa_tpu.resilience.journal import RunJournal

    journal = RunJournal(tmp_path / "journal.json")
    with pytest.warns(UserWarning, match="int8 serving path refused"):
        eng = _live_engine(live_model, precision="int8",
                           int8_max_score_delta=1e-12, journal=journal)
    assert eng.precision == "f32"
    rec = journal.read()
    assert rec["event"] == "int8_gate_refused"
    assert rec["int8_max_score_delta"] == 1e-12
    assert rec["int8_score_delta"] > 1e-12
    assert "exceeds" in rec["reason"]
    # the fallback engine still serves
    p = eng.score([_chain(8, eng.feat_keys)], eng.buckets[0])
    assert p.shape == (1,) and np.isfinite(p).all()


def test_int8_gate_refuses_nan_poisoned_checkpoint(live_model, tmp_path):
    """calibrate_int8 raises on non-finite kernels; from_model must turn
    that into a journaled refusal (reason prefixed 'calibration refused'),
    not a crash and not an int8 engine."""
    import jax

    from deepdfa_tpu.resilience.journal import RunJournal

    model, params, label_style, keys = live_model
    poisoned = jax.tree.map(lambda x: np.array(x), params)
    poisoned["ggnn"]["edge_linear"]["kernel"][0, 0] = np.nan

    from deepdfa_tpu.serve import ScoringEngine

    journal = RunJournal(tmp_path / "journal.json")
    with pytest.warns(UserWarning, match="calibration refused"):
        eng = ScoringEngine.from_model(
            model, poisoned, label_style, feat_keys=keys, max_batch=4,
            precision="int8", journal=journal)
    assert eng.precision == "f32"
    rec = journal.read()
    assert rec["event"] == "int8_gate_refused"
    assert "non-finite" in rec["reason"]


# ---------------------------------------------------------------------------
# distributed fleet: consistent-hash ring + warm store (pytest -m fleet —
# the lint_gate unit slice: pure logic, no engine compiles)


@pytest.mark.fleet
def test_hash_ring_join_moves_about_one_over_n_keys():
    """The consistent-hashing contract: adding the (N+1)th backend remaps
    ~1/(N+1) of the keyspace — NOT the ~N/(N+1) a modulo scheme would."""
    from deepdfa_tpu.serve import HashRing

    ring = HashRing()
    for i in range(4):
        ring.add(f"b{i}:80")
    keys = [f"key-{i}" for i in range(2000)]
    before = {k: ring.route(k) for k in keys}
    assert all(v is not None for v in before.values())
    ring.add("b4:80")
    moved = sum(before[k] != ring.route(k) for k in keys)
    # ideal is 1/5 = 400; allow generous vnode variance either side
    assert 0.10 * len(keys) < moved < 0.35 * len(keys)
    # every moved key moved TO the new node (stability for the others)
    for k in keys:
        if before[k] != ring.route(k):
            assert ring.route(k) == "b4:80"


@pytest.mark.fleet
def test_hash_ring_leave_only_reassigns_leaving_nodes_keys():
    from deepdfa_tpu.serve import HashRing

    ring = HashRing()
    for i in range(4):
        ring.add(f"b{i}:80")
    keys = [f"key-{i}" for i in range(2000)]
    before = {k: ring.route(k) for k in keys}
    ring.remove("b2:80")
    for k in keys:
        after = ring.route(k)
        assert after != "b2:80"
        if before[k] != "b2:80":
            assert after == before[k]  # survivors keep their shard


@pytest.mark.fleet
def test_hash_ring_exclude_walks_and_empty_ring_routes_none():
    from deepdfa_tpu.serve import HashRing

    ring = HashRing()
    assert ring.route("k") is None
    ring.add("a:1")
    ring.add("b:2")
    owner = ring.route("k")
    other = ring.route("k", exclude={owner})
    assert other is not None and other != owner
    assert ring.route("k", exclude={"a:1", "b:2"}) is None


@pytest.mark.fleet
def test_hash_ring_spreads_keys_across_all_nodes():
    from deepdfa_tpu.serve import HashRing

    ring = HashRing()
    names = [f"b{i}:80" for i in range(4)]
    for n in names:
        ring.add(n)
    counts = {n: 0 for n in names}
    for i in range(2000):
        counts[ring.route(f"key-{i}")] += 1
    assert all(c > 0.1 * 2000 / 4 for c in counts.values()), counts


@pytest.mark.fleet
def test_warm_store_roundtrip_keys_and_stats(tmp_path):
    from deepdfa_tpu.serve import WarmStore

    ws = WarmStore(tmp_path / "store")
    assert ws.get("nope") is None and ws.keys() == []
    ws.put("k1", b"program-bytes", {"compile_seconds": 1.25})
    e = ws.get("k1")
    assert e.payload == b"program-bytes"
    assert e.meta["compile_seconds"] == 1.25
    assert ws.keys() == ["k1"]
    assert ws.stats() == {"entries": 1, "bytes": len(b"program-bytes")}


@pytest.mark.fleet
def test_warm_store_payload_without_meta_is_absent(tmp_path):
    """The commit protocol: meta.json is the marker. A payload that landed
    without its meta (kill -9 mid-put) must read as a MISS, never as a
    torn artifact."""
    from deepdfa_tpu.serve import WarmStore

    ws = WarmStore(tmp_path / "store")
    (ws.root / "torn.stablehlo").write_bytes(b"half-written")
    assert ws.get("torn") is None and ws.keys() == []
    (ws.root / "bad.stablehlo").write_bytes(b"x")
    (ws.root / "bad.json").write_text("{not json")
    assert ws.get("bad") is None and ws.keys() == []


@pytest.mark.fleet
def test_bucket_artifact_key_covers_every_program_input():
    """Everything that changes the lowered module must change the key —
    a collision would hand a replica a program compiled for different
    weights/vocab/shape."""
    from deepdfa_tpu.serve import bucket_artifact_key

    base = dict(vocab_hash="vh", model_rev="mr", precision="f32",
                label_style="graph", feat_keys=("_ABS_DATAFLOW",),
                max_graphs=5, max_nodes=128, max_edges=512)
    k0 = bucket_artifact_key(**base)
    assert k0 == bucket_artifact_key(**base)  # deterministic
    for field, val in [("vocab_hash", "other"), ("model_rev", "other"),
                       ("precision", "int8"), ("label_style", "node"),
                       ("feat_keys", ("_ABS_DATAFLOW", "_API")),
                       ("max_graphs", 9), ("max_nodes", 256),
                       ("max_edges", 1024)]:
        assert bucket_artifact_key(**{**base, field: val}) != k0, field


# ---------------------------------------------------------------------------
# fleet router over stub backends (pytest -m fleet — no engines)


class _FakeBackend:
    """A /healthz + /score stub standing in for a ScoreServer replica:
    records every source it scores, health body is mutable per test."""

    def __init__(self, name):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.name = name
        self.scored = []
        self.health = {"status": "ok", "draining": False, "warm": True,
                       "replica_id": name}
        backend = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _send(self, code, body):
                data = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                h = backend.health
                self._send(503 if h.get("draining") else 200, h)

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                payload = json.loads(self.rfile.read(n) or b"{}")
                backend.scored.append(payload.get("source"))
                self._send(200, {"results": [], "backend": backend.name})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        self.thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True)
        self.thread.start()

    @property
    def addr(self):
        return f"127.0.0.1:{self.httpd.server_address[1]}"

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture()
def fake_fleet():
    backends = [_FakeBackend(f"r{i}") for i in range(3)]
    from deepdfa_tpu.serve import FleetRouter

    router = FleetRouter([b.addr for b in backends], port=0,
                         probe_interval_s=60.0)
    router.probe_once()
    router.start(probe=False)
    try:
        yield router, backends
    finally:
        router.shutdown()
        for b in backends:
            b.stop()


def _route_post(port, source):
    status, data = _req(port, "POST", "/score",
                        json.dumps({"source": source}))
    return status, json.loads(data)


@pytest.mark.fleet
def test_router_shards_keys_stably_across_backends(fake_fleet):
    """Same source → same backend on every request (the property the
    sharded cache rides on), and the keyspace actually spreads."""
    router, backends = fake_fleet
    assert all(b.state == "ready" for b in router.backends.values())
    sources = [f"int f{i}(int x) {{ return x + {i}; }}" for i in range(24)]
    for s in sources:
        assert _route_post(router.port, s)[0] == 200
    counts_first = {b.name: len(b.scored) for b in backends}
    assert sum(counts_first.values()) == 24
    assert all(c > 0 for c in counts_first.values())  # every replica routed
    for s in sources:  # replay: every key lands on the SAME shard
        assert _route_post(router.port, s)[0] == 200
    for b in backends:
        assert b.scored[: len(b.scored) // 2] == b.scored[len(b.scored) // 2:]


@pytest.mark.fleet
def test_router_readiness_gates_cold_replicas(fake_fleet):
    """warm:false in /healthz keeps a replica out of the ring (state
    pending) until it reports warm — a compiling replica must not stall
    its keyspace."""
    router, backends = fake_fleet
    backends[0].health["warm"] = False
    router.probe_once()
    assert router.backends[backends[0].addr].state == "pending"
    assert backends[0].addr not in router.ring.nodes
    for i in range(12):
        assert _route_post(router.port, f"int g{i}() {{ return {i}; }}")[0] == 200
    assert backends[0].scored == []  # took no traffic while cold
    backends[0].health["warm"] = True
    router.probe_once()
    assert router.backends[backends[0].addr].state == "ready"


@pytest.mark.fleet
def test_router_drain_rebalances_keyspace(fake_fleet):
    """A draining backend (503 + draining:true — its SIGTERM flag) leaves
    the ring on the next probe; its keys reroute to survivors, the
    survivors keep theirs."""
    router, backends = fake_fleet
    sources = [f"int h{i}(int x) {{ return x * {i}; }}" for i in range(18)]
    for s in sources:
        _route_post(router.port, s)
    owner_before = {s: next(b.name for b in backends if s in b.scored)
                    for s in sources}
    drained = backends[1]
    drained.health.update(status="draining", draining=True)
    router.probe_once()
    assert router.backends[drained.addr].state == "draining"
    assert drained.addr not in router.ring.nodes
    n_drained_before = len(drained.scored)
    for s in sources:
        assert _route_post(router.port, s)[0] == 200
    assert len(drained.scored) == n_drained_before  # no new traffic
    survivors = [b for b in backends if b is not drained]
    for s in sources:
        if owner_before[s] == drained.name:
            # drained keys rerouted somewhere live
            assert any(s in b.scored for b in survivors), s
        else:
            # survivor keys stayed put: scored twice by the SAME backend
            b = next(x for x in survivors if x.name == owner_before[s])
            assert b.scored.count(s) == 2, s


@pytest.mark.fleet
def test_router_fails_over_dead_backend_and_healthz_reports(fake_fleet):
    """A backend dying mid-service: the forward fails at the socket, the
    router marks it down and retries the next ring node — the request
    still answers 200."""
    router, backends = fake_fleet
    dead = backends[2]
    dead.stop()
    for i in range(12):
        status, body = _route_post(router.port,
                                   f"int k{i}(int x) {{ return x - {i}; }}")
        assert status == 200, body
    assert router.backends[dead.addr].state == "down"
    status, data = _req(router.port, "GET", "/healthz")
    health = json.loads(data)
    assert status == 200  # fleet still has ready backends
    assert dead.addr not in health["ready_backends"]
    assert health["backends"][dead.addr]["state"] == "down"
    assert router.metrics.snapshot()["retries_total"] >= 1


@pytest.mark.fleet
def test_router_with_no_ready_backend_is_503(fake_fleet):
    router, backends = fake_fleet
    for b in backends:
        b.health.update(status="draining", draining=True)
    router.probe_once()
    status, data = _req(router.port, "GET", "/healthz")
    assert status == 503
    status, body = _route_post(router.port, "int z() { return 0; }")
    assert status == 503 and "no ready backend" in body["error"]


@pytest.mark.fleet
def test_router_metrics_render(fake_fleet):
    router, backends = fake_fleet
    _route_post(router.port, "int m() { return 1; }")
    status, data = _req(router.port, "GET", "/metrics")
    text = data.decode()
    assert status == 200
    for field in ("deepdfa_router_requests_total",
                  "deepdfa_router_forwarded_total",
                  "deepdfa_router_retries_total",
                  "deepdfa_router_no_backend_total"):
        assert field in text, field


@pytest.mark.fleet
def test_router_sharded_cache_hits_real_servers(demo):
    """The cache-shard property end-to-end on REAL ScoreServers (stub
    engines): replayed sources route back to the replica that cached
    them, so per-shard hit counters climb and no shard duplicates
    another's entries."""
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.serve import FleetRouter, ScoreServer

    vocabs, sources = demo
    servers = [ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                           ServeConfig(port=0, max_wait_ms=2.0),
                           replica_id=f"r{i}").start()
               for i in range(2)]
    for s in servers:
        s.engine.warmup()  # readiness: the probe gates on warm
    router = FleetRouter([f"127.0.0.1:{s.port}" for s in servers], port=0,
                         probe_interval_s=60.0)
    router.probe_once()
    router.start(probe=False)
    try:
        assert sorted(router.ring.nodes) == sorted(
            f"127.0.0.1:{s.port}" for s in servers)
        for src in sources:  # cold: populate the shards
            status, body = _route_post(router.port, src)
            assert status == 200 and body["cached"] is False
        for src in sources:  # hot: every replay must hit ITS shard
            status, body = _route_post(router.port, src)
            assert status == 200 and body["cached"] is True, body
        hits = [s.cache.stats()["hits"] for s in servers]
        entries = [s.cache.stats()["entries"] for s in servers]
        assert sum(hits) == len(sources)  # all replays were shard hits
        assert all(h > 0 for h in hits)   # both shards took keys
        assert sum(entries) == len(sources)  # shards partition, not mirror
    finally:
        router.shutdown()
        for s in servers:
            s.shutdown()


# ---------------------------------------------------------------------------
# fleet perf-gate plumbing that needs no devices


@pytest.mark.fleet
def test_healthz_reports_fleet_readiness_fields(server):
    srv, _ = server
    status, data = _req(srv.port, "GET", "/healthz")
    health = json.loads(data)
    assert status == 200
    assert health["replica_id"] == f"127.0.0.1:{srv.port}"
    assert health["warm"] is False and health["warm_buckets"] == []
    report = srv.warmup()
    assert (report["hits"], report["misses"]) == (0, 3)
    health = json.loads(_req(srv.port, "GET", "/healthz")[1])
    assert health["warm"] is True
    assert health["warm_buckets"] == [126, 1022, 4094]
    assert health["precision"] == "f32" and health["n_replicas"] == 1
    assert "vocab_hash" in health and "model_rev" in health


@pytest.mark.fleet
def test_metrics_render_warmup_and_warm_store_counters(server):
    srv, _ = server
    srv.warmup()
    text = _req(srv.port, "GET", "/metrics")[1].decode()
    for field in ("deepdfa_serve_warm_store_hits_total 0",
                  "deepdfa_serve_warm_store_misses_total 3",
                  "deepdfa_serve_warm_store_compile_seconds_saved",
                  'deepdfa_serve_warmup_compile_seconds{bucket="126"'):
        assert field in text, field


# ---------------------------------------------------------------------------
# warm-store joins + mesh replication (live engines — serve marker only:
# these compile, so they stay out of the fast `pytest -m fleet` gate)


def test_warm_store_join_loads_ladder_with_zero_recompiles(live_model,
                                                           tmp_path):
    """The zero-cold-compile join, end to end in-process: replica A
    compiles + exports every bucket; replica B (same weights → same
    model_rev → same keys) warms entirely from the store, journals
    compile-seconds-saved, and serves IDENTICAL scores."""
    from deepdfa_tpu.resilience.journal import RunJournal
    from deepdfa_tpu.serve import WarmStore

    ws = WarmStore(tmp_path / "store")
    ja = RunJournal(tmp_path / "a.json")
    jb = RunJournal(tmp_path / "b.json")

    eng_a = _live_engine(live_model)
    rep_a = eng_a.warmup(warm_store=ws, journal=ja)
    assert (rep_a["hits"], rep_a["misses"]) == (0, 3)
    assert len(ws.keys()) == 3
    assert ja.read()["event"] == "warmup"

    gs = [_chain(10, eng_a.feat_keys), _chain(25, eng_a.feat_keys)]
    want = eng_a.score(gs, eng_a.buckets[0])

    eng_b = _live_engine(live_model)
    assert eng_b.model_rev == eng_a.model_rev  # content-addressed weights
    rep_b = eng_b.warmup(warm_store=ws, journal=jb)
    assert (rep_b["hits"], rep_b["misses"]) == (3, 0)  # zero recompiles
    rec = jb.read()
    assert rec["event"] == "warmup"
    assert rec["compile_seconds_saved"] > 0  # journaled, positive
    got = eng_b.score(gs, eng_b.buckets[0])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_warm_store_programs_are_for_the_hosts_platform_only(live_model,
                                                            tmp_path):
    """A bucket is exported for the platform that compiled it (a Mosaic
    kernel has no CPU lowering; an interpret-mode trace must never be what
    a TPU joiner loads), and the key carries that platform."""
    from jax import export as jexport

    from deepdfa_tpu.serve import WarmStore, bucket_artifact_key

    ws = WarmStore(tmp_path / "store")
    eng = _live_engine(live_model)
    eng.warmup(warm_store=ws)
    for key in ws.keys():
        exported = jexport.deserialize(ws.get(key).payload)
        assert tuple(exported.platforms) == ("cpu",)
    b = eng.buckets[0]
    args = (eng.vocab_hash, eng.model_rev, eng.precision, eng.label_style,
            eng.feat_keys, b.spec.max_graphs, b.spec.max_nodes,
            b.spec.max_edges)
    assert eng.bucket_key(b) == bucket_artifact_key(*args, platform="cpu")
    assert eng.bucket_key(b) != bucket_artifact_key(*args, platform="tpu")
    assert eng.bucket_key(b) != bucket_artifact_key(*args)


def test_warm_store_export_failure_surfaces_but_write_failure_degrades(
        live_model, tmp_path):
    """A lowering/serialization failure is a bug and raises out of warmup;
    only the store WRITE is best-effort (the bucket is already warm)."""
    from deepdfa_tpu.serve import WarmStore

    eng = _live_engine(live_model)

    def broken_export(bucket):
        raise ValueError("Only interpret mode is supported on CPU backend.")

    eng._export_fn = broken_export
    with pytest.raises(ValueError, match="interpret mode"):
        eng.warmup(warm_store=WarmStore(tmp_path / "a"))

    class _FullDisk(WarmStore):
        def put(self, key, payload, meta):
            raise OSError(28, "No space left on device")

    eng2 = _live_engine(live_model)
    with pytest.warns(UserWarning, match="warm-store write failed"):
        rep = eng2.warmup(warm_store=_FullDisk(tmp_path / "b"))
    assert rep["misses"] == 3 and eng2.warm_buckets
    assert all("export_error" in row for row in rep["per_bucket"].values())


def test_warm_store_keys_change_with_model_rev(live_model, tmp_path):
    """Different weights → different model_rev → a joiner must MISS (and
    recompile) rather than load another revision's program."""
    import jax

    from deepdfa_tpu.serve import WarmStore

    ws = WarmStore(tmp_path / "store")
    eng_a = _live_engine(live_model)
    eng_a.warmup(warm_store=ws)

    model, params, label_style, keys = live_model
    bumped = jax.tree.map(lambda x: np.asarray(x) + 0.01, params)
    from deepdfa_tpu.serve import ScoringEngine

    eng_c = ScoringEngine.from_model(model, bumped, label_style,
                                     feat_keys=keys, max_batch=4)
    assert eng_c.model_rev != eng_a.model_rev
    rep = eng_c.warmup(warm_store=ws)
    assert rep["hits"] == 0 and rep["misses"] == 3
    assert len(ws.keys()) == 6  # both revisions coexist, shared-nothing


def test_concurrent_latency_submits_do_not_interleave_buffers(live_model):
    """The engine-lock regression test: concurrent submit()/result()
    callers in latency mode, each with DISTINCT inputs, must each get the
    scores of their own batch — interleaved donated buffers would hand
    one thread the other's probabilities (or poison a donated buffer
    mid-upload)."""
    import warnings

    eng = _live_engine(live_model, latency_mode=True)
    keys = eng.feat_keys
    bucket = eng.buckets[0]
    inputs = [[_chain(5 + i, keys)] for i in range(6)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # donation-unusable compile noise
        want = []
        eng.latency_mode = False
        for gs in inputs:
            want.append(eng.score(gs, bucket))
        eng.latency_mode = True

        results = {}
        errors = []
        barrier = threading.Barrier(len(inputs))

        def worker(idx):
            try:
                barrier.wait(timeout=30)
                for _ in range(8):
                    got = eng.submit(inputs[idx], bucket).result()
                    np.testing.assert_allclose(got, want[idx], atol=1e-6)
                results[idx] = got
            except Exception as exc:  # noqa: BLE001
                errors.append((idx, exc))

        threads = [threading.Thread(target=worker, args=(i,), daemon=True)
                   for i in range(len(inputs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not errors, errors
    assert len(results) == len(inputs)


def test_mesh_replicated_engine_matches_single_replica(live_model):
    """mesh= replication: score_groups stacks one padded batch per dp
    device, ONE dispatch scores them all, and every group's probabilities
    match the single-replica engine bit-for-bit (pure replication — no
    collectives, no math changes)."""
    from deepdfa_tpu.parallel.mesh import local_mesh
    from deepdfa_tpu.serve import ScoringEngine

    model, params, label_style, keys = live_model
    single = _live_engine(live_model)
    mesh = local_mesh(2)
    eng = ScoringEngine.from_model(model, params, label_style,
                                   feat_keys=keys, max_batch=4, mesh=mesh)
    assert eng.n_replicas == 2
    assert eng.model_rev == single.model_rev
    rep = eng.warmup()
    assert rep["buckets"] == 3

    bucket = eng.buckets[0]
    groups = [[_chain(10, keys)], [_chain(25, keys), _chain(7, keys)]]
    eng.n_dispatches = 0
    got = eng.score_groups(groups, bucket)
    assert eng.n_dispatches == 1  # two groups, one stacked dispatch
    for g, w in zip(got, (single.score(x, single.buckets[0])
                          for x in groups)):
        np.testing.assert_allclose(g, w, atol=1e-5)
    # plain score() routes through the stack too (batcher compatibility)
    np.testing.assert_allclose(
        eng.score(groups[1], bucket),
        single.score(groups[1], single.buckets[0]), atol=1e-5)
    with pytest.raises(ValueError, match="groups > 2 replicas"):
        eng.score_groups([[], [], []], bucket)


def test_batcher_chunks_window_across_replicas():
    """With a stacked (mesh) engine the batcher must hand up to
    n_replicas packed batches to ONE score_groups dispatch instead of
    n sequential score() calls."""
    from deepdfa_tpu.serve import MicroBatcher, ScoringEngine, serve_buckets

    calls = []

    def stacked_fn(stacked):
        n_graphs = np.asarray(stacked.graph_mask).sum(axis=1)
        calls.append([int(x) for x in n_graphs])
        return np.full((stacked.graph_mask.shape[0],
                        stacked.graph_mask.shape[1]), 0.125, np.float32)

    eng = ScoringEngine(None, serve_buckets(2), feat_keys=("_ABS_DATAFLOW",),
                        stacked_fn=stacked_fn, n_replicas=2)
    b = MicroBatcher(eng, max_batch=8, max_wait_ms=100.0)
    futs = [b.submit(_chain(5)) for _ in range(5)]  # packs to 3 batches of <=2
    b.start()
    assert [f.result(timeout=10) for f in futs] == [0.125] * 5
    # 3 packed batches / 2 replicas -> 2 stacked dispatches, none wider
    # than the replica count
    assert eng.n_dispatches == 2
    assert len(calls) == 2 and all(len(c) == 2 for c in calls)
    b.stop()
