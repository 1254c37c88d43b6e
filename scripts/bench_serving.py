#!/usr/bin/env python
"""Closed-loop load test of the online scoring service.

Stands up a REAL in-process :class:`deepdfa_tpu.serve.ScoreServer` — live
GGNN engine (fresh params: the serving contract under test is the
pipeline + batching + cache machinery, which is training-independent,
same rationale as check_serving.py), hermetic demo-corpus vocabularies —
and drives it over HTTP with a fixed number of concurrent closed-loop
workers (each fires its next request only when the previous one
answered; offered load adapts to service rate, so the numbers measure
the server, not a queue explosion).

Two phases:

1. **cold** — every request body is unique (corpus function + a
   per-request unique helper function), so each one pays the full
   frontend + encode + batch + score path;
2. **hot** — the exact cold bodies replayed, so every request must be a
   content-addressed cache hit that skips the frontend entirely. The
   artifact asserts this via the cache HIT COUNTER, never via timing.

Prints ONE JSON line (``bench.assemble_serve_result``): requests/sec,
p50/p99 latency, mean batch occupancy (gate: >= 0.5 — the micro-batcher
must actually coalesce), cache hit rate + hits, ok. The notes block also
carries ``precision_tiers`` — per-bucket-tier p50/p99 of single-graph
engine dispatches at BOTH serving precisions (f32 and, gate permitting,
int8) from the same checkpoint, so one artifact answers "what does each
tier cost at each precision" (``serve.precision`` in config.py). Notes
also record p50/p99 QUEUE-WAIT and DISPATCH durations (from the serve
metrics reservoirs the tracing plane feeds) plus a ``trace_overhead``
block — micro-measured span-record cost vs the measured p50, guarding
the roadmap invariant that tracing stays under 2% of request latency.

``--fleet N`` grows the run into the distributed topology: the baseline
single replica above doubles as the warm-store POPULATOR (its cold
warmup exports every bucket's compiled program), then N fresh replicas
join by warm-loading the ladder (the gate: zero cold compiles,
journaled compile-seconds-saved > 0), a consistent-hash router fronts
them, and a cold + ``--load-x``× hot replay runs closed-loop through
the router. The artifact gains a ``fleet`` block
(``bench.assemble_fleet_result``): aggregate vs single-replica cold
throughput (speedup gated on TPU only — one starved CPU core cannot
exhibit device parallelism and a "passing" CPU number would be a lie),
per-replica routing/occupancy, sharded-cache hit counters, aggregate
p50/p99 under the multiplied load.

``--autoscale N`` closes the loop: an SLO-driven
:class:`~deepdfa_tpu.serve.Autoscaler` supervises 2..N warm-joining
replicas behind the router while the load sawtooths 10x and a chaos
``kill -9`` (the ``autoscale.replica_crash`` fault) lands mid-load. The
artifact gains an ``autoscale`` block (``bench.assemble_autoscale_result``)
gated on the chaos criteria: replacement within the deadline with zero
join compiles, SLO burn minutes within budget, zero client-visible
errors beyond the failover window, and every scale decision recorded.

``--frontend`` runs the encode-pool stage: an inline-frontend baseline
phase and a pool-enabled phase drive the same-shaped cold (unique-body)
load, then a chaos phase kills the pool mid-load. The artifact gains a
``frontend`` block (``bench.assemble_frontend_result``): pool vs inline
cold throughput (the ≥ 0.75×/worker scaling gate binds only when
``host_cpus >= workers`` — a 1-CPU host records the honest ratio with
``scaling_ok: null``), the measured encode↔dispatch overlap fraction
(must be > 0: the pool actually hid frontend work behind device
dispatches), encode/queue-wait percentiles, and the degradation gates —
zero errors with the pool dead, inline fallback counter > 0, /healthz
green (standing invariant 25).

``--cascade`` runs the two-tier escalation stage: a no-cascade baseline
phase doubles as the tier-1 score oracle (the engine is deterministic),
the borderline band is placed at the observed scores' 30th/70th
percentiles — so the expected escalation fraction is the band's exact
measured mass — and the identical load replays against a cascade-enabled
server backed by a hermetic tier-2 joint engine. The artifact gains a
``cascade`` block (``bench.assemble_cascade_result``) gated on: measured
escalation fraction within ±20% of expected, ZERO degraded answers under
nominal load, and tier-1 p50 (requests that never escalated) within 10%
of the baseline phase.

``--overload`` runs the admission/brownout sawtooth: ONE admission-enabled
replica (generous interactive budget, deliberately tiny batch budget,
short SLO windows so the burn signal tracks the sawtooth) takes an
interactive-only nominal trickle, then a 10×-saturation mixed
interactive+batch leg replayed until the brownout ladder visibly
escalates, then a cache-hot recovery trickle until it steps back down.
The artifact gains an ``admission`` block
(``bench.assemble_admission_result``) gated on the explicit-overload
contract (invariant candidate 30): nominal sheds ZERO, the saturation leg
sheds (starting with the batch class), every shed is a 429 carrying its
Retry-After header, zero 5xx anywhere (the interactive class above all),
interactive sheds only after the ladder's last level, every decision
journaled (zero drops), /healthz reported the degradation while it was
happening, and the SLO burn the sawtooth paged stays within budget.

``--federation N`` runs the cell-killed sawtooth: N complete cells (each
ONE admission-enabled replica behind its own FleetRouter, all
warm-joined from the shared store) behind a live
:class:`~deepdfa_tpu.serve.FederationRouter`. A nominal trickle, then a
``--load-x``× replay first saturates the fleet until saturation
spillover is visible, then the ``federation.cell_kill`` fault SIGKILLs
one whole cell from the federation's own probe loop; survivors absorb
its keyspace. A promotion attempted mid-brownout must be REFUSED by the
brownout gate; the killed cell heals (replacement replica warm-joins
behind a fresh cell router, rejoins the federation through the readiness
gate), a recovery trickle drains the ladder, and the SAME promotion then
rolls a real perturbed-params candidate rev across the healed cell. The
artifact gains a ``federation`` block
(``bench.assemble_federation_result``) gated on invariant candidate 32:
zero client-visible 5xx through the whole sawtooth, spillover served
> 0 with zero spillover errors, every 429 carrying Retry-After, rejoin
within the recovery deadline with ``join_cold_compiles == 0``, promotion
refused during brownout and completed after recovery.
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _uniq_source(base: str, i: int) -> str:
    """A distinct-content request body that still parses: the corpus
    function plus a tiny unique helper (also exercises multi-function
    requests — occupancy counts graphs, not HTTP calls)."""
    return f"{base}\nint bench_uniq_{i}(int a) {{\n  int b = a + {i};\n  return b;\n}}\n"


def _build_corpus(corpus_n: int):
    """Hermetic demo corpus + real vocabularies (no training)."""
    from deepdfa_tpu.config import ExperimentConfig
    from deepdfa_tpu.cpg.features import add_dependence_edges
    from deepdfa_tpu.cpg.frontend import parse_source
    from deepdfa_tpu.data.codegen import demo_corpus
    from deepdfa_tpu.data.materialize import CorpusBuilder

    df = demo_corpus(corpus_n, seed=0)
    rows = df.to_dict("records")
    cpgs = {int(r["id"]): add_dependence_edges(parse_source(r["before"]))
            for r in rows}
    labels = {int(r["id"]): int(r["vul"]) for r in rows}
    cfg = ExperimentConfig()
    _, vocabs = CorpusBuilder(cfg.data.feature).build(
        cpgs, list(cpgs), graph_labels=labels)
    return cfg, vocabs, [r["before"] for r in rows]


def _build_ckpt(cfg, vocabs):
    """Fresh-params live model — the one 'checkpoint' every replica in a
    fleet run serves (identical weights → identical ``model_rev`` → the
    joiners' warm-store keys match the populating baseline's)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepdfa_tpu.data.graphs import Graph, batch_np
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.pipeline import vocab_content_hash

    model = make_model(cfg.model, cfg.input_dim)
    n = 4
    feats = {k: np.zeros(n, np.int32) for k in vocabs}
    dummy = Graph(senders=np.arange(n - 1, dtype=np.int32),
                  receivers=np.arange(1, n, dtype=np.int32),
                  node_feats=feats).with_self_loops()
    example = jax.tree.map(jnp.asarray, batch_np([dummy], 2, 8, 128))
    params = model.init(jax.random.key(0), example)["params"]
    return {"model": model, "params": params,
            "label_style": cfg.model.label_style,
            "feat_keys": tuple(vocabs),
            "vocab_hash": vocab_content_hash(vocabs)}


def _make_server(ckpt, vocabs, max_batch: int, max_wait_ms: float,
                 warm_store=None, journal=None, replica_id=None,
                 latency_window=None, obs=None, cascade=None,
                 tier2_engine=None, frontend=None, admission=None):
    """One ScoreServer replica over a FRESH engine from the shared
    checkpoint (each replica pays — or warm-loads — its own ladder)."""
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.serve import ScoreServer, ScoringEngine

    engine = ScoringEngine.from_model(
        ckpt["model"], ckpt["params"], ckpt["label_style"],
        feat_keys=ckpt["feat_keys"], max_batch=max_batch,
        vocab_hash=ckpt["vocab_hash"], journal=journal)
    extra = {}
    if latency_window is not None:
        extra["latency_window"] = latency_window
    if obs is not None:
        extra["obs"] = obs
    if cascade is not None:
        extra["cascade"] = cascade
    if frontend is not None:
        extra["frontend"] = frontend
    if admission is not None:
        extra["admission"] = admission
    serve_cfg = ServeConfig(port=0, max_batch=max_batch,
                            max_wait_ms=max_wait_ms, **extra)
    return ScoreServer(engine, vocabs, serve_cfg, replica_id=replica_id,
                       warm_store=warm_store, journal=journal,
                       tier2_engine=tier2_engine)


def _build_fixture(max_batch: int, max_wait_ms: float, corpus_n: int):
    cfg, vocabs, sources = _build_corpus(corpus_n)
    ckpt = _build_ckpt(cfg, vocabs)
    server = _make_server(ckpt, vocabs, max_batch, max_wait_ms)
    ckpt["vocabs"] = vocabs
    return server, sources, ckpt


def _precision_tiers(ckpt: dict, max_batch: int, requests_per_tier: int):
    """Per-tier p50/p99 of single-graph engine dispatches at BOTH serving
    precisions, from the same checkpoint the HTTP server ran. The int8
    engine goes through the normal accuracy gate (synthesized calibration
    graphs); a refusal is reported, not hidden — the tier table then
    carries f32-only rows. Measures ``engine.score`` directly (no HTTP):
    the tier numbers isolate dispatch, the phase numbers above carry the
    full-service path."""
    import warnings

    import numpy as np

    from deepdfa_tpu.serve.engine import ScoringEngine, _calibration_graphs

    engines, refusal = {}, None
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for prec in ("f32", "int8"):
            engines[prec] = ScoringEngine.from_model(
                ckpt["model"], ckpt["params"], ckpt["label_style"],
                feat_keys=ckpt["feat_keys"], max_batch=max_batch,
                precision=prec)
            engines[prec].warmup()
    for w in caught:
        if "int8 serving path refused" in str(w.message):
            refusal = str(w.message)

    cal = _calibration_graphs(
        ckpt["feat_keys"], engines["f32"].buckets, n_per_bucket=4)
    tiers = {}
    for bi, bucket in enumerate(engines["f32"].buckets):
        gs = [g for g in cal if bucket.admits(g)]
        row = {}
        for prec, eng in engines.items():
            if prec == "int8" and eng.precision != "int8":
                row[prec] = None  # gate refused: served f32, no int8 tier
                continue
            b = eng.buckets[bi]
            eng.score([gs[0]], b)  # warm (compiled by warmup)
            lat = []
            for i in range(requests_per_tier):
                t0 = time.perf_counter()
                eng.score([gs[i % len(gs)]], b)
                lat.append((time.perf_counter() - t0) * 1e3)
            row[prec] = {"p50_ms": round(float(np.percentile(lat, 50)), 3),
                         "p99_ms": round(float(np.percentile(lat, 99)), 3)}
        tiers[str(bucket.graph_nodes)] = row
    return tiers, engines["int8"].precision, refusal


def _trace_overhead(p50_ms, spans_per_request: int = 6, n: int = 2000):
    """Micro-measured cost of the tracing plane: time ``n`` raw span
    records on a throwaway :class:`Tracer`, scale by the spans a scoring
    request actually emits (server.request, cache.lookup, queue.wait,
    batch.assembly, engine.dispatch, host.reduce), and compare against
    the measured p50. Reported in notes (ROADMAP invariant: < 2% of
    request latency) but NOT ANDed into the artifact gate — overhead is
    a budget to watch, not a serving-correctness property."""
    from deepdfa_tpu.obs import Tracer

    tracer = Tracer(proc="bench-overhead", max_spans=n + 16)
    t0 = time.perf_counter()
    for i in range(n):
        t = time.perf_counter()
        tracer.record("overhead.probe", t, t, i=i)
    per_span_ms = (time.perf_counter() - t0) / n * 1e3
    per_request_ms = per_span_ms * spans_per_request
    frac = (per_request_ms / p50_ms) if p50_ms else None
    return {
        "per_span_us": round(per_span_ms * 1e3, 3),
        "spans_per_request": spans_per_request,
        "per_request_ms": round(per_request_ms, 4),
        "fraction_of_p50": round(frac, 5) if frac is not None else None,
        "under_2pct": (frac < 0.02) if frac is not None else None,
    }


def _flight_overhead(p50_ms, events_per_request: int = 2, n: int = 2000):
    """Same budget probe as :func:`_trace_overhead`, for the crash flight
    recorder: time ``n`` raw ``record()`` calls on a throwaway ring, scale
    by the events a scoring request emits (the per-request record plus its
    share of batch/dispatch records), compare against the measured p50.
    Shares the trace plane's < 2% invariant-15 budget; reported, not
    gated."""
    from deepdfa_tpu.obs import FlightRecorder

    rec = FlightRecorder(capacity=256, proc="bench-overhead")
    t0 = time.perf_counter()
    for i in range(n):
        rec.record("overhead.probe", i=i, code=200, ms=1.0)
    per_event_ms = (time.perf_counter() - t0) / n * 1e3
    per_request_ms = per_event_ms * events_per_request
    frac = (per_request_ms / p50_ms) if p50_ms else None
    return {
        "per_event_us": round(per_event_ms * 1e3, 3),
        "events_per_request": events_per_request,
        "per_request_ms": round(per_request_ms, 4),
        "fraction_of_p50": round(frac, 5) if frac is not None else None,
        "under_2pct": (frac < 0.02) if frac is not None else None,
    }


def _run_phase(port: int, bodies: list[str], concurrency: int):
    """Closed loop: ``concurrency`` workers share one request list; each
    worker loops request → wait for response → next. Returns elapsed
    seconds and the number of non-200 responses."""
    import http.client

    next_i = {"i": 0}
    lock = threading.Lock()
    errors = {"n": 0}

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=90)
        while True:
            with lock:
                i = next_i["i"]
                if i >= len(bodies):
                    break
                next_i["i"] = i + 1
            try:
                conn.request("POST", "/score", body=bodies[i],
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status != 200:
                    with lock:
                        errors["n"] += 1
            except Exception:
                with lock:
                    errors["n"] += 1
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=90)
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, errors["n"]


def _run_phase_collect(port: int, bodies: list[str], concurrency: int):
    """Closed loop like :func:`_run_phase`, but parses every ``/score``
    response and records per-request client-side latency. Returns
    ``(elapsed_s, errors, results)`` where ``results`` is a list of
    ``(latency_ms, rows)`` — one entry per answered request."""
    import http.client

    next_i = {"i": 0}
    lock = threading.Lock()
    errors = {"n": 0}
    results: list[tuple[float, list[dict]]] = []

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
        while True:
            with lock:
                i = next_i["i"]
                if i >= len(bodies):
                    break
                next_i["i"] = i + 1
            try:
                t0 = time.perf_counter()
                conn.request("POST", "/score", body=bodies[i],
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = resp.read()
                lat_ms = (time.perf_counter() - t0) * 1e3
                if resp.status != 200:
                    with lock:
                        errors["n"] += 1
                    continue
                rows = json.loads(payload).get("results", [])
                with lock:
                    results.append((lat_ms, rows))
            except Exception:
                with lock:
                    errors["n"] += 1
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=180)
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, errors["n"], results


def _run_phase_admission(port: int, items: list[tuple[str, str]],
                         concurrency: int):
    """Closed loop like :func:`_run_phase`, but QoS-aware: ``items`` are
    ``(qos_class, body)`` pairs and the collector records a per-class
    histogram of response codes plus every 429 that arrived WITHOUT its
    Retry-After header — the raw material of the admission gates
    (``bench.assemble_admission_result``). A 429 is a shed doing its
    job, never an error; a transport failure is recorded as code 599 so
    it trips the zero-5xx gate honestly."""
    import http.client

    next_i = {"i": 0}
    lock = threading.Lock()
    responses: dict[str, dict[str, int]] = {}
    missing = {"n": 0}

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
        while True:
            with lock:
                i = next_i["i"]
                if i >= len(items):
                    break
                next_i["i"] = i + 1
            klass, body = items[i]
            try:
                conn.request("POST", "/score", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                code = resp.status
                retry_after = resp.getheader("Retry-After")
            except Exception:
                code, retry_after = 599, None
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=180)
            with lock:
                hist = responses.setdefault(klass, {})
                hist[str(code)] = hist.get(str(code), 0) + 1
                if code == 429 and retry_after is None:
                    missing["n"] += 1
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {
        "requests_total": len(items),
        "elapsed_s": round(time.perf_counter() - t0, 3),
        "responses": responses,
        "retry_after_missing": missing["n"],
    }


def _merge_admission_phase(acc: dict, part: dict) -> None:
    """Fold one replay lap's collector dict into the accumulated phase."""
    acc["requests_total"] += part["requests_total"]
    acc["elapsed_s"] = round(acc["elapsed_s"] + part["elapsed_s"], 3)
    acc["retry_after_missing"] += part["retry_after_missing"]
    for cls, codes in part["responses"].items():
        hist = acc["responses"].setdefault(cls, {})
        for code, cnt in codes.items():
            hist[code] = hist.get(code, 0) + cnt


def _run_phase_codes(port: int, bodies: list[str], concurrency: int):
    """Closed loop like :func:`_run_phase_admission`, classless: the
    collector is a flat response-code histogram plus every 429 that
    arrived WITHOUT its Retry-After header — the raw material of the
    federation gates (``bench.assemble_federation_result``). A transport
    failure is recorded as code 599 so it trips the zero-5xx gate
    honestly (the federation FRONT must never die; cells may)."""
    import http.client

    next_i = {"i": 0}
    lock = threading.Lock()
    codes: dict[str, int] = {}
    missing = {"n": 0}

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=180)
        while True:
            with lock:
                i = next_i["i"]
                if i >= len(bodies):
                    break
                next_i["i"] = i + 1
            try:
                conn.request("POST", "/score", body=bodies[i],
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                code = resp.status
                retry_after = resp.getheader("Retry-After")
            except Exception:
                code, retry_after = 599, None
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=180)
            with lock:
                codes[str(code)] = codes.get(str(code), 0) + 1
                if code == 429 and retry_after is None:
                    missing["n"] += 1
        conn.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {
        "requests_total": len(bodies),
        "elapsed_s": round(time.perf_counter() - t0, 3),
        "codes": codes,
        "retry_after_missing": missing["n"],
    }


def _merge_codes_phase(acc: dict, part: dict) -> None:
    acc["requests_total"] += part["requests_total"]
    acc["elapsed_s"] = round(acc["elapsed_s"] + part["elapsed_s"], 3)
    acc["retry_after_missing"] += part["retry_after_missing"]
    for code, cnt in part["codes"].items():
        acc["codes"][code] = acc["codes"].get(code, 0) + cnt


def _run_overload(ckpt, vocabs, base_sources, args, backend: str,
                  device_kind: str) -> dict:
    """The admission/brownout sawtooth (ISSUE 18, invariant candidate 30),
    three legs against ONE admission-enabled replica:

    1. **nominal** — interactive-only trickle (2 workers). The
       interactive burst covers the whole leg, so ZERO sheds is a hard
       gate, not a hope.
    2. **saturation** — ``ADMISSION_SATURATION_X`` × the nominal count,
       half batch, at full concurrency, replayed with fresh unique
       bodies every lap until the brownout ladder visibly escalates
       (bounded). The batch budget is deliberately tiny, so the batch
       class sheds first and keeps shedding — 429 + Retry-After,
       measured per response by the collector.
    3. **recovery** — the nominal bodies replayed (content-addressed
       cache hits: cheap, fast, admission-free) until the ladder steps
       back to 0 (bounded).

    Background samplers scrape ``/slo`` (burn seconds → the artifact's
    ``slo_burn_minutes``) and ``/healthz`` (max ``brownout_level`` seen
    mid-flight — the honesty gate: the endpoint must have reported the
    degradation while it was happening, not after)."""
    import http.client
    import re

    from bench import ADMISSION_SATURATION_X, assemble_admission_result

    from deepdfa_tpu.config import AdmissionConfig, ObsConfig

    n = max(8, args.requests // 2)
    sat = ADMISSION_SATURATION_X

    def _qos_bodies(offset: int, count: int, klass: str):
        return [(klass, json.dumps({
                    "source": _uniq_source(
                        base_sources[i % len(base_sources)], offset + i),
                    "class": klass}))
                for i in range(count)]

    # interactive budget effectively unbounded (the class must never
    # bucket-shed — "interactive sheds LAST" means only the ladder's
    # level 3 may touch it); batch budget tiny so saturation sheds it
    # immediately; short brownout hysteresis so the ladder moves within
    # the bench's bounded legs (same rationale as the autoscale stage's
    # short SLO windows).
    adm = AdmissionConfig(
        enabled=True,
        interactive_rate=500.0, interactive_burst=100_000.0,
        batch_rate=1.0, batch_burst=4.0,
        interactive_deadline_ms=120_000.0, batch_deadline_ms=1_000.0,
        brownout=True, burn_high=1.4, burn_low=0.8,
        up_consecutive=2, down_consecutive=4,
        cooldown_s=1.0, poll_interval_s=0.25, max_level=3)
    obs = ObsConfig(slo_p99_ms=100.0, slo_fast_window_s=2.0,
                    slo_slow_window_s=4.0)
    server = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms,
                          latency_window=64, obs=obs, admission=adm)
    server.warmup()
    server.start()

    alert_re = re.compile(r"slo_alert\{[^}]*\}\s+1(?:\.0*)?\s*$", re.M)
    alert = {"seconds": 0.0}
    health = {"level_max": 0, "green": 0, "samples": 0}
    sampler_stop = threading.Event()

    def _sample():
        period = 0.2
        while not sampler_stop.wait(period):
            try:
                conn = http.client.HTTPConnection(
                    "127.0.0.1", server.port, timeout=2.0)
                try:
                    conn.request("GET", "/slo")
                    slo_text = conn.getresponse().read().decode()
                finally:
                    conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", server.port, timeout=2.0)
                try:
                    conn.request("GET", "/healthz")
                    resp = conn.getresponse()
                    hz = json.loads(resp.read())
                    status = resp.status
                finally:
                    conn.close()
            except OSError:
                continue
            if alert_re.search(slo_text):
                alert["seconds"] += period
            health["samples"] += 1
            health["level_max"] = max(health["level_max"],
                                      int(hz.get("brownout_level") or 0))
            if status == 200 and hz.get("status") == "ok":
                health["green"] += 1

    threading.Thread(target=_sample, daemon=True).start()

    try:
        # leg 1 — nominal trickle
        nominal = _run_phase_admission(
            server.port, _qos_bodies(400_000, n, "interactive"),
            concurrency=2)

        # leg 2 — saturation, replayed until the ladder escalates
        overload = {"requests_total": 0, "elapsed_s": 0.0,
                    "responses": {}, "retry_after_missing": 0}
        lap, t_high = 0, time.perf_counter()
        while True:
            half = sat * n // 2
            inter = _qos_bodies(500_000 + lap * 10_000, half, "interactive")
            batch = _qos_bodies(700_000 + lap * 10_000, half, "batch")
            mixed = [item for pair in zip(inter, batch) for item in pair]
            _merge_admission_phase(
                overload,
                _run_phase_admission(server.port, mixed, args.concurrency))
            lap += 1
            escalated = (server.brownout is not None
                         and server.brownout.level >= 1)
            if escalated or time.perf_counter() - t_high > 25.0:
                break

        # leg 3 — recovery until the ladder steps back down (bounded)
        recovery_laps = 0
        t_low = time.perf_counter()
        while (server.brownout is not None and server.brownout.level > 0
               and time.perf_counter() - t_low < 30.0):
            _run_phase_admission(
                server.port, _qos_bodies(400_000, n, "interactive"),
                concurrency=2)
            recovery_laps += 1
        recovered_level = (server.brownout.level
                           if server.brownout is not None else None)
    finally:
        sampler_stop.set()
        snap = server.shutdown()

    return assemble_admission_result(
        backend=backend, device_kind=device_kind, saturation_x=sat,
        nominal=nominal, overload=overload,
        admission=snap.get("admission") or {},
        brownout=snap.get("brownout") or {},
        slo_burn_minutes=alert["seconds"] / 60.0,
        healthz_brownout_level_max=health["level_max"],
        notes={
            "nominal_requests": n,
            "overload_laps": lap,
            "recovery_laps": recovery_laps,
            "recovered_level": recovered_level,
            "healthz_samples": health["samples"],
            "healthz_green_samples": health["green"],
            "slo_p99_ms": obs.slo_p99_ms,
            "interactive_rate": adm.interactive_rate,
            "batch_rate": adm.batch_rate,
            "batch_burst": adm.batch_burst,
        })


def _build_tier2(max_batch: int):
    """Hermetic tier-2 joint engine for the cascade stage: tiny-LLM +
    HashTokenizer, fresh fusion params, text-only (``use_gnn=False`` keeps
    the bench independent of the demo corpus's graph feature schema — the
    routing/latency contract under test does not care which branch the
    fusion head reads). The REAL ``JointEngine.score`` path: tokenize,
    pad to ``max_batch``, jitted trainer ``eval_step``."""
    import jax
    import numpy as np

    from deepdfa_tpu.config import FeatureConfig, GGNNConfig
    from deepdfa_tpu.llm.dataset import HashTokenizer
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig
    from deepdfa_tpu.llm.joint_engine import JointEngine
    from deepdfa_tpu.llm.llama import LlamaModel, tiny_llama

    jcfg = JointConfig(block_size=128)
    llm_cfg = tiny_llama(vocab_size=512)
    tokenizer = HashTokenizer(vocab_size=llm_cfg.vocab_size)
    llm = LlamaModel(llm_cfg)
    llm_params = llm.init(
        jax.random.key(0), np.zeros((2, jcfg.block_size), np.int32)
    )["params"]
    fusion = FusionModel(
        gnn_cfg=GGNNConfig(), input_dim=FeatureConfig().input_dim,
        llm_hidden_size=llm_cfg.hidden_size, use_gnn=False,
        dropout_rate=0.1, pool="last")
    fusion_params = JointEngine._template_params(
        llm, llm_params, fusion, jcfg, 512, 1024)
    engine = JointEngine(llm, llm_params, fusion, fusion_params, tokenizer,
                         jcfg, max_batch=max_batch, max_nodes=512,
                         max_edges=1024)
    engine.warmup()
    return engine


def _run_cascade(ckpt, vocabs, bodies, args, backend: str,
                 device_kind: str) -> dict:
    """The two-phase cascade stage. Phase A is the no-cascade baseline —
    it doubles as the tier-1 score ORACLE: the engine is deterministic, so
    phase A's scores are exactly the tier-1 scores phase B will produce,
    and placing the band at their 30th/70th percentiles makes the expected
    escalation fraction the band's measured mass (analytic, not guessed).
    Phase B replays the identical load with the cascade enabled and gates
    the measured escalation fraction, zero degradations, and the tier-1
    p50 (client-side latency of requests no row of which escalated)
    against phase A's same-instrument p50."""
    import numpy as np

    from bench import assemble_cascade_result

    from deepdfa_tpu.config import CascadeConfig

    # phase A — baseline + oracle
    server = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms)
    server.warmup()
    server.start()
    try:
        _, err_a, res_a = _run_phase_collect(
            server.port, bodies, args.concurrency)
    finally:
        server.shutdown()
    scores = [r["vulnerable_probability"] for _, rows in res_a for r in rows
              if "vulnerable_probability" in r]
    baseline_p50 = (float(np.percentile([lat for lat, _ in res_a], 50))
                    if res_a else None)
    # the band edges land ON score mass points (they are quantiles of the
    # observed scores); widen by 1e-6 — past the rows' round(prob, 6)
    # radius — so a boundary score cannot flip membership between the
    # oracle (rounded rows) and phase B's in_band check (unrounded probs)
    lo = float(np.quantile(scores, 0.30)) - 1e-6
    hi = float(np.quantile(scores, 0.70)) + 1e-6
    lo = min(max(lo, 0.0), 1.0 - 1e-6)
    hi = min(max(hi, lo + 1e-6), 1.0)
    expected = float(np.mean([lo <= s <= hi for s in scores]))

    # phase B — same load, cascade on, band at the measured quantiles.
    # Nominal run: the deadline/queue bounds are generous on purpose —
    # the gate asserts ZERO degradations, so the bounds must not be the
    # thing that trips (test_cascade.py owns the degradation paths).
    tier2 = _build_tier2(args.max_batch)
    ccfg = CascadeConfig(
        enabled=True, band_lo=lo, band_hi=hi,
        tier2_max_batch=args.max_batch, tier2_max_wait_ms=args.max_wait_ms,
        tier2_max_queue=max(256, 4 * args.requests),
        tier2_deadline_ms=120_000.0)
    server = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms,
                          cascade=ccfg, tier2_engine=tier2)
    server.warmup()
    server.start()
    try:
        _, err_b, res_b = _run_phase_collect(
            server.port, bodies, args.concurrency)
    finally:
        snap = server.shutdown()

    # count tiers CLIENT-SIDE from the rows, not from the server snapshot:
    # the scan cache replays a repeated body's stored rows (tier
    # attribution preserved) without re-escalating, so the snapshot's
    # escalated_total is unique-bodies-only while expected_frac is row
    # mass over the whole load — rows are the commensurate instrument
    rows_b = [r for _, rows in res_b for r in rows
              if "vulnerable_probability" in r]
    escalated_rows = sum(1 for r in rows_b
                         if r.get("tier") == 2 or r.get("tier2_degraded"))
    answered2_rows = sum(1 for r in rows_b if r.get("tier") == 2)
    t1_lats = [lat for lat, rows in res_b
               if rows and all(r.get("tier") != 2 and not r.get("tier2_degraded")
                               for r in rows)]
    answered = snap.get("cascade_answered") or {}
    return assemble_cascade_result(
        backend=backend, device_kind=device_kind, band=(lo, hi),
        expected_frac=expected,
        escalated_total=escalated_rows,
        answered_tier2=answered2_rows,
        degraded_total=snap.get("cascade_degraded_total", 0),
        requests_total=len(rows_b),
        tier1_p50_ms=(float(np.percentile(t1_lats, 50)) if t1_lats else None),
        baseline_p50_ms=baseline_p50,
        tier2_p50_ms=snap.get("tier2_latency_p50_ms"),
        tier2_p99_ms=snap.get("tier2_latency_p99_ms"),
        errors_total=err_a + err_b,
        notes={
            "n_scored_baseline": len(scores),
            "n_tier1_only_requests": len(t1_lats),
            "snap_escalated_total": snap.get("cascade_escalated_total", 0),
            "snap_answered_tier2": answered.get(2, 0),
            "tier2_queue_wait_p99_ms": snap.get("tier2_queue_wait_p99_ms"),
            "tier2_dispatch_p99_ms": snap.get("tier2_dispatch_p99_ms"),
            "tier2_model_rev": tier2.model_rev,
            "tier2_block_size": tier2.cfg.block_size,
            "tier2_use_gnn": False,
        })


def _run_frontend(ckpt, vocabs, base_sources, args, backend: str,
                  device_kind: str) -> dict:
    """The frontend encode-pool stage, three phases on cold (unique-body)
    load so every request pays the full frontend:

    A. **inline baseline** — a default (``mode="inline"``) server, cold
       replay → ``inline_requests_per_sec``;
    B. **pool** — a pool-enabled server, same-shaped cold load →
       ``pool_requests_per_sec``, the pool's encode intervals intersected
       with the batcher's dispatch intervals (same wall clock) →
       ``overlap_frac``, and the encode/queue-wait reservoirs. The
       ≥ 0.75×N scaling gate only binds when the host actually has the
       cores (``host_cpus >= workers``) — on a 1-CPU host the artifact
       records the honest ratio with ``scaling_ok: null``;
    C. **degradation chaos** — the pool is killed (``stop(drain=False)``)
       mid-load on the SAME server; every remaining request must still
       answer 200 via inline fallback (``frontend_inline_total`` > 0
       proves the fallback ran) and /healthz stays green — standing
       invariant 25, measured through real HTTP."""
    import http.client
    import os

    from bench import assemble_frontend_result, overlap_fraction

    from deepdfa_tpu.config import FrontendConfig

    n = args.requests

    def _bodies(offset: int) -> list[str]:
        return [json.dumps({"source": _uniq_source(
                    base_sources[i % len(base_sources)], offset + i)})
                for i in range(n)]

    # phase A — inline baseline (the default ServeConfig frontend)
    server = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms)
    server.warmup()
    server.start()
    try:
        inline_s, err_a = _run_phase(
            server.port, _bodies(100_000), args.concurrency)
    finally:
        server.shutdown()

    fcfg = FrontendConfig(mode=args.frontend_mode,
                          workers=args.frontend_workers)
    server = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms,
                          frontend=fcfg)
    server.warmup()
    server.start()
    pool_report = deg = None
    health_green = False
    try:
        # phase B — pool-fronted cold load
        pool_s, err_b = _run_phase(
            server.port, _bodies(200_000), args.concurrency)
        enc_intervals = server.frontend.encode_intervals()
        dis_intervals = server.metrics.dispatch_interval_list()

        # phase C — kill the pool mid-load; the rest must answer inline
        deg_bodies = _bodies(300_000)
        deg = {"elapsed": None, "errors": len(deg_bodies)}

        def _deg_phase():
            s, e = _run_phase(server.port, deg_bodies, args.concurrency)
            deg.update(elapsed=s, errors=e)

        t = threading.Thread(target=_deg_phase, daemon=True)
        t.start()
        time.sleep(0.05)  # let the first requests enter through the pool
        server.frontend.stop(drain=False)
        t.join(timeout=600.0)

        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        try:
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            health = json.loads(resp.read())
            health_green = (resp.status == 200
                            and health.get("status") == "ok")
        finally:
            conn.close()
        pool_report = server.frontend.report()
    finally:
        snap = server.shutdown()

    overlap = overlap_fraction(enc_intervals, dis_intervals)
    return assemble_frontend_result(
        backend=backend, device_kind=device_kind, mode=fcfg.mode,
        n_workers=fcfg.workers, host_cpus=os.cpu_count(),
        inline_rps=(n / inline_s if inline_s > 0 else None),
        pool_rps=(n / pool_s if pool_s > 0 else None),
        encode_p50_ms=snap.get("frontend_encode_p50_ms"),
        encode_p99_ms=snap.get("frontend_encode_p99_ms"),
        queue_wait_ms=snap.get("frontend_queue_wait_p50_ms"),
        overlap_frac=overlap,
        requests_total=2 * n,
        errors_total=err_a + err_b,
        degraded_requests_total=len(deg_bodies),
        degraded_errors_total=deg["errors"],
        degraded_inline_total=snap.get("frontend_inline_total", 0),
        degraded_health_green=health_green,
        notes={
            "inline_elapsed_s": round(inline_s, 3),
            "pool_elapsed_s": round(pool_s, 3),
            "degraded_elapsed_s": (None if deg["elapsed"] is None
                                   else round(deg["elapsed"], 3)),
            "encode_intervals": len(enc_intervals),
            "dispatch_intervals": len(dis_intervals),
            "queue_wait_p99_ms": snap.get("frontend_queue_wait_p99_ms"),
            "pool_report": pool_report,
            "healthz_frontend": health.get("frontend"),
        })


def _run_fleet(ckpt, vocabs, bodies, args, single_cold_rps: float,
               warm_store_dir, backend: str, device_kind: str,
               baseline_warm: dict) -> dict:
    """The fleet topology end-to-end: N fresh replicas warm-load the
    bucket ladder from the store the baseline populated (zero cold
    compiles), a consistent-hash router fronts them, and a cold +
    ``load_x``× hot replay drives the whole thing closed-loop through the
    router. Returns the ``assemble_fleet_result`` block."""
    import tempfile

    from bench import assemble_fleet_result

    from deepdfa_tpu.resilience.journal import RunJournal
    from deepdfa_tpu.serve import FleetRouter, WarmStore

    store = WarmStore(warm_store_dir)
    jdir = Path(tempfile.mkdtemp(prefix="deepdfa-fleet-journal-"))
    servers, journals, reports = [], [], []
    for i in range(args.fleet):
        # per-replica journal files: RunJournal is single-record
        # (last write wins), and each replica's warmup must stay auditable
        journal = RunJournal(jdir / f"replica{i}.json")
        srv = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms,
                           warm_store=store, journal=journal,
                           replica_id=f"replica{i}")
        reports.append(srv.warmup())
        srv.start()
        servers.append(srv)
        journals.append(journal)
    join_cold_compiles = sum(r["misses"] for r in reports)
    # the acceptance criterion is compile-seconds-saved JOURNALED, so read
    # it back from the journal files, not the in-memory reports
    journaled_saved = 0.0
    for journal in journals:
        rec = journal.read() or {}
        if rec.get("event") == "warmup":
            journaled_saved += float(rec.get("compile_seconds_saved") or 0.0)

    router = FleetRouter([f"127.0.0.1:{s.port}" for s in servers], port=0,
                         probe_interval_s=args.probe_interval_s)
    try:
        router.start()  # initial probe registers every warm replica
        probe_states = {b.name: b.state for b in router.backends.values()}
        cold_s, cold_err = _run_phase(router.port, bodies, args.concurrency)
        hot_bodies = bodies * args.load_x
        hot_s, hot_err = _run_phase(router.port, hot_bodies,
                                    args.concurrency)
    finally:
        rsnap = router.shutdown()
        snaps = [s.shutdown() for s in servers]

    per_replica = {}
    for srv, snap in zip(servers, snaps):
        name = f"127.0.0.1:{srv.port}"
        per_replica[srv.replica_id] = {
            "forwarded": rsnap["forwarded_total"].get(name, 0),
            "requests_total": snap["requests_total"],
            "cache_hits": snap["cache"].get("hits", 0),
            "mean_batch_occupancy": snap.get("mean_batch_occupancy"),
        }
    shard_cache_hits = sum(r["cache_hits"] for r in per_replica.values())
    return assemble_fleet_result(
        backend=backend, device_kind=device_kind, n_replicas=args.fleet,
        single_cold_rps=single_cold_rps,
        fleet_cold_rps=len(bodies) / cold_s if cold_s > 0 else None,
        aggregate_p50_ms=rsnap.get("latency_p50_ms"),
        aggregate_p99_ms=rsnap.get("latency_p99_ms"),
        per_replica=per_replica,
        shard_cache_hits=shard_cache_hits,
        join_cold_compiles=join_cold_compiles,
        compile_seconds_saved=journaled_saved,
        load_x=args.load_x,
        errors_total=cold_err + hot_err + rsnap["no_backend_total"],
        notes={
            "hot_requests_per_sec": (round(len(hot_bodies) / hot_s, 2)
                                     if hot_s > 0 else None),
            "baseline_warmup": {k: baseline_warm[k] for k in
                                ("hits", "misses", "compile_seconds_saved")},
            "join_warmups": [{k: r[k] for k in
                              ("hits", "misses", "compile_seconds_saved")}
                             for r in reports],
            "warm_store": store.stats(),
            "probe_states": probe_states,
            "router_retries": rsnap["retries_total"],
        })


def _run_autoscale(ckpt, vocabs, bodies, args, warm_store_dir, backend: str,
                   device_kind: str) -> dict:
    """The closed-loop actuator end-to-end: an SLO-driven autoscaler
    supervises warm-joining in-process replicas behind the router while
    the load sawtooths 10x (trickle → ``load_x``× replay → trickle) and a
    chaos kill lands mid-load. The ``autoscale`` block gates on the chaos
    criteria: replacement within ``replace_deadline_s`` with zero join
    compiles, SLO burn minutes within budget, no spawn give-ups, zero
    client-visible errors beyond the failover window, and every scale
    decision recorded in the artifact."""
    import re
    import tempfile

    from bench import assemble_autoscale_result

    from deepdfa_tpu.config import AutoscaleConfig, ObsConfig
    from deepdfa_tpu.obs import FlightRecorder
    from deepdfa_tpu.resilience import faults
    from deepdfa_tpu.resilience.journal import RunJournal
    from deepdfa_tpu.serve import Autoscaler, FleetRouter, WarmStore

    acfg = AutoscaleConfig(
        enabled=True, min_replicas=2, max_replicas=args.autoscale,
        poll_interval_s=0.5, burn_high=1.4, burn_low=0.8,
        up_consecutive=2, down_consecutive=4, cooldown_s=3.0,
        replace_deadline_s=args.replace_deadline_s, spawn_attempts=3,
        spawn_backoff_s=0.2)
    # short SLO windows + a small latency reservoir so the burn signal
    # tracks the sawtooth instead of the whole run's history; the p99
    # target sits between the trickle and saturated latency so the 10x
    # leg reads burn > burn_high and the trickle leg burn < burn_low
    obs = ObsConfig(slo_p99_ms=60.0, slo_fast_window_s=2.0,
                    slo_slow_window_s=4.0)
    store = WarmStore(warm_store_dir)
    jdir = Path(tempfile.mkdtemp(prefix="deepdfa-autoscale-"))

    class _Replica:
        """In-process stand-in for SubprocessReplica (same handle duck
        type). ``kill()`` is the in-process analogue of ``kill -9``: the
        listening socket closes abruptly, new connections are refused,
        the router fails the keyspace over."""

        def __init__(self, server, report):
            self.server = server
            self.host = "127.0.0.1"
            self.port = server.port
            self.name = f"127.0.0.1:{server.port}"
            self.join_cold_compiles = report["misses"]
            self._exit = None

        def poll(self):
            return self._exit

        def drain(self):
            threading.Thread(target=self.server.shutdown,
                             daemon=True).start()

        def kill(self):
            self._exit = 137
            try:
                self.server.httpd.shutdown()
                self.server.httpd.server_close()
            except OSError:
                pass

    class _Launcher:
        def __init__(self):
            self.spawned = 0

        def spawn(self):
            i = self.spawned
            self.spawned += 1
            journal = RunJournal(jdir / f"replica{i}.json")
            srv = _make_server(ckpt, vocabs, args.max_batch,
                               args.max_wait_ms, warm_store=store,
                               journal=journal, replica_id=f"auto{i}",
                               latency_window=64, obs=obs)
            report = srv.warmup()  # warm join: store hits, zero compiles
            srv.start()
            return _Replica(srv, report)

    router = FleetRouter([], port=0, probe_interval_s=0.25,
                         allow_empty=True)
    router.start(probe=True)
    flight = FlightRecorder(capacity=256, proc="autoscaler",
                            dump_dir=str(jdir))
    launcher = _Launcher()
    scaler = Autoscaler(acfg, router, launcher,
                        journal=RunJournal(jdir / "autoscaler.json"),
                        flight=flight)

    # burn sampler: accumulate wall time while any ready replica's /slo
    # exposes a firing alert — the artifact's slo_burn_minutes
    alert_re = re.compile(r"slo_alert\{[^}]*\}\s+1(?:\.0*)?\s*$", re.M)
    alert = {"seconds": 0.0}
    sampler_stop = threading.Event()

    def _sample_alerts():
        import http.client

        period = 0.25
        while not sampler_stop.wait(period):
            _, body = router.admin_backends()
            firing = False
            for name, info in body["backends"].items():
                if info.get("state") != "ready":
                    continue
                host, _, port = name.rpartition(":")
                try:
                    conn = http.client.HTTPConnection(host, int(port),
                                                      timeout=2.0)
                    try:
                        conn.request("GET", "/slo")
                        text = conn.getresponse().read().decode()
                    finally:
                        conn.close()
                except OSError:
                    continue
                if alert_re.search(text):
                    firing = True
                    break
            if firing:
                alert["seconds"] += period

    threading.Thread(target=_sample_alerts, daemon=True).start()

    errors_total = 0
    try:
        scaler.start()  # spawns min_replicas warm joiners synchronously

        # sawtooth leg 1 — trickle (replay, 2 workers)
        _, err = _run_phase(router.port, bodies, concurrency=2)
        errors_total += err

        # sawtooth leg 2a — load_x× replay at full concurrency until the
        # burn streak grows the fleet (bounded; one replay lasts about a
        # second, shorter than streak × poll interval, so repeat it)
        high_bodies = bodies * args.load_x
        high = {"elapsed": 0.0, "requests": 0}
        burn_scale_up = False
        t_high = time.perf_counter()
        while time.perf_counter() - t_high < 20.0:
            s, e = _run_phase(router.port, high_bodies, args.concurrency)
            high["elapsed"] += s
            high["requests"] += len(high_bodies)
            errors_total += e
            if any(d.get("reason") == "burn_high"
                   for d in scaler.summary()["decisions"]):
                burn_scale_up = True
                break

        # sawtooth leg 2b — the chaos kill lands mid-load on one more
        # high replay
        def _high_phase():
            s, e = _run_phase(router.port, high_bodies, args.concurrency)
            high["elapsed"] += s
            high["requests"] += len(high_bodies)
            high["errors"] = e

        high_thread = threading.Thread(target=_high_phase, daemon=True)
        high_thread.start()
        time.sleep(2 * acfg.poll_interval_s)  # let the queue build
        faults.install("autoscale.replica_crash@1")  # next poll kills one
        deadline = time.perf_counter() + acfg.replace_deadline_s + 10.0
        while time.perf_counter() < deadline:
            if scaler.summary()["replacements"] > 0:
                break
            time.sleep(0.1)
        faults.clear()
        high_thread.join(timeout=600.0)
        errors_total += high.get("errors", 0)

        # sawtooth leg 3 — trickle until the loop scales back down
        # (bounded: cooldown + down_consecutive polls)
        t_low = time.perf_counter()
        while time.perf_counter() - t_low < 30.0:
            _, err = _run_phase(router.port, bodies[:8], concurrency=1)
            errors_total += err
            if any(d["action"] == "scale_down"
                   for d in scaler.summary()["decisions"]):
                break
    finally:
        faults.clear()
        sampler_stop.set()
        summary = scaler.stop(drain=True)
        rsnap = router.shutdown()
    errors_total += rsnap["no_backend_total"]

    return assemble_autoscale_result(
        backend=backend, device_kind=device_kind,
        min_replicas=acfg.min_replicas, max_replicas=acfg.max_replicas,
        replace_deadline_s=acfg.replace_deadline_s, summary=summary,
        slo_burn_minutes=alert["seconds"] / 60.0,
        errors_total=errors_total,
        notes={
            "low_requests": len(bodies),
            "high_requests": high["requests"],
            "load_x": args.load_x,
            "burn_scale_up": burn_scale_up,
            "high_requests_per_sec": (
                round(high["requests"] / high["elapsed"], 2)
                if high.get("elapsed") else None),
            "router_retries": rsnap["retries_total"],
            "no_backend_total": rsnap["no_backend_total"],
            "replicas_spawned": launcher.spawned,
            "journal_dir": str(jdir),
        })


def _run_federation(ckpt, vocabs, base_sources, args, warm_store_dir,
                    backend: str, device_kind: str) -> dict:
    """The cell-killed sawtooth (ISSUE 20, invariant candidate 32):
    N complete cells — each ONE warm-joined replica behind its own
    :class:`~deepdfa_tpu.serve.FleetRouter` — behind one live
    :class:`~deepdfa_tpu.serve.FederationRouter`, five legs:

    1. **nominal** — trickle through the federation; sticky routing,
       zero sheds, zero 5xx.
    2. **cell kill** — ``federation.cell_kill`` SIGKILLs one whole cell
       (replica + router sockets) from the federation's own probe loop
       while a ``load_x``× replay runs; survivors absorb the dead cell's
       keyspace (the spillover counters are the evidence) and the lap
       repeats until a survivor's brownout ladder visibly escalates.
    3. **promotion refused** — a :class:`PromotionController` aimed at
       the cells is asked to roll mid-brownout; the brownout gate must
       refuse (journaled ``promotion_transition``, ROADMAP direction 1
       residual).
    4. **heal** — a replacement replica warm-joins from the shared store
       (zero cold compiles) behind a fresh cell router, and the cell
       rejoins the federation through the readiness gate; the recovery
       clock runs from the kill to ready.
    5. **recovery + promotion completes** — a trickle drains the
       brownout ladder back to 0, then the SAME promotion (fresh
       controller, same gates) rolls a real candidate rev across the
       healed cell — staged warm, ``join_cold_compiles == 0``."""
    import tempfile

    import jax

    from bench import assemble_federation_result

    from deepdfa_tpu.config import (
        AdmissionConfig,
        FederationConfig,
        ObsConfig,
    )
    from deepdfa_tpu.continual import PromotionController, stage_candidate
    from deepdfa_tpu.continual.shadow import SCHEMA as SHADOW_SCHEMA
    from deepdfa_tpu.obs.slo import write_alerts_artifact
    from deepdfa_tpu.resilience import faults
    from deepdfa_tpu.resilience.journal import RunJournal
    from deepdfa_tpu.serve import FederationRouter, FleetRouter, WarmStore
    from deepdfa_tpu.serve.engine import ScoringEngine

    n_cells = args.federation
    store = WarmStore(warm_store_dir)
    jdir = Path(tempfile.mkdtemp(prefix="deepdfa-federation-"))
    # the overload stage's admission shape: generous interactive budget
    # (sheds come from the ladder, not the bucket), short brownout
    # hysteresis + short SLO windows so the ladder tracks the sawtooth
    adm = AdmissionConfig(
        enabled=True,
        interactive_rate=500.0, interactive_burst=100_000.0,
        batch_rate=1.0, batch_burst=4.0,
        interactive_deadline_ms=120_000.0, batch_deadline_ms=1_000.0,
        brownout=True, burn_high=1.4, burn_low=0.8,
        up_consecutive=2, down_consecutive=4,
        cooldown_s=1.0, poll_interval_s=0.25, max_level=3)
    obs = ObsConfig(slo_p99_ms=100.0, slo_fast_window_s=2.0,
                    slo_slow_window_s=4.0)

    class _Replica:
        """In-process replica handle (the autoscale stage's duck type);
        ``kill()`` closes the listening socket abruptly — kill -9."""

        def __init__(self, server, report, replica_id):
            self.server = server
            self.host = "127.0.0.1"
            self.port = server.port
            self.name = f"127.0.0.1:{server.port}"
            self.replica_id = replica_id
            self.join_cold_compiles = report["misses"]
            self._exit = None

        def poll(self):
            return self._exit

        def drain(self):
            threading.Thread(target=self.server.shutdown,
                             daemon=True).start()

        def kill(self):
            self._exit = 137
            try:
                self.server.httpd.shutdown()
                self.server.httpd.server_close()
            except OSError:
                pass

    spawned = {"n": 0}

    def _spawn_replica(ckpt_for, tag):
        i = spawned["n"]
        spawned["n"] += 1
        srv = _make_server(ckpt_for, vocabs, args.max_batch,
                           args.max_wait_ms, warm_store=store,
                           journal=RunJournal(jdir / f"{tag}{i}.json"),
                           replica_id=f"{tag}{i}", latency_window=64,
                           obs=obs, admission=adm)
        report = srv.warmup()  # warm join off the shared store
        srv.start()
        return _Replica(srv, report, f"{tag}{i}")

    class _CellLauncher:
        """PromotionController-facing launcher: spawns a replica of one
        rev into the HEALED cell (the roll's target)."""

        def __init__(self, ckpt_for, tag):
            self.ckpt_for = ckpt_for
            self.tag = tag
            self.handles = []

        def spawn(self):
            h = _spawn_replica(self.ckpt_for, self.tag)
            self.handles.append(h)
            return h

    # ---- stand up N cells + the federation front
    cells: dict[str, dict] = {}
    for i in range(n_cells):
        replica = _spawn_replica(ckpt, f"cell{i}r")
        router = FleetRouter([], port=0, probe_interval_s=0.2,
                             allow_empty=True)
        router.start(probe=True)
        router.add_backend(replica.name)
        cells[f"127.0.0.1:{router.port}"] = {
            "router": router, "replicas": [replica], "index": i}

    kill_info = {"t": None, "victim": None}

    def _kill_hook(name):
        cell = cells.get(name)
        if cell is None:
            return
        kill_info["t"] = time.perf_counter()
        kill_info["victim"] = name
        for r in cell["replicas"]:
            r.kill()
        try:
            cell["router"].httpd.shutdown()
            cell["router"].httpd.server_close()
        except OSError:
            pass

    fcfg = FederationConfig(
        enabled=True, vnodes=16, probe_interval_s=0.2,
        spill_brownout_level=1, spill_queue_wait_p99_ms=5000.0,
        spill_burn_high=2.0, drain_deadline_s=5.0, retry_after_floor_s=1)
    fed = FederationRouter(cells=list(cells), cfg=fcfg,
                           kill_hook=_kill_hook)
    fed.start(probe=True)

    def _live_brownout_max():
        level = 0
        for name, cell in cells.items():
            if name == kill_info["victim"]:
                continue
            for r in cell["replicas"]:
                if r.poll() is None and r.server.brownout is not None:
                    level = max(level, r.server.brownout.level)
        return level

    bodies = [json.dumps({"source": _uniq_source(
                  base_sources[i % len(base_sources)], 800_000 + i),
                  "class": "interactive"})
              for i in range(max(8, args.requests // 2))]
    cell_addrs = list(cells)
    alerts = write_alerts_artifact(jdir / "alerts.json", [])
    shadow_report = {"schema": SHADOW_SCHEMA, "pass": True,
                     "max_psi": 0.0, "max_abs_delta": 0.01,
                     "synthetic": "bench_serving --federation"}

    # the candidate rev: same architecture, perturbed params — a REAL,
    # distinct model_rev whose warm ladder is staged before the roll
    ckpt_cand = dict(ckpt)
    ckpt_cand["params"] = jax.tree.map(
        lambda x: x * (1 + 1e-6), ckpt["params"])

    def _controller(name):
        return PromotionController(
            _roll_router(), cand_launcher, prior_launcher,
            candidate_rev=cand_rev, prior_rev=prior_rev,
            alerts_path=alerts,
            journal=RunJournal(jdir / f"decisions_{name}.json"),
            state_journal=RunJournal(jdir / f"state_{name}.json"),
            brownout_targets=lambda: cell_addrs,
            brownout_pause_timeout_s=5.0,
            drift_settle_polls=2, poll_interval_s=0.1,
            join_timeout_s=60.0)

    error = None
    nominal = killed = recovery = None
    cell_kill_recovery_s = None
    rejoined = False
    join_cold = 0
    refused_during_brownout = False
    completed_after = False
    heal_router = None
    cand_launcher = prior_launcher = None
    fsnap = {}
    try:
        # ---- leg 1: nominal trickle
        nominal = _run_phase_codes(fed.port, bodies, concurrency=2)

        # ---- leg 2: load_x× load in two movements. First saturate the
        # live fleet until the federation visibly spills (one cell's
        # ladder escalates → its keyspace prefers the least-burned
        # sibling); THEN arm federation.cell_kill so the probe loop
        # SIGKILLs a whole cell mid-replay and the survivors absorb its
        # keyspace. Both movements land in the same ``killed`` phase —
        # the gate reads one histogram: zero 5xx through all of it.
        killed = {"requests_total": 0, "elapsed_s": 0.0, "codes": {},
                  "retry_after_missing": 0}
        high = bodies * args.load_x
        t_high = time.perf_counter()
        while True:
            _merge_codes_phase(
                killed, _run_phase_codes(fed.port, high, args.concurrency))
            snap = fed.metrics.snapshot()
            if int(snap.get("spillover_total") or 0) >= 1 \
                    or time.perf_counter() - t_high > 20.0:
                break
        faults.install("federation.cell_kill@1")
        t_kill = time.perf_counter()
        while True:
            _merge_codes_phase(
                killed, _run_phase_codes(fed.port, high, args.concurrency))
            if kill_info["victim"] is not None \
                    and (_live_brownout_max() >= 1
                         or time.perf_counter() - t_kill > 25.0):
                break
            if time.perf_counter() - t_kill > 40.0:
                break
        faults.clear()
        brownout_seen = _live_brownout_max()

        # ---- leg 3: a promotion attempted mid-brownout must be REFUSED
        # by the brownout gate (before the shadow gate even runs)
        prior_rev = None
        for cell in cells.values():
            for r in cell["replicas"]:
                if r.poll() is None:
                    prior_rev = r.server.engine.model_rev
        cand_engine = ScoringEngine.from_model(
            ckpt_cand["model"], ckpt_cand["params"],
            ckpt_cand["label_style"], feat_keys=ckpt_cand["feat_keys"],
            max_batch=args.max_batch, vocab_hash=ckpt_cand["vocab_hash"])
        cand_rev = cand_engine.model_rev
        cand_launcher = _CellLauncher(ckpt_cand, "cand")
        prior_launcher = _CellLauncher(ckpt, "prior")

        def _roll_router():
            return (heal_router if heal_router is not None
                    else next(iter(cells.values()))["router"])

        pc = _controller("refusal")
        refusal = pc.check_gates(shadow_report)
        refused_during_brownout = (
            refusal is not None and refusal.get("gate") == "brownout"
            and brownout_seen >= 1)

        # ---- leg 4: heal — replacement replica warm-joins behind a
        # fresh cell router, the cell rejoins through the readiness gate
        heal_replica = _spawn_replica(ckpt, "heal")
        join_cold += heal_replica.join_cold_compiles
        heal_router = FleetRouter([], port=0, probe_interval_s=0.2,
                                  allow_empty=True)
        heal_router.start(probe=True)
        heal_router.add_backend(heal_replica.name)
        victim = kill_info["victim"]
        if victim is not None:
            fed.remove_cell(victim)
            old = cells.pop(victim)
            heal_name = f"127.0.0.1:{heal_router.port}"
            cells[heal_name] = {"router": heal_router,
                                "replicas": [heal_replica],
                                "index": old["index"]}
            cell_addrs = list(cells)
            cell = fed.add_cell(heal_name)
            deadline = time.perf_counter() + 30.0
            while cell.state != "ready" \
                    and time.perf_counter() < deadline:
                time.sleep(0.1)
                fed.probe_once()
            rejoined = cell.state == "ready"
            if rejoined and kill_info["t"] is not None:
                cell_kill_recovery_s = time.perf_counter() - kill_info["t"]

        # ---- leg 5a: recovery trickle until the ladder drains
        recovery = {"requests_total": 0, "elapsed_s": 0.0, "codes": {},
                    "retry_after_missing": 0}
        t_low = time.perf_counter()
        while _live_brownout_max() > 0 \
                and time.perf_counter() - t_low < 30.0:
            _merge_codes_phase(
                recovery, _run_phase_codes(fed.port, bodies, concurrency=2))
        if not recovery["requests_total"]:
            _merge_codes_phase(
                recovery, _run_phase_codes(fed.port, bodies, concurrency=2))

        # ---- leg 5b: the SAME promotion now completes — staged warm,
        # rolled replica-by-replica across the healed cell
        stage_candidate(cand_engine, store)
        roll = _controller("roll")
        for h in ([heal_replica] if rejoined else []):
            roll.adopt(h)
        roll_summary = roll.promote(shadow_report)
        join_cold += int(roll_summary.get("join_cold_compiles") or 0)
        completed_after = bool(roll_summary.get("completed"))
    except Exception as exc:  # noqa: BLE001 — the artifact records the
        # failure; the gate turns it into ok=False
        error = f"{type(exc).__name__}: {exc}"
    finally:
        faults.clear()
        fsnap = fed.shutdown()
        for cell in cells.values():
            try:
                cell["router"].shutdown()
            except Exception:  # noqa: BLE001 — the killed cell's router
                # is already gone
                pass
            for r in cell["replicas"]:
                try:
                    r.kill()
                except Exception:  # noqa: BLE001
                    pass
        for launcher in (cand_launcher, prior_launcher):
            for h in getattr(launcher, "handles", None) or []:
                try:
                    h.kill()
                except Exception:  # noqa: BLE001
                    pass

    return assemble_federation_result(
        backend=backend, device_kind=device_kind, n_cells=n_cells,
        nominal=nominal, killed=killed, recovery=recovery,
        federation=fsnap,
        cell_kill_recovery_s=cell_kill_recovery_s,
        rejoined=rejoined, join_cold_compiles=join_cold,
        promotion_refused_during_brownout=refused_during_brownout,
        promotion_completed_after=completed_after,
        notes={
            "victim": kill_info["victim"],
            "load_x": args.load_x,
            "replicas_spawned": spawned["n"],
            "journal_dir": str(jdir),
            "spill_brownout_level": fcfg.spill_brownout_level,
        },
        error=error)


def main(argv=None) -> dict:
    import argparse
    import tempfile

    from bench import assemble_serve_result

    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64,
                    help="unique requests in the cold phase (the hot phase "
                    "replays all of them)")
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=25.0)
    ap.add_argument("--corpus", type=int, default=12,
                    help="distinct demo-corpus base functions")
    ap.add_argument("--tier-requests", type=int, default=16,
                    help="single-graph dispatches per bucket tier for the "
                    "per-precision p50/p99 table (0 disables)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="N>=2: after the single-replica baseline, stand up "
                    "N router-fronted replicas that warm-load from the "
                    "store and drive cold + load-x hot through the router")
    ap.add_argument("--load-x", type=int, default=10, dest="load_x",
                    help="hot-phase load multiplier for the fleet run "
                    "(aggregate p99 is gated at this multiple)")
    ap.add_argument("--warm-store", default=None, dest="warm_store",
                    help="warm-start store dir (default: a fresh tempdir — "
                    "pass a path to measure cross-process joins)")
    ap.add_argument("--probe-interval", type=float, default=2.0,
                    dest="probe_interval_s")
    ap.add_argument("--autoscale", type=int, default=0,
                    help="N>=2: run the SLO-driven autoscaler sawtooth "
                    "stage (2..N replicas, chaos kill mid-load, "
                    "warm-join replacement gated on the replace deadline)")
    ap.add_argument("--replace-deadline", type=float, default=30.0,
                    dest="replace_deadline_s",
                    help="serve.autoscale.replace_deadline_s for the "
                    "--autoscale stage")
    ap.add_argument("--frontend", action="store_true",
                    help="run the frontend encode-pool stage: inline "
                    "baseline vs pool cold throughput, encode-dispatch "
                    "overlap fraction, and a pool-kill degradation phase "
                    "(every request answered via inline fallback, "
                    "/healthz green)")
    ap.add_argument("--frontend-workers", type=int, default=2,
                    dest="frontend_workers",
                    help="serve.frontend.workers for the --frontend stage")
    ap.add_argument("--frontend-mode", default="process",
                    choices=("process", "thread"), dest="frontend_mode",
                    help="serve.frontend.mode for the --frontend stage")
    ap.add_argument("--overload", action="store_true",
                    help="run the admission/brownout sawtooth stage: an "
                    "admission-enabled replica takes a nominal trickle, a "
                    "10x-saturation mixed interactive+batch leg, and a "
                    "recovery trickle; gates the explicit-overload "
                    "contract (429+Retry-After sheds, zero 5xx, batch "
                    "first, interactive last, honest /healthz)")
    ap.add_argument("--federation", type=int, default=0,
                    help="N>=2: run the multi-cell federation sawtooth — N "
                    "complete cells (replica + cell router) behind a "
                    "FederationRouter, one cell SIGKILLed mid-load by the "
                    "federation.cell_kill fault; gates zero client 5xx, "
                    "spillover served, warm cell rejoin, and the "
                    "promotion brownout gate (refused during, completes "
                    "after)")
    ap.add_argument("--cascade", action="store_true",
                    help="run the two-tier cascade stage: a no-cascade "
                    "baseline phase doubles as the tier-1 score oracle, "
                    "then the same load replays with the borderline band "
                    "at the scores' 30th/70th percentiles feeding a "
                    "hermetic tier-2 joint engine")
    args = ap.parse_args(argv)
    if args.fleet == 1:
        ap.error("--fleet needs N >= 2 (the baseline IS the single replica)")
    if args.autoscale == 1:
        ap.error("--autoscale needs N >= 2 (min_replicas is 2)")
    if args.federation == 1:
        ap.error("--federation needs N >= 2 (one cell cannot spill over)")

    from bench import start_on_device

    backend, device_kind = start_on_device()
    cfg, vocabs, base_sources = _build_corpus(args.corpus)
    ckpt = _build_ckpt(cfg, vocabs)
    bodies = [
        json.dumps({"source": _uniq_source(base_sources[i % len(base_sources)], i)})
        for i in range(args.requests)
    ]

    warm_store = journal0 = warm_dir = None
    if args.fleet or args.autoscale or args.federation:
        from deepdfa_tpu.resilience.journal import RunJournal
        from deepdfa_tpu.serve import WarmStore

        warm_dir = args.warm_store or tempfile.mkdtemp(
            prefix="deepdfa-warmstore-")
        warm_store = WarmStore(warm_dir)
        journal0 = RunJournal(Path(warm_dir) / "baseline-journal.json")

    server = _make_server(ckpt, vocabs, args.max_batch, args.max_wait_ms,
                          warm_store=warm_store, journal=journal0,
                          replica_id="baseline")
    try:
        baseline_warm = server.warmup()  # fleet runs: populates the store
        server.start()
        cold_s, cold_err = _run_phase(server.port, bodies, args.concurrency)
        hot_s, hot_err = _run_phase(server.port, bodies, args.concurrency)
    finally:
        snap = server.shutdown()

    fleet = None
    if args.fleet:
        fleet = _run_fleet(ckpt, vocabs, bodies, args,
                           single_cold_rps=len(bodies) / cold_s,
                           warm_store_dir=warm_dir, backend=backend,
                           device_kind=device_kind,
                           baseline_warm=baseline_warm)

    autoscale = None
    if args.autoscale:
        autoscale = _run_autoscale(ckpt, vocabs, bodies, args,
                                   warm_store_dir=warm_dir, backend=backend,
                                   device_kind=device_kind)

    cascade = None
    if args.cascade:
        cascade = _run_cascade(ckpt, vocabs, bodies, args, backend=backend,
                               device_kind=device_kind)

    frontend = None
    if args.frontend:
        frontend = _run_frontend(ckpt, vocabs, base_sources, args,
                                 backend=backend, device_kind=device_kind)

    admission = None
    if args.overload:
        admission = _run_overload(ckpt, vocabs, base_sources, args,
                                  backend=backend, device_kind=device_kind)

    federation = None
    if args.federation:
        federation = _run_federation(ckpt, vocabs, base_sources, args,
                                     warm_store_dir=warm_dir,
                                     backend=backend,
                                     device_kind=device_kind)

    tiers = tier_precision = tier_refusal = None
    if args.tier_requests > 0:
        tiers, tier_precision, tier_refusal = _precision_tiers(
            ckpt, args.max_batch, args.tier_requests)

    total = 2 * len(bodies)
    elapsed = cold_s + hot_s
    cache = snap["cache"]
    result = assemble_serve_result(
        backend=backend,
        device_kind=device_kind,
        requests_per_sec=total / elapsed if elapsed > 0 else 0.0,
        p50_ms=snap.get("latency_p50_ms"),
        p99_ms=snap.get("latency_p99_ms"),
        mean_batch_occupancy=snap.get("mean_batch_occupancy"),
        cache_hit_rate=cache.get("hit_rate"),
        cache_hits=cache.get("hits", 0),
        requests_total=total,
        errors_total=cold_err + hot_err,
        concurrency=args.concurrency,
        fleet=fleet,
        autoscale=autoscale,
        cascade=cascade,
        frontend=frontend,
        admission=admission,
        federation=federation,
        notes={
            "cold_requests_per_sec": round(len(bodies) / cold_s, 2),
            "hot_requests_per_sec": round(len(bodies) / hot_s, 2),
            "batches_total": snap.get("batches_total"),
            "batch_graphs_total": snap.get("batch_graphs_total"),
            "max_batch": args.max_batch,
            "max_wait_ms": args.max_wait_ms,
            "baseline_warmup": {k: baseline_warm[k] for k in
                                ("hits", "misses", "compile_seconds_saved")},
            "queue_wait_ms": {"p50": snap.get("queue_wait_p50_ms"),
                              "p99": snap.get("queue_wait_p99_ms")},
            "dispatch_ms": {"p50": snap.get("dispatch_p50_ms"),
                            "p99": snap.get("dispatch_p99_ms")},
            "trace_overhead": _trace_overhead(snap.get("latency_p50_ms")),
            "flight_overhead": _flight_overhead(snap.get("latency_p50_ms")),
            "precision_tiers": tiers,
            "tier_precision_served": tier_precision,
            "int8_refused_reason": tier_refusal,
        },
    )
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    # the JSON line carries the measured numbers; the exit code carries ok
    raise SystemExit(0 if main()["ok"] else 1)
