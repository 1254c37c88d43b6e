"""The telemetry plane: W3C traceparent propagation, the bounded span
buffer, Chrome trace-event export, the shared metrics registry (ONE
exposition formatter for serve / router / trainer), the score-drift
sentinel, and training-step telemetry. Everything here is device-free —
stub engines, no XLA compiles — so ``pytest -m obs`` runs in seconds and
is wired into scripts/lint_gate.py."""

import contextlib
import gc
import json
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# traceparent + tracer core


def test_traceparent_roundtrip():
    from deepdfa_tpu.obs import SpanContext, parse_traceparent

    ctx = SpanContext("ab" * 16, "cd" * 8)
    header = ctx.traceparent()
    assert header == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(header)
    assert back == ctx
    assert parse_traceparent(SpanContext("ef" * 16, "01" * 8,
                                         sampled=False).traceparent()
                             ).sampled is False


def test_traceparent_rejects_malformed():
    from deepdfa_tpu.obs import parse_traceparent

    bad = [
        None, "", "not-a-header",
        "00-" + "g" * 32 + "-" + "ab" * 8 + "-01",      # non-hex trace
        "00-" + "ab" * 16 + "-" + "cd" * 8,             # missing flags
        "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",     # forbidden version
        "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",      # all-zero trace id
        "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",     # all-zero span id
    ]
    for header in bad:
        assert parse_traceparent(header) is None, header
    # case-insensitive per spec: uppercase hex still parses
    up = ("00-" + "AB" * 16 + "-" + "CD" * 8 + "-01")
    assert parse_traceparent(up).trace_id == "ab" * 16


def test_tracer_nesting_and_bounded_buffer():
    from deepdfa_tpu.obs import Tracer

    tracer = Tracer(proc="t", max_spans=4)
    with tracer.span("outer", root=True) as outer:
        assert tracer.current() == outer.ctx
        with tracer.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    assert tracer.current() is None
    spans = tracer.spans(outer.trace_id)
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    for i in range(10):  # bounded: old traces fall off the back
        tracer.record(f"s{i}", time.time())
    assert len(tracer) == 4
    assert tracer.recorded_total == 12


def test_tracer_clocks_and_profiler_annotations():
    """A span keeps its wall-clock start and takes its duration from
    perf_counter; with an ``annotation`` class every ``span`` is also put on
    the profiler's clock as ``deepdfa:<name>``."""
    from deepdfa_tpu.obs import Tracer

    seen = []

    class Annotation:
        def __init__(self, name, **attrs):
            self.row = [name, attrs]
            seen.append(self.row)

        def __enter__(self):
            self.row.append("in")

        def __exit__(self, *exc):
            self.row.append("out")

    tracer = Tracer(proc="t", max_spans=2, annotation=Annotation)
    assert tracer.capacity == 2 and tracer.current_span() is None
    before = time.time()
    with tracer.span("step.dispatch", step=7) as sp:
        assert tracer.current_span() is sp
        tracer.current_span().attrs["rows"] = 16  # set by whoever runs inside
        time.sleep(0.02)
    assert before <= sp.start_s <= time.time()
    assert 0.02 <= sp.dur_s < 1.0
    assert sp.attrs == {"step": 7, "rows": 16}
    assert sp.to_record()["tid"] == sp.tid
    assert seen == [["deepdfa:step.dispatch", {"step": 7}, "in", "out"]]
    # a span told after the fact cannot be backdated on the profiler's
    # clock: it goes to the host ring only
    tracer.record("jit.lower", 100.0, 100.25, name="train_step")
    assert len(seen) == 1
    assert tracer.spans()[-1].attrs == {"name": "train_step"}
    assert tracer.spans()[-1].dur_s == 0.25
    # no annotation class (the router, the server): host spans only
    plain = Tracer(proc="t")
    with plain.span("x"):
        pass
    assert plain.annotation is None and len(plain) == 1


def test_process_wide_train_telemetry_is_one_bounded_ring():
    import jax

    from deepdfa_tpu import obs
    from deepdfa_tpu.obs.telemetry import RING_SPANS

    t = obs.train_telemetry()
    assert obs.train_telemetry() is t
    assert t.tracer.capacity == RING_SPANS == 16_384
    assert t.tracer.annotation is jax.profiler.TraceAnnotation
    assert t.tracer.exemplar_dir is None and t.slo is None and t.flight is None
    # compile events reach every live telemetry, not only the newest
    mine = obs.TrainTelemetry()
    before = t.snapshot()["compiles"]
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.01)
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert t.snapshot()["compiles"] == before + 1
    assert mine.snapshot()["compiles"] == 1 and mine.snapshot()["cache_misses"] == 1


# ---------------------------------------------------------------------------
# exposition conformance — the ONE checker all three endpoints must pass

_TYPE_RE = re.compile(r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) "
                      r"(counter|gauge|histogram)$")
_HELP_RE = re.compile(r"^# HELP ([a-zA-Z_:][a-zA-Z0-9_:]*) \S")
_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                        r"(\{[^{}]*\})? (\S+)$")


def _assert_exposition(text: str) -> None:
    """Prometheus text-format v0.0.4 conformance: HELP then TYPE exactly
    once per family, every sample belongs to a declared family (histogram
    suffixes allowed), values parse, no duplicate (name, labels) sample."""
    assert text.endswith("\n"), "exposition must end with a newline"
    declared: dict[str, str] = {}
    helped: set[str] = set()
    samples: set[tuple] = set()
    for line in text.splitlines():
        assert line == line.strip(), f"stray whitespace: {line!r}"
        if line.startswith("# HELP "):
            m = _HELP_RE.match(line)
            assert m, f"malformed HELP: {line!r}"
            assert m.group(1) not in helped, f"duplicate HELP {m.group(1)}"
            helped.add(m.group(1))
        elif line.startswith("# TYPE "):
            m = _TYPE_RE.match(line)
            assert m, f"malformed TYPE: {line!r}"
            name, kind = m.groups()
            assert name not in declared, f"duplicate TYPE for {name}"
            assert name in helped, f"TYPE before HELP for {name}"
            declared[name] = kind
        else:
            m = _SAMPLE_RE.match(line)
            assert m, f"malformed sample: {line!r}"
            name, labels, value = m.groups()
            family = name
            for suffix in ("_bucket", "_sum", "_count"):
                base = name[: -len(suffix)] if name.endswith(suffix) else None
                if base and declared.get(base) == "histogram":
                    family = base
            assert family in declared, f"undeclared family for {line!r}"
            float(value)  # +Inf / integers / floats all parse
            key = (name, labels or "")
            assert key not in samples, f"duplicate sample {key}"
            samples.add(key)
    assert declared and samples


def _populated_serve_metrics():
    from deepdfa_tpu.obs import ScoreDriftSentinel, Tracer
    from deepdfa_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    for code, lat in ((200, 5.0), (200, 9.0), (400, 1.0), (422, 2.0)):
        m.inc("requests_total")
        m.observe_response(code, lat)
    m.observe_batch(n_real=3, capacity=4)
    m.queue_wait.observe(0.5)
    m.queue_wait.observe(1.5)
    m.dispatch.observe(2.0)
    m.tracer = Tracer(proc="test")
    m.tracer.record("x", time.time())
    m.drift = ScoreDriftSentinel(window=8, bins=4, min_samples=2)
    for s in (0.1, 0.2, 0.8, 0.9):
        m.drift.observe(s, "rev-a")
    return m


def test_serve_exposition_conformance_and_single_type_per_family():
    m = _populated_serve_metrics()
    text = m.render(cache_stats={"hits": 1, "encode_hits": 0, "misses": 3,
                                 "evictions": 0, "entries": 3,
                                 "hit_rate": 0.25})
    _assert_exposition(text)
    # the regression this PR fixes: labeled families (quantile gauges,
    # per-code counters) must declare HELP/TYPE once, not once per sample
    assert text.count("# TYPE deepdfa_serve_latency_ms ") == 1
    assert text.count('deepdfa_serve_latency_ms{quantile="0.5"}') == 1
    assert text.count('deepdfa_serve_latency_ms{quantile="0.99"}') == 1
    assert text.count("# TYPE deepdfa_serve_responses_total ") == 1
    assert 'deepdfa_serve_responses_total{code="200"} 2' in text
    assert "# TYPE deepdfa_serve_queue_wait_ms gauge" in text
    assert "# TYPE deepdfa_serve_dispatch_ms gauge" in text
    assert 'deepdfa_serve_score_drift{model_rev="rev-a"}' in text
    assert 'deepdfa_serve_score_bucket{model_rev="rev-a",le="+Inf"} 4' in text


def test_router_exposition_conformance():
    from deepdfa_tpu.obs import Tracer
    from deepdfa_tpu.serve.router import RouterMetrics

    m = RouterMetrics()
    m.inc("requests_total")
    m.observe_forward("127.0.0.1:1")
    m.observe_forward("127.0.0.1:2")
    m.latency.observe(3.0)
    m.latency.observe(7.0)
    m.inc("retries_total")
    m.tracer = Tracer(proc="router")
    text = m.render()
    _assert_exposition(text)
    assert text.count("# TYPE deepdfa_router_forwarded_total ") == 1
    assert 'deepdfa_router_forwarded_total{backend="127.0.0.1:1"} 1' in text


def test_train_exposition_conformance():
    from deepdfa_tpu.obs import TrainTelemetry

    import jax.monitoring

    t = TrainTelemetry()
    t.observe_epoch(0)
    t.observe_step(0.01, 0.02, 0.25)
    t.observe_step(0.01, 0.02, 0.25)
    # a compile as jax reports it when it is over (no XLA compile here)
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.5, fun_name="step")
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    with t.tracer.span("batch.build"):
        pass
    text = t.render()
    _assert_exposition(text)
    assert "deepdfa_train_steps_total 2" in text
    assert "deepdfa_train_compiles_total 1" in text
    assert "deepdfa_train_compile_cache_hits_total 1" in text
    assert "deepdfa_train_loss_sync_seconds_total 0.5" in text
    assert "deepdfa_train_prefetch_build_seconds_total " in text
    assert "deepdfa_train_prefetch_h2d_seconds_total 0" in text


def test_registry_label_escaping_and_histogram_cumulation():
    from deepdfa_tpu.obs import MetricsRegistry

    reg = MetricsRegistry("x_")
    g = reg.gauge("g", "gauge with hostile labels", labels=("who",))
    g.set(1, who='a"b\\c\nd')
    h = reg.histogram("h", "histogram", buckets=(1.0, 5.0))
    for v in (0.5, 3.0, 10.0):
        h.observe(v)
    text = reg.render()
    _assert_exposition(text)
    assert r'x_g{who="a\"b\\c\nd"} 1' in text
    assert 'x_h_bucket{le="1"} 1' in text     # cumulative, not per-bucket
    assert 'x_h_bucket{le="5"} 2' in text
    assert 'x_h_bucket{le="+Inf"} 3' in text
    assert "x_h_sum 13.5" in text and "x_h_count 3" in text
    with pytest.raises(ValueError):
        reg.counter("g", "kind mismatch on an existing family")


# ---------------------------------------------------------------------------
# drift sentinel


def test_drift_sentinel_quiet_on_reference_flips_on_shift():
    from deepdfa_tpu.obs import ScoreDriftSentinel

    sent = ScoreDriftSentinel(window=64, bins=10, threshold=0.2,
                              min_samples=32)
    low = [((i % 40) + 1) / 100 for i in range(64)]   # scores in (0, 0.41]
    for s in low:
        sent.observe(s, "rev-1")                      # freezes the reference
    for s in low:
        sent.observe(s, "rev-1")                      # same shape again
    snap = sent.snapshot()["rev-1"]
    assert snap["ready"] is True
    assert snap["alert"] is False and snap["psi"] < 0.1
    for i in range(64):                                # distribution walks
        sent.observe(0.6 + ((i % 40) + 1) / 100, "rev-1")
    snap = sent.snapshot()["rev-1"]
    assert snap["alert"] is True and snap["psi"] > 0.25
    assert snap["n_observed"] == 192
    # a cold rev never alerts, whatever it scores
    sent.observe(0.99, "rev-cold")
    assert sent.snapshot()["rev-cold"]["alert"] is False


def test_psi_symmetric_properties():
    from deepdfa_tpu.obs import psi

    assert psi([10, 10, 10], [10, 10, 10]) == pytest.approx(0.0)
    assert psi([30, 0, 0], [0, 0, 30]) > 1.0
    with pytest.raises(ValueError):
        psi([1, 2], [1, 2, 3])


# ---------------------------------------------------------------------------
# training telemetry


def test_train_telemetry_windows_and_server_scrape():
    from deepdfa_tpu.obs import TelemetryServer, TrainTelemetry

    import jax.monitoring

    t = TrainTelemetry()
    t.observe_epoch(3)
    t.observe_step(0.010, 0.030)
    t.observe_step(0.005, 0.015, 0.060)
    for event, secs in (("jaxpr_trace_duration", 0.1),
                        ("jaxpr_to_mlir_module_duration", 0.2),
                        ("backend_compile_duration", 0.3)):
        jax.monitoring.record_event_duration_secs(
            f"/jax/core/compile/{event}", secs, fun_name="train_step")
    jax.monitoring.record_event_duration_secs("/jax/other/event", 9.0)
    # the jnp calls inside a traced function: thousands, microseconds, no span
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 2e-5, fun_name="add")
    epoch = t.epoch_stats()                 # drains the window...
    assert epoch["steps"] == 2 and epoch["compiles"] == 1
    assert epoch["data_wait_frac"] == pytest.approx(0.125, abs=0.01)
    assert epoch["sync_s"] == pytest.approx(0.060)
    again = t.epoch_stats()                 # ...which resets
    assert again["steps"] == 0 and again["compiles"] == 0
    snap = t.snapshot()                     # cumulative view unaffected
    assert snap["steps"] == 2 and snap["epoch"] == 3 and snap["compiles"] == 1
    # each event is a span [now - duration, now] carrying jax's fun_name
    jit = {s.name: s for s in t.tracer.spans() if s.name.startswith("jit.")}
    assert sorted(jit) == ["jit.backend_compile", "jit.lower", "jit.trace"]
    assert jit["jit.lower"].dur_s == pytest.approx(0.2)
    assert jit["jit.lower"].attrs == {"fun_name": "train_step"}

    srv = TelemetryServer(t, port=0).start()
    try:
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        text = resp.read().decode()
        assert resp.status == 200
        _assert_exposition(text)
        assert "deepdfa_train_steps_total 2" in text
        conn.request("GET", "/healthz")
        health = json.loads(conn.getresponse().read())
        assert health["ok"] is True and health["role"] == "trainer"
        assert health["steps"] == 2
        conn.close()
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# what a slow run is made of: on-CPU time, the collector's pauses, intervals


@contextlib.contextmanager
def _no_automatic_collections():
    """Only the collections a test forces run (and call the callbacks)."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


@pytest.mark.parametrize("kind", ["sleeping", "spinning"])
def test_a_span_keeps_its_threads_cpu_time_beside_its_wall_time(kind):
    from deepdfa_tpu.obs import Tracer

    tracer = Tracer(proc="t")
    with tracer.span("step.dispatch", step=1) as sp:
        if kind == "sleeping":
            time.sleep(0.1)
        else:
            c0 = time.thread_time()
            while time.thread_time() - c0 < 0.03:
                pass
    assert sp.attrs == {"step": 1}  # a field of the span, not an attribute
    # (room for a CPU clock that ticks in 10 ms, as the chip's host has)
    assert 0.0 <= sp.cpu_s <= sp.dur_s + 0.011
    if kind == "sleeping":
        assert sp.dur_s >= 0.1 and sp.cpu_s < 0.5 * sp.dur_s
    else:
        assert sp.cpu_s >= 0.03


def test_a_told_interval_has_no_cpu_time_and_an_exemplar_round_trips_cpu_ms(tmp_path):
    from deepdfa_tpu.obs import Tracer, chrome_trace, load_trace_records

    tracer = Tracer(proc="t", slow_ms=0.0, exemplar_dir=tmp_path)
    told = tracer.record("jit.lower", 100.0, 100.25)
    assert told.cpu_s is None and told.to_record()["cpu_ms"] is None
    timed = tracer.record("gc.pause", 100.0, 100.004, cpu_s=0.0035, generation=2)
    assert timed.cpu_s == 0.0035 and timed.attrs == {"generation": 2}
    assert timed.to_record()["cpu_ms"] == 3.5
    with tracer.span("train.epoch", root=True) as root:
        with tracer.span("step.dispatch", step=0):
            time.sleep(0.002)
        tracer.record("jit.trace", time.time() - 0.001, parent=root.ctx)
    (rec,) = load_trace_records(tmp_path)
    by_name = {s["name"]: s for s in rec["spans"]}
    assert set(by_name) == {"train.epoch", "step.dispatch", "jit.trace"}
    assert by_name["jit.trace"]["cpu_ms"] is None
    for name in ("train.epoch", "step.dispatch"):
        live = next(s for s in tracer.spans() if s.name == name)
        assert by_name[name]["cpu_ms"] == round(live.cpu_s * 1e3, 4) <= by_name[name]["dur_ms"] + 11
    # the timeline shows it where the span has it
    args = {e["name"]: e["args"] for e in chrome_trace(rec["spans"])["traceEvents"]
            if e["ph"] == "X"}
    assert args["step.dispatch"]["cpu_ms"] == by_name["step.dispatch"]["cpu_ms"]
    assert "cpu_ms" not in args["jit.trace"] and args["step.dispatch"]["step"] == 0


def test_a_forced_collection_inside_an_open_span_is_one_gc_pause():
    from deepdfa_tpu.obs import Tracer, TrainTelemetry

    t = TrainTelemetry(tracer=Tracer(proc="train", max_spans=64))
    with _no_automatic_collections():
        before = t.snapshot()
        with t.tracer.span("step.dispatch", step=5) as call:
            cycle = []
            cycle.append(cycle)
            del cycle
            gc.collect()
        after = t.snapshot()
    (pause,) = [s for s in t.tracer.spans() if s.name == "gc.pause"]
    assert pause.attrs["generation"] == 2 and pause.attrs["step"] == 5
    assert isinstance(pause.attrs["collected"], int) and pause.attrs["collected"] >= 1
    assert set(pause.attrs) == {"generation", "collected", "step"}
    assert pause.parent_id == call.span_id and pause.trace_id == call.trace_id
    assert pause.tid == call.tid and 0 <= pause.cpu_s <= pause.dur_s + 0.011
    assert call.start_s - 1e-3 <= pause.start_s
    assert pause.start_s + pause.dur_s <= call.start_s + call.dur_s + 1e-3
    assert after["gc_n"] == before["gc_n"] + 1
    assert after["gc_s"] == pytest.approx(before["gc_s"] + pause.dur_s, abs=2e-6)
    # outside any span: a pause all the same, with no parent and no step; and it
    # takes no hit of a chaos schedule, which counts the program's own spans
    from deepdfa_tpu.resilience import faults

    with _no_automatic_collections(), faults.installed("obs.trace_drop:p=1") as armed:
        gc.collect()
        assert armed.counters()["hits"] == {}
    lone = t.tracer.spans()[-1]
    assert lone.name == "gc.pause" and lone.parent_id is None and "step" not in lone.attrs
    assert t.tracer.dropped_total == 0


def test_short_collections_are_tallied_and_leave_no_span():
    from deepdfa_tpu.obs import Tracer, TrainTelemetry
    from deepdfa_tpu.obs.telemetry import MIN_GC_SPAN_S

    t = TrainTelemetry(tracer=Tracer(proc="train", max_spans=64))
    with _no_automatic_collections():
        for _ in range(5):
            gc.collect(0)
    stats = t.epoch_stats()
    assert stats["gc_n"] == 5 and 0 < stats["gc_s"] < 5.0
    assert t.epoch_stats()["gc_n"] == 0 and t.snapshot()["gc_n"] == 5  # window and lifetime
    # (a young collection on a starved machine may take a millisecond: then it is a span)
    assert all(s.dur_s >= MIN_GC_SPAN_S for s in t.tracer.spans())
    n = len(t.tracer)
    t.observe_gc(100.0, MIN_GC_SPAN_S * 0.99, 1e-4, generation=1, collected=7)
    assert len(t.tracer) == n and t.snapshot()["gc_n"] == 6
    t.observe_gc(100.0, MIN_GC_SPAN_S, 1e-3, generation=0, collected=7)   # long
    t.observe_gc(101.0, 1e-5, 1e-5, generation=2, collected=0)            # full
    assert [s.attrs["generation"] for s in t.tracer.spans()[n:]] == [0, 2]
    text = t.render()
    _assert_exposition(text)
    assert "deepdfa_train_gc_collections_total 8" in text
    assert "deepdfa_train_gc_pause_seconds_total " in text


def test_one_gc_callback_a_process_whatever_its_telemetries_do(monkeypatch):
    import weakref

    from deepdfa_tpu.obs import Tracer, TrainTelemetry, telemetry

    mine = [TrainTelemetry(tracer=Tracer(proc="train", max_spans=8)) for _ in range(3)]
    assert gc.callbacks.count(telemetry._on_gc) == 1
    assert all(t in telemetry._live() for t in mine)

    def boom(*args, **kwargs):
        raise RuntimeError("telemetry")

    # a raising telemetry fails neither the collection nor the others' account of it
    monkeypatch.setattr(mine[0], "observe_gc", boom)
    with _no_automatic_collections():
        assert isinstance(gc.collect(), int)
    assert [t.snapshot()["gc_n"] for t in mine] == [0, 1, 1]
    # a dead one is dropped: nothing holds it but the listeners' weak set
    dead = weakref.ref(mine.pop())
    gc.collect()
    assert dead() is None and len([t for t in telemetry._live() if t in mine]) == 2
    # a stop with no start (registered between a collection's two calls) is nothing
    telemetry._on_gc("stop", {"generation": 2, "collected": 0, "uncollectable": 0})
    telemetry._on_gc("start", {})  # nor is a malformed call
    telemetry._collecting = None


def _loop(t, n, sleep_s=0.004, alone=()):
    """What ``JointTrainer`` does at each loss read, ``n`` times."""
    for k in range(n):
        with t.tracer.span("loss.sync", step=k, alone=int(k in alone)) as sp:
            time.sleep(sleep_s)
            t.observe_read(sp, alone=k in alone)
        t.observe_step(0.001, 0.002, sp.dur_s)


def test_intervals_between_reads_reach_the_span_the_epoch_and_the_gauge():
    from deepdfa_tpu.obs import Tracer, TrainTelemetry

    t = TrainTelemetry(tracer=Tracer(proc="train", max_spans=64))
    t.observe_epoch(0)
    with _no_automatic_collections():
        _loop(t, 5, alone={2})
        gc.collect(0)
        _loop(t, 2)
    syncs = [s for s in t.tracer.spans() if s.name == "loss.sync"]
    # absent on the first read and on the one after the lone read; the mark
    # outlives whatever else the host does (here a collection) until a lone
    # read or a new epoch
    assert ["interval_s" in s.attrs for s in syncs] == [False, True, True, False, True, True, True]
    for s in syncs:
        assert ("gc_s" in s.attrs) == ("gc_n" in s.attrs) == ("interval_s" in s.attrs)
    assert [s.attrs["gc_n"] for s in syncs if "gc_n" in s.attrs] == [0, 0, 0, 1, 0]
    assert syncs[5].attrs["gc_s"] > 0 == syncs[6].attrs["gc_s"]
    assert all(s.attrs["interval_s"] >= 0.004 for s in syncs if "interval_s" in s.attrs)
    last = syncs[-1].attrs["interval_s"]
    assert f"deepdfa_train_last_step_seconds {round(last, 6)}" in t.render().splitlines()
    stats = t.epoch_stats()
    assert stats["steps"] == 7 and stats["stalls"] == 0 and stats["gc_n"] == 1
    assert 4.0 <= stats["interval_p50_ms"] <= stats["interval_max_ms"]
    assert stats["interval_max_ms"] == round(1e3 * max(
        s.attrs["interval_s"] for s in syncs if "interval_s" in s.attrs), 4)
    assert "interval_p50_ms" not in t.epoch_stats()  # the window was taken
    # a new epoch's first read ends no interval
    t.observe_epoch(1)
    _loop(t, 1)
    assert "interval_s" not in t.tracer.spans()[-1].attrs
    # a loop that reads no loss: the gauge is the host's time of its last step
    t.observe_step(0.25, 0.5)
    assert "deepdfa_train_last_step_seconds 0.75" in t.render().splitlines()


def _ring(step_s, n=40, stalls=()):
    """A hand-made ring of ``n`` steps of ``step_s``: ``data.wait`` 1 ms, a
    ``step.dispatch`` of 10 ms (8 on the CPU), then ``loss.sync`` to the end
    of the step. ``stalls`` = ``{step: (extra seconds, span name or None)}``:
    the extra time sits in the read, under a span of that name if given."""
    from deepdfa_tpu.obs import Tracer

    tracer = Tracer(proc="train", max_spans=4096)
    root = tracer.record("train.epoch", 1000.0, 1000.0, root=True, epoch=0)
    at = 1000.0
    for k in range(n):
        extra, name = dict(stalls).get(k, (0.0, None))
        tracer.record("data.wait", at, at + 0.001, parent=root.ctx, step=k)
        tracer.record("step.dispatch", at + 0.001, at + 0.011, parent=root.ctx,
                      cpu_s=0.008, step=k)
        end = at + step_s + extra
        attrs = {"interval_s": end - at, "gc_s": 0.0, "gc_n": 0} if k else {}
        sync = tracer.record("loss.sync", at + 0.011, end, parent=root.ctx,
                             cpu_s=0.0002, step=k, reads=1, alone=0, **attrs)
        if name == "gc.pause":
            tracer.record(name, at + 0.02, at + 0.02 + extra, parent=sync.ctx,
                          cpu_s=extra, generation=2, collected=9, step=k)
        elif name is not None:
            tracer.record(name, at + 0.02, at + 0.02 + extra, parent=sync.ctx, step=k)
        at = end
    return tracer


def test_the_summariser_tells_a_uniformly_slow_run_from_stalls_and_names_their_causes():
    from deepdfa_tpu.obs import interval_stats, step_cadence

    assert interval_stats([]) == {"steps": 0} == step_cadence([])
    healthy = step_cadence(_ring(0.100).spans())
    slow = step_cadence(_ring(0.112).spans())
    for run in (healthy, slow):
        assert run["steps"] == 39 and run["stalls"] == 0 and run["stall_share"] == 0.0
        assert run["causes"] == {} and run["worst"] == []
    # every step longer: the median is what differs
    assert healthy["interval_p50_ms"] == pytest.approx(100.0, abs=0.01)
    assert slow["interval_p50_ms"] == pytest.approx(112.0, abs=0.01)
    stalled = step_cadence(_ring(0.100, stalls={
        7: (0.120, "gc.pause"), 19: (0.300, "jit.backend_compile"), 31: (0.080, None)}).spans())
    assert stalled["interval_p50_ms"] == pytest.approx(100.0, abs=0.01)
    assert stalled["interval_max_ms"] == pytest.approx(400.0, abs=0.01)
    assert stalled["stalls"] == 3
    assert stalled["stall_share"] == pytest.approx(100 * 0.5 / (39 * 0.1 + 0.5), abs=0.01)
    assert [(s["step"], s["cause"]) for s in stalled["worst"]] == [
        (19, "jit"), (7, "gc.pause"), (31, "device")]
    assert [s["excess_ms"] for s in stalled["worst"]] == pytest.approx([300, 120, 80], abs=0.01)
    assert stalled["causes"] == {
        "jit": {"n": 1, "excess_ms": pytest.approx(300, abs=0.01)},
        "gc.pause": {"n": 1, "excess_ms": pytest.approx(120, abs=0.01)},
        "device": {"n": 1, "excess_ms": pytest.approx(80, abs=0.01)}}
    # the median, less what the stalls took, is the rate
    mean_ms = (39 * 100 + 500) / 39
    assert stalled["interval_p50_ms"] / (1 - stalled["stall_share"] / 100) == pytest.approx(
        mean_ms, rel=1e-3)


def test_the_summariser_splits_a_long_call_by_its_cpu_clock_and_reads_an_exemplar():
    from deepdfa_tpu.obs import step_cadence

    def ring(cpu_s):
        tracer = _ring(0.050, n=20)
        spans = tracer.spans()
        # step 12's call took 90 ms more, with the producer's H2D beside it
        call = next(s for s in spans if s.name == "step.dispatch" and s.attrs["step"] == 12)
        for s in spans:
            if s.start_s > call.start_s:
                s.start_s += 0.090
        call.dur_s += 0.090
        call.cpu_s = cpu_s
        sync = next(s for s in spans if s.name == "loss.sync" and s.attrs["step"] == 12)
        sync.attrs["interval_s"] += 0.090
        tracer.record("batch.h2d", call.start_s + 0.01, call.start_s + 0.08, cpu_s=0.002)
        return tracer.spans()

    (blocked,) = step_cadence(ring(0.008))["worst"]
    assert blocked["step"] == 12 and blocked["cause"] == "step.dispatch blocked"
    assert blocked["excess_ms"] == pytest.approx(90, abs=0.01)
    assert blocked["producer"] == [
        {"name": "batch.h2d", "overlap_ms": pytest.approx(70, abs=0.01), "cpu_ms": 2.0}]
    (working,) = step_cadence(ring(0.098))["worst"]
    assert working["cause"] == "step.dispatch on-CPU" and "producer" not in working
    # the loop's thread held up between two of its spans: no span's seconds grew
    spans = _ring(0.050, n=20).spans()
    for s in spans:
        if s.attrs.get("step", 0) > 12 or (s.attrs.get("step") == 12 and s.name != "data.wait"):
            s.start_s += 0.090  # after step 12's data.wait, before its call
    next(s for s in spans if s.name == "loss.sync"
         and s.attrs["step"] == 12).attrs["interval_s"] += 0.090
    (held,) = step_cadence(spans)["worst"]
    assert held["step"] == 12 and held["cause"] == "no span"
    assert held["cause_ms"] == pytest.approx(90, abs=0.01)
    # spans without the clock (an older run's exemplar): the call, undivided
    (older,) = step_cadence(ring(None))["worst"]
    assert older["cause"] == "step.dispatch"
    # a journaled exemplar's records read the same as the ring they were made from
    spans = ring(0.008)
    assert step_cadence([s.to_record() for s in spans]) == step_cadence(spans)


# ---------------------------------------------------------------------------
# end-to-end: a fleet request is ONE trace across router + backend


def _chain(n, keys=("_ABS_DATAFLOW",)):
    from deepdfa_tpu.data.graphs import Graph

    feats = {k: np.zeros(n, np.int32) for k in keys}
    return Graph(senders=np.arange(n - 1, dtype=np.int32),
                 receivers=np.arange(1, n, dtype=np.int32),
                 node_feats=feats).with_self_loops()


class _StubEngine:
    """Real ScoringEngine over a stub score_fn (same shape as
    test_serve.py's — no XLA, no devices)."""

    def __new__(cls, vocabs=(), max_batch=4, prob=0.25):
        from deepdfa_tpu.serve import ScoringEngine, serve_buckets

        def score_fn(batch):
            return np.full(batch.max_graphs, prob, np.float32)

        return ScoringEngine(score_fn, serve_buckets(max_batch),
                             feat_keys=tuple(vocabs))


@pytest.fixture(scope="module")
def demo():
    """(vocabs, sources) — real frontend + vocabularies, no training."""
    from deepdfa_tpu.config import FeatureConfig
    from deepdfa_tpu.cpg.features import add_dependence_edges
    from deepdfa_tpu.cpg.frontend import parse_source
    from deepdfa_tpu.data.codegen import demo_corpus
    from deepdfa_tpu.data.materialize import CorpusBuilder

    rows = demo_corpus(4, seed=0).to_dict("records")
    cpgs = {int(r["id"]): add_dependence_edges(parse_source(r["before"]))
            for r in rows}
    labels = {int(r["id"]): int(r["vul"]) for r in rows}
    _, vocabs = CorpusBuilder(FeatureConfig()).build(
        cpgs, list(cpgs), graph_labels=labels)
    return vocabs, [r["before"] for r in rows]


def _req(port, method, path, body=None, headers=None, timeout=30):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request(method, path, body=body,
                     headers={"Content-Type": "application/json",
                              **(headers or {})})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _wait_spans(tracer, trace_id, n, timeout_s=5.0):
    """Dispatcher-thread spans (host.reduce) land just after the response
    is sent — poll instead of racing them."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        spans = tracer.spans(trace_id)
        if len(spans) >= n:
            return spans
        time.sleep(0.01)
    return tracer.spans(trace_id)


def test_fleet_request_is_one_trace_across_router_and_backend(demo):
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.obs import chrome_trace
    from deepdfa_tpu.serve import FleetRouter, ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                      ServeConfig(port=0, max_wait_ms=2.0),
                      replica_id="r0").start()
    srv.engine.warmup()
    router = FleetRouter([f"127.0.0.1:{srv.port}"], port=0,
                         probe_interval_s=60.0)
    router.probe_once()
    router.start(probe=False)
    try:
        status, data = _req(router.port, "POST", "/score",
                            json.dumps({"source": sources[0]}))
        assert status == 200 and json.loads(data)["results"]

        assert len(router.tracer.trace_ids()) == 1
        trace_id = router.tracer.trace_ids()[0]
        backend_spans = _wait_spans(srv.tracer, trace_id, 6)
        router_spans = router.tracer.spans(trace_id)
        names = {s.name for s in router_spans} | {s.name for s in backend_spans}
        # the acceptance criterion: >= 5 spans, one trace id, both procs
        assert {"router.request", "router.forward", "server.request",
                "queue.wait", "engine.dispatch"} <= names, names
        assert {"router.route", "cache.lookup", "batch.assembly",
                "host.reduce"} <= names, names
        all_spans = router_spans + backend_spans
        assert len(all_spans) >= 5
        assert {s.trace_id for s in all_spans} == {trace_id}
        assert {s.proc for s in all_spans} == {"router", "serve:r0"}
        roots = [s for s in all_spans if s.root]
        assert [s.name for s in roots if s.proc == "router"] == [
            "router.request"]
        # parent chain crosses the HTTP hop: server.request's parent is
        # the router.forward span on the other side
        fwd = next(s for s in router_spans if s.name == "router.forward")
        root = next(s for s in backend_spans if s.name == "server.request")
        assert root.parent_id == fwd.span_id

        doc = chrome_trace(all_spans)
        json.dumps(doc)  # must be valid JSON
        assert doc["displayTimeUnit"] == "ms"
        metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in metas} == {"router", "serve:r0"}
        for ev in doc["traceEvents"]:
            assert {"name", "ph", "pid"} <= set(ev)
            if ev["ph"] == "X":
                assert {"ts", "dur"} <= set(ev) and ev["dur"] >= 1.0
                assert ev["args"]["trace_id"] == trace_id
    finally:
        router.shutdown()
        srv.shutdown()


def test_serve_latency_reservoirs_and_drift_feed(demo):
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                      ServeConfig(port=0, max_wait_ms=2.0)).start()
    try:
        status, _ = _req(srv.port, "POST", "/score",
                         json.dumps({"source": sources[0]}))
        assert status == 200
        snap = srv.metrics.snapshot()
        assert snap["queue_wait_p50_ms"] is not None
        assert snap["dispatch_p50_ms"] is not None
        assert snap["queue_wait_p99_ms"] >= snap["queue_wait_p50_ms"]
        # every scored request feeds the sentinel under the engine's rev
        drift = srv.drift.snapshot()
        assert sum(row["n_observed"] for row in drift.values()) >= 1
    finally:
        srv.shutdown()


@pytest.mark.faults
def test_trace_drop_fault_never_fails_the_request(demo):
    """The obs.trace_drop chaos point: losing a span export bumps
    dropped_total and NOTHING else — the request it annotates succeeds."""
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.resilience import faults
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                      ServeConfig(port=0, max_wait_ms=2.0)).start()
    try:
        with faults.installed("obs.trace_drop@1,2"):
            status, data = _req(srv.port, "POST", "/score",
                                json.dumps({"source": sources[0]}))
            assert status == 200
            body = json.loads(data)
            assert body["results"][0]["vulnerable_probability"] == 0.25
        deadline = time.time() + 5.0
        while srv.tracer.dropped_total < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert srv.tracer.dropped_total == 2
        text = srv.metrics.render(cache_stats=srv.cache.stats())
        assert "deepdfa_serve_trace_spans_dropped_total 2" in text
        _assert_exposition(text)
    finally:
        srv.shutdown()


def test_obs_config_validation_and_override():
    from deepdfa_tpu.config import ObsConfig, ServeConfig, load_config

    cfg = ServeConfig()
    assert cfg.obs.trace is True and cfg.obs.train_port == -1
    exp = load_config(overrides={"serve.obs.drift_threshold": 0.5,
                                 "serve.obs.trace": False})
    assert exp.serve.obs.drift_threshold == 0.5
    assert exp.serve.obs.trace is False
    with pytest.raises(ValueError):
        ObsConfig(trace_buffer=0)
    with pytest.raises(ValueError):
        ObsConfig(drift_bins=1)


# ---------------------------------------------------------------------------
# exemplar journaling + export CLI


def test_slow_request_exemplars_and_trace_export_cli(tmp_path):
    from deepdfa_tpu.obs import Tracer, load_trace_records
    from deepdfa_tpu.train.cli import trace_export

    traces = tmp_path / "traces"
    tracer = Tracer(proc="serve", slow_ms=0.0, exemplar_dir=traces,
                    max_exemplars=2)
    for i in range(4):
        t0 = time.time()
        with tracer.span("server.request", root=True, i=i) as sp:
            tracer.record("queue.wait", t0, t0 + 0.001, parent=sp.ctx)
    files = sorted(traces.glob("trace-*.json"))
    assert len(files) == 2  # capped: oldest exemplars evicted
    records = load_trace_records(tmp_path)  # recursive: run dir works
    assert len(records) == 2
    assert all(r["event"] == "trace" and r["root"] == "server.request"
               for r in records)
    assert all(len(r["spans"]) == 2 for r in records)

    summary = trace_export(tmp_path)
    out = Path(summary["out"])
    assert out.exists() and summary["trace_records"] == 2
    assert summary["spans"] == 4
    doc = json.loads(out.read_text())
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 4 and all(e["dur"] >= 1.0 for e in xs)


def test_trace_export_via_main_entrypoint(tmp_path, capsys):
    from deepdfa_tpu.obs import Tracer
    from deepdfa_tpu.train.cli import main

    tracer = Tracer(proc="train", slow_ms=0.0, exemplar_dir=tmp_path)
    with tracer.span("train.epoch", root=True):
        pass
    out = tmp_path / "export.json"
    summary = main(["trace", "export", "--run-dir", str(tmp_path),
                    "--out", str(out)])
    assert summary["trace_records"] == 1 and out.exists()
    assert "traceEvents" in json.loads(out.read_text())


# ---------------------------------------------------------------------------
# crash flight recorder


def test_flight_recorder_ring_bound_and_atomic_dump(tmp_path):
    from deepdfa_tpu.obs import FlightRecorder

    rec = FlightRecorder(capacity=4, proc="test", dump_dir=tmp_path)
    for i in range(10):
        assert rec.record("request", code=200, i=i) is True
    events = rec.snapshot()
    assert len(events) == 4                      # bounded: oldest fell off
    assert [e["i"] for e in events] == [6, 7, 8, 9]
    assert [e["seq"] for e in events] == [7, 8, 9, 10]
    assert rec.recorded_total == 10 and rec.dropped_total == 0

    path = rec.dump("unit_test")
    assert path is not None and path.name.startswith("flight-")
    doc = json.loads(path.read_text())
    assert doc["schema"] == 1 and doc["proc"] == "test"
    assert doc["reason"] == "unit_test"
    assert doc["recorded_total"] == 10
    assert [e["i"] for e in doc["events"]] == [6, 7, 8, 9]
    # no torn temp file left behind (atomic_write_text protocol)
    assert [p.name for p in tmp_path.iterdir()] == [path.name]
    # same-instant second dump gets a distinct name, not an overwrite
    path2 = rec.dump("unit_test")
    assert path2 is not None and path2 != path
    assert rec.dumps_total == 2

    # unserializable field values degrade via repr, never raise
    rec.record("weird", obj=object())
    assert rec.dump("weird") is not None


def test_flight_recorder_dump_failure_never_raises(tmp_path):
    from deepdfa_tpu.obs import FlightRecorder

    blocked = tmp_path / "blocked"
    blocked.write_text("a file where the dump dir should be")
    rec = FlightRecorder(capacity=2, proc="test", dump_dir=blocked)
    rec.record("request")
    assert rec.dump("crash") is None             # swallowed, counted
    assert rec.dropped_total == 1


def test_flight_recorder_unconfigured_dump_avoids_cwd(tmp_path, monkeypatch):
    """Regression: with no dump dir configured, a dump must land in the
    system temp dir, never the process CWD (a fault-injection test once
    littered the repo root with flight-*.json)."""
    import tempfile

    from deepdfa_tpu.obs import FlightRecorder

    monkeypatch.chdir(tmp_path)
    rec = FlightRecorder(capacity=2, proc="test")
    rec.record("request")
    path = rec.dump("crash")
    assert path is not None
    assert path.parent == Path(tempfile.gettempdir())
    assert not list(tmp_path.glob("flight-*.json"))
    path.unlink()


def test_flight_recorder_sigusr2_dumps(tmp_path):
    import os
    import signal as _signal

    from deepdfa_tpu.obs import FlightRecorder, install_sigusr2

    rec = FlightRecorder(capacity=8, proc="test", dump_dir=tmp_path)
    rec.record("request", code=200)
    prev = install_sigusr2(rec)
    try:
        os.kill(os.getpid(), _signal.SIGUSR2)
        deadline = time.time() + 5.0
        while rec.dumps_total < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert rec.dumps_total == 1
        dumps = list(tmp_path.glob("flight-*.json"))
        assert len(dumps) == 1
        assert json.loads(dumps[0].read_text())["reason"] == "sigusr2"
    finally:
        if prev is not None:
            _signal.signal(_signal.SIGUSR2, prev)


@pytest.mark.faults
def test_flight_drop_fault_never_fails_the_request(demo):
    """The obs.flight_drop chaos point: losing a flight-recorder event
    bumps the dropped counter and NOTHING else — the request it annotates
    succeeds and both scrape endpoints export the drop."""
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.resilience import faults
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                      ServeConfig(port=0, max_wait_ms=2.0)).start()
    try:
        with faults.installed("obs.flight_drop@1"):
            status, data = _req(srv.port, "POST", "/score",
                                json.dumps({"source": sources[0]}))
            assert status == 200
            assert json.loads(data)["results"]
        deadline = time.time() + 5.0
        while srv.flight.dropped_total < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert srv.flight.dropped_total == 1
        assert srv.flight.recorded_total >= 1  # later events still land
        text = srv.metrics.render(cache_stats=srv.cache.stats())
        _assert_exposition(text)
        assert "deepdfa_serve_obs_dropped_total 1" in text
        status, body = _req(srv.port, "GET", "/slo")
        assert status == 200
        assert "deepdfa_serve_obs_dropped_total 1" in body.decode()
    finally:
        srv.shutdown()


@pytest.mark.faults
def test_engine_fault_dumps_flight_record(demo, tmp_path):
    """A serve.engine_raises 500 must leave a flight-<ts>.json post-mortem
    in the configured dump dir, with the failed request's events in the
    ring."""
    from deepdfa_tpu.config import ObsConfig, ServeConfig
    from deepdfa_tpu.resilience import faults
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    cfg = ServeConfig(port=0, max_wait_ms=2.0,
                      obs=ObsConfig(flight_dir=str(tmp_path)))
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs, cfg).start()
    try:
        srv.engine.warmup()  # arm AFTER warmup (invariant 13)
        with faults.installed("serve.engine_raises@1"):
            status, data = _req(srv.port, "POST", "/score",
                                json.dumps({"source": sources[0]}))
        assert status == 500
        assert "serve.engine_raises" in json.loads(data)["error"]
        dumps = sorted(tmp_path.glob("flight-*.json"))
        assert dumps, "engine fault did not dump a flight record"
        doc = json.loads(dumps[-1].read_text())
        assert doc["schema"] == 1 and doc["proc"] == "serve"
        assert doc["reason"] == "engine_error"
        kinds = {e["kind"] for e in doc["events"]}
        assert "engine.error" in kinds
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# SLO burn-rate engine


def test_slo_engine_multi_window_burn_and_transitions():
    from deepdfa_tpu.obs import FlightRecorder, SLOEngine, SLOSpec

    t = [1000.0]
    flight = FlightRecorder(capacity=16, proc="test", clock=lambda: t[0])
    eng = SLOEngine(
        (SLOSpec("availability", "ratio", 0.99,
                 bad="bad_total", total="requests_total"),
         SLOSpec("latency_p99", "max", 100.0, value="p99_ms")),
        fast_window_s=10.0, slow_window_s=60.0, burn_threshold=2.0,
        clock=lambda: t[0], flight=flight)

    assert eng.observe({"bad_total": 0, "requests_total": 100,
                        "p99_ms": 50.0}) == []
    t[0] += 5.0  # 5% of traffic failing = 5x the 1% budget: both windows
    events = eng.observe({"bad_total": 5, "requests_total": 200,
                          "p99_ms": 50.0})
    assert [ (e["slo"], e["state"]) for e in events] == [
        ("availability", "firing")]
    assert events[0]["burn_fast"] > 2.0 and events[0]["burn_slow"] > 2.0
    by_name = {s["slo"]: s for s in eng.statuses()}
    assert by_name["availability"]["alert"] is True
    assert by_name["latency_p99"]["alert"] is False  # 50 < 100: burn 0.5

    # the incident ages out of the fast window -> resolved (multi-window:
    # a long-dead burst must not page forever)
    t[0] += 30.0
    events = eng.observe({"bad_total": 5, "requests_total": 400,
                          "p99_ms": 50.0})
    assert [(e["slo"], e["state"]) for e in events] == [
        ("availability", "resolved")]
    assert eng.transitions_total == 2
    # every transition was mirrored into the flight recorder
    kinds = [e["kind"] for e in flight.snapshot()]
    assert kinds.count("slo.transition") == 2

    text = eng.render("deepdfa_serve_")
    _assert_exposition(text)
    assert 'deepdfa_serve_slo_alert{slo="availability"} 0' in text
    assert 'deepdfa_serve_slo_burn_rate{slo="latency_p99",window="fast"}' \
        in text
    assert "deepdfa_serve_slo_evaluations_total 3" in text
    assert "deepdfa_serve_obs_dropped_total 0" in text


def test_slo_engine_gauge_floor_and_never_raises():
    from deepdfa_tpu.obs import SLOEngine, train_specs

    t = [0.0]
    from deepdfa_tpu.obs import SLOSpec

    floor = SLOSpec("rate_floor", "min", 0.4, value="steps_per_s")
    eng = SLOEngine(train_specs(step_ms=100.0) + (floor,),
                    fast_window_s=10.0, slow_window_s=10.0,
                    clock=lambda: t[0])
    for _ in range(3):
        t[0] += 1.0
        eng.observe({"mean_step_ms": 250.0, "steps_per_s": 0.1})
    by_name = {s["slo"]: s for s in eng.statuses()}
    assert by_name["step_time"]["alert"] is True       # 250/100 = 2.5 > 1
    assert by_name["rate_floor"]["alert"] is True      # 0.4/0.1 = 4 > 1
    # a hostile snapshot cannot fail the scrape (invariant 14)
    assert eng.observe(None) == []
    assert eng.observe({"mean_step_ms": "not-a-number"}) == []
    assert eng.dropped_total == 2
    _assert_exposition(eng.render("deepdfa_train_"))


def test_write_alerts_artifact_promotion_veto(tmp_path):
    from deepdfa_tpu.obs import write_alerts_artifact

    path = tmp_path / "alerts.json"
    out = write_alerts_artifact(
        path,
        [{"slo": "latency_p99", "alert": True, "burn_fast": 3.0},
         {"slo": "availability", "alert": False}],
        extra_alerts=[{"slo": "score_drift", "alert": True,
                       "model_rev": "rev-a"}],
        clock=lambda: 1234.0)
    assert out == path
    doc = json.loads(path.read_text())
    assert doc["schema"] == 1
    assert doc["generated_at_unix"] == 1234
    assert doc["firing"] == ["latency_p99", "score_drift"]
    assert doc["promotion_vetoed"] is True

    quiet = write_alerts_artifact(path, [{"slo": "availability",
                                          "alert": False}])
    assert quiet == path
    assert json.loads(path.read_text())["promotion_vetoed"] is False
    # unserializable statuses -> None, never an exception
    assert write_alerts_artifact(path, [{"slo": object()}]) is None


def test_slo_endpoint_on_all_three_processes(demo):
    """The acceptance criterion: /slo exists on the serve server, the
    router, and the trainer telemetry server, and all three bodies pass
    the SAME exposition conformance checker under their own prefixes."""
    from deepdfa_tpu.config import ServeConfig
    from deepdfa_tpu.obs import (
        SLOEngine,
        TelemetryServer,
        TrainTelemetry,
        train_specs,
    )
    from deepdfa_tpu.serve import FleetRouter, ScoreServer

    vocabs, sources = demo
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs,
                      ServeConfig(port=0, max_wait_ms=2.0)).start()
    router = FleetRouter([f"127.0.0.1:{srv.port}"], port=0,
                         probe_interval_s=60.0)
    router.probe_once()
    router.start(probe=False)
    telemetry = TrainTelemetry(
        slo=SLOEngine(train_specs(step_ms=100.0), fast_window_s=10.0,
                      slow_window_s=10.0))
    telemetry.observe_step(0.01, 0.02)
    tsrv = TelemetryServer(telemetry, port=0).start()
    try:
        _req(router.port, "POST", "/score",
             json.dumps({"source": sources[0]}))
        for port, prefix in ((srv.port, "deepdfa_serve_"),
                             (router.port, "deepdfa_router_"),
                             (tsrv.port, "deepdfa_train_")):
            status, body = _req(port, "GET", "/slo")
            assert status == 200, prefix
            text = body.decode()
            _assert_exposition(text)
            assert f"{prefix}slo_evaluations_total" in text, prefix
            assert f"{prefix}obs_dropped_total 0" in text, prefix
        # serve + router declare their default objectives
        _, body = _req(srv.port, "GET", "/slo")
        assert 'slo_objective{slo="availability"} 0.99' in body.decode()
        _, body = _req(router.port, "GET", "/slo")
        assert 'slo_objective{slo="latency_p99"}' in body.decode()
        # trainer: the configured step-time spec is being evaluated
        _, body = _req(tsrv.port, "GET", "/slo")
        assert 'deepdfa_train_slo_objective{slo="step_time"} 100' \
            in body.decode()
    finally:
        tsrv.stop()
        router.shutdown()
        srv.shutdown()


def test_serve_slo_transition_journals_and_writes_alerts(demo, tmp_path):
    """End to end on the serve server: an unmeetable p99 objective fires
    on the first /slo scrape after traffic -> the transition is journaled
    as an event AND alerts.json flips promotion_vetoed (the ROADMAP 5(b)
    alert-ACTION)."""
    from deepdfa_tpu.config import ObsConfig, ServeConfig
    from deepdfa_tpu.resilience.journal import RunJournal
    from deepdfa_tpu.serve import ScoreServer

    vocabs, sources = demo
    alerts = tmp_path / "alerts.json"
    cfg = ServeConfig(port=0, max_wait_ms=2.0,
                      obs=ObsConfig(slo_p99_ms=0.001,  # unmeetable ceiling
                                    slo_fast_window_s=5.0,
                                    slo_slow_window_s=5.0,
                                    alerts_path=str(alerts)))
    srv = ScoreServer(_StubEngine(vocabs, max_batch=4), vocabs, cfg,
                      journal=RunJournal(tmp_path / "journal.json")).start()
    try:
        status, _ = _req(srv.port, "POST", "/score",
                         json.dumps({"source": sources[0]}))
        assert status == 200
        status, body = _req(srv.port, "GET", "/slo")
        assert status == 200
        text = body.decode()
        _assert_exposition(text)
        assert 'deepdfa_serve_slo_alert{slo="latency_p99"} 1' in text

        rec = srv.journal.read()
        assert rec is not None and rec["event"] == "slo_transition"
        assert rec["slo"] == "latency_p99" and rec["state"] == "firing"
        assert rec["burn_fast"] > 1.0

        doc = json.loads(alerts.read_text())
        assert doc["promotion_vetoed"] is True
        assert "latency_p99" in doc["firing"]
        # the engine's transition ring kept the event too
        assert [e["slo"] for e in srv.slo.transitions] == ["latency_p99"]
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# drift sentinel rev bound (LRU)


def test_drift_sentinel_bounds_model_revs():
    from deepdfa_tpu.obs import ScoreDriftSentinel

    sent = ScoreDriftSentinel(window=8, bins=4, min_samples=2, max_revs=3)
    for i in range(5):
        for s in (0.1, 0.9):
            sent.observe(s, f"rev-{i}")
    snap = sent.snapshot()
    assert len(snap) == 3                       # bounded, not 5
    assert set(snap) == {"rev-2", "rev-3", "rev-4"}  # LRU: oldest evicted
    assert sent.evicted_revs_total == 2
    # re-observing a surviving rev refreshes it instead of re-evicting
    sent.observe(0.5, "rev-2")
    assert set(sent.snapshot()) == {"rev-2", "rev-3", "rev-4"}
    with pytest.raises(ValueError):
        ScoreDriftSentinel(max_revs=0)


def test_drift_eviction_counter_rendered_in_serve_metrics():
    m = _populated_serve_metrics()
    m.drift.max_revs = 1
    m.drift.observe(0.5, "rev-b")               # evicts rev-a
    text = m.render()
    _assert_exposition(text)
    assert "deepdfa_serve_score_drift_evicted_revs_total 1" in text
    assert 'model_rev="rev-a"' not in text      # bounded cardinality


def test_report_profiling_traces_view(tmp_path, capsys):
    import report_profiling

    from deepdfa_tpu.obs import Tracer

    tracer = Tracer(proc="serve", slow_ms=0.0, exemplar_dir=tmp_path)
    t0 = time.time()
    with tracer.span("server.request", root=True) as sp:
        tracer.record("engine.dispatch", t0, t0 + 0.002, parent=sp.ctx)
    report = report_profiling.trace_report(tmp_path)
    assert report["trace_records"] == 1
    assert set(report["spans"]) == {"server.request", "engine.dispatch"}
    assert report["spans"]["engine.dispatch"]["count"] == 1
    report_profiling.main(["--traces", str(tmp_path)])
    line = json.loads(capsys.readouterr().out.strip())
    assert line["trace_records"] == 1
