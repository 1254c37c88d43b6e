"""Training-step timelines: per-step host wall / data-wait / dispatch /
loss-sync accounting, exact jit-compile events, the garbage collector's
pauses, the interval between completed steps, an optional trainer HTTP
``/metrics``+``/healthz`` endpoint, and per-epoch journal stats.

What one step leaves behind: its ``data.wait``, ``step.dispatch`` and
``loss.sync`` spans (and the producer's ``batch.build`` / ``batch.h2d``), each
with its wall seconds and ``cpu_s``, the seconds its thread was running; on
``loss.sync``, where the loop reads each loss, ``interval_s`` (this read's
return less the one before: a completed step), ``gc_s`` / ``gc_n`` (the
collections that ended in between) and whatever the encoder sowed; any
``jit.*`` or ``gc.pause`` span that fell inside one, carrying its ``step``.

The trainer's ``StepProfiler`` writes jsonl files nobody scrapes; this
is the live complement: :class:`TrainTelemetry` is fed from inside
``Trainer.train_epoch`` and ``JointTrainer.train`` (the spans they open
round the prefetch iterator, the step call and the loss read) and renders
through the same :class:`~deepdfa_tpu.obs.registry.MetricsRegistry` as
the serve and router endpoints, so all three expositions share one
formatter and one conformance test.

Compiles are not guessed: jax reports every trace, lowering and backend
compile (a persistent-cache read included) as a ``jax.monitoring``
duration event when it is over. One pair of listeners a process, registered
by the first :class:`TrainTelemetry` made, hands them to every live one:
each becomes a ``jit.trace`` / ``jit.lower`` / ``jit.backend_compile``
span ``[now - duration, now]`` under whatever span is open on the compiling
thread (so a re-jit inside the loop carries its ``step``), and
``jit.backend_compile`` is what ``compiles`` counts. Trace events nest (a
function's covers those of the functions it calls): sum them as a union.

The collector is not guessed either: one ``gc.callbacks`` entry a process,
registered with the same first :class:`TrainTelemetry`, times every
collection. All of them add to ``gc_s`` / ``gc_n``; one of generation 2, or of
``MIN_GC_SPAN_S`` or longer, is also a ``gc.pause`` span under whatever span
was open on the thread it ran on, and inside a ``jax.profiler`` session a
generation-2 collection is a ``deepdfa:gc.pause`` annotation on that thread.

:func:`train_telemetry` is the process-wide instance a trainer records into
when it is handed none — always recording into a bounded ring, exporting
nothing unless asked.
"""

from __future__ import annotations

import gc
import json
import threading
import time
import weakref
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from deepdfa_tpu.obs.cadence import interval_stats
from deepdfa_tpu.obs.registry import MetricsRegistry
from deepdfa_tpu.obs.tracing import Tracer

__all__ = ["TrainTelemetry", "TelemetryServer", "train_telemetry"]

# what a benchmark run has to find in it afterwards: the set-up's compile
# events and warm steps (~100 spans), then 22 s of window and traced slice at
# 6 spans a step — 2,300 at the fastest cell's 17.6 steps/s (PR 30) — and the
# gc.pause spans (the long and generation-2 collections only: a handful)
RING_SPANS = 16_384

_JIT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.backend_compile",
}
# jax reports a trace event for every jnp call inside a function being
# traced, each nested in its caller's: a train step fires thousands, a few
# microseconds long. Those are counted in their caller's event; only a trace
# of at least this long becomes a span (compiles are always spans).
MIN_TRACE_SPAN_S = 1e-3
# the young generations are collected many times a step, in microseconds:
# those are tallied only. A collection this long, or any of generation 2,
# becomes a span.
MIN_GC_SPAN_S = 1e-3
_WINDOW_INTERVALS = 65_536  # step intervals kept for one epoch_stats()
_JIT_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_lock = threading.RLock()
_listening: weakref.WeakSet | None = None  # None until the listeners are registered
_process_telemetry: "TrainTelemetry | None" = None


def _listen(telemetry: "TrainTelemetry") -> None:
    """Feed ``telemetry`` jax's compile events and the collector's pauses;
    the first call registers the process's listeners (jax.monitoring cannot
    say whether one is there)."""
    global _listening
    with _lock:
        if _listening is None:
            from jax import monitoring

            _listening = weakref.WeakSet()
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            gc.callbacks.append(_on_gc)
        _listening.add(telemetry)


def _live() -> list["TrainTelemetry"]:
    with _lock:
        return list(_listening or ())


# jax calls the listeners from inside its compile path: they never raise there

def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    name = _JIT_SPANS.get(event)
    if name is None:
        return
    try:
        for telemetry in _live():
            telemetry.observe_jit(name, duration_secs, kwargs.get("fun_name"))
    except Exception:  # noqa: BLE001 — telemetry must not fail a compile
        pass


def _on_event(event: str, **_kwargs) -> None:
    name = _JIT_COUNTS.get(event)
    if name is None:
        return
    try:
        for telemetry in _live():
            telemetry.observe_count(name)
    except Exception:  # noqa: BLE001
        pass


# the collection that is running: (perf_counter, thread_time, time.time at its
# start, the profiler annotations entered for it). One slot: the interpreter
# runs one collection at a time, callbacks included.
_collecting: tuple | None = None


def _on_gc(phase: str, info: dict) -> None:
    """The process's ``gc.callbacks`` entry, on the thread that collects.
    It never raises into the collector, and one telemetry's failure does not
    cost the others the collection."""
    global _collecting
    try:
        if phase == "start":
            entered, seen = [], set()
            if info["generation"] == 2:
                # one annotation a profiler, however many telemetries share it
                for tracer in (t.tracer for t in _live()):
                    if tracer.annotation not in seen:
                        seen.add(tracer.annotation)
                        entered.append(tracer.annotate("gc.pause", generation=2))
            _collecting = (time.perf_counter(), time.thread_time(), time.time(), entered)
            return
        if _collecting is None:  # registered between a collection's two calls
            return
        (t0, c0, start_s, entered), _collecting = _collecting, None
        dur_s, cpu_s = time.perf_counter() - t0, time.thread_time() - c0
        for ann in entered:
            Tracer.end_annotation(ann)
        for telemetry in _live():
            try:
                telemetry.observe_gc(start_s, dur_s, cpu_s, info["generation"],
                                     info["collected"])
            except Exception:  # noqa: BLE001 — telemetry must not fail a collection
                pass
    except Exception:  # noqa: BLE001
        pass


def train_telemetry() -> "TrainTelemetry":
    """The process-wide :class:`TrainTelemetry`: what ``JointTrainer``
    records into when it is given none, and how anything else in the
    process (a benchmark's reader, a debugger) reaches those spans:
    ``train_telemetry().tracer.spans()``. A ring of ``RING_SPANS``, no
    exemplar directory, no HTTP server."""
    global _process_telemetry
    with _lock:
        if _process_telemetry is None:
            _process_telemetry = TrainTelemetry()
        return _process_telemetry


class TrainTelemetry:
    """Aggregates per-step timings; thread-safe (the watchdog may drive
    steps from a worker thread, the prefetch producer records from its
    own)."""

    def __init__(self, tracer: Tracer | None = None, slo=None, flight=None):
        if tracer is None:
            from jax.profiler import TraceAnnotation

            tracer = Tracer(proc="train", max_spans=RING_SPANS,
                            annotation=TraceAnnotation)
        self.tracer = tracer
        # verdict-layer attachments (both optional): the SLO engine backs
        # the /slo endpoint; the flight recorder takes step/fault events
        self.slo = slo
        self.flight = flight
        # re-entrant: a collection may start on a thread that holds it, and
        # observe_gc then runs on that thread
        self._lock = threading.RLock()
        self._started_s = time.time()
        # cumulative (lifetime) and window (since last epoch_stats) tallies
        self._cum = self._zero()
        self._win = self._zero()
        # the window's intervals between completed steps (observe_read);
        # bounded like the ring, for a loop whose epochs never end
        self._intervals: deque[float] = deque(maxlen=_WINDOW_INTERVALS)
        # when the last loss read returned (perf_counter) and the cumulative
        # gc_s / gc_n then; None where the next read ends no interval
        self._last_read: tuple | None = None
        self.epoch = -1
        self.last_step_s = 0.0
        _listen(self)

    @staticmethod
    def _zero() -> dict:
        return {"steps": 0, "wall_s": 0.0, "data_wait_s": 0.0,
                "dispatch_s": 0.0, "sync_s": 0.0, "build_s": 0.0,
                "h2d_s": 0.0, "compiles": 0, "cache_hits": 0,
                "cache_misses": 0, "gc_s": 0.0, "gc_n": 0}

    # -- feed path (inside the train loops) ---------------------------------

    def observe_step(self, wait_s: float, dispatch_s: float,
                     sync_s: float | None = None) -> None:
        """One step's host times: waiting for the batch, inside the call of
        the step, and (where the loop reads the loss each step, and then
        tells :meth:`observe_read` of it) waiting for the device in that
        read."""
        wait_s = max(0.0, float(wait_s))
        dispatch_s = max(0.0, float(dispatch_s))
        with self._lock:
            if sync_s is None:
                # a loop that reads no loss knows no completed step: the
                # host's own time of this one is what its gauge can say
                self.last_step_s = wait_s + dispatch_s
            sync_s = max(0.0, float(sync_s or 0.0))
            for t in (self._cum, self._win):
                t["steps"] += 1
                t["wall_s"] += wait_s + dispatch_s + sync_s
                t["data_wait_s"] += wait_s
                t["dispatch_s"] += dispatch_s
                t["sync_s"] += sync_s

    def observe_read(self, span, alone: bool) -> None:
        """A loss read has just returned inside its open ``loss.sync``
        ``span``: the one moment the host knows a step is complete. Sets
        ``interval_s`` (this return less the one before), ``gc_s`` and
        ``gc_n`` (the collections that ended on any thread in between) on the
        span — not on an epoch's first read, nor on the one after an
        ``alone`` read, where an evaluation or a flush lies between."""
        now = time.perf_counter()
        with self._lock:
            gc_s, gc_n = self._cum["gc_s"], self._cum["gc_n"]
            last = self._last_read
            self._last_read = None if alone else (now, gc_s, gc_n)
            if last is None:
                return
            interval_s = now - last[0]
            self._intervals.append(interval_s)
            self.last_step_s = interval_s
        span.attrs.update(interval_s=interval_s, gc_s=gc_s - last[1], gc_n=gc_n - last[2])

    _PRODUCER_TALLY = {"batch.build": "build_s", "batch.h2d": "h2d_s"}

    def observe_producer(self, span) -> None:
        """A closed ``batch.build`` / ``batch.h2d`` span, from the prefetch
        producer's thread (``prefetch_to_device(on_span=...)``): the ring
        forgets, these totals do not."""
        key = self._PRODUCER_TALLY.get(span.name)
        if key is not None:
            with self._lock:
                self._cum[key] += span.dur_s
                self._win[key] += span.dur_s

    def _interrupted(self) -> tuple:
        """``(context, {"step": ..})`` of the span open on this thread — what
        an event that fell inside it hangs under and which step it cost —
        or ``(None, {})``."""
        within = self.tracer.current_span()
        if within is None:
            return None, {}
        return within.ctx, ({"step": within.attrs["step"]} if "step" in within.attrs else {})

    def observe_jit(self, name: str, duration_s: float,
                    fun_name: str | None = None) -> None:
        """One of jax's compile events, just over, on the thread that
        compiled."""
        if name == "jit.trace" and duration_s < MIN_TRACE_SPAN_S:
            return
        end_s = time.time()
        parent, attrs = self._interrupted()
        if fun_name is not None:
            attrs["fun_name"] = fun_name
        self.tracer.record(name, end_s - duration_s, end_s, parent=parent, **attrs)
        if name == "jit.backend_compile":
            self.observe_count("compiles")

    def observe_gc(self, start_s: float, dur_s: float, cpu_s: float,
                   generation: int, collected: int) -> None:
        """One collection, just over, on the thread it ran on."""
        with self._lock:
            for t in (self._cum, self._win):
                t["gc_s"] += dur_s
                t["gc_n"] += 1
        if generation < 2 and dur_s < MIN_GC_SPAN_S:
            return
        parent, attrs = self._interrupted()
        self.tracer.record("gc.pause", start_s, start_s + dur_s, parent=parent, cpu_s=cpu_s,
                           unprompted=True, generation=generation, collected=collected,
                           **attrs)

    def observe_count(self, name: str) -> None:
        with self._lock:
            self._cum[name] += 1
            self._win[name] += 1

    def observe_epoch(self, epoch: int) -> None:
        with self._lock:
            self.epoch = int(epoch)
            self._last_read = None  # an epoch's first read ends no interval

    # -- journal path -------------------------------------------------------

    @staticmethod
    def _stats(t: dict) -> dict:
        steps = t["steps"]
        out = {
            "steps": steps,
            "wall_s": round(t["wall_s"], 6),
            "data_wait_s": round(t["data_wait_s"], 6),
            "dispatch_s": round(t["dispatch_s"], 6),
            "sync_s": round(t["sync_s"], 6),
            "build_s": round(t["build_s"], 6),
            "h2d_s": round(t["h2d_s"], 6),
            "compiles": t["compiles"],
            "cache_hits": t["cache_hits"],
            "cache_misses": t["cache_misses"],
            "gc_s": round(t["gc_s"], 6),
            "gc_n": t["gc_n"],
        }
        if steps:
            out["mean_step_ms"] = round(t["wall_s"] / steps * 1e3, 4)
            out["data_wait_frac"] = round(
                t["data_wait_s"] / t["wall_s"], 6) if t["wall_s"] else 0.0
        return out

    def epoch_stats(self) -> dict:
        """Stats for the steps since the previous call (one epoch's worth
        when called from the per-epoch journal write); resets the window."""
        with self._lock:
            win, self._win = self._win, self._zero()
            intervals = list(self._intervals)
            self._intervals.clear()
        out = self._stats(win)
        if intervals:
            cadence = interval_stats(intervals)
            out.update({k: cadence[k] for k in (
                "interval_p50_ms", "interval_max_ms", "stalls")})
        return out

    def snapshot(self) -> dict:
        with self._lock:
            cum = dict(self._cum)
        out = self._stats(cum)
        out["epoch"] = self.epoch
        out["uptime_s"] = round(time.time() - self._started_s, 3)
        return out

    # -- scrape path --------------------------------------------------------

    def render(self) -> str:
        reg = MetricsRegistry("deepdfa_train_")
        with self._lock:
            cum = dict(self._cum)
            epoch, last_step_s = self.epoch, self.last_step_s
            dropped = self.tracer.dropped_total
        reg.counter("steps_total", "Training steps completed").set(
            cum["steps"])
        reg.counter("compiles_total",
                    "XLA backend compiles jax reported (cache reads included)"
                    ).set(cum["compiles"])
        reg.counter("compile_cache_hits_total",
                    "Compiles answered by the persistent compilation cache"
                    ).set(cum["cache_hits"])
        reg.counter("compile_cache_misses_total",
                    "Compiles the persistent compilation cache did not hold"
                    ).set(cum["cache_misses"])
        reg.counter("data_wait_seconds_total",
                    "Host seconds spent waiting on the input stream").set(
            round(cum["data_wait_s"], 6))
        reg.counter("dispatch_seconds_total",
                    "Host seconds spent in step dispatch").set(
            round(cum["dispatch_s"], 6))
        reg.counter("loss_sync_seconds_total",
                    "Host seconds the loop waited for the device in its "
                    "loss read").set(round(cum["sync_s"], 6))
        reg.counter("prefetch_build_seconds_total",
                    "Producer-thread seconds spent building batches").set(
            round(cum["build_s"], 6))
        reg.counter("prefetch_h2d_seconds_total",
                    "Producer-thread seconds spent staging batches on the "
                    "device").set(round(cum["h2d_s"], 6))
        reg.counter("gc_pause_seconds_total",
                    "Seconds inside the garbage collector, any thread").set(
            round(cum["gc_s"], 6))
        reg.counter("gc_collections_total",
                    "Garbage collections of any generation").set(cum["gc_n"])
        reg.gauge("epoch", "Current epoch index").set(epoch)
        reg.gauge("last_step_seconds",
                  "Seconds between the two most recent completed steps (loss "
                  "reads); in a loop that reads no loss, the host's wait and "
                  "dispatch of the last step").set(round(last_step_s, 6))
        reg.counter("trace_spans_dropped_total",
                    "Spans lost by the trainer tracer (never fatal)").set(
            dropped)
        return reg.render()

    def healthz(self) -> dict:
        snap = self.snapshot()
        return {"ok": True, "role": "trainer", **snap}

    def record_event(self, kind: str, **fields) -> None:
        """Forward one structured event to the flight recorder (a no-op
        without one; never raises — invariant 14/17: telemetry must not
        perturb the step it annotates)."""
        if self.flight is not None:
            self.flight.record(kind, **fields)

    def render_slo(self) -> str:
        """The trainer's ``/slo`` body. With no engine attached an empty
        one is built on the fly so the endpoint still renders the
        conformant counter families (and the obs_dropped_total account)."""
        if self.slo is None:
            from deepdfa_tpu.obs.slo import SLOEngine

            self.slo = SLOEngine((), flight=self.flight)
        snap = self.snapshot()
        self.slo.observe({"mean_step_ms": snap.get("mean_step_ms")})
        return self.slo.render("deepdfa_train_")


class _TelemetryHandler(BaseHTTPRequestHandler):
    server: "TelemetryServer"

    def log_message(self, fmt, *args):  # quiet — tests run many scrapes
        pass

    def _send(self, code: int, body: bytes, content_type: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802 — http.server API
        telemetry = self.server.telemetry
        if self.path.startswith("/metrics"):
            self._send(200, telemetry.render().encode(),
                       "text/plain; version=0.0.4")
        elif self.path.startswith("/slo"):
            self._send(200, telemetry.render_slo().encode(),
                       "text/plain; version=0.0.4")
        elif self.path.startswith("/healthz"):
            self._send(200, json.dumps(telemetry.healthz()).encode(),
                       "application/json")
        else:
            self._send(404, b'{"error": "not found"}', "application/json")


class TelemetryServer(ThreadingHTTPServer):
    """Optional trainer-side scrape endpoint (``serve.obs.train_port``;
    -1 disables, 0 binds an ephemeral port). Serves in a daemon thread —
    a hung scrape never blocks training shutdown."""

    daemon_threads = True

    def __init__(self, telemetry: TrainTelemetry, host: str = "127.0.0.1",
                 port: int = 0):
        super().__init__((host, port), _TelemetryHandler)
        self.telemetry = telemetry
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self.server_address[1]

    def start(self) -> "TelemetryServer":
        self._thread = threading.Thread(
            target=self.serve_forever, name="train-telemetry", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        self.shutdown()
        self.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
