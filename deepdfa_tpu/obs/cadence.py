"""The cadence of completed steps, and what a long one was made of.

``JointTrainer._read_loss`` sets ``interval_s`` on each ``loss.sync`` span: the
seconds between this read's return and the one before it, the one moment a
step is known to be complete (``TrainTelemetry.observe_read``). A mean cannot
tell a uniformly slow run from one with a few long steps; the distribution of
those intervals can: a slow device or machine moves the median, a stall leaves
the median where it was and adds its excess.

:func:`interval_stats` reduces a list of intervals (an epoch's, in
``TrainTelemetry.epoch_stats``); :func:`step_cadence` does the same over a
list of spans — the ring's ``Span`` objects or a journaled exemplar's
``to_record()`` dicts — and names a cause for every stall from the other
spans that lay inside its interval. Stdlib only, like :mod:`.tracing`.
"""

from __future__ import annotations

from bisect import bisect_right
from collections import defaultdict

from deepdfa_tpu.obs.tracing import Span

__all__ = ["STALL_FACTOR", "interval_stats", "quantile", "step_cadence"]

# an interval over this many medians is a stall; what it has over the median
# is what the stall cost
STALL_FACTOR = 1.5
# a stall is put down to a cause that explains at least this much of its excess
_MIN_EXPLAINED = 0.25
_MAX_LISTED = 32  # stalls listed one by one, worst first; `causes` counts all
_PRODUCER = ("batch.build", "batch.h2d")


def quantile(values, q: float) -> float:
    """Nearest rank, as the benchmark's readers take it (``q`` 1.0: the
    largest)."""
    d = sorted(values)
    return d[min(len(d) - 1, int(q * len(d)))]


def interval_stats(intervals) -> dict:
    """``{steps, interval_p50_ms, interval_p99_ms, interval_max_ms, stalls,
    stall_share}`` of a list of seconds. ``stall_share`` is the share of their
    sum, in %, that the intervals over ``STALL_FACTOR`` medians have over the
    median: ~0 in a uniformly slow run, whose median is what differs."""
    intervals = list(intervals)
    if not intervals:
        return {"steps": 0}
    p50 = quantile(intervals, 0.5)
    over = [v - p50 for v in intervals if v > STALL_FACTOR * p50]
    return {
        "steps": len(intervals),
        "interval_p50_ms": round(1e3 * p50, 4),
        "interval_p99_ms": round(1e3 * quantile(intervals, 0.99), 4),
        "interval_max_ms": round(1e3 * max(intervals), 4),
        "stalls": len(over),
        "stall_share": round(100.0 * sum(over) / sum(intervals), 4),
    }


def _record(span) -> dict:
    """A span as its ``to_record()`` dict, with ``dur_s`` and ``end_s`` beside
    ``start_s``."""
    rec = span.to_record() if isinstance(span, Span) else span
    dur_s = rec.get("dur_ms", 0.0) / 1e3
    return {**rec, "dur_s": dur_s, "end_s": rec["start_s"] + dur_s}


def _union_s(parts) -> float:
    covered, reached = 0.0, float("-inf")
    for a, b in sorted(parts):
        if b > reached:
            covered += b - max(a, reached)
            reached = b
    return covered


def _self_parts(rec: dict, children: list[dict]) -> tuple[float, float | None]:
    """``(seconds, on-CPU seconds or None)`` of a span without the
    ``gc.pause`` / ``jit.*`` spans recorded under it. A compile event has no
    CPU reading: it is taken as running."""
    gc = [c for c in children if c["name"] == "gc.pause"]
    jit_s = _union_s((c["start_s"], c["end_s"])
                     for c in children if c["name"].startswith("jit."))
    self_s = max(0.0, rec["dur_s"] - sum(c["dur_s"] for c in gc) - jit_s)
    if rec.get("cpu_ms") is None:
        return self_s, None
    gc_cpu = sum(c["dur_s"] if c.get("cpu_ms") is None else c["cpu_ms"] / 1e3 for c in gc)
    return self_s, min(self_s, max(0.0, rec["cpu_ms"] / 1e3 - gc_cpu - jit_s))


def step_cadence(spans) -> dict:
    """:func:`interval_stats` of the ``loss.sync`` spans' ``interval_s``, and
    for every stall its ``step``, its excess over the median and its cause:
    the part of the interval whose seconds exceed that part's own median over
    all intervals by the most — ``gc.pause``, ``jit`` (compile events),
    ``data.wait``, ``step.dispatch on-CPU`` / ``step.dispatch blocked`` (the
    call's own seconds by its thread's CPU clock; ``step.dispatch`` where the
    span has none), ``device`` (the loop sat in ``loss.sync``), ``no span``
    (the loop's thread was between its spans: it was not running) — or
    ``other`` where none explains a quarter of the excess. A blocked call
    lists the producer's spans that overlapped it, with their on-CPU time."""
    recs = [_record(s) for s in spans]
    reads = sorted((r for r in recs if r["name"] == "loss.sync"
                    and "interval_s" in r.get("attrs", {})),
                   key=lambda r: r["end_s"])
    out = interval_stats(r["attrs"]["interval_s"] for r in reads)
    if not reads:
        return out
    ends = [r["end_s"] for r in reads]
    starts = [e - r["attrs"]["interval_s"] for e, r in zip(ends, reads)]
    under = defaultdict(list)  # span id -> the gc.pause / jit.* recorded under it
    for r in recs:
        if r["name"] == "gc.pause" or r["name"].startswith("jit."):
            under[r.get("parent_id")].append(r)

    def overlaps(a: float, b: float):
        """``(interval index, seconds)`` of ``[a, b)`` in each interval it meets."""
        i = bisect_right(ends, a)
        while i < len(ends) and starts[i] < b:
            s = min(b, ends[i]) - max(a, starts[i])
            if s > 0:
                yield i, s
            i += 1

    seconds = defaultdict(lambda: [0.0] * len(reads))  # cause -> seconds an interval
    # what of an interval none of the loop's own spans covers
    seconds["no span"] = [r["attrs"]["interval_s"] for r in reads]
    jit_parts = defaultdict(list)  # compile events nest: a union an interval
    calls = defaultdict(list)  # interval -> its step.dispatch spans
    for r in recs:
        name, a, b = r["name"], r["start_s"], r["end_s"]
        if b <= a:
            continue
        if name.startswith("jit."):
            for i, _ in overlaps(a, b):
                jit_parts[i].append((max(a, starts[i]), min(b, ends[i])))
        elif name == "gc.pause":
            for i, s in overlaps(a, b):
                seconds[name][i] += s
        elif name == "data.wait":
            for i, s in overlaps(a, b):
                seconds[name][i] += s
                seconds["no span"][i] -= s
        elif name in ("step.dispatch", "loss.sync"):
            self_s, cpu_s = _self_parts(r, under.get(r.get("span_id"), []))
            for i, s in overlaps(a, b):
                seconds["no span"][i] -= s
                part = s / (b - a)
                if name == "loss.sync":
                    seconds["device"][i] += self_s * part
                    continue
                calls[i].append(r)
                if cpu_s is None:
                    seconds["step.dispatch"][i] += self_s * part
                else:
                    seconds["step.dispatch on-CPU"][i] += cpu_s * part
                    seconds["step.dispatch blocked"][i] += (self_s - cpu_s) * part
    for i, parts in jit_parts.items():
        seconds["jit"][i] = _union_s(parts)

    p50 = out["interval_p50_ms"] / 1e3
    medians = {cause: quantile(per, 0.5) for cause, per in seconds.items()}
    producer = [r for r in recs if r["name"] in _PRODUCER
                and not r.get("attrs", {}).get("exhausted")]
    stalls, causes = [], defaultdict(lambda: {"n": 0, "excess_ms": 0.0})
    for i, r in enumerate(reads):
        interval = r["attrs"]["interval_s"]
        if interval <= STALL_FACTOR * p50:
            continue
        excess = interval - p50
        over = {cause: per[i] - medians[cause] for cause, per in seconds.items()}
        cause = max(over, key=over.get, default="other")
        if over.get(cause, 0.0) < _MIN_EXPLAINED * excess:
            cause = "other"
        row = {"step": r["attrs"].get("step"), "interval_ms": round(1e3 * interval, 3),
               "excess_ms": round(1e3 * excess, 3), "cause": cause,
               "cause_ms": round(1e3 * over.get(cause, 0.0), 3)}
        if cause == "step.dispatch blocked":
            row["producer"] = [
                {"name": p["name"], "overlap_ms": round(1e3 * s, 3), "cpu_ms": p.get("cpu_ms")}
                for c in calls[i] for p in producer
                if (s := min(c["end_s"], p["end_s"]) - max(c["start_s"], p["start_s"])) > 0]
        stalls.append(row)
        causes[cause]["n"] += 1
        causes[cause]["excess_ms"] = round(causes[cause]["excess_ms"] + 1e3 * excess, 3)
    stalls.sort(key=lambda s: -s["excess_ms"])
    out["causes"] = dict(causes)
    out["worst"] = stalls[:_MAX_LISTED]
    return out
