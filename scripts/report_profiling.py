#!/usr/bin/env python
"""Aggregate profiling jsonl into per-example stats.

Parity with the reference's ``scripts/report_profiling.py:1-66`` (gflops /
gmacs / avg ms per example over ``profiledata.jsonl`` + ``timedata.jsonl``);
the aggregation itself lives in ``deepdfa_tpu.train.profiling.report``.

``--traces`` switches to the tracing view: per-span-name duration stats
(and on-CPU share) and the cadence of each epoch's completed steps
over a run dir's ``event=trace`` exemplars (``deepdfa_tpu.obs``) — where
a slow request actually spent its time (queue wait vs batch assembly vs
engine dispatch), straight from the journaled traces. Use
``deepdfa-tpu trace export --run-dir <dir>`` for the Perfetto-openable
Chrome JSON.

Usage: python scripts/report_profiling.py [--traces] RUN_DIR [RUN_DIR ...]
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _by_name_row(rows: list[tuple]) -> dict:
    """``rows`` = one span name's ``(dur_ms, cpu_ms or None)``."""
    durs = [d for d, _ in rows]
    out = {"count": len(rows), "mean_ms": round(sum(durs) / len(rows), 4),
           "max_ms": round(max(durs), 4)}
    timed = [(d, c) for d, c in rows if c is not None]
    if timed:
        # on-CPU share of the spans that carry their thread's CPU clock
        out["cpu_share"] = round(
            sum(c for _, c in timed) / max(sum(d for d, _ in timed), 1e-9), 4)
    return out


def trace_report(run_dir) -> dict:
    """Per-span-name {count, mean_ms, max_ms, cpu_share} over the run's
    exemplars, and for each one that holds a training epoch the cadence of
    its completed steps (``obs.step_cadence``: interval quantiles, the share
    lost to stalls, each stall's cause)."""
    from deepdfa_tpu.obs import load_trace_records, step_cadence

    records = load_trace_records(run_dir)
    by_name: dict[str, list[tuple]] = {}
    cadence = []
    for rec in records:
        spans = rec.get("spans", [])
        for span in spans:
            by_name.setdefault(span["name"], []).append(
                (float(span.get("dur_ms", 0.0)), span.get("cpu_ms")))
        summary = step_cadence(spans)
        if summary["steps"]:
            epoch = next((s["attrs"].get("epoch") for s in spans
                          if s["name"] == rec.get("root")), None)
            cadence.append({"epoch": epoch, **summary})
    report = {"trace_records": len(records),
              "spans": {name: _by_name_row(rows) for name, rows in sorted(by_name.items())}}
    if cadence:
        report["cadence"] = cadence
    return report


def main(argv=None) -> None:
    args = list(argv if argv is not None else sys.argv[1:])
    traces = "--traces" in args
    if traces:
        args.remove("--traces")
    for run_dir in args:
        if traces:
            print(json.dumps({"run_dir": str(run_dir),
                              **trace_report(run_dir)}))
        else:
            from deepdfa_tpu.train.profiling import report

            stats = report(run_dir)
            print(json.dumps({"run_dir": str(run_dir), **stats}))


if __name__ == "__main__":
    main()
