"""Reduce a profiler trace to device busy time, idle gaps and the costliest ops.

Two steps, so that the arithmetic can be tested on a small recorded trace:
:func:`load_events` turns an ``.xplane.pb`` into plain rows
``[plane, line, name, start_ns, dur_ns]`` (device-op rows and the benchmark's
own ``bench:*`` host annotations only), and :func:`reduce` turns rows into
numbers. All planes of one trace share one clock.
"""

from __future__ import annotations

import re
import shutil
import tempfile
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
_HLO = re.compile(r"^(%[\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def short_name(hlo: str) -> str:
    """An op as the trace names it, cut to ``%name shape``: the trace gives
    the whole HLO instruction, operands and layouts included."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    return m.group(1) + (f" {m.group(2)}" if m.group(2) else "")


class Profiler:
    """The JAX profiler writing under a fresh directory inside ``TMPDIR``."""

    def __init__(self):
        self.dir = Path(tempfile.mkdtemp(prefix="bench_trace_"))
        self.running = False

    def start(self) -> None:
        import jax

        jax.profiler.start_trace(str(self.dir))
        self.running = True

    def stop(self) -> None:
        import jax

        if self.running:
            jax.profiler.stop_trace()
            self.running = False

    def events(self) -> list[list]:
        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return load_events(files[-1])

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def load_events(path) -> list[list]:
    from jax.profiler import ProfileData

    rows = []
    for plane in ProfileData.from_file(str(path)).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(HOST_PREFIX):
                    rows.append([plane.name, line.name, ev.name,
                                 int(ev.start_ns), int(ev.duration_ns)])
    return rows


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def reduce(rows: list[list], top: int = 10) -> dict | None:
    """``busy_s`` (union of device-op intervals, averaged over the device
    planes), ``window_s`` (first start to last end of anything kept),
    ``device_ops`` and ``idle_gaps`` (each at most ``top`` ``[name, seconds]``,
    largest first; a gap is named by the benchmark's host span that covers
    most of it). ``None`` when no operation ran on a device."""
    by_plane: dict[str, list[tuple[int, int]]] = defaultdict(list)
    op_ns: dict[str, int] = defaultdict(int)
    host: list[tuple[int, int, str]] = []
    for plane, _line, name, start, dur in rows:
        if plane.startswith(DEVICE_PLANE):
            by_plane[plane].append((start, start + dur))
            op_ns[short_name(name)] += dur
        else:
            host.append((start, start + dur, name[len(HOST_PREFIX):]))
    if not by_plane:
        return None
    t0 = min([a for iv in by_plane.values() for a, _ in iv] + [a for a, _, _ in host])
    t1 = max([b for iv in by_plane.values() for _, b in iv] + [b for _, b, _ in host])
    busy_ns, gap_ns = 0, defaultdict(int)
    host.sort()
    for intervals in by_plane.values():
        merged = _union(intervals)
        busy_ns += sum(b - a for a, b in merged)
        edges = [t0] + [t for iv in merged for t in iv] + [t1]
        for ga, gb in zip(edges[0::2], edges[1::2]):
            if gb <= ga:
                continue
            cover: dict[str, int] = defaultdict(int)
            for ha, hb, name in host:
                if ha >= gb:
                    break
                if hb > ga:
                    cover[name] += min(hb, gb) - max(ha, ga)
            name = max(cover, key=cover.get) if cover else "no-host-span"
            gap_ns[name] += gb - ga
    n = len(by_plane)
    rank = lambda d, div: [[k, v / div / 1e9] for k, v in
                           sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "device_ops": rank(op_ns, n),
        "idle_gaps": rank(gap_ns, n),
    }
