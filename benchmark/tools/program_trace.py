"""Lay the program's own spans beside the device trace of one cell's traced
slice. A tool, not a metric: it prints tables for PERF.md and goes when
``harness/trace.py`` reads the same (PERF.md section 7 says what to fold in).

    python3 benchmark/tools/program_trace.py --workload <name> --seed <n> \
        [--seconds 6] [--benchmark-file ...]

It runs the cell as ``run.py --trace 1`` does (set-up, a window, then the
traced slice) and reads the slice's xplane itself, because the harness keeps
only its own ``bench:*`` annotations and only the instruction's name:

(a) the device's idle gaps by the program's ``deepdfa:*`` span that covers
    most of each, once by the loop's thread and once by the producer's;
(b) the loop's ``step.dispatch`` by how much of it a producer span
    (``batch.build`` / ``batch.h2d``) overlapped, from the program's ring over
    window and slice: whether the producer's work lengthens the call;
(c) device time by named scope: the ``tf_op`` stat of the ``XLA Ops`` events'
    metadata (``jit(train_step)/<scopes>/<primitive>:``) without its
    ``jit(..)`` wrappers and its primitive, cut to a root and three levels.

One JSON object goes to ``chiprun_out/program_trace_<workload>.json`` too.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import types

_PROCESS_START = time.time()

from collections import Counter, defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))

PREFIX = "deepdfa:"
ROOTS = ("train.epoch",)  # spans that only hold others: no gap is theirs
PRODUCER = ("batch.build", "batch.h2d")
BENCH_SPANS = ("data.wait", "step.dispatch", "loss.sync")  # the driver's, in Phases.spans
SCOPE_STAT = "tf_op"  # where the chip's trace keeps jit(train_step)/<scopes>/<primitive>:
LEVELS = 3


def scope_of(op_name: str, levels: int = LEVELS) -> str:
    """``jit(train_step)/jit(main)/jvp(M)/a/b/c/d/mul`` -> ``jvp(M)/a/b/c``."""
    parts = [p for p in op_name.split("/") if p and not p.startswith(("jit(", "pjit("))]
    parts = parts[:-1] or parts  # the last part is the primitive
    return "/".join(parts[:1 + levels]) or "(unnamed)"


def xspace_class():
    """The ``XSpace`` message of an ``.xplane.pb``, declared here field by
    field (tsl/profiler/protobuf/xplane.proto): ``jax.profiler.ProfileData``
    yields an event's own stats only, and an op's name and counts sit on its
    *metadata* (``tf_op``, ``flops``, ``bytes_accessed``, ``hlo_category``)."""
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    f = descriptor_pb2.FileDescriptorProto(name="xplane_min.proto", package="xp", syntax="proto3")
    T = descriptor_pb2.FieldDescriptorProto
    kinds = {"i": T.TYPE_INT64, "u": T.TYPE_UINT64, "d": T.TYPE_DOUBLE, "s": T.TYPE_STRING,
             "b": T.TYPE_BYTES}
    messages = {
        "XStat": "metadata_id:1:i double_value:2:d uint64_value:3:u int64_value:4:i "
                 "str_value:5:s bytes_value:6:b ref_value:7:u",
        "XEvent": "metadata_id:1:i offset_ps:2:i duration_ps:3:i stats:4:*XStat",
        "XLine": "id:1:i name:2:s timestamp_ns:3:i events:4:*XEvent",
        "XEventMetadata": "id:1:i name:2:s display_name:4:s stats:5:*XStat",
        "XStatMetadata": "id:1:i name:2:s",
        "EventMetadataEntry": "key:1:i value:2:XEventMetadata",  # map<int64, ..> on the wire
        "StatMetadataEntry": "key:1:i value:2:XStatMetadata",
        "XPlane": "id:1:i name:2:s lines:3:*XLine event_metadata:4:*EventMetadataEntry "
                  "stat_metadata:5:*StatMetadataEntry",
        "XSpace": "planes:1:*XPlane",
    }
    for name, fields in messages.items():
        m = f.message_type.add(name=name)
        for field in fields.split():
            fname, number, kind = field.split(":")
            fd = m.field.add(name=fname, number=int(number), label=(
                T.LABEL_REPEATED if kind.startswith("*") else T.LABEL_OPTIONAL))
            if kind.lstrip("*") in kinds:
                fd.type = kinds[kind.lstrip("*")]
            else:
                fd.type, fd.type_name = T.TYPE_MESSAGE, ".xp." + kind.lstrip("*")
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(pool.FindMessageTypeByName("xp.XSpace"))


def read_xplane(path) -> dict:
    """Device-op intervals with their metadata's stats, and the ``deepdfa:*``
    host events by thread (a thread is a line of a host plane; their names
    need not differ, so they are told by place)."""
    from harness.trace import DEVICE_PLANE, OPS_LINE

    space = xspace_class()()
    space.ParseFromString(Path(path).read_bytes())
    ops, host, stat_names = [], defaultdict(list), defaultdict(int)
    for plane in space.planes:
        device = plane.name.startswith(DEVICE_PLANE)
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        metadata = {e.key: e.value for e in plane.event_metadata}
        op_stats: dict[int, dict] = {}  # one dict an instruction, not an event
        for at, line in enumerate(plane.lines):
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                md = metadata.get(ev.metadata_id)
                if md is None or not (device or md.name.startswith(PREFIX)):
                    continue
                a = line.timestamp_ns + ev.offset_ps // 1000
                b = a + ev.duration_ps // 1000
                if not device:
                    host[(plane.name, at, line.name)].append((a, b, md.name[len(PREFIX):]))
                    continue
                if ev.metadata_id not in op_stats:
                    op_stats[ev.metadata_id] = {
                        stat_name.get(s.metadata_id, "?"): s.str_value or s.int64_value
                        or s.uint64_value or s.double_value or stat_name.get(s.ref_value, "")
                        for s in md.stats}
                    for k in op_stats[ev.metadata_id]:
                        stat_names[k] += 1
                ops.append((plane.name, a, b, md.name, op_stats[ev.metadata_id]))
    return {"ops": ops, "host": dict(host), "stat_names": dict(stat_names)}


def gaps_by_span(ops, spans) -> tuple[dict, float]:
    """Seconds of device idle time by the span of ``spans`` (``[(a, b, name)]``
    of one thread) that covers most of each gap, and the idle seconds in all.
    Gaps are taken inside first op start .. last op end of each device plane."""
    from harness.trace import _union

    by_plane = defaultdict(list)
    for plane, a, b, _name, _stats in ops:
        by_plane[plane].append((a, b))
    spans = sorted(s for s in spans if s[2] not in ROOTS)
    out, idle = defaultdict(int), 0
    for intervals in by_plane.values():
        merged = _union(intervals)
        for (_, ga), (gb, _) in zip(merged, merged[1:]):
            cover = defaultdict(int)
            for a, b, name in spans:
                if a >= gb:
                    break
                if b > ga:
                    cover[name] += min(b, gb) - max(a, ga)
            out[max(cover, key=cover.get) if cover else "(no span)"] += gb - ga
            idle += gb - ga
    n = max(1, len(by_plane))
    return {k: v / n / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}, idle / n / 1e9


def dispatch_by_overlap(ring, since: float) -> list[dict]:
    """The ring's ``step.dispatch`` spans that began after ``since``, in three
    bins by the producer time that overlapped each: none, under and over the
    median of those overlapped."""
    calls = [s for s in ring if s.name == "step.dispatch" and s.start_s >= since]
    busy = sorted((s.start_s, s.start_s + s.dur_s) for s in ring
                  if s.name in PRODUCER and not s.attrs.get("exhausted"))
    rows = []
    for s in calls:
        a, b = s.start_s, s.start_s + s.dur_s
        rows.append((sum(max(0.0, min(b, y) - max(a, x)) for x, y in busy), s.dur_s))
    over = sorted(o for o, _ in rows if o > 0)
    median = over[len(over) // 2] if over else 0.0
    bins = {"no producer span overlaps": [r for r in rows if r[0] == 0],
            "overlap under its median": [r for r in rows if 0 < r[0] < median],
            "overlap at or over its median": [r for r in rows if r[0] > 0 and r[0] >= median]}
    return [{"steps": k, "n": len(v),
             "dispatch_ms": 1e3 * sum(d for _, d in v) / len(v) if v else None,
             "overlap_ms": 1e3 * sum(o for o, _ in v) / len(v) if v else None}
            for k, v in bins.items()]


def ring_means(ring, since: float) -> dict:
    """``{name: [n, mean ms]}`` of the ring's spans that began after ``since``."""
    by_name = defaultdict(list)
    for s in ring:
        if s.start_s >= since and not s.attrs.get("exhausted") and s.name not in ROOTS:
            by_name[s.name].append(s.dur_s)
    return {k: [len(v), 1e3 * sum(v) / len(v)] for k, v in sorted(by_name.items())}


def device_by_scope(ops) -> dict:
    """Seconds on the device by named scope (ops without the stat by
    instruction name), largest first."""
    from harness.trace import short_name

    planes = len({o[0] for o in ops}) or 1
    out = defaultdict(int)
    for _plane, a, b, name, stats in ops:
        op_name = str(stats.get(SCOPE_STAT, "")).rstrip(":")
        out[scope_of(op_name) if op_name else f"(no op name) {short_name(name)}"] += b - a
    return {k: v / planes / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=6.0, help="the window before the slice")
    ap.add_argument("--trace-seconds", type=float, default=2.0)
    ap.add_argument("--benchmark-file", default=None)
    ap.add_argument("--keep-xplane", default=None, help="copy the slice's .xplane.pb here")
    args = ap.parse_args(argv)

    from deepdfa_tpu import obs, utils
    from harness import spec, traffic
    from harness.phases import Phases
    from harness.trace import Profiler

    utils.setup_compile_cache()
    info = utils.require_backend()
    bench = json.loads(Path(args.benchmark_file).read_text()) if args.benchmark_file else None
    cell = spec.load_cell(args.workload, bench)
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    driver = spec.load_module("drivers", cfg["entry"]).Driver(cfg, reference)
    data = traffic.generate(cell["cell"]["traffic"], args.seed)
    driver.load(data, reference.make_weights(cfg, args.seed), args.seed)
    profiler = Profiler()
    phases = Phases(_PROCESS_START, driver.setup_steps, args.seconds,
                    trace_seconds=args.trace_seconds, profiler=profiler)
    try:
        driver.run(phases)
    finally:
        profiler.stop()
    try:
        files = sorted(profiler.dir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {profiler.dir}")
        trace = read_xplane(files[-1])
        if args.keep_xplane:
            import shutil

            shutil.copy(files[-1], args.keep_xplane)
    finally:
        profiler.cleanup()

    ops, host = trace["ops"], trace["host"]
    threads = {"loop": [], "producer": []}
    for (_plane, _at, _name), events in host.items():
        names = {n for _, _, n in events}
        if "step.dispatch" in names:
            threads["loop"] += events
        elif names & set(PRODUCER):
            threads["producer"] += events
    telemetry = obs.train_telemetry()
    ring = telemetry.tracer.spans()
    window_t0 = _PROCESS_START + phases.setup_s
    covered = spec.load_module("readers", "program_span_covered_s").read
    before = lambda *names: covered(types.SimpleNamespace(phases=phases), list(names), "setup")
    counts = telemetry.snapshot()
    out = {
        "workload": args.workload, "seed": args.seed, "platform": info["platform"],
        "steps": {"window": phases.window_steps, "traced": phases.traced_steps,
                  "window_s": phases.window_s, "traced_s": phases.traced_s},
        "setup": {"setup_s": phases.setup_s,
                  "jit_trace_lower_s": before("jit.trace", "jit.lower"),
                  "jit_backend_s": before("jit.backend_compile"),
                  "cache_hits": counts["cache_hits"], "cache_misses": counts["cache_misses"]},
        "annotations": {k: dict(sorted(Counter(n for _, _, n in v).items())) for k, v in threads.items()},
        "ring_mean_ms": ring_means(ring, window_t0),
        # the benchmark's own spans round the same boundaries, from outside
        "bench_mean_ms": {name: [len(d), 1e3 * sum(d) / len(d)] for name in BENCH_SPANS
                          if (d := phases.durations(name) + phases.durations(name, "traced"))},
        "dispatch_by_overlap": dispatch_by_overlap(ring, window_t0),
        "stat_names": trace["stat_names"],
    }
    if ops:
        for thread, events in threads.items():
            table, idle = gaps_by_span(ops, events)
            out[f"idle_gaps_by_{thread}_span"] = table
            out["idle_s"] = idle
        named = sum(v for k, v in out["idle_gaps_by_loop_span"].items() if k != "(no span)")
        out["idle_named_share"] = named / out["idle_s"] if out["idle_s"] else None
        out["device_s_by_scope"] = device_by_scope(ops)
    _print(out)
    out_dir = BENCH.parent / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    (out_dir / f"program_trace_{args.workload}.json").write_text(json.dumps(out, indent=1))
    return 0


def _print(out: dict) -> None:
    print(f"# {out['workload']} seed {out['seed']} on {out['platform']}: "
          f"{out['steps']['window']} steps in the window, {out['steps']['traced']} in the slice")
    print(f"set-up: {out['setup']}")
    for thread, counts in out["annotations"].items():
        print(f"annotations on the {thread}'s thread: {counts}")
    for thread in ("loop", "producer"):
        table = out.get(f"idle_gaps_by_{thread}_span")
        if table is not None:
            print(f"\n(a) device idle {out['idle_s']:.4f} s of the slice, by the {thread}'s span")
            for name, s in table.items():
                print(f"  {s:9.4f} s  {100 * s / out['idle_s'] if out['idle_s'] else 0:5.1f}%  {name}")
    if out.get("idle_named_share") is not None:
        print(f"  named by a loop span: {100 * out['idle_named_share']:.1f}% of the idle time")
    print("\nring, window + slice: " + ", ".join(
        f"{k} {ms:.3f} ms x {n}" for k, (n, ms) in out["ring_mean_ms"].items()))
    print("benchmark's spans, window + slice: " + ", ".join(
        f"{k} {ms:.3f} ms x {n}" for k, (n, ms) in out["bench_mean_ms"].items()))
    print("\n(b) step.dispatch by producer overlap (ring: window + slice)")
    for row in out["dispatch_by_overlap"]:
        shown = lambda v: "-" if v is None else f"{v:8.3f}"
        print(f"  n {row['n']:4d}  dispatch {shown(row['dispatch_ms'])} ms  "
              f"overlap {shown(row['overlap_ms'])} ms  {row['steps']}")
    if "device_s_by_scope" in out:
        print(f"\n(c) device time by scope (stat {SCOPE_STAT!r} of the ops' metadata; stats seen: "
              f"{sorted(out['stat_names'])})")
        for name, s in list(out["device_s_by_scope"].items())[:45]:
            print(f"  {s:9.5f} s  {name}")


if __name__ == "__main__":
    sys.exit(main())
