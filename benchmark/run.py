"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: it makes the cell's inputs and weights from ``--seed``, has the
cell's driver build the program's state and drive the entry point through the
checked and warm steps (all of that is ``setup_s``), measures for
``--seconds``, and — with ``--trace 1`` — lets the loop run a few seconds more
under the profiler. Then it reads the memory peak, frees the program's state,
runs the plain reference over the checked steps, and prints one JSON object as
the last line of standard output. It fails, and prints no result, when the
backend is not the TPU (unless ``JAX_PLATFORMS`` pins another, which the result
then names) or holds fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

_PROCESS_START = time.time()

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

TRACE_SECONDS = 2.0  # the traced slice that follows the window under --trace 1


@dataclasses.dataclass
class ReadCtx:
    """What a metric's reader may read."""

    config: dict
    phases: object
    counters: dict  # the window's exact counts of work, from the driver
    device: dict
    trace: dict | None  # harness.trace.reduce of the traced slice


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-file", default=None,
                    help="another BENCHMARK.json (the tests' tiny one)")
    args = ap.parse_args(argv)

    from deepdfa_tpu import utils  # absent in a directory that holds only the benchmark

    from harness import compare, spec, traffic
    from harness.phases import Phases
    from harness.trace import Profiler, reduce

    utils.setup_compile_cache()
    info = utils.require_backend()  # raises unless tpu, or the platform JAX_PLATFORMS pins

    import jax

    bench = None
    if args.benchmark_file:
        bench = json.loads(Path(args.benchmark_file).read_text())
    cell = spec.load_cell(args.workload, bench)
    cfg = cell["config"]
    chips = cell["cell"]["chips"]
    if info["device_count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), JAX found {info['device_count']}")
    devices = jax.devices()[:chips]

    marks = [("start", _PROCESS_START), ("backend", time.time())]  # set-up's timeline
    reference = spec.load_module("reference", cfg["reference"])
    driver = spec.load_module("drivers", cfg["entry"]).Driver(cfg, reference)
    data = traffic.generate(cell["cell"]["traffic"], args.seed)
    marks.append(("traffic", time.time()))
    driver.load(data, reference.make_weights(cfg, args.seed), args.seed)
    marks.append(("weights_and_state", time.time()))

    profiler = Profiler() if args.trace else None
    phases = Phases(
        _PROCESS_START, driver.setup_steps, args.seconds,
        trace_seconds=TRACE_SECONDS if args.trace else 0.0, profiler=profiler)
    try:
        out = driver.run(phases)
    finally:
        if profiler is not None:
            profiler.stop()

    marks.append(("checked_and_warm_steps", _PROCESS_START + phases.setup_s))
    print("setup: " + ", ".join(f"{name} {t1 - t0:.1f}s" for (_, t0), (name, t1)
                                in zip(marks, marks[1:])), file=sys.stderr)
    stats = [d.memory_stats() or {} for d in devices]
    print(f"memory_stats: {json.dumps(stats)}", file=sys.stderr)
    device = {
        "platform": info["platform"],
        "kind": info["device_kind"],
        "count": chips,
        # what the chip held at its fullest: the allocator's buffers (state,
        # batches, program code) plus the region the runtime reserves for the
        # loaded programs' temporaries, which peak_bytes_in_use leaves out
        "memory_peak_bytes": max(
            (s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0) for s in stats),
            default=0),
        "memory_peak_bytes_in_use": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
    }
    trace = breakdown = None
    if profiler is not None:
        trace = reduce(profiler.events())
        profiler.cleanup()
        if trace is not None:
            device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
            breakdown = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}

    # the program's state goes before the reference touches the chip
    driver.free()
    t_ref = time.perf_counter()
    ref = reference.run(cfg, data, args.seed, **out["follow"])
    t_ref = time.perf_counter() - t_ref
    nums = compare.numbers(reference.COMPARISON, out["readings"], ref)
    correct, shown = compare.judge(nums, cfg["limits"])

    # every metric is its own data file naming a reader; a per-layer reader
    # that finds nothing to read returns nothing and the line leaves it out
    ctx = ReadCtx(cfg, phases, out["counters"], device, trace)
    metrics = {}
    for m in cell["per_layer" if args.trace else "end_to_end"]:
        value = spec.load_module("readers", m["reader"]).read(ctx, **m["args"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not args.trace:
            raise RuntimeError(f"end-to-end metric {m['name']} found nothing to read")

    failed = out["failed"]
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": out["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = {"seconds": phases.window_s, "steps": phases.window_steps,
                        "reference_s": t_ref}
    result["observed"] = {k: v for k, v in nums.items()
                          if k not in shown and not k.endswith("_at")}
    result["compared"] = shown
    for name, row in shown.items():
        print(f"compared {name}: {row['value']:.6g} (limit {row['limit']:.6g})"
              + (f" at {row['at']}" if "at" in row else ""), file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
