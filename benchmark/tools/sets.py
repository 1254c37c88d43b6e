"""Run a cell's sets of runs the way the driver does — one new process a run,
the same seeds in every set — and print each metric's spread (distance between
the first and third quartile as a share of the median, per set). The parent
never imports JAX: a chip belongs to one process at a time.

    python3 benchmark/tools/sets.py --workload <name> --seeds 1,2,3,4,5,6 \
        --sets 2 [--seconds 20] [--trace-seeds 7,8,9]
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent


def one(workload: str, seed: int, seconds: float, trace: int, extra: list[str]) -> dict:
    cmd = [sys.executable, "benchmark/run.py", "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace), *extra]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(f"{' '.join(cmd)} exited {proc.returncode}:\n{proc.stderr[-3000:]}")
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    row.update(workload=workload, seed=seed, trace=trace, wall_s=time.time() - t0,
               setup=next((ln for ln in proc.stderr.splitlines() if ln.startswith("setup:")), ""))
    return row


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--benchmark-file", default=None)
    args = ap.parse_args()
    bench = json.loads(Path(args.benchmark_file or ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    extra = ["--benchmark-file", args.benchmark_file] if args.benchmark_file else []
    seeds = [int(s) for s in args.seeds.split(",")]
    out = ROOT / "chiprun_out"
    out.mkdir(exist_ok=True)
    with open(out / f"sets_{args.workload}.jsonl", "a") as sink:
        def run(seed, trace, label):
            row = one(args.workload, seed, seconds, trace, extra)
            row["set"] = label
            sink.write(json.dumps(row) + "\n")
            sink.flush()
            shown = {k: round(v["value"], 4) for k, v in row["metrics"].items()}
            print(label, seed, "correct" if row["correct"] else "NOT CORRECT", shown,
                  f"wall {row['wall_s']:.0f}s", row["setup"], flush=True)
            return row

        sets = [[run(s, 0, f"set{i}") for s in seeds] for i in range(args.sets)]
        for s in (int(x) for x in args.trace_seeds.split(",") if x):
            run(s, 1, "trace")
    for name in sets[0][0]["metrics"]:
        per_set = [[r["metrics"][name]["value"] for r in rows] for rows in sets]
        print(name, "medians", [statistics.median(v) for v in per_set],
              "spreads", [round(spread(v), 5) for v in per_set],
              "first runs", [v[0] for v in per_set])
    return 0


if __name__ == "__main__":
    sys.exit(main())
