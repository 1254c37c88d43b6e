"""Read, on many seeds in one process, the numbers ``correct`` compares: the
program against the plain reference (the lower readings), the control (the
reference in the precision below, in the program's place) and the planted
faults (the upper readings). No measured window: training's readings need
none. One JSON line a seed, to ``chiprun_out/`` and to standard output, with
the count of vulnerable rows in each checked batch and, with ``--leaves``, both
sides' per-leaf norms (``grad1``, ``delta``) for a look at a seed that reads
far off.

    python3 benchmark/tools/prove.py --workload <name> --seeds 11,12,13 \
        [--control-seeds 3] [--leaves] [--benchmark-file ...]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also run the control and the faults")
    ap.add_argument("--leaves", action="store_true", help="also both sides' per-leaf norms")
    ap.add_argument("--benchmark-file", default=None)
    args = ap.parse_args()

    from deepdfa_tpu import utils
    from harness import compare, spec, traffic
    from harness.phases import Phases

    utils.setup_compile_cache()
    info = utils.require_backend()
    bench = json.loads(Path(args.benchmark_file).read_text()) if args.benchmark_file else None
    cell = spec.load_cell(args.workload, bench)
    cfg = cell["config"]
    reference = spec.load_module("reference", cfg["reference"])
    driver = spec.load_module("drivers", cfg["entry"]).Driver(cfg, reference)
    out_dir = BENCH.parent / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    numbers = lambda a, b: compare.numbers(reference.COMPARISON, a, b)
    strip = lambda nums: {k: v for k, v in nums.items() if not k.endswith("_at")}

    with open(out_dir / f"prove_{args.workload}.jsonl", "a") as sink:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            data = traffic.generate(cell["cell"]["traffic"], seed)
            driver.load(data, reference.make_weights(cfg, seed), seed)
            run = driver.run(Phases(t0, driver.setup_steps, 0.0))
            driver.free()
            t1 = time.time()
            ref = reference.run(cfg, data, seed, **run["follow"])
            row = {"workload": args.workload, "seed": seed, "device": info["device_kind"],
                   "program": numbers(run["readings"], ref),
                   "program_s": t1 - t0, "reference_s": time.time() - t1,
                   "loss": run["readings"]["loss"], "ref_loss": ref["loss"],
                   "vulnerable_rows": [int(data["labels"][r].sum())
                                       for r in run["follow"]["step_rows"]]}
            if args.leaves:
                row["grad1"] = {"program": run["readings"]["grad1"], "reference": ref["grad1"]}
                row["delta"] = {"program": run["readings"]["delta"], "reference": ref["delta"]}
            if i < args.control_seeds:
                for name, kw in [("control_fp8", {"precision": "fp8"}),
                                 ("fault_half_batch", {"fault": "half_batch"}),
                                 ("fault_state_unchanged", {"fault": "state_unchanged"})]:
                    other = reference.run(cfg, data, seed, **run["follow"], **kw)
                    row[name] = strip(numbers(other, ref))
            line = json.dumps(row)
            sink.write(line + "\n")
            sink.flush()
            print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
