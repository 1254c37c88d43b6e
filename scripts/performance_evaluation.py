#!/usr/bin/env python
"""End-to-end performance evaluation — the reference protocol, no GPU needed.

Parity with ``scripts/performance_evaluation.sh`` / ``_cpu.sh`` (3 timed
train+test runs; the reference shells into Docker and flips
``--trainer.gpus``): here each run is ``fit`` then ``test`` (with
profiling on) through the public CLI, in this one process, on the TPU — or
on the platform ``JAX_PLATFORMS`` pins (the reference's own protocol has a
CPU leg, ``performance_evaluation_cpu.sh``), labelled as such. Emits
``performance_evaluation.json`` with per-run wall times, test F1 and
profiled throughput, plus the aggregate.

Usage: python scripts/performance_evaluation.py [--runs 3] [--out DIR]
       [--config cfg.yaml ...] [--set k=v ...]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def full_protocol(args, out_dir: Path) -> dict:
    """The reference's ACTUAL 3-stage protocol
    (``scripts/performance_evaluation.sh``): train DeepDFA, train LineVul,
    train DeepDFA+LineVul — here hermetically on the demo sample corpus
    (DeepDFA = GGNN fit/test; LineVul = roberta encoder only, no GNN;
    combined = roberta + frozen pretrained GGNN), with per-stage wall
    times and test metrics. Honors ``--runs`` (the reference repeats the
    protocol 3×); ``stages``/``total_seconds`` quote the LAST run, every
    run is in ``runs``."""
    import jax

    import scripts.preprocess as pp
    import scripts.train_joint as tj
    from deepdfa_tpu.train import cli

    # demo sample shards (idempotent)
    pp.main(["--dataset", "demo", "--n", "120", "--sample"])

    runs: list[dict] = []
    agg = {
        "protocol": "full (train DeepDFA; train LineVul; train DeepDFA+LineVul "
                    "- performance_evaluation.sh parity, hermetic demo corpus)",
        "backend": jax.default_backend(),
        "stages": None,
        "total_seconds": None,
        "runs": runs,
    }

    for i in range(args.runs):
        run_dir = out_dir / f"run_{i}" if args.runs > 1 else out_dir
        stages: dict[str, dict] = {}
        agg["stages"] = stages
        runs.append({"stages": stages, "total_seconds": None})

        def timed(name, fn):
            t0 = time.monotonic()
            out = fn()
            stages[name] = {"seconds": round(time.monotonic() - t0, 2), **out}
            print(json.dumps({name: stages[name]}), file=sys.stderr, flush=True)

        ggnn_dir = run_dir / "deepdfa"
        small = [x for o in (
            "data.sample=true", "data.dsname=demo", "optim.max_epochs=3",
        ) + tuple(args.overrides) for x in ("--set", o)]

        def stage_deepdfa():
            cli.main(["fit", "--run-dir", str(ggnn_dir), *small])
            r = cli.main(["test", "--run-dir", str(ggnn_dir),
                          "--ckpt-dir", str(ggnn_dir / "checkpoints"), *small])
            return {"test_F1Score": r.get("test_F1Score")}

        def stage_linevul():
            r = tj.main(["--dataset", "demo", "--sample", "--encoder", "roberta",
                         "--no_flowgnn", "--do_train", "--do_test",
                         "--epochs", "2",
                         "--output_dir", str(run_dir / "linevul")])
            return {"test_f1_weighted": r.get("test_f1_weighted")}

        def stage_combined():
            r = tj.main(["--dataset", "demo", "--sample", "--encoder", "roberta",
                         "--freeze-graph", str(ggnn_dir / "checkpoints"),
                         "--do_train", "--do_test", "--epochs", "2",
                         "--output_dir", str(run_dir / "combined")])
            return {"test_f1_weighted": r.get("test_f1_weighted")}

        timed("deepdfa", stage_deepdfa)
        timed("linevul", stage_linevul)
        timed("deepdfa_linevul", stage_combined)
        total = round(sum(s["seconds"] for s in stages.values()), 2)
        runs[-1]["total_seconds"] = agg["total_seconds"] = total

    (out_dir / "performance_evaluation.json").write_text(json.dumps(agg, indent=2))
    print(json.dumps(agg))
    return agg


def main(argv=None) -> dict:
    parser = argparse.ArgumentParser()
    parser.add_argument("--runs", type=int, default=3)  # 3-run repetition
    parser.add_argument("--protocol", choices=("ggnn", "full"), default="ggnn",
                        help="ggnn: N timed GGNN fit/test repetitions (fast, "
                        "the bench-loop default); full: the reference's "
                        "3-stage DeepDFA / LineVul / DeepDFA+LineVul protocol")
    parser.add_argument("--out", default=None)
    parser.add_argument("--config", action="append", default=[])
    parser.add_argument("--set", action="append", default=[], dest="overrides")
    args = parser.parse_args(argv)

    from bench import start_on_device
    from deepdfa_tpu import utils
    from deepdfa_tpu.train import cli

    start_on_device()
    if args.protocol == "full":
        out_dir = Path(args.out) if args.out else utils.storage_dir() / "perf_eval_full"
        out_dir.mkdir(parents=True, exist_ok=True)
        return full_protocol(args, out_dir)

    out_dir = Path(args.out) if args.out else utils.storage_dir() / "perf_eval"
    out_dir.mkdir(parents=True, exist_ok=True)

    # Keep the default protocol fast enough to run in the bench loop: the
    # sample-scale corpus and a short fit unless a config overrides it.
    base_overrides = [
        "data.sample=true",
        "optim.max_epochs=3",
        "profile=true",
        "time=true",
    ] + args.overrides

    runs = []
    for i in range(args.runs):
        run_dir = out_dir / f"run_{i}"
        t0 = time.monotonic()
        cli.main(
            ["fit", "--run-dir", str(run_dir)]
            + [x for c in args.config for x in ("--config", c)]
            + [x for o in base_overrides for x in ("--set", o)]
        )
        fit_s = time.monotonic() - t0
        t1 = time.monotonic()
        results = cli.main(
            ["test", "--run-dir", str(run_dir)]
            + [x for c in args.config for x in ("--config", c)]
            + [x for o in base_overrides for x in ("--set", o)]
        )
        test_s = time.monotonic() - t1
        runs.append(
            {
                "run": i,
                "fit_seconds": round(fit_s, 2),
                "test_seconds": round(test_s, 2),
                "test_F1Score": results.get("test_F1Score"),
                "profile_examples_per_sec": results.get("profile_examples_per_sec"),
                "profile_gflops_per_example": results.get("profile_gflops_per_example"),
            }
        )
        # progress to stderr: stdout carries the one JSON line at the end
        print(json.dumps(runs[-1]), file=sys.stderr, flush=True)

    import jax

    f1s = [r["test_F1Score"] for r in runs if r["test_F1Score"] is not None]
    agg = {
        "backend": jax.default_backend(),
        "runs": runs,
        "mean_fit_seconds": sum(r["fit_seconds"] for r in runs) / len(runs),
        "mean_test_seconds": sum(r["test_seconds"] for r in runs) / len(runs),
        # None (not 0.0) when a run produced no F1 — don't deflate the mean
        "mean_test_F1Score": sum(f1s) / len(f1s) if len(f1s) == len(runs) else None,
    }
    # Golden-quality floor check (committed band, same one the test gate
    # asserts). The band was measured under a pinned protocol (n, seed,
    # max_epochs, full corpus) — comparing a different protocol's F1 against
    # it would raise false drift alarms, so ``within_band`` is only set when
    # the effective overrides match the band spec; otherwise the band is
    # echoed with ``protocol_matches: false`` and no verdict.
    def _last_override(key: str, default: str) -> str:
        return next(
            (o.split("=", 1)[1] for o in reversed(base_overrides)
             if o.startswith(f"{key}=")), default,
        )

    dsname = _last_override("data.dsname", "bigvul")
    golden = json.loads(
        (REPO / "configs" / "golden_quality.json").read_text()
    ).get(dsname)
    if isinstance(golden, dict) and agg["mean_test_F1Score"] is not None:
        matches = (
            _last_override("optim.max_epochs", "") == str(golden["max_epochs"])
            and _last_override("data.sample", "false") == "false"
            and _last_override("seed", "0") == str(golden["train_seed"])
        )
        agg["golden_quality"] = {
            "dsname": dsname,
            "min_test_f1": golden["min_test_f1"],
            "protocol_matches": matches,
            "within_band": (
                agg["mean_test_F1Score"] >= golden["min_test_f1"]
                if matches else None
            ),
            # corpus shape cannot be verified from here — the shards on disk
            # must have been built with the band's n/corpus_seed (the test
            # gate, which builds its own corpus, IS the authoritative check)
            "unchecked": [f"corpus n={golden['n']} corpus_seed={golden['corpus_seed']}"],
        }
    (out_dir / "performance_evaluation.json").write_text(json.dumps(agg, indent=2))
    print(json.dumps({k: v for k, v in agg.items() if k != "runs"}))
    return agg


if __name__ == "__main__":
    main()
