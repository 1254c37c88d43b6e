#!/usr/bin/env python
"""Chaos battery for the fault-tolerance layer (resilience/).

Drives REAL subprocess ``fit`` runs on the deterministic synthetic corpus
and proves the resilience invariants end-to-end:

1. **clean**    — uninterrupted fit; its final val metrics are the oracle.
2. **crash**    — same config, ``DEEPDFA_FAULTS`` arms
   ``ckpt.crash_between_state_and_meta@2``: the process hard-exits
   (``os._exit(137)``, a simulated ``kill -9``) in the worst spot — after
   the checkpoint state payload is written but before its ``meta.json``
   commit marker. A ``*.tmp`` partial must be left behind.
3. **resume**   — ``fit --resume`` on the crashed run dir: the partial is
   garbage-collected, training restarts from the last committed epoch, and
   the final val metrics must MATCH the clean run (bit-identical modulo
   float noise — same seeds, same restored rng/opt-state).
4. **sentinel** — ``step.nan_grads`` poisons three consecutive steps; with
   ``sentinel_patience=2`` the run must detect divergence, roll back to the
   last good checkpoint (or re-init), halve the LR, and still COMPLETE with
   ``n_rollbacks >= 1`` in its final metrics.
5. **preempt** — ``preempt.sigterm@2`` simulates a SIGTERM mid-epoch on a
   2-device host mesh: the run must commit an emergency checkpoint within
   the ``preempt_deadline_s`` budget, journal the preemption, and exit with
   the distinct resumable rc 75 (EX_TEMPFAIL).
6. **elastic_resume** — ``fit --resume`` on the preempted run dir with HALF
   the devices (1 vs 2): the mesh-elastic restore path reshards params, the
   seed-deterministic sampler replays the same global batch sequence, and
   the final val metrics must MATCH the clean oracle within 1e-6.
7. **hang** — ``step.hang@2`` wedges a train step forever; with
   ``step_deadline_s=5`` the watchdog must convert the infinite hang into a
   journaled ``watchdog_timeout`` abort in bounded time (never rc 0, never
   a battery-level subprocess timeout).

Prints one JSON verdict line; exit 0 iff every scenario held. Slow (seven
small subprocess fits): the pytest wrapper is marked ``slow``; tier-1 runs
the same invariants in-process instead.

Usage: python scripts/chaos_train.py [--workdir DIR] [--keep] [--epochs N]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

SMALL = [
    "--set", "model.hidden_dim=4",
    "--set", "model.n_steps=1",
    "--set", "model.num_output_layers=2",
    "--set", "data.sample=true",
    "--set", "data.batch.batch_graphs=64",
    "--set", "data.batch.max_nodes=4096",
    "--set", "data.batch.max_edges=8192",
]

# metrics that define "same final state" across clean vs crash+resume
COMPARE_KEYS = ("val_F1Score", "val_loss")
TOLERANCE = 1e-6


def run_fit(run_dir: Path, storage: Path, epochs: int, *, faults: str = "",
            resume: bool = False, extra: list[str] | None = None,
            env_extra: dict[str, str] | None = None,
            timeout: float = 900.0) -> subprocess.CompletedProcess:
    cmd = [
        sys.executable, "-m", "deepdfa_tpu.train.cli", "fit",
        "--run-dir", str(run_dir),
        "--set", f"optim.max_epochs={epochs}",
        *SMALL, *(extra or []),
    ]
    if resume:
        cmd.append("--resume")
    env = dict(os.environ) | {
        "JAX_PLATFORMS": "cpu",
        "DEEPDFA_STORAGE": str(storage),
        "DEEPDFA_FAULTS": faults,
        "PYTHONPATH": str(REPO),
    }
    env |= env_extra or {}
    return subprocess.run(
        cmd, cwd=REPO, env=env, capture_output=True, text=True, timeout=timeout
    )


def final_metrics(run_dir: Path) -> dict:
    return json.loads((run_dir / "final_metrics.json").read_text())


def scenario_clean(work: Path, epochs: int) -> tuple[dict, dict]:
    run_dir = work / "clean"
    proc = run_fit(run_dir, work / "storage_clean", epochs)
    ok = proc.returncode == 0 and (run_dir / "final_metrics.json").exists()
    detail = {"ok": ok, "returncode": proc.returncode}
    if not ok:
        detail["stderr_tail"] = proc.stderr[-2000:]
        return detail, {}
    return detail, final_metrics(run_dir)


def scenario_crash(work: Path, epochs: int) -> dict:
    """Kill -9 mid-commit: rc 137, a .tmp partial checkpoint left behind."""
    run_dir = work / "crashed"
    proc = run_fit(run_dir, work / "storage_crash", epochs,
                   faults="ckpt.crash_between_state_and_meta@2")
    partials = list((run_dir / "checkpoints").glob("*.tmp"))
    committed = list((run_dir / "checkpoints").glob("*/meta.json"))
    detail = {
        "ok": proc.returncode == 137 and bool(partials) and bool(committed),
        "returncode": proc.returncode,
        "partial_dirs": [p.name for p in partials],
        "committed": len(committed),
    }
    if not detail["ok"]:
        detail["stderr_tail"] = proc.stderr[-2000:]
    return detail


def scenario_resume(work: Path, epochs: int, oracle: dict) -> dict:
    """--resume on the crashed dir completes and matches the clean oracle."""
    run_dir = work / "crashed"
    proc = run_fit(run_dir, work / "storage_crash", epochs, resume=True)
    detail: dict = {"ok": False, "returncode": proc.returncode}
    if proc.returncode != 0 or not (run_dir / "final_metrics.json").exists():
        detail["stderr_tail"] = proc.stderr[-2000:]
        return detail
    resumed = final_metrics(run_dir)
    diffs = {
        k: abs(float(resumed[k]) - float(oracle[k]))
        for k in COMPARE_KEYS
        if k in resumed and k in oracle
    }
    # GC proof: restore must never have seen the partial
    partials = list((run_dir / "checkpoints").glob("*.tmp"))
    detail |= {
        "ok": bool(diffs) and all(d <= TOLERANCE for d in diffs.values())
        and not partials,
        "metric_diffs": diffs,
        "partials_left": [p.name for p in partials],
        "resumed_from_journal": (run_dir / "journal.json").exists(),
    }
    if not detail["ok"]:
        detail["stderr_tail"] = proc.stderr[-2000:]
    return detail


def scenario_sentinel(work: Path, epochs: int) -> dict:
    """Three consecutive NaN-grad steps: the run rolls back and completes.

    ``p=1:max=3`` poisons the first three steps regardless of how many
    steps an epoch has (the tiny sample config runs ~1 step/epoch, so a
    fixed hit list like ``@4,5,6`` would straddle the end of the run)."""
    run_dir = work / "nan"
    proc = run_fit(
        run_dir, work / "storage_nan", epochs,
        faults="step.nan_grads:p=1:max=3",
        extra=["--set", "resilience.sentinel_patience=2"],
    )
    detail: dict = {"ok": False, "returncode": proc.returncode}
    if proc.returncode != 0 or not (run_dir / "final_metrics.json").exists():
        detail["stderr_tail"] = proc.stderr[-2000:]
        return detail
    fm = final_metrics(run_dir)
    detail |= {
        "ok": fm.get("n_rollbacks", 0) >= 1 and fm.get("lr_scale", 1.0) < 1.0,
        "n_rollbacks": fm.get("n_rollbacks"),
        "lr_scale": fm.get("lr_scale"),
        "sentinel_bad_steps": fm.get("sentinel_bad_steps"),
    }
    if not detail["ok"]:
        detail["stderr_tail"] = proc.stderr[-2000:]
    return detail


def _journal(run_dir: Path) -> dict:
    path = run_dir / "journal.json"
    if not path.exists():
        return {}
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError):
        return {}


def scenario_preempt(work: Path, epochs: int) -> dict:
    """SIGTERM mid-epoch on a 2-device mesh: emergency ckpt within deadline,
    journaled preemption, distinct resumable rc 75."""
    run_dir = work / "preempted"
    proc = run_fit(
        run_dir, work / "storage_preempt", epochs,
        faults="preempt.sigterm@2",
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"},
    )
    detail: dict = {"ok": False, "returncode": proc.returncode}
    committed = sorted(
        (run_dir / "checkpoints").glob("*/meta.json"),
        key=lambda p: int(p.parent.name),
    )
    if proc.returncode != 75 or not committed:
        detail["stderr_tail"] = proc.stderr[-2000:]
        return detail
    meta = json.loads(committed[-1].read_text())
    journal = _journal(run_dir)
    commit_s = journal.get("emergency_commit_s")
    deadline_s = journal.get("emergency_deadline_s")
    detail |= {
        "ok": (
            "preempted" in meta
            and "emergency" in meta.get("reasons", [])
            and journal.get("preempted") is not None
            and commit_s is not None
            and deadline_s is not None
            and float(commit_s) <= float(deadline_s)
            and journal.get("mesh", {}).get("devices") == 2
        ),
        "meta_preempted": meta.get("preempted"),
        "meta_reasons": meta.get("reasons"),
        "emergency_commit_s": commit_s,
        "emergency_deadline_s": deadline_s,
        "mesh": journal.get("mesh"),
    }
    if not detail["ok"]:
        detail["stderr_tail"] = proc.stderr[-2000:]
    return detail


def scenario_elastic_resume(work: Path, epochs: int, oracle: dict) -> dict:
    """--resume the preempted run on HALF the devices (1 vs 2): the restore
    reshards, replays the same global batch order, and matches the oracle."""
    run_dir = work / "preempted"
    # pin the half-mesh explicitly: relying on the ambient 1-device CPU
    # default breaks under pytest, whose conftest exports an
    # XLA_FLAGS=...device_count=8 that the subprocess would inherit
    proc = run_fit(
        run_dir, work / "storage_preempt", epochs, resume=True,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=1"},
    )
    detail: dict = {"ok": False, "returncode": proc.returncode}
    if proc.returncode != 0 or not (run_dir / "final_metrics.json").exists():
        detail["stderr_tail"] = proc.stderr[-2000:]
        return detail
    resumed = final_metrics(run_dir)
    diffs = {
        k: abs(float(resumed[k]) - float(oracle[k]))
        for k in COMPARE_KEYS
        if k in resumed and k in oracle
    }
    journal = _journal(run_dir)
    detail |= {
        "ok": (
            bool(diffs)
            and all(d <= TOLERANCE for d in diffs.values())
            and int(resumed.get("resharded", 0)) == 1
            and journal.get("mesh", {}).get("devices") == 1
        ),
        "metric_diffs": diffs,
        "resharded": resumed.get("resharded"),
        "mesh": journal.get("mesh"),
    }
    if not detail["ok"]:
        detail["stderr_tail"] = proc.stderr[-2000:]
    return detail


def scenario_hang(work: Path, epochs: int) -> dict:
    """step.hang wedges a step forever; the watchdog must journal a timeout
    and abort in bounded time (subprocess timeout here is the upper proof)."""
    run_dir = work / "hung"
    try:
        proc = run_fit(
            run_dir, work / "storage_hang", epochs,
            faults="step.hang@2",
            extra=["--set", "resilience.step_deadline_s=5"],
            timeout=300.0,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": "battery timeout — watchdog never fired"}
    journal = _journal(run_dir)
    wt = journal.get("watchdog_timeout") or {}
    detail = {
        "ok": (
            proc.returncode not in (0, 75, 137)
            and wt.get("point") == "train_step"
        ),
        "returncode": proc.returncode,
        "watchdog_timeout": wt,
    }
    if not detail["ok"]:
        detail["stderr_tail"] = proc.stderr[-2000:]
    return detail


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workdir", default=None,
                        help="scratch dir (default: a fresh temp dir)")
    parser.add_argument("--keep", action="store_true",
                        help="keep the scratch dir for inspection")
    parser.add_argument("--epochs", type=int, default=4)
    parser.add_argument("--skip-sentinel", action="store_true")
    args = parser.parse_args(argv)

    work = Path(args.workdir) if args.workdir else Path(
        tempfile.mkdtemp(prefix="chaos_train_")
    )
    work.mkdir(parents=True, exist_ok=True)
    verdict: dict = {"workdir": str(work)}
    try:
        clean, oracle = scenario_clean(work, args.epochs)
        verdict["clean"] = clean
        if clean["ok"]:
            verdict["crash"] = scenario_crash(work, args.epochs)
            verdict["resume"] = (
                scenario_resume(work, args.epochs, oracle)
                if verdict["crash"]["ok"]
                else {"ok": False, "skipped": "crash scenario failed"}
            )
            if not args.skip_sentinel:
                verdict["sentinel"] = scenario_sentinel(work, args.epochs)
            verdict["preempt"] = scenario_preempt(work, args.epochs)
            verdict["elastic_resume"] = (
                scenario_elastic_resume(work, args.epochs, oracle)
                if verdict["preempt"]["ok"]
                else {"ok": False, "skipped": "preempt scenario failed"}
            )
            verdict["hang"] = scenario_hang(work, args.epochs)
        ok = all(
            v.get("ok", False)
            for k, v in verdict.items()
            if isinstance(v, dict)
        )
        verdict["ok"] = ok
        print(json.dumps(verdict))
        return 0 if ok else 1
    finally:
        if not args.keep and not args.workdir:
            shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
