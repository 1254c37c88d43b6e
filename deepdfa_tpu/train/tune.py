"""Hyperparameter tuning — the NNI-hooks replacement.

The reference wires NNI in three places: experiment-param injection with
feat-string rewriting (``DDFA/code_gnn/main_cli.py:110-121``), per-epoch
intermediate F1 reporting (``base_module.py:346``) and final F1 reporting
(``main_cli.py:184``). The TPU build replaces the external NNI service with a
self-contained random-search driver over the typed config:

- a **search space** maps dotted config keys to value lists
  (``{"model.hidden_dim": [32, 64], "optim.lr": [1e-3, 3e-4]}``) — dotted
  keys go straight through :func:`deepdfa_tpu.config.load_config` overrides,
  replacing NNI's feat-string surgery with structured overrides;
- each trial runs ``cli.fit`` in-process; the per-epoch ``tuning.jsonl`` the
  CLI already writes *is* the intermediate-report stream, and the trial's
  returned ``val_F1Score`` is the final report;
- trials append to ``trials.jsonl``; :func:`best_trial` selects the winner
  (objective = final val F1, parity with the NNI objective).

If the real ``nni`` package is importable (it is not in this image), trial
results are additionally forwarded to it — gated, never required.

NNI-practice parity (round-3): ``isolate=True`` runs every trial in a fresh
subprocess — its own XLA client, compilation cache and device memory die with
it, so peak parent RSS stays flat across a long sweep and a crashing trial
cannot take the sweep down. ``pruner=MedianPruner(...)`` watches each live
trial's ``tuning.jsonl`` stream and kills it early when its intermediate val
F1 falls below the median of prior trials at the same epoch (NNI's
``Medianstop`` assessor); pruned trials keep their best-so-far F1 as the
objective, exactly as NNI scores early-stopped trials.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import logging
import os
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Iterator, Mapping, Sequence

import numpy as np

logger = logging.getLogger("deepdfa_tpu")

__all__ = [
    "Trial",
    "MedianPruner",
    "sample_space",
    "grid_space",
    "run_trials",
    "best_trial",
]


@dataclasses.dataclass(frozen=True)
class Trial:
    trial_id: int
    overrides: dict[str, Any]
    metrics: dict[str, float]
    error: str | None = None  # set when the trial raised; objective is -inf
    pruned: bool = False  # stopped early by the pruner; metrics = best-so-far

    @property
    def objective(self) -> float:
        if self.error is not None:
            return float("-inf")
        return self.metrics.get("val_F1Score", float("-inf"))


@dataclasses.dataclass
class MedianPruner:
    """NNI ``Medianstop``: kill a trial whose val F1 at epoch *e* is below
    the median of all prior trials' F1 at epoch *e* — after ``warmup_epochs``
    and only once ``min_history`` prior curves reach that epoch."""

    warmup_epochs: int = 2
    min_history: int = 2
    poll_seconds: float = 0.25
    histories: list[list[float]] = dataclasses.field(default_factory=list)

    def should_prune(self, epoch: int, f1: float) -> bool:
        if epoch < self.warmup_epochs:
            return False
        at_epoch = [h[epoch] for h in self.histories if len(h) > epoch]
        if len(at_epoch) < self.min_history:
            return False
        return f1 < float(np.median(at_epoch))

    def record(self, curve: list[float]) -> None:
        self.histories.append(curve)


def sample_space(
    space: Mapping[str, Sequence[Any]], n_trials: int, seed: int = 0
) -> Iterator[dict[str, Any]]:
    """Random search: draw each key independently per trial."""
    rng = np.random.default_rng(seed)
    for _ in range(n_trials):
        yield {k: v[int(rng.integers(len(v)))] for k, v in space.items()}


def grid_space(space: Mapping[str, Sequence[Any]]) -> Iterator[dict[str, Any]]:
    """Exhaustive grid search."""
    keys = list(space)
    for combo in itertools.product(*(space[k] for k in keys)):
        yield dict(zip(keys, combo))


_WORKER_SNIPPET = (
    "import json, sys\n"
    "from pathlib import Path\n"
    "spec = json.loads(Path(sys.argv[1]).read_text())\n"
    "from deepdfa_tpu.config import load_config\n"
    "from deepdfa_tpu.train import cli\n"
    "cfg = load_config(*spec['configs'], overrides=spec['overrides'])\n"
    "cli.fit(cfg, Path(spec['run_dir']))\n"
)


def _read_curve(tuning_file: Path) -> list[float]:
    """Per-epoch val F1 curve from a (possibly still-growing) tuning.jsonl."""
    if not tuning_file.exists():
        return []
    curve: list[float] = []
    for line in tuning_file.read_text().splitlines():
        try:
            row = json.loads(line)
        except json.JSONDecodeError:  # torn tail of an in-flight write
            break
        if "epoch" in row:
            curve.append(float(row["val_F1Score"]))
    return curve


def _run_trial_isolated(
    spec: dict, run_dir: Path, pruner: MedianPruner | None
) -> tuple[dict, str | None, bool]:
    """One trial in a fresh subprocess (own XLA client / compile cache /
    device memory); the parent tails ``tuning.jsonl`` for the pruner.
    Returns (metrics, error, pruned)."""
    spec_path = run_dir / "trial_spec.json"
    spec_path.write_text(json.dumps(spec))
    repo_root = Path(__file__).resolve().parents[2]
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{repo_root}{os.pathsep}{env.get('PYTHONPATH', '')}"
    stderr_path = run_dir / "trial_stderr.log"
    with open(stderr_path, "w") as stderr_f:
        # stderr goes to a file, not a pipe: a chatty child (XLA warnings,
        # long tracebacks) would fill a pipe buffer and deadlock the sweep
        proc = subprocess.Popen(
            [sys.executable, "-c", _WORKER_SNIPPET, str(spec_path)],
            env=env,
            cwd=repo_root,
            stdout=subprocess.DEVNULL,
            stderr=stderr_f,
            text=True,
        )
        tuning_file = run_dir / "tuning.jsonl"
        pruned = False
        curve: list[float] = []
        while proc.poll() is None:
            time.sleep(pruner.poll_seconds if pruner else 0.5)
            if pruner is None:
                continue
            curve = _read_curve(tuning_file)
            for epoch in range(len(curve)):
                if pruner.should_prune(epoch, curve[epoch]):
                    proc.kill()
                    proc.wait()
                    pruned = True
                    break
            if pruned:
                break
    stderr = stderr_path.read_text() if stderr_path.exists() else ""
    curve = _read_curve(tuning_file)
    if pruner is not None:
        pruner.record(curve)
    if pruned:
        best = max(curve) if curve else float("-inf")
        return {"val_F1Score": best}, None, True
    if proc.returncode != 0:
        return {}, f"trial subprocess rc={proc.returncode}: {stderr[-500:]}", False
    final = run_dir / "final_metrics.json"
    metrics = json.loads(final.read_text()) if final.exists() else {}
    return metrics, None, False


def run_trials(
    candidates: Iterator[dict[str, Any]],
    out_dir: str | Path,
    configs: Sequence[str] = (),
    base_overrides: Mapping[str, Any] | None = None,
    isolate: bool = False,
    pruner: MedianPruner | None = None,
) -> list[Trial]:
    """Run one ``fit`` per candidate override-set; log every trial to
    ``trials.jsonl``. Failures are recorded (objective -inf), not raised —
    a bad hyperparameter draw must not kill the sweep.

    ``isolate=True``: subprocess per trial (fresh XLA client; flat parent
    RSS; crash containment — the parent never even imports the training
    stack). A chip belongs to one process at a time and every trial child
    needs it, so a sweep parent must not have initialised a JAX backend:
    this function touches none in isolated mode, and trials run strictly
    one after another. ``pruner``: median early-stopping on the live
    ``tuning.jsonl`` stream (requires ``isolate=True``)."""
    if pruner is not None and not isolate:
        raise ValueError("pruning requires isolate=True (a live child to stop)")
    if not isolate:
        # import once, outside the per-trial try: a broken environment must
        # raise, not masquerade as N failed hyperparameter draws
        from deepdfa_tpu.config import load_config
        from deepdfa_tpu.train import cli
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    trials_file = out_dir / "trials.jsonl"
    trials: list[Trial] = []
    for i, overrides in enumerate(candidates):
        merged = {**(base_overrides or {}), **overrides}
        run_dir = out_dir / f"trial_{i}"
        run_dir.mkdir(parents=True, exist_ok=True)
        error = None
        pruned = False
        metrics: dict = {}
        if isolate:
            spec = {"configs": list(configs), "overrides": merged,
                    "run_dir": str(run_dir)}
            try:
                json.dumps(spec)
            except TypeError as exc:
                error = f"overrides not serialisable: {exc}"
            else:
                metrics, error, pruned = _run_trial_isolated(spec, run_dir, pruner)
        else:
            try:
                cfg = load_config(*configs, overrides=merged)
                metrics = cli.fit(cfg, run_dir)
            except Exception as exc:  # noqa: BLE001 — sweep survives bad draws
                logger.warning("trial %d failed: %s", i, exc)
                error = str(exc)
        trial = Trial(
            i,
            dict(merged),
            {k: v for k, v in metrics.items() if isinstance(v, float)},
            error=error,
            pruned=pruned,
        )
        trials.append(trial)
        with open(trials_file, "a") as f:
            f.write(json.dumps({"trial_id": i, "overrides": trial.overrides,
                                "metrics": trial.metrics, "error": trial.error,
                                "pruned": trial.pruned}) + "\n")
        _forward_to_nni(trial)
    return trials


def _forward_to_nni(trial: Trial) -> None:
    try:
        import nni  # noqa: F401 — not in this image; external clusters only
    except ImportError:
        return
    nni.report_final_result(trial.objective)


def best_trial(trials: Sequence[Trial]) -> Trial:
    if not trials:
        raise ValueError("no trials")
    return max(trials, key=lambda t: t.objective)
