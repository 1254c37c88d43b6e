#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the GGNN's main path once, end to end, through the entry points a
user types, at the golden width (``configs/ggnn.yaml`` +
``configs/bigvul.yaml``: hidden 32, 5 rounds, concat_all_absdf → conv
width 128, 3 output layers, batch 256, corpus-derived buckets, f32):

    preprocess → fit (2 epochs) → predict → serve (+ POST /score, SIGTERM)
    → kernels (fused / megabatch / hier encoder / int8 through Mosaic,
      parity with the segment layout) → multichip (when >1 device)

**One process per chip.** This file drives the stages as CHILD processes,
strictly one after another, and the parent never imports JAX — a parent
that has touched JAX holds the chip and a child that needs it fails or
hangs. The two stages that are not a CLI (``kernels``, ``multichip``) are
this same file re-entered with ``--child``; only that child imports JAX.
Every child is pinned with ``JAX_PLATFORMS=tpu``: with no chip JAX refuses
to start instead of dropping to CPU, the smoke prints no result line and
exits non-zero saying why. Every stage states where it ran and the parent
checks that all of them agree.

``--rehearse-cpu`` is the explicit toy-size CPU rehearsal (tiny widths,
Pallas interpreter, everything labelled ``cpu``) for debugging the script
itself before spending chip time. It is not what the driver runs and
proves nothing about the chip.

The corpus is generated from a seed into ``storage/chip_smoke/`` under the
checkout (``DEEPDFA_STORAGE``; git-ignored, wiped at start). No network, no
``.git``. The compile cache is where ``utils.setup_compile_cache`` puts it.

Last stdout line on success — and only then:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``;
the line before it is the full per-stage report.

Tolerances (stated here, checked below; each is 20x or more above what
the first passing run on a TPU v5 lite measured, PERF.md has the values):

- serve vs predict probability, same checkpoint: ``1e-5`` absolute (both
  are the segment forward at the default matmul precision; only the
  padded batch shape differs; both round to six decimals).
- kernel layouts vs the segment layout under
  ``jax.default_matmul_precision("highest")``: logits and hierarchical
  embeddings ``1e-5`` absolute, loss ``1e-5`` relative, gradients ``1e-4``
  of the largest entry of the segment layout's gradient tree. The repo's
  "bit-identical to the twin" statements are facts about the Pallas
  interpreter on CPU; on the chip a Pallas ``dot`` and an XLA ``dot``
  round differently, so the comparison is by tolerance.
- int8 matmul kernel vs the XLA dequantise-then-matmul at highest
  precision: ``1e-5`` relative to the largest output entry; the int8
  ENGINE vs f32 is the engine's own gate (``serve.int8_max_score_delta``).
- dp loss on N chips vs the weighted mean of N single-device steps, and
  mesh ``score_groups`` vs a single engine: ``1e-4``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK = REPO / "storage" / "chip_smoke"

SERVE_VS_PREDICT_ATOL = 1e-5
LOGIT_ATOL = 1e-5
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4
INT8_KERNEL_RTOL = 1e-5
MULTICHIP_RTOL = 1e-4

GOLDEN = dict(n_functions=2000, overrides=[], fused_batch_graphs=128,
              min_steps=4)
# toy widths so the Pallas interpreter finishes in minutes on a CPU
REHEARSAL = dict(
    n_functions=160, fused_batch_graphs=8, min_steps=4,
    overrides=["model.hidden_dim=8", "model.n_steps=2",
               "data.batch.batch_graphs=16", "serve.max_batch=4"])

_SOURCES = {
    "copy_checked.c": """
int copy_checked(char *dst, const char *src, int n) {
    int i = 0;
    if (n <= 0) { return -1; }
    while (i < n - 1 && src[i] != 0) { dst[i] = src[i]; i = i + 1; }
    dst[i] = 0;
    return i;
}
""",
    "copy_unchecked.c": """
int copy_unchecked(char *dst, const char *src) {
    int i = 0;
    char buf[16];
    while (src[i] != 0) { buf[i] = src[i]; i = i + 1; }
    strcpy(dst, buf);
    return i;
}
""",
    "sum_array.c": """
long sum_array(const int *xs, int n) {
    long total = 0;
    for (int i = 0; i < n; i++) { total += xs[i]; }
    return total;
}
""",
    "alloc_use.c": """
int alloc_use(int n) {
    int *p = malloc(n * sizeof(int));
    int acc = 0;
    for (int i = 0; i <= n; i++) { p[i] = i; acc += p[i]; }
    free(p);
    return acc + p[0];
}
""",
    "parse_len.c": """
int parse_len(const char *s) {
    int len = atoi(s);
    char buf[64];
    if (len > 0) { memcpy(buf, s, len); }
    return buf[0];
}
""",
}


def _long_function(n_stmts: int = 90) -> str:
    """One function past the 126-node size class, so a request also takes
    the 1022-node serve bucket."""
    body = "\n".join(
        f"    acc[{i % 8}] = acc[{(i + 1) % 8}] + x * {i + 1};"
        for i in range(n_stmts))
    return ("int long_chain(int x) {\n    int acc[8];\n"
            "    for (int i = 0; i < 8; i++) { acc[i] = i; }\n"
            f"{body}\n    return acc[0];\n}}\n")


_UNIT = {
    "reader.c": """
int read_len(void) { char b[32]; gets(b); return atoi(b); }
int entry(char *dst) { int n = read_len(); return fill(dst, n); }
""",
    "filler.c": """
int fill(char *dst, int n) { char src[8]; memcpy(dst, src, n); return n; }
int helper(int a) { return a + 1; }
""",
}


class StageFailed(RuntimeError):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise StageFailed(what)


def _tail(path: Path, n: int = 25) -> str:
    try:
        return "".join(path.read_text(errors="replace").splitlines(True)[-n:])
    except OSError:
        return ""


# ---------------------------------------------------------------------------
# parent: drives children, never imports JAX


class Smoke:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.size = REHEARSAL if rehearse else GOLDEN
        self.platform = "cpu" if rehearse else "tpu"
        self.env = dict(os.environ) | {
            "JAX_PLATFORMS": self.platform,
            "DEEPDFA_STORAGE": str(WORK),
            "PYTHONPATH": str(REPO) + os.pathsep + os.environ.get(
                "PYTHONPATH", ""),
            "PYTHONUNBUFFERED": "1",
        }
        if rehearse:
            # XLA:CPU's cache loader logs two multi-KB feature-list errors
            # per persistent-cache hit; they would bury every log tail
            self.env["TF_CPP_MIN_LOG_LEVEL"] = "3"
        self.report: dict = {"rehearsal": rehearse, "stages": {}}
        self.device: dict | None = None
        self.live: list[subprocess.Popen] = []
        self.fit_dir = WORK / "runs" / "fit"
        self.src_dir = WORK / "sources"

    # -- plumbing -----------------------------------------------------------

    def log(self, msg: str) -> None:
        print(f"[chip_smoke +{time.monotonic() - T0:.0f}s] {msg}",
              file=sys.stderr, flush=True)

    def run(self, name: str, argv: list[str], timeout: float) -> str:
        """Run one child to completion; returns its stdout. stderr goes to
        ``<work>/logs/<name>.log`` (tail shown on failure)."""
        log = WORK / "logs" / f"{name}.log"
        with open(log, "w") as err:
            proc = subprocess.Popen(
                [sys.executable, *argv], cwd=REPO, env=self.env,
                stdout=subprocess.PIPE, stderr=err, text=True)
            self.live.append(proc)
            try:
                out, _ = proc.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.communicate()
                raise StageFailed(
                    f"{name}: no exit within {timeout:.0f}s\n{_tail(log)}")
            finally:
                self.live.remove(proc)
        if proc.returncode != 0:
            raise StageFailed(
                f"{name}: exit code {proc.returncode}\n{_tail(log)}")
        return out

    def stage(self, name: str, fn) -> None:
        self.log(f"stage {name} …")
        t0 = time.monotonic()
        out = fn() or {}
        out["seconds"] = round(time.monotonic() - t0, 1)
        self.report["stages"][name] = out
        self.log(f"stage {name} ok ({out['seconds']}s)")

    def same_device(self, name: str, where: dict) -> None:
        """Every stage states where it ran; all of them must agree."""
        got = (where.get("backend"), where.get("device_kind"),
               int(where.get("device_count", -1)))
        want = (self.device["backend"], self.device["device_kind"],
                self.device["device_count"])
        _check(got == want,
               f"{name} ran on {got}, the device stage reported {want}")

    # -- stages -------------------------------------------------------------

    def s_device(self) -> dict:
        code = ("import json, jax\n"
                "from deepdfa_tpu import utils\n"
                "cache = utils.setup_compile_cache()\n"
                "w = utils.require_backend()\n"
                "print(json.dumps({**w, 'jax': jax.__version__, "
                "'compile_cache': str(cache)}))\n")
        out = self.run("device", ["-c", code], timeout=300)
        self.device = json.loads(out.strip().splitlines()[-1])
        _check(self.device["backend"] == self.platform,
               f"backend is {self.device['backend']!r}, not "
               f"{self.platform!r}")
        return dict(self.device)

    def s_preprocess(self) -> dict:
        out = self.run("preprocess", [
            "scripts/preprocess.py", "--dataset", "demo", "--n",
            str(self.size["n_functions"]), "--workers", "1", "--seed", "0"],
            timeout=900)
        summary = json.loads(out.strip().splitlines()[-1])
        _check(summary.get("status") == "ok" and summary["failed"] == 0,
               f"preprocess: {summary}")
        status = self.run("dataflow_backend", ["-c", (
            "from deepdfa_tpu.cpg.analyses import native_solver_status\n"
            "print(native_solver_status())\n")], timeout=300).strip()
        return {"functions": summary["functions"],
                "graphs": summary["graphs"],
                "vul_graphs": summary["vul_graphs"],
                "dataflow_backend": status.splitlines()[-1]}

    def _cli(self, *args: str) -> list[str]:
        return ["-m", "deepdfa_tpu.train.cli", *args]

    def _run_log_where(self, run_dir: Path, command: str) -> dict:
        """The backend a CLI run stated at start (``run.log``)."""
        pat = re.compile(
            rf": {command} backend=(\w+) device_kind='([^']*)' devices=(\d+)")
        hits = pat.findall((run_dir / "run.log").read_text())
        _check(bool(hits), f"{command}: no backend statement in run.log")
        backend, kind, count = hits[-1]
        return {"backend": backend, "device_kind": kind,
                "device_count": int(count)}

    def s_fit(self) -> dict:
        sets = ["data.dsname=demo", "optim.max_epochs=2",
                *self.size["overrides"]]
        self.run("fit", self._cli(
            "fit", "--config", "configs/default.yaml",
            "--config", "configs/ggnn.yaml", "--config", "configs/bigvul.yaml",
            *[x for s in sets for x in ("--set", s)],
            "--run-dir", str(self.fit_dir)), timeout=1500)
        self.same_device("fit", self._run_log_where(self.fit_dir, "fit"))
        journal = json.loads((self.fit_dir / "journal.json").read_text())
        _check(journal.get("completed") is True, "fit: journal not completed")
        epochs = journal["epochs"]
        _check(len(epochs) == 2, f"fit: {len(epochs)} epochs journaled")
        for row in epochs:
            tele = row["telemetry"]
            _check(tele["steps"] >= self.size["min_steps"],
                   f"fit: epoch {row['epoch']} took {tele['steps']} steps "
                   f"(< {self.size['min_steps']})")
            _check(row["twin_routed_steps"] == 0,
                   f"fit: {row['twin_routed_steps']} twin-routed steps at "
                   "layout=segment")
        _check(epochs[0]["telemetry"]["compiles"] >= 1,
               "fit: first epoch counted no compile")
        _check(epochs[1]["telemetry"]["compiles"] == 0,
               f"fit: epoch 2 compiled "
               f"{epochs[1]['telemetry']['compiles']} shape(s)")
        losses = [float(x) for x in re.findall(
            r"epoch \d+: train_loss=(\S+)",
            (self.fit_dir / "run.log").read_text())]
        _check(len(losses) == 2 and all(math.isfinite(x) for x in losses),
               f"fit: train losses {losses}")
        committed = sorted(
            p.parent.name for p in
            (self.fit_dir / "checkpoints").glob("*/meta.json"))
        _check(bool(committed), "fit: no committed checkpoint (meta.json)")
        final = json.loads((self.fit_dir / "final_metrics.json").read_text())
        _check(math.isfinite(final["val_loss"]), f"fit: val_loss {final}")
        return {"train_loss": losses, "val_loss": final["val_loss"],
                "steps": [r["telemetry"]["steps"] for r in epochs],
                "compiles": [r["telemetry"]["compiles"] for r in epochs],
                "twin_routed_steps": [r["twin_routed_steps"] for r in epochs],
                "mean_step_ms": [r["telemetry"].get("mean_step_ms")
                                 for r in epochs],
                "checkpoints": committed}

    def s_predict(self) -> dict:
        self.src_dir.mkdir(parents=True, exist_ok=True)
        for name, code in {**_SOURCES,
                           "long_chain.c": _long_function()}.items():
            (self.src_dir / name).write_text(code)
        out = self.run("predict", self._cli(
            "predict", "--run-dir", str(self.fit_dir), "--saliency", "gate",
            "--source", str(self.src_dir)), timeout=900)
        self.same_device("predict",
                         self._run_log_where(self.fit_dir, "predict"))
        rep = json.loads(out.strip().splitlines()[-1])
        _check(rep["n_errors"] == 0 and rep["n_scored"] == len(_SOURCES) + 1,
               f"predict: {rep['n_scored']} scored, {rep['n_errors']} errors")
        self.predicted = {r["function"]: r["vulnerable_probability"]
                          for r in rep["results"]}
        _check(all(0.0 <= p <= 1.0 for p in self.predicted.values()),
               f"predict: probabilities {self.predicted}")
        return {"scores": self.predicted}

    def _http(self, port: int, method: str, path: str, payload=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        try:
            body = None if payload is None else json.dumps(payload).encode()
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"{}")
        finally:
            conn.close()

    def s_serve(self) -> dict:
        log = WORK / "logs" / "serve.log"
        err = open(log, "w")
        proc = subprocess.Popen(
            [sys.executable, *self._cli(
                "serve", "--run-dir", str(self.fit_dir),
                "--set", "serve.port=0", "--set", "serve.host=127.0.0.1")],
            cwd=REPO, env=self.env, stdout=subprocess.PIPE, stderr=err,
            text=True)
        self.live.append(proc)
        lines: list[str] = []
        serving: dict = {}
        ready = threading.Event()

        def _read():
            for line in proc.stdout:
                lines.append(line)
                if not ready.is_set() and '"serving"' in line:
                    serving.update(json.loads(line))
                    ready.set()

        reader = threading.Thread(target=_read, daemon=True)
        reader.start()
        try:
            t0 = time.monotonic()
            while not ready.wait(1.0):
                if proc.poll() is not None:
                    raise StageFailed(
                        f"serve: exited {proc.returncode} before its "
                        f"serving line\n{_tail(log)}")
                if time.monotonic() - t0 > 900:
                    raise StageFailed(
                        f"serve: no serving line in 900s\n{_tail(log)}")
            warm_s = round(time.monotonic() - t0, 1)
            self.same_device("serve", serving)
            _check(serving["buckets_warmed"] == 3,
                   f"serve: warmed {serving['buckets_warmed']} buckets")
            port = serving["port"]
            code, health = self._http(port, "GET", "/healthz")
            _check(code == 200 and health["status"] == "ok"
                   and health["warm"] and len(health["warm_buckets"]) == 3,
                   f"serve: /healthz {code} {health}")
            served: dict[str, float] = {}
            for path in sorted(self.src_dir.glob("*.c")):
                code, body = self._http(port, "POST", "/score",
                                        {"source": path.read_text()})
                _check(code == 200, f"serve: POST {path.name} → {code} {body}")
                for row in body["results"]:
                    served[row["function"]] = row["vulnerable_probability"]
            _check(len(served) >= 5, f"serve: only {len(served)} scores")
            _check(set(served) == set(self.predicted),
                   f"serve scored {sorted(served)}, predict "
                   f"{sorted(self.predicted)}")
            diffs = {k: abs(served[k] - self.predicted[k]) for k in served}
            worst = max(diffs.values())
            _check(worst <= SERVE_VS_PREDICT_ATOL,
                   f"serve vs predict: max |Δp| {worst:.2e} > "
                   f"{SERVE_VS_PREDICT_ATOL:.0e} ({diffs})")
            proc.send_signal(signal.SIGTERM)
            try:
                rc = proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                raise StageFailed("serve: no exit within 120s of SIGTERM")
            reader.join(timeout=10)
            _check(rc == 0, f"serve: exit code {rc} after SIGTERM\n"
                            f"{_tail(log)}")
            drained = [json.loads(x) for x in lines if '"drained"' in x]
            _check(bool(drained), "serve: no drained line after SIGTERM")
            _check(drained[-1].get("requests_total", 0) >= len(served),
                   f"serve: drained summary {drained[-1]}")
            return {"warmup_seconds": warm_s, "requests": len(served),
                    "max_abs_diff_vs_predict": worst,
                    "tolerance": SERVE_VS_PREDICT_ATOL,
                    "warm_store": serving.get("warm_store"),
                    "drained": drained[-1]}
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            self.live.remove(proc)
            err.close()

    def _child(self, name: str, timeout: float) -> dict:
        argv = [str(REPO / "chip_smoke.py"), "--child", name]
        if self.rehearse:
            argv.append("--rehearse-cpu")
        out = self.run(name, argv, timeout=timeout)
        rep = json.loads(out.strip().splitlines()[-1])
        self.same_device(name, rep.pop("where"))
        return rep

    def s_kernels(self) -> dict:
        (WORK / "unit").mkdir(parents=True, exist_ok=True)
        for name, code in _UNIT.items():
            (WORK / "unit" / name).write_text(code)
        return self._child("kernels", timeout=1800)

    def s_multichip(self) -> dict:
        if self.device["device_count"] < 2:
            return {"skipped": "one device — the dp step and the mesh "
                               "engine need at least two"}
        return self._child("multichip", timeout=900)

    # -- driver -------------------------------------------------------------

    def main(self) -> int:
        missing = [p for p in ("deepdfa_tpu", "scripts/preprocess.py",
                               "configs/ggnn.yaml", "native/dfa_solver.cpp")
                   if not (REPO / p).exists()]
        if missing:
            print(f"chip_smoke: not a checkout of the repository — missing "
                  f"{missing} next to {Path(__file__).name}", file=sys.stderr)
            return 2
        shutil.rmtree(WORK, ignore_errors=True)
        (WORK / "logs").mkdir(parents=True)
        try:
            for name in ("device", "preprocess", "fit", "predict", "serve",
                         "kernels", "multichip"):
                self.stage(name, getattr(self, f"s_{name}"))
        except StageFailed as exc:
            print(f"chip_smoke: FAILED — {exc}", file=sys.stderr)
            return 1
        finally:
            for proc in list(self.live):
                if proc.poll() is None:
                    proc.kill()
        self.report["wall_seconds"] = round(time.monotonic() - T0, 1)
        self.report["jax"] = self.device["jax"]
        self.report["compile_cache"] = self.device["compile_cache"]
        print(json.dumps(self.report))
        print(json.dumps({"ok": True, "device": {
            "platform": self.device["platform"],
            "kind": self.device["device_kind"],
            "count": self.device["device_count"]}}))
        return 0


# ---------------------------------------------------------------------------
# children: the two stages that are not a CLI. Only these import JAX.


def _golden_cfg(rehearse: bool, **sets):
    from deepdfa_tpu.config import load_config
    from deepdfa_tpu.train.cli import _parse_overrides

    size = REHEARSAL if rehearse else GOLDEN
    overrides = {"data.dsname": "demo",
                 **_parse_overrides(size["overrides"]), **sets}
    return load_config(REPO / "configs" / "default.yaml",
                       REPO / "configs" / "ggnn.yaml",
                       REPO / "configs" / "bigvul.yaml", overrides=overrides)


def _loss_fn(model, pos_weight):
    from deepdfa_tpu.train.loop import bce_with_logits, extract_labels

    def loss(params, batch):
        logits = model.apply({"params": params}, batch)
        labels, weights = extract_labels(batch, "graph")
        return bce_with_logits(logits, labels, weights, pos_weight), logits

    return loss


def _n_mosaic(lowered) -> int:
    return lowered.as_text().count("tpu_custom_call")


def _layout_stage(layout: str, rehearse: bool, corpus, pos_weight) -> dict:
    """A few Trainer steps in ``layout`` (zero twin-routed), proof the step
    went through Mosaic, and loss/logit/gradient parity with the segment
    layout on the same params and batch at highest matmul precision."""
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.train import cli
    from deepdfa_tpu.train.loop import Trainer
    from deepdfa_tpu.train.metrics import ConfusionState

    size = REHEARSAL if rehearse else GOLDEN
    sets = {"model.layout": layout}
    if layout == "fused":
        # the batch the fused kernel's own plan admits on a Big-Vul-shaped
        # corpus (bench.FUSED_BATCH_GRAPHS)
        sets["data.batch.batch_graphs"] = size["fused_batch_graphs"]
    cfg = _golden_cfg(rehearse, **sets)
    train = corpus["train"]
    model = make_model(cfg.model, cfg.input_dim)
    trainer = Trainer(model, cfg, pos_weight=pos_weight)
    batcher = cli._batcher(cfg, train + corpus["val"])
    batches = list(itertools.islice(cli._batch_stream(batcher, train), 3))
    dev = [jax.tree.map(jnp.asarray, b) for b in batches]
    state = trainer.init_state(dev[0])
    params = state.params

    step, _ = trainer.steps_for(dev[0])
    _check(step is trainer.train_step, f"{layout}: first bucket refused")
    n_mosaic = _n_mosaic(step.lower(state, dev[0], ConfusionState.zeros()))
    state, _metrics, loss = trainer.train_epoch(state, batches)
    _check(math.isfinite(loss), f"{layout}: train loss {loss}")
    _check(trainer.twin_routed_steps == 0,
           f"{layout}: {trainer.twin_routed_steps} twin-routed steps")

    import dataclasses

    seg = make_model(dataclasses.replace(cfg.model, layout="segment"),
                     cfg.input_dim)
    pw = pos_weight if cfg.optim.use_weighted_loss else None
    with jax.default_matmul_precision("highest"):
        (lk, zk), gk = jax.jit(jax.value_and_grad(
            _loss_fn(model, pw), has_aux=True))(params, dev[0])
        (ls, zs), gs = jax.jit(jax.value_and_grad(
            _loss_fn(seg, pw), has_aux=True))(params, dev[0])
    mask = np.asarray(dev[0].graph_mask)
    logit_diff = float(np.max(np.abs(np.asarray(zk) - np.asarray(zs))[mask]))
    loss_rel = abs(float(lk) - float(ls)) / max(abs(float(ls)), 1e-12)
    # one scale for the whole tree: a leaf whose gradient is zero in exact
    # arithmetic (the pooling gate's bias — softmax is shift-invariant)
    # holds only rounding noise, and a per-leaf ratio would divide by it
    pairs = [(np.asarray(a), np.asarray(b)) for a, b in
             zip(jax.tree.leaves(gk), jax.tree.leaves(gs))]
    _check(all(bool(np.all(np.isfinite(a))) for a, _ in pairs),
           f"{layout}: non-finite gradient")
    grad_rel = (max(float(np.max(np.abs(a - b))) for a, b in pairs)
                / max(float(np.max(np.abs(b))) for _, b in pairs))
    _check(logit_diff <= LOGIT_ATOL, f"{layout}: logits Δ {logit_diff:.2e}")
    _check(loss_rel <= LOSS_RTOL, f"{layout}: loss rel Δ {loss_rel:.2e}")
    _check(grad_rel <= GRAD_RTOL, f"{layout}: grad rel Δ {grad_rel:.2e}")
    return {"bucket": [int(dev[0].max_graphs), int(dev[0].max_nodes),
                       int(dev[0].senders.shape[0])],
            "train_steps": len(batches), "train_loss": round(loss, 6),
            "twin_routed_steps": trainer.twin_routed_steps,
            "mosaic_calls_in_train_step": n_mosaic,
            "logit_max_abs_diff": logit_diff, "loss_rel_diff": loss_rel,
            "grad_rel_diff": grad_rel}


def _int8_kernel_stage(width: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepdfa_tpu.ops.int8_matmul import calibrate_int8, int8_matmul

    interpret = jax.default_backend() != "tpu"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2176, width)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(width, 3 * width)) * 0.1, jnp.float32)
    q, scale = calibrate_int8(w)
    fn = jax.jit(lambda x, q, s: int8_matmul(
        x, q, s, out_dtype=jnp.float32, interpret=interpret))
    n_mosaic = _n_mosaic(fn.lower(x, q, scale))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fn(x, q, scale))
        want = np.asarray(jax.jit(
            lambda x, q, s: x @ (q.astype(jnp.float32) * s))(x, q, scale))
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    _check(bool(np.all(np.isfinite(got))) and rel <= INT8_KERNEL_RTOL,
           f"int8_matmul: rel Δ {rel:.2e}")
    return {"shape": [2176, width, 3 * width], "mosaic_calls": n_mosaic,
            "rel_diff_vs_xla_dequant": rel}


def _hier_encoder_stage(hier, graphs) -> dict:
    """The hierarchical level-1 encoder through Mosaic vs its segment-twin
    math — the routing target of an over-plan shape, forced here by zeroing
    the plan's cap around a second pass of the same scorer."""
    import jax
    import numpy as np

    from deepdfa_tpu.ops import megabatch as mb

    with jax.default_matmul_precision("highest"):
        got = hier.embed_graphs(graphs)
        fused = hier.stats()
        cap = mb.VMEM_CAP_BYTES
        mb.VMEM_CAP_BYTES = 0
        try:
            want = hier.embed_graphs(graphs)
        finally:
            mb.VMEM_CAP_BYTES = cap
    _check(fused["fallback_dispatches"] == 0 and fused["dispatches"] > 0,
           f"hier encoder: {fused}")
    _check(hier.n_level1_dispatches == fused["dispatches"]
           and hier.n_fallback_dispatches > 0,
           f"hier twin pass: {hier.stats()}")
    hier.reset_counters()
    diff = float(np.max(np.abs(got - want)))
    _check(bool(np.all(np.isfinite(got))) and diff <= LOGIT_ATOL,
           f"hier encoder: embedding Δ {diff:.2e}")
    return {"graphs": len(graphs), "dispatches": fused["dispatches"],
            "fallback_dispatches": fused["fallback_dispatches"],
            "embedding_max_abs_diff": diff}


def child_kernels(rehearse: bool) -> dict:
    import numpy as np

    from deepdfa_tpu import utils
    from deepdfa_tpu.data.sampler import positive_weight
    from deepdfa_tpu.pipeline import load_vocabs
    from deepdfa_tpu.scan import scan_paths
    from deepdfa_tpu.serve.engine import ScoringEngine
    from deepdfa_tpu.train import cli

    utils.setup_compile_cache()
    where = utils.require_backend()
    on_chip = where["backend"] == "tpu"
    cfg = _golden_cfg(rehearse, **{"serve.precision": "int8"})
    corpus = cli.load_corpus(cfg)
    labels = np.array([int(g.node_feats["_VULN"].max())
                       for g in corpus["train"]])
    pos_weight = positive_weight(labels)
    out: dict = {"where": where, "conv_width": cfg.model.out_dim // 2,
                 "n_steps": cfg.model.n_steps}

    out["int8_matmul"] = _int8_kernel_stage(cfg.model.out_dim // 2)
    out["fused"] = _layout_stage("fused", rehearse, corpus, pos_weight)
    out["megabatch"] = _layout_stage("megabatch", rehearse, corpus,
                                     pos_weight)

    # the int8 engine from the fit's checkpoint, through the constructor
    # serve uses; its hierarchical scorer serves score_unit
    shard_dir = utils.processed_dir() / "demo" / "shards"
    vocabs = load_vocabs(shard_dir)
    engine = ScoringEngine.from_checkpoint(
        cfg, WORK / "runs" / "fit" / "checkpoints", vocabs)
    _check(engine.precision == "int8",
           f"int8 engine refused: delta {engine.int8_score_delta}")
    engine.warmup()
    g0 = corpus["test"][0]
    p8 = float(engine.score([g0], engine.assign_bucket(g0))[0])
    _check(0.0 <= p8 <= 1.0, f"int8 engine score {p8}")
    out["int8_engine"] = {"precision": engine.precision,
                          "score_delta_vs_f32": engine.int8_score_delta,
                          "gate": cfg.serve.int8_max_score_delta,
                          "buckets_warm": engine.warm_buckets}

    out["hier_encoder"] = _hier_encoder_stage(engine.hier,
                                              corpus["test"][:24])

    rep = scan_paths([WORK / "unit"], vocabs, engine=engine, n_workers=1,
                     interproc=True)
    unit = rep["interproc"].get("unit") or {}
    _check("unit_score" in unit, f"score_unit: {rep['interproc']}")
    _check(0.0 <= unit["unit_score"] <= 1.0, f"score_unit: {unit}")
    _check(unit["level1"]["fallback_dispatches"] == 0
           and unit["level1"]["dispatches"] >= 1,
           f"score_unit level 1: {unit['level1']}")
    out["score_unit"] = {"unit_score": unit["unit_score"],
                         "n_functions": unit["n_functions"],
                         "call_edges": unit["call_edges"],
                         "level1": {k: unit["level1"][k] for k in
                                    ("dispatches", "fallback_dispatches")}}

    if on_chip:
        for name in ("fused", "megabatch"):
            want = 2 if name == "fused" else 1  # fused: fwd + Pallas bwd
            got = out[name]["mosaic_calls_in_train_step"]
            _check(got >= want,
                   f"{name}: {got} Mosaic call(s) in the train step — the "
                   "kernel did not go through the TPU compiler")
        _check(out["int8_matmul"]["mosaic_calls"] >= 1,
               "int8_matmul did not go through the TPU compiler")
    out["interpret"] = not on_chip
    return out


def child_multichip(rehearse: bool) -> dict:
    """One dp train step of the golden model over every chip of the host
    (loss vs the single-device steps, per-device shard placement), and
    ``score_groups`` through a ``local_mesh`` engine vs a single engine."""
    import itertools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepdfa_tpu import utils
    from deepdfa_tpu.config import MeshConfig
    from deepdfa_tpu.data.sampler import positive_weight
    from deepdfa_tpu.models import make_model
    from deepdfa_tpu.parallel.dp import (
        dp_init_state, make_dp_train_step, stack_batches)
    from deepdfa_tpu.parallel.mesh import build_mesh, local_mesh
    from deepdfa_tpu.pipeline import load_vocabs
    from deepdfa_tpu.serve.engine import ScoringEngine
    from deepdfa_tpu.train import cli
    from deepdfa_tpu.train.loop import Trainer, make_train_step
    from deepdfa_tpu.train.metrics import ConfusionState

    utils.setup_compile_cache()
    where = utils.require_backend()
    devices = jax.devices()
    n = len(devices)
    cfg = _golden_cfg(rehearse)
    corpus = cli.load_corpus(cfg)
    train = corpus["train"]
    labels = np.array([int(g.node_feats["_VULN"].max()) for g in train])
    pw = positive_weight(labels)
    model = make_model(cfg.model, cfg.input_dim)
    tx = Trainer(model, cfg, pos_weight=pw).optimizer

    batcher = cli._batcher(cfg, train + corpus["val"])
    by_shape: dict = {}
    for b in itertools.islice(cli._batch_stream(batcher, train), 8 * n):
        by_shape.setdefault(np.shape(b.node_mask), []).append(b)
    batches = max(by_shape.values(), key=len)[:n]
    _check(len(batches) == n, f"only {len(batches)} same-bucket batches")

    mesh = build_mesh(MeshConfig(dp=-1), devices)
    state = dp_init_state(model, tx, jax.tree.map(jnp.asarray, batches[0]))
    stacked = jax.device_put(stack_batches(batches),
                             NamedSharding(mesh, P("dp")))
    shards = [(s.device.id, list(s.data.shape))
              for s in stacked.node_mask.addressable_shards]
    _check(len({d for d, _ in shards}) == n
           and all(shape[0] == 1 for _, shape in shards),
           f"batch placement {shards}")
    dp_step = make_dp_train_step(model, tx, mesh, pos_weight=pw, donate=False)
    new_state, _m, dp_loss, dp_w = dp_step(state, stacked,
                                           ConfusionState.zeros())
    leaf = jax.tree.leaves(new_state.params)[0]
    _check(len(leaf.sharding.device_set) == n, "params not on every device")

    single = make_train_step(model, tx, pos_weight=pw, sentinel_guard=False)
    num = den = 0.0
    for b in batches:
        _s, _mm, loss_i, w_i = single(state, jax.tree.map(jnp.asarray, b),
                                      ConfusionState.zeros())
        num += float(loss_i) * float(w_i)
        den += float(w_i)
    ref = num / den
    rel = abs(float(dp_loss) - ref) / max(abs(ref), 1e-12)
    _check(math.isfinite(float(dp_loss)) and rel <= MULTICHIP_RTOL,
           f"dp loss {float(dp_loss)} vs single-device {ref} (rel {rel:.2e})")
    _check(abs(float(dp_w) - den) < 0.5,
           f"dp weight sum {float(dp_w)} vs {den}")

    vocabs = load_vocabs(utils.processed_dir() / "demo" / "shards")
    params = new_state.params
    kw = dict(label_style="graph", feat_keys=tuple(vocabs),
              max_batch=cfg.serve.max_batch)
    eng_mesh = ScoringEngine.from_model(model, params, mesh=local_mesh(n),
                                        **kw)
    eng_one = ScoringEngine.from_model(
        model, jax.device_get(params), **kw)
    bucket = eng_mesh.buckets[0]
    test = [g for g in corpus["test"] if bucket.admits(g)]
    per = min(bucket.capacity, max(len(test) // n, 1))
    groups = [test[i * per:(i + 1) * per] for i in range(n)]
    got = eng_mesh.score_groups(groups, bucket)
    _check(eng_mesh.n_dispatches == 1,
           f"mesh engine took {eng_mesh.n_dispatches} dispatches")
    want = [eng_one.score(g, bucket) for g in groups]
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(got, want))
    _check(diff <= MULTICHIP_RTOL, f"mesh score_groups Δ {diff:.2e}")
    return {"where": where, "mesh": dict(mesh.shape),
            "dp_loss": float(dp_loss), "single_device_loss": ref,
            "loss_rel_diff": rel, "batch_shards": shards,
            "params_devices": len(leaf.sharding.device_set),
            "score_groups": {"groups": n, "graphs_per_group": per,
                             "dispatches": eng_mesh.n_dispatches,
                             "max_abs_diff_vs_single": diff}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-size CPU rehearsal of this script (labelled "
                         "cpu; proves nothing about the chip)")
    ap.add_argument("--child", choices=("kernels", "multichip"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        fn = child_kernels if args.child == "kernels" else child_multichip
        print(json.dumps(fn(args.rehearse_cpu)))
        return 0
    return Smoke(args.rehearse_cpu).main()


T0 = time.monotonic()

if __name__ == "__main__":
    raise SystemExit(main())
