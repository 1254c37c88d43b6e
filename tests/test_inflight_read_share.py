"""``inflight_read_share.train``: the benchmark's count of loss reads that had
a later step launched beside them — a data file over the reader that was
there, printed by the tiny cell, silent on a program without the counts."""

import functools
import json
import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

pytestmark = pytest.mark.obs

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
NAME = "inflight_read_share.train"


@functools.cache
def _files() -> tuple[dict, dict, dict]:
    """``BENCHMARK.json``, the metric's entry in it (by name, not by place:
    later PRs append after it) and the metric's data file."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == NAME)
    return bench, entry, json.loads((BENCH / "layer_metrics" / f"{NAME}.json").read_text())


def test_the_entry_and_its_data_file_agree():
    bench, entry, data = _files()
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert {k: data[k] for k in ("name", "layer", "moves", "unit", "better", "source")} == {
        k: v for k, v in entry.items() if k != "workloads"}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"] if m is not entry}
    assert (entry["better"], entry["moves"]) == ("higher", "train_functions_per_s")
    assert data["reader"] == "program_attr_pad_share"
    assert data["args"] == {"span": "loss.sync", "real": "alone", "padded": "reads"}


@pytest.mark.parametrize("attrs,expected", [
    ([{}] * 3, None),  # the parent's loop: loss.sync spans without the counts
    ([{"reads": 1, "alone": 0}] * 3 + [{"reads": 1, "alone": 1}], 75.0),
    ([{"reads": 1, "alone": 1}] * 2, 0.0),  # every read a flush: nothing in flight
])
def test_the_reader_counts_reads_with_a_later_step_launched(monkeypatch, attrs, expected):
    from deepdfa_tpu import obs

    monkeypatch.syspath_prepend(str(BENCH))
    from harness import spec

    telemetry = obs.TrainTelemetry(tracer=obs.Tracer(proc="train", max_spans=64))
    monkeypatch.setattr(obs, "train_telemetry", lambda: telemetry)
    for at, a in enumerate(attrs):
        telemetry.tracer.record("loss.sync", 1030.0 + at, 1030.5 + at, step=at, **a)
    ctx = types.SimpleNamespace(phases=types.SimpleNamespace(
        process_start=1000.0, setup_s=30.0, window_s=20.0))
    data = _files()[2]
    assert spec.load_module("readers", data["reader"]).read(ctx, **data["args"]) == expected


def test_the_tiny_cell_prints_it(tmp_path):
    """A traced run of the tiny fusion cell through ``run.py``: no window
    reaches an evaluation point or an epoch's end, so every read of the loop
    has the next step queued behind it."""
    bench = json.loads((BENCH / "tests" / "BENCHMARK.program.tiny.json").read_text())
    cell = bench["workloads"][0]["name"]
    bench["per_layer"].append({**_files()[1], "workloads": [cell]})
    (tmp_path / "b.json").write_text(json.dumps(bench))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "3",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"), "TMPDIR": str(tmp_path)}
    env.pop("XLA_FLAGS", None)  # one device, as the cell asks
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", str(2**31 + 28),
         "--seconds", "1", "--trace", "1", "--benchmark-file", str(tmp_path / "b.json")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    assert row["correct"] is True and row["failed"] == 0 and row["attempted"] > 2
    assert row["metrics"][NAME] == {"value": 100.0, "unit": "%"}
    assert row["metrics"]["trainer_loss_sync_share.train"]["value"] > 0
