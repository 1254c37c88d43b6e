"""The readers of the cadence of completed steps (PR 37): each on a ring of
known spans, ``None`` on a program that sets neither the attribute nor the
field, the ring's overflow, and — through ``run.py`` at the tiny size — all
nine metrics in one traced run on the CPU."""

import contextlib
import io
import json
import sys
import types

import pytest
from conftest import BENCH
from harness import spec

CADENCE_TINY = str(BENCH / "tests" / "BENCHMARK.cadence.tiny.json")
NEW = ["step_interval_p50_ms.train", "step_interval_max_ms.train", "step_stall_share.train",
       "trainer_dispatch_max_ms.train", "dispatch_cpu_share.train", "h2d_cpu_share.train",
       "gc_pause_share.train", "gc_pause_max_ms.train", "gc_pause_s.setup"]
START, SETUP, WINDOW = 1000.0, 30.0, 20.0  # the run: set-up to 1030, window to 1050


def _ctx():
    return types.SimpleNamespace(phases=types.SimpleNamespace(
        process_start=START, setup_s=SETUP, window_s=WINDOW))


def _read(name, ctx=None):
    """The metric ``name`` as ``run.py`` reads it: its data file's reader and
    arguments."""
    m = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
    return spec.load_module("readers", m["reader"]).read(ctx or _ctx(), **m["args"])


@pytest.fixture
def ring(monkeypatch):
    """A telemetry in the accessor's place; ``ring(name, start, end, cpu_s=, **attrs)``
    records one span."""
    from deepdfa_tpu import obs

    telemetry = obs.TrainTelemetry(tracer=obs.Tracer(proc="train", max_spans=64))
    monkeypatch.setattr(obs, "train_telemetry", lambda: telemetry)

    def record(name, start, end, **attrs):
        return telemetry.tracer.record(name, start, end, **attrs)

    record.tracer = telemetry.tracer
    return record


def test_the_nine_are_appended_and_list_every_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    layers = {m["layer"] for m in bench["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = by_name[name]
        data = json.loads((BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert m["workloads"] == cells and m["layer"] in layers and m["source"] == "program_span"
        assert m["moves"] == ("setup_s" if name.endswith(".setup") else "train_functions_per_s")
        assert {k: data[k] for k in ("name", "layer", "moves", "unit", "better", "source")} == {
            k: m[k] for k in ("name", "layer", "moves", "unit", "better", "source")}
        assert (BENCH / "readers" / f"{data['reader']}.py").is_file()


def test_each_reader_on_a_ring_of_known_spans(ring):
    # set-up: the traffic's objects are collected before the window opens
    ring("gc.pause", 1010.0, 1010.4, cpu_s=0.4, generation=2, collected=9)
    ring("gc.pause", 1010.2, 1010.5, cpu_s=0.3, generation=2, collected=0)  # overlapping: once
    ring("step.dispatch", 1029.0, 1029.9, cpu_s=0.9, step=4)  # a warm step: not the window's
    ring("loss.sync", 1029.9, 1029.95, cpu_s=0.0, step=3, interval_s=9.0, gc_s=1.0, gc_n=1)
    # the window: ten steps of 100 ms, one of them 400 ms with a 250 ms pause in its call
    at = 1030.0
    for k in range(5, 15):
        long = 0.3 if k == 9 else 0.0
        ring("step.dispatch", at, at + 0.040 + long, cpu_s=0.010 + long, step=k)
        ring("batch.h2d", at + 0.001, at + 0.021, cpu_s=0.001)
        if long:
            ring("gc.pause", at + 0.01, at + 0.26, cpu_s=0.25, generation=2, collected=3, step=k)
        ring("gc.pause", at + 0.05 + long, at + 0.052 + long, cpu_s=0.002, generation=1,
             collected=0, step=k)
        ring("loss.sync", at + 0.040 + long, at + 0.1 + long, cpu_s=0.0001, step=k - 1,
             interval_s=0.1 + long, gc_s=0.002 + (0.25 if long else 0.0), gc_n=2 if long else 1)
        at += 0.1 + long
    # the read that finds the deadline ends after the window: not counted
    ring("loss.sync", 1049.99, 1050.5, cpu_s=0.0, step=15, interval_s=5.0, gc_s=4.0, gc_n=1)
    assert _read("step_interval_p50_ms.train") == pytest.approx(100.0)
    assert _read("step_interval_max_ms.train") == pytest.approx(400.0)
    # one stall, 300 ms over the median, of 1.3 s of intervals
    assert _read("step_stall_share.train") == pytest.approx(100 * 0.3 / 1.3)
    assert _read("trainer_dispatch_max_ms.train") == pytest.approx(340.0)
    assert _read("dispatch_cpu_share.train") == pytest.approx(100 * (10 * 0.010 + 0.3) / 0.7)
    assert _read("h2d_cpu_share.train") == pytest.approx(5.0)
    assert _read("gc_pause_share.train") == pytest.approx(100 * (10 * 0.002 + 0.25) / 1.3)
    assert _read("gc_pause_max_ms.train") == pytest.approx(250.0)
    assert _read("gc_pause_s.setup") == pytest.approx(0.5)
    # the median, less what the stalls took, is the mean step
    mean_ms = _read("step_interval_p50_ms.train") / (1 - _read("step_stall_share.train") / 100)
    assert mean_ms == pytest.approx(130.0)


def test_a_uniformly_slow_run_moves_the_median_and_no_stall_share(ring):
    for k in range(10):
        ring("loss.sync", 1030.0 + 0.112 * k, 1030.1 + 0.112 * k, step=k, interval_s=0.112,
             gc_s=0.0, gc_n=0)
    assert _read("step_interval_p50_ms.train") == pytest.approx(112.0)
    assert _read("step_stall_share.train") == 0.0 and _read("gc_pause_share.train") == 0.0
    # the program looked for pauses (gc_n is set) and none made a span: 0, not nothing
    assert _read("gc_pause_max_ms.train") == 0.0


def test_a_program_that_sets_neither_reads_none(ring, monkeypatch):
    from deepdfa_tpu import obs

    assert [_read(n) for n in NEW] == [None] * 9  # a ring nothing was recorded into
    # the parent's spans: no interval_s / gc_s on loss.sync, no cpu_s on any span
    for k in range(4):
        ring("step.dispatch", 1031.0 + k, 1031.04 + k, step=k)
        ring("batch.h2d", 1031.0 + k, 1031.02 + k)
        ring("loss.sync", 1031.04 + k, 1031.9 + k, step=k, reads=1, alone=0)
    assert all(span.cpu_s is None for span in ring.tracer.spans())
    got = {n: _read(n) for n in NEW}
    # what only needs the spans the parent has reads; set-up held no pause: 0 s, not nothing
    assert got.pop("trainer_dispatch_max_ms.train") == pytest.approx(40.0)
    assert got.pop("gc_pause_s.setup") == 0.0
    assert set(got.values()) == {None}
    no_window = _ctx()
    no_window.phases.window_s = None
    assert [_read(n, no_window) for n in NEW] == [None] * 9
    monkeypatch.delattr(obs, "train_telemetry")
    assert [_read(n) for n in NEW] == [None] * 9


def test_a_ring_that_dropped_spans_of_the_interval_is_an_error(ring):
    for i in range(ring.tracer.capacity):
        ring("loss.sync", 1031.0 + i * 0.1, 1031.05 + i * 0.1, step=i, interval_s=0.1,
             gc_s=0.0, gc_n=0)
    for name in NEW:
        with pytest.raises(RuntimeError, match="overflowed"):
            _read(name)


@pytest.fixture(scope="module")
def row():
    """One traced run of the tiny fusion cell, every metric of the cadence file."""
    sys.path.insert(0, str(BENCH))
    import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", "tiny-linevul-fusion.finetune", "--seed", "2147483693",
                         "--seconds", "1.5", "--trace", "1",
                         "--benchmark-file", CADENCE_TINY]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_run_prints_all_nine_on_the_cpu(row):
    assert row["correct"] is True and row["device"]["platform"] == "cpu"
    value = lambda name: row["metrics"][name]["value"]
    # (a window in which no collection made a span reads 0 for the longest one)
    assert set(NEW) <= set(row["metrics"]) and value("gc_pause_max_ms.train") >= 0
    assert 0 < value("step_interval_p50_ms.train") <= value("step_interval_max_ms.train")
    assert 0 <= value("step_stall_share.train") < 100
    assert value("trainer_dispatch_max_ms.train") >= value("trainer_dispatch_ms.train")
    assert 0 <= value("dispatch_cpu_share.train") <= 105
    assert 0 <= value("h2d_cpu_share.train") <= 105
    assert 0 <= value("gc_pause_share.train") < 100 and value("gc_pause_s.setup") >= 0
    # the median interval, less what the stalls took, is the rate (loose: CPU, tiny steps)
    steps, seconds = row["window"]["steps"], row["window"]["seconds"]
    mean_ms = value("step_interval_p50_ms.train") / (1 - value("step_stall_share.train") / 100)
    assert mean_ms == pytest.approx(1e3 * seconds / steps, rel=0.35)
