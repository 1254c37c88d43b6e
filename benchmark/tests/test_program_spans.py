"""The readers of the program's own spans (``readers/program_*.py``): each on
a ring of known spans, the ring's overflow, a program without the ring, and —
through ``run.py`` at the tiny size — each twin beside the benchmark's own
metric of the same boundary."""

import contextlib
import io
import json
import sys
import types

import pytest
from conftest import BENCH
from harness import spec

PROGRAM_TINY = str(BENCH / "tests" / "BENCHMARK.program.tiny.json")
NEW = ["trainer_data_wait_share.train", "trainer_dispatch_ms.train",
       "trainer_loss_sync_share.train", "prefetch_busy_share.train",
       "pad_share_tokens.train", "compiles.train", "jit_trace_lower_s.setup",
       "jit_backend_s.setup"]
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
    """A telemetry in the accessor's place; ``ring(name, start, end, **attrs)``
    records one span."""
    from deepdfa_tpu import obs

    telemetry = obs.TrainTelemetry(tracer=obs.Tracer(proc="train", max_spans=64))
    monkeypatch.setattr(obs, "train_telemetry", lambda: telemetry)

    def record(name, start, end, **attrs):
        telemetry.tracer.record(name, start, end, **attrs)

    record.tracer = telemetry.tracer
    return record


def test_the_new_metrics_are_the_last_eight_and_list_both_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    tail = bench["per_layer"][-8:]
    assert [m["name"] for m in tail] == NEW
    cells = [w["name"] for w in bench["workloads"]]
    layers = {m["layer"] for m in bench["per_layer"][:-8]}
    for m in tail:
        assert m["workloads"] == cells and m["layer"] in layers
        assert m["source"] == ("program_counter" if m["name"].startswith("pad_") else "program_span")
        assert m["moves"] == ("setup_s" if m["name"].endswith(".setup") else "train_functions_per_s")


def test_a_span_belongs_to_the_phase_it_ended_in(ring):
    # set-up: compile events, and the warm steps' spans
    ring("jit.trace", 1001.0, 1003.0, fun_name="train_step")
    ring("jit.trace", 1001.5, 1002.0, fun_name="apply")  # nested in it: covered once
    ring("jit.lower", 1003.0, 1004.5)
    ring("jit.backend_compile", 1004.5, 1024.5)
    ring("jit.backend_compile", 1025.0, 1025.5)
    ring("step.dispatch", 1029.0, 1029.9, step=4)
    # the first timed step's call opens a moment before the window does
    ring("step.dispatch", 1029.999, 1030.040, step=5)
    ring("loss.sync", 1030.040, 1030.130, step=5)
    ring("data.wait", 1030.130, 1030.132, step=6)
    ring("step.dispatch", 1030.132, 1030.192, step=6)
    ring("loss.sync", 1030.192, 1030.300, step=6)
    ring("batch.build", 1030.050, 1030.060, tokens_real=300, tokens=512)
    ring("batch.h2d", 1030.060, 1030.062)
    ring("batch.build", 1030.200, 1030.230, tokens_real=212, tokens=512)
    ring("batch.build", 1030.300, 1030.301, exhausted=True)
    # the call that finds the deadline ends after the window: not counted
    ring("data.wait", 1049.990, 1049.999, step=150)
    ring("step.dispatch", 1049.9995, 1050.300, step=150)
    ring("jit.backend_compile", 1050.5, 1051.0)  # the reference's, after the window
    assert _read("trainer_dispatch_ms.train") == pytest.approx((41 + 60) / 2)
    assert _read("trainer_loss_sync_share.train") == pytest.approx(100 * 0.198 / 20)
    assert _read("trainer_data_wait_share.train") == pytest.approx(100 * 0.011 / 20)
    assert _read("prefetch_busy_share.train") == pytest.approx(100 * 0.043 / 20)
    assert _read("pad_share_tokens.train") == pytest.approx(50.0)
    assert _read("compiles.train") == 0
    assert _read("jit_trace_lower_s.setup") == pytest.approx(3.5)
    assert _read("jit_backend_s.setup") == pytest.approx(20.5)
    ring("jit.backend_compile", 1040.0, 1041.0, step=77)  # a re-jit in the window
    assert _read("compiles.train") == 1
    assert _read("jit_backend_s.setup") == pytest.approx(20.5)


def test_nothing_to_read_is_none_not_zero(ring, monkeypatch):
    from deepdfa_tpu import obs

    assert [_read(n) for n in NEW] == [None] * 8  # a ring nothing was recorded into
    ring("eval", 1031.0, 1032.0)
    # the ring was there and held none: a count of 0, sums of 0, no mean, no share of nothing
    assert _read("compiles.train") == 0 and _read("jit_backend_s.setup") == 0
    assert _read("trainer_dispatch_ms.train") is None
    assert _read("pad_share_tokens.train") is None
    no_window = _ctx()
    no_window.phases.window_s = None
    assert [_read(n, no_window) for n in NEW] == [None] * 8
    # the parent of the PR that added the accessor: every reader returns nothing
    monkeypatch.delattr(obs, "train_telemetry")
    assert [_read(n) for n in NEW] == [None] * 8


def test_a_ring_that_dropped_spans_of_the_interval_is_an_error(ring):
    for i in range(ring.tracer.capacity):
        ring("step.dispatch", 1031.0 + i * 0.1, 1031.05 + i * 0.1, step=i)
    # full, and its oldest span ended after the window began: the window's
    # first steps may be gone, and the set-up's certainly
    for name in NEW:
        with pytest.raises(RuntimeError, match="overflowed"):
            _read(name)
    # full but reaching back before the interval asked for: nothing is lost
    late = _ctx()
    late.phases.setup_s = 31.06
    assert _read("trainer_dispatch_ms.train", late) == pytest.approx(50.0)
    with pytest.raises(RuntimeError, match="set-up|setup"):
        _read("jit_backend_s.setup", late)


@pytest.fixture(scope="module")
def row():
    """One traced run of the tiny fusion cell, every metric of the new file."""
    sys.path.insert(0, str(BENCH))
    import run

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", "tiny-linevul-fusion.finetune", "--seed", "2147483659",
                         "--seconds", "1.5", "--trace", "1",
                         "--benchmark-file", PROGRAM_TINY]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_run_prints_all_eight_on_the_cpu(row):
    assert row["correct"] is True and row["device"]["platform"] == "cpu"
    assert set(NEW) <= set(row["metrics"])
    value = lambda name: row["metrics"][name]["value"]
    assert 30 < value("pad_share_tokens.train") < 70  # the tiny mix's lengths
    assert value("compiles.train") == 0
    assert 0 < value("prefetch_busy_share.train") < 100
    assert value("jit_trace_lower_s.setup") > 0 and value("jit_backend_s.setup") > 0
    # all of it happened inside set-up
    assert value("jit_trace_lower_s.setup") + value("jit_backend_s.setup") < 60


def test_each_twin_lies_on_its_side_of_the_outside_metric(row):
    """The program's ``step.dispatch`` and ``loss.sync`` wrap the driver's
    wrapper and its timed ``__float__``, so they read at or above the outside
    spans; the program's ``data.wait`` is the loop's ``next()`` alone, inside
    the outside span (end of the loss read to the next call of the step)."""
    value = lambda name: row["metrics"][name]["value"]
    assert value("trainer_dispatch_ms.train") >= value("dispatch_ms.train")
    assert value("trainer_loss_sync_share.train") >= value("loss_sync_share.train")
    assert value("trainer_data_wait_share.train") <= value("data_wait_share.train")
    # and close: the same boundaries, a few Python calls apart (loose: CPU, tiny steps)
    assert value("trainer_dispatch_ms.train") < value("dispatch_ms.train") + 5.0
    assert value("trainer_loss_sync_share.train") < value("loss_sync_share.train") + 10.0


# -- tools/program_trace.py: the reductions, on a hand-made xplane ------------

def _tool():
    sys.path.insert(0, str(BENCH))
    from tools import program_trace

    return program_trace


def _xplane(tmp_path):
    """Two steps: ops at 100-200 and 400-500 us on the device; the loop inside
    step.dispatch during the gaps, the producer inside batch.h2d for half the
    second one."""
    tool = _tool()
    space = tool.xspace_class()()
    dev = space.planes.add(name="/device:TPU:0")
    for key, name in ((1, "tf_op"), (2, "flops")):
        e = dev.stat_metadata.add(key=key)
        e.value.id, e.value.name = key, name
    for key, hlo, op_name in (
            (1, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
             "jit(train_step)/jit(main)/optimizer/add:"),
            (2, "%fusion.2 = f32[8,4]{1,0} fusion(f32[8]{0} %p), kind=kCustom",
             "jit(train_step)/jvp(FusionModel)/flowgnn_encoder/ggnn/round_3/scatter-add:"),
            (3, "%copy.7 = f32[8]{0} copy(f32[8]{0} %p)", "")):
        e = dev.event_metadata.add(key=key)
        e.value.id, e.value.name = key, hlo
        if op_name:
            e.value.stats.add(metadata_id=1, str_value=op_name)
            e.value.stats.add(metadata_id=2, int64_value=64)
    skipped = dev.lines.add(name="Steps", timestamp_ns=0)
    skipped.events.add(metadata_id=1, offset_ps=0, duration_ps=10**9)
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=1_000_000)
    for md, at_us, dur_us in ((1, 100, 60), (2, 160, 40), (1, 400, 50), (2, 450, 40), (3, 490, 10)):
        ops.events.add(metadata_id=md, offset_ps=at_us * 10**6, duration_ps=dur_us * 10**6)
    host = space.planes.add(name="/host:CPU")
    names = ["deepdfa:train.epoch", "deepdfa:step.dispatch", "deepdfa:loss.sync",
             "deepdfa:batch.h2d", "$python.py:1 f"]
    for key, name in enumerate(names, 1):
        e = host.event_metadata.add(key=key)
        e.value.id, e.value.name = key, name
    loop = host.lines.add(name="python", timestamp_ns=1_000_000)
    for md, at_us, dur_us in ((1, 0, 600), (2, 50, 60), (3, 110, 90), (2, 200, 210), (3, 410, 90),
                              (5, 0, 600)):
        loop.events.add(metadata_id=md, offset_ps=at_us * 10**6, duration_ps=dur_us * 10**6)
    producer = host.lines.add(name="python", timestamp_ns=1_000_000)
    producer.events.add(metadata_id=4, offset_ps=300 * 10**6, duration_ps=100 * 10**6)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    return tool, tool.read_xplane(path)


def test_program_trace_reads_op_names_off_the_metadata(tmp_path):
    tool, trace = _xplane(tmp_path)
    assert len(trace["ops"]) == 5 and trace["stat_names"] == {"tf_op": 2, "flops": 2}
    plane, a, b, name, stats = trace["ops"][1]
    assert (plane, a, b) == ("/device:TPU:0", 1_160_000, 1_200_000)
    assert stats["flops"] == 64 and stats["tf_op"].endswith("round_3/scatter-add:")
    # device time by scope: root and three levels, wrappers and primitive gone
    assert tool.device_by_scope(trace["ops"]) == pytest.approx({
        "optimizer": 110e-6, "jvp(FusionModel)/flowgnn_encoder/ggnn/round_3": 80e-6,
        "(no op name) %copy.7 f32[8]": 10e-6})
    assert tool.scope_of("jit(f)/jit(main)/jvp(M)/a/b/c/d/mul") == "jvp(M)/a/b/c"
    assert tool.scope_of("jit(f)/mul") == "mul" and tool.scope_of("") == "(unnamed)"
    # the program's annotations, one list a thread, python-tracer events dropped
    (loop,), (producer,) = ([v for k, v in trace["host"].items() if k[1] == at] for at in (0, 1))
    assert sorted({n for _, _, n in loop}) == ["loss.sync", "step.dispatch", "train.epoch"]
    assert producer == [(1_300_000, 1_400_000, "batch.h2d")]
    # the one gap (200-400 us) is the loop's second call's; the epoch root names nothing
    table, idle = tool.gaps_by_span(trace["ops"], loop)
    assert idle == pytest.approx(200e-6) and table == pytest.approx({"step.dispatch": 200e-6})
    table, _ = tool.gaps_by_span(trace["ops"], producer)
    assert table == pytest.approx({"batch.h2d": 200e-6})
    assert tool.gaps_by_span(trace["ops"], [])[0] == pytest.approx({"(no span)": 200e-6})


def test_program_trace_bins_dispatch_by_producer_overlap():
    from deepdfa_tpu.obs import Tracer

    tool = _tool()
    tracer = Tracer(proc="t")
    tracer.record("step.dispatch", 9.0, 9.5)              # before the window: left out
    tracer.record("step.dispatch", 10.0, 10.040, step=0)  # no producer span overlaps
    tracer.record("step.dispatch", 11.0, 11.042, step=1)  # 10 ms of a build
    tracer.record("step.dispatch", 12.0, 12.050, step=2)  # 30 ms of build + H2D
    tracer.record("step.dispatch", 13.0, 13.050, step=3)  # an exhausted pull is no work
    tracer.record("batch.build", 10.990, 11.010)
    tracer.record("batch.build", 11.990, 12.010)
    tracer.record("batch.h2d", 12.010, 12.030)
    tracer.record("batch.build", 13.0, 13.050, exhausted=True)
    rows = tool.dispatch_by_overlap(tracer.spans(), 9.9)
    assert [r["n"] for r in rows] == [2, 1, 1]
    assert [r["dispatch_ms"] for r in rows] == pytest.approx([45.0, 42.0, 50.0])
    assert [r["overlap_ms"] for r in rows] == pytest.approx([0.0, 10.0, 30.0])
    means = tool.ring_means(tracer.spans(), 9.9)
    assert means["step.dispatch"] == [4, pytest.approx(45.5)]
    assert means["batch.build"] == [2, pytest.approx(20.0)]
