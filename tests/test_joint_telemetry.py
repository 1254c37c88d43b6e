"""Step telemetry inside ``JointTrainer.train``: the loop's and the prefetch
producer's spans, compile events with the step they fell in, the profiler's
annotations — and that none of it can touch the training itself."""

import contextlib
import gc
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.obs import Tracer, TrainTelemetry
from deepdfa_tpu.resilience import faults

pytestmark = pytest.mark.obs

LOOP_SPANS = ("data.wait", "step.dispatch", "loss.sync")


@pytest.fixture(scope="module")
def tiny():
    """One tiny LineVul-combined trainer, built once (one compile), with the
    state every run starts from."""
    from deepdfa_tpu.config import GGNNConfig
    from deepdfa_tpu.data.synthetic import random_dataset
    from deepdfa_tpu.llm.dataset import (
        GraphJoin,
        HashTokenizer,
        encode_functions,
        text_batches,
    )
    from deepdfa_tpu.llm.fusion import FusionModel
    from deepdfa_tpu.llm.joint import JointConfig, JointTrainer
    from deepdfa_tpu.llm.roberta import RobertaEncoder, tiny_roberta

    cfg = tiny_roberta(vocab_size=256)
    enc = RobertaEncoder(cfg)
    jcfg = JointConfig(
        block_size=32, train_batch_size=4, eval_batch_size=4, epochs=1,
        train_llm=True, freeze_gnn=True, use_gnn=True, first_eval_steps=1)
    graphs = random_dataset(12, seed=0, input_dim=8)
    funcs = [f"int f{i}(int a) {{ return a + {i}; }}" * (1 + i % 3) for i in range(12)]
    examples = encode_functions(
        funcs, [i % 2 for i in range(12)], HashTokenizer(vocab_size=cfg.vocab_size),
        jcfg.block_size, indices=[g.gid for g in graphs])
    fusion = FusionModel(
        gnn_cfg=GGNNConfig(hidden_dim=8, n_steps=2), input_dim=8,
        llm_hidden_size=cfg.hidden_size, use_gnn=True, pool="cls")
    enc_params = enc.init(
        jax.random.key(0), jnp.zeros((2, jcfg.block_size), jnp.int32),
        jnp.ones((2, jcfg.block_size), bool))["params"]
    trainer = JointTrainer(
        llm=enc, llm_params=enc_params, fusion=fusion, cfg=jcfg,
        join=GraphJoin.from_list(graphs, max_nodes=512, max_edges=1024))
    first = trainer._joined(next(text_batches(examples, jcfg.train_batch_size)))
    state0 = trainer._build(3, first)
    trainer.train(examples, examples, state=state0)  # compiles both steps
    return trainer, examples, state0


def _telemetry(**tracer_kw) -> TrainTelemetry:
    tracer_kw.setdefault("annotation", jax.profiler.TraceAnnotation)
    return TrainTelemetry(tracer=Tracer(proc="train", max_spans=4096, **tracer_kw))


def _train(tiny, telemetry):
    trainer, examples, state0 = tiny
    trainer.telemetry = telemetry
    trainer.history.clear()
    return trainer.train(examples, examples, state=state0)


def _same(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True))


def test_each_step_leaves_its_spans_under_the_epoch_root(tiny):
    from deepdfa_tpu.llm.dataset import text_batches

    trainer, examples, _ = tiny
    telemetry = _telemetry()
    _train(tiny, telemetry)
    spans = telemetry.tracer.spans()
    (root,) = [s for s in spans if s.name == "train.epoch"]
    assert root.root and root.attrs == {"epoch": 0} and root.parent_id is None
    here = threading.get_ident() % 1_000_000
    for name in LOOP_SPANS:
        found = [s for s in spans if s.name == name]
        assert [s.attrs["step"] for s in found] == [0, 1, 2], name
        assert {s.parent_id for s in found} == {root.span_id}
        assert {s.tid for s in found} == {here}
        assert all(s.trace_id == root.trace_id and s.dur_s > 0 for s in found)
    # the producer's thread: one build and one H2D a batch, and the pull that
    # found the stream exhausted
    builds = [s for s in spans if s.name == "batch.build"]
    assert [bool(s.attrs.get("exhausted")) for s in builds] == [False] * 3 + [True]
    h2d = [s for s in spans if s.name == "batch.h2d"]
    assert len(h2d) == 3
    assert {s.parent_id for s in builds + h2d} == {root.span_id}
    (producer,) = {s.tid for s in builds + h2d}
    assert producer != here
    # the counts are the batch's own, from the numpy arrays
    batches = list(text_batches(examples, 4, shuffle=True, seed=trainer.cfg.seed))
    for sp, tb in zip(builds, batches):
        assert sp.attrs == {
            "tokens_real": int(tb.pad_mask.sum()), "tokens": 4 * 32}
    assert 0 < builds[0].attrs["tokens_real"] < builds[0].attrs["tokens"]
    # eval at the epoch's one eval point, inside the root too
    (ev,) = [s for s in spans if s.name == "eval"]
    assert ev.attrs == {"step": 2} and ev.parent_id == root.span_id
    # the epoch's history entry carries the window's stats
    stats = trainer.history[-1]["telemetry"]
    assert stats["steps"] == 3 and stats["compiles"] == 0
    assert stats["sync_s"] > 0 and stats["dispatch_s"] > 0
    # the producer's seconds are the sums of its spans (it is joined, its
    # empty last pull over, before the window's stats are taken)
    assert stats["build_s"] == pytest.approx(sum(s.dur_s for s in builds), abs=2e-6)
    assert stats["h2d_s"] == pytest.approx(sum(s.dur_s for s in h2d), abs=2e-6)
    assert telemetry.epoch_stats()["steps"] == 0
    text = telemetry.render()
    assert "deepdfa_train_steps_total 3" in text
    assert f"deepdfa_train_prefetch_h2d_seconds_total {stats['h2d_s']}" in text


def test_an_exemplar_dir_exports_the_epoch_with_eval_and_checkpoint(tiny, tmp_path):
    """What ``scripts/train_joint.py`` sets up: a tracer that journals every
    epoch root, read by name with ``report_profiling.py --traces``."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "scripts"))
    try:
        from report_profiling import trace_report
    finally:
        sys.path.pop(0)

    trainer = tiny[0]
    trainer.run_dir = tmp_path
    try:
        _train(tiny, _telemetry(slow_ms=0.0, exemplar_dir=tmp_path / "traces"))
    finally:
        trainer.run_dir = None
    report = trace_report(tmp_path)
    assert report["trace_records"] == 1
    counts = {name: row["count"] for name, row in report["spans"].items()}
    counts.pop("gc.pause", None)  # the collector's long pauses, where it had any
    assert counts == {
        "train.epoch": 1, "data.wait": 3, "step.dispatch": 3, "loss.sync": 3,
        "batch.build": 4, "batch.h2d": 3, "eval": 1, "checkpoint.save": 1}
    assert report["spans"]["checkpoint.save"]["mean_ms"] > 0
    # every span the loop timed carries its thread's CPU clock
    assert all(row["cpu_share"] >= 0 for row in report["spans"].values())
    # and the epoch's cadence: two intervals between its three reads
    (cadence,) = report["cadence"]
    assert cadence["epoch"] == 0 and cadence["steps"] == 2
    assert 0 < cadence["interval_p50_ms"] <= cadence["interval_max_ms"]


@contextlib.contextmanager
def _a_collection_in_step(tiny, k):
    """The collector held off, but for one full collection forced inside the
    call of step ``k`` (none for ``None``): the run's pauses are known."""
    trainer = tiny[0]
    real_train, real_eval = trainer._steps
    calls = []

    def train_step(state, llm_arg, jb):
        calls.append(1)
        if k is not None and len(calls) == k + 1:
            gc.collect()
        return real_train(state, llm_arg, jb)

    trainer._steps = (train_step, real_eval)
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()
        trainer._steps = (real_train, real_eval)


def test_params_equal_a_run_with_every_span_dropped(tiny):
    on = _telemetry()
    with _a_collection_in_step(tiny, 1):
        with_spans = _train(tiny, on)
    off = _telemetry()
    with faults.installed("obs.trace_drop:p=1"), _a_collection_in_step(tiny, None):
        without = _train(tiny, off)
    # every recording was on in the first run: the CPU clock, the pause, the intervals
    spans = on.tracer.spans()
    (pause,) = [s for s in spans if s.name == "gc.pause"]
    (call,) = [s for s in spans if s.name == "step.dispatch" and s.attrs["step"] == 1]
    assert pause.attrs["generation"] == 2 and pause.attrs["step"] == 1
    assert pause.parent_id == call.span_id and pause.tid == call.tid
    assert all(s.cpu_s is not None for s in spans)
    assert sum("interval_s" in s.attrs for s in spans if s.name == "loss.sync") == 2
    # (a pause takes no hit of the drop schedule: it is no span of the program's making)
    assert len(on.tracer) > 15 and len(off.tracer) == 0
    assert off.tracer.dropped_total == on.tracer.recorded_total - 1
    assert _same(with_spans.params, without.params)
    assert _same(with_spans.opt_state, without.opt_state)
    assert int(with_spans.step) == int(without.step) == 3


@pytest.mark.parametrize("where", ["init", "enter", "exit"])
def test_a_raising_annotation_never_fails_the_step(tiny, where):
    class Boom:
        def __init__(self, name, **attrs):
            if where == "init":
                raise RuntimeError("annotation init")

        def __enter__(self):
            if where == "enter":
                raise RuntimeError("annotation enter")

        def __exit__(self, *exc):
            if where == "exit":
                raise RuntimeError("annotation exit")

    good = _train(tiny, _telemetry())
    broken = _telemetry(annotation=Boom)
    state = _train(tiny, broken)
    assert _same(good.params, state.params)
    names = [s.name for s in broken.tracer.spans()]
    assert names.count("step.dispatch") == 3 and names.count("batch.h2d") == 3


def test_a_rejit_inside_the_loop_is_one_backend_compile_with_its_step(tiny):
    trainer, _, _ = tiny
    x = jnp.ones(3)  # its own little program compiles here, before anyone listens
    telemetry = _telemetry()
    real_train, real_eval = trainer._steps
    calls = []

    def rejit_probe(v):
        return v * 2.0 + 1.0

    def train_step(state, llm_arg, jb):
        if len(calls) == 1:
            jax.jit(rejit_probe)(x).block_until_ready()  # a new program, mid-loop
        calls.append(1)
        return real_train(state, llm_arg, jb)

    trainer._steps = (train_step, real_eval)
    try:
        _train(tiny, telemetry)
    finally:
        trainer._steps = (real_train, real_eval)
    spans = telemetry.tracer.spans()
    # jax names the traced function, then the module it lowers and compiles
    ours = {s.name: s for s in spans if "rejit_probe" in s.attrs.get("fun_name", "")}
    # (its trace is a span only if it took a millisecond: MIN_TRACE_SPAN_S)
    assert {"jit.backend_compile", "jit.lower"} <= set(ours)
    assert ours["jit.backend_compile"].attrs["fun_name"] == "jit(rejit_probe)"
    (dispatch,) = [s for s in spans if s.name == "step.dispatch" and s.attrs["step"] == 1]
    for s in ours.values():
        assert s.attrs["step"] == 1 and s.parent_id == dispatch.span_id
        # [now - duration, now] lies inside the dispatch it fell in
        assert dispatch.start_s - 1e-3 <= s.start_s
        assert s.start_s + s.dur_s <= dispatch.start_s + dispatch.dur_s + 1e-3
    compiles = [s for s in spans if s.name == "jit.backend_compile"]
    assert len(compiles) == 1, [s.attrs for s in compiles]
    assert trainer.history[-1]["telemetry"]["compiles"] == 1


def test_tracing_module_imports_no_jax():
    code = ("import sys; import deepdfa_tpu.obs.tracing; from deepdfa_tpu.obs import Tracer; "
            "Tracer().record('x', 0.0, 1.0); assert 'jax' not in sys.modules, 'jax imported'")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          cwd=Path(__file__).resolve().parent.parent, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_a_profiler_session_holds_the_programs_annotations(tiny, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        _train(tiny, _telemetry())
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    lines: dict[str, set] = {}
    steps = []
    for plane in ProfileData.from_file(str(xplane)).planes:
        # one line a thread; their names need not differ, so tell them by place
        for at, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("deepdfa:"):
                    lines.setdefault(ev.name, set()).add((plane.name, at))
                elif ev.name == "train":
                    steps.append(dict(ev.stats).get("step_num"))
    for name in (*LOOP_SPANS, "train.epoch", "eval", "batch.build", "batch.h2d"):
        assert f"deepdfa:{name}" in lines, sorted(lines)
    loop = lines["deepdfa:step.dispatch"]
    assert len(loop) == 1
    assert lines["deepdfa:data.wait"] == lines["deepdfa:loss.sync"] == loop
    assert lines["deepdfa:batch.build"] == lines["deepdfa:batch.h2d"]
    assert lines["deepdfa:batch.build"].isdisjoint(loop)
    assert sorted(steps) == [0, 1, 2]


def test_a_profiler_session_holds_a_full_collection_on_the_thread_it_ran_on(tiny, tmp_path):
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)), _a_collection_in_step(tiny, 1):
        _train(tiny, _telemetry())
        gc.collect(1)  # a young collection is no annotation
    (xplane,) = tmp_path.rglob("*.xplane.pb")
    lines: dict[str, list] = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        for at, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("deepdfa:"):
                    lines.setdefault(ev.name, []).append(
                        ((plane.name, at), ev.start_ns, ev.start_ns + ev.duration_ns))
    (pause,) = lines["deepdfa:gc.pause"]
    calls = sorted(lines["deepdfa:step.dispatch"], key=lambda e: e[1])
    assert len(calls) == 3
    # on the loop's line, on the profiler's clock, inside the call it interrupted
    where, a, b = calls[1]
    assert pause[0] == where and a <= pause[1] <= pause[2] <= b


# -- the interval between completed steps ---------------------------------------

class _Timed:
    """A loss that notes when its read returns, on the clock the telemetry reads."""

    def __init__(self, value, returned):
        self._value, self._returned = value, returned

    def __float__(self) -> float:
        value = float(self._value)
        self._returned.append(time.perf_counter())
        return value


@pytest.mark.parametrize("points,present", [
    (None, [False, True, True]),  # the fixture's eval point is the last step: its lone read ends an interval
    ([1], [False, True, False]),  # an evaluation lies before step 2's read: no interval
    ([], [False, True, True]),
])
def test_interval_s_spans_consecutive_reads_and_nothing_else(tiny, monkeypatch, points, present):
    from deepdfa_tpu.llm import joint

    if points is not None:
        monkeypatch.setattr(joint, "eval_points", lambda *_: set(points))
    trainer = tiny[0]
    real_train, real_eval = trainer._steps
    returned: list[float] = []

    def train_step(state, llm_arg, jb):
        state, loss, probs = real_train(state, llm_arg, jb)
        return state, _Timed(loss, returned), probs

    trainer._steps = (train_step, real_eval)
    telemetry = _telemetry()
    try:
        _train(tiny, telemetry)
    finally:
        trainer._steps = (real_train, real_eval)
    sync = [s for s in telemetry.tracer.spans() if s.name == "loss.sync"]
    assert ["interval_s" in s.attrs for s in sync] == present
    for s in sync:
        assert ("gc_s" in s.attrs) == ("gc_n" in s.attrs) == ("interval_s" in s.attrs)
    intervals = [s.attrs.get("interval_s") for s in sync]
    # each is the time from the read before it to this one; together, where no
    # evaluation lies between, they are the time from the first read to the last
    for k in (1, 2):
        if present[k]:
            assert intervals[k] == pytest.approx(returned[k] - returned[k - 1], abs=2e-4)
    if all(present[1:]):
        assert sum(intervals[1:]) == pytest.approx(returned[-1] - returned[0], abs=4e-4)
    assert all(s.attrs["gc_n"] >= 0 and s.attrs["gc_s"] >= 0 for s in sync if "gc_n" in s.attrs)
    # the epoch's entry and the scrape carry them; the gauge is the last interval
    stats = next(h["telemetry"] for h in trainer.history if "telemetry" in h)
    known = [v for v in intervals if v is not None]
    assert stats["interval_max_ms"] == round(1e3 * max(known), 4)
    assert 0 < stats["interval_p50_ms"] <= stats["interval_max_ms"] and stats["stalls"] >= 0
    assert stats["gc_n"] >= 0 and stats["gc_s"] >= 0
    text = telemetry.render().splitlines()
    assert f"deepdfa_train_last_step_seconds {round(known[-1], 6)}" in text
    assert any(line.startswith("deepdfa_train_gc_collections_total ") for line in text)


# -- one step in flight: the loop launches step k, then reads step k-1's loss --

class _Probe:
    """A loss that notes when the loop reads it — through ``float()``, as the
    benchmark's driver depends on."""

    def __init__(self, value, step, events):
        self._value, self._step, self._events = value, step, events

    def __float__(self) -> float:
        self._events.append(("read", self._step))
        return float(self._value)


def _probed(tiny, telemetry, raise_at=None):
    """One epoch with every call of the step, every loss read and every
    evaluation noted in order. Returns ``(events, state or the exception)``."""
    trainer = tiny[0]
    real_train, real_eval = trainer._steps
    events: list[tuple] = []

    def train_step(state, llm_arg, jb):
        k = sum(1 for e in events if e[0] == "dispatch")
        events.append(("dispatch", k))
        if k == raise_at:
            raise RuntimeError(f"step {k} failed")
        state, loss, probs = real_train(state, llm_arg, jb)
        return state, _Probe(loss, k, events), probs

    def eval_step(*args):
        if events[-1] != ("eval",):  # one mark an evaluation, not one a batch
            events.append(("eval",))
        return real_eval(*args)

    trainer._steps = (train_step, eval_step)
    try:
        return events, _train(tiny, telemetry)
    except RuntimeError as e:
        return events, e
    finally:
        trainer._steps = (real_train, real_eval)


def test_an_epoch_equals_a_loop_that_reads_every_loss_at_once(tiny):
    """Same program, same order of updates, same sum: reading a loss a step
    late changes no number."""
    from deepdfa_tpu.llm.dataset import text_batches

    trainer, examples, state0 = tiny
    state = _train(tiny, _telemetry())
    train_step, _ = trainer._steps
    ref, tr_loss = state0, 0.0
    for tb in text_batches(examples, 4, shuffle=True, seed=trainer.cfg.seed):
        ref, loss, _ = train_step(ref, trainer._llm_arg, trainer._joined(tb))
        tr_loss += float(loss)
    assert _same(state.params, ref.params) and _same(state.opt_state, ref.opt_state)
    assert np.array_equal(jax.random.key_data(state.rng), jax.random.key_data(ref.rng))
    assert int(state.step) == int(ref.step) == 3
    (epoch,) = [h for h in trainer.history if "train_loss" in h]
    assert epoch["train_loss"] == tr_loss / 3
    assert epoch["telemetry"]["steps"] == 3


@pytest.mark.parametrize("points,expected", [
    # the fixture's own eval point, the epoch's last step: its flush is the one lone read
    (None, [("dispatch", 0), ("dispatch", 1), ("read", 0), ("dispatch", 2), ("read", 1),
            ("read", 2), ("eval",)]),
    # mid-epoch: the pending loss is read before the evaluation opens, the next
    # step starts with nothing in flight, and the epoch's end flushes it
    ([1], [("dispatch", 0), ("dispatch", 1), ("read", 0), ("read", 1), ("eval",),
           ("dispatch", 2), ("read", 2)]),
    # none: the end of the epoch flushes
    ([], [("dispatch", 0), ("dispatch", 1), ("read", 0), ("dispatch", 2), ("read", 1),
          ("read", 2)]),
])
def test_step_k_is_launched_before_the_loss_of_step_k_minus_1_is_read(
        tiny, monkeypatch, points, expected):
    from deepdfa_tpu.llm import joint

    if points is not None:
        monkeypatch.setattr(joint, "eval_points", lambda *_: set(points))
    telemetry = _telemetry()
    events, state = _probed(tiny, telemetry)
    assert events == expected and int(state.step) == 3
    # one loss.sync a step, named for the step whose loss it read; `alone`
    # marks the reads with no later step launched: the flushes
    spans = telemetry.tracer.spans()
    sync = [s for s in spans if s.name == "loss.sync"]
    reads = [k for kind, *k in events if kind == "read"]
    assert [[s.attrs["step"]] for s in sync] == reads == [[0], [1], [2]]
    flushes = [e[1] for at, e in enumerate(events)
               if e[0] == "read" and ("dispatch", e[1] + 1) not in events[:at]]
    assert [s.attrs["step"] for s in sync if s.attrs["alone"]] == flushes
    assert {s.attrs["alone"] for s in sync} <= {0, 1} and {s.attrs["reads"] for s in sync} == {1}
    # the spans closed in that order on the loop's thread (the ring keeps the
    # order of closing): a dispatch before the read of the step before it
    kinds = {"step.dispatch": "dispatch", "loss.sync": "read", "eval": "eval"}
    assert [kinds[s.name] for s in spans if s.name in kinds] == [e[0] for e in events]
    (ev,) = [s for s in spans if s.name == "eval"] or [None]
    if ev is not None:
        read = next(s for s in sync if s.attrs["step"] == ev.attrs["step"])
        assert read.start_s + read.dur_s <= ev.start_s + 1e-4
    assert telemetry.epoch_stats()["steps"] == 0  # the epoch's window took all three


@pytest.mark.parametrize("k", [0, 1, 2])
def test_a_step_that_raises_leaves_as_itself_with_the_pending_loss_dropped(tiny, k):
    telemetry = _telemetry()
    events, error = _probed(tiny, telemetry, raise_at=k)
    assert isinstance(error, RuntimeError) and str(error) == f"step {k} failed"
    # the loss of step k-1 was in flight: dropped, never read
    assert [e for e in events if e[0] == "read"] == [("read", s) for s in range(k - 1)]
    assert events[-1] == ("dispatch", k)
    assert not [t for t in threading.enumerate() if t.name == "prefetch_to_device"]
    names = [s.name for s in telemetry.tracer.spans()]
    assert names.count("loss.sync") == max(k - 1, 0) and names.count("train.epoch") == 1
