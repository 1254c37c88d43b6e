"""Host→device prefetch pipeline (data/prefetch.py) — the reference's
DataLoader-worker analogue (datamodule.py:110-129 train_workers)."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from deepdfa_tpu.data.prefetch import prefetch_to_device


def test_yields_all_items_in_order_on_device():
    items = [{"x": np.full((4,), i, np.float32)} for i in range(7)]
    out = list(prefetch_to_device(iter(items), size=2))
    assert len(out) == 7
    for i, o in enumerate(out):
        assert isinstance(o["x"], jnp.ndarray)
        assert float(o["x"][0]) == i


def test_producer_exception_reraised_consumer_side():
    def gen():
        yield {"x": np.zeros(2, np.float32)}
        raise ValueError("oversize graph gid=7")

    it = prefetch_to_device(gen(), size=2)
    next(it)
    with pytest.raises(ValueError, match="gid=7"):
        next(it)


def test_overlaps_host_work_with_consumption():
    """The producer runs AHEAD of the consumer (liveness, not wall-clock —
    timing assertions flake on loaded runners): while the consumer is still
    holding item N, the producer must already have built item N+1."""
    import threading

    produced = []
    consumed_at_produce = []

    def gen(n=6):
        for i in range(n):
            produced.append(i)
            consumed_at_produce.append(len(consumed))
            yield {"x": np.full((2,), i, np.float32)}

    consumed = []
    for item in prefetch_to_device(gen(), size=2):
        time.sleep(0.03)  # consumer (device step) cost
        consumed.append(int(item["x"][0]))

    assert consumed == list(range(6))
    # at least one item was produced while an earlier one was still
    # unconsumed (ran ahead) — impossible in a serial loop
    ahead = [p - c for p, c in zip(produced, consumed_at_produce)]
    assert max(ahead) >= 1, ahead


def test_size_zero_passthrough():
    items = [np.ones(2), np.zeros(2)]
    out = list(prefetch_to_device(iter(items), size=0))
    assert len(out) == 2 and isinstance(out[0], np.ndarray)


def _producer_threads():
    import threading

    return [
        t for t in threading.enumerate()
        if t.name == "prefetch_to_device" and t.is_alive()
    ]


@pytest.mark.parametrize("size", [2, 0])
def test_tracer_gets_the_producers_spans(size):
    """One ``batch.build`` round each pull (the upstream generator runs
    inside it and may set its counts) and one ``batch.h2d`` a staged item,
    under the span that was open where the stream was made; ``on_span`` is
    handed each once it has closed."""
    import threading

    from deepdfa_tpu.obs import Tracer

    tracer = Tracer(proc="t")
    closed = []

    def gen():
        for i in range(3):
            tracer.current_span().attrs["rows"] = i
            yield {"x": np.full((2,), i, np.float32)}

    with tracer.span("train.epoch", root=True) as root:
        stream = prefetch_to_device(
            gen(), size=size, tracer=tracer, on_span=closed.append)
        with tracer.span("data.wait"):  # open on the consumer's thread only
            first = next(stream)
        rest = list(stream)
    assert [float(o["x"][0]) for o in [first, *rest]] == [0.0, 1.0, 2.0]
    spans = tracer.spans()
    builds = [s for s in spans if s.name == "batch.build"]
    assert [s.attrs for s in builds] == [{"rows": 0}, {"rows": 1}, {"rows": 2},
                                         {"exhausted": True}]
    h2d = [s for s in spans if s.name == "batch.h2d"]
    assert len(h2d) == (3 if size else 0)  # pass-through stages nothing
    assert closed == [s for s in spans if s.name.startswith("batch.")]
    here = threading.get_ident() % 1_000_000
    if size:
        assert {s.parent_id for s in builds + h2d} == {root.span_id}
        assert here not in {s.tid for s in builds + h2d}
    else:
        assert {s.tid for s in builds} == {here}


@pytest.mark.faults
def test_abandoned_iterator_joins_producer_thread():
    """Regression: breaking out of the consumer loop used to leave the
    producer thread (and its staged device batches) alive for process
    lifetime — the finally now joins it with a timeout."""
    items = ({"x": np.full((4,), i, np.float32)} for i in range(100))
    it = prefetch_to_device(items, size=2)
    next(it)
    it.close()  # the abandonment path: GeneratorExit through the finally
    assert _producer_threads() == []


@pytest.mark.faults
def test_break_mid_stream_joins_producer_thread():
    for item in prefetch_to_device(
        ({"x": np.zeros(2, np.float32)} for _ in range(50)), size=2
    ):
        break  # consumer walks away; refcount closes the generator
    assert _producer_threads() == []


@pytest.mark.faults
def test_producer_raises_fault_surfaces_and_joins():
    """The prefetch.producer_raises chaos point: the injected error must
    surface at the consumer's next() — never hang — and the thread must be
    joined afterwards."""
    from deepdfa_tpu.resilience import faults

    items = [{"x": np.zeros(2, np.float32)} for _ in range(5)]
    with faults.installed("prefetch.producer_raises@2"):
        it = prefetch_to_device(iter(items), size=2)
        next(it)  # item 1 passes (fault arms on hit 2)
        with pytest.raises(faults.InjectedFault, match="prefetch.producer_raises"):
            list(it)
    assert _producer_threads() == []


def test_batched_graphs_roundtrip_structure():
    """BatchedGraphs (NamedTuple) survives device_put with structure intact
    (the Trainer's steps_for dispatch reads hasattr node_gidx)."""
    from deepdfa_tpu.data.graphs import BucketSpec, GraphBatcher
    from deepdfa_tpu.data.synthetic import random_dataset

    graphs = random_dataset(4, seed=0, input_dim=40)
    b = next(GraphBatcher([BucketSpec(8, 512, 1024)]).batches(graphs))
    (staged,) = list(prefetch_to_device(iter([b]), size=1))
    assert hasattr(staged, "node_gidx")
    assert type(staged).__name__ == "BatchedGraphs"
    np.testing.assert_array_equal(np.asarray(staged.graph_mask),
                                  np.asarray(b.graph_mask))
