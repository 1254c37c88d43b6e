"""Host→device prefetch for training streams.

The reference feeds its trainer through DGL ``GraphDataLoader`` worker
processes (``linevd/datamodule.py:110-129``, ``train_workers`` — host-side
collation overlapped with GPU compute). The JAX-native equivalent is a
background thread that builds the next batches and stages them on device
(``jax.device_put``) while the current step runs: device dispatch is async,
so the only way the host stalls the chip is by not having the NEXT batch
ready — exactly what this removes. The loop must also not wait for a step
(read its loss) before launching the next: ``JointTrainer.train`` keeps one
step in flight, else the staged batch sits through the whole launch.

Usage::

    for batch in prefetch_to_device(batch_iter, size=2):
        state, metrics, loss, _ = trainer.train_step(state, batch, metrics)

Exceptions raised by the producer (e.g. an oversize graph rejected by the
batcher mid-stream) are re-raised in the consumer at the point of ``next()``
— never swallowed in the thread.

With a ``tracer`` (:class:`deepdfa_tpu.obs.Tracer`) the producer records, on
its own thread, one ``batch.build`` span round each pull from the upstream
iterator (whatever builds the batch runs inside it and may set its counts on
``tracer.current_span().attrs``; the pull that finds the iterator exhausted
is marked ``exhausted``) and one ``batch.h2d`` round each ``device_put``,
both under the span that was open where the stream was made. ``on_span`` is
called on that thread with each of them once it has closed
(``TrainTelemetry.observe_producer`` keeps the lifetime totals).
"""

from __future__ import annotations

import contextlib
import queue
import threading
from typing import Any, Iterable, Iterator

from deepdfa_tpu.obs.tracing import no_span
from deepdfa_tpu.resilience import faults

__all__ = ["prefetch_to_device"]

_SENTINEL = object()


class _ProducerError:
    def __init__(self, exc: BaseException):
        self.exc = exc


def prefetch_to_device(
    iterator: Iterable[Any], size: int = 2, device=None, tracer=None,
    on_span=None,
) -> Iterator[Any]:
    """Yield items from ``iterator`` staged on device ``size`` items ahead.

    ``size`` bounds host memory (at most ``size`` staged batches + one being
    built). ``device=None`` uses JAX's default placement; pass a
    ``jax.Device`` (or ``NamedSharding``) to pin. ``size <= 0`` disables
    prefetching and yields pass-through (useful to A/B the overlap).
    """
    if tracer is None:
        return _prefetch(iterator, size, device, no_span)
    # the producer's thread has no open span: hang its under this one's
    parent = tracer.current()

    @contextlib.contextmanager
    def span(name):
        with tracer.span(name, parent=parent) as sp:
            yield sp
        if on_span is not None:
            on_span(sp)

    return _prefetch(iterator, size, device, span)


def _pulls(iterator: Iterable[Any], span) -> Iterator[Any]:
    """``iterator``'s items, each pulled inside a ``batch.build`` span."""
    it = iter(iterator)
    while True:
        with span("batch.build") as sp:
            try:
                item = next(it)
            except StopIteration:
                if sp is not None:
                    sp.attrs["exhausted"] = True
                return
        yield item


def _prefetch(iterator, size, device, span) -> Iterator[Any]:
    import jax

    if size <= 0:
        yield from _pulls(iterator, span)
        return

    q: queue.Queue = queue.Queue(maxsize=size)
    stop = threading.Event()

    def _put(item) -> bool:
        """Bounded put that respects ``stop`` — EVERY producer put goes
        through here (a blocking put of the sentinel/error with a full queue
        and a gone consumer would leak the thread and its staged batches
        for process lifetime)."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def produce():
        try:
            for item in _pulls(iterator, span):
                # chaos point: a batcher blowing up mid-stream inside the
                # thread (must surface at the consumer's next(), never hang)
                faults.raise_if("prefetch.producer_raises")
                with span("batch.h2d"):
                    staged = (
                        jax.device_put(item, device)
                        if device is not None
                        else jax.device_put(item)
                    )
                if not _put(staged):
                    return
        except BaseException as e:  # re-raised consumer-side
            _put(_ProducerError(e))
            return
        _put(_SENTINEL)

    t = threading.Thread(target=produce, daemon=True, name="prefetch_to_device")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _SENTINEL:
                return
            if isinstance(item, _ProducerError):
                raise item.exc
            yield item
    finally:
        # Thread-leak fix: stop.set() alone only *asks* the producer to
        # exit — an early-exiting consumer (break / exception / abandoned
        # iterator) used to leave the thread and its staged device batches
        # alive until interpreter exit. The producer's _put loop polls
        # ``stop`` every 0.1 s, so this join completes promptly; the
        # timeout is a backstop against a producer wedged inside
        # device_put, and a still-alive thread after it is a bug worth
        # surfacing loudly.
        stop.set()
        t.join(timeout=5.0)
        if t.is_alive():  # pragma: no cover — requires a wedged device_put
            import warnings

            warnings.warn(
                "prefetch_to_device producer thread failed to exit within 5s",
                RuntimeWarning,
                stacklevel=2,
            )
