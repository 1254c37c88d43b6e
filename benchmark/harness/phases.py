"""Phases, spans and counters of one run.

A driver calls :meth:`Phases.step_begin` on entry to every call of the timed
entry's step, wraps the call in :meth:`span`, and calls :meth:`wait_begin`
once the loop has what it waits for. The phases are, in order:

``setup``   the checked steps and the warm steps; everything before counts as
            ``setup_s``
``window``  ``seconds`` of wall time from the entry to the first timed step to
            the entry to the first step at or past the deadline; every step
            counted in it has completed, because the loop reads each loss
``traced``  (``--trace 1`` only) ``trace_seconds`` more under the profiler

The step after the last phase never runs: ``step_begin`` raises :class:`Stop`
at its boundary.
"""

from __future__ import annotations

import contextlib
import time


class Stop(Exception):
    """Raised at a step boundary to end the entry's loop from outside."""


class Phases:
    def __init__(self, process_start: float, setup_steps: int, seconds: float,
                 trace_seconds: float = 0.0, profiler=None):
        self.process_start = process_start
        self.setup_steps = setup_steps
        self.seconds = seconds
        self.trace_seconds = trace_seconds
        self.profiler = profiler  # object with start(); the harness stops it
        self.phase = "setup"
        self.calls = 0
        self.spans: list[tuple[str, str, float, float]] = []  # (phase, name, t0, t1)
        self.setup_s: float | None = None
        self.window_t0 = self.window_s = None
        self.window_steps = 0
        self.traced_t0 = self.traced_s = None
        self.traced_steps = 0
        self._wait_t0: float | None = None
        self._wait_ann = None

    # -- spans ------------------------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        ann = self._annotate(name)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((self.phase, name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def _annotate(self, name: str):
        """The same span on the profiler's clock, while it is tracing."""
        if self.phase != "traced":
            return None
        import jax

        ann = jax.profiler.TraceAnnotation(f"bench:{name}")
        ann.__enter__()
        return ann

    def wait_begin(self) -> None:
        """The loop now waits for its next batch (until ``step_begin``)."""
        self._wait_ann = self._annotate("data.wait")
        self._wait_t0 = time.perf_counter()

    def _wait_end(self, now: float) -> None:
        if self._wait_t0 is not None:
            self.spans.append((self.phase, "data.wait", self._wait_t0, now))
            self._wait_t0 = None
        if self._wait_ann is not None:
            self._wait_ann.__exit__(None, None, None)
            self._wait_ann = None

    # -- phases -----------------------------------------------------------
    def step_begin(self) -> int:
        """Index of the call that is about to run; raises :class:`Stop` when
        the last phase is over."""
        now = time.perf_counter()
        self._wait_end(now)
        n = self.calls
        if self.phase == "setup" and n >= self.setup_steps:
            self.phase = "window"
            self.window_t0 = now
            self.setup_s = time.time() - self.process_start
        if self.phase == "window" and now - self.window_t0 >= self.seconds:
            self.window_s = now - self.window_t0
            self.window_steps = n - self.setup_steps
            if not self.trace_seconds:
                raise Stop
            self.profiler.start()
            self.phase = "traced"
            self.traced_t0 = now = time.perf_counter()
        if self.phase == "traced" and now - self.traced_t0 >= self.trace_seconds:
            self.traced_s = now - self.traced_t0
            self.traced_steps = n - self.setup_steps - self.window_steps
            raise Stop
        self.calls += 1
        return n

    # -- reading ----------------------------------------------------------
    def durations(self, name: str, phase: str = "window") -> list[float]:
        return [t1 - t0 for p, nm, t0, t1 in self.spans if p == phase and nm == name]
