"""The program's own spans, selected by time. Not a metric's reader: the
``program_*`` readers share it.

``deepdfa_tpu.obs.train_telemetry()`` is the process-wide ring that
``JointTrainer.train`` and its prefetch producer record into, with no hook of
the benchmark's in between. A span's start is ``time.time()``, as
``Phases.process_start`` is, so the run's phases are intervals on its clock:
set-up = ``(process_start, process_start + setup_s]``, the window = the
``window_s`` after it. A span belongs to the phase it *ended* in, as
``Phases.span`` files the benchmark's own spans: the first timed step's
``step.dispatch`` opens a moment before the window does and counts, the one
that finds the deadline does not.
"""


def spans(ctx, names, phase: str = "window"):
    """The ring's spans called one of ``names`` that ended in ``phase``,
    oldest first. ``None`` where there is nothing to read: a program without
    the accessor (the parent of the PR that added it), a ring nothing was
    recorded into, a run without a window. Raises if the ring has dropped
    spans of the interval asked for."""
    from deepdfa_tpu import obs

    accessor = getattr(obs, "train_telemetry", None)
    p = ctx.phases
    if accessor is None or not p.window_s:
        return None
    tracer = accessor().tracer
    ring = tracer.spans()
    if not ring:
        return None
    t0 = p.process_start + p.setup_s
    lo, hi = {"setup": (p.process_start, t0), "window": (t0, t0 + p.window_s)}[phase]
    oldest = ring[0].start_s + ring[0].dur_s
    if len(ring) >= tracer.capacity and oldest > lo:
        raise RuntimeError(
            f"the program's span ring ({tracer.capacity} spans) overflowed: its oldest span "
            f"ended {oldest - lo:.3f} s after the {phase} phase began, so spans of that phase "
            "are lost (RING_SPANS in deepdfa_tpu/obs/telemetry.py)")
    return [s for s in ring if s.name in names and lo < s.start_s + s.dur_s <= hi]
