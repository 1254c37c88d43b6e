"""Seconds of one phase of the run (``setup``: before the window) covered by
the program's own spans of the given names that ended in it. Overlapping spans
count once: jax's trace event of a function covers those of the functions it
calls."""

from harness import spec


def read(ctx, spans: list, phase: str):
    found = spec.load_module("readers", "program_ring").spans(ctx, spans, phase)
    if found is None:
        return None
    covered, reached = 0.0, float("-inf")
    for a, b in sorted((s.start_s, s.start_s + s.dur_s) for s in found):
        if b > reached:
            covered += b - max(a, reached)
            reached = b
    return covered
