"""Share, in %, of the program's own spans of the given names (those that
ended in the window) that their thread spent running: the sum of the spans'
``cpu_s`` (the thread's CPU clock beside the wall clock) over the sum of their
durations. The rest the thread was blocked: in the runtime, on a lock, waiting
for the interpreter. ``None`` on a program whose spans carry no ``cpu_s`` (the
parent of the PR that added it)."""

from harness import spec


def read(ctx, spans: list):
    found = spec.load_module("readers", "program_ring").spans(ctx, spans)
    found = [s for s in found or () if getattr(s, "cpu_s", None) is not None]
    wall = sum(s.dur_s for s in found)
    if not wall:
        return None
    return 100.0 * sum(s.cpu_s for s in found) / wall
