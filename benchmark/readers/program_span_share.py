"""Share of the window spent inside the program's own spans of the given
names (summed: name spans of one thread that do not overlap), in %."""

from harness import spec


def read(ctx, spans: list):
    found = spec.load_module("readers", "program_ring").spans(ctx, spans)
    if found is None:
        return None
    return 100.0 * sum(s.dur_s for s in found) / ctx.phases.window_s
