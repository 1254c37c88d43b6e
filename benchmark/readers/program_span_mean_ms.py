"""Mean duration of the program's own spans of one name that ended in the
window, in ms."""

from harness import spec


def read(ctx, span: str):
    found = spec.load_module("readers", "program_ring").spans(ctx, [span])
    if not found:
        return None
    return 1e3 * sum(s.dur_s for s in found) / len(found)
