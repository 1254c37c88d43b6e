"""A quantile (nearest rank; ``q`` 1.0: the longest) of the durations of the
program's own spans of one name that ended in the window, in ms. Where there
was none: ``None``, unless ``recorded_by`` = ``[span, attr]`` names an
attribute that only a program which records such spans sets, and a span of the
window carries it: then 0, the program looked and had none to record. (A
program that does not record them — the parent of the PR that added them —
still reads ``None``.)"""

from harness import spec


def read(ctx, span: str, q: float, recorded_by: list = ()):
    ring = spec.load_module("readers", "program_ring")
    d = sorted(s.dur_s for s in ring.spans(ctx, [span]) or ())
    if d:
        return 1e3 * d[min(len(d) - 1, int(q * len(d)))]
    if recorded_by:
        name, attr = recorded_by
        if any(attr in s.attrs for s in ring.spans(ctx, [name]) or ()):
            return 0.0
    return None
