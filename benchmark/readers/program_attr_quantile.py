"""``scale`` x a quantile (nearest rank; ``q`` 1.0: the largest) of one
attribute the program set on its own spans of one name that ended in the
window. ``None`` where no such span carries it: a program that does not set it
(the parent of the PR that added it)."""

from harness import spec


def read(ctx, span: str, attr: str, q: float, scale: float = 1.0):
    found = spec.load_module("readers", "program_ring").spans(ctx, [span])
    d = sorted(s.attrs[attr] for s in found or () if attr in s.attrs)
    if not d:
        return None
    return scale * d[min(len(d) - 1, int(q * len(d)))]
