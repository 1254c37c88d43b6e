"""``scale * product(sums of num attributes) / product(sums of den
attributes)`` over the program's own spans of one name that ended in the
window: a count (no ``den``), a mean or a ratio of means of what the program
set on its spans. ``None`` where no such span carries the first attribute: a
program that does not set it (the parent of the PR that added it)."""

from harness import spec


def read(ctx, span: str, num: list, den: list = (), scale: float = 1.0):
    found = spec.load_module("readers", "program_ring").spans(ctx, [span])
    found = [s for s in found or () if num[0] in s.attrs]
    if not found:
        return None
    total = lambda name: sum(s.attrs.get(name, 0) for s in found)
    value = scale
    for name in num:
        value *= total(name)
    for name in den:
        if not total(name):
            return None
        value /= total(name)
    return value
