"""Share, in %, of the sum of one attribute (the program's own spans of one
name that ended in the window) that its values over ``factor`` medians have
over the median: of step intervals, the share of the window lost to stalls.
~0 where every value is longer alike: the median is then what differs.
``None`` where no such span carries the attribute."""

from harness import spec


def read(ctx, span: str, attr: str, factor: float):
    found = spec.load_module("readers", "program_ring").spans(ctx, [span])
    d = sorted(s.attrs[attr] for s in found or () if attr in s.attrs)
    if not d or not sum(d):
        return None
    p50 = d[min(len(d) - 1, int(0.5 * len(d)))]
    return 100.0 * sum(v - p50 for v in d if v > factor * p50) / sum(d)
