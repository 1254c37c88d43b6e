"""A quantile of the window's spans of one name, in ms (nearest rank)."""


def read(ctx, span: str, q: float):
    d = sorted(ctx.phases.durations(span))
    if not d:
        return None
    return 1e3 * d[min(len(d) - 1, int(q * len(d)))]
