"""1 - real / padded of two of the window's counters, in %: the share of a
padded axis that carries no real entry. Exact on any backend."""


def read(ctx, real: str, padded: str):
    c = ctx.counters
    if not c.get(padded):
        return None
    return 100.0 * (1.0 - c[real] / c[padded])
