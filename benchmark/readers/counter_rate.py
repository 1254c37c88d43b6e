"""One of the window's counters over the window's whole wall time: all the
work that completed in the window, per second."""


def read(ctx, counter: str):
    if not ctx.phases.window_s:
        return None
    return ctx.counters[counter] / ctx.phases.window_s
