"""Share of the window spent inside spans of one name, in %."""


def read(ctx, span: str):
    if not ctx.phases.window_s:
        return None
    return 100.0 * sum(ctx.phases.durations(span)) / ctx.phases.window_s
