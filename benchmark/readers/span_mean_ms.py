"""Mean duration of the window's spans of one name, in ms."""


def read(ctx, span: str):
    d = ctx.phases.durations(span)
    return 1e3 * sum(d) / len(d) if d else None
