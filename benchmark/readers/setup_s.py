"""Process start to the entry of the first timed step, in s."""


def read(ctx):
    return ctx.phases.setup_s
