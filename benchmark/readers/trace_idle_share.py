"""1 - busy / window of the traced slice, in %, from the profiler's trace."""


def read(ctx):
    t = ctx.trace
    if ctx.device["platform"] != "tpu" or not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
