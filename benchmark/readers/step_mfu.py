"""The whole step's share of the chip's peak, in %: the FLOPs the window's
steps needed (``benchmark/flops/<name>.py``, by the configuration's ``flops``
name) over the window's wall time and the peak of the device kind. Device
metric: nothing is returned off the TPU."""

from harness import peaks, spec


def read(ctx, peak: str):
    if ctx.device["platform"] != "tpu" or not ctx.phases.window_s:
        return None
    needed = spec.load_module("flops", ctx.config["flops"]).count(ctx.config, ctx.counters)
    rate = needed / ctx.phases.window_s / ctx.device["count"]
    return 100.0 * rate / peaks.of(ctx.device["kind"])[peak]
