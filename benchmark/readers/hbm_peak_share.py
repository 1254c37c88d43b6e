"""Peak bytes in use on the fullest chip over the chip's memory, in %."""

from harness import peaks


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    if ctx.device["platform"] != "tpu" or not peak:
        return None
    return 100.0 * peak / peaks.of(ctx.device["kind"])["hbm_bytes"]
