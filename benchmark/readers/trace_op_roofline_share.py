"""One kernel's share of its roofline, in %, from the profiler's trace: the
least time the chip could take for what the window's steps asked of the
kernel — the larger of its operations over the peak FLOP/s and its bytes over
the peak bytes/s, by the configuration's ``flops`` module — over the time the
kernel took in those steps.

The kernel's time over the window is its share of the device's busy time in
the traced slice (the summed device durations of the ``device_ops`` entries
whose name holds ``op``, over the slice's ``busy_s``) times the window's wall
time: a traced slice of 2 s holds too few of a slow cell's steps to be divided
by, and its tail is the harness's epilogue, idle (a fifth of the Brumby cell's
slice), which a share of ``window_s`` would count as no kernel. Where the loop
waits on the device, as in the frozen-decoder cells, busy time is step time;
where the host holds the device back, this overstates the kernel's time and
understates the share. ``None`` off the TPU,
without a trace, or where the op is not among the trace's kept ops (a program
without the kernel)."""

from harness import peaks, spec


def read(ctx, op: str, ops: str, bytes: str, peak: str = "bf16_flops_per_s",
         bandwidth: str = "hbm_bytes_per_s"):
    t = ctx.trace
    if ctx.device["platform"] != "tpu" or not t or not t["busy_s"]:
        return None
    kernel_s = sum(s for name, s in t["device_ops"] if op in name)
    p = ctx.phases
    if not kernel_s or not p.window_s or not p.window_steps:
        return None
    flops = spec.load_module("flops", ctx.config["flops"])
    chip = peaks.of(ctx.device["kind"])
    least = max(getattr(flops, ops)(ctx.config, ctx.counters) / chip[peak],
                getattr(flops, bytes)(ctx.config, ctx.counters) / chip[bandwidth])
    # the kernel's seconds over the window: its share of the busy slice times the window's time
    took = kernel_s / t["busy_s"] * p.window_s
    return 100.0 * least / took
