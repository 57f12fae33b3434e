"""The whole pod-round step's share of the chip's bf16 peak while the
device is busy: model FLOPs of the forward and backward passes (no
recompute) of the rounds traced, over the device's busy time in the trace
(averaged over the chips used) x chips x peak. It bounds the rooflines of
the kernels inside the step; beside the end-to-end `mfu` it leaves out the
time in which the device idles."""


def read(ctx):
    if ctx.trace is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * ctx.work["flops"] / (
        ctx.trace.busy_s * ctx.chips * ctx.peaks["bf16_flops"])
