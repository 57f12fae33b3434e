"""Model FLOPs of the forward and backward passes (no recompute) for the
samples or tokens of the window, over window x chips x the bf16 peak, in
percent."""


def read(ctx):
    return 100.0 * ctx.work["flops"] / (
        ctx.work["elapsed_s"] * ctx.chips * ctx.peaks["bf16_flops"])
