"""`kernels.magnitude_hist` against its roofline, over the traced window.

Each call histograms one chip's EF accumulator, n = n_blocks * blk float32
entries (the pod path calls it twice a round: the coarse and the fine
pass). What the algorithm needs per call, whatever the number of edges:
  bytes  4 n    every entry read once (edges and counts are negligible)
  ops    2 n    |x| and one bucket increment per entry
The least time is max(bytes / HBM peak, ops / bf16 peak); the share is
the calls' least time over their measured device time."""

NAMES = ("magnitude_hist", "_hist_kernel")


def counts(n: int) -> tuple[float, float]:
    return 4.0 * n, 2.0 * n


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = ctx.trace.kernel_calls(NAMES)
    if not calls:
        return None
    n = ctx.run.n_blocks * ctx.run.blk
    nbytes, ops = counts(n)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                ops / ctx.peaks["bf16_flops"])
    return 100.0 * least * len(calls) / sum(e - s for s, e in calls)
