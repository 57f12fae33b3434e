"""`kernels.compact_topk.compact_blocks` against its roofline, over the
traced window.

Each call packs one chip's accumulator of nb = n_blocks blocks of blk
float32 entries into `budget` slots a block. What the algorithm needs per
call (not the one-hot matmuls of the implementation, so that another
implementation is measured against the same work):
  bytes  4 nb blk          the accumulator read
       + 4 nb blk          the residual written
       + 8 nb budget       values and indices written
       + 4 nb              the kept-count headers written
  ops    2 nb blk          one compare and one prefix count per entry
The least time is max(bytes / HBM peak, ops / bf16 peak); the share is
the calls' least time over their measured device time."""

NAMES = ("compact_blocks", "_compact_kernel")


def counts(nb: int, blk: int, budget: int) -> tuple[float, float]:
    return 8.0 * nb * blk + 8.0 * nb * budget + 4.0 * nb, 2.0 * nb * blk


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = ctx.trace.kernel_calls(NAMES)
    if not calls:
        return None
    run = ctx.run
    budget = max(1, min(run.blk, int(round(float(ctx.mix["rate"])
                                           * run.blk))))
    nbytes, ops = counts(run.n_blocks, run.blk, budget)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                ops / ctx.peaks["bf16_flops"])
    return 100.0 * least * len(calls) / sum(e - s for s, e in calls)
