"""Device time of the ops under the `topk.threshold` named scope
(`kernels.ops.solve_threshold`: the max, both `magnitude_hist` passes and
the bracket math), per chip and round of the traced window. A part of
`pod.sync_ms`."""

SCOPES = ("topk.threshold",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
