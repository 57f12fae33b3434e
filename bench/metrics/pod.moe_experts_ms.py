"""Device time of the ops under the `moe.experts` and `moe.shared` named
scopes (`models.moe.share_apply`: the held experts' grouped-matmul kernels
and relu², the shared expert), forward, recompute and backward, per chip
and round of the traced window. A part of the local round."""

LAYER_SCOPES = ("moe.experts", "moe.shared")


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in LAYER_SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
