"""Device time of the ops under the `moe.route` and `moe.dispatch` named
scopes (`models.moe.share_apply`: the router's float32 linear, sigmoid,
top-k and normalisation; the sort of the token slots to the held experts,
their gather and the weighted combine back), forward and backward, per
chip and round of the traced window. A part of the local round."""

LAYER_SCOPES = ("moe.route", "moe.dispatch")


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in LAYER_SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
