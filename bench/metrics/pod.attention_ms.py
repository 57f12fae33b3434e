"""Device time of the ops under the `attn` named scope
(`models.transformer._attn_full`: the q, k, v and o projections and flash
attention), forward and backward, per chip and round of the traced window.
A part of the local round."""

LAYER_SCOPES = ("attn",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in LAYER_SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
