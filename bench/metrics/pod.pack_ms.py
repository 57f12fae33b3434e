"""Device time of the ops under the `topk.pack` named scope (the
`compact_blocks` call of `kernels.ops.compact_shard_topk`), per chip and
round of the traced window. A part of `pod.sync_ms`."""

SCOPES = ("topk.pack",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
