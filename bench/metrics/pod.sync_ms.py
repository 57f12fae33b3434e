"""Device time of the ops under the `pod_sync.*` named scopes
(`dist.collectives`: threshold solve and pack, exchange, scatter-apply),
per chip and round of the traced window."""

SCOPES = ("pod_sync.compact_pack", "pod_sync.all_gather",
          "pod_sync.scatter_apply", "pod_sync.dense")


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
