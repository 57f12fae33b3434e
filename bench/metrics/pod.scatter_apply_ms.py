"""Device time of the ops under the `pod_sync.scatter_apply` named scope
(the compact sync's apply of the gathered payload to the parameters), per
chip and round of the traced window. A part of `pod.sync_ms`."""

SCOPES = ("pod_sync.scatter_apply",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
