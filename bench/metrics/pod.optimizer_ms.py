"""Device time of the ops under the `opt.update` named scope (the
optimizer step of each local step in `dist.steps.make_local_round_step`),
per chip and round of the traced window. A part of
`pod.local_round_ms`."""

SCOPES = ("opt.update",)


def read(ctx):
    if ctx.trace is None:
        return None
    s = sum(ctx.trace.by_scope(name) for name in SCOPES)
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
