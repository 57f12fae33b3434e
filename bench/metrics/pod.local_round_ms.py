"""Device time of the ops under the `local_round` named scope
(`dist.steps.make_local_round_step`: forward, backward and the
optimizer), per chip and round of the traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.by_scope("local_round")
    if s <= 0:
        return None
    return 1e3 * s / ctx.chips / ctx.work["rounds"]
