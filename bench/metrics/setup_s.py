"""Process start to the first timed unit of work: imports, data, weights,
compilation and the checked first steps."""


def read(ctx):
    return ctx.setup_s
