"""Window time over pod rounds completed (each ends in
block_until_ready)."""


def read(ctx):
    return 1e3 * ctx.work["elapsed_s"] / ctx.work["rounds"]
