"""Device-busy time in the traced span over the mini-batch steps in it."""


def read(ctx):
    t = ctx.trace
    steps = ctx.result.get("steps")
    if not t or not steps:
        return None
    return 1e3 * t["busy_s"] / steps
