"""Device-idle time inside ops (between a `bench:call` mark's start and end:
staging, packing, dispatch, loss fetches, emission), per mini-batch step."""


def read(ctx):
    t = ctx.trace
    steps = ctx.result.get("steps")
    if not t or not steps:
        return None
    return 1e3 * t["idle_in_calls_s"] / steps
