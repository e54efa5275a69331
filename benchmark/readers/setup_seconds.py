"""Process start to the first timed op: imports, device start-up, the native
library's build on a checkout's first run, data from the seed, compilation
and the warm-up op."""


def read(ctx):
    return ctx.setup_s
