"""Work units finished in the window over all the wall time they took: from
the first op's start to the last op's end, ops issued back to back. No
median of ops or steps, so a stall anywhere in the window moves it."""


def read(ctx):
    r = ctx.result
    if not r["units"] or r["wall_s"] <= 0:
        return None
    return r["units"] / r["wall_s"]
