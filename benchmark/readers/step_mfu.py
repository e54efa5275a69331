"""The least time the chip could take for the steps' required work (the
larger of FLOPs over peak FLOP/s and bytes over peak bytes/s: bandwidth, for
every learner here) over the wall time of the traced span: the whole step's
share of the chip's peak, emission and staging included in the time."""

from benchmark import work


def read(ctx):
    t = ctx.trace
    steps = ctx.result.get("steps")
    if not t or not steps or ctx.peaks is None or t["window_s"] <= 0:
        return None
    required = work.step_work(ctx.cell.config)
    if required is None:
        return None
    return 100.0 * work.least_seconds(required, ctx.peaks) * steps / t["window_s"]
