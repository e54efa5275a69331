"""The bytes the steps' gathers and scatters must move (work.step_work, from
shapes and table dtypes) over the chip's peak bytes/s, divided by the summed
duration of the gather and scatter ops in the traced span. Bandwidth bounds
it. Returns nothing where the trace names no gather or scatter op."""

from benchmark import work


def read(ctx):
    t = ctx.trace
    steps = ctx.result.get("steps")
    if not t or not steps or ctx.peaks is None:
        return None
    gs_s = t["class_s"]["gather"] + t["class_s"]["scatter"]
    required = work.step_work(ctx.cell.config)
    if gs_s <= 0 or required is None:
        return None
    least = required["gather_scatter_bytes"] * steps / ctx.peaks["bytes_per_s"]
    return 100.0 * least / gs_s
