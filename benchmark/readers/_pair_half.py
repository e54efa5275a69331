"""One half (`gather` or `scatter`) of `scatter_gather_roofline`, for a work
model that splits its bytes (`<half>_bytes`): the required bytes of the
steps' ops of that half over the chip's peak bytes/s, divided by those ops'
summed duration in the traced span. Bandwidth bounds it. Returns nothing
where the work model does not split its bytes or the trace names no such op.

Which ops make a half. `ctx.trace["class_s"]` is `xplane.classify`'s, which
reads a fusion's operand order and so classes an in-place write that takes
no float operand after its indices (`train_ffm`'s flag scatter, a constant
set at the block's keys into `s8[v_dims]`) as a gather. A gather's result is
as long as the block and a write's is as long as the table, so the listed
ops (`device_ops`: the span's ten longest) that are classed `gather` and
whose result is as long as one of the configuration's tables
(`num_features`, `v_dims`) are counted with the scatters, where the work
model counts their bytes. An op under the ten longest stays where
`classify` put it: at most the tenth entry's seconds an op. Emission's row
gathers run in the traced span too and stay in the gather half's divisor
with no bytes in the work model, as in `scatter_gather_roofline` (PERF.md
section 3 gives both in seconds for the FFM cell)."""

import re

from benchmark import work

_RESULT_ROWS = re.compile(r" [a-z]+\d*\[(\d+)[,\]]")
_TABLES = ("num_features", "v_dims")


def written_in_place_s(ctx) -> float:
    """Seconds of the listed ops classed `gather` whose result is as long as
    a table of the configuration: writes in place, whatever their operands."""
    cfg = ctx.cell.config
    tables = {int(cfg[k]) for k in _TABLES if k in cfg}
    moved = 0.0
    for key, seconds in ctx.trace.get("device_ops", ()):
        rows = _RESULT_ROWS.search(key)
        if key.endswith("[gather]") and rows and int(rows.group(1)) in tables:
            moved += seconds
    return moved


def read(ctx, half: str):
    t = ctx.trace
    steps = ctx.result.get("steps")
    if not t or not steps or ctx.peaks is None:
        return None
    required = work.step_work(ctx.cell.config)
    if required is None or half + "_bytes" not in required:
        return None
    moved = written_in_place_s(ctx)
    seconds = t["class_s"].get(half, 0.0) + (moved if half == "scatter"
                                             else -moved)
    if seconds <= 0:
        return None
    least = required[half + "_bytes"] * steps / ctx.peaks["bytes_per_s"]
    return 100.0 * least / seconds
