"""Of the traced span's device-busy seconds, the share in the listed ops
(`breakdown.device_ops`: the span's ten longest) that move the optimizer's
float32 slot tables, the part of the linear step that only a slot-carrying
rule has. With n float32 slot tables beside narrower weights (the 4-byte
entries of `work_model.table_bytes`, whose first entry is the weights'):

- the in-place writes: the listed ops whose result is `f32[num_features]`;
- the gathers: the listed ops classed `gather` whose result is a float32
  array of whole blocks of lanes (a multiple of `mini_batch`, shorter than
  the table).

A listed op's key carries its result's type and length and `classify`'s
class, not the program's scope (the profiler's events on a v5e have no
`op_name`: PERF.md section 3), so that is what an op is told by. The share is
given only where exactly n writes and n gathers are listed: a write that was
fused away, renamed or fell under the ten longest would otherwise read as a
smaller share, which is this metric's better direction. Nothing is returned
then, nor where the weights are float32 themselves (their ops would read as
a slot's)."""

import re

_RESULT = re.compile(r" ([a-z]+\d*)\[(\d+)\]")


def read(ctx):
    t = ctx.trace
    cfg = ctx.cell.config
    widths = cfg.get("work_model", {}).get("table_bytes")
    if not t or t.get("busy_s", 0) <= 0 or not isinstance(widths, list) \
            or int(widths[0]) == 4:
        return None
    slots = [int(b) for b in widths[1:]].count(4)
    dims, block = int(cfg["num_features"]), int(cfg["mini_batch"])
    writes, gathers = [], []
    for key, s in t.get("device_ops", ()):
        m = _RESULT.search(key)
        if not m or m.group(1) != "f32":
            continue
        n = int(m.group(2))
        if n == dims:
            writes.append(s)
        elif key.endswith("[gather]") and n < dims and n % block == 0:
            gathers.append(s)
    if not slots or len(writes) != slots or len(gathers) != slots:
        return None
    return 100.0 * (sum(writes) + sum(gathers)) / t["busy_s"]
