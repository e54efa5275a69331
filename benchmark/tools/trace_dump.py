#!/usr/bin/env python3
"""Look at one trace by hand: run a cell's set-up and a few traced ops on the
chip and write what the profiler recorded as plain JSON.

    python3 benchmark/tools/trace_dump.py --workload <cell> --seed 1 \
        --calls 1 --out chiprun_out/look.json [--dims N --rows N]

The output holds, for every plane and line, the event count, the first
events with all their stats, the line's top event names by summed time, and
under "loaded" what `benchmark.xplane.load` makes of the trace (with
`--dims/--rows` small, that is a recorded trace small enough for a test).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import devmem, manifest, run, xplane  # noqa: E402


def describe(path: str, head: int = 12, top: int = 25) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            total: dict = {}
            for ev in events:
                total[ev.name] = total.get(ev.name, 0.0) + float(ev.duration_ns)
            lines.append({
                "line": line.name, "events": len(events),
                "first": [{"name": e.name, "start_ns": float(e.start_ns),
                           "dur_ns": float(e.duration_ns),
                           "stats": {k: str(v)[:300] for k, v in e.stats}}
                          for e in events[:head]],
                "top_ns": sorted(total.items(), key=lambda kv: -kv[1])[:top]})
        planes.append({"plane": plane.name, "lines": lines})
    return planes


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--calls", type=int, default=1)
    p.add_argument("--out", required=True)
    p.add_argument("--dims", type=int)
    p.add_argument("--rows", type=int)
    args = p.parse_args()
    cell = manifest.resolve(args.workload)
    if args.dims:
        cell.config["options"] = cell.config["options"].replace(
            str(cell.config["num_features"]), str(args.dims))
        cell.config["num_features"] = args.dims
    if args.rows:
        cell.traffic["rows_per_call"] = args.rows
    run.require_chips(cell, run.device_info())
    run.enable_compile_cache()
    op = run.make_op(cell, args.seed)
    op.setup()
    result, loaded, seen = run.traced_window(
        op, None, args.calls,
        inspect=lambda path: (os.path.getsize(path), describe(path)))
    out = {"xplane_bytes": seen[0],
           "result": {k: v for k, v in result.items() if k != "calls"},
           "calls": result["calls"],
           "memory": devmem.figures(),
           "reduced": xplane.reduce(loaded), "planes": seen[1],
           "loaded": loaded}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f)
    print(json.dumps({"wrote": args.out, "reduced": out["reduced"],
                      "memory": out["memory"], "result": out["result"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
