#!/usr/bin/env python3
"""Run the benchmark's command many times, each run a new process, and
summarise: the full sets that bounds are set from, and the rehearsal of what
a fresh checkout lacks. The parent never touches JAX (one process per chip).

    python3 benchmark/tools/repeat.py --cells a,b --seeds 1,2,3,4,5,6 --sets 2 \
        --seconds 40 --traces 0 --out chiprun_out/sets.jsonl [--cwd DIR]

Runs go set by set, within a set cell by cell in the order given, within a
cell seed by seed, and for each seed every value of --traces. The same seeds
are used in every set. With --fresh-seeds every run gets a seed of its own
(base + run index) instead. A spread is (Q3 - Q1) / median with
`statistics.quantiles(values, n=4)`.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--cwd", default=".")
    p.add_argument("--cells", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--fresh-seeds", type=int)
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--traces", default="0")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--out", required=True)
    args = p.parse_args()
    man = json.load(open(os.path.join(args.cwd, "BENCHMARK.json")))
    cells = args.cells.split(",")
    traces = [int(t) for t in args.traces.split(",")]
    seeds = [int(s) for s in args.seeds.split(",") if s] or [None]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    records, n = [], 0
    with open(args.out, "w", encoding="utf-8") as out:
        for s in range(args.sets):
            for cell in cells:
                for seed in seeds:
                    for trace in traces:
                        if args.fresh_seeds is not None:
                            seed = args.fresh_seeds + n
                        n += 1
                        cmd = man["command"] + [
                            "--workload", cell, "--seed", str(seed), "--seconds",
                            str(args.seconds), "--trace", str(trace)]
                        t = time.perf_counter()
                        r = subprocess.run(cmd, cwd=args.cwd, text=True,
                                           capture_output=True)
                        wall = time.perf_counter() - t
                        last = r.stdout.strip().splitlines()[-1:] or [""]
                        try:
                            line = json.loads(last[0])
                        except ValueError:
                            line = None
                        rec = {"set": s, "cell": cell, "seed": seed,
                               "trace": trace, "rc": r.returncode,
                               "wall_s": wall, "line": line,
                               "stderr_tail": r.stderr[-1500:]}
                        records.append(rec)
                        out.write(json.dumps(rec) + "\n")
                        out.flush()
                        brief = {k: v["value"] for k, v in
                                 (line or {}).get("metrics", {}).items()}
                        print(f"set {s} {cell} seed {seed} trace {trace} rc "
                              f"{r.returncode} wall {wall:.1f}s correct "
                              f"{(line or {}).get('correct')} ops "
                              f"{(line or {}).get('attempted')} mem "
                              f"{(line or {}).get('device', {}).get('memory_peak_bytes')}"
                              f" {json.dumps(brief)}", flush=True)
                        if r.returncode != 0 or not line or not line["correct"]:
                            print("  stderr tail: " + r.stderr[-1500:], flush=True)
    print("---- summary: per cell, metric, set: median, spread ----")
    bad = [r for r in records if r["rc"] != 0 or not r["line"]
           or not r["line"]["correct"]]
    for cell in cells:
        names = sorted({k for r in records if r["cell"] == cell and r["line"]
                        for k in r["line"]["metrics"]})
        for name in names:
            row = []
            for s in range(args.sets):
                vals = [r["line"]["metrics"][name]["value"] for r in records
                        if r["cell"] == cell and r["set"] == s and r["line"]
                        and name in r["line"]["metrics"]]
                if name == "setup_s":
                    vals = vals[1:] if s == 0 else vals  # the compiling run
                if vals:
                    sp = spread(vals)
                    row.append(f"set{s} n={len(vals)} median "
                               f"{statistics.median(vals):.6g} spread "
                               f"{'n/a' if sp is None else format(sp, '.4%')}")
            print(f"{cell} {name}: " + "; ".join(row))
    print(f"runs {len(records)}, not ok {len(bad)}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
