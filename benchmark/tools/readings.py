#!/usr/bin/env python3
"""The readings a cell's limits are set from, in one process (set-up is long):

    python3 benchmark/tools/readings.py --workload <cell> \
        --seeds 101,102,... --control-seeds 201,202,203 --out chiprun_out/r.json

For every seed: one split from the seed, one timed call at the cell's own
size, the plain reference, and each number `correct` compares (the LOWER
readings: sound runs of the program). For every control seed the same with
the control in the program's place (the UPPER readings), as the
configuration's `correct.control` says: the reference computed in the
storage type it names. `--host-fault half_batch` needs no
chip: it plants the fault in the reference put in the program's place.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datagen, devmem, manifest, run  # noqa: E402


def one_call(cell, seed: int, table_dtype=None) -> dict:
    op = run.make_op(cell, seed)
    op.n_splits = 1   # one call is read: the second split would only cost set-up
    t = time.perf_counter()
    op.setup()
    setup_s = time.perf_counter() - t
    result = op.window(None, max_calls=1)
    checked = op.check(table_dtype=table_dtype)
    return {"seed": seed, "numbers": checked["numbers"],
            "notes": checked["notes"], "setup_s": setup_s,
            "call_s": result["wall_s"], "emit_s": result["emit_s"],
            "memory": devmem.figures()}


def host_fault(cell, seed: int, fault: str) -> dict:
    """A fault planted in the reference, put in the program's place."""
    ref = importlib.import_module("benchmark.refs." + cell.config["reference"])
    cfg, epochs = cell.config, int(cell.traffic.get("epochs", 1))
    sp = datagen.make_split(cfg["data"], int(cfg["num_features"]),
                            int(cell.traffic["rows_per_call"]), seed, 0)
    sound, _ = ref.reference(sp, cfg, epochs)
    if fault != "half_batch":
        raise KeyError(fault)
    b = int(cfg["mini_batch"])
    keep = (np.arange(sp.rows) % b) < b // 2   # first half of each batch
    half = datagen.Split(sp.ids[keep], sp.vals[keep], sp.labels[keep])
    half_cfg = dict(cfg, mini_batch=b // 2)
    faulty, _ = ref.reference(half, half_cfg, epochs)
    return {"seed": seed, "fault": fault,
            "numbers": compare.model_gaps(faulty, sound)}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--host-fault")
    p.add_argument("--out", required=True)
    args = p.parse_args()
    cell = manifest.resolve(args.workload)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    out = {"workload": args.workload, "sound": [], "control": [], "faults": []}
    if args.host_fault:
        for s in seeds:
            out["faults"].append(host_fault(cell, s, args.host_fault))
            print(json.dumps(out["faults"][-1]), flush=True)
    else:
        run.require_chips(cell, run.device_info())
        run.enable_compile_cache()
        for s in seeds:
            out["sound"].append(one_call(cell, s))
            print(json.dumps(out["sound"][-1]), flush=True)
        control = cell.config["correct"]["control"]
        for s in control_seeds:
            r = one_call(cell, s, table_dtype=control["table_dtype"])
            out["control"].append(r)
            print(json.dumps(dict(r, control=control["what"])), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
