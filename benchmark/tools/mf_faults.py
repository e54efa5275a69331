#!/usr/bin/env python3
"""The UPPER readings of a rating cell's limits: the lower-precision control
and faults planted in its plain reference (`refs/mf.py`), each put in the
program's place at the cell's own size and compared as `correct` compares
(held-out RMSE included). Needs no chip (the reference is numpy):

    python3 benchmark/tools/mf_faults.py --workload <cell> --seeds 1,2,3 \
        [--rows N]

- `control`: the reference with the row tables in the storage that
  `correct.control` names;
- `dup_lane_dropped`: in every block, one item lane whose item appears
  earlier in the block is left out of the item side's sum (Q, Q_gg, Bi),
  its user side kept: a write that folds a block's duplicate ids and loses
  one of them.

Each fault is planted here by `planted(ref, name)` for the length of one
`reference(...)` call; the reference itself has no switch for it. Each
line also gives the sound reference's held-out RMSE and that of the split's
mean rating. `benchmark/run.py` gives the sound seeds (the LOWER readings)
of the same cell on the chip. `--rows` cuts the split (a quick look; the
cell's readings are at its own size).
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, ratings  # noqa: E402
from benchmark.ops.train_call import HELDOUT_ROWS  # noqa: E402
from benchmark.ops.train_call_ratings import model_gaps  # noqa: E402


def _dup_lane_dropped(ref):
    every = ref.item_lanes

    def one_left_out(ci):
        keep = every(ci)
        order = np.argsort(ci, kind="stable")
        repeats = np.flatnonzero(ci[order][1:] == ci[order][:-1])
        if len(repeats):
            keep[order[repeats[0] + 1]] = False
        return keep

    return {"item_lanes": one_left_out}


FAULTS = {"dup_lane_dropped": _dup_lane_dropped}


@contextlib.contextmanager
def planted(ref, fault: str):
    """The reference module `ref` with `fault` in it, put back on exit."""
    patch = FAULTS[fault](ref)
    kept = {name: getattr(ref, name) for name in patch}
    for name, fn in patch.items():
        setattr(ref, name, fn)
    try:
        yield ref
    finally:
        for name, fn in kept.items():
            setattr(ref, name, fn)


def readings(cell, seed: int, rows=None) -> dict:
    cfg = cell.config
    ref = importlib.import_module("benchmark.refs." + cfg["reference"])
    epochs = int(cell.traffic.get("epochs", 1))
    sp = ratings.make_split(cfg["data"], int(cfg["num_users"]),
                            int(cfg["num_items"]),
                            rows or int(cell.traffic["rows_per_call"]), seed, 0)
    held = ratings.make_heldout(cfg["data"], sp, int(cfg["num_items"]),
                                HELDOUT_ROWS, seed, 0)
    t = time.perf_counter()
    sound = ref.reference(sp, cfg, epochs)
    out = {"seed": seed, "users": int(sound["users"].size),
           "items": int(sound["items"].size),
           "heldout_rmse": ref.rmse(sound, held),
           "heldout_rmse_of_mean": float(np.sqrt(np.mean(
               (held.ratings - np.mean(sp.ratings)) ** 2))),
           "reference_s": round(time.perf_counter() - t, 2)}
    out["control"] = model_gaps(
        ref.reference(sp, cfg, epochs,
                      table_dtype=cfg["correct"]["control"]["table_dtype"]),
        sound, ref, held)
    for fault in FAULTS:
        with planted(ref, fault):
            out[fault] = model_gaps(ref.reference(sp, cfg, epochs), sound,
                                    ref, held)
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--rows", type=int, default=None)
    args = p.parse_args()
    cell = manifest.resolve(args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), args.rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
