#!/usr/bin/env python3
"""The UPPER readings of a `-mix` cell's limits: the lower-storage control and
two faults planted in the plain reference, each put in the program's place
at the cell's own size and compared as `correct` compares (held-out logloss
included). Needs no chip (the reference is numpy):

    python3 benchmark/tools/mix_faults.py --workload <cell> --seeds 1,2,3

- `control`: the reference in the storage `correct.control` names (what
  `readings.py --control-seeds` computes after a chip call it does not need);
- `mix_skipped`: every round left out; what comes back is replica 0's
  unmixed tables over the union of the touched features, as a program that
  trained the replicas and never mixed them would emit;
- `share_twice`: the second replica is dealt the first replica's share again
  (its own is never trained); the row counter still reads the rows given.

`benchmark/tools/readings.py` gives the sound seeds (the LOWER readings) of
the same cell on the chip.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datagen, manifest  # noqa: E402
from benchmark.ops.train_call import HELDOUT_INDEX, HELDOUT_ROWS  # noqa: E402


def readings(cell, seed: int) -> dict:
    cfg = cell.config
    ref = importlib.import_module("benchmark.refs." + cfg["reference"])
    args = cfg["reference_args"]
    sp = datagen.make_split(cfg["data"], int(cfg["num_features"]),
                            int(cell.traffic["rows_per_call"]), seed, 0)
    common = dict(dims=int(cfg["num_features"]), mini_batch=int(cfg["mini_batch"]),
                  replicas=int(args["replicas"]), mix_every=int(args["mix_every"]),
                  r=float(args.get("r", 0.1)), table_dtype=args.get("storage"))

    def model(ids, vals, labels, **kw):
        f, w, c, info = ref.train(ids, vals, labels, **dict(common, **kw))
        return {"feats": f, "tables": {"w": w, "cov": c}, "scalars": {}}, info

    heldout = datagen.make_split(cfg["data"], common["dims"], HELDOUT_ROWS,
                                 seed, HELDOUT_INDEX)

    def gaps(faulty):
        out = compare.model_gaps(faulty, sound)
        out["logloss_gap"] = compare.heldout_gap(
            faulty, sound, lambda m, i, v: ref.score_rows(m, i, v, cfg), heldout)
        return out

    sound, info = model(sp.ids, sp.vals, sp.labels)
    skipped, _ = model(sp.ids, sp.vals, sp.labels, mix=False)
    control, _ = model(sp.ids, sp.vals, sp.labels, **{
        "table_dtype": cfg["correct"]["control"]["table_dtype"]})
    (a, b), (_, c) = ref.shares_of(sp.rows, common["replicas"])[:2]
    again = np.r_[np.arange(a, b), np.arange(a, b)[:c - b], np.arange(c, sp.rows)]
    twice, _ = model(sp.ids[again], sp.vals[again], sp.labels[again])
    return {"seed": seed, "emitted": int(sound["feats"].size),
            "mix_rounds": info["mix_rounds"],
            "mix_due_entries": info["mix_due_entries"],
            "control": gaps(control), "mix_skipped": gaps(skipped),
            "share_twice": gaps(twice)}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args()
    cell = manifest.resolve(args.workload)
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
