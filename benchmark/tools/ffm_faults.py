#!/usr/bin/env python3
"""The UPPER readings of the FFM cell's limits: the lower-storage control and
two faults planted in the plain reference, each put in the program's place
at the cell's own size and compared as `correct` compares (held-out logloss
included). Needs no chip (the reference is numpy):

    python3 benchmark/tools/ffm_faults.py --workload <cell> --seeds 1,2,3

- `control`: the reference in the storage `correct.control` names;
- `half_block`: the second half of every batch's rows left out (the row
  counter would still read the rows given);
- `own_field`: a lane paired with its OWN field's entry, `V[h(i, f_i)]`:
  FM's term under FFM's name.

`benchmark/run.py` and `tools/readings.py` give the sound seeds (the LOWER
readings) of the same cell on the chip.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datagen, manifest  # noqa: E402
from benchmark.ops.train_call import HELDOUT_INDEX, HELDOUT_ROWS  # noqa: E402


def readings(cell, seed: int) -> dict:
    cfg = cell.config
    ref = importlib.import_module("benchmark.refs." + cfg["reference"])
    epochs = int(cell.traffic.get("epochs", 1))
    dims = int(cfg["num_features"])
    sp = datagen.make_split(cfg["data"], dims,
                            int(cell.traffic["rows_per_call"]), seed, 0)
    heldout = datagen.make_split(cfg["data"], dims, HELDOUT_ROWS, seed,
                                 HELDOUT_INDEX)
    t = time.perf_counter()
    sound, _ = ref.reference(sp, cfg, epochs)
    out = {"seed": seed, "emitted": int(sound["feats"].size),
           "linear_rows": sound["linear_rows"],
           "reference_s": round(time.perf_counter() - t, 2)}

    def gaps(faulty):
        g = compare.model_gaps(faulty, sound)
        g["logloss_gap"] = compare.heldout_gap(
            faulty, sound, lambda m, i, v: ref.score_rows(m, i, v, cfg), heldout)
        return g

    out["control"] = gaps(ref.reference(
        sp, cfg, epochs,
        table_dtype=cfg["correct"]["control"]["table_dtype"])[0])
    for fault in ("half_block", "own_field"):
        out[fault] = gaps(ref.reference(sp, cfg, epochs, fault=fault)[0])
    return out


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
