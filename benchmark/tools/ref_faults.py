#!/usr/bin/env python3
"""The UPPER readings of a `train_call` cell's limits: the lower-storage
control and faults planted in the cell's plain reference, each put in the
program's place at the cell's own size and compared as `correct` compares
(held-out logloss included). Needs no chip (the references are numpy):

    python3 benchmark/tools/ref_faults.py --workload <cell> --seeds 1,2,3 \
        --faults half_block,slots_summed

- `control`: the reference in the storage `correct.control` names (what
  that storage applies to is the reference's: `refs/adagrad_rda.py` rounds
  both slots, the sums of gradients and of squared gradients);
- each of `--faults`: one of `FAULTS`, planted here by `planted(ref, name)`
  for the length of one `reference(...)` call; the reference itself has no
  switch for it. For `refs/adagrad_rda.py`: `half_block`, the second half of
  every batch's rows left out while the row counter reads the rows given;
  `slots_summed`, G takes the sum of a batch's squared gradients where the
  rule squares the batch's summed gradient (every slot a plain sum of the
  rows' deltas: the step before PR 34).

Each line also gives the sound reference's held-out accuracy, the majority
share of the held-out labels and the share of emitted weights that are
exactly 0. `benchmark/run.py` and `tools/readings.py` give the sound seeds
(the LOWER readings) of the same cell on the chip. (`ffm_faults.py` and
`mix_faults.py` are the same reading with their cells' faults written in.)
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

from benchmark import compare, datagen, manifest  # noqa: E402
from benchmark.ops.train_call import HELDOUT_INDEX, HELDOUT_ROWS  # noqa: E402

def _half_block(ref):
    fired_rows = ref.fired_rows

    def first_half(m):
        fired = fired_rows(m)
        fired[max(1, m.shape[0] // 2):] = False
        return fired

    return {"fired_rows": first_half}


def _slots_summed(ref):
    def sums(feature, du, n_features):
        return (np.bincount(feature, du, minlength=n_features),
                np.bincount(feature, du * du, minlength=n_features))

    return {"batch_increments": sums}


FAULTS = {"half_block": _half_block, "slots_summed": _slots_summed}


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


def readings(cell, seed: int, faults) -> dict:
    cfg = cell.config
    ref = importlib.import_module("benchmark.refs." + cfg["reference"])
    epochs = int(cell.traffic.get("epochs", 1))
    dims = int(cfg["num_features"])
    sp = datagen.make_split(cfg["data"], dims,
                            int(cell.traffic["rows_per_call"]), seed, 0)
    heldout = datagen.make_split(cfg["data"], dims, HELDOUT_ROWS, seed,
                                 HELDOUT_INDEX)
    score = lambda m, i, v: ref.score_rows(m, i, v, cfg)
    t = time.perf_counter()
    sound, _ = ref.reference(sp, cfg, epochs)
    positive = heldout.labels > 0
    out = {"seed": seed, "emitted": int(sound["feats"].size),
           "zero_share": float(np.mean(sound["tables"]["w"] == 0)),
           "heldout_accuracy": float(np.mean(
               (score(sound, heldout.ids, heldout.vals) > 0) == positive)),
           "majority_share": float(max(positive.mean(), 1 - positive.mean())),
           "reference_s": round(time.perf_counter() - t, 2)}

    def gaps(faulty):
        g = compare.model_gaps(faulty, sound)
        g["logloss_gap"] = compare.heldout_gap(faulty, sound, score, heldout)
        return g

    out["control"] = gaps(ref.reference(
        sp, cfg, epochs,
        table_dtype=cfg["correct"]["control"]["table_dtype"])[0])
    for fault in faults:
        with planted(ref, fault):
            out[fault] = gaps(ref.reference(sp, cfg, epochs)[0])
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--faults", default="")
    args = p.parse_args()
    cell = manifest.resolve(args.workload)
    faults = [f for f in args.faults.split(",") if f]
    for s in args.seeds.split(","):
        print(json.dumps(readings(cell, int(s), faults)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
