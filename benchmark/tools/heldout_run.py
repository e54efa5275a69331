#!/usr/bin/env python3
"""`benchmark/run.py` with the model's held-out accuracy in the line's notes:

    python3 benchmark/tools/heldout_run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The same run (`run.execute`: set-up, window, peak, `correct`, metrics), the
same line, and for a cell of op kind `train_call` three notes more, computed
after it from the rows the compared calls emitted and the held-out split
that `logloss_gap` uses (rows no call saw):

- `heldout_accuracy`: [least, largest] over the compared calls of the share
  of held-out rows whose score's sign is the label's;
- `majority_share`: the larger class's share of the held-out labels, what a
  constant answer scores;
- `zero_weight_share`: the largest share of a call's emitted weights that
  are exactly 0.

`train_call.check` holds the held-out split and the notes and is an accepted
file; until a `benchmark` issue gives it these lines they are read here.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import datagen, manifest, run  # noqa: E402
from benchmark.ops import train_call  # noqa: E402


def heldout_notes(op) -> dict:
    cfg = op.cfg
    heldout = datagen.make_split(cfg["data"], op.dims, train_call.HELDOUT_ROWS,
                                 op.seed, train_call.HELDOUT_INDEX)
    positive = heldout.labels > 0
    accuracy, zeros = [], []
    for call in op.compared_calls():
        rows = op.ref.rows_of(call["emitted"])
        scores = op.ref.score_rows(rows, heldout.ids, heldout.vals, cfg)
        accuracy.append(float(np.mean((scores > 0) == positive)))
        w = rows["tables"]["w"]
        zeros.append(float(np.mean(w == 0)) if w.size else 0.0)
    return {"heldout_accuracy": [min(accuracy), max(accuracy)],
            "majority_share": float(max(positive.mean(), 1 - positive.mean())),
            "zero_weight_share": max(zeros)}


def main(argv=None) -> int:
    args = run.parse_args(argv)
    cell = manifest.resolve(args.workload)
    dev = run.device_info()
    run.require_chips(cell, dev)
    run.enable_compile_cache()
    ops, make_op = [], run.make_op
    run.make_op = lambda c, seed: ops.append(make_op(c, seed)) or ops[-1]
    line = run.execute(cell, args.seed, args.seconds, args.trace, t0=run._T0,
                       dev=dev)
    if isinstance(ops[0], train_call.Op):
        line["notes"].update(heldout_notes(ops[0]))
    run.print_numbers(line["numbers"], sys.stderr)
    sys.stdout.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
