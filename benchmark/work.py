"""The chip's peaks and the work a step REQUIRES, from shapes.

Required work is the algorithm's, not the implementation's: what one step
must read, write and compute whatever the table size and whichever backend
does it. How that is counted belongs to a learner family: a configuration's
`work_model.kind` names a module in `benchmark/work_models/`, found by name
as refs, ops and readers are, whose `step_work(config)` returns
`{"gather_scatter_bytes", "bytes", "flops"}` of one step. A configuration
with no `work_model` has no required work to state, and the readers that
need one leave their metric out.

`lane_work` is the arithmetic the hashed-feature mini-batch learners share.
Per non-zero lane (a row's real features; the program's padding to a 64-lane
bucket is its own waste and is not counted):

- read the lane's id and value (4 + 4 bytes);
- gather one entry of each model table the rule reads (`entry_bytes`);
- scatter one entry of each table the rule writes: a read-modify-write,
  twice the entry, plus the id again (4 bytes);
- set the `touched` flag (1 byte).
"""

from __future__ import annotations

import importlib
import json
import os
from typing import Optional

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """Peaks of one chip, by `device_kind`. An unknown device is an error."""
    with open(_PEAKS, "r", encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"benchmark/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def step_work(config: dict) -> Optional[dict]:
    """Required work of one step of the configuration, by its work model's
    own module; None where the configuration states no work model."""
    model = config.get("work_model")
    if not model:
        return None
    try:
        mod = importlib.import_module("benchmark.work_models." + model["kind"])
    except ModuleNotFoundError as e:
        raise KeyError(f"unknown work model {model['kind']!r}: no "
                       f"benchmark/work_models/{model['kind']}.py") from e
    return mod.step_work(config)


def nonzeros_per_row(config: dict) -> int:
    data = config["data"]
    return int(data["numeric_lanes"]) + int(data["categorical_lanes"])


def lane_work(rows: int, nnz: int, entry_bytes: int, flops_per_lane: int) -> dict:
    """Required bytes and FLOPs of one mini-batch of `rows` x `nnz` lanes
    whose rule gathers and scatters `entry_bytes` of tables per lane."""
    lanes = int(rows) * int(nnz)
    gs = lanes * (4 + entry_bytes + 4 + 2 * entry_bytes + 1)
    return {
        "lanes": lanes,
        "gather_scatter_bytes": gs,
        "bytes": gs + lanes * 4 + int(rows) * 4,   # + values, labels
        "flops": lanes * int(flops_per_lane),
    }


def least_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(work["flops"] / peaks["flops_per_s"],
               work["bytes"] / peaks["bytes_per_s"])
