"""BENCHMARK.json and the files it names, resolved for one cell.

Nothing here knows a cell, a configuration or a metric by name: a cell is
`workloads[i]`, its configuration is the file `configs[j].file`, its traffic
mix is `benchmark/traffic/<traffic>.json`, and each metric that applies to
it has `benchmark/metrics/<name>.json`, which names the reader module in
`benchmark/readers/`. A later PR adds files and entries and edits none.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    metric_files: Dict[str, dict] = field(default_factory=dict)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named `workload`, with every file it needs read."""
    man = load_manifest(root)
    entries = [w for w in man["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        known = ", ".join(w["name"] for w in man["workloads"])
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has: {known}")
    w = entries[0]
    cfg_entry = next(c for c in man["configs"] if c["name"] == w["config"])
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.join(root, "benchmark")
    traffic = _read_json(os.path.join(bench_dir, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in man["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"]
                 if _applies(m, workload) and m["moves"] in e2e_names]
    files = {m["name"]: _read_json(
        os.path.join(bench_dir, "metrics", m["name"] + ".json"))
        for m in e2e + per_layer}
    return Cell(name=workload, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, metric_files=files)
