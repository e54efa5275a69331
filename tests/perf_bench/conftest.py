"""Fixtures of the benchmark's own tests: a temporary copy of the benchmark
in which tiny cells are ADDED AS NEW FILES AND ENTRIES, the way a later PR
adds a cell (nothing that is there is edited)."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_CELLS = {
    "arow_tiny.replay": ("arow_criteo1tb", 1 << 16, "train_replay"),
    "fm_tiny.replay": ("fm_criteo1tb", 1 << 14, "train_replay_2ep"),
    "arow_tiny.text": ("arow_criteo1tb", 1 << 16, "train_text"),
    # over 2^24 dims, where the program's tables are bfloat16 as the cell's are
    "arow_bf16.replay": ("arow_criteo1tb", 1 << 25, "train_replay"),
}
TINY_ROWS = 4096
# at or under 2^24 dims fit_linear keeps float32 tables: the tiny AROW cells
# state that storage, and the limits that float32 tables hold (PERF.md)
F32_LIMITS = {"rows_diff": 0, "steps_diff": 0, "w_gap": 1e-4, "cov_gap": 1e-5,
              "logloss_gap": 5e-7}


# an op kind and a work-model kind that the benchmark does not have, as a
# later PR would bring them: one module each, found by name
COUNT_OP = '''"""Op kind `count_op`: one op sums `n` seeded numbers on the host."""
import time

import numpy as np


class Op:
    trace_max_ops = 2

    def __init__(self, cell, seed):
        self.n = int(cell.traffic["n"])
        self.numbers = np.random.default_rng(seed).random(self.n)
        self.sums = []

    def setup(self):
        pass

    def window(self, seconds, max_calls=None):
        start = time.perf_counter()
        while True:
            self.sums.append(float(np.sum(self.numbers)))
            now = time.perf_counter()
            if (max_calls and len(self.sums) >= max_calls) or (
                    seconds is not None and now - start >= seconds):
                break
        return {"attempted": len(self.sums), "failed": 0,
                "units": float(self.n * len(self.sums)), "wall_s": now - start}

    def check(self):
        want = sum(float(x) for x in self.numbers)
        worst = max(abs(s - want) for s in self.sums)
        return {"numbers": {"sum_diff": worst}, "notes": {"compared": len(self.sums)}}
'''
DENSE_ROWS = '''"""Work model `dense_rows`: a step reads `row_bytes` of every row once."""


def step_work(config):
    b = int(config["mini_batch"]) * int(config["work_model"]["row_bytes"])
    return {"gather_scatter_bytes": 0, "bytes": b, "flops": int(config["mini_batch"])}
'''
COUNT_CONFIGS = {
    "count_dense": {"mini_batch": 8,
                    "work_model": {"kind": "dense_rows", "row_bytes": 100}},
    "count_plain": {},   # states no work model: the cell runs all the same
}


def _dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


def _load(path):
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def build_tiny_root(dst: str) -> str:
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = _load(os.path.join(REPO, "BENCHMARK.json"))
    bench = os.path.join(dst, "benchmark")
    done = set()
    for name, (cfg, dims, traffic) in TINY_CELLS.items():
        tiny_cfg = name.split(".")[0]
        if tiny_cfg not in done:
            c = _load(os.path.join(bench, "configs", cfg + ".json"))
            c["options"] = c["options"].replace(str(c["num_features"]), str(dims))
            c["num_features"] = dims
            if c["reference"] == "arow" and dims <= 1 << 24:
                c["table_dtype"] = "float32"
                c["reference_args"].pop("storage")
                c["correct"]["limits"] = dict(F32_LIMITS)
            _dump(c, os.path.join(bench, "configs", tiny_cfg + ".json"))
            man["configs"].append({
                "name": tiny_cfg, "source": "test", "reduced": ["num_features"],
                "file": f"benchmark/configs/{tiny_cfg}.json", "why": "tiny"})
            done.add(tiny_cfg)
        t = _load(os.path.join(bench, "traffic", traffic + ".json"))
        t["rows_per_call"] = TINY_ROWS
        _dump(t, os.path.join(bench, "traffic", traffic + "_tiny.json"))
        man["workloads"].append({"name": name, "config": tiny_cfg, "chips": 1,
                                 "traffic": traffic + "_tiny", "why": "tiny"})
    # a per-layer metric added as a new file with an existing reader
    _dump({"name": "emit_pct_again.train", "reader": "emit_pct_of_call",
           "unit": "%", "better": "lower", "source": "host_clock",
           "layer": "emission", "moves": "train_rows_per_s"},
          os.path.join(bench, "metrics", "emit_pct_again.train.json"))
    man["per_layer"].append({
        "name": "emit_pct_again.train", "unit": "%", "better": "lower",
        "source": "host_clock", "layer": "emission",
        "moves": "train_rows_per_s", "workloads": ["arow_tiny.replay"]})
    # an op kind and a work-model kind added as new files, with their cells
    with open(os.path.join(bench, "ops", "count_op.py"), "w") as f:
        f.write(COUNT_OP)
    with open(os.path.join(bench, "work_models", "dense_rows.py"), "w") as f:
        f.write(DENSE_ROWS)
    _dump({"op": "count_op", "n": 1000},
          os.path.join(bench, "traffic", "count_mix.json"))
    for name, extra in COUNT_CONFIGS.items():
        _dump(dict(extra, name=name, correct={"limits": {"sum_diff": 1e-9}}),
              os.path.join(bench, "configs", name + ".json"))
        man["configs"].append({"name": name, "source": "test", "reduced": [],
                               "file": f"benchmark/configs/{name}.json",
                               "why": "no model"})
        man["workloads"].append({"name": name + ".count_mix", "config": name,
                                 "traffic": "count_mix", "chips": 1,
                                 "why": "a new op kind"})
    _dump(man, os.path.join(dst, "BENCHMARK.json"))
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return build_tiny_root(str(tmp_path_factory.mktemp("bench_root")))
