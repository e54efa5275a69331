"""Op kind `train_call`: one op is one UDTF lifetime on one mapper's split,
as the SQL adapters run it:

    model = sql.get_function(entry_point)(rows, labels, options)
    rows_out = model.model_rows()          # close(): the function's output
    steps = int(model.state.step)          # a value fetch: the work ran

Calls follow each other, each on a fresh state, until the window is up; the
call in flight is finished and counted with its time. The harness drops each
model before the next call, so no run depends on how many states fit.

`correct` compares the rows the timed calls themselves emitted (every call
up to KEEP_CALLS; beyond that a sample drawn from the seed that holds the
first call of each split and the last call) with the configuration's plain
reference of the same split, once the window has closed and the device's
peak has been read.
"""

from __future__ import annotations

import gc
import importlib
import time
from typing import Dict, List, Optional

import numpy as np

from .. import compare, datagen, devmem

SPLITS = 2            # a run's splits: no call repeats the last one's rows
KEEP_CALLS = 6        # calls whose emitted rows are held for `correct`
TRACE_CALLS = 2       # whole calls a traced window records
HELDOUT_ROWS = 8192
HELDOUT_INDEX = 1_000_003  # a split index no run reaches


class Op:
    def __init__(self, cell, seed: int):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.dims = int(self.cfg["num_features"])
        self.mini_batch = int(self.cfg["mini_batch"])
        self.rows_per_call = int(self.traffic["rows_per_call"])
        self.epochs = int(self.traffic["epochs"])
        self.n_splits = SPLITS
        self.trace_max_ops = TRACE_CALLS
        self.row_form = self.traffic["row_form"]
        self.options = self.cfg["options"]
        if self.epochs > 1:
            self.options += f" -iters {self.epochs} -disable_cv"
        self.ref = importlib.import_module(
            "benchmark.refs." + self.cfg["reference"])
        self.splits: List[datagen.Split] = []
        self.forms: list = []
        self.fn = None
        self.calls: List[dict] = []
        self.kept: Dict[int, dict] = {}
        self._last: Optional[dict] = None

    # ---- set-up: data from the seed, the entry point, every shape warm ----

    def _form(self, split: datagen.Split, rows: Optional[int] = None):
        if rows is not None:
            split = datagen.Split(split.ids[:rows], split.vals[:rows],
                                  split.labels[:rows])
        if self.row_form == "arrays":
            return split.as_arrays()
        if self.row_form == "text":
            return split.as_text()
        raise KeyError(f"unknown row_form {self.row_form!r}")

    def setup(self) -> None:
        from hivemall_tpu.sql.registry import get_function

        self.fn = get_function(self.cfg["entry_point"])
        self.splits = [datagen.make_split(self.cfg["data"], self.dims,
                                          self.rows_per_call, self.seed, i)
                       for i in range(self.n_splits)]
        self.forms = [self._form(s) for s in self.splits]
        # the warm-up is the timed call itself on the first two blocks of
        # split 0: the same entry point, options, block shape and emission
        warm_rows = min(2 * self.mini_batch, self.rows_per_call)
        sp = self.splits[0]
        with devmem.Sampler():   # state and step scratch, read together
            model = self.fn(self._form(sp, warm_rows), sp.labels[:warm_rows],
                            self.options)
            model.model_rows()
            int(model.state.step)
        del model
        gc.collect()

    # ---- the window ----

    def _one_call(self, n: int) -> dict:
        import jax.profiler as prof

        i = n % self.n_splits
        sp = self.splits[i]
        t0, c0 = time.perf_counter(), time.process_time()
        with prof.TraceAnnotation("bench:call"):
            model = self.fn(self.forms[i], sp.labels, self.options)
            devmem.snapshot()   # the state in hand: see devmem
            e0 = time.perf_counter()
            with prof.TraceAnnotation("bench:emit"):
                emitted = model.model_rows()
            e1 = time.perf_counter()
            steps = int(model.state.step)
        t1 = time.perf_counter()
        del model
        return {"n": n, "split": i, "t0": t0, "t1": t1, "emit_s": e1 - e0,
                "train_s": e0 - t0, "cpu_s": time.process_time() - c0,
                "steps": steps, "rows": sp.rows * self.epochs,
                "emitted": emitted}

    def _keep(self, call: dict, rng: np.random.Generator) -> None:
        """At most KEEP_CALLS + 1 calls' rows held: the first of each split,
        a seeded reservoir of the rest, and always the newest."""
        slots = KEEP_CALLS
        n = call["n"]
        self._last = call
        if n < slots:
            self.kept[n] = call
            return
        j = int(rng.integers(0, n + 1))
        if self.n_splits <= j < slots:
            self.kept[j] = call

    def window(self, seconds: Optional[float],
               max_calls: Optional[int] = None) -> dict:
        """Calls back to back until `seconds` are up (the call in flight is
        finished) or `max_calls` are done; `seconds=None` sets no time limit."""
        rng = np.random.default_rng([self.seed, 0xCA11])
        start = time.perf_counter()
        n = 0
        while True:
            call = self._one_call(n)
            self._keep(call, rng)
            self.calls.append({k: v for k, v in call.items() if k != "emitted"})
            n += 1
            if max_calls and n >= max_calls:
                break
            if seconds is not None and call["t1"] - start >= seconds:
                break
        end = self.calls[-1]["t1"]
        return {
            "attempted": n,
            "failed": 0,
            "units": float(sum(c["rows"] for c in self.calls)),
            "wall_s": end - start,
            "call_s": float(sum(c["t1"] - c["t0"] for c in self.calls)),
            "emit_s": float(sum(c["emit_s"] for c in self.calls)),
            "steps": int(sum(c["steps"] for c in self.calls)) // self.mini_batch,
            "calls": self.calls,
            # per call [train_s, emit_s, cpu_s], for a reader of a run that
            # reads slow: which part of which call took longer, and whether
            # the process worked more (CPU seconds rise) or waited
            "notes": {"calls_s": [[round(c[k], 4) for k in
                                   ("train_s", "emit_s", "cpu_s")]
                                  for c in self.calls]},
        }

    # ---- correct: after the window, after the peak has been read ----

    def compared_calls(self) -> List[dict]:
        calls = {c["n"]: c for c in self.kept.values()}
        if self._last is not None:
            calls[self._last["n"]] = self._last
        return [calls[k] for k in sorted(calls)]

    def check(self, table_dtype: Optional[str] = None) -> dict:
        """{"numbers": {name: worst value over the compared calls}, "notes"}.
        `table_dtype` puts the reference in a lower precision: the control,
        compared with the full-precision reference in the program's place."""
        heldout = datagen.make_split(
            self.cfg["data"], self.dims,
            HELDOUT_ROWS, self.seed, HELDOUT_INDEX)
        scorer = lambda m, ids, vals: self.ref.score_rows(m, ids, vals, self.cfg)
        worst: Dict[str, float] = {}
        notes = {"compared_calls": 0, "ambiguous_rows": 0, "followed_rows": 0,
                 "followed_margin": 0.0, "reference_s": 0.0}
        refs: Dict[tuple, tuple] = {}
        for call in self.compared_calls():
            sp = self.splits[call["split"]]
            prog = self.ref.rows_of(call["emitted"])
            t = time.perf_counter()
            if table_dtype is not None:
                # the control: lower precision in the program's place
                if call["split"] not in refs:
                    refs[call["split"]] = (
                        self.ref.reference(sp, self.cfg, self.epochs)[0],
                        self.ref.reference(sp, self.cfg, self.epochs,
                                           table_dtype=table_dtype)[0])
                ref, prog = refs[call["split"]]
                info = {}
            else:
                # one reference run a split and emitted feature set: the set
                # is all that the reference reads of the program (rows at the
                # firing boundary follow it), so calls that emitted the same
                # features share the run; its notes count once
                key = (call["split"], prog["feats"].size,
                       int(prog["feats"].sum()))
                if key in refs:
                    ref, info = refs[key][0], {}
                else:
                    ref, info = refs[key] = self.ref.reference(
                        sp, self.cfg, self.epochs, prog=prog)
            notes["reference_s"] += time.perf_counter() - t
            numbers = compare.model_gaps(prog, ref)
            numbers["logloss_gap"] = compare.heldout_gap(prog, ref, scorer,
                                                         heldout)
            numbers["steps_diff"] = float(abs(call["steps"] - call["rows"])) \
                if table_dtype is None else 0.0
            for k, v in numbers.items():
                worst[k] = max(worst.get(k, 0.0), v)
            notes["compared_calls"] += 1
            for k in ("ambiguous_rows", "followed_rows"):
                notes[k] += int(info.get(k, 0))
            notes["followed_margin"] = max(
                notes["followed_margin"], float(info.get("followed_margin", 0.0)))
        return {"numbers": worst, "notes": notes}
