"""Op kind `train_call_ratings`: `train_call` for a rating entry point
(`train_mf_adagrad`, `train_mf_sgd`). One op is the same UDTF lifetime on
one mapper's split, the rows handed over as three arrays:

    model = sql.get_function(entry_point)(users, items, ratings, options)
    rows_out = model.model_rows()          # close(): users', items' rows, mu
    steps = int(model.state.step)          # a value fetch: the work ran

The splits are `benchmark/ratings.py`'s, sized by the configuration's
`num_users` and `num_items`. The window, the kept calls and the result's
keys are `train_call`'s. `correct` compares each compared call's emitted
rows with the configuration's plain reference of the same split
(`refs/mf.py`): the users and items emitted (`rows_diff`), the rows trained
(`steps_diff`), the widest relative gap of P, of Q and of the two bias
tables (`compare.table_gap`), and the held-out RMSE of the two models on
ratings of the split's users that it does not hold (`rmse_gap`).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np

from .. import compare, devmem, ratings
from . import train_call


class Op(train_call.Op):
    def __init__(self, cell, seed: int):
        super().__init__(cell, seed)
        self.num_users = int(self.cfg["num_users"])
        self.num_items = int(self.cfg["num_items"])
        self.heldout: Dict[int, ratings.RatingSplit] = {}

    def _form(self, split, rows: Optional[int] = None):
        if rows is not None:
            split = split.head(rows)
        return (split.users, split.items)

    def setup(self) -> None:
        import gc

        from hivemall_tpu.sql.registry import get_function

        entry = get_function(self.cfg["entry_point"])
        self.fn = lambda form, labels, options: entry(*form, labels, options)
        self.splits = [ratings.make_split(
            self.cfg["data"], self.num_users, self.num_items,
            self.rows_per_call, self.seed, i) for i in range(self.n_splits)]
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

    def _heldout(self, index: int) -> ratings.RatingSplit:
        if index not in self.heldout:
            self.heldout[index] = ratings.make_heldout(
                self.cfg["data"], self.splits[index], self.num_items,
                train_call.HELDOUT_ROWS, self.seed, index)
        return self.heldout[index]

    def check(self, table_dtype: Optional[str] = None) -> dict:
        """{"numbers": {name: worst value over the compared calls}, "notes"}.
        `table_dtype` puts the reference in a lower precision in the
        program's place: the control."""
        worst: Dict[str, float] = {}
        notes = {"compared_calls": 0, "reference_s": 0.0, "heldout_rmse": 0.0,
                 "heldout_rmse_of_mean": 0.0}
        refs: Dict[int, dict] = {}
        for call in self.compared_calls():
            i = call["split"]
            t = time.perf_counter()
            if i not in refs:
                refs[i] = self.ref.reference(self.splits[i], self.cfg,
                                             self.epochs)
            ref = refs[i]
            if table_dtype is None:
                prog = self.ref.rows_of(call["emitted"])
                steps = call["steps"]
            else:
                prog = self.ref.reference(self.splits[i], self.cfg,
                                          self.epochs, table_dtype=table_dtype)
                steps = prog["steps"]
            notes["reference_s"] += time.perf_counter() - t
            numbers = model_gaps(prog, ref, self.ref, self._heldout(i))
            numbers["steps_diff"] = float(abs(steps - call["rows"]))
            for k, v in numbers.items():
                worst[k] = max(worst.get(k, 0.0), v)
            held = self._heldout(i)
            notes["heldout_rmse"] = max(notes["heldout_rmse"],
                                        self.ref.rmse(ref, held))
            mean = float(np.mean(self.splits[i].ratings))
            notes["heldout_rmse_of_mean"] = max(
                notes["heldout_rmse_of_mean"],
                float(np.sqrt(np.mean((held.ratings - mean) ** 2))))
            notes["compared_calls"] += 1
        return {"numbers": worst, "notes": notes}


def _side_gaps(prog: dict, ref: dict, ids: str, tables) -> tuple:
    pf, rf = prog[ids], ref[ids]
    if pf.size == rf.size and np.array_equal(pf, rf):
        diff, pi, ri = 0, slice(None), slice(None)
    else:
        common, pi, ri = np.intersect1d(pf, rf, assume_unique=True,
                                        return_indices=True)
        diff = int(pf.size + rf.size - 2 * common.size)
    return diff, [compare.table_gap(prog[t][pi], ref[t][ri]) for t in tables]


def model_gaps(prog: dict, ref: dict, refmod, heldout) -> Dict[str, float]:
    """The numbers `correct` compares, but `steps_diff`."""
    du, (p_gap, bu_gap) = _side_gaps(prog, ref, "users", ("P", "Bu"))
    di, (q_gap, bi_gap) = _side_gaps(prog, ref, "items", ("Q", "Bi"))
    return {"rows_diff": float(du + di), "p_gap": p_gap, "q_gap": q_gap,
            "bias_gap": max(bu_gap, bi_gap),
            "rmse_gap": abs(refmod.rmse(prog, heldout)
                            - refmod.rmse(ref, heldout))}
