"""Plain AROW (Crammer et al., NIPS 2009) under Hivemall's mini-batch rule.

Per mini-batch of B rows, against the weights and covariances at the batch's
start (AROWClassifierUDTF.java:99-150 for the row rule;
RegressionBaseUDTF.java:236-295 + FloatAccumulator.java:38-41 for the
accumulate-then-apply-the-mean rule):

    m = y * sum_i w_i x_i            v = sum_i cov_i x_i^2
    the row fires iff m < 1;  beta = 1 / (v + r);  alpha = (1 - m) * beta
    dw_i = y * alpha * cov_i * x_i;  dcov_i = -beta * (cov_i * x_i)^2
    per feature: w += sum(dw) / n, cov += sum(dcov) / n,
                 n = the number of lanes of FIRED rows that carry it

`close()` emits (feature, weight, covariance) for every feature a fired row
carried. All arithmetic is float64 on the ids the split touches; `table_dtype`
rounds both tables after every batch's write: to the configuration's storage
type (`reference_args.storage`: bfloat16 above 2^24 dims, as the program
stores them), or to a lower one put in the reference's place (the control).

**Rows at the firing boundary.** `m < 1` is a branch. Where the reference's m
is within `tau` of 1, the program's rounding decides which side it lands on,
and either side is a correct AROW. For such a row the reference follows the
program, as a served model's reference follows the served tokens, where the
program's decision can be read exactly: off a WITNESS, a feature that this
row alone carries in the split, which `close()` emits if and only if the row
fired (one epoch). A row with no witness (about 1 in 10^4 at 2^28 dims) keeps
the reference's own reading. The run reports how many rows were within tau,
how many it followed against its own reading, and their widest |m - 1|.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _rounder(table_dtype: Optional[str]):
    if table_dtype in (None, "float64"):
        return lambda a: a
    if table_dtype == "float32":
        return lambda a: a.astype(np.float32).astype(np.float64)
    import ml_dtypes

    dt = {"bfloat16": ml_dtypes.bfloat16,
          "float8_e4m3fn": ml_dtypes.float8_e4m3fn}[table_dtype]
    return lambda a: a.astype(np.float32).astype(dt).astype(np.float64)


def train(ids, vals, labels, *, dims: int, mini_batch: int, epochs: int = 1,
          r: float = 0.1, table_dtype: Optional[str] = None,
          emitted_feats: Optional[np.ndarray] = None, tau: float = 0.0):
    """Returns (feats, weights, covars, info): model rows sorted by feature.

    `emitted_feats` (sorted) switches following on for rows with
    |m - 1| < tau that carry a witness (one epoch only); info counts them.
    """
    ids = np.asarray(ids, dtype=np.int64) % int(dims)
    vals = np.asarray(vals, dtype=np.float64)
    y = np.where(np.asarray(labels) > 0, 1.0, -1.0)
    rnd = _rounder(table_dtype)
    uid, inv = np.unique(ids, return_inverse=True)
    inv = inv.reshape(ids.shape)
    w = np.zeros(uid.size)
    cov = np.ones(uid.size)
    touched = np.zeros(uid.size, dtype=bool)
    in_emitted = None
    if emitted_feats is not None and tau > 0 and int(epochs) <= 1:
        pos = np.searchsorted(emitted_feats, uid)
        pos[pos >= emitted_feats.size] = 0
        in_emitted = (emitted_feats[pos] == uid) if emitted_feats.size \
            else np.zeros(uid.size, dtype=bool)
        alone = np.bincount(inv.ravel(), minlength=uid.size) == 1
    info = {"ambiguous_rows": 0, "followed_rows": 0, "followed_margin": 0.0,
            "steps": 0}
    n = ids.shape[0]
    for _ in range(max(1, int(epochs))):
        for s in range(0, n, mini_batch):
            idx = inv[s:s + mini_batch]
            x = vals[s:s + mini_batch]
            yy = y[s:s + mini_batch]
            wg = w[idx]
            cg = cov[idx]
            m = yy * np.sum(wg * x, axis=1)
            var = np.sum(cg * x * x, axis=1)
            fired = m < 1.0
            if in_emitted is not None:
                amb = np.nonzero(np.abs(m - 1.0) < tau)[0]
                info["ambiguous_rows"] += int(amb.size)
                for i in amb:
                    witness = idx[i][alone[idx[i]]]
                    if witness.size == 0:
                        continue
                    prog = bool(in_emitted[witness[0]])
                    if prog != bool(fired[i]):
                        fired[i] = prog
                        info["followed_rows"] += 1
                        info["followed_margin"] = max(
                            info["followed_margin"], float(abs(m[i] - 1.0)))
            info["steps"] += int(idx.shape[0])
            if not fired.any():
                continue
            beta = 1.0 / (var + r)
            alpha = (1.0 - m) * beta
            cv = cg * x
            f = np.nonzero(fired)[0]
            dw = (yy[f] * alpha[f])[:, None] * cv[f]
            dc = -beta[f][:, None] * cv[f] * cv[f]
            bu, binv = np.unique(idx[f].ravel(), return_inverse=True)
            cnt = np.bincount(binv, minlength=bu.size)
            w[bu] = rnd(w[bu] + np.bincount(binv, dw.ravel(),
                                            minlength=bu.size) / cnt)
            cov[bu] = rnd(cov[bu] + np.bincount(binv, dc.ravel(),
                                                minlength=bu.size) / cnt)
            touched[bu] = True
    return uid[touched], w[touched], cov[touched], info


def scores(feats, weights, ids, vals, dims: int) -> np.ndarray:
    """sum_i w_i x_i of each row from model rows (absent feature: 0)."""
    ids = np.asarray(ids, dtype=np.int64) % int(dims)
    if feats.size == 0:
        return np.zeros(ids.shape[0])
    pos = np.clip(np.searchsorted(feats, ids), 0, feats.size - 1)
    wg = np.where(feats[pos] == ids, np.asarray(weights, np.float64)[pos], 0.0)
    return np.sum(wg * np.asarray(vals, np.float64), axis=1)


# ---- the adapter the op kinds use (same three names in every reference) ----

def rows_of(emitted) -> dict:
    """What `TrainedLinearModel.model_rows()` returned, as a model dict."""
    feats, weights, covars = emitted
    return {"feats": np.asarray(feats, np.int64),
            "tables": {"w": np.asarray(weights, np.float64),
                       "cov": np.asarray(covars, np.float64)},
            "scalars": {}}


def reference(split, cfg: dict, epochs: int, prog: Optional[dict] = None,
              table_dtype: Optional[str] = None):
    """The reference's model of one split, and its run's notes."""
    args = cfg.get("reference_args", {})
    feats, w, cov, info = train(
        split.ids, split.vals, split.labels, dims=int(cfg["num_features"]),
        mini_batch=int(cfg["mini_batch"]), epochs=epochs,
        r=float(args.get("r", 0.1)),
        table_dtype=table_dtype or args.get("storage"),
        emitted_feats=None if prog is None else prog["feats"],
        tau=float(cfg.get("correct", {}).get("tau", 0.0)))
    return {"feats": feats, "tables": {"w": w, "cov": cov}, "scalars": {}}, info


def score_rows(model: dict, ids, vals, cfg: dict) -> np.ndarray:
    return scores(model["feats"], model["tables"]["w"], ids, vals,
                  int(cfg["num_features"]))
