"""Plain AdaGradRDA (Duchi, Hazan, Singer, JMLR 2011: the primal-dual, RDA,
form with l1; Xiao, JMLR 2010 for RDA) as Hivemall's `train_adagrad_rda`
runs it (`classifier/AdaGradRDAUDTF.java:40-143`: hinge loss, `-eta 0.1
-lambda 1e-6 -scale 100`), a mini-batch at a time.

Per mini-batch of B rows, against the weights at the batch's start, with
s = scale and t = rows trained when the batch ends:

    m = y * sum_i w_i x_i;  the row fires iff m < 1 (hinge);  g_i = -y x_i
    per feature: S_i = sum of s g_i over the lanes of FIRED rows that carry i
                 u_i += S_i,  G_i += S_i^2        (the batch is ONE subgradient)
    for every feature a fired row carries:
        w_i = 0 if |s u_i| / t < lambda, else
              -sign(u_i) * eta * t * (|s u_i| / t - lambda) / sqrt(s G_i)

This is mini-batch dual averaging as published (one step on the batch's mean
gradient S / B: u += S / B, G += (S / B)^2, t += 1; Dekel, Gilad-Bachrach,
Shamir, Xiao, "Optimal Distributed Online Prediction Using Mini-Batches",
JMLR 2012) written in sums: u times B, G times B^2 and t times B give the
same weight, t stays the row counter, |u| / t stays the mean gradient over
ROWS that lambda is set against, and B = 1 is the row rule. (A last batch of
n < B rows weighs n / B of a step in that reading.)

The scale enters twice: the stored sums are of scaled gradients and are
scaled again where they are read, as `models/classifier.py` ports upstream's
lines 112-141 (SURVEY.md cites them; the upstream file is not in this
sandbox, so that reading could not be checked against it a second time).
At the defaults the rule is eta 1.0, lambda 1e-10 in unscaled sums.

`close()` emits (feature, weight) for every feature a fired row carried, a
derived weight of exactly 0 included. Arithmetic is float64 on the ids the
split touches. The program keeps u and G in float32 and, above 2^24 dims, w
in bfloat16: `storage` rounds w at every batch's write
(`reference_args.storage`), `slot_dtype` rounds u and G at every batch's
write. The configuration states float32 slots, and the reference keeps them
in float64 (float32's rounding is the comparison's noise); the CONTROL is
`slot_dtype="bfloat16"`, what upstream's half-float SpaceEfficientDenseModel
would store, put in the program's place.

**Departures from upstream**, each the port's and the reference's alike:

- the batch. Upstream updates this learner a row at a time
  (`AdaGradRDAUDTF.update`), so for B > 1 the model differs from upstream's
  for the same rows; with B = 1 it is upstream's row rule.
- a weight is rewritten only when a fired row carries its feature, so it
  keeps the t of its last firing batch.
- a feature inside the l1 ball keeps its sums and emits weight 0 (the
  port's `derive_w` writes 0 and leaves u and G).

**Rows at the firing boundary.** `m < 1` is a branch. Where the reference's
m is within `tau` of 1 the program's rounding decides the side, and either
is a correct step. For such a row the reference follows the program where
its decision can be read exactly: off a WITNESS, a feature that this row
alone carries in the split, which `close()` emits if and only if the row
fired (one epoch). A row with no witness keeps the reference's own reading.

**The comparison's floor.** `compare.table_gap` divides a weight's gap by
|reference weight| + the table's median |reference weight|. Were more than
half of a run's emitted weights exactly 0 that median would be 0 and a
weight just outside the l1 ball could read any gap; `rows_of` and
`reference` would then have to pad as `refs/ffm.py` does with `W_FLOOR`. At
the defaults the share of exact zeros is a few percent (features whose
gradients cancelled exactly; PERF.md section 4 gives the share read), the
median is that of the one-hot features seen once, and no floor is set.
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _rounder(dtype: Optional[str]):
    if dtype in (None, "float64"):
        return lambda a: a
    if dtype == "float32":
        return lambda a: a.astype(np.float32).astype(np.float64)
    import ml_dtypes

    dt = {"bfloat16": ml_dtypes.bfloat16}[dtype]
    return lambda a: a.astype(np.float32).astype(dt).astype(np.float64)


def derive_w(u, g, t: float, eta: float, lam: float, scale: float):
    """The weights of the sums u, G at row counter t (float64)."""
    su, sg = u * scale, g * scale
    sign = np.where(su > 0.0, 1.0, -1.0)
    mog = sign * su / t - lam
    w = -sign * eta * t * mog / np.sqrt(np.maximum(sg, 1e-30))
    return np.where(mog < 0.0, 0.0, w)


def fired_rows(m) -> np.ndarray:
    """Which rows of a batch take a step: the hinge's branch."""
    return m < 1.0


def batch_increments(feature, du, n_features: int):
    """What one batch adds to (u, G) per feature: S and S^2, S the sum of
    the fired lanes' scaled gradients `du` by `feature`."""
    s = np.bincount(feature, du, minlength=n_features)
    return s, s * s


def train(ids, vals, labels, *, dims: int, mini_batch: int, epochs: int = 1,
          eta: float = 0.1, lam: float = 1e-6, scale: float = 100.0,
          storage: Optional[str] = None, slot_dtype: Optional[str] = None,
          emitted_feats: Optional[np.ndarray] = None, tau: float = 0.0):
    """Returns (feats, weights, info): model rows sorted by feature.

    `emitted_feats` (sorted) switches following on for rows with
    |m - 1| < tau that carry a witness (one epoch only); info counts them.
    """
    ids = np.asarray(ids, dtype=np.int64) % int(dims)
    vals = np.asarray(vals, dtype=np.float64)
    y = np.where(np.asarray(labels) > 0, 1.0, -1.0)
    rnd_w, rnd_slot = _rounder(storage), _rounder(slot_dtype)
    uid, inv = np.unique(ids, return_inverse=True)
    inv = inv.reshape(ids.shape)
    w = np.zeros(uid.size)
    u = np.zeros(uid.size)
    g = np.zeros(uid.size)
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
            m = yy * np.sum(w[idx] * x, axis=1)
            fired = fired_rows(m)
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
            f = np.nonzero(fired)[0]
            du = scale * (-yy[f])[:, None] * x[f]
            bu, binv = np.unique(idx[f].ravel(), return_inverse=True)
            su, sg = batch_increments(binv, du.ravel(), bu.size)
            u[bu] = rnd_slot(u[bu] + su)
            g[bu] = rnd_slot(g[bu] + sg)
            t = float(info["steps"])
            w[bu] = rnd_w(derive_w(u[bu], g[bu], t, eta, lam, scale))
            touched[bu] = True
    return uid[touched], w[touched], info


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
    feats, weights = emitted
    return {"feats": np.asarray(feats, np.int64),
            "tables": {"w": np.asarray(weights, np.float64)},
            "scalars": {}}


def reference(split, cfg: dict, epochs: int, prog: Optional[dict] = None,
              table_dtype: Optional[str] = None):
    """The reference's model of one split, and its run's notes. `table_dtype`
    is the control's: the storage of the two SLOTS (w keeps the
    configuration's)."""
    args = cfg.get("reference_args", {})
    feats, w, info = train(
        split.ids, split.vals, split.labels, dims=int(cfg["num_features"]),
        mini_batch=int(cfg["mini_batch"]), epochs=epochs,
        eta=float(args.get("eta", 0.1)), lam=float(args.get("lambda", 1e-6)),
        scale=float(args.get("scale", 100.0)), storage=args.get("storage"),
        slot_dtype=table_dtype,
        emitted_feats=None if prog is None else prog["feats"],
        tau=float(cfg.get("correct", {}).get("tau", 0.0)))
    info["zero_weights"] = int(np.count_nonzero(w == 0.0))
    return {"feats": feats, "tables": {"w": w}, "scalars": {}}, info


def score_rows(model: dict, ids, vals, cfg: dict) -> np.ndarray:
    return scores(model["feats"], model["tables"]["w"], ids, vals,
                  int(cfg["num_features"]))
