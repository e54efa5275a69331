"""Plain AROW on R mappers with MIX: `train_arow ... -mix <servers>` as one
call (LearnerBaseUDTF.java:92-103), from the published rules.

The call's rows are the mappers' splits laid end to end: replica r of R
trains the r-th contiguous share of `ceil(n / R)` rows (the last one
shorter), in mini-batches of B rows under the rule `refs/arow.py` states
(Crammer, Kulesza, Dredze 2009; Hivemall's accumulate-then-apply-the-mean).
The replicas walk their shares block by block, side by side. After every
`mix_every` blocks, and after the share's last block, they mix. For every
feature with an update pending on ANY replica since the last mix
(PartialArgminKLD.java:43-63, the server's reply; upstream's per-feature push
gate, MixClient.java:117-142, is not modelled, as the program says of itself):

    cov' = 1 / sum_r (1 / cov_r)        w' = cov' * sum_r (w_r / cov_r)

written to every replica, and the pending counts reset. A feature pending
nowhere keeps each replica's own value. `close()` emits one model: (feature,
weight, covariance) for every feature a fired row carried on any replica;
after the last mix the replicas agree on all of them.

All arithmetic is float64 on the ids the call touches; `table_dtype` rounds
both tables at every block's write and every mix's write (the storage the
configuration states, or a lower one in its place: the control). Rows within
`tau` of the firing boundary follow the program's decision where a WITNESS
shows it, exactly as in `refs/arow.py`: a feature that one row of the whole
call alone carries is emitted if and only if that row fired.

Imports nothing of the program.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from benchmark.refs.arow import _rounder, rows_of, score_rows  # noqa: F401


def shares_of(n_rows: int, replicas: int):
    each = -(-n_rows // replicas)
    return [(min(r * each, n_rows), min((r + 1) * each, n_rows))
            for r in range(replicas)]


def argmin_kld(w, cov, due, rnd):
    """The mix of one round over the columns `due`, in place on `w`, `cov`
    ([R, U] each)."""
    # a covariance that the control's storage rounded to zero mixes to
    # not-a-number, which the comparison reads as not correct
    with np.errstate(divide="ignore", invalid="ignore"):
        inv = 1.0 / cov[:, due]
        mixed_cov = 1.0 / inv.sum(axis=0)
        mixed_w = mixed_cov * (w[:, due] * inv).sum(axis=0)
    w[:, due] = rnd(mixed_w)
    cov[:, due] = rnd(mixed_cov)


def train(ids, vals, labels, *, dims: int, mini_batch: int, replicas: int,
          mix_every: int, epochs: int = 1, r: float = 0.1,
          table_dtype: Optional[str] = None,
          emitted_feats: Optional[np.ndarray] = None, tau: float = 0.0,
          mix: bool = True):
    """Returns (feats, weights, covars, info): the model rows sorted by
    feature, read off replica 0. `mix=False` leaves every round out (what a
    program that trained the replicas and never mixed them would emit:
    `benchmark/tools/mix_faults.py`)."""
    ids = np.asarray(ids, dtype=np.int64) % int(dims)
    vals = np.asarray(vals, dtype=np.float64)
    y = np.where(np.asarray(labels) > 0, 1.0, -1.0)
    rnd = _rounder(table_dtype)
    uid, inv = np.unique(ids, return_inverse=True)
    inv = inv.reshape(ids.shape)
    R = int(replicas)
    w = np.zeros((R, uid.size))
    cov = np.ones((R, uid.size))
    pending = np.zeros((R, uid.size), dtype=bool)
    touched = np.zeros(uid.size, dtype=bool)
    in_emitted = None
    if emitted_feats is not None and tau > 0 and int(epochs) <= 1:
        pos = np.searchsorted(emitted_feats, uid)
        pos[pos >= emitted_feats.size] = 0
        in_emitted = (emitted_feats[pos] == uid) if emitted_feats.size \
            else np.zeros(uid.size, dtype=bool)
        alone = np.bincount(inv.ravel(), minlength=uid.size) == 1
    info = {"ambiguous_rows": 0, "followed_rows": 0, "followed_margin": 0.0,
            "steps": 0, "mix_rounds": 0, "mix_due_entries": 0}
    shares = shares_of(ids.shape[0], R)
    n_blocks = max(1, -(-(shares[0][1] - shares[0][0]) // mini_batch))
    for _ in range(max(1, int(epochs))):
        for j in range(n_blocks):
            for rep, (lo, hi) in enumerate(shares):
                s = lo + j * mini_batch
                e = min(s + mini_batch, hi)
                if e <= s:
                    continue
                idx, x, yy = inv[s:e], vals[s:e], y[s:e]
                wg, cg = w[rep][idx], cov[rep][idx]
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
                info["steps"] += int(e - s)
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
                w[rep][bu] = rnd(w[rep][bu] + np.bincount(
                    binv, dw.ravel(), minlength=bu.size) / cnt)
                cov[rep][bu] = rnd(cov[rep][bu] + np.bincount(
                    binv, dc.ravel(), minlength=bu.size) / cnt)
                pending[rep][bu] = True
                touched[bu] = True
            if mix and ((j + 1) % mix_every == 0 or j == n_blocks - 1):
                due = np.nonzero(pending.any(axis=0))[0]
                argmin_kld(w, cov, due, rnd)
                pending[:] = False
                info["mix_rounds"] += 1
                info["mix_due_entries"] += int(due.size)
    return uid[touched], w[0][touched], cov[0][touched], info


# ---- the adapter the op kinds use (same three names in every reference) ----

def reference(split, cfg: dict, epochs: int, prog: Optional[dict] = None,
              table_dtype: Optional[str] = None):
    """The reference's mixed model of one call's rows, and its run's notes."""
    args = cfg.get("reference_args", {})
    feats, w, cov, info = train(
        split.ids, split.vals, split.labels, dims=int(cfg["num_features"]),
        mini_batch=int(cfg["mini_batch"]), replicas=int(args["replicas"]),
        mix_every=int(args["mix_every"]), epochs=epochs,
        r=float(args.get("r", 0.1)),
        table_dtype=table_dtype or args.get("storage"),
        emitted_feats=None if prog is None else prog["feats"],
        tau=float(cfg.get("correct", {}).get("tau", 0.0)))
    return {"feats": feats, "tables": {"w": w, "cov": cov}, "scalars": {}}, info
