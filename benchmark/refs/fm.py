"""Plain factorization machine (Rendle, ICDM 2010) trained by SGD under
Hivemall's mini-batch rule, classification (`train_fm -c`).

    p = w0 + sum_i w_i x_i + 1/2 sum_f [(sum_i V_if x_i)^2 - sum_i V_if^2 x_i^2]
    g = (sigmoid(p y) - 1) y,  y in {-1, +1}
    eta_t = eta0 / t^power_t, t the 1-based count of rows seen
    dw0 = -eta (g + 2 lambda w0)
    dw_i = -eta (g x_i + 2 lambda w_i)
    dV_if = -eta (g (x_i sum_j V_jf x_j - V_if x_i^2) + 2 lambda V_if)

(FactorizationMachineUDTF.java:115-560, FactorizationMachineModel.java:118-300).
Per mini-batch every delta is computed against the parameters at the batch's
start and applied as its per-feature mean (w0: the mean over the rows), the
reference's FloatAccumulator rule. V starts as `sigma * N(0, 1)` drawn by
`jax.random.normal(PRNGKey(seed), (dims, factors))`, which is the entry
point's documented initialisation: the reference draws it itself (a library
call, nothing the program made) and keeps the rows the split touches.

float64 on the touched ids; `table_dtype` rounds w and V after every batch's
write (the control: a lower storage precision in the reference's place).
`close()` emits w0 and (feature, w_i, V_i) for every feature a row carried.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .arow import _rounder


def initial_factors(dims: int, factors: int, seed: int, sigma: float,
                    uid: np.ndarray) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    v = jax.random.normal(jax.random.PRNGKey(int(seed)), (int(dims), factors),
                          dtype=jnp.float32) * sigma
    # the whole table to the host, rows picked there: a device gather would
    # compile anew for every split's count of distinct ids
    return np.asarray(v)[uid].astype(np.float64)


def train(ids, vals, labels, *, dims: int, mini_batch: int, epochs: int = 1,
          factors: int = 5, seed: int = 31, sigma: float = 0.1,
          lambda0: float = 0.01, eta0: float = 0.05, power_t: float = 0.1,
          table_dtype: Optional[str] = None):
    """Returns (w0, feats, w, V[:, :factors], info)."""
    ids = np.asarray(ids, dtype=np.int64) % int(dims)
    vals = np.asarray(vals, dtype=np.float64)
    y = np.where(np.asarray(labels) > 0, 1.0, -1.0)
    rnd = _rounder(table_dtype)
    uid, inv = np.unique(ids, return_inverse=True)
    inv = inv.reshape(ids.shape)
    w0 = 0.0
    w = np.zeros(uid.size)
    v = rnd(initial_factors(dims, factors, seed, sigma, uid))
    n = ids.shape[0]
    t = 0
    for _ in range(max(1, int(epochs))):
        for s in range(0, n, mini_batch):
            idx = inv[s:s + mini_batch]
            x = vals[s:s + mini_batch]
            yy = y[s:s + mini_batch]
            b = idx.shape[0]
            ts = t + 1.0 + np.arange(b)
            eta = eta0 / np.power(np.maximum(ts, 1.0), power_t)
            wg = w[idx]                                  # [b, K]
            vg = v[idx]                                  # [b, K, k]
            vx = vg * x[:, :, None]
            sum_vfx = vx.sum(axis=1)                     # [b, k]
            p = w0 + np.sum(wg * x, axis=1) + 0.5 * np.sum(
                sum_vfx * sum_vfx - np.sum(vx * vx, axis=1), axis=1)
            z = p * yy
            g = (1.0 / (1.0 + np.exp(-z)) - 1.0) * yy
            dw0 = -eta * (g + 2.0 * lambda0 * w0)
            dw = -eta[:, None] * (g[:, None] * x + 2.0 * lambda0 * wg)
            grad_v = x[:, :, None] * sum_vfx[:, None, :] \
                - vg * (x * x)[:, :, None]
            dv = -eta[:, None, None] * (g[:, None, None] * grad_v
                                        + 2.0 * lambda0 * vg)
            bu, binv = np.unique(idx.ravel(), return_inverse=True)
            cnt = np.bincount(binv, minlength=bu.size).astype(np.float64)
            w[bu] = rnd(w[bu] + np.bincount(binv, dw.ravel(),
                                            minlength=bu.size) / cnt)
            dv2 = dv.reshape(-1, factors)
            acc = np.stack([np.bincount(binv, dv2[:, f], minlength=bu.size)
                            for f in range(factors)], axis=1)
            v[bu] = rnd(v[bu] + acc / cnt[:, None])
            w0 = w0 + float(np.sum(dw0)) / b
            t += b
    return w0, uid, w, v, {"steps": t}


def scores(w0, feats, w, v, ids, vals, dims: int) -> np.ndarray:
    """FM prediction of each row from model rows (absent feature: zeros)."""
    ids = np.asarray(ids, dtype=np.int64) % int(dims)
    x = np.asarray(vals, np.float64)
    if feats.size == 0:
        return np.full(ids.shape[0], float(w0))
    pos = np.clip(np.searchsorted(feats, ids), 0, feats.size - 1)
    hit = feats[pos] == ids
    wg = np.where(hit, np.asarray(w, np.float64)[pos], 0.0)
    vg = np.where(hit[..., None], np.asarray(v, np.float64)[pos], 0.0)
    vx = vg * x[..., None]
    s = vx.sum(axis=1)
    return w0 + np.sum(wg * x, axis=1) + 0.5 * np.sum(
        s * s - np.sum(vx * vx, axis=1), axis=1)


# ---- the adapter the op kinds use (same three names in every reference) ----

def rows_of(emitted) -> dict:
    """What `TrainedFMModel.model_rows()` returned, as a model dict."""
    w0, feats, w, v = emitted
    return {"feats": np.asarray(feats, np.int64),
            "tables": {"w": np.asarray(w, np.float64),
                       "v": np.asarray(v, np.float64)},
            "scalars": {"w0": float(w0)}}


def reference(split, cfg: dict, epochs: int, prog: Optional[dict] = None,
              table_dtype: Optional[str] = None):
    """The reference's model of one split, and its run's notes."""
    a = cfg.get("reference_args", {})
    w0, feats, w, v, info = train(
        split.ids, split.vals, split.labels, dims=int(cfg["num_features"]),
        mini_batch=int(cfg["mini_batch"]), epochs=epochs,
        factors=int(a["factors"]), seed=int(a.get("seed", 31)),
        sigma=float(a.get("sigma", 0.1)), lambda0=float(a.get("lambda0", 0.01)),
        eta0=float(a.get("eta0", 0.05)), power_t=float(a.get("power_t", 0.1)),
        table_dtype=table_dtype)
    return {"feats": feats, "tables": {"w": w, "v": v},
            "scalars": {"w0": w0}}, info


def score_rows(model: dict, ids, vals, cfg: dict) -> np.ndarray:
    return scores(model["scalars"]["w0"], model["feats"], model["tables"]["w"],
                  model["tables"]["v"], ids, vals, int(cfg["num_features"]))
