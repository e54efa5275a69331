"""Plain field-aware factorization machine (Juan, Zhuang, Chin, Lin, RecSys
2016) on hashed (feature, field) entries, trained under `train_ffm`'s
mini-batch rule: AdaGrad on V, FTRL-proximal on w, no bias.

    entry e(i, j) = h(feature_i, field_j)              (`pair_hash`, below)
    p = sum_i w_i x_i + sum_{i<j} <V[e(i, j)], V[e(j, i)]> x_i x_j
    g = (sigmoid(p y) - 1) y,  y in {-1, +1}
    for every ordered pair i != j of a row's non-zero lanes:
        grad = g x_i x_j V[e(j, i)]
        dV[e(i, j)]  = -eta0_V / sqrt(eps + gg[e(i, j)]) (grad + 2 lambda V[e(i, j)])
        dgg[e(i, j)] = |grad|^2
    for every lane i:  grad_i = g x_i,  dn_i = grad_i^2,
        dz_i = grad_i - (sqrt(n_i + dn_i) - sqrt(n_i)) / alpha * w_i

(FieldAwareFactorizationMachineModel.java:126-181, updateWiFTRL,
FFMStringFeatureMapModel.java:133-157.) The field of a lane is its column,
as `ffm_features` numbers a table's columns.

Departures from upstream, each also the program's and documented there:

- the gradient's `x_j`: upstream's sumVfX multiplies by `x_i` where the
  derivative has `x_j`; the two agree on one-hot rows;
- the block rule: per mini-batch every term above is computed against the
  tables as the batch found them, and V, gg, z and n take the SUM of their
  lanes' deltas (upstream is a row at a time);
- the FTRL weight is derived once a batch from the summed duals,
  `w = 0 if |z| <= lambda1 else (sign(z) lambda1 - z) / ((beta + sqrt(n)) / alpha + lambda2)`;
- entries live in one hashed table of `v_dims` rows, so two (feature, field)
  pairs may share an entry; both then read and move the same values.

V starts at `initial_v(entry)`, a hash of (seed, entry, factor) stated in
`train_ffm -help` and the configuration: the reference computes it itself
for the entries the split touches, and for a held-out row's entries that no
row trained. float64 on the touched entries; `table_dtype` rounds every
table after every batch's write (the control). `close()` emits `(feature,
w)` for every feature a row carried and `(entry, V[k])` for every entry a
row's pair addressed.

A model here is ONE key space: the linear features (below 2^31, the most
`-feature_hashing` allows), then the entries at `ENTRY_KEY0 + entry`. Its `v` table holds zeros on the
linear keys; its `w` table holds `W_FLOOR` on the entry keys, on both
sides, so that the comparison's floor for the linear weights (the median
|reference entry| of the table, which the entries outnumber forty to one)
is that constant and not zero: an FTRL weight just past its L1 threshold is
as small as one likes.
"""

from __future__ import annotations

import math
from typing import Optional

import numpy as np

from .arow import _rounder

W_FLOOR = 0.01
ENTRY_KEY0 = 1 << 32
_U32 = np.uint32


def pair_hash(feature, field, dv: int) -> np.ndarray:
    """(feature, field) -> entry of the V table, uint32 arithmetic."""
    h = np.asarray(feature).astype(_U32) * _U32(0x9E3779B1) \
        + np.asarray(field).astype(_U32) * _U32(0x85EBCA6B)
    h ^= h >> _U32(15)
    h *= _U32(0x2C1B3C6D)
    h ^= h >> _U32(12)
    return (h % _U32(dv)).astype(np.int64)


def _fmix32(h: np.ndarray) -> np.ndarray:
    h = h ^ (h >> _U32(16))
    h = h * _U32(0x7FEB352D)
    h = h ^ (h >> _U32(15))
    h = h * _U32(0x846CA68B)
    return h ^ (h >> _U32(16))


def initial_v(entries: np.ndarray, factors: int, seed: int,
              sigma: float) -> np.ndarray:
    """[n, factors] float64: the float32 values the entry point documents,
    `(u1 + u2 + u3 + u4 - 131070) * float32(sqrt(3) sigma / 65536)` with
    four 16-bit uniforms from two hashes of (entry, factor, seed)."""
    f = np.arange(factors, dtype=_U32)
    a = np.asarray(entries).astype(_U32)[:, None] * _U32(0x9E3779B1) \
        + f * _U32(0x85EBCA6B) + _U32((int(seed) * 0xC2B2AE35) & 0xFFFFFFFF)
    h1 = _fmix32(a)
    h2 = _fmix32(a ^ _U32(0x68E31DA4))
    lo = _U32(0xFFFF)
    s = (h1 & lo).astype(np.int64) + (h1 >> _U32(16)) + (h2 & lo) \
        + (h2 >> _U32(16))
    scale = np.float32(math.sqrt(3.0) * float(sigma) / 65536.0)
    return ((s - 131070).astype(np.float32) * scale).astype(np.float64)


def _compact(keys: np.ndarray, space: int):
    """(sorted distinct keys, each key's position among them)."""
    present = np.zeros(int(space), np.bool_)
    present[keys] = True
    distinct = np.flatnonzero(present)
    slot = np.empty(int(space), np.int32)
    slot[distinct] = np.arange(distinct.size, dtype=np.int32)
    return distinct, slot[keys]


def _pair_keys(ids: np.ndarray, fields: np.ndarray, dv: int,
               fault: Optional[str]) -> np.ndarray:
    """[n, K, K]: entry of lane i against lane j's field."""
    n, k = ids.shape
    partner = np.broadcast_to(fields[None, None, :], (n, k, k))
    if fault == "own_field":   # FM's term under FFM's name
        partner = np.broadcast_to(fields[None, :, None], (n, k, k))
    return pair_hash(ids[:, :, None], partner, dv)


def _ftrl_w(z, n, alpha, beta, lambda1, lambda2):
    return np.where(np.abs(z) <= lambda1, 0.0,
                    (np.sign(z) * lambda1 - z)
                    / ((beta + np.sqrt(n)) / alpha + lambda2))


def _sum_into(slots: np.ndarray, *pairs):
    """For each (table, deltas): table[slot] += the sum of its lanes'
    deltas, in float64 (the lanes sorted by slot, each run summed).
    Returns the distinct slots."""
    order = np.argsort(slots, kind="stable")
    ranked = slots[order]
    starts = np.flatnonzero(np.r_[True, ranked[1:] != ranked[:-1]])
    distinct = ranked[starts]
    for table, deltas in pairs:
        table[distinct] += np.add.reduceat(deltas[order], starts, axis=0)
    return distinct


def train(ids, vals, labels, *, num_features: int, v_dims: int,
          mini_batch: int, epochs: int = 1, factors: int = 4, seed: int = 31,
          sigma: float = 0.1, lambda0: float = 0.01, eta0_v: float = 1.0,
          eps: float = 1.0, alpha: float = 0.1, beta: float = 1.0,
          lambda1: float = 0.1, lambda2: float = 0.01, num_fields: int = 1024,
          table_dtype: Optional[str] = None, fault: Optional[str] = None):
    """Returns (feats, w, v_keys, V, info). `fault="own_field"` plants a
    wrong program in the reference's place: a lane paired with its OWN
    field's entry (`reference` plants `half_block` by the rows it hands)."""
    ids = np.asarray(ids, dtype=np.int64) % int(num_features)
    x_all = np.asarray(vals, dtype=np.float64)
    y_all = np.where(np.asarray(labels) > 0, 1.0, -1.0)
    n_rows, k_lanes = ids.shape
    fields = np.arange(k_lanes, dtype=np.int64) % int(num_fields)
    rnd = _rounder(table_dtype)

    real_all = (x_all != 0)[:, :, None] & (x_all != 0)[:, None, :] \
        & ~np.eye(k_lanes, dtype=bool)
    keys = _pair_keys(ids, fields, v_dims, fault)
    v_keys, slot_real = _compact(keys[real_all], v_dims)
    # a pair that is not real reads and moves one spare row of zeros, the
    # last: no term of it is ever other than zero
    slots = np.full(keys.shape, v_keys.size, np.int32)
    slots[real_all] = slot_real
    del keys, slot_real, real_all
    feats, wpos = np.unique(ids, return_inverse=True)
    wpos = wpos.reshape(ids.shape)

    v = np.concatenate([rnd(initial_v(v_keys, factors, seed, sigma)),
                        np.zeros((1, factors))])
    gg = np.zeros(v_keys.size + 1)
    w = np.zeros(feats.size)
    z = np.zeros(feats.size)
    n = np.zeros(feats.size)
    t = 0
    for _ in range(max(1, int(epochs))):
        for s in range(0, n_rows, mini_batch):
            e = min(s + mini_batch, n_rows)
            x, y = x_all[s:e], y_all[s:e]
            sl = slots[s:e]
            vg = v[sl]                                      # [b, K, K, k]
            vgt = vg.transpose(0, 2, 1, 3)
            xx = x[:, :, None] * x[:, None, :]
            wi = wpos[s:e]
            p = 0.5 * np.einsum("bijf,bjif,bij->b", vg, vg, xx) \
                + np.sum(w[wi] * x, axis=1)
            g = (1.0 / (1.0 + np.exp(-p * y)) - 1.0) * y
            grad = (g[:, None, None] * xx)[..., None] * vgt
            dgg = np.einsum("bijf,bijf->bij", grad, grad)
            # dv = -eta0_V / sqrt(eps + gg) (grad + 2 lambda V), in place
            dv = vg * (2.0 * lambda0)
            dv += grad
            dv *= (-eta0_v / np.sqrt(eps + gg[sl]))[..., None]
            bv = _sum_into(sl.ravel(), (v, dv.reshape(-1, factors)),
                           (gg, dgg.ravel()))
            gi = g[:, None] * x
            n_old = n[wi]
            dn = gi * gi
            dz = gi - (np.sqrt(n_old + dn) - np.sqrt(n_old)) / alpha * w[wi]
            bw = _sum_into(wi.ravel(), (z, dz.ravel()), (n, dn.ravel()))
            z[bw], n[bw] = rnd(z[bw]), rnd(n[bw])
            w[bw] = rnd(_ftrl_w(z[bw], n[bw], alpha, beta, lambda1, lambda2))
            if table_dtype is not None:
                v[bv], gg[bv] = rnd(v[bv]), rnd(gg[bv])
            t += e - s
    v = v[:-1]
    return feats, w, v_keys, v, {"steps": t}


_ROWS_KEYS: dict = {}   # the last scored rows' distinct entries, kept


def _rows_entries(ids, x, fields, v_dims, factors, seed, sigma):
    """(distinct entries of the rows' real pairs, each pair's position among
    them, their initial values): the same held-out rows are scored once a
    compared call and once a reference, so the last rows' are kept."""
    tag = (ids.__array_interface__["data"][0], ids.shape, int(v_dims),
           int(factors), int(seed), float(sigma))
    if _ROWS_KEYS.get("tag") != tag:
        real = (x != 0)[:, :, None] & (x != 0)[:, None, :] \
            & ~np.eye(ids.shape[1], dtype=bool)
        distinct, inverse = np.unique(
            _pair_keys(ids, fields, v_dims, None)[real], return_inverse=True)
        _ROWS_KEYS.clear()
        _ROWS_KEYS.update(tag=tag, real=real, distinct=distinct,
                          inverse=inverse,
                          initial=initial_v(distinct, factors, seed, sigma))
    return _ROWS_KEYS


def scores(feats, w, v_keys, v, ids, vals, *, num_features: int, v_dims: int,
           factors: int, seed: int, sigma: float,
           num_fields: int = 1024) -> np.ndarray:
    """FFM prediction of each row from emitted rows: a feature that was not
    emitted weighs 0, an entry that was not emitted holds `initial_v`."""
    ids = np.asarray(ids, dtype=np.int64) % int(num_features)
    x = np.asarray(vals, np.float64)
    n_rows, k_lanes = ids.shape
    fields = np.arange(k_lanes, dtype=np.int64) % int(num_fields)
    out = np.zeros(n_rows)
    if feats.size:
        pos = np.clip(np.searchsorted(feats, ids), 0, feats.size - 1)
        out += np.sum(np.where(feats[pos] == ids,
                               np.asarray(w, np.float64)[pos], 0.0) * x, axis=1)
    rows = _rows_entries(ids, x, fields, v_dims, factors, seed, sigma)
    table = rows["initial"].copy()
    if v_keys.size:
        pos = np.clip(np.searchsorted(v_keys, rows["distinct"]), 0,
                      v_keys.size - 1)
        hit = v_keys[pos] == rows["distinct"]
        table[hit] = np.asarray(v, np.float64)[pos[hit]]
    real = rows["real"]
    for s in range(0, n_rows, 1024):       # [1024, K, K, k] at a time
        m = real[s:s + 1024]
        lo = int(np.count_nonzero(real[:s]))
        vg = np.zeros(m.shape + (factors,))
        vg[m] = table[rows["inverse"][lo:lo + int(np.count_nonzero(m))]]
        xs = x[s:s + 1024]
        xx = xs[:, :, None] * xs[:, None, :]
        out[s:s + 1024] += 0.5 * np.sum(
            np.sum(vg * vg.transpose(0, 2, 1, 3), axis=-1) * xx, axis=(1, 2))
    return out


# ---- the adapter the op kinds use (same three names in every reference) ----

def _one_key_space(w0, feats, w, v_keys, v) -> dict:
    feats = np.asarray(feats, np.int64)
    v_keys = np.asarray(v_keys, np.int64)
    v = np.asarray(v, np.float64).reshape(v_keys.size, -1)
    return {"feats": np.concatenate([feats, ENTRY_KEY0 + v_keys]),
            "tables": {
                "w": np.concatenate([np.asarray(w, np.float64),
                                     np.full(v_keys.size, W_FLOOR)]),
                "v": np.concatenate([np.zeros((feats.size, v.shape[1])), v])},
            "scalars": {"w0": float(w0)},
            "linear_rows": int(feats.size)}


def rows_of(emitted) -> dict:
    """What `TrainedFFMModel.model_rows()` returned, as a model dict."""
    return _one_key_space(*emitted)


def _args(cfg: dict) -> dict:
    a = cfg.get("reference_args", {})
    return {"num_features": int(cfg["num_features"]),
            "v_dims": int(cfg["v_dims"]),
            "factors": int(a.get("factors", 4)), "seed": int(a.get("seed", 31)),
            "sigma": float(a.get("sigma", 0.1)),
            "num_fields": int(a.get("num_fields", 1024))}


def reference(split, cfg: dict, epochs: int, prog: Optional[dict] = None,
              table_dtype: Optional[str] = None, fault: Optional[str] = None):
    """The reference's model of one split, and its run's notes. `fault`
    puts a wrong program in its place: `half_block` trains the first half
    of every batch's rows alone, `own_field` is `train`'s."""
    a = cfg.get("reference_args", {})
    rates = {k: float(a[k]) for k in ("lambda0", "eta0_v", "eps", "alpha",
                                      "beta", "lambda1", "lambda2") if k in a}
    ids, vals, labels = split.ids, split.vals, split.labels
    batch = int(cfg["mini_batch"])
    if fault == "half_block":
        keep = (np.arange(len(ids)) % batch) < batch // 2
        ids, vals, labels, batch = ids[keep], vals[keep], labels[keep], \
            batch // 2
    feats, w, v_keys, v, info = train(
        ids, vals, labels, mini_batch=batch, epochs=epochs,
        table_dtype=table_dtype, fault=fault, **_args(cfg), **rates)
    return _one_key_space(0.0, feats, w, v_keys, v), info


def score_rows(model: dict, ids, vals, cfg: dict) -> np.ndarray:
    """A model's scores of the rows; kept with the model, since a split's
    reference scores the same held-out rows once a compared call."""
    kept = model.setdefault("_scores", {})
    tag = (ids.__array_interface__["data"][0], ids.shape)
    if tag not in kept:
        kept.clear()
        kept[tag] = _score_rows(model, ids, vals, cfg)
    return kept[tag]


def _score_rows(model: dict, ids, vals, cfg: dict) -> np.ndarray:
    n_lin = model["linear_rows"]
    return scores(model["feats"][:n_lin], model["tables"]["w"][:n_lin],
                  model["feats"][n_lin:] - ENTRY_KEY0,
                  model["tables"]["v"][n_lin:], ids, vals, **_args(cfg))
