"""Plain rating matrix factorization with AdaGrad, as Hivemall's
`train_mf_adagrad` defines it (`mf/MatrixFactorizationAdaGradUDTF.java`,
`mf/OnlineMatrixFactorizationUDTF.java`), a block of ratings at a time, in
float32 numpy.

Per block of B ratings (u, i, r), against the tables at the block's start,
with t the rating's 1-based position in the call, eta(t) = eta0 / t^power_t
(`-eta0`, `-power_t`: invscaling, the default estimator), lambda, eps, s
the scale (x100) and mu the mean rating:

    err  = r - (mu + Bu[u] + Bi[i] + P[u] . Q[i])
    gp   = err Q[i] - lambda P[u]         gq = err P[u] - lambda Q[i]
    dGp  = gp (gp / s)                    dGq = gq (gq / s)
    dP   = eta0 / sqrt(eps + (Gp[u] + dGp) s) gp,   dQ likewise
    dBu  = eta(t) (err - lambda Bu[u])    dBi = eta(t) (err - lambda Bi[i])

then every table adds its rows' deltas at their ids (a user or an item that
repeats in the block adds each of its rows' deltas). `train_mf_sgd`'s rule
(`reference_args.rule` "sgd") steps P and Q by eta(t) gp and eta(t) gq and
keeps no accumulators; `use_bias` false (`-disable_bias`) keeps both biases
at 0. `close()` emits
(user, P[u], Bu[u]) for every user a rating touched, (item, Q[i], Bi[i]) for
every item, and mu.

The initial tables are data made from `-seed` (`reference_args.seed`):
`jax.random.uniform` over `[num_users, k]` and `[num_items, k]` in [0, maxval)
under the two halves of `jax.random.split(jax.random.PRNGKey(seed))`
(`train_mf_adagrad -help`, `-rankinit random`). They are computed here for
the rows a split touches, with threefry-2x32 written out in numpy as JAX
defines it (the partitionable form: element n's bits are the xor of the two
words of threefry(key, (n >> 32, n & 0xffffffff))); `tests/test_mf_reference.py`
holds it to `jax.random.uniform`.

**Departures from upstream**, each the port's and the reference's alike:

- the block. Upstream updates a rating at a time, so each rating reads the
  tables after the one before it; here every rating of a block reads the
  block's starting tables and the deltas are summed, so for B > 1 the model
  differs from upstream's on the same ratings. `-mini_batch 1` is upstream's
  rule.
- the AdaGrad accumulator a rating reads is the block's starting one plus
  its own dGp, not the sum of the block's earlier ratings' too.
- the tables are dense arrays of a fixed size (`-num_users`, `-num_items`)
  where upstream grows hash maps; an id outside them is refused.

`control` (`table_dtype="bfloat16"`) rounds the four row tables (P, Q and
their accumulators) to bfloat16 at every block's write. The hook
`item_lanes` (which of a block's item lanes the item side adds) is where
`benchmark/tools/mf_faults.py` plants its fault; the reference itself has
no switch for it.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_M32 = np.uint64(0xFFFFFFFF)


def _rotl(x, r):
    return ((x << np.uint32(r)) | (x >> np.uint32(32 - r))).astype(np.uint32)


def threefry2x32(k0: int, k1: int, x0: np.ndarray, x1: np.ndarray):
    """Threefry-2x32, 20 rounds (Salmon et al., SC 2011), as JAX runs it."""
    ks = [np.uint32(k0), np.uint32(k1),
          np.uint32(k0) ^ np.uint32(k1) ^ np.uint32(0x1BD11BDA)]
    x0 = (x0.astype(np.uint32) + ks[0]).astype(np.uint32)
    x1 = (x1.astype(np.uint32) + ks[1]).astype(np.uint32)
    with np.errstate(over="ignore"):
        for n in range(5):
            for r in _ROTATIONS[n % 2]:
                x0 = (x0 + x1).astype(np.uint32)
                x1 = _rotl(x1, r) ^ x0
            x0 = (x0 + ks[(n + 1) % 3]).astype(np.uint32)
            x1 = (x1 + ks[(n + 2) % 3] + np.uint32(n + 1)).astype(np.uint32)
    return x0, x1


def split_key(seed: int):
    """The two keys of `jax.random.split(jax.random.PRNGKey(seed))`."""
    seed = int(seed)
    b0, b1 = threefry2x32(seed >> 32 & 0xFFFFFFFF, seed & 0xFFFFFFFF,
                          np.zeros(2, np.uint32), np.arange(2, dtype=np.uint32))
    return (int(b0[0]), int(b1[0])), (int(b0[1]), int(b1[1]))


def uniform_rows(key, rows: np.ndarray, k: int, maxval: float) -> np.ndarray:
    """Rows `rows` of `jax.random.uniform(key, (N, k), maxval=maxval)`."""
    n = (np.asarray(rows, np.uint64)[:, None] * np.uint64(k)
         + np.arange(k, dtype=np.uint64)[None, :])
    hi = (n >> np.uint64(32)).astype(np.uint32)
    lo = (n & _M32).astype(np.uint32)
    b0, b1 = threefry2x32(key[0], key[1], hi, lo)
    bits = (b0 ^ b1) >> np.uint32(9) | np.uint32(0x3F800000)
    u = bits.view(np.float32) - np.float32(1.0)
    return np.maximum(np.float32(0.0), u * np.float32(maxval))


def _rounder(dtype: Optional[str]):
    if dtype in (None, "float32"):
        return None
    import ml_dtypes

    low = np.dtype(getattr(ml_dtypes, dtype))
    return lambda a: a.astype(low).astype(np.float32)


def block_rows(n: int, block: int):
    """The (start, end) of each block of a call of n ratings."""
    return [(s, min(s + block, n)) for s in range(0, n, block)]


def item_lanes(ci: np.ndarray) -> np.ndarray:
    """Which of a block's item lanes the item side adds: every one."""
    return np.ones(len(ci), bool)


def reference(split, cfg: dict, epochs: int = 1,
              table_dtype: Optional[str] = None) -> dict:
    """The model `close()` emits after `epochs` passes over the split:
    {"users", "P", "Bu", "items", "Q", "Bi", "mu", "steps"}."""
    a = cfg["reference_args"]
    f32 = np.float32
    k, lam = int(a["factors"]), f32(a["lambda"])
    eta0, power_t = f32(a["eta0"]), f32(a["power_t"])
    eps, s = f32(a["eps"]), f32(a["scale"])
    mu = f32(a["mu"])
    adagrad = a.get("rule", "adagrad") == "adagrad"
    bias = f32(1.0 if a.get("use_bias", True) else 0.0)
    rnd = _rounder(table_dtype)
    users, cu = np.unique(split.users, return_inverse=True)
    items, ci_all = np.unique(split.items, return_inverse=True)
    ku, ki = split_key(a["seed"])
    P = uniform_rows(ku, users, k, a["maxval"])
    Q = uniform_rows(ki, items, k, a["maxval"])
    if rnd is not None:
        P, Q = rnd(P), rnd(Q)
    Gp = np.zeros_like(P)
    Gq = np.zeros_like(Q)
    Bu = np.zeros(len(users), f32)
    Bi = np.zeros(len(items), f32)
    r_all = np.asarray(split.ratings, f32)
    t0 = 0
    for _ in range(epochs):
        for lo, hi in block_rows(len(r_all), int(cfg["mini_batch"])):
            u, i, r = cu[lo:hi], ci_all[lo:hi], r_all[lo:hi]
            t = (np.arange(t0 + lo + 1, t0 + hi + 1)).astype(f32)
            eta = eta0 / np.power(t, power_t)
            Pu, Qi, bu, bi = P[u], Q[i], Bu[u], Bi[i]
            err = r - (mu + bu + bi + np.sum(Pu * Qi, axis=1))
            e = err[:, None]
            gp = e * Qi - lam * Pu
            gq = e * Pu - lam * Qi
            if adagrad:
                dgp, dgq = gp * (gp / s), gq * (gq / s)
                dP = eta0 / np.sqrt(eps + (Gp[u] + dgp) * s) * gp
                dQ = eta0 / np.sqrt(eps + (Gq[i] + dgq) * s) * gq
            else:
                dP, dQ = eta[:, None] * gp, eta[:, None] * gq
            dbu = bias * eta * (err - lam * bu)
            dbi = bias * eta * (err - lam * bi)
            keep = item_lanes(i)
            _add(P, u, dP, rnd)
            _add(Bu, u, dbu)
            _add(Q, i[keep], dQ[keep], rnd)
            _add(Bi, i[keep], dbi[keep])
            if adagrad:
                _add(Gp, u, dgp, rnd)
                _add(Gq, i[keep], dgq[keep], rnd)
        t0 += len(r_all)
    return {"users": users.astype(np.int64), "P": P, "Bu": Bu,
            "items": items.astype(np.int64), "Q": Q, "Bi": Bi,
            "mu": float(mu), "steps": int(epochs * len(r_all))}


def _add(table: np.ndarray, ids: np.ndarray, deltas: np.ndarray,
         rnd=None) -> None:
    """The deltas added at their ids in place; `rnd` rounds the rows written
    to the table's storage."""
    np.add.at(table, ids, deltas)
    if rnd is not None:
        table[ids] = rnd(table[ids])


def rows_of(emitted: dict) -> dict:
    """The program's `model_rows()` in the reference's form."""
    users, P, Bu = emitted["users"]
    items, Q, Bi = emitted["items"]
    return {"users": np.asarray(users, np.int64), "P": np.asarray(P),
            "Bu": np.asarray(Bu), "items": np.asarray(items, np.int64),
            "Q": np.asarray(Q), "Bi": np.asarray(Bi),
            "mu": float(emitted["mu"])}


def predict(model: dict, users, items) -> np.ndarray:
    """mu + Bu + Bi + P[u] . Q[i] on the emitted rows, as a LEFT OUTER JOIN
    of the ratings with them scores: a side that was not emitted adds 0."""
    def lookup(ids, keys, *tables):
        at = np.searchsorted(keys, ids)
        at = np.minimum(at, max(len(keys) - 1, 0))
        found = (keys[at] == ids) if len(keys) else np.zeros(len(ids), bool)
        return found, [np.where(found.reshape((-1,) + (1,) * (t.ndim - 1)),
                                t[at], 0.0) for t in tables]

    fu, (Pu, bu) = lookup(np.asarray(users, np.int64), model["users"],
                          model["P"], model["Bu"])
    fi, (Qi, bi) = lookup(np.asarray(items, np.int64), model["items"],
                          model["Q"], model["Bi"])
    return (model["mu"] + bu + bi
            + np.sum(np.asarray(Pu, np.float64) * Qi, axis=1))


def rmse(model: dict, heldout) -> float:
    err = predict(model, heldout.users, heldout.items) - heldout.ratings
    return float(np.sqrt(np.mean(err * err)))
