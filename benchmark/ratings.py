"""Rating splits of a user x item table, made from parameters in a
configuration's `data` group: the generator of the rating cells (op kind
`train_call_ratings`), beside `datagen.py`'s hashed-feature rows.

- Users: each of `num_users` ids has an activity `c_u = M ** h(u)`, with
  `h(u)` a hash of (seed, u) in [0, 1), so the counts are log-uniform on
  [1, M] and the active ids are spread over the table as a hash spreads
  them. `M` (`user_count_max`) is set so that the mean count is the
  source's ratings per user. A rating's user is drawn with probability
  proportional to `c_u` (by rejection: an id drawn uniformly is kept with
  probability `c_u / M`), so a split of a few million ratings out of
  billions meets almost every user once.
- Items: Zipf with exponent `item_zipf` over `num_items` ranks, rank r
  placed at id `(r * ITEM_STRIDE + offset) % num_items`, a bijection.
- Ratings: a planted rank-`rank` model with item biases,
  `center + c_i + a_u . b_i` plus Gaussian noise of `noise`, clipped to
  `[low, high]`; `a_u` and `b_i` are Gaussian of `planted_scale` a
  coordinate and `c_i` Gaussian of `item_bias`, hashes of (seed, id,
  coordinate), so every split and the held-out ratings share one model.

Ratings are drawn independently, so a split's order is already shuffled, as
a `CLUSTER BY rand()` split is. Everything is a pure function of
(data parameters, sizes, seed, split index).
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .datagen import mix64

_U64 = np.uint64
_COUNT_SALT = 0x6A09E667F3BCC909
_USER_SALT = 0xBB67AE8584CAA73B
_ITEM_SALT = 0x3C6EF372FE94F82B
_BIAS_SALT = 0xA54FF53A5F1D36F1
ITEM_STRIDE = 7919          # a prime: a bijection mod any table it does not divide


@dataclass
class RatingSplit:
    users: np.ndarray     # int32 [n]
    items: np.ndarray     # int32 [n]
    ratings: np.ndarray   # float32 [n]

    @property
    def rows(self) -> int:
        return len(self.ratings)

    @property
    def labels(self) -> np.ndarray:
        return self.ratings

    def head(self, rows: int) -> "RatingSplit":
        return RatingSplit(self.users[:rows], self.items[:rows],
                           self.ratings[:rows])


def _unit(x: np.ndarray) -> np.ndarray:
    """A uint64 hash -> float64 in [0, 1)."""
    return (mix64(x) >> _U64(11)).astype(np.float64) * 2.0 ** -53


def _salted(ids, seed: int, salt: int) -> np.ndarray:
    return np.asarray(ids, _U64) ^ mix64(np.asarray([seed], _U64) ^ _U64(salt))


def user_activity(users, seed: int, count_max: float) -> np.ndarray:
    """c_u / M in (0, 1]: the chance that a uniformly drawn id is kept."""
    h = _unit(_salted(users, seed, _COUNT_SALT))
    return np.exp((h - 1.0) * np.log(count_max))


def draw_users(rng, n: int, num_users: int, seed: int,
               count_max: float) -> np.ndarray:
    out, have = [], 0
    while have < n:
        cand = rng.integers(0, num_users, size=8 * (n - have) + 1024)
        keep = cand[rng.random(len(cand)) < user_activity(cand, seed, count_max)]
        out.append(keep[:n - have])
        have += len(out[-1])
    return np.concatenate(out).astype(np.int32)


def draw_items(rng, n: int, num_items: int, zipf: float,
               offset: int) -> np.ndarray:
    cdf = np.cumsum(1.0 / np.arange(1, num_items + 1) ** zipf)
    rank = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    rank = np.minimum(rank, num_items - 1)
    return ((rank.astype(np.int64) * ITEM_STRIDE + offset)
            % num_items).astype(np.int32)


def planted(ids, seed: int, salt: int, rank: int, scale: float) -> np.ndarray:
    """[n, rank] Gaussian coordinates of the planted model (Box-Muller on
    two hashes of (seed, id, coordinate))."""
    base = (np.asarray(ids, _U64)[:, None] * _U64(2 * rank)
            + np.arange(2 * rank, dtype=_U64)[None, :])
    u = _unit(_salted(base, seed, salt))
    u1, u2 = u[:, :rank], u[:, rank:]
    return scale * np.sqrt(-2.0 * np.log1p(-u1)) * np.cos(2 * np.pi * u2)


def rate(data: dict, users, items, seed: int, rng) -> np.ndarray:
    k = int(data["rank"])
    s = float(data["planted_scale"])
    a = planted(users, seed, _USER_SALT, k, s)
    b = planted(items, seed, _ITEM_SALT, k, s)
    c = planted(items, seed, _BIAS_SALT, 1, float(data["item_bias"]))[:, 0]
    r = float(data["center"]) + c + np.sum(a * b, axis=1) \
        + rng.normal(0.0, float(data["noise"]), len(users))
    return np.clip(r, float(data["low"]), float(data["high"])).astype(np.float32)


def make_split(data: dict, num_users: int, num_items: int, rows: int,
               seed: int, index: int) -> RatingSplit:
    rng = np.random.default_rng([seed, index, 0x5A7])
    users = draw_users(rng, rows, num_users, seed, float(data["user_count_max"]))
    offset = int(mix64(np.asarray([seed], _U64))[0] % _U64(num_items))
    items = draw_items(rng, rows, num_items, float(data["item_zipf"]), offset)
    return RatingSplit(users, items, rate(data, users, items, seed, rng))


def make_heldout(data: dict, split: RatingSplit, num_items: int, rows: int,
                 seed: int, index: int) -> RatingSplit:
    """`rows` ratings that the split does not hold, of users that it does:
    a user of a random rating of the split, a fresh item, the planted
    model's rating."""
    rng = np.random.default_rng([seed, index, 0x4E1D])
    users = split.users[rng.integers(0, split.rows, rows)]
    offset = int(mix64(np.asarray([seed], _U64))[0] % _U64(num_items))
    items = draw_items(rng, rows, num_items, float(data["item_zipf"]), offset)
    return RatingSplit(users, items, rate(data, users, items, seed, rng))
