"""The one general traffic generator: rows of a hashed-feature data set, made
from parameters in a configuration's `data` group and a traffic file.

A row has `numeric_lanes` features that every row carries (one fixed id each,
a seeded float value) and `categorical_lanes` one-hot features (value 1.0)
whose id is drawn with LOG-UNIFORM frequency over the hashed space and then placed
by an integer hash, so hot ids are spread over the table as murmur-hashed
names are. That keeps `runtime/benchmark.make_workload_ids`'s distribution
without its host `permutation(dims)` (2 GiB and tens of seconds at 2^28).

Labels come from a planted sparse weight vector that is itself a hash of the
id (no table), so a held-out logloss means something.

Everything is a pure function of (data parameters, seed, split index): the
same seed gives the same rows, and every seed gives the same sizes.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np

_U64 = np.uint64
_PLACE_SALT = 0x9E3779B97F4A7C15
_PLANT_SALT = 0xD1B54A32D192ED03
_NUMERIC_SALT = 0x8CB92BA72F3D8DD7


def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer on a uint64 array (wraps, as C does)."""
    x = np.asarray(x, dtype=_U64).copy()
    x ^= x >> _U64(30)
    x *= _U64(0xBF58476D1CE4E5B9)
    x ^= x >> _U64(27)
    x *= _U64(0x94D049BB133111EB)
    x ^= x >> _U64(31)
    return x


def log_uniform_ranks(u: np.ndarray, rank_space: int) -> np.ndarray:
    """u in [0, 1) -> rank in [1, rank_space): P(rank <= r) = ln r / ln space."""
    r = np.exp(u * np.log(float(rank_space))).astype(np.int64)
    return np.clip(r, 1, rank_space - 1)


def place(rank: np.ndarray, field: np.ndarray, dims: int) -> np.ndarray:
    """Hash-uniform placement of (field, rank) into [0, dims)."""
    key = rank.astype(_U64) * _U64(64) + field.astype(_U64)
    return (mix64(key ^ _U64(_PLACE_SALT)) % _U64(dims)).astype(np.int64)


def numeric_ids(n_lanes: int, dims: int) -> np.ndarray:
    lanes = np.arange(n_lanes, dtype=_U64)
    return (mix64(lanes ^ _U64(_NUMERIC_SALT)) % _U64(dims)).astype(np.int64)


def planted_weight(ids: np.ndarray, support_one_in: int) -> np.ndarray:
    """The planted model's weight of each id: a hash, zero off the support."""
    h = mix64(ids.astype(_U64) ^ _U64(_PLANT_SALT))
    unit = (h >> _U64(11)).astype(np.float64) / float(1 << 53)  # [0, 1)
    on = (h % _U64(support_one_in)) == 0
    return np.where(on, 2.0 * unit - 1.0, 0.0)


@dataclass
class Split:
    """One mapper's split: `ids`/`vals` [n, lanes], `labels` [n] in {0, 1}."""

    ids: np.ndarray
    vals: np.ndarray
    labels: np.ndarray

    @property
    def rows(self) -> int:
        return int(self.ids.shape[0])

    def as_arrays(self):
        """The pre-hashed `(idx_rows, val_rows)` row form."""
        return (self.ids, self.vals)

    def as_text(self) -> List[List[str]]:
        """Hivemall feature strings, `"<id>:<value>"`. Values are exact in
        float32 and print exactly, so a parser reads back what was made."""
        val_str = {float(v): repr(float(v)) for v in np.unique(self.vals)}
        return [[f"{i}:{val_str[v]}" for i, v in zip(ri, rv)]
                for ri, rv in zip(self.ids.tolist(), self.vals.tolist())]


def make_split(data: dict, dims: int, rows: int, seed: int, index: int) -> Split:
    """Rows of one split. `data` is the configuration's `data` group."""
    n_num = int(data["numeric_lanes"])
    n_cat = int(data["categorical_lanes"])
    levels = int(data["numeric_levels"])
    step = float(data["numeric_step"])
    rng = np.random.default_rng([int(seed), int(index), 0x5EED])

    u = rng.random((rows, n_cat))
    fields = np.broadcast_to(np.arange(n_cat, dtype=np.int64), (rows, n_cat))
    cat_ids = place(log_uniform_ranks(u, dims), fields, dims)
    num_ids = np.broadcast_to(numeric_ids(n_num, dims), (rows, n_num))
    # k * step with k >= 1: never zero, exact in float32 and in decimal
    num_vals = rng.integers(1, levels + 1, size=(rows, n_num)) * step
    ids = np.concatenate([num_ids, cat_ids], axis=1).astype(np.int64)
    vals = np.concatenate(
        [num_vals, np.ones((rows, n_cat))], axis=1).astype(np.float32)

    w_star = planted_weight(ids, int(data["planted_support_one_in"]))
    # centre the always-positive numeric values so the classes balance
    centred = vals.astype(np.float64)
    centred[:, :n_num] -= 0.5 * (levels + 1) * step
    score = np.sum(w_star * centred, axis=1)
    noise = rng.logistic(scale=float(data["label_noise"]), size=rows)
    labels = (score + noise > 0).astype(np.float32)
    return Split(ids=np.ascontiguousarray(ids), vals=np.ascontiguousarray(vals),
                 labels=labels)
