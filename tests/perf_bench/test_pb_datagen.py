"""The id generator's distribution, determinism and the text row form."""

import numpy as np
import pytest

from benchmark import datagen

DATA = {"numeric_lanes": 13, "categorical_lanes": 26, "numeric_levels": 1024,
        "numeric_step": 0.00390625, "planted_support_one_in": 4,
        "label_noise": 0.5}


def test_same_seed_same_rows_other_seed_other_rows():
    a = datagen.make_split(DATA, 1 << 20, 512, 2 ** 31 + 5, 0)
    b = datagen.make_split(DATA, 1 << 20, 512, 2 ** 31 + 5, 0)
    c = datagen.make_split(DATA, 1 << 20, 512, 2 ** 31 + 6, 0)
    d = datagen.make_split(DATA, 1 << 20, 512, 2 ** 31 + 5, 1)
    assert np.array_equal(a.ids, b.ids) and np.array_equal(a.labels, b.labels)
    assert not np.array_equal(a.ids, c.ids)
    assert not np.array_equal(a.ids, d.ids)
    assert a.ids.shape == c.ids.shape == (512, 39)


def test_row_shape_values_and_labels():
    s = datagen.make_split(DATA, 1 << 22, 4096, 3, 0)
    assert s.ids.min() >= 0 and s.ids.max() < (1 << 22)
    assert s.vals.dtype == np.float32 and np.all(s.vals > 0)
    assert np.all(s.vals[:, 13:] == 1.0)
    assert np.all(s.vals[:, :13] * 256 == np.round(s.vals[:, :13] * 256))
    # the numeric lanes carry one fixed id each, every row
    assert np.all(s.ids[:, :13] == s.ids[0, :13])
    assert 0.35 < s.labels.mean() < 0.65


def test_ranks_are_log_uniform():
    u = np.random.default_rng(0).random(200_000)
    r = datagen.log_uniform_ranks(u, 1 << 28)
    assert r.min() >= 1 and r.max() < (1 << 28)
    # P(rank <= x) = ln x / ln space: a quarter of the draws per quartile
    edges = np.exp(np.log(2.0 ** 28) * np.array([0.25, 0.5, 0.75]))
    got = np.searchsorted(np.sort(r), edges) / r.size
    assert np.allclose(got, [0.25, 0.5, 0.75], atol=0.01)


def test_placement_is_spread_and_keeps_duplicates():
    r = np.arange(1, 100_001)
    f = np.zeros_like(r)
    ids = datagen.place(r, f, 1 << 20)
    # hot (low) ranks do not sit at the table's start
    assert np.mean(ids[:1000] < 1000) < 0.01
    counts = np.bincount(ids >> 16, minlength=16)
    assert counts.min() > 0.9 * r.size / 16
    assert np.array_equal(ids, datagen.place(r, f, 1 << 20))
    assert not np.array_equal(ids, datagen.place(r, f + 1, 1 << 20))


def test_planted_weights_are_sparse_and_signed():
    w = datagen.planted_weight(np.arange(100_000), 4)
    assert 0.22 < np.mean(w != 0) < 0.28
    assert w.min() < -0.9 and w.max() > 0.9


def test_text_rows_read_back_exactly():
    s = datagen.make_split(DATA, 1 << 20, 64, 9, 0)
    text = s.as_text()
    assert len(text) == 64 and len(text[0]) == 39
    for row, ids, vals in zip(text, s.ids, s.vals):
        for tok, i, v in zip(row, ids, vals):
            name, value = tok.split(":")
            assert int(name) == i and np.float32(float(value)) == v


@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 + 11])
def test_large_seeds_are_taken(seed):
    assert datagen.make_split(DATA, 1 << 16, 8, seed, 0).rows == 8
