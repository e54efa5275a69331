"""The plain references against train_arow / train_fm at small size, the
control (a lower storage precision in the program's place) failing the
limits, and the firing-boundary rule."""

import json
import os

import numpy as np
import pytest

from benchmark import compare, datagen, manifest
from benchmark.refs import arow as ref_arow
from benchmark.refs import fm as ref_fm

DATA = {"numeric_lanes": 13, "categorical_lanes": 26, "numeric_levels": 1024,
        "numeric_step": 0.00390625, "planted_support_one_in": 4,
        "label_noise": 0.5}


def _limits(config):
    path = os.path.join(manifest.ROOT, "benchmark", "configs", config + ".json")
    return json.load(open(path))["correct"]["limits"]


@pytest.fixture(scope="module")
def arow_case():
    from hivemall_tpu.sql.registry import get_function

    dims = 1 << 16
    sp = datagen.make_split(DATA, dims, 4096, 5, 0)
    model = get_function("train_arow")(
        sp.as_arrays(), sp.labels, f"-dims {dims} -mini_batch 1024 -disable_halffloat")
    prog = ref_arow.rows_of(model.model_rows())
    feats, w, cov, info = ref_arow.train(sp.ids, sp.vals, sp.labels, dims=dims,
                                         mini_batch=1024)
    ref = {"feats": feats, "tables": {"w": w, "cov": cov}, "scalars": {}}
    return sp, dims, prog, ref, int(model.state.step)


@pytest.fixture(scope="module")
def fm_case():
    from hivemall_tpu.sql.registry import get_function

    dims = 1 << 14
    sp = datagen.make_split(DATA, dims, 4096, 5, 0)
    model = get_function("train_fm")(
        sp.as_arrays(), sp.labels,
        f"-c -factor 10 -dims {dims} -mini_batch 1024 -iters 2 -disable_cv")
    prog = ref_fm.rows_of(model.model_rows())
    w0, feats, w, v, info = ref_fm.train(sp.ids, sp.vals, sp.labels, dims=dims,
                                         mini_batch=1024, epochs=2, factors=10)
    ref = {"feats": feats, "tables": {"w": w, "v": v}, "scalars": {"w0": w0}}
    return sp, dims, prog, ref, int(model.state.step)


def test_arow_reference_matches_train_arow(arow_case):
    sp, dims, prog, ref, steps = arow_case
    gaps = compare.model_gaps(prog, ref)
    v = compare.verdict(gaps, {k: l for k, l in _limits("arow_criteo1tb").items()
                               if k in gaps})
    assert all(n["ok"] for n in v.values()), v
    assert gaps["w_gap"] < 1e-5 and gaps["cov_gap"] < 1e-5
    assert steps == 4096


def test_fm_reference_matches_train_fm(fm_case):
    sp, dims, prog, ref, steps = fm_case
    gaps = compare.model_gaps(prog, ref)
    v = compare.verdict(gaps, {k: l for k, l in _limits("fm_criteo1tb").items()
                               if k in gaps})
    assert all(n["ok"] for n in v.values()), v
    assert prog["tables"]["v"].shape[1] == 10
    assert steps == 2 * 4096


F32_LIMITS = {"rows_diff": 0, "w_gap": 1e-4, "cov_gap": 1e-5}   # PERF.md, PR 24


def test_bfloat16_in_the_place_of_float32_tables_fails_their_limits(arow_case):
    sp, dims, prog, ref, _ = arow_case
    feats, w, cov, _ = ref_arow.train(sp.ids, sp.vals, sp.labels, dims=dims,
                                      mini_batch=1024, table_dtype="bfloat16")
    low = {"feats": feats, "tables": {"w": w, "cov": cov}, "scalars": {}}
    gaps = compare.model_gaps(low, ref)
    assert not all(n["ok"] for n in compare.verdict(gaps, F32_LIMITS).values())
    assert gaps["w_gap"] > 3 * F32_LIMITS["w_gap"]


def test_arow_control_fails_a_limit():
    """The cell's control: float8 tables in the place of the bfloat16 ones
    the configuration states, on 16 steps (the cell runs 64 and 128)."""
    sp = datagen.make_split(DATA, 1 << 16, 16384, 6, 0)
    models = {}
    for dtype in ("bfloat16", "float8_e4m3fn"):
        feats, w, cov, _ = ref_arow.train(sp.ids, sp.vals, sp.labels,
                                          dims=1 << 16, mini_batch=1024,
                                          table_dtype=dtype)
        models[dtype] = {"feats": feats, "tables": {"w": w, "cov": cov},
                         "scalars": {}}
    gaps = compare.model_gaps(models["float8_e4m3fn"], models["bfloat16"])
    lim = _limits("arow_criteo1tb")
    v = compare.verdict(gaps, {k: l for k, l in lim.items() if k in gaps})
    assert not all(n["ok"] for n in v.values())
    assert gaps["cov_gap"] > 3 * lim["cov_gap"] and gaps["w_gap"] > lim["w_gap"]


def test_fm_control_fails_a_limit(fm_case):
    sp, dims, prog, ref, _ = fm_case
    w0, feats, w, v, _ = ref_fm.train(sp.ids, sp.vals, sp.labels, dims=dims,
                                      mini_batch=1024, epochs=2, factors=10,
                                      table_dtype="bfloat16")
    low = {"feats": feats, "tables": {"w": w, "v": v}, "scalars": {"w0": w0}}
    gaps = compare.model_gaps(low, ref)
    lim = _limits("fm_criteo1tb")
    assert gaps["v_gap"] > 3 * lim["v_gap"] or gaps["w_gap"] > 3 * lim["w_gap"]


def test_float32_storage_in_the_reference_stays_inside(arow_case):
    sp, dims, prog, ref, _ = arow_case
    feats, w, cov, _ = ref_arow.train(sp.ids, sp.vals, sp.labels, dims=dims,
                                      mini_batch=1024, table_dtype="float32")
    f32 = {"feats": feats, "tables": {"w": w, "cov": cov}, "scalars": {}}
    gaps = compare.model_gaps(f32, ref)
    assert gaps["rows_diff"] == 0 and gaps["w_gap"] < 1e-5


def test_a_row_at_the_firing_boundary_follows_the_program():
    # two rows with disjoint features in one mini-batch; both fire (m = 0)
    ids = np.array([[1, 2], [3, 4]])
    vals = np.ones((2, 2), np.float32)
    labels = np.array([1.0, 0.0])
    own = ref_arow.train(ids, vals, labels, dims=16, mini_batch=2)
    assert list(own[0]) == [1, 2, 3, 4] and own[3]["followed_rows"] == 0
    # the program emitted row 1's features only: inside tau the reference
    # follows it, outside tau it keeps its own reading
    emitted = np.array([3, 4])
    far = ref_arow.train(ids, vals, labels, dims=16, mini_batch=2,
                         emitted_feats=emitted, tau=0.5)
    assert list(far[0]) == [1, 2, 3, 4] and far[3]["ambiguous_rows"] == 0
    near = ref_arow.train(ids, vals, labels, dims=16, mini_batch=2,
                          emitted_feats=emitted, tau=1.5)
    assert list(near[0]) == [3, 4]
    assert near[3]["followed_rows"] == 1 and near[3]["followed_margin"] == 1.0


def test_a_boundary_row_without_a_witness_keeps_the_references_reading():
    # row 0's features are both carried by row 1 too: whether row 0 fired
    # cannot be read off the emitted set, so the reference does not guess
    ids = np.array([[1, 2], [1, 2]])
    vals = np.ones((2, 2), np.float32)
    labels = np.array([1.0, 0.0])
    out = ref_arow.train(ids, vals, labels, dims=16, mini_batch=2,
                         emitted_feats=np.array([], np.int64), tau=1.5)
    assert out[3]["ambiguous_rows"] == 2 and out[3]["followed_rows"] == 0
    assert list(out[0]) == [1, 2]
    # and with two epochs a feature is emitted if the row fired in either:
    # no following at all
    two = ref_arow.train(np.array([[1, 2], [3, 4]]), vals, labels, dims=16,
                         mini_batch=2, epochs=2,
                         emitted_feats=np.array([3, 4]), tau=1.5)
    assert two[3]["ambiguous_rows"] == 0 and list(two[0]) == [1, 2, 3, 4]


def test_arow_by_hand_one_row():
    # w = 0, cov = 1, x = (1, 2), y = +1: m = 0, v = 5, beta = 1/5.1,
    # alpha = beta; dw = alpha * cov * x; dcov = -beta * (cov x)^2
    f, w, cov, _ = ref_arow.train(np.array([[7, 9]]), np.array([[1.0, 2.0]]),
                                  np.array([1.0]), dims=16, mini_batch=1)
    beta = 1 / 5.1
    assert list(f) == [7, 9]
    assert np.allclose(w, [beta, 2 * beta])
    assert np.allclose(cov, [1 - beta, 1 - 4 * beta])


def test_table_gap_and_verdict():
    ref = np.array([1.0, 0.0, -2.0])
    assert compare.table_gap(ref, ref) == 0.0
    # median |ref| = 1: an entry of 0 off by 0.5 reads 0.5 / (0 + 1)
    assert compare.table_gap(np.array([1.0, 0.5, -2.0]), ref) == 0.5
    v = compare.verdict({"a": 0.1, "b": float("nan")},
                        {"a": 0.2, "b": 1.0, "c": 1.0})
    assert v["a"]["ok"] and not v["b"]["ok"] and not v["c"]["ok"]


def test_rows_diff_counts_both_sides():
    a = {"feats": np.array([1, 2, 3]), "tables": {"w": np.ones(3)}, "scalars": {}}
    b = {"feats": np.array([2, 3, 4, 5]), "tables": {"w": np.ones(4)}, "scalars": {}}
    g = compare.model_gaps(a, b)
    assert g["rows_diff"] == 3 and g["w_gap"] == 0.0
