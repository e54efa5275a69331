"""A rectangular `(idx, val)` array pair stays an array from the call's
argument to the block (models/base.py::_stage_rows / stage_training_rows,
core/batch.py::pack_rows / shuffle_rows): the same rows given as 2-D arrays
and as lists of a row's arrays give the same staged width, the same blocks and
the same trained state, bit for bit, and every caller that shares the routine
scores them alike."""

import jax
import numpy as np
import pytest

from hivemall_tpu.core.batch import is_rect, pack_rows, shuffle_rows
from hivemall_tpu.models import ffm as FFM
from hivemall_tpu.models.base import (_stage_rows, prepared_blocks,
                                      prepared_replica_blocks,
                                      stage_training_rows)
from hivemall_tpu.parallel import mix as pmix
from hivemall_tpu.runtime.metrics import REGISTRY
from hivemall_tpu.runtime.tracing import TRACER
from hivemall_tpu.sql.registry import get_function

DIMS = 1 << 10


def rect_rows(n, lanes, seed=0, lo=0, hi=DIMS):
    rng = np.random.RandomState(seed)
    ids = rng.randint(lo, hi, size=(n, lanes)).astype(np.int64)
    ids[:, 0] = 7                       # a feature every row carries
    vals = (rng.rand(n, lanes) + 0.25).astype(np.float32)
    return ids, vals, rng.choice([-1.0, 1.0], n).astype(np.float32)


def as_lists(ids, vals):
    return list(ids), list(vals)


def same_arrays(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


def same_blocks(got, want):
    got, want = list(got), list(want)
    assert len(got) == len(want) and got
    for g, w in zip(got, want):
        same_arrays(g, w)


def va_mask(seed):
    """FM's `-adareg` validation mask as `train_fm` draws it: one generator a
    call, one draw a block, in block order."""
    rng = np.random.RandomState(seed)
    return lambda blk: ((rng.rand(blk.batch_size) < 0.3).astype(np.float32),)


# rows, lanes, block, the width handed to the packer (None: the staged one),
# the range the ids are drawn from, -shuffle's seed, FM's mask
BLOCK_CASES = {
    "whole_blocks": (256, 39, 64, None, (0, DIMS), None, False),
    "last_block_partial": (250, 39, 64, None, (0, DIMS), None, False),
    "ids_negative_and_over_range": (200, 7, 64, None, (-5 * DIMS, 5 * DIMS),
                                    None, False),
    "row_wider_than_the_block": (100, 12, 32, 8, (0, DIMS), None, False),
    "shuffle_seed_31": (250, 9, 64, None, (0, DIMS), 31, False),
    "shuffle_seed_32": (250, 9, 64, None, (-DIMS, 3 * DIMS), 32, False),
    "adareg_mask": (250, 9, 64, None, (0, DIMS), None, True),
    "adareg_mask_shuffled": (250, 9, 64, None, (0, DIMS), 33, True),
    "one_lane": (70, 1, 16, None, (0, DIMS), None, False),
}


@pytest.mark.parametrize("case", sorted(BLOCK_CASES))
def test_blocks_of_a_rectangular_pair_are_the_lists_blocks(case):
    n, lanes, block, width, (lo, hi), seed, masked = BLOCK_CASES[case]
    ids, vals, y = rect_rows(n, lanes, seed=n + lanes, lo=lo, hi=hi)
    ai, av, aw = stage_training_rows((ids, vals), DIMS)
    li, lv, lw = stage_training_rows(as_lists(ids, vals), DIMS)
    assert is_rect(ai) and is_rect(av) and isinstance(li, list)
    assert aw == lw
    assert ai.dtype == np.int64 and av.dtype == np.float32
    assert ai.min() >= 0 and ai.max() < DIMS
    same_arrays(list(ai), li)
    same_arrays(list(av), lv)
    ay = ly = y
    if seed is not None:
        ai, av, ay = shuffle_rows(ai, av, y, seed)
        li, lv, ly = shuffle_rows(li, lv, y, seed)
        assert is_rect(ai) and isinstance(li, list)
        same_arrays([ay], [ly])
    extra = (va_mask(5), va_mask(5)) if masked else (None, None)
    same_blocks(
        prepared_blocks(ai, av, ay, DIMS, block, width or aw, extra[0]),
        prepared_blocks(li, lv, ly, DIMS, block, width or lw, extra[1]))
    # the block itself: pad id `dims`, pad value 0, the rows' true lengths
    blk = pack_rows(ai[:block], av[:block], ay[:block], DIMS,
                    width=width or aw, batch_size=block + 3)
    k = min(lanes, width or aw)
    assert blk.indices.dtype == np.int32 and blk.values.dtype == np.float32
    assert (blk.indices[:block, k:] == DIMS).all()
    assert (blk.indices[block:] == DIMS).all()
    assert (blk.values[:block, k:] == 0).all() and (blk.values[block:] == 0).all()
    assert (blk.nnz == [k] * block + [0] * 3).all()
    same_arrays(blk, pack_rows(li[:block], lv[:block], ly[:block], DIMS,
                               width=width or lw, batch_size=block + 3))


# rows, block, -shuffle's seed: shares of whole blocks; a last share short;
# a share that runs out a block before the others; a replica with no rows
REPLICA_CASES = {
    "shares_of_whole_blocks": (256, 32, None),
    "last_share_short": (250, 32, None),
    "a_share_runs_out": (70, 8, None),
    "a_share_is_empty": (9, 2, None),
    "each_share_shuffled": (250, 32, 32),
}


@pytest.mark.parametrize("case", sorted(REPLICA_CASES))
def test_replica_blocks_of_a_rectangular_pair_are_the_lists_blocks(case):
    n, block, seed = REPLICA_CASES[case]
    ids, vals, y = rect_rows(n, 9, seed=n, lo=-DIMS, hi=2 * DIMS)
    a_i, a_v, aw = stage_training_rows((ids, vals), DIMS, replicas=4)
    l_i, l_v, lw = stage_training_rows(as_lists(ids, vals), DIMS, replicas=4)
    assert aw == lw and len(a_i) == len(l_i) == 4
    assert all(is_rect(s) for s in a_i + a_v)
    assert all(s.base is not None for s in a_v)   # dealt as views
    a_y = l_y = [y[lo:hi] for lo, hi in pmix.deal_rows(n, 4)]
    if seed is not None:
        dealt = [shuffle_rows(i, v, s, seed) for i, v, s in zip(a_i, a_v, a_y)]
        a_i, a_v, a_y = map(list, zip(*dealt))
        dealt = [shuffle_rows(i, v, s, seed) for i, v, s in zip(l_i, l_v, l_y)]
        l_i, l_v, l_y = map(list, zip(*dealt))
    got = list(prepared_replica_blocks(a_i, a_v, a_y, DIMS, block, aw))
    same_blocks(got, prepared_replica_blocks(l_i, l_v, l_y, DIMS, block, lw))
    assert sum(int(b[3].sum()) for b in got) == n
    if case in ("a_share_runs_out", "a_share_is_empty"):
        assert got[-1][3][-1] == 0    # the last replica sends padding


@pytest.mark.parametrize("fields_as", ["array", "broadcast", "lists"])
@pytest.mark.parametrize("n,lanes", [(64, 6), (50, 12)])
def test_ffm_blocks_of_rectangular_arrays_are_the_lists_blocks(n, lanes,
                                                               fields_as):
    ids, vals, _ = rect_rows(n, lanes, seed=lanes, lo=-DIMS, hi=1 << 20)
    fld = np.arange(lanes, dtype=np.int32) + 5 * (np.arange(n)[:, None] % 3)
    fields = {"array": fld, "lists": list(fld),
              "broadcast": np.broadcast_to(fld[0], fld.shape)}[fields_as]
    hyper = FFM.FFMHyper(num_features=1 << 18, num_fields=8)
    want = FFM._stage_ffm_rows(
        (list(ids), list(vals), [np.asarray(r) for r in fields]), None, hyper)
    got = FFM._stage_ffm_rows((ids, vals, fields), None, hyper)
    same_arrays(got[:3], want[:3])
    assert got[2].max() < 8 and got[0].shape[1] == want[0].shape[1]
    # a training block's field lanes, a slice at a time as `train_ffm` packs
    for s in range(0, n, 16):
        same_arrays(
            [FFM._pack_fields(fields[s:s + 16], got[0].shape[1], 8)],
            [got[2][s:s + 16]])


def _same_state(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb) and la
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(x, y)


TRAIN_CASES = {
    "arow": ("train_arow", f"-dims {DIMS} -mini_batch 32", 250),
    "arow_scan": ("train_arow", f"-dims {DIMS}", 100),
    "arow_shuffled_epochs": (
        "train_arow",
        f"-dims {DIMS} -mini_batch 32 -iters 3 -disable_cv -shuffle", 250),
    "arow_shuffled_seed_7": (
        "train_arow",
        f"-dims {DIMS} -mini_batch 32 -iters 2 -disable_cv -shuffle -seed 7",
        250),
    "arow_batch_backend": ("train_arow", f"-dims {DIMS} -batch 32", 250),
    "arow_mix": ("train_arow",
                 f"-dims {DIMS} -mini_batch 16 -mix local -mix_threshold 2",
                 250),
    "arow_mix_shuffled": (
        "train_arow", f"-dims {DIMS} -mini_batch 16 -mix local "
        "-mix_threshold 3 -iters 2 -disable_cv -shuffle", 70),
    "fm": ("train_fm", f"-c -factor 4 -dims {DIMS} -mini_batch 32 -iters 2 "
           "-disable_cv", 250),
    "fm_adareg_shuffled": (
        "train_fm", f"-c -factor 4 -dims {DIMS} -mini_batch 32 -iters 3 "
        "-disable_cv -adareg -va_ratio 0.3 -shuffle", 250),
    "multiclass": ("train_multiclass_arow", f"-dims {DIMS} -mini_batch 32",
                   250),
}


@pytest.mark.parametrize("case", sorted(TRAIN_CASES))
def test_training_a_rectangular_pair_gives_the_lists_state(case, monkeypatch):
    entry, options, n = TRAIN_CASES[case]
    if "-mix" in options:
        devices = jax.local_devices()[:4]
        assert len(devices) == 4, "the tests' virtual CPU mesh is missing"
        monkeypatch.setattr(pmix, "mix_devices", lambda: devices)
    ids, vals, y = rect_rows(n, 9, seed=n, lo=-DIMS, hi=3 * DIMS)
    if entry == "train_multiclass_arow":
        y = np.arange(n) % 3
    fn = get_function(entry)
    a = fn((ids, vals), y, options)
    b = fn(as_lists(ids, vals), y, options)
    _same_state(a.state, b.state)
    assert int(a.state.step) >= n


@pytest.mark.parametrize("options", [
    "-mini_batch 16 -eta0_V 0.05", "-eta0_V 0.05",
    "-mini_batch 16 -eta0_V 0.05 -iters 2 -disable_cv"],
    ids=["minibatch", "scan", "two_epochs"])
def test_train_ffm_on_rectangular_arrays_gives_the_lists_state(options):
    n, lanes = 70, 6
    ids, vals, y = rect_rows(n, lanes, seed=3, lo=-DIMS, hi=1 << 20)
    fld = np.broadcast_to(np.arange(lanes), ids.shape)
    opts = f"-factor 4 -feature_hashing 18 -num_fields 6 -v_bits 12 {options}"
    a = FFM.train_ffm((ids, vals, fld), y, opts)
    b = FFM.train_ffm((list(ids), list(vals), list(fld)), y, opts)
    _same_state(a.state, b.state)
    np.testing.assert_array_equal(a.predict((ids, vals, fld)),
                                  b.predict((list(ids), list(vals),
                                             list(fld))))


def _stage_span(fn, *args):
    counter = REGISTRY.counter("train", "rows_staged_rect")
    before = counter.value
    TRACER.clear()
    fn(*args)
    call = next(t for t in TRACER.traces() if t["root"] == "train.call")
    (stage,) = [s for s in call["spans"] if s["name"] == "train.stage"]
    return stage["args"], counter.value - before


def test_the_stage_span_says_which_layout_the_rows_took(monkeypatch):
    n, lanes = 96, 5
    ids, vals, y = rect_rows(n, lanes, seed=1)
    ragged_i = [r[:3 + i % 3] for i, r in enumerate(ids)]
    ragged_v = [r[:3 + i % 3] for i, r in enumerate(vals)]
    text = [[f"{i}:{v!r}" for i, v in zip(ri, rv.tolist())]
            for ri, rv in zip(ids, vals)]
    arow, fm = get_function("train_arow"), get_function("train_fm")
    opts = f"-dims {DIMS} -mini_batch 32"
    want_rect = {"form": "arrays", "layout": "rect", "rows": n,
                 "nnz": n * lanes}
    assert _stage_span(arow, (ids, vals), y, opts) == (want_rect, n)
    assert _stage_span(fm, (ids, vals), y, f"-c {opts}") == (want_rect, n)
    # int32 ids and float64 values are a rectangular pair too
    assert _stage_span(arow, (ids.astype(np.int32), vals.astype(np.float64)),
                       y, opts) == (want_rect, n)
    assert _stage_span(arow, as_lists(ids, vals), y, opts) == (
        dict(want_rect, layout="rows"), 0)
    assert _stage_span(arow, (ragged_i, ragged_v), y, opts) == (
        {"form": "arrays", "layout": "rows", "rows": n,
         "nnz": sum(len(r) for r in ragged_i)}, 0)
    assert _stage_span(arow, text, y, opts) == (
        {"form": "text", "layout": "rows", "rows": n, "nnz": n * lanes}, 0)
    # arrays of unequal shape are no pair: they go the rows' way
    assert _stage_span(arow, (ids, list(vals)), y, opts)[0]["layout"] == "rows"
    # FFM: the field-array form, and its text form through the `stage=` hook
    fld = np.broadcast_to(np.arange(lanes), ids.shape)
    ffm_opts = ("-factor 2 -feature_hashing 18 -num_fields 8 -v_bits 10 "
                "-mini_batch 32 -eta0_V 0.05")
    assert _stage_span(FFM.train_ffm, (ids, vals, fld), y, ffm_opts) == (
        want_rect, n)
    ffm_text = [[f"{f}:{i}:{v!r}" for f, (i, v) in enumerate(
        zip(ri, rv.tolist()))] for ri, rv in zip(ids, vals)]
    assert _stage_span(FFM.train_ffm, ffm_text, y, ffm_opts) == (
        {"form": "text", "layout": "rows", "rows": n, "nnz": n * lanes}, 0)
    # -mix deals the arrays: every replica's rows counted once
    devices = jax.local_devices()[:4]
    monkeypatch.setattr(pmix, "mix_devices", lambda: devices)
    assert _stage_span(arow, (ids, vals), y,
                       f"{opts} -mix local -mix_threshold 2") == (want_rect, n)


def test_staging_a_rectangular_pair_copies_only_what_it_changes():
    ids, vals, _ = rect_rows(64, 5, lo=-DIMS, hi=2 * DIMS)
    si, sv = _stage_rows((ids, vals), DIMS)
    assert sv is vals and si is not ids
    np.testing.assert_array_equal(si, ids % DIMS)
    # ids already in range are what a caller who hashed them holds
    for edge in (0, DIMS - 1):
        ids = si.copy()
        ids[3, 2] = edge
        assert _stage_rows((ids, vals), DIMS)[0] is ids
    for edge in (-1, DIMS):
        ids = si.copy()
        ids[3, 2] = edge
        staged = _stage_rows((ids, vals), DIMS)[0]
        assert staged is not ids and staged[3, 2] == edge % DIMS
    empty = _stage_rows((ids[:0], vals[:0]), DIMS)
    assert empty[0].shape == (0, 5) and empty[1].shape == (0, 5)


def test_callers_that_share_the_routine_score_a_rectangular_pair_alike():
    """`predict` of the linear, FM and multiclass models and the serving
    engine's staging: no cell runs them, and all take `_stage_rows`' rows."""
    from hivemall_tpu.serving import ServingEngine

    n, lanes = 150, 9
    ids, vals, y = rect_rows(n, lanes, seed=4, lo=-DIMS, hi=3 * DIMS)
    pair, lists = (ids, vals), as_lists(ids, vals)
    opts = f"-dims {DIMS} -mini_batch 32"
    arow = get_function("train_arow")(pair, y, opts)
    fm = get_function("train_fm")(pair, y, f"-c -factor 4 {opts}")
    mc = get_function("train_multiclass_arow")(pair, np.arange(n) % 3, opts)
    np.testing.assert_array_equal(arow.predict(pair), arow.predict(lists))
    for got, want in zip(arow.predict(pair, return_variance=True),
                         arow.predict(lists, return_variance=True)):
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(fm.predict(pair), fm.predict(lists))
    np.testing.assert_array_equal(mc.scores(pair), mc.scores(lists))
    assert mc.predict(pair) == mc.predict(lists)
    for name, model in (("rect_linear", arow), ("rect_fm", fm),
                        ("rect_mc", mc)):
        # 150 rows through a 64-row engine: chunks of an array are arrays
        eng = ServingEngine(model, name=name, max_batch=64, max_width=8)
        got, want = eng.predict(pair), eng.predict(lists)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert eng.servable.row_keys(pair, 16) == \
            eng.servable.row_keys(lists, 16)
        a = eng.servable.stage(pair, 256, 8)      # 9 lanes truncate at 8
        b = eng.servable.stage(lists, 256, 8)
        same_arrays(a, b)
