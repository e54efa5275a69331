"""The `-mini_batch` step's block-local application, against a plain dense
apply.

Where the table is long against the block (`engine.apply_strategy`), the
step reduces a block's deltas in the block's own index space (sort with
payload, segmented scan, in-place writes of the touched entries:
core/engine.py, ops/scatter.reduce_block_runs). The reference here is the
dense formula, written on whole `[D]` arrays in numpy: per-feature sums and
fired counts, `new = old + sum / max(count, 1)`, one rounding to the table's
storage type (slots take the sum, or what the rule's `block_slots` makes of
the sums). The rule's own arithmetic is not under test: the reference
calls the same `rule.update` on rows it gathered itself. Every block here is
small enough for its table that the block-local strategy runs; the last
tests hold the two strategies against each other.
"""

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from hivemall_tpu.core.engine import (DELTA_SLOT, DENSE_APPLY_BELOW,
                                      RowContext, apply_strategy,
                                      make_train_fn, make_train_step)
from hivemall_tpu.core.state import init_linear_state
from hivemall_tpu.models import classifier as C
from hivemall_tpu.models import regression as R

DIMS = 1 << 17

# name -> (rule, hyper, table dtype, track_deltas, binary labels)
CASES = {
    "arow_f32": (C.AROW, {"r": 0.1}, jnp.float32, False, True),
    "arow_bf16": (C.AROW, {"r": 0.1}, jnp.bfloat16, False, True),
    "scw1": (C.SCW1, {"phi": 1.0, "c": 1.0}, jnp.float32, False, True),
    "scw1_track_deltas": (C.SCW1, {"phi": 1.0, "c": 1.0}, jnp.float32, True,
                          True),
    "pa1": (C.PA1, {"c": 1.0}, jnp.float32, False, True),
    "pa1_regr": (R.PA1_REGR, {"c": 1.0, "epsilon": 0.01}, jnp.float32, False,
                 False),
    "adagrad_regr": (R.ADAGRAD_REGR, {"eta": 1.0, "eps": 1.0, "scale": 100.0},
                     jnp.float32, False, False),
    "adagrad_rda": (C.ADAGRAD_RDA,
                    {"eta": 0.1, "lambda": 1e-6, "scale": 100.0},
                    jnp.float32, False, True),
    "adagrad_rda_track_deltas": (C.ADAGRAD_RDA,
                                 {"eta": 0.1, "lambda": 1e-6, "scale": 100.0},
                                 jnp.float32, True, True),
    "pa1a_regr_globals": (R.PA1A_REGR, {"c": 1.0, "epsilon": 0.01},
                          jnp.float32, False, False),
    "arow_track_deltas": (C.AROW, {"r": 0.1}, jnp.float32, True, True),
    "arow_bf16_track_deltas": (C.AROW, {"r": 0.1}, jnp.bfloat16, True, True),
}

# name -> (rows, lanes, distinct ids drawn from, share of pad lanes)
BLOCKS = {
    "heavy_duplicates": (48, 8, 6, 0.0),
    "pad_lanes": (32, 8, 60, 0.4),
    "mixed": (40, 6, 25, 0.15),
    "small_mixed": (16, 6, 12, 0.15),
    "one_row": (1, 8, 60, 0.25),
}


def _state(rule, dtype, track, seed):
    """A state in mid-training: non-zero tables, some rows will not fire."""
    rng = np.random.default_rng(seed)
    slots = tuple(rule.slot_names) + ((DELTA_SLOT,) if track else ())
    st = init_linear_state(DIMS, use_covariance=rule.use_covariance,
                           slot_names=slots, global_names=rule.global_names,
                           dtype=dtype)
    w = rng.normal(scale=0.7, size=DIMS).astype(np.float32)
    w[rng.random(DIMS) < 0.3] = 0.0
    st = st.replace(
        weights=jnp.asarray(w, dtype),
        touched=jnp.asarray((w != 0).astype(np.int8)),
        slots={k: jnp.asarray(
            rng.integers(0, 4, DIMS).astype(np.float32) if k == DELTA_SLOT
            else rng.random(DIMS).astype(np.float32) * 50.0)
            for k in slots},
        step=jnp.asarray(17, jnp.int32),
        globals={k: jnp.asarray(v, jnp.float32) for k, v in
                 zip(rule.global_names, (17.0, 0.1, 3.0))})
    if rule.use_covariance:
        st = st.replace(covars=jnp.asarray(
            rng.uniform(0.05, 1.0, DIMS).astype(np.float32), dtype))
    return st


def _block(kind, binary, seed):
    rows, lanes, distinct, pad = BLOCKS[kind]
    rng = np.random.default_rng(seed + 1000)
    pool = rng.choice(DIMS, size=distinct, replace=False)
    idx = rng.choice(pool, size=(rows, lanes)).astype(np.int32)
    idx[rng.random((rows, lanes)) < pad] = DIMS
    val = rng.normal(size=(rows, lanes)).astype(np.float32)
    val[idx == DIMS] = 0.0
    y = np.sign(rng.normal(size=rows)).astype(np.float32) if binary \
        else rng.normal(size=rows).astype(np.float32)
    assert apply_strategy(DIMS, idx.size) == "batch_local"
    return idx, val, y


def _rule_outputs(rule, hyper, state, idx, val, y, gl):
    """rule.update on rows gathered here in numpy (pad lanes: w 0, cov 1)."""
    live = idx < DIMS
    safe = np.where(live, idx, 0)

    def rows_of(table, fill):
        t = np.asarray(table)
        return jnp.asarray(np.where(live, t[safe], np.asarray(fill, t.dtype)))

    w = rows_of(state.weights, 0)
    cov = rows_of(state.covars, 1) if rule.use_covariance else None
    sl = {k: rows_of(v, 0) for k, v in state.slots.items()}
    ts = (int(state.step) + 1 + np.arange(len(y))).astype(np.float32)

    def one(w, cov, sl, val, y, t):
        variance = jnp.sum(cov * val * val) if rule.use_covariance \
            else jnp.zeros(())
        ctx = RowContext(w, cov, sl, val, y, jnp.sum(w * val),
                         jnp.sum(val * val), variance, t, gl)
        return rule.update(ctx, hyper)

    return jax.vmap(one)(w, cov, sl, jnp.asarray(val), jnp.asarray(y),
                         jnp.asarray(ts))


def _sum_at(idx, col):
    """Per-feature float64 sums of a [B, K] column, pad lanes dropped."""
    out = np.zeros(DIMS, np.float64)
    live = idx < DIMS
    np.add.at(out, idx[live], np.asarray(col, np.float64)[live])
    return out


def _dense_reference(rule, hyper, state, idx, val, y, track):
    gl = state.globals
    if rule.pre_batch is not None:
        gl = rule.pre_batch(gl, jnp.asarray(y))
    outs = _rule_outputs(rule, hyper, state, idx, val, y, gl)
    fired = np.asarray(outs.updated, np.float64)[:, None] * np.ones(idx.shape)
    counts = _sum_at(idx, fired)
    occurs = _sum_at(idx, np.ones(idx.shape))
    denom = np.maximum(counts, 1.0)
    f64 = lambda t: np.asarray(t).astype(np.float64)
    ref = {"weights": f64(state.weights) + _sum_at(idx, outs.dw) / denom}
    if rule.use_covariance and outs.dcov is not None:
        ref["covars"] = f64(state.covars) + _sum_at(idx, outs.dcov) / denom
    slots = {k: f64(v) for k, v in state.slots.items()}
    # slots take the block's sums; a rule that derives w from its slots says
    # what one block adds (core/engine.py, DERIVED_W_BLOCK_RULE)
    adds = {k: _sum_at(idx, d) for k, d in outs.dslots.items()}
    if rule.block_slots is not None:
        adds = {k: np.asarray(v, np.float64)
                for k, v in rule.block_slots(adds).items()}
    for k, d in adds.items():
        slots[k] = slots[k] + d
    if track:
        slots[DELTA_SLOT] = slots[DELTA_SLOT] + counts
    if rule.derive_w is not None:
        tf_end = jnp.asarray(int(state.step) + len(y), jnp.float32)
        derived = np.asarray(rule.derive_w(
            {k: jnp.asarray(v, jnp.float32) for k, v in slots.items()},
            tf_end, hyper), np.float64)
        ref["weights"] = np.where(counts > 0, derived, ref["weights"])
    return {"tables": ref, "slots": slots, "counts": counts,
            "occurs": occurs,
            "touched": np.maximum(np.asarray(state.touched), counts > 0),
            "step": int(state.step) + len(y), "globals": gl,
            "loss": float(jnp.sum(outs.loss))}


def _check_table(name, got, old, ref64, occurs):
    got, old = np.asarray(got), np.asarray(old)
    in_block = occurs > 0
    # entries the block does not name are the same bits, not the same value
    assert got[~in_block].tobytes() == old[~in_block].tobytes(), name
    g64 = got.astype(np.float64)
    if got.dtype == np.float32:
        np.testing.assert_allclose(g64[in_block], ref64[in_block],
                                   rtol=3e-6, atol=1e-6, err_msg=name)
        return
    assert got.dtype == ml_dtypes.bfloat16
    # ONE rounding of the f32 result: within half a bfloat16 step of it (a
    # delta rounded to bfloat16 and then added in bfloat16 lands a whole
    # step off on some entries), and exactly it where a feature has one lane
    half_step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(ref64), 1e-30)))
                        - 8)
    assert np.all(np.abs(g64 - ref64)[in_block]
                  <= half_step[in_block] * 1.02 + 1e-7), name
    once = occurs == 1
    exact = ref64.astype(np.float32).astype(ml_dtypes.bfloat16)
    close = np.abs(g64 - exact.astype(np.float64)) <= 2 * half_step * 1.01
    assert np.all(close[once]), name
    # f32 against f64 arithmetic may sit on the other side of a tie on a rare
    # entry; nearly all single-lane entries are the same bits
    same = got[once] == exact[once]
    assert same.mean() >= 0.95 if same.size else True, name


def _check_state(new, state, ref, rule, track):
    assert int(new.step) == ref["step"]
    np.testing.assert_array_equal(np.asarray(new.touched), ref["touched"])
    _check_table("weights", new.weights, state.weights,
                 ref["tables"]["weights"], ref["occurs"])
    if rule.use_covariance:
        _check_table("covars", new.covars, state.covars,
                     ref["tables"]["covars"], ref["occurs"])
    for k in state.slots:
        if k == DELTA_SLOT:
            np.testing.assert_array_equal(np.asarray(new.slots[k]),
                                          ref["slots"][k])
        else:
            _check_table(k, new.slots[k], state.slots[k], ref["slots"][k],
                         ref["occurs"])
    for k in rule.global_names:
        np.testing.assert_allclose(float(new.globals[k]),
                                   float(ref["globals"][k]), rtol=1e-6)


@pytest.mark.parametrize("kind", sorted(BLOCKS))
@pytest.mark.parametrize("case", sorted(CASES))
def test_block_apply_equals_dense_reference(case, kind):
    rule, hyper, dtype, track, binary = CASES[case]
    state = _state(rule, dtype, track, seed=len(case))
    idx, val, y = _block(kind, binary, seed=len(kind))
    ref = _dense_reference(rule, hyper, state, idx, val, y, track)
    if kind != "one_row":
        # the case means something: duplicates, and rows on both sides
        assert ref["occurs"].max() > 1
    step = jax.jit(make_train_fn(rule, hyper, mode="minibatch",
                                 track_deltas=track))
    new, loss = step(state, idx, val, y)
    _check_state(jax.device_get(new), state, ref, rule, track)
    assert float(loss) == pytest.approx(ref["loss"], rel=1e-5, abs=1e-6)


@pytest.mark.parametrize("case", ["arow_f32", "arow_bf16", "adagrad_rda"])
def test_rows_that_do_not_fire_leave_touched_and_counts_alone(case):
    """A block in which some rows fire and some do not: a feature that only
    quiet rows carry keeps `touched` 0 and its delta count, and is averaged
    by the fired count where both kinds of row carry it."""
    rule, hyper, dtype, _, binary = CASES[case]
    state = _state(rule, dtype, True, seed=5)
    idx, val, y = _block("mixed", binary, seed=11)
    # five rows get a feature of their own whose weight alone puts the row
    # far on the right side of its margin
    w = np.asarray(state.weights, np.float32).copy()
    own = np.setdiff1d(np.arange(DIMS), idx)[:5]
    for row, feature in enumerate(own):
        idx[row, 0], val[row, 0], w[feature] = feature, 1.0, 50.0 * y[row]
    state = state.replace(weights=jnp.asarray(w, dtype),
                          touched=jnp.zeros_like(state.touched))
    ref = _dense_reference(rule, hyper, state, idx, val, y, True)
    quiet = (ref["occurs"] > 0) & (ref["counts"] == 0)
    both = (ref["counts"] > 0) & (ref["counts"] < ref["occurs"])
    assert quiet.any() and both.any(), "the block does not show the case"
    step = jax.jit(make_train_fn(rule, hyper, mode="minibatch",
                                 track_deltas=True))
    new, _ = step(state, idx, val, y)
    new = jax.device_get(new)
    assert not np.asarray(new.touched)[quiet].any()
    np.testing.assert_array_equal(np.asarray(new.slots[DELTA_SLOT])[quiet],
                                  np.asarray(state.slots[DELTA_SLOT])[quiet])
    _check_state(new, state, ref, rule, True)


@pytest.mark.parametrize("case", ["arow_f32", "adagrad_rda",
                                  "pa1a_regr_globals"])
def test_one_row_block_equals_scan(case):
    rule, hyper, dtype, _, binary = CASES[case]
    state = _state(rule, dtype, False, seed=3)
    idx, val, y = _block("one_row", binary, seed=2)
    # a row names a feature once, as staged rows do
    idx[0] = np.where(idx[0] == DIMS, DIMS,
                      np.random.default_rng(0).permutation(DIMS)[:idx.shape[1]])
    got, _ = make_train_step(rule, hyper, mode="minibatch",
                             donate=False)(state, idx, val, y)
    want, _ = make_train_step(rule, hyper, mode="scan",
                              donate=False)(state, idx, val, y)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(got)),
                    jax.tree_util.tree_leaves(jax.device_get(want))):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64),
                                   rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("case", ["arow_f32", "arow_bf16", "adagrad_rda"])
def test_feature_shard_stripe_equals_dense_reference(case):
    """The same step on [D/4] stripes inside shard_map: stripe-local ids,
    pad index = the stripe's length, row scalars psum'd."""
    from jax.sharding import PartitionSpec as P

    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.runtime.jax_compat import shard_map

    rule, hyper, dtype, track, binary = CASES[case]
    n_dev = 4
    mesh = make_mesh(n_dev)
    axis = mesh.axis_names[0]
    state = _state(rule, dtype, track, seed=8)
    idx, val, y = _block("small_mixed", binary, seed=4)
    assert apply_strategy(DIMS // n_dev, idx.size) == "batch_local"
    ref = _dense_reference(rule, hyper, state, idx, val, y, track)
    body = make_train_fn(rule, hyper, mode="minibatch", track_deltas=track,
                         feature_shard=(axis, DIMS // n_dev))
    specs = jax.tree.map(lambda leaf: P(axis) if leaf.ndim == 1 else P(),
                         state)
    step = jax.jit(shard_map(body, mesh=mesh,
                             in_specs=(specs, P(), P(), P()),
                             out_specs=(specs, P()), check_vma=False))
    new, _ = step(state, idx, val, y)
    _check_state(jax.device_get(new), state, ref, rule, track)


def test_negative_ids_count_from_the_end_as_at_does():
    """`.at[]` reads a negative id from the table's end, and the gather side
    of the step still does; the write side follows it."""
    rule, hyper, dtype, _, _ = CASES["arow_f32"]
    state = _state(rule, dtype, False, seed=1)
    idx, val, y = _block("mixed", True, seed=6)
    neg = np.where(idx < DIMS, idx - DIMS, idx).astype(np.int32)
    step = jax.jit(make_train_fn(rule, hyper, mode="minibatch"))
    a, _ = step(state, idx, val, y)
    b, _ = step(state, neg, val, y)
    for x, z in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


@pytest.mark.parametrize("dims,lanes,want", [
    (1 << 28, 1024 * 64, "batch_local"),   # the benchmark's AROW cells
    (1 << 24, 1024 * 64, "batch_local"),   # the default -dims: where they meet
    (1 << 22, 1024 * 64, "dense"),
    (1 << 22, 1024 * 16, "batch_local"),
    (DENSE_APPLY_BELOW * 4096 - 1, 4096, "dense"),
    (DENSE_APPLY_BELOW * 4096, 4096, "batch_local"),
])
def test_strategy_is_a_function_of_shapes(dims, lanes, want):
    assert apply_strategy(dims, lanes) == want


@pytest.mark.parametrize("case", [
    "arow_f32", "arow_bf16", "pa1_regr", "arow_track_deltas",
    # covariance + hyper, a plain classifier, derived weights with and
    # without the delta clock, running label statistics (`pre_batch`)
    "scw1", "scw1_track_deltas", "pa1", "adagrad_rda",
    "adagrad_rda_track_deltas", "pa1a_regr_globals"])
def test_the_two_strategies_agree_on_the_same_rows(case):
    """The same rows once as they are and once with as many pad lanes again
    as make the block too wide for the table: the dense strategy runs on
    those, and leaves the same state."""
    rule, hyper, dtype, track, binary = CASES[case]
    state = _state(rule, dtype, track, seed=9)
    idx, val, y = _block("heavy_duplicates", binary, seed=3)
    times = -(-DIMS // (DENSE_APPLY_BELOW * idx.size)) + 1
    wide_idx = np.concatenate(
        [idx] + [np.full_like(idx, DIMS)] * (times - 1), axis=1)
    wide_val = np.concatenate(
        [val] + [np.zeros_like(val)] * (times - 1), axis=1)
    assert apply_strategy(DIMS, wide_idx.size) == "dense"
    step = jax.jit(make_train_fn(rule, hyper, mode="minibatch",
                                 track_deltas=track))
    local, _ = step(state, idx, val, y)
    dense, _ = step(state, wide_idx, wide_val, y)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(local)),
                    jax.tree_util.tree_leaves(jax.device_get(dense))):
        if a.dtype == ml_dtypes.bfloat16:   # a tie may round the other way
            assert (a != b).mean() < 1e-4
            np.testing.assert_allclose(a.astype(np.float32),
                                       b.astype(np.float32), rtol=2 ** -7)
        else:
            np.testing.assert_allclose(a, b, rtol=3e-6, atol=1e-6)


def test_mix_replicas_run_the_block_local_step():
    """MixTrainer's replicas (`track_deltas`, a leading replica axis inside
    shard_map) on tables long enough for the block-local strategy: one mixed
    step equals the delta-weighted average of two replicas trained apart."""
    from hivemall_tpu.parallel import MixConfig, MixTrainer, make_mesh

    rng = np.random.default_rng(12)
    rows, lanes = 16, 4
    assert apply_strategy(DIMS, rows * lanes) == "batch_local"
    idx = rng.choice(40, size=(2, rows, lanes)).astype(np.int32) * 3001
    val = rng.normal(size=(2, rows, lanes)).astype(np.float32)
    y = np.sign(rng.normal(size=(2, rows))).astype(np.float32)
    fn = jax.jit(make_train_fn(C.PERCEPTRON, {}, mode="minibatch",
                               track_deltas=True))
    replicas = []
    for i in range(2):
        st, _ = fn(init_linear_state(DIMS, slot_names=(DELTA_SLOT,)),
                   idx[i], val[i], y[i])
        replicas.append(jax.device_get(st))
    (w0, d0), (w1, d1) = [(np.asarray(r.weights), np.asarray(
        r.slots[DELTA_SLOT])) for r in replicas]
    assert d0.sum() > 0 and d1.sum() > 0
    tot = d0 + d1
    want = np.where(tot > 0, (w0 * d0 + w1 * d1) / np.maximum(tot, 1), w0)

    trainer = MixTrainer(C.PERCEPTRON, {}, DIMS, make_mesh(2),
                         MixConfig(reduction="average"))
    state, _ = trainer.step(trainer.init(),
                            *trainer.shard_blocks(idx, val, y))
    final = trainer.final_state(state)
    np.testing.assert_allclose(np.asarray(final.weights), want, rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(final.touched), tot > 0)
