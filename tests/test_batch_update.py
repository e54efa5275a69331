"""The segment-sum batched backend (core/batch_update.py + the staged
plans in ops/scatter.py): plan construction invariants, parity pins
against the engine's scan/minibatch modes, and the equal-holdout-logloss
gate at the default batch size across the AROW / CW / AdaGrad rule
families.

Parity contract (docs/execution_backends.md): the batched backend IS the
minibatch semantics — same per-feature sums, f32 accumulation, count
averaging — up to float reduction order, so integer tables (touched,
DELTA_SLOT counts) pin EXACT and float tables pin to tolerance. For
derive_w rules a feature shared by an updated and a non-updated row of
the same chunk gets the recomputed weight (w is a pure function of the
post-update slots), as in the minibatch step."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hivemall_tpu.core.batch_update import (make_batch_train_step,
                                            stage_block_plans,
                                            stage_epoch_plans)
from hivemall_tpu.core.engine import DELTA_SLOT, make_train_step
from hivemall_tpu.core.state import init_linear_state
from hivemall_tpu.models import classifier as C
from hivemall_tpu.models import regression as R
from hivemall_tpu.ops.scatter import (build_staged_plan, pad_plan,
                                      plan_slot_bucket, staged_gather,
                                      staged_scatter_add,
                                      staged_segment_totals)

RULES = [
    (C.PERCEPTRON, {}, True),
    (C.PA, {}, True),
    (C.PA1, {"c": 1.0}, True),
    (C.PA2, {"c": 1.0}, True),
    (C.CW, {"phi": 1.0}, True),
    (C.AROW, {"r": 0.1}, True),
    (C.AROWH, {"r": 0.1, "c": 1.0}, True),
    (C.SCW1, {"phi": 1.0, "c": 1.0}, True),
    (C.SCW2, {"phi": 1.0, "c": 1.0}, True),
    (C.ADAGRAD_RDA, {"eta": 0.1, "lambda": 1e-6, "scale": 100.0}, True),
    (R.AROW_REGR, {"r": 0.1}, False),
    (R.AROWE2_REGR, {"r": 0.1, "epsilon": 0.01}, False),
    (R.ADAGRAD_REGR, {"eta": 1.0, "eps": 1.0, "scale": 100.0}, False),
    (R.ADADELTA_REGR, {"rho": 0.95, "eps": 1e-6, "scale": 100.0}, False),
]
RULE_IDS = [r[0].name for r in RULES]


def _state(rule, d, track_deltas=False):
    return init_linear_state(
        d, use_covariance=rule.use_covariance,
        slot_names=rule.slot_names + ((DELTA_SLOT,) if track_deltas else ()),
        global_names=rule.global_names)


def _data(n, k, d, seed=2, binary=True, pad_frac=0.25):
    """Hashed-style rows: features collide within and across rows."""
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, d, size=(n, k)).astype(np.int32)
    if pad_frac:
        idx[:, -1] = np.where(rng.rand(n) < pad_frac, d, idx[:, -1])
    val = rng.randn(n, k).astype(np.float32)
    val[idx >= d] = 0.0
    y = np.sign(rng.randn(n)).astype(np.float32) if binary else \
        rng.randn(n).astype(np.float32) * 0.1
    return idx, val, y


# ---------------------------------------------------------------- plan layer

def test_staged_plan_matches_numpy_reduction():
    rng = np.random.RandomState(7)
    d = 100
    idx = rng.randint(0, d, size=400).astype(np.int32)
    idx[::7] = d  # pad lanes
    upd = rng.randn(400).astype(np.float32)
    plan = build_staged_plan(idx, d)
    table = jnp.zeros((d,), jnp.float32)
    out = staged_scatter_add(table, jax.tree_util.tree_map(jnp.asarray, plan),
                             staged_segment_totals(
                                 jax.tree_util.tree_map(jnp.asarray, plan),
                                 jnp.asarray(upd)))
    expect = np.zeros(d, np.float32)
    np.add.at(expect, idx[idx < d], upd[idx < d])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5,
                               atol=1e-5)


def test_staged_plan_invariants_and_padding():
    rng = np.random.RandomState(1)
    d = 50
    idx = rng.randint(0, d, size=96).astype(np.int32)
    idx[-10:] = d
    plan = build_staged_plan(idx, d)
    rep = np.asarray(plan.rep)
    # strictly ascending incl. the dropped tail => unique+sorted promises
    assert np.all(np.diff(rep.astype(np.int64)) > 0)
    # live segment spans tile the live lanes exactly once
    live = rep < d
    assert (np.asarray(plan.ends)[live]
            - np.asarray(plan.starts)[live]).sum() == (idx < d).sum()
    # lane_seg stays in range even when the bucket exactly fits
    assert np.asarray(plan.lane_seg).max() < rep.shape[0]
    # widening to a larger bucket keeps the structure; shrinking refuses
    wider = pad_plan(plan, rep.shape[0] + 64, d)
    assert np.all(np.diff(np.asarray(wider.rep).astype(np.int64)) > 0)
    assert np.all(np.asarray(wider.starts)[-64:] == idx.shape[0])
    with pytest.raises(ValueError):
        pad_plan(wider, rep.shape[0], d)
    # bucket sizing: 8 buckets per octave, floor at min_slots
    assert plan_slot_bucket(1) == 256
    assert plan_slot_bucket(300) == 320
    assert plan_slot_bucket(100_000) == 106_496


def test_staged_gather_reads_fill_on_dropped_slots():
    d = 16
    idx = np.asarray([0, 3, 3, d, d], np.int32)
    plan = jax.tree_util.tree_map(jnp.asarray, build_staged_plan(idx, d))
    table = jnp.arange(d, dtype=jnp.float32) + 10.0
    uniq = staged_gather(table, plan, fill=1.0)
    # slots: [0, 3, pad...] -> table rows for live, fill for drops
    assert float(uniq[0]) == 10.0 and float(uniq[1]) == 13.0
    assert float(uniq[2]) == 1.0


def test_stage_block_plans_shapes_and_tail():
    rng = np.random.RandomState(0)
    idx = rng.randint(0, 64, size=(53, 4)).astype(np.int32)
    plans = stage_block_plans(idx, 8, 64)
    assert plans.main.order.shape == (6, 32)
    assert plans.tail is not None
    assert plans.tail.order.shape == (5 * 4,)
    # divisible block: no tail
    assert stage_block_plans(idx[:48], 8, 64).tail is None
    # epoch staging: common bucket across blocks, loud on indivisible rows
    epoch_idx = rng.randint(0, 64, size=(3, 16, 4)).astype(np.int32)
    ep = stage_epoch_plans(epoch_idx, 8, 64)
    assert ep.main.order.shape[:2] == (3, 2)
    with pytest.raises(ValueError):
        stage_epoch_plans(epoch_idx[:, :15], 8, 64)


# ------------------------------------------------------------- parity pins

@pytest.mark.parametrize("rule,hyper,binary", RULES, ids=RULE_IDS)
def test_batch_b1_equals_minibatch_b1(rule, hyper, binary):
    """B=1 through the staged-plan backend == minibatch B=1 (which the
    engine pins equal to scan mode): same float tables to tolerance,
    integer tables exact."""
    d = 48
    idx, val, y = _data(40, 4, d, binary=binary)
    mb = make_train_step(rule, hyper, mode="minibatch", donate=False)
    s_ref = _state(rule, d)
    for i in range(len(y)):
        s_ref, _ = mb(s_ref, idx[i:i + 1], val[i:i + 1], y[i:i + 1])
    bstep = make_batch_train_step(rule, hyper, batch_size=1, donate=False)
    s_b, _ = bstep(_state(rule, d), idx, val, y,
                   stage_block_plans(idx, 1, d))
    # derive_w rules rebuild w from slot sums the batch backend forms as
    # cumsum[end] - cumsum[start] (ops/scatter.staged_segment_totals): a
    # 65.09 squared-gradient lane behind a 66,940 chunk prefix is rounded
    # to the prefix's ulp (2^-8 -> 4.4e-5 relative in G, 2.3e-5 in w ~
    # G^-1/2). Sequential numpy f32 cumsum reproduces the batch value bit
    # for bit and the reference slots give w to 2e-8 in float64, so this
    # is the documented reduction-order difference, not a divergence; the
    # bound is eps_f32 * prefix / segment (6e-5 here), hence 1e-4.
    w_rtol = 1e-4 if rule.derive_w is not None else 2e-5
    np.testing.assert_allclose(np.asarray(s_b.weights),
                               np.asarray(s_ref.weights),
                               rtol=w_rtol, atol=1e-6)
    if rule.use_covariance:
        np.testing.assert_allclose(np.asarray(s_b.covars),
                                   np.asarray(s_ref.covars),
                                   rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(s_b.touched),
                                  np.asarray(s_ref.touched))
    assert int(s_b.step) == int(s_ref.step)
    for g in rule.global_names:
        np.testing.assert_allclose(float(s_b.globals[g]),
                                   float(s_ref.globals[g]), rtol=1e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("rule,hyper,binary", RULES, ids=RULE_IDS)
def test_batch_equals_minibatch_blocks(rule, hyper, binary):
    """The batched backend vs the xla minibatch path at B=8 over a block
    with a tail chunk: float tables to tolerance, touched and DELTA_SLOT
    counts EXACT, derive_w rules on colliding features too."""
    d, b = 128, 8
    idx, val, y = _data(53, 4, d, binary=binary)
    from hivemall_tpu.core.engine import make_train_fn

    mb = jax.jit(make_train_fn(rule, hyper, mode="minibatch",
                               track_deltas=True))
    s_ref = _state(rule, d, track_deltas=True)
    for s in range(0, len(y), b):
        s_ref, _ = mb(s_ref, idx[s:s + b], val[s:s + b], y[s:s + b])
    bstep = make_batch_train_step(rule, hyper, batch_size=b, donate=False,
                                  track_deltas=True)
    s_b, _ = bstep(_state(rule, d, track_deltas=True), idx, val, y,
                   stage_block_plans(idx, b, d))
    # derive_w rules rebuild w from slot sums the two paths form in
    # different orders
    np.testing.assert_allclose(np.asarray(s_b.weights),
                               np.asarray(s_ref.weights),
                               rtol=1e-4 if rule.derive_w is not None
                               else 5e-5, atol=5e-6)
    if rule.use_covariance:
        np.testing.assert_allclose(np.asarray(s_b.covars),
                                   np.asarray(s_ref.covars),
                                   rtol=5e-5, atol=5e-6)
    np.testing.assert_array_equal(np.asarray(s_b.touched),
                                  np.asarray(s_ref.touched))
    # integer update-count table: exact (f32 cumsum of 0/1 under 2^24)
    np.testing.assert_array_equal(
        np.asarray(s_b.slots[DELTA_SLOT]),
        np.asarray(s_ref.slots[DELTA_SLOT]))


def test_batch_update_variant_equals_vmapped_row_update():
    """Rules shipping an explicit batch_update (perceptron/CW/AROW/AROWh)
    must produce the same updates as the vmapped row rule — drop the
    explicit form and the staged path must not move."""
    from dataclasses import replace

    d, b = 96, 8
    idx, val, y = _data(24, 4, d, seed=5)
    for rule, hyper in [(C.AROW, {"r": 0.1}),
                        (C.AROWH, {"r": 0.1, "c": 1.0}),
                        (C.CW, {"phi": 1.0}),
                        (C.PERCEPTRON, {})]:
        assert rule.batch_update is not None
        stripped = replace(rule, batch_update=None)
        plans = stage_block_plans(idx, b, d)
        s1, l1 = make_batch_train_step(rule, hyper, batch_size=b,
                                       donate=False)(
            _state(rule, d), idx, val, y, plans)
        s2, l2 = make_batch_train_step(stripped, hyper, batch_size=b,
                                       donate=False)(
            _state(stripped, d), idx, val, y, plans)
        np.testing.assert_allclose(np.asarray(s1.weights),
                                   np.asarray(s2.weights), rtol=1e-6,
                                   atol=1e-7)
        if rule.use_covariance:
            np.testing.assert_allclose(np.asarray(s1.covars),
                                       np.asarray(s2.covars), rtol=1e-6,
                                       atol=1e-7)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_fit_linear_batch_option_end_to_end():
    """-batch B through the public train_* entry: trains, predicts, and
    matches -mini_batch B to tolerance on the same rows; invalid
    combinations refuse loudly."""
    rng = np.random.RandomState(11)
    n, d = 120, 256
    idx_rows = [rng.choice(d, 5, replace=False).astype(np.int64)
                for _ in range(n)]
    val_rows = [rng.randn(5).astype(np.float32) for _ in range(n)]
    w_true = rng.randn(d).astype(np.float32)
    labels = [1.0 if w_true[i].sum() + v @ w_true[i] > 0 else -1.0
              for i, v in zip(idx_rows, val_rows)]
    m_batch = C.train_arow((idx_rows, val_rows), labels,
                           f"-dims {d} -batch 16")
    m_mini = C.train_arow((idx_rows, val_rows), labels,
                          f"-dims {d} -mini_batch 16")
    np.testing.assert_allclose(np.asarray(m_batch.state.weights),
                               np.asarray(m_mini.state.weights),
                               rtol=5e-5, atol=5e-6)
    s_b = m_batch.predict((idx_rows[:8], val_rows[:8]))
    s_m = m_mini.predict((idx_rows[:8], val_rows[:8]))
    np.testing.assert_allclose(s_b, s_m, rtol=5e-4, atol=5e-5)
    for bad in ("-batch 16 -mini_batch 4", "-batch 16 -native_scan",
                "-batch 16 -pallas", "-batch 0"):
        with pytest.raises(ValueError):
            C.train_arow((idx_rows, val_rows), labels, f"-dims {d} {bad}")


def test_fit_linear_batch_multi_epoch_plan_cache():
    """-batch with -iters replays cached plans (no shuffle) and restages
    under -shuffle; both converge to a usable model."""
    rng = np.random.RandomState(4)
    n, d = 80, 128
    idx_rows = [rng.choice(d, 4, replace=False).astype(np.int64)
                for _ in range(n)]
    val_rows = [np.ones(4, np.float32) for _ in range(n)]
    w_true = rng.randn(d).astype(np.float32)
    labels = [1.0 if w_true[i].sum() > 0 else -1.0 for i in idx_rows]
    for opts in (f"-dims {d} -batch 8 -iters 3 -disable_cv",
                 f"-dims {d} -batch 8 -iters 3 -disable_cv -shuffle"):
        m = C.train_arow((idx_rows, val_rows), labels, opts)
        scores = m.predict((idx_rows, val_rows))
        acc = np.mean((scores > 0) == (np.asarray(labels) > 0))
        assert acc > 0.8, (opts, acc)


def test_batch_backend_bf16_storage():
    """bf16 tables (the above-2^24-dims storage policy) go through the
    staged path: per-window widening only, f32 accumulation, finite
    results."""
    d, b = 64, 8
    idx, val, y = _data(24, 4, d, seed=9)
    st = init_linear_state(d, use_covariance=True, dtype=jnp.bfloat16)
    plans = stage_block_plans(idx, b, d)
    step = make_batch_train_step(C.AROW, {"r": 0.1}, batch_size=b,
                                 donate=False)
    s2, loss = step(st, idx, val, y, plans)
    assert s2.weights.dtype == jnp.bfloat16
    assert s2.covars.dtype == jnp.bfloat16
    w = np.asarray(s2.weights, dtype=np.float32)
    assert np.isfinite(w).all() and np.abs(w).sum() > 0


# ------------------------------------------------- equal-holdout-logloss gate

def _planted(n, k, d, rng, w_true):
    """Train and holdout MUST share w_true — labels drawn from an
    independent weight vector would make holdout logloss independent of
    what the model learned, and the gate below would measure score-shape
    noise instead of generalization."""
    idx = rng.randint(0, d, size=(n, k)).astype(np.int32)
    val = np.abs(rng.randn(n, k)).astype(np.float32)
    margin = np.einsum("nk,nk->n", val, w_true[idx])
    y = np.where(margin + 0.3 * rng.randn(n) > 0, 1.0, -1.0) \
        .astype(np.float32)
    return idx, val, y


@pytest.mark.parametrize("rule,hyper", [
    (C.AROW, {"r": 0.1}),
    (C.CW, {"phi": 1.0}),
    (C.ADAGRAD_RDA, {"eta": 0.1, "lambda": 1e-6, "scale": 100.0}),
], ids=["arow", "cw", "adagrad_rda"])
def test_equal_holdout_logloss_at_default_batch(rule, hyper):
    """The AdaBatch accuracy gate, in-miniature: at the default batch
    size, the batched backend's holdout logloss must sit within the
    pinned parity tolerance of the per-row (B=1) model on a planted-
    signal task — batching may move individual weights, it may not move
    generalization. Margin classifiers are not calibrated, so every arm
    gets the SAME single-parameter score standardization before the
    sigmoid (bench.py's holdout_logloss convention — scale-free, smooth
    where raw-sigmoid logloss saturates)."""
    from hivemall_tpu.evaluation.metrics import logloss

    d, k, b = 512, 8, 64
    rng = np.random.RandomState(13)
    w_true = (rng.randn(d) * (rng.rand(d) < 0.3)).astype(np.float32)
    idx, val, y = _planted(1536, k, d, rng, w_true)
    h_idx, h_val, h_y = _planted(512, k, d, rng, w_true)

    def holdout_ll(batch_size):
        step = make_batch_train_step(rule, hyper, batch_size=batch_size,
                                     donate=False)
        st, _ = step(_state(rule, d), idx, val, y,
                     stage_block_plans(idx, batch_size, d))
        w = np.asarray(st.weights, dtype=np.float32)
        scores = np.einsum("nk,nk->n", h_val, w[h_idx])
        scores = scores / max(float(np.std(scores)), 1e-9)
        return logloss(1.0 / (1.0 + np.exp(-scores)), h_y)

    ll_b1 = holdout_ll(1)
    ll_bd = holdout_ll(b)
    assert abs(ll_bd - ll_b1) <= 0.02, (
        f"{rule.name}: holdout logloss moved {ll_b1:.4f} -> {ll_bd:.4f} "
        f"at B={b} (tolerance 0.02)")
