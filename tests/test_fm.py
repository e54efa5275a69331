"""Factorization Machine tests.

Mirrors the reference's convergence-threshold test style
(ref: core/src/test/java/hivemall/fm/FactorizationMachineUDTFTest.java:23-63,
which asserts final average loss <= 0.1 after iterating) on synthetic
interaction data instead of the bundled fixture."""

import numpy as np
import pytest

from hivemall_tpu.models import fm as FM


def _gen_interaction_data(n=1500, d=30, k=2, seed=11, classification=True):
    """Data generated by a ground-truth FM: y from w0 + wx + pairwise V."""
    rng = np.random.RandomState(seed)
    w = rng.randn(d) * 0.3
    v = rng.randn(d, k) * 0.4
    rows_i, rows_v, ys = [], [], []
    for _ in range(n):
        nnz = rng.randint(3, 8)
        idx = rng.choice(d, size=nnz, replace=False).astype(np.int64)
        val = np.ones(nnz, dtype=np.float32)
        s = w[idx].sum()
        vx = v[idx]
        s += 0.5 * float((vx.sum(0) ** 2 - (vx ** 2).sum(0)).sum())
        rows_i.append(idx)
        rows_v.append(val)
        ys.append(np.sign(s) if classification else s)
    return (rows_i, rows_v), np.asarray(ys, dtype=np.float32)


def _logloss(p, y):
    z = p * y
    return float(np.mean(np.log1p(np.exp(-np.clip(z, -30, 30)))))


def test_fm_classification_convergence():
    feats, y = _gen_interaction_data(classification=True)
    model = FM.train_fm(feats, y,
                        "-dims 64 -classification -factor 5 -iters 50 -eta 0.05 "
                        "-lambda0 0.0 -disable_cv -seed 1")
    p = model.predict(feats)
    # reference test asserts cumulative avg loss <= 0.1
    assert _logloss(p, y) <= 0.1, _logloss(p, y)


def test_fm_regression_convergence():
    feats, y = _gen_interaction_data(classification=False)
    model = FM.train_fm(feats, y, "-dims 64 -factor 5 -iters 20 -eta 0.01 -disable_cv")
    rmse = float(np.sqrt(np.mean((model.predict(feats) - y) ** 2)))
    assert rmse < 0.35, rmse


def test_fm_minibatch_mode():
    feats, y = _gen_interaction_data(classification=True)
    # averaged minibatch application (sum/count, FloatAccumulator semantics)
    # means one block advances like ~one per-row step: eta scaled up vs scan
    model = FM.train_fm(feats, y,
                        "-dims 64 -classification -factor 5 -iters 30 -eta 0.2 "
                        "-mini_batch 128 -disable_cv")
    p = model.predict(feats)
    acc = float(np.mean(np.sign(p) == y))
    assert acc >= 0.93, acc


def test_fm_adaptive_regularization_runs():
    feats, y = _gen_interaction_data(n=400)
    model = FM.train_fm(feats, y,
                        "-dims 64 -classification -factor 5 -iters 3 -adareg -disable_cv")
    assert np.isfinite(float(model.state.lambda_w0))
    assert np.all(np.isfinite(np.asarray(model.state.lambda_v)))


def test_fm_model_rows_and_fm_predict():
    feats, y = _gen_interaction_data(n=300)
    model = FM.train_fm(feats, y, "-dims 64 -classification -factor 5 -iters 5 -disable_cv")
    w0, f, w, v = model.model_rows()
    assert v.shape[1] == 5
    # fm_predict over model rows == model.predict for a row using those features
    idx = feats[0][0]
    val = feats[1][0]
    sel = {int(ff): i for i, ff in enumerate(f)}
    rows = [sel[int(i)] for i in idx if int(i) in sel]
    p_udaf = FM.fm_predict(w0, w[rows], v[rows], idx, val[: len(rows)])
    p_model = float(model.predict(([idx], [val]))[0])
    assert p_udaf == pytest.approx(p_model, rel=1e-4, abs=1e-4)


def test_fm_convergence_check_stops_early():
    feats, y = _gen_interaction_data(n=300)
    model = FM.train_fm(feats, y, "-dims 64 -classification -factor 5 -iters 100 -cv_rate 0.05")
    # with a loose cv_rate the run must stop well before 100 epochs
    assert int(model.state.step) < 100 * 300


def _plain_accumulator_step(st, hyper, idx, val, y, va):
    """One `-mini_batch` block of FM in numpy float64, the reference's
    FloatAccumulator rule (RegressionBaseUDTF.java:281-295): every row
    against the block-start parameters, each parameter's deltas summed and
    divided by the number of training rows that carry it (w0: by the number
    of training rows), validation rows (`va` 1) adapting the lambdas only."""
    dims, k = st.w.shape[0], hyper.factors
    w0, w = float(st.w0), np.asarray(st.w, np.float64)
    v = np.asarray(st.v, np.float64)[:, :k]
    lam_w0, lam_w = float(st.lambda_w0), float(st.lambda_w)
    lam_v = np.asarray(st.lambda_v, np.float64)[:k]
    sum_w, sum_v, count = np.zeros(dims), np.zeros((dims, k)), np.zeros(dims)
    sum_w0 = loss = 0.0
    touched = np.asarray(st.touched).copy()
    dl = [0.0, 0.0, np.zeros(k)]
    for r in range(len(y)):
        # as `.at[]` reads an id: a negative one counts from the table's
        # end, and what is still outside the table is dropped
        wrapped = np.where(idx[r] < 0, idx[r] + dims, idx[r])
        live = (wrapped >= 0) & (wrapped < dims)
        ids, x = wrapped[live], val[r][live].astype(np.float64)
        eta = hyper.eta.eta0 / (int(st.step) + 1 + r) ** hyper.eta.power_t
        wg, vg = w[ids], v[ids]
        sum_f = (vg * x[:, None]).sum(0)
        p = w0 + (wg * x).sum() + 0.5 * (
            sum_f ** 2 - ((vg * x[:, None]) ** 2).sum(0)).sum()
        if hyper.classification:
            z = p * y[r]
            g = (1.0 / (1.0 + np.exp(-z)) - 1.0) * y[r]
            row_loss = np.logaddexp(0.0, -z)
        else:
            g = p - y[r]
            row_loss = 0.5 * g * g
        grad_v = x[:, None] * sum_f[None, :] - vg * (x * x)[:, None]
        dv = -eta * (g * grad_v + 2.0 * lam_v[None, :] * vg)
        if va[r]:
            v_dash = vg + dv
            f_dash = (x[:, None] * v_dash).sum(0)
            f_dash_f = (x[:, None] ** 2 * v_dash * vg).sum(0)
            dl[0] += 2.0 * eta * eta * g * w0
            dl[1] += 2.0 * eta * eta * g * (wg * x).sum()
            dl[2] += 2.0 * eta * eta * g * (f_dash * sum_f - f_dash_f)
            continue
        loss += row_loss
        sum_w0 += -eta * (g + 2.0 * lam_w0 * w0)
        np.add.at(sum_w, ids, -eta * (g * x + 2.0 * lam_w * wg))
        np.add.at(sum_v, ids, dv)
        np.add.at(count, ids, 1.0)
        touched[ids] = 1
    denom = np.maximum(count, 1.0)
    out = {"w0": w0 + sum_w0 / max(float((1 - va).sum()), 1.0),
           "w": w + sum_w / denom, "v": v + sum_v / denom[:, None],
           "touched": touched, "loss": loss,
           "lambda_w0": lam_w0, "lambda_w": lam_w, "lambda_v": lam_v}
    if hyper.adareg:
        out.update(lambda_w0=max(0.0, lam_w0 + dl[0]),
                   lambda_w=max(0.0, lam_w + dl[1]),
                   lambda_v=np.maximum(0.0, lam_v + dl[2]))
    return out


@pytest.mark.parametrize("adareg", [False, True], ids=["plain", "adareg"])
@pytest.mark.parametrize("classification", [True, False],
                         ids=["classification", "regression"])
@pytest.mark.parametrize("factors,lanes_of_v", [(4, 4), (5, 8), (8, 8),
                                                (10, 16)])
def test_minibatch_step_equals_plain_accumulator_reference(
        factors, lanes_of_v, classification, adareg):
    """The mini-batch step with V unpadded (k = 4, 8) and padded to the
    next multiple of 8 lanes (k = 5, 10), against the accumulator written
    out above: duplicates within and across rows, pad lanes, two blocks in
    a row so that the second starts from non-zero w and counters."""
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step

    hyper = FMHyper(factors=factors, classification=classification,
                    adareg=adareg)
    assert hyper.padded_factors == lanes_of_v
    rng = np.random.RandomState(11)
    b, lanes, dims = 48, 6, 512
    step = make_fm_step(hyper, "minibatch")
    state = init_fm_state(dims, hyper)
    for _ in range(2):
        idx = rng.choice(40, size=(b, lanes)).astype(np.int32) * 11
        idx[rng.rand(b, lanes) < 0.15] = dims
        val = rng.randn(b, lanes).astype(np.float32)
        val[idx == dims] = 0.0
        y = rng.choice([-1.0, 1.0], b).astype(np.float32) if classification \
            else rng.randn(b).astype(np.float32)
        va = (rng.rand(b) < 0.25).astype(np.float32) if adareg \
            else np.zeros(b, np.float32)
        before = jax.device_get(state)
        want = _plain_accumulator_step(before, hyper, idx, val, y, va)
        state, loss = step(state, jnp.asarray(idx), jnp.asarray(val),
                           jnp.asarray(y), jnp.asarray(va))
        got = jax.device_get(state)
        assert int(got.step) == int(before.step) + b
        assert float(loss) == pytest.approx(want["loss"], rel=1e-5)
        assert float(got.w0) == pytest.approx(want["w0"], rel=1e-5, abs=1e-7)
        np.testing.assert_allclose(got.w, want["w"], rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(got.v[:, :factors], want["v"], rtol=1e-5,
                                   atol=1e-7)
        # V's pad lanes are written as the zeros they are
        assert (got.v[:, factors:] == 0).all()
        np.testing.assert_array_equal(got.touched, want["touched"])
        for name in ("lambda_w0", "lambda_w"):
            assert float(getattr(got, name)) == pytest.approx(
                want[name], rel=1e-5, abs=1e-9)
        np.testing.assert_allclose(got.lambda_v[:factors], want["lambda_v"],
                                   rtol=1e-5, atol=1e-9)


# name -> (rows, lanes, ids drawn from, share of pad lanes, a feature on
# every row, share of negative ids)
BLOCKS = {
    # one feature sits on every row and most other lanes repeat: the runs
    # of equal ids whose lanes must all write one value
    "one_feature_on_every_row": (64, 8, 5, 0.0, True, 0.0),
    "pad_lanes": (32, 8, 60, 0.4, False, 0.0),
    # `.at[]` counts a negative id from the table's end and drops what is
    # still outside; so must the block's own index space
    "negative_ids": (40, 6, 25, 0.15, True, 0.2),
    "one_row": (1, 8, 60, 0.25, False, 0.0),
}
DIMS = 4096


def _block(kind, adareg, seed=5):
    rows, lanes, distinct, pad, everywhere, negative = BLOCKS[kind]
    rng = np.random.RandomState(seed)
    pool = rng.choice(DIMS, size=distinct, replace=False)
    idx = rng.choice(pool, size=(rows, lanes)).astype(np.int32)
    if everywhere:
        idx[:, 0] = pool[0]
    neg = rng.rand(rows, lanes) < negative
    # in range from the end (-1 .. -dims) and beyond it (dropped)
    idx[neg] = -rng.randint(1, 2 * DIMS, size=int(neg.sum()))
    idx[rng.rand(rows, lanes) < pad] = DIMS
    val = rng.randn(rows, lanes).astype(np.float32)
    # a lane outside the table carries no value, as staging pads it
    val[(idx >= DIMS) | (idx < -DIMS)] = 0.0
    y = rng.choice([-1.0, 1.0], rows).astype(np.float32)
    va = (rng.rand(rows) < 0.3).astype(np.float32) if adareg \
        else np.zeros(rows, np.float32)
    return idx, val, y, va


def _mid_training_state(hyper, seed=9):
    """Non-zero w, some entries touched, the counter past zero."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    st = FM.init_fm_state(DIMS, hyper)
    w = (rng.randn(DIMS) * 0.2).astype(np.float32)
    w[rng.rand(DIMS) < 0.5] = 0.0
    return st.replace(w=jnp.asarray(w), w0=jnp.asarray(0.03, jnp.float32),
                      touched=jnp.asarray((w != 0).astype(np.int8)),
                      step=jnp.asarray(17, jnp.int32))


def _step_once(hyper, block):
    import jax
    import jax.numpy as jnp

    before = jax.device_get(_mid_training_state(hyper))
    state, loss = FM.make_fm_step(hyper, "minibatch")(
        _mid_training_state(hyper), *map(jnp.asarray, block))
    return before, jax.device_get(state), float(loss)


@pytest.mark.parametrize("adareg", [False, True], ids=["plain", "adareg"])
@pytest.mark.parametrize("factors", [8, 10])
@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_block_kinds_equal_the_plain_accumulator(kind, factors, adareg):
    """What the sort, the scans and the in-place writes must get right,
    block kind by block kind, against the accumulator over whole tables:
    the same entries touched, pad lanes and ids outside the table dropped
    as `.at[]` drops them, V's pad lanes exactly zero, and nothing outside
    the block's own entries moved by a bit."""
    hyper = FM.FMHyper(factors=factors, classification=True, adareg=adareg)
    block = _block(kind, adareg)
    before, got, loss = _step_once(hyper, block)
    want = _plain_accumulator_step(before, hyper, *block)
    np.testing.assert_array_equal(got.touched, want["touched"])
    assert int(got.step) == int(before.step) + len(block[2])
    assert loss == pytest.approx(want["loss"], rel=1e-5)
    assert float(got.w0) == pytest.approx(want["w0"], rel=1e-5, abs=1e-7)
    np.testing.assert_allclose(got.w, want["w"], rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(got.v[:, :factors], want["v"], rtol=1e-5,
                               atol=1e-7)
    assert (got.v[:, factors:] == 0).all()
    for name in ("lambda_w0", "lambda_w"):
        assert float(getattr(got, name)) == pytest.approx(
            want[name], rel=1e-5, abs=1e-9)
    np.testing.assert_allclose(got.lambda_v[:factors], want["lambda_v"],
                               rtol=1e-5, atol=1e-9)
    ids = np.where(block[0] < 0, block[0] + DIMS, block[0])
    quiet = np.setdiff1d(np.arange(DIMS), ids[(ids >= 0) & (ids < DIMS)])
    np.testing.assert_array_equal(got.w[quiet], before.w[quiet])
    np.testing.assert_array_equal(got.v[quiet], before.v[quiet])
    np.testing.assert_array_equal(got.touched[quiet], before.touched[quiet])


def test_validation_rows_leave_the_tables_alone():
    """Under -adareg a validation row adapts the lambdas only: a feature
    that validation rows alone carry keeps `touched`, w and its V row bit
    for bit, also where training rows of the same block write beside it."""
    hyper = FM.FMHyper(factors=10, classification=True, adareg=True)
    idx, val, y, _ = _block("pad_lanes", False)
    va = np.zeros(len(y), np.float32)
    va[::2] = 1.0
    # ids 1000.. on validation rows only, never on a training row
    idx[::2, :3] = 1000 + np.arange(3)
    idx[1::2][np.isin(idx[1::2], 1000 + np.arange(3))] = 7
    val[idx != DIMS] = 0.5
    before, got, _ = _step_once(hyper, (idx, val, y, va))
    only_va = np.setdiff1d(idx[::2][idx[::2] < DIMS],
                           idx[1::2][idx[1::2] < DIMS])
    assert set(1000 + np.arange(3)) <= set(only_va.tolist())
    np.testing.assert_array_equal(got.touched[only_va], before.touched[only_va])
    np.testing.assert_array_equal(got.w[only_va], before.w[only_va])
    np.testing.assert_array_equal(got.v[only_va], before.v[only_va])
    trained = np.unique(idx[1::2][idx[1::2] < DIMS])
    assert (got.touched[trained] == 1).all()
    assert float(got.lambda_w) != float(before.lambda_w)


@pytest.mark.parametrize("options,want", [
    ("-dims 262144 -mini_batch 4", "batch_local"),
    ("-dims 512 -mini_batch 64", "batch_local"),   # one plan, any shape
    ("-dims 4096", None),                          # the scan has no such stage
])
def test_train_call_span_says_which_plan_ran(options, want):
    from hivemall_tpu.runtime.tracing import SPAN_CALL, TRACER

    rng = np.random.default_rng(0)
    idx = rng.integers(0, 512, size=(128, 5))
    FM.train_fm((idx, np.ones(idx.shape, np.float32)),
                np.sign(rng.normal(size=128)), "-c -factor 10 " + options)
    call = next(sp for sp in TRACER.traces()[-1]["spans"]
                if sp["name"] == SPAN_CALL)
    assert call["args"]["entry"] == "fm"
    assert call["args"].get("apply") == want
