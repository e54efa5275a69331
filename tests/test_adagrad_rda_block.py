"""`train_adagrad_rda` under the block rule for derived weights
(core/engine.py, DERIVED_W_BLOCK_RULE) against the plain reference
`benchmark/refs/adagrad_rda.py`: numpy float64 from the published rule,
importing nothing of the program.

Rows are made with what the rule has to get right: ids repeated across the
rows of a block, pad lanes (ragged rows), one feature that every row carries
and a ragged tail block. Both of the step's arms (`dense`,
`batch_local`) run on the same input, with float32 and with bfloat16
weights; B = 1 is the exact scan bit for bit; a block's rows may come in
any order; `t` is rows x epochs after a tail block and after `-iters 2`;
emission equals a host selection with ids next to `dims - 1`.
"""

import os
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.refs import adagrad_rda as ref  # noqa: E402
from hivemall_tpu.core import engine  # noqa: E402
from hivemall_tpu.core.engine import make_train_fn, make_train_step  # noqa: E402
from hivemall_tpu.core.state import init_linear_state, model_rows  # noqa: E402
from hivemall_tpu.models.classifier import ADAGRAD_RDA, train_adagrad_rda  # noqa: E402

HYPER = {"eta": 0.1, "lambda": 1e-6, "scale": 100.0}
DIMS = 1 << 14
ROWS = 150          # 150 = 2 x 64 + 22 = 21 x 7 + 3: ragged tails
EVERY_ROW = 77      # the feature that every row carries
ARMS = {"dense": 1 << 40, "batch_local": 0}   # DENSE_APPLY_BELOW that forces it


def _rows(seed=0, rows=ROWS, dims=DIMS, pool=40):
    """Ragged rows over a small pool of ids (heavy repeats across rows), no
    id twice in one row, one feature on every row; labels from a planted
    model so that rows fire and rows do not."""
    rng = np.random.default_rng(seed)
    ids = rng.choice(dims, size=pool, replace=False)
    ids = ids[ids != EVERY_ROW]
    planted = rng.normal(size=dims)
    idx_rows, val_rows, labels = [], [], []
    for _ in range(rows):
        k = int(rng.integers(2, 8))
        row = np.concatenate([[EVERY_ROW], rng.choice(ids, k, replace=False)])
        val = np.round(rng.uniform(0.25, 2.0, row.size) * 64) / 64
        idx_rows.append(row.astype(np.int64))
        val_rows.append(val.astype(np.float32))
        labels.append(1.0 if planted[row] @ val + rng.normal() > 0 else 0.0)
    return idx_rows, val_rows, np.asarray(labels, np.float32)


def _padded(idx_rows, val_rows, dims):
    width = max(len(r) for r in idx_rows)
    ids = np.full((len(idx_rows), width), dims, np.int64)
    vals = np.zeros((len(idx_rows), width), np.float32)
    for i, (r, v) in enumerate(zip(idx_rows, val_rows)):
        ids[i, :len(r)], vals[i, :len(r)] = r, v
    return ids, vals


def _reference(idx_rows, val_rows, labels, dims, b, epochs=1, storage=None):
    # a pad lane's id `dims` is the table's entry 0 under the reference's
    # modulo: give pad lanes a real id with value 0 and drop it afterwards
    ids, vals = _padded(idx_rows, val_rows, dims)
    pad = ids == dims
    spare = dims - 1
    assert not (ids == spare).any()
    ids = np.where(pad, spare, ids)
    feats, w, info = ref.train(ids, vals, labels, dims=dims, mini_batch=b,
                               epochs=epochs, storage=storage, **{
                                   "eta": HYPER["eta"], "lam": HYPER["lambda"],
                                   "scale": HYPER["scale"]})
    keep = feats != spare
    return feats[keep], w[keep], info


def _assert_rows_equal(got, want, bf16=False):
    (gf, gw), (wf, ww) = got, want
    np.testing.assert_array_equal(np.asarray(gf), wf)
    gw = np.asarray(gw).astype(np.float64)
    if not bf16:
        np.testing.assert_allclose(gw, ww, rtol=2e-5, atol=2e-6)
        return
    # the reference rounds w to bfloat16 at every write, as the program
    # does: equal, but for a float32 value that sat on a rounding tie
    assert np.all(np.abs(gw - ww) <= 2.0 ** -7 * np.abs(ww) + 1e-30)
    assert np.mean(gw == ww) >= 0.97


@pytest.mark.parametrize("arm", sorted(ARMS))
@pytest.mark.parametrize("b", [1, 7, 64, 1024])
def test_entry_point_equals_the_plain_reference(monkeypatch, b, arm):
    monkeypatch.setattr(engine, "DENSE_APPLY_BELOW", ARMS[arm])
    idx_rows, val_rows, labels = _rows(seed=b)
    model = train_adagrad_rda((idx_rows, val_rows), labels,
                              f"-dims {DIMS} -mini_batch {b}")
    assert int(model.state.step) == ROWS
    feats, w, info = _reference(idx_rows, val_rows, labels, DIMS, b)
    assert info["steps"] == ROWS and EVERY_ROW in feats
    _assert_rows_equal(model.model_rows(), (feats, w))
    # a block counted as ONE subgradient: G of the feature on every row is
    # a sum of squared block sums, over its sum of squared rows as soon as
    # two rows of a block fire the same way
    u, g = (float(model.state.slots[k][EVERY_ROW])
            for k in ("sum_grad", "sum_sqgrad"))
    assert g >= u * u / -(-ROWS // b) * (1 - 1e-5)
    if b >= 64:
        assert g > (HYPER["scale"] * 2.0) ** 2 * ROWS


def _block_state(dtype, seed):
    """A state in mid-training and a block on it: repeats, pads, quiet rows."""
    rng = np.random.default_rng(seed)
    st = init_linear_state(DIMS, slot_names=ADAGRAD_RDA.slot_names, dtype=dtype)
    u = rng.normal(scale=300.0, size=DIMS).astype(np.float32)
    g = (np.abs(u) * rng.uniform(50.0, 400.0, DIMS)).astype(np.float32)
    t0 = 640
    w = np.asarray(ADAGRAD_RDA.derive_w(
        {"sum_grad": jnp.asarray(u), "sum_sqgrad": jnp.asarray(g)},
        jnp.float32(t0), HYPER))
    st = st.replace(weights=jnp.asarray(w, dtype),
                    slots={"sum_grad": jnp.asarray(u),
                           "sum_sqgrad": jnp.asarray(g)},
                    step=jnp.asarray(t0, jnp.int32))
    idx_rows, val_rows, labels = _rows(seed=seed + 1, rows=48)
    ids, vals = _padded(idx_rows, val_rows, DIMS)
    y = np.where(labels > 0, 1.0, -1.0).astype(np.float32)
    return st, ids.astype(np.int32), vals, y


def _step(arm, monkeypatch):
    monkeypatch.setattr(engine, "DENSE_APPLY_BELOW", ARMS[arm])
    return jax.jit(make_train_fn(ADAGRAD_RDA, HYPER, mode="minibatch"))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_two_arms_agree_on_one_block(monkeypatch, dtype):
    dt = jnp.dtype(dtype)
    st, ids, vals, y = _block_state(dt, seed=5)
    a, _ = _step("dense", monkeypatch)(st, ids, vals, y)
    b, _ = _step("batch_local", monkeypatch)(st, ids, vals, y)
    a, b = jax.device_get((a, b))
    assert int(a.step) == int(b.step) == 640 + 48
    np.testing.assert_array_equal(np.asarray(a.touched), np.asarray(b.touched))
    assert np.asarray(a.touched).sum() > 10
    for k in ADAGRAD_RDA.slot_names:
        np.testing.assert_allclose(np.asarray(a.slots[k]),
                                   np.asarray(b.slots[k]), rtol=3e-6)
    wa = np.asarray(a.weights).astype(np.float64)
    wb = np.asarray(b.weights).astype(np.float64)
    if dtype == "float32":
        np.testing.assert_allclose(wa, wb, rtol=2e-5, atol=1e-6)
    else:
        assert a.weights.dtype == ml_dtypes.bfloat16
        assert np.all(np.abs(wa - wb) <= 2.0 ** -7 * np.abs(wb))
        assert np.mean(wa == wb) > 0.999
    # a feature that only quiet rows carry keeps its weight and its flag
    fired_any = np.asarray(a.touched) != 0
    quiet = np.setdiff1d(np.unique(ids[ids < DIMS]), np.nonzero(fired_any)[0])
    old = np.asarray(st.weights).astype(np.float64)
    np.testing.assert_array_equal(wa[quiet], old[quiet])


@pytest.mark.parametrize("arm", sorted(ARMS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_one_block_equals_the_block_rule_in_numpy(monkeypatch, arm, dtype):
    """u += S, G += S^2, S the sum over the fired lanes; w derived at t0 + B
    where a fired row carries the feature; one rounding to the weights'
    storage."""
    dt = jnp.dtype(dtype)
    st, ids, vals, y = _block_state(dt, seed=9)
    new, _ = _step(arm, monkeypatch)(st, ids, vals, y)
    new = jax.device_get(new)
    w0 = np.asarray(st.weights).astype(np.float64)
    live = ids < DIMS
    safe = np.where(live, ids, 0)
    m = y * np.sum(np.where(live, w0[safe], 0.0) * vals, axis=1)
    fired = m < 1.0
    assert fired.any() and not fired.all()
    lanes = live & fired[:, None]
    du = HYPER["scale"] * (-y)[:, None] * vals.astype(np.float64)
    cnt = np.zeros(DIMS)
    np.add.at(cnt, ids[lanes], 1.0)
    su = np.zeros(DIMS)
    np.add.at(su, ids[lanes], du[lanes])
    assert cnt.max() == fired.sum() > 1      # the feature on every row
    u = np.asarray(st.slots["sum_grad"], np.float64) + su
    g = np.asarray(st.slots["sum_sqgrad"], np.float64) + su * su
    np.testing.assert_allclose(np.asarray(new.slots["sum_grad"]), u, rtol=3e-6)
    np.testing.assert_allclose(np.asarray(new.slots["sum_sqgrad"]), g,
                               rtol=3e-6)
    want = np.where(cnt > 0, ref.derive_w(u, g, 640.0 + 48, HYPER["eta"],
                                          HYPER["lambda"], HYPER["scale"]), w0)
    got = np.asarray(new.weights).astype(np.float64)
    tol = 2.0 ** -7 if dtype == "bfloat16" else 2e-5
    assert np.all(np.abs(got - want) <= tol * np.abs(want) + 1e-6)
    np.testing.assert_array_equal(np.asarray(new.touched) != 0, cnt > 0)


def test_a_block_of_one_row_is_the_exact_scan_bit_for_bit():
    idx_rows, val_rows, labels = _rows(seed=3, rows=60)
    ids, vals = _padded(idx_rows, val_rows, DIMS)
    ids = ids.astype(np.int32)
    y = np.where(labels > 0, 1.0, -1.0).astype(np.float32)

    def fresh():
        return init_linear_state(DIMS, slot_names=ADAGRAD_RDA.slot_names)

    scan, _ = make_train_step(ADAGRAD_RDA, HYPER, mode="scan",
                              donate=False)(fresh(), ids, vals, y)
    step = make_train_step(ADAGRAD_RDA, HYPER, mode="minibatch", donate=False)
    mb = fresh()
    for i in range(len(y)):
        mb, _ = step(mb, ids[i:i + 1], vals[i:i + 1], y[i:i + 1])
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(scan)),
                    jax.tree_util.tree_leaves(jax.device_get(mb))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.asarray(scan.touched).sum() > 10


@pytest.mark.parametrize("arm", sorted(ARMS))
def test_a_blocks_rows_may_come_in_any_order(monkeypatch, arm):
    st, ids, vals, y = _block_state(jnp.float32, seed=13)
    perm = np.random.default_rng(0).permutation(len(y))
    step = _step(arm, monkeypatch)
    a, la = step(st, ids, vals, y)
    b, lb = step(st, ids[perm], vals[perm], y[perm])
    a, b = jax.device_get((a, b))
    np.testing.assert_array_equal(np.asarray(a.touched), np.asarray(b.touched))
    for x, z in ((a.weights, b.weights),
                 (a.slots["sum_grad"], b.slots["sum_grad"]),
                 (a.slots["sum_sqgrad"], b.slots["sum_sqgrad"])):
        np.testing.assert_allclose(np.asarray(x), np.asarray(z), rtol=3e-6,
                                   atol=1e-6)
    assert float(la) == pytest.approx(float(lb), rel=1e-6)


@pytest.mark.parametrize("b,iters", [(64, 1), (64, 2), (7, 2), (1, 2)])
def test_t_is_rows_times_epochs_after_tails_and_replays(b, iters):
    """The counter that `derive_w` reads moves by the rows given, tail blocks
    and replayed epochs included: the reference, whose t is its own count of
    rows, derives the same weights."""
    idx_rows, val_rows, labels = _rows(seed=21 + b)
    opts = f"-dims {DIMS} -mini_batch {b}"
    if iters > 1:
        opts += f" -iters {iters} -disable_cv"
    model = train_adagrad_rda((idx_rows, val_rows), labels, opts)
    assert int(model.state.step) == ROWS * iters
    feats, w, info = _reference(idx_rows, val_rows, labels, DIMS, b,
                                epochs=iters)
    assert info["steps"] == ROWS * iters
    _assert_rows_equal(model.model_rows(), (feats, w))


def test_bfloat16_weights_beside_float32_slots_through_the_entry_point():
    """Above 2^24 dims `fit_linear` keeps w in bfloat16 and the slots in
    float32 (11 B an entry), and the block-local arm runs."""
    dims = (1 << 24) + 4096
    idx_rows, val_rows, labels = _rows(seed=2, rows=96, dims=dims)
    model = train_adagrad_rda((idx_rows, val_rows), labels,
                              f"-dims {dims} -mini_batch 32")
    st = model.state
    assert st.weights.dtype == ml_dtypes.bfloat16 and st.covars is None
    assert {k: v.dtype for k, v in st.slots.items()} == {
        "sum_grad": np.float32, "sum_sqgrad": np.float32}
    assert sum(x.nbytes for x in jax.tree_util.tree_leaves(st)) \
        == 11 * dims + 4
    feats, w, _ = _reference(idx_rows, val_rows, labels, dims, 32,
                             storage="bfloat16")
    _assert_rows_equal(model.model_rows(), (feats, w), bf16=True)


def test_emission_equals_a_host_selection_next_to_the_tables_end():
    """`model_rows()` of a table whose touched ids lie next to `dims - 1`
    (and at 0), where `dims` is no multiple of 32: the ids, the weights and
    a derived weight of exactly 0 come out as a host pass gives them."""
    dims = (1 << 26) + 37
    rng = np.random.default_rng(4)
    ids = np.unique(np.concatenate([
        [0, 1, dims - 1, dims - 2, dims - 33, dims // 32, dims // 32 + 1],
        rng.integers(0, dims, 5000), dims - 1 - rng.integers(0, 4096, 500)]))
    w = rng.normal(size=ids.size).astype(np.float32)
    w[::7] = 0.0                     # inside the l1 ball: emitted all the same
    weights = jnp.zeros((dims,), jnp.bfloat16).at[ids].set(
        jnp.asarray(w, jnp.bfloat16))
    touched = jnp.zeros((dims,), jnp.int8).at[ids].set(1)
    st = init_linear_state(8).replace(weights=weights, touched=touched)
    feats, got = model_rows(st)
    np.testing.assert_array_equal(feats, ids)
    assert feats.dtype == np.int64 and feats[-1] == dims - 1
    np.testing.assert_array_equal(
        np.asarray(got), w.astype(ml_dtypes.bfloat16))
    assert (np.asarray(got) == 0).sum() >= ids.size // 7
    feats_nz, _ = model_rows(st, filter_zero=True)
    np.testing.assert_array_equal(
        feats_nz, ids[w.astype(ml_dtypes.bfloat16) != 0])


def test_id_arithmetic_holds_at_the_cells_dims():
    """What the step and emission compute from ids at 2^29 entries stays
    inside int32: the pad id and sort sentinel `dims`, a negative id's
    `idx + dims`, and emission's `plane x n + word` (host numpy)."""
    from hivemall_tpu.core import emission

    dims = 1 << 29
    assert dims <= np.iinfo(np.int32).max
    n = -(-dims // emission.MASK_BITS)
    words = np.zeros(n, np.uint32)
    words[0] = 1 | (1 << 31)
    words[n - 1] = 1 << 31           # entry 31 * n + n - 1 = dims - 1
    words[n // 2] = 1 << 15
    ids = emission.mask_to_ids(words)
    assert ids.dtype == np.int32
    np.testing.assert_array_equal(
        ids, sorted([0, 31 * n, 15 * n + n // 2, dims - 1]))
