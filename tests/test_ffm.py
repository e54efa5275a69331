"""Field-aware FM tests."""

import numpy as np
import pytest

from hivemall_tpu.models import ffm as FFM


def _gen_ffm_data(n=1200, n_fields=4, per_field=6, seed=5):
    """CTR-style rows: one active feature per field, value 1; labels from a
    ground-truth field-aware interaction structure."""
    rng = np.random.RandomState(seed)
    k = 3
    V = rng.randn(n_fields * per_field, n_fields, k) * 0.5
    rows, ys = [], []
    for _ in range(n):
        active = [f * per_field + rng.randint(per_field) for f in range(n_fields)]
        s = 0.0
        for a in range(n_fields):
            for b in range(a + 1, n_fields):
                i, j = active[a], active[b]
                s += float(np.dot(V[i, b], V[j, a]))
        rows.append([f"{f}:{active[f]}:1" for f in range(n_fields)])
        ys.append(np.sign(s) if s != 0 else 1.0)
    return rows, np.asarray(ys, np.float32)


def test_ffm_learns_interactions():
    rows, y = _gen_ffm_data()
    model = FFM.train_ffm(rows, y,
                          "-factor 4 -iters 15 -feature_hashing 18 -v_bits 18 "
                          "-lambda0 0.0 -disable_cv -seed 2")
    p = model.predict(rows)
    acc = float(np.mean(np.sign(p) == y))
    assert acc > 0.85, acc


def test_ffm_minibatch():
    rows, y = _gen_ffm_data(n=800)
    model = FFM.train_ffm(rows, y,
                          "-factor 4 -iters 20 -feature_hashing 18 -v_bits 18 "
                          "-lambda0 0.0 -mini_batch 64 -disable_cv")
    acc = float(np.mean(np.sign(model.predict(rows)) == y))
    assert acc > 0.8, acc


def test_ffm_row_chunk_exact_vs_unchunked():
    """The K^2 activation tiling (-row_chunk) must not change the math: the
    chunked minibatch step computes every row against block-start parameters
    and accumulates the identical scatters."""
    import jax

    from hivemall_tpu.models.ffm import (FFMHyper, _stage_ffm_rows,
                                         init_ffm_state, make_ffm_step)

    rows, y = _gen_ffm_data(n=256)
    # global_bias on: the w0 update must also match (one batch-level update
    # with eta at the batch's final timestep, not per-chunk)
    hyper = FFMHyper(factors=4, num_features=1 << 18, v_dims=1 << 18, seed=3,
                     global_bias=True)
    idx, val, fld, lab = _stage_ffm_rows(rows, y, hyper)

    plain = make_ffm_step(hyper, "minibatch")
    tiled = make_ffm_step(hyper, "minibatch", row_chunk=32)
    s1, l1 = plain(init_ffm_state(hyper), idx, val, fld, lab)
    s2, l2 = tiled(init_ffm_state(hyper), idx, val, fld, lab)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    h1, h2 = jax.device_get(s1), jax.device_get(s2)
    np.testing.assert_allclose(h2.v, h1.v, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(h2.v_gg, h1.v_gg, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(h2.w, h1.w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(h2.z, h1.z, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(h2.n, h1.n, rtol=1e-5, atol=1e-7)
    assert int(h2.step) == int(h1.step)
    np.testing.assert_array_equal(h2.touched, h1.touched)
    assert float(h2.w0) == pytest.approx(float(h1.w0), abs=1e-7)


def test_ffm_row_chunk_via_options():
    rows, y = _gen_ffm_data(n=800)
    model = FFM.train_ffm(rows, y,
                          "-factor 4 -iters 20 -feature_hashing 18 -v_bits 18 "
                          "-lambda0 0.0 -mini_batch 64 -row_chunk 16 -disable_cv")
    acc = float(np.mean(np.sign(model.predict(rows)) == y))
    assert acc > 0.8, acc


def test_ffm_ftrl_sparsifies_linear_term():
    rows, y = _gen_ffm_data(n=300)
    model = FFM.train_ffm(rows, y,
                          "-factor 2 -iters 2 -feature_hashing 18 -lambda1 1e6 "
                          "-disable_cv")
    w0, feats, w, v_keys, v = model.model_rows()
    # huge L1 -> all linear weights clamped to zero
    assert np.allclose(w, 0.0)
    assert len(v_keys) == len(v) > 0 and v.shape[1] == 2


def test_ffm_options_parity():
    rows, y = _gen_ffm_data(n=100)
    # exercise the reference option surface
    model = FFM.train_ffm(rows, y,
                          "-factor 2 -iters 1 -w0 -disable_ftrl -disable_adagrad "
                          "-feature_hashing 18 -disable_cv")
    assert np.isfinite(float(model.state.w0))


def test_ffm_warns_of_the_default_rate_under_the_block_rule():
    """`-mini_batch B > 1` sums B rows' AdaGrad steps at the rate from
    before the block: at the default `-eta0_V 1.0` that diverges at a
    deployment's block size, so the entry point says so; it is silent once
    the rate is given, and in the per-row scan."""
    import warnings

    rows, y = _gen_ffm_data(n=64)
    base = "-factor 2 -feature_hashing 18 -disable_cv"
    with pytest.warns(UserWarning, match="-mini_batch 32 at the default "
                                         "-eta0_V 1.0"):
        FFM.train_ffm(rows, y, f"{base} -mini_batch 32")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        FFM.train_ffm(rows, y, f"{base} -mini_batch 32 -eta0_V 0.05")
        FFM.train_ffm(rows, y, f"{base} -mini_batch 32 -disable_adagrad")
        FFM.train_ffm(rows, y, base)


def test_ffm_p_beside_feature_hashing_is_ignored():
    """`-p` is FM's option; `train_ffm` hashes into 2^feature_hashing and a
    `-p` beside it changes nothing, whatever it says (as before PR 32)."""
    rows, y = _gen_ffm_data(n=64)
    base = "-factor 2 -feature_hashing 18 -disable_cv"
    plain = FFM.train_ffm(rows, y, base)
    for p in (1 << 18, 12345):
        other = FFM.train_ffm(rows, y, f"{base} -p {p}")
        assert other.hyper == plain.hyper
        np.testing.assert_array_equal(np.asarray(other.state.v),
                                      np.asarray(plain.state.v))


def test_pair_hash_deterministic():
    import jax.numpy as jnp

    a = FFM.pair_hash(jnp.array([5], dtype=jnp.uint32), jnp.array([7], dtype=jnp.uint32),
                      1 << 20)
    b = FFM.pair_hash(jnp.array([5], dtype=jnp.uint32), jnp.array([7], dtype=jnp.uint32),
                      1 << 20)
    assert int(a[0]) == int(b[0])
    c = FFM.pair_hash(jnp.array([7], dtype=jnp.uint32), jnp.array([5], dtype=jnp.uint32),
                      1 << 20)
    assert int(a[0]) != int(c[0])  # order matters: (i, fj) != (j, fi)


# ---- the step against the plain reference, at Criteo's 39 fields ----

FIELDS, FB, VB = 39, 12, 14
OPTS = f"-factor 4 -feature_hashing {FB} -num_fields {FIELDS} -v_bits {VB}"


def _criteo_like(n=128, seed=3):
    """[n, 39] rows: 13 lanes whose id every row carries (a float value),
    26 one-hot lanes, each drawn from a few ids of its own column (so a
    block repeats them, a row does not), and a few zeroed lanes (a lane
    that carries no value pairs with nothing)."""
    rng = np.random.RandomState(seed)
    ids = np.concatenate([np.broadcast_to(np.arange(13) * 7 + 1, (n, 13)),
                          100 + 20 * np.arange(26) + rng.randint(
                              0, 12, size=(n, 26))], axis=1)
    vals = np.concatenate([rng.randint(1, 9, size=(n, 13)) / 8.0,
                           np.ones((n, 26))], axis=1).astype(np.float32)
    vals[rng.rand(n, FIELDS) < 0.05] = 0.0
    labels = np.sign(rng.randn(n)).astype(np.float32)
    fields = np.broadcast_to(np.arange(FIELDS), ids.shape)
    return ids.astype(np.int64), vals, fields, labels


def _reference(ids, vals, labels, mini_batch, **kw):
    from types import SimpleNamespace

    from benchmark.refs import ffm as ref

    cfg = {"num_features": 1 << FB, "v_dims": 1 << VB, "mini_batch": mini_batch,
           "reference_args": dict({"factors": 4, "num_fields": FIELDS}, **kw)}
    split = SimpleNamespace(ids=ids, vals=vals, labels=labels)
    return ref, cfg, ref.reference(split, cfg, 1)[0]


@pytest.mark.parametrize("mini_batch,row_chunk", [
    (1, 0), (7, 0), (64, 0), (64, 16)])
def test_ffm_entry_point_equals_the_plain_reference(mini_batch, row_chunk):
    """train_ffm == benchmark/refs/ffm.py (numpy float64, written from the
    equations) on rows with repeated ids, zeroed lanes and features on
    every row: the per-row scan (B = 1), a block that does not divide the
    rows (7), a whole block and a tiled one."""
    from benchmark import compare

    ids, vals, fields, labels = _criteo_like()
    opts = f"{OPTS} -mini_batch {mini_batch}" + (
        f" -row_chunk {row_chunk}" if row_chunk else "")
    model = FFM.train_ffm((ids, vals, fields), labels, opts)
    ref, cfg, want = _reference(ids, vals, labels, mini_batch)
    got = ref.rows_of(model.model_rows())
    gaps = compare.model_gaps(got, want)
    assert gaps["rows_diff"] == 0 and int(model.state.step) == len(ids)
    assert gaps["w_gap"] < 5e-4 and gaps["v_gap"] < 5e-4, gaps
    # the model's own predict is the reference's score of the emitted rows
    np.testing.assert_allclose(
        model.predict((ids, vals, fields)),
        ref.score_rows(got, ids, vals, cfg), rtol=1e-4, atol=1e-5)


def test_ffm_tiled_and_chosen_tile_agree():
    """-row_chunk overrides the tile chosen from the block's bytes; both
    sum the same deltas against the same block-start tables."""
    import jax

    from hivemall_tpu.models import ffm

    ids, vals, fields, labels = _criteo_like(n=64)
    assert ffm.choose_row_tile(1024, 40, 4) == 256
    assert ffm.choose_row_tile(7, 40, 4) == 7
    states = [jax.device_get(FFM.train_ffm(
        (ids, vals, fields), labels, f"{OPTS} -mini_batch 64 {rc}").state)
        for rc in ("", "-row_chunk 8", "-row_chunk 64")]
    for other in states[1:]:
        for name in ("v", "v_gg", "w", "z", "n"):
            np.testing.assert_allclose(getattr(other, name),
                                       getattr(states[0], name),
                                       rtol=1e-6, atol=1e-8)
        np.testing.assert_array_equal(other.v_touched, states[0].v_touched)


def test_ffm_array_rows_and_text_rows_give_the_same_model():
    import jax

    ids, vals, fields, labels = _criteo_like(n=96)
    text = [[f"{f}:{i}:{v!r}" for f, i, v in zip(fr, ir, vr.tolist())]
            for fr, ir, vr in zip(fields, ids, vals)]
    opts = f"{OPTS} -mini_batch 32"
    a = jax.device_get(FFM.train_ffm((ids, vals, fields), labels, opts).state)
    b = jax.device_get(FFM.train_ffm(text, labels, opts).state)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(x, y)


def test_ffm_block_does_not_depend_on_its_lanes_order():
    """Every write is a function of the block: the FTRL weight is derived
    from the block's summed duals (it used to be `set` a lane at a time, so
    a feature on several rows kept whichever lane was written last)."""
    import jax

    ids, vals, fields, labels = _criteo_like(n=64)
    perm = np.random.RandomState(5).permutation(FIELDS)
    opts = f"{OPTS} -mini_batch 64"
    a = jax.device_get(FFM.train_ffm((ids, vals, fields), labels, opts).state)
    b = jax.device_get(FFM.train_ffm(
        (ids[:, perm], vals[:, perm], fields[:, perm]), labels, opts).state)
    rows = np.random.RandomState(6).permutation(len(ids))
    c = jax.device_get(FFM.train_ffm(
        (ids[rows], vals[rows], fields[rows]), labels[rows], opts).state)
    for other in (b, c):
        np.testing.assert_array_equal(other.touched, a.touched)
        np.testing.assert_array_equal(other.v_touched, a.v_touched)
        # sums in another order: float32 rounding, nothing else
        for name in ("w", "z", "n", "v", "v_gg"):
            np.testing.assert_allclose(getattr(other, name), getattr(a, name),
                                       rtol=2e-5, atol=1e-7)


def test_ffm_emission_is_a_host_selection_over_both_key_spaces():
    import jax

    from hivemall_tpu.runtime.tracing import TRACER

    ids, vals, fields, labels = _criteo_like(n=96)
    model = FFM.train_ffm((ids, vals, fields), labels, f"{OPTS} -mini_batch 32")
    w0, feats, w, v_keys, v = model.model_rows()
    emit = TRACER.traces()[-1]
    st = jax.device_get(model.state)
    np.testing.assert_array_equal(feats, np.nonzero(st.touched)[0])
    np.testing.assert_array_equal(w, st.w[feats])
    np.testing.assert_array_equal(v_keys, np.nonzero(st.v_touched)[0])
    np.testing.assert_array_equal(v, st.v[v_keys])
    # every other entry still holds its initial value
    init = jax.device_get(FFM.init_ffm_state(model.hyper))
    rest = st.v_touched == 0
    np.testing.assert_array_equal(st.v[rest], init.v[rest])
    # what crossed: each key space's packed flags and its emitted entries
    root = next(s for s in emit["spans"] if s["name"] == "emit.model_rows")
    mask_bytes = (1 << FB) // 8 + (1 << VB) // 8
    assert root["args"]["rows_out"] == len(feats) + len(v_keys)
    assert root["args"]["d2h_bytes"] <= 2 * (
        mask_bytes + 4 * len(feats) + 16 * len(v_keys))
    # the blob is built from that output and decodes to the same model
    back = FFM.TrainedFFMModel.from_blob(model.to_blob(half_float=False))
    np.testing.assert_array_equal(back.predict((ids, vals, fields)),
                                  model.predict((ids, vals, fields)))
