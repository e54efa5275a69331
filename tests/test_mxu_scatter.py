"""Parity for the sorted-window MXU gather/scatter (ops/mxu_scatter.py) and
the engine/FM update backends built on it.

The module re-expresses XLA's scalar gather/scatter as one-hot matmuls over
dynamic-slice windows of the sorted id stream (see its docstring for the v5e
cost model it attacks). Everything here pins it against the plain `.at[]`
ops: gather must be bit-exact (each output is one 1.0*value product),
scatter-add to f32 tolerance (duplicate-id sums reassociate — XLA's own
scatter leaves that order unspecified too,
ref: core/src/main/java/hivemall/model/DenseModel.java:193-201 is the
sequential hot loop both replace).

Invalid-id semantics deviate from `.at[]` ON PURPOSE: negative ids are
treated like >= E (gather 0.0 / scatter drop), never Python-wrapped — the
engine's padding protocol only produces ids in [0, dims].
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from hivemall_tpu.ops import mxu_scatter as mx


def _mask_ref_ids(ids: np.ndarray, e: int) -> jnp.ndarray:
    return jnp.asarray(np.where((ids >= 0) & (ids < e), ids, e))


@pytest.mark.parametrize("n,c,chunk,wr", [
    (4096, 1, 256, 64),
    (4096, 2, 256, None),      # auto window
    (5000, 4, 256, None),      # N not a chunk multiple
    (64, 8, 256, 16),          # N < chunk
])
def test_gather_scatter_parity(n, c, chunk, wr):
    rng = np.random.RandomState(0)
    e = 1 << 14
    ids = rng.randint(0, e, size=n).astype(np.int32)
    ids[::17] = e + rng.randint(0, 5, size=ids[::17].shape)  # oob
    ids[::23] = -1                                           # negative
    table = rng.randn(e, c).astype(np.float32)
    upd = rng.randn(n, c).astype(np.float32)
    t = jnp.asarray(table if c > 1 else table[:, 0])
    u = jnp.asarray(upd if c > 1 else upd[:, 0])
    ref_ids = _mask_ref_ids(ids, e)

    plan = mx.make_plan(jnp.asarray(ids), e, chunk=chunk)
    g = np.asarray(mx.gather(t, plan, window_rows=wr))
    ref_g = np.asarray(t.at[ref_ids].get(mode="fill", fill_value=0.0))
    np.testing.assert_array_equal(g, ref_g)  # exact: one-hot products

    s = np.asarray(mx.scatter_add(t, jnp.asarray(ids), u, plan,
                                  window_rows=wr))
    ref_s = np.asarray(t.at[ref_ids].add(u, mode="drop"))
    np.testing.assert_allclose(s, ref_s, atol=1e-4)


def test_scatter_fewer_update_columns():
    """kl < c scatters only the leading lanes (scatter_rows_flat protocol —
    FM's pad lanes stay untouched)."""
    rng = np.random.RandomState(1)
    e, n, c, kl = 1 << 10, 512, 8, 6
    ids = rng.randint(0, e, size=n).astype(np.int32)
    table = rng.randn(e, c).astype(np.float32)
    upd = rng.randn(n, kl).astype(np.float32)
    plan = mx.make_plan(jnp.asarray(ids), e, chunk=128)
    s = np.asarray(mx.scatter_add(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(upd), plan))
    flat_idx = jnp.asarray(ids)[:, None] * c + jnp.arange(kl)
    ref = np.asarray(jnp.asarray(table).reshape(-1)
                     .at[flat_idx].add(jnp.asarray(upd), mode="drop")
                     .reshape(e, c))
    np.testing.assert_allclose(s, ref, atol=1e-4)
    np.testing.assert_array_equal(s[:, kl:], table[:, kl:])


def test_residual_path_adversarial_spans():
    """Clustered ids whose chunk span exceeds the window must fall through
    the exact residual pass — the window size is a performance knob only."""
    rng = np.random.RandomState(2)
    e = 1 << 14
    ids = np.concatenate([
        np.zeros(100, np.int32), np.full(100, e - 1, np.int32),
        rng.randint(0, e, 56).astype(np.int32)])
    table = rng.randn(e).astype(np.float32)
    upd = rng.randn(ids.size).astype(np.float32)
    plan = mx.make_plan(jnp.asarray(ids), e, chunk=256)
    g = np.asarray(mx.gather(jnp.asarray(table), plan, window_rows=128))
    ref = np.asarray(jnp.asarray(table).at[jnp.asarray(ids)]
                     .get(mode="fill", fill_value=0.0))
    np.testing.assert_array_equal(g, ref)
    s = np.asarray(mx.scatter_add(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(upd), plan, window_rows=128))
    ref_s = np.asarray(jnp.asarray(table).at[jnp.asarray(ids)]
                       .add(jnp.asarray(upd), mode="drop"))
    np.testing.assert_allclose(s, ref_s, atol=1e-4)


def test_all_invalid_block():
    e = 1 << 10
    table = np.random.RandomState(3).randn(e).astype(np.float32)
    ids = np.full(128, e, np.int32)
    plan = mx.make_plan(jnp.asarray(ids), e, chunk=64)
    assert (np.asarray(mx.gather(jnp.asarray(table), plan)) == 0).all()
    s = np.asarray(mx.scatter_add(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.ones(128, jnp.float32), plan))
    np.testing.assert_allclose(s, table)


def test_duplicate_heavy_ids():
    """Zipf-ish duplication (the CTR regime the engine actually sees)."""
    rng = np.random.RandomState(4)
    e, n = 1 << 12, 1 << 14
    ids = (rng.zipf(1.3, size=n) % e).astype(np.int32)
    table = np.zeros(e, np.float32)
    upd = np.ones(n, np.float32)
    plan = mx.make_plan(jnp.asarray(ids), e, chunk=512)
    s = np.asarray(mx.scatter_add(jnp.asarray(table), jnp.asarray(ids),
                                  jnp.asarray(upd), plan))
    ref = np.bincount(ids, minlength=e).astype(np.float32)
    # integer counts accumulate exactly in f32 at this scale
    np.testing.assert_array_equal(s, ref)


def test_random_shape_sweep():
    """Seeded sweep over (E, c, N, chunk, window) combinations — the
    hardware A/B burns budgeted chip time, so shape-dependent bugs must
    die here. Mix of id regimes per trial: uniform, duplicate-heavy,
    clustered (residual-triggering), with oob sprinkled in."""
    rng = np.random.RandomState(42)
    for trial in range(10):
        e = int(2 ** rng.randint(8, 15))
        c = int(2 ** rng.randint(0, 4))
        n = int(rng.randint(50, 5000))
        chunk = int(2 ** rng.randint(5, 10))
        wr = [None, 64, 256][rng.randint(3)]
        regime = trial % 3
        if regime == 0:
            ids = rng.randint(0, e, size=n)
        elif regime == 1:
            ids = (rng.zipf(1.5, size=n) % e)
        else:  # clustered
            ids = np.concatenate([
                rng.randint(0, max(2, e // 64), size=n // 2),
                rng.randint(max(1, e - 64), e, size=n - n // 2)])
        ids = ids.astype(np.int32)
        ids[:: 13] = e + 1  # oob
        ref_ids = _mask_ref_ids(ids, e)
        table = rng.randn(e, c).astype(np.float32)
        upd = rng.randn(n, c).astype(np.float32)
        t = jnp.asarray(table)
        plan = mx.make_plan(jnp.asarray(ids), e, chunk=chunk)
        g = np.asarray(mx.gather(t, plan, window_rows=wr))
        ref_g = np.asarray(t.at[ref_ids].get(mode="fill", fill_value=0.0))
        np.testing.assert_array_equal(
            g, ref_g, err_msg=f"trial {trial} E={e} c={c} n={n} "
                              f"chunk={chunk} wr={wr}")
        s = np.asarray(mx.scatter_add(t, jnp.asarray(ids),
                                      jnp.asarray(upd), plan,
                                      window_rows=wr))
        ref_s = np.asarray(t.at[ref_ids].add(jnp.asarray(upd),
                                             mode="drop"))
        np.testing.assert_allclose(
            s, ref_s, atol=2e-4,
            err_msg=f"trial {trial} E={e} c={c} n={n}")


def test_ffm_backend_production_shape():
    """FFM mxu at a realistic (if shrunken) shape — hashed pair keys over a
    2^16 table, 24 lanes/row, 256-row block — the closest CPU-feasible
    stand-in for the bench shape the chip run will hit."""
    from hivemall_tpu.models.ffm import (FFMHyper, init_ffm_state,
                                         make_ffm_step)

    rng = np.random.RandomState(3)
    hyper = FFMHyper(factors=4, classification=True, num_features=1 << 14,
                     v_dims=1 << 16, num_fields=32)
    b, k = 256, 24
    idx = rng.randint(0, hyper.num_features, size=(b, k)).astype(np.int32)
    val = np.ones((b, k), np.float32)
    fld = rng.randint(0, 32, size=(b, k)).astype(np.int32)
    lab = np.sign(rng.randn(b)).astype(np.float32)
    v0 = rng.randn(hyper.v_dims, hyper.factors).astype(np.float32) * 0.05

    def mk():  # the jitted step donates its input state — fresh per call
        return init_ffm_state(hyper).replace(v=jnp.asarray(v0))

    args = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(fld),
            jnp.asarray(lab))
    sx, lx = make_ffm_step(hyper, "minibatch")(mk(), *args)
    sm, lm = make_ffm_step(hyper, "minibatch", update_backend="mxu")(
        mk(), *args)
    assert np.allclose(float(lx), float(lm), rtol=1e-5)
    for f in ("w", "v", "v_gg", "z", "n"):
        np.testing.assert_allclose(np.asarray(getattr(sx, f)),
                                   np.asarray(getattr(sm, f)), atol=1e-5,
                                   err_msg=f)


def test_engine_minibatch_backend_parity():
    """xla vs mxu minibatch steps across rule shapes: covariance (AROW),
    plain (PA1), covariance+hyper (SCW1), slots+derive_w (AdaGradRDA) —
    weights/covars/slots/touched/step/loss all line up."""
    from hivemall_tpu.core.engine import DELTA_SLOT, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import (ADAGRAD_RDA, AROW, PA1,
                                                SCW1)

    rng = np.random.RandomState(0)
    d, b, k = 1 << 12, 512, 8
    idx = rng.randint(0, d, size=(b, k)).astype(np.int32)
    idx[0, -2:] = d  # pad lanes
    val = rng.rand(b, k).astype(np.float32)
    lab = np.sign(rng.randn(b)).astype(np.float32)
    cases = [
        (AROW, {"r": 0.1}, True),
        (AROW, {"r": 0.1}, False),
        (PA1, {"c": 1.0}, True),
        (SCW1, {"phi": 1.0, "eta": 0.9, "c": 1.0}, True),
        (ADAGRAD_RDA, {"eta": 0.1, "lambda": 1e-6, "scale": 100.0}, True),
    ]
    for rule, hyper, avg in cases:
        for track in (False, True):
            st = init_linear_state(d, use_covariance=rule.use_covariance,
                                   slot_names=rule.slot_names,
                                   global_names=rule.global_names)
            if track:
                st = st.replace(slots={**st.slots,
                                       DELTA_SLOT: jnp.zeros((d,),
                                                             jnp.float32)})
            kw = dict(mode="minibatch", mini_batch_average=avg,
                      track_deltas=track)
            sx, lx = jax.jit(make_train_fn(rule, hyper, **kw))(
                st, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lab))
            sm, lm = jax.jit(make_train_fn(rule, hyper, **kw,
                                           update_backend="mxu"))(
                st, jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lab))
            label = (rule.name, avg, track)
            assert np.allclose(float(lx), float(lm), rtol=1e-5), label
            np.testing.assert_allclose(np.asarray(sx.weights),
                                       np.asarray(sm.weights), atol=2e-5,
                                       err_msg=str(label))
            if rule.use_covariance:
                np.testing.assert_allclose(np.asarray(sx.covars),
                                           np.asarray(sm.covars), atol=2e-5,
                                           err_msg=str(label))
            for s in sx.slots:
                np.testing.assert_allclose(np.asarray(sx.slots[s]),
                                           np.asarray(sm.slots[s]),
                                           atol=2e-5, err_msg=str(label))
            np.testing.assert_array_equal(np.asarray(sx.touched),
                                          np.asarray(sm.touched))
            assert int(sx.step) == int(sm.step)


def test_engine_backend_validation():
    from hivemall_tpu.core.engine import make_train_fn
    from hivemall_tpu.models.classifier import AROW

    with pytest.raises(ValueError, match="minibatch"):
        make_train_fn(AROW, {"r": 0.1}, mode="scan", update_backend="mxu")
    with pytest.raises(ValueError, match="feature_shard"):
        make_train_fn(AROW, {"r": 0.1}, feature_shard=("x", 4),
                      update_backend="mxu")
    with pytest.raises(ValueError, match="update_backend"):
        make_train_fn(AROW, {"r": 0.1}, update_backend="cuda")


def test_fm_backend_parity():
    """FM minibatch xla vs mxu: averaged/summed x plain/adareg, VA rows
    masked, pad-lane-zero invariant, and the no-counts-lane (k=7) split."""
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step

    rng = np.random.RandomState(1)
    d, b, k = 1 << 12, 256, 8
    idx = rng.randint(0, d, size=(b, k)).astype(np.int32)
    idx[0, -2:] = d
    val = rng.rand(b, k).astype(np.float32)
    lab = np.sign(rng.randn(b)).astype(np.float32)
    va = (rng.rand(b) < 0.1).astype(np.float32)
    v0 = np.random.RandomState(7).randn(d, 16).astype(np.float32) * 0.01

    def mk(hyper):
        st = init_fm_state(d, hyper)
        return st.replace(
            v=jnp.asarray(v0[:, : hyper.padded_factors])
            .at[:, hyper.factors:].set(0.0))

    args = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lab),
            jnp.asarray(va))
    shapes = [(5, True, False), (5, False, False), (5, True, True),
              (7, True, False)]  # k=7: counts lane doesn't fit -> split
    for k_f, avg, adareg in shapes:
        hyper = FMHyper(factors=k_f, classification=True, adareg=adareg)
        sx, lx = make_fm_step(hyper, mode="minibatch",
                              mini_batch_average=avg)(mk(hyper), *args)
        sm, lm = make_fm_step(hyper, mode="minibatch",
                              mini_batch_average=avg,
                              update_backend="mxu")(mk(hyper), *args)
        label = (k_f, avg, adareg)
        assert np.allclose(float(lx), float(lm), rtol=1e-5), label
        for f in ("w", "v", "w0", "lambda_w0", "lambda_w", "lambda_v"):
            np.testing.assert_allclose(np.asarray(getattr(sx, f)),
                                       np.asarray(getattr(sm, f)),
                                       atol=3e-6, err_msg=str(label))
        np.testing.assert_array_equal(np.asarray(sx.touched),
                                      np.asarray(sm.touched))
        assert (np.asarray(sm.v)[:, hyper.factors:] == 0).all(), \
            "pad lanes must stay zero"


def test_fm_backend_validation():
    from hivemall_tpu.models.fm import FMHyper, make_fm_step

    with pytest.raises(ValueError, match="pad lane"):
        make_fm_step(FMHyper(factors=8, classification=True),
                     mode="minibatch", update_backend="mxu")
    with pytest.raises(ValueError, match="minibatch"):
        make_fm_step(FMHyper(factors=5, classification=True), mode="scan",
                     update_backend="mxu")


def test_ffm_backend_parity():
    """FFM minibatch xla vs mxu, unchunked and row_chunk-tiled: the packed
    V+gg table pads to 8 lanes, one shared plan serves the batch's pairwise
    gather and scatter."""
    from hivemall_tpu.models.ffm import (FFMHyper, init_ffm_state,
                                         make_ffm_step)

    rng = np.random.RandomState(0)
    hyper = FFMHyper(factors=4, classification=True, num_features=1 << 10,
                     v_dims=1 << 12)
    b, k = 128, 8
    idx = rng.randint(0, hyper.num_features, size=(b, k)).astype(np.int32)
    val = (rng.rand(b, k) > 0.2).astype(np.float32)  # zero lanes too
    fld = rng.randint(0, 16, size=(b, k)).astype(np.int32)
    lab = np.sign(rng.randn(b)).astype(np.float32)
    v0 = np.random.RandomState(9).randn(hyper.v_dims, hyper.factors) \
        .astype(np.float32) * 0.05

    def mk():
        return init_ffm_state(hyper).replace(v=jnp.asarray(v0))

    args = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(fld),
            jnp.asarray(lab))
    for rc in (None, 32):
        sx, lx = make_ffm_step(hyper, "minibatch", row_chunk=rc)(mk(), *args)
        sm, lm = make_ffm_step(hyper, "minibatch", row_chunk=rc,
                               update_backend="mxu")(mk(), *args)
        assert np.allclose(float(lx), float(lm), rtol=1e-5), rc
        for f in ("w0", "w", "z", "n", "v", "v_gg"):
            np.testing.assert_allclose(np.asarray(getattr(sx, f)),
                                       np.asarray(getattr(sm, f)),
                                       atol=3e-6, err_msg=f"rc={rc} {f}")
        np.testing.assert_array_equal(np.asarray(sx.touched),
                                      np.asarray(sm.touched))


def test_ffm_backend_validation():
    from hivemall_tpu.models.ffm import FFMHyper, make_ffm_step

    with pytest.raises(ValueError, match="minibatch"):
        make_ffm_step(FFMHyper(factors=4), mode="scan",
                      update_backend="mxu")
    with pytest.raises(ValueError, match="pack_v"):
        make_ffm_step(FFMHyper(factors=4), mode="minibatch", pack_v=False,
                      update_backend="mxu")


def test_fit_linear_mxu_option():
    """-mxu_scatter trains end-to-end through fit_linear and matches the
    default backend's model on the same data."""
    from hivemall_tpu.models.classifier import train_arow

    rng = np.random.RandomState(5)
    n, dim = 256, 64
    rows = [[f"{rng.randint(dim)}:{rng.rand():.3f}" for _ in range(6)]
            for _ in range(n)]
    labels = np.sign(rng.randn(n))
    m_x = train_arow(rows, labels, options="-mini_batch 64")
    m_m = train_arow(rows, labels, options="-mini_batch 64 -mxu_scatter")
    np.testing.assert_allclose(np.asarray(m_x.state.weights),
                               np.asarray(m_m.state.weights), atol=1e-5)
