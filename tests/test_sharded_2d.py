"""DP x feature-sharding composition on the simulated 8-device CPU mesh.

The topology under test is the reference's production shape: N mapper
clients training concurrently against M feature-sharded MIX servers
(ref: mix/client/MixRequestRouter.java:56-60 routing,
mixserv/.../MixServerHandler.java:118-158 clock-gated averaging,
MixServerTest.java:122-151 five concurrent clients). Sharded2DTrainer maps
clients -> replica axis, servers -> stripe axis; correctness bar: a 2x4
(replicas x stripes) run is numerically the replicas-only MixTrainer run —
the stripe axis must not change the math — including on dims that do NOT
divide the stripe count (padding path).
"""

import jax
import numpy as np
import pytest

from hivemall_tpu.models.classifier import AROW, PERCEPTRON
from hivemall_tpu.parallel import (MixConfig, MixTrainer, make_mesh,
                                   make_mesh_2d)
from hivemall_tpu.parallel.sharded_train import Sharded2DTrainer, ShardedTrainer

R, S = 2, 4
DIMS = 1003  # deliberately not divisible by S (stripe 251, padded 1004)


def _gen_blocks(n_blocks, batch=16, width=8, seed=0, dims=DIMS):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, dims, size=(R, n_blocks, batch, width)).astype(np.int32)
    val = rng.rand(R, n_blocks, batch, width).astype(np.float32)
    lab = np.sign(rng.randn(R, n_blocks, batch)).astype(np.float32)
    return idx, val, lab


@pytest.mark.parametrize("rule,hyper", [(PERCEPTRON, {}), (AROW, {"r": 0.1})],
                         ids=["average", "argmin_kld"])
def test_2d_parity_vs_replicas_only(rule, hyper):
    """2x4 (replicas x stripes) == 2-replica MixTrainer on the same blocks:
    weights, covars, touched, and loss all match on the unpadded prefix."""
    k = 4
    idx, val, lab = _gen_blocks(k)

    t2d = Sharded2DTrainer(rule, hyper, DIMS, make_mesh_2d(R, S),
                           config=MixConfig(mix_every=2))
    s2 = t2d.init()
    s2, loss2 = t2d.step(s2, idx, val, lab)

    tmix = MixTrainer(rule, hyper, DIMS, make_mesh(R),
                      config=MixConfig(mix_every=2))
    s1 = tmix.init()
    s1, loss1 = tmix.step(s1, idx, val, lab)

    h2, h1 = jax.device_get(s2), jax.device_get(s1)
    np.testing.assert_allclose(np.asarray(h2.weights)[:, :DIMS],
                               np.asarray(h1.weights), rtol=2e-5, atol=1e-6)
    if rule.use_covariance:
        np.testing.assert_allclose(np.asarray(h2.covars)[:, :DIMS],
                                   np.asarray(h1.covars), rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(h2.touched)[:, :DIMS],
                                  np.asarray(h1.touched))
    assert float(loss2) == pytest.approx(float(loss1), rel=1e-4)


def test_2d_final_state_unpads_and_serves():
    """final_state collapses the replica axis AND slices the padding off;
    make_predict serves the trained sharded state directly with scores equal
    to the host dot product."""
    k = 2
    idx, val, lab = _gen_blocks(k, seed=3)
    trainer = Sharded2DTrainer(AROW, {"r": 0.1}, DIMS, make_mesh_2d(R, S))
    state = trainer.init()
    state, _ = trainer.step(state, idx, val, lab)

    final = trainer.final_state(state)
    assert final.weights.shape == (DIMS,)
    assert final.covars.shape == (DIMS,)
    assert int(final.step) == 2 * k * 16  # scan-mode? minibatch: B rows/block
    w = np.asarray(final.weights)

    predict = trainer.make_predict()
    q_idx = idx[0, 0][:4]
    q_val = val[0, 0][:4]
    got = np.asarray(predict(state, q_idx, q_val))
    want = (w[q_idx] * q_val).sum(axis=-1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)


def test_2d_mix_every_gates_replica_collective():
    """mix_every must gate the replica-axis collective in the 2-D composition
    exactly as in the 1-D MixTrainer: k=4 with one trailing mix differs from
    mixing after every block."""
    idx, val, lab = _gen_blocks(4, seed=5)
    once = Sharded2DTrainer(AROW, {"r": 0.1}, DIMS, make_mesh_2d(R, S),
                            config=MixConfig(mix_every=4))
    s_once = once.init()
    s_once, _ = once.step(s_once, idx, val, lab)
    every = Sharded2DTrainer(AROW, {"r": 0.1}, DIMS, make_mesh_2d(R, S),
                             config=MixConfig(mix_every=1))
    s_every = every.init()
    s_every, _ = every.step(s_every, idx, val, lab)
    dw = np.abs(np.asarray(jax.device_get(s_once.weights))
                - np.asarray(jax.device_get(s_every.weights))).max()
    assert dw > 1e-6


def test_fm_sharded_parity():
    """Feature-dim sharded FM == single-device FM step for step: weights, V,
    touched, loss — on non-divisible dims (padding), both modes."""
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step
    from hivemall_tpu.ops.eta import fixed
    from hivemall_tpu.parallel.sharded_train import FMShardedTrainer

    dims = 1003
    hyper = FMHyper(factors=4, classification=True, lambda0=0.01,
                    eta=fixed(0.05), seed=2)
    rng = np.random.RandomState(11)
    n_blocks, B, K = 3, 32, 8
    idx = rng.randint(0, dims, size=(n_blocks, B, K)).astype(np.int32)
    val = rng.rand(n_blocks, B, K).astype(np.float32)
    lab = np.sign(rng.randn(n_blocks, B)).astype(np.float32)
    va = np.zeros((B,), np.float32)

    for mode in ("minibatch", "scan"):
        step = make_fm_step(hyper, mode)
        ref = init_fm_state(dims, hyper)
        for b in range(n_blocks):
            ref, ref_loss = step(ref, idx[b], val[b], lab[b], va)
        ref = jax.device_get(ref)

        trainer = FMShardedTrainer(hyper, dims, make_mesh(8), mode=mode)
        assert trainer.dims_padded == 1008
        state = trainer.init()
        for b in range(n_blocks):
            state, loss = trainer.step(state, idx[b], val[b], lab[b])
        got = trainer.final_state(state)
        np.testing.assert_allclose(np.asarray(got.w), np.asarray(ref.w),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.v), np.asarray(ref.v),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got.touched),
                                      np.asarray(ref.touched))
        assert float(got.w0) == pytest.approx(float(ref.w0), rel=1e-5)
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-4)

        # trained sharded state serves directly
        predict = trainer.make_predict()
        scores = np.asarray(predict(state, idx[0], val[0]))
        from hivemall_tpu.models.fm import _fm_scores

        want = np.asarray(_fm_scores(ref, idx[0], val[0]))
        np.testing.assert_allclose(scores, want, rtol=2e-5, atol=1e-5)


def test_ffm_sharded_parity():
    """Feature-dim sharded FFM == single-device FFM step for step: the
    pairwise V block is rebuilt per row by one psum of owner-gathered
    entries, so w, z/n, V, gg, touched, and loss all match — seeded from
    the SAME initial state, non-divisible table sizes, minibatch and
    row_chunk-tiled variants."""
    from hivemall_tpu.models.ffm import (FFMHyper, init_ffm_state,
                                         make_ffm_step)
    from hivemall_tpu.parallel.sharded_train import FFMShardedTrainer

    hyper = FFMHyper(factors=3, num_features=1001, v_dims=2003, num_fields=8,
                     seed=6)
    rng = np.random.RandomState(17)
    n_blocks, B, K = 3, 32, 6
    idx = rng.randint(0, 1001, size=(n_blocks, B, K)).astype(np.int32)
    val = rng.rand(n_blocks, B, K).astype(np.float32)
    fld = rng.randint(0, 8, size=(n_blocks, B, K)).astype(np.int32)
    lab = np.sign(rng.randn(n_blocks, B)).astype(np.float32)

    init = jax.device_get(init_ffm_state(hyper))

    step = make_ffm_step(hyper, "minibatch")
    ref = init_ffm_state(hyper)
    for b in range(n_blocks):
        ref, ref_loss = step(ref, idx[b], val[b], fld[b], lab[b])
    ref = jax.device_get(ref)

    for rc in (None, 16):
        trainer = FFMShardedTrainer(hyper, make_mesh(8), row_chunk=rc)
        assert trainer.nf_padded == 1008 and trainer.dv_padded == 2008
        state = trainer.init(from_state=init)
        for b in range(n_blocks):
            state, loss = trainer.step(state, idx[b], val[b], fld[b], lab[b])
        got = trainer.final_state(state)
        np.testing.assert_allclose(np.asarray(got.w), np.asarray(ref.w),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.z), np.asarray(ref.z),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.n), np.asarray(ref.n),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.v), np.asarray(ref.v),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.v_gg), np.asarray(ref.v_gg),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got.touched),
                                      np.asarray(ref.touched))
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-4)

        # sharded serving matches unsharded scoring of the same model
        from hivemall_tpu.models.ffm import _ffm_scores

        predict = trainer.make_predict()
        scores = np.asarray(predict(state, idx[0], val[0], fld[0]))
        want = np.asarray(_ffm_scores(ref, hyper, idx[0], val[0], fld[0]))
        np.testing.assert_allclose(scores, want, rtol=2e-5, atol=1e-5)


def test_ffm_sharded_state_round_trips_through_the_blob():
    """A sharded trainer's own `init` starts V at `initial_v`, as
    `init_ffm_state` does, so its `final_state` wrapped in a
    `TrainedFFMModel` encodes and decodes to the model that was trained:
    `from_blob` refills every entry that is not flagged from `initial_v`,
    and a row whose pairs no trained row addressed scores the same."""
    from hivemall_tpu.models.ffm import (FFMHyper, TrainedFFMModel,
                                         init_ffm_state)
    from hivemall_tpu.parallel.sharded_train import FFMShardedTrainer

    hyper = FFMHyper(factors=3, num_features=1001, v_dims=2003, num_fields=8,
                     seed=6, eta0_v=0.05)
    rng = np.random.RandomState(23)
    B, K = 32, 6
    # training rows carry features under 500, the unseen rows above
    idx = rng.randint(0, 500, size=(2, B, K)).astype(np.int32)
    val = rng.rand(2, B, K).astype(np.float32)
    fld = rng.randint(0, 8, size=(2, B, K)).astype(np.int32)
    lab = np.sign(rng.randn(2, B)).astype(np.float32)

    trainer = FFMShardedTrainer(hyper, make_mesh(8))
    state = trainer.init()
    fresh = trainer.final_state(state)
    np.testing.assert_array_equal(
        np.asarray(fresh.v), np.asarray(init_ffm_state(hyper).v))
    for b in range(2):
        state, _ = trainer.step(state, idx[b], val[b], fld[b], lab[b])
    model = TrainedFFMModel(state=trainer.final_state(state), hyper=hyper)
    back = TrainedFFMModel.from_blob(model.to_blob(half_float=False))

    seen = (list(idx[0]), list(val[0]), list(fld[0]))
    unseen_idx = rng.randint(500, 1001, size=(B, K)).astype(np.int32)
    unseen = (list(unseen_idx), list(val[1]), list(fld[1]))
    for rows in (seen, unseen):
        np.testing.assert_array_equal(back.predict(rows), model.predict(rows))
    assert np.abs(model.predict(unseen)).max() > 0    # initial_v, not zeros
    np.testing.assert_array_equal(np.asarray(back.state.v),
                                  np.asarray(model.state.v))


def test_mc_sharded_parity():
    """Feature-dim sharded multiclass == single-device step for step:
    weights, covars, touched, loss — covariance rule, non-divisible dims,
    both modes."""
    from hivemall_tpu.models.multiclass import (MC_AROW, MulticlassState,
                                                make_mc_train_step)
    from hivemall_tpu.parallel.sharded_train import MCShardedTrainer

    dims, L = 1003, 3
    rng = np.random.RandomState(13)
    n_blocks, B, K = 3, 32, 8
    idx = rng.randint(0, dims, size=(n_blocks, B, K)).astype(np.int32)
    val = rng.rand(n_blocks, B, K).astype(np.float32)
    lab = rng.randint(0, L, size=(n_blocks, B)).astype(np.int32)

    import jax.numpy as jnp

    for mode in ("minibatch", "scan"):
        step = make_mc_train_step(MC_AROW, {"r": 0.1}, mode)
        ref = MulticlassState(
            weights=jnp.zeros((L, dims), jnp.float32),
            covars=jnp.ones((L, dims), jnp.float32),
            touched=jnp.zeros((L, dims), jnp.int8),
            step=jnp.zeros((), jnp.int32),
        )
        for b in range(n_blocks):
            ref, ref_loss = step(ref, idx[b], val[b], lab[b])
        ref = jax.device_get(ref)

        trainer = MCShardedTrainer(MC_AROW, {"r": 0.1}, num_labels=L,
                                   dims=dims, mesh=make_mesh(8), mode=mode)
        assert trainer.dims_padded == 1008
        state = trainer.init()
        for b in range(n_blocks):
            state, loss = trainer.step(state, idx[b], val[b], lab[b])
        got = trainer.final_state(state)
        np.testing.assert_allclose(np.asarray(got.weights),
                                   np.asarray(ref.weights),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got.covars),
                                   np.asarray(ref.covars),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(got.touched),
                                      np.asarray(ref.touched))
        assert float(loss) == pytest.approx(float(ref_loss), rel=1e-4)

        # sharded serving: per-label scores match the host matmul
        predict = trainer.make_predict()
        scores = np.asarray(predict(state, idx[0], val[0]))  # [B, L]
        W = np.asarray(got.weights)
        want = np.stack([W[:, idx[0][r]] @ val[0][r] for r in range(B)])
        np.testing.assert_allclose(scores, want, rtol=2e-5, atol=1e-5)


def test_1d_sharded_padding_parity():
    """ShardedTrainer on non-divisible dims pads internally and still matches
    the single-device engine on the real prefix."""
    from hivemall_tpu.core.engine import make_train_step
    from hivemall_tpu.core.state import init_linear_state

    dims = 1003
    rng = np.random.RandomState(7)
    idx = rng.randint(0, dims, size=(3, 16, 8)).astype(np.int32)
    val = rng.rand(3, 16, 8).astype(np.float32)
    lab = np.sign(rng.randn(3, 16)).astype(np.float32)

    step = make_train_step(AROW, {"r": 0.1}, donate=False)
    ref = init_linear_state(dims, use_covariance=True)
    for i in range(3):
        ref, _ = step(ref, idx[i], val[i], lab[i])
    ref = jax.device_get(ref)

    trainer = ShardedTrainer(AROW, {"r": 0.1}, dims, make_mesh(8))
    assert trainer.dims_padded == 1008 and trainer.stripe == 126
    state = trainer.init()
    for i in range(3):
        state, _ = trainer.step(state, idx[i], val[i], lab[i])
    got = trainer.final_state(state)  # unpads back to [dims]
    assert got.weights.shape == (dims,)
    np.testing.assert_allclose(np.asarray(got.weights), ref.weights,
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got.covars), ref.covars,
                               rtol=2e-5, atol=1e-6)

    # the trained sharded state serves directly (weak #5: one placement)
    predict = trainer.make_predict()
    got_scores = np.asarray(predict(state, idx[0][:4], val[0][:4]))
    want = (np.asarray(ref.weights)[idx[0][:4]] * val[0][:4]).sum(axis=-1)
    np.testing.assert_allclose(got_scores, want, rtol=2e-5, atol=1e-6)
