"""Collective mixing tests on the simulated 8-device CPU mesh — the analog of
the reference's in-process loopback MIX server tests
(ref: mixserv/src/test/java/hivemall/mix/server/MixServerTest.java:46-167)."""

import jax
import numpy as np
import pytest

from hivemall_tpu.core.batch import iter_blocks, pad_to_bucket
from hivemall_tpu.models.classifier import AROW, PERCEPTRON
from hivemall_tpu.parallel import MixConfig, MixTrainer, make_mesh


def _gen_blobs(n=1024, d=16, seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(d)
    x = rng.randn(n, d).astype(np.float32)
    y = np.sign(x @ w_true).astype(np.float32)
    idx_rows = [np.arange(d, dtype=np.int64) for _ in range(n)]
    val_rows = [x[i] for i in range(n)]
    return idx_rows, val_rows, y


def _stack_blocks(idx_rows, val_rows, y, dims, batch):
    blocks = list(iter_blocks(idx_rows, val_rows, y, dims, batch))
    return (np.stack([b.indices for b in blocks]),
            np.stack([b.values for b in blocks]),
            np.stack([b.labels for b in blocks]))


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_make_mesh_refuses_more_devices_than_exist():
    """A mesh smaller than the one asked for would train on fewer workers
    without anyone noticing — make_mesh raises like make_mesh_2d does."""
    with pytest.raises(ValueError, match="need 9 devices, have 8"):
        make_mesh(9)
    assert make_mesh(4).devices.size == 4


def test_mix_average_trains_across_replicas():
    dims, n_dev = 64, 8
    mesh = make_mesh(n_dev)
    trainer = MixTrainer(PERCEPTRON, {}, dims, mesh, MixConfig(reduction="average"))
    idx_rows, val_rows, y = _gen_blobs(n=1024)
    ib, vb, lb = _stack_blocks(idx_rows, val_rows, y, dims, batch=128)  # 8 blocks
    state = trainer.init()
    for _ in range(3):
        state, loss = trainer.step(state, *trainer.shard_blocks(ib, vb, lb))
    final = trainer.final_state(state)
    # replicas must be identical after the trailing mix
    host = jax.device_get(state)
    for i in range(1, n_dev):
        np.testing.assert_allclose(np.asarray(host.weights)[i],
                                   np.asarray(host.weights)[0], rtol=1e-6)
    # and the mixed model must classify the data
    w = np.asarray(final.weights)
    scores = np.stack([v @ w[idx] for idx, v in zip(idx_rows, val_rows)])
    acc = np.mean(np.sign(scores) == y)
    assert acc > 0.9, acc


def test_mix_argmin_kld_covariance_learner():
    dims, n_dev = 64, 8
    mesh = make_mesh(n_dev)
    trainer = MixTrainer(AROW, {"r": 0.1}, dims, mesh, MixConfig(reduction="auto"))
    assert trainer.reduction == "argmin_kld"
    idx_rows, val_rows, y = _gen_blobs(n=1024, seed=5)
    ib, vb, lb = _stack_blocks(idx_rows, val_rows, y, dims, batch=128)
    state = trainer.init()
    state, _ = trainer.step(state, *trainer.shard_blocks(ib, vb, lb))
    final = trainer.final_state(state)
    cov = np.asarray(final.covars)
    # mixed covariance = 1/sum(1/cov) over 8 replicas -> shrinks below any
    # single replica's covariance for features updated everywhere
    assert np.all(cov[:16] < 1.0 / n_dev + 1e-3)
    w = np.asarray(final.weights)
    scores = np.stack([v @ w[idx] for idx, v in zip(idx_rows, val_rows)])
    acc = np.mean(np.sign(scores) == y)
    assert acc > 0.9, acc


def test_untouched_features_keep_local_value():
    """Features never updated on any replica must not be disturbed by mixing
    (threshold-gated push analog)."""
    dims, n_dev = 32, 8
    mesh = make_mesh(n_dev)
    trainer = MixTrainer(PERCEPTRON, {}, dims, mesh, MixConfig(reduction="average"))
    # all rows use only features 0..3
    idx_rows = [np.array([0, 1, 2, 3])] * 64
    val_rows = [np.random.RandomState(i).randn(4).astype(np.float32) for i in range(64)]
    y = np.sign(np.array([v[0] for v in val_rows])).astype(np.float32)
    ib, vb, lb = _stack_blocks(idx_rows, val_rows, y, dims, batch=8)
    state = trainer.init()
    state, _ = trainer.step(state, *trainer.shard_blocks(ib, vb, lb))
    final = trainer.final_state(state)
    np.testing.assert_allclose(np.asarray(final.weights)[8:], 0.0)
    assert np.asarray(final.touched)[8:].sum() == 0


def test_mix_matches_manual_average():
    """One mixed step on 2 'devices' == manual delta-weighted average of two
    independently trained replicas (PartialAverage parity)."""
    dims = 16
    mesh = make_mesh(2)
    trainer = MixTrainer(PERCEPTRON, {}, dims, mesh, MixConfig(reduction="average"))
    rng = np.random.RandomState(1)
    idx_rows = [np.arange(4, dtype=np.int64) for _ in range(32)]
    val_rows = [rng.randn(4).astype(np.float32) for _ in range(32)]
    y = np.sign(np.array([v.sum() for v in val_rows])).astype(np.float32)
    ib, vb, lb = _stack_blocks(idx_rows, val_rows, y, dims, batch=16)  # 2 blocks

    # manual replicas via the single-device engine
    from hivemall_tpu.core.engine import DELTA_SLOT, make_train_fn
    from hivemall_tpu.core.state import init_linear_state

    fn = make_train_fn(PERCEPTRON, {}, mode="minibatch", track_deltas=True)
    fn = jax.jit(fn)
    replicas = []
    for i in range(2):
        st = init_linear_state(dims, slot_names=(DELTA_SLOT,))
        st, _ = fn(st, ib[i], vb[i], lb[i])
        replicas.append(jax.device_get(st))
    d0 = np.asarray(replicas[0].slots[DELTA_SLOT])
    d1 = np.asarray(replicas[1].slots[DELTA_SLOT])
    w0 = np.asarray(replicas[0].weights)
    w1 = np.asarray(replicas[1].weights)
    tot = d0 + d1
    expected = np.where(tot > 0, (w0 * d0 + w1 * d1) / np.maximum(tot, 1), w0)

    state = trainer.init()
    state, _ = trainer.step(state, *trainer.shard_blocks(ib, vb, lb))
    final = trainer.final_state(state)
    np.testing.assert_allclose(np.asarray(final.weights), expected, rtol=1e-5, atol=1e-6)
