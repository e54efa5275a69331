"""Failure detection + elastic restart (runtime/recovery.py).

The reference delegates fault tolerance to Hadoop task retry and retracts a
failed task's MIX contributions with cancel messages
(ref: AbstractPredictionModel.java:88-118, MixClient.java:134-166,
SURVEY.md §5 failure detection). Synchronous SPMD fails at job granularity,
so the equivalent capability is: periodic checkpoints of the MIXED model,
failure detected by the driver, restart on the SURVIVING topology seeded
from the checkpoint — exercised here both in-process (8-replica run resumed
on a 4-replica mesh) and across real processes (2-process job aborts after
checkpointing; the parent detects rc != 0 and resumes single-process)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _data(dims, n_dev, k, B=16, K=8, seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dims)
    idx = rng.randint(0, dims, size=(n_dev, k, B, K)).astype(np.int32)
    val = rng.rand(n_dev, k, B, K).astype(np.float32)
    lab = np.sign(np.sum(w_true[idx] * val, axis=-1)).astype(np.float32)
    return idx, val, lab, w_true


def _acc(weights, w_true, dims, n=2000, seed=99):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, dims, size=(n, 8))
    val = rng.rand(n, 8).astype(np.float32)
    y = np.sign(np.sum(w_true[idx] * val, axis=-1))
    s = np.sum(np.asarray(weights)[idx] * val, axis=-1)
    return float(np.mean(np.sign(s) == y))


def test_elastic_resume_smaller_mesh(tmp_path):
    """Train on 8 replicas, checkpoint, resume on 4 — the mixed model
    carries over exactly and keeps improving on the smaller mesh."""
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel import MixConfig, make_mesh
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    dims = 256
    ckpt = str(tmp_path / "ckpt.npz")

    trainer8, state8 = elastic_resume(AROW, {"r": 0.1}, dims, ckpt,
                                      mesh=make_mesh(8),
                                      config=MixConfig(mix_every=8))
    idx, val, lab, w_true = _data(dims, 8, 8)
    state8, _ = trainer8.step(state8, idx, val, lab)
    checkpoint(trainer8, state8, ckpt)
    acc_before = _acc(trainer8.final_state(state8).weights, w_true, dims)

    # "failure": the 8-replica job is gone; resume on a 4-device mesh
    trainer4, state4 = elastic_resume(AROW, {"r": 0.1}, dims, ckpt,
                                      mesh=make_mesh(4),
                                      config=MixConfig(mix_every=8))
    # the resumed replicas carry the checkpointed weights exactly
    import jax

    host = jax.device_get(state4)
    merged_prev = trainer8.final_state(state8)
    for r in range(4):
        np.testing.assert_allclose(np.asarray(host.weights)[r],
                                   np.asarray(merged_prev.weights),
                                   rtol=1e-6)
    # and training continues: more data on the new topology improves acc
    idx2, val2, lab2, _ = _data(dims, 4, 8, seed=1)
    lab2 = np.sign(np.sum(w_true[idx2] * val2, axis=-1)).astype(np.float32)
    state4, _ = trainer4.step(state4, idx2, val2, lab2)
    final4 = trainer4.final_state(state4)
    acc_after = _acc(final4.weights, w_true, dims)
    # the resumed run keeps improving on the new topology
    assert acc_after >= acc_before, (acc_before, acc_after)
    assert acc_after > 0.8, acc_after
    # the step counter stays = total examples across the resume boundary
    # (8 replicas x 8 blocks x 16 rows, then 4 x 8 x 16 more)
    assert int(final4.step) == 8 * 8 * 16 + 4 * 8 * 16, int(final4.step)


def test_resume_preserves_additive_statistics(tmp_path):
    """Sum-kind optimizer slots (AdaGrad curvature), the step counter, and
    Welford globals must NOT be multiplied by the replica count across a
    checkpoint/resume cycle: resuming and immediately collapsing is the
    identity, and new work adds on top exactly once."""
    import jax

    from hivemall_tpu.models.regression import ADAGRAD_REGR, PA1A_REGR
    from hivemall_tpu.parallel import MixConfig, make_mesh
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    dims = 128

    def reg_blocks(n_dev, k, seed):
        rng = np.random.RandomState(seed)
        idx = rng.randint(0, dims, size=(n_dev, k, 16, 8)).astype(np.int32)
        val = rng.rand(n_dev, k, 16, 8).astype(np.float32)
        lab = rng.rand(n_dev, k, 16).astype(np.float32)
        return idx, val, lab

    for rule, hyper, check in (
        (ADAGRAD_REGR, {"eta": 1.0, "eps": 1.0, "scale": 100.0}, "slot"),
        (PA1A_REGR, {"c": 1.0, "epsilon": 0.1}, "welford"),
    ):
        ck = str(tmp_path / f"{rule.name}.npz")
        t4, s4 = elastic_resume(rule, hyper, dims, ck, mesh=make_mesh(4),
                                config=MixConfig(mix_every=2))
        s4, _ = t4.step(s4, *reg_blocks(4, 2, 1))
        checkpoint(t4, s4, ck)
        base = t4.final_state(s4)

        # resume on MORE replicas; immediate collapse == the checkpoint
        t8, s8 = elastic_resume(rule, hyper, dims, ck, mesh=make_mesh(8),
                                config=MixConfig(mix_every=2))
        again = t8.final_state(s8)
        assert int(again.step) == int(base.step) == 4 * 2 * 16
        if check == "slot":
            np.testing.assert_allclose(
                np.asarray(again.slots["sum_sqgrad"]),
                np.asarray(base.slots["sum_sqgrad"]), rtol=1e-6, atol=1e-7)
        else:
            assert float(again.globals["n"]) == pytest.approx(
                float(base.globals["n"]))
            assert float(again.globals["mean"]) == pytest.approx(
                float(base.globals["mean"]), rel=1e-5)
            assert float(again.globals["m2"]) == pytest.approx(
                float(base.globals["m2"]), rel=1e-4)

        # and new work adds exactly once
        s8, _ = t8.step(s8, *reg_blocks(8, 2, 2))
        final = t8.final_state(s8)
        assert int(final.step) == int(base.step) + 8 * 2 * 16
        if check == "slot":
            assert np.all(np.asarray(final.slots["sum_sqgrad"])
                          >= np.asarray(base.slots["sum_sqgrad"]) - 1e-7)
        else:
            assert float(final.globals["n"]) == pytest.approx(
                float(base.globals["n"]) + 8 * 2 * 16)


def _holdout_logloss(weights, w_true, dims, n=4096, seed=97):
    from hivemall_tpu.evaluation.metrics import logloss

    rng = np.random.RandomState(seed)
    idx = rng.randint(0, dims, size=(n, 8))
    val = rng.rand(n, 8).astype(np.float32)
    y = (np.sum(w_true[idx] * val, axis=-1) > 0).astype(float)
    s = np.sum(np.asarray(weights, np.float32)[idx] * val, axis=-1)
    return logloss(1.0 / (1.0 + np.exp(-s)), y)


def _row_blocks(dims, w_true, start, n, B=16, K=8):
    """Replicated [B, K] blocks for the 1-D sharded trainers — step i's
    block is a pure function of i, so every topology consumes the SAME
    stream (what makes interrupted-vs-uninterrupted comparable)."""
    out = []
    for i in range(start, start + n):
        r = np.random.RandomState(5000 + i)
        idx = r.randint(0, dims, size=(B, K)).astype(np.int32)
        val = r.rand(B, K).astype(np.float32)
        lab = np.sign(np.sum(w_true[idx] * val, axis=-1)).astype(np.float32)
        out.append((idx, val, lab))
    return out


def test_sharded_elastic_round_trip_linear_bit_identical(tmp_path):
    """The linear-family elastic pin, non-divisible dims (259 pads to
    260/4=65-stripes and 260/2=130-stripes):

    - resume-then-collapse is BIT-IDENTICAL to the checkpoint on a
      smaller AND a larger mesh (the re-stripe is lossless both ways);
    - an N→N resume continues BIT-IDENTICALLY to the uninterrupted run
      (the checkpoint loses nothing: weights, covars, step, all slots);
    - N→M continuations land within logloss tolerance of uninterrupted.
    """
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    dims = 259
    rng = np.random.RandomState(3)
    w_true = rng.randn(dims)
    ck = str(tmp_path / "ck.npz")
    blocks = _row_blocks(dims, w_true, 0, 10)

    # uninterrupted 4-device run over all 10 blocks
    t_full, s_full = elastic_resume(AROW, {"r": 0.1}, dims, ck,
                                    mesh=make_mesh(4), family="sharded")
    for blk in blocks:
        s_full, _ = t_full.step(s_full, *blk)
    full = t_full.final_state(s_full)
    full_ll = _holdout_logloss(full.weights, w_true, dims)

    # checkpointed run: 6 blocks, checkpoint, resume, 4 more
    t_a, s_a = elastic_resume(AROW, {"r": 0.1}, dims, ck,
                              mesh=make_mesh(4), family="sharded")
    for blk in blocks[:6]:
        s_a, _ = t_a.step(s_a, *blk)
    checkpoint(t_a, s_a, ck, block_step=6)
    ck_state = t_a.final_state(s_a)

    finals = {}
    for n_dev in (2, 4, 8):  # smaller, same, larger — both directions
        t_n, s_n = elastic_resume(AROW, {"r": 0.1}, dims, ck,
                                  mesh=make_mesh(n_dev), family="sharded")
        # resume-then-collapse == the checkpoint, bit for bit
        back = t_n.final_state(s_n)
        np.testing.assert_array_equal(np.asarray(back.weights),
                                      np.asarray(ck_state.weights))
        np.testing.assert_array_equal(np.asarray(back.covars),
                                      np.asarray(ck_state.covars))
        assert int(back.step) == int(ck_state.step) == 6 * 16
        for blk in blocks[6:]:
            s_n, _ = t_n.step(s_n, *blk)
        finals[n_dev] = t_n.final_state(s_n)

    # N→N: the interruption is invisible — bit-identical to uninterrupted
    np.testing.assert_array_equal(np.asarray(finals[4].weights),
                                  np.asarray(full.weights))
    np.testing.assert_array_equal(np.asarray(finals[4].covars),
                                  np.asarray(full.covars))
    # N→M (both directions): same examples, psum grouping differs — the
    # model must land at the same quality
    for n_dev in (2, 8):
        assert int(finals[n_dev].step) == int(full.step) == 10 * 16
        ll = _holdout_logloss(finals[n_dev].weights, w_true, dims)
        assert abs(ll - full_ll) < 0.02, (n_dev, ll, full_ll)


def test_fm_sharded_elastic_round_trip(tmp_path):
    """FM family: checkpoint under 4 devices, resume under 2 and 8 — the
    [D, k] V table re-stripes losslessly (resume-collapse equals the
    checkpoint exactly) and continuations match the uninterrupted run's
    holdout logloss within tolerance."""
    from hivemall_tpu.models.fm import FMHyper
    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    dims = 133  # non-divisible by 2, 4, 8
    hyper = FMHyper(factors=4, classification=True)
    rng = np.random.RandomState(4)
    w_true = rng.randn(dims)
    ck = str(tmp_path / "fm.npz")

    def fm_blocks(start, n):
        return [(i_, v_, (l_ > 0).astype(np.float32))
                for i_, v_, l_ in _row_blocks(dims, w_true, start, n)]

    t_full, s_full = elastic_resume(None, hyper, dims, ck,
                                    mesh=make_mesh(4), family="fm_sharded")
    for blk in fm_blocks(0, 8):
        s_full, _ = t_full.step(s_full, *blk)
    full = t_full.final_state(s_full)

    t_a, s_a = elastic_resume(None, hyper, dims, ck,
                              mesh=make_mesh(4), family="fm_sharded")
    for blk in fm_blocks(0, 5):
        s_a, _ = t_a.step(s_a, *blk)
    checkpoint(t_a, s_a, ck, block_step=5)
    ck_state = t_a.final_state(s_a)

    for n_dev in (2, 8):
        t_n, s_n = elastic_resume(None, hyper, dims, ck,
                                  mesh=make_mesh(n_dev), family="fm_sharded")
        back = t_n.final_state(s_n)
        np.testing.assert_array_equal(np.asarray(back.w),
                                      np.asarray(ck_state.w))
        np.testing.assert_array_equal(np.asarray(back.v),
                                      np.asarray(ck_state.v))
        assert int(back.step) == int(ck_state.step)
        for blk in fm_blocks(5, 3):
            s_n, loss = t_n.step(s_n, *blk)
        fin = t_n.final_state(s_n)
        assert int(fin.step) == int(full.step)
        # same stream, different psum grouping: quality must agree
        np.testing.assert_allclose(np.asarray(fin.w), np.asarray(full.w),
                                   rtol=5e-3, atol=5e-4)


def test_ffm_sharded_elastic_round_trip(tmp_path):
    """FFM family: BOTH stripe grids (linear tables at num_features, V at
    v_dims) re-stripe across a 4→2 resume; the round trip is exact and
    the continuation tracks the uninterrupted run."""
    from hivemall_tpu.models.ffm import FFMHyper
    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    hyper = FFMHyper(num_features=67, v_dims=131, factors=4, num_fields=8,
                     seed=5)
    rng = np.random.RandomState(6)
    w_true = rng.randn(hyper.num_features)

    def ffm_blocks(start, n, B=8, K=4):
        out = []
        for i in range(start, start + n):
            r = np.random.RandomState(7000 + i)
            idx = r.randint(0, hyper.num_features,
                            size=(B, K)).astype(np.int32)
            val = r.rand(B, K).astype(np.float32)
            fld = r.randint(0, hyper.num_fields, size=(B, K)).astype(np.int32)
            lab = np.sign(np.sum(w_true[idx] * val, axis=-1)
                          ).astype(np.float32)
            out.append((idx, val, fld, lab))
        return out

    ck = str(tmp_path / "ffm.npz")
    t_full, s_full = elastic_resume(None, hyper, hyper.num_features, ck,
                                    mesh=make_mesh(4), family="ffm_sharded")
    for blk in ffm_blocks(0, 6):
        s_full, _ = t_full.step(s_full, *blk)
    full = t_full.final_state(s_full)

    t_a, s_a = elastic_resume(None, hyper, hyper.num_features, ck,
                              mesh=make_mesh(4), family="ffm_sharded")
    for blk in ffm_blocks(0, 4):
        s_a, _ = t_a.step(s_a, *blk)
    checkpoint(t_a, s_a, ck, block_step=4)
    ck_state = t_a.final_state(s_a)

    t_2, s_2 = elastic_resume(None, hyper, hyper.num_features, ck,
                              mesh=make_mesh(2), family="ffm_sharded")
    back = t_2.final_state(s_2)
    np.testing.assert_array_equal(np.asarray(back.w), np.asarray(ck_state.w))
    np.testing.assert_array_equal(np.asarray(back.v), np.asarray(ck_state.v))
    np.testing.assert_array_equal(np.asarray(back.z), np.asarray(ck_state.z))
    for blk in ffm_blocks(4, 2):
        s_2, _ = t_2.step(s_2, *blk)
    fin = t_2.final_state(s_2)
    assert int(fin.step) == int(full.step)
    np.testing.assert_allclose(np.asarray(fin.w), np.asarray(full.w),
                               rtol=5e-3, atol=5e-4)


def test_ffm_checkpoint_from_before_v_touched_is_refused_in_words(tmp_path):
    """A checkpoint whose FFM state has no `v_touched` table (written
    before PR 32) is refused with a message that says so, not a KeyError."""
    from hivemall_tpu.models.ffm import FFMHyper
    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.runtime import recovery

    hyper = FFMHyper(num_features=67, v_dims=131, factors=4, num_fields=8,
                     seed=5)
    ck = str(tmp_path / "ffm.npz")
    trainer, state = recovery.elastic_resume(
        None, hyper, hyper.num_features, ck, mesh=make_mesh(2),
        family="ffm_sharded")
    arrays = recovery._pack_ffm_state(trainer.final_state(state))
    assert "v_touched" in arrays
    recovery._unpack_ffm_state(arrays)              # today's round trip
    del arrays["v_touched"]
    with pytest.raises(ValueError, match="before PR 32.*v_touched"):
        recovery._unpack_ffm_state(arrays)


def test_sharded_2d_elastic_resume(tmp_path):
    """The 2-D (replicas × stripes) family resumes across BOTH axes at
    once — (2×4) → (2×2) — with MixTrainer-grade additive-statistics
    discipline: resume-then-collapse is the identity on the step counter
    and sum-kind slots (nothing multiplied by the replica count), and
    training continues on the new topology."""
    from hivemall_tpu.models.regression import ADAGRAD_REGR
    from hivemall_tpu.parallel import MixConfig
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    dims = 101
    rng = np.random.RandomState(8)
    w_true = rng.randn(dims)

    def blocks_2d(R, k, seed):
        r = np.random.RandomState(seed)
        idx = r.randint(0, dims, size=(R, k, 16, 8)).astype(np.int32)
        val = r.rand(R, k, 16, 8).astype(np.float32)
        lab = np.sum(w_true[idx] * val, axis=-1).astype(np.float32)
        return idx, val, lab

    ck = str(tmp_path / "2d.npz")
    hyper = {"eta": 1.0, "eps": 1.0, "scale": 100.0}
    t_a, s_a = elastic_resume(ADAGRAD_REGR, hyper, dims, ck,
                              config=MixConfig(mix_every=2),
                              family="sharded_2d", n_replicas=2, n_shards=4)
    s_a, _ = t_a.step(s_a, *blocks_2d(2, 4, 1))
    checkpoint(t_a, s_a, ck, block_step=1)
    base = t_a.final_state(s_a)
    assert int(base.step) == 2 * 4 * 16

    t_b, s_b = elastic_resume(ADAGRAD_REGR, hyper, dims, ck,
                              config=MixConfig(mix_every=2),
                              family="sharded_2d", n_replicas=2, n_shards=2)
    again = t_b.final_state(s_b)
    # resume + immediate collapse == the checkpoint: step and sum-kind
    # slots counted once, not once per replica
    assert int(again.step) == int(base.step)
    np.testing.assert_allclose(np.asarray(again.slots["sum_sqgrad"]),
                               np.asarray(base.slots["sum_sqgrad"]),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(again.weights),
                               np.asarray(base.weights), rtol=1e-6)
    # new work on the new topology adds exactly once
    s_b, _ = t_b.step(s_b, *blocks_2d(2, 2, 2))
    fin = t_b.final_state(s_b)
    assert int(fin.step) == int(base.step) + 2 * 2 * 16
    assert np.all(np.asarray(fin.slots["sum_sqgrad"])
                  >= np.asarray(base.slots["sum_sqgrad"]) - 1e-7)


def test_cross_family_refusal_and_linear_interop(tmp_path):
    """An FM checkpoint refuses to resume as a linear family (loudly);
    a MixTrainer checkpoint seeds a feature-sharded trainer (the model
    outgrew one device — the cross-family elastic path)."""
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.models.fm import FMHyper
    from hivemall_tpu.parallel import MixConfig, make_mesh
    from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

    dims = 101
    rng = np.random.RandomState(9)
    w_true = rng.randn(dims)

    fm_ck = str(tmp_path / "fm.npz")
    t_fm, s_fm = elastic_resume(None, FMHyper(factors=4), dims, fm_ck,
                                mesh=make_mesh(2), family="fm_sharded")
    checkpoint(t_fm, s_fm, fm_ck)
    with pytest.raises(ValueError, match="fm_sharded"):
        elastic_resume(AROW, {"r": 0.1}, dims, fm_ck,
                       mesh=make_mesh(2), family="sharded")

    mix_ck = str(tmp_path / "mix.npz")
    t_mix, s_mix = elastic_resume(AROW, {"r": 0.1}, dims, mix_ck,
                                  mesh=make_mesh(4),
                                  config=MixConfig(mix_every=2))
    idx = rng.randint(0, dims, size=(4, 2, 16, 8)).astype(np.int32)
    val = rng.rand(4, 2, 16, 8).astype(np.float32)
    lab = np.sign(np.sum(w_true[idx] * val, axis=-1)).astype(np.float32)
    s_mix, _ = t_mix.step(s_mix, idx, val, lab)
    checkpoint(t_mix, s_mix, mix_ck)
    mix_final = t_mix.final_state(s_mix)

    t_sh, s_sh = elastic_resume(AROW, {"r": 0.1}, dims, mix_ck,
                                mesh=make_mesh(2), family="sharded")
    back = t_sh.final_state(s_sh)
    np.testing.assert_array_equal(np.asarray(back.weights),
                                  np.asarray(mix_final.weights))
    assert int(back.step) == int(mix_final.step)


def test_multiprocess_failure_then_elastic_restart(tmp_path):
    """The Hadoop-retry analog end-to-end: a 2-process job checkpoints its
    mixed model and aborts (rc=7); the driver detects the failure and
    elastically resumes SINGLE-process from the checkpoint."""
    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    portno = port.getsockname()[1]
    port.close()

    procs = []
    for pid in range(2):
        env = {
            **os.environ,
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "HIVEMALL_TPU_COORDINATOR": f"127.0.0.1:{portno}",
            "HIVEMALL_TPU_NUM_PROCS": "2",
            "HIVEMALL_TPU_PROC_ID": str(pid),
        }
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "_recovery_child.py"),
             str(tmp_path)],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("recovery child timed out")
        logs.append(out)

    if any("Multiprocess computations aren't implemented on the CPU "
           "backend" in log for log in logs):
        pytest.skip("installed jax cannot run cross-process collectives "
                    "on the CPU backend")
    # failure detection: the job died non-zero AFTER checkpointing
    for pid, (p, log) in enumerate(zip(procs, logs)):
        assert p.returncode == 7, f"child {pid}: rc={p.returncode}\n{log}"
        assert f"CHILD {pid} CHECKPOINTED" in log
    ckpt = str(tmp_path / "ckpt.npz")
    assert os.path.exists(ckpt)

    # elastic restart on the surviving topology (this process, 8 local devs)
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel import MixConfig, make_mesh
    from hivemall_tpu.runtime.recovery import elastic_resume

    dims = 128
    trainer, state = elastic_resume(AROW, {"r": 0.1}, dims, ckpt,
                                    mesh=make_mesh(4),
                                    config=MixConfig(mix_every=2))
    # reproduce the children's ground truth to keep training the same task
    rng = np.random.RandomState(21)
    w_true = rng.randn(dims)
    acc0 = _acc(trainer.final_state(state).weights, w_true, dims)
    assert acc0 > 0.75, f"checkpoint did not carry the trained model: {acc0}"
    idx = rng.randint(0, dims, size=(4, 4, 16, 8)).astype(np.int32)
    val = rng.rand(4, 4, 16, 8).astype(np.float32)
    lab = np.sign(np.sum(w_true[idx] * val, axis=-1)).astype(np.float32)
    state, loss = trainer.step(state, idx, val, lab)
    acc1 = _acc(trainer.final_state(state).weights, w_true, dims)
    assert np.isfinite(float(loss))
    assert acc1 >= acc0 - 0.02, (acc0, acc1)
