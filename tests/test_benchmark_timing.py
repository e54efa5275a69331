"""runtime/benchmark.py — the value-verified bench timing loop (round 4).

Motivated by a measured artifact: a "dispatch N, block once" loop reporting
the enqueue rate instead of the execution rate. These tests pin the
helper's contract: budget-bounded, chunk auto-ranging, and the step-counter
verification that catches dropped executions.
"""

import time

import pytest

from hivemall_tpu.runtime.benchmark import honest_timed_loop


class _Counter:
    def __init__(self):
        self.n = 0


def test_counts_and_budget():
    def run(s):
        s.n += 1
        time.sleep(0.001)
        return s

    iters, secs, state = honest_timed_loop(
        run, _Counter(), lambda s: float(s.n), budget_s=0.05,
        expect_probe_delta=1)
    assert iters >= 1
    assert state.n == iters
    assert secs >= 0.05


def test_chunk_growth_fast_backend():
    # near-zero per-iter cost: chunks must double so iters >> budget/overhead
    iters, secs, _ = honest_timed_loop(
        lambda s: s + 1, 0, lambda s: float(s), budget_s=0.05,
        expect_probe_delta=1)
    assert iters > 64  # doubling happened


def test_probe_mismatch_raises():
    # a "runtime" that silently drops every other execution
    class Flaky:
        def __init__(self):
            self.n = 0
            self.calls = 0

    def run(s):
        s.calls += 1
        if s.calls % 2 == 0:
            s.n += 1  # half the executions "complete"
        return s

    with pytest.raises(RuntimeError, match="probe counter mismatch"):
        honest_timed_loop(run, Flaky(), lambda s: float(s.n),
                          budget_s=0.2, expect_probe_delta=1)


def test_engine_epoch_probe_is_step_counter():
    # the real usage shape: a jitted epoch over staged blocks, probed via
    # the engine's own step counter
    import jax.numpy as jnp
    import numpy as np

    from hivemall_tpu.core.engine import make_epoch, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW

    fn = make_train_fn(AROW, {"r": 0.1}, mode="minibatch")
    epoch = make_epoch(fn)
    rng = np.random.RandomState(0)
    n_blocks, batch, width, dims = 2, 8, 4, 64
    idx = jnp.asarray(rng.randint(0, dims, size=(n_blocks, batch, width),
                                  dtype=np.int32))
    val = jnp.ones((n_blocks, batch, width), jnp.float32)
    lab = jnp.asarray(np.sign(rng.randn(n_blocks, batch)).astype(np.float32))

    state = init_linear_state(dims, use_covariance=True)
    state, _ = epoch(state, idx, val, lab)
    iters, secs, state = honest_timed_loop(
        lambda s: epoch(s, idx, val, lab)[0], state,
        lambda s: float(s.step), budget_s=0.2,
        expect_probe_delta=n_blocks * batch)
    assert iters >= 1
    assert float(state.step) == (iters + 1) * n_blocks * batch
