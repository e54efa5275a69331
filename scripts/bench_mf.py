"""MF training throughput — train_mf_sgd AND train_bprmf at an ML-20M-ish
shape (2^20 users, 2^17 items, k=16), HBM-staged blocks, device-scan epochs.

Completes the per-family TPU throughput suite (AROW/FM/FFM/MC/forest had
rows; the MF family had none). Same methodology as bench_fm.py: one epoch =
one jitted lax.scan over staged blocks (the deployment shape — io/records.py
prefetch + on-device epoch replay, mirroring the reference's NIO replay,
OnlineMatrixFactorizationUDTF.java:92,203), timing chunked +
step-counter-verified (runtime/benchmark.honest_timed_loop) so
enqueued-but-unexecuted work cannot inflate the rate.

Run (real chip): python scripts/bench_mf.py
Run (CPU):       JAX_PLATFORMS=cpu python scripts/bench_mf.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

N_USERS = 1 << 20
N_ITEMS = 1 << 17
K = 16
BATCH = 16384
N_BLOCKS = 8


def main() -> None:
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.runtime.benchmark import make_workload_ids as make_ids
    from hivemall_tpu.core.engine import make_epoch
    from hivemall_tpu.models.mf import (BPRHyper, MFHyper, init_mf_state,
                                        make_bpr_step, make_mf_step)
    from hivemall_tpu.runtime.benchmark import honest_timed_loop

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(0)
    # users log-uniform (activity skew), items log-uniform (popularity skew),
    # both hash-uniformly placed — the bench.make_ids workload shape
    users = jnp.asarray(make_ids(rng, (N_BLOCKS, BATCH), dims=N_USERS))
    items = jnp.asarray(make_ids(rng, (N_BLOCKS, BATCH), dims=N_ITEMS))
    ratings = jnp.asarray(
        (1.0 + 4.0 * rng.rand(N_BLOCKS, BATCH)).astype(np.float32))
    neg_items = jnp.asarray(make_ids(rng, (N_BLOCKS, BATCH), dims=N_ITEMS))

    def bench_one(tag, state, epoch):
        state, losses = epoch(state)  # compile + warm
        jax.block_until_ready(losses)
        iters, dt, _ = honest_timed_loop(
            lambda s: epoch(s)[0], state,
            lambda s: float(s.step), budget_s=6.0,
            expect_probe_delta=N_BLOCKS * BATCH)
        rows_per_sec = iters * N_BLOCKS * BATCH / dt
        print(json.dumps({
            "metric": f"{tag}_train_throughput_2^20users_2^17items_k{K}"
                      f"_device_scan_{platform}",
            "value": round(rows_per_sec, 1),
            "unit": "rows/sec",
            "ms_per_step": round(1e3 * dt / (iters * N_BLOCKS), 3),
        }), flush=True)

    mf_hyper = MFHyper(factor=K)
    mf_fn = make_mf_step(mf_hyper, mode="minibatch", jit=False)
    mf_epoch = make_epoch(mf_fn)
    bench_one("mf_sgd", init_mf_state(N_USERS, N_ITEMS, mf_hyper),
              lambda s: mf_epoch(s, users, items, ratings))

    bpr_hyper = BPRHyper(factor=K)
    bpr_fn = make_bpr_step(bpr_hyper, mode="minibatch", jit=False)
    bpr_epoch = make_epoch(bpr_fn)
    bench_one("bprmf", init_mf_state(N_USERS, N_ITEMS, bpr_hyper),
              lambda s: bpr_epoch(s, users, items, neg_items))


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
