"""Multiclass training throughput: train_multiclass_arow at a
news20-multiclass-like shape (26 labels, 2^20 dims, 64 nnz/row), device-scan
epochs over HBM-staged blocks — the stacked-[L, D] tensor counterpart of
bench.py (ref: MulticlassOnlineClassifierUDTF's per-label model map becomes
one [L, D] weight + [L, D] covariance tensor; every label scores in one
[L, K] @ [K] matmul per row).

Run (real chip): python scripts/bench_mc.py
Run (CPU):       JAX_PLATFORMS=cpu python scripts/bench_mc.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_epoch
    from hivemall_tpu.models.multiclass import (MC_AROW, MulticlassState,
                                                make_mc_train_step)

    platform = jax.devices()[0].platform
    L, dims, batch, width, n_blocks = 26, 1 << 20, 4096, 64, 8

    rng = np.random.RandomState(0)
    from hivemall_tpu.runtime.benchmark import make_workload_ids as make_ids
    idx = make_ids(rng, (n_blocks, batch, width), dims=dims)
    val = np.ones((n_blocks, batch, width), dtype=np.float32)
    lab = rng.randint(0, L, size=(n_blocks, batch)).astype(np.int32)

    idx_d = jnp.asarray(idx)
    val_d = jnp.asarray(val)
    lab_d = jnp.asarray(lab)

    fn = make_mc_train_step(MC_AROW, {"r": 0.1}, mode="minibatch", jit=False)
    epoch = make_epoch(fn)

    def fresh():
        return MulticlassState(
            weights=jnp.zeros((L, dims), jnp.float32),
            covars=jnp.ones((L, dims), jnp.float32),
            touched=jnp.zeros((L, dims), jnp.int8),
            step=jnp.zeros((), jnp.int32),
        )

    from hivemall_tpu.runtime.benchmark import honest_timed_loop

    state = fresh()
    state, losses = epoch(state, idx_d, val_d, lab_d)
    jax.block_until_ready(losses)

    # chunked + step-counter-verified timing (runtime/benchmark.py) so
    # enqueued-but-unexecuted work cannot inflate the rate
    iters, dt, _ = honest_timed_loop(
        lambda s: epoch(s, idx_d, val_d, lab_d)[0], state,
        lambda s: float(s.step), budget_s=6.0,
        expect_probe_delta=n_blocks * batch)
    print(json.dumps({
        "metric": f"mc_arow_train_throughput_{L}labels_2^20dims_{width}nnz_"
                  f"device_scan_{platform}",
        "value": round(iters * n_blocks * batch / dt, 1),
        "unit": "rows/sec",
        "ms_per_step": round(1e3 * dt / (iters * n_blocks), 3),
    }), flush=True)


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
