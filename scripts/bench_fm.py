"""FM training throughput at the CTR shape (2^22 dims, k=5, 32 nnz/row),
HBM-staged blocks, one epoch a dispatch.

Run (real chip): python scripts/bench_fm.py
Run (CPU):       JAX_PLATFORMS=cpu python scripts/bench_fm.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step

    platform = jax.devices()[0].platform
    dims = 1 << 22
    batch = 16384
    width = 32
    n_blocks = 8

    rng = np.random.RandomState(0)
    from hivemall_tpu.runtime.benchmark import make_workload_ids as make_ids
    idx = make_ids(rng, (n_blocks, batch, width), dims=dims)
    val = np.ones((n_blocks, batch, width), dtype=np.float32)
    lab = np.sign(rng.randn(n_blocks, batch)).astype(np.float32)
    no_va = np.zeros((batch,), dtype=bool)

    # stage the epoch's blocks in HBM once, stacked for a device-resident scan
    idx_d = jnp.asarray(idx)
    val_d = jnp.asarray(val)
    lab_d = jnp.asarray(lab)
    va_d = jnp.asarray(no_va)

    from hivemall_tpu.core.engine import make_epoch

    hyper = FMHyper(factors=5, classification=True)

    from hivemall_tpu.runtime.benchmark import honest_timed_loop

    # one epoch = one dispatch (the deployment shape — io/records.py prefetch
    # + on-device epoch replay, mirroring FactorizationMachineUDTF.java:521);
    # timing is chunked + step-counter-verified (runtime/benchmark.py) so
    # enqueued-but-unexecuted work cannot inflate the rate
    fn = make_fm_step(hyper, mode="minibatch", jit=False)
    epoch = make_epoch(lambda s, bi, bv, bl: fn(s, bi, bv, bl, va_d))
    state = init_fm_state(dims, hyper)
    state, losses = epoch(state, idx_d, val_d, lab_d)
    jax.block_until_ready(losses)

    iters, dt, state = honest_timed_loop(
        lambda s: epoch(s, idx_d, val_d, lab_d)[0], state,
        lambda s: float(s.step), budget_s=6.0,
        expect_probe_delta=n_blocks * batch)
    rows_per_sec = iters * n_blocks * batch / dt
    print(json.dumps({
        "metric": f"fm_train_throughput_2^22dims_k5_{width}nnz_"
                  f"device_scan_{platform}",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec",
        "ms_per_step": round(1e3 * dt / (iters * n_blocks), 3),
    }), flush=True)

if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
