"""FFM training throughput at the CTR shape (hashed features, 32 nnz/row,
64 fields, k=4), HBM-staged blocks — the train_ffm counterpart of
bench_fm.py, with and without -row_chunk activation tiling so the K^2
pairwise memory/time tradeoff is measured on hardware.

Run (real chip): python scripts/bench_ffm.py
Run (CPU):       JAX_PLATFORMS=cpu python scripts/bench_ffm.py
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.models.ffm import FFMHyper, init_ffm_state, make_ffm_step

    platform = jax.devices()[0].platform
    batch = 4096
    width = 32
    fields = 64
    n_blocks = 4
    hyper = FFMHyper(factors=4, num_features=1 << 20, v_dims=1 << 22,
                     num_fields=fields, seed=0)

    rng = np.random.RandomState(0)
    from hivemall_tpu.runtime.benchmark import make_workload_ids as make_ids
    idx = make_ids(rng, (n_blocks, batch, width), dims=1 << 20)
    val = np.ones((n_blocks, batch, width), dtype=np.float32)
    fld = rng.randint(0, fields, size=(n_blocks, batch, width)).astype(np.int32)
    lab = np.sign(rng.randn(n_blocks, batch)).astype(np.float32)

    idx_d = jnp.asarray(idx)
    val_d = jnp.asarray(val)
    fld_d = jnp.asarray(fld)
    lab_d = jnp.asarray(lab)

    from hivemall_tpu.core.engine import make_epoch
    from hivemall_tpu.runtime.benchmark import honest_timed_loop

    for name, rc in (("untiled", None), ("row_chunk512", 512)):
        fn = make_ffm_step(hyper, "minibatch", row_chunk=rc, jit=False)
        # one epoch = one dispatch (device-resident scan over staged blocks);
        # timing is chunked + step-counter-verified (runtime/benchmark.py) so
        # enqueued-but-unexecuted work cannot inflate the rate
        epoch = make_epoch(fn)

        state = init_ffm_state(hyper)
        state, losses = epoch(state, idx_d, val_d, fld_d, lab_d)
        jax.block_until_ready(losses)
        iters, dt, _ = honest_timed_loop(
            lambda s: epoch(s, idx_d, val_d, fld_d, lab_d)[0], state,
            lambda s: float(s.step), budget_s=6.0,
            expect_probe_delta=n_blocks * batch)
        print(json.dumps({
            "metric": f"ffm_train_throughput_k4_{width}nnz_{fields}fields_"
                      f"{name}_device_scan_{platform}",
            "value": round(iters * n_blocks * batch / dt, 1),
            "unit": "rows/sec",
            "ms_per_step": round(1e3 * dt / (iters * n_blocks), 3),
        }), flush=True)
        del state

if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
