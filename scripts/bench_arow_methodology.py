"""Methodology disambiguation for the AROW headline number.

Round 1 self-reported 702M rows/s; the round-2 driver recorded 469M for the
same metric name (both later retracted as enqueue rates — docs/perf_history.md). This
script runs the SAME workload
(AROW minibatch, 2^22 dims, 32 nnz, 16384-row blocks, HBM-staged) under
three timing methodologies so the gap is attributable, not guessed:

1. python-loop  — bench.py's loop: each step dispatched from Python, one
   block_until_ready at the end. Includes per-step Python dispatch
   overhead whenever dispatch cannot stay ahead of the step time.
2. device-scan  — the whole epoch as ONE lax.scan jitted over the staged
   blocks: zero per-step dispatch, pure device compute. The framework's
   actual deployment shape (the training loop lives on device).
3. single-step  — per-step wall time of an isolated step (what round 1's
   0.023 ms profile measured), extrapolated.

Every methodology ends its timed region with a VALUE fetch of a result
scalar (runtime/benchmark.py): a sync no runtime can satisfy before the
producing execution has finished.

Prints one JSON line per methodology. Rerunnable:
    python scripts/bench_arow_methodology.py [--rounds N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

DIMS = 1 << 22
BATCH = 16384
WIDTH = 32
N_BLOCKS = 8


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=None,
                    help="timing rounds (default: 40 on accelerators, 2 on cpu)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_train_fn, make_train_step
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(0)
    idx = (rng.zipf(1.3, size=(N_BLOCKS, BATCH, WIDTH)) % DIMS).astype(np.int32)
    val = np.ones((N_BLOCKS, BATCH, WIDTH), dtype=np.float32)
    lab = np.sign(rng.randn(N_BLOCKS, BATCH)).astype(np.float32)
    idx_d = jnp.asarray(idx)
    val_d = jnp.asarray(val)
    lab_d = jnp.asarray(lab)
    rounds = args.rounds if args.rounds is not None \
        else (40 if platform != "cpu" else 2)
    print(f"# platform={platform} rounds={rounds}", file=sys.stderr)

    def report(name, rows, secs):
        print(json.dumps({
            "metric": f"arow_methodology_{name}_{platform}",
            "value": round(rows / secs, 1),
            "unit": "rows/sec",
            "vs_baseline": round(rows / secs / 2.5e5, 3),
            "wall_s": round(secs, 4),
        }), flush=True)

    # 1. python-loop (bench.py methodology)
    step = make_train_step(AROW, {"r": 0.1}, mode="minibatch", donate=True)
    state = init_linear_state(DIMS, use_covariance=True)
    state, loss = step(state, idx_d[0], val_d[0], lab_d[0])
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    total = 0
    for _ in range(rounds):
        for b in range(N_BLOCKS):
            state, loss = step(state, idx_d[b], val_d[b], lab_d[b])
            total += BATCH
    _ = float(loss)  # value fetch: un-fakeable sync (see runtime/benchmark.py)
    report("python_loop", total, time.perf_counter() - t0)
    del state

    # 2. device-scan: the whole multi-round epoch is one jitted program
    fn = make_train_fn(AROW, {"r": 0.1}, mode="minibatch")

    @jax.jit
    def epoch(state, idx, val, lab):
        def body(s, blk):
            s, loss = fn(s, *blk)
            return s, loss

        return jax.lax.scan(body, state, (idx, val, lab))

    state = init_linear_state(DIMS, use_covariance=True)
    state, losses = epoch(state, idx_d, val_d, lab_d)
    jax.block_until_ready(losses)
    t0 = time.perf_counter()
    total = 0
    for _ in range(rounds):
        state, losses = epoch(state, idx_d, val_d, lab_d)
        total += N_BLOCKS * BATCH
    _ = float(losses[-1])  # value fetch: un-fakeable sync
    report("device_scan", total, time.perf_counter() - t0)
    del state

    # 3. single-step wall time, synchronized each step (profile methodology)
    step2 = make_train_step(AROW, {"r": 0.1}, mode="minibatch", donate=True)
    state = init_linear_state(DIMS, use_covariance=True)
    state, loss = step2(state, idx_d[0], val_d[0], lab_d[0])
    jax.block_until_ready(loss)
    n = max(rounds // 2, 2)
    t0 = time.perf_counter()
    for i in range(n):
        state, loss = step2(state, idx_d[i % N_BLOCKS], val_d[i % N_BLOCKS],
                            lab_d[i % N_BLOCKS])
        _ = float(loss)  # value fetch: un-fakeable per-step sync
    report("single_step_sync", n * BATCH, time.perf_counter() - t0)


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
