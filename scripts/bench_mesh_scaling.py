"""Multi-device scaling curve on the simulated CPU mesh (VERDICT r4 weak #6).

Measures rows/s vs mesh size (1/2/4/8 virtual CPU devices) at a FIXED total
workload for the three scale-out trainers:

- MixTrainer (data-parallel replicas + periodic collective mix — the MIX
  protocol's SPMD redesign, ref: mix/client/MixClient.java -> parallel/mix.py)
- ShardedTrainer (1-D feature-sharded model; every device sees every row —
  the S-fold input replication docs/perf_history.md flags is visible here)
- Sharded2DTrainer (replicas x stripes)

IMPORTANT CAVEAT (printed in every JSON line): virtual devices on one host
ADD NO COMPUTE — XLA multiplexes all N "devices" onto the same cores (this
driver host has 2). So these curves CANNOT show speedup; what they expose is
the OVERHEAD structure of the scale-out path — collective cost, 1-D input
replication, per-device dispatch. The model: total work is FIXED and the
cores are shared, so an overhead-free partition keeps total rows/s CONSTANT
as n grows (ideal retention 1.0); any decay is work the scale-out path
ADDS — collectives, replicated input processing, extra dispatch — and that
added work taxes real hardware too. `throughput_retention_vs_smallest` =
(rows/s at n) / (rows/s at the trainer's smallest mesh) is the number a
real-mesh run wants near 1.0.

Run (virtual CPU mesh): JAX_PLATFORMS=cpu \\
       XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python scripts/bench_mesh_scaling.py [--budget 4]
Run (chip host): python scripts/bench_mesh_scaling.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the forced host device count only shapes the CPU platform: under
# JAX_PLATFORMS=cpu this is an 8-device virtual mesh, on a chip host the
# mesh is whatever chips jax reports
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

import numpy as np

DIMS = 1 << 20
BATCH = 4096
WIDTH = 32
N_BLOCKS = 8  # fixed total workload: N_BLOCKS * BATCH rows per measured pass


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=4.0,
                    help="seconds of verified wall per point")
    args = ap.parse_args()

    import jax

    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel import (MixConfig, MixTrainer, make_mesh)
    from hivemall_tpu.parallel.sharded_train import (Sharded2DTrainer,
                                                     ShardedTrainer)
    from hivemall_tpu.runtime.benchmark import (honest_timed_loop,
                                                make_workload_ids)

    host_cores = os.cpu_count()
    rng = np.random.RandomState(0)
    idx = make_workload_ids(rng, (N_BLOCKS, BATCH, WIDTH), DIMS)
    val = np.ones((N_BLOCKS, BATCH, WIDTH), np.float32)
    lab = np.sign(rng.randn(N_BLOCKS, BATCH)).astype(np.float32)
    rows_total = N_BLOCKS * BATCH

    results: dict = {}

    def emit(trainer_name, n_dev, rps):
        # efficiency is measured against the trainer's SMALLEST mesh point
        # (1 dev, or 4 for the 2-D trainer which needs >= 2x2)
        base = results.setdefault(trainer_name, (n_dev, rps))
        ret = round(rps / base[1], 3)
        print(json.dumps({
            "metric": f"mesh_scaling_{trainer_name}_{n_dev}dev_cpu",
            "value": round(rps, 1),
            "unit": "rows/sec",
            "n_devices": n_dev,
            "throughput_retention_vs_smallest": ret,
            "caveat": (f"virtual devices on one {host_cores}-core host — "
                       "overhead structure only, no real scaling possible"),
        }), flush=True)

    for n_dev in (1, 2, 4, 8):
        # ---- MixTrainer: rows split across replicas
        mesh = make_mesh(n_dev)
        tr = MixTrainer(AROW, {"r": 0.1}, DIMS, mesh,
                        MixConfig(reduction="auto"))
        state = tr.init()
        # [N_BLOCKS, B, K] splits into [n_dev, N_BLOCKS/n_dev, B, K]: the
        # fixed workload divides across replicas, the scale-out contract
        blk = tr.shard_blocks(idx, val, lab)

        def run_mix(s, blk=blk, tr=tr):
            s, _ = tr.step(s, *blk)
            return s

        state = run_mix(state)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        iters, secs, state = honest_timed_loop(
            run_mix, state,
            lambda s: float(np.asarray(jax.tree.leaves(s)[-1]).reshape(-1)[0]),
            budget_s=args.budget)
        emit("mix_dp", n_dev, iters * rows_total / secs)
        del state, tr

        # ---- ShardedTrainer: model striped, rows replicated to all devices
        tr = ShardedTrainer(AROW, {"r": 0.1}, DIMS, make_mesh(n_dev))
        state = tr.init()

        def run_sh(s, tr=tr):
            for b in range(N_BLOCKS):
                s, _ = tr.step(s, idx[b], val[b], lab[b])
            return s

        state = run_sh(state)
        jax.block_until_ready(jax.tree.leaves(state)[0])
        iters, secs, state = honest_timed_loop(
            run_sh, state,
            lambda s: float(np.asarray(jax.tree.leaves(s)[-1]).reshape(-1)[0]),
            budget_s=args.budget)
        emit("sharded_1d", n_dev, iters * rows_total / secs)
        del state, tr

        # ---- Sharded2DTrainer: replicas x stripes (square-ish split)
        if n_dev >= 4:
            n_rep = 2
            n_sh = n_dev // 2
            tr = Sharded2DTrainer(AROW, {"r": 0.1}, DIMS,
                                  n_replicas=n_rep, n_shards=n_sh)
            state = tr.init()
            blk2 = tr.shard_blocks(idx, val, lab)  # [R, k, B, K]

            def run_2d(s, tr=tr, blk2=blk2):
                s, _ = tr.step(s, *blk2)
                return s

            state = run_2d(state)
            jax.block_until_ready(jax.tree.leaves(state)[0])
            iters, secs, state = honest_timed_loop(
                run_2d, state,
                lambda s: float(np.asarray(
                    jax.tree.leaves(s)[-1]).reshape(-1)[0]),
                budget_s=args.budget)
            emit(f"sharded_2d_{n_rep}x{n_sh}", n_dev,
                 iters * rows_total / secs)
            del state, tr


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
