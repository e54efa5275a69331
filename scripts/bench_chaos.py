#!/usr/bin/env python
"""Chaos bench: kill a feature-sharded training run mid-stream, resume it
elastically on a DIFFERENT simulated device count, and publish what the
fault actually cost — recovery seconds, lost (replayed) steps, and the
final-holdout-logloss delta vs an uninterrupted run of the same data
stream. One BENCH-style JSON line (the bench.py shape).

The scenario is ISSUE 8's robustness matrix end to end: a seeded
runtime/faults.FaultPlan injects a device loss at step K (and, in the full
run, a corrupt-checkpoint rot), runtime/recovery.run_elastic catches the
dead job, rebuilds the mesh over the survivors via parallel/mesh, resumes
from the last valid checkpoint (re-striping the table N→M through
core/striping.restripe), and replays the steps since. The data stream is
deterministic and device-count-independent (ShardedTrainer blocks
replicate), so the uninterrupted baseline and the chaos run see the SAME
examples in the same order — the logloss delta isolates what elasticity
costs, not what the data reshuffle costs.

--smoke (tier-1 gate in scripts/test.sh): a small run that must (1)
actually fire the planned faults, (2) finish on a device count != the
starting one, (3) keep the holdout-logloss delta within --tol-logloss of
the uninterrupted baseline, and (4) lose zero checkpointed work (the final
step counter equals the uninterrupted run's exactly). Non-zero exit on any
violation.

Run:  JAX_PLATFORMS=cpu python scripts/bench_chaos.py [--smoke]
"""

import argparse
import json
import os
import sys
import time

# simulated fleet BEFORE jax import (same discipline as tests/conftest.py);
# the forced device count only shapes the CPU platform — scripts/test.sh
# passes JAX_PLATFORMS=cpu, a chip host runs on its chips
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def make_stream(dims, n_steps, batch, width, seed):
    """Deterministic planted-signal stream: step i's block is a pure
    function of (seed, i) — identical whatever mesh consumes it."""
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dims)

    def block(i):
        r = np.random.RandomState(seed * 100_003 + i)
        idx = r.randint(0, dims, size=(batch, width)).astype(np.int32)
        val = r.rand(batch, width).astype(np.float32)
        lab = np.sign(np.sum(w_true[idx] * val, axis=-1)).astype(np.float32)
        return idx, val, lab

    return w_true, block


def holdout_logloss(weights, w_true, dims, width, n=4096, seed=999):
    from hivemall_tpu.evaluation.metrics import logloss

    rng = np.random.RandomState(seed)
    idx = rng.randint(0, dims, size=(n, width))
    val = rng.rand(n, width).astype(np.float32)
    y = (np.sum(w_true[idx] * val, axis=-1) > 0).astype(float)
    score = np.sum(np.asarray(weights, np.float32)[idx] * val, axis=-1)
    return logloss(1.0 / (1.0 + np.exp(-score)), y)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dims", type=int, default=None,
                    help="model dims, deliberately non-divisible "
                         "(default 65539; 515 under --smoke)")
    ap.add_argument("--steps", type=int, default=None,
                    help="driver steps (default 96; 24 under --smoke)")
    ap.add_argument("--batch", type=int, default=None,
                    help="rows per step (default 256; 32 under --smoke)")
    ap.add_argument("--width", type=int, default=8, help="nnz per row")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="steps between checkpoints (default 8; 4 smoke)")
    ap.add_argument("--seed", type=int, default=42,
                    help="seeds the data stream AND the fault plan")
    ap.add_argument("--fault-step", type=int, default=None,
                    help="device-loss step (default: seeded placement in "
                         "the middle third of the run)")
    ap.add_argument("--n-lost", type=int, default=2,
                    help="devices lost at the fault (resume runs on "
                         "start_devices - n_lost)")
    ap.add_argument("--tol-logloss", type=float, default=0.02,
                    help="max |final holdout logloss delta| vs the "
                         "uninterrupted run")
    ap.add_argument("--smoke", action="store_true",
                    help="small shape + hard gates; tier-1 in test.sh")
    args = ap.parse_args()

    dims = args.dims if args.dims is not None else (515 if args.smoke
                                                    else 65539)
    n_steps = args.steps if args.steps is not None else (24 if args.smoke
                                                         else 96)
    batch = args.batch if args.batch is not None else (32 if args.smoke
                                                       else 256)
    ck_every = args.checkpoint_every if args.checkpoint_every is not None \
        else (4 if args.smoke else 8)

    import tempfile

    import jax

    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel.mesh import make_mesh
    from hivemall_tpu.runtime import faults
    from hivemall_tpu.runtime.recovery import elastic_resume, run_elastic

    all_devices = list(jax.devices())
    n_start = len(all_devices)
    if n_start - args.n_lost < 1:
        print(f"bench_chaos: need > {args.n_lost} devices, have {n_start}",
              file=sys.stderr)
        return 2

    w_true, block = make_stream(dims, n_steps, batch, args.width, args.seed)

    def data_fn(_trainer, i):
        return block(i)

    # --- uninterrupted baseline: same stream, no faults, N devices -------
    t0 = time.monotonic()
    base_trainer, base_state = elastic_resume(
        AROW, {"r": 0.1}, dims, os.path.join(tempfile.mkdtemp(), "base.npz"),
        mesh=make_mesh(n_start), family="sharded")
    for i in range(n_steps):
        base_state, _ = base_trainer.step(base_state, *block(i))
    base_final = base_trainer.final_state(base_state)
    base_s = time.monotonic() - t0
    base_ll = holdout_logloss(base_final.weights, w_true, dims, args.width)

    # --- chaos run: seeded fault plan, elastic driver --------------------
    rng = np.random.RandomState(args.seed)
    fault_step = args.fault_step if args.fault_step is not None else int(
        rng.randint(n_steps // 3, 2 * n_steps // 3))
    plan_faults = [faults.Fault("device_loss", at_step=fault_step,
                                n_lost=args.n_lost)]
    if not args.smoke:
        # full run also rots the FIRST checkpoint written after recovery,
        # then injects a transient step failure before the next write — the
        # restart must load the rotted newest, fall back (loudly) to .prev,
        # and still converge. Write counter: fault_step//ck_every writes
        # land before the device loss; the next one is +1.
        corrupt_write = max(2, fault_step // ck_every + 1)
        plan_faults.append(faults.Fault("corrupt", at_write=corrupt_write))
        transient_at = corrupt_write * ck_every + max(1, ck_every // 2)
        if transient_at < n_steps:
            plan_faults.append(
                faults.Fault("transient_step", at_step=transient_at))
    plan = faults.FaultPlan(seed=args.seed, faults=tuple(plan_faults))

    ckpt = os.path.join(tempfile.mkdtemp(), "chaos.npz")

    def make_trainer(devices):
        return elastic_resume(AROW, {"r": 0.1}, dims, ckpt,
                              mesh=make_mesh(devices=list(devices)),
                              family="sharded")

    t1 = time.monotonic()
    with faults.inject(plan) as injector:
        trainer, state, report = run_elastic(
            make_trainer, data_fn, n_steps, ckpt,
            checkpoint_every=ck_every, devices=all_devices)
    chaos_s = time.monotonic() - t1
    chaos_final = trainer.final_state(state)
    chaos_ll = holdout_logloss(chaos_final.weights, w_true, dims, args.width)

    delta = chaos_ll - base_ll
    zero_lost_work = int(chaos_final.step) == int(base_final.step)
    result = {
        "metric": f"chaos_recovery_logloss_delta_arow_{dims}dims",
        "value": round(delta, 6),
        "unit": "logloss",
        "methodology": "seeded_device_loss_elastic_resume_vs_uninterrupted",
        "seed": args.seed,
        "steps": n_steps,
        "rows_per_step": batch,
        "checkpoint_every": ck_every,
        "device_set": {
            "platform": all_devices[0].platform,
            "start_devices": n_start,
            "final_devices": report["final_devices"],
        },
        "faults_planned": [
            {"kind": f.kind, "at_step": f.at_step, "at_write": f.at_write,
             "n_lost": f.n_lost} for f in plan.faults],
        "faults_fired": injector.fired,
        "recovery": {
            "restarts": report["restarts"],
            "lost_steps_replayed": report["lost_steps"],
            "checkpoints_written": report["checkpoints_written"],
            "recovery_s": round(report["recovery_s"], 3),
        },
        "uninterrupted": {"final_logloss": round(base_ll, 6),
                          "train_s": round(base_s, 3),
                          "final_step": int(base_final.step)},
        "chaos": {"final_logloss": round(chaos_ll, 6),
                  "train_s": round(chaos_s, 3),
                  "final_step": int(chaos_final.step)},
        "zero_lost_work": zero_lost_work,
        "tolerance_logloss": args.tol_logloss,
    }
    print(json.dumps(result))

    ok = True
    if not injector.fired:
        print("bench_chaos: FAIL — no planned fault fired", file=sys.stderr)
        ok = False
    if report["final_devices"] == n_start:
        print("bench_chaos: FAIL — run finished on the starting device "
              "count; elasticity was not exercised", file=sys.stderr)
        ok = False
    if abs(delta) > args.tol_logloss:
        print(f"bench_chaos: FAIL — |logloss delta| {abs(delta):.6f} > "
              f"tolerance {args.tol_logloss}", file=sys.stderr)
        ok = False
    if not zero_lost_work:
        print(f"bench_chaos: FAIL — final step counter "
              f"{int(chaos_final.step)} != uninterrupted "
              f"{int(base_final.step)}: checkpointed work was lost or "
              "double-counted", file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
