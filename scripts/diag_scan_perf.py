"""Bisect device-scan training throughput with value-verified timing.

Round 4 once measured bench_ffm at 0.015 ms/step — below that step's own
HBM scatter traffic bound — while the fully-synced ctr_e2e measured ~34 ms
per AROW step on the same chip: a "dispatch N, block once" loop had
measured the enqueue rate. Every timing here goes through
`runtime/benchmark.honest_timed_loop`: chunks end with a device_get of a
scalar computed from the carried state, and (for engine variants) the
engine's own step counter is verified to have advanced — neither can be
satisfied without the work having run.

Sections:
  A. scatter/gather microbenches at the CTR shape (524288 updates into
     2^22 slots): duplicate zipf ids vs sorted vs unique, FM's [D,k] layout
     vs [k,D], the minibatch-average counts pattern, plus sort cost.
     These give the true TPU cost model for the engine's hot ops.
  B. AROW engine epoch (8/128 blocks, donate/no-donate, jit/AOT).
  C. FM epoch variants (k, w-only vs V-only).

Prints one JSON line per variant. Run:
    python scripts/diag_scan_perf.py [--budget S] [--only PREFIX]
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
N_UPD = BATCH * WIDTH  # 524288 scatter rows per step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=float, default=3.0,
                    help="seconds of verified wall per variant")
    ap.add_argument("--only", type=str, default="")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_epoch, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step
    from hivemall_tpu.runtime.benchmark import honest_timed_loop

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(0)

    def emit(name, iters, secs, unit_per_iter, unit):
        print(json.dumps({
            "metric": f"diag_{name}_{platform}",
            "value": round(unit_per_iter * iters / secs, 1),
            "unit": unit,
            "ms_per_iter": round(1e3 * secs / iters, 4),
            "iters": iters,
        }), flush=True)

    def want(name):
        return not args.only or name.startswith(args.only)

    # ---------------- A. microbenches ------------------------------------
    # All table-mutating micros DONATE the table (the engine's real path —
    # without donation an undonated [2^22, 5] scatter pays a full 84MB
    # table copy per call, measured 17x the donated cost on CPU).
    dup_idx = jnp.asarray((rng.zipf(1.3, size=(N_UPD,)) % DIMS).astype(np.int32))
    sorted_idx = jnp.sort(dup_idx)
    # unique ids: a slice of a permutation (no duplicates by design)
    uniq_idx = jnp.asarray(rng.permutation(DIMS)[:N_UPD].astype(np.int32))
    uniq_sorted = jnp.sort(uniq_idx)
    upd = jnp.asarray(rng.randn(N_UPD).astype(np.float32))
    upd5 = jnp.asarray(rng.randn(N_UPD, 5).astype(np.float32))
    upd5T = jnp.asarray(np.ascontiguousarray(np.asarray(upd5).T))

    def micro(name, init, f, *fargs):
        """f is jitted with donate_argnums=(0,); carried state = the table."""
        if not want(name):
            return
        st = f(init(), *fargs)  # compile + warm
        jax.block_until_ready(st)
        iters, secs, st = honest_timed_loop(
            lambda s: f(s, *fargs), st,
            lambda s: float(jnp.reshape(s, (-1,))[0]),
            budget_s=args.budget)
        emit(name, iters, secs, N_UPD, "updates/sec")
        del st

    def t1():
        return jnp.zeros((DIMS,), jnp.float32)

    scat = jax.jit(lambda v, i, u: v.at[i].add(u, mode="drop"),
                   donate_argnums=(0,))
    scat_uni = jax.jit(lambda v, i, u: v.at[i].add(
        u, mode="drop", unique_indices=True), donate_argnums=(0,))
    scat_uni_srt = jax.jit(lambda v, i, u: v.at[i].add(
        u, mode="drop", unique_indices=True, indices_are_sorted=True),
        donate_argnums=(0,))
    scat_srt = jax.jit(lambda v, i, u: v.at[i].add(
        u, mode="drop", indices_are_sorted=True), donate_argnums=(0,))
    gath = jax.jit(
        lambda v, i: v.at[0].add(jnp.sum(v.at[i].get(
            mode="fill", fill_value=0.0))), donate_argnums=(0,))

    micro("micro_gather_dup", t1, gath, dup_idx)
    micro("micro_scatter_add_dup", t1, scat, dup_idx, upd)
    micro("micro_scatter_add_sorted", t1, scat_srt, sorted_idx, upd)
    micro("micro_scatter_add_unique", t1, scat_uni, uniq_idx, upd)
    micro("micro_scatter_add_unique_sorted", t1, scat_uni_srt,
          uniq_sorted, upd)
    micro("micro_scatter_v5_dup", lambda: jnp.zeros((DIMS, 5), jnp.float32),
          scat, dup_idx, upd5)
    micro("micro_scatter_v5T_dup", lambda: jnp.zeros((5, DIMS), jnp.float32),
          jax.jit(lambda v, i, u: v.at[:, i].add(u, mode="drop"),
                  donate_argnums=(0,)), dup_idx, upd5T)
    # sort-inside-program then scatter (the dedup-path building block)
    micro("micro_sort_then_scatter", t1,
          jax.jit(lambda v, i, u: v.at[jnp.sort(i)].add(
              u, mode="drop", indices_are_sorted=True),
              donate_argnums=(0,)), dup_idx, upd)
    # the minibatch-average counts pattern (fresh zeros + scatter + gather)
    micro("micro_counts_pattern", t1,
          jax.jit(lambda v, i, u: v.at[i].add(
              u / jnp.maximum(
                  jnp.zeros((DIMS,), jnp.float32).at[i].add(
                      jnp.ones_like(u), mode="drop")
                  .at[i].get(mode="fill", fill_value=1.0), 1.0),
              mode="drop"), donate_argnums=(0,)), dup_idx, upd)

    # ---- micro2: round-4b variants suggested by the first TPU capture
    # (v5 row-scatter 9x the scalar cost; gather 2x the scatter; k=4 FM
    # epoch 1.4x faster than k=5 => lane-alignment hypothesis) ----
    # uniform placement (hash-realistic): same duplicate frequency as zipf,
    # ids spread over [0, D) by a fixed permutation
    perm = rng.permutation(DIMS).astype(np.int32)
    uni_idx = jnp.asarray(perm[np.asarray(dup_idx)])
    micro("micro2_scatter_add_dup_uniform_placed", t1, scat, uni_idx, upd)
    micro("micro2_gather_dup_uniform_placed", t1, gath, uni_idx)

    # packed pair table [D,2] (w+cov interleaved): one row gather vs two
    # scalar gathers; row scatter vs two scalar scatters
    upd2 = jnp.asarray(rng.randn(N_UPD, 2).astype(np.float32))

    def t2():
        return jnp.zeros((DIMS, 2), jnp.float32)

    micro("micro2_gather_pair_dup", t2,
          jax.jit(lambda v, i: v.at[0, 0].add(jnp.sum(v.at[i].get(
              mode="fill", fill_value=0.0))), donate_argnums=(0,)), dup_idx)
    micro("micro2_scatter_pair_rows_dup", t2, scat, dup_idx, upd2)

    # FM V-update alternatives: flat [D*k] scalar scatter with computed
    # lane ids; k unrolled scalar scatters into [k, D] planes; and the
    # engine's chosen fix — [D, 8] lane-padded rows (k=5 in 8 lanes)
    flat_idx5 = (dup_idx[:, None] * 5 +
                 jnp.arange(5, dtype=jnp.int32)[None, :]).reshape(-1)

    def t5flat():
        return jnp.zeros((DIMS * 5,), jnp.float32)

    micro("micro2_scatter_v5_flat_dup", t5flat, scat, flat_idx5,
          upd5.reshape(-1))

    def scat_perk(v, i, u):
        for f in range(5):
            v = v.at[f, i].add(u[:, f], mode="drop")
        return v

    micro("micro2_scatter_v5_perk_dup",
          lambda: jnp.zeros((5, DIMS), jnp.float32),
          jax.jit(scat_perk, donate_argnums=(0,)), dup_idx, upd5)

    upd8 = jnp.concatenate(
        [upd5, jnp.zeros((N_UPD, 3), jnp.float32)], axis=1)
    micro("micro2_scatter_v8pad_dup",
          lambda: jnp.zeros((DIMS, 8), jnp.float32), scat, dup_idx, upd8)

    # gather side of the same layouts
    micro("micro2_gather_v5_rows_dup",
          lambda: jnp.zeros((DIMS, 5), jnp.float32),
          jax.jit(lambda v, i: v.at[0, 0].add(jnp.sum(v.at[i].get(
              mode="fill", fill_value=0.0))), donate_argnums=(0,)), dup_idx)
    micro("micro2_gather_v8pad_dup",
          lambda: jnp.zeros((DIMS, 8), jnp.float32),
          jax.jit(lambda v, i: v.at[0, 0].add(jnp.sum(v.at[i].get(
              mode="fill", fill_value=0.0))), donate_argnums=(0,)), dup_idx)

    def gath_perk(v, i):
        s = 0.0
        for f in range(5):
            s = s + jnp.sum(v.at[f, i].get(mode="fill", fill_value=0.0))
        return v.at[0, 0].add(s)

    micro("micro2_gather_v5_perk_dup",
          lambda: jnp.zeros((5, DIMS), jnp.float32),
          jax.jit(gath_perk, donate_argnums=(0,)), dup_idx)

    # ---------------- B/C. engine epochs ---------------------------------
    def blocks(n):
        # the shared workload shape (runtime/benchmark.make_workload_ids):
        # log-uniform frequency, hash-uniform placement
        from hivemall_tpu.runtime.benchmark import make_workload_ids as make_ids

        idx = make_ids(rng, (n, BATCH, WIDTH), dims=DIMS)
        val = np.ones((n, BATCH, WIDTH), dtype=np.float32)
        lab = np.sign(rng.randn(n, BATCH)).astype(np.float32)
        return jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lab)

    idx8, val8, lab8 = blocks(8)

    def epoch_bench(name, n_blocks, make_state, run_epoch, step_attr="step"):
        """Engine variants: probe = the carried step counter (verified)."""
        if not want(name):
            return
        state = make_state()
        state = run_epoch(state)  # compile+warm
        jax.block_until_ready(state)
        iters, secs, state = honest_timed_loop(
            run_epoch, state,
            lambda s: float(getattr(s, step_attr)),
            budget_s=args.budget,
            expect_probe_delta=n_blocks * BATCH)
        emit(name, iters, secs, n_blocks * BATCH, "rows/sec")
        del state

    fn = make_train_fn(AROW, {"r": 0.1}, mode="minibatch")

    def arow_state():
        return init_linear_state(DIMS, use_covariance=True)

    @jax.jit
    def ep_nodonate(state, idx, val, lab):
        def body(s, blk):
            s, loss = fn(s, *blk)
            return s, loss
        return jax.lax.scan(body, state, (idx, val, lab))

    epoch_bench("arow_scan8_nodonate", 8, arow_state,
                lambda s: ep_nodonate(s, idx8, val8, lab8)[0])

    ep_don = make_epoch(fn)
    epoch_bench("arow_scan8_donate", 8, arow_state,
                lambda s: ep_don(s, idx8, val8, lab8)[0])

    if want("arow_scan128_donate") or want("arow_scan128_aot_closure"):
        idx128, val128, lab128 = blocks(128)
        epoch_bench("arow_scan128_donate", 128, arow_state,
                    lambda s: ep_don(s, idx128, val128, lab128)[0])
        values_c = jnp.ones((BATCH, WIDTH), jnp.float32)
        ep_ctr = make_epoch(lambda s, bidx, blab: fn(s, bidx, values_c, blab))
        ep_ctr_c = ep_ctr.lower(arow_state(), idx128, lab128).compile()
        epoch_bench("arow_scan128_aot_closure", 128, arow_state,
                    lambda s: ep_ctr_c(s, idx128, lab128)[0])
        del idx128, val128, lab128

    va = jnp.zeros((BATCH,), jnp.float32)

    for tag, k in (("fm_k5_avg", 5), ("fm_k4_avg", 4)):
        hyper = FMHyper(factors=k, classification=True)
        fm_fn = make_fm_step(hyper, mode="minibatch", jit=False)
        ep = make_epoch(lambda s, bi, bv, bl, _f=fm_fn: _f(s, bi, bv, bl, va))
        epoch_bench(tag, 8, lambda _h=hyper: init_fm_state(DIMS, _h),
                    lambda s, _e=ep: _e(s, idx8, val8, lab8)[0])

    # stripped FM steps: w path only vs V path only
    hyper5 = FMHyper(factors=5, classification=True)

    def fm_w_only(state, idx, val, lab):
        wg = state.w.at[idx].get(mode="fill", fill_value=0.0)
        p = state.w0 + jnp.sum(wg * val, axis=1)
        g = (jax.nn.sigmoid(p * lab) - 1.0) * lab
        dw = -0.05 * (g[:, None] * val + 0.02 * wg)
        return state.replace(w=state.w.at[idx].add(dw, mode="drop"),
                             step=state.step + idx.shape[0]), jnp.sum(g)

    def fm_v_only(state, idx, val, lab):
        vg = state.v.at[idx].get(mode="fill", fill_value=0.0)
        vx = vg * val[..., None]
        sum_vfx = jnp.sum(vx, axis=1)
        p = state.w0 + 0.5 * jnp.sum(
            sum_vfx * sum_vfx - jnp.sum(vx * vx, axis=1), axis=1)
        g = (jax.nn.sigmoid(p * lab) - 1.0) * lab
        grad_v = val[..., None] * sum_vfx[:, None, :] - vg * (val * val)[..., None]
        dv = -0.05 * (g[:, None, None] * grad_v + 0.02 * vg)
        return state.replace(v=state.v.at[idx].add(dv, mode="drop"),
                             step=state.step + idx.shape[0]), jnp.sum(g)

    for tag, step in (("fm_w_only", fm_w_only), ("fm_v_only", fm_v_only)):
        ep = make_epoch(step)
        epoch_bench(tag, 8, lambda: init_fm_state(DIMS, hyper5),
                    lambda s, _e=ep: _e(s, idx8, val8, lab8)[0])


if __name__ == "__main__":
    main()
