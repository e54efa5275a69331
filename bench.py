"""Benchmark: online-trainer throughput at the reference's headline workload
shape (KDD2012 Track 2 CTR-style sparse rows, hashed 2^22-dim model,
32 nnz/row — BASELINE.json names BOTH train_arow and train_fm).

Prints ONE JSON line. The primary metric keeps a STABLE name
(`arow_train_throughput_2^22dims_32nnz`); the device it ran on
(platform, device_kind, device count) and the methodology are separate
fields on every line. A `train_fm` companion metric rides in
`extra_metrics` on the same line.

vs_baseline divides by a MEASURED anchor: the reference's per-row JVM hot
loop transliterated to C and timed on THIS host (native hm_arow_reference_
rowloop / hm_fm_reference_rowloop — parse/boxing costs excluded, which
flatters the reference). The old 2.5e5 rows/s JVM-mapper estimate is kept as
a labeled secondary (`vs_estimated_jvm_mapper`).

One process measures everything (a chip belongs to one process). The main
line needs an accelerator: without one the run exits non-zero and prints no
result — a CPU number is never reported under the device metric's name.
`--batch-smoke` is the host-backend gate (scripts/test.sh gate 8) and runs
on the CPU by design; its line names the device all the same.
"""

import json
import sys
import time

import numpy as np

ESTIMATED_JVM_MAPPER_ROWS_PER_SEC = 250_000.0  # labeled secondary anchor

WIDTH = 32  # nnz per row, KDD CTR-ish
DIMS = 1 << 22
FM_FACTORS = 5
# holdout-logloss parity pin of the batched backends vs B=1 (the same pin
# bench_serving uses for int8)
BATCH_PARITY_TOL_LOGLOSS = 0.02
BATCH_SMOKE_MIN_VS_SCAN = 1.5  # tier-1 gate: batched >= 1.5x row-serial
# tier-1 gate (native half): the -native_apply backend at the standard
# 2^22-dim regime must beat the XLA batch path >= 1.2x AND the measured C
# row loop >= 1.0x — the ROADMAP raw-speed front (d), "beating the C row
# loop outright on CPU", as a standing gate instead of a one-off claim
NATIVE_SMOKE_MIN_VS_BATCH = 1.2
NATIVE_SMOKE_MIN_VS_ROWLOOP = 1.0
NATIVE_SMOKE_DIMS = 1 << 22


def make_ids(rng, shape, dims=DIMS):
    """Shared workload generator (see
    hivemall_tpu.runtime.benchmark.make_workload_ids for the rationale);
    kept here as the bench-policy entry point with the headline DIMS
    default."""
    from hivemall_tpu.runtime.benchmark import make_workload_ids

    return make_workload_ids(rng, shape, dims)


def _measure_anchors() -> dict:
    """Measure the reference's per-row hot loops (C transliterations, this
    host, sequential single mapper) — the vs_baseline denominators, host
    metrics by construction."""
    from hivemall_tpu import native

    out = {
        "kind": "c_transliterated_reference_rowloop_this_host",
        "note": ("sequential per-row loop, JVM parse/boxing excluded "
                 "(flatters the reference); see native/hivemall_native.cpp. "
                 "The same loop ships as the -native_scan execution "
                 "backend (train_arow), so host-only workers match this "
                 "anchor by construction"),
        "estimated_jvm_mapper_rows_per_sec": ESTIMATED_JVM_MAPPER_ROWS_PER_SEC,
    }
    if not native.available():
        return out
    from hivemall_tpu.runtime.benchmark import measure_reference_rowloops

    rng = np.random.RandomState(0)
    n = 1 << 16
    idx = make_ids(rng, (n, WIDTH))
    val = np.ones((n, WIDTH), np.float32)
    lab = np.sign(rng.randn(n)).astype(np.float32)
    out.update(measure_reference_rowloops(idx, val, lab, DIMS, k=FM_FACTORS))
    return out


def _std_sigmoid_logloss(scores, labels) -> float:
    """Holdout logloss of standardized scores. Margin classifiers emit
    uncalibrated scores, so every arm gets the SAME single-parameter
    standardization (score / std) before the sigmoid — scale-free and
    smooth where raw-sigmoid logloss saturates, which is what a batch-size
    parity comparison needs. Recorded as score_calibration: "std"."""
    from hivemall_tpu.evaluation.metrics import logloss

    s = np.asarray(scores, np.float32)
    s = s / max(float(np.std(s)), 1e-9)
    return logloss(1.0 / (1.0 + np.exp(-s)), labels)


def _planted_weights(rng, dims):
    """The ONE planted weight vector both splits are labeled by — train
    and holdout must share it or holdout logloss is independent of what
    the model learned and the parity gate measures score-shape noise."""
    return (rng.randn(dims) * (rng.rand(dims) < 0.05)).astype(np.float32)


def _planted_workload(rng, n, dims, w_true, noise=0.3):
    """Rows labeled by the SHARED planted weights + label noise, so
    holdout logloss measures model quality, not chance — the AdaBatch
    accuracy side needs labels worth predicting."""
    idx = make_ids(rng, (n, WIDTH), dims)
    val = np.abs(rng.randn(n, WIDTH)).astype(np.float32)
    margin = np.einsum("nk,nk->n", val, w_true[idx])
    lab = np.where(margin + noise * np.std(margin) * rng.randn(n) > 0,
                   1.0, -1.0).astype(np.float32)
    return idx, val, lab


def _batch_holdout_logloss(b, train, holdout, dims) -> float:
    """ONE exact epoch of AROW through the batched backend at batch size
    `b`; returns standardized holdout logloss (see _std_sigmoid_logloss)."""
    from hivemall_tpu.core.batch_update import (make_batch_train_step,
                                                stage_block_plans)
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW

    idx, val, lab = train
    h_idx, h_val, h_lab = holdout
    step = make_batch_train_step(AROW, {"r": 0.1}, batch_size=b)
    st = init_linear_state(dims, use_covariance=True)
    st, _ = step(st, idx, val, lab, stage_block_plans(idx, b, dims))
    w = np.asarray(st.weights, dtype=np.float32)
    return _std_sigmoid_logloss(np.einsum("nk,nk->n", h_val, w[h_idx]),
                                h_lab)


def _native_batch_available() -> "str | None":
    """None when -native_apply can serve AROW, else the reason (reported
    in-artifact so a fallback round names its cause)."""
    from hivemall_tpu.core.native_batch import native_batch_unsupported_reason
    from hivemall_tpu.models.classifier import AROW

    return native_batch_unsupported_reason(AROW)


def _native_batch_rps(idx, val, lab, b, dims, budget_s=2.0) -> float:
    """Throughput of the -native_apply backend over staged blocks
    [n_blocks, N, K]: host plans staged once (the fit_linear plan-cache
    deployment shape), every epoch one vectorized C pass per block."""
    from hivemall_tpu.core.batch_update import stage_block_plans
    from hivemall_tpu.core.native_batch import (init_native_tables,
                                                make_native_batch_step)
    from hivemall_tpu.models.classifier import AROW

    n_blocks, block = idx.shape[0], idx.shape[1]
    plans = [stage_block_plans(idx[i], b, dims) for i in range(n_blocks)]
    step = make_native_batch_step(AROW, {"r": 0.1})
    tables = init_native_tables(dims, use_covariance=True)
    step(tables, val[0], lab[0], plans[0])  # warm allocations
    t0 = time.perf_counter()
    total = 0
    while time.perf_counter() - t0 < budget_s:
        for i in range(n_blocks):
            step(tables, val[i], lab[i], plans[i])
        total += n_blocks * block
    return total / (time.perf_counter() - t0)


def _native_batch_holdout_logloss(b, train, holdout, dims) -> float:
    """_batch_holdout_logloss through the -native_apply backend — the
    same one-epoch protocol, so the equal-holdout-logloss pin covers the
    native pass itself, not just its XLA twin."""
    from hivemall_tpu.core.batch_update import stage_block_plans
    from hivemall_tpu.core.native_batch import (init_native_tables,
                                                make_native_batch_step)
    from hivemall_tpu.models.classifier import AROW

    idx, val, lab = train
    h_idx, h_val, h_lab = holdout
    step = make_native_batch_step(AROW, {"r": 0.1})
    tables = init_native_tables(dims, use_covariance=True)
    step(tables, val, lab, stage_block_plans(idx, b, dims))
    w = tables["w"]
    return _std_sigmoid_logloss(np.einsum("nk,nk->n", h_val, w[h_idx]),
                                h_lab)


def _device_block() -> dict:
    """The device every printed line names, as jax reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "process_count": jax.process_count()}


def _measure() -> dict:
    """Run the AROW + FM scan-epoch measurements on the accelerator and
    return the raw numbers. Exits non-zero, printing no result, when jax
    finds only the CPU.

    Methodology (stable since round 3): the epoch loop is ONE jitted
    `lax.scan` over the HBM-staged blocks — the framework's deployment shape
    (io/records.py prefetch + on-device epoch loop; the reference likewise
    replays epochs from its in-memory/NIO buffer,
    FactorizationMachineUDTF.java:521). scripts/bench_arow_methodology.py
    attributes dispatch overhead separately (analysis in docs/perf_history.md)."""
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_epoch, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    device = _device_block()
    if device["platform"] == "cpu":
        sys.exit("bench.py: jax found no accelerator (devices: "
                 f"{jax.devices()}); the device metrics are not measured on "
                 "a CPU — run on the chip machine")
    enable_compile_cache()
    batch = 16384
    # 128 staged blocks: amortizes per-epoch dispatch (diag arow_scan128 =
    # +26% over scan8 on v5e) while the 2M-row epoch still fits HBM easily
    n_blocks = 128

    rng = np.random.RandomState(0)
    # log-uniform frequency, hash-uniform placement (see make_ids)
    idx = make_ids(rng, (n_blocks, batch, WIDTH))
    val = np.ones((n_blocks, batch, WIDTH), dtype=np.float32)
    lab = np.sign(rng.randn(n_blocks, batch)).astype(np.float32)

    # stage the epoch's blocks in HBM once
    blocks = (jnp.asarray(idx), jnp.asarray(val), jnp.asarray(lab))
    rows_per_epoch = n_blocks * batch

    def timed_epoch_loop(epoch, state, budget_s=6.0):
        from hivemall_tpu.runtime.benchmark import honest_timed_loop

        state, losses = epoch(state, *blocks)  # compile+warm
        jax.block_until_ready(losses)

        def run(s):
            s2, _ = epoch(s, *blocks)
            return s2

        # Chunked + budget-bounded + verified: every chunk ends with a
        # device_get of the carried step counter (checked to have advanced
        # by exactly chunk * rows_per_epoch), so work that was enqueued but
        # not executed cannot inflate the rate, and however slow the
        # backend is the loop exits within its budget.
        iters, secs, _ = honest_timed_loop(
            run, state, lambda s: float(s.step), budget_s=budget_s,
            expect_probe_delta=rows_per_epoch)
        return iters * rows_per_epoch / secs

    fn = make_train_fn(AROW, {"r": 0.1}, mode="minibatch")
    arow_rps = timed_epoch_loop(make_epoch(fn),
                                init_linear_state(DIMS, use_covariance=True))

    hyper = FMHyper(factors=FM_FACTORS, classification=True)
    fm_fn = make_fm_step(hyper, mode="minibatch", jit=False)
    no_va = jnp.zeros((batch,), dtype=bool)
    fm_epoch = make_epoch(lambda s, bi, bv, bl: fm_fn(s, bi, bv, bl, no_va))
    fm_rps = timed_epoch_loop(fm_epoch, init_fm_state(DIMS, hyper))

    out = {
        "device": device,
        "arow_rows_per_sec": round(arow_rps, 1),
        "fm_rows_per_sec": round(fm_rps, 1),
    }
    # A/B the sorted-window MXU update backend (ops/mxu_scatter.py) in the
    # same process — the default stays whichever side this data says (r4c
    # keep-or-revert policy). Each side is fenced: a compile/OOM failure in
    # the EXPERIMENTAL backend must not cost the headline numbers already
    # in `out`.
    try:
        fn_mxu = make_train_fn(AROW, {"r": 0.1}, mode="minibatch",
                               update_backend="mxu")
        out["arow_mxu_rows_per_sec"] = round(timed_epoch_loop(
            make_epoch(fn_mxu),
            init_linear_state(DIMS, use_covariance=True)), 1)
    except Exception as e:  # noqa: BLE001 - experimental side
        print(f"bench: arow mxu A/B failed: {e!r}", file=sys.stderr)
    try:
        fm_fn_mxu = make_fm_step(hyper, mode="minibatch", jit=False,
                                 update_backend="mxu")
        fm_epoch_mxu = make_epoch(
            lambda s, bi, bv, bl: fm_fn_mxu(s, bi, bv, bl, no_va))
        out["fm_mxu_rows_per_sec"] = round(
            timed_epoch_loop(fm_epoch_mxu, init_fm_state(DIMS, hyper)), 1)
    except Exception as e:  # noqa: BLE001
        print(f"bench: fm mxu A/B failed: {e!r}", file=sys.stderr)
    return out


def batch_smoke() -> int:
    """Tier-1 gate (scripts/test.sh gate 8): the batched backend must beat
    the row-serial JAX scan on THIS host by >= BATCH_SMOKE_MIN_VS_SCAN at
    a batch size whose holdout logloss stays within the pinned parity
    tolerance of B=1. Small shapes (2^20 dims) so the gate runs in tens
    of seconds; the full-size numbers live in the main bench line. Runs
    in-process on the CPU backend and prints ONE BENCH-style JSON line.

    The native half (PR 14): when the -native_apply backend is available
    it must additionally beat the XLA batch path >= 1.2x AND the measured
    C row loop >= 1.0x at the same B — measured at the STANDARD 2^22-dim
    regime (the scoreboard shape; at toy dims the row loop's whole table
    is cache-resident and the comparison prices nothing real) — with its
    own holdout logloss inside the B=1 parity tolerance. An unavailable
    native backend (no .so AND no compiler to build one —
    scripts/build_native.sh --if-stale) skips those gates LOUDLY: the
    JSON carries the reason, never a silent pass-by-omission."""
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.batch_update import (make_batch_train_fn,
                                                stage_epoch_plans)
    from hivemall_tpu.core.engine import make_epoch, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.runtime.benchmark import honest_timed_loop

    device = _device_block()
    if device["platform"] != "cpu":
        # a host-backend gate measured on an accelerator would compare the
        # wrong things; say so instead of printing a skipped line with rc 0
        sys.exit(f"bench.py --batch-smoke is the HOST-backend gate; jax "
                 f"landed on {device['platform']} — run it with "
                 f"JAX_PLATFORMS=cpu")

    dims = 1 << 20
    block, n_blocks, smoke_b = 8192, 4, 2048
    rng = np.random.RandomState(0)
    idx = make_ids(rng, (n_blocks, block, WIDTH), dims)
    val = np.ones((n_blocks, block, WIDTH), np.float32)
    lab = np.sign(rng.randn(n_blocks, block)).astype(np.float32)
    idx_d, val_d, lab_d = jnp.asarray(idx), jnp.asarray(val), \
        jnp.asarray(lab)

    def rps(epoch, staged, budget_s=3.0, table_dims=dims):
        st = init_linear_state(table_dims, use_covariance=True)
        st, losses = epoch(st, *staged)
        jax.block_until_ready(losses)
        rows = int(staged[0].shape[0]) * int(staged[0].shape[1])

        def run(s):
            s2, _ = epoch(s, *staged)
            return s2

        iters, secs, _ = honest_timed_loop(run, st, lambda s: float(s.step),
                                           budget_s=budget_s,
                                           expect_probe_delta=rows)
        return iters * rows / secs

    scan_rps = rps(make_epoch(make_train_fn(AROW, {"r": 0.1}, mode="scan")),
                   (idx_d[:1], val_d[:1], lab_d[:1]))
    plans = jax.tree_util.tree_map(
        jax.device_put, stage_epoch_plans(idx, smoke_b, dims))
    bfn = make_batch_train_fn(AROW, {"r": 0.1}, batch_size=smoke_b)
    batch_rps = rps(make_epoch(lambda s, bi, bv, bl, pl:
                               bfn(s, bi, bv, bl, pl)),
                    (idx_d, val_d, lab_d, plans))
    speedup = batch_rps / scan_rps if scan_rps else 0.0

    # 2^16 rows -> 32 updates at the smoke B: the smallest scale where
    # batch-count starvation doesn't masquerade as staleness
    rng_acc = np.random.RandomState(5)
    w_true = _planted_weights(rng_acc, dims)
    train = _planted_workload(rng_acc, 1 << 16, dims, w_true)
    holdout = _planted_workload(rng_acc, 1 << 13, dims, w_true)
    ll_b1 = _batch_holdout_logloss(1, train, holdout, dims)
    ll_b = _batch_holdout_logloss(smoke_b, train, holdout, dims)
    ll_delta = abs(ll_b - ll_b1)

    ok_speed = speedup >= BATCH_SMOKE_MIN_VS_SCAN
    ok_parity = ll_delta <= BATCH_PARITY_TOL_LOGLOSS

    # ---- native half: -native_apply vs the XLA batch path AND the C row
    # loop, at the STANDARD 2^22-dim regime on a 2-block slice
    native_block = {}
    ok_native = True
    native_reason = _native_batch_available()
    if native_reason is None:
        from hivemall_tpu import native

        ndims, nblocks = NATIVE_SMOKE_DIMS, 2
        idx_n = make_ids(rng, (nblocks, block, WIDTH), ndims)
        val_n = np.ones((nblocks, block, WIDTH), np.float32)
        lab_n = lab[:nblocks]
        nplans = jax.tree_util.tree_map(
            jax.device_put, stage_epoch_plans(idx_n, smoke_b, ndims))
        nbfn = make_batch_train_fn(AROW, {"r": 0.1}, batch_size=smoke_b)
        xla_rps = rps(make_epoch(lambda s, bi, bv, bl, pl:
                                 nbfn(s, bi, bv, bl, pl)),
                      (jnp.asarray(idx_n), jnp.asarray(val_n),
                       jnp.asarray(lab_n), nplans), table_dims=ndims)

        nat_rps = _native_batch_rps(idx_n, val_n, lab_n, smoke_b, ndims,
                                    budget_s=2.0)
        st: dict = {}
        native.arow_reference_rowloop(idx_n[0][:2048], val_n[0][:2048],
                                      lab_n[0][:2048], ndims + 1, state=st)
        t0 = time.perf_counter()
        done = 0
        while time.perf_counter() - t0 < 2.0:
            for i in range(nblocks):
                native.arow_reference_rowloop(idx_n[i], val_n[i], lab_n[i],
                                              ndims + 1, state=st)
            done += nblocks * block
        rowloop_rps = done / (time.perf_counter() - t0)
        ll_native = _native_batch_holdout_logloss(smoke_b, train, holdout,
                                                  dims)
        ll_native_delta = abs(ll_native - ll_b1)
        vs_batch = nat_rps / xla_rps if xla_rps else 0.0
        vs_rowloop = nat_rps / rowloop_rps if rowloop_rps else 0.0
        ok_nat_speed = (vs_batch >= NATIVE_SMOKE_MIN_VS_BATCH
                        and vs_rowloop >= NATIVE_SMOKE_MIN_VS_ROWLOOP)
        ok_nat_parity = ll_native_delta <= BATCH_PARITY_TOL_LOGLOSS
        ok_native = ok_nat_speed and ok_nat_parity
        native_block = {
            "execution_backend": "native_batch",
            "dims": ndims,
            "batch_size": smoke_b,
            "native_batch_rows_per_sec": round(nat_rps, 1),
            "xla_batch_rows_per_sec": round(xla_rps, 1),
            "rowloop_rows_per_sec": round(rowloop_rps, 1),
            "vs_xla_batch": round(vs_batch, 3),
            "vs_rowloop": round(vs_rowloop, 3),
            "min_vs_xla_batch": NATIVE_SMOKE_MIN_VS_BATCH,
            "min_vs_rowloop": NATIVE_SMOKE_MIN_VS_ROWLOOP,
            "holdout_logloss_native": round(ll_native, 5),
            "logloss_delta_vs_b1": round(ll_native_delta, 5),
            "pass": bool(ok_native),
        }
        if not ok_nat_speed:
            print(f"batch-smoke FAIL: native-apply {nat_rps:.0f} rows/s is "
                  f"{vs_batch:.2f}x the XLA batch path ({xla_rps:.0f}) and "
                  f"{vs_rowloop:.2f}x the C row loop ({rowloop_rps:.0f}); "
                  f"gate needs >= {NATIVE_SMOKE_MIN_VS_BATCH}x and >= "
                  f"{NATIVE_SMOKE_MIN_VS_ROWLOOP}x at 2^22 dims",
                  file=sys.stderr)
        if not ok_nat_parity:
            print(f"batch-smoke FAIL: native-apply holdout logloss moved "
                  f"{ll_b1:.4f} -> {ll_native:.4f} at B={smoke_b} (tol "
                  f"{BATCH_PARITY_TOL_LOGLOSS})", file=sys.stderr)
    else:
        # no .so and no compiler: the gate skips, but the reason is in
        # the artifact and on stderr — never a silent pass-by-omission
        native_block = {"skipped": native_reason}
        print(f"batch-smoke: native-apply gates skipped: {native_reason}",
              file=sys.stderr)

    print(json.dumps({
        "metric": "arow_batch_vs_scan_speedup",
        "value": round(speedup, 3),
        "unit": "x",
        "device": device,
        "methodology": {"name": "batch_smoke_2^20dims_32nnz",
                        "execution_backend": "batch",
                        "batch_size": smoke_b,
                        "score_calibration": "std"},
        "scan_rows_per_sec": round(scan_rps, 1),
        "batch_rows_per_sec": round(batch_rps, 1),
        "min_speedup": BATCH_SMOKE_MIN_VS_SCAN,
        "holdout_logloss_b1": round(ll_b1, 5),
        "holdout_logloss_batch": round(ll_b, 5),
        "logloss_delta": round(ll_delta, 5),
        "parity_tol_logloss": BATCH_PARITY_TOL_LOGLOSS,
        "native_apply": native_block,
        "pass": bool(ok_speed and ok_parity and ok_native),
    }))
    if not ok_speed:
        print(f"batch-smoke FAIL: batched {batch_rps:.0f} rows/s is only "
              f"{speedup:.2f}x the row-serial scan ({scan_rps:.0f}); gate "
              f"needs >= {BATCH_SMOKE_MIN_VS_SCAN}x", file=sys.stderr)
    if not ok_parity:
        print(f"batch-smoke FAIL: holdout logloss moved {ll_b1:.4f} -> "
              f"{ll_b:.4f} at B={smoke_b} (tol "
              f"{BATCH_PARITY_TOL_LOGLOSS})", file=sys.stderr)
    return 0 if (ok_speed and ok_parity and ok_native) else 1


def main() -> None:
    raw = _measure()
    anchors = _measure_anchors()

    arow = raw["arow_rows_per_sec"]
    fm = raw["fm_rows_per_sec"]
    arow_anchor = float(anchors.get("arow_rows_per_sec") or
                        ESTIMATED_JVM_MAPPER_ROWS_PER_SEC)
    fm_anchor = float(anchors.get("fm_rows_per_sec") or
                      ESTIMATED_JVM_MAPPER_ROWS_PER_SEC)

    def _meth(backend):
        # `name` keeps the historical methodology string;
        # `execution_backend` names the ladder rung (minibatch / mxu)
        return {"name": "hbm_staged_device_scan_epoch",
                "execution_backend": backend}

    arow_metric = "arow_train_throughput_2^22dims_32nnz"
    fm_metric = f"fm_train_throughput_2^22dims_k{FM_FACTORS}_32nnz"
    extra = [{
        "metric": fm_metric,
        "value": fm,
        "unit": "rows/sec",
        "methodology": _meth("minibatch"),
        "vs_baseline": round(fm / fm_anchor, 3),
        "vs_estimated_jvm_mapper": round(
            fm / ESTIMATED_JVM_MAPPER_ROWS_PER_SEC, 3),
    }]
    extra += [{
        # sorted-window MXU update backend A/B (ops/mxu_scatter.py)
        "metric": m,
        "methodology": _meth("mxu"),
        "value": raw[k],
        "unit": "rows/sec",
        "vs_baseline": round(raw[k] / a, 3),
    } for m, k, a in [
        (arow_metric, "arow_mxu_rows_per_sec", arow_anchor),
        (fm_metric, "fm_mxu_rows_per_sec", fm_anchor),
    ] if k in raw]
    print(json.dumps({
        "metric": arow_metric,
        "value": arow,
        "unit": "rows/sec",
        "vs_baseline": round(arow / arow_anchor, 3),
        "device": raw["device"],
        "methodology": _meth("minibatch"),
        "baseline_anchor": anchors,
        "vs_estimated_jvm_mapper": round(
            arow / ESTIMATED_JVM_MAPPER_ROWS_PER_SEC, 3),
        "extra_metrics": extra,
    }))


if __name__ == "__main__":
    if "--batch-smoke" in sys.argv:
        sys.exit(batch_smoke())
    else:
        main()
