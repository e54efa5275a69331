"""Microbenchmark the AROW minibatch step's components on the current device.

Times (a) full step, (b) gather+math only, (c) each scatter variant, to find
where the ~10ms/step goes (docs/perf_history.md optimization plan step 1).

Compile time and steady-state step time are reported SEPARATELY: the first
call is timed under `recompile_guard` (runtime/metrics.py), which counts jit
cache misses, and the steady loop runs under `expect_stable=True` so a
kernel that silently retraces per call (a G001 recompile hazard) fails the
benchmark loudly instead of publishing a compile-dominated number.

`--trace-out PATH` additionally emits the same breakdown as a
Chrome/Perfetto trace via runtime/tracing.py — one `profile.<kernel>` root
per kernel with `compile` / `steady` child spans (the compile span carries
the jit_recompile instant events recompile_guard fires, and one
jit_retrace_attrib instant per compile naming the jitted function and its
argument-shape delta — so a retrace inside the paying step span is
attributed to a line, not just counted), loadable in ui.perfetto.dev next
to serving traces: training and serving share one trace format
(docs/observability.md).
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hivemall_tpu.runtime.metrics import recompile_guard
from hivemall_tpu.runtime.tracing import TRACER


def timeit(name, fn, *args, n=20):
    """-> (compile_ms, steady_ms, n_compiles). First call timed apart from
    the steady loop; cache misses counted per phase. Each phase is also a
    trace span under a `profile.<name>` root."""
    with TRACER.span(f"profile.{name}"):
        with TRACER.span("compile"), \
                recompile_guard(f"profile.{name}.warmup", fn) as warm:
            t0 = time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            compile_ms = (time.perf_counter() - t0) * 1e3
        with TRACER.span("steady", args={"iters": n}), \
                recompile_guard(f"profile.{name}", fn, expect_stable=True):
            t0 = time.perf_counter()
            for _ in range(n):
                out = fn(*args)
            jax.block_until_ready(out)
            steady_ms = (time.perf_counter() - t0) / n * 1e3
    return compile_ms, steady_ms, warm.compiles, warm.attributions


def report(name, fn, *args, n=20):
    compile_ms, steady_ms, misses, attribs = timeit(name, fn, *args, n=n)
    print(f"{name:<17}: {steady_ms:8.3f} ms/step steady | "
          f"first call {compile_ms:8.1f} ms ({misses} compile)")
    for a in attribs:
        delta = f" (was {a['prev']})" if a["delta"] else ""
        print(f"{'':<17}   compiled {a['fn']} {a['shapes']}{delta}")


def timeit_host(name, fn, *args, n=20):
    """Host-native timing: ONE `host_native` bucket, no compile/steady
    split — a ctypes call has no jit cache to miss and no dispatch stream
    to drain, so folding it into 'steady' would misattribute host CPU
    time as device step time in traces. The span is `host_native` so the
    Perfetto breakdown keeps the bucket distinct."""
    fn(*args)  # warm allocations (table/scratch), outside the window
    with TRACER.span(f"profile.{name}"):
        with TRACER.span("host_native", args={"iters": n}):
            t0 = time.perf_counter()
            for _ in range(n):
                fn(*args)
            host_ms = (time.perf_counter() - t0) / n * 1e3
    return host_ms


def report_host(name, fn, *args, n=20):
    host_ms = timeit_host(name, fn, *args, n=n)
    print(f"{name:<17}: {host_ms:8.3f} ms/step host-native | "
          "(no jit: own bucket, not 'steady')")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace-out", default=None,
                    help="write the compile-vs-steady breakdown as "
                         "Chrome/Perfetto trace JSON (ui.perfetto.dev)")
    args = ap.parse_args()
    if args.trace_out:
        TRACER.clear()  # the file should hold exactly this run's kernels
    dims = 1 << 22
    batch = 16384
    width = 32
    rng = np.random.RandomState(0)
    idx = jnp.asarray((rng.zipf(1.3, size=(batch, width)) % dims).astype(np.int32))
    val = jnp.ones((batch, width), dtype=np.float32)
    lab = jnp.asarray(np.sign(rng.randn(batch)).astype(np.float32))
    w = jnp.zeros((dims,), jnp.float32)
    cov = jnp.ones((dims,), jnp.float32)

    @jax.jit
    def gather_math(w, cov, idx, val, lab):
        wg = w.at[idx].get(mode="fill", fill_value=0.0)
        cg = cov.at[idx].get(mode="fill", fill_value=1.0)
        score = jnp.sum(wg * val, axis=-1)
        var = jnp.sum(cg * val * val, axis=-1)
        m = lab * score
        beta = 1.0 / (var + 0.1)
        alpha = jnp.maximum(0.0, 1.0 - m) * beta
        dw = (alpha * lab)[:, None] * cg * val
        dcov = -(beta[:, None] * (cg * val) ** 2)
        return dw, dcov

    @jax.jit
    def one_scatter(w, idx, dw):
        return jnp.zeros_like(w).at[idx].add(dw, mode="drop")

    @jax.jit
    def scatter_into_2d(w, idx, dw, dcov, upd):
        # fused: one scatter of [B,K,3] into [D,3]
        acc = jnp.zeros((w.shape[0], 3), jnp.float32)
        payload = jnp.stack([dw, dcov, upd], axis=-1)
        return acc.at[idx].add(payload, mode="drop")

    @jax.jit
    def sort_segsum(w, idx, dw):
        flat_i = idx.reshape(-1)
        flat_d = dw.reshape(-1)
        order = jnp.argsort(flat_i)
        si = flat_i[order]
        sd = flat_d[order]
        return jnp.zeros_like(w).at[si].add(sd, mode="drop")

    @jax.jit
    def full_d_pass(w, dw_sum, counts):
        return w + dw_sum / jnp.maximum(counts, 1.0)

    report("gather+math", gather_math, w, cov, idx, val, lab)
    dw, dcov = gather_math(w, cov, idx, val, lab)
    upd = jnp.ones_like(dw)
    report("one scatter [D]", one_scatter, w, idx, dw)
    report("fused [D,3] scat", scatter_into_2d, w, idx, dw, dcov, upd)
    report("sort+scatter", sort_segsum, w, idx, dw)
    dw_sum = one_scatter(w, idx, dw)
    counts = one_scatter(w, idx, upd)
    report("full-D pass", full_d_pass, w, dw_sum, counts)

    # int8 touched scatter-max
    touched = jnp.zeros((dims,), jnp.int8)

    @jax.jit
    def touch_max(t, idx, lane):
        return t.at[idx].max(lane, mode="drop")

    lane = jnp.ones_like(idx, jnp.int8)
    report("touched max int8", touch_max, touched, idx, lane)

    # the -native_apply backend's whole per-block apply (gather -> batch
    # closed form -> segment reduce -> scatter-back in one C pass) as its
    # own host-native bucket — attributable next to the jitted kernels
    # instead of disappearing into a 'steady' number it doesn't belong to
    from hivemall_tpu.core.native_batch import (
        init_native_tables, make_native_batch_step,
        native_batch_unsupported_reason)
    from hivemall_tpu.models.classifier import AROW

    reason = native_batch_unsupported_reason(AROW)
    if reason is None:
        from hivemall_tpu.core.batch_update import stage_block_plans

        idx_h = np.asarray(idx)
        val_h = np.ones((batch, width), np.float32)
        lab_h = np.sign(rng.randn(batch)).astype(np.float32)
        plans = stage_block_plans(idx_h, 2048, dims)
        tables = init_native_tables(dims, use_covariance=True)
        step = make_native_batch_step(AROW, {"r": 0.1})
        report_host("native apply", step, tables, val_h, lab_h, plans)
    else:
        print(f"native apply      : skipped ({reason})")

    if args.trace_out:
        doc = TRACER.export_chrome(args.trace_out)
        print(f"wrote {len(doc['traceEvents'])} trace events "
              f"({doc['otherData']['traces']} kernels) to {args.trace_out} "
              f"— load in ui.perfetto.dev")


if __name__ == "__main__":
    main()
