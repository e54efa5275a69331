"""Honest device-timing helpers for every throughput benchmark.

Motivation (round 4, measured): the classic "dispatch N times, block once
at the end" loop can measure *enqueue* rate rather than execution rate — by
orders of magnitude (bench_ffm once reported 0.015 ms for a step whose
scatter traffic alone lower-bounds it at ~0.17 ms of HBM time). The one
sync that holds on any runtime is a value round-trip: fetching a scalar
**computed from the carried state** must wait for the real result, and a
step counter carried the same way proves no execution was dropped.

`honest_timed_loop` therefore times auto-ranged chunks of work, ending
every chunk with a `device_get` of a probe scalar (and verifying a
monotone step counter when the caller provides one), and includes those
syncs in the measured wall — so the reported rate can never exceed what
the device actually sustained.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Optional, Tuple

_PERMS: dict = {}


def make_workload_ids(rng, shape, dims: int):
    """Benchmark feature ids: log-uniform (heavy-tailed) FREQUENCY with
    hash-UNIFORM placement — the workload shape shared by every
    scripts/bench_*.py, diag_scan_perf.py and chip_smoke.py (the
    benchmark's `datagen.py` keeps the distribution without the host
    permutation).

    Two deliberate properties, both measured to matter (round 4):
    - Frequency: zipf(1.3) (rounds 1-3) is TOO head-heavy — 2M draws touch
      so few distinct features that the C anchor's whole working set stays
      cache-resident. Log-uniform over [1, dims) gives a realistic
      distinct-feature count per epoch.
    - Placement: raw samples concentrate hot ids in the table's first
      cache lines — a contiguity gift real murmur-hashed features never
      give. A fixed permutation spreads them uniformly, preserving the
      duplicate multiset (same TPU scatter collisions; TPU measured
      placement-insensitive — docs/perf_history.md, round 4)."""
    import numpy as np

    if dims not in _PERMS:
        _PERMS[dims] = np.random.RandomState(12345).permutation(
            dims).astype(np.int32)
    u = rng.random_sample(shape)
    ids = np.exp(u * np.log(float(dims))).astype(np.int64) % dims
    return _PERMS[dims][ids]


def honest_timed_loop(
    run_once: Callable[[Any], Any],
    state: Any,
    probe: Callable[[Any], float],
    budget_s: float = 6.0,
    max_chunk: int = 512,
    grow_below_s: float = 0.25,
    expect_probe_delta: Optional[float] = None,
) -> Tuple[int, float, Any]:
    """Run `state = run_once(state)` repeatedly for ~`budget_s` seconds of
    *verified* wall time; return (iterations, elapsed_s, state).

    - `probe(state)` must fetch a scalar derived from the carried state
      (e.g. `lambda s: float(s.step)`); it runs after every chunk and its
      cost is INCLUDED in elapsed, so async-dispatch artifacts cannot
      inflate the rate. Chunks auto-double (up to `max_chunk`) while a
      chunk completes in under `grow_below_s` sec, keeping sync overhead
      under ~1% for fast backends while a slow backend stays at chunk=1.
    - With `expect_probe_delta`, the probe value must advance by
      `expect_probe_delta * chunk` each chunk (e.g. the engine's step
      counter: blocks_per_epoch * batch); a mismatch raises — catching a
      runtime that silently skipped executions. The engine's counters are
      int32, so the loop also returns early before the cumulative count
      could reach 2^31 and wrap (a fast backend can get there inside the
      budget).
    """
    chunk = 1
    iters = 0
    last = probe(state)  # also forces any warmup stragglers to finish
    counter_cap = (float(2 ** 31 - 1) - last) \
        if (expect_probe_delta is not None and expect_probe_delta > 0) else None
    t0 = time.perf_counter()
    while True:
        if counter_cap is not None and \
                (iters + chunk) * expect_probe_delta >= counter_cap:
            if iters == 0:
                raise RuntimeError(
                    f"probe counter {last} already within one chunk of int32 "
                    "wrap — reset the state before timing")
            return iters, time.perf_counter() - t0, state
        c0 = time.perf_counter()
        for _ in range(chunk):
            state = run_once(state)
        val = probe(state)
        c1 = time.perf_counter()
        if expect_probe_delta is not None:
            want = last + expect_probe_delta * chunk
            if abs(val - want) > 0.5:
                raise RuntimeError(
                    f"probe counter mismatch: expected {want}, got {val} "
                    f"after {chunk} iteration(s) — executions were dropped?")
        last = val
        iters += chunk
        if c1 - t0 >= budget_s:
            return iters, c1 - t0, state
        if (c1 - c0) < grow_below_s and chunk < max_chunk:
            chunk *= 2


def measure_reference_rowloops(idx, val, lab, dims: int, k: int = 5,
                               budget_s: float = 2.0) -> dict:
    """Time the C transliterations of the reference's per-row hot loops
    (native hm_arow_reference_rowloop / hm_fm_reference_rowloop) on the
    given host arrays — the measured vs_baseline anchor denominators of
    scripts/bench_ctr_e2e.py. Parse/boxing costs are
    excluded (flatters the reference). Returns {} when the native library
    is missing or predates the anchor symbols (a probe call returning None
    — never time no-op calls)."""
    from .. import native

    out: dict = {}
    if not native.available():
        return out
    n = len(lab)
    # ONE closure per family, used for both the probe and the timed loop,
    # so the probe can never validate a different code path than the one
    # being timed
    for name, rowloop in (
        ("arow", lambda i, v, l, s: native.arow_reference_rowloop(
            i, v, l, dims, state=s)),
        ("fm", lambda i, v, l, s: native.fm_reference_rowloop(
            i, v, l, dims, k=k, state=s)),
    ):
        st: dict = {}
        # probe on st itself: detects missing symbols AND warms the model
        # table allocation so it never lands inside the timed window
        if rowloop(idx[:2048], val[:2048], lab[:2048], st) is None:
            continue
        t0 = time.perf_counter()
        done = 0
        while time.perf_counter() - t0 < budget_s:
            rowloop(idx, val, lab, st)
            done += n
        out[f"{name}_rows_per_sec"] = round(
            done / (time.perf_counter() - t0), 1)
    return out
