#!/usr/bin/env python
"""Serving bench: open- and closed-loop throughput + latency percentiles.

Measures the in-process serving stack (ServingEngine + DynamicBatcher —
the same objects the /predict endpoint drives, minus HTTP parse noise):

- **closed loop**: T worker threads each issue sequential requests and wait
  (throughput under a fixed concurrency, the classic saturation probe);
- **open loop**: requests arrive at a fixed rate regardless of completions
  (the coordinated-omission-free latency probe — queueing delay shows up in
  the numbers instead of silently throttling the load generator).

`--http` switches to the end-to-end surface instead: a ModelRegistry +
`serving.serve()` endpoint is stood up in-process and the closed loop and
hot-swap probe drive `POST /predict` over real sockets — HTTP parse, JSON
(de)serialization, and handler threading included — reporting the same
BENCH-style JSON (methodology `http_post_predict_closed_loop`).

Verifies the two serving invariants while measuring:
- after warmup, a request sweep spanning every shape bucket leaves the
  `graftcheck.recompiles.serving.*` counter FLAT (zero steady-state
  recompiles);
- an in-flight v1 -> v2 hot swap completes with zero failed requests.

Output: one BENCH-style JSON line (the bench.py shape). `--smoke` runs a
seconds-scale version and exits non-zero if an invariant breaks — wired
into scripts/test.sh as the serving smoke gate.

Tracing (runtime/tracing.py): under `--http` the run also writes the
request traces as Chrome/Perfetto JSON (`--trace-out`, default
serving_trace.json — load in ui.perfetto.dev) and embeds a per-stage
(queue/pad/dispatch/block) time breakdown plus the top-5 slowest traces in
the BENCH JSON, so a latency regression is attributable from the artifact
alone; the smoke gate additionally fails unless the traces cover >= 4 of
the request-path stage names (docs/observability.md).

`--quantize` switches to the quantized-artifact parity bench instead: one
model is frozen three ways (f32 / bf16 / int8 — serving/artifact
freeze(quantize=...)), every precision warms its own engine, and the SAME
pre-parsed request pool is driven through all three in interleaved paired
trials, each trial a concurrent closed loop (precision order rotates per
trial, so drift in the host's background load cancels in the per-trial
ratios; concurrent drivers keep the memory system under serving-shaped
pressure — the regime quantization exists for). Reported per precision:
throughput, p50/p99 (+ deltas vs f32), artifact bytes on disk, resident
table bytes, steady-state recompiles (must be zero — the bucket mesh is
identical across precisions), and holdout logloss/AUC via
evaluation/metrics.py. The int8-vs-f32 logloss delta is a HARD parity pin
(`--parity-tol-logloss`): quantization that moves holdout logloss more
than the tolerance fails the run whether or not --smoke is set — speed
that costs accuracy is a regression, not a win (docs/serving.md
"Quantized artifacts").

`--sharded` switches to the sharded-placement bench (docs/serving.md
"Sharded serving"): ONE model served single-device and NamedSharding-
striped over every (batch, model) mesh shape the host's devices admit,
driven by interleaved paired trials over one shared pre-parsed pool —
throughput/p50/p99 per placement with deltas vs single-device at EQUAL
model, a hard score-parity pin across placements, and the
models-bigger-than-one-device demonstration: under a simulated
device_byte_budget the single-device load must REFUSE
(ModelExceedsDeviceBudget) while the sharded placement serves the same
artifact within budget. --smoke additionally gates zero steady-state
recompiles on every placement (tier-1 gate in scripts/test.sh).

`--skew` switches to the Zipfian hot-row workload (docs/serving.md "Score
caching & coalescing"): one model deploys cache-on and cache-off into a
registry, per-trial fresh pinned-Zipf request streams drive both arms in
interleaved paired trials through ``registry.submit`` (the batcher front
the cache lives on), and the BENCH JSON reports effective rows/sec per
arm, the paired speedup, and the measured hit ratio — with hard gates on
the speedup floor, the hit-ratio floor, cached == computed BIT-parity at
every precision (f32/bf16/int8), a mid-bench hot-swap that must fail zero
requests and never label an old version's score with the new version, and
zero steady-state recompiles. ``--smoke`` is tier-1 gate 10.

`--topk` switches to the top-K retrieval bench (docs/serving.md "Top-K
retrieval"): one MF model is trained, frozen WITH a signed-random-
projection index (freeze(retrieval_index=...)) and served through a
RetrievalEngine; interleaved paired trials report exact and LSH-pruned
queries/sec over the blocked-streamed catalog. Hard gates, smoke or not:
the blocked merge must be BIT-identical (ids and f32 scores) to a
stable argsort over the materialized catalog scores, pruned recall@K
must hold ``--recall-floor`` (the recall/candidate-fraction/speedup
trade is reported), and sharded catalogs (model-axis stripes, >= 2
devices) must reproduce single-device scores within
``--parity-tol-score``. ``--smoke`` additionally gates zero
steady-state recompiles and a non-vacuous pruned path — tier-1 gate 11
in scripts/test.sh.

`--overload` switches to the overload sweep (docs/serving.md "Overload
behavior"): a closed-loop calibration pins the saturation throughput,
then stepped open-loop offered load (0.25x .. 2x saturation) drives
POST /predict through real persistent sockets with a production-shaped
priority mix (20% high / 60% normal / 20% low via ``x-priority``) and
per-class ``x-deadline-ms`` budgets. Recorded per step: offered vs
achieved rate, goodput (200s/sec), per-priority p50/p99 from the
SCHEDULED arrival (coordinated-omission-free), and per-priority
shed/expiry/quota-reject counts. Hard gates: goodput at 2x saturation
must stay >= 0.8x peak goodput (degradation must be flat, never a
collapse), the server-side admission counters must be consistent with
the client-observed outcomes (accepted == 200s + sheds + expiries;
quota rejects == quota 503s; zero transport errors), and the sweep must
run with zero steady-state recompiles. Full (non-smoke) runs
additionally gate high-priority p99 at 2x overload <= 2x its light-load
p99 — the priority classes must actually protect the high class.
``--smoke`` is tier-1 gate 7 in scripts/test.sh.

`--slo` reuses the overload ladder as an end-to-end alerting gate
(docs/observability.md "SLOs & burn rates"): the time-series sampler
(runtime/timeseries.py) and SLO engine (runtime/slo.py) run live on the
process singletons while light -> 2x-saturation -> recovery phases drive
POST /predict, so ``GET /slo``, the SLO-aware ``/healthz`` and
``GET /debug/bundle`` are exercised mid-incident over real sockets. Hard
gates: the latency burn-rate alert must FIRE (reach ``page``) during the
2x step and CLEAR after recovery, must NOT fire at light load, the
sampler must cost < 5% of wall time, the mid-overload flight-recorder
bundle must carry every section (models, metrics, time series, SLO
state, traces, recompile attributions), and the ladder must run with
zero steady-state recompiles. ``--smoke`` is tier-1 gate 13 in
scripts/test.sh.

Every mode records the ``device_set`` it actually measured on (platform,
device count, device kinds, process count — plus the mesh shapes a
sharded run used), the bench.py discipline since PR 6: a round that fell
back to CPU or got fewer devices than expected stays attributable from
the BENCH JSON alone.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

sys.path.insert(0, ".")  # noqa: E402 — runnable as scripts/bench_serving.py

from hivemall_tpu.runtime.metrics import REGISTRY  # noqa: E402
from hivemall_tpu.runtime.tracing import TRACER  # noqa: E402
from hivemall_tpu.serving import (DynamicBatcher, ServingEngine,  # noqa: E402
                                  load)

# the stage vocabulary a request trace must cover for the bench artifact to
# be attribution-grade (server root, queue wait, pad, device dispatch/block)
REQUIRED_STAGES = {"server.predict", "queue.wait", "engine.pad",
                   "engine.dispatch", "engine.block"}


def _device_set(extra=None):
    """The device set this run ACTUALLY measured on — recorded in every
    BENCH JSON line (the bench.py shape since PR 6) so a degraded round
    (CPU fallback, fewer simulated devices than the gate expects) is
    diagnosable from the artifact alone."""
    import jax

    ds = {
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "device_kinds": sorted({d.device_kind for d in jax.devices()}),
    }
    if extra:
        ds.update(extra)
    return ds


def _recompile_counters():
    """Final ``graftcheck.recompiles.<guard>`` counter values, recorded
    next to device_set in every BENCH JSON line: the artifact's own proof
    the zero-recompile contract held (or exactly which guarded engine
    retraced, and how often) — the dynamic end of the static G032-G036
    traceflow rules."""
    return {k.split("graftcheck.recompiles.", 1)[1]: v
            for k, v in REGISTRY.snapshot().items()
            if k.startswith("graftcheck.recompiles.")}


def trace_report(trace_path):
    """Export the tracer ring to `trace_path` (Chrome/Perfetto JSON) and
    return the BENCH-JSON tracing block: per-stage time breakdown + the
    top-5 slowest traces — a p99 regression is attributable from the
    artifact alone, no re-run needed."""
    doc = TRACER.export_chrome(trace_path)
    stage_names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    return {
        "trace_file": trace_path,
        "traces_committed": doc["otherData"]["traces"],
        "distinct_stages": sorted(stage_names),
        "stage_breakdown_ms": TRACER.stage_breakdown(),
        "slowest_traces": TRACER.slowest(5),
    }, stage_names


def _train_default(dims: int, n_rows: int, seed: int = 7):
    from hivemall_tpu.models.classifier import train_arow

    rng = np.random.RandomState(seed)
    rows = [[f"{rng.randint(dims)}:{rng.rand():.3f}"
             for _ in range(rng.randint(4, 14))] for _ in range(n_rows)]
    labels = rng.choice([-1, 1], n_rows)
    return train_arow(rows, labels, f"-dims {dims}"), rows


def _request_pool(rows, n_requests: int, k: int, seed: int = 13):
    rng = np.random.RandomState(seed)
    pool = []
    for _ in range(n_requests):
        take = rng.randint(1, k + 1)
        idx = rng.randint(0, len(rows), take)
        pool.append([rows[i] for i in idx])
    return pool


def _percentiles(lat_s):
    lat_ms = np.asarray(lat_s) * 1000.0
    return {p: float(np.percentile(lat_ms, p)) for p in (50, 95, 99)}


def _planted_weights(dims: int, seed: int = 5) -> np.ndarray:
    return np.random.RandomState(seed).randn(dims).astype(np.float32)


def _planted_rows(w_true: np.ndarray, n_rows: int, seed: int,
                  noise: float = 0.5, nnz=(4, 14)):
    """Pre-parsed rows + labels from a planted linear model: labels carry
    real signal, so holdout logloss/AUC measure what quantization actually
    costs (random labels would pin every precision at logloss ~0.69 and
    hide it). Rows come back in the models.base ``(idx_rows, val_rows)``
    pre-parsed convention — training, the request pool, and the holdout
    all skip the "i:v" string round-trip, so what the trials price is
    table gathers, not tokenization."""
    dims = w_true.shape[0]
    rng = np.random.RandomState(seed)
    idx_rows, val_rows, labels = [], [], []
    for _ in range(n_rows):
        k = rng.randint(nnz[0], nnz[1])
        idx = rng.randint(0, dims, k).astype(np.int64)
        val = rng.rand(k).astype(np.float32)
        margin = float(np.sum(w_true[idx] * val))
        labels.append(1 if margin + noise * rng.randn() > 0 else -1)
        idx_rows.append(idx)
        val_rows.append(val)
    return (idx_rows, val_rows), labels


def _preparsed_pool(rows, n_requests: int, k: int, seed: int = 13):
    """Requests sampled from pre-parsed rows, each in the engine's flat
    ``(flat_idx, flat_val, lens)`` packed form — the request arrives
    ready to stage, so the trials price staging + table gathers, never
    per-row Python overhead."""
    idx_rows, val_rows = rows
    rng = np.random.RandomState(seed)
    pool = []
    for _ in range(n_requests):
        take = rng.randint(1, k + 1)
        sel = rng.randint(0, len(idx_rows), take)
        pool.append((np.concatenate([idx_rows[i] for i in sel]),
                     np.concatenate([val_rows[i] for i in sel]),
                     np.fromiter((len(idx_rows[i]) for i in sel),
                                 np.int64, count=take)))
    return pool


def _drive_closed_loop(eng, pool, concurrency: int):
    """Drain the request pool through ``eng.predict`` with ``concurrency``
    closed-loop driver threads. Returns (wall_seconds, per-request
    latencies). Concurrency is part of the measurement, not just load:
    serving hosts run hot, and it is exactly under memory pressure that a
    4x-smaller weight table keeps its rows cached while the f32 table
    thrashes — single-threaded trials systematically understate what
    quantization buys a loaded server."""
    lats: list = []
    lock = threading.Lock()

    def worker(shard):
        local = []
        for req in shard:
            r0 = time.perf_counter()
            eng.predict(req)
            local.append(time.perf_counter() - r0)
        with lock:
            lats.extend(local)

    shards = [pool[i::concurrency] for i in range(concurrency)]
    threads = [threading.Thread(target=worker, args=(s,))
               for s in shards if s]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, lats


# the three serving precisions the parity bench compares, in the fixed
# reference order (trial t rotates the EXECUTION order by t, so every
# precision runs first equally often — host-load drift cancels in the
# per-trial ratios)
QUANT_PRECISIONS = ("float32", "bfloat16", "int8")
_QUANT_FREEZE_ARG = {"float32": None, "bfloat16": "bf16", "int8": "int8"}


def run_quantize_mode(args) -> int:
    """Paired-trial f32 / bf16 / int8 parity bench on one frozen model.

    The same trained AROW model freezes three ways; the same pre-parsed
    request pool drives all three engines in interleaved paired trials,
    each trial a concurrent closed loop (_drive_closed_loop) — wide rows
    against a table sized past cache, because table bandwidth is the
    quantity the precisions change. Hard gates: the int8 holdout logloss
    must sit within --parity-tol-logloss of f32 (always — a parity break
    fails the run even without --smoke), and under --smoke every precision
    must additionally show zero steady-state recompiles across the whole
    trial sweep.
    """
    import os
    import tempfile

    from hivemall_tpu.evaluation.metrics import auc, logloss
    from hivemall_tpu.models.classifier import train_arow
    from hivemall_tpu.serving import freeze

    nnz = (4, 14) if args.smoke else (16, args.max_width + 1)
    w_true = _planted_weights(args.dims)
    train_rows, train_labels = _planted_rows(w_true, args.train_rows,
                                             seed=7, nnz=nnz)
    hold_rows, hold_labels = _planted_rows(w_true, args.holdout, seed=99,
                                           nnz=nnz)
    t0 = time.perf_counter()
    model = train_arow(train_rows, train_labels, f"-dims {args.dims}")
    train_s = time.perf_counter() - t0

    tmp = tempfile.mkdtemp(prefix="hivemall_quant_bench_")
    engines, disk_bytes, warm = {}, {}, {}
    for prec in QUANT_PRECISIONS:
        path = os.path.join(tmp, prec)
        freeze(model, path, name=f"qbench_{prec}", version="1",
               quantize=_QUANT_FREEZE_ARG[prec])
        disk_bytes[prec] = sum(
            os.path.getsize(os.path.join(path, f)) for f in os.listdir(path))
        eng = ServingEngine(load(path), name=f"qbench_{prec}",
                            max_batch=args.max_batch,
                            max_width=args.max_width)
        t0 = time.perf_counter()
        compiles = eng.warmup()
        warm[prec] = {"compiles": int(compiles),
                      "seconds": round(time.perf_counter() - t0, 3)}
        engines[prec] = eng

    # holdout quality per precision: the margin through a sigmoid is the
    # probability logloss scores; AUC ranks the raw margins
    quality = {}
    for prec, eng in engines.items():
        scores = np.asarray(eng.predict(hold_rows), np.float32)
        prob = 1.0 / (1.0 + np.exp(-scores))
        quality[prec] = {"logloss": float(logloss(prob, hold_labels)),
                         "auc": float(auc(scores, hold_labels))}

    # interleaved paired trials over ONE shared pre-parsed request pool,
    # each trial a concurrent closed loop — see _drive_closed_loop for why
    # concurrency is part of the measurement
    pool = _preparsed_pool(train_rows, args.requests,
                           args.instances_per_request)
    total_rows = sum(len(r[2]) for r in pool)  # r = (flat_i, flat_v, lens)
    total_nnz = sum(int(np.sum(r[2])) for r in pool)
    guards = {p: REGISTRY.counter("graftcheck",
                                  f"recompiles.serving.qbench_{p}")
              for p in QUANT_PRECISIONS}
    recompiles0 = {p: guards[p].value for p in QUANT_PRECISIONS}
    trials = {p: [] for p in QUANT_PRECISIONS}
    lats = {p: [] for p in QUANT_PRECISIONS}
    for t in range(args.quant_trials):
        rot = t % len(QUANT_PRECISIONS)
        for prec in QUANT_PRECISIONS[rot:] + QUANT_PRECISIONS[:rot]:
            wall, trial_lats = _drive_closed_loop(engines[prec], pool,
                                                  args.concurrency)
            lats[prec].extend(trial_lats)
            trials[prec].append(total_rows / wall)
    steady = {p: int(guards[p].value - recompiles0[p])
              for p in QUANT_PRECISIONS}

    def paired_ratio(prec):
        return float(np.median(np.asarray(trials[prec])
                               / np.asarray(trials["float32"])))

    pcts = {p: _percentiles(lats[p]) for p in QUANT_PRECISIONS}
    precisions_block = {
        p: {
            "throughput_rows_per_sec": round(float(np.median(trials[p])), 1),
            "p50_ms": round(pcts[p][50], 3),
            "p99_ms": round(pcts[p][99], 3),
            "artifact_bytes": int(disk_bytes[p]),
            "resident_table_bytes": int(engines[p].table_bytes),
            "weights_dtype": engines[p].weights_dtype,
            "steady_state_recompiles": steady[p],
            "warmup": warm[p],
            "holdout_logloss": round(quality[p]["logloss"], 6),
            "holdout_auc": round(quality[p]["auc"], 6),
        } for p in QUANT_PRECISIONS
    }
    deltas = {
        p: {
            "throughput_x": round(paired_ratio(p), 3),
            "p50_ms": round(pcts[p][50] - pcts["float32"][50], 3),
            "p99_ms": round(pcts[p][99] - pcts["float32"][99], 3),
            "logloss": round(quality[p]["logloss"]
                             - quality["float32"]["logloss"], 6),
            "auc": round(quality[p]["auc"] - quality["float32"]["auc"], 6),
            "artifact_bytes_x": round(disk_bytes[p]
                                      / max(1, disk_bytes["float32"]), 3),
            "resident_table_bytes_x": round(
                engines[p].table_bytes
                / max(1, engines["float32"].table_bytes), 3),
        } for p in ("bfloat16", "int8")
    }
    int8_delta = abs(deltas["int8"]["logloss"])
    bf16_delta = abs(deltas["bfloat16"]["logloss"])
    parity_ok = (int8_delta <= args.parity_tol_logloss
                 and bf16_delta <= args.parity_tol_logloss)
    # structured methodology, the bench.py shape since PR 14: `name` keeps
    # the historical string, the structured fields make serving rounds
    # comparable to training's regime-labeled rows
    meth = {"name": "interleaved_paired_trials_closed_loop_engine",
            "execution_backend": "serving_engine",
            "dims": int(args.dims),
            "concurrency": int(args.concurrency)}
    result = {
        "metric": f"serving_int8_throughput_vs_f32_arow_{args.dims}dims",
        "value": deltas["int8"]["throughput_x"],
        "unit": "x",
        "methodology": meth,
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "trials": int(args.quant_trials),
        "concurrency": int(args.concurrency),
        "requests_per_trial": len(pool),
        "rows_per_trial": int(total_rows),
        "nnz_per_trial": int(total_nnz),
        "train": {"rows": len(train_rows[0]), "seconds": round(train_s, 3)},
        "holdout_rows": len(hold_rows[0]),
        "precisions": precisions_block,
        "deltas_vs_f32": deltas,
        "parity": {
            "tolerance_logloss": args.parity_tol_logloss,
            "int8_logloss_delta": round(int8_delta, 6),
            "bf16_logloss_delta": round(bf16_delta, 6),
            "ok": parity_ok,
        },
    }
    # the serving-side cache-pressure number as a STANDING metric (the
    # ROADMAP raw-speed front (e)): at the full 2^24-dim shape the f32
    # weight table (64 MB) is past any cache this fleet runs on, so the
    # int8-vs-f32 ratio prices exactly what resident-table bytes buy a
    # loaded server — recorded as a regime-labeled row riding the same
    # structured-methodology block as training's cache_pressure rows
    cache_pressure_dims = 1 << 24
    if args.dims == cache_pressure_dims:
        result["extra_metrics"] = [{
            "metric": "serving_int8_throughput_vs_f32_arow_2^24dims",
            "regime": "cache_pressure",
            "value": deltas["int8"]["throughput_x"],
            "unit": "x",
            "methodology": {**meth, "regime": "cache_pressure",
                            "resident_tables": "int8_vs_f32"},
            "int8_rows_per_sec":
                precisions_block["int8"]["throughput_rows_per_sec"],
            "f32_rows_per_sec":
                precisions_block["float32"]["throughput_rows_per_sec"],
            "int8_resident_table_bytes":
                precisions_block["int8"]["resident_table_bytes"],
            "f32_resident_table_bytes":
                precisions_block["float32"]["resident_table_bytes"],
            "int8_p99_delta_ms": deltas["int8"]["p99_ms"],
        }]
    else:
        # the smoke shape is parity-gate-sized, not bandwidth-sized; say
        # so instead of silently omitting the standing row
        # the standing row's name pins the regime — a run at any OTHER
        # dims (smoke's tiny shape, an operator's 2^25 experiment) says
        # so instead of mislabeling its measurement as the 2^24 regime
        result["cache_pressure"] = {
            "skipped": f"dims {args.dims} != 2^24 — the standing "
                       "cache-pressure metric rides the full --quantize "
                       "run at its default shape"}
    print(json.dumps(result))

    if not parity_ok:
        # parity is a hard pin with or without --smoke: quantization that
        # moves holdout logloss past the tolerance is a regression
        print(f"PARITY FAIL: int8 logloss delta {int8_delta:.6f} / bf16 "
              f"{bf16_delta:.6f} vs tolerance {args.parity_tol_logloss}",
              file=sys.stderr)
        return 1
    if args.smoke and any(steady.values()):
        print(f"SMOKE FAIL: steady_state_recompiles={steady}",
              file=sys.stderr)
        return 1
    return 0


def run_sharded_mode(args) -> int:
    """Sharded-placement bench: single-device vs NamedSharding servables.

    One AROW model (planted weights, pre-parsed pool — the quantize-bench
    methodology) serves through a single-device engine and through a
    model-sharded engine per admissible (batch, model) mesh shape; the
    SAME pool drives every placement in interleaved paired trials. Hard
    gates: sharded holdout scores must match single-device within
    tolerance on every mesh (always), the simulated-budget demo must show
    single-device REFUSING a model the sharded placement then serves, and
    under --smoke every placement must sweep the whole bucket mesh with
    zero steady-state recompiles.
    """
    import jax

    from hivemall_tpu.models.classifier import train_arow
    from hivemall_tpu.serving import (ModelExceedsDeviceBudget, ModelSharded,
                                      ServingEngine, SingleDevice,
                                      make_servable)

    ndev = jax.device_count()
    if ndev < 2:
        print(f"SHARDED FAIL: needs >= 2 devices, have {ndev} "
              f"(CPU runs force 8 via xla_force_host_platform_device_count)",
              file=sys.stderr)
        return 1
    mesh_shapes = [(1, m) for m in (2, 4) if m <= ndev]
    if ndev >= 4:
        mesh_shapes.append((2, 2))

    nnz = (4, 14) if args.smoke else (16, args.max_width + 1)
    w_true = _planted_weights(args.dims)
    train_rows, train_labels = _planted_rows(w_true, args.train_rows,
                                             seed=7, nnz=nnz)
    hold_rows, _ = _planted_rows(w_true, args.holdout, seed=99, nnz=nnz)
    t0 = time.perf_counter()
    model = train_arow(train_rows, train_labels, f"-dims {args.dims}")
    train_s = time.perf_counter() - t0

    def key_of(shape):
        return "single" if shape is None else f"mesh_{shape[0]}x{shape[1]}"

    placements = [None] + mesh_shapes
    engines, warm = {}, {}
    for shape in placements:
        key = key_of(shape)
        pl = None if shape is None else ModelSharded(shape[1],
                                                     batch_shards=shape[0])
        eng = ServingEngine(model, name=f"shard_{key}",
                            max_batch=args.max_batch,
                            max_width=args.max_width, placement=pl)
        t0 = time.perf_counter()
        compiles = eng.warmup()
        warm[key] = {"compiles": int(compiles),
                     "seconds": round(time.perf_counter() - t0, 3)}
        engines[key] = eng

    # score parity at EQUAL model: every placement must reproduce the
    # single-device scores (same staged arrays, same stripe math as
    # training — tests pin bit-identity on dyadic rows; random-valued
    # rows leave only reduction-order rounding)
    ref = np.asarray(engines["single"].predict(hold_rows), np.float32)
    scale = float(np.max(np.abs(ref))) or 1.0
    parity = {}
    for shape in mesh_shapes:
        out = np.asarray(engines[key_of(shape)].predict(hold_rows),
                         np.float32)
        parity[key_of(shape)] = float(np.max(np.abs(out - ref)) / scale)
    parity_ok = all(v <= args.parity_tol_score for v in parity.values())

    # interleaved paired trials over ONE shared pre-parsed pool
    pool = _preparsed_pool(train_rows, args.requests,
                           args.instances_per_request)
    total_rows = sum(len(r[2]) for r in pool)
    guards = {k: REGISTRY.counter("graftcheck",
                                  f"recompiles.serving.shard_{k}")
              for k in engines}
    recompiles0 = {k: guards[k].value for k in engines}
    keys = [key_of(s) for s in placements]
    trials = {k: [] for k in keys}
    lats = {k: [] for k in keys}
    for t in range(args.quant_trials):
        rot = t % len(keys)
        for k in keys[rot:] + keys[:rot]:
            wall, trial_lats = _drive_closed_loop(engines[k], pool,
                                                  args.concurrency)
            lats[k].extend(trial_lats)
            trials[k].append(total_rows / wall)
    steady = {k: int(guards[k].value - recompiles0[k]) for k in engines}

    # the models-bigger-than-one-device demo: a budget below the table
    # bytes must refuse single-device and serve sharded — per-device
    # bytes are what sharding divides
    budget = engines["single"].table_bytes // 2
    max_shards = max(m for _, m in mesh_shapes)
    budget_block = {"budget_bytes": int(budget),
                    "table_bytes": int(engines["single"].table_bytes),
                    "single_device_refused": False, "sharded_served": False}
    try:
        make_servable(model, placement=SingleDevice(
            device_byte_budget=budget))
    except ModelExceedsDeviceBudget:
        budget_block["single_device_refused"] = True
    try:
        eng_b = ServingEngine(model, name="shard_budget",
                              max_batch=args.max_batch,
                              max_width=args.max_width,
                              placement=ModelSharded(
                                  max_shards, device_byte_budget=budget))
        eng_b.warmup()
        n_scored = len(eng_b.predict(hold_rows))
        budget_block["sharded_served"] = n_scored == len(hold_rows[0])
        budget_block["per_device_bytes"] = int(eng_b.per_device_table_bytes)
        budget_block["model_shards"] = int(max_shards)
    except ModelExceedsDeviceBudget as e:
        budget_block["error"] = str(e)
    budget_ok = (budget_block["single_device_refused"]
                 and budget_block["sharded_served"])

    pcts = {k: _percentiles(lats[k]) for k in keys}

    def paired_ratio(k):
        return float(np.median(np.asarray(trials[k])
                               / np.asarray(trials["single"])))

    placements_block = {
        k: {
            "throughput_rows_per_sec": round(float(np.median(trials[k])), 1),
            "p50_ms": round(pcts[k][50], 3),
            "p99_ms": round(pcts[k][99], 3),
            "steady_state_recompiles": steady[k],
            "warmup": warm[k],
            "placement": engines[k].placement,
            "per_device_table_bytes": int(engines[k].per_device_table_bytes),
        } for k in keys
    }
    deltas = {
        k: {
            "throughput_x": round(paired_ratio(k), 3),
            "p50_ms": round(pcts[k][50] - pcts["single"][50], 3),
            "p99_ms": round(pcts[k][99] - pcts["single"][99], 3),
            "max_rel_score_delta": parity[k],
        } for k in keys if k != "single"
    }
    best = max(deltas, key=lambda k: deltas[k]["throughput_x"])
    result = {
        "metric": f"serving_sharded_throughput_vs_single_arow_"
                  f"{args.dims}dims",
        "value": deltas[best]["throughput_x"],
        "unit": "x",
        "methodology": "interleaved_paired_trials_closed_loop_engine",
        "device_set": _device_set(
            {"mesh_shapes": [list(s) for s in mesh_shapes]}),
        "recompiles": _recompile_counters(),
        "trials": int(args.quant_trials),
        "concurrency": int(args.concurrency),
        "requests_per_trial": len(pool),
        "rows_per_trial": int(total_rows),
        "train": {"rows": len(train_rows[0]), "seconds": round(train_s, 3)},
        "holdout_rows": len(hold_rows[0]),
        "best_mesh": best,
        "placements": placements_block,
        "deltas_vs_single": deltas,
        "exceeds_single_device": budget_block,
        "parity": {"tolerance_rel_score": args.parity_tol_score,
                   "max_rel_score_delta": max(parity.values()),
                   "ok": parity_ok},
    }
    print(json.dumps(result))

    if not parity_ok:
        print(f"PARITY FAIL: sharded scores drift {parity} past "
              f"{args.parity_tol_score} of single-device", file=sys.stderr)
        return 1
    if not budget_ok:
        print(f"BUDGET FAIL: {budget_block}", file=sys.stderr)
        return 1
    if args.smoke and any(steady.values()):
        print(f"SMOKE FAIL: steady_state_recompiles={steady}",
              file=sys.stderr)
        return 1
    return 0


def run_topk_mode(args) -> int:
    """Top-K retrieval bench: queries/sec against a blocked-streamed MF
    catalog (serving/retrieval.py — docs/serving.md "Top-K retrieval"),
    with the subsystem's correctness pins gated alongside the number:

    - **exact parity** (hard gate, always): the blocked streamed merge
      must be BIT-identical — ids and f32 scores — to a stable argsort
      over the materialized catalog scores. ``score_catalog`` shares the
      block score expression with the merge, so any drift here is merge
      logic, not arithmetic;
    - **pruned recall@K** (hard gate, always): the signed-random-
      projection probe (index built at freeze time into the artifact)
      must keep mean recall@K vs exact scoring >= ``--recall-floor``,
      with the recall / candidate-fraction / speedup trade reported —
      the AdaBatch-style gate: pruning that loses more recall than the
      pin is a regression whether or not it is faster;
    - **sharded score parity** (hard gate when >= 2 devices): the
      model-axis-striped catalog must reproduce single-device top-K
      scores within ``--parity-tol-score`` at equal model (the
      cross-stripe merge may permute equal-score ties, so scores gate
      and id agreement is reported);
    - **zero steady-state recompiles** (hard gate under --smoke): after
      warmup, the whole sweep — exact and probed, every batch and
      candidate bucket — leaves the recompile counters flat, and at
      least one probed query must actually take the pruned path (a
      100%-fallback run would gate recall vacuously).

    ``--smoke`` is tier-1 gate 11 in scripts/test.sh.
    """
    import os
    import tempfile

    import jax

    from hivemall_tpu.models.mf import train_mf_sgd
    from hivemall_tpu.serving import ModelSharded, RetrievalEngine
    from hivemall_tpu.serving.artifact import freeze

    n_items = args.catalog_items
    k = args.topk_k
    n_users = min(1024, max(16, n_items // 8))
    rng = np.random.RandomState(11)
    n_r = args.train_rows
    u = rng.randint(0, n_users, n_r)
    it = rng.randint(0, n_items, n_r)
    rat = rng.rand(n_r) * 4 + 1
    u[-1], it[-1] = n_users - 1, n_items - 1  # pin the table shapes
    t0 = time.perf_counter()
    model = train_mf_sgd(u, it, rat,
                         f"-factor {args.mf_factor} -iter 2 -disable_cv")
    train_s = time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as td:
        # freeze -> load: the bench measures the artifact path — the LSH
        # index rides the manifest, exactly what production serves
        art_dir = os.path.join(td, "mf", "1")
        freeze(model, art_dir,
               retrieval_index={"planes": args.lsh_planes, "seed": 0})
        art = load(art_dir)
        # candidate cap sized from the probe's expected union: 1+planes
        # Hamming<=1 buckets of ~n/2^planes items each, doubled for
        # bucket skew (the engine pow2-rounds)
        expected_cand = int(n_items * (1 + args.lsh_planes)
                            / (1 << args.lsh_planes))
        cand_cap = max(64, 2 * expected_cand)
        geom = dict(k=k, block_items=args.topk_block_items,
                    max_batch=args.max_batch, candidate_cap=cand_cap)
        eng = RetrievalEngine(art, name="topk_bench", **geom)
        t0 = time.perf_counter()
        warm_compiles = eng.warmup()
        warm_s = time.perf_counter() - t0

        qrng = np.random.RandomState(23)
        qs = qrng.randint(0, n_users, args.topk_queries).tolist()
        guard = REGISTRY.counter("graftcheck",
                                 "recompiles.serving.topk_bench.topk")
        recompiles0 = guard.value

        # -- exact parity pin: blocked merge == stable argsort, bit for bit
        n_par = min(len(qs), max(8, args.max_batch))
        par_q = qs[:n_par]
        res_exact_par = eng.topk(par_q, probe=False)
        scores = eng.score_catalog(par_q)  # [n_par, n_items] f32
        bit_exact = True
        for row, res in zip(scores, res_exact_par):
            order = np.argsort(-row, kind="stable")[:k]
            if not (np.array_equal(np.asarray(res["items"], np.int64),
                                   order)
                    and np.array_equal(
                        np.asarray(res["scores"], np.float32),
                        row[order])):
                bit_exact = False
                break

        # -- pruned recall@K vs exact, fallbacks and candidate volume
        p0 = REGISTRY.counter("retrieval", "topk_bench.probed").value
        f0 = REGISTRY.counter("retrieval", "topk_bench.fallback").value
        c0 = REGISTRY.counter("retrieval", "topk_bench.candidates").value
        res_probe = eng.topk(qs, probe=True)
        res_exact = eng.topk(qs, probe=False)
        probed = int(REGISTRY.counter("retrieval",
                                      "topk_bench.probed").value - p0)
        fallbacks = int(REGISTRY.counter("retrieval",
                                         "topk_bench.fallback").value - f0)
        cands = int(REGISTRY.counter("retrieval",
                                     "topk_bench.candidates").value - c0)
        recalls = [len(set(p["items"]) & set(e["items"])) / len(e["items"])
                   for p, e in zip(res_probe, res_exact)]
        recall = float(np.mean(recalls))
        avg_cand = cands / probed if probed else 0.0

        # -- throughput: interleaved paired exact/probed trials
        rows_exact = [(q, None, False) for q in qs]
        rows_probe = [(q, None, True) for q in qs]
        exact_qps, probe_qps = [], []
        for _ in range(args.quant_trials):
            t0 = time.perf_counter()
            eng.topk_batch(rows_exact)
            exact_qps.append(len(qs) / (time.perf_counter() - t0))
            t0 = time.perf_counter()
            eng.topk_batch(rows_probe)
            probe_qps.append(len(qs) / (time.perf_counter() - t0))
        steady = int(guard.value - recompiles0)

        # -- sharded catalog: score parity with single-device at equal model
        ndev = jax.device_count()
        shard_counts = [m for m in (2, 4) if m <= ndev]
        sharded_block, sharded_ok = {}, True
        for m in shard_counts:
            eng_sh = RetrievalEngine(art, name=f"topk_sh{m}",
                                     placement=ModelSharded(m), **geom)
            eng_sh.warmup()
            g_sh = REGISTRY.counter(
                "graftcheck", f"recompiles.serving.topk_sh{m}.topk")
            r_sh0 = g_sh.value
            res_sh = eng_sh.topk(par_q, probe=False)
            max_rel, ids_equal = 0.0, True
            for a, b in zip(res_sh, res_exact_par):
                va = np.asarray(a["scores"], np.float32)
                vb = np.asarray(b["scores"], np.float32)
                scale = float(np.max(np.abs(vb))) or 1.0
                max_rel = max(max_rel,
                              float(np.max(np.abs(va - vb))) / scale)
                ids_equal = ids_equal and a["items"] == b["items"]
            ok = max_rel <= args.parity_tol_score
            sharded_ok = sharded_ok and ok
            sharded_block[f"shards_{m}"] = {
                "max_rel_score_delta": max_rel, "ids_equal": ids_equal,
                "steady_state_recompiles": int(g_sh.value - r_sh0),
                "ok": ok}

    exact_med = float(np.median(exact_qps))
    probe_med = float(np.median(probe_qps))
    result = {
        "metric": f"serving_topk_qps_mf_{n_items}items",
        "value": round(exact_med, 1),
        "unit": "queries/s",
        "methodology": "in_process_engine_interleaved_paired_trials",
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "catalog_items": int(n_items),
        "k": int(k),
        "factor": int(args.mf_factor),
        "block_items": int(args.topk_block_items),
        "queries": len(qs),
        "trials": int(args.quant_trials),
        "train": {"ratings": int(n_r), "users": int(n_users),
                  "seconds": round(train_s, 3)},
        "warmup": {"compiles": int(warm_compiles),
                   "seconds": round(warm_s, 3)},
        "steady_state_recompiles": steady,
        "exact": {
            "qps": round(exact_med, 1),
            "items_scored_per_sec": round(exact_med * n_items, 0),
            "bit_exact_vs_argsort": bit_exact,
            "parity_queries": int(n_par),
        },
        "pruned": {
            "qps": round(probe_med, 1),
            "speedup_x": round(probe_med / exact_med, 3) if exact_med
            else 0.0,
            "recall_at_k": round(recall, 4),
            "recall_floor": args.recall_floor,
            "planes": int(args.lsh_planes),
            "candidate_cap": int(cand_cap),
            "avg_candidates": round(avg_cand, 1),
            "candidate_fraction": round(avg_cand / n_items, 4),
            "probed": probed,
            "fallbacks": fallbacks,
        },
        "sharded": sharded_block
        or {"skipped": f"{ndev} device(s) — needs >= 2"},
    }
    print(json.dumps(result))

    if not bit_exact:
        print("PARITY FAIL: blocked top-K is not bit-identical to the "
              "stable-argsort baseline", file=sys.stderr)
        return 1
    if recall < args.recall_floor:
        print(f"RECALL FAIL: pruned recall@{k} {recall:.4f} below the "
              f"{args.recall_floor} floor", file=sys.stderr)
        return 1
    if not sharded_ok:
        print(f"SHARDED PARITY FAIL: {sharded_block}", file=sys.stderr)
        return 1
    if args.smoke and probed == 0:
        print("SMOKE FAIL: no query took the pruned path — the recall "
              "gate ran vacuously (all fallbacks)", file=sys.stderr)
        return 1
    if args.smoke and (steady or any(
            b["steady_state_recompiles"] for b in sharded_block.values())):
        print(f"SMOKE FAIL: steady_state_recompiles={steady} "
              f"sharded={sharded_block}", file=sys.stderr)
        return 1
    return 0


# the overload sweep's arrival mix: high / normal / low fractions — the
# production shape (a thin interactive tier over bulk default traffic
# with a batch tail), so strict-priority drain and quota shedding both
# have work to act on
OVERLOAD_MIX = (0.2, 0.6, 0.2)


def _overload_step(port, bodies, classes, rate, deadlines_ms, workers,
                   timeout):
    """Open-loop arrivals at ``rate`` req/s over persistent HTTP/1.1
    connections (http.client — urllib burns an ephemeral port per
    request; a sweep would exhaust them). Request i is SCHEDULED at
    ``start + i/rate``; its latency is measured from the SEND (the
    server-attributable part) while the send's lateness vs the schedule
    is recorded alongside as slip — nothing is silently omitted, and a
    client that cannot hold the schedule is visible in the artifact
    instead of polluting the per-priority percentiles. Priority and
    deadline ride the ``x-priority`` / ``x-deadline-ms`` headers — the
    wire contract under test. Returns (records, wall): records are
    (class, status, reason, latency_s, slip_s)."""
    import http.client

    from hivemall_tpu.serving.admission import PRIORITY_NAMES

    n = len(bodies)
    period = 1.0 / rate
    counter = itertools.count()
    records: list = []
    lock = threading.Lock()
    start = time.perf_counter() + 0.05

    def worker():
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        local = []
        while True:
            i = next(counter)
            if i >= n:
                break
            sched = start + i * period
            now = time.perf_counter()
            if sched > now:
                time.sleep(sched - now)
            sent = time.perf_counter()  # slip = sent - sched (recorded)
            c = int(classes[i])
            try:
                conn.request(
                    "POST", "/predict", body=bodies[i],
                    headers={"Content-Type": "application/json",
                             "x-priority": PRIORITY_NAMES[c],
                             "x-deadline-ms": repr(deadlines_ms[c])})
                resp = conn.getresponse()
                data = resp.read()  # drain so the connection can be reused
                status = resp.status
                reason = ""
                if status in (503, 504):
                    # the structured "reason" field distinguishes the
                    # admission quota refusal from an in-queue shed, a
                    # deadline expiry, and the at-the-door concurrency
                    # refusal — cheap substring check, no JSON parse on
                    # the hot client path
                    for r in ("shed", "quota", "deadline", "concurrency"):
                        if f'"{r}"'.encode() in data:
                            reason = r
                            break
                    else:
                        reason = "other"
            except Exception:
                try:
                    conn.close()
                except Exception:
                    pass
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=timeout)
                status, reason = -1, "transport"
            local.append((c, status, reason, time.perf_counter() - sent,
                          sent - sched))
        try:
            conn.close()
        except Exception:
            pass
        with lock:
            records.extend(local)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records, time.perf_counter() - start


def run_overload_mode(args) -> int:
    """Goodput-vs-offered-load sweep: calibrate saturation, then step the
    offered rate from light load past 2x saturation and pin that goodput
    degrades FLAT (quotas + deadline shedding), never collapses.
    """
    # dozens of runnable threads (client workers + handler threads + the
    # batcher worker) convoy on the GIL at the default 5 ms switch
    # interval — worst-case rotation is threads * interval, which lands
    # straight in the p99. A 1 ms interval bounds the convoy; restored on
    # exit.
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        return _run_overload_mode(args)
    finally:
        sys.setswitchinterval(prev_switch)


def _run_overload_mode(args) -> int:
    from hivemall_tpu.serving import ModelRegistry
    from hivemall_tpu.serving.admission import PRIORITY_NAMES
    from hivemall_tpu.serving.server import serve

    model, rows = _train_default(args.dims, args.train_rows)
    registry = ModelRegistry(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        engine_kwargs={"max_batch": args.max_batch,
                       "max_width": args.max_width})
    registry.deploy("bench", model, version="1")
    server = serve(registry)
    port = server.server_address[1]

    # calibration: the SAME persistent-connection driver the sweep uses,
    # at an unattainable offered rate — the schedule is immediately
    # behind, so each worker runs back-to-back sends: a closed loop at
    # `concurrency` over the sockets the steps will reuse (urllib would
    # pay a TCP setup per request and understate the knee ~2x). Doubles
    # as HTTP-path warmup.
    calib_pool = _request_pool(rows, args.calib_requests,
                               args.instances_per_request)
    calib_bodies = [json.dumps({"model": "bench", "instances": req}).encode()
                    for req in calib_pool]
    calib_classes = np.ones(len(calib_bodies), dtype=int)  # all "normal"
    calib_deadlines = (1e4, 1e4, 1e4)  # effectively none: measure capacity
    recs, wall = _overload_step(port, calib_bodies, calib_classes,
                                rate=1e6, deadlines_ms=calib_deadlines,
                                workers=args.concurrency, timeout=60.0)
    served = sum(1 for r in recs if r[1] == 200)
    if not served:
        print(f"OVERLOAD FAIL: calibration served nothing "
              f"({recs[:3]})", file=sys.stderr)
        return 1
    burst_rps = len(recs) / wall
    mean_rows = sum(len(r) for r in calib_pool) / len(calib_pool)

    # saturation search: the burst closed loop overstates the SUSTAINABLE
    # rate (zero schedule overhead, a fixed worker set, perfectly full
    # batches) — the knee that matters is where an open-loop schedule
    # stops being met. Probe ascending rates with the sweep's own driver
    # until goodput falls under 90% of offered; the last rate that held
    # is the saturation anchor.
    probe_s = min(2.0, args.step_seconds / 2)
    rate_cap = burst_rps * 0.25
    probe = rate_cap
    probes = []
    while probe <= burst_rps * 1.25:
        attempts = 0
        while True:
            n = max(16, int(probe * probe_s))
            bodies = [calib_bodies[i % len(calib_bodies)]
                      for i in range(n)]
            recs, wall = _overload_step(
                port, bodies, np.ones(n, dtype=int), rate=probe,
                deadlines_ms=calib_deadlines,
                workers=int(min(args.max_workers, max(8, probe * 0.25))),
                timeout=60.0)
            good = sum(1 for r in recs if r[1] == 200) / wall
            probes.append({"offered_rps": round(probe, 1),
                           "goodput_rps": round(good, 1)})
            attempts += 1
            if good >= 0.9 * probe or attempts >= 2:
                break  # held, or failed twice (one noisy window is noise)
        if good < 0.9 * probe:
            break
        if attempts > 1:
            # passed only on the retry: borderline by definition — stop
            # the climb at the previous (cleanly-held) anchor instead of
            # anchoring the sweep on host-weather luck
            break
        rate_cap = probe
        probe *= 1.6

    # ladder pre-validation: the sweep's TOP step (2x knee) must be
    # transportable by the joint client+server system RIGHT NOW — host
    # speed on a shared box drifts between the probe and the sweep, and
    # a ladder anchored on a lucky quiet window would melt every step
    # into client slip instead of exercising admission. If 2x cannot be
    # carried, re-anchor saturation at half of what was.
    top = rate_cap * 2.0
    n = max(24, int(top * probe_s))
    recs, wall = _overload_step(
        port, [calib_bodies[i % len(calib_bodies)] for i in range(n)],
        np.ones(n, dtype=int), rate=top, deadlines_ms=calib_deadlines,
        workers=int(min(args.max_workers, max(8, top * 0.25))),
        timeout=60.0)
    achieved_top = len(recs) / wall
    probes.append({"offered_rps": round(top, 1), "validation": True,
                   "achieved_rps": round(achieved_top, 1)})
    if achieved_top < 0.8 * top:
        rate_cap = achieved_top / 2.0

    # admission posture sized from the measured capacity: the queue holds
    # ~queue_seconds of backlog (bounded staleness — an accepted request
    # drains well inside its deadline), low-priority work quota-sheds at
    # 60% fill, normal at 85%, and the AIMD controller may widen the
    # window toward its caps under the sustained steps. In-flight
    # handlers are bounded too (serve()'s max_concurrent_requests,
    # installed here once the queue size is known): past ~2 queues' worth
    # of concurrent requests the server refuses at the door, before the
    # parse — otherwise overload's OWN handler threads starve the batcher
    # worker of the CPU that is the service capacity. Deployed as v2 — an
    # in-flight swap that must fail zero requests, per the PR 3 contract.
    max_queue_rows = max(4 * args.max_batch,
                         int(rate_cap * mean_rows * args.queue_seconds))
    inflight_limit = max(12,
                         int(max_queue_rows / max(1.0, mean_rows)) + 4)
    server.inflight = threading.BoundedSemaphore(inflight_limit)
    server.inflight_reserve = threading.BoundedSemaphore(
        max(2, inflight_limit // 4))
    registry.deploy(
        "bench", model, version="2",
        batcher_overrides=dict(
            max_queue_rows=max_queue_rows,
            max_delay_ms_cap=args.max_delay_ms_cap,
            # the DELAY widens under load (fuller batches at moderate
            # rates); the batch cap stays at base — a wider dispatch
            # quantum here would tax exactly the head-of-line wait a
            # just-arrived high-priority request eats
            max_batch_cap=args.max_batch,
            priority_quota_fracs=(1.0, 0.85, 0.6)))

    # warm the freshly-deployed v2 stack (new batcher lanes, first-touch
    # costs) with a short closed-loop burst so the sweep's light-load
    # step measures steady state, not deploy transients
    n_warm = 4 * inflight_limit
    _overload_step(port, [calib_bodies[i % len(calib_bodies)]
                          for i in range(n_warm)],
                   np.ones(n_warm, dtype=int), rate=1e6,
                   deadlines_ms=calib_deadlines,
                   workers=args.concurrency, timeout=60.0)

    # GC discipline for the measured window (the production-server
    # recipe): JSON parsing churns ~1e5-1e6 acyclic objects/sec, and the
    # collector's gen2 passes over the whole heap stop every thread for
    # hundreds of ms — tails that would be charged to the admission
    # machinery. Freeze the warmed heap out of the collector's view and
    # leave reclamation to refcounting for the sweep; restored after.
    import gc

    gc.collect()
    gc.freeze()
    gc.disable()

    deadlines = (args.deadline_high_ms, args.deadline_normal_ms,
                 args.deadline_low_ms)
    fracs = (0.25, 1.0, 2.0) if args.smoke else (0.25, 0.5, 1.0, 1.5, 2.0)
    counters = {k: [REGISTRY.counter("serving", f"bench.batcher.{k}.{p}")
                    for p in PRIORITY_NAMES]
                for k in ("accepted", "quota_rejected", "shed", "expired")}
    base = {k: [c.value for c in cs] for k, cs in counters.items()}
    guard = REGISTRY.counter("graftcheck", "recompiles.serving.bench")
    recompiles0 = guard.value
    TRACER.clear()

    rng = np.random.RandomState(31)
    steps_out = []
    totals = {"ok": 0, "shed": 0, "quota": 0, "deadline": 0,
              "concurrency": 0, "errors": 0}
    for frac in fracs:
        rate = max(4.0, rate_cap * frac)
        n = max(40, int(rate * args.step_seconds))
        classes = rng.choice(len(PRIORITY_NAMES), n, p=OVERLOAD_MIX)
        bodies = [json.dumps(
            {"model": "bench",
             "instances": calib_pool[rng.randint(len(calib_pool))]}
        ).encode() for _ in range(n)]
        # enough blocking workers to sustain the schedule: rejects
        # return in single-digit ms and accepted work inside the short
        # bounded queue, so ~150 ms of in-flight requests covers the
        # worker pool — more threads would only thrash the GIL the server
        # shares with this in-process client
        workers = int(min(args.max_workers, max(8, rate * 0.4)))
        recs, wall = _overload_step(
            port, bodies, classes, rate, deadlines, workers,
            timeout=max(deadlines) / 1e3 + 10.0)
        ok = [r for r in recs if r[1] == 200]
        reasons = {r: sum(1 for x in recs if x[2] == r)
                   for r in ("shed", "quota", "deadline", "concurrency")}
        errors = sum(1 for r in recs if r[1] not in (200, 503, 504))
        slips = [r[4] * 1e3 for r in recs]
        per_cls = {}
        for c, pname in enumerate(PRIORITY_NAMES):
            ls = sorted(r[3] * 1e3 for r in ok if r[0] == c)
            per_cls[pname] = {
                "sent": int(np.sum(classes == c)), "ok": len(ls),
                "p50_ms": round(float(np.percentile(ls, 50)), 2)
                if ls else None,
                "p99_ms": round(float(np.percentile(ls, 99)), 2)
                if ls else None,
            }
        totals["ok"] += len(ok)
        totals["errors"] += errors
        for r in ("shed", "quota", "deadline", "concurrency"):
            totals[r] += reasons[r]
        steps_out.append({
            "offered_x": frac,
            "offered_rps": round(rate, 1),
            "achieved_rps": round(len(recs) / wall, 1),
            "goodput_rps": round(len(ok) / wall, 1),
            "ok": len(ok), "shed_503": reasons["shed"],
            "quota_503": reasons["quota"],
            "concurrency_503": reasons["concurrency"],
            "expired_504": reasons["deadline"], "errors": errors,
            "workers": workers,
            # schedule honesty: how late sends left the client — latency
            # percentiles are only attributable to the SERVER when the
            # slip stays small
            "arrival_slip_p99_ms": round(float(np.percentile(slips, 99)), 2),
            "by_priority": per_cls,
        })
    gc.enable()
    gc.unfreeze()
    gc.collect()
    steady_recompiles = int(guard.value - recompiles0)
    delta = {k: {p: int(cs[c].value - base[k][c])
                 for c, p in enumerate(PRIORITY_NAMES)}
             for k, cs in counters.items()}
    state = registry.get("bench").batcher.overload_state()
    # post-sweep capacity recheck (after the counter deltas, so its own
    # traffic stays out of the consistency identities): the sweep runs
    # minutes after calibration on a shared host whose speed drifts, so
    # goodput retention is ALSO evaluated against the contemporaneous
    # sustainable rate — a host that slowed mid-sweep must not read as a
    # server collapse, while a genuine queue collapse fails both (this
    # burst still measures high capacity when sweep goodput cratered)
    n = max(24, int(rate_cap * probe_s))
    recs, wall = _overload_step(
        port, [calib_bodies[i % len(calib_bodies)] for i in range(n)],
        np.ones(n, dtype=int), rate=1e6, deadlines_ms=calib_deadlines,
        workers=args.concurrency, timeout=60.0)
    post_burst_rps = len(recs) / wall
    knee_frac = rate_cap / burst_rps if burst_rps else 1.0
    sustainable_now = post_burst_rps * knee_frac
    tracing_block, _ = trace_report(args.trace_out
                                    or "serving_overload_trace.json")
    server.shutdown()
    registry.shutdown()

    # the three accounting identities that make the degradation auditable:
    # every accepted request resolved exactly one way (served, shed, or
    # expired), every quota refusal was a client-visible quota 503, and
    # nothing fell off the wire
    acc = sum(delta["accepted"].values())
    shed = sum(delta["shed"].values())
    exp = sum(delta["expired"].values())
    quota = sum(delta["quota_rejected"].values())
    consistency = {
        "accepted_vs_outcomes": {
            "accepted": acc, "ok": totals["ok"], "shed": shed,
            "expired": exp,
            "ok_": acc == totals["ok"] + shed + exp,
        },
        "quota_rejects_vs_503s": {
            "quota_rejected": quota, "quota_503": totals["quota"],
            "ok_": quota == totals["quota"],
        },
        "client_shed_vs_counters": {
            "shed_counter": shed, "shed_503": totals["shed"],
            "expired_counter": exp, "expired_504": totals["deadline"],
            "ok_": shed == totals["shed"] and exp == totals["deadline"],
        },
        # at-the-door refusals never reach the batcher: accounted on the
        # client side only (plus the serving.http.concurrency_rejected
        # counter), outside the accepted-vs-outcomes identity
        "concurrency_503": totals["concurrency"],
        "transport_errors": totals["errors"],
    }
    consistency_ok = (consistency["accepted_vs_outcomes"]["ok_"]
                      and consistency["quota_rejects_vs_503s"]["ok_"]
                      and consistency["client_shed_vs_counters"]["ok_"]
                      and totals["errors"] == 0)

    goodputs = [s["goodput_rps"] for s in steps_out]
    peak = max(goodputs)
    at_2x = steps_out[-1]["goodput_rps"]
    retention = at_2x / peak if peak else 0.0
    retention_now = at_2x / sustainable_now if sustainable_now else 0.0
    retention_eff = max(retention, retention_now)
    hi_light = steps_out[0]["by_priority"]["high"]["p99_ms"]
    hi_over = steps_out[-1]["by_priority"]["high"]["p99_ms"]
    hi_ratio = (hi_over / hi_light) if hi_light and hi_over else None
    # the protection bound: 2x the light-load p99, floored at the class's
    # own deadline SLO — on a host whose light-load p99 sits far below
    # the SLO, "stayed inside the latency contract under 2x overload" is
    # the meaningful guarantee, and the deadline is that contract
    hi_bound_ms = max(2.0 * hi_light, args.deadline_high_ms) \
        if hi_light else args.deadline_high_ms
    hi_protected = hi_over is not None and hi_over <= hi_bound_ms

    result = {
        "metric": f"serving_overload_goodput_retention_arow_"
                  f"{args.dims}dims",
        "value": round(retention, 3),
        "unit": "x",
        "methodology": "http_open_loop_stepped_offered_load",
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "calibration": {"burst_closed_loop_rps": round(burst_rps, 1),
                        "saturation_rps": round(rate_cap, 1),
                        "probes": probes,
                        "concurrency": int(args.concurrency),
                        "mean_rows_per_request": round(mean_rows, 1)},
        "admission": {"max_queue_rows": int(max_queue_rows),
                      "max_concurrent_requests": int(inflight_limit),
                      "queue_seconds": args.queue_seconds,
                      "quota_fracs": state["quota_fracs"],
                      "deadlines_ms": {p: deadlines[c] for c, p in
                                       enumerate(PRIORITY_NAMES)},
                      "mix": {p: OVERLOAD_MIX[c] for c, p in
                              enumerate(PRIORITY_NAMES)},
                      "controller": state["controller"],
                      "rows_per_sec": state["rows_per_sec"]},
        "steps": steps_out,
        "peak_goodput_rps": peak,
        "goodput_at_2x_rps": at_2x,
        "retention_x": round(retention, 3),
        "post_sweep": {"burst_rps": round(post_burst_rps, 1),
                       "knee_frac": round(knee_frac, 3),
                       "sustainable_rps": round(sustainable_now, 1),
                       "retention_vs_now_x": round(retention_now, 3),
                       "retention_effective_x": round(retention_eff, 3)},
        "high_priority_p99": {"light_ms": hi_light, "overload_ms": hi_over,
                              "ratio_x": round(hi_ratio, 3)
                              if hi_ratio else None,
                              "bound_ms": round(hi_bound_ms, 2),
                              "protected": hi_protected},
        "counters": delta,
        "consistency": consistency,
        "steady_state_recompiles": steady_recompiles,
        "tracing": tracing_block,
    }
    print(json.dumps(result))

    rc = 0
    if retention_eff < args.goodput_retention_min:
        print(f"OVERLOAD FAIL: goodput at 2x saturation is "
              f"{retention:.3f}x peak and {retention_now:.3f}x the "
              f"post-sweep sustainable rate (both < "
              f"{args.goodput_retention_min}x) — degradation collapsed "
              f"instead of flattening", file=sys.stderr)
        rc = 1
    if not consistency_ok:
        print(f"OVERLOAD FAIL: shed counters inconsistent with observed "
              f"outcomes: {json.dumps(consistency)}", file=sys.stderr)
        rc = 1
    if steady_recompiles:
        print(f"OVERLOAD FAIL: steady_state_recompiles="
              f"{steady_recompiles}", file=sys.stderr)
        rc = 1
    if not args.smoke and not hi_protected:
        # statistically meaningful only at full scale; smoke records it
        print(f"OVERLOAD FAIL: high-priority p99 at 2x overload is "
              f"{hi_over} ms, past max(2x light-load p99, class deadline) "
              f"= {hi_bound_ms:.1f} ms — the priority classes are not "
              f"protecting the high class", file=sys.stderr)
        rc = 1
    return rc


# -- slo mode: burn-rate alerting over the overload ladder -------------------

def run_slo_mode(args) -> int:
    """SLO burn-rate alert gate: drive the overload ladder (light -> 2x
    saturation -> recovery) with the time-series sampler + SLO engine
    live, and pin that the latency burn alert FIRES during the induced
    overload, CLEARS after recovery, the sampler stays under 5% overhead,
    and the mid-overload /debug/bundle is complete.
    """
    # same GIL posture as the overload sweep: dozens of runnable threads
    # convoy at the default 5 ms switch interval, straight into the p99
    prev_switch = sys.getswitchinterval()
    sys.setswitchinterval(0.001)
    try:
        return _run_slo_mode(args)
    finally:
        sys.setswitchinterval(prev_switch)


def _run_slo_mode(args) -> int:
    from hivemall_tpu.runtime import timeseries
    from hivemall_tpu.runtime.slo import ENGINE, SLO
    from hivemall_tpu.serving import ModelRegistry
    from hivemall_tpu.serving.admission import PRIORITY_NAMES
    from hivemall_tpu.serving.server import serve

    model, rows = _train_default(args.dims, args.train_rows)
    registry = ModelRegistry(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        engine_kwargs={"max_batch": args.max_batch,
                       "max_width": args.max_width})
    registry.deploy("bench", model, version="1")
    server = serve(registry)
    port = server.server_address[1]

    # calibration: the overload mode's closed-loop burst over the same
    # persistent-connection driver, doubling as HTTP-path warmup
    calib_pool = _request_pool(rows, args.calib_requests,
                               args.instances_per_request)
    calib_bodies = [json.dumps({"model": "bench", "instances": req}).encode()
                    for req in calib_pool]
    nodeadline = (1e4, 1e4, 1e4)
    recs, wall = _overload_step(port, calib_bodies,
                                np.ones(len(calib_bodies), dtype=int),
                                rate=1e6, deadlines_ms=nodeadline,
                                workers=args.concurrency, timeout=60.0)
    if not any(r[1] == 200 for r in recs):
        print(f"SLO FAIL: calibration served nothing ({recs[:3]})",
              file=sys.stderr)
        return 1
    burst_rps = len(recs) / wall
    mean_rows = sum(len(r) for r in calib_pool) / len(calib_pool)

    # saturation search: the fixed-worker closed loop can understate the
    # OPEN-LOOP capacity (the sweep scales its worker pool with the
    # offered rate) as badly as it overstates the sustainable rate on a
    # loaded host — and an "overload" phase anchored under capacity never
    # queues, so the alert it is supposed to trip never has cause. Find
    # the knee the way the overload sweep does: climb offered rates with
    # the sweep's own driver until goodput falls under 90% of offered;
    # the last rate that held is the saturation anchor.
    probe_s = min(2.0, args.step_seconds / 2)
    sat = burst_rps * 0.25
    probe = sat
    while probe <= burst_rps * 8.0:
        n = max(16, int(probe * probe_s))
        recs, wall = _overload_step(
            port, [calib_bodies[i % len(calib_bodies)] for i in range(n)],
            np.ones(n, dtype=int), rate=probe, deadlines_ms=nodeadline,
            workers=int(min(args.max_workers, max(8, probe * 0.25))),
            timeout=60.0)
        good = sum(1 for r in recs if r[1] == 200) / wall
        if good < 0.9 * probe:
            break
        sat = probe
        probe *= 1.6
    # the 2x step must be transportable by the joint client+server system
    # RIGHT NOW, or the "overload" melts into client slip instead of the
    # server-side queueing the burn alert watches: validate once and
    # re-anchor down if the schedule slips
    top = sat * 2.0
    n = max(24, int(top * probe_s))
    recs, wall = _overload_step(
        port, [calib_bodies[i % len(calib_bodies)] for i in range(n)],
        np.ones(n, dtype=int), rate=top, deadlines_ms=nodeadline,
        workers=int(min(args.max_workers, max(8, top * 0.25))), timeout=60.0)
    achieved_top = len(recs) / wall
    if achieved_top < 0.8 * top:
        sat = achieved_top / 2.0

    # admission posture sized from measured capacity (the PR 10 ladder
    # deploy: bounded queue-seconds of backlog, quota fracs, door limit)
    max_queue_rows = max(4 * args.max_batch,
                         int(sat * mean_rows * args.queue_seconds))
    inflight_limit = max(12, int(max_queue_rows / max(1.0, mean_rows)) + 4)
    server.inflight = threading.BoundedSemaphore(inflight_limit)
    server.inflight_reserve = threading.BoundedSemaphore(
        max(2, inflight_limit // 4))
    registry.deploy(
        "bench", model, version="2",
        batcher_overrides=dict(max_queue_rows=max_queue_rows,
                               max_delay_ms_cap=args.max_delay_ms_cap,
                               max_batch_cap=args.max_batch,
                               priority_quota_fracs=(1.0, 0.85, 0.6)))
    n_warm = 4 * inflight_limit
    _overload_step(port, [calib_bodies[i % len(calib_bodies)]
                          for i in range(n_warm)],
                   np.ones(n_warm, dtype=int), rate=1e6,
                   deadlines_ms=nodeadline,
                   workers=args.concurrency, timeout=60.0)

    # the sampler + SLO engine, on the PROCESS singletons — GET /slo,
    # /healthz and /debug/bundle read those, and this gate checks the
    # HTTP surface mid-overload, not private objects. Windows scale with
    # the step so the full-size run exercises the same mechanics.
    step_s = args.step_seconds
    interval = max(0.05, step_s / 16.0)
    fast_w = max(3 * interval, step_s / 5.0)
    slow_w = max(2 * fast_w, step_s * 0.8)
    ring = timeseries.RING
    ring.interval_s = interval
    engine = ENGINE

    deadlines = (args.deadline_high_ms, args.deadline_normal_ms,
                 args.deadline_low_ms)
    rng = np.random.RandomState(47)

    def drive(frac, seconds):
        rate = max(4.0, sat * frac)
        n = max(40, int(rate * seconds))
        classes = rng.choice(len(PRIORITY_NAMES), n, p=OVERLOAD_MIX)
        bodies = [json.dumps(
            {"model": "bench",
             "instances": calib_pool[rng.randint(len(calib_pool))]}
        ).encode() for _ in range(n)]
        workers = int(min(args.max_workers, max(8, rate * 0.4)))
        recs, wall = _overload_step(
            port, bodies, classes, rate, deadlines, workers,
            timeout=max(deadlines) / 1e3 + 10.0)
        ok = [r[3] * 1e3 for r in recs if r[1] == 200]
        ok.sort()
        return {"offered_x": frac, "offered_rps": round(rate, 1),
                "achieved_rps": round(len(recs) / wall, 1),
                "goodput_rps": round(len(ok) / wall, 1),
                "ok": len(ok),
                "sent": n,
                "p50_ms": round(float(np.percentile(ok, 50)), 2)
                if ok else None,
                "p99_ms": round(float(np.percentile(ok, 99)), 2)
                if ok else None}

    guard = REGISTRY.counter("graftcheck", "recompiles.serving.bench")
    recompiles0 = guard.value
    TRACER.clear()
    ring.start()

    # phase 1 (light): measure the healthy latency the objective anchors
    # on — the SLO threshold is 2x the light-load p99, capped at half the
    # queue's drain bound so an overloaded queue CAN breach it even on a
    # host whose light-load p99 is already high
    light = drive(0.25, step_s)
    light_p99_ms = light["p99_ms"] or 50.0
    threshold_s = min(max(2.0 * light_p99_ms / 1e3, 0.02),
                      0.5 * args.queue_seconds)
    slo = SLO(name="bench.latency", kind="latency",
              histogram="serving.http.latency_seconds",
              threshold_s=threshold_s, objective=0.9,
              fast_window_s=fast_w, slow_window_s=slow_w,
              warn_burn=1.0, page_burn=2.0,
              raise_after=2, clear_after=2,
              labels={"model": "bench", "bench": "slo"})
    engine.register(slo)
    # availability rides along for the artifact (warn-only shape: the
    # overload phase SHEDS by design — quota/shed/expiry are the bad
    # events a fleet operator would watch, not gate here)
    engine.register(SLO(
        name="bench.availability", kind="availability", objective=0.5,
        good_keys=tuple(f"serving.bench.batcher.accepted.{p}"
                        for p in PRIORITY_NAMES),
        bad_keys=tuple(f"serving.bench.batcher.{k}.{p}"
                       for k in ("quota_rejected", "shed", "expired")
                       for p in PRIORITY_NAMES),
        fast_window_s=fast_w, slow_window_s=slow_w,
        warn_burn=1.2, page_burn=1.8, raise_after=2, clear_after=2,
        labels={"model": "bench", "bench": "slo"}))
    engine.attach()

    # phase 2 (confirm): the objective must hold at light load
    confirm = drive(0.25, max(slow_w, step_s * 0.6))
    st = engine.status()["slos"]["bench.latency"]
    confirm_state = st["state"]
    false_fire = st["peak_state"] == "page"

    # phase 3 (overload): 2x saturation, long enough that BOTH windows
    # burn and the hysteresis can fire; mid-phase, a side thread pulls
    # /debug/bundle + /slo + /healthz off the live server
    over_s = max(step_s, slow_w + 4 * fast_w)
    mid = {}

    def fetch_mid():
        time.sleep(0.6 * over_s)
        for key, url in (("bundle", f"/debug/bundle?n=20"),
                         ("slo", "/slo"), ("healthz", "/healthz")):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{url}", timeout=10) as r:
                    mid[key] = json.loads(r.read())
            except Exception as e:
                mid[key + "_error"] = repr(e)

    fetcher = threading.Thread(target=fetch_mid, daemon=True)
    fetcher.start()
    over = drive(2.0, over_s)
    fetcher.join(timeout=30.0)
    fired = engine.status()["slos"]["bench.latency"]["peak_state"] == "page"

    # phase 4 (recovery): light load until the overload observations age
    # out of the slow window, then give the hysteresis a grace period of
    # empty-window evaluations (an idle window is clearing evidence)
    recovery = drive(0.25, slow_w + max(step_s, 4 * fast_w))
    deadline_t = time.monotonic() + max(5.0, slow_w)
    while time.monotonic() < deadline_t:
        if engine.status()["slos"]["bench.latency"]["state"] == "ok":
            break
        time.sleep(interval)
    final = engine.status()
    cleared = final["slos"]["bench.latency"]["state"] == "ok"

    ring.stop()
    engine.detach()
    steady_recompiles = int(guard.value - recompiles0)
    overhead = ring.overhead()
    server.shutdown()
    registry.shutdown()

    # mid-overload bundle completeness: every flight-recorder section,
    # the deployed model, live SLO state and time-series history must be
    # present in the document a curl got DURING the incident
    from hivemall_tpu.runtime.debug_bundle import SECTIONS

    bundle = mid.get("bundle") or {}
    missing = [s for s in SECTIONS if s not in bundle]
    bundle_ok = (not missing and not mid.get("bundle_error")
                 and any(m.get("name") == "bench"
                         for m in bundle.get("models", []))
                 and "bench.latency" in bundle.get("slo", {}).get("slos", {})
                 and len(bundle.get("timeseries", {}).get("samples", [])) > 0
                 and len(bundle.get("traces", {}).get("last", [])) > 0)
    healthz_mid = mid.get("healthz") or {}

    result = {
        "metric": f"serving_slo_burn_alert_arow_{args.dims}dims",
        "value": float(fired and cleared),
        "unit": "bool",
        "methodology": "http_overload_ladder_multiwindow_burn_rate",
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "calibration": {"burst_closed_loop_rps": round(burst_rps, 1),
                        "saturation_rps": round(sat, 1),
                        "mean_rows_per_request": round(mean_rows, 1),
                        "max_queue_rows": int(max_queue_rows),
                        "max_concurrent_requests": int(inflight_limit)},
        "slo": {"threshold_ms": round(threshold_s * 1e3, 2),
                "objective": 0.9,
                "fast_window_s": round(fast_w, 3),
                "slow_window_s": round(slow_w, 3),
                "sample_interval_s": round(interval, 3)},
        "phases": {"light": light, "confirm": confirm,
                   "overload": over, "recovery": recovery},
        "alert": {"fired_during_overload": fired,
                  "cleared_after_recovery": cleared,
                  "false_fire_at_light_load": false_fire,
                  "confirm_state": confirm_state,
                  "final_state": final["slos"]["bench.latency"]["state"],
                  "transitions":
                      final["slos"]["bench.latency"]["transitions"],
                  "availability_peak":
                      final["slos"]["bench.availability"]["peak_state"]},
        "sampler": overhead,
        "bundle_mid_overload": {"ok": bundle_ok,
                                "missing_sections": missing,
                                "error": mid.get("bundle_error"),
                                "healthz_status":
                                    healthz_mid.get("status"),
                                "healthz_slo":
                                    healthz_mid.get("slo")},
        "steady_state_recompiles": steady_recompiles,
    }
    print(json.dumps(result))

    rc = 0
    if false_fire:
        print("SLO FAIL: the latency objective PAGED at light load before "
              "the overload step — the alert is not credible (threshold "
              f"{threshold_s * 1e3:.1f} ms, light p99 {light_p99_ms} ms)",
              file=sys.stderr)
        rc = 1
    if not fired:
        print("SLO FAIL: the latency burn-rate alert never reached 'page' "
              "during the 2x overload step — both windows must burn "
              f"(threshold {threshold_s * 1e3:.1f} ms, overload p99 "
              f"{over['p99_ms']} ms)", file=sys.stderr)
        rc = 1
    if not cleared:
        print("SLO FAIL: the alert did not clear after recovery (state "
              f"{final['slos']['bench.latency']['state']!r} after "
              f"{slow_w:.1f}s slow window + grace)", file=sys.stderr)
        rc = 1
    if overhead["fraction"] >= 0.05:
        print(f"SLO FAIL: sampler overhead {overhead['fraction']:.4f} >= "
              f"0.05 of wall time ({overhead['samples']} samples, "
              f"{overhead['sample_seconds']:.3f}s sampling over "
              f"{overhead['elapsed_s']:.1f}s)", file=sys.stderr)
        rc = 1
    if not bundle_ok:
        print(f"SLO FAIL: mid-overload /debug/bundle incomplete: "
              f"missing={missing} error={mid.get('bundle_error')}",
              file=sys.stderr)
        rc = 1
    if steady_recompiles:
        print(f"SLO FAIL: steady_state_recompiles={steady_recompiles}",
              file=sys.stderr)
        rc = 1
    return rc


# -- skew mode: the hot-row cache under Zipfian traffic ----------------------

def _zipf_probs(universe: int, s: float) -> np.ndarray:
    """Pinned-Zipf rank probabilities: p(r) ~ r^-s over the row universe
    (the production shape — PAPERS.md ads-infra repetition, hashed-feature
    mass concentration)."""
    ranks = np.arange(1, universe + 1, dtype=np.float64)
    p = ranks ** -s
    return p / p.sum()


def _zipf_stream(universe_rows, probs, n_requests: int, k: int, seed: int):
    """One request stream: each request is ``k`` rows drawn i.i.d. from
    the pinned-Zipf distribution over the row universe. Fresh seed per
    trial — repetition comes from the DISTRIBUTION, not pool identity."""
    rng = np.random.RandomState(seed)
    draws = rng.choice(len(universe_rows), size=(n_requests, k), p=probs)
    return [[universe_rows[i] for i in req] for req in draws]


def _registry_closed_loop(registry, name, pool, concurrency: int):
    """Closed loop over ``registry.submit`` — the batcher-front path the
    hot-row cache actually lives on (engine-direct driving would bypass
    it). Returns (wall_seconds, errors)."""
    errors = []
    lock = threading.Lock()
    it = iter(pool)

    def worker():
        while True:
            with lock:
                req = next(it, None)
            if req is None:
                return
            try:
                _, fut = registry.submit(name, req)
                fut.result(timeout=60)
            except Exception as e:
                with lock:
                    errors.append(repr(e))

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, errors


def _skew_swap_probe(registry, name, probe, model2, concurrency: int = 4):
    """Hammer the cache-fronted model with one fixed (hence hot-cached)
    request while deploying v2 over v1. Every observation must be
    (version, that version's OWN score) — a stale v1 score labeled v2 is
    the bug the version-keyed cache exists to make impossible — and a
    swap must fail zero requests."""
    expected = {"1": [float(x)
                      for x in registry.get(name).engine.predict(probe)]}
    observed, failures = [], []
    stop = threading.Event()
    lock = threading.Lock()

    def hammer():
        while not stop.is_set():
            try:
                entry, fut = registry.submit(name, probe)
                scores = [float(x) for x in fut.result(timeout=30)]
                with lock:
                    observed.append((entry.version, scores))
            except Exception as e:
                with lock:
                    failures.append(repr(e))

    threads = [threading.Thread(target=hammer) for _ in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(0.15)
    registry.deploy(name, model2, version="2")
    expected["2"] = [float(x)
                     for x in registry.get(name).engine.predict(probe)]
    time.sleep(0.15)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    versions = sorted({v for v, _ in observed})
    mislabeled = sum(1 for v, s in observed if s != expected[v])
    return {
        "requests_served": len(observed),
        "failed_requests": len(failures),
        "failures": failures[:3],
        "versions_observed": versions,
        "mislabeled_scores": mislabeled,
        "ok": (not failures and versions == ["1", "2"]
               and mislabeled == 0),
    }


def _skew_parity_gate(model, probe_pool, args) -> dict:
    """The hard parity pin: for every serving precision (f32 / bf16 /
    int8), scores served THROUGH the cache (second pass, all hits) are
    bit-identical to the first-pass computed ones AND to a cache-off
    deploy of the same artifact. Exact float equality — quantized
    precisions compare against their own computed scores, not f32's."""
    import os
    import tempfile

    from hivemall_tpu.serving import ModelRegistry, freeze

    tmp = tempfile.mkdtemp(prefix="hivemall_skew_parity_")
    out = {}
    ok = True
    for prec in QUANT_PRECISIONS:
        path = os.path.join(tmp, prec)
        freeze(model, path, name=f"skewpar_{prec}", version="1",
               quantize=_QUANT_FREEZE_ARG[prec])
        reg = ModelRegistry(score_cache_bytes=args.cache_mb << 20,
                            engine_kwargs={"max_batch": args.max_batch,
                                           "max_width": args.max_width})
        reg.deploy("on", path, version="1")
        reg.deploy("off", path, version="1", score_cache_bytes=0)
        computed, cached, offline = [], [], []
        for req in probe_pool:
            computed.append([float(x)
                             for x in reg.submit("on", req)[1].result(30)])
        hits0 = reg.get("on").describe()["cache"]["hit_rows"]
        for req in probe_pool:
            cached.append([float(x)
                           for x in reg.submit("on", req)[1].result(30)])
        hits1 = reg.get("on").describe()["cache"]["hit_rows"]
        for req in probe_pool:
            offline.append([float(x)
                            for x in reg.submit("off", req)[1].result(30)])
        n_rows = sum(len(r) for r in probe_pool)
        prec_ok = (cached == computed == offline
                   and hits1 - hits0 == n_rows)
        out[prec] = {"ok": prec_ok,
                     "rows": n_rows,
                     "second_pass_hit_rows": int(hits1 - hits0),
                     "bit_identical": cached == computed == offline}
        ok = ok and prec_ok
        reg.shutdown()
    out["ok"] = ok
    return out


def run_skew_mode(args) -> int:
    """Zipfian hot-row workload: cache-on vs cache-off at equal skew.

    One AROW model deploys twice into one registry — ``skew_on`` fronted
    by the hot-row score cache (serving/cache.py), ``skew_off`` with the
    cache disabled — and per-trial FRESH pinned-Zipf request streams
    drive both through ``registry.submit`` (the batcher path the cache
    lives on) in interleaved paired trials. Hard gates: effective
    rows/sec (cache-on / cache-off, paired median) >= --skew-speedup-min,
    measured hit ratio over the timed window >= --skew-hit-floor, the
    cached == computed bit-parity pin at every precision (f32/bf16/int8),
    a mid-bench hot-swap with zero failed requests and zero stale-labeled
    scores, and zero steady-state recompiles."""
    from hivemall_tpu.serving import ModelRegistry

    model, _rows = _train_default(args.dims, args.train_rows)
    model2, _ = _train_default(args.dims, args.train_rows, seed=11)

    # the row universe: distinct rows whose ranks carry the Zipf mass
    rng = np.random.RandomState(17)
    universe = [[f"{rng.randint(args.dims)}:{rng.rand():.3f}"
                 for _ in range(rng.randint(4, 14))]
                for _ in range(args.universe_rows)]
    probs = _zipf_probs(args.universe_rows, args.zipf_s)
    k = max(1, int(args.instances_per_request))

    cache_bytes = args.cache_mb << 20
    registry = ModelRegistry(engine_kwargs={"max_batch": args.max_batch,
                                            "max_width": args.max_width})
    registry.deploy("skew_on", model, version="1",
                    score_cache_bytes=cache_bytes)
    registry.deploy("skew_off", model, version="1", score_cache_bytes=0)

    # warm pass (untimed, both arms): first-touch costs out of the way
    # and the cache at its Zipf steady state — what a long-running server
    # actually serves; the cold ramp is visible in the warm_pass block
    warm_stream = _zipf_stream(universe, probs, args.requests, k, seed=100)
    for name in ("skew_on", "skew_off"):
        _, errs = _registry_closed_loop(registry, name, warm_stream,
                                        args.concurrency)
        if errs:
            print(f"SKEW FAIL: warm pass errors on {name}: {errs[:3]}",
                  file=sys.stderr)
            return 1
    warm_stats = registry.get("skew_on").describe()["cache"]

    guards = {n: REGISTRY.counter("graftcheck", f"recompiles.serving.{n}")
              for n in ("skew_on", "skew_off")}
    recompiles0 = {n: g.value for n, g in guards.items()}
    hit0 = registry.get("skew_on").cache.stats()
    arms = ("skew_on", "skew_off")
    trials = {n: [] for n in arms}
    errors = {n: 0 for n in arms}
    rows_per_trial = args.requests * k
    for t in range(args.quant_trials):
        stream = _zipf_stream(universe, probs, args.requests, k,
                              seed=200 + t)
        order = arms if t % 2 == 0 else arms[::-1]
        for name in order:
            wall, errs = _registry_closed_loop(registry, name, stream,
                                               args.concurrency)
            errors[name] += len(errs)
            trials[name].append(rows_per_trial / wall)
    steady = {n: int(guards[n].value - recompiles0[n]) for n in arms}
    hit1 = registry.get("skew_on").cache.stats()
    looked = (hit1["hit_rows"] - hit0["hit_rows"]
              + hit1["miss_rows"] - hit0["miss_rows"])
    hit_ratio = ((hit1["hit_rows"] - hit0["hit_rows"]) / looked
                 if looked else 0.0)

    speedup = float(np.median(np.asarray(trials["skew_on"])
                              / np.asarray(trials["skew_off"])))

    # mid-bench hot swap on the cache-fronted arm: zero failures, both
    # versions observed, every score labeled with the version that
    # actually computed it (the version-key invalidation made auditable)
    probe = _zipf_stream(universe, probs, 1, k, seed=999)[0]
    swap = _skew_swap_probe(registry, "skew_on", probe, model2,
                            concurrency=min(4, args.concurrency))
    cache_stats = registry.get("skew_on").cache.stats()
    registry.shutdown()

    # cached == computed, bit-identical, at every precision
    parity = _skew_parity_gate(model,
                               _zipf_stream(universe, probs, 8, k,
                                            seed=555),
                               args)

    meth = {"name": "zipf_closed_loop_paired_trials_registry",
            "execution_backend": "serving_registry",
            "dims": int(args.dims),
            "concurrency": int(args.concurrency),
            "zipf_s": float(args.zipf_s),
            "universe_rows": int(args.universe_rows),
            "rows_per_request": k,
            "cache_budget_bytes": int(cache_bytes)}
    result = {
        "metric": f"serving_skew_cache_speedup_arow_{args.dims}dims",
        "value": round(speedup, 3),
        "unit": "x",
        "methodology": meth,
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "trials": int(args.quant_trials),
        "requests_per_trial": int(args.requests),
        "rows_per_trial": int(rows_per_trial),
        "arms": {
            n: {"effective_rows_per_sec":
                round(float(np.median(trials[n])), 1),
                "steady_state_recompiles": steady[n],
                "request_errors": errors[n]} for n in arms
        },
        "warm_pass": {"hit_ratio": warm_stats["hit_ratio"],
                      "entries": warm_stats["entries"],
                      "resident_bytes": warm_stats["resident_bytes"]},
        "hit_ratio": round(hit_ratio, 4),
        "coalesced_rows": int(hit1["coalesced_rows"]
                              - hit0["coalesced_rows"]),
        "cache": cache_stats,
        "hot_swap": swap,
        "parity": parity,
        "gates": {"speedup_min_x": args.skew_speedup_min,
                  "hit_floor": args.skew_hit_floor},
    }
    print(json.dumps(result))

    rc = 0
    if speedup < args.skew_speedup_min:
        print(f"SKEW FAIL: cache-on effective rows/sec is {speedup:.3f}x "
              f"cache-off at zipf_s={args.zipf_s} — below the "
              f"{args.skew_speedup_min}x gate", file=sys.stderr)
        rc = 1
    if hit_ratio < args.skew_hit_floor:
        print(f"SKEW FAIL: measured hit ratio {hit_ratio:.4f} below the "
              f"pinned floor {args.skew_hit_floor}", file=sys.stderr)
        rc = 1
    if not parity["ok"]:
        print(f"SKEW FAIL: cached scores are not bit-identical to "
              f"computed ones: {json.dumps(parity)}", file=sys.stderr)
        rc = 1
    if not swap["ok"]:
        print(f"SKEW FAIL: hot-swap probe: {json.dumps(swap)}",
              file=sys.stderr)
        rc = 1
    if any(steady.values()):
        print(f"SKEW FAIL: steady_state_recompiles={steady}",
              file=sys.stderr)
        rc = 1
    if any(errors.values()):
        print(f"SKEW FAIL: request errors {errors}", file=sys.stderr)
        rc = 1
    return rc


def closed_loop(batcher, pool, concurrency: int):
    lat, errors = [], []
    lock = threading.Lock()
    it = iter(pool)

    def worker():
        while True:
            with lock:
                req = next(it, None)
            if req is None:
                return
            t0 = time.perf_counter()
            try:
                batcher.submit(req).result(timeout=60)
            except Exception as e:
                with lock:
                    errors.append(repr(e))
                continue
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return lat, wall, errors


def open_loop(batcher, pool, rate_rps: float):
    """Fixed-rate arrivals; latency = completion - SCHEDULED arrival (no
    coordinated omission)."""
    period = 1.0 / rate_rps
    pending, lat, errors = [], [], []
    lock = threading.Lock()
    start = time.perf_counter()
    for i, req in enumerate(pool):
        sched = start + i * period
        now = time.perf_counter()
        if sched > now:
            time.sleep(sched - now)
        try:
            fut = batcher.submit(req)
        except Exception as e:  # backpressure rejections count as errors
            errors.append(repr(e))
            continue

        def _done(f, sched=sched):
            # completion is stamped HERE, on the batcher worker thread —
            # stamping at collection time would charge early requests for
            # the whole submit phase
            done = time.perf_counter()
            with lock:
                if f.exception() is not None:
                    errors.append(repr(f.exception()))
                else:
                    lat.append(done - sched)

        fut.add_done_callback(_done)
        pending.append(fut)
    for fut in pending:
        try:
            fut.result(timeout=60)
        except Exception:
            pass  # recorded by the callback
    wall = time.perf_counter() - start
    return lat, wall, errors


def hot_swap_probe(model_factory, batcher_kw, engine_kw, pool,
                   concurrency: int):
    """Hammer a registry-held model from `concurrency` threads while
    swapping v1 -> v2; returns (requests_served, failures)."""
    from hivemall_tpu.serving import ModelRegistry

    registry = ModelRegistry(max_delay_ms=batcher_kw["max_delay_ms"],
                             engine_kwargs=engine_kw)
    registry.deploy("bench", model_factory(1), version="1")
    served, failures = [], []
    stop = threading.Event()
    lock = threading.Lock()

    def hammer(i):
        j = 0
        while not stop.is_set():
            try:
                # registry.submit retries across the swap (the same path
                # the /predict handler uses)
                _, fut = registry.submit("bench",
                                         pool[(i * 31 + j) % len(pool)])
                fut.result(timeout=60)
                with lock:
                    served.append(1)
            except Exception as e:
                with lock:
                    failures.append(repr(e))
            j += 1

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    registry.deploy("bench", model_factory(2), version="2")
    time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    registry.shutdown()
    return len(served), failures


def _http_post(port, payload, timeout=60.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def http_closed_loop(port, pool, concurrency: int, model: str = "bench"):
    """Closed loop over POST /predict — the same probe as closed_loop()
    but end-to-end: sockets, HTTP parse, JSON, handler threads."""
    lat, errors = [], []
    lock = threading.Lock()
    it = iter(pool)

    def worker():
        while True:
            with lock:
                req = next(it, None)
            if req is None:
                return
            t0 = time.perf_counter()
            try:
                out = _http_post(port, {"model": model, "instances": req})
                if len(out["predictions"]) != len(req):
                    raise RuntimeError("prediction count mismatch")
            except Exception as e:  # 5xx surfaces as HTTPError: an error
                with lock:
                    errors.append(repr(e))
                continue
            dt = time.perf_counter() - t0
            with lock:
                lat.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return lat, wall, errors


def http_hot_swap_probe(registry, port, model_factory, pool,
                        concurrency: int):
    """Hammer POST /predict while deploying v2 over v1; a swap must fail
    zero requests at the HTTP surface too (503s included)."""
    served, failures = [], []
    versions = set()
    stop = threading.Event()
    lock = threading.Lock()

    def hammer(i):
        j = 0
        while not stop.is_set():
            try:
                out = _http_post(port, {"model": "bench",
                                        "instances":
                                            pool[(i * 31 + j) % len(pool)]})
                with lock:
                    served.append(1)
                    versions.add(out["version"])
            except Exception as e:
                with lock:
                    failures.append(repr(e))
            j += 1

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(concurrency)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    registry.deploy("bench", model_factory(2), version="2")
    time.sleep(0.2)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    return len(served), failures, versions


def run_http_mode(args, source, rows, tag) -> int:
    from hivemall_tpu.serving import ModelRegistry
    from hivemall_tpu.serving.server import serve

    registry = ModelRegistry(
        max_batch=args.max_batch, max_delay_ms=args.max_delay_ms,
        engine_kwargs={"max_batch": args.max_batch,
                       "max_width": args.max_width})
    t0 = time.perf_counter()
    registry.deploy("bench", source, version="1")  # warms every bucket
    warm_s = time.perf_counter() - t0
    server = serve(registry)
    port = server.server_address[1]
    snap = REGISTRY.snapshot()
    warm_compiles = int(snap.get("serving.bench.warmup_compiles", 0))
    pool = _request_pool(rows, args.requests, args.instances_per_request)
    guard = REGISTRY.counter("graftcheck", "recompiles.serving.bench")

    TRACER.clear()  # measure request traces only, not deploy/warmup ones
    recompiles0 = guard.value
    lat, wall, errors = http_closed_loop(port, pool, args.concurrency)
    steady_recompiles = guard.value - recompiles0
    p = _percentiles(lat) if lat else {50: 0, 95: 0, 99: 0}
    tracing_block, stage_names = trace_report(args.trace_out)

    def factory(v):
        return _train_default(args.dims, args.train_rows, seed=v)[0]

    swap_served, swap_failures, versions = http_hot_swap_probe(
        registry, port, factory, pool, args.concurrency)
    server.shutdown()
    registry.shutdown()

    result = {
        "metric": f"serving_http_closed_loop_throughput_{tag}",
        "value": round(len(lat) / wall, 1) if wall else 0.0,
        "unit": "req/s",
        "methodology": "http_post_predict_closed_loop",
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "steady_state_recompiles": int(steady_recompiles),
        "warmup": {"compiles": warm_compiles,
                   "seconds": round(warm_s, 3)},
        "hot_swap": {"requests_served": swap_served,
                     "failed_requests": len(swap_failures),
                     "versions_observed": sorted(versions)},
        "request_errors": len(errors),
        "tracing": tracing_block,
        "extra_metrics": [
            {"metric": "http_p50_ms", "value": round(p[50], 3)},
            {"metric": "http_p95_ms", "value": round(p[95], 3)},
            {"metric": "http_p99_ms", "value": round(p[99], 3)},
        ],
    }
    print(json.dumps(result))

    # a request trace missing most of the stage vocabulary means the span
    # wiring broke somewhere between server.py and engine.py — gate on it
    traced_ok = len(stage_names & REQUIRED_STAGES) >= 4
    ok = (steady_recompiles == 0 and not swap_failures and not errors
          and {"1", "2"} <= versions and traced_ok)
    if args.smoke and not ok:
        print(f"SMOKE FAIL: steady_state_recompiles={steady_recompiles} "
              f"swap_failures={swap_failures[:3]} errors={errors[:3]} "
              f"versions={sorted(versions)} "
              f"traced_stages={sorted(stage_names & REQUIRED_STAGES)}",
              file=sys.stderr)
        return 1
    return 0


def _reexec_with_mode_flags(args) -> None:
    """Some modes need XLA flags that only take effect before jax
    initializes; re-exec once with them set (modes do not compose — main()
    refuses any pair — so at most one branch applies).

    - --topk / --sharded need a mesh: CPU runs force 8 host devices the way
      the test suite does (tests/conftest.py); real accelerator runs keep
      their native device set.
    - --quantize pins the CPU backend to single-threaded ops —
      serving-shaped XLA threading: production servers give each request
      one core (request-level parallelism) instead of letting every
      dispatch fan out over the whole intra-op pool, and it is under that
      per-core regime that table bytes, not the scheduler, price a request.
      Operators override by setting XLA_FLAGS themselves."""
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    extra = ""
    if args.topk or args.sharded:
        if os.environ.get("JAX_PLATFORMS", "") == "cpu" \
                and "xla_force_host_platform_device_count" not in flags:
            extra = " --xla_force_host_platform_device_count=8"
    elif args.quantize and "intra_op_parallelism_threads" not in flags:
        extra = (" --xla_cpu_multi_thread_eigen=false "
                 "intra_op_parallelism_threads=1")
    if extra:
        os.environ["XLA_FLAGS"] = (flags + extra).strip()
        os.execv(sys.executable, [sys.executable] + sys.argv)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--artifact", help="serve this artifact dir instead of "
                                       "training a tiny AROW model")
    # sizing flags default to None so --smoke can tell "left unset" from
    # "explicitly passed the full-size value"; resolved below
    ap.add_argument("--dims", type=int, default=None,
                    help="default 65536 (1024 under --smoke)")
    ap.add_argument("--train-rows", type=int, default=None,
                    help="default 2000 (300 under --smoke)")
    ap.add_argument("--requests", type=int, default=None,
                    help="default 2000 (300 under --smoke)")
    ap.add_argument("--instances-per-request", type=int, default=None,
                    help="max rows per request; default 8 (1024 in the "
                         "full --quantize bench, 4 in its smoke)")
    ap.add_argument("--concurrency", type=int, default=None,
                    help="default 8 (4 under --smoke)")
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop arrival rate, req/s; default 500 "
                         "(300 under --smoke)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="default 256 (64 under --smoke)")
    ap.add_argument("--max-width", type=int, default=None,
                    help="default 64 (32 under --smoke)")
    ap.add_argument("--max-delay-ms", type=float, default=2.0)
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale run; exit non-zero on any "
                         "invariant violation (scripts/test.sh gate)")
    ap.add_argument("--http", action="store_true",
                    help="drive POST /predict end-to-end (registry + HTTP "
                         "endpoint in-process) instead of calling the "
                         "engine directly")
    ap.add_argument("--quantize", action="store_true",
                    help="paired-trial f32/bf16/int8 parity bench on one "
                         "frozen model (freeze(quantize=...)); hard-fails "
                         "when int8 holdout logloss drifts past "
                         "--parity-tol-logloss")
    ap.add_argument("--overload", action="store_true",
                    help="goodput-vs-offered-load sweep: stepped open-loop "
                         "offered load (0.25x..2x calibrated saturation) "
                         "over POST /predict with priority mix + deadline "
                         "budgets; hard-fails when goodput at 2x drops "
                         "below --goodput-retention-min of peak, on shed-"
                         "counter inconsistency, or on recompiles")
    ap.add_argument("--slo", action="store_true",
                    help="SLO burn-rate alert gate: overload ladder "
                         "(light -> 2x saturation -> recovery) with the "
                         "time-series sampler + SLO engine live; "
                         "hard-fails unless the latency burn alert fires "
                         "during the 2x step AND clears after recovery, "
                         "sampler overhead stays under 5%%, the "
                         "mid-overload /debug/bundle is complete, and "
                         "zero steady-state recompiles")
    ap.add_argument("--step-seconds", type=float, default=None,
                    help="seconds per offered-load step; default 8 "
                         "(2.5 under --smoke)")
    ap.add_argument("--calib-requests", type=int, default=None,
                    help="closed-loop calibration requests; default 600 "
                         "(150 under --smoke)")
    ap.add_argument("--queue-seconds", type=float, default=0.6,
                    help="queue depth as seconds of backlog at the "
                         "calibrated rate (sizes max_queue_rows)")
    ap.add_argument("--max-delay-ms-cap", type=float, default=20.0,
                    help="AIMD cap for the adaptive co-ride window")
    ap.add_argument("--deadline-high-ms", type=float, default=1500.0)
    ap.add_argument("--deadline-normal-ms", type=float, default=1000.0)
    ap.add_argument("--deadline-low-ms", type=float, default=700.0)
    ap.add_argument("--goodput-retention-min", type=float, default=0.8,
                    help="min goodput at 2x saturation as a fraction of "
                         "peak goodput (hard gate)")
    ap.add_argument("--max-workers", type=int, default=48,
                    help="open-loop client thread cap per step")
    ap.add_argument("--skew", action="store_true",
                    help="Zipfian hot-row workload: cache-on vs cache-off "
                         "registry arms at equal skew (serving/cache.py); "
                         "hard-fails when the paired speedup drops below "
                         "--skew-speedup-min, hit ratio below "
                         "--skew-hit-floor, on any cached!=computed "
                         "parity break, a failed request across the "
                         "mid-bench hot-swap, or recompiles")
    ap.add_argument("--zipf-s", type=float, default=1.2,
                    help="Zipf exponent of the request-row distribution "
                         "(pinned; recorded in the methodology dict)")
    ap.add_argument("--universe-rows", type=int, default=None,
                    help="distinct rows the Zipf mass spreads over; "
                         "default 8000 (400 under --smoke)")
    ap.add_argument("--cache-mb", type=int, default=None,
                    help="hot-row cache byte budget in MB; default 64 "
                         "(8 under --smoke)")
    ap.add_argument("--skew-speedup-min", type=float, default=None,
                    help="min cache-on/cache-off effective rows/sec "
                         "(hard gate); default 1.5 (1.3 under --smoke)")
    ap.add_argument("--skew-hit-floor", type=float, default=None,
                    help="min measured cache-hit ratio over the timed "
                         "window (hard gate); default 0.6 (0.5 under "
                         "--smoke)")
    ap.add_argument("--sharded", action="store_true",
                    help="sharded-placement bench: single-device vs "
                         "NamedSharding servables per (batch, model) mesh "
                         "shape at equal model, plus the simulated-budget "
                         "model-only-fits-sharded demo; hard-fails on "
                         "score-parity drift past --parity-tol-score")
    ap.add_argument("--parity-tol-score", type=float, default=1e-4,
                    help="max |sharded - single| / max|single| holdout "
                         "score drift a placement may show (hard gate)")
    ap.add_argument("--topk", action="store_true",
                    help="top-K retrieval bench (serving/retrieval.py): "
                         "queries/sec against a blocked-streamed MF "
                         "catalog; hard-fails unless the blocked merge is "
                         "bit-identical to the stable-argsort baseline, "
                         "LSH-pruned recall@K holds --recall-floor, and "
                         "sharded catalogs match single-device scores")
    ap.add_argument("--catalog-items", type=int, default=None,
                    help="items in the MF catalog; default 200000 "
                         "(2048 under --smoke)")
    ap.add_argument("--topk-queries", type=int, default=None,
                    help="distinct user queries per trial; default 512 "
                         "(24 under --smoke)")
    ap.add_argument("--topk-k", type=int, default=None,
                    help="results per query; default 32 (8 under --smoke)")
    ap.add_argument("--topk-block-items", type=int, default=None,
                    help="catalog block size of the streamed merge; "
                         "default 8192 (256 under --smoke)")
    ap.add_argument("--lsh-planes", type=int, default=None,
                    help="signed-random-projection planes of the frozen "
                         "index; default 8 (4 under --smoke)")
    ap.add_argument("--recall-floor", type=float, default=None,
                    help="min mean pruned recall@K vs exact scoring "
                         "(hard gate); default 0.3 (0.5 under --smoke — "
                         "pinned from the measured smoke-shape recall "
                         "with margin)")
    ap.add_argument("--mf-factor", type=int, default=None,
                    help="MF embedding width; default 32 (8 under "
                         "--smoke)")
    ap.add_argument("--quant-trials", type=int, default=None,
                    help="paired trials per precision/placement; default 5 "
                         "(3 under --smoke)")
    ap.add_argument("--holdout", type=int, default=None,
                    help="holdout rows for the logloss/AUC parity pin; "
                         "default 4000 (300 under --smoke)")
    ap.add_argument("--parity-tol-logloss", type=float, default=0.02,
                    help="max |holdout logloss - f32 logloss| a quantized "
                         "precision may show (hard gate)")
    ap.add_argument("--trace-out", default=None,
                    help="write the request traces as Chrome/Perfetto JSON "
                         "here (default serving_trace.json under --http; "
                         "off in in-process mode unless set)")
    args = ap.parse_args()
    # resolve the sentinel defaults: full-size normally, seconds-scale
    # under --smoke; an explicitly-passed flag always wins, even when its
    # value coincides with a default
    sizing = {"dims": (1 << 16, 1 << 10), "train_rows": (2000, 300),
              "requests": (2000, 300), "concurrency": (8, 4),
              "rate": (500.0, 300.0), "max_batch": (256, 64),
              "max_width": (64, 32), "instances_per_request": (8, 8),
              "quant_trials": (5, 3),
              "holdout": (4000, 300),
              "step_seconds": (8.0, 2.5),
              "calib_requests": (600, 150)}
    if args.overload or args.slo:
        # the overload sweep sizes for SCORING-bound saturation: requests
        # carry hundreds of rows (prebuilt bytes on the client), so the
        # batcher's queue — where the admission machinery lives — is the
        # binding constraint at a rate the HTTP ingest layer and the
        # in-process client can both comfortably double. Ingest-bound
        # saturation would melt in the handler threads BEFORE admission,
        # where no queue policy can defend goodput.
        sizing.update({"dims": (1 << 16, 1 << 10),
                       "train_rows": (2000, 300),
                       "concurrency": (12, 8),
                       "max_batch": (1024, 128),
                       "max_width": (32, 16),
                       "instances_per_request": (2048, 256),
                       "calib_requests": (120, 60)})
    if args.sharded:
        # the sharded bench sizes for a table worth striping: 2^22-dim f32
        # (16 MB) full-scale so per-device slices actually differ, tiny
        # under --smoke where the subject is the invariants (parity, zero
        # recompiles, the budget refusal), not bandwidth
        sizing.update({"dims": (1 << 22, 1 << 12),
                       "train_rows": (50000, 300),
                       "requests": (800, 120),
                       "concurrency": (0, 2),
                       "max_batch": (1024, 64),
                       "instances_per_request": (512, 16)})
    if args.skew:
        # the skew bench sizes for DISPATCH-bound misses: a table big
        # enough that a miss pays a real gather-dot (that is what a hit
        # skips), requests small enough that full-request coverage is
        # common at the pinned skew, and a universe the Zipf head
        # concentrates on. The cache budget comfortably holds the touched
        # set — byte-budget eviction is pinned in unit tests; what the
        # bench measures is the steady-state fast path.
        sizing.update({"dims": (1 << 20, 1 << 10),
                       "train_rows": (20000, 300),
                       "requests": (2500, 300),
                       "concurrency": (8, 4),
                       "max_batch": (256, 64),
                       "instances_per_request": (4, 2),
                       "universe_rows": (8000, 400),
                       "cache_mb": (64, 8),
                       "skew_speedup_min": (1.5, 1.3),
                       "skew_hit_floor": (0.6, 0.5)})
    if args.quantize:
        # the quantized bench sizes for table-bandwidth sensitivity: a
        # 2^24-dim f32 weight table (64 MB) is past any cache this host
        # has, wide (16-64 nnz) rows and 1024-row batches amortize
        # dispatch into gather traffic, and per-core closed-loop drivers
        # keep the memory system under serving-shaped pressure; training
        # densely enough (~100k wide rows) that the tables hold real
        # weights, so on-disk compression compares trained bytes, not
        # runs of zeros. --smoke keeps the tiny parity-gate shape.
        # concurrency 0 = resolve to the host's core count below (the
        # drivers are request-level parallelism under 1-thread XLA ops)
        sizing.update({"dims": (1 << 24, 1 << 10),
                       "train_rows": (100000, 300),
                       "requests": (1200, 200),
                       "concurrency": (0, 2),
                       "max_batch": (1024, 64),
                       "instances_per_request": (1024, 4)})
    if args.topk:
        # the retrieval bench sizes for a catalog worth streaming: 200k
        # items x 32 factors full-scale (the blocked merge sweeps ~25
        # blocks per query batch), tiny under --smoke where the subject
        # is the gates (bit-exact parity, recall floor, zero recompiles,
        # sharded score parity), not bandwidth. The smoke recall floor
        # (0.5) is pinned from measured smoke-shape recall (~0.7 at 4
        # planes) with margin; the full-scale floor is looser — at 8
        # planes the probe touches ~3.5% of the catalog and the
        # recall/speedup trade is the thing being REPORTED.
        sizing.update({"catalog_items": (200000, 2048),
                       "topk_queries": (512, 24),
                       "topk_k": (32, 8),
                       "topk_block_items": (8192, 256),
                       "lsh_planes": (8, 4),
                       "recall_floor": (0.3, 0.5),
                       "mf_factor": (32, 8),
                       "train_rows": (400000, 4000),
                       "max_batch": (8, 4)})
    for name, (full, small) in sizing.items():
        if getattr(args, name) is None:
            setattr(args, name, small if args.smoke else full)

    _reexec_with_mode_flags(args)
    # only now may jax initialize: the re-exec above must replace a process
    # that has not touched the device
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.topk:
        if args.artifact or args.http or args.quantize or args.sharded \
                or args.skew or args.overload:
            raise SystemExit("--topk trains and freezes its own MF "
                             "catalog; it does not compose with "
                             "--artifact, --http, --quantize, --sharded, "
                             "--skew or --overload")
        return run_topk_mode(args)

    if args.slo:
        if args.artifact or args.http or args.quantize or args.sharded \
                or args.skew or args.topk or args.overload:
            raise SystemExit("--slo trains and deploys its own model and "
                             "owns the process SLO engine; it does not "
                             "compose with --artifact, --http, --quantize, "
                             "--sharded, --skew, --topk or --overload")
        return run_slo_mode(args)

    if args.overload:
        if args.artifact or args.http or args.quantize or args.sharded \
                or args.skew or args.topk:
            raise SystemExit("--overload trains and deploys its own model; "
                             "it does not compose with --artifact, --http, "
                             "--quantize, --sharded, --skew or --topk")
        return run_overload_mode(args)

    if args.skew:
        if args.artifact or args.http or args.quantize or args.sharded \
                or args.topk:
            raise SystemExit("--skew trains and deploys its own model "
                             "twice (cache-on / cache-off); it does not "
                             "compose with --artifact, --http, --quantize, "
                             "--sharded or --topk")
        return run_skew_mode(args)

    if args.sharded:
        if args.artifact or args.http or args.quantize or args.topk:
            raise SystemExit("--sharded trains and places its own model; "
                             "it does not compose with --artifact, --http, "
                             "--quantize or --topk")
        import os

        if not args.concurrency:  # 0 from sizing: drivers match cores
            args.concurrency = min(8, os.cpu_count() or 2)
        return run_sharded_mode(args)

    if args.quantize:
        if args.artifact or args.http or args.topk:
            raise SystemExit("--quantize freezes its own model at three "
                             "precisions; it does not compose with "
                             "--artifact, --http or --topk")
        import os

        if not args.concurrency:  # 0 from sizing: drivers match cores
            args.concurrency = min(8, os.cpu_count() or 2)
        return run_quantize_mode(args)

    if args.artifact:
        source = load(args.artifact)
        rows = None
        tag = source.manifest["name"]
    else:
        model, rows = _train_default(args.dims, args.train_rows)
        source = model
        tag = f"arow_{args.dims}dims"

    if args.http:
        if rows is None:
            raise SystemExit("--http benching needs a request generator "
                             "for the artifact family; only the default "
                             "AROW flow ships one")
        if args.trace_out is None:
            args.trace_out = "serving_trace.json"
        return run_http_mode(args, source, rows, tag)

    engine_kw = {"max_batch": args.max_batch, "max_width": args.max_width}
    engine = ServingEngine(source, name="bench", **engine_kw)
    t0 = time.perf_counter()
    warm_compiles = engine.warmup()
    warm_s = time.perf_counter() - t0
    if rows is None:
        raise SystemExit("--artifact benching needs a request generator for "
                         "its family; only the default AROW flow ships one")
    pool = _request_pool(rows, args.requests, args.instances_per_request)

    batcher_kw = {"max_batch": args.max_batch,
                  "max_delay_ms": args.max_delay_ms}
    guard = REGISTRY.counter("graftcheck", "recompiles.serving.bench")

    # -- closed loop ---------------------------------------------------------
    TRACER.clear()  # request traces only, not the warmup sweep's
    batcher = DynamicBatcher(engine.predict, name="bench", **batcher_kw)
    recompiles0 = guard.value
    closed_lat, closed_wall, closed_err = closed_loop(
        batcher, pool, args.concurrency)
    batcher.close()
    closed_p = _percentiles(closed_lat)

    # -- open loop -----------------------------------------------------------
    batcher = DynamicBatcher(engine.predict, name="bench", **batcher_kw)
    open_lat, open_wall, open_err = open_loop(batcher, pool, args.rate)
    batcher.close()
    open_p = _percentiles(open_lat) if open_lat else {50: 0, 95: 0, 99: 0}
    steady_recompiles = guard.value - recompiles0

    # -- hot swap under load -------------------------------------------------
    def factory(v):
        return _train_default(args.dims, args.train_rows, seed=v)[0]

    swap_served, swap_failures = hot_swap_probe(
        factory, batcher_kw, engine_kw, pool, args.concurrency)

    tracing_block = None
    if args.trace_out:
        tracing_block, _ = trace_report(args.trace_out)

    occupancy = REGISTRY.histogram("serving.bench.batch_occupancy")
    result = {
        "metric": f"serving_closed_loop_throughput_{tag}",
        "value": round(len(closed_lat) / closed_wall, 1),
        "unit": "req/s",
        "methodology": "in_process_batcher_closed_loop",
        "device_set": _device_set(),
        "recompiles": _recompile_counters(),
        "steady_state_recompiles": int(steady_recompiles),
        "warmup": {"compiles": int(warm_compiles),
                   "seconds": round(warm_s, 3),
                   "buckets": len(engine.warmed_buckets)},
        "hot_swap": {"requests_served": swap_served,
                     "failed_requests": len(swap_failures)},
        "request_errors": len(closed_err) + len(open_err),
        **({"tracing": tracing_block} if tracing_block else {}),
        "extra_metrics": [
            {"metric": "closed_loop_p50_ms", "value": round(closed_p[50], 3)},
            {"metric": "closed_loop_p95_ms", "value": round(closed_p[95], 3)},
            {"metric": "closed_loop_p99_ms", "value": round(closed_p[99], 3)},
            {"metric": "open_loop_throughput", "unit": "req/s",
             "value": round(len(open_lat) / open_wall, 1)},
            {"metric": "open_loop_p50_ms", "value": round(open_p[50], 3)},
            {"metric": "open_loop_p95_ms", "value": round(open_p[95], 3)},
            {"metric": "open_loop_p99_ms", "value": round(open_p[99], 3)},
            {"metric": "mean_batch_occupancy_rows",
             "value": round(occupancy.sum / max(1, occupancy.count), 2)},
        ],
    }
    print(json.dumps(result))

    ok = (steady_recompiles == 0 and not swap_failures
          and not closed_err and not open_err)
    if args.smoke and not ok:
        print(f"SMOKE FAIL: steady_state_recompiles={steady_recompiles} "
              f"swap_failures={swap_failures[:3]} "
              f"closed_err={closed_err[:3]} open_err={open_err[:3]}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
