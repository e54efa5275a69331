#!/usr/bin/env python
"""Continuous-training pipeline bench: train, freeze, gate and hot-swap
under live traffic, and publish END-TO-END FRESHNESS as the metric.

The scenario is the ROADMAP's train->serve loop closed
(docs/continuous_training.md): a `ContinuousPipeline` consumes a seeded
concept-drift stream (dataset/lr_datagen.DriftStream) on a worker thread —
training, checkpointing through the PR 8 elastic seams, freezing versioned
artifacts, gating them on a rolling holdout, and atomically hot-swapping
passing versions into a live ModelRegistry — WHILE closed-loop traffic
threads hammer the same registry and a sampler thread tracks the served
model's holdout logloss over time. Mid-run the stream serves a
deterministic bad-data window (label_flip_events covering one full freeze
cadence): the cycle trained on it MUST be refused by the eval gate, and
revert-on-refuse quarantines the poisoned update.

Headline metric: end-to-end freshness — "event observed -> a model trained
on it is serving", exact event-weighted p50/p99 over the run (the
always-on view is the ``pipeline.<name>.freshness_seconds`` histogram on
/metrics). Refused cycles keep their events' clocks running, so gate
refusals surface in the p99 instead of vanishing.

--smoke (tier-1 gate 9 in scripts/test.sh) hard-fails unless, in one run:
  (1) >= --min-publishes evaluation-gated publishes landed under live
      traffic (>= 2 of them atomic hot-swaps of a serving version),
  (2) >= 1 publish was REFUSED on the injected regression,
  (3) zero traffic requests failed across all swaps,
  (4) freshness p99 <= --freshness-p99-bound seconds,
  (5) the trace ring covers the pipeline stages (train/freeze/gate/
      publish visible per docs/observability.md).

Run:  JAX_PLATFORMS=cpu python scripts/bench_pipeline.py [--smoke]
"""

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

REQUIRED_STAGES = {"pipeline.train", "pipeline.freeze", "pipeline.gate",
                   "pipeline.publish"}


def _device_set():
    import jax

    return {
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "device_kinds": sorted({d.device_kind for d in jax.devices()}),
    }


def _request_pool(stream, n_requests: int, k: int, seed: int = 13):
    """String-row requests drawn from the stream's feature distribution —
    traffic pays the full parse path, like real /predict bodies would."""
    rng = np.random.RandomState(seed)
    pool = []
    for _ in range(n_requests):
        rows = []
        for _r in range(max(1, rng.randint(1, k + 1))):
            idx = rng.randint(0, stream.dims, stream.width)
            val = rng.rand(stream.width)
            rows.append([f"{int(i)}:{v:.3f}" for i, v in zip(idx, val)])
        pool.append(rows)
    return pool


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--dims", type=int, default=None,
                    help="model dims (default 2^16; 2^12 under --smoke)")
    ap.add_argument("--batches", type=int, default=None,
                    help="stream batches (default 256; 96 under --smoke)")
    ap.add_argument("--batch", type=int, default=64, help="events per batch")
    ap.add_argument("--width", type=int, default=8, help="nnz per event")
    ap.add_argument("--freeze-every", type=int, default=512,
                    help="events per freeze->gate->publish cycle")
    ap.add_argument("--checkpoint-every", type=int, default=256,
                    help="events per elastic checkpoint")
    ap.add_argument("--drift-every", type=int, default=2048,
                    help="events per concept phase")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--traffic-threads", type=int, default=2)
    ap.add_argument("--instances-per-request", type=int, default=32)
    ap.add_argument("--quantize", choices=("bf16", "int8"), default=None,
                    help="freeze candidates straight to this precision")
    ap.add_argument("--amplify-x", type=int, default=1,
                    help="ftvec/amplify multi-epoch factor per batch")
    ap.add_argument("--freshness-p99-bound", type=float, default=20.0,
                    help="hard gate: event-weighted freshness p99 (s)")
    ap.add_argument("--min-publishes", type=int, default=3,
                    help="hard gate: gated publishes under traffic "
                         "(first publish + >= 2 hot-swaps)")
    ap.add_argument("--smoke", action="store_true",
                    help="small shape + hard gates; tier-1 in test.sh")
    args = ap.parse_args()

    dims = args.dims if args.dims is not None else (
        1 << 12 if args.smoke else 1 << 16)
    n_batches = args.batches if args.batches is not None else (
        96 if args.smoke else 256)

    import tempfile

    from hivemall_tpu.dataset.lr_datagen import DriftStream
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.pipeline import ContinuousPipeline, PipelineConfig
    from hivemall_tpu.pipeline.gate import score_metrics
    from hivemall_tpu.runtime.tracing import TRACER
    from hivemall_tpu.serving.server import ModelRegistry

    total_events = n_batches * args.batch
    # the injected regression: a full-cycle label-flip window, aligned to
    # the freeze cadence, in the middle of the run — the candidate frozen
    # at its end trained on poison only and must be refused
    flip_cycle = max(2, (total_events // args.freeze_every) // 2)
    flip = (flip_cycle * args.freeze_every,
            (flip_cycle + 1) * args.freeze_every)
    stream = DriftStream(dims, batch=args.batch, width=args.width,
                         seed=args.seed, drift_every=args.drift_every,
                         label_flip_events=flip)

    root = tempfile.mkdtemp(prefix="bench_pipeline_")
    registry = ModelRegistry(
        max_batch=64, max_delay_ms=2.0,
        engine_kwargs={"max_width": 32})
    cfg = PipelineConfig(
        artifact_root=root, dims=dims, rule=AROW, hyper={"r": 0.1},
        name="ctr", width=args.width,
        freeze_every_events=args.freeze_every,
        checkpoint_every_events=args.checkpoint_every,
        min_holdout_rows=64, quantize=args.quantize,
        amplify_x=args.amplify_x)
    # holdout ring reads CLEAN labels (the trusted-delayed-ground-truth
    # pattern): the label-flip window corrupts only what the trainer sees,
    # so the gate's refusal decision is a pure function of the seeds
    pipe = ContinuousPipeline(registry, stream.block, cfg,
                              holdout_stream_fn=stream.clean_block)

    # --- concurrent load: closed-loop traffic + a served-quality sampler -
    pool = _request_pool(stream, 256, args.instances_per_request,
                         seed=args.seed + 1)
    stop = threading.Event()
    counts = {"ok": 0, "failed": 0, "no_model": 0, "rows": 0}
    versions_served = set()
    errors = []
    clock = {"lock": threading.Lock()}

    def traffic(tid: int):
        rng = np.random.RandomState(args.seed * 7 + tid)
        while not stop.is_set():
            req = pool[rng.randint(len(pool))]
            try:
                entry, fut = registry.submit("ctr", req)
                if entry is None:
                    with clock["lock"]:
                        counts["no_model"] += 1
                    time.sleep(0.05)
                    continue
                preds = fut.result(timeout=30)
                assert len(preds) == len(req)
                with clock["lock"]:
                    counts["ok"] += 1
                    counts["rows"] += len(req)
                    versions_served.add(entry.version)
            except Exception as e:  # any failed in-flight request = gate 3
                with clock["lock"]:
                    counts["failed"] += 1
                    if len(errors) < 5:
                        errors.append(f"{type(e).__name__}: {e}")

    quality = []  # (elapsed_s, version, served logloss on current concept)

    def sampler():
        t0 = time.monotonic()
        while not stop.is_set():
            entry = registry.get("ctr")
            if entry is not None:
                ev = pipe.status()["events"]
                hi, hv, hl = stream.holdout(max(0, ev - 1), n=512,
                                            seed=args.seed + 5)
                try:
                    m = score_metrics(entry.engine, hi, hv, hl)
                    quality.append((round(time.monotonic() - t0, 2),
                                    entry.version,
                                    round(m["logloss"], 4)))
                except Exception:
                    pass  # engine mid-swap teardown: sample again next tick
            stop.wait(0.5)

    threads = [threading.Thread(target=traffic, args=(t,), daemon=True)
               for t in range(args.traffic_threads)]
    threads.append(threading.Thread(target=sampler, daemon=True))

    t_start = time.monotonic()
    pipe.start(n_batches)
    for t in threads:
        t.start()
    # the pipeline finishing ends the measured window; a hung publisher
    # must fail the gate, not wedge CI
    finished = pipe.join(timeout=900)
    stop.set()
    for t in threads:
        t.join(10)
    wall_s = time.monotonic() - t_start

    status = pipe.status()
    fresh = status["freshness"]
    swaps = max(0, len(status["published_versions"]) - 1)
    breakdown = TRACER.stage_breakdown()
    stages = {k for k in breakdown if k.startswith("pipeline.")}

    result = {
        "metric": f"pipeline_freshness_p99_s_arow_{dims}dims",
        "value": fresh["p99"],
        "unit": "seconds",
        "methodology": {
            "name": "continuous_training_freshness",
            "definition": "event observed -> the first model version "
                          "published after the pipeline processed it is "
                          "serving (gate-refused cycles keep accruing; a "
                          "quarantined window counts as "
                          "processed-by-discard)",
            "stream": "seeded piecewise-rotating concept drift + one "
                      "full-cycle label-flip window",
            "load": f"{args.traffic_threads} closed-loop traffic threads "
                    f"over registry.submit during the whole run",
            "weighting": "event-weighted exact percentiles over raw "
                         "per-batch samples",
        },
        "seed": args.seed,
        "events": status["events"],
        "batches": status["batches"],
        "wall_s": round(wall_s, 2),
        "freeze_every_events": args.freeze_every,
        "drift_every_events": args.drift_every,
        "label_flip_events": list(flip),
        "quantize": args.quantize,
        "device_set": _device_set(),
        "freshness": {
            "p50_s": fresh["p50"], "p99_s": fresh["p99"],
            "samples": status["freshness_samples"],
            "events_covered": status["freshness_events"],
        },
        "publisher": {
            "publishes": status["publishes"],
            "hot_swaps": swaps,
            "refusals": status["refusals"],
            "rollbacks": status["rollbacks"],
            "restarts": status["restarts"],
            "checkpoints_written": status["checkpoints_written"],
            "published_versions": status["published_versions"],
            "gate_decisions": [
                {k: d.get(k) for k in ("version", "published", "reason",
                                       "candidate_logloss",
                                       "incumbent_logloss",
                                       "holdout_rows")}
                for d in status["decisions"]],
        },
        "traffic": {
            "requests_ok": counts["ok"],
            "requests_failed": counts["failed"],
            "no_model_yet": counts["no_model"],
            "rows_scored": counts["rows"],
            "distinct_versions_served": sorted(versions_served,
                                               key=lambda v: int(v)),
            "errors": errors,
        },
        "served_logloss_over_time": quality[:: max(1, len(quality) // 50)],
        "tracing": {
            "pipeline_stages": sorted(stages),
            "stage_breakdown_ms": {k: v for k, v in breakdown.items()
                                   if k.startswith("pipeline.")},
        },
    }
    print(json.dumps(result))

    ok = True
    refused = [d for d in status["decisions"]
               if not d["published"] and d["reason"] == "regression"]
    if status["publishes"] < args.min_publishes or swaps < 2:
        print(f"bench_pipeline: FAIL — {status['publishes']} gated "
              f"publishes / {swaps} hot-swaps under traffic; need >= "
              f"{args.min_publishes} publishes incl. >= 2 swaps",
              file=sys.stderr)
        ok = False
    if not refused:
        print("bench_pipeline: FAIL — the injected label-flip regression "
              "was never refused by the eval gate", file=sys.stderr)
        ok = False
    if counts["failed"] or not counts["ok"]:
        print(f"bench_pipeline: FAIL — {counts['failed']} failed in-flight "
              f"requests across {swaps} hot-swaps ({counts['ok']} ok): "
              f"{errors}", file=sys.stderr)
        ok = False
    if fresh["p99"] is None or fresh["p99"] > args.freshness_p99_bound:
        print(f"bench_pipeline: FAIL — freshness p99 {fresh['p99']}s over "
              f"the {args.freshness_p99_bound}s bound", file=sys.stderr)
        ok = False
    if len(versions_served) < 2:
        print(f"bench_pipeline: FAIL — traffic observed only versions "
              f"{sorted(versions_served)}; hot-swaps did not reach live "
              "requests", file=sys.stderr)
        ok = False
    missing = REQUIRED_STAGES - stages
    if missing:
        print(f"bench_pipeline: FAIL — trace ring is missing pipeline "
              f"stages {sorted(missing)}", file=sys.stderr)
        ok = False
    if status["fatal"]:
        print(f"bench_pipeline: FAIL — pipeline died: {status['fatal']}",
              file=sys.stderr)
        ok = False
    if not finished:
        print("bench_pipeline: FAIL — pipeline did not finish inside the "
              "900s window", file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
