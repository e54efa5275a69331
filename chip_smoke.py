#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the full
width of one model the repo supports (AROW, 2^24 hashed dims, 32 nnz/row —
the reference's default capacity), in ONE process:

  train    hivemall_tpu.sql.get_function("train_arow") on seeded rows with
           planted labels; finite loss, state on the chip, held-out accuracy
           against the same call on this host's CPU backend at the same seed
  serve    freeze -> ModelRegistry.deploy (full warmup) -> serve; POST
           /predict over HTTP at several batch sizes and row widths, scores
           equal to model.predict; retrain, hot-swap to v2; /metrics shows
           zero post-warmup recompiles
  kernels  the optional update backend: -pallas refused in words at dims
           that cannot be VMEM-resident and compiled + matched where they
           can
  mesh     (>= 4 devices) MixTrainer / ShardedTrainer / Sharded2DTrainer at
           the same dims, ModelSharded(4) serving == single-device serving,
           sharded /topk == single-device /topk, per-device bytes spread.
           With fewer devices it is reported as not run, never as passed.

Any failed check raises: the exit code is non-zero and no result line is
printed. Without a TPU the script exits non-zero before doing anything, and
nothing makes it pass on a CPU; tests/test_chip_smoke.py calls the stage
functions directly at toy sizes instead.

The last two stdout lines are one JSON object each. The second to last,
{"report": {...}}, carries versions, stages passed and not run, per-stage
wall/compile/steady seconds, peak device bytes, native-library provenance and
the compile-cache directory. The last is the verdict and nothing else:
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}, the
device as jax reports it.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# the full-width run (main); tests pass their own toy sizes to the stages
FULL = {
    "dims": 1 << 24,
    "width": 32,
    "train_rows": 1 << 17,
    "holdout_rows": 1 << 14,
    "mini_batch": 1024,
    "iters": 2,
    "max_batch": 512,    # the ServingEngine default ladder: 7 batch buckets
    "max_width": 256,    # x 6 width buckets = 42 warmed programs
    # (rows, nnz) of each /predict request: every batch-bucket regime from
    # a single row to a request that chunks above max_batch, narrow to wide
    "request_shapes": ((1, 5), (3, 32), (40, 17), (200, 64), (512, 200),
                       (700, 32)),
    "pallas_dims": 1 << 16,
}
# |accuracy(chip) - accuracy(cpu)| on the held-out rows. Measured on the
# v5e at the full size (PR 21): the two backends differ only in scatter-add
# summation order, which moved 0 of 16,384 predictions; the bound allows
# 16 (0.1%).
ACCURACY_TOL = 1e-3
# the planted signal must have been learned at the full size
MIN_ACCURACY = 0.6
# served score vs model.predict on the same rows: the same f32 gather-dot in
# another batch bucket — products are summed in another order at most
SCORE_RTOL, SCORE_ATOL = 1e-5, 1e-5


class SmokeFailure(RuntimeError):
    """A check did not hold. Never caught: the run ends non-zero."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def say(*parts) -> None:
    print("[chip_smoke]", *parts, flush=True)


def require_tpu() -> dict:
    """The device block of the verdict line — or exit non-zero, naming what
    jax found, before anything else runs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke.py: no TPU: jax.devices() is {devs} (platform "
                 f"{devs[0].platform!r}); this script only passes on the "
                 f"chip")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# --- compile accounting -----------------------------------------------------


class CompileMeter:
    """Seconds this process spent tracing, lowering and compiling (or loading
    from the persistent cache), and how many compile requests the cache
    answered — read from jax's own monitoring events, so the entry points
    under test stay untouched."""

    def __init__(self) -> None:
        import jax.monitoring

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.cache_requests += 1
            elif event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

    def timed(self, fn, *args, **kwargs):
        """Run fn; returns (its result, {wall_s, compile_s, steady_s})."""
        c0, t0 = self.seconds, time.perf_counter()
        out = fn(*args, **kwargs)
        wall, comp = time.perf_counter() - t0, self.seconds - c0
        return out, {"wall_s": round(wall, 3), "compile_s": round(comp, 3),
                     "steady_s": round(wall - comp, 3)}


@contextlib.contextmanager
def _cpu_reference():
    """Run the body on this host's CPU backend without persisting what it
    compiles: jaxlib's CPU loader reports every cached CPU executable as
    built for another machine (~6 KB of stderr per program)."""
    import jax

    key = "jax_persistent_cache_min_compile_time_secs"
    prev = getattr(jax.config, key)
    jax.config.update(key, 1e9)
    try:
        with jax.default_device(jax.devices("cpu")[0]):
            yield
    finally:
        jax.config.update(key, prev)


# --- data -------------------------------------------------------------------


def planted_weights(rng, dims: int):
    """The sparse weight vector every split is labeled by (5% of features
    carry signal), so accuracy means something."""
    return (rng.randn(dims) * (rng.rand(dims) < 0.05)).astype(np.float32)


def make_rows(rng, n: int, dims: int, width: int, w_true=None, noise=0.3):
    """Seeded rows in the wire format ("feature:value" strings — what a Hive
    user and an HTTP client both send): log-uniform hash-placed ids
    (runtime/benchmark.make_workload_ids), |N(0,1)| values on a 1e-3 grid.
    Returns (rows, labels); labels are None without planted weights."""
    from hivemall_tpu.runtime.benchmark import make_workload_ids

    idx = make_workload_ids(rng, (n, width), dims)
    val = np.round(np.abs(rng.randn(n, width)), 3).astype(np.float32)
    rows = [[f"{i}:{v:.3f}" for i, v in zip(ri, rv)]
            for ri, rv in zip(idx.tolist(), val.tolist())]
    if w_true is None:
        return rows, None
    margin = np.einsum("nk,nk->n", val, w_true[idx])
    labels = np.where(margin + noise * np.std(margin) * rng.randn(n) > 0,
                      1, -1)
    return rows, labels


def _accuracy(scores, labels) -> float:
    return float(np.mean(np.where(np.asarray(scores) > 0, 1, -1) == labels))


# --- stage: train -----------------------------------------------------------


def stage_train(meter: CompileMeter, dims: int, train_rows: int,
                holdout_rows: int, width: int, mini_batch: int, iters: int,
                seed: int = 7, min_accuracy: float = MIN_ACCURACY):
    """The README "Use" call at the given size, then the same call on the
    CPU backend as the reference. Returns (report, model, data)."""
    import jax

    from hivemall_tpu.sql import get_function

    platform = jax.devices()[0].platform
    rng = np.random.RandomState(seed)
    w_true = planted_weights(rng, dims)
    rows, labels = make_rows(rng, train_rows, dims, width, w_true)
    hold_rows, hold_labels = make_rows(rng, holdout_rows, dims, width, w_true)
    options = f"-dims {dims} -mini_batch {mini_batch} -iters {iters}"
    train_arow = get_function("train_arow")

    def fit():
        model = train_arow(rows, labels, options)
        jax.block_until_ready(model.state)
        return model

    model, timing = meter.timed(fit)
    steps = int(model.state.step) // mini_batch
    check(int(model.state.step) == train_rows * iters,
          f"train: step counter {int(model.state.step)} != "
          f"{train_rows} rows x {iters} epochs")
    on = {d.platform for leaf in jax.tree.leaves(model.state)
          for d in leaf.devices()}
    check(on == {platform},
          f"train: state arrays live on {on}, expected {{{platform!r}}}")
    w = np.asarray(model.state.weights)
    cov = np.asarray(model.state.covars)
    check(np.isfinite(w).all() and np.isfinite(cov).all(),
          "train: non-finite weights/covariances")
    check(np.count_nonzero(w) > 0, "train: no weight moved")

    scores = model.predict(hold_rows)
    check(scores.shape == (holdout_rows,) and np.isfinite(scores).all(),
          f"train: held-out scores not finite [{holdout_rows}]")
    hinge = float(np.mean(np.maximum(0.0, 1.0 - hold_labels * scores)))
    acc = _accuracy(scores, hold_labels)
    check(np.isfinite(hinge), "train: held-out loss not finite")

    def fit_cpu():
        with _cpu_reference():
            ref = train_arow(rows, labels, options)
            return ref.predict(hold_rows)

    ref_scores, ref_timing = meter.timed(fit_cpu)
    ref_acc = _accuracy(ref_scores, hold_labels)
    check(abs(acc - ref_acc) <= ACCURACY_TOL,
          f"train: held-out accuracy {acc:.5f} on {platform} vs "
          f"{ref_acc:.5f} on the CPU backend (tolerance {ACCURACY_TOL})")
    check(acc >= min_accuracy,
          f"train: held-out accuracy {acc:.4f} is below {min_accuracy}")
    report = {
        "fit": timing, "steps": steps, "rows": train_rows * iters,
        "state_platform": sorted(on), "holdout_loss": round(hinge, 5),
        "holdout_accuracy": round(acc, 5),
        "cpu_holdout_accuracy": round(ref_acc, 5),
        "max_abs_score_delta_vs_cpu": float(
            np.max(np.abs(scores - ref_scores))),
        "cpu_reference_wall_s": ref_timing["wall_s"],
    }
    data = {"rows": rows, "labels": labels, "hold_rows": hold_rows,
            "seed": seed}
    return report, model, data


# --- stage: serve -----------------------------------------------------------


def _post(port: int, path: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _recompiles(port: int, name: str) -> float:
    """The serving recompile counter for `name`, read off /metrics."""
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
    key = f"hivemall_tpu_graftcheck_recompiles_serving_{name} "
    lines = [ln for ln in text.splitlines() if ln.startswith(key)]
    check(len(lines) == 1, f"serve: /metrics has no {key.strip()} counter")
    return float(lines[0].rsplit(" ", 1)[1])


def _predict_requests(port, name, version, model, dims, shapes, rng,
                      tag) -> list:
    """POST one /predict per (rows, nnz) shape; every answer must carry the
    expected version and equal model.predict on the same rows."""
    out = []
    for n, width in shapes:
        rows, _ = make_rows(rng, n, dims, width)
        t0 = time.perf_counter()
        resp = _post(port, "/predict", {"model": name, "instances": rows})
        dt = time.perf_counter() - t0
        check(resp["version"] == version,
              f"{tag}: /predict answered version {resp['version']!r}, "
              f"expected {version!r}")
        got = np.asarray(resp["predictions"], np.float32)
        want = model.predict(rows)
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"{tag}: /predict {n}x{width} returned shape {got.shape}")
        check(np.allclose(got, want, rtol=SCORE_RTOL, atol=SCORE_ATOL),
              f"{tag}: /predict {n}x{width} scores differ from "
              f"model.predict by {float(np.max(np.abs(got - want))):.3g}")
        out.append({"rows": n, "nnz": width, "seconds": round(dt, 4),
                    "max_abs_delta": float(np.max(np.abs(got - want)))})
    return out


def stage_serve(meter: CompileMeter, model, data: dict, dims: int,
                mini_batch: int, max_batch: int, max_width: int,
                request_shapes) -> dict:
    """freeze -> deploy (warmup on) -> serve -> /predict over HTTP ->
    retrain -> hot swap -> /predict again -> zero recompiles."""
    from hivemall_tpu.serving import ModelRegistry, freeze, serve
    from hivemall_tpu.sql import get_function

    rng = np.random.RandomState(data["seed"] + 1)
    name = "ctr"
    with tempfile.TemporaryDirectory(prefix="chip_smoke_artifacts_") as root:
        _, t_freeze = meter.timed(freeze, model, os.path.join(root, "1"),
                                  name=name, version="1")
        registry = ModelRegistry(max_batch=max_batch,
                                 engine_kwargs={"max_width": max_width})
        server = None
        try:
            entry, t_deploy = meter.timed(registry.deploy, name,
                                          os.path.join(root, "1"))
            engine = entry.engine
            n_buckets = len(engine.batch_buckets()) \
                * len(engine.width_buckets())
            check(len(engine.warmed_buckets) == n_buckets,
                  f"serve: warmed {len(engine.warmed_buckets)} of "
                  f"{n_buckets} buckets")
            server = serve(registry)
            port = server.server_address[1]
            v1 = _predict_requests(port, name, "1", model, dims,
                                   request_shapes, rng, "serve v1")

            # retrain (one epoch fewer -> different weights) and hot-swap,
            # as examples/serve_ctr.py does
            def retrain_and_swap():
                m2 = get_function("train_arow")(
                    data["rows"], data["labels"],
                    f"-dims {dims} -mini_batch {mini_batch} -iters 1")
                freeze(m2, os.path.join(root, "2"), name=name, version="2")
                registry.deploy(name, os.path.join(root, "2"))
                return m2

            model2, t_swap = meter.timed(retrain_and_swap)
            check(not np.array_equal(np.asarray(model2.state.weights),
                                     np.asarray(model.state.weights)),
                  "serve: v2 has v1's weights; the swap would prove nothing")
            v2 = _predict_requests(port, name, "2", model2, dims,
                                   request_shapes[:3], rng, "serve v2")
            recompiles = _recompiles(port, name)
            check(recompiles == 0.0,
                  f"serve: {recompiles} post-warmup recompiles on /metrics")
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            registry.shutdown()
    return {
        "freeze": t_freeze, "deploy": t_deploy, "swap": t_swap,
        "warmed_buckets": n_buckets, "requests": v1 + v2,
        "post_warmup_recompiles": recompiles,
        "table_bytes": engine.table_bytes,
    }


# --- stage: kernels ---------------------------------------------------------


def stage_kernels(dims: int, pallas_dims: int, width: int,
                  seed: int = 11, pallas_interpret: bool = False) -> dict:
    """The optional update backend compiles and matches, or is refused in
    words before reaching the compiler. It is not timed for a claim."""
    from hivemall_tpu.kernels.linear_scan import vmem_resident_reason
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.sql import get_function

    rng = np.random.RandomState(seed)
    train_arow = get_function("train_arow")
    report: dict = {}

    # -pallas through fit_linear, at a width that is VMEM-resident
    rows, labels = make_rows(rng, 512, pallas_dims, width,
                             planted_weights(rng, pallas_dims))
    m_ref = train_arow(rows, labels, f"-dims {pallas_dims}")
    m_pal = train_arow(rows, labels, f"-dims {pallas_dims} -pallas",
                       pallas_interpret=pallas_interpret)
    for what, got, want in (("weights", m_pal.state.weights,
                             m_ref.state.weights),
                            ("covars", m_pal.state.covars,
                             m_ref.state.covars)):
        check(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                          atol=1e-5),
              f"kernels: -pallas {what} differ from the scan backend at "
              f"{pallas_dims} dims")
    report["pallas"] = {"dims": pallas_dims, "rows": len(rows),
                        "interpret": pallas_interpret,
                        "matches_scan": True}
    # ... and refused, with the arithmetic, where it is not
    if vmem_resident_reason(AROW, dims) is not None:
        try:
            train_arow(rows[:8], labels[:8], f"-dims {dims} -pallas",
                       pallas_interpret=pallas_interpret)
        except ValueError as e:
            report["pallas"]["refused_at_smoke_dims"] = str(e)
        else:
            raise SmokeFailure(f"kernels: -pallas at {dims} dims was not "
                               f"refused")
    return report


# --- stage: mesh ------------------------------------------------------------


def _device_bytes(devices) -> list:
    """bytes_in_use per device, or None where the backend reports none."""
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return [int(s["bytes_in_use"]) for s in stats]


def _check_spread(tag: str, before, after, report: dict) -> None:
    """What a trainer's init allocated must be spread over the devices, not
    stacked on device 0."""
    if after is None:
        report[f"{tag}_bytes_in_use"] = "not reported by this backend"
        return
    delta = [a - b for a, b in zip(after, before)]
    say(f"mesh: {tag} init bytes_in_use per device: {after} "
        f"(allocated by init: {delta})")
    report[f"{tag}_bytes_in_use"] = after
    report[f"{tag}_init_bytes"] = delta
    check(min(delta) > 0,
          f"mesh: {tag} init left a device without state: {delta}")
    check(max(delta) <= 1.25 * min(delta),
          f"mesh: {tag} init is not spread over the devices: {delta}")


def stage_mesh(dims: int, width: int, mini_batch: int, n_devices: int = 4,
               max_batch: int = 64, max_width: int = 32,
               catalog_items: int = 1024, seed: int = 13) -> dict:
    """The scale-out layer on real chips: the three linear trainers at the
    smoke's dims, then sharded serving and sharded /topk against their
    single-device twins. Runs BEFORE the single-chip stages so device 0's
    peak is this stage's own (a replicated init that staged every replica on
    device 0 shows up there)."""
    import jax

    from hivemall_tpu.core.engine import make_train_step
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.base import TrainedLinearModel
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.models.mf import train_mf_sgd
    from hivemall_tpu.parallel import MixConfig, MixTrainer, make_mesh
    from hivemall_tpu.parallel.mesh import make_mesh_2d
    from hivemall_tpu.parallel.sharded_train import (Sharded2DTrainer,
                                                     ShardedTrainer)
    from hivemall_tpu.runtime.benchmark import make_workload_ids
    from hivemall_tpu.serving import (ModelRegistry, ModelSharded, freeze,
                                      serve)

    check(n_devices >= 4 and n_devices % 2 == 0,
          f"mesh: needs an even device count >= 4, got {n_devices}")
    devices = jax.devices()[:n_devices]
    rng = np.random.RandomState(seed)
    w_true = planted_weights(rng, dims)
    hyper = {"r": 0.1}
    report: dict = {"devices": n_devices}

    def blocks(n_blocks):
        idx = make_workload_ids(rng, (n_blocks, mini_batch, width), dims)
        val = np.round(np.abs(rng.randn(n_blocks, mini_batch, width)),
                       3).astype(np.float32)
        margin = np.einsum("nbk,nbk->nb", val, w_true[idx])
        return idx, val, np.where(margin > 0, 1.0, -1.0).astype(np.float32)

    def finite_model(tag, state):
        w = np.asarray(state.weights)
        check(w.shape == (dims,) and np.isfinite(w).all()
              and np.count_nonzero(w) > 0,
              f"mesh: {tag} final weights bad (shape {w.shape})")

    # 1. data-parallel replicas with collective mixing
    k = 2  # blocks per device per mixed step
    mix = MixTrainer(AROW, hyper, dims=dims, mesh=make_mesh(n_devices))
    before = _device_bytes(devices)
    state = mix.init()
    jax.block_until_ready(state)
    after = _device_bytes(devices)
    _check_spread("mix", before, after, report)
    if after is not None:
        peak0 = int(devices[0].memory_stats()["peak_bytes_in_use"])
        replica = after[1] - before[1]
        report["mix_init_peak_device0"] = peak0
        check(peak0 <= 2.5 * replica,
              f"mesh: replicated init peaked at {peak0} bytes on device 0 "
              f"for a {replica}-byte replica — every replica was staged "
              f"there")
    per_step = n_devices * k
    mi, mv, ml = blocks(3 * per_step)
    for s in range(3):
        at = slice(s * per_step, (s + 1) * per_step)
        state, loss = mix.step(state, *mix.shard_blocks(mi[at], mv[at], ml[at]))
        check(np.isfinite(float(loss)), "mesh: mix loss not finite")
    final = mix.final_state(state)
    finite_model("mix", final)
    report["mix"] = {"steps": 3, "blocks_per_step": per_step,
                     "loss": float(loss)}
    # the same rows through the normal path: `train_arow -mix`, each
    # replica's blocks laid end to end as its contiguous share, a mix after
    # every block as the hand-built trainer's default cadence has it
    from hivemall_tpu.runtime.tracing import TRACER
    from hivemall_tpu.sql.registry import get_function

    order = [s * per_step + r * k + j for r in range(n_devices)
             for s in range(3) for j in range(k)]
    entry = get_function("train_arow")(
        (list(mi[order].reshape(-1, width)), list(mv[order].reshape(-1, width))),
        ml[order].reshape(-1),
        f"-dims {dims} -mini_batch {mini_batch} -mix smoke -mix_threshold 1")
    call = next(sp["args"] for sp in TRACER.traces()[-1]["spans"]
                if sp["name"] == "train.call")
    check(call.get("replicas") == n_devices,
          f"mesh: train_arow -mix trained {call.get('replicas')} replica(s), "
          f"the hand-built trainer {n_devices}")
    check(int(entry.state.step) == 3 * per_step * mini_batch,
          f"mesh: train_arow -mix counted {int(entry.state.step)} rows")
    for what in ("weights", "covars", "touched"):
        a = np.asarray(getattr(entry.state, what), np.float32)
        b = np.asarray(getattr(final, what), np.float32)
        check(np.allclose(a, b, rtol=1e-4, atol=1e-5),
              f"mesh: train_arow -mix {what} differ from MixTrainer's by "
              f"{float(np.max(np.abs(a - b))):.3g}")
    report["mix"]["entry_point_matches"] = True
    del entry
    mixed_model = TrainedLinearModel(
        state=jax.device_put(final), rule=AROW, dims=dims,
        block_width=width)
    del state, mix
    gc.collect()

    # 2. one model striped over the mesh == the single-device step
    sharded = ShardedTrainer(AROW, hyper, dims, make_mesh(n_devices))
    before = _device_bytes(devices)
    s_state = sharded.init()
    jax.block_until_ready(s_state)
    _check_spread("sharded", before, _device_bytes(devices), report)
    ref_step = make_train_step(AROW, hyper, mode="minibatch")
    r_state = init_linear_state(dims, use_covariance=True)
    bi, bv, bl = blocks(3)
    for i in range(3):
        s_state, s_loss = sharded.step(s_state, bi[i], bv[i], bl[i])
        r_state, r_loss = ref_step(r_state, bi[i], bv[i], bl[i])
    s_final = sharded.final_state(s_state)
    finite_model("sharded", s_final)
    for what in ("weights", "covars"):
        a = np.asarray(getattr(s_final, what))
        b = np.asarray(getattr(r_state, what))
        check(np.allclose(a, b, rtol=1e-4, atol=1e-5),
              f"mesh: sharded {what} differ from the single-device step by "
              f"{float(np.max(np.abs(a - b))):.3g}")
    check(abs(float(s_loss) - float(r_loss))
          <= 1e-4 * max(1.0, abs(float(r_loss))),
          f"mesh: sharded loss {float(s_loss)} vs {float(r_loss)}")
    report["sharded"] = {"steps": 3, "loss": float(s_loss),
                         "matches_single_device": True}
    del s_state, r_state, sharded
    gc.collect()

    # 3. replicas x feature stripes
    n_rep, n_shard = 2, n_devices // 2
    t2d = Sharded2DTrainer(AROW, hyper, dims, make_mesh_2d(n_rep, n_shard),
                           config=MixConfig(mix_every=2))
    before = _device_bytes(devices)
    state2d = t2d.init()
    jax.block_until_ready(state2d)
    _check_spread("sharded2d", before, _device_bytes(devices), report)
    for _ in range(2):
        bi, bv, bl = blocks(n_rep * k)
        state2d, loss2d = t2d.step(
            state2d, bi.reshape((n_rep, k) + bi.shape[1:]),
            bv.reshape((n_rep, k) + bv.shape[1:]),
            bl.reshape((n_rep, k) + bl.shape[1:]))
        check(np.isfinite(float(loss2d)), "mesh: 2-D loss not finite")
    finite_model("sharded2d", t2d.final_state(state2d))
    report["sharded2d"] = {"mesh": [n_rep, n_shard], "steps": 2,
                           "loss": float(loss2d)}
    del state2d, t2d
    gc.collect()

    # 4. the mixed model served model-sharded == served single-device, and
    #    a sharded /topk == the single-device /topk
    n_users = 64
    u = rng.randint(0, n_users, 8000)
    it = rng.randint(0, catalog_items, 8000)
    u[-1], it[-1] = n_users - 1, catalog_items - 1
    mf = train_mf_sgd(u, it, rng.rand(8000) * 4 + 1,
                      "-factor 8 -iter 2 -disable_cv")
    retrieval = {"k": 8, "block_items": 128, "max_batch": 4}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_mesh_") as root:
        freeze(mixed_model, os.path.join(root, "ctr"), name="ctr",
               version="1")
        registry = ModelRegistry(max_batch=max_batch,
                                 engine_kwargs={"max_width": max_width})
        server = None
        try:
            registry.deploy("ctr1", os.path.join(root, "ctr"))
            before = _device_bytes(devices)
            e4 = registry.deploy("ctr4", os.path.join(root, "ctr"),
                                 placement=ModelSharded(n_devices))
            _check_spread("serving_sharded", before, _device_bytes(devices),
                          report)
            registry.deploy("rec1", mf, version="1", retrieval=retrieval)
            registry.deploy("rec4", mf, version="1", retrieval=retrieval,
                            placement=ModelSharded(n_devices))
            server = serve(registry)
            port = server.server_address[1]
            worst = 0.0
            for n, w in ((1, 5), (9, width), (max_batch, max_width)):
                rows, _ = make_rows(rng, n, dims, w)
                one = np.asarray(_post(port, "/predict", {
                    "model": "ctr1", "instances": rows})["predictions"])
                four = np.asarray(_post(port, "/predict", {
                    "model": "ctr4", "instances": rows})["predictions"])
                want = mixed_model.predict(rows)
                worst = max(worst, float(np.max(np.abs(four - one))))
                check(np.allclose(four, one, rtol=SCORE_RTOL,
                                  atol=SCORE_ATOL)
                      and np.allclose(one, want, rtol=SCORE_RTOL,
                                      atol=SCORE_ATOL),
                      f"mesh: sharded /predict {n}x{w} differs from "
                      f"single-device by {worst:.3g}")
            queries = [0, 3, 17, n_users - 1]
            top1 = _post(port, "/topk", {"model": "rec1",
                                         "queries": queries})["results"]
            top4 = _post(port, "/topk", {"model": "rec4",
                                         "queries": queries})["results"]
            for a, b in zip(top4, top1):
                check(a["items"] == b["items"]
                      and np.allclose(a["scores"], b["scores"], atol=1e-5),
                      f"mesh: sharded /topk differs: {a} vs {b}")
            for nm in ("ctr1", "ctr4"):
                check(_recompiles(port, nm) == 0.0,
                      f"mesh: {nm} recompiled after warmup")
        finally:
            if server is not None:
                server.shutdown()
                server.server_close()
            registry.shutdown()
    report["serving"] = {
        "placement": e4.engine.placement,
        "per_device_table_bytes": e4.engine.per_device_table_bytes,
        "max_abs_delta_vs_single_device": worst,
        "topk_queries": len(queries), "topk_matches": True,
    }
    return report


# --- main -------------------------------------------------------------------


def _versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    out = {"python": sys.version.split()[0], "jax": jax.__version__,
           "jaxlib": jaxlib.__version__}
    try:
        out["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        out["libtpu"] = None
    return out


def emit_result(device: dict, report: dict) -> None:
    """The run's last two stdout lines, printed only when every stage
    passed: the measurements, then the verdict — exactly {"ok", "device"},
    nothing after it."""
    print(json.dumps({"report": report}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def main() -> int:
    t_start = time.perf_counter()
    device = require_tpu()
    try:
        from hivemall_tpu import native
        from hivemall_tpu.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        sys.exit(f"chip_smoke.py: cannot import the repo from {REPO}: {e}")
    import jax

    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    say(f"device {device}; compile cache at {cache_dir}")

    build = native.build_info()
    check(build["loaded"],
          f"native library not loaded: {build['load_error']}")
    say(f"native library {build['path']}: "
        + (f"built here at first use ({build['built_at_first_use']})"
           if build["built_at_first_use"] else "this host's build")
        + f"; stamp {build['stamp']}")

    s = FULL
    stages: dict = {}
    not_run: dict = {}
    if device["count"] >= 4:
        rep, timing = meter.timed(stage_mesh, s["dims"], s["width"],
                                  s["mini_batch"], n_devices=4)
        stages["mesh"] = {**timing, **rep}
        say(f"mesh ok {timing}")
    else:
        not_run["mesh"] = f"{device['count']} device"
        say(f"mesh not run: {device['count']} device")

    # the stage's own wall also holds data generation and the CPU
    # reference; the chip's train_arow call is timed apart as "fit"
    (rep, model, data), timing = meter.timed(
        stage_train, meter, s["dims"], s["train_rows"], s["holdout_rows"],
        s["width"], s["mini_batch"], s["iters"])
    stages["train"] = {**timing, **rep}
    say(f"train ok: {rep['steps']} steps, accuracy "
        f"{rep['holdout_accuracy']} (cpu {rep['cpu_holdout_accuracy']}), "
        f"fit {rep['fit']}")

    rep, timing = meter.timed(stage_serve, meter, model, data, s["dims"],
                              s["mini_batch"], s["max_batch"],
                              s["max_width"], s["request_shapes"])
    stages["serve"] = {**timing, **rep}
    say(f"serve ok: {rep['warmed_buckets']} buckets, "
        f"{len(rep['requests'])} requests, deploy {rep['deploy']}, "
        f"swap {rep['swap']}")
    del model, data
    gc.collect()

    rep, timing = meter.timed(stage_kernels, s["dims"], s["pallas_dims"],
                              s["width"])
    stages["kernels"] = {**timing, **rep}
    say(f"kernels ok {timing}")

    stats = jax.devices()[0].memory_stats() or {}
    emit_result(device, {
        "versions": _versions(),
        "stages_passed": sorted(stages),
        "stages_not_run": not_run,
        "stages": stages,
        "compile_s_total": round(meter.seconds, 3),
        "wall_s_total": round(time.perf_counter() - t_start, 3),
        "compile_cache": {"dir": cache_dir,
                          "requests": meter.cache_requests,
                          "hits": meter.cache_hits},
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "native_library": build,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
