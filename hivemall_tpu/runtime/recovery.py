"""Failure detection + elastic restart — the fault-tolerance story.

The reference's fault tolerance is thin by design (SURVEY.md §5): MIX
clients reconnect dead channels on the next send (MixClient.java:134-137),
server sessions expire by TTL, cancel messages retract a failed task's
contributions (AbstractPredictionModel.java:88-118), and everything else is
delegated to Hadoop task retry — a failed mapper is simply rerun and the
surviving tasks' model rows are what the final ensemble averages.

Under synchronous SPMD the failure unit is the JOB, not a task: a dead
process breaks the collectives, the step errors, and recovery is
restart-from-checkpoint on whatever topology survives. That is strictly
stronger than the reference's story (which loses the failed mapper's entire
contribution since its close() never runs): here the periodic checkpoint of
the MIXED model preserves every replica's averaged-in work up to the last
mix. The cancel machinery is unnecessary — a checkpoint never contains a
partial, retractable contribution.

Elastic checkpoints cover EVERY trainer family, not just the data-parallel
MixTrainer: the on-disk form is always the COLLAPSED, stripe-free model (a
final_state() result) plus a manifest recording the striping metadata the
run had (family, dims, dims_padded, n_shards, stripe, rule/hyper, step) and
a sha256 digest over the payload (io/checkpoint.save_elastic). Resume
re-stripes N→M through core.striping.restripe — unpad at the old
``stripe*N`` grid, re-pad at the new mesh's ``stripe'*M``, re-place with
NamedSharding — so a run checkpointed on 4 devices resumes bit-compatibly
on 2 or 8.

Usage (manual driver loop):

    trainer, state = elastic_resume(AROW, {"r": 0.1}, dims, "ckpt.npz",
                                    family="sharded", mesh=mesh)
    while blocks:
        state, loss = trainer.step(state, *next_blocks)
        if step % k == 0:
            checkpoint(trainer, state, "ckpt.npz")

Or let ``run_elastic`` drive: it catches distributed step failure (a worker
vanishing kills the job under synchronous SPMD), rebuilds the mesh over the
surviving devices, resumes from the last valid checkpoint, and replays the
steps since — zero mixed work lost since the last checkpoint. Restarts are
visible in Perfetto: each resume runs under a ``recovery.restore`` span and
the fault harness stamps ``fault.injected`` instants (docs/
elastic_training.md).

# graftcheck: serving-module
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
import warnings
from dataclasses import asdict, is_dataclass
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from ..core.engine import Rule
from ..io.checkpoint import (PREV_SUFFIX, load_elastic, load_linear_state,
                             pack_linear_state, save_elastic,
                             unpack_linear_state)
from ..parallel.mesh import make_mesh
from ..parallel.mix import MixConfig, MixTrainer
from . import faults
from .tracing import TRACER

FAMILIES = ("mix", "sharded", "sharded_2d", "fm_sharded", "ffm_sharded")

# Linear backoff between elastic restarts (sleep = backoff * restarts,
# capped at 1 s): a persistently failing step must not burn the whole
# max_restarts budget in microseconds or hammer a failing device at CPU
# speed (graftcheck G031).
RESTART_BACKOFF_S = 0.02


def _hyper_jsonable(hyper) -> object:
    """Best-effort record of the run's hyperparameters for the manifest —
    documentation, not the resume source (the caller re-supplies rule/hyper
    exactly as elastic_resume always required)."""
    if is_dataclass(hyper) and not isinstance(hyper, type):
        hyper = asdict(hyper)
    try:
        json.dumps(hyper)
        return hyper
    except TypeError:  # graftcheck: disable=G028 (hyper is documentation: repr is the documented conversion)
        if isinstance(hyper, dict):
            return {k: v if _is_jsonable(v) else repr(v)
                    for k, v in hyper.items()}
        return repr(hyper)


def _is_jsonable(v) -> bool:
    try:
        json.dumps(v)
        return True
    except TypeError:
        return False


# --- family adapters ---------------------------------------------------------
# One (collapse+pack, unpack+resume) pair per trainer family. The pack side
# always goes through the trainer's OWN final_state() so the on-disk form is
# the same collapsed model a cold export would produce; the resume side goes
# through the trainer's init(from_state=...) which re-stripes via
# core.striping.restripe.


def _family_of(trainer) -> str:
    from ..parallel.sharded_train import (FFMShardedTrainer, FMShardedTrainer,
                                          Sharded2DTrainer, ShardedTrainer)

    if isinstance(trainer, MixTrainer):
        return "mix"
    if isinstance(trainer, ShardedTrainer):
        return "sharded"
    if isinstance(trainer, Sharded2DTrainer):
        return "sharded_2d"
    if isinstance(trainer, FMShardedTrainer):
        return "fm_sharded"
    if isinstance(trainer, FFMShardedTrainer):
        return "ffm_sharded"
    raise TypeError(f"no elastic-checkpoint support for {type(trainer)}")


def _pack_fm_state(host) -> dict:
    from ..io.checkpoint import np_saveable

    return {
        "w0": np.asarray(host.w0), "w": np_saveable(host.w),
        "v": np_saveable(host.v),
        "lambda_w0": np.asarray(host.lambda_w0),
        "lambda_w": np.asarray(host.lambda_w),
        "lambda_v": np.asarray(host.lambda_v),
        "touched": np.asarray(host.touched), "step": np.asarray(host.step),
    }


def _unpack_fm_state(arrays):
    import jax.numpy as jnp

    from ..models.fm import FMState

    f32 = jnp.float32
    return FMState(
        w0=jnp.asarray(arrays["w0"], f32), w=jnp.asarray(arrays["w"], f32),
        v=jnp.asarray(arrays["v"], f32),
        lambda_w0=jnp.asarray(arrays["lambda_w0"], f32),
        lambda_w=jnp.asarray(arrays["lambda_w"], f32),
        lambda_v=jnp.asarray(arrays["lambda_v"], f32),
        touched=jnp.asarray(arrays["touched"], jnp.int8),
        step=jnp.asarray(arrays["step"], jnp.int32),
    )


def _pack_ffm_state(host) -> dict:
    from ..io.checkpoint import np_saveable

    return {
        "w0": np.asarray(host.w0), "w": np_saveable(host.w),
        "z": np.asarray(host.z), "n": np.asarray(host.n),
        "v": np_saveable(host.v), "v_gg": np.asarray(host.v_gg),
        "touched": np.asarray(host.touched),
        "v_touched": np.asarray(host.v_touched),
        "step": np.asarray(host.step),
    }


def _unpack_ffm_state(arrays):
    import jax.numpy as jnp

    from ..models.ffm import FFMState

    if "v_touched" not in arrays:
        raise ValueError(
            "this FFM checkpoint was written before PR 32 (FFMState has no "
            "v_touched table in it): its untouched V entries are another "
            "draw than models/ffm.py::initial_v, so emission and blobs "
            "could not reproduce it; train again (docs/migration.md)")
    f32 = jnp.float32
    return FFMState(
        w0=jnp.asarray(arrays["w0"], f32), w=jnp.asarray(arrays["w"], f32),
        z=jnp.asarray(arrays["z"], f32), n=jnp.asarray(arrays["n"], f32),
        v=jnp.asarray(arrays["v"], f32),
        v_gg=jnp.asarray(arrays["v_gg"], f32),
        touched=jnp.asarray(arrays["touched"], jnp.int8),
        v_touched=jnp.asarray(arrays["v_touched"], jnp.int8),
        step=jnp.asarray(arrays["step"], jnp.int32),
    )


def _striping_manifest(trainer, family: str) -> dict:
    """The re-stripe metadata block: what grid the run was on. Resume does
    NOT need it to rebuild (the new trainer derives its own grid from the
    new mesh) — it needs it to validate dims and to make a degraded round
    attributable from the artifact alone."""
    m = {"family": family}
    for attr in ("dims", "dims_padded", "stripe", "n_shards", "n_replicas",
                 "stripe_w", "stripe_v", "nf_padded", "dv_padded"):
        if hasattr(trainer, attr):
            m[attr] = int(getattr(trainer, attr))
    if hasattr(trainer, "mesh"):
        m["n_devices"] = int(trainer.mesh.devices.size)
    if family == "sharded":
        m["n_shards"] = int(trainer.mesh.devices.size)
    if family == "mix":
        m["n_replicas"] = int(trainer.n_dev)
    rule = getattr(trainer, "rule", None)
    if rule is not None:
        m["rule"] = getattr(rule, "name", repr(rule))
    m["hyper"] = _hyper_jsonable(getattr(trainer, "hyper", None))
    return m


def checkpoint(trainer, state, path: str,
               block_step: Optional[int] = None) -> dict:
    """Atomically persist the COLLAPSED (mixed, replica-free, stripe-free)
    model — the form any future mesh size can resume from — plus a manifest
    with striping metadata and a payload digest (io/checkpoint.save_elastic:
    write-then-rename, previous checkpoint rotated to ``.prev``). Covers
    every trainer family: MixTrainer, ShardedTrainer, Sharded2DTrainer,
    FMShardedTrainer, FFMShardedTrainer. ``block_step`` is the driver's
    completed-step count — run_elastic resumes its data stream there.

    Under multi-process jax (mix family) this is a COLLECTIVE: every
    process must call it (the global state is not addressable from one
    process; an allgather brings it to every host), and only process 0
    writes the file."""
    import jax

    family = _family_of(trainer)
    manifest = _striping_manifest(trainer, family)
    if block_step is not None:
        manifest["block_step"] = int(block_step)

    if family == "mix" and jax.process_count() > 1:
        from jax.experimental import multihost_utils

        host = multihost_utils.process_allgather(state, tiled=True)
        if jax.process_index() == 0:
            merged = trainer.collapse_host(host)
            manifest["step"] = int(np.asarray(merged.step))
            manifest = save_elastic(path, pack_linear_state(merged), manifest)
        # trailing barrier: no process may act on "checkpoint written"
        # (e.g. tear the job down for an elastic downscale) until the
        # write+rename actually completed on process 0
        multihost_utils.sync_global_devices("hivemall_tpu_checkpoint")
        return manifest

    merged = trainer.final_state(state)
    # the COLLAPSED model's step counter (a resumed replicated run's
    # per-replica counters each carry the seeded base; the collapse strips
    # it and restores it once — summing raw leaves would over-count)
    manifest["step"] = int(np.asarray(merged.step))
    if family in ("mix", "sharded", "sharded_2d"):
        arrays = pack_linear_state(merged)
    elif family == "fm_sharded":
        arrays = _pack_fm_state(merged)
    else:
        arrays = _pack_ffm_state(merged)
    return save_elastic(path, arrays, manifest)


def peek_manifest(path: str) -> Optional[dict]:
    """The newest valid checkpoint's manifest (falling back to ``.prev``
    like the resume path does), or None when no usable checkpoint exists."""
    try:
        _, manifest = load_elastic(path)
        return manifest
    except Exception:  # graftcheck: disable=G028 (peek probe: None is the documented no-usable-checkpoint answer)
        return None


def _load_for_resume(path: str, family: str):
    """(state, manifest) from the newest valid checkpoint, or (None, None)
    when no checkpoint exists yet (cold start). Legacy pre-manifest
    checkpoints (a bare save_linear_state npz) still resume for the linear
    families. A valid checkpoint whose manifest names a different family
    or dims is a hard error — resuming an FM run into a linear trainer
    silently would be worse than crashing."""
    from ..io.checkpoint import NotElasticCheckpoint

    if not (os.path.exists(path) or os.path.exists(path + ".prev")):
        return None, None
    try:
        arrays, manifest = load_elastic(path)
    except NotElasticCheckpoint:
        # legacy format: a bare save_linear_state npz, no embedded
        # manifest. The NotElasticCheckpoint may have surfaced from the
        # ``.prev`` half of load_elastic's fallback (corrupt elastic
        # newest rotated over a legacy previous) — so the newest itself
        # can still be unreadable: fall back to the legacy .prev, loudly.
        if family not in ("mix", "sharded", "sharded_2d"):
            raise
        try:
            return load_linear_state(path), None
        except Exception as e:
            prev = path + PREV_SUFFIX
            if not os.path.exists(prev):
                raise
            warnings.warn(
                f"elastic checkpoint {path} is unusable ({e}); falling "
                f"back to the previous legacy checkpoint {prev} — work "
                "since that checkpoint will be replayed", RuntimeWarning,
                stacklevel=3)
            return load_linear_state(prev), None
    except FileNotFoundError:
        return None, None
    ck_family = manifest.get("family")
    linear = ("mix", "sharded", "sharded_2d")
    compatible = (ck_family == family
                  or (ck_family in linear and family in linear))
    if not compatible:
        raise ValueError(f"checkpoint {path} holds a {ck_family!r}-family "
                         f"model; cannot resume it as {family!r}")
    if family in linear:
        return unpack_linear_state(arrays), manifest
    if family == "fm_sharded":
        return _unpack_fm_state(arrays), manifest
    return _unpack_ffm_state(arrays), manifest


def elastic_resume(rule: Optional[Rule], hyper, dims: int, path: str,
                   mesh=None, config: MixConfig = MixConfig(),
                   mode: str = "minibatch", family: str = "mix",
                   **trainer_kwargs) -> Tuple[object, object]:
    """Build a trainer of ``family`` over the CURRENT mesh (whatever
    jax.devices() — or the passed mesh — says survives) and seed it from
    the checkpoint at ``path`` if a valid one exists, else from zeros.
    Returns (trainer, state).

    Families: ``mix`` (data-parallel MixTrainer — rule/hyper/dims/config),
    ``sharded`` (feature-striped ShardedTrainer), ``sharded_2d`` (replicas
    x stripes — pass a 2-D mesh or n_replicas/n_shards kwargs),
    ``fm_sharded`` (hyper is an FMHyper; rule ignored), ``ffm_sharded``
    (hyper is an FFMHyper; rule and dims ignored). The sharded families
    re-stripe the checkpoint N→M for whatever device count the new mesh
    has, including non-divisible dims (the stripe grid re-pads)."""
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; one of {FAMILIES}")
    state, manifest = _load_for_resume(path, family)
    if manifest is not None and "dims" in manifest \
            and family != "ffm_sharded" and int(manifest["dims"]) != dims:
        raise ValueError(
            f"checkpoint {path} was trained at dims {manifest['dims']} != "
            f"requested {dims}; resume with the dims the model was trained "
            "at")

    if family == "mix":
        trainer = MixTrainer(rule, hyper, dims, mesh, config, mode=mode)
    else:
        from ..parallel.sharded_train import (FFMShardedTrainer,
                                              FMShardedTrainer,
                                              Sharded2DTrainer,
                                              ShardedTrainer)

        if family == "sharded":
            trainer = ShardedTrainer(rule, hyper, dims, mesh, mode=mode,
                                     **trainer_kwargs)
        elif family == "sharded_2d":
            trainer = Sharded2DTrainer(rule, hyper, dims, mesh, config=config,
                                       mode=mode, **trainer_kwargs)
        elif family == "fm_sharded":
            trainer = FMShardedTrainer(hyper, dims, mesh, mode=mode,
                                       **trainer_kwargs)
        else:
            trainer = FFMShardedTrainer(hyper, mesh, mode=mode,
                                        **trainer_kwargs)
    # the manifest this resume actually loaded (None on cold start or a
    # legacy checkpoint) — run_elastic reads it instead of re-loading and
    # re-hashing the whole payload just to learn block_step
    trainer._elastic_manifest = manifest
    return trainer, trainer.init(from_state=state)


# --- the elastic driver loop -------------------------------------------------

_PEEK = object()  # "factory did not come through elastic_resume" sentinel


def run_elastic(make_trainer: Callable[[Sequence], Tuple[object, object]],
                data_fn: Callable[[object, int], tuple], n_steps: int,
                path: str, *, checkpoint_every: int = 8,
                max_restarts: int = 4,
                devices: Optional[Sequence] = None,
                recoverable: Optional[Tuple[type, ...]] = None,
                min_devices: int = 1) -> Tuple[object, object, dict]:
    """Worker-loss-tolerant driver: run ``n_steps`` training steps with a
    checkpoint every ``checkpoint_every``, and on ANY recoverable step
    failure rebuild over the surviving devices and resume from the last
    valid checkpoint, replaying the steps since it (zero mixed work lost
    since the last checkpoint).

    - ``make_trainer(devices) -> (trainer, state)``: build the family over
      a mesh on exactly these devices and seed from ``path`` — typically a
      closure over elastic_resume(..., mesh=make_mesh(devices=devices)).
      A ``faults.WorkerLost`` shrinks the device list before the rebuild
      (the simulated fleet); any other recoverable error retries the same
      topology.
    - ``data_fn(trainer, i) -> step-args tuple`` for driver step ``i`` —
      the deterministic data stream; after a restart it is replayed from
      the checkpoint's ``block_step``.

    Recovery is traced: each rebuild runs under a ``recovery.restore``
    span (device count, resumed step in args) inside the run's
    ``recovery.run_elastic`` root, and injected faults stamp
    ``fault.injected`` instants — a restart is visible in Perfetto as a
    restore span sandwiched between step spans.

    **Preemption-aware**: for the duration of the run a SIGTERM handler is
    installed (main thread only — elsewhere the signal module refuses and
    the run proceeds without it). On SIGTERM the in-flight step finishes,
    the state checkpoints IMMEDIATELY — not at the next cadence boundary —
    and the driver returns early with ``report["preempted"] = True`` and
    ``report["preempted_at_step"]``, so a preempted pod loses zero
    completed steps and the next ``run_elastic`` on whatever hardware
    replaces it resumes from the exact step the eviction interrupted (the
    cloud-preemption half of elastic training; cadence checkpoints only
    bound the loss from UNANNOUNCED failures). The previous handler is
    restored on exit.

    Returns ``(trainer, state, report)``; the report carries restarts,
    per-restart causes, lost (replayed) steps, checkpoints written, and
    recovery seconds — the numbers scripts/bench_chaos.py publishes."""
    import jax

    if recoverable is None:
        recoverable = (faults.WorkerLost, faults.TransientStepError,
                       faults.CrashMidWrite)
    devices = list(devices if devices is not None else jax.devices())
    report = {"restarts": 0, "causes": [], "lost_steps": 0,
              "checkpoints_written": 0, "recovery_s": 0.0,
              "preempted": False,
              "initial_devices": len(devices), "final_devices": len(devices)}
    term = threading.Event()
    prev_handler = None
    if threading.current_thread() is threading.main_thread():
        try:
            prev_handler = signal.signal(
                signal.SIGTERM, lambda signum, frame: term.set())
        except ValueError:  # exotic embeddings where signal still refuses
            prev_handler = None
    try:
        return _run_elastic_loop(make_trainer, data_fn, n_steps, path,
                                 checkpoint_every, max_restarts, devices,
                                 recoverable, min_devices, report, term)
    finally:
        if prev_handler is not None:
            signal.signal(signal.SIGTERM, prev_handler)


def _run_elastic_loop(make_trainer, data_fn, n_steps, path, checkpoint_every,
                      max_restarts, devices, recoverable, min_devices,
                      report, term):
    with TRACER.span("recovery.run_elastic",
                     args={"n_steps": int(n_steps), "path": path}):
        while True:
            t0 = time.monotonic()
            with TRACER.span("recovery.restore",
                             args={"devices": len(devices)}) as sp:
                trainer, state = make_trainer(devices)
                # elastic_resume stashed the manifest it loaded; fall back
                # to a peek only for factories that build trainers some
                # other way
                manifest = getattr(trainer, "_elastic_manifest", _PEEK)
                if manifest is _PEEK:
                    manifest = peek_manifest(path)
                start = int((manifest or {}).get("block_step", 0))
                if manifest is not None and "block_step" not in manifest \
                        or manifest is None and (
                            os.path.exists(path)
                            or os.path.exists(path + PREV_SUFFIX)):
                    warnings.warn(
                        f"checkpoint at {path} carries no block_step — "
                        "run_elastic will replay the data stream from step "
                        "0 on top of the seeded state (examples applied "
                        "twice). Stamp checkpoints via run_elastic or "
                        "checkpoint(..., block_step=...) to resume the "
                        "stream where it stopped", RuntimeWarning,
                        stacklevel=2)
                if sp is not None and hasattr(sp, "args"):
                    sp.args["resumed_step"] = start
            if report["restarts"] or report["checkpoints_written"]:
                report["recovery_s"] += time.monotonic() - t0
            last_ckpt = start
            completed = start  # steps whose update landed this attempt
            try:
                for i in range(start, n_steps):
                    faults.step_hook(i)
                    with TRACER.span("train.step", args={"step": i}):
                        state, loss = trainer.step(state, *data_fn(trainer, i))
                    completed = i + 1
                    if term.is_set():
                        # SIGTERM landed: checkpoint the completed step NOW
                        # instead of waiting for the cadence, then hand
                        # control back so the process can exit inside its
                        # grace period — the next run_elastic resumes here
                        checkpoint(trainer, state, path, block_step=i + 1)
                        report["checkpoints_written"] += 1
                        report["preempted"] = True
                        report["preempted_at_step"] = i + 1
                        report["final_devices"] = len(devices)
                        TRACER.instant("recovery.preempted",
                                       args={"step": i + 1})
                        return trainer, state, report
                    if (i + 1) % checkpoint_every == 0:
                        checkpoint(trainer, state, path, block_step=i + 1)
                        report["checkpoints_written"] += 1
                        last_ckpt = i + 1
                if n_steps % checkpoint_every != 0 or n_steps == 0:
                    checkpoint(trainer, state, path, block_step=n_steps)
                    report["checkpoints_written"] += 1
                report["final_devices"] = len(devices)
                return trainer, state, report
            except recoverable as e:
                report["restarts"] += 1
                # every completed-but-not-checkpointed step gets replayed
                report["lost_steps"] += max(0, completed - last_ckpt)
                report["causes"].append(
                    {"type": type(e).__name__, "step": completed,
                     "devices": len(devices)})
                if report["restarts"] > max_restarts:
                    # supervisor give-up: drop the flight-recorder bundle
                    # next to the checkpoint before re-raising (the crash
                    # postmortem artifact; write_crash_bundle never
                    # raises, so the fatal exception stays the signal)
                    from .debug_bundle import write_crash_bundle

                    write_crash_bundle(
                        path + ".crash_bundle.json",
                        reason=(f"run_elastic gave up after "
                                f"{report['restarts']} restarts (last "
                                f"cause: {type(e).__name__}: {e}; "
                                f"devices={len(devices)}, "
                                f"step={completed})"))
                    raise
                time.sleep(min(RESTART_BACKOFF_S * report["restarts"], 1.0))
                if isinstance(e, faults.WorkerLost):
                    survivors = devices[: max(min_devices,
                                              len(devices) - e.n_lost)]
                    if len(survivors) == len(devices) \
                            and len(devices) > min_devices:
                        survivors = devices[:-1]
                    devices = survivors
                TRACER.instant("recovery.restart",
                               args={"cause": type(e).__name__,
                                     "devices": len(devices)})


def make_elastic_mesh(devices: Sequence, n_devices: Optional[int] = None):
    """The default mesh rebuild for run_elastic closures: a 1-D mesh over
    exactly the surviving devices (parallel/mesh.make_mesh)."""
    return make_mesh(n_devices=n_devices, devices=list(devices))
