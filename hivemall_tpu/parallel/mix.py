"""Collective model mixing — the TPU-native replacement of the MIX subsystem.

The reference's MIX protocol (ref: SURVEY.md §2.18; mix/client/MixClient.java:48-173,
mixserv/.../MixServerHandler.java:54-158) is an asynchronous, feature-sharded
parameter server over Netty TCP: clients push (weight, covar, deltaUpdates)
when a feature's local update count crosses `mixThreshold`, servers keep
per-feature partial aggregates and push back the global mean when the clock
difference crosses `syncThreshold`.

Under synchronous SPMD on a TPU mesh the whole TCP path collapses into
collectives inside one jitted step:

- each device trains a full model replica on its data shard (the Hadoop-mapper
  analog), with per-feature update counts tracked since the last mix;
- every `mix_every` blocks, replicas are averaged over the mesh axis with one
  of the reference's two reduction operators:
    * `average`   — delta-weighted arithmetic mean
                    sum(w * delta) / sum(delta)          (ref: PartialAverage.java:43-67)
    * `argmin_kld` — precision-weighted mean
                    sum(w/cov) / sum(1/cov), cov' = 1/sum(1/cov)
                                                        (ref: PartialArgminKLD.java:43-63)
- features untouched on every replica keep their local value (the server never
  saw them — exact analog of threshold-gated pushes);
- the cancel/staleness machinery (MixClient.java:145-166) is unnecessary:
  synchronous collectives cannot observe stale contributions.

ICI carries the psum on-pod; multi-slice/multi-host runs get DCN collectives
from XLA with the same program (scaling-book recipe: mesh + shardings, let XLA
insert the collectives).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.engine import DELTA_SLOT, Rule, make_train_fn
from ..core.state import LinearState, init_linear_state
from .mesh import WORKER_AXIS, make_mesh
from ..runtime.jax_compat import shard_map
from ..runtime.tracing import (SCOPE_MIX, SCOPE_MIX_ALLREDUCE, SCOPE_MIX_APPLY,
                               SPAN_COMPILED_STEP, SPAN_DATA_PREP, SPAN_SYNC,
                               TRACER, TRAINER_MIX)


def _f32(x):
    """Tables may be stored compact (bfloat16 above 2^24 dims); a mix sums in
    float32 and rounds once at the write, as the step does."""
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


def mix_average(weights, delta_upd, axis_name: str = WORKER_AXIS):
    """Delta-weighted arithmetic mean across the mesh axis
    (ref: PartialAverage.java getWeight = scaledSumWeights/totalUpdates)."""
    with jax.named_scope(SCOPE_MIX_ALLREDUCE):
        total = jax.lax.psum(delta_upd, axis_name)
        wsum = jax.lax.psum(_f32(weights) * delta_upd, axis_name)
    with jax.named_scope(SCOPE_MIX_APPLY):
        mixed = (wsum / jnp.maximum(total, 1.0)).astype(weights.dtype)
        return jnp.where(total > 0.0, mixed, weights), total


def mix_argmin_kld(weights, covars, delta_upd, axis_name: str = WORKER_AXIS):
    """Precision-weighted (inverse-variance) mean across the mesh axis
    (ref: PartialArgminKLD.java:43-63, ensemble/ArgminKLDistanceUDAF.java:28-90)."""
    with jax.named_scope(SCOPE_MIX_ALLREDUCE):
        total = jax.lax.psum(delta_upd, axis_name)
        inv = 1.0 / _f32(covars)
        sum_inv = jax.lax.psum(inv, axis_name)
        sum_wdiv = jax.lax.psum(_f32(weights) * inv, axis_name)
    with jax.named_scope(SCOPE_MIX_APPLY):
        mixed_w = jnp.where(total > 0.0,
                            (sum_wdiv / sum_inv).astype(weights.dtype), weights)
        mixed_cov = jnp.where(total > 0.0,
                              (1.0 / sum_inv).astype(covars.dtype), covars)
    return mixed_w, mixed_cov, total


def grouped_mix_scan(local_body, mix, state, blocks, mix_every: int):
    """Consume `blocks` (a tuple of arrays, each [k, ...]) in groups of
    `mix_every`, training locally within a group and applying `mix` once per
    group — the sync-threshold semantic shared by every mix trainer (the
    server replies with the global average only when a feature's clock
    advanced >= syncThreshold, ref: MixServerHandler.java:142-148).

    local_body: (state, block_tuple) -> (state, loss)
    mix:        state -> state
    Returns (state, total_loss).
    """
    k = jax.tree.leaves(blocks)[0].shape[0]
    if k % mix_every != 0:
        raise ValueError(
            f"{k} blocks per device not divisible by mix_every={mix_every}")
    groups = jax.tree.map(
        lambda a: a.reshape((k // mix_every, mix_every) + a.shape[1:]), blocks)

    def group_body(s, grp):
        s, losses = jax.lax.scan(local_body, s, grp)
        return mix(s), jnp.sum(losses)

    state, losses = jax.lax.scan(group_body, state, groups)
    return state, jnp.sum(losses)


def merge_slot_arrays(slots: dict, touched_all: np.ndarray, kinds: dict,
                      drop: Tuple[str, ...] = ()) -> dict:
    """Merge per-replica optimizer-slot arrays ([n_dev, ...]) into one model
    per each slot's declared kind (Rule.slot_merge): "sum" for additive
    statistics over the replicas' disjoint data shards, "mean" (default) for
    decayed/averaged ones — weighted by which replicas actually touched each
    entry. Slots named in `drop` reset to zero (pending-delta counters).
    Shared by every trainer's final_state so no trainer silently keeps
    replica 0's slots (the bug class fixed for linear/FFM in round 2)."""
    tmask = touched_all.astype(np.float32)
    n_touch = np.maximum(tmask.sum(axis=0), 1.0)
    merged = {}
    for name, arr in slots.items():
        arr = np.asarray(arr)  # [n_dev, ...]
        if name in drop:
            merged[name] = np.zeros_like(arr[0])
            continue
        mask = tmask
        denom = n_touch
        # broadcast the touch mask over trailing axes (e.g. factor dims)
        while mask.ndim < arr.ndim:
            mask = mask[..., None]
            denom = denom[..., None]
        total = (arr * mask).sum(axis=0)
        if kinds.get(name, "mean") == "sum":
            merged[name] = total
        else:
            merged[name] = total / denom
    return merged


def replicate_state(one, n_replicas: int, mesh: Mesh, specs=None,
                    axis: str = WORKER_AXIS):
    """Give a single-model pytree a leading [n_replicas] axis, placed on the
    mesh. Default placement: replica axis sharded over `axis`, everything
    else replicated; pass `specs` (a pytree of PartitionSpec with the
    leading replica dim included) to additionally stripe trailing dims. One
    copy of the replicate-and-place init shared by every replicated trainer.

    Each device receives exactly its shard, cut from a zero-copy host
    broadcast of the one replica: the stacked [n_replicas, ...] tables never
    exist on any single device (broadcasting on the default device first
    put every replica's tables on chip 0 before the placement moved them)."""
    if specs is None:
        specs = jax.tree.map(
            lambda x: P(*((axis,) + (None,) * np.ndim(x))), one)

    def place(x, spec):
        host = np.asarray(x)
        shape = (n_replicas,) + host.shape
        stacked = np.broadcast_to(host[None], shape)  # a view, not a copy
        return jax.make_array_from_callback(
            shape, NamedSharding(mesh, spec), lambda index: stacked[index])

    return jax.tree.map(place, one, specs)


def split_replica_blocks(n_replicas: int, *arrays):
    """Host helper shared by the replicated trainers: split [R * k, B, ...]
    blocks into the [R, k, B, ...] layout."""
    nk = arrays[0].shape[0]
    k = nk // n_replicas
    if k * n_replicas != nk:
        raise ValueError(f"{nk} blocks not divisible by {n_replicas} replicas")
    return tuple(a.reshape((n_replicas, k) + a.shape[1:]) for a in arrays)


def mix_linear_replica(st: LinearState, reduction: str, axis: str):
    """One mix round of a LinearState replica inside shard_map: the
    delta-weighted average or argminKLD over `axis` of every feature with a
    pending update on any replica (the rest keep their local value: upstream's
    per-feature push gate, MixClient.java:117-142, is not reproduced), then
    the pending counts reset. Returns (state, entries that were due)."""
    delta = st.slots[DELTA_SLOT]
    with jax.named_scope(SCOPE_MIX):
        if reduction == "argmin_kld":
            w, cov, total = mix_argmin_kld(st.weights, st.covars, delta, axis)
            st = st.replace(weights=w, covars=cov)
        else:
            w, total = mix_average(st.weights, delta, axis)
            st = st.replace(weights=w)
        due = jnp.sum(total > 0.0, dtype=jnp.int32)
        st = st.replace(slots={**st.slots, DELTA_SLOT: jnp.zeros_like(delta)})
    return st, due


def make_linear_mix(reduction: str, axis: str):
    """The collective mix applied to a LinearState replica (see
    mix_linear_replica). Shared by the data-parallel MixTrainer and the
    replica axis of the 2-D (replicas x feature stripes) trainer."""

    def mix(st: LinearState) -> LinearState:
        return mix_linear_replica(st, reduction, axis)[0]

    return mix


def resolve_reduction(reduction: str, rule: Rule) -> str:
    """`auto`: argminKLD for covariance learners, the delta-weighted average
    else (the reference's event selection)."""
    if reduction == "auto":
        return "argmin_kld" if rule.use_covariance else "average"
    return reduction


def _welford_sub(nc, mc, m2c, n0, mu0, m20):
    """Chan-inverse: remove the base stream (n0, mu0, m20) from a combined
    (nc, mc, m2c), returning the local remainder — exact."""
    n_l = nc - n0
    if n_l <= 0:
        return 0.0, 0.0, 0.0
    mean_l = (mc * nc - mu0 * n0) / n_l
    m2_l = m2c - m20 - (n0 * n_l / nc) * (mean_l - mu0) ** 2
    return n_l, mean_l, max(m2_l, 0.0)


def _welford_add(n_a, mu_a, m2_a, n_b, mu_b, m2_b):
    """Chan parallel merge of two streams — exact."""
    n = n_a + n_b
    if n == 0:
        return 0.0, 0.0, 0.0
    delta = mu_b - mu_a
    mean = mu_a + delta * n_b / n
    m2 = m2_a + m2_b + delta * delta * n_a * n_b / n
    return n, mean, m2


def strip_replica_base(host: LinearState, base: LinearState,
                       slot_kinds: dict) -> LinearState:
    """Remove a warm-start base (the checkpoint every replica was seeded
    with) from each replica's ADDITIVE statistics, leaving only the local
    contributions, so a subsequent collapse_linear_replicas does not count
    the base once per replica: "sum"-kind slots and the step counter
    subtract the base per replica; Welford globals chan-subtract it. Mean
    -kind (EMA) slots stay — averaging seeded EMAs is their semantics.
    add_replica_base() restores the base once after the collapse."""
    b = jax.device_get(base)
    new_slots = dict(host.slots or {})
    for name, kind in slot_kinds.items():
        if kind == "sum" and name in new_slots and name in (b.slots or {}):
            new_slots[name] = np.asarray(new_slots[name]) \
                - np.asarray(b.slots[name])[None]
    gl = dict(host.globals or {})
    if {"n", "mean", "m2"} <= set(gl) and {"n", "mean", "m2"} <= set(
            b.globals or {}):
        n0 = float(np.asarray(b.globals["n"]))
        mu0 = float(np.asarray(b.globals["mean"]))
        m20 = float(np.asarray(b.globals["m2"]))
        ns, mus, m2s = [], [], []
        for r in range(np.asarray(gl["n"]).shape[0]):
            n_l, mu_l, m2_l = _welford_sub(
                float(np.asarray(gl["n"])[r]), float(np.asarray(gl["mean"])[r]),
                float(np.asarray(gl["m2"])[r]), n0, mu0, m20)
            ns.append(n_l)
            mus.append(mu_l)
            m2s.append(m2_l)
        gl = {**gl, "n": np.asarray(ns, np.float32),
              "mean": np.asarray(mus, np.float32),
              "m2": np.asarray(m2s, np.float32)}
    return host.replace(
        slots=new_slots,
        globals=gl,
        step=np.asarray(host.step) - int(np.asarray(b.step)),
    )


def add_replica_base(merged: LinearState, base: LinearState,
                     slot_kinds: dict) -> LinearState:
    """Restore the warm-start base ONCE into a collapsed model (see
    strip_replica_base)."""
    b = jax.device_get(base)
    new_slots = dict(merged.slots or {})
    for name, kind in slot_kinds.items():
        if kind == "sum" and name in new_slots and name in (b.slots or {}):
            new_slots[name] = np.asarray(new_slots[name]) \
                + np.asarray(b.slots[name])
    gl = dict(merged.globals or {})
    if {"n", "mean", "m2"} <= set(gl) and {"n", "mean", "m2"} <= set(
            b.globals or {}):
        n, mu, m2 = _welford_add(
            float(np.asarray(gl["n"])), float(np.asarray(gl["mean"])),
            float(np.asarray(gl["m2"])),
            float(np.asarray(b.globals["n"])),
            float(np.asarray(b.globals["mean"])),
            float(np.asarray(b.globals["m2"])))
        gl = {**gl, "n": np.float32(n), "mean": np.float32(mu),
              "m2": np.float32(m2)}
    step = np.asarray(merged.step) + int(np.asarray(b.step))
    return merged.replace(slots=new_slots, globals=gl,
                          step=step.astype(np.asarray(merged.step).dtype))


def collapse_linear_replicas(host: LinearState, slot_kinds: dict) -> LinearState:
    """Collapse a host-side LinearState whose leaves carry a leading replica
    axis into one model a warm restart can resume from (the mixed analog of
    -loadmodel, ref: LearnerBaseUDTF.java:215-333).

    - weights/covars: identical across replicas after the trailing mix —
      replica 0's copy IS the mixed model;
    - touched: max (union of features any replica updated);
    - optimizer slots: merged per the rule's declared kind over the replicas
      that touched each feature (merge_slot_arrays); the delta counter resets;
    - Welford globals (n, mean, m2): exact Chan parallel merge across the
      replicas' disjoint shards (ref: common/OnlineVariance.java); other
      globals keep replica 0's value.
    """
    merged = jax.tree.map(lambda x: x[0], host)
    touched_all = np.asarray(host.touched)
    merged = merged.replace(touched=np.max(touched_all, axis=0))

    if host.slots:
        merged = merged.replace(slots=merge_slot_arrays(
            host.slots, touched_all, slot_kinds, drop=(DELTA_SLOT,)))

    gl = {k: np.asarray(v) for k, v in host.globals.items()}  # [n_dev] each
    if {"n", "mean", "m2"} <= set(gl):
        n = gl["n"].astype(np.float64)
        tot = n.sum()
        if tot > 0:
            mean = float((gl["mean"] * n).sum() / tot)
            m2 = float(gl["m2"].sum()
                       + (n * (gl["mean"] - mean) ** 2).sum())
            merged = merged.replace(globals={
                **merged.globals,
                "n": np.float32(tot),
                "mean": np.float32(mean),
                "m2": np.float32(m2),
            })
    step_all = np.asarray(host.step)
    merged = merged.replace(step=step_all.sum().astype(step_all.dtype))
    return merged


@dataclass(frozen=True)
class MixConfig:
    # Mix after this many blocks — the sync-threshold analog: the reference's
    # server replies with the global average only when a feature's clock
    # advanced >= syncThreshold since the last reply
    # (ref: mixserv/.../MixServerHandler.java:142-148). Each step() call's
    # per-device blocks are consumed in groups of `mix_every`, with one
    # collective mix after each group.
    #
    # Cadence matters for covariance learners: every argminKLD mix REPLACES
    # the covariance with the combined precision 1/sum(1/cov) — the
    # reference's own reply semantics (PartialArgminKLD.java:43-63) — so
    # mixing after every block shrinks it ~n_dev-fold per block and freezes
    # the learner early. The reference's default effective cadence is tens
    # of updates between mixes (threshold 3 x syncThreshold 30); pick
    # mix_every on that order for argminKLD runs, not 1.
    mix_every: int = 1
    reduction: str = "auto"  # average | argmin_kld | auto (covariance -> argmin_kld,
    # mirroring the reference's event selection for covariance learners)
    axis_name: str = WORKER_AXIS


class MixTrainer:
    """Data-parallel trainer: N replicas on an N-device mesh with periodic
    collective mixing. The device axis is materialized as a leading [n_dev]
    axis on every state leaf, sharded over the mesh.
    """

    def __init__(self, rule: Rule, hyper: dict, dims: int, mesh: Optional[Mesh] = None,
                 config: MixConfig = MixConfig(), mode: str = "minibatch",
                 dtype=jnp.float32):
        self.rule = rule
        self.hyper = hyper
        self.dims = dims
        self.dtype = dtype  # the tables' storage (fit_linear: bf16 above 2^24)
        self.mesh = mesh if mesh is not None else make_mesh()
        self.config = config
        self.reduction = resolve_reduction(config.reduction, rule)
        self.n_dev = self.mesh.devices.size
        self._resume_base = None  # set by init(from_state=...) on warm restart
        axis = config.axis_name

        local_fn = make_train_fn(rule, hyper, mode=mode, track_deltas=True)

        mix_every = config.mix_every
        mix = make_linear_mix(self.reduction, axis)

        def device_step(state: LinearState, indices, values, labels):
            # state leaves carry a leading [1] device axis inside shard_map
            st = jax.tree.map(lambda x: x[0], state)

            def body(s, blk):
                s, loss = local_fn(s, *blk)
                return s, loss

            st, loss = grouped_mix_scan(
                body, mix, st, (indices[0], values[0], labels[0]), mix_every)
            loss_sum = jax.lax.psum(loss, axis)
            return jax.tree.map(lambda x: x[None], st), loss_sum

        spec_state = jax.tree.map(lambda _: P(self.config.axis_name),
                                  jax.eval_shape(self._init_abstract))
        self._step = jax.jit(
            shard_map(
                device_step,
                mesh=self.mesh,
                in_specs=(spec_state, P(axis), P(axis), P(axis)),
                out_specs=(spec_state, P()),
            ),
            donate_argnums=(0,),
        )

    def _init_abstract(self):
        return self._init_one()

    def _init_one(self) -> LinearState:
        return init_linear_state(
            self.dims,
            use_covariance=self.rule.use_covariance,
            slot_names=tuple(self.rule.slot_names) + (DELTA_SLOT,),
            global_names=self.rule.global_names,
            dtype=self.dtype,
        )

    def init(self, from_state: Optional[LinearState] = None) -> LinearState:
        """Replicated initial state with a leading device axis, sharded over
        the mesh. `from_state` seeds every replica from a collapsed
        single-model state (a final_state() result or an
        io/checkpoint.load_linear_state) — the elastic-restart path: resume
        the same model on whatever mesh size survives. Missing optimizer
        slots (e.g. the mix delta counter) fill with zeros; each replica
        resumes at the checkpoint's step/curvature so eta schedules
        continue. collapse_host()/final_state() strip the seeded base from
        each replica's ADDITIVE statistics (step counter, sum-kind slots,
        Welford globals) before merging and restore it once after, so
        nothing is counted n_dev times no matter how many checkpoint/resume
        cycles stack (strip_replica_base/add_replica_base)."""
        one = self._init_one()
        self._resume_base = None
        if from_state is not None:
            host = jax.device_get(from_state)
            if np.asarray(host.weights).shape[0] != self.dims:
                raise ValueError(
                    f"checkpoint has dims {np.asarray(host.weights).shape[0]}"
                    f" != trainer dims {self.dims}; resume with the dims the"
                    " model was trained at")
            self._resume_base = host
            have = dict(host.slots) if host.slots else {}
            one = one.replace(
                weights=jnp.asarray(host.weights),
                covars=(jnp.asarray(host.covars)
                        if one.covars is not None and host.covars is not None
                        else one.covars),
                slots={name: (jnp.asarray(have[name]) if name in have
                              else zero)
                       for name, zero in one.slots.items()},
                touched=jnp.asarray(host.touched),
                step=jnp.asarray(host.step),
                globals={name: (jnp.asarray(np.asarray(host.globals[name]))
                                if name in (host.globals or {}) else zero)
                         for name, zero in one.globals.items()},
            )
        return replicate_state(one, self.n_dev, self.mesh,
                               axis=self.config.axis_name)

    def step(self, state: LinearState, indices, values, labels):
        """One mixed step. indices/values/labels: [n_dev, k, B, ...] — each
        device consumes k blocks then the replicas mix. The dispatch runs
        under a ``train.compiled_step`` span: inside a driver's
        ``tracing.step_span`` it becomes the per-step timeline's
        compiled-step stage (runtime/tracing.py)."""
        with TRACER.span(SPAN_COMPILED_STEP, args={"trainer": TRAINER_MIX}):
            return self._step(state, indices, values, labels)

    def shard_blocks(self, indices, values, labels):
        """Host helper: split [n_dev * k, B, ...] host blocks into the
        [n_dev, k, B, ...] layout."""
        with TRACER.span(SPAN_DATA_PREP, args={"trainer": TRAINER_MIX}):
            return split_replica_blocks(self.n_dev, indices, values, labels)

    def collapse_host(self, host: LinearState) -> LinearState:
        """Collapse a host-side replicated state (see
        collapse_linear_replicas). For a warm-started run, every replica was
        seeded with the checkpoint's additive statistics (step, sum-kind
        slots, Welford globals); strip that base per replica before merging
        and restore it once after, so each statistic equals
        base + sum(local contributions) exactly."""
        kinds = dict(self.rule.slot_merge)
        base = getattr(self, "_resume_base", None)
        if base is not None:
            host = strip_replica_base(host, base, kinds)
        merged = collapse_linear_replicas(host, kinds)
        if base is not None:
            merged = add_replica_base(merged, base, kinds)
        return merged

    def final_state(self, state: LinearState) -> LinearState:
        """Collapse the device axis after the trailing mix into one model a
        warm restart can resume from — see collapse_host."""
        with TRACER.span(SPAN_SYNC, args={"trainer": TRAINER_MIX}):
            host = jax.device_get(state)
        return self.collapse_host(host)


def mix_devices():
    """The devices `-mix` trains one replica on each: this process's own."""
    return jax.local_devices()


def deal_rows(n_rows: int, n_replicas: int):
    """[(lo, hi)] of each replica's share of a call's rows: the mappers'
    splits laid end to end, `ceil(n / R)` rows each, the last one shorter."""
    each = -(-n_rows // n_replicas)
    return [(min(r * each, n_rows), min((r + 1) * each, n_rows))
            for r in range(n_replicas)]


def merge_slots_on_device(slots: dict, touched, kinds: dict, axis: str):
    """merge_slot_arrays inside shard_map: each rule slot summed ("sum") or
    averaged ("mean") over the replicas that touched the entry; the pending
    delta counter is dropped by the caller."""
    if not slots:
        return {}
    mask = touched.astype(jnp.float32)
    n_touch = jnp.maximum(jax.lax.psum(mask, axis), 1.0)
    merged = {}
    for name, arr in slots.items():
        total = jax.lax.psum(arr * mask, axis)
        merged[name] = total if kinds.get(name, "mean") == "sum" \
            else total / n_touch
    return merged


class MixedReplicas:
    """The `-mix` path of `fit_linear`: one model replica a device, the
    replicated local step and the mix as two programs whose shapes do not
    depend on how many rows a call brings, and a collapse that leaves ONE
    model on the first device (the tables of replica 0, which the trailing
    mix made equal on all; `touched` their union; the step counter and the
    rule's slots merged), so that `model_rows()` reads one model, on that
    device.

    Every state leaf is one global array with the replicas end to end
    (`[R * dims]` tables, `[R]` scalars), sharded over the mesh axis: inside
    shard_map a device sees its own `[dims]` replica, and a device's shard
    IS a single-device array of the model's shape, with no copy.
    """

    def __init__(self, rule: Rule, hyper: dict, dims: int, dtype, devices):
        self.rule, self.dims, self.dtype = rule, dims, dtype
        self.axis = axis = WORKER_AXIS
        self.mesh = make_mesh(devices=list(devices), axis_name=axis)
        self.n_dev = self.mesh.devices.size
        self.reduction = resolve_reduction("auto", rule)
        local_fn = make_train_fn(rule, hyper, mode="minibatch",
                                 track_deltas=True)
        pad_loss = _pad_row_loss(rule, hyper)
        kinds = dict(rule.slot_merge)

        one = jax.eval_shape(self._init_one)          # a replica's leaves
        merged_one = jax.eval_shape(lambda: self._init_one(False))

        def replica_step(state, indices, values, labels, n_real):
            st = _replica_in(state, one)
            t0 = st.step
            st, loss = local_fn(st, indices, values, labels)
            # rows that pad a share's last block up to the block's shape
            # write nothing (all their lanes are out of range): take them
            # back out of the row counter and of the loss
            real = n_real[0]
            st = st.replace(step=t0 + real)
            loss = loss - pad_loss * (indices.shape[0] - real)
            # each replica's own loss: the host sums them, and the step
            # holds no collective (the replicas meet only in the mix)
            return _replica_out(st), loss.reshape((1,))

        def mix_round(state):
            st, due = mix_linear_replica(_replica_in(state, one),
                                         self.reduction, axis)
            return _replica_out(st), due

        def collapse_replicas(state):
            st = _replica_in(state, one)
            slots = {k: v for k, v in st.slots.items() if k != DELTA_SLOT}
            st = st.replace(
                slots=merge_slots_on_device(slots, st.touched, kinds, axis),
                touched=jax.lax.pmax(st.touched, axis),
                step=jax.lax.psum(st.step, axis))
            return _replica_out(st)

        spec = P(axis)
        state_spec = jax.tree.map(lambda _: spec, one)
        merged_spec = jax.tree.map(lambda _: spec, merged_one)
        smap = partial(shard_map, mesh=self.mesh)
        self._state_spec = state_spec
        self.step = jax.jit(
            smap(replica_step, in_specs=(state_spec, spec, spec, spec, spec),
                 out_specs=(state_spec, spec)), donate_argnums=(0,))
        self.mix = jax.jit(
            smap(mix_round, in_specs=(state_spec,),
                 out_specs=(state_spec, P())), donate_argnums=(0,))
        merge = jax.jit(
            smap(collapse_replicas, in_specs=(state_spec,),
                 out_specs=merged_spec), donate_argnums=(0,))

        def collapse(state: LinearState) -> LinearState:
            """One model, on the first device: see the class's description."""
            return jax.tree.map(
                lambda x, a: x.addressable_shards[0].data.reshape(a.shape),
                merge(state), merged_one)

        # a caller that spans the dispatch (models/base.py::dispatch_spanned)
        # reads a fresh compile off the jit's own cache
        collapse._cache_size = merge._cache_size
        self.collapse = collapse

    def _init_one(self, delta_slot: bool = True, initial_weights=None,
                  initial_covars=None) -> LinearState:
        slots = tuple(self.rule.slot_names)
        return init_linear_state(
            self.dims, use_covariance=self.rule.use_covariance,
            slot_names=slots + ((DELTA_SLOT,) if delta_slot else ()),
            global_names=self.rule.global_names, dtype=self.dtype,
            initial_weights=initial_weights, initial_covars=initial_covars)

    def init(self, initial_weights=None, initial_covars=None) -> LinearState:
        """Every replica's fresh state, made on its own device (a warm start
        seeds each with the same weights and covariances)."""
        if initial_weights is None and initial_covars is not None:
            raise ValueError("initial covariances need initial weights")
        warm = [np.asarray(a) for a in (initial_weights, initial_covars)
                if a is not None]

        def init_replicas(*warm):
            return _replica_out(self._init_one(True, *warm))

        return jax.jit(shard_map(
            init_replicas, mesh=self.mesh, in_specs=(P(),) * len(warm),
            out_specs=self._state_spec))(*warm)


def _replica_in(state, one):
    """A device's view of the replicated state as one model shaped like
    `one`: scalars arrive as `[1]` slices of their `[R]` arrays."""
    return jax.tree.map(lambda x, a: x.reshape(a.shape), state, one)


def _replica_out(state):
    return jax.tree.map(lambda x: x.reshape((1,)) if x.ndim == 0 else x, state)


def _pad_row_loss(rule: Rule, hyper: dict) -> float:
    """The rule's loss on a row whose every lane is padding (score 0, label
    0): what one such row adds to a block's loss sum."""
    from ..core.engine import _row_ctx

    slots = {k: jnp.zeros((1,)) for k in tuple(rule.slot_names) + (DELTA_SLOT,)}
    cov = jnp.ones((1,)) if rule.use_covariance else None
    ctx = _row_ctx((jnp.zeros((1,)), cov, slots), jnp.asarray([1]),
                   jnp.zeros((1,)), jnp.float32(0.0), jnp.float32(1.0),
                   rule.use_covariance, {})
    return float(rule.update(ctx, hyper).loss)
