"""The batched update engine shared by every hashed-feature linear learner.

The reference's hot loop is `process(row) -> train -> model.set(feature, ...)`
(ref: BinaryOnlineClassifierUDTF.java:111-247). On TPU that becomes, per
FeatureBlock [B, K]:

- **scan mode** — `lax.scan` over the B rows; each row gathers its K touched
  slots, computes the rule's closed-form update, scatter-adds the deltas.
  Bit-faithful to the reference's sequential semantics (used for parity tests
  and small models).
- **minibatch mode** — one vectorized gather [B, K], the rule vmapped over
  rows against the *stale* batch-start weights, deltas averaged per feature
  and applied once. This is exactly the reference's own
  documented mini-batch semantic (ref: RegressionBaseUDTF.java:236-295 +
  utils/lang/FloatAccumulator.java:38-41: accumulate per-feature deltas over
  the batch, apply sum/count once), and is the TPU hot path: one big gather +
  vectorized math + one big scatter. The reference only routes weight-only
  regressors through its mini-batch path (covariance learners override
  train() around it); here every rule supports it — a documented superset,
  with batch size 1 exactly equal to scan mode.

Padding protocol (see core/batch.py): pad index == dims is out-of-range, so
gathers use mode='fill' and scatters mode='drop' — no mask tensors anywhere.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from ..ops.scatter import (RunWrite, reduce_block_runs,
                           write_runs_together)
from ..runtime.tracing import (SCOPE_APPLY, SCOPE_GATHER, SCOPE_PACK_TABLES,
                               SCOPE_REDUCE, SCOPE_RULE, SCOPE_TOUCHED)
from .state import LinearState


@struct.dataclass
class RowContext:
    """Everything a rule sees for one row (gathered, padded lanes are 0)."""

    w: jnp.ndarray  # [K] current weights
    cov: Optional[jnp.ndarray]  # [K] current covariance (None if unused)
    slots: Dict[str, jnp.ndarray]  # [K] optimizer aux
    val: jnp.ndarray  # [K] feature values (0 on padding)
    y: jnp.ndarray  # [] label (+-1 or target)
    score: jnp.ndarray  # [] sum(w * val)
    sq_norm: jnp.ndarray  # [] sum(val^2)
    variance: jnp.ndarray  # [] sum(cov * val^2) (0 if no covariance)
    t: jnp.ndarray  # [] float 1-based example counter
    globals: Dict[str, jnp.ndarray] = struct.field(default_factory=dict)  # scalar running stats


@struct.dataclass
class RuleOutput:
    dw: jnp.ndarray  # [K] additive weight delta
    loss: jnp.ndarray  # [] per-row loss contribution
    updated: jnp.ndarray  # [] bool/float — did the rule fire (for touched/deltas)
    dcov: Optional[jnp.ndarray] = None  # [K] additive covariance delta
    dslots: Dict[str, jnp.ndarray] = struct.field(default_factory=dict)


@dataclass(frozen=True)
class Rule:
    """A learner's closed-form per-row update.

    `update(ctx, hyper) -> RuleOutput`. If `derive_w` is set, weights are a
    pure function of the slots (dual-averaging learners like AdaGradRDA):
    after slot deltas are applied the engine recomputes w at touched lanes
    (ref: AdaGradRDAUDTF.java:112-142 where w is rebuilt from u, G, t).
    """

    name: str
    update: Callable[[RowContext, dict], RuleOutput]
    use_covariance: bool = False
    slot_names: Tuple[str, ...] = ()
    derive_w: Optional[Callable[[Dict[str, jnp.ndarray], jnp.ndarray, dict], jnp.ndarray]] = None
    # `block_slots(sums) -> increments`: what ONE -mini_batch block adds to
    # each slot, from the per-feature sums of its fired lanes' `dslots`
    # (DERIVED_W_BLOCK_RULE, for a rule with `derive_w`). Without it a block
    # adds the sums themselves.
    block_slots: Optional[Callable[[Dict[str, jnp.ndarray]], Dict[str, jnp.ndarray]]] = None
    # Scalar running stats threaded through training (e.g. Welford target
    # variance, ref: regression/PassiveAggressiveRegressionUDTF.java preTrain).
    # `pre_row(globals, y) -> globals` runs before each row in scan mode;
    # `pre_batch(globals, labels) -> globals` merges a whole block in
    # minibatch mode (rules then see the post-merge values).
    global_names: Tuple[str, ...] = ()
    pre_row: Optional[Callable] = None
    pre_batch: Optional[Callable] = None
    # loss used for convergence accounting only
    is_regression: bool = False
    # How each optimizer slot merges across data-parallel replicas when a
    # mixed model is collapsed to one (MixTrainer.final_state): "sum" for
    # additive per-example statistics (AdaGrad G accumulators — replicas saw
    # disjoint shards, so the union stream's sum is the sum of per-shard
    # sums), "mean" for decayed/EMA statistics (AdaDelta). Unlisted slots
    # default to "mean" over the replicas that touched the feature.
    slot_merge: Tuple[Tuple[str, str], ...] = ()
    # Batch-aware variant of `update`: same closed form applied to a whole
    # minibatch context at once (ctx fields carry a leading [B] axis —
    # w/cov/val [B, K], y/score/sq_norm/variance/t [B]) with the row-axis
    # broadcasts written out explicitly. Optional: rules without one run
    # the per-row update under vmap (identical math; the explicit form
    # exists because the batched backend is the CPU hot path and the
    # traced program stays smaller without the vmap batching pass).
    batch_update: Optional[Callable[["RowContext", dict], "RuleOutput"]] = None


def _gather(table: jnp.ndarray, idx: jnp.ndarray, fill: float = 0.0) -> jnp.ndarray:
    return table.at[idx].get(mode="fill", fill_value=fill)


def _row_ctx(state_tables, idx, val, y, t, use_cov, globals_=None, packed=None):
    weights, covars, slots = state_tables
    if packed is not None:
        # w+cov interleaved as a [D,2] table: ONE pair-row gather costs the
        # same as ONE scalar gather on v5e (0.5 ms per 65536 ids at 2^22
        # dims), which pays for stacking a SMALL table every block; at
        # 2^28 dims the stack alone was 20 ms of a 52 ms step, so only the
        # dense strategy packs (apply_strategy). The pair fill is 0.0;
        # cov's fill is 1.0 (fresh variance), restored on the pad lanes.
        pairs = packed.at[idx].get(mode="fill", fill_value=0.0)
        w = pairs[..., 0]
        oob = (idx < 0) | (idx >= weights.shape[0])
        cov = jnp.where(oob, 1.0, pairs[..., 1])
    else:
        w = _gather(weights, idx)
        cov = _gather(covars, idx, fill=1.0) if use_cov else None
    sl = {k: _gather(v, idx) for k, v in slots.items()}
    score = jnp.sum(w * val)
    sq_norm = jnp.sum(val * val)
    variance = jnp.sum(cov * val * val) if use_cov else jnp.zeros(())
    return RowContext(w, cov, sl, val, y, score, sq_norm, variance, t, globals_ or {})


DELTA_SLOT = "__delta_upd"  # per-feature update count since the last mix —
# the TPU analog of DenseModel's deltaUpdates byte array (ref: DenseModel.java:52)


# The -mini_batch step applies a block in one of two ways, chosen when the
# step is traced, from shapes alone. "batch_local": reduce the block's
# deltas in the block's own index space and write the touched entries in
# place, whatever the table's length. "dense": sum them into zeroed [dims]
# tables and pass over the whole table, with w and cov packed for one pair
# gather: ten passes that cost less than the sort, the scans and the second
# gather while the table is small. On a v5e with a [1024, 64] block the two
# meet at 2^24 dims (3.65 against 3.80 ms a step), "dense" is ahead by 12-36%
# at 2^22 and 75% at 2^20, "batch_local" by 2.2x at 2^26 and 5.5x at 2^28
# (PERF.md section 6, PR 27).
DENSE_APPLY_BELOW = 256  # table entries per block lane


# DERIVED_W_BLOCK_RULE. How a block of B rows updates a rule whose weights
# are a function of its slots (`Rule.derive_w`: AdaGradRDA). Every row is
# decided against the block's starting weights, and the block is ONE
# subgradient: per feature, S = the sum of the block's fired lanes' deltas.
# `Rule.block_slots` turns those sums into the block's one increment of each
# slot (AdaGradRDA: u += S, G += S^2, where a sum of rows would give
# G += sum of squares); w is derived from the updated slots where a fired
# row carries the feature and kept elsewhere. It is mini-batch dual
# averaging as published (the batch's mean gradient g = S / B is one step:
# u += g, G += g^2, t += 1; Dekel, Gilad-Bachrach, Shamir, Xiao, JMLR 2012;
# Duchi, Hazan, Singer, JMLR 2011) written in sums: multiply u by B, G by
# B^2 and t by B and `derive_w` gives the same weight. So `derive_w` sees
# the ROW counter at the block's end, `t0 + B`: the state's one counter
# stays rows x epochs, lambda keeps its per-row meaning (|u| / rows against
# lambda, as under the row rule), and B = 1 is upstream's row rule bit for
# bit. A weight keeps the t of its last firing block. Summing the squares
# instead (the step before PR 34) hands a feature that every row carries B
# steps at once with no word from the loss in between: at B = 1024 the
# model is a coin (PERF.md section 4). Upstream updates this learner a row
# at a time, so results differ from upstream's for the same SQL wherever
# B > 1. Slots of a rule without `block_slots` (adagrad_regr,
# adadelta_regr: their dw is already meaned) and -mix's pending count keep
# their sums. The same rule in core/batch_update.py (-batch) and, through
# this step, in the stripes of parallel/sharded_train.py.


def apply_strategy(dims: int, lanes: int) -> str:
    """Which way `minibatch_step` applies a block of `lanes` = B x K lanes
    to tables of `dims` entries (a stripe's length inside shard_map)."""
    return "dense" if dims < DENSE_APPLY_BELOW * lanes else "batch_local"


def make_batch_update(rule: Rule, hyper: dict):
    """Batch-aware application of a Rule: one call over a whole minibatch.

    Returns `apply(w, cov, sl, val, y, ts, gl) -> RuleOutput` where w/cov/
    val are [B, K], sl maps slot name -> [B, K], y/ts are [B] and gl is the
    rule's scalar globals dict. Uses `rule.batch_update` when the rule
    ships an explicit batch form, else vmaps the per-row update — the two
    are the same closed form, pinned equal by tests/test_batch_update.py.
    """
    use_cov = rule.use_covariance

    if rule.batch_update is not None:
        def apply(w, cov, sl, val, y, ts, gl):
            score = jnp.sum(w * val, axis=-1)
            sq_norm = jnp.sum(val * val, axis=-1)
            variance = jnp.sum(cov * val * val, axis=-1) if use_cov \
                else jnp.zeros_like(score)
            ctx = RowContext(w, cov, sl, val, y, score, sq_norm, variance,
                             ts, gl)
            return rule.batch_update(ctx, hyper)

        return apply

    def apply(w, cov, sl, val, y, ts, gl):
        def per_row(w_r, cov_r, sl_r, val_r, y_r, t_r):
            score = jnp.sum(w_r * val_r)
            sq_norm = jnp.sum(val_r * val_r)
            variance = jnp.sum(cov_r * val_r * val_r) if use_cov \
                else jnp.zeros(())
            ctx = RowContext(w_r, cov_r, sl_r, val_r, y_r, score, sq_norm,
                             variance, t_r, gl)
            return rule.update(ctx, hyper)

        return jax.vmap(per_row)(w, cov, sl, val, y, ts)

    return apply


def make_train_fn(
    rule: Rule,
    hyper: dict,
    mode: str = "minibatch",
    track_deltas: bool = False,
    feature_shard: Optional[Tuple[str, int]] = None,
):
    """Build the raw (unjitted) `step(state, indices, values, labels) ->
    (state, loss_sum)` — composable inside shard_map/scan by parallel/mix.py.

    `mode='scan'` replays rows sequentially (reference-exact); `mode='minibatch'`
    applies the whole block against batch-start weights (reference's
    -mini_batch semantics). With `track_deltas`, state.slots[DELTA_SLOT]
    accumulates per-feature update counts (for delta-weighted model averaging,
    ref: PartialAverage.java:43-67).

    `feature_shard=(axis_name, stripe)` runs the same step on a [D/stripe]
    model stripe inside shard_map — the training analog of the reference's
    feature-sharded parameter store (`hash(feature) mod numNodes` routing,
    ref: mix/client/MixRequestRouter.java:56-60): lanes this device doesn't
    own are masked out, per-row score/norm/variance partials psum over the
    axis (so every device sees the global row scalars), and scatters land in
    the local stripe only. Exact, not approximate: every rule's lane update
    is a function of (global row scalars, lane-local state), which is what
    the owning device computes.
    """
    if mode not in ("scan", "minibatch"):
        raise ValueError(f"unknown mode {mode!r}")
    use_cov = rule.use_covariance

    if feature_shard is None:
        def build_ctx(tables, idx, val, y, tf, gl, packed=None):
            return _row_ctx(tables, idx, val, y, tf, use_cov, gl, packed), idx
    else:
        shard_axis, stripe = feature_shard
        from .striping import translate_to_stripe

        def build_ctx(tables, idx, val, y, tf, gl, packed=None):
            local_idx, vmask = translate_to_stripe(idx, val, shard_axis, stripe)
            # same gathers/row scalars as the local path, on the stripe's
            # lanes only — then the scalar partials psum to global values
            ctx = _row_ctx(tables, local_idx, vmask, y, tf, use_cov, gl, packed)
            ctx = ctx.replace(
                score=jax.lax.psum(ctx.score, shard_axis),
                sq_norm=jax.lax.psum(ctx.sq_norm, shard_axis),
                variance=jax.lax.psum(ctx.variance, shard_axis)
                if use_cov else ctx.variance,
            )
            return ctx, local_idx

    def scan_step(state: LinearState, indices, values, labels):
        def body(carry, row):
            weights, covars, slots, touched, t, gl = carry
            idx, val, y = row
            tf = (t + 1).astype(jnp.float32)
            if rule.pre_row is not None:
                gl = rule.pre_row(gl, y)
            with jax.named_scope(SCOPE_GATHER):
                ctx, sidx = build_ctx((weights, covars, slots), idx, val, y,
                                      tf, gl)
            with jax.named_scope(SCOPE_RULE):
                out = rule.update(ctx, hyper)
            with jax.named_scope(SCOPE_APPLY):
                # rule math runs in f32; bf16 tables (the
                # SpaceEfficientDenseModel analog) take the delta cast to
                # their storage dtype
                weights = weights.at[sidx].add(
                    out.dw.astype(weights.dtype), mode="drop")
                if use_cov and out.dcov is not None:
                    covars = covars.at[sidx].add(
                        out.dcov.astype(covars.dtype), mode="drop")
                new_slots = dict(slots)
                for k, d in out.dslots.items():
                    new_slots[k] = slots[k].at[sidx].add(
                        d.astype(slots[k].dtype), mode="drop")
                if rule.derive_w is not None:
                    # lane-wise slot values after this row's delta
                    sl_new = {k: ctx.slots[k] + out.dslots.get(k, 0.0)
                              for k in slots}
                    w_new = rule.derive_w(sl_new, tf, hyper)
                    w_new = jnp.where(out.updated, w_new, ctx.w)
                    weights = weights.at[sidx].set(
                        w_new.astype(weights.dtype), mode="drop")
            with jax.named_scope(SCOPE_TOUCHED):
                upd = out.updated.astype(jnp.int8)
                touched = touched.at[sidx].max(
                    jnp.broadcast_to(upd, sidx.shape), mode="drop")
            if track_deltas:
                with jax.named_scope(SCOPE_APPLY):
                    new_slots[DELTA_SLOT] = slots[DELTA_SLOT].at[sidx].add(
                        jnp.broadcast_to(
                            out.updated.astype(slots[DELTA_SLOT].dtype),
                            sidx.shape),
                        mode="drop")
            return (weights, covars, new_slots, touched, t + 1, gl), out.loss

        carry0 = (state.weights, state.covars, state.slots, state.touched, state.step,
                  state.globals)
        (weights, covars, slots, touched, step, gl), losses = jax.lax.scan(
            body, carry0, (indices, values, labels)
        )
        new_state = state.replace(
            weights=weights, covars=covars, slots=slots, touched=touched, step=step,
            globals=gl,
        )
        return new_state, jnp.sum(losses)

    def minibatch_step(state: LinearState, indices, values, labels):
        b = indices.shape[0]
        t0 = state.step
        ts = (t0 + 1 + jnp.arange(b)).astype(jnp.float32)
        gl = state.globals
        if rule.pre_batch is not None:
            gl = rule.pre_batch(gl, labels)
        dense = apply_strategy(state.weights.shape[0],
                               indices.size) == "dense"

        # a small table: pack w+cov once per block so every row's two
        # scalar gathers become one pair-row gather (see _row_ctx)
        with jax.named_scope(SCOPE_PACK_TABLES):
            packed = (jnp.stack([state.weights, state.covars], axis=-1)
                      if use_cov and dense else None)

        def per_row(idx, val, y, tf):
            with jax.named_scope(SCOPE_GATHER):
                ctx, sidx = build_ctx(
                    (state.weights, state.covars, state.slots), idx, val, y,
                    tf, gl, packed)
            with jax.named_scope(SCOPE_RULE):
                # the gathered batch-start values ride along: the block-local
                # write is `old + sum/count`, with no second gather
                old = {"w": ctx.w, "cov": ctx.cov, "slots": ctx.slots}
                return rule.update(ctx, hyper), sidx, old

        outs, sidx, old = jax.vmap(per_row)(indices, values, labels, ts)
        with jax.named_scope(SCOPE_RULE):
            upd = outs.updated.astype(jnp.float32)  # [B]
            lane_upd = upd[:, None] * jnp.ones_like(values)  # [B, K]

        weights, covars, slots = state.weights, state.covars, state.slots
        new_slots = dict(slots)
        has_cov = use_cov and outs.dcov is not None
        # Per-feature averaged application, exactly the reference's
        # FloatAccumulator semantics (RegressionBaseUDTF.java:236-295).
        # Accumulate in f32 even over bf16 tables, cast once at the table
        # write (the SpaceEfficientDenseModel analog stores compact, never
        # accumulates compact).
        acc = jnp.promote_types(weights.dtype, jnp.float32)
        if not dense:
            # In the BLOCK's index space: the lanes are sorted by feature id
            # with their deltas and their gathered old values as payload,
            # each run of equal ids is summed in f32, and the run's lanes
            # write `old + sum/count` back with one rounding to the table's
            # storage type. Nothing but the in-place writes is as long as
            # the table (PERF.md section 6, PR 27).
            summed = {"count": lane_upd, "w": outs.dw, "slots": outs.dslots,
                      "cov": outs.dcov if has_cov else None}
            with jax.named_scope(SCOPE_REDUCE):
                runs = reduce_block_runs(
                    sidx.reshape(-1), weights.shape[0],
                    jax.tree_util.tree_map(
                        lambda v: v.reshape(-1).astype(acc), summed),
                    jax.tree_util.tree_map(lambda v: v.reshape(-1), old))
            with jax.named_scope(SCOPE_APPLY):
                sums = runs.sums
                old = jax.tree_util.tree_map(lambda v: v.astype(acc),
                                             runs.carried)
                count = sums["count"]
                denom = jnp.maximum(count, 1.0)
                w_new = old["w"] + sums["w"] / denom
                # optimizer slots take a block's sum; a rule whose weights
                # are derived from them says what one block adds
                # (DERIVED_W_BLOCK_RULE)
                slot_sums = dict(sums["slots"])
                if rule.block_slots is not None:
                    slot_sums = rule.block_slots(slot_sums)
                if track_deltas:
                    slot_sums[DELTA_SLOT] = count
                sl_new = {k: v + slot_sums[k] if k in slot_sums else v
                          for k, v in old["slots"].items()}
                if rule.derive_w is not None:
                    # Dual-averaging weights are a pure function of the
                    # *updated* accumulators; a feature no row fired on
                    # keeps its value
                    tf_end = (t0 + b).astype(jnp.float32)
                    w_new = jnp.where(
                        count > 0,
                        rule.derive_w(sl_new, tf_end, hyper).astype(acc),
                        w_new)
                writes = {"w": RunWrite(weights, w_new, scope=SCOPE_APPLY)}
                if has_cov:
                    writes["cov"] = RunWrite(
                        covars, old["cov"] + sums["cov"] / denom,
                        scope=SCOPE_APPLY)
                for k in slot_sums:
                    writes["slot " + k] = RunWrite(slots[k], sl_new[k],
                                                   scope=SCOPE_APPLY)
                writes["touched"] = RunWrite(state.touched, count > 0, "max",
                                             SCOPE_TOUCHED)
            # each under its own scope down XLA's path; one walk of the
            # block's ids for all the tables the kernel takes
            written = dict(zip(writes,
                               write_runs_together(runs, writes.values())))
            weights, touched = written["w"], written["touched"]
            covars = written.get("cov", covars)
            for k in slot_sums:
                new_slots[k] = written["slot " + k]
            new_state = state.replace(
                weights=weights, covars=covars, slots=new_slots,
                touched=touched, step=t0 + b, globals=gl)
            with jax.named_scope(SCOPE_RULE):
                return new_state, jnp.sum(outs.loss)

        # A table a few hundred times the block at most: ten passes over it
        # cost less than the sort, the scans and the second gather.
        # scopes follow the statements' order: moving one would change the
        # traced program, and with it the compile cache's key
        with jax.named_scope(SCOPE_REDUCE):
            counts = jnp.zeros(weights.shape, acc).at[sidx].add(
                lane_upd, mode="drop")
        with jax.named_scope(SCOPE_APPLY):
            denom = jnp.maximum(counts, 1.0)
        with jax.named_scope(SCOPE_REDUCE):
            dw_sum = jnp.zeros(weights.shape, acc).at[sidx].add(
                outs.dw.astype(acc), mode="drop")
        with jax.named_scope(SCOPE_APPLY):
            weights = (weights.astype(acc) + dw_sum / denom) \
                .astype(weights.dtype)
        if has_cov:
            with jax.named_scope(SCOPE_REDUCE):
                dc_sum = jnp.zeros(covars.shape, acc).at[sidx].add(
                    outs.dcov.astype(acc), mode="drop")
            with jax.named_scope(SCOPE_APPLY):
                covars = (covars.astype(acc) + dc_sum / denom) \
                    .astype(covars.dtype)
        with jax.named_scope(SCOPE_APPLY):
            if rule.block_slots is not None:
                # the block's one increment from its per-feature sums
                # (DERIVED_W_BLOCK_RULE)
                ds = rule.block_slots({
                    k: jnp.zeros(slots[k].shape, acc).at[sidx].add(
                        d.astype(acc), mode="drop")
                    for k, d in outs.dslots.items()})
                for k, d in ds.items():
                    new_slots[k] = (slots[k].astype(acc) + d) \
                        .astype(slots[k].dtype)
            else:
                for k in rule.slot_names:
                    if k in outs.dslots:
                        new_slots[k] = slots[k].at[sidx].add(
                            outs.dslots[k].astype(slots[k].dtype),
                            mode="drop")
            if rule.derive_w is not None:
                # Dual-averaging weights are a pure function of the
                # *updated* accumulators: one more pass over the table,
                # kept where no row fired on the feature (as the
                # block-local arm keeps it). A per-lane write would let a
                # quiet row's lane put the old weight back over a firing
                # row's.
                tf_end = (t0 + b).astype(jnp.float32)
                w_full = rule.derive_w(
                    {k: v.astype(acc) for k, v in new_slots.items()},
                    tf_end, hyper)
                weights = jnp.where(counts > 0,
                                    w_full.astype(weights.dtype), weights)
        # `counts` is exactly this block's per-feature lane_upd scatter, so
        # touched and the MIX delta clock derive from it with full-table
        # elementwise ops instead of two more scatters
        with jax.named_scope(SCOPE_TOUCHED):
            touched = jnp.maximum(state.touched,
                                  (counts > 0).astype(jnp.int8))
        if track_deltas:
            with jax.named_scope(SCOPE_APPLY):
                delta_tab = new_slots.get(DELTA_SLOT,
                                          state.slots[DELTA_SLOT])
                new_slots[DELTA_SLOT] = delta_tab + counts.astype(
                    delta_tab.dtype)
        new_state = state.replace(
            weights=weights,
            covars=covars,
            slots=new_slots,
            touched=touched,
            step=t0 + b,
            globals=gl,
        )
        with jax.named_scope(SCOPE_RULE):
            loss_sum = jnp.sum(outs.loss)
        return new_state, loss_sum

    if mode == "scan":
        return scan_step
    return minibatch_step


def make_train_step(
    rule: Rule,
    hyper: dict,
    mode: str = "minibatch",
    donate: bool = True,
):
    """Jitted wrapper over make_train_fn (the single-replica path)."""
    fn = make_train_fn(rule, hyper, mode=mode)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def make_cut_step(step, lanes: int):
    """`step(state, indices, values, *rest)` as one jitted program that
    cuts its `[B, K]` block to the `lanes` leading lanes first, so that
    nothing of the step gathers, sorts, scans or writes a lane beyond them.

    For a `-mini_batch` step whose call's rows fill only so many lanes of
    the block's power-of-two bucket (core/batch.py::fillable_lanes: 39
    features on 64 lanes work on 40): `pack_rows` fills a row from lane 0,
    so the lanes beyond hold the padding id in every row and add no delta,
    no count and no flag, while a gather costs a padding lane what it costs
    a real one and XLA's sorted write pays for a dropped lane too (PERF.md
    section 5). The slice is static and the step is traced inside it, so
    `apply_strategy` and every shape in the step see the cut block; the
    state is donated as the step's own jit donates it. A jitted `step` is
    traced through its own function (`__wrapped__`), one flat module: a jit
    called inside this one runs the same device program, and cost a
    2^17-row `train_arow` call 50 ms more on the chip machine's host
    (PERF.md section 6, PR 35).
    """
    inner = getattr(step, "__wrapped__", step)

    def cut(state, indices, values, *rest):
        return inner(state, indices[:, :lanes], values[:, :lanes], *rest)

    return jax.jit(cut, donate_argnums=(0,))


def make_epoch(step_fn, donate: bool = True):
    """Whole-epoch driver: ONE jitted `lax.scan` of `step_fn` over a stack of
    HBM-staged blocks — the framework's deployment shape (io/records.py
    prefetches blocks; the epoch replays them device-resident, the TPU analog
    of the reference's buffered epoch replay,
    FactorizationMachineUDTF.java:521-559). Dispatch cost is paid once per
    epoch instead of once per block.

    `step_fn(state, *block) -> (state, loss)` is a raw traceable step —
    `make_train_fn(...)`, `make_fm_step(..., jit=False)`,
    `make_ffm_step(..., jit=False)`, or a lambda closing over static extras.
    Returns jitted `epoch(state, *stacked) -> (state, losses)` where each
    element of `stacked` has a leading [n_blocks] axis and `losses` is the
    per-block loss stack.
    """

    def epoch(state, *stacked):
        def body(s, blk):
            s, loss = step_fn(s, *blk)
            return s, loss

        return jax.lax.scan(body, state, stacked)

    return jax.jit(epoch, donate_argnums=(0,) if donate else ())


_PREDICT_CACHE: Dict[bool, Callable] = {}


def make_predict(use_covariance: bool = False):
    if use_covariance in _PREDICT_CACHE:
        return _PREDICT_CACHE[use_covariance]
    _PREDICT_CACHE[use_covariance] = _build_predict(use_covariance)
    return _PREDICT_CACHE[use_covariance]


def _build_predict(use_covariance: bool = False):
    """Jitted batched predict: score [B] (and variance [B] for covariance
    learners) — the reference's calcScoreAndNorm/calcScoreAndVariance
    (ref: BinaryOnlineClassifierUDTF.java:169-229)."""

    @jax.jit
    def predict(state: LinearState, indices, values):
        w = _gather(state.weights, indices)
        score = jnp.sum(w * values, axis=-1)
        if use_covariance and state.covars is not None:
            cov = _gather(state.covars, indices, fill=1.0)
            variance = jnp.sum(cov * values * values, axis=-1)
            return score, variance
        return score

    return predict
