"""The segment-sum batched update backend — the CPU hot path.

The engine's `minibatch` mode computes every row of a block against the
batch-start tables (the reference's FloatAccumulator semantics,
RegressionBaseUDTF.java:236-295) but APPLIES the block through three
full-[D] temporaries (counts, dw sums, dcov sums) plus duplicate-index
scatters — on XLA:CPU, where scatter executes element-at-a-time (~15 M
elt/s measured on this host, vs 400-800 M elt/s for gathers), that
application is the whole step: BENCH r03-r05 sat at ~1.0 M rows/sec while
the transliterated C row loop did 2.4 M on the same machine.

This module promotes the ops/scatter.py sort->segment-reduce->unique-
scatter pattern from a TPU workaround to the primary CPU execution
backend, with the sort moved OUT of the step entirely:

- staging builds ONE StagedDedupPlan per minibatch of B rows on the host
  (numpy radix argsort, 4x faster than XLA:CPU's comparator sort, and
  replayed free every epoch — the kernels/linear_scan.py chunking
  discipline: host-side shaping once, fixed-shape device replay after);
- the jitted step scans the staged block in B-row chunks; each chunk
  gathers every table ONCE at the plan's unique slots (ascending ids — a
  sequential table walk), fans values out to lanes with a take, runs the
  rule batch-aware (`core.engine.make_batch_update`), reduces all delta
  columns with ONE chunk-local cumsum, and writes each table back with a
  single compact unique+sorted scatter — U unique lanes instead of B*K
  update lanes, no full-[D] temporaries anywhere;
- B is the AdaBatch dial (PAPERS.md): batch size trades throughput
  against update staleness; the last sweep's numbers (holdout logloss
  per B, CPU) are in docs/execution_backends.md.

Semantics are the engine's minibatch mode exactly (same sums, f32
accumulation, per-feature count averaging) up to float reduction order;
B=1 reproduces minibatch B=1. Integer tables (touched, DELTA_SLOT
counts) are EXACT: the 0/1 count column's chunk-local cumsum only ever
forms integers below 2^24, all representable in f32.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.scatter import (StagedDedupPlan, broadcast_lanes,
                           build_staged_plan, pad_plan, staged_gather,
                           staged_scatter_add, staged_scatter_set,
                           staged_segment_totals, staged_touch_max)
from .engine import DELTA_SLOT, Rule, make_batch_update
from .state import LinearState


class BlockPlans(NamedTuple):
    """Staged plans for one block: `main` stacks the block's full B-row
    chunks ([nb, ...] leading axis, shared U bucket so one lax.scan body
    serves them all); `tail` covers the remainder rows (its own shapes —
    no sentinel rows, so the example counter and scalar globals stay
    exact)."""

    main: Optional[StagedDedupPlan]
    tail: Optional[StagedDedupPlan]

    @property
    def slot_bucket(self) -> int:
        return int(self.main.rep.shape[-1]) if self.main is not None else 0


def _chunk_plans(indices, batch_size: int, dims: int):
    """Host-side: one UNSTACKED dedup plan per B-row minibatch of a
    staged block [N, K], plus the remainder chunk's plan. The expensive
    part (numpy argsort + segment pass per chunk) happens exactly once
    here — stacking to a common U bucket is pad_plan, not a re-sort."""
    n = int(indices.shape[0])
    b = min(batch_size, n)
    nb = n // b
    chunks: List[StagedDedupPlan] = [
        build_staged_plan(np.asarray(indices[c * b:(c + 1) * b]).reshape(-1),
                          dims)
        for c in range(nb)]
    tail = None
    if n - nb * b:
        tail = build_staged_plan(
            np.asarray(indices[nb * b:]).reshape(-1), dims)
    return chunks, tail


def _stack_chunks(chunks: List[StagedDedupPlan], slots: int,
                  dims: int) -> StagedDedupPlan:
    widened = [pad_plan(p, slots, dims) for p in chunks]
    return StagedDedupPlan(*[np.stack([getattr(p, f) for p in widened])
                             for f in StagedDedupPlan._fields])


def stage_block_plans(indices, batch_size: int, dims: int,
                      slots: Optional[int] = None) -> BlockPlans:
    """Host-side: build one dedup plan per B-row minibatch of a staged
    block [N, K]. `slots` pins the main chunks' U bucket (epoch stacking
    passes a common bucket so every block compiles to one shape)."""
    chunks, tail = _chunk_plans(indices, batch_size, dims)
    main = None
    if chunks:
        u = max(p.rep.shape[0] for p in chunks)
        if slots is not None:
            u = max(u, slots)
        main = _stack_chunks(chunks, u, dims)
    return BlockPlans(main=main, tail=tail)


def stage_epoch_plans(indices, batch_size: int, dims: int) -> BlockPlans:
    """Plans for an epoch's stacked blocks [n_blocks, N, K] (the bench /
    make_epoch deployment shape): every block's chunks share one U bucket
    so the whole epoch replays through a single compiled scan. Blocks
    below the epoch-wide bucket are WIDENED with pad_plan — their sorts
    are never redone."""
    n_blocks = int(indices.shape[0])
    per_block = [_chunk_plans(indices[i], batch_size, dims)
                 for i in range(n_blocks)]
    if any(t is not None for _, t in per_block):
        raise ValueError("epoch staging requires block rows divisible by "
                         "the batch size (blocks are operator-shaped; pad "
                         "or trim the trailing rows at the caller)")
    u = max(p.rep.shape[0] for chunks, _ in per_block for p in chunks)
    stacked = [_stack_chunks(chunks, u, dims) for chunks, _ in per_block]
    main = StagedDedupPlan(*[np.stack([getattr(sb, f) for sb in stacked])
                             for f in StagedDedupPlan._fields])
    return BlockPlans(main=main, tail=None)


def make_batch_train_fn(
    rule: Rule,
    hyper: dict,
    batch_size: int,
    track_deltas: bool = False,
):
    """Raw (unjitted) `step(state, indices, values, labels, plans) ->
    (state, loss_sum)` — the batched execution backend's step. `plans`
    must be `stage_block_plans(indices, batch_size, dims)` for the same
    indices (the plan IS the block's sort, staged host-side)."""
    use_cov = rule.use_covariance
    apply_update = make_batch_update(rule, hyper)

    def chunk_update(tables, idx, val, y, plan, t0, gl):
        weights, covars, slots, touched = tables
        bsz = idx.shape[0]
        ts = (t0 + 1 + jnp.arange(bsz)).astype(jnp.float32)
        if rule.pre_batch is not None:
            gl = rule.pre_batch(gl, y)

        # one gather per table at the unique slots (ascending feature ids:
        # a sequential walk of the table), fanned out to lanes by a take.
        # Pad lanes belong to dropped slots whose gather reads the fill,
        # so no mask tensors appear anywhere (the core/batch.py protocol).
        # bf16 tables widen per-[U]-window only, G021 accumulation in f32.
        uw = staged_gather(weights, plan).astype(jnp.float32)
        w_l = broadcast_lanes(uw, plan).reshape(idx.shape)
        cov_l = None
        ucov = None
        if use_cov:
            ucov = staged_gather(covars, plan, fill=1.0).astype(jnp.float32)
            cov_l = broadcast_lanes(ucov, plan).reshape(idx.shape)
        sl_u = {k: staged_gather(slots[k], plan).astype(jnp.float32)
                for k in rule.slot_names}
        sl_l = {k: broadcast_lanes(v, plan).reshape(idx.shape)
                for k, v in sl_u.items()}

        out = apply_update(w_l, cov_l, sl_l, val, y, ts, gl)
        upd = out.updated.astype(jnp.float32)  # [B]
        lane_upd = upd[:, None] * jnp.ones_like(val)  # [B, K]

        # ALL delta columns reduce under the one plan: dw [+ dcov]
        # [+ dslots] + the update counts, one permute + one cumsum total
        cols = [out.dw]
        if use_cov and out.dcov is not None:
            cols.append(out.dcov)
        scat_slots = [k for k in rule.slot_names if k in out.dslots]
        cols += [out.dslots[k] for k in scat_slots]
        cols.append(lane_upd)
        nd = len(cols)
        stack = jnp.stack([c.astype(jnp.float32).reshape(-1) for c in cols],
                          axis=-1)
        sums = staged_segment_totals(plan, stack)  # [U, nd]
        counts = sums[:, nd - 1]

        weights = staged_scatter_add(weights, plan, sums[:, 0], counts)
        pos = 1
        if use_cov and out.dcov is not None:
            covars = staged_scatter_add(covars, plan, sums[:, pos], counts)
            pos += 1
        new_slots = dict(slots)
        slot_sums = {k: sums[:, pos + i] for i, k in enumerate(scat_slots)}
        if rule.block_slots is not None:
            # derived weights: the block is one subgradient
            # (core/engine.py, DERIVED_W_BLOCK_RULE)
            slot_sums = rule.block_slots(slot_sums)
        for k in scat_slots:
            new_slots[k] = staged_scatter_add(slots[k], plan, slot_sums[k])
        if rule.derive_w is not None:
            # dual-averaging weights are a pure per-feature function of the
            # post-update slots — computed per UNIQUE slot, so the dense
            # gather-after-scatter round trip disappears entirely
            tf_end = (t0 + bsz).astype(jnp.float32)
            sl_new = {k: sl_u[k] + slot_sums[k] if k in slot_sums
                      else sl_u[k] for k in rule.slot_names}
            w_new = rule.derive_w(sl_new, tf_end, hyper)  # [U]
            weights = staged_scatter_set(weights, plan, w_new, counts > 0)
        touched = staged_touch_max(touched, plan, counts)
        if track_deltas:
            delta_tab = new_slots.get(DELTA_SLOT, slots[DELTA_SLOT])
            new_slots[DELTA_SLOT] = staged_scatter_add(delta_tab, plan,
                                                       counts)
        return (weights, covars, new_slots, touched), gl, jnp.sum(out.loss)

    def step(state: LinearState, indices, values, labels,
             plans: BlockPlans):
        n = indices.shape[0]
        tables = (state.weights, state.covars, state.slots, state.touched)
        gl = state.globals
        t = state.step
        loss_total = jnp.zeros(())
        if plans.main is not None:
            nb = plans.main.order.shape[0]
            b = (n // nb) if plans.tail is None else batch_size
            n_main = nb * b
            xs = (indices[:n_main].reshape(nb, b, -1),
                  values[:n_main].reshape(nb, b, -1),
                  labels[:n_main].reshape(nb, b), plans.main)

            def body(carry, x):
                tables, gl, t = carry
                idx, val, y, plan = x
                tables, gl, loss = chunk_update(tables, idx, val, y, plan,
                                                t, gl)
                return (tables, gl, t + b), loss

            (tables, gl, t), losses = jax.lax.scan(body, (tables, gl, t),
                                                   xs)
            loss_total = jnp.sum(losses)
        if plans.tail is not None:
            n_tail = n - (plans.main.order.shape[0] * batch_size
                          if plans.main is not None else 0)
            tables, gl, loss_t = chunk_update(
                tables, indices[n - n_tail:], values[n - n_tail:],
                labels[n - n_tail:], plans.tail, t, gl)
            loss_total = loss_total + loss_t
        weights, covars, slots, touched = tables
        new_state = state.replace(weights=weights, covars=covars,
                                  slots=slots, touched=touched,
                                  step=state.step + n, globals=gl)
        return new_state, loss_total

    return step


def make_batch_train_step(
    rule: Rule,
    hyper: dict,
    batch_size: int,
    track_deltas: bool = False,
    donate: bool = True,
):
    """Jitted wrapper over make_batch_train_fn (the single-replica path)."""
    fn = make_batch_train_fn(rule, hyper, batch_size,
                             track_deltas=track_deltas)
    return jax.jit(fn, donate_argnums=(0,) if donate else ())
