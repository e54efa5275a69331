"""Sort a block's feature ids, reduce each run of equal ids, write each id
once: the engine's hot write is `table.at[idx].add(upd)` with HEAVILY
duplicated indices (hashed CTR ids are zipf-like), which XLA applies one
update at a time.

Two forms of the idea live here. `BlockRuns` (`reduce_block_runs`,
`write_runs`) works on the device in the block's own index space and is the
`-mini_batch` step's reduction (core/engine.py, and models/fm.py since
PR 31); `StagedDedupPlan` is built on the host at staging time and is what
the `-batch` / `-native_apply` backends and the frozen C ABI take
(core/batch_update.py). Both give identical sums up to float reduction
order (a duplicate-index scatter-add has no defined application order
either). `scatter_rows_flat` is the row scatter-add through the flat scalar
view that FFM and FM's `feature_shard` stripes keep.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import NamedTuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Block runs: the -mini_batch step's reduction, in the block's own index
# space. The flat lane ids are sorted with every per-lane column riding as
# payload, a segmented scan sums each run of equal ids, and every lane of a
# run ends up holding its feature's totals — nothing here is as long as the
# table, nothing is gathered a second time, and no segment_sum/segment_min
# (which lower back to scatters) is involved. The writes that follow take
# the sorted ids as they are: all lanes of a feature write the same value,
# so `indices_are_sorted` is true and the duplicates cannot disagree. (On
# the v5e a sorted scatter into a 2^28 table costs half an unsorted one, and
# a false `indices_are_sorted` writes wrong entries: PERF.md section 6,
# PR 27.)
# --------------------------------------------------------------------------


class BlockRuns(NamedTuple):
    """One block's lanes sorted by feature id and reduced per feature."""

    ids: jnp.ndarray  # [N] int32 — the lanes' feature ids, ascending; every
    # lane outside the table carries `dims` and sits at the tail
    sums: object  # `summed`'s tree of [N]: a run's total, on all its lanes
    carried: object  # `carried`'s tree of [N], in sorted lane order


def _sort_columns(ids: jnp.ndarray, cols):
    """Each [N] column of `cols` (one dtype) reordered as `ids` sorts.

    The columns ride one at a time, each through the same two-operand sort
    inside a loop: the TPU compiler takes about 10 s per operand of a
    65536-lane sort (90 s for all of an AROW step's columns at once), and
    a loop's body is compiled once. On a v5e the step's six sorts take
    0.2 ms together (PERF.md section 6, PR 27)."""
    return jax.lax.map(
        lambda col: jax.lax.sort((ids, col), num_keys=1, is_stable=False)[1],
        jnp.stack(cols))


def reduce_block_runs(idx_flat: jnp.ndarray, dims: int, summed,
                      carried) -> BlockRuns:
    """`idx_flat` [N] int lane ids (pad lanes == dims); the [N] columns of
    the tree `summed` are added up over each run of equal ids in a fixed
    order; the [N] columns of the tree `carried` (equal on all lanes of one
    id) just ride the sort. Every column comes back in `summed`'s dtype.

    Two Hillis-Steele passes over the sorted lanes, s = 1, 2, 4, ...: lane i
    adds lane i-s while both hold the same id (sorted ids make "same id"
    the whole segment test), which leaves a run's total on its last lane;
    then lane i copies lane i+s under the same test, which hands that total
    back to the run's other lanes, bit for bit. Each pass is a loop with a
    rolled shift, not 16 unrolled steps: `fit_linear` traces its step anew
    every call, and 500 traced operations cost it a quarter of a second."""
    n = idx_flat.shape[0]
    sum_cols, sum_tree = jax.tree_util.tree_flatten(summed)
    ride_cols, ride_tree = jax.tree_util.tree_flatten(carried)
    acc = sum_cols[0].dtype
    # `.at[]` counts negative ids from the table's end; so do we. Whatever
    # is outside the table after that is a dropped lane, at the sort's tail
    ids = jnp.where(idx_flat < 0, idx_flat + dims, idx_flat)
    ids = jnp.where((ids >= 0) & (ids < dims), ids, dims)
    cols = _sort_columns(ids, [c.astype(acc) for c in sum_cols + ride_cols])
    ids = jax.lax.sort(ids, is_stable=False)
    lane = jnp.arange(n)
    passes = max(n - 1, 0).bit_length()

    def add_left(p, sums):
        s = 1 << p
        same = (jnp.roll(ids, s) == ids) & (lane >= s)
        return sums + jnp.where(same, jnp.roll(sums, s, axis=1), 0)

    def copy_right(p, sums):
        s = 1 << p
        same = (jnp.roll(ids, -s) == ids) & (lane < n - s)
        return jnp.where(same, jnp.roll(sums, -s, axis=1), sums)

    sums = jax.lax.fori_loop(0, passes, add_left, cols[:len(sum_cols)])
    sums = jax.lax.fori_loop(0, passes, copy_right, sums)
    return BlockRuns(ids=ids, sums=sum_tree.unflatten(list(sums)),
                     carried=ride_tree.unflatten(list(cols[len(sum_cols):])))


# A `[D]` table's runs are written down one of two paths, chosen when the
# step is traced from what it can see there: the table's storage type and
# length, the block's lanes, and the backend. "xla" is the sorted in-place
# scatter, which on a v5e costs a lane part plus the table's bytes streamed
# once each way whatever the lanes; "kernel" is
# `kernels/run_write.py::write_runs_kernel`, which moves only the 1,024-entry
# tiles that hold a touched entry, so its time follows the lanes and no
# table's length. The two compute the same bits; only their cost differs
# with D and N, and the rule is that cost model, its constants here and
# nowhere else, each with the run it came from (PERF.md section 7 has both
# tables):
#   stream_ms  XLA's stream per 2^28 entries  (scripts/scatter_cost.py,
#   xla_ns     XLA's part per lane             builder's chip run, PR 35)
#   kernel_ns  the kernel's part per lane for ONE table alone, its sort
#              and plan included (scripts/scatter_cost.py `write.kernel` at
#              2^28 over 16,384 / 40,960 / 65,536 lanes, builder's chip
#              run, PR 39; 2^29 reads 2% more, 2^24 a third less: shorter
#              tables have fewer tiles to move)
# A table that joins a state's other tables in one walk costs less than
# alone (AdaGradRDA's four: 3.7 ms a step together, 6.3 as the sum of each
# alone), so the rule errs toward XLA. A lane is what the step can count;
# what the kernel pays for is a run head and its tile. The constants are
# read on ids placed as the cells' are, 0.55 heads a lane, nearly each in a
# tile of its own; a block whose ids all differ pays 1.8 times `kernel_ns`
# a lane, which alone at 2^28 would put the bf16 and s8 writes behind XLA's
# by a fifth (hashed CTR ids repeat heavily: a third of Criteo's lanes are
# on every row).
WRITE_COST = {
    "int8": {"stream_ms": 1.597, "xla_ns": 8.45, "kernel_ns": 41.7},
    "bfloat16": {"stream_ms": 2.117, "xla_ns": 8.75, "kernel_ns": 37.5},
    "float32": {"stream_ms": 3.177, "xla_ns": 4.91, "kernel_ns": 29.6},
}
# what a kernel call costs before its first lane (0.07-0.09 ms: the launch,
# the head sort and the plan, one chunk's two waits) over XLA's 0.034
KERNEL_FIXED_MS = 0.05


def write_path(dtype, dims: int, lanes: int, backend: str) -> str:
    """"kernel" or "xla": how a block of `lanes` lanes is written
    into a `[dims]` table of `dtype` on `backend`. The kernel is a Mosaic
    program, so any backend but a TPU takes XLA's write."""
    cost = WRITE_COST.get(jnp.dtype(dtype).name)
    if backend != "tpu" or cost is None:
        return "xla"
    stream_ms = cost["stream_ms"] * dims / 2 ** 28
    lanes_ms = lanes * (cost["kernel_ns"] - cost["xla_ns"]) * 1e-6
    return "kernel" if stream_ms > KERNEL_FIXED_MS + lanes_ms else "xla"


def kernel_written(tables, dims: int, lanes: int, backend: str) -> list:
    """The names in `tables` (name -> a `[dims]` table, or a stack of them
    a replica) that a step on `backend` writes through the kernel, `lanes`
    lanes a block: `train.call`'s `write`."""
    return [name for name, t in tables.items()
            if write_path(t.dtype, dims, lanes, backend) == "kernel"]


class RunWrite(NamedTuple):
    """One table's write at a block's runs, for `write_runs_together`."""

    table: jnp.ndarray  # [D], or [D, k] rows (FM's V)
    values: jnp.ndarray  # [N] or [N, k]: equal on all lanes of one id, as
    # anything computed from `runs.sums` and `runs.carried` is
    op: str = "set"  # or "max": `max(table[id], values)`, the flag's
    scope: str | None = None  # the `hm.*` scope XLA's write stands under


def write_runs(table: jnp.ndarray, runs: BlockRuns, values: jnp.ndarray,
               op: str = "set") -> jnp.ndarray:
    """One table's `write_runs_together`."""
    return write_runs_together(runs, [RunWrite(table, values, op)])[0]


def write_runs_together(runs: BlockRuns, writes) -> list:
    """Each `RunWrite`'s `table[id] = values` (or `max(table[id], values)`)
    at the block's ids, in place, in the order given. Down XLA's path a
    table is one sorted scatter (a `[D, k]` table one sorted row scatter:
    6.2 ms per 65,536 rows of 16 lanes into 2^23 on a v5e). The `[D]`
    tables that `write_path` sends there go through the run-write kernel
    instead, all of one length in one walk of the ids (the same bits);
    only then is the kernel's module, and Pallas, imported."""
    writes = list(writes)
    out = [w.table for w in writes]
    lanes, backend = runs.ids.shape[0], jax.default_backend()
    scoped = lambda w: jax.named_scope(w.scope) if w.scope else nullcontext()
    walks = {}   # (table length, path) -> the positions of the kernel's
    for at, w in enumerate(writes):
        path = "xla" if w.table.ndim != 1 else write_path(
            w.table.dtype, w.table.shape[0], lanes, backend)
        if path != "xla":
            walks.setdefault((w.table.shape[0], path), []).append(at)
            continue
        with scoped(w):
            out[at] = getattr(w.table.at[runs.ids], w.op)(
                w.values.astype(w.table.dtype), mode="drop",
                indices_are_sorted=True)
    for (_, path), walk in walks.items():
        from ..kernels.run_write import write_runs_kernel

        taken = [writes[at] for at in walk]
        with scoped(taken[0]):
            done = write_runs_kernel(
                [w.table for w in taken], runs.ids,
                [w.values for w in taken], [w.op for w in taken],
                # a test's patched rule may say "interpret"
                interpret=path == "interpret")
        for at, table in zip(walk, done):
            out[at] = table
    return out


# A `[D, k]` table's block of row deltas (FFM's V: every lane of an entry
# adds) is added down one of two paths, chosen the same way. "xla" is XLA's
# row scatter-add of the lanes as they come (the compiler sorts them): a
# part per lane, and no part per table length that 2^20 ... 2^28 rows tell
# apart. "kernel" is `kernels/run_write.py::add_rows_kernel` after one sort
# of the lanes by row: a part per lane (the sorts, the plan, the add) plus
# a part per 128-row tile it moves, and it moves at most the table's
# `D / 128` tiles. The constants, fit to `scripts/scatter_cost.py --rows`
# on a v5e (`[D, 4]` f32 at 2^20, 2^22, 2^24, 2^26 and 2^28 rows over
# 409,600 / 819,200 / 1,638,400 lanes of pair keys as the FFM cell's rows
# hash them, 15 readings a path, the fit within 0.7 ms of each; PERF.md
# section 7 has them):
#   xla_ns, stream_ms   XLA's part per lane (81.2-81.6 ns at every size),
#                       and per 2^28 rows
#   kernel_ns, tile_ns  the kernel's part per lane, and per tile it moves
#   tiles_per_lane      the most tiles a lane opened where the table has
#                       room (2^28 rows: 0.40 at 1,024 rows a block, 0.50
#                       at 256; a third of a row's pairs are on the numeric
#                       fields' ids, which every row carries)
ROW_WRITE_COST = {
    ("float32", 4): {"xla_ns": 81.3, "stream_ms": 0.0, "kernel_ns": 18.6,
                     "tile_ns": 44.3, "tiles_per_lane": 0.50},
}


def row_write_path(dtype, dims: int, k: int, lanes: int, backend: str) -> str:
    """"kernel" or "xla": how a block of `lanes` row deltas is added into a
    `[dims, k]` table of `dtype` on `backend`."""
    cost = ROW_WRITE_COST.get((jnp.dtype(dtype).name, k))
    if backend != "tpu" or cost is None or dims % 128:
        return "xla"
    xla_ms = lanes * cost["xla_ns"] * 1e-6 + cost["stream_ms"] * dims / 2 ** 28
    tiles = min(lanes * cost["tiles_per_lane"], dims / 128)
    kernel_ms = KERNEL_FIXED_MS + (lanes * cost["kernel_ns"]
                                   + tiles * cost["tile_ns"]) * 1e-6
    return "kernel" if xla_ms > kernel_ms else "xla"


def sort_rows(ids: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """`rows` [m, N] with each row reordered as `ids` [N] sorts, in the
    rows' own buffer: each row through the same two-operand sort inside a
    loop (as `_sort_columns`), written back where it was read. A row
    reordered on its own is as good as together where the ids are a
    block's keys: lanes of one id differ only in the order they add. No
    second `[m, N]` is live (FFM's V deltas are 25 MiB a block)."""
    def one(i, rows):
        row = jax.lax.dynamic_index_in_dim(rows, i, keepdims=False)
        row = jax.lax.sort((ids, row), num_keys=1, is_stable=False)[1]
        return jax.lax.dynamic_update_index_in_dim(rows, row, i, 0)

    return jax.lax.fori_loop(0, rows.shape[0], one, rows)


# --------------------------------------------------------------------------
# Staged plans: the sort moved to staging time, the scatter shrunk to the
# unique slots.
#
# A plan built inside the jitted step pays two costs that XLA:CPU cannot
# hide: the argsort runs INSIDE the step (measured 193 ms per 512k-lane
# block on this host — XLA's comparator sort, vs 50 ms for numpy's radix
# argsort on the same data), and a scatter that carries one lane per UPDATE
# (scatter is the one primitive XLA:CPU executes element-at-a-time, ~15 M
# elt/s here, while gathers/takes run 400-800 M elt/s). Both are
# structural, not tuning: the sort is a pure function of the block's
# feature ids, and the scatter only needs one lane per UNIQUE feature.
#
# A StagedDedupPlan therefore moves both out of the hot path:
#
# - built ON THE HOST (numpy) at block-staging time, next to the existing
#   pack_rows staging — it rides into HBM with the block and is replayed
#   every epoch for free (the kernels/linear_scan.py chunking discipline:
#   host-side shaping once, device replay after);
# - the slot axis is COMPACT: [U] unique features (U bucketed so jit
#   shapes stay bounded), so every table write scatters U lanes instead
#   of B*K — on zipf-like CTR ids that is a 2-3x cut before the
#   unique+sorted promises even apply;
# - segment totals come from ONE f32 cumsum over the sorted lanes plus
#   two boundary gathers (cumsum runs at ~200 M elt/s here vs 22 M for
#   segment_sum, which XLA lowers back to a scatter). The cumsum is
#   chunk-local (<= B*K lanes), so its prefix error stays bounded; the
#   0/1 update-count column is EXACT in f32 for any chunk under 2^24
#   lanes (all partial sums are representable integers).
# --------------------------------------------------------------------------


class StagedDedupPlan(NamedTuple):
    """Host-built sort/segment structure for one chunk of B rows.

    All arrays are plain numpy at build time; they become device arrays
    when staged. `N = B*K` flat lanes, `U` = bucketed unique-slot count.
    """

    order: "jnp.ndarray"  # [N] int32 — permutation sorting the flat ids
    lane_seg: "jnp.ndarray"  # [N] int32 — slot id of each ORIGINAL lane
    rep: "jnp.ndarray"  # [U] int32 — ascending unique feature ids; pad
    # slots get distinct out-of-range ids (drop-mode + honest promises)
    starts: "jnp.ndarray"  # [U] int32 — inclusive start in sorted order
    ends: "jnp.ndarray"  # [U] int32 — exclusive end (== start on pads)


def plan_slot_bucket(n_unique: int, min_slots: int = 256) -> int:
    """Round a unique-slot count up to 8 buckets per octave (<= 12.5%
    scatter-lane waste, bounded distinct jit shapes — the pad_to_bucket
    discipline, finer-grained because scatter lanes are the cost)."""
    n = max(int(n_unique), 1)
    if n <= min_slots:
        return min_slots
    step = max(1 << (max(n.bit_length() - 1, 3) - 3), min_slots // 8)
    return -(-n // step) * step


def build_staged_plan(idx_flat, dims: int, slots: int | None = None
                      ) -> StagedDedupPlan:
    """Numpy plan builder (staging time, host side).

    `idx_flat` [N] — a chunk's flat feature ids; the padding protocol's
    out-of-range ids (== dims) sort to the tail and become dropped slots.
    `slots` pins the U bucket (callers stacking several chunks into one
    scan pass the max bucket over the chunks).
    """
    import numpy as np

    flat = np.asarray(idx_flat, dtype=np.int64).reshape(-1)
    n = flat.shape[0]
    order = np.argsort(flat, kind="stable")
    si = flat[order]
    head = np.empty(n, np.bool_)
    head[0] = True
    np.not_equal(si[1:], si[:-1], out=head[1:])
    lane_seg = np.empty(n, np.int32)
    lane_seg[order] = (np.cumsum(head) - 1).astype(np.int32)
    # every segment gets a slot, INCLUDING the pad-id segments (ids >=
    # dims): their reps are naturally out-of-range so the table ops drop
    # them, but their lanes still broadcast a well-defined fill value and
    # their counts never leak into a live feature's denominator
    uniq = si[head]
    n_seg = uniq.shape[0]
    ends_all = np.append(np.flatnonzero(head[1:]) + 1, n).astype(np.int32)
    u = slots if slots is not None else plan_slot_bucket(n_seg)
    if n_seg > u:
        raise ValueError(f"plan bucket {u} < {n_seg} unique ids")
    # unused tail slots take distinct ascending out-of-range ids past any
    # real segment's, keeping the unique_indices/indices_are_sorted
    # promises honest among the drops
    pad_base = max(int(uniq[-1]) + 1 if n_seg else dims, dims)
    rep = np.concatenate([
        uniq.astype(np.int64),
        pad_base + np.arange(u - n_seg, dtype=np.int64)])
    starts = np.zeros(u, np.int32)
    ends = np.zeros(u, np.int32)
    starts[1:n_seg] = ends_all[: n_seg - 1]
    ends[:n_seg] = ends_all
    starts[n_seg:] = n
    ends[n_seg:] = n
    return StagedDedupPlan(order=order.astype(np.int32), lane_seg=lane_seg,
                           rep=rep.astype(np.int32), starts=starts,
                           ends=ends)


# --------------------------------------------------------------------------
# Plan ctypes ABI (FROZEN, v1) — the contract for plans crossing into
# native/hivemall_native.cpp (hm_batch_apply_block, the -native_apply
# backend):
#
#   field     dtype  shape            meaning
#   order     int32  [N] / [nb, N]    permutation sorting the flat lane ids
#   lane_seg  int32  [N] / [nb, N]    slot id of each ORIGINAL lane
#   rep       int32  [U] / [nb, U]    ascending unique feature ids; pads
#                                     carry distinct ids >= dims (dropped)
#   starts    int32  [U] / [nb, U]    inclusive start in sorted lane order
#   ends      int32  [U] / [nb, U]    exclusive end (== start on pads)
#
# All arrays C-contiguous host numpy; N = chunk_rows * width. The stacked
# ([nb, ...]) form is BlockPlans.main — chunk c lives at flat offset c*N /
# c*U, which is what C contiguity guarantees. Changing any dtype, field
# order, pad convention, or the ascending-rep promise is an ABI break:
# bump PLAN_ABI_VERSION and the .so together (scripts/build_native.sh
# --if-stale re-probes the symbol so a stale library can't run silently).
# --------------------------------------------------------------------------

PLAN_ABI_VERSION = 1


def plan_abi_arrays(plan: StagedDedupPlan, stacked: bool = False):
    """Validate `plan` against the frozen ctypes ABI above and return its
    arrays as host numpy in field order. Raises TypeError/ValueError on any
    dtype, contiguity, or rank violation — a plan that came back from
    device (jnp) or was built with the wrong dtype must fail HERE, not
    corrupt memory inside the native call."""
    import numpy as np

    ndim = 2 if stacked else 1
    out = []
    for f in StagedDedupPlan._fields:
        a = getattr(plan, f)
        if not isinstance(a, np.ndarray):
            raise TypeError(
                f"plan.{f} is {type(a).__name__}, not host numpy — the "
                "native ABI takes staging-time plans (device plans have "
                "no stable buffer address)")
        if a.dtype != np.int32:
            raise TypeError(f"plan.{f} dtype {a.dtype} != int32 (ABI v"
                            f"{PLAN_ABI_VERSION})")
        if a.ndim != ndim:
            raise ValueError(f"plan.{f} rank {a.ndim} != {ndim} "
                             f"({'stacked' if stacked else 'single-chunk'} "
                             "form)")
        if not a.flags["C_CONTIGUOUS"]:
            raise ValueError(f"plan.{f} is not C-contiguous (ABI v"
                             f"{PLAN_ABI_VERSION})")
        out.append(a)
    return tuple(out)


def pad_plan(plan: StagedDedupPlan, slots: int, dims: int
             ) -> StagedDedupPlan:
    """Widen a host-built plan to a larger U bucket (chunks scanned
    together must share one shape). Extra slots are empty drops: distinct
    ascending out-of-range reps, start == end == N."""
    import numpy as np

    u0 = plan.rep.shape[0]
    if slots == u0:
        return plan
    if slots < u0:
        raise ValueError(f"cannot shrink plan bucket {u0} -> {slots}")
    n = plan.order.shape[0]
    extra = slots - u0
    pad_base = max(int(plan.rep[-1]) + 1, dims)
    rep = np.concatenate([
        np.asarray(plan.rep, np.int64),
        pad_base + np.arange(extra, dtype=np.int64)]).astype(np.int32)
    fill = np.full(extra, n, np.int32)
    return StagedDedupPlan(
        order=plan.order, lane_seg=plan.lane_seg, rep=rep,
        starts=np.concatenate([plan.starts, fill]),
        ends=np.concatenate([plan.ends, fill]))


def staged_gather(table: jnp.ndarray, plan: StagedDedupPlan,
                  fill: float = 0.0) -> jnp.ndarray:
    """[U] — each unique feature's row read ONCE (ascending ids, so the
    table walk is sequential; pad slots read the fill)."""
    return table.at[plan.rep].get(mode="fill", fill_value=fill)


def broadcast_lanes(uniq_vals: jnp.ndarray,
                    plan: StagedDedupPlan) -> jnp.ndarray:
    """[N] — unique-slot values fanned back out to the original lanes."""
    return uniq_vals[plan.lane_seg]


def staged_segment_totals(plan: StagedDedupPlan,
                          cols: jnp.ndarray) -> jnp.ndarray:
    """Per-slot sums of `cols` ([N] or [N, k] lane-ordered, f32) — one
    permute + one chunk-local cumsum + two boundary gathers; no scatter."""
    csort = cols[plan.order]
    zero = jnp.zeros((1,) + csort.shape[1:], csort.dtype)
    csum = jnp.concatenate([zero, jnp.cumsum(csort, axis=0)])
    return csum[plan.ends] - csum[plan.starts]


def staged_scatter_add(table: jnp.ndarray, plan: StagedDedupPlan,
                       sums: jnp.ndarray,
                       denom: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply per-slot sums [U] (pre-reduced, optionally count-averaged):
    the only scatter left, and it is unique+sorted+compact."""
    if denom is not None:
        sums = sums / jnp.maximum(denom, 1.0)
    return table.at[plan.rep].add(sums.astype(table.dtype), mode="drop",
                                  unique_indices=True,
                                  indices_are_sorted=True)


def staged_scatter_set(table: jnp.ndarray, plan: StagedDedupPlan,
                       vals: jnp.ndarray,
                       keep: jnp.ndarray) -> jnp.ndarray:
    """`table.at[rep].set(vals)` where `keep` [U] (bool) falls back to the
    slot's current value — the derive_w write, computed per UNIQUE slot so
    no gather-after-scatter round trip is needed."""
    old = staged_gather(table, plan)
    out = jnp.where(keep, vals.astype(table.dtype), old)
    return table.at[plan.rep].set(out, mode="drop", unique_indices=True,
                                  indices_are_sorted=True)


def staged_touch_max(table: jnp.ndarray, plan: StagedDedupPlan,
                     counts: jnp.ndarray) -> jnp.ndarray:
    """`touched.at[idx].max(fired)` — int8, U lanes."""
    return table.at[plan.rep].max((counts > 0).astype(table.dtype),
                                  mode="drop", unique_indices=True,
                                  indices_are_sorted=True)


def scatter_rows_flat(table: jnp.ndarray, keys: jnp.ndarray,
                      upd: jnp.ndarray,
                      _flat_limit: int = 2**31) -> jnp.ndarray:
    """Row scatter-add via the flat scalar view, for UNSORTED keys with
    duplicates into a table the caller has just zeroed (FFM's step, FM's
    `feature_shard` stripe).

    r4's micro on a v5e read such a scatter at 36.9 ms per 512k rows
    through the flat [E*k] view against 71.2 ms as [N,k] rows (8-lane
    padding did not rescue the row form: 69.1 ms). What it left out is the
    view itself: on the chip `table.reshape(-1)` of a `[2^23, 16]` f32
    table is a relayout of the whole table each way (three of the ten
    longest ops of FM's old 113 ms step), so the flat form is for short
    tables. FM's unsharded step left it in PR 31 for a sorted in-place row
    scatter (`write_runs`). `upd`'s last dim may carry fewer lanes than the
    table (k_logical <= k, e.g. FM's padded V): only those lanes are
    scattered, so pad lanes stay untouched. Drop semantics are preserved:
    pad keys (>= E) flatten to >= E*k.

    Falls back to the row form when E*k would overflow the int32 flat-index
    space (the flat product wraps negative and mode="drop" would silently
    discard every update). `_flat_limit` exists so tests can exercise the
    fallback branch at small table sizes.
    """
    e, k = table.shape
    kl = upd.shape[-1]
    if e * k < _flat_limit:
        fidx = keys[..., None] * k + jnp.arange(kl)
        return table.reshape(-1).at[fidx].add(upd, mode="drop").reshape(e, k)
    if kl != k:
        upd = jnp.concatenate(
            [upd, jnp.zeros(upd.shape[:-1] + (k - kl,), upd.dtype)], axis=-1)
    return table.at[keys].add(upd, mode="drop")
