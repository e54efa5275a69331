"""Sorted-window MXU gather/scatter: random model-table access as matmuls.

The engine's single-chip floor is XLA's scalar gather/scatter engine: the
verified v5e cost model (docs/perf_history.md, diag micros) puts one 524288-id gather at
~13 ms (~38M ids/s) and one scatter-add at ~7 ms (~70M updates/s) — both
latency-bound serial loops ~20x off the HBM roofline, and together they ARE
the AROW/FM step time (reference hot loop being beaten:
core/src/main/java/hivemall/model/DenseModel.java:193-201 — get/set by
feature index). This module re-expresses both ops as MXU work:

1. `lax.sort` the block's flat feature ids ONCE, carrying payloads through
   the sort network (positions for gather un-sorting, update columns for
   scatter) — bitonic sort is data-parallel vector ops, so payloads ride
   ~free where a permutation gather would hit the same 38M/s scalar engine.
2. The [E, c] table is viewed as [R, 128] lane tiles (c power-of-two entry
   columns interleave within a tile, 128//c entries per row). A chunk of C
   consecutive *sorted* ids spans a short contiguous row range (ids are
   hash-uniform over E — see runtime/benchmark.make_workload_ids), so each
   chunk touches one `dynamic_slice` window of W rows.
3. Within a chunk, gather = one-hot row matrix [C, W] @ window [W, 128]
   (MXU) followed by a cheap lane select (VPU); scatter-add = the transpose
   matmul [W, C] @ lane-spread updates [C, 128] accumulated into the window
   via `dynamic_update_slice`. A `lax.scan` threads the table through the
   chunks, so overlapping windows read-modify-write sequentially and
   duplicate ids accumulate inside the matmul — f32 sums, same value set as
   XLA's scatter-add up to addition order (which a duplicate scatter leaves
   unspecified anyway).

Total MXU volume is N * W * 128 MACs per pass — ~1-3 ms at the bench shape
(N=2^19, W=512) against the ~20 ms the scalar engine charges, and every
stage is dense vector/matrix work.

Correctness is unconditional: ids that land outside their chunk's window
(possible only for adversarially sparse/clustered ids — never for hashed
features) are counted, and a `lax.cond` routes JUST those through the
ordinary XLA gather/scatter as a residual pass, so the fast path's window
parameter is a performance knob, not a semantics knob. Out-of-range ids
follow the engine protocol: gather fills 0.0, scatter drops.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

LANES = 128

# The MXU's fast path multiplies in bf16; under the default precision XLA
# would round the gathered/scattered f32 TABLE values to 8 mantissa bits on
# TPU (CPU ignores precision — the parity suite would never see it).
# HIGHEST keeps every one-hot product exact in f32 (the default here).
# HIGH (TPU: 3-pass bf16) halves the MXU passes at <= 1-ulp f32 error —
# the one-hot operand is exact in bf16, so only the table side splits; the
# diag mxu_ group A/Bs both so the hardware window prices the trade.
PRECISION = jax.lax.Precision.HIGHEST


def _resolve_precision(precision):
    if precision is None:
        return PRECISION
    if isinstance(precision, str):
        return {"high": jax.lax.Precision.HIGH,
                "highest": jax.lax.Precision.HIGHEST}[precision]
    return precision


class WindowPlan(NamedTuple):
    """One block's sorted-id structure, shared by gathers and scatters.

    Invalid ids — negative OR >= n_entries — are mapped to the sentinel
    `n_entries` (gather fills 0.0, scatter drops). NOTE this deliberately
    differs from `.at[ids].get/add`, which wrap negative indices Python-style:
    the engine's padding protocol only ever produces ids in [0, dims] (parsers
    floor-mod, pad lanes use dims), so wrapping would just turn a caller bug
    into silent corruption of entry E-1."""

    sid: jnp.ndarray        # [Np] int32 sorted ids; invalid ids -> E (tail)
    spos: jnp.ndarray       # [Np] int32 original position of each sorted slot
    n: int                  # original (unpadded) id count
    n_entries: int          # E: table entry count the plan was built for
    chunk: int              # C: sorted ids per window


def _pad_to(x: jnp.ndarray, m: int, fill) -> jnp.ndarray:
    n = x.shape[0]
    if n % m == 0:
        return x
    return jnp.concatenate(
        [x, jnp.full((m - n % m,) + x.shape[1:], fill, x.dtype)])


def make_plan(ids_flat: jnp.ndarray, n_entries: int,
              *, chunk: int = 1024) -> WindowPlan:
    """Sort the block's flat ids once. `ids_flat` [N] int32; anything outside
    [0, n_entries) is mapped to the sentinel `n_entries` (sorts to the tail,
    gathers 0, scatters dropped)."""
    ids_flat = jnp.asarray(ids_flat, jnp.int32).reshape(-1)
    n = ids_flat.shape[0]
    ids_m = jnp.where((ids_flat >= 0) & (ids_flat < n_entries), ids_flat,
                      n_entries)
    pos = jnp.arange(n, dtype=jnp.int32)
    sid, spos = jax.lax.sort((ids_m, pos), num_keys=1)
    sid = _pad_to(sid, chunk, n_entries)
    if spos.shape[0] != sid.shape[0]:
        # pad positions with DISTINCT values >= n so the un-sorting sort in
        # gather() sends pad slots to the tail instead of colliding with
        # real position 0
        extra = jnp.arange(n, sid.shape[0] - spos.shape[0] + n,
                           dtype=jnp.int32)
        spos = jnp.concatenate([spos, extra])
    return WindowPlan(sid=sid, spos=spos, n=n, n_entries=n_entries,
                      chunk=chunk)


def _auto_window(plan: WindowPlan, rows: int) -> int:
    """Window rows per chunk: 4x the expected span of `chunk` consecutive
    sorted ids (hash-uniform ids make span concentration tight; anything
    past the window goes through the exact residual pass), power-of-two,
    floored at 128 rows so the dynamic-slice stays tile-aligned and the
    matmul K-dim stays MXU-worthy."""
    expected = max(1, rows * plan.chunk // max(1, plan.sid.shape[0]))
    w = 128
    while w < 4 * expected:
        w *= 2
    return min(w, rows)


def pad_cols(n: int) -> int:
    """Smallest power-of-two column count >= n — THE lane-protocol helper:
    tables fed to gather/scatter_add must have power-of-two columns so
    entries tile the 128-lane rows evenly (_table_geometry)."""
    c = 1
    while c < n:
        c *= 2
    return c


def _table_geometry(n_entries: int, cols: int, window_rows: int):
    if cols & (cols - 1) or cols > LANES:
        raise ValueError(f"cols must be a power of two <= {LANES}: {cols}")
    ipr = LANES // cols                      # entries per 128-lane row
    rows = max((n_entries + ipr - 1) // ipr, window_rows)
    return ipr, rows


def _tiles_of(table: jnp.ndarray, rows: int) -> jnp.ndarray:
    flat = table.reshape(-1)
    want = rows * LANES
    if flat.shape[0] < want:
        flat = jnp.concatenate(
            [flat, jnp.zeros((want - flat.shape[0],), flat.dtype)])
    return flat.reshape(rows, LANES)


def _chunk_meta(plan: WindowPlan, ipr: int, rows: int, w: int):
    """Per-chunk window starts + per-id window-relative geometry."""
    c = plan.chunk
    sid = plan.sid
    srow = jnp.minimum(sid, plan.n_entries - 1) // ipr  # valid ids only matter
    n_chunks = sid.shape[0] // c
    starts = jnp.minimum(srow.reshape(n_chunks, c)[:, 0], rows - w)
    rel = srow.reshape(n_chunks, c) - starts[:, None]           # [nc, C]
    valid = (sid < plan.n_entries).reshape(n_chunks, c)
    in_win = valid & (rel >= 0) & (rel < w)
    group = (jnp.minimum(sid, plan.n_entries - 1) % ipr).reshape(n_chunks, c)
    return starts, rel, group, valid, in_win


def gather(table: jnp.ndarray, plan: WindowPlan,
           window_rows: int | None = None,
           precision=None) -> jnp.ndarray:
    """`table.at[ids].get(mode="fill", fill_value=0.0)` over the plan's ids,
    returned in ORIGINAL id order. `table` is [E] or [E, c] (c a power of two
    <= 128); result is [N] or [N, c] f32."""
    squeeze = table.ndim == 1
    t2 = table[:, None] if squeeze else table
    e, c = t2.shape
    if e != plan.n_entries:
        raise ValueError(f"plan built for E={plan.n_entries}, table has {e}")
    prec = _resolve_precision(precision)
    ipr, rows = _table_geometry(e, c, 128)
    w = window_rows or _auto_window(plan, rows)
    ipr, rows = _table_geometry(e, c, w)
    tiles = _tiles_of(t2.astype(jnp.float32), rows)
    starts, rel, group, valid, in_win = _chunk_meta(plan, ipr, rows, w)
    cch = plan.chunk
    iota_w = jnp.arange(w, dtype=jnp.int32)
    iota_g = jnp.arange(ipr, dtype=jnp.int32)

    def body(_, xs):
        start, rel_c, grp_c, inw_c = xs
        win = jax.lax.dynamic_slice(tiles, (start, 0), (w, LANES))
        oh_row = ((rel_c[:, None] == iota_w[None, :]) & inw_c[:, None]) \
            .astype(jnp.float32)                                  # [C, W]
        picked = jnp.matmul(oh_row, win, precision=prec)     # [C, 128]
        oh_g = (grp_c[:, None] == iota_g[None, :]).astype(jnp.float32)
        vals = jnp.einsum("cg,cgk->ck", oh_g,
                          picked.reshape(cch, ipr, c),
                          precision=prec)                    # [C, c]
        return None, vals

    _, vals = jax.lax.scan(body, None, (starts, rel, group, in_win))
    vals = vals.reshape(-1, c)                                    # sorted order

    # residual pass: ids whose row fell outside their chunk's window
    res = valid & ~in_win
    any_res = jnp.any(res)

    def with_residual(v):
        rid = jnp.where(res.reshape(-1), plan.sid, e)
        rv = t2.astype(jnp.float32).at[rid].get(mode="fill", fill_value=0.0)
        return v + rv

    vals = jax.lax.cond(any_res, with_residual, lambda v: v, vals)

    # un-sort: one more payload-carrying sort, keyed by original position
    outs = jax.lax.sort((plan.spos,) + tuple(vals[:, j] for j in range(c)),
                        num_keys=1)
    out = jnp.stack(outs[1:], axis=-1)[: plan.n]
    return out[:, 0] if squeeze else out


def scatter_add(table: jnp.ndarray, ids_flat: jnp.ndarray,
                upd: jnp.ndarray, plan: WindowPlan,
                window_rows: int | None = None,
                precision=None) -> jnp.ndarray:
    """`table.at[ids].add(upd, mode="drop")` with the update columns carried
    through one id-keyed sort and accumulated window-by-window on the MXU.
    `table` [E] or [E, c]; `upd` [N] or [N, kl] with kl <= c (original id
    order; rides the sort; missing columns scatter nothing — the padded-lane
    protocol of scatter_rows_flat). Returns the updated table in its original
    shape/dtype. Sum order within a duplicated id differs from XLA's scatter
    (both are unspecified); values match to f32 tolerance."""
    squeeze = table.ndim == 1
    t2 = table[:, None] if squeeze else table
    u2 = upd[:, None] if upd.ndim == 1 else upd
    e, c = t2.shape
    if e != plan.n_entries:
        raise ValueError(f"plan built for E={plan.n_entries}, table has {e}")
    prec = _resolve_precision(precision)
    ipr, rows = _table_geometry(e, c, 128)
    w = window_rows or _auto_window(plan, rows)
    ipr, rows = _table_geometry(e, c, w)
    tiles = _tiles_of(t2.astype(jnp.float32), rows)

    # sort the updates into id order (stable sort == plan's order; equal keys
    # commute under addition anyway). Only the kl real columns ride the sort;
    # pad columns (kl < c) materialize as zeros afterwards.
    kl = u2.shape[-1]
    ids_flat = jnp.asarray(ids_flat, jnp.int32).reshape(-1)
    ids_m = jnp.where((ids_flat >= 0) & (ids_flat < e), ids_flat, e)
    sorted_ops = jax.lax.sort(
        (ids_m,) + tuple(u2[:, j].astype(jnp.float32) for j in range(kl)),
        num_keys=1)
    su = jnp.stack(sorted_ops[1:], axis=-1)                        # [N, kl]
    if kl < c:
        su = jnp.concatenate(
            [su, jnp.zeros(su.shape[:-1] + (c - kl,), su.dtype)], axis=-1)
    su = _pad_to(su, plan.chunk, 0.0)

    starts, rel, group, valid, in_win = _chunk_meta(plan, ipr, rows, w)
    cch = plan.chunk
    iota_w = jnp.arange(w, dtype=jnp.int32)
    iota_g = jnp.arange(ipr, dtype=jnp.int32)
    su3 = su.reshape(-1, cch, c)

    def body(tiles, xs):
        start, rel_c, grp_c, inw_c, u_c = xs
        win = jax.lax.dynamic_slice(tiles, (start, 0), (w, LANES))
        oh_row = ((rel_c[:, None] == iota_w[None, :]) & inw_c[:, None]) \
            .astype(jnp.float32)                                  # [C, W]
        oh_g = (grp_c[:, None] == iota_g[None, :]).astype(jnp.float32)
        spread = jnp.einsum("cg,ck->cgk", oh_g, u_c,
                            precision=prec).reshape(cch, LANES)
        win = win + jnp.matmul(oh_row.T, spread, precision=prec)
        return jax.lax.dynamic_update_slice(tiles, win, (start, 0)), None

    tiles, _ = jax.lax.scan(body, tiles,
                            (starts, rel, group, in_win, su3))

    res = valid & ~in_win
    any_res = jnp.any(res)

    def with_residual(t):
        rid = jnp.where(res.reshape(-1), plan.sid, e)
        flat = t.reshape(-1)
        # scatter the residual (sorted-order) updates through the flat view
        base = jnp.minimum(rid, e - 1) * c
        lanes = jnp.arange(c, dtype=jnp.int32)
        f = jnp.where(rid[:, None] < e, base[:, None] + lanes[None, :],
                      t.size)
        return flat.at[f].add(su, mode="drop").reshape(t.shape)

    tiles = jax.lax.cond(any_res, with_residual, lambda t: t, tiles)
    out = tiles.reshape(-1)[: e * c].reshape(e, c).astype(table.dtype)
    return out[:, 0] if squeeze else out
