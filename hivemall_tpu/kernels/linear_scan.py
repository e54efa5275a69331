"""Generic Pallas scan backend for the linear-learner engine.

Executes the SAME Rule definitions as core/engine.py (perceptron ... AdaGradRDA,
all regressors) but with every model table VMEM-resident and the block's rows
replayed sequentially in ONE kernel — the reference's per-row semantics
(ref: BinaryOnlineClassifierUDTF.java:111-247) without an HBM round trip per
row. Usable when the model fits on-chip (`vmem_resident_reason`: every table
twice within VMEM_TABLE_BUDGET_BYTES); larger models are refused before the
kernel is traced.

Hardware layout (lowers on real TPU Mosaic — scalar VMEM stores do not):
- model tables are reshaped to [D/128, 128]; a feature id becomes
  (row = id//128, lane = id%128). Gather = dynamic-slice the row + one-hot
  lane reduce; scatter = read-modify-write the row with a one-hot mask.
- indices/values/labels live in SMEM so feature ids are readable as scalars
  for the dynamic row slices. SMEM is ~1MB, so large blocks are chunked
  *outside* the kernel: `lax.scan` threads the tables through one grid-less
  pallas call per ~512-row chunk (tables ride HBM<->VMEM once per chunk).
- scalar globals (Welford stats) live in SMEM refs; `derive_w` (dual
  averaging) is honored lane-wise like the engine's scan mode.

The rule's `update(ctx, hyper)` is traced *inside* the kernel. Validated
against the engine's scan mode in interpret mode (tests/test_pallas_kernels.py)
and compiled on a real v5e chip (scripts/pallas_tpu_check.py).

Opt-in: `fit_linear(..., options="-pallas")` routes scan-mode training here
on a TPU and refuses elsewhere; interpret mode is a Python argument that only
tests pass.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import pad_rows_to_multiple
from ..core.engine import Rule, RowContext
from ..core.state import LinearState

LANES = 128


def _make_kernel(rule: Rule, hyper: dict, K: int, D: int, chunk: int,
                 slot_names: Tuple[str, ...], global_names: Tuple[str, ...]):
    use_cov = rule.use_covariance
    n_slots = len(slot_names)
    n_globals = len(global_names)

    def kernel(*refs):
        from jax.experimental import pallas as pl

        # layout: idx, val, y, meta(step0, live_rows), w_in, [cov_in],
        #         *slots_in, [globals_in], w_out, [cov_out], *slots_out,
        #         [globals_out], loss_out
        pos = 0
        idx_ref = refs[pos]; pos += 1     # SMEM [chunk, K] i32
        val_ref = refs[pos]; pos += 1     # SMEM [chunk, K] f32
        y_ref = refs[pos]; pos += 1       # SMEM [chunk, 1] f32
        meta_ref = refs[pos]; pos += 1    # SMEM [2] i32
        w_in = refs[pos]; pos += 1        # VMEM [D/128, 128]
        cov_in = None
        if use_cov:
            cov_in = refs[pos]; pos += 1
        slots_in = refs[pos : pos + n_slots]; pos += n_slots
        glob_in = refs[pos] if n_globals else None  # SMEM [n_globals, 1]
        pos += 1 if n_globals else 0
        w_out = refs[pos]; pos += 1
        cov_out = None
        if use_cov:
            cov_out = refs[pos]; pos += 1
        slots_out = refs[pos : pos + n_slots]; pos += n_slots
        glob_out = refs[pos] if n_globals else None
        pos += 1 if n_globals else 0
        loss_out = refs[pos]              # SMEM [chunk, 1] f32

        w_out[:, :] = w_in[:, :]
        if use_cov:
            cov_out[:, :] = cov_in[:, :]
        for s in range(n_slots):
            slots_out[s][:, :] = slots_in[s][:, :]
        # SMEM refs only allow scalar loads; copy element-wise
        for gi in range(n_globals):
            glob_out[gi, 0] = glob_in[gi, 0]

        step0 = meta_ref[0]
        live_rows = meta_ref[1]
        iota = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

        def row(b, _):
            row_live = (b < live_rows).astype(jnp.float32)
            y = y_ref[b, 0]
            t = (step0 + b + 1).astype(jnp.float32)

            gl = {}
            if n_globals:
                gl = {g: glob_out[gi, 0] for gi, g in enumerate(global_names)}
                if rule.pre_row is not None:
                    gl_new = rule.pre_row(dict(gl), y)
                    gl = {g: jnp.where(row_live > 0, gl_new[g], gl[g])
                          for g in global_names}
                    for gi, g in enumerate(global_names):
                        glob_out[gi, 0] = gl[g]

            rows = []
            ohs = []       # [1, LANES] one-hot lane masks
            livefs = []
            vals = []
            for k in range(K):
                fidx = idx_ref[b, k]
                live = jnp.logical_and(fidx >= 0, fidx < D)
                livef = live.astype(jnp.float32) * row_live
                sidx = jnp.where(live, fidx, 0)
                rows.append(sidx // LANES)
                ohs.append((iota == (sidx % LANES)).astype(jnp.float32))
                livefs.append(livef)
                vals.append(val_ref[b, k] * livef)

            def lane_gather(table, k, fill=0.0):
                v = jnp.sum(table[pl.ds(rows[k], 1), :] * ohs[k])
                if fill == 0.0:
                    return v * livefs[k]
                return jnp.where(livefs[k] > 0, v, fill)

            w = jnp.stack([lane_gather(w_out, k) for k in range(K)])
            val = jnp.stack(vals)
            cov = None
            variance = jnp.float32(0.0)
            if use_cov:
                cov = jnp.stack([lane_gather(cov_out, k, fill=1.0)
                                 for k in range(K)])
                variance = jnp.sum(cov * val * val)
            sl = {}
            for s, name in enumerate(slot_names):
                sl[name] = jnp.stack([lane_gather(slots_out[s], k)
                                      for k in range(K)])
            score = jnp.sum(w * val)
            sq_norm = jnp.sum(val * val)
            ctx = RowContext(w, cov, sl, val, y, score, sq_norm, variance, t, gl)
            out = rule.update(ctx, hyper)

            def lane_add(table, k, delta):
                r = table[pl.ds(rows[k], 1), :]
                table[pl.ds(rows[k], 1), :] = r + (delta * livefs[k]) * ohs[k]

            def lane_set(table, k, value, gate):
                r = table[pl.ds(rows[k], 1), :]
                m = ohs[k] * (gate * livefs[k])
                table[pl.ds(rows[k], 1), :] = r * (1.0 - m) + value * m

            if rule.derive_w is not None:
                sl_new = {n: ctx.slots[n] + out.dslots.get(n, 0.0) for n in sl}
                w_new = rule.derive_w(sl_new, t, hyper)
                w_new = jnp.where(out.updated, w_new, ctx.w)
                gate = out.updated.astype(jnp.float32)
                for k in range(K):
                    lane_set(w_out, k, w_new[k], gate)
            else:
                for k in range(K):
                    lane_add(w_out, k, out.dw[k])
            if use_cov and out.dcov is not None:
                for k in range(K):
                    lane_add(cov_out, k, out.dcov[k])
            for s, name in enumerate(slot_names):
                if name in out.dslots:
                    for k in range(K):
                        lane_add(slots_out[s], k, out.dslots[name][k])
            loss_out[b, 0] = out.loss * row_live
            return 0

        jax.lax.fori_loop(0, chunk, row, 0)

    return kernel


# VMEM the resident tables may claim: the v5e's capacity as Mosaic reports it
# ("Used 256.00M of 128.00M vmem"). The kernel holds every table twice, as a
# single-buffered whole-array input window and an output window. Measured on
# the v5e (jax 0.9.0, libtpu 0.0.34, PR 21): perceptron at 2^24 dims and AROW
# at 2^23 (both exactly 128 MiB) compile and match the engine scan;
# perceptron at 2^25 and AROW at 2^24 (256 MiB) are refused by the compiler.
VMEM_TABLE_BUDGET_BYTES = 128 << 20


def vmem_resident_reason(rule: Rule, dims: int):
    """Why a `dims`-wide model of this rule cannot run VMEM-resident, or
    None when it fits: 2 (in + out window) x tables (w [+ cov] + slots) x
    padded dims x 4 B against VMEM_TABLE_BUDGET_BYTES. Checked before the
    kernel is traced, so an oversized model is refused in words instead of
    dying inside Mosaic."""
    n_tables = 1 + (1 if rule.use_covariance else 0) + len(rule.slot_names)
    d_pad = (dims + LANES - 1) // LANES * LANES
    need = 2 * n_tables * d_pad * 4
    if need <= VMEM_TABLE_BUDGET_BYTES:
        return None
    max_dims = VMEM_TABLE_BUDGET_BYTES // (2 * n_tables * 4)
    return (f"{rule.name} at {dims} dims needs {need >> 20} MiB of VMEM "
            f"({n_tables} f32 table(s), each held as an input and an output "
            f"window) and the chip has {VMEM_TABLE_BUDGET_BYTES >> 20} MiB — "
            f"at most {max_dims} dims fit; use the default scan backend or "
            f"-mini_batch")


def _table_2d(flat: jnp.ndarray, d_pad: int) -> jnp.ndarray:
    d = flat.shape[0]
    if d_pad != d:
        flat = jnp.concatenate([flat, jnp.zeros((d_pad - d,), flat.dtype)])
    return flat.reshape(d_pad // LANES, LANES)


def _pick_chunk(b: int, k: int) -> int:
    # bound SMEM bytes: chunk*K*(4+4) <= ~32KB. SMEM is nominally 1MB but
    # Mosaic's own reservations leave well under 10% headroom (measured:
    # chunk*K=8192 overflowed by 1.6KB on v5e). Floor of 1, not more — a
    # higher floor would break the bound for very wide rows (K > 4096 still
    # cannot fit a single row's lanes; that regime doesn't fit the
    # VMEM-resident model path anyway).
    return max(1, min(b, 4096 // max(1, k)))


def pallas_scan_raw(rule: Rule, hyper: dict, state: LinearState,
                    indices, values, labels, interpret: bool = False):
    """Run one block through the VMEM-resident scan kernel.

    Returns (new_state, per_row_losses). API building block for
    make_pallas_scan_step and the dedicated AROW entry point.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slot_names = tuple(sorted(rule.slot_names))
    global_names = tuple(sorted(rule.global_names))
    use_cov = rule.use_covariance

    indices = jnp.asarray(indices, jnp.int32)
    values = jnp.asarray(values, jnp.float32)
    labels = jnp.asarray(labels, jnp.float32)
    B, K = indices.shape
    D = state.weights.shape[0]
    reason = vmem_resident_reason(rule, D)
    if reason is not None:
        raise ValueError(f"pallas scan kernel refused: {reason}")
    d_pad = (D + LANES - 1) // LANES * LANES
    n_rows = d_pad // LANES
    chunk = _pick_chunk(B, K)
    indices, values, labels = pad_rows_to_multiple(indices, values, labels,
                                                   chunk, D)
    n_chunks = indices.shape[0] // chunk

    kernel = _make_kernel(rule, hyper, K, D, chunk, slot_names, global_names)

    smem_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    # tables are whole-array VMEM refs
    vmem_spec = pl.BlockSpec((n_rows, LANES), lambda: (0, 0))

    n_tables = 1 + (1 if use_cov else 0) + len(slot_names)
    in_specs = [smem_spec, smem_spec, smem_spec, smem_spec] + \
               [vmem_spec] * n_tables + ([smem_spec] if global_names else [])
    out_specs = [vmem_spec] * n_tables + \
                ([smem_spec] if global_names else []) + [smem_spec]
    out_shape = [jax.ShapeDtypeStruct((n_rows, LANES), jnp.float32)] * n_tables
    if global_names:
        out_shape.append(
            jax.ShapeDtypeStruct((len(global_names), 1), jnp.float32))
    out_shape.append(jax.ShapeDtypeStruct((chunk, 1), jnp.float32))
    # alias table (and globals) inputs to outputs: in-place update chunk to chunk
    aliases = {4 + t: t for t in range(n_tables)}
    if global_names:
        aliases[4 + n_tables] = n_tables

    call = pl.pallas_call(kernel, in_specs=in_specs, out_specs=out_specs,
                          out_shape=out_shape,
                          input_output_aliases=aliases,
                          interpret=interpret)

    tables0 = [_table_2d(state.weights.astype(jnp.float32), d_pad)]
    if use_cov:
        tables0.append(_table_2d(state.covars.astype(jnp.float32), d_pad))
    for s in slot_names:
        tables0.append(_table_2d(state.slots[s].astype(jnp.float32), d_pad))
    gvec0 = (jnp.stack([state.globals[g].astype(jnp.float32)
                        for g in global_names]).reshape(-1, 1)
             if global_names else None)

    idx3 = indices.reshape(n_chunks, chunk, K)
    val3 = values.reshape(n_chunks, chunk, K)
    y3 = labels.reshape(n_chunks, chunk, 1)
    step0 = jnp.asarray(state.step, jnp.int32)
    b_live = jnp.minimum(
        jnp.maximum(B - jnp.arange(n_chunks, dtype=jnp.int32) * chunk, 0),
        chunk)

    def body(carry, xs):
        tables, gvec = carry
        ci, cv, cy, coff, clive = xs
        meta = jnp.stack([step0 + coff * chunk, clive])
        args = [ci, cv, cy, meta] + list(tables) + \
               ([gvec] if gvec is not None else [])
        outs = call(*args)
        new_tables = list(outs[:n_tables])
        new_gvec = outs[n_tables] if gvec is not None else None
        losses = outs[-1]
        return (new_tables, new_gvec), losses.reshape(-1)

    (tables, gvec), losses = jax.lax.scan(
        body, (tables0, gvec0),
        (idx3, val3, y3, jnp.arange(n_chunks, dtype=jnp.int32), b_live))
    losses = losses.reshape(-1)[:B]

    pos = 0
    w = tables[pos].reshape(-1)[:D]; pos += 1
    cov = None
    if use_cov:
        cov = tables[pos].reshape(-1)[:D]; pos += 1
    slots = {}
    for s in slot_names:
        slots[s] = tables[pos].reshape(-1)[:D]; pos += 1
    globals_ = dict(state.globals)
    if global_names:
        gflat = gvec.reshape(-1)
        globals_ = {g: gflat[gi] for gi, g in enumerate(global_names)}

    # touched: any live lane of any row (one cheap scatter outside the kernel)
    touched = state.touched.at[indices[:B]].max(
        jnp.ones((B, K), dtype=jnp.int8), mode="drop")
    new_state = state.replace(weights=w, covars=cov, slots=slots,
                              touched=touched, globals=globals_,
                              step=state.step + B)
    return new_state, losses


def make_pallas_scan_step(rule: Rule, hyper: dict, interpret: bool = False):
    """step(state, indices, values, labels) -> (state, loss_sum), API-equal to
    core.engine.make_train_step(mode='scan')."""

    @jax.jit
    def step(state: LinearState, indices, values, labels):
        new_state, losses = pallas_scan_raw(rule, hyper, state, indices,
                                            values, labels,
                                            interpret=interpret)
        return new_state, jnp.sum(losses)

    return step
