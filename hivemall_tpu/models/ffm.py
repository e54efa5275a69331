"""Field-aware Factorization Machines: train_ffm / ffm_predict.

Mirrors the reference FFM subsystem (ref: fm/FieldAwareFactorizationMachineUDTF.java:57-200,
fm/FieldAwareFactorizationMachineModel.java:40-200, fm/FFMStringFeatureMapModel.java:32-200,
fm/FFMHyperParameters.java):

- prediction  p = [w0] + [sum_i w_i x_i] + sum_{i<j} <V_{i,f_j}, V_{j,f_i}> x_i x_j
  (global bias and linear term both optional: -w0 / -disable_wi)
- V updates: SGD with per-factor L2, AdaGrad per-entry learning rate
  eta0_V / sqrt(eps + gg) using the accumulator value BEFORE the current
  gradient (ref: etaV, FieldAwareFactorizationMachineModel.java:126-134)
- W updates: FTRL by default (z/n accumulators, L1 sparsity; ref:
  updateWiFTRL, FFMStringFeatureMapModel.java:133-157), plain SGD with
  -disable_ftrl
- gradient note: the correct pairwise gradient d p/d V_{i,f_j,f} =
  x_i x_j V_{j,f_i,f} is used here; the reference's sumVfX multiplies by x_i
  instead of x_j (FieldAwareFactorizationMachineModel.java:170-181), which
  coincides exactly on the usual FFM encoding where all feature values are 1.

TPU-first: the reference's (feature, field) hash-map entries become ONE dense
[Dv, k] HBM table addressed by a mixed pair-hash (the standard hashed-FFM
trick); a row's pairwise term is a [K, K, k] gather + einsum, its V gradient
one scatter-add of K*K rows.

The `-mini_batch` rule (`block_step`): every row's update is computed against
the parameters at the block's start and the updates are SUMMED per entry
(V, its AdaGrad accumulator, FTRL's z and n; SGD's w with -disable_ftrl).
One departure from a row-at-a-time FTRL: the weight is derived once from the
block's summed z and n by FTRL's closed form, as the linear learners'
derive_w rules are, so no write depends on the order of a block's lanes.

V's initial value is a function of the entry alone (`initial_v`), so that a
reader of emitted entries, or a reference, can have it for the entries it
needs without drawing a table.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.batch import is_rect, longest_row, pack_rows, pad_to_bucket
from ..core.emission import select_rows, table_to_host
from ..ops.convergence import ConversionState
from ..ops.eta import EtaEstimator, get_eta
from ..ops import scatter
from ..ops.scatter import reduce_block_runs, write_runs
from ..runtime.metrics import REGISTRY
from ..runtime.tracing import (SCOPE_APPLY, SCOPE_GATHER, SCOPE_LOSS,
                               SCOPE_REDUCE, SCOPE_RULE, SCOPE_TOUCHED,
                               SPAN_CALL, SPAN_EMIT, SPAN_EPOCH, SPAN_PARSE,
                               SPAN_SYNC, TRACER)
from ..utils.feature import FMFeature
from ..utils.options import Options
from .base import (_stage_rows, dispatch_step, init_state_spanned,
                   prepared_blocks, record_write_path, stage_training_rows)
from .fm import _fm_options

_MIX1 = 0x9E3779B1
_MIX2 = 0x85EBCA6B
_MIX3 = 0xC2B2AE35

# One [rows, K, K, k + 1] f32 activation of the pairwise block may take this
# much: the row tile of a -mini_batch step is the largest divisor of the
# block's rows that keeps it under (`choose_row_tile`). A 1024-row block of
# 40-lane pairs at k = 4 (32 MiB whole) runs as four tiles of 256 rows.
ROW_TILE_BYTES = 8 << 20


def pair_hash(feature_idx, field, dv: int):
    """Deterministic (feature, field) -> V-table row. Works identically in
    numpy and jnp (int32 wraparound mixing)."""
    h = feature_idx.astype(jnp.uint32) * jnp.uint32(_MIX1) \
        + field.astype(jnp.uint32) * jnp.uint32(_MIX2)
    h ^= h >> 15
    h *= jnp.uint32(0x2C1B3C6D)
    h ^= h >> 12
    return (h % jnp.uint32(dv)).astype(jnp.int32)


def _fmix32(h):
    h ^= h >> 16
    h *= jnp.uint32(0x7FEB352D)
    h ^= h >> 15
    h *= jnp.uint32(0x846CA68B)
    h ^= h >> 16
    return h


def initial_v(entries, factors: int, seed: int, sigma: float):
    """V's initial value at `entries` ([...] ints) -> [..., factors] f32: a
    bell-shaped draw of standard deviation `sigma` that is a function of
    (seed, entry, factor) alone. Two 32-bit hashes of
    `entry * 0x9E3779B1 + factor * 0x85EBCA6B + seed * 0xC2B2AE35` (the
    `lowbias32` finalizer, the second on the first's input xor 0x68E31DA4)
    give four 16-bit uniforms u1..u4; the value is
    `(u1 + u2 + u3 + u4 - 131070) * float32(sqrt(3) * sigma / 65536)`: an
    Irwin-Hall sum, exact in float32 up to its one product."""
    f = jnp.arange(factors, dtype=jnp.uint32)
    a = entries.astype(jnp.uint32)[..., None] * jnp.uint32(_MIX1) \
        + f * jnp.uint32(_MIX2) \
        + jnp.uint32((int(seed) * _MIX3) & 0xFFFFFFFF)
    h1 = _fmix32(a)
    h2 = _fmix32(a ^ jnp.uint32(0x68E31DA4))
    lo = jnp.uint32(0xFFFF)
    s = (h1 & lo) + (h1 >> 16) + (h2 & lo) + (h2 >> 16)
    scale = np.float32(math.sqrt(3.0) * float(sigma) / 65536.0)
    return (s.astype(jnp.int32) - 131070).astype(jnp.float32) * scale


@struct.dataclass
class FFMState:
    w0: jnp.ndarray  # []
    w: jnp.ndarray  # [D]
    z: jnp.ndarray  # [D] FTRL z
    n: jnp.ndarray  # [D] FTRL n (or adagrad gg for SGD-W — unused then)
    v: jnp.ndarray  # [Dv, k]; on a v5e `{0,1:T(4,128)}` at k = 4: compact,
    # an entry's k values in one tile (PERF.md section 4)
    v_gg: jnp.ndarray  # [Dv] adagrad accumulator for V
    touched: jnp.ndarray  # [D] int8
    v_touched: jnp.ndarray  # [Dv] int8: entries a trained row's pair addressed
    step: jnp.ndarray  # []


@dataclass(frozen=True)
class FFMHyper:
    factors: int = 4
    classification: bool = True
    lambda_w: float = 0.01
    lambda_v: float = 0.01
    global_bias: bool = False
    linear_coeff: bool = True
    use_ftrl: bool = True
    use_adagrad: bool = True
    eta0_v: float = 1.0
    eps: float = 1.0
    alpha: float = 0.1  # FTRL
    beta: float = 1.0
    lambda1: float = 0.1
    lambda2: float = 0.01
    sigma: float = 0.1
    num_features: int = 1 << 21  # -feature_hashing 21 default
    num_fields: int = 1024
    v_dims: int = 1 << 22
    eta: EtaEstimator = EtaEstimator("invscaling", 0.2, power_t=0.1)
    min_target: float = -3.0e38
    max_target: float = 3.0e38
    seed: int = 31


@partial(jax.jit, static_argnums=(0,))
def init_ffm_state(hyper: FFMHyper) -> FFMState:
    """One compiled program: V is written where it will live, an entry at a
    time from `initial_v`, with no second table beside it."""
    d, dv, k = hyper.num_features, hyper.v_dims, hyper.factors
    return FFMState(
        w0=jnp.zeros(()),
        w=jnp.zeros((d,)),
        z=jnp.zeros((d,)),
        n=jnp.zeros((d,)),
        v=initial_v(jnp.arange(dv, dtype=jnp.uint32), k, hyper.seed,
                    hyper.sigma),
        v_gg=jnp.zeros((dv,)),
        touched=jnp.zeros((d,), jnp.int8),
        v_touched=jnp.zeros((dv,), jnp.int8),
        step=jnp.zeros((), jnp.int32),
    )


def _row_pair_keys(idx, fields, dv):
    """[K] features -> [K, K] pair table rows: keys[i, j] = h(idx_i, field_j)."""
    return pair_hash(idx[:, None].astype(jnp.uint32),
                     jnp.broadcast_to(fields[None, :], (idx.shape[0], idx.shape[0]))
                     .astype(jnp.uint32), dv)


def _real_pairs(val):
    """[..., K] values -> [..., K, K] bool: lanes i != j that both carry a
    value (a pad lane's value is 0)."""
    lane = val != 0.0
    k = val.shape[-1]
    return lane[..., :, None] & lane[..., None, :] & ~jnp.eye(k, dtype=bool)


def _row_predict(state: FFMState, idx, val, fields, hyper: FFMHyper,
                 Vg=None, keys=None):
    K = idx.shape[0]
    if keys is None:
        keys = _row_pair_keys(idx, fields, hyper.v_dims)  # [K, K]
    if Vg is None:
        Vg = state.v[keys]  # [K, K, k]
    # pair mask: i < j and both lanes real (padded lanes have val 0)
    inter = jnp.einsum("ijf,jif->ij", Vg, Vg)  # <V_{i,fj}, V_{j,fi}>
    xx = val[:, None] * val[None, :]
    pair_term = jnp.sum(jnp.triu(inter * xx, 1))
    p = pair_term
    if hyper.linear_coeff:
        w = state.w.at[idx].get(mode="fill", fill_value=0.0)
        p = p + jnp.sum(w * val)
    if hyper.global_bias:
        p = p + state.w0
    return p, keys, Vg, xx


def sharded_ffm_gather(st: FFMState, idx, val, fields, hyper: FFMHyper,
                       shard_axis: str, stripe_w: int, stripe_v: int):
    """The ONE copy of the feature-sharded FFM row gather + prediction,
    shared by the sharded train step and the sharded serving path. Each
    device gathers the entries it owns of the row's [K, K, k] pair block
    (exactly one owner per hashed key) and ONE psum rebuilds the full block
    (and its gg) everywhere. Returns (p, local_keys, Vg, xx, gg, own)."""
    from ..core.striping import translate_to_stripe

    keys = _row_pair_keys(idx, fields, hyper.v_dims)
    dev = jax.lax.axis_index(shard_axis)
    lkeys = keys - dev * stripe_v
    owned = (lkeys >= 0) & (lkeys < stripe_v)
    lkeys = jnp.where(owned, lkeys, stripe_v)
    own = owned.astype(val.dtype)
    Vg, gg = jax.lax.psum(
        (st.v.at[lkeys].get(mode="fill", fill_value=0.0),
         st.v_gg.at[lkeys].get(mode="fill", fill_value=0.0)),
        shard_axis)
    xx = val[:, None] * val[None, :]
    inter = jnp.einsum("ijf,jif->ij", Vg, Vg)
    p = jnp.sum(jnp.triu(inter * xx, 1))
    if hyper.linear_coeff:
        lidx, vmask = translate_to_stripe(idx, val, shard_axis, stripe_w)
        w = st.w.at[lidx].get(mode="fill", fill_value=0.0)
        p = p + jax.lax.psum(jnp.sum(w * vmask), shard_axis)
    if hyper.global_bias:
        p = p + st.w0
    return p, lkeys, Vg, xx, gg, own


def choose_row_tile(rows: int, lanes: int, factors: int) -> int:
    """Rows of one tile of a -mini_batch block's pairwise work: the largest
    divisor of `rows` whose [tile, lanes, lanes, factors + 1] f32 activation
    stays under ROW_TILE_BYTES (as `core/engine.apply_strategy` chooses from
    shapes). `-row_chunk` overrides it."""
    fit = max(1, ROW_TILE_BYTES // (lanes * lanes * (factors + 1) * 4))
    return max(c for c in range(1, min(rows, fit) + 1) if rows % c == 0)


def make_ffm_step(hyper: FFMHyper, mode: str = "scan",
                  row_chunk: Optional[int] = None,
                  feature_shard: Optional[Tuple[str, int, int]] = None,
                  pair_width: Optional[int] = None,
                  jit: bool = True):
    """Jitted FFM block update. scan = per row, each row against the state
    the row before it left; minibatch = the block rule of this module's
    header.

    The mini-batch step off a stripe (`block_step`) works in the block's own
    index space and writes in place, with no pass and no temporary as long
    as a table. The [B, K, K, k] pairwise activations are its memory hot
    spot (they grow with the square of the field count), so the block's rows
    go through the gathers and the rule a tile at a time (`row_chunk` rows,
    or `choose_row_tile`'s where it is None or does not divide the block),
    every tile against the SAME block-start tables: nothing is written
    before the last tile has read. The block's deltas, [B, K, K, k + 1] and
    their keys, are kept between the two phases.

    `pair_width`: the leading lanes of a block that can carry a feature (the
    caller's longest row, rounded up to 8); the lanes beyond are the
    bucket's padding and are cut off before anything is gathered, so a
    39-field row on the 64-lane bucket works on 40 x 40 pair lanes, not
    64 x 64.

    `feature_shard=(axis_name, stripe_w, stripe_v)` stripes the linear
    tables (w/z/n/touched, [num_features]) and the pairwise V tables
    (v/v_gg/v_touched, [v_dims]) across the mesh. Unlike FM, a row's
    pairwise term needs CROSS-stripe products <V_{i,f_j}, V_{j,f_i}> — the
    two rows of a pair can live on different devices — so each device
    gathers the entries it owns of the row's [K, K, k] block (exactly one
    owner per hashed key) and ONE psum reconstructs the full block
    everywhere; updates scatter back owned entries only. Keys hash with the
    ORIGINAL v_dims, so the model is the same function as the unsharded
    one. A stripe keeps the scatter-add of each row group into the carried
    tables (`apply_row_group`, `row_chunk` rows a group): its gather needs
    a psum a row, and no benchmark cell runs it."""

    if feature_shard is None:
        translate_w = None

        def predict_gather(st: FFMState, idx, val, fields):
            p, keys, Vg, xx = _row_predict(st, idx, val, fields, hyper)
            gg = st.v_gg[keys]
            own = jnp.ones(keys.shape, val.dtype)
            return p, keys, Vg, xx, gg, own
    else:
        from ..core.striping import translate_to_stripe

        shard_axis, stripe_w, stripe_v = feature_shard

        def translate_w(idx, val):
            return translate_to_stripe(idx, val, shard_axis, stripe_w)

        def predict_gather(st: FFMState, idx, val, fields):
            return sharded_ffm_gather(st, idx, val, fields, hyper,
                                      shard_axis, stripe_w, stripe_v)

    def cut(*lanes):   # `pair_width` None: every lane stays
        return tuple(a[:, :pair_width] for a in lanes)

    def dloss_fn(p, y):
        if hyper.classification:
            z = p * y
            return (jax.nn.sigmoid(z) - 1.0) * y, jnp.logaddexp(0.0, -z)
        pc = jnp.clip(p, hyper.min_target, hyper.max_target)
        return pc - y, 0.5 * (pc - y) ** 2

    def eta_of(gg, t):
        # AdaGrad eta per (i,j) entry, using gg BEFORE this grad
        if hyper.use_adagrad:
            return hyper.eta0_v / jnp.sqrt(hyper.eps + gg)
        return jnp.broadcast_to(hyper.eta.eta(t), gg.shape)

    def pair_rule(Vg, gg, xx, g, t):
        """The ONE copy of the V rule, for a row ([K, K, k] / [K, K] blocks,
        scalar g and t) or a tile of rows (a leading axis on each):
        dV[i, j] = -eta * (g x_i x_j V_{j, f_i} + 2 lambda V_{i, f_j}) with
        AdaGrad's eta from gg BEFORE this gradient, and the gradient's
        square for gg. -> (dV, dgg)."""
        spread = lambda a: jnp.asarray(a)[..., None, None]
        gradV = (spread(g) * xx)[..., None] * jnp.swapaxes(Vg, -3, -2)
        dV = -eta_of(gg, spread(t))[..., None] * (
            gradV + 2.0 * hyper.lambda_v * Vg)
        return dV, jnp.sum(gradV * gradV, axis=-1)

    def row_updates(st: FFMState, idx, val, fields, y, t):
        p, keys, Vg, xx, gg, own = predict_gather(st, idx, val, fields)
        g, loss = dloss_fn(p, y)
        dV, dgg = pair_rule(Vg, gg, xx, g, t)
        # padded lanes (val == 0 kills the gradient already; the L2 pull
        # must not apply to untouched entries), the diagonal and, sharded,
        # foreign entries neither move nor count as touched
        real = _real_pairs(val) & (own > 0)
        dV = jnp.where(real[:, :, None], dV, 0.0)
        dgg = jnp.where(real, dgg, 0.0)
        return p, g, loss, keys, dV, dgg, real

    def ftrl_w(z, n):
        return jnp.where(
            jnp.abs(z) <= hyper.lambda1,
            0.0,
            (jnp.sign(z) * hyper.lambda1 - z)
            / ((hyper.beta + jnp.sqrt(n)) / hyper.alpha + hyper.lambda2))

    def w_deltas(w_old, z_old, n_old, val, g, t):
        """A lane's (dz, dn, dw) against the given old values: FTRL's dual
        updates (dw unused) or SGD's weight step (dz, dn zero)."""
        grad = g * val
        if hyper.use_ftrl:
            dn = grad * grad
            sigma = (jnp.sqrt(n_old + dn) - jnp.sqrt(n_old)) / hyper.alpha
            return grad - sigma * w_old, dn, jnp.zeros_like(val)
        dw = -hyper.eta.eta(t) * (grad + 2.0 * hyper.lambda_w * w_old)
        return jnp.zeros_like(val), jnp.zeros_like(val), dw

    def gather_w(st: FFMState, idx):
        take = lambda tab: tab.at[idx].get(mode="fill", fill_value=0.0)
        return take(st.w), take(st.z), take(st.n)

    def scan_step(state: FFMState, indices, values, fields, labels):
        def body(st: FFMState, row):
            idx, val, fld, y = row
            t = (st.step + 1).astype(jnp.float32)
            p, g, loss, keys, dV, dgg, real = row_updates(
                st, idx, val, fld, y, t)
            widx, wval = (idx, val) if translate_w is None \
                else translate_w(idx, val)
            keys = keys.reshape(-1)
            st = st.replace(
                v=st.v.at[keys].add(dV.reshape(-1, dV.shape[-1]),
                                    mode="drop"),
                v_gg=st.v_gg.at[keys].add(dgg.reshape(-1), mode="drop"),
                v_touched=st.v_touched.at[keys].max(
                    real.reshape(-1).astype(jnp.int8), mode="drop"),
                step=st.step + 1)
            if hyper.linear_coeff:
                w_old, z_old, n_old = gather_w(st, widx)
                dz, dn, dw = w_deltas(w_old, z_old, n_old, wval, g, t)
                w_new = ftrl_w(z_old + dz, n_old + dn) if hyper.use_ftrl \
                    else w_old + dw
                st = st.replace(
                    z=st.z.at[widx].add(dz, mode="drop"),
                    n=st.n.at[widx].add(dn, mode="drop"),
                    w=st.w.at[widx].set(w_new, mode="drop"),
                )
            if hyper.global_bias:
                eta = hyper.eta.eta(t)
                st = st.replace(w0=st.w0 - eta * (g + 2.0 * hyper.lambda_w * st.w0))
            touched = st.touched.at[widx].max(
                jnp.ones_like(widx, dtype=jnp.int8), mode="drop")
            return st.replace(touched=touched), loss

        state, losses = jax.lax.scan(
            body, state, (*cut(indices, values, fields), labels))
        return state, jnp.sum(losses)

    def apply_w0(st: FFMState, base: FFMState, g_sum, b, t_last):
        # one batch-level w0 update with eta at the batch's final timestep
        if not hyper.global_bias:
            return st
        eta = hyper.eta.eta(t_last)
        return st.replace(w0=base.w0 - eta * (
            g_sum + b * 2.0 * hyper.lambda_w * base.w0))

    # ---- the mini-batch step off a stripe: block-local, in place ----

    def tile_deltas(state: FFMState, idx, val, fld, lab, ts):
        """One tile's rows against the block-start `state`: the pair keys
        [c * K * K] (a pair that is not real carries v_dims, outside the
        table), their V and gg deltas, and each row's g and loss."""
        with jax.named_scope(SCOPE_GATHER):
            keys = jax.vmap(
                lambda i, f: _row_pair_keys(i, f, hyper.v_dims))(idx, fld)
            keys = jnp.where(_real_pairs(val), keys, hyper.v_dims)
            Vg = state.v.at[keys].get(mode="fill", fill_value=0.0)
            gg = state.v_gg.at[keys].get(mode="fill", fill_value=0.0)
            w_old = state.w.at[idx].get(mode="fill", fill_value=0.0)
        with jax.named_scope(SCOPE_LOSS):
            # a pair that is not real gathered zeros, and the block is
            # symmetric: half the whole sum is the sum over i < j
            xx = val[:, :, None] * val[:, None, :]
            p = 0.5 * jnp.sum(
                jnp.sum(Vg * jnp.swapaxes(Vg, 1, 2), axis=-1) * xx,
                axis=(1, 2))
            if hyper.linear_coeff:
                p = p + jnp.sum(w_old * val, axis=1)
            if hyper.global_bias:
                p = p + state.w0
            g, loss = dloss_fn(p, lab)
        with jax.named_scope(SCOPE_RULE):
            # zeros where the pair is not real: both gathers filled zeros
            dV, dgg = pair_rule(Vg, gg, xx, g, ts)
        # kept flat between the two phases: a [c, K, K] array's short minor
        # axis sits in padded tiles (40 lanes of 128 at 39 fields)
        return (keys.reshape(-1), dV.reshape(-1, dV.shape[-1]),
                dgg.reshape(-1), g, loss)

    def apply_pairs(state: FFMState, keys, dV, dgg):
        """The block's V and gg deltas added at its keys ([N], [N, k], [N]:
        every lane of an entry adds, so an entry takes its lanes' sum), in
        place; the keys' entries flagged. Where V's rows go through the
        run-write kernel (`ops/scatter.py::row_write_path`), `sorted_pairs`;
        elsewhere plain scatter-adds: on a v5e the compiler sorts the lanes
        of the two scalar scatters itself, and a sort of ours before all
        three (with or without each run summed first) made XLA's write no
        faster (PERF.md section 5)."""
        # a table that varies over a mesh axis (an `ffm_mix` replica) keeps
        # XLA's add: no cell measures the kernel there
        path = "xla" if jax.typeof(state.v).vma else scatter.row_write_path(
            state.v.dtype, *state.v.shape, keys.shape[0],
            jax.default_backend())
        if path != "xla":   # a test's patched rule may say "interpret"
            return sorted_pairs(state, keys, dV, dgg, path == "interpret")
        with jax.named_scope(SCOPE_APPLY):
            v = state.v.at[keys].add(dV, mode="drop")
            v_gg = state.v_gg.at[keys].add(dgg, mode="drop")
        with jax.named_scope(SCOPE_TOUCHED):
            v_touched = state.v_touched.at[keys].set(
                jnp.ones(keys.shape, jnp.int8), mode="drop")
        return state.replace(v=v, v_gg=v_gg, v_touched=v_touched)

    def sorted_pairs(state: FFMState, keys, dV, dgg, interpret: bool):
        """`apply_pairs` through the kernel: the lanes sorted by key once
        with V's k columns and gg's riding, V's rows added lane by lane in
        the 128-row tiles that hold a touched entry and nowhere else, gg
        and the flag written by XLA's sorted in-place scatters at the same
        keys. The sums of XLA's form in another order."""
        from ..kernels.run_write import add_rows_kernel

        with jax.named_scope(SCOPE_REDUCE):
            ids, dgg = jax.lax.sort((keys, dgg), num_keys=1, is_stable=False)
            dV = scatter.sort_rows(keys, dV.T)
        with jax.named_scope(SCOPE_APPLY):
            v = add_rows_kernel(state.v, ids, list(dV), interpret=interpret)
            v_gg = state.v_gg.at[ids].add(dgg, mode="drop",
                                          indices_are_sorted=True)
        with jax.named_scope(SCOPE_TOUCHED):
            v_touched = state.v_touched.at[ids].set(
                jnp.ones(ids.shape, jnp.int8), mode="drop",
                indices_are_sorted=True)
        return state.replace(v=v, v_gg=v_gg, v_touched=v_touched)

    def apply_linear(state: FFMState, idx, val, g, ts):
        """The block's linear term through `reduce_block_runs`: a lane's dz
        and dn (SGD: dw) summed over each run of one feature's lanes, the
        old values riding the sort, each written once at the sorted ids."""
        flat = lambda a: a.reshape(-1)
        with jax.named_scope(SCOPE_GATHER):
            w_old, z_old, n_old = gather_w(state, idx)
        with jax.named_scope(SCOPE_RULE):
            dz, dn, dw = w_deltas(w_old, z_old, n_old, val, g[:, None],
                                  ts[:, None])
        with jax.named_scope(SCOPE_REDUCE):
            runs = reduce_block_runs(
                flat(idx), hyper.num_features,
                {"z": flat(dz), "n": flat(dn), "w": flat(dw),
                 "count": jnp.ones((idx.size,), jnp.float32)},
                {"w": flat(w_old), "z": flat(z_old), "n": flat(n_old)})
        with jax.named_scope(SCOPE_APPLY):
            z = runs.carried["z"] + runs.sums["z"]
            n = runs.carried["n"] + runs.sums["n"]
            w = ftrl_w(z, n) if hyper.use_ftrl \
                else runs.carried["w"] + runs.sums["w"]
            state = state.replace(w=write_runs(state.w, runs, w))
            if hyper.use_ftrl:
                state = state.replace(z=write_runs(state.z, runs, z),
                                      n=write_runs(state.n, runs, n))
        return state, runs

    def block_step(state: FFMState, indices, values, fields, labels):
        indices, values, fields = cut(indices, values, fields)
        b, kp = indices.shape
        c = row_chunk if row_chunk and b % row_chunk == 0 \
            else choose_row_tile(b, kp, hyper.factors)
        ts = (state.step + 1 + jnp.arange(b)).astype(jnp.float32)
        rows = (indices, values, fields, labels, ts)
        one = lambda tile: tile_deltas(state, *tile)
        if c == b:
            keys, dV, dgg, g, loss = one(rows)
        else:
            keys, dV, dgg, g, loss = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]),
                jax.lax.map(one, jax.tree.map(
                    lambda a: a.reshape((b // c, c) + a.shape[1:]), rows)))
        st = apply_pairs(state, keys, dV, dgg)
        if hyper.linear_coeff:
            st, runs = apply_linear(st, indices, values, g, ts)
            with jax.named_scope(SCOPE_TOUCHED):
                st = st.replace(touched=write_runs(
                    st.touched, runs, runs.sums["count"] > 0, "max"))
        else:
            with jax.named_scope(SCOPE_TOUCHED):
                st = st.replace(touched=st.touched.at[indices].max(
                    jnp.ones_like(indices, dtype=jnp.int8), mode="drop"))
        st = apply_w0(st, state, jnp.sum(g), b, ts[-1])
        with jax.named_scope(SCOPE_LOSS):
            loss_sum = jnp.sum(loss)
        return st.replace(step=state.step + b), loss_sum

    # ---- the mini-batch step on a stripe: scatter-adds a row group ----

    def apply_row_group(carry: FFMState, base: FFMState, idx, val, fld, lab,
                        ts):
        """Compute one row group's updates against the block-start `base`
        parameters and scatter-accumulate them into `carry`: V, gg, FTRL's
        duals (SGD: w) and the flags. The FTRL weight is the caller's, once
        the block's last group is in."""
        p, g, loss, keys, dV, dgg, real = jax.vmap(
            lambda i, v, f, y, t: row_updates(base, i, v, f, y, t))(
                idx, val, fld, lab, ts)
        widx, wval = jax.vmap(translate_w)(idx, val)
        k = dV.shape[-1]
        keys = keys.reshape(-1)
        carry = carry.replace(
            v=carry.v.at[keys].add(dV.reshape(-1, k), mode="drop"),
            v_gg=carry.v_gg.at[keys].add(dgg.reshape(-1), mode="drop"),
            v_touched=carry.v_touched.at[keys].max(
                real.reshape(-1).astype(jnp.int8), mode="drop"),
        )
        if hyper.linear_coeff:
            dz, dn, dw = w_deltas(*gather_w(base, widx), wval, g[:, None],
                                  ts[:, None])
            carry = carry.replace(
                z=carry.z.at[widx].add(dz, mode="drop"),
                n=carry.n.at[widx].add(dn, mode="drop"),
                w=carry.w.at[widx].add(dw, mode="drop"),
            )
        carry = carry.replace(touched=carry.touched.at[widx].max(
            jnp.ones_like(widx, dtype=jnp.int8), mode="drop"))
        return carry, jnp.sum(loss), jnp.sum(g)

    def stripe_step(state: FFMState, indices, values, fields, labels):
        indices, values, fields = cut(indices, values, fields)
        b = indices.shape[0]
        c = row_chunk or b
        if b % c != 0:
            raise ValueError(f"batch {b} not divisible by row_chunk {c}")
        ts = (state.step + 1 + jnp.arange(b)).astype(jnp.float32)
        if c == b:
            st, loss, g_sum = apply_row_group(
                state, state, indices, values, fields, labels, ts)
        else:
            def body(st, group):
                st, loss, g_sum = apply_row_group(st, state, *group)
                return st, (loss, g_sum)

            st, (losses, g_sums) = jax.lax.scan(body, state, jax.tree.map(
                lambda a: a.reshape((b // c, c) + a.shape[1:]),
                (indices, values, fields, labels, ts)))
            loss, g_sum = jnp.sum(losses), jnp.sum(g_sums)
        if hyper.linear_coeff and hyper.use_ftrl:
            # every lane of a feature writes the same value: the closed
            # form of the block's summed duals
            widx, _ = jax.vmap(translate_w)(indices, values)
            _, z, n = gather_w(st, widx)
            st = st.replace(w=st.w.at[widx].set(ftrl_w(z, n), mode="drop"))
        st = apply_w0(st, state, g_sum, b, ts[-1])
        return st.replace(step=state.step + b), loss

    if row_chunk is not None and mode != "minibatch":
        raise ValueError("row_chunk applies to minibatch mode only")
    if row_chunk is not None and row_chunk <= 0:
        raise ValueError(f"row_chunk must be positive, got {row_chunk}")
    if mode == "scan":
        fn = scan_step
    elif feature_shard is not None:
        fn = stripe_step
    else:
        fn = block_step
    # jit=False returns the raw traceable fn for embedding in an outer scan
    # (e.g. a whole-epoch lax.scan over staged blocks, scripts/bench_ffm.py)
    return jax.jit(fn, donate_argnums=(0,)) if jit else fn


@partial(jax.jit, static_argnums=(0,))
def _ffm_scores_jit(hyper: FFMHyper, st: FFMState, idx, val, fld):
    def one(i, v, f):
        p, _, _, _ = _row_predict(st, i, v, f, hyper)
        return p

    return jax.vmap(one)(idx, val, fld)


def _ffm_scores(state: FFMState, hyper: FFMHyper, indices, values, fields):
    # module-level jit (hyper static): repeated same-shape calls — e.g. the
    # SQL engine's per-row ffm_predict scalar — hit the trace cache instead
    # of re-tracing a fresh closure every call
    return _ffm_scores_jit(hyper, state, indices, values, fields)


# 2: V entries come from `v_touched` and the rest is `initial_v` (1 compared
# the whole table with a `jax.random.normal` draw)
_BLOB_VERSION = 2


@partial(jax.jit, donate_argnums=(0,))
def _with_rows(st: FFMState, feats, w, v_keys, v):
    """`st` with the emitted rows written over it and flagged (from_blob)."""
    return st.replace(
        w=st.w.at[feats].set(w), touched=st.touched.at[feats].set(1),
        v=st.v.at[v_keys].set(v), v_touched=st.v_touched.at[v_keys].set(1))


@dataclass
class TrainedFFMModel:
    state: FFMState
    hyper: FFMHyper

    def predict(self, rows) -> np.ndarray:
        idx, val, fld, _ = _stage_ffm_rows(rows, None, self.hyper)
        return np.asarray(_ffm_scores(self.state, self.hyper, idx, val, fld))

    def model_rows(self):
        """close()'s whole output, `(w0, feats, w, v_keys, v)`: the bias,
        the linear rows `(feature, w)` of every feature a trained row
        carried, and the V entries `(entry, V[factors])` that a trained
        row's pair addressed (`entry = pair_hash(feature, partner's field)`;
        every other entry still holds `initial_v`). Both key spaces are
        selected where the tables are (`core/emission.select_rows`): what
        crosses to the host is each space's packed flags and its emitted
        entries."""
        st = self.state
        with TRACER.span(SPAN_EMIT, args={
                "table_dtype": str(st.v.dtype)}) as emit:
            feats, (w,), stats = select_rows(st.touched, [("w", st.w)])
            v_keys, (v,), v_stats = select_rows(st.v_touched, [("v", st.v)])
            for key in ("chunks", "d2h_bytes", "h2d_bytes"):
                stats[key] = stats.get(key, 0) + v_stats.get(key, 0)
            w0 = table_to_host(st.w0, "w0", stats)
            emit.set(rows_out=len(feats) + len(v_keys), v_rows_out=len(v_keys),
                     **stats)
        REGISTRY.counter("emit", "rows").increment(len(feats) + len(v_keys))
        return float(w0), feats, w, v_keys, v

    def to_blob(self, half_float: bool = True, rows=None) -> bytes:
        """Serialize the whole predictable model to one compressed blob —
        the FFMPredictionModel.writeExternal analog (ref:
        fm/FFMPredictionModel.java:46,149-200: ZigZag-LEB128 feature keys +
        half-float values + compression). Built from `model_rows()` (or
        from `rows`, what it returned, where the caller has them): the
        linear part reuses encode_sparse_model (the same recipe); the
        emitted V entries are stored as (delta-zigzag key, k values), and
        the rest is `initial_v` again at decode, so from_blob().predict
        reproduces this model's predict (bit-exact with half_float=False)."""
        import struct as _struct

        from ..utils.codec import (compress_model_blob, encode_sparse_model,
                                   float_to_half, zigzag_leb128_encode_array)

        hy = self.hyper
        w0, feats, w, v_keys, v = rows or self.model_rows()
        w_blob = encode_sparse_model(feats, w, half_float=half_float)
        vkeys = zigzag_leb128_encode_array(np.diff(v_keys, prepend=0))
        vvals = np.asarray(v, np.float32).ravel()
        v_bytes = (float_to_half(vvals).tobytes() if half_float
                   else vvals.astype("<f4").tobytes())
        flags = ((1 if hy.linear_coeff else 0)
                 | (2 if hy.global_bias else 0)
                 | (4 if hy.classification else 0)
                 | (8 if half_float else 0))
        header = _struct.pack(
            "<4sBiqqqqfBf", b"HFM1", _BLOB_VERSION, hy.factors,
            hy.num_features, hy.num_fields, hy.v_dims, hy.seed, hy.sigma,
            flags, w0)
        v_section = compress_model_blob(
            _struct.pack("<qq", len(v_keys), len(vkeys)) + vkeys + v_bytes)
        return (header + _struct.pack("<qq", len(w_blob), len(v_section))
                + w_blob + v_section)

    @classmethod
    def from_blob(cls, blob: bytes) -> "TrainedFFMModel":
        """Decode a to_blob() emission back into a servable model — the
        FFMPredictUDF deserialization path (ref: fm/FFMPredictUDF.java +
        FFMPredictionModel.readExternal)."""
        import struct as _struct

        from ..utils.codec import (decode_sparse_model,
                                   decompress_model_blob, half_to_float,
                                   zigzag_leb128_decode_array)

        magic, version, k, d, nf, dv, seed, sigma, flags, w0 = \
            _struct.unpack_from("<4sBiqqqqfBf", blob, 0)
        if magic != b"HFM1" or version != _BLOB_VERSION:
            raise ValueError("not an FFM model blob of this version")
        off = _struct.calcsize("<4sBiqqqqfBf")
        wlen, vlen = _struct.unpack_from("<qq", blob, off)
        off += 16
        feats, w_sparse = decode_sparse_model(blob[off:off + wlen])
        off += wlen
        v_section = decompress_model_blob(blob[off:off + vlen])
        n_changed, keys_len = _struct.unpack_from("<qq", v_section, 0)
        deltas = zigzag_leb128_decode_array(v_section[16:16 + keys_len],
                                            n_changed)
        vkeys = np.cumsum(np.asarray(deltas, np.int64))
        raw = v_section[16 + keys_len:]
        if flags & 8:
            vvals = half_to_float(
                np.frombuffer(raw, np.float16, count=n_changed * k))
        else:
            vvals = np.frombuffer(raw, "<f4", count=n_changed * k).copy()
        vvals = np.asarray(vvals, np.float32).reshape(n_changed, k)

        hyper = FFMHyper(factors=int(k), classification=bool(flags & 4),
                         global_bias=bool(flags & 2),
                         linear_coeff=bool(flags & 1),
                         num_features=int(d), num_fields=int(nf),
                         v_dims=int(dv), seed=int(seed), sigma=float(sigma))
        st = _with_rows(init_ffm_state(hyper),
                        np.asarray(feats, np.int32),
                        np.asarray(w_sparse, np.float32),
                        vkeys.astype(np.int32), vvals)
        return cls(state=st.replace(w0=jnp.asarray(np.float32(w0))),
                   hyper=hyper)


def _is_field_arrays(rows) -> bool:
    return isinstance(rows, tuple) and len(rows) == 3


def _parse_ffm_text(rows, hyper: FFMHyper):
    """`"<field>:<index>:<value>"` rows -> (idx_rows, val_rows, fld_rows),
    a list of arrays each."""
    idx_rows, val_rows, fld_rows = [], [], []
    for row in rows:
        parsed = [FMFeature.parse(f, num_features=hyper.num_features,
                                  num_fields=hyper.num_fields) for f in row]
        idx_rows.append(np.asarray([f.index for f in parsed], np.int64))
        val_rows.append(np.asarray([f.value for f in parsed], np.float32))
        fld_rows.append(np.asarray([max(f.field, 0) for f in parsed],
                                   np.int64))
    return idx_rows, val_rows, fld_rows


def _pack_fields(fld_rows, width: int, num_fields: int) -> np.ndarray:
    """[rows, width] int32 field lanes of one block (pad lane: field 0)."""
    out = np.zeros((len(fld_rows), width), np.int32)
    if is_rect(fld_rows):
        k = min(width, fld_rows.shape[1])
        out[:, :k] = fld_rows[:, :k] % num_fields
        return out
    for r, row in enumerate(fld_rows):
        k = min(width, len(row))
        out[r, :k] = np.asarray(row[:k], np.int64) % num_fields
    return out


def _stage_ffm_rows(rows, labels, hyper: FFMHyper):
    """Rows of either form as padded [B, K] arrays (pad lane: idx =
    num_features OOB, value 0, field 0) and their labels as the steps take
    them: `predict`'s staging, and a test's."""
    if _is_field_arrays(rows):
        idx_rows, val_rows, fld_rows = rows
    else:
        idx_rows, val_rows, fld_rows = _parse_ffm_text(rows, hyper)
    idx_rows, val_rows = _stage_rows((idx_rows, val_rows), hyper.num_features)
    width = pad_to_bucket(longest_row(idx_rows))
    blk = pack_rows(idx_rows, val_rows, np.zeros(len(idx_rows)),
                    hyper.num_features, width=width)
    lab = None
    if labels is not None:
        lab = np.asarray(labels, np.float32)
        if hyper.classification:
            lab = np.where(lab > 0, 1.0, -1.0).astype(np.float32)
    return (blk.indices, blk.values,
            _pack_fields(fld_rows, width, hyper.num_fields), lab)


def _ffm_options() -> Options:
    o = _fm_options()
    o.add("w0", "global_bias", False, "Include global bias w0 [default: OFF]")
    o.add("disable_wi", "no_coeff", False, "Exclude the linear term")
    o.add("feature_hashing", None, True, "Feature hashing bits [18,31] [default 21]",
          default=21, type=int)
    o.add("num_fields", None, True, "Number of fields [default 1024]", default=1024,
          type=int)
    o.add("disable_adagrad", None, False, "Disable AdaGrad for V")
    o.add("eta0_V", None, True,
          "Initial learning rate for V [default 1.0]. -mini_batch B sums a "
          "block's B steps at the rate from before the block: lower it with "
          "B (1.0 diverges at B = 1024)", default=1.0, type=float)
    o.add("eps", None, True, "AdaGrad denominator constant [default 1.0]",
          default=1.0, type=float)
    o.add("disable_ftrl", None, False, "Disable FTRL for W")
    o.add("alpha", "alphaFTRL", True, "FTRL alpha [default 0.1]", default=0.1,
          type=float)
    o.add("beta", "betaFTRL", True, "FTRL beta [default 1.0]", default=1.0, type=float)
    o.add("lambda1", None, True, "FTRL L1 [default 0.1]", default=0.1, type=float)
    o.add("lambda2", None, True, "FTRL L2 [default 0.01]", default=0.01, type=float)
    o.add("v_bits", None, True,
          "log2 size of the hashed V table [default 22]. An entry starts at "
          "sigma * sqrt(3) * (u1 + u2 + u3 + u4 - 2), four 16-bit uniforms "
          "hashed from (seed, entry, factor): models/ffm.py::initial_v",
          default=22, type=int)
    o.add("row_chunk", None, True,
          "Rows of one tile of a minibatch's K^2 pairwise work, in place of "
          "the tile chosen from the block's shape (bounds activation "
          "memory; must divide -mini_batch; 0 = the chosen tile)",
          default=0, type=int)
    return o


def train_ffm(rows, labels, options: Optional[str] = None) -> TrainedFFMModel:
    """`rows`: `"<field>:<index>:<value>"` strings a row, or the pre-hashed
    `(idx_rows, val_rows, field_rows)`, three `[n, lanes]` arrays (or lists
    of a row's arrays). Both forms of the same rows give the same model."""
    with TRACER.span(SPAN_CALL, args={"entry": "ffm"}) as call:
        return _train_ffm(call, rows, labels, options)


def _train_ffm(call, rows, labels, options) -> TrainedFFMModel:
    cl = _ffm_options().parse(options, "train_ffm")
    lam = cl.get_float("lambda0", 0.01)
    hyper = FFMHyper(
        factors=cl.get_int("factor", 4),
        classification=True,  # FFM is a CTR classifier; -c accepted for parity
        lambda_w=lam,
        lambda_v=lam,
        global_bias=cl.has("w0"),
        linear_coeff=not cl.has("disable_wi"),
        use_ftrl=not cl.has("disable_ftrl"),
        use_adagrad=not cl.has("disable_adagrad"),
        eta0_v=cl.get_float("eta0_V", 1.0),
        eps=cl.get_float("eps", 1.0),
        alpha=cl.get_float("alpha", 0.1),
        beta=cl.get_float("beta", 1.0),
        lambda1=cl.get_float("lambda1", 0.1),
        lambda2=cl.get_float("lambda2", 0.01),
        sigma=cl.get_float("sigma", 0.1),
        num_features=1 << cl.get_int("feature_hashing", 21),
        num_fields=cl.get_int("num_fields", 1024),
        v_dims=1 << cl.get_int("v_bits", 22),
        eta=get_eta(cl, 0.2),
        seed=cl.get_int("seed", 31),
    )
    dims = hyper.num_features
    lab = np.asarray(labels, np.float32)
    if hyper.classification:
        lab = np.where(lab > 0, 1.0, -1.0).astype(np.float32)
    fld_rows: list = []

    def parse_text(features, dims_):
        with TRACER.span(SPAN_PARSE) as sp:
            idx_rows, val_rows, fields = _parse_ffm_text(features, hyper)
            tokens = sum(len(r) for r in idx_rows)
            sp.set(tokens=tokens, native=False)
        REGISTRY.counter("train", "parse_tokens").increment(tokens)
        fld_rows.extend(fields)
        return [r % dims_ for r in idx_rows], val_rows

    if _is_field_arrays(rows):
        fld_rows = rows[2]
        idx_rows, val_rows, width = stage_training_rows(rows[:2], dims)
    else:
        idx_rows, val_rows, width = stage_training_rows(rows, dims,
                                                        stage=parse_text)
    n = len(idx_rows)
    longest = longest_row(idx_rows)
    pair_width = min(width, -(-longest // 8) * 8)
    mini_batch = cl.get_int("mini_batch", 1)
    mode = "minibatch" if mini_batch > 1 else "scan"
    if mode == "minibatch" and hyper.use_adagrad and not cl.has("eta0_V"):
        import warnings

        warnings.warn(
            f"train_ffm -mini_batch {mini_batch} at the default -eta0_V 1.0: "
            "the block rule sums a block's rows' steps at AdaGrad's rate "
            "from BEFORE the block, so an entry that many of its rows "
            "address takes that many full steps at once and V can diverge "
            "(docs/migration.md); set -eta0_V lower (the benchmark's "
            "Criteo deployment runs 0.01 at -mini_batch 1024)", stacklevel=3)
    block = mini_batch if mode == "minibatch" else cl.get_int("block_size", 4096)
    row_chunk = cl.get_int("row_chunk", 0) or None
    if row_chunk is not None:
        # positivity is validated by make_ffm_step (single source)
        if mode != "minibatch":
            raise ValueError("-row_chunk requires -mini_batch > 1 "
                             "(it tiles the minibatch pairwise work)")
        if block % row_chunk != 0:
            raise ValueError(
                f"-mini_batch {block} not divisible by -row_chunk {row_chunk}")
    pairs = n * longest * (longest - 1) if is_rect(idx_rows) \
        else sum(len(r) * (len(r) - 1) for r in idx_rows)
    call.set(dims=dims, rows=n, mini_batch=mini_batch, mode=mode,
             fields=longest, pairs_per_row=pairs // max(n, 1),
             v_dims=hyper.v_dims)
    if mode == "minibatch":
        # the one plan `make_ffm_step` has off a `feature_shard` stripe; the
        # tile of a full block (a trailing partial block, which an explicit
        # -row_chunk need not divide, takes the tile chosen from its rows)
        call.set(apply="batch_local", row_tile=row_chunk or choose_row_tile(
            min(block, max(n, 1)), pair_width, hyper.factors))
    step = make_ffm_step(hyper, mode, row_chunk=row_chunk,
                         pair_width=pair_width)
    state = init_state_spanned(init_ffm_state, hyper)
    call.set(table_dtype=str(state.v.dtype))
    kernel_lanes = 0   # of a row's lanes, those the step hands the kernel
    if mode == "minibatch":
        # V's rows are added through the kernel where `row_write_path` says
        # so (gg and the flag by XLA's scatters); the linear term's tables
        # are the step's writes at a block's runs
        linear = {}
        if hyper.linear_coeff:
            linear = {"w": state.w, "touched": state.touched}
            if hyper.use_ftrl:
                linear.update(z=state.z, n=state.n)
        rows = ()
        if scatter.row_write_path(state.v.dtype, *state.v.shape,
                                  block * pair_width ** 2,
                                  jax.default_backend()) != "xla":
            rows = ("v",)
            kernel_lanes = pair_width ** 2
        kernel_lanes += pair_width * record_write_path(
            call, linear, dims, block * pair_width, rows=rows)
    iters = cl.get_int("iters", 1)
    conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
    # progress counters, as fit_linear keeps them
    iter_counter = REGISTRY.counter("hivemall", "ffm.iterations")
    row_counter = REGISTRY.counter("hivemall", "ffm.examples")
    real_lanes = REGISTRY.counter("train", "pair_lanes")
    padded_lanes = REGISTRY.counter("train", "pair_lanes_padded")
    kernel_counter = REGISTRY.counter("train", "kernel_write_lanes")
    step_no = 0
    for it in range(max(1, iters)):
        with TRACER.span(SPAN_EPOCH, args={"epoch": it}) as epoch:
            at = iter(range(0, n, block))

            def fields_of(blk):
                s = next(at)
                return (_pack_fields(fld_rows[s:s + blk.batch_size], width,
                                     hyper.num_fields),)

            # losses stay on the device through the epoch, as fit_linear's do
            epoch_losses = []
            for blk in prepared_blocks(idx_rows, val_rows, lab, dims, block,
                                       width, extra=fields_of):
                state, loss = dispatch_step(step, step_no, state, *blk[:2],
                                            blk[3], blk[2])
                step_no += 1
                epoch_losses.append(loss)
                row_counter.increment(blk[0].shape[0])
                padded_lanes.increment(blk[0].shape[0] * pair_width ** 2)
                kernel_counter.increment(blk[0].shape[0] * kernel_lanes)
            iter_counter.increment()
            real_lanes.increment(pairs)
            with TRACER.span(SPAN_SYNC, args={"fetches": len(epoch_losses)}):
                epoch_loss = float(np.sum(jax.device_get(epoch_losses)))
            epoch.set(steps=len(epoch_losses), pair_lanes=pairs,
                      pair_lanes_padded=n * pair_width ** 2)
            call.set(epochs=it + 1)
            conv.incr_loss(epoch_loss)
            if iters > 1 and conv.is_converged(n):
                break
    return TrainedFFMModel(state=state, hyper=hyper)


def ffm_predict(model: TrainedFFMModel, rows: Sequence[Sequence[str]]) -> np.ndarray:
    """`ffm_predict` equivalent (ref: fm/FFMPredictUDF.java deserializes the
    compressed model; here the trained model object scores directly)."""
    return model.predict(rows)
