"""Factorization Machines: train_fm / fm_predict.

Mirrors the reference FM subsystem (ref: fm/FactorizationMachineUDTF.java:115-560,
fm/FactorizationMachineModel.java:118-300, fm/FMHyperParameters.java:30-110):

- prediction  p = w0 + sum_i w_i x_i + 1/2 sum_f [(sum_i V_if x_i)^2 - sum_i V_if^2 x_i^2]
- dloss: classification (sigmoid(p*y) - 1)*y with y in {-1,1}; regression
  p clamped to [min_target, max_target], p - y
- SGD updates with per-group L2: w0 -= eta*(g + 2*lambda_w0*w0),
  wi -= eta*(g*xi + 2*lambda_w*wi),
  Vif -= eta*(g*(xi*sumVfX_f - Vif*xi^2) + 2*lambda_Vf*Vif)
- adaptive regularization (-adareg): a validation fraction of rows updates the
  lambdas instead of theta (ref: trainLambda, FactorizationMachineUDTF.java:404-412,
  FactorizationMachineModel.java:253-300)
- multi-epoch: the reference serializes rows to a NioStatefullSegment temp
  file and replays in close() (ref: :291-332, :521-559); TPU-first the staged
  FeatureBlocks simply re-run, with the same ConversionState early exit.

TPU-first design: V is one [D, k] HBM table; a row's factor block is a [K, k]
gather, sumVfX is a matvec, and the V update is one fused outer-product —
batched across B rows in minibatch mode (the bench hot path).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..constants import DEFAULT_NUM_FEATURES
from ..core.batch import (fillable_lanes, iter_blocks, longest_row,
                          pad_to_bucket, shuffle_rows)
from ..core.emission import select_rows, table_to_host
from ..core.engine import make_cut_step
from ..ops.convergence import ConversionState
from ..ops.eta import EtaEstimator, get_eta
from ..ops.scatter import reduce_block_runs, scatter_rows_flat, write_runs
from ..runtime.metrics import REGISTRY
from ..runtime.tracing import (SCOPE_APPLY, SCOPE_GATHER, SCOPE_LOSS,
                               SCOPE_REDUCE, SCOPE_RULE, SCOPE_TOUCHED,
                               SPAN_CALL, SPAN_EMIT, SPAN_EPOCH, SPAN_SYNC,
                               TRACER)
from ..utils.options import Options
from .base import (FeatureRows, _stage_rows, base_options, dispatch_step,
                   init_state_spanned, prepared_blocks, record_write_path,
                   stage_training_rows)

DOUBLE_MIN = -1.7976931348623157e308  # mirrors Double.MIN_VALUE default semantics:
# the reference's minTarget default is Double.MIN_VALUE (smallest positive!),
# maxTarget Double.MAX_VALUE — i.e. clamping is effectively [tiny, huge] unless
# the user passes -min/-max. We default to no-op bounds instead (saner, and
# identical whenever the user sets them explicitly).


@struct.dataclass
class FMState:
    w0: jnp.ndarray  # []
    w: jnp.ndarray  # [D]
    v: jnp.ndarray  # [D, k]
    lambda_w0: jnp.ndarray  # []
    lambda_w: jnp.ndarray  # []
    lambda_v: jnp.ndarray  # [k]
    touched: jnp.ndarray  # [D] int8
    step: jnp.ndarray  # [] int32


@dataclass(frozen=True)
class FMHyper:
    factors: int = 5
    classification: bool = False
    lambda0: float = 0.01
    sigma: float = 0.1
    min_target: float = -3.0e38
    max_target: float = 3.0e38
    eta: EtaEstimator = EtaEstimator("invscaling", 0.05, power_t=0.1)
    adareg: bool = False
    va_ratio: float = 0.05
    seed: int = 31

    @property
    def padded_factors(self) -> int:
        """Physical lane count of the V table: k rounded up to a multiple
        of 8 when k > 4 (TPU f32 sublane granularity), for tile-aligned
        storage and row gathers at zero measured cost (r4: row gather
        28.5M/s == padded 28.2M/s). Pad lanes init to 0 and stay 0: their
        grad terms are products with their own zero V entries, their
        lambda_v is 0, and the mini-batch step writes a row's pad lanes as
        the zeros they are; model_rows / codecs slice back to the logical k.
        What the padding costs the step on a v5e, as the chip has V
        (`{0,1:T(8,128)}`, a row's lanes 32 MiB apart at 2^23 dims): the
        sorted in-place row scatter of a [1024, 64] block's 65,536 rows
        writes all 16 lanes, 6.2 ms of an 11.7 ms step at k = 10 (PERF.md
        section 6, PR 31); whether 10 lanes would cost ten sixteenths of
        that has not been measured."""
        k = self.factors
        if k > 4 and k % 8:
            return k + (8 - k % 8)
        return k


def init_fm_state(dims: int, hyper: FMHyper) -> FMState:
    k = hyper.factors
    k_pad = hyper.padded_factors
    key = jax.random.PRNGKey(hyper.seed)
    # 'random' init: uniform in [-maxval..maxval]-ish; 'gaussian': N(0, sigma).
    # We use gaussian * sigma for both (the reference default for
    # classification; regression's 'random' differs only in distribution shape,
    # ref: fm/VInitScheme.java).
    v = jax.random.normal(key, (dims, k), dtype=jnp.float32) * hyper.sigma
    if k_pad != k:
        v = jnp.concatenate(
            [v, jnp.zeros((dims, k_pad - k), jnp.float32)], axis=1)
    return FMState(
        w0=jnp.zeros((), jnp.float32),
        w=jnp.zeros((dims,), jnp.float32),
        v=v,
        lambda_w0=jnp.asarray(hyper.lambda0, jnp.float32),
        lambda_w=jnp.asarray(hyper.lambda0, jnp.float32),
        # pad-lane lambdas are 0: their V entries are pinned at 0, so any
        # nonzero lambda would only add a dead multiply
        lambda_v=jnp.concatenate(
            [jnp.full((k,), hyper.lambda0, jnp.float32),
             jnp.zeros((k_pad - k,), jnp.float32)]),
        touched=jnp.zeros((dims,), jnp.int8),
        step=jnp.zeros((), jnp.int32),
    )


def _row_predict(w0, wg, vg, val):
    """p and sumVfX for one row from gathered slices (padding lanes are 0)."""
    linear = jnp.sum(wg * val)
    vx = vg * val[:, None]  # [K, k]
    sum_vfx = jnp.sum(vx, axis=0)  # [k]
    sum_v2x2 = jnp.sum(vx * vx, axis=0)  # [k]
    p = w0 + linear + 0.5 * jnp.sum(sum_vfx * sum_vfx - sum_v2x2)
    return p, sum_vfx


def sharded_gather_predict(w, v, w0, idx, val, shard_axis: str, stripe: int):
    """The ONE copy of the feature-sharded FM gather + prediction, used by
    both the sharded train step and the sharded serving path (so train-time
    and serve-time p can never drift): translate global ids into the local
    [stripe] tables (foreign/pad lanes -> the drop slot, value masked to 0),
    gather owned lanes, and combine the three prediction partials with a
    single fused psum over the stripe axis. Works on any leading batch
    shape; idx/val are [..., K]."""
    from ..core.striping import translate_to_stripe

    lidx, vmask = translate_to_stripe(idx, val, shard_axis, stripe)
    wg = w.at[lidx].get(mode="fill", fill_value=0.0)
    vg = v.at[lidx].get(mode="fill", fill_value=0.0)
    vx = vg * vmask[..., None]
    linear, sum_vfx, sum_v2x2 = jax.lax.psum(
        (jnp.sum(wg * vmask, axis=-1),
         jnp.sum(vx, axis=-2),
         jnp.sum(vx * vx, axis=-2)), shard_axis)
    p = w0 + linear + 0.5 * jnp.sum(sum_vfx * sum_vfx - sum_v2x2, axis=-1)
    return wg, vg, vmask, lidx, p, sum_vfx


def _dloss_and_loss(p, y, hyper: FMHyper):
    if hyper.classification:
        # dloss = (sigmoid(p*y) - 1)*y; loss = log(1 + exp(-p*y))
        z = p * y
        g = (jax.nn.sigmoid(z) - 1.0) * y
        loss = jnp.logaddexp(0.0, -z)
    else:
        pc = jnp.clip(p, hyper.min_target, hyper.max_target)
        g = pc - y
        loss = 0.5 * g * g  # squared loss for cv tracking
    return g, loss


def make_fm_step(hyper: FMHyper, mode: str = "minibatch",
                 feature_shard: Optional[Tuple[str, int]] = None,
                 jit: bool = True):
    """Jitted FM block update. scan = reference-exact sequential; minibatch =
    accumulate-then-apply against block-start parameters: each parameter's
    accumulated delta divided by its update count — w/V per-feature touch
    counts, w0 by the batch size — exactly the reference's own mini-batch
    application rule (sum/count, ref: RegressionBaseUDTF.java:281-295 +
    utils/lang/FloatAccumulator.java:38-41; the reference FM itself is
    per-row-only, so averaging is the documented bridge semantic, same as
    core/engine.py's minibatch mode).

    The mini-batch step applies a block in the block's own index space
    (`apply_block`, as core/engine.py's `batch_local` strategy does): what
    it costs follows rows x lanes x factors, not the table. On a v5e with a
    [1024, 64] block and k = 10 in 16 lanes it takes 11.5 ms at 2^23 dims,
    5.9 at 2^20, 3.0 at 2^16, where the step it replaced (zeroed [D,16]
    delta tables through the flat view, w riding a pad lane of a packed
    copy of V, one divide-add pass over the table) took 113, 19.0 and 7.5;
    no table is short enough for that plan to win, so there is one (PERF.md
    section 6, PR 31).

    `feature_shard=(axis_name, stripe)` runs the same step on a [D/stripe]
    model stripe inside shard_map — the FM analog of the engine's
    feature-sharded training (the V table is the framework's largest model
    state: [2^24, k] does not fit one chip with optimizer state). Per row,
    each device gathers its owned lanes, the three prediction partials
    (linear term, sumVfX[k], sumV2X2[k]) psum over the stripe axis, and the
    lane updates — functions of (global g, global sumVfX, lane-local w/V) —
    scatter into the local stripe only. Exact up to psum order. adareg is
    not supported sharded (its lambda updates need cross-stripe v' sums).
    A stripe keeps the dense apply (`apply_stripe`: zeroed delta tables and
    one pass over each): its prediction needs a psum a row, no benchmark
    cell runs it, and it has not been measured against the block-local one."""
    if feature_shard is not None and hyper.adareg:
        raise ValueError("adareg is not supported with feature_shard")

    if feature_shard is None:
        def gather_and_predict(state: FMState, idx, val):
            wg = state.w.at[idx].get(mode="fill", fill_value=0.0)
            vg = state.v.at[idx].get(mode="fill", fill_value=0.0)
            p, sum_vfx = _row_predict(state.w0, wg, vg, val)
            return wg, vg, val, idx, p, sum_vfx
    else:
        shard_axis, stripe = feature_shard

        def gather_and_predict(state: FMState, idx, val):
            wg, vg, vmask, lidx, p, sum_vfx = sharded_gather_predict(
                state.w, state.v, state.w0, idx, val, shard_axis, stripe)
            return wg, vg, vmask, lidx, p, sum_vfx

    def row_deltas(state: FMState, idx, val, y, t):
        with jax.named_scope(SCOPE_RULE):
            eta = hyper.eta.eta(t)
        with jax.named_scope(SCOPE_GATHER):
            wg, vg, eff_val, sidx, p, sum_vfx = gather_and_predict(
                state, idx, val)
        with jax.named_scope(SCOPE_LOSS):
            g, loss = _dloss_and_loss(p, y, hyper)
        with jax.named_scope(SCOPE_RULE):
            dw0 = -eta * (g + 2.0 * state.lambda_w0 * state.w0)
            dw = -eta * (g * eff_val + 2.0 * state.lambda_w * wg)
            x2 = eff_val * eff_val
            grad_v = eff_val[:, None] * sum_vfx[None, :] - vg * x2[:, None]
            dv = -eta * (g * grad_v + 2.0 * state.lambda_v[None, :] * vg)
        return dw0, dw, dv, loss, g, p, sum_vfx, wg, vg, eta, sidx

    def lambda_deltas(state: FMState, idx, val, y, t, wg, vg, g, sum_vfx, eta):
        # adaptive regularization (ref: FactorizationMachineModel.java:253-300)
        dl_w0 = -eta * g * (-2.0 * eta * state.w0)
        sum_wx = jnp.sum(wg * val)
        dl_w = -eta * g * (-2.0 * eta * sum_wx)
        grad_v = val[:, None] * sum_vfx[None, :] - vg * (val * val)[:, None]
        v_dash = vg - eta * (g * grad_v + 2.0 * state.lambda_v[None, :] * vg)
        sum_f_dash = jnp.sum(val[:, None] * v_dash, axis=0)
        sum_f = sum_vfx
        sum_f_dash_f = jnp.sum(val[:, None] * v_dash * val[:, None] * vg, axis=0)
        dl_v = -eta * g * (-2.0 * eta * (sum_f_dash * sum_f - sum_f_dash_f))
        return dl_w0, dl_w, dl_v

    def scan_step(state: FMState, indices, values, labels, va_mask):
        def body(st: FMState, row):
            idx, val, y, is_va = row
            t = (st.step + 1).astype(jnp.float32)
            dw0, dw, dv, loss, g, p, sum_vfx, wg, vg, eta, sidx = \
                row_deltas(st, idx, val, y, t)
            theta = 1.0 - is_va
            st2 = st.replace(
                w0=st.w0 + theta * dw0,
                w=st.w.at[sidx].add(theta * dw, mode="drop"),
                v=st.v.at[sidx].add(theta * dv, mode="drop"),
                touched=st.touched.at[sidx].max(
                    jnp.broadcast_to((theta > 0).astype(jnp.int8), sidx.shape),
                    mode="drop"),
                step=st.step + 1,
            )
            if hyper.adareg:
                dl_w0, dl_w, dl_v = lambda_deltas(st, idx, val, y, t, wg, vg, g,
                                                  sum_vfx, eta)
                st2 = st2.replace(
                    lambda_w0=jnp.maximum(0.0, st2.lambda_w0 + is_va * dl_w0),
                    lambda_w=jnp.maximum(0.0, st2.lambda_w + is_va * dl_w),
                    lambda_v=jnp.maximum(0.0, st2.lambda_v + is_va * dl_v),
                )
            return st2, theta * loss

        state, losses = jax.lax.scan(body, state, (indices, values, labels, va_mask))
        return state, jnp.sum(losses)

    def averaged_w0(state: FMState, theta, dw0):
        return state.w0 + jnp.sum(theta * dw0) / jnp.maximum(
            jnp.sum(theta), 1.0)

    def apply_block(state: FMState, sidx, theta, dw0, dw, dv, wg, vg):
        """(w0, w, V, touched) after the block, written in the block's own
        index space as the linear step writes (core/engine.py): the lanes
        sorted by feature id, with count, dw and dv's k logical columns
        summed over each run of equal ids and the gathered old w and V row
        riding the sort as payload (11 more sorts: 2 ms a step cheaper on a
        v5e than gathering both again at the sorted ids); every lane of a
        run then writes `old + sum / max(count, 1)` at its sorted id, in
        place. V is written as whole rows, pad lanes as the zeros they are:
        the flat `[D * kp]` view is a relayout of the whole table on the
        chip, and a flat lane id is not ascending across a repeated
        feature's lanes. Nothing but the three writes is as long as the
        table."""
        k = hyper.factors
        # accumulate in f32 even if the tables ever go compact (same
        # store-compact/accumulate-wide policy as core/engine.py)
        acc = jnp.promote_types(state.v.dtype, jnp.float32)
        flat = lambda a: a.reshape(-1).astype(acc)
        cols = lambda a: [flat(a[..., j]) for j in range(k)]
        lane_theta = jnp.broadcast_to(theta[:, None], sidx.shape)
        with jax.named_scope(SCOPE_REDUCE):
            runs = reduce_block_runs(
                sidx.reshape(-1), state.w.shape[0],
                {"count": flat(lane_theta), "w": flat(lane_theta * dw),
                 "v": cols(theta[:, None, None] * dv)},
                {"w": flat(wg), "v": cols(vg)})
        with jax.named_scope(SCOPE_APPLY):
            count = runs.sums["count"]
            denom = jnp.maximum(count, 1.0)
            new_w = write_runs(state.w, runs,
                               runs.carried["w"] + runs.sums["w"] / denom)
            v_rows = jnp.stack(
                [old + total / denom for old, total
                 in zip(runs.carried["v"], runs.sums["v"])], axis=-1)
            new_v = write_runs(state.v, runs, jnp.pad(
                v_rows, ((0, 0), (0, state.v.shape[1] - k))))
            new_w0 = averaged_w0(state, theta, dw0)
        with jax.named_scope(SCOPE_TOUCHED):
            touched = write_runs(state.touched, runs, count > 0, "max")
        return new_w0, new_w, new_v, touched

    def apply_stripe(state: FMState, sidx, theta, dw0, dw, dv):
        """The same (w0, w, V, touched) on a `feature_shard` stripe: delta
        sums and counts scattered into zeroed tables, one divide-add pass
        over each table."""
        # accumulate in f32 even if the tables ever go compact
        acc_w = jnp.promote_types(state.w.dtype, jnp.float32)
        acc_v = jnp.promote_types(state.v.dtype, jnp.float32)
        # FloatAccumulator denominators: per-feature touch counts, w0 by
        # the effective batch size
        with jax.named_scope(SCOPE_REDUCE):
            counts = jnp.zeros((state.w.shape[0],), jnp.float32) \
                .at[sidx].add(jnp.broadcast_to(theta[:, None], sidx.shape),
                              mode="drop")
        with jax.named_scope(SCOPE_APPLY):
            denom = jnp.maximum(counts, 1.0)
        with jax.named_scope(SCOPE_REDUCE):
            dw_sum = jnp.zeros(state.w.shape, acc_w).at[sidx].add(
                theta[:, None] * dw.astype(acc_w), mode="drop")
        with jax.named_scope(SCOPE_APPLY):
            new_w = (state.w.astype(acc_w) + dw_sum / denom) \
                .astype(state.w.dtype)
        with jax.named_scope(SCOPE_REDUCE):
            # Only the logical k lanes carry nonzero grads (pad-lane grads
            # are products with their own zero V entries), so scatter those
            # and pad lanes stay provably zero.
            dv_sum = scatter_rows_flat(
                jnp.zeros(state.v.shape, acc_v), sidx,
                (theta[:, None, None] * dv.astype(acc_v))[..., :hyper.factors])
        with jax.named_scope(SCOPE_APPLY):
            new_v = (state.v.astype(acc_v) + dv_sum / denom[:, None]) \
                .astype(state.v.dtype)
            new_w0 = averaged_w0(state, theta, dw0)
        with jax.named_scope(SCOPE_TOUCHED):
            touched = state.touched.at[sidx].max(
                jnp.broadcast_to((theta > 0).astype(jnp.int8)[:, None],
                                 sidx.shape),
                mode="drop")
        return new_w0, new_w, new_v, touched

    def minibatch_step(state: FMState, indices, values, labels, va_mask):
        b = indices.shape[0]
        ts = (state.step + 1 + jnp.arange(b)).astype(jnp.float32)

        def per_row(idx, val, y, t):
            return row_deltas(state, idx, val, y, t)

        dw0, dw, dv, loss, g, p, sum_vfx, wg, vg, eta, sidx = \
            jax.vmap(per_row)(indices, values, labels, ts)
        theta = (1.0 - va_mask)  # [B]
        if feature_shard is None:
            new_w0, new_w, new_v, touched = apply_block(
                state, sidx, theta, dw0, dw, dv, wg, vg)
        else:
            new_w0, new_w, new_v, touched = apply_stripe(
                state, sidx, theta, dw0, dw, dv)
        new_state = state.replace(
            w0=new_w0,
            w=new_w,
            v=new_v,
            touched=touched,
            step=state.step + b,
        )
        if hyper.adareg:
            def per_row_lambda(idx, val, y, t, wg_, vg_, g_, sv_, eta_):
                return lambda_deltas(state, idx, val, y, t, wg_, vg_, g_, sv_, eta_)

            dl_w0, dl_w, dl_v = jax.vmap(per_row_lambda)(
                indices, values, labels, ts, wg, vg, g, sum_vfx, eta)
            vam = va_mask
            new_state = new_state.replace(
                lambda_w0=jnp.maximum(0.0, state.lambda_w0 + jnp.sum(vam * dl_w0)),
                lambda_w=jnp.maximum(0.0, state.lambda_w + jnp.sum(vam * dl_w)),
                lambda_v=jnp.maximum(0.0, state.lambda_v
                                     + jnp.sum(vam[:, None] * dl_v, axis=0)),
            )
        with jax.named_scope(SCOPE_LOSS):
            loss_sum = jnp.sum(theta * loss)
        return new_state, loss_sum

    step = scan_step if mode == "scan" else minibatch_step
    # jit=False returns the raw traceable fn for embedding in an outer scan
    # (e.g. a whole-epoch lax.scan over staged blocks, scripts/bench_ctr_e2e.py)
    return jax.jit(step, donate_argnums=(0,)) if jit else step


@jax.jit
def _fm_scores(state: FMState, indices, values):
    def one(idx, val):
        wg = state.w.at[idx].get(mode="fill", fill_value=0.0)
        vg = state.v.at[idx].get(mode="fill", fill_value=0.0)
        p, _ = _row_predict(state.w0, wg, vg, val)
        return p

    return jax.vmap(one)(indices, values)


@dataclass
class TrainedFMModel:
    state: FMState
    hyper: FMHyper
    dims: int

    def predict(self, features: FeatureRows) -> np.ndarray:
        idx_rows, val_rows = _stage_rows(features, self.dims)
        n = len(idx_rows)
        width = pad_to_bucket(longest_row(idx_rows))
        out = []
        for blk in iter_blocks(idx_rows, val_rows, np.zeros(n), self.dims, 4096, width):
            out.append(np.asarray(_fm_scores(self.state, blk.indices, blk.values)))
        return np.concatenate(out)[:n]

    def model_rows(self):
        """(feature, Wi, Vi[factors]) rows + the w0 bias row (feature 0 carries
        w0, ref: forwardAsIntFeature FactorizationMachineUDTF.java:446-519)."""
        st = self.state
        with TRACER.span(SPAN_EMIT, args={
                "table_dtype": str(st.v.dtype)}) as emit:
            feats, (w, v), stats = select_rows(
                st.touched, [("w", st.w), ("v", st.v)])
            w0 = table_to_host(st.w0, "w0", stats)
            # slice physical lane padding (padded_factors) back to the
            # logical k
            out = (float(w0), feats, w, v[:, :self.hyper.factors])
            emit.set(rows_out=len(feats), **stats)
        REGISTRY.counter("emit", "rows").increment(len(feats))
        return out


def _fm_options() -> Options:
    o = base_options()
    o.add("c", "classification", False, "Act as classification")
    o.add("seed", None, True, "Seed value [default: 31]", default=31, type=int)
    o.add("p", "num_features", True, "The size of feature dimensions", type=int)
    o.add("factor", "factors", True, "Number of latent factors [default: 5]",
          default=5, type=int)
    o.add("sigma", None, True, "Stddev for initializing V [default: 0.1]",
          default=0.1, type=float)
    o.add("lambda0", "lambda", True, "Regularization lambda [default: 0.01]",
          default=0.01, type=float)
    o.add("min", "min_target", True, "Min target value", type=float)
    o.add("max", "max_target", True, "Max target value", type=float)
    o.add("eta", None, True, "Fixed learning rate", type=float)
    o.add("eta0", None, True, "Initial learning rate [default 0.05]", default=0.05,
          type=float)
    o.add("t", "total_steps", True, "Total training steps", type=int)
    o.add("power_t", None, True, "Inverse-scaling exponent [default 0.1]",
          default=0.1, type=float)
    o.add("adareg", "adaptive_regularizaion", False, "Adaptive regularization")
    o.add("va_ratio", "validation_ratio", True, "Validation ratio [default 0.05]",
          default=0.05, type=float)
    o.add("int_feature", "feature_as_integer", False, "Parse features as integers")
    return o


def train_fm(features: FeatureRows, targets, options: Optional[str] = None,
             **kw) -> TrainedFMModel:
    with TRACER.span(SPAN_CALL, args={"entry": "fm"}) as call:
        return _train_fm(call, features, targets, options)


def _train_fm(call, features, targets, options) -> TrainedFMModel:
    cl = _fm_options().parse(options, "train_fm")
    dims = cl.get_int("dims") or cl.get_int("p") or DEFAULT_NUM_FEATURES
    hyper = FMHyper(
        factors=cl.get_int("factor", 5),
        classification=cl.has("c"),
        lambda0=cl.get_float("lambda0", 0.01),
        sigma=cl.get_float("sigma", 0.1),
        min_target=cl.get_float("min", -3.0e38),
        max_target=cl.get_float("max", 3.0e38),
        eta=get_eta(cl, 0.05),
        adareg=cl.has("adareg"),
        va_ratio=cl.get_float("va_ratio", 0.05),
        seed=cl.get_int("seed", 31),
    )
    targets = np.asarray(targets, dtype=np.float32)
    if hyper.classification:
        targets = np.where(targets > 0, 1.0, -1.0).astype(np.float32)
    idx_rows, val_rows, width = stage_training_rows(features, dims)
    n = len(idx_rows)
    mini_batch = cl.get_int("mini_batch", 1)
    mode = "minibatch" if mini_batch > 1 else "scan"
    block = mini_batch if mode == "minibatch" else cl.get_int("block_size", 4096)
    iters = cl.get_int("iters", 1)
    call.set(dims=dims, rows=n, mini_batch=mini_batch, mode=mode)
    lanes = width   # the scan works on the block as it comes
    if mode == "minibatch":
        # the one plan `make_fm_step` has off a `feature_shard` stripe, on
        # the lanes of the block that this call's rows can fill
        lanes = fillable_lanes(longest_row(idx_rows), width)
        call.set(apply="batch_local", width=width, lanes=lanes)
    if cl.has("native_scan"):
        return _train_fm_native_scan(cl, hyper, dims, idx_rows, val_rows,
                                     targets, width, block, mode, iters)
    step = make_fm_step(hyper, mode)
    if lanes < width:
        step = make_cut_step(step, lanes)
    state = init_state_spanned(init_fm_state, dims, hyper)
    call.set(table_dtype=str(state.v.dtype))
    kernel_tables = 0
    if mode == "minibatch":
        # V's rows stay on XLA's row scatter: `w` and the flag are the
        # step's `[dims]` tables
        kernel_tables = record_write_path(
            call, {"w": state.w, "touched": state.touched}, dims,
            block * lanes)
    rng = np.random.RandomState(hyper.seed)

    def va_mask(blk):
        va = (rng.rand(blk.batch_size) < hyper.va_ratio).astype(np.float32) \
            if hyper.adareg else np.zeros(blk.batch_size, np.float32)
        return (va,)

    conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
    # progress counters, as fit_linear keeps them
    iter_counter = REGISTRY.counter("hivemall", "fm.iterations")
    row_counter = REGISTRY.counter("hivemall", "fm.examples")
    cut_counter = REGISTRY.counter("train", "lanes_cut")
    kernel_counter = REGISTRY.counter("train", "kernel_write_lanes")
    step_no = 0
    for it in range(max(1, iters)):
        with TRACER.span(SPAN_EPOCH, args={"epoch": it}) as epoch:
            if cl.has("shuffle") and it > 0:
                idx_rows, val_rows, targets = shuffle_rows(
                    idx_rows, val_rows, targets, hyper.seed + it)
            epoch_loss = 0.0
            steps = 0
            for blk in prepared_blocks(idx_rows, val_rows, targets, dims,
                                       block, width, extra=va_mask):
                state, loss = dispatch_step(step, step_no, state, *blk)
                step_no += 1
                steps += 1
                with TRACER.span(SPAN_SYNC, args={"fetches": 1}):
                    epoch_loss += float(loss)
                row_counter.increment(blk[0].shape[0])
                cut_counter.increment(blk[0].shape[0] * (width - lanes))
                kernel_counter.increment(
                    blk[0].shape[0] * lanes * kernel_tables)
            iter_counter.increment()
            epoch.set(steps=steps)
            call.set(epochs=it + 1)
            conv.incr_loss(epoch_loss)
            if iters > 1 and conv.is_converged(n):
                break
    return TrainedFMModel(state=state, hyper=hyper, dims=dims)


def _train_fm_native_scan(cl, hyper: FMHyper, dims, idx_rows, val_rows,
                          targets, width, block, mode, iters
                          ) -> TrainedFMModel:
    """`-native_scan`: exact sequential FM epochs through the C row loop
    (native/hivemall_native.cpp::hm_fm_reference_rowloop — the train_fm
    bench anchor shipped as a host execution backend, like AROW's in
    models/base.py). Envelope = where the C loop and the framework step
    coincide: -classification, a FIXED -eta, no -adareg, per-row scan
    mode; anything else refuses loudly. Starts from the framework's own
    seeded V init, so results match the engine's scan mode (one pinned
    deviation: a feature duplicated WITHIN a row sees in-place partial
    updates lane to lane, exactly like the reference's per-feature loop,
    where the engine batch-gathers the row once)."""
    from .. import native

    problems = []
    if not hyper.classification:
        problems.append("-classification (the C loop is the logistic form)")
    if hyper.eta.kind != "fixed":
        problems.append("a fixed -eta (C runs a constant learning rate)")
    if hyper.adareg:
        problems.append("no -adareg")
    if mode != "scan":
        problems.append("per-row scan mode (drop -mini_batch)")
    if problems:
        raise ValueError("-native_scan for train_fm requires: "
                         + "; ".join(problems))
    state0 = init_fm_state(dims, hyper)
    k = hyper.factors
    # one sentinel slot at index dims: block padding writes land there and
    # are sliced off (value-0 lanes still take the L2 decay term, like the
    # reference's own loop — confined to the sentinel)
    st = {
        "w0": np.zeros(1, np.float32),
        "w": np.concatenate([np.asarray(state0.w), np.zeros(1, np.float32)]),
        "V": np.concatenate([np.asarray(state0.v)[:, :k],
                             np.zeros((1, k), np.float32)]),
        "touch": np.zeros(dims + 1, np.uint8),
    }
    # zero-row probe: availability check that cannot touch the state
    # (a fake row would shift the GLOBAL w0 — advisor-caught)
    probe = native.fm_reference_rowloop(
        np.zeros((0, 1), np.int32), np.zeros((0, 1), np.float32),
        np.zeros(0, np.float32), dims + 1, k=k, eta=hyper.eta.eta0,
        lam=hyper.lambda0, state=st, track_touched=True)
    if probe is None:
        raise RuntimeError("-native_scan requires the native library "
                           "(bash scripts/build_native.sh)")
    n = len(idx_rows)
    conv = ConversionState(not cl.has("disable_cv"),
                           cl.get_float("cv_rate", 0.005))
    for it in range(max(1, iters)):
        if cl.has("shuffle") and it > 0:
            idx_rows, val_rows, targets = shuffle_rows(
                idx_rows, val_rows, targets, hyper.seed + it)
        epoch_errors = 0
        for blk in iter_blocks(idx_rows, val_rows, targets, dims, block,
                               width):
            epoch_errors += native.fm_reference_rowloop(
                blk.indices, blk.values, blk.labels, dims + 1, k=k,
                eta=hyper.eta.eta0, lam=hyper.lambda0, state=st,
                track_touched=True)
        # convergence proxy = sign-error count (the C loop's return);
        # the engine tracks logloss — documented deviation
        conv.incr_loss(float(epoch_errors))
        if iters > 1 and conv.is_converged(n):
            break
    v_back = st["V"][:dims]
    if hyper.padded_factors != k:  # restore the physical lane padding
        v_back = np.concatenate(
            [v_back, np.zeros((dims, hyper.padded_factors - k), np.float32)],
            axis=1)
    state = state0.replace(
        w0=jnp.asarray(np.float32(st["w0"][0])),
        w=jnp.asarray(st["w"][:dims]),
        v=jnp.asarray(v_back),
        touched=jnp.asarray((st["touch"][:dims] != 0).astype(np.int8)),
        step=jnp.asarray(np.int32(n * (it + 1))),
    )
    return TrainedFMModel(state=state, hyper=hyper, dims=dims)


def fm_predict(w0: float, w: Sequence[float], v: Sequence[Sequence[float]],
               feats: Sequence[int], xs: Sequence[float]) -> float:
    """`fm_predict` UDAF equivalent: score one row from model rows
    (ref: fm/FMPredictGenericUDAF.java) — p = w0 + sum w_i x_i + pairwise V term."""
    w = np.asarray(w, dtype=np.float64)
    v = np.asarray(v, dtype=np.float64)
    x = np.asarray(xs, dtype=np.float64)
    linear = float(np.sum(w * x))
    vx = v * x[:, None]
    s = np.sum(vx, axis=0)
    s2 = np.sum(vx * vx, axis=0)
    return float(w0 + linear + 0.5 * np.sum(s * s - s2))
