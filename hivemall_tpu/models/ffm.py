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
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.batch import pad_to_bucket
from ..ops.scatter import scatter_rows_flat
from ..ops.convergence import ConversionState
from ..ops.eta import EtaEstimator, get_eta
from ..utils.feature import FMFeature
from ..utils.options import Options
from .fm import _fm_options

_MIX1 = 0x9E3779B1
_MIX2 = 0x85EBCA6B


def pair_hash(feature_idx, field, dv: int):
    """Deterministic (feature, field) -> V-table row. Works identically in
    numpy and jnp (int32 wraparound mixing)."""
    h = feature_idx.astype(jnp.uint32) * jnp.uint32(_MIX1) \
        + field.astype(jnp.uint32) * jnp.uint32(_MIX2)
    h ^= h >> 15
    h *= jnp.uint32(0x2C1B3C6D)
    h ^= h >> 12
    return (h % jnp.uint32(dv)).astype(jnp.int32)


@struct.dataclass
class FFMState:
    w0: jnp.ndarray  # []
    w: jnp.ndarray  # [D]
    z: jnp.ndarray  # [D] FTRL z
    n: jnp.ndarray  # [D] FTRL n (or adagrad gg for SGD-W — unused then)
    v: jnp.ndarray  # [Dv, k]
    v_gg: jnp.ndarray  # [Dv] adagrad accumulator for V
    touched: jnp.ndarray  # [D] int8
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


def init_ffm_state(hyper: FFMHyper) -> FFMState:
    key = jax.random.PRNGKey(hyper.seed)
    d, dv, k = hyper.num_features, hyper.v_dims, hyper.factors
    return FFMState(
        w0=jnp.zeros(()),
        w=jnp.zeros((d,)),
        z=jnp.zeros((d,)),
        n=jnp.zeros((d,)),
        v=jax.random.normal(key, (dv, k)) * hyper.sigma,
        v_gg=jnp.zeros((dv,)),
        touched=jnp.zeros((d,), jnp.int8),
        step=jnp.zeros((), jnp.int32),
    )


def _row_pair_keys(idx, fields, dv):
    """[K] features -> [K, K] pair table rows: keys[i, j] = h(idx_i, field_j)."""
    return pair_hash(idx[:, None].astype(jnp.uint32),
                     jnp.broadcast_to(fields[None, :], (idx.shape[0], idx.shape[0]))
                     .astype(jnp.uint32), dv)


def _row_predict(state: FFMState, idx, val, fields, hyper: FFMHyper,
                 Vg=None, keys=None):
    K = idx.shape[0]
    if keys is None:
        keys = _row_pair_keys(idx, fields, hyper.v_dims)  # [K, K]
    if Vg is None:
        Vg = state.v[keys]  # [K, K, k]
    # pair mask: i < j and both lanes real (padded lanes have val 0)
    iu = jnp.triu_indices(K, 1)
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


def make_ffm_step(hyper: FFMHyper, mode: str = "scan",
                  row_chunk: Optional[int] = None,
                  feature_shard: Optional[Tuple[str, int, int]] = None,
                  pack_v: Optional[bool] = None,
                  jit: bool = True):
    """`row_chunk` (minibatch mode only) tiles the batch's K^2 pairwise work:
    the [B, K, K, k] dV / [B, K, K] gg activations are the FFM memory hot
    spot (256MB at B=16384, K=32, k=4 — grows with the square of the field
    count), so the batch is processed in chunks of `row_chunk` rows — every
    chunk computes against the SAME block-start parameters (identical
    accumulate-then-apply semantics, tested exact vs unchunked) and
    scatter-adds into the carried tables, bounding peak activation memory at
    [row_chunk, K, K, k].

    `feature_shard=(axis_name, stripe_w, stripe_v)` stripes the linear
    tables (w/z/n/touched, [num_features]) and the pairwise V tables
    (v/v_gg, [v_dims]) across the mesh. Unlike FM, a row's pairwise term
    needs CROSS-stripe products <V_{i,f_j}, V_{j,f_i}> — the two rows of a
    pair can live on different devices — so each device gathers the entries
    it owns of the row's [K, K, k] block (exactly one owner per hashed key)
    and ONE psum reconstructs the full block everywhere; updates scatter
    back owned entries only. Keys hash with the ORIGINAL v_dims, so the
    model is the same function as the unsharded one."""

    if feature_shard is None:
        translate_w = None

        def predict_gather(st: FFMState, idx, val, fields, packed=None):
            if packed is None:
                p, keys, Vg, xx = _row_predict(st, idx, val, fields, hyper)
                gg = st.v_gg[keys]
            else:
                # v+gg interleaved [Dv, k+1]: ONE [K,K]-row gather yields
                # both — the separate scalar gg gather (K^2 scalars/row)
                # rides the V row gather for free (a borrowed lane; v5e
                # cost model in docs/perf_history.md round 4c)
                keys = _row_pair_keys(idx, fields, hyper.v_dims)
                pg = packed[keys]  # [K, K, k+1]
                Vg, gg = pg[..., :-1], pg[..., -1]
                p, _, _, xx = _row_predict(st, idx, val, fields, hyper,
                                           Vg=Vg, keys=keys)
            own = jnp.ones(keys.shape, val.dtype)
            return p, keys, Vg, xx, gg, own
    else:
        from ..core.striping import translate_to_stripe

        shard_axis, stripe_w, stripe_v = feature_shard

        def translate_w(idx, val):
            return translate_to_stripe(idx, val, shard_axis, stripe_w)

        def predict_gather(st: FFMState, idx, val, fields, packed=None):
            return sharded_ffm_gather(st, idx, val, fields, hyper,
                                      shard_axis, stripe_w, stripe_v)

    def dloss_fn(p, y):
        if hyper.classification:
            z = p * y
            return (jax.nn.sigmoid(z) - 1.0) * y, jnp.logaddexp(0.0, -z)
        pc = jnp.clip(p, hyper.min_target, hyper.max_target)
        return pc - y, 0.5 * (pc - y) ** 2

    def row_updates(st: FFMState, idx, val, fields, y, t, packed=None):
        p, keys, Vg, xx, gg, own = predict_gather(st, idx, val, fields,
                                                  packed)
        g, loss = dloss_fn(p, y)
        K = idx.shape[0]
        # dV[i, j] = g * x_i x_j * V_{j, f_i} for i != j
        offdiag = 1.0 - jnp.eye(K)
        coeff = g * xx * offdiag  # [K, K]
        gradV = coeff[:, :, None] * jnp.transpose(Vg, (1, 0, 2))  # [K,K,k]
        # AdaGrad eta per (i,j) entry, using gg BEFORE this grad
        if hyper.use_adagrad:
            eta_v = hyper.eta0_v / jnp.sqrt(hyper.eps + gg)
        else:
            eta_v = jnp.broadcast_to(hyper.eta.eta(t), gg.shape)
        Vcur = Vg
        dV = -eta_v[:, :, None] * (gradV + 2.0 * hyper.lambda_v * Vcur)
        # zero out padded lanes (val == 0 kills coeff already; L2 pull must
        # not apply to untouched entries) and, sharded, foreign entries
        lane = (val != 0.0).astype(val.dtype)
        pair_real = lane[:, None] * lane[None, :] * offdiag * own
        dV = dV * pair_real[:, :, None]
        dgg = jnp.sum(gradV * gradV, axis=-1) * pair_real  # entry-level gg sum
        return p, g, loss, keys, dV, dgg

    def w_updates(st: FFMState, idx, val, g, t):
        """Linear-term update: FTRL (default) or SGD."""
        grad = g * val
        if hyper.use_ftrl:
            n_old = st.n.at[idx].get(mode="fill", fill_value=0.0)
            w_old = st.w.at[idx].get(mode="fill", fill_value=0.0)
            n_new = n_old + grad * grad
            sigma = (jnp.sqrt(n_new) - jnp.sqrt(n_old)) / hyper.alpha
            z_old = st.z.at[idx].get(mode="fill", fill_value=0.0)
            z_new = z_old + grad - sigma * w_old
            w_new = jnp.where(
                jnp.abs(z_new) <= hyper.lambda1,
                0.0,
                (jnp.sign(z_new) * hyper.lambda1 - z_new)
                / ((hyper.beta + jnp.sqrt(n_new)) / hyper.alpha + hyper.lambda2),
            )
            return (z_new - z_old), (n_new - n_old), w_new
        eta = hyper.eta.eta(t)
        w_old = st.w.at[idx].get(mode="fill", fill_value=0.0)
        dw = -eta * (grad + 2.0 * hyper.lambda_w * w_old)
        return jnp.zeros_like(val), jnp.zeros_like(val), w_old + dw

    def scan_step(state: FFMState, indices, values, fields, labels):
        def body(st: FFMState, row):
            idx, val, fld, y = row
            t = (st.step + 1).astype(jnp.float32)
            p, g, loss, keys, dV, dgg = row_updates(st, idx, val, fld, y, t)
            widx, wval = (idx, val) if translate_w is None \
                else translate_w(idx, val)
            v = scatter_rows_flat(
                st.v, keys.reshape(-1), dV.reshape(-1, dV.shape[-1]))
            v_gg = st.v_gg.at[keys.reshape(-1)].add(dgg.reshape(-1),
                                                    mode="drop")
            st = st.replace(v=v, v_gg=v_gg, step=st.step + 1)
            if hyper.linear_coeff:
                dz, dn, w_new = w_updates(st, widx, wval, g, t)
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

        state, losses = jax.lax.scan(body, state, (indices, values, fields, labels))
        return state, jnp.sum(losses)

    def apply_row_group(carry: FFMState, base: FFMState, idx, val, fld, lab,
                        ts, pk_carry=None, pk_base=None):
        """Compute one row group's updates against the block-start `base`
        parameters and scatter-accumulate them into `carry` — the single
        accumulate-then-apply body shared by the unchunked minibatch step
        (carry == base, one group) and the tiled step (scan over groups).

        With `pk_base`/`pk_carry` (local path), V and gg live interleaved
        in one [Dv, k+1] table for the block: gathers and scatters each
        collapse to a single row op; carry.v / carry.v_gg are STALE inside
        and the caller unpacks at block end."""
        p, g, loss, keys, dV, dgg = jax.vmap(
            lambda i, v, f, y, t: row_updates(base, i, v, f, y, t,
                                              pk_base))(
                idx, val, fld, lab, ts)
        widx, wval = (idx, val) if translate_w is None \
            else jax.vmap(translate_w)(idx, val)
        k = dV.shape[-1]
        if pk_carry is not None:
            upd = jnp.concatenate([dV, dgg[..., None]], axis=-1)
            pk_carry = scatter_rows_flat(pk_carry, keys.reshape(-1),
                                         upd.reshape(-1, k + 1))
        else:
            carry = carry.replace(
                v=scatter_rows_flat(carry.v, keys.reshape(-1),
                                    dV.reshape(-1, k)),
                v_gg=carry.v_gg.at[keys.reshape(-1)].add(dgg.reshape(-1),
                                                         mode="drop"),
            )
        if hyper.linear_coeff:
            dz, dn, w_new = jax.vmap(
                lambda i, v_, g_, t: w_updates(base, i, v_, g_, t))(
                    widx, wval, g, ts)
            carry = carry.replace(
                z=carry.z.at[widx].add(dz, mode="drop"),
                n=carry.n.at[widx].add(dn, mode="drop"),
                w=carry.w.at[widx].set(w_new, mode="drop"),
            )
        carry = carry.replace(touched=carry.touched.at[widx].max(
            jnp.ones_like(widx, dtype=jnp.int8), mode="drop"))
        return carry, jnp.sum(loss), jnp.sum(g), pk_carry

    def apply_w0(st: FFMState, base: FFMState, g_sum, b, t_last):
        # one batch-level w0 update with eta at the batch's final timestep
        if not hyper.global_bias:
            return st
        eta = hyper.eta.eta(t_last)
        return st.replace(w0=base.w0 - eta * (
            g_sum + b * 2.0 * hyper.lambda_w * base.w0))

    def _want_pack(b: int, K: int, state: FFMState) -> bool:
        """Packing costs ~2 full [Dv, k+1] table passes per block; the win
        is the B*K^2 random-scalar gg gather+scatter it absorbs into the V
        row ops. Pack only when the block's pairwise volume dominates the
        table traffic (always true at the deployment block sizes; tiny
        test minibatches stay on the split path). `pack_v` overrides."""
        if feature_shard is not None:
            return False
        if pack_v is not None:
            return pack_v
        return b * K * K * 8 >= state.v.shape[0]

    def _pack_v(state: FFMState):
        return jnp.concatenate([state.v, state.v_gg[:, None]], axis=1)

    def _unpack_v(st: FFMState, pk):
        k = hyper.factors
        return st.replace(v=pk[:, :k], v_gg=pk[:, k])

    def minibatch_step(state: FFMState, indices, values, fields, labels):
        b = indices.shape[0]
        ts = (state.step + 1 + jnp.arange(b)).astype(jnp.float32)
        pk = _pack_v(state) if _want_pack(
            b, indices.shape[1], state) else None
        st, loss, g_sum, pk = apply_row_group(state, state, indices, values,
                                              fields, labels, ts,
                                              pk_carry=pk, pk_base=pk)
        if pk is not None:
            st = _unpack_v(st, pk)
        st = apply_w0(st, state, g_sum, b, ts[-1])
        return st.replace(step=state.step + b), loss

    def chunked_minibatch_step(state: FFMState, indices, values, fields, labels):
        b = indices.shape[0]
        c = row_chunk
        if b % c != 0:
            raise ValueError(f"batch {b} not divisible by row_chunk {c}")
        chunks = jax.tree.map(
            lambda a: a.reshape((b // c, c) + a.shape[1:]),
            (indices, values, fields, labels))
        ts_all = (state.step + 1 + jnp.arange(b)).astype(jnp.float32) \
            .reshape(b // c, c)
        pk0 = _pack_v(state) if _want_pack(
            b, indices.shape[1], state) else None

        def body(carry, chunk_in):
            st, pk = carry
            idx, val, fld, lab, ts = chunk_in
            st, loss, g_sum, pk = apply_row_group(st, state, idx, val, fld,
                                                  lab, ts, pk_carry=pk,
                                                  pk_base=pk0)
            return (st, pk), (loss, g_sum)

        (st, pk), (losses, g_sums) = jax.lax.scan(
            body, (state, pk0), (*chunks, ts_all))
        if pk is not None:
            st = _unpack_v(st, pk)
        st = apply_w0(st, state, jnp.sum(g_sums), b, ts_all[-1, -1])
        return st.replace(step=state.step + b), jnp.sum(losses)

    if row_chunk is not None and mode != "minibatch":
        raise ValueError("row_chunk applies to minibatch mode only")
    if row_chunk is not None and row_chunk <= 0:
        raise ValueError(f"row_chunk must be positive, got {row_chunk}")
    if mode == "scan":
        fn = scan_step
    elif row_chunk is not None:
        fn = chunked_minibatch_step
    else:
        fn = minibatch_step
    # jit=False returns the raw traceable fn for embedding in an outer scan
    # (e.g. a whole-epoch lax.scan over staged blocks, scripts/bench_ffm.py)
    return jax.jit(fn, donate_argnums=(0,)) if jit else fn


from functools import partial


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


@dataclass
class TrainedFFMModel:
    state: FFMState
    hyper: FFMHyper

    def predict(self, rows: Sequence[Sequence[str]]) -> np.ndarray:
        idx, val, fld, _ = _stage_ffm_rows(rows, None, self.hyper)
        return np.asarray(_ffm_scores(self.state, self.hyper, idx, val, fld))

    def model_rows(self):
        touched = np.asarray(self.state.touched) != 0
        feats = np.nonzero(touched)[0]
        return feats, np.asarray(self.state.w)[feats], float(self.state.w0)

    def to_blob(self, half_float: bool = True) -> bytes:
        """Serialize the whole predictable model to one compressed blob —
        the FFMPredictionModel.writeExternal analog (ref:
        fm/FFMPredictionModel.java:46,149-200: ZigZag-LEB128 feature keys +
        half-float values + compression). The linear part reuses
        encode_sparse_model (the same recipe); V rows are stored sparsely
        as (delta-zigzag key, k values) for exactly the rows that differ
        from the seeded gaussian init — the untouched rest is re-derived
        from the PRNG at decode, so from_blob().predict reproduces this
        model's predict (bit-exact with half_float=False)."""
        import struct as _struct

        from ..utils.codec import (compress_model_blob, encode_sparse_model,
                                   float_to_half, zigzag_leb128_encode_array)

        st, hy = self.state, self.hyper
        feats, w, w0 = self.model_rows()
        w_blob = encode_sparse_model(feats, w, half_float=half_float)
        v = np.asarray(st.v, np.float32)
        init_v = np.asarray(
            jax.random.normal(jax.random.PRNGKey(hy.seed), v.shape)
            * hy.sigma, np.float32)
        changed = np.nonzero(np.any(v != init_v, axis=1))[0]
        vkeys = zigzag_leb128_encode_array(np.diff(changed, prepend=0))
        vvals = v[changed].ravel()
        v_bytes = (float_to_half(vvals).tobytes() if half_float
                   else vvals.astype("<f4").tobytes())
        flags = ((1 if hy.linear_coeff else 0)
                 | (2 if hy.global_bias else 0)
                 | (4 if hy.classification else 0)
                 | (8 if half_float else 0))
        header = _struct.pack(
            "<4sBiqqqqfBf", b"HFM1", 1, hy.factors, hy.num_features,
            hy.num_fields, hy.v_dims, hy.seed, hy.sigma, flags, w0)
        v_section = compress_model_blob(
            _struct.pack("<qq", len(changed), len(vkeys)) + vkeys + v_bytes)
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
        if magic != b"HFM1" or version != 1:
            raise ValueError("not an FFM model blob")
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
        st = init_ffm_state(hyper)
        w_full = np.zeros(int(d), np.float32)
        w_full[np.asarray(feats, np.int64)] = w_sparse
        touched = np.zeros(int(d), np.int8)
        touched[np.asarray(feats, np.int64)] = 1
        v = np.asarray(st.v, np.float32).copy()
        v[vkeys] = vvals
        st = st.replace(w0=jnp.asarray(np.float32(w0)),
                        w=jnp.asarray(w_full), v=jnp.asarray(v),
                        touched=jnp.asarray(touched))
        return cls(state=st, hyper=hyper)


def _stage_ffm_rows(rows, labels, hyper: FFMHyper):
    """Parse "field:idx:value" rows into padded [B, K] arrays (pad lane:
    idx = num_features OOB, value 0, field 0)."""
    parsed = [[FMFeature.parse(f, num_features=hyper.num_features,
                               num_fields=hyper.num_fields) for f in row]
              for row in rows]
    width = pad_to_bucket(max((len(r) for r in parsed), default=1))
    B = len(parsed)
    idx = np.full((B, width), hyper.num_features, np.int32)
    val = np.zeros((B, width), np.float32)
    fld = np.zeros((B, width), np.int32)
    for r, row in enumerate(parsed):
        for c, f in enumerate(row[:width]):
            idx[r, c] = f.index % hyper.num_features
            val[r, c] = f.value
            fld[r, c] = (f.field if f.field >= 0 else 0) % hyper.num_fields
    lab = None
    if labels is not None:
        lab = np.asarray(labels, np.float32)
        if hyper.classification:
            lab = np.where(lab > 0, 1.0, -1.0).astype(np.float32)
    return idx, val, fld, lab


def _ffm_options() -> Options:
    o = _fm_options()
    o.add("w0", "global_bias", False, "Include global bias w0 [default: OFF]")
    o.add("disable_wi", "no_coeff", False, "Exclude the linear term")
    o.add("feature_hashing", None, True, "Feature hashing bits [18,31] [default 21]",
          default=21, type=int)
    o.add("num_fields", None, True, "Number of fields [default 1024]", default=1024,
          type=int)
    o.add("disable_adagrad", None, False, "Disable AdaGrad for V")
    o.add("eta0_V", None, True, "Initial learning rate for V [default 1.0]",
          default=1.0, type=float)
    o.add("eps", None, True, "AdaGrad denominator constant [default 1.0]",
          default=1.0, type=float)
    o.add("disable_ftrl", None, False, "Disable FTRL for W")
    o.add("alpha", "alphaFTRL", True, "FTRL alpha [default 0.1]", default=0.1,
          type=float)
    o.add("beta", "betaFTRL", True, "FTRL beta [default 1.0]", default=1.0, type=float)
    o.add("lambda1", None, True, "FTRL L1 [default 0.1]", default=0.1, type=float)
    o.add("lambda2", None, True, "FTRL L2 [default 0.01]", default=0.01, type=float)
    o.add("v_bits", None, True, "log2 size of the hashed V table [default 22]",
          default=22, type=int)
    o.add("row_chunk", None, True,
          "Tile minibatch K^2 pairwise work in chunks of this many rows "
          "(bounds activation memory; 0 = no tiling)", default=0, type=int)
    return o


def train_ffm(rows: Sequence[Sequence[str]], labels, options: Optional[str] = None
              ) -> TrainedFFMModel:
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
    idx, val, fld, lab = _stage_ffm_rows(rows, labels, hyper)
    mini_batch = cl.get_int("mini_batch", 1)
    mode = "minibatch" if mini_batch > 1 else "scan"
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
    step = make_ffm_step(hyper, mode, row_chunk=row_chunk)
    # the trailing partial block (n % block rows) won't divide by row_chunk;
    # it goes through an untiled step (same semantics, small shape)
    tail_step = make_ffm_step(hyper, mode) \
        if row_chunk is not None else step
    state = init_ffm_state(hyper)
    iters = cl.get_int("iters", 1)
    conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
    n = len(rows)
    for it in range(max(1, iters)):
        epoch_loss = 0.0
        for s in range(0, n, block):
            e = min(s + block, n)
            use = step if (row_chunk is None or (e - s) % row_chunk == 0) \
                else tail_step
            state, loss = use(state, idx[s:e], val[s:e], fld[s:e], lab[s:e])
            epoch_loss += float(loss)
        conv.incr_loss(epoch_loss)
        if iters > 1 and conv.is_converged(n):
            break
    return TrainedFFMModel(state=state, hyper=hyper)


def ffm_predict(model: TrainedFFMModel, rows: Sequence[Sequence[str]]) -> np.ndarray:
    """`ffm_predict` equivalent (ref: fm/FFMPredictUDF.java deserializes the
    compressed model; here the trained model object scores directly)."""
    return model.predict(rows)
