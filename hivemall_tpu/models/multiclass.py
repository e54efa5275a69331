"""Multiclass online classifiers: train_multiclass_{perceptron, pa, pa1, pa2,
cw, arow, arowh, scw, scw2}.

The reference keeps a lazily-grown per-label model map
(`Map<Object, PredictionModel> label2model`,
ref: classifier/multiclass/MulticlassOnlineClassifierUDTF.java:70-110). TPU-first
this becomes ONE stacked weight tensor [num_labels, dims]: scoring every label
is a [L, K] gather + matvec instead of L hash lookups, and the correct/missed
row updates are two scatter-adds into the same tensor.

Semantics note: the reference computes the "max another" margin over labels
seen so far; we compute it over the full fixed label vocabulary (unseen rows
score 0 from zero weights) — identical once every label has occurred, which is
the steady state.

Update rules mirror (file:line cited in each rule):
- perceptron: misclassify -> +x to actual, -x to predicted
  (ref: MulticlassPerceptronUDTF.java:50-57)
- PA: loss = 1 - margin, eta = loss/(2|x|^2); PA1 clips at C; PA2
  eta = loss/(2|x|^2 + 1/2C) (ref: MulticlassPassiveAggressiveUDTF.java:51-123)
- CW: gamma from margin + variance(correct) + variance(missed), covariance
  1/(1/cov + 2*alpha*phi*x^2) on both rows
  (ref: MulticlassConfidenceWeightedUDTF.java:112-192)
- AROW: alpha = (1-m)*beta, beta = 1/(var + r); AROWh: alpha = (c-m)*beta when
  c-m > 0; covariance cov - beta*(cov*x)^2 on both rows
  (ref: MulticlassAROWClassifierUDTF.java:99-234)
- SCW1/SCW2: binary SCW closed forms with m := margin, var := var_correct +
  var_missed (ref: MulticlassSoftConfidenceWeightedUDTF.java)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..constants import DEFAULT_NUM_FEATURES
from ..core.batch import iter_blocks, longest_row, pad_to_bucket
from ..utils.options import Options
from .base import FeatureRows, _stage_rows, base_options
from .classifier import _resolve_phi, _safe_div

NEG_INF = -3.0e38


@struct.dataclass
class MulticlassState:
    weights: jnp.ndarray  # [L, D]
    covars: Optional[jnp.ndarray]  # [L, D] init 1.0
    touched: jnp.ndarray  # [L, D] int8
    step: jnp.ndarray  # [] int32
    # optimizer aux, [L, D] per name — empty for every current rule (the
    # reference's multiclass learners are all closed-form alpha/beta with no
    # accumulator state). mc_mix.final_state merges these per
    # MCRule.slot_merge so a distributed collapse can never silently keep
    # replica 0's accumulators; a slotted rule would additionally need
    # init/update plumbing here and in make_mc_train_step.
    slots: Dict[str, jnp.ndarray] = struct.field(default_factory=dict)


@dataclass(frozen=True)
class MCRule:
    """alpha/beta from (margin m, variance, sq_norm); cov_kind selects the
    covariance update shape ('none' | 'arow' | 'cw')."""

    name: str
    compute: Callable  # (m, var, sq_norm, hyper) -> (alpha, beta, loss, updated)
    cov_kind: str = "none"
    # (slot_name, "sum"|"mean") merge kinds for distributed final_state —
    # same contract as core.engine.Rule.slot_merge; empty for every current
    # rule (no multiclass rule carries accumulator slots)
    slot_merge: Tuple[Tuple[str, str], ...] = ()

    @property
    def use_covariance(self) -> bool:
        return self.cov_kind != "none"


def _perceptron_compute(m, var, sq_norm, hyper):
    updated = m <= 0.0  # predicted (max other) >= correct
    return jnp.where(updated, 1.0, 0.0), jnp.zeros(()), jnp.where(updated, 1.0, 0.0), updated


def _pa_compute_factory(variant: str):
    def compute(m, var, sq_norm, hyper):
        loss = 1.0 - m
        if variant == "pa":
            eta = _safe_div(loss, 2.0 * sq_norm)
        elif variant == "pa1":
            eta = jnp.minimum(hyper["c"], _safe_div(loss, 2.0 * sq_norm))
        else:
            eta = loss / (2.0 * sq_norm + 0.5 / hyper["c"])
        updated = (loss > 0.0) & (sq_norm > 0.0)
        return jnp.where(updated, eta, 0.0), jnp.zeros(()), jnp.maximum(loss, 0.0), updated

    return compute


def _cw_compute(m, var, sq_norm, hyper):
    phi = hyper["phi"]
    b = 1.0 + 2.0 * phi * m
    disc = jnp.maximum(0.0, b * b - 8.0 * phi * (m - phi * var))
    gamma = _safe_div(-b + jnp.sqrt(disc), 4.0 * phi * var)
    updated = gamma > 0.0
    alpha = jnp.where(updated, gamma, 0.0)
    return alpha, alpha * phi, jnp.where(m <= 0.0, 1.0, 0.0), updated


def _arow_compute_factory(hinge: bool):
    def compute(m, var, sq_norm, hyper):
        beta = 1.0 / (var + hyper["r"])
        if hinge:
            loss = hyper["c"] - m
        else:
            loss = 1.0 - m
        updated = loss > 0.0
        alpha = jnp.where(updated, loss * beta, 0.0)
        beta = jnp.where(updated, beta, 0.0)
        return alpha, beta, jnp.maximum(loss, 0.0), updated

    return compute


def _scw_compute_factory(variant: int):
    def compute(m, var, sq_norm, hyper):
        phi, c = hyper["phi"], hyper["c"]
        loss = jnp.maximum(0.0, phi * jnp.sqrt(jnp.maximum(var, 0.0)) - m)
        sq_phi = phi * phi
        if variant == 1:
            psi = 1.0 + sq_phi / 2.0
            zeta = 1.0 + sq_phi
            numer = -m * psi + jnp.sqrt(
                jnp.maximum(0.0, m * m * sq_phi * sq_phi / 4.0 + var * sq_phi * zeta))
            alpha = _safe_div(numer, var * zeta)
            alpha = jnp.where(alpha <= 0.0, 0.0, jnp.maximum(c, alpha))  # mirrors ref max()
        else:
            n = var + c / 2.0
            vpp = var * sq_phi
            vppm = vpp * m
            term = vppm * m * var + 4.0 * n * var * (n + vpp)
            gamma = phi * jnp.sqrt(jnp.maximum(0.0, term))
            numer = -(2.0 * m * n + vppm) + gamma
            alpha = jnp.where(numer <= 0.0, 0.0, _safe_div(numer, 2.0 * (n * n + n * vpp)))
        beta_numer = alpha * phi
        vap = var * beta_numer
        u = -vap + jnp.sqrt(jnp.maximum(0.0, vap * vap + 4.0 * var))
        beta = _safe_div(beta_numer, u / 2.0 + vap)
        updated = (loss > 0.0) & (alpha != 0.0) & (beta != 0.0)
        return (jnp.where(updated, alpha, 0.0), jnp.where(updated, beta, 0.0),
                loss, updated)

    return compute


MC_PERCEPTRON = MCRule("mc_perceptron", _perceptron_compute)
MC_PA = MCRule("mc_pa", _pa_compute_factory("pa"))
MC_PA1 = MCRule("mc_pa1", _pa_compute_factory("pa1"))
MC_PA2 = MCRule("mc_pa2", _pa_compute_factory("pa2"))
MC_CW = MCRule("mc_cw", _cw_compute, cov_kind="cw")
MC_AROW = MCRule("mc_arow", _arow_compute_factory(False), cov_kind="arow")
MC_AROWH = MCRule("mc_arowh", _arow_compute_factory(True), cov_kind="arow")
MC_SCW1 = MCRule("mc_scw1", _scw_compute_factory(1), cov_kind="arow")
MC_SCW2 = MCRule("mc_scw2", _scw_compute_factory(2), cov_kind="arow")


def _take2(table, idx, fill):
    # [L, D] gathered at idx [K] -> [L, K]; OOB padding -> fill
    return jnp.take(table, idx, axis=1, mode="fill", fill_value=fill)


def _margin_from_scores(scores, variances, COV, label, val, use_cov):
    """Margin / missed label / variance / cov rows from (global) per-label
    scores — the ONE copy of the downstream selection logic shared by the
    local and feature-sharded gathers (so their semantics cannot drift)."""
    L = scores.shape[0]
    correct = scores[label]
    if L == 1:
        # No other label yet: the reference scores "max another" as 0 with a
        # null missed label and only updates the correct row
        # (ref: MulticlassOnlineClassifierUDTF.getMargin:211-229 null branch).
        missed = label
        m = correct
    else:
        others = scores.at[label].set(NEG_INF)
        missed = jnp.argmax(others)
        m = correct - others[missed]
    if use_cov:
        var = variances[label] + jnp.where(missed == label, 0.0,
                                           variances[missed])
        cov_a, cov_m = COV[label], COV[missed]
    else:
        var = jnp.zeros(())
        cov_a = cov_m = jnp.ones_like(val)
    return m, var, missed, cov_a, cov_m


def _row_quantities(weights, covars, idx, val, label, use_cov):
    W = _take2(weights, idx, 0.0)  # [L, K]
    scores = W @ val  # [L]
    COV = variances = None
    if use_cov:
        COV = _take2(covars, idx, 1.0)
        variances = COV @ (val * val)
    return _margin_from_scores(scores, variances, COV, label, val, use_cov)


def _cov_delta(kind, cov, val, alpha, beta):
    if kind == "arow":
        cv = cov * val
        return -beta * cv * cv
    # cw: new = cov / (1 + 2*beta_term*x^2*cov) with beta_term = alpha*phi
    denom = 1.0 + 2.0 * beta * val * val * cov
    return cov / denom - cov


def _row_quantities_sharded(weights, covars, idx, val, label, use_cov,
                            shard_axis, stripe):
    """Sharded twin of _row_quantities: tables are [L, D/S] stripes; the
    per-label score/variance partials psum over the stripe axis (one fused
    collective), everything downstream (margin, missed label, closed-form
    alpha/beta) is the same _margin_from_scores as the local path. Returns
    the translated lane indices + masked values for the scatters."""
    from ..core.striping import translate_to_stripe

    lidx, vmask = translate_to_stripe(idx, val, shard_axis, stripe)
    W = _take2(weights, lidx, 0.0)  # [L, K] owned lanes only
    COV = variances = None
    if use_cov:
        COV = _take2(covars, lidx, 1.0)
        scores, variances = jax.lax.psum(
            (W @ vmask, COV @ (vmask * vmask)), shard_axis)
    else:
        scores = jax.lax.psum(W @ vmask, shard_axis)
    m, var, missed, cov_a, cov_m = _margin_from_scores(
        scores, variances, COV, label, val, use_cov)
    return m, var, missed, cov_a, cov_m, lidx, vmask


def make_mc_train_step(rule: MCRule, hyper: dict, mode: str = "scan",
                       feature_shard: Optional[Tuple[str, int]] = None,
                       jit: bool = True):
    """`feature_shard=(axis_name, stripe)` runs the same step on [L, D/S]
    table stripes inside shard_map — the multiclass analog of the engine's
    feature-sharded training (an L-label covariance model at 2^24 dims is
    L x 2 tables that do not fit one chip)."""
    use_cov = rule.use_covariance

    if feature_shard is None:
        def row_q(weights, covars, idx, val, label):
            m, var, missed, cov_a, cov_m = _row_quantities(
                weights, covars, idx, val, label, use_cov)
            return m, var, missed, cov_a, cov_m, idx, val
    else:
        shard_axis, stripe = feature_shard

        def row_q(weights, covars, idx, val, label):
            return _row_quantities_sharded(weights, covars, idx, val, label,
                                           use_cov, shard_axis, stripe)

    def apply_row(state_arrays, idx, val, label, alpha, beta, updated, cov_a, cov_m, missed):
        weights, covars, touched = state_arrays
        upd = updated.astype(val.dtype)
        has_miss = jnp.where(missed == label, 0.0, 1.0)  # L==1 degenerate case
        dwa = upd * alpha * cov_a * val
        dwm = -upd * has_miss * alpha * cov_m * val
        weights = weights.at[label, idx].add(dwa, mode="drop")
        weights = weights.at[missed, idx].add(dwm, mode="drop")
        if use_cov:
            dca = upd * _cov_delta(rule.cov_kind, cov_a, val, alpha, beta)
            dcm = upd * has_miss * _cov_delta(rule.cov_kind, cov_m, val, alpha, beta)
            covars = covars.at[label, idx].add(dca, mode="drop")
            covars = covars.at[missed, idx].add(dcm, mode="drop")
        u8 = updated.astype(jnp.int8)
        miss8 = (updated & (missed != label)).astype(jnp.int8)
        touched = touched.at[label, idx].max(jnp.broadcast_to(u8, idx.shape), mode="drop")
        touched = touched.at[missed, idx].max(jnp.broadcast_to(miss8, idx.shape), mode="drop")
        return weights, covars, touched

    def scan_step(state: MulticlassState, indices, values, labels):
        def body(carry, row):
            weights, covars, touched, t = carry
            idx, val, label = row
            # sq_norm from the raw replicated values: a global row scalar
            sq_norm = jnp.sum(val * val)
            m, var, missed, cov_a, cov_m, sidx, eff_val = row_q(
                weights, covars, idx, val, label)
            alpha, beta, loss, updated = rule.compute(m, var, sq_norm, hyper)
            weights, covars, touched = apply_row((weights, covars, touched),
                                                 sidx, eff_val,
                                                 label, alpha, beta, updated, cov_a,
                                                 cov_m, missed)
            return (weights, covars, touched, t + 1), loss

        carry0 = (state.weights, state.covars, state.touched, state.step)
        (weights, covars, touched, step), losses = jax.lax.scan(
            body, carry0, (indices, values, labels))
        return state.replace(weights=weights, covars=covars, touched=touched,
                             step=step), jnp.sum(losses)

    def minibatch_step(state: MulticlassState, indices, values, labels):
        b = indices.shape[0]

        def per_row(idx, val, label):
            sq_norm = jnp.sum(val * val)
            m, var, missed, cov_a, cov_m, sidx, eff_val = row_q(
                state.weights, state.covars, idx, val, label)
            alpha, beta, loss, updated = rule.compute(m, var, sq_norm, hyper)
            return m, missed, cov_a, cov_m, alpha, beta, loss, updated, \
                sidx, eff_val

        (m, missed, cov_a, cov_m, alpha, beta, loss, updated, sidx,
         eff_val) = jax.vmap(per_row)(indices, values, labels)
        upd = updated.astype(values.dtype)[:, None]
        has_miss = jnp.where(missed == labels, 0.0, 1.0)[:, None]
        dwa = upd * alpha[:, None] * cov_a * eff_val
        dwm = -upd * has_miss * alpha[:, None] * cov_m * eff_val
        weights = state.weights.at[labels[:, None], sidx].add(dwa, mode="drop")
        weights = weights.at[missed[:, None], sidx].add(dwm, mode="drop")
        covars = state.covars
        if use_cov:
            dca = upd * jax.vmap(
                lambda c, v, a, be: _cov_delta(rule.cov_kind, c, v, a, be))(
                    cov_a, eff_val, alpha, beta)
            dcm = upd * has_miss * jax.vmap(
                lambda c, v, a, be: _cov_delta(rule.cov_kind, c, v, a, be))(
                    cov_m, eff_val, alpha, beta)
            covars = covars.at[labels[:, None], sidx].add(dca, mode="drop")
            covars = covars.at[missed[:, None], sidx].add(dcm, mode="drop")
        u8 = jnp.broadcast_to(updated.astype(jnp.int8)[:, None], sidx.shape)
        touched = state.touched.at[labels[:, None], sidx].max(u8, mode="drop")
        touched = touched.at[missed[:, None], sidx].max(u8, mode="drop")
        return state.replace(weights=weights, covars=covars, touched=touched,
                             step=state.step + b), jnp.sum(loss)

    step = scan_step if mode == "scan" else minibatch_step
    # jit=False returns the raw traceable fn for embedding in an outer scan
    # (e.g. a whole-epoch lax.scan over staged blocks, scripts/bench_mc.py)
    return jax.jit(step, donate_argnums=(0,)) if jit else step


@jax.jit
def _mc_scores(weights, indices, values):
    W = jnp.take(weights, indices, axis=1, mode="fill", fill_value=0.0)  # [L, B, K]
    return jnp.einsum("lbk,bk->bl", W, values)


@dataclass
class TrainedMulticlassModel:
    state: MulticlassState
    label_vocab: List
    dims: int

    def scores(self, features: FeatureRows) -> np.ndarray:
        idx_rows, val_rows = _stage_rows(features, self.dims)
        n = len(idx_rows)
        width = pad_to_bucket(longest_row(idx_rows))
        out = []
        for blk in iter_blocks(idx_rows, val_rows, np.zeros(n), self.dims, 1024, width):
            out.append(np.asarray(_mc_scores(self.state.weights, blk.indices, blk.values)))
        return np.concatenate(out)[:n]

    def predict(self, features: FeatureRows) -> List:
        s = self.scores(features)
        return [self.label_vocab[i] for i in np.argmax(s, axis=1)]

    def model_rows(self):
        """(label, feature, weight[, covar]) rows over touched entries —
        the reference's per-label close() emission."""
        t = np.asarray(self.state.touched) != 0
        lab_i, feat_i = np.nonzero(t)
        labels = [self.label_vocab[i] for i in lab_i]
        weights = np.asarray(self.state.weights)[lab_i, feat_i]
        if self.state.covars is not None:
            return labels, feat_i, weights, np.asarray(self.state.covars)[lab_i, feat_i]
        return labels, feat_i, weights


def _fit_multiclass(rule: MCRule, hyper: dict, cl, features: FeatureRows,
                    labels: Sequence, num_classes: Optional[int] = None):
    dims = cl.get_int("dims") or DEFAULT_NUM_FEATURES
    mini_batch = cl.get_int("mini_batch", 1)
    iters = cl.get_int("iters", 1)
    vocab = sorted(set(labels), key=lambda x: str(x))
    if num_classes is not None and num_classes > len(vocab):
        vocab = vocab + [f"__unused_{i}" for i in range(num_classes - len(vocab))]
    lab2i = {l: i for i, l in enumerate(vocab)}
    y = np.array([lab2i[l] for l in labels], dtype=np.int32)
    idx_rows, val_rows = _stage_rows(features, dims)
    width = pad_to_bucket(longest_row(idx_rows))
    L = len(vocab)
    state = MulticlassState(
        weights=jnp.zeros((L, dims), dtype=jnp.float32),
        covars=jnp.ones((L, dims), dtype=jnp.float32) if rule.use_covariance else None,
        touched=jnp.zeros((L, dims), dtype=jnp.int8),
        step=jnp.zeros((), dtype=jnp.int32),
    )
    mode = "minibatch" if mini_batch > 1 else "scan"
    block = mini_batch if mode == "minibatch" else cl.get_int("block_size", 4096)
    step = make_mc_train_step(rule, hyper, mode)
    for _ in range(max(1, iters)):
        for blk in iter_blocks(idx_rows, val_rows, y, dims, block, width):
            state, _ = step(state, blk.indices, blk.values,
                            blk.labels.astype(np.int32))
    return TrainedMulticlassModel(state=state, label_vocab=vocab, dims=dims)


def _mc_opts(phi: bool = False, c: bool = False, r: bool = False) -> Options:
    o = base_options()
    if phi:
        o.add("phi", "confidence", True, "Confidence parameter [default 1.0]", type=float)
        o.add("eta", "hyper_c", True, "Confidence hyperparameter in (0.5, 1]", type=float)
    if c:
        o.add("c", "aggressiveness", True, "Aggressiveness parameter C [default 1.0]",
              default=1.0, type=float)
    if r:
        o.add("r", "regularization", True, "Regularization parameter r [default 0.1]",
              default=0.1, type=float)
    return o


def _make_train(name, rule, opts_kw, hyper_fn):
    def train(features: FeatureRows, labels, options: Optional[str] = None,
              num_classes: Optional[int] = None):
        cl = _mc_opts(**opts_kw).parse(options, name)
        return _fit_multiclass(rule, hyper_fn(cl), cl, features, labels, num_classes)

    train.__name__ = name
    return train


train_multiclass_perceptron = _make_train(
    "train_multiclass_perceptron", MC_PERCEPTRON, {}, lambda cl: {})
train_multiclass_pa = _make_train(
    "train_multiclass_pa", MC_PA, {}, lambda cl: {})
train_multiclass_pa1 = _make_train(
    "train_multiclass_pa1", MC_PA1, {"c": True}, lambda cl: {"c": cl.get_float("c", 1.0)})
train_multiclass_pa2 = _make_train(
    "train_multiclass_pa2", MC_PA2, {"c": True}, lambda cl: {"c": cl.get_float("c", 1.0)})
train_multiclass_cw = _make_train(
    "train_multiclass_cw", MC_CW, {"phi": True}, lambda cl: {"phi": _resolve_phi(cl)})
train_multiclass_arow = _make_train(
    "train_multiclass_arow", MC_AROW, {"r": True}, lambda cl: {"r": cl.get_float("r", 0.1)})
train_multiclass_arowh = _make_train(
    "train_multiclass_arowh", MC_AROWH, {"r": True, "c": True},
    lambda cl: {"r": cl.get_float("r", 0.1), "c": cl.get_float("c", 1.0)})
train_multiclass_scw = _make_train(
    "train_multiclass_scw", MC_SCW1, {"phi": True, "c": True},
    lambda cl: {"phi": _resolve_phi(cl), "c": cl.get_float("c", 1.0)})
train_multiclass_scw2 = _make_train(
    "train_multiclass_scw2", MC_SCW2, {"phi": True, "c": True},
    lambda cl: {"phi": _resolve_phi(cl), "c": cl.get_float("c", 1.0)})
