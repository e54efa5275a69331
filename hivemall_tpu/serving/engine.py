"""Shape-bucketed online predictors — zero steady-state recompiles.

XLA compiles one program per input shape, so a naive server retraces on
every distinct (batch, row-width) pair — the recompilation-count failure
mode the ads-infra paper tracks as a production metric (PAPERS.md). The
serving discipline here is the training-side G001 discipline
(core/batch.py) applied to inference:

- row width pads to a power of two >= 8 (``pad_to_bucket``), capped at
  ``max_width`` (longer rows truncate, counted);
- batch size pads to a power of two >= ``min_batch_bucket``, capped at
  ``max_batch`` (bigger requests chunk);
- ``warmup()`` drives a dummy batch through EVERY (batch, width) bucket at
  load time, so the steady state never compiles — witnessed at run time by
  ``runtime.metrics.recompile_guard`` around every predict call
  (counter ``graftcheck.recompiles.serving.<name>`` stays flat).

Every family reuses the SAME jitted scorer its live model uses
(core/engine.make_predict, models/fm._fm_scores, models/ffm._ffm_scores_jit,
models/multiclass._mc_scores, models/trees/grow.predict_forest_binned), so
served predictions are bit-identical to the trained object's — padding rows
are row-independent no-ops. MF is the exception by design: its predict is a
host-side embedding lookup (numpy gather-dot, no device batch work to
amortize), identical to TrainedMFModel.predict.

Attribution caveat: because those scorers (and their jit caches) are shared
process-wide, a deploy WARMING another same-family model concurrently with
an open predict guard can transiently attribute its warmup compiles to the
serving engine's counter. The flat-counter invariant is exact whenever no
deploy is in flight; sharing the cache is the point (a new version of the
same shapes warms for free), so the counter trades per-engine attribution
for that.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.batch import FeatureBlock, pack_rows, pad_to_bucket
from ..runtime.metrics import REGISTRY, recompile_guard
from ..runtime.tracing import TRACER
from .artifact import Artifact, family_of, load, manifest_dtype, \
    manifest_quant, rebuild_model

# serving latency is sub-ms-to-seconds shaped; finer low end than the
# metrics default
LATENCY_BUCKETS = (0.0002, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
                   0.05, 0.1, 0.25, 0.5, 1.0, 2.5)


# The serving dtype contract (graftcheck G017-G021, docs/static_analysis.md
# "quantized artifacts"): request payloads and host staging are f32, device
# tables reload at their MANIFEST dtype (artifact.manifest_dtype) — never at
# whatever width the widened-at-rest pack happens to hold — and nothing on
# the score path allocates f64. Quantized artifacts extend the contract
# downward: bf16 tables serve AT bf16 through the families' own scorers
# (the gathered window promotes to f32 inside the dot product), and int8
# tables serve through the _q8_* scorers below, which gather the int8 rows,
# widen ONLY that [B, K] window, and fold the per-block absmax scale into
# the f32 accumulation — the full table is never dequantized (G019: cast
# the gathered window, never the table).


_QUANT_JIT: dict = {}


def _quant_jit_fns() -> dict:
    """Build (once per process) the jitted dequant-free int8 scorers.

    Shared across every engine instance the way the families' own scorers
    are, so a second int8 model of the same shapes warms for free and
    ``recompile_guard`` can watch one stable set of jit caches. Built
    lazily: importing serving must not drag jax in before the engine is
    actually used (the bench.py parent-process contract).

    ``block_shift`` is static (= log2 of the manifest's scale-block rows),
    so ``id >> block_shift`` resolves each gathered id to its scale block
    with one shift — one extra tiny gather against the f32 scale array
    replaces any widened copy of the table.
    """
    if _QUANT_JIT:
        return _QUANT_JIT
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ..models.fm import _row_predict

    @partial(jax.jit, static_argnums=(4,))
    def q8_linear_scores(qw, scales, indices, values, block_shift):
        # per-window dequant: only the gathered [B, K] rows widen (G019),
        # the scale folds into the product, and the sum accumulates f32
        # (G021); pad lanes gather q=0 so they stay no-ops
        wq = qw.at[indices].get(mode="fill", fill_value=0)
        sg = scales.at[indices >> block_shift].get(mode="fill",
                                                   fill_value=0.0)
        return jnp.sum(wq.astype(jnp.float32) * sg * values, axis=-1)

    @partial(jax.jit, static_argnums=(4,))
    def q8_mc_scores(qW, scales, indices, values, block_shift):
        # weights [L, D] int8, scales [L, nb] f32 (blocked along features,
        # the gathered axis) — the [L, B, K] gathered window widens, the
        # einsum accumulates f32
        Wq = jnp.take(qW, indices, axis=1, mode="fill", fill_value=0)
        S = jnp.take(scales, indices >> block_shift, axis=1, mode="fill",
                     fill_value=0.0)
        return jnp.einsum("lbk,bk->bl", Wq.astype(jnp.float32) * S, values)

    @partial(jax.jit, static_argnums=(7,))
    def q8_fm_scores(w0, qw, w_scales, qv, v_scales, indices, values,
                     block_shift):
        # same _row_predict core as the live FM scorer, fed per-row
        # dequantized windows: w [D] and v [D, F] gather int8, widen the
        # [K] / [K, F] window, fold the row-block scales
        def one(idx, val):
            sw = w_scales.at[idx >> block_shift].get(mode="fill",
                                                     fill_value=0.0)
            wg = qw.at[idx].get(mode="fill",
                                fill_value=0).astype(jnp.float32) * sw
            sv = v_scales.at[idx >> block_shift].get(mode="fill",
                                                     fill_value=0.0)
            vg = qv.at[idx].get(mode="fill",
                                fill_value=0).astype(jnp.float32) * sv
            p, _ = _row_predict(w0, wg, vg, val)
            return p

        return jax.vmap(one)(indices, values)

    _QUANT_JIT.update(linear=q8_linear_scores, multiclass=q8_mc_scores,
                      fm=q8_fm_scores)
    return _QUANT_JIT


class _Servable:
    """THE servable protocol: host staging + padded scoring, placement-free.

    Every placement (single-device, replicated, model-sharded —
    serving/placement.py) serves through this same interface; the engine,
    batcher, registry and /predict endpoint depend on nothing else. The
    single-device family adapters below implement it with tables on one
    device; serving/sharded.py implements it with NamedSharding-striped
    tables — ``make_servable(obj, placement=...)`` picks.

    The request path is three explicitly separated stages so the tracer
    (runtime/tracing.py) can attribute time per stage:

    - ``stage(instances, b_pad, width_cap)`` — host-side parse + pad to
      ``[b_pad, width_bucket]`` arrays (the "pad" span);
    - ``dispatch(staged)`` — the device scoring call on staged arrays,
      asynchronous for the jitted families (the "dispatch" span);
    - ``finalize(raw, n)`` — map padded raw output back to ``n``
      user-facing predictions; materializing the device result here is
      where the host blocks (the "block" span).

    ``run_padded`` composes stage+dispatch for callers that don't need
    the split (warmup).
    """

    family: str = ""
    jit_fns: Tuple = ()
    # families with a row-width axis warm up over width buckets; the rest
    # only have the batch axis
    has_width: bool = True
    # the dtype the weight tables SERVE at (the manifest weights_dtype for
    # artifacts) — surfaced per model on /models and /metrics
    weights_dtype: str = "float32"
    # placement surface: single-device servables leave the defaults; the
    # sharded servables (serving/sharded.py) fill in their mesh shape and
    # the /models placement block
    mesh_shape: Optional[Tuple[int, ...]] = None
    placement_info: Optional[dict] = None

    def device_tables(self):
        """The resident score tables (arrays or pytrees of arrays) —
        whatever a request's gathers actually read. Feeds table_bytes."""
        return []

    def table_bytes(self) -> int:
        """Resident bytes of the score tables — the quantity bf16/int8
        artifacts shrink 2-4x (reported per model on /models + /metrics
        and in the bench_serving --quantize artifact)."""
        import jax

        total = 0
        for leaf in jax.tree_util.tree_leaves(self.device_tables()):
            size = getattr(leaf, "size", None)
            dt = getattr(leaf, "dtype", None)
            if size is not None and dt is not None:
                total += int(size) * np.dtype(dt).itemsize
        return total

    def stage(self, instances, b_pad: int, width_cap: int):
        raise NotImplementedError

    def dispatch(self, staged):
        raise NotImplementedError

    def row_keys(self, instances, width_cap: int):
        """Per-row canonical cache keys for the hot-row score cache
        (serving/cache.py), or None when this request — or this family —
        is not cacheable. The key hashes the canonical PRE-PARSED row
        form (what staging actually scores: ids mod dims, f32 values for
        the sparse families; binned int32 rows for trees; normalized
        (field, id, value) triples for FFM), so a string row and its
        pre-parsed twin share one cache line. The default is None —
        uncacheable — for any family without an override."""
        return None

    def run_padded(self, instances, b_pad: int, width_cap: int):
        return self.dispatch(self.stage(instances, b_pad, width_cap))

    def finalize(self, raw, n: int):
        return np.asarray(raw)[:n]

    def dummy_instance(self, width: Optional[int]):
        raise NotImplementedError

    def max_nnz(self, instances) -> int:
        return max((len(r) for r in instances), default=1)

    def count_overwide(self, instances, width_cap: int) -> int:
        """How many rows will actually truncate at ``width_cap`` — the
        operator signal for sizing max_width (exact, not per-chunk)."""
        return sum(1 for r in instances if len(r) > width_cap)


def _is_preparsed(instances) -> bool:
    """Pre-parsed requests, honored end to end (sparse-row families only;
    a LIST is always rows to parse):

    - 2-TUPLE ``(idx_rows, val_rows)`` of per-row arrays — the
      models.base._stage_rows convention; a rectangular pair (two
      ``[n, lanes]`` arrays) is such a tuple and stays an array through
      chunking;
    - 3-TUPLE ``(flat_idx, flat_val, lens)`` — the same rows pre-packed
      into flat arrays with per-row lengths, so staging needs no
      per-request concatenate at all.

    In-process callers (bench_serving --quantize, embedded scorers) skip
    the string-parse cost per call this way — essential when the thing
    being measured is table bandwidth, not tokenization."""
    return isinstance(instances, tuple) and len(instances) in (2, 3)


def _preparsed_len(instances) -> int:
    """Row count of a pre-parsed request (either tuple form)."""
    return len(instances[2] if len(instances) == 3 else instances[0])


def _preparsed_offsets(instances):
    """Element offsets for slicing a flat pre-parsed request — computed
    ONCE per predict call (not per chunk: the cumsum is O(rows), and a
    large offline predict chunks thousands of times)."""
    if len(instances) == 2:
        return None
    lens = instances[2]
    off = np.zeros(len(lens) + 1, np.int64)
    np.cumsum(lens, out=off[1:])
    return off


def _preparsed_chunk(instances, s: int, e: int, off=None):
    """Rows [s:e) of a pre-parsed request, preserving its form (the flat
    form slices by the precomputed element offsets ``off``)."""
    if len(instances) == 2:
        return (instances[0][s:e], instances[1][s:e])
    flat_i, flat_v, lens = instances
    return (flat_i[off[s]:off[e]], flat_v[off[s]:off[e]], lens[s:e])


class _SparseRowServable(_Servable):
    """Shared staging for the "feature[:value]" row families (linear,
    multiclass, FM): parse -> width-bucket -> one padded FeatureBlock.
    Subclasses only provide the jitted score call."""

    def __init__(self, dims: int) -> None:
        self.dims = dims

    def count_overwide(self, instances, width_cap: int) -> int:
        if _is_preparsed(instances):
            if len(instances) == 3:
                return int(np.count_nonzero(
                    np.asarray(instances[2]) > width_cap))
            instances = instances[0]
        return sum(1 for r in instances if len(r) > width_cap)

    def stage(self, instances, b_pad: int, width_cap: int):
        if _is_preparsed(instances):
            return self._stage_preparsed(instances, b_pad, width_cap)
        from ..models.base import _stage_rows

        idx_rows, val_rows = _stage_rows(instances, self.dims)
        n = len(idx_rows)
        width = min(pad_to_bucket(self.max_nnz(idx_rows)), width_cap)
        return pack_rows(idx_rows, val_rows, np.zeros(n, dtype=np.float32),
                         self.dims, width=width, batch_size=b_pad)

    def _stage_preparsed(self, instances, b_pad: int, width_cap: int):
        """Vectorized staging for pre-parsed requests: one masked
        [n, width] gather over the flattened rows replaces the per-row
        Python loop of pack_rows. Semantics are identical (hash ids mod
        dims, truncate rows past width_cap, pad lanes carry index == dims
        with value 0) but the host cost drops to C-speed array ops — on
        the quantized-serving bench the staging would otherwise price the
        host side and bury the table-bandwidth difference the precisions
        exist to change. The flat 3-tuple form skips even the
        concatenate: for wide-batch requests the per-row-array overhead
        alone is several ms."""
        if len(instances) == 3:
            flat_i, flat_v, lens = instances
            n = len(lens)
            lens = np.asarray(lens, np.int64)
            flat_i = np.asarray(flat_i)
            flat_v = np.asarray(flat_v, np.float32)
        else:
            idx_rows, val_rows = instances
            n = len(idx_rows)
            lens = np.fromiter((len(r) for r in idx_rows), np.int64,
                               count=n)
            flat_i = (np.concatenate(
                [np.asarray(r, np.int64).ravel() for r in idx_rows])
                if n else np.zeros(0, np.int64))
            flat_v = (np.concatenate(
                [np.asarray(r, np.float32).ravel() for r in val_rows])
                if n else np.zeros(0, np.float32))
        max_nnz = int(lens.max()) if n else 1
        width = min(pad_to_bucket(max(1, max_nnz)), width_cap)
        k = np.minimum(lens, width)
        indices = np.full((b_pad, width), self.dims, dtype=np.int32)
        values = np.zeros((b_pad, width), dtype=np.float32)
        nnz = np.zeros(b_pad, dtype=np.int32)
        total = int(lens.sum())
        if total:
            off = np.zeros(n, np.int64)
            np.cumsum(lens[:-1], out=off[1:])
            pos = np.arange(width, dtype=np.int64)
            mask = pos[None, :] < k[:, None]
            src = np.minimum(off[:, None] + pos[None, :], total - 1)
            indices[:n] = np.where(mask, flat_i[src] % self.dims,
                                   self.dims)
            values[:n] = np.where(mask, flat_v[src], np.float32(0.0))
        nnz[:n] = k.astype(np.int32)
        return FeatureBlock(indices, values,
                            np.zeros(b_pad, dtype=np.float32), nnz)

    def dummy_instance(self, width):
        return [(i, 1.0) for i in range(width)]

    def row_keys(self, instances, width_cap: int):
        """blake2b-128 digests over (ids mod dims as int64, values as
        f32), in row order. Rows wider than ``width_cap`` make the WHOLE
        request uncacheable (None): truncation semantics live in staging,
        and replicating them here would be a second source of truth. Row
        order is part of the key — a permuted duplicate is a different
        fp-reduction order, so it conservatively gets its own entry."""
        from hashlib import blake2b

        if _is_preparsed(instances):
            if len(instances) == 3:
                flat_i, flat_v, lens = instances
                lens = np.asarray(lens, np.int64)
                if lens.size and int(lens.max()) > width_cap:
                    return None
                flat_i = np.asarray(flat_i, np.int64) % self.dims
                flat_v = np.asarray(flat_v, np.float32)
                off = np.zeros(len(lens) + 1, np.int64)
                np.cumsum(lens, out=off[1:])
                idx_rows = [flat_i[off[i]:off[i + 1]]
                            for i in range(len(lens))]
                val_rows = [flat_v[off[i]:off[i + 1]]
                            for i in range(len(lens))]
            else:
                idx_rows = [np.asarray(r, np.int64) % self.dims
                            for r in instances[0]]
                val_rows = [np.asarray(v, np.float32) for v in instances[1]]
        else:
            from ..models.base import _stage_rows

            try:
                idx_rows, val_rows = _stage_rows(instances, self.dims)
            except Exception:  # graftcheck: disable=G028 (None = uncacheable; the error re-surfaces on the predict path)
                return None
        keys = []
        for idx, val in zip(idx_rows, val_rows):
            if len(idx) > width_cap:
                return None
            keys.append(blake2b(
                np.ascontiguousarray(idx, np.int64).tobytes()
                + np.ascontiguousarray(val, np.float32).tobytes(),
                digest_size=16).digest())
        return keys


class _LinearServable(_SparseRowServable):
    family = "linear"

    def __init__(self, state, dims: int) -> None:
        from ..core.engine import make_predict

        super().__init__(dims)
        self.state = state
        self.weights_dtype = np.dtype(state.weights.dtype).name
        self._predict = make_predict(use_covariance=False)
        self.jit_fns = (self._predict,)

    def dispatch(self, staged):
        return self._predict(self.state, staged.indices, staged.values)

    def device_tables(self):
        # weights only: the serving predict is built use_covariance=False,
        # so a resident covariance table is reload baggage, not score-path
        # bytes — counting it would overstate what requests actually gather
        return [self.state.weights]


class _ArgmaxLabelServable(_SparseRowServable):
    """Shared label selection for the multiclass servables (f32 and int8):
    argmax over the [B, L] score matrix, mapped through label_vocab."""

    label_vocab: list

    def finalize(self, raw, n):
        scores = np.asarray(raw)[:n]
        return [self.label_vocab[i] for i in np.argmax(scores, axis=1)]


class _MulticlassServable(_ArgmaxLabelServable):
    family = "multiclass"

    def __init__(self, state, label_vocab, dims: int) -> None:
        from ..models.multiclass import _mc_scores

        super().__init__(dims)
        self.state = state
        self.label_vocab = list(label_vocab)
        self.weights_dtype = np.dtype(state.weights.dtype).name
        self._scores = _mc_scores
        self.jit_fns = (_mc_scores,)

    def dispatch(self, staged):
        return self._scores(self.state.weights, staged.indices,
                            staged.values)

    def device_tables(self):
        # _mc_scores reads the weight matrix only (see _LinearServable)
        return [self.state.weights]


class _FMServable(_SparseRowServable):
    family = "fm"

    def __init__(self, state, dims: int) -> None:
        from ..models.fm import _fm_scores

        super().__init__(dims)
        self.state = state
        self.weights_dtype = np.dtype(state.w.dtype).name
        self._scores = _fm_scores
        self.jit_fns = (_fm_scores,)

    def dispatch(self, staged):
        return self._scores(self.state, staged.indices, staged.values)

    def device_tables(self):
        return [self.state.w, self.state.v]


class _FFMServable(_Servable):
    family = "ffm"

    def __init__(self, state, hyper) -> None:
        from ..models.ffm import _ffm_scores_jit

        self.state = state
        self.hyper = hyper
        self._scores = _ffm_scores_jit
        self.jit_fns = (_ffm_scores_jit,)

    def device_tables(self):
        # _row_predict reads v/w/w0; the FTRL optimizer slots riding on the
        # state pytree are not score-path bytes
        return [self.state.v, self.state.w, self.state.w0]

    def stage(self, instances, b_pad, width_cap):
        from ..utils.feature import FMFeature

        hy = self.hyper
        parsed = [[FMFeature.parse(f, num_features=hy.num_features,
                                   num_fields=hy.num_fields) for f in row]
                  for row in instances]
        width = min(pad_to_bucket(self.max_nnz(parsed)), width_cap)
        idx = np.full((b_pad, width), hy.num_features, np.int32)
        val = np.zeros((b_pad, width), np.float32)
        fld = np.zeros((b_pad, width), np.int32)
        for r, row in enumerate(parsed):
            for c, f in enumerate(row[:width]):
                idx[r, c] = f.index % hy.num_features
                val[r, c] = f.value
                fld[r, c] = (f.field if f.field >= 0 else 0) % hy.num_fields
        return idx, val, fld

    def dispatch(self, staged):
        idx, val, fld = staged
        return self._scores(self.hyper, self.state, idx, val, fld)

    def dummy_instance(self, width):
        return [f"{k % 8}:{k}:1.0" for k in range(width)]

    def row_keys(self, instances, width_cap: int):
        """blake2b-128 over the canonical (field, id, value) triples —
        ids mod num_features, fields normalized exactly as staging does
        (negative -> 0, mod num_fields), values f32 — so a string row and
        a differently-written equivalent share one cache line. Rows wider
        than ``width_cap`` make the request uncacheable (truncation
        semantics live in staging, not here); unparseable rows too — the
        parse error re-surfaces on the predict path with its real
        message."""
        from hashlib import blake2b

        from ..utils.feature import FMFeature

        hy = self.hyper
        keys = []
        try:
            for row in instances:
                if len(row) > width_cap:
                    return None
                idx = np.empty(len(row), np.int64)
                fld = np.empty(len(row), np.int64)
                val = np.empty(len(row), np.float32)
                for c, f in enumerate(row):
                    p = FMFeature.parse(f, num_features=hy.num_features,
                                        num_fields=hy.num_fields)
                    idx[c] = p.index % hy.num_features
                    fld[c] = (p.field if p.field >= 0 else 0) % hy.num_fields
                    val[c] = p.value
                keys.append(blake2b(
                    idx.tobytes() + fld.tobytes() + val.tobytes(),
                    digest_size=16).digest())
        except Exception:  # graftcheck: disable=G028 (None = uncacheable; the error re-surfaces on the predict path)
            return None
        return keys


class _PairServable(_Servable):
    """Shared (user, item) pair staging for the MF servables (f32 and
    quantized): there is no [B, K] device batch shape to bucket, so
    has_width is False and jit_fns is empty."""

    family = "mf"
    has_width = False

    def stage(self, instances, b_pad, width_cap):
        pairs = np.asarray(instances, np.int64).reshape(len(instances), 2)
        u = np.zeros(b_pad, np.int64)
        i = np.zeros(b_pad, np.int64)
        u[:len(instances)] = pairs[:, 0]
        i[:len(instances)] = pairs[:, 1]
        return u, i

    def dummy_instance(self, width):
        return (0, 0)

    def row_keys(self, instances, width_cap: int):
        """A (user, item) pair IS its own canonical 16-byte key — no
        digest needed (same length as the sparse families' blake2b-128,
        so cache cost accounting is uniform)."""
        try:
            pairs = np.ascontiguousarray(
                np.asarray(instances, np.int64).reshape(len(instances), 2))
        except (TypeError, ValueError):
            return None
        return [p.tobytes() for p in pairs]


class _MFServable(_PairServable):
    """Host-side embedding lookup — numpy gather-dot, bit-identical to
    TrainedMFModel.predict."""

    def __init__(self, model) -> None:
        self.model = model
        self.weights_dtype = np.dtype(model.state.P.dtype).name

    def device_tables(self):
        return [self.model.state.P, self.model.state.Q,
                self.model.state.Bu, self.model.state.Bi]

    def dispatch(self, staged):
        u, i = staged
        return self.model.predict(u, i)


class _QuantLinearServable(_SparseRowServable):
    """int8 linear rows served dequant-free: gather the int8 window, fold
    the per-block absmax scale into the f32 dot product (_quant_jit_fns)."""

    family = "linear"
    weights_dtype = "int8"

    def __init__(self, qw, scales, block_rows: int, dims: int) -> None:
        super().__init__(dims)
        self.qw = qw
        self.scales = scales
        self.block_shift = int(block_rows).bit_length() - 1
        self._scores = _quant_jit_fns()["linear"]
        self.jit_fns = (self._scores,)

    def dispatch(self, staged):
        return self._scores(self.qw, self.scales, staged.indices,
                            staged.values, self.block_shift)

    def device_tables(self):
        return [self.qw, self.scales]


class _QuantMulticlassServable(_ArgmaxLabelServable):
    """int8 multiclass [L, D] table, scales blocked along the feature
    axis; argmax label selection shared with _MulticlassServable."""

    family = "multiclass"
    weights_dtype = "int8"

    def __init__(self, qW, scales, block_rows: int, label_vocab,
                 dims: int) -> None:
        super().__init__(dims)
        self.qW = qW
        self.scales = scales
        self.label_vocab = list(label_vocab)
        self.block_shift = int(block_rows).bit_length() - 1
        self._scores = _quant_jit_fns()["multiclass"]
        self.jit_fns = (self._scores,)

    def dispatch(self, staged):
        return self._scores(self.qW, self.scales, staged.indices,
                            staged.values, self.block_shift)

    def device_tables(self):
        return [self.qW, self.scales]


class _QuantFMServable(_SparseRowServable):
    """int8 FM: w [D] and v [D, F] gather int8, the per-row-block scales
    fold into the gathered windows, and the same _row_predict core as the
    live scorer combines them (f32 throughout)."""

    family = "fm"
    weights_dtype = "int8"

    def __init__(self, w0, qw, w_scales, qv, v_scales, block_rows: int,
                 dims: int) -> None:
        super().__init__(dims)
        self.w0 = w0
        self.qw = qw
        self.w_scales = w_scales
        self.qv = qv
        self.v_scales = v_scales
        self.block_shift = int(block_rows).bit_length() - 1
        self._scores = _quant_jit_fns()["fm"]
        self.jit_fns = (self._scores,)

    def dispatch(self, staged):
        return self._scores(self.w0, self.qw, self.w_scales, self.qv,
                            self.v_scales, staged.indices, staged.values,
                            self.block_shift)

    def device_tables(self):
        return [self.qw, self.w_scales, self.qv, self.v_scales]


class _QuantMFServable(_PairServable):
    """MF embedding lookup over reduced P/Q tables (bf16 or int8): gather
    the requested rows, widen ONLY the gathered window to f32 — never the
    table — and fold the int8 row-block scales when present. Host-side
    numpy like _MFServable (no device batch work to amortize); pair
    staging shared via _PairServable."""

    def __init__(self, P, Q, Bu, Bi, mu, use_bias: bool, *,
                 p_scales=None, q_scales=None, block_rows: int = 1,
                 weights_dtype: str = "bfloat16") -> None:
        self.P = P
        self.Q = Q
        self.Bu = Bu
        self.Bi = Bi
        self.mu = np.float32(mu)
        self.use_bias = bool(use_bias)
        self.p_scales = p_scales
        self.q_scales = q_scales
        self.block_shift = int(block_rows).bit_length() - 1
        self.weights_dtype = weights_dtype

    def _rows(self, table, scales, ids):
        g = np.asarray(table[ids], np.float32)  # per-window widen (G019)
        if scales is not None:
            g = g * scales[ids >> self.block_shift]
        return g

    def dispatch(self, staged):
        u, i = staged
        out = np.sum(self._rows(self.P, self.p_scales, u)
                     * self._rows(self.Q, self.q_scales, i),
                     axis=-1) + self.mu
        if self.use_bias:
            out = out + self.Bu[u] + self.Bi[i]
        return out

    def device_tables(self):
        return [t for t in (self.P, self.Q, self.p_scales, self.q_scales,
                            self.Bu, self.Bi) if t is not None]


class _TreeServable(_Servable):
    """Shared host binning + padded vmapped tree walk (forest, GBT)."""

    has_width = False

    def __init__(self, trees_flat, bins) -> None:
        from ..models.trees.binning import BinInfo
        from ..models.trees.grow import predict_forest_binned, stack_trees

        # f32 request staging with edges narrowed ALONGSIDE: an edge that IS
        # a data value stays equal to it (both sides of the searchsorted
        # round identically), so every training-valued instance bins as the
        # tree was grown. Request values within one f32 ulp of an edge may
        # bin to the neighbor — the f32-resolution quantization the serving
        # dtype contract accepts (request payloads stage f32, G018). NOT
        # acceptable is distinct edges that collapse under f32 — nominal
        # category codes >= 2^24, or quantile edges of large-magnitude
        # quantitative features (timestamps ~1.7e9 have f32 spacing of 128)
        # — where a duplicated edge makes a bin entirely unreachable: any
        # collapsing bin keeps the model on the f64 path end to end.
        if any(np.unique(np.asarray(b.edges, np.float32)).size
               != len(b.edges) for b in bins):
            self.stage_dtype = np.float64  # graftcheck: disable=G018 (distinct bin edges collapse under f32; binning parity needs f64)
            self.bins = bins
        else:
            self.stage_dtype = np.float32
            self.bins = [BinInfo(b.nominal, np.asarray(b.edges, np.float32),
                                 b.n_bins) for b in bins]
        self.n_features = len(bins)
        self.stacked = stack_trees(trees_flat) if trees_flat else None
        self._walk = predict_forest_binned
        self.jit_fns = (predict_forest_binned,)

    def device_tables(self):
        return ([self.stacked] if self.stacked is not None else []) + \
            [b.edges for b in self.bins]

    def stage(self, instances, b_pad, width_cap):
        from ..models.trees.binning import bin_data

        X = np.asarray(instances, self.stage_dtype).reshape(
            len(instances), self.n_features)
        Xb = np.zeros((b_pad, self.n_features), np.int32)
        Xb[:len(instances)] = bin_data(X, self.bins)
        return Xb

    def dispatch(self, staged):
        if self.stacked is None:
            return np.zeros((0, staged.shape[0]), dtype=np.float32)
        return self._walk(self.stacked, staged)

    def dummy_instance(self, width):
        return [0.0] * self.n_features

    def row_keys(self, instances, width_cap: int):
        """blake2b-128 over the BINNED row (int32 bin ids) — the canonical
        form the tree walk actually consumes, so any two raw rows that
        bin identically share one cache line (and an edge-straddling
        perturbation correctly does not). Malformed requests are
        uncacheable (None); the shape error re-surfaces on the predict
        path."""
        from hashlib import blake2b

        from ..models.trees.binning import bin_data

        try:
            X = np.asarray(instances, self.stage_dtype).reshape(
                len(instances), self.n_features)
        except (TypeError, ValueError):
            return None
        Xb = np.ascontiguousarray(bin_data(X, self.bins), np.int32)
        return [blake2b(row.tobytes(), digest_size=16).digest()
                for row in Xb]


class _ForestServable(_TreeServable):
    family = "forest"

    def __init__(self, trees, bins, classification: bool,
                 n_classes: int) -> None:
        super().__init__(trees, bins)
        self.classification = classification
        self.n_classes = n_classes

    def finalize(self, raw, n):
        from ..models.trees.forest import forest_vote

        leaf_vals = np.asarray(raw)[:, :n]  # [T, n]
        if self.classification:
            return forest_vote(leaf_vals, self.n_classes)
        return leaf_vals.mean(axis=0)


class _GBTServable(_TreeServable):
    family = "gbt"

    def __init__(self, trees_flat, n_rounds: int, n_class_trees: int,
                 intercept, shrinkage: float, classes, bins) -> None:
        super().__init__(trees_flat, bins)
        self.n_rounds = n_rounds
        self.K = n_class_trees
        # staged at the tree path's dtype: f32 normally, f64 when the
        # collapse guard kept the model on the f64 path end to end
        self.intercept = np.asarray(intercept, self.stage_dtype)
        self.shrinkage = float(shrinkage)
        self.classes = np.asarray(classes)

    def finalize(self, raw, n):
        from ..models.trees.forest import gbt_decision_scores

        leaf_vals = np.asarray(raw)[:, :n]
        scores = gbt_decision_scores(leaf_vals, self.intercept,
                                     self.shrinkage, self.n_rounds, self.K)
        if scores.shape[1] == 1:
            return self.classes[(scores[:, 0] > 0).astype(int)]
        return self.classes[np.argmax(scores, axis=1)]


def _quant_servable_from_artifact(art: Artifact) -> _Servable:
    """Quantized artifact -> dequant-free servable. bf16 tables reload AT
    bf16 through the families' own scorers (raw uint16 bit patterns view
    back losslessly — io.checkpoint.bf16_unpack_raw); int8 tables keep
    their q arrays + f32 scales and score through the _q8_* kernels."""
    import jax.numpy as jnp

    from ..io.checkpoint import QUANT_SCHEME_BF16, QUANT_SCHEME_INT8, \
        SCALE_SUFFIX, bf16_unpack_raw

    meta, a = art.meta, art.arrays
    quant = manifest_quant(meta)
    fam = art.family
    if quant["scheme"] == QUANT_SCHEME_BF16:
        if fam == "linear":
            from ..core.state import init_linear_state

            state = init_linear_state(
                int(meta["dims"]), use_covariance=False,
                dtype=jnp.bfloat16,
                initial_weights=bf16_unpack_raw(a["weight"]))
            return _LinearServable(state, int(meta["dims"]))
        if fam == "multiclass":
            from ..models.multiclass import MulticlassState

            W = jnp.asarray(bf16_unpack_raw(a["weights"]), jnp.bfloat16)
            state = MulticlassState(
                weights=W, covars=None,
                touched=jnp.ones(W.shape, jnp.int8),
                step=jnp.zeros((), jnp.int32))
            return _MulticlassServable(state, meta["label_vocab"],
                                       int(meta["dims"]))
        if fam == "fm":
            from ..models.fm import FMState

            w = jnp.asarray(bf16_unpack_raw(a["w"]), jnp.bfloat16)
            v = jnp.asarray(bf16_unpack_raw(a["v"]), jnp.bfloat16)
            # training-only fields are placeholders: _fm_scores reads
            # w0/w/v only, and the quantized payload dropped the rest
            state = FMState(
                w0=jnp.asarray(a["w0"], jnp.float32), w=w, v=v,
                lambda_w0=jnp.zeros((), jnp.float32),
                lambda_w=jnp.zeros((), jnp.float32),
                lambda_v=jnp.zeros((v.shape[1],), jnp.float32),
                touched=jnp.ones((w.shape[0],), jnp.int8),
                step=jnp.zeros((), jnp.int32))
            return _FMServable(state, int(meta["dims"]))
        if fam == "mf":
            return _QuantMFServable(
                bf16_unpack_raw(a["P"]), bf16_unpack_raw(a["Q"]),
                np.asarray(a["Bu"], np.float32),
                np.asarray(a["Bi"], np.float32), float(a["mu"]),
                bool(meta["use_bias"]), weights_dtype="bfloat16")
    elif quant["scheme"] == QUANT_SCHEME_INT8:
        br = int(quant["block_rows"])
        if fam == "linear":
            return _QuantLinearServable(
                jnp.asarray(a["weight"], jnp.int8),
                jnp.asarray(a["weight" + SCALE_SUFFIX], jnp.float32),
                br, int(meta["dims"]))
        if fam == "multiclass":
            return _QuantMulticlassServable(
                jnp.asarray(a["weights"], jnp.int8),
                jnp.asarray(a["weights" + SCALE_SUFFIX], jnp.float32),
                br, meta["label_vocab"], int(meta["dims"]))
        if fam == "fm":
            return _QuantFMServable(
                jnp.asarray(a["w0"], jnp.float32),
                jnp.asarray(a["w"], jnp.int8),
                jnp.asarray(a["w" + SCALE_SUFFIX], jnp.float32),
                jnp.asarray(a["v"], jnp.int8),
                jnp.asarray(a["v" + SCALE_SUFFIX], jnp.float32),
                br, int(meta["dims"]))
        if fam == "mf":
            return _QuantMFServable(
                np.asarray(a["P"], np.int8), np.asarray(a["Q"], np.int8),
                np.asarray(a["Bu"], np.float32),
                np.asarray(a["Bi"], np.float32), float(a["mu"]),
                bool(meta["use_bias"]),
                p_scales=np.asarray(a["P" + SCALE_SUFFIX], np.float32),
                q_scales=np.asarray(a["Q" + SCALE_SUFFIX], np.float32),
                block_rows=br, weights_dtype="int8")
    raise ValueError(f"unknown quantized artifact: family {fam!r}, "
                     f"scheme {quant['scheme']!r}")


def _servable_from_artifact(art: Artifact) -> _Servable:
    import jax.numpy as jnp

    meta = art.meta
    a = art.arrays
    if manifest_quant(meta) is not None:
        return _quant_servable_from_artifact(art)
    # every device table reloads at its MANIFEST dtype: the pack stores
    # reduced tables widened (value-exact), so asarray without a pin would
    # silently serve a bf16-trained model at 2x HBM traffic (G020)
    table_dt = manifest_dtype(meta)
    if art.family == "linear":
        from ..core.state import init_linear_state
        from ..io.checkpoint import dense_from_rows

        w, c = dense_from_rows(int(meta["dims"]), a["feature"], a["weight"],
                               a.get("covar"))
        state = init_linear_state(
            int(meta["dims"]), use_covariance=bool(meta["use_covariance"]),
            dtype=table_dt, initial_weights=w, initial_covars=c)
        return _LinearServable(state, int(meta["dims"]))
    if art.family == "multiclass":
        from ..models.multiclass import MulticlassState

        weights = jnp.asarray(a["weights"], table_dt)
        state = MulticlassState(
            weights=weights,
            covars=jnp.asarray(a["covars"], table_dt) if "covars" in a
            else None,
            touched=jnp.ones(weights.shape, jnp.int8),
            step=jnp.zeros((), jnp.int32))
        return _MulticlassServable(state, meta["label_vocab"],
                                   int(meta["dims"]))
    if art.family == "fm":
        from ..models.fm import FMState

        state = FMState(
            w0=jnp.asarray(a["w0"], table_dt),
            w=jnp.asarray(a["w"], table_dt),
            v=jnp.asarray(a["v"], table_dt),
            lambda_w0=jnp.asarray(a["lambda_w0"], table_dt),
            lambda_w=jnp.asarray(a["lambda_w"], table_dt),
            lambda_v=jnp.asarray(a["lambda_v"], table_dt),
            touched=jnp.asarray(a["touched"], jnp.int8),
            step=jnp.zeros((), jnp.int32))
        return _FMServable(state, int(meta["dims"]))
    if art.family == "ffm":
        model = rebuild_model(art)
        return _FFMServable(model.state, model.hyper)
    if art.family == "mf":
        return _MFServable(rebuild_model(art))
    if art.family == "forest":
        from .artifact import _unpack_bins, _unpack_trees

        trees = _unpack_trees("tree", int(meta["n_trees"]), a)
        return _ForestServable(trees, _unpack_bins(meta, a),
                               bool(meta["classification"]),
                               int(meta["n_classes"]))
    if art.family == "gbt":
        from .artifact import _unpack_bins, _unpack_trees

        n = int(meta["n_rounds"]) * int(meta["n_class_trees"])
        trees = _unpack_trees("tree", n, a)
        return _GBTServable(trees, int(meta["n_rounds"]),
                            int(meta["n_class_trees"]), a["intercept"],
                            float(meta["shrinkage"]), a["classes"],
                            _unpack_bins(meta, a))
    raise ValueError(f"unknown artifact family {art.family!r}")


def _servable_from_model(model) -> _Servable:
    family = family_of(model)
    if family == "linear":
        return _LinearServable(model.state, model.dims)
    if family == "multiclass":
        return _MulticlassServable(model.state, model.label_vocab, model.dims)
    if family == "fm":
        return _FMServable(model.state, model.dims)
    if family == "ffm":
        return _FFMServable(model.state, model.hyper)
    if family == "mf":
        return _MFServable(model)
    if family == "forest":
        return _ForestServable([t.tree for t in model.trees], model.bins,
                               model.classification, model.n_classes)
    if family == "gbt":
        flat = [t for round_trees in model.trees for t in round_trees]
        return _GBTServable(flat, len(model.trees),
                            len(model.trees[0]) if model.trees else 0,
                            model.intercept, model.shrinkage, model.classes,
                            model.bins)
    raise ValueError(f"unknown family {family!r}")


def _dtype_bits(name: str) -> int:
    """Bits per element of a weights_dtype name (bf16 is not a stock numpy
    dtype string, so map it explicitly)."""
    if name == "bfloat16":
        return 16
    try:
        return int(np.dtype(name).itemsize) * 8
    except TypeError:
        return 32


# Warmup dummy instances keyed by bucket shape, shared across engines:
# deploying N same-family models re-warms the same (batch, width) mesh, and
# re-CONSTRUCTING the dummy rows per model is pure host-side waste (jit
# caches are already shared — see the module docstring). dummy_instance is
# shape-determined (family + width + feature count), so one construction
# serves every model. Plain dict mutation is GIL-atomic; a racing deploy at
# worst constructs one duplicate.
_WARMUP_DUMMIES: dict = {}


def _warmup_dummy(servable: _Servable, width: int):
    # mesh shape is part of the key: a sharded servable's warmup sweep is
    # logically per-mesh (the jit caches it fills are keyed by mesh), so a
    # (1, 4) engine must not hand its cache hit to a (2, 2) one — even
    # though the dummy CONTENT only depends on shape, keeping the keys
    # honest keeps the dedup test meaningful per mesh
    key = (servable.family, width, getattr(servable, "n_features", None),
           servable.mesh_shape)
    inst = _WARMUP_DUMMIES.get(key)
    if inst is None:
        inst = _WARMUP_DUMMIES[key] = servable.dummy_instance(width)
    return inst


# the protocol's public name: external servable implementations (and type
# hints) should spell it Servable; the underscore spelling predates the
# placement refactor and the in-tree adapters keep it
Servable = _Servable


def make_servable(obj, placement=None) -> _Servable:
    """Artifact | artifact dir path | trained model -> family servable.

    ``placement`` (None | kind string | serving.placement.Placement)
    decides where the score tables live: the default single-device
    adapters below, or the NamedSharding-striped servables of
    serving/sharded.py for ``replicated`` / ``model_sharded``. A
    ``device_byte_budget`` on the placement is enforced here — a model
    whose per-device resident score-table bytes exceed it refuses to load
    (ModelExceedsDeviceBudget) instead of OOMing at first request."""
    from .placement import resolve_placement

    placement = resolve_placement(placement)
    if isinstance(obj, str):
        obj = load(obj)
    if placement.kind != "single_device":
        from .sharded import sharded_servable

        return sharded_servable(obj, placement)
    servable = _servable_from_artifact(obj) if isinstance(obj, Artifact) \
        else _servable_from_model(obj)
    if placement.device_byte_budget is not None:
        placement.check_budget(servable.table_bytes(),
                               f"{servable.family} model "
                               f"({servable.weights_dtype})")
    return servable


class ServingEngine:
    """Bucketed, warmed, metered predictor for one model version.

    `predict(instances)` is thread-safe for the jitted families (the state
    is immutable and jit dispatch is reentrant); the dynamic batcher
    (serving/batcher.py) serializes calls anyway so each batch is one
    device dispatch.
    """

    def __init__(self, source, *, name: str = "default",
                 max_batch: int = 512, max_width: int = 256,
                 min_batch_bucket: int = 8, placement=None) -> None:
        if max_batch < min_batch_bucket:
            raise ValueError("max_batch must be >= min_batch_bucket")
        self.servable = source if isinstance(source, _Servable) \
            else make_servable(source, placement=placement)
        self.placement = self.servable.placement_info or \
            {"kind": "single_device", "devices": 1, "mesh_shape": None,
             "batch_shards": 1, "model_shards": 1}
        bs = int(self.placement.get("batch_shards", 1))
        if bs > 1 and (min_batch_bucket % bs or max_batch % bs):
            # every batch bucket must split evenly over the batch axis —
            # buckets are min_batch_bucket * 2^k capped at max_batch, so
            # divisibility of the two ends covers the whole ladder
            raise ValueError(
                f"batch_shards={bs} must divide min_batch_bucket "
                f"({min_batch_bucket}) and max_batch ({max_batch})")
        self.family = self.servable.family
        self.name = name
        self.max_batch = int(max_batch)
        self.max_width = int(max_width)
        self.min_batch_bucket = int(min_batch_bucket)
        self._latency = REGISTRY.histogram(
            f"serving.{name}.predict_seconds", LATENCY_BUCKETS)
        self._rows = REGISTRY.counter("serving", f"{name}.rows")
        self._truncated = REGISTRY.counter("serving", f"{name}.truncated_rows")
        self.warmed_buckets: List[Tuple[int, Optional[int]]] = []
        # dispatch-level service-rate estimate (rows/sec EWMA over recent
        # predicts) — the capacity signal the overload surface reads:
        # /metrics exports it and the batcher's Retry-After math uses its
        # own copy of the same quantity. The express and general batcher
        # lanes both call predict, so the read-modify-write is guarded.
        self.rows_per_sec = 0.0
        self._rate_lock = threading.Lock()
        # per-model precision surface (/models + /metrics): the dtype the
        # tables serve at and the resident bytes a request's gathers read —
        # what bf16/int8 artifacts shrink 2-4x
        self.weights_dtype = self.servable.weights_dtype
        self.table_bytes = int(self.servable.table_bytes())
        REGISTRY.set_gauge(f"serving.{name}.table_bytes",
                           float(self.table_bytes))
        REGISTRY.set_gauge(f"serving.{name}.weights_bits",
                           float(_dtype_bits(self.weights_dtype)))
        # placement gauges: how many devices this model's bytes spread over
        # and what one device actually holds (total for single-device)
        self.per_device_table_bytes = int(getattr(
            self.servable, "per_device_table_bytes", 0)) or self.table_bytes
        REGISTRY.set_gauge(f"serving.{name}.model_shards",
                           float(self.placement.get("model_shards", 1)))
        REGISTRY.set_gauge(f"serving.{name}.per_device_table_bytes",
                           float(self.per_device_table_bytes))

    # -- buckets -------------------------------------------------------------

    def batch_buckets(self) -> List[int]:
        out, b = [], self.min_batch_bucket
        while b < self.max_batch:
            out.append(b)
            b <<= 1
        out.append(self.max_batch)
        return out

    def width_buckets(self) -> List[Optional[int]]:
        if not self.servable.has_width:
            return [None]
        out, w = [], 8
        while w < self.max_width:
            out.append(w)
            w <<= 1
        out.append(self.max_width)
        return out

    def bucket_batch(self, n: int) -> int:
        b = self.min_batch_bucket
        while b < n:
            b <<= 1
        return min(b, self.max_batch)

    # -- serving -------------------------------------------------------------

    def warmup(self) -> int:
        """Precompile every (batch, width) bucket; returns the number of jit
        cache misses the sweep cost (all of them paid here, none in steady
        state). Idempotent — a second warmup compiles nothing."""
        t0 = time.perf_counter()
        # the warmup span makes every deploy-time compile visible as a
        # jit_recompile instant INSIDE a trace (recompile_guard emits them)
        with TRACER.span("engine.warmup", args={"engine": self.name,
                                                "family": self.family}), \
                recompile_guard(f"serving.{self.name}.warmup",
                                *self.servable.jit_fns) as g:
            for width in self.width_buckets():
                # dummy construction is keyed by bucket shape and shared
                # across engines (_WARMUP_DUMMIES) — pure host-side dedup,
                # the jit-cache semantics are untouched
                inst = _warmup_dummy(self.servable, width or 8)
                for b in self.batch_buckets():
                    raw = self.servable.run_padded([inst], b, self.max_width)
                    self.servable.finalize(raw, 1)
                    self.warmed_buckets.append((b, width))
        REGISTRY.set_gauge(f"serving.{self.name}.warmup_seconds",
                           time.perf_counter() - t0)
        REGISTRY.set_gauge(f"serving.{self.name}.warmup_compiles",
                           float(g.compiles))
        return g.compiles

    def row_keys(self, instances):
        """Per-row canonical cache keys for this request, or None when it
        is not cacheable (unsupported family, over-wide rows, malformed
        input — which then fails through the normal predict path). The
        hot-row score cache keys ``(model_version, row_key)`` on these
        (serving/cache.py; docs/serving.md "Score caching &
        coalescing")."""
        try:
            return self.servable.row_keys(instances, self.max_width)
        except Exception:  # graftcheck: disable=G028 (None = uncacheable; the error re-surfaces on the predict path)
            return None

    def predict(self, instances: Sequence):
        """Score a request of any size (chunks above max_batch). Each
        chunk's path is traced stage by stage — bucket selection, host
        pad, device dispatch, host block — as child spans of whatever
        request span is active (runtime/tracing.py), so a slow predict is
        attributable from the trace alone.

        ``instances`` is a list of rows, or — for the sparse-row families
        ONLY (other families treat any tuple as a plain sequence of rows)
        — a pre-parsed tuple: ``(idx_rows, val_rows)`` per-row arrays (the
        ``models.base._stage_rows`` convention; two ``[n, lanes]`` arrays
        are accepted as they are, and chunks of them are views) or the flat
        ``(flat_idx, flat_val, lens)`` packed form (see _is_preparsed)."""
        pre = (isinstance(self.servable, _SparseRowServable)
               and _is_preparsed(instances))
        off = _preparsed_offsets(instances) if pre else None
        n = _preparsed_len(instances) if pre else len(instances)
        if n == 0:
            return []
        t0 = time.perf_counter()
        outs = []
        with TRACER.span("engine.predict",
                         args={"engine": self.name, "family": self.family,
                               "rows": n}) as pspan:
            for s in range(0, n, self.max_batch):
                if pre:
                    chunk = _preparsed_chunk(instances, s,
                                             min(s + self.max_batch, n),
                                             off)
                    chunk_n = _preparsed_len(chunk)
                else:
                    chunk = instances[s:s + self.max_batch]
                    chunk_n = len(chunk)
                with TRACER.span("engine.bucket") as bspan:
                    if self.servable.has_width:
                        overwide = self.servable.count_overwide(
                            chunk, self.max_width)
                        if overwide:
                            self._truncated.increment(overwide)
                    b_pad = self.bucket_batch(chunk_n)
                    bspan.set(rows=chunk_n, b_pad=b_pad)
                with TRACER.span("engine.pad", args={"b_pad": b_pad}):
                    staged = self.servable.stage(chunk, b_pad,
                                                 self.max_width)
                with recompile_guard(f"serving.{self.name}",
                                     *self.servable.jit_fns):
                    with TRACER.span("engine.dispatch"):
                        raw = self.servable.dispatch(staged)
                    # finalize materializes the device result on the host
                    # — this is where an async dispatch is actually waited
                    # on (block_until_ready by another name)
                    with TRACER.span("engine.block"):
                        out = self.servable.finalize(raw, chunk_n)
                outs.append(out)
            self._rows.increment(n)
            dt = time.perf_counter() - t0
            self._latency.observe(dt, trace_id=TRACER.exemplar_id(pspan))
            if dt > 0:
                inst = n / dt
                with self._rate_lock:
                    self.rows_per_sec = inst if self.rows_per_sec <= 0.0 \
                        else 0.8 * self.rows_per_sec + 0.2 * inst
                    rate = self.rows_per_sec
                REGISTRY.set_gauge(f"serving.{self.name}.engine_rows_per_sec",
                                   rate)
        if len(outs) == 1:
            return outs[0]
        if isinstance(outs[0], np.ndarray):
            return np.concatenate(outs)
        return [x for o in outs for x in o]
