"""Top-K retrieval serving: score one user against the full item catalog.

Every other serving path is pointwise — a row in, a score out — but the
embedding families (MF, FM) are *retrieval* models: the production-shaped
query is "given user u, return the top-K of N items", a [B,F]x[F,N] matmul
plus top_k that is MXU-shaped and bandwidth-bound (the ads-infra paper's
scoring tier, PAPERS.md). This module is that workload as a subsystem:

- **Staged query, streamed catalog.** The user side is gathered ONCE per
  request into ``(qvec, base)`` such that for every item j

      score(u, j) = base_u + bias_j + <qvec_u, vec_j>

  For MF that is ``mu + Bu[u]`` / ``Bi[j]`` / ``P[u]·Q[j]``; for FM it is
  algebra on the factorization identity — with item feature j one-hot at
  value 1, ``FM(x_u + e_j) = p(x_u) + w[j] + <sumVfX(x_u), v[j]>``
  exactly — so ONE block scorer serves both families. The catalog is then
  scored in fixed-size jitted blocks with a running top-K merge
  (``lax.top_k`` over carry ++ block), so no [N_items] score vector is
  ever materialized and the jit cache is independent of catalog size.
- **Zero steady-state recompiles.** Batch sizes pad to pow2 buckets, FM
  query widths pad to the engine width buckets, candidate slices pad to
  pow2 buckets; :meth:`RetrievalEngine.warmup` sweeps them all and
  ``recompile_guard`` pins the steady state (counter
  ``graftcheck.recompiles.serving.<name>.topk``).
- **Sharded catalogs.** Under a :class:`~.placement.ModelSharded`
  placement the catalog is striped over the model axis by the PR 9 grid
  arithmetic (core.striping.stripe_grid); each device scores its local
  item slice and the cross-stripe merge is an ``all_gather`` of the
  per-device block scores + global ids into the same top-K carry. int8
  catalogs serve dequant-free per the ``_q8_*`` pattern: only the sliced
  window widens to f32, scales fold by ``id >> block_shift``, and the
  accumulation is f32 (graftcheck G019/G021).
- **LSH candidate pruning.** ``freeze(..., retrieval_index=...)`` builds
  signed-random-projection buckets over the item vectors into the
  artifact (manifest ``index`` block, arrays ``index__*``); probe-time
  hashes ``qvec`` once, unions the Hamming-<=1 buckets, and the SAME
  blocked scorer consumes the padded candidate slice. Requests fall back
  to exact scoring (counted) when a bucket union is smaller than k or
  larger than ``candidate_cap`` — recall@K vs exact is measured and
  gated in ``scripts/bench_serving.py --topk``.

Tie-breaking: the streamed merge concatenates the carry BEFORE the new
block and blocks arrive in ascending-id order, so equal scores resolve to
the LOWEST item id — bit-for-bit the order of a stable argsort on the
materialized scores (the bench parity pin). The sharded merge interleaves
stripes per step, so exact ties across stripes may resolve differently;
its gate is score parity with the single-device engine (see
docs/serving.md "Top-K retrieval").

Ordering contract with the score cache: /topk results are never row-cached
(a top-K set is not a row score); the hot-row cache stays a /predict
concern.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..core.striping import stripe_grid
from ..runtime.metrics import REGISTRY, recompile_guard
from ..runtime.tracing import TRACER
from .artifact import Artifact, family_of, host_score_tables, load
from .engine import LATENCY_BUCKETS
from .placement import MODEL_AXIS, ModelSharded, resolve_placement

RETRIEVAL_FAMILIES = ("mf", "fm")

# jitted retrieval kernels are keyed by everything closure-static and
# shared process-wide (the engine.py _QUANT_JIT discipline): two engines
# with the same block geometry — or one engine across hot-swaps — reuse
# one jit cache
_RETRIEVAL_JIT: dict = {}
_RETRIEVAL_JIT_LOCK = threading.Lock()


def _retrieval_jit(key, build):
    with _RETRIEVAL_JIT_LOCK:
        fn = _RETRIEVAL_JIT.get(key)
        if fn is None:
            fn = _RETRIEVAL_JIT[key] = build()
        return fn


def _pow2_at_least(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


# --- jitted kernels ----------------------------------------------------------
#
# One score expression, used by the streamed merge step AND the
# materializing parity baseline, so "blocked top-K == argsort of the
# materialized scores" is an identity on the score bits, not a tolerance.


def _make_block_scorer(bk: int, block_shift: Optional[int],
                       bias_scaled: bool):
    import jax
    import jax.numpy as jnp

    def score(vec, bias, vscale, bscale, qvec, base, start, n_valid):
        blk = jax.lax.dynamic_slice_in_dim(vec, start, bk, axis=0)
        bb = jax.lax.dynamic_slice_in_dim(bias, start, bk, axis=0)
        ids = start + jnp.arange(bk, dtype=jnp.int32)
        w = blk.astype(jnp.float32)  # per-window widen only (G019)
        b = bb.astype(jnp.float32)
        if block_shift is not None:
            # scales are [nb, F] for 2-D tables (io.checkpoint
            # quantize_int8: per block-of-rows, per column) — the gather
            # aligns shapes, the fold is elementwise
            w = w * vscale.at[ids >> block_shift].get(
                mode="fill", fill_value=0.0)
            if bias_scaled:
                b = b * bscale.at[ids >> block_shift].get(
                    mode="fill", fill_value=0.0)
        scores = base[:, None] + qvec @ w.T + b[None, :]
        # pad lanes (catalog rows past n_valid) must lose every merge
        return jnp.where(ids[None, :] < n_valid, scores, -jnp.inf), ids

    return score


def _build_block_step(bk: int, k_pad: int, block_shift: Optional[int],
                      bias_scaled: bool):
    """One streamed-merge step: score a [bk] catalog block, merge into the
    running [B, k_pad] carry. Carry-first concat + ascending block ids =
    stable-argsort tie order (lax.top_k keeps the lowest position)."""
    import jax
    import jax.numpy as jnp

    score = _make_block_scorer(bk, block_shift, bias_scaled)

    def step(vec, bias, vscale, bscale, qvec, base, start, n_valid, cv, ci):
        scores, ids = score(vec, bias, vscale, bscale, qvec, base, start,
                            n_valid)
        vals = jnp.concatenate([cv, scores], axis=1)
        cand = jnp.concatenate(
            [ci, jnp.broadcast_to(ids[None, :], scores.shape)], axis=1)
        tv, pos = jax.lax.top_k(vals, k_pad)
        return tv, jnp.take_along_axis(cand, pos, axis=1)

    # the carry buffers are donated: run_blocks rebinds (cv, ci) to the
    # step's outputs every iteration, so the ingoing pair is dead — XLA
    # reuses it instead of holding 2x the carry live across the sweep
    return jax.jit(step, donate_argnums=(8, 9))


def _build_block_scores(bk: int, block_shift: Optional[int],
                        bias_scaled: bool):
    """Materializing baseline (bench/tests only — not a serving path)."""
    import jax

    score = _make_block_scorer(bk, block_shift, bias_scaled)

    def block_scores(vec, bias, vscale, bscale, qvec, base, start, n_valid):
        return score(vec, bias, vscale, bscale, qvec, base, start,
                     n_valid)[0]

    return jax.jit(block_scores)


def _build_cand_step(k_pad: int, block_shift: Optional[int],
                     bias_scaled: bool):
    """Score a padded candidate slice [B, C] (LSH probe output) directly:
    per-request gather instead of the block sweep. One fn per engine;
    jit caches per (B, C) bucket shape, all swept at warmup."""
    import jax
    import jax.numpy as jnp

    def cand(vec, bias, vscale, bscale, qvec, base, ids, mask):
        rows = vec.at[ids].get(mode="fill", fill_value=0)
        w = rows.astype(jnp.float32)
        b = bias.at[ids].get(mode="fill", fill_value=0).astype(jnp.float32)
        if block_shift is not None:
            w = w * vscale.at[ids >> block_shift].get(
                mode="fill", fill_value=0.0)
            if bias_scaled:
                b = b * bscale.at[ids >> block_shift].get(
                    mode="fill", fill_value=0.0)
        scores = base[:, None] + jnp.einsum("bf,bcf->bc", qvec, w) + b
        scores = jnp.where(mask, scores, -jnp.inf)
        tv, pos = jax.lax.top_k(scores, k_pad)
        return tv, jnp.take_along_axis(ids, pos, axis=1)

    return jax.jit(cand)


def _build_fm_stage():
    """FM query staging: (p, sumVfX) per row — exactly models.fm's
    _row_predict on gathered slices, so base_u matches the /predict path."""
    import jax

    from ..models.fm import _row_predict

    def stage(w0, w, v, idx, val):
        def one(i, x):
            wg = w.at[i].get(mode="fill", fill_value=0.0)
            vg = v.at[i].get(mode="fill", fill_value=0.0)
            return _row_predict(w0, wg, vg, x)

        return jax.vmap(one)(idx, val)

    return jax.jit(stage)


def _build_q8_fm_stage(block_shift: int):
    """int8 FM query staging: per-window widen + scale fold (q8_fm_scores
    extended to also return sumVfX)."""
    import jax
    import jax.numpy as jnp

    from ..models.fm import _row_predict

    def stage(w0, qw, ws, qv, vs, idx, val):
        def one(i, x):
            sw = ws.at[i >> block_shift].get(mode="fill", fill_value=0.0)
            wg = qw.at[i].get(mode="fill",
                              fill_value=0).astype(jnp.float32) * sw
            sv = vs.at[i >> block_shift].get(mode="fill", fill_value=0.0)
            vg = qv.at[i].get(mode="fill", fill_value=0).astype(
                jnp.float32) * sv
            return _row_predict(w0, wg, vg, x)

        return jax.vmap(one)(idx, val)

    return jax.jit(stage)


# --- sharded kernels ---------------------------------------------------------


def _build_sh_block_step(mesh, stripe: int, bk: int, k_pad: int,
                         block_shift: Optional[int], bias_scaled: bool):
    """Sharded streamed-merge step: each device scores a [bk] window of
    its LOCAL stripe, the cross-stripe merge is an all_gather of scores +
    global ids into the replicated carry (psum's role in the pointwise
    path becomes a top-K merge here)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..runtime.jax_compat import shard_map

    def local(vec_l, bias_l, vscale_l, bscale_l, qvec, base, start, n_valid,
              cv, ci):
        blk = jax.lax.dynamic_slice_in_dim(vec_l, start, bk, axis=0)
        bb = jax.lax.dynamic_slice_in_dim(bias_l, start, bk, axis=0)
        lids = start + jnp.arange(bk, dtype=jnp.int32)
        gids = (jax.lax.axis_index(MODEL_AXIS) * stripe + lids).astype(
            jnp.int32)
        w = blk.astype(jnp.float32)
        b = bb.astype(jnp.float32)
        if block_shift is not None:
            w = w * vscale_l.at[lids >> block_shift].get(
                mode="fill", fill_value=0.0)
            if bias_scaled:
                b = b * bscale_l.at[lids >> block_shift].get(
                    mode="fill", fill_value=0.0)
        scores = base[:, None] + qvec @ w.T + b[None, :]
        scores = jnp.where(gids[None, :] < n_valid, scores, -jnp.inf)
        allv = jax.lax.all_gather(scores, MODEL_AXIS)  # [n, B, bk]
        alli = jax.lax.all_gather(gids, MODEL_AXIS)  # [n, bk]
        allv = jnp.moveaxis(allv, 0, 1).reshape(scores.shape[0], -1)
        alli = alli.reshape(-1)
        vals = jnp.concatenate([cv, allv], axis=1)
        cand = jnp.concatenate(
            [ci, jnp.broadcast_to(alli[None, :], allv.shape)], axis=1)
        tv, pos = jax.lax.top_k(vals, k_pad)
        return tv, jnp.take_along_axis(cand, pos, axis=1)

    m = MODEL_AXIS
    # check_vma=False: the merged carry IS replicated (every device runs
    # the same top_k over the same all_gather result), but the checker
    # types all_gather's output as varying over the model axis and the
    # public API has no invariant-typed gather, so P() out_specs cannot be
    # proven. Parity with the single-device engine is pinned by
    # tests/test_serving_retrieval.py.
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(m), P(m), P(m), P(m), P(), P(), P(), P(),
                             P(), P()),
                   out_specs=(P(), P()), check_vma=False)
    return jax.jit(fn)


def _build_sh_cand_step(mesh, stripe: int, k_pad: int,
                        block_shift: Optional[int], bias_scaled: bool):
    """Sharded candidate scorer: global candidate ids translate into each
    stripe (foreign lanes drop), per-device partial scores psum back up.
    Pad lanes carry mask 0, so their (real row 0) contribution zeroes out
    and the replicated mask pins them to -inf before the top_k."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..core.striping import translate_to_stripe
    from ..runtime.jax_compat import shard_map

    def local(vec_l, bias_l, vscale_l, bscale_l, qvec, base, ids, mask):
        lid, m = translate_to_stripe(ids, mask, MODEL_AXIS, stripe)
        rows = vec_l.at[lid].get(mode="fill",
                                 fill_value=0).astype(jnp.float32)
        b = bias_l.at[lid].get(mode="fill", fill_value=0).astype(jnp.float32)
        if block_shift is not None:
            rows = rows * vscale_l.at[lid >> block_shift].get(
                mode="fill", fill_value=0.0)
            if bias_scaled:
                b = b * bscale_l.at[lid >> block_shift].get(
                    mode="fill", fill_value=0.0)
        part = (jnp.einsum("bf,bcf->bc", qvec, rows) + b) * m
        s = jax.lax.psum(part, MODEL_AXIS)
        scores = jnp.where(mask > 0, base[:, None] + s, -jnp.inf)
        tv, pos = jax.lax.top_k(scores, k_pad)
        return tv, jnp.take_along_axis(ids, pos, axis=1)

    m_ = MODEL_AXIS
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(m_), P(m_), P(m_), P(m_), P(), P(), P(),
                             P()),
                   out_specs=(P(), P()))
    return jax.jit(fn)


def _build_sh_fm_stage(mesh, stripe: int):
    """Sharded FM query staging: models.fm.sharded_gather_predict (the ONE
    feature-sharded gather+predict) already psums (p, sumVfX) — exactly
    the staging pair."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..models.fm import sharded_gather_predict
    from ..runtime.jax_compat import shard_map

    def local(w0, w_l, v_l, idx, val):
        out = sharded_gather_predict(w_l, v_l, w0, idx, val, MODEL_AXIS,
                                     stripe)
        return out[4], out[5]  # p, sum_vfx

    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(), P(MODEL_AXIS), P(MODEL_AXIS), P(), P()),
                   out_specs=(P(), P()))
    return jax.jit(fn)


def _build_sh_q8_fm_stage(mesh, stripe: int, block_shift: int):
    """Sharded int8 FM staging: serving/sharded.py's _build_q8_fm partials
    extended to return sumVfX alongside p."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..core.striping import translate_to_stripe
    from ..runtime.jax_compat import shard_map

    def local(w0, qw_l, ws_l, qv_l, vs_l, idx, val):
        lidx, vmask = translate_to_stripe(idx, val, MODEL_AXIS, stripe)
        sw = ws_l.at[lidx >> block_shift].get(mode="fill", fill_value=0.0)
        wg = qw_l.at[lidx].get(mode="fill",
                               fill_value=0).astype(jnp.float32) * sw
        sv = vs_l.at[lidx >> block_shift].get(mode="fill", fill_value=0.0)
        vg = qv_l.at[lidx].get(mode="fill", fill_value=0).astype(
            jnp.float32) * sv
        vx = vg * vmask[..., None]
        linear, sum_vfx, sum_v2x2 = jax.lax.psum(
            (jnp.sum(wg * vmask, axis=-1),
             jnp.sum(vx, axis=-2),
             jnp.sum(vx * vx, axis=-2)), MODEL_AXIS)
        p = w0 + linear + 0.5 * jnp.sum(sum_vfx * sum_vfx - sum_v2x2,
                                        axis=-1)
        return p, sum_vfx

    m = MODEL_AXIS
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(), P(m), P(m), P(m), P(m), P(), P()),
                   out_specs=(P(), P()))
    return jax.jit(fn)


def _build_sh_mf_stage(mesh, stripe_u: int, block_shift: Optional[int]):
    """Sharded MF query staging: gather P[u] / Bu[u] from the user stripes
    (serving/sharded.py _build_mf gather pattern), psum up the owned
    lanes. Returns (qvec, base=mu+Bu[u])."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..core.striping import translate_to_stripe
    from ..runtime.jax_compat import shard_map

    def local(p_l, bu_l, mu, ps_l, users):
        ones = jnp.ones(users.shape, jnp.float32)
        lid, _ = translate_to_stripe(users, ones, MODEL_AXIS, stripe_u)
        g = p_l.at[lid].get(mode="fill", fill_value=0).astype(jnp.float32)
        if block_shift is not None:
            g = g * ps_l.at[lid >> block_shift].get(
                mode="fill", fill_value=0.0)
        bu = bu_l.at[lid].get(mode="fill", fill_value=0.0)
        g, bu = jax.lax.psum((g, bu), MODEL_AXIS)
        return g, mu + bu

    m = MODEL_AXIS
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(m), P(m), P(), P(m), P()),
                   out_specs=(P(), P()))
    return jax.jit(fn)


# --- LSH index ---------------------------------------------------------------


def build_srp_index(item_vectors, n_planes: int = 8, seed: int = 0,
                    item_lo: int = 0):
    """Signed-random-projection buckets over item vectors (the randomized-
    hashing paper's candidate pruning, PAPERS.md): deterministic in
    ``seed``, built from the f32 vectors (BEFORE any quantization — the
    index approximates angles, not stored bits).

    Returns ``(planes [P,F] f32, item_ids [N] int64 global ids grouped by
    bucket, offsets [2^P+1] int64)`` — the ``index__*`` arrays
    freeze(..., retrieval_index=...) packs into the artifact."""
    vecs = np.asarray(item_vectors, np.float32)
    if vecs.ndim != 2 or vecs.shape[0] == 0:
        raise ValueError(
            f"retrieval index needs a non-empty [N, F] vector table, got "
            f"shape {vecs.shape}")
    n_planes = int(n_planes)
    if not 1 <= n_planes <= 24:
        raise ValueError(f"n_planes must be in [1, 24], got {n_planes}")
    rng = np.random.RandomState(int(seed))
    planes = rng.standard_normal((n_planes, vecs.shape[1])).astype(
        np.float32)
    # MIPS shift trick: hash items CENTERED on the catalog mean. For any
    # query q, <q, x_j> = <q, x_j - c> + <q, c> and the second term is
    # constant over j, so top-K by score == top-K by <q, x_j - c> — and
    # centered directions spread a trained catalog (whose vectors cluster
    # in a halfspace) across the bucket space instead of piling into a
    # few buckets, which is what lets the probe actually prune. The query
    # hashes UNCENTERED (its shift is the same constant), so the center
    # never needs to ship in the artifact.
    bits = ((vecs - vecs.mean(axis=0)) @ planes.T) > 0.0
    codes = (bits.astype(np.int64)
             << np.arange(n_planes, dtype=np.int64)).sum(axis=1)
    order = np.argsort(codes, kind="stable")
    item_ids = (order + int(item_lo)).astype(np.int64)
    counts = np.bincount(codes, minlength=1 << n_planes)
    offsets = np.zeros((1 << n_planes) + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    return planes, item_ids, offsets


class SRPIndex:
    """Query-time view of a frozen SRP index: hash qvec once, union the
    Hamming-<=1 buckets (1 + n_planes probes) into a sorted candidate id
    list per query. Host-side — probing is O(P·F + candidates)."""

    def __init__(self, planes, item_ids, offsets, item_lo: int,
                 item_hi: int, n_planes: int, seed: int) -> None:
        self.planes = np.asarray(planes, np.float32)
        self.item_ids = np.asarray(item_ids, np.int64)
        self.offsets = np.asarray(offsets, np.int64)
        self.item_lo = int(item_lo)
        self.item_hi = int(item_hi)
        self.n_planes = int(n_planes)
        self.seed = int(seed)

    @classmethod
    def from_artifact(cls, artifact: "Artifact") -> Optional["SRPIndex"]:
        info = artifact.meta.get("index")
        if not info:
            return None
        if info.get("scheme") != "srp_lsh":
            raise ValueError(
                f"unknown retrieval index scheme {info.get('scheme')!r} "
                f"(this build reads 'srp_lsh')")
        a = artifact.arrays
        return cls(a["index__planes"], a["index__item_ids"],
                   a["index__offsets"], int(info["item_lo"]),
                   int(info["item_hi"]), int(info["planes"]),
                   int(info["seed"]))

    def probe(self, qvecs: np.ndarray) -> List[np.ndarray]:
        bits = (np.asarray(qvecs, np.float32) @ self.planes.T) > 0.0
        codes = (bits.astype(np.int64)
                 << np.arange(self.n_planes, dtype=np.int64)).sum(axis=1)
        out = []
        for code in codes:
            buckets = [code] + [code ^ (1 << i)
                                for i in range(self.n_planes)]
            parts = [self.item_ids[self.offsets[b]:self.offsets[b + 1]]
                     for b in buckets]
            ids = np.concatenate(parts)
            ids.sort()  # ascending ids = stable tie order in the scorer
            out.append(ids)
        return out

    def describe(self) -> dict:
        return {"scheme": "srp_lsh", "planes": self.n_planes,
                "seed": self.seed,
                "item_range": [self.item_lo, self.item_hi],
                "buckets": 1 << self.n_planes}


# --- catalogs ----------------------------------------------------------------


class _SingleCatalog:
    """The padded item tables on ONE device + the jitted scorers over
    them. ``vec``/``bias`` are zero-padded to a block_items multiple so
    dynamic_slice windows never clamp (a clamped window would desync the
    slice content from the computed ids)."""

    def __init__(self, vec, bias, vscale, bscale, n_items: int,
                 block_items: int, k_pad: int,
                 block_shift: Optional[int], bias_scaled: bool) -> None:
        import jax.numpy as jnp

        self.n_items = int(n_items)
        self.bk = int(block_items)
        self.k_pad = int(k_pad)
        self.n_pad = -(-self.n_items // self.bk) * self.bk
        self.n_steps = self.n_pad // self.bk
        pad = self.n_pad - self.n_items
        vec = np.asarray(vec)
        bias = np.asarray(bias)
        if pad:
            vec = np.concatenate(
                [vec, np.zeros((pad,) + vec.shape[1:], vec.dtype)])
            bias = np.concatenate([bias, np.zeros((pad,), bias.dtype)])
        self.vec = jnp.asarray(vec)  # serving dtype (f32/bf16/int8, G020)
        self.bias = jnp.asarray(bias)
        if block_shift is not None:
            nb_pad = self.n_pad >> block_shift
            vscale = np.asarray(vscale, np.float32)  # [nb] or [nb, F]
            vs = np.zeros((nb_pad,) + vscale.shape[1:], np.float32)
            vs[:len(vscale)] = vscale
            self.vscale = jnp.asarray(vs)
            if bias_scaled:
                bscale = np.asarray(bscale, np.float32)
                bs = np.zeros((nb_pad,) + bscale.shape[1:], np.float32)
                bs[:len(bscale)] = bscale
                self.bscale = jnp.asarray(bs)
            else:
                self.bscale = self.vscale
        else:
            # inert stand-ins: traced but never read (block_shift is None
            # inside the kernels), keeps every kernel one signature
            self.vscale = self.bscale = self.bias
        self._step = _retrieval_jit(
            ("block", self.bk, self.k_pad, block_shift, bias_scaled),
            lambda: _build_block_step(self.bk, self.k_pad, block_shift,
                                      bias_scaled))
        self._scores = _retrieval_jit(
            ("scores", self.bk, block_shift, bias_scaled),
            lambda: _build_block_scores(self.bk, block_shift, bias_scaled))
        self._cand = _retrieval_jit(
            ("cand", self.k_pad, block_shift, bias_scaled),
            lambda: _build_cand_step(self.k_pad, block_shift, bias_scaled))
        # _scores is the bench baseline, deliberately NOT in jit_fns: it
        # is not a serving path and must not count against the zero-
        # steady-state-recompiles pin
        self.jit_fns = (self._step, self._cand)

    def run_blocks(self, qvec: np.ndarray, base: np.ndarray):
        import jax.numpy as jnp

        b = qvec.shape[0]
        cv = jnp.full((b, self.k_pad), -np.inf, jnp.float32)
        ci = jnp.full((b, self.k_pad), self.n_pad, jnp.int32)
        q = jnp.asarray(qvec)
        bs = jnp.asarray(base)
        nv = np.int32(self.n_items)
        for s in range(self.n_steps):
            cv, ci = self._step(self.vec, self.bias, self.vscale,
                                self.bscale, q, bs, np.int32(s * self.bk),
                                nv, cv, ci)
        return cv, ci

    def run_cand(self, qvec, base, ids, mask):
        import jax.numpy as jnp

        return self._cand(self.vec, self.bias, self.vscale, self.bscale,
                          jnp.asarray(qvec), jnp.asarray(base),
                          jnp.asarray(ids), jnp.asarray(mask))

    def block_scores(self, qvec: np.ndarray, base: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        q = jnp.asarray(qvec)
        bs = jnp.asarray(base)
        nv = np.int32(self.n_items)
        outs = [np.asarray(self._scores(self.vec, self.bias, self.vscale,
                                        self.bscale, q, bs,
                                        np.int32(s * self.bk), nv))
                for s in range(self.n_steps)]
        return np.concatenate(outs, axis=1)[:, :self.n_items]

    @property
    def table_bytes(self) -> int:
        n = self.vec.nbytes + self.bias.nbytes
        if self.vscale is not self.bias:
            n += self.vscale.nbytes
            if self.bscale is not self.vscale:
                n += self.bscale.nbytes
        return int(n)


class _ShardedCatalog:
    """Item tables striped over the serving mesh's model axis (the PR 9
    grid arithmetic: stripe aligned to block_items so no merge window
    straddles a stripe boundary, and to the int8 scale blocks)."""

    def __init__(self, vec, bias, vscale, bscale, n_items: int,
                 block_items: int, k_pad: int,
                 block_shift: Optional[int], bias_scaled: bool, mesh,
                 n_shards: int) -> None:
        from .sharded import _mesh_key, _stripe_put

        self.n_items = int(n_items)
        self.bk = int(block_items)
        self.k_pad = int(k_pad)
        stripe, padded = stripe_grid(self.n_items, n_shards,
                                     align=self.bk)
        self.stripe = stripe
        self.n_pad = padded
        self.n_steps = stripe // self.bk
        self.vec = _stripe_put(np.asarray(vec), 0, self.n_items, padded,
                               mesh)
        self.bias = _stripe_put(np.asarray(bias), 0, self.n_items, padded,
                                mesh)
        if block_shift is not None:
            vs = np.asarray(vscale, np.float32)
            self.vscale = _stripe_put(vs, 0, len(vs),
                                      padded >> block_shift, mesh)
            if bias_scaled:
                bs = np.asarray(bscale, np.float32)
                self.bscale = _stripe_put(bs, 0, len(bs),
                                          padded >> block_shift, mesh)
            else:
                self.bscale = self.vscale
        else:
            self.vscale = self.bscale = self.bias  # inert striped stand-in
        mk = _mesh_key(mesh)
        self._step = _retrieval_jit(
            ("sh_block", mk, stripe, self.bk, self.k_pad, block_shift,
             bias_scaled),
            lambda: _build_sh_block_step(mesh, stripe, self.bk, self.k_pad,
                                         block_shift, bias_scaled))
        self._cand = _retrieval_jit(
            ("sh_cand", mk, stripe, self.k_pad, block_shift, bias_scaled),
            lambda: _build_sh_cand_step(mesh, stripe, self.k_pad,
                                        block_shift, bias_scaled))
        self.jit_fns = (self._step, self._cand)

    def run_blocks(self, qvec: np.ndarray, base: np.ndarray):
        import jax.numpy as jnp

        b = qvec.shape[0]
        cv = jnp.full((b, self.k_pad), -np.inf, jnp.float32)
        ci = jnp.full((b, self.k_pad), self.n_pad, jnp.int32)
        q = jnp.asarray(qvec)
        bs = jnp.asarray(base)
        nv = np.int32(self.n_items)
        for s in range(self.n_steps):
            cv, ci = self._step(self.vec, self.bias, self.vscale,
                                self.bscale, q, bs, np.int32(s * self.bk),
                                nv, cv, ci)
        return cv, ci

    def run_cand(self, qvec, base, ids, mask):
        import jax.numpy as jnp

        return self._cand(self.vec, self.bias, self.vscale, self.bscale,
                          jnp.asarray(qvec), jnp.asarray(base),
                          jnp.asarray(ids),
                          jnp.asarray(mask, jnp.float32))

    def block_scores(self, qvec, base):
        raise NotImplementedError(
            "the materializing parity baseline runs on the single-device "
            "engine; the sharded gate is score parity against it "
            "(docs/serving.md 'Top-K retrieval')")

    @property
    def table_bytes(self) -> int:
        n = self.vec.nbytes + self.bias.nbytes
        if self.vscale is not self.bias:
            n += self.vscale.nbytes
            if self.bscale is not self.vscale:
                n += self.bscale.nbytes
        return int(n)


# --- query stagers -----------------------------------------------------------


class _MFStager:
    """MF user staging is a host gather: qvec = P[u] (scale-folded for
    int8), base = mu + Bu[u]. No device work, so no jit_fns."""

    has_width = False
    jit_fns: tuple = ()

    def __init__(self, p_table, bu, mu, p_scales,
                 block_shift: Optional[int], num_users: int) -> None:
        self.p_table = p_table
        self.bu = np.asarray(bu, np.float32)
        self.mu = float(np.asarray(mu))
        self.p_scales = None if p_scales is None \
            else np.asarray(p_scales, np.float32)
        self.block_shift = block_shift
        self.num_users = int(num_users)

    def width_buckets(self) -> list:
        return [None]

    def dummy(self, width=None):
        return 0

    def _uids(self, queries) -> np.ndarray:
        uids = np.empty(len(queries), np.int64)
        for i, q in enumerate(queries):
            if isinstance(q, dict):
                q = q["user"]
            elif isinstance(q, (list, tuple, np.ndarray)):
                q = q[0]
            u = int(q)
            if not 0 <= u < self.num_users:
                raise ValueError(
                    f"user id {u} out of range [0, {self.num_users})")
            uids[i] = u
        return uids

    def stage(self, queries: Sequence, b_pad: int):
        u = self._uids(queries)
        g = np.asarray(self.p_table[u], np.float32)
        if self.p_scales is not None:
            g = g * self.p_scales[u >> self.block_shift]
        base = self.mu + self.bu[u]
        n = len(u)
        if b_pad > n:
            g = np.concatenate(
                [g, np.zeros((b_pad - n, g.shape[1]), np.float32)])
            base = np.concatenate([base, np.zeros(b_pad - n, np.float32)])
        return np.ascontiguousarray(g, np.float32), \
            np.ascontiguousarray(base, np.float32)


class _ShardedMFStager:
    """MF user staging against user-striped P/Bu (the predict path's
    gather pattern). Out-of-range users land in no stripe and stage to
    (0, mu) instead of raising — the sharded trade documented on the
    /predict path too."""

    has_width = False

    def __init__(self, p_l, bu_l, mu_rep, ps_l, num_users: int, fn) -> None:
        self.tables = (p_l, bu_l, mu_rep, ps_l)
        self.num_users = int(num_users)
        self.fn = fn
        self.jit_fns = (fn,)

    def width_buckets(self) -> list:
        return [None]

    def dummy(self, width=None):
        return 0

    def stage(self, queries: Sequence, b_pad: int):
        u = np.zeros(b_pad, np.int64)
        for i, q in enumerate(queries):
            if isinstance(q, dict):
                q = q["user"]
            elif isinstance(q, (list, tuple, np.ndarray)):
                q = q[0]
            u[i] = int(q)
        g, base = self.fn(*self.tables, u)
        return np.asarray(g, np.float32), np.asarray(base, np.float32)


class _FMStager:
    """FM query staging: parse/pad sparse rows to a width bucket, run the
    jitted (p, sumVfX) stage. One class covers single-device, sharded and
    q8 variants — they differ only in (tables, fn)."""

    has_width = True

    def __init__(self, tables: tuple, fn, dims: int, max_width: int) -> None:
        self.tables = tables
        self.fn = fn
        self.dims = int(dims)
        self.max_width = int(max_width)
        self.jit_fns = (fn,)

    def width_buckets(self) -> list:
        out, w = [], 8
        while w < self.max_width:
            out.append(w)
            w <<= 1
        out.append(self.max_width)
        return out

    def dummy(self, width: Optional[int] = None):
        w = min(width or 8, self.max_width)
        return [(i % self.dims, 1.0) for i in range(w)]

    def stage(self, queries: Sequence, b_pad: int):
        from ..models.base import _stage_rows

        idx_rows, val_rows = _stage_rows(list(queries), self.dims)
        width = max((len(r) for r in idx_rows), default=1)
        w_pad = min(max(8, _pow2_at_least(width)), self.max_width)
        idx = np.full((b_pad, w_pad), self.dims, np.int64)
        val = np.zeros((b_pad, w_pad), np.float32)
        for i, (ir, vr) in enumerate(zip(idx_rows, val_rows)):
            t = min(len(ir), w_pad)  # over-wide rows truncate (engine rule)
            idx[i, :t] = ir[:t]
            val[i, :t] = vr[:t]
        base, qvec = self.fn(*self.tables, idx, val)
        return np.asarray(qvec, np.float32), np.asarray(base, np.float32)


# --- the engine --------------------------------------------------------------


class RetrievalEngine:
    """Blocked streamed top-K over an MF/FM catalog (module docstring).

    ``source`` is an :class:`Artifact`, an artifact path, or a trained
    model (an LSH index rides only in artifacts). Queries are user ids
    (MF) or sparse feature rows (FM); results are
    ``{"items": [...], "scores": [...]}`` per query, item ids in the
    catalog's id space (MF item index / FM feature index).

    ``k`` is the engine ceiling: per-request k clamps to it (and pads to
    ``k_pad``, the pow2 the merge carry is compiled at). ``probe``
    requests candidate pruning; without an index — or when the bucket
    union is < k or > ``candidate_cap`` — the request falls back to
    exact scoring (counter ``retrieval.<name>.fallback``)."""

    def __init__(self, source, *, name: str = "default", k: int = 16,
                 block_items: int = 4096, max_batch: int = 8,
                 max_width: int = 64, candidate_cap: int = 1024,
                 probe_default: bool = False,
                 item_range: Optional[Tuple[int, int]] = None,
                 placement=None) -> None:
        from ..io.checkpoint import QUANT_SCHEME_INT8

        if isinstance(source, str):
            source = load(source)
        family = source.family if isinstance(source, Artifact) \
            else family_of(source)
        if family not in RETRIEVAL_FAMILIES:
            raise ValueError(
                f"family {family!r} has no retrieval path — top-K serves "
                f"the embedding families ({', '.join(RETRIEVAL_FAMILIES)})")
        self.name = name
        self.family = family
        spec = host_score_tables(source)
        meta = spec["meta"]
        quant = spec["quant"]
        is_int8 = bool(quant) and quant["scheme"] == QUANT_SCHEME_INT8
        block_rows = int(quant["block_rows"]) if is_int8 else 1
        block_shift = block_rows.bit_length() - 1 if is_int8 else None
        self.weights_dtype = spec["weights_dtype"]

        self.index = SRPIndex.from_artifact(source) \
            if isinstance(source, Artifact) else None
        full = (0, int(meta["num_items"])) if family == "mf" \
            else (0, int(meta["dims"]))
        if self.index is not None:
            lo, hi = self.index.item_lo, self.index.item_hi
            if item_range is not None and tuple(item_range) != (lo, hi):
                raise ValueError(
                    f"item_range {tuple(item_range)} does not match the "
                    f"artifact index's ({lo}, {hi})")
        elif item_range is not None:
            lo, hi = int(item_range[0]), int(item_range[1])
        else:
            lo, hi = full
        if not (full[0] <= lo < hi <= full[1]):
            raise ValueError(
                f"item_range ({lo}, {hi}) outside the catalog's {full}")
        self.item_lo, self.item_hi = lo, hi
        self.n_items = hi - lo

        block_items = int(block_items)
        if block_items < 1:
            raise ValueError(f"block_items must be >= 1, got {block_items}")
        if is_int8 and (block_items % block_rows or lo % block_rows):
            raise ValueError(
                f"int8 catalogs need block_items ({block_items}) and "
                f"item_lo ({lo}) aligned to the quant block_rows "
                f"({block_rows}) so scale blocks never straddle a window")
        self.block_items = block_items
        self.k = int(k)
        if not 1 <= self.k <= self.n_items:
            raise ValueError(
                f"k={k} out of range [1, {self.n_items}] for this catalog")
        self.k_pad = _pow2_at_least(self.k)
        self.max_batch = _pow2_at_least(int(max_batch))
        self.max_width = max(8, _pow2_at_least(int(max_width)))
        self.cand_min = max(16, self.k_pad)
        self.candidate_cap = max(_pow2_at_least(int(candidate_cap)),
                                 self.cand_min)
        self.probe_default = bool(probe_default)

        striped = {nm: arr for nm, arr, _axis, _grid in spec["striped"]}
        scales = spec["scales"]
        if family == "mf":
            use_bias = bool(meta.get("use_bias", True))
            bi = striped["Bi"] if use_bias \
                else np.zeros_like(striped["Bi"])
            vec_host = striped["Q"][lo:hi]
            bias_host = bi[lo:hi]
            vscale = scales.get("Q")
            bscale = None
            bias_scaled = False
        else:
            vec_host = striped["v"][lo:hi]
            bias_host = striped["w"][lo:hi]
            vscale = scales.get("v")
            bscale = scales.get("w")
            bias_scaled = is_int8
        if block_shift is not None:
            blo, bhi = lo >> block_shift, ((hi - 1) >> block_shift) + 1
            vscale = np.asarray(vscale, np.float32)[blo:bhi]
            if bias_scaled:
                bscale = np.asarray(bscale, np.float32)[blo:bhi]

        placement = resolve_placement(placement)
        self.sharded = isinstance(placement, ModelSharded)
        self.placement_info = placement.describe() \
            if hasattr(placement, "describe") else {"kind": placement.kind}
        if self.sharded:
            mesh = placement.mesh()
            n_sh = int(placement.model_shards)
            self.mesh_shape = tuple(int(s) for s in
                                    (placement.batch_shards, n_sh))
            self._catalog = _ShardedCatalog(
                vec_host, bias_host, vscale, bscale, self.n_items,
                self.block_items, self.k_pad, block_shift, bias_scaled,
                mesh, n_sh)
            self._stager = self._make_sharded_stager(
                spec, striped, scales, meta, mesh, n_sh, block_shift)
        else:
            self.mesh_shape = ()
            self._catalog = _SingleCatalog(
                vec_host, bias_host, vscale, bscale, self.n_items,
                self.block_items, self.k_pad, block_shift, bias_scaled)
            self._stager = self._make_single_stager(
                spec, striped, scales, meta, block_shift)
        self.jit_fns = tuple(self._catalog.jit_fns) \
            + tuple(self._stager.jit_fns)

        self._queries_ctr = REGISTRY.counter("retrieval",
                                             f"{name}.queries")
        self._exact_ctr = REGISTRY.counter("retrieval", f"{name}.exact")
        self._probed_ctr = REGISTRY.counter("retrieval", f"{name}.probed")
        self._fallback_ctr = REGISTRY.counter("retrieval",
                                              f"{name}.fallback")
        self._cand_ctr = REGISTRY.counter("retrieval",
                                          f"{name}.candidates")
        self._latency = REGISTRY.histogram(
            f"retrieval.{name}.topk_seconds", LATENCY_BUCKETS)
        REGISTRY.set_gauge(f"retrieval.{name}.catalog_items",
                           float(self.n_items))
        REGISTRY.set_gauge(f"retrieval.{name}.table_bytes",
                           float(self.table_bytes()))

    # -- construction helpers ------------------------------------------------

    def _make_single_stager(self, spec, striped, scales, meta,
                            block_shift):
        import jax.numpy as jnp

        if self.family == "mf":
            use_bias = bool(meta.get("use_bias", True))
            bu = striped["Bu"] if use_bias \
                else np.zeros_like(striped["Bu"])
            return _MFStager(striped["P"], bu, spec["replicated"]["mu"],
                             scales.get("P"), block_shift,
                             int(meta["num_users"]))
        dims = int(meta["dims"])
        w0 = jnp.asarray(spec["replicated"]["w0"], jnp.float32)
        if block_shift is not None:
            tables = (w0, jnp.asarray(striped["w"]),
                      jnp.asarray(scales["w"], jnp.float32),
                      jnp.asarray(striped["v"]),
                      jnp.asarray(scales["v"], jnp.float32))
            fn = _retrieval_jit(("q8_fm_stage", block_shift),
                                lambda: _build_q8_fm_stage(block_shift))
        else:
            tables = (w0, jnp.asarray(striped["w"]),
                      jnp.asarray(striped["v"]))
            fn = _retrieval_jit(("fm_stage",), _build_fm_stage)
        return _FMStager(tables, fn, dims, self.max_width)

    def _make_sharded_stager(self, spec, striped, scales, meta, mesh,
                             n_sh, block_shift):
        from .sharded import _mesh_key, _replicate_put, _stripe_put

        mk = _mesh_key(mesh)
        block_rows = 1 if block_shift is None else 1 << block_shift
        if self.family == "mf":
            use_bias = bool(meta.get("use_bias", True))
            num_users = int(meta["num_users"])
            stripe_u, padded_u = stripe_grid(num_users, n_sh,
                                             align=block_rows)
            p_l = _stripe_put(striped["P"], 0, num_users, padded_u, mesh)
            bu = striped["Bu"] if use_bias \
                else np.zeros_like(striped["Bu"])
            bu_l = _stripe_put(bu, 0, num_users, padded_u, mesh)
            mu_rep = _replicate_put(spec["replicated"]["mu"], mesh)
            if block_shift is not None:
                ps = np.asarray(scales["P"], np.float32)
                ps_l = _stripe_put(ps, 0, len(ps),
                                   padded_u >> block_shift, mesh)
            else:
                ps_l = bu_l  # inert striped stand-in, never read
            fn = _retrieval_jit(
                ("sh_mf_stage", mk, stripe_u, block_shift),
                lambda: _build_sh_mf_stage(mesh, stripe_u, block_shift))
            return _ShardedMFStager(p_l, bu_l, mu_rep, ps_l, num_users, fn)
        dims = int(meta["dims"])
        stripe_f, padded_f = stripe_grid(dims, n_sh, align=block_rows)
        w0 = _replicate_put(np.asarray(spec["replicated"]["w0"],
                                       np.float32), mesh)
        w_l = _stripe_put(striped["w"], 0, dims, padded_f, mesh)
        v_l = _stripe_put(striped["v"], 0, dims, padded_f, mesh)
        if block_shift is not None:
            ws = np.asarray(scales["w"], np.float32)
            vs = np.asarray(scales["v"], np.float32)
            ws_l = _stripe_put(ws, 0, len(ws), padded_f >> block_shift,
                               mesh)
            vs_l = _stripe_put(vs, 0, len(vs), padded_f >> block_shift,
                               mesh)
            tables = (w0, w_l, ws_l, v_l, vs_l)
            fn = _retrieval_jit(
                ("sh_q8_fm_stage", mk, stripe_f, block_shift),
                lambda: _build_sh_q8_fm_stage(mesh, stripe_f, block_shift))
        else:
            tables = (w0, w_l, v_l)
            fn = _retrieval_jit(
                ("sh_fm_stage", mk, stripe_f),
                lambda: _build_sh_fm_stage(mesh, stripe_f))
        return _FMStager(tables, fn, dims, self.max_width)

    # -- buckets -------------------------------------------------------------

    def batch_buckets(self) -> list:
        out, b = [], 1
        while b < self.max_batch:
            out.append(b)
            b <<= 1
        out.append(self.max_batch)
        return out

    def _bucket(self, n: int) -> int:
        return min(_pow2_at_least(n), self.max_batch)

    def cand_buckets(self) -> list:
        out, c = [], self.cand_min
        while c < self.candidate_cap:
            out.append(c)
            c <<= 1
        out.append(self.candidate_cap)
        return out

    def _cand_bucket(self, m: int) -> int:
        return min(max(_pow2_at_least(m), self.cand_min),
                   self.candidate_cap)

    # -- serving -------------------------------------------------------------

    def warmup(self) -> int:
        """Precompile every (batch, width) x (block merge, candidate)
        bucket; all jit misses are paid here, none in steady state.
        Idempotent across engines sharing _RETRIEVAL_JIT geometry."""
        t0 = time.perf_counter()
        with TRACER.span("retrieval.warmup",
                         args={"engine": self.name,
                               "family": self.family}), \
                recompile_guard(f"serving.{self.name}.topk.warmup",
                                *self.jit_fns) as g:
            for b in self.batch_buckets():
                qvec = base = None
                for w in self._stager.width_buckets():
                    qvec, base = self._stager.stage(
                        [self._stager.dummy(w)] * b, b)
                cv, _ci = self._catalog.run_blocks(qvec, base)
                np.asarray(cv)  # block: compiles surface here
                if self.index is not None:
                    for c in self.cand_buckets():
                        ids = np.zeros((b, c), np.int32)
                        mask = np.zeros((b, c), bool)
                        tv, _ti = self._catalog.run_cand(qvec, base, ids,
                                                         mask)
                        np.asarray(tv)
        REGISTRY.set_gauge(f"retrieval.{self.name}.warmup_seconds",
                           time.perf_counter() - t0)
        REGISTRY.set_gauge(f"retrieval.{self.name}.warmup_compiles",
                           float(g.compiles))
        return g.compiles

    def topk(self, queries: Sequence, k: Optional[int] = None,
             probe: Optional[bool] = None) -> List[dict]:
        """Top-K for a list of queries (one shared k/probe)."""
        return self.topk_batch([(q, k, probe) for q in queries])

    def topk_batch(self, rows: Sequence[tuple]) -> List[dict]:
        """Batcher entry point: rows of ``(query, k|None, probe|None)``.
        Chunks above max_batch; per-row k clamps to the engine k."""
        n = len(rows)
        if n == 0:
            return []
        t0 = time.perf_counter()
        outs: List[dict] = []
        with TRACER.span("retrieval.topk",
                         args={"engine": self.name, "rows": n}) as rspan:
            for s in range(0, n, self.max_batch):
                outs.extend(self._topk_chunk(rows[s:s + self.max_batch]))
            self._queries_ctr.increment(n)
            self._latency.observe(time.perf_counter() - t0,
                                  trace_id=TRACER.exemplar_id(rspan))
        return outs

    def _topk_chunk(self, rows: Sequence[tuple]) -> List[dict]:
        n = len(rows)
        queries = [r[0] for r in rows]
        ks = []
        for _q, rk, _p in rows:
            kk = self.k if rk is None else int(rk)
            if kk < 1:
                raise ValueError(f"k must be >= 1, got {kk}")
            ks.append(min(kk, self.k))
        probes = [self.probe_default if rp is None else bool(rp)
                  for _q, _k, rp in rows]
        b_pad = self._bucket(n)
        with recompile_guard(f"serving.{self.name}.topk", *self.jit_fns):
            with TRACER.span("topk.gather",
                             args={"rows": n, "b_pad": b_pad}):
                qvec, base = self._stager.stage(queries, b_pad)
            exact_idx = []
            cand: dict = {}
            for i in range(n):
                if probes[i] and self.index is None:
                    self._fallback_ctr.increment()  # probe without index
                if probes[i] and self.index is not None:
                    cand[i] = None  # resolved below
                else:
                    exact_idx.append(i)
            if cand:
                probed = self.index.probe(qvec[sorted(cand)])
                for i, c in zip(sorted(cand), probed):
                    if len(c) < ks[i] or len(c) > self.candidate_cap:
                        del cand[i]
                        exact_idx.append(i)
                        self._fallback_ctr.increment()
                    else:
                        cand[i] = c
                exact_idx.sort()
            pidx = sorted(cand)
            results: List[Optional[dict]] = [None] * n
            cv = ci = pv = pi = None
            with TRACER.span("topk.block_score",
                             args={"exact": len(exact_idx),
                                   "probed": len(pidx)}):
                if exact_idx:
                    bb = self._bucket(len(exact_idx))
                    qe = np.zeros((bb, qvec.shape[1]), np.float32)
                    qe[:len(exact_idx)] = qvec[exact_idx]
                    be = np.zeros((bb,), np.float32)
                    be[:len(exact_idx)] = base[exact_idx]
                    cv, ci = self._catalog.run_blocks(qe, be)
                    self._exact_ctr.increment(len(exact_idx))
                if pidx:
                    cmax = max(len(cand[i]) for i in pidx)
                    c_pad = self._cand_bucket(cmax)
                    bb = self._bucket(len(pidx))
                    ids = np.zeros((bb, c_pad), np.int32)
                    mask = np.zeros((bb, c_pad), bool)
                    total = 0
                    for r, i in enumerate(pidx):
                        c = cand[i] - self.item_lo  # catalog-row space
                        ids[r, :len(c)] = c
                        mask[r, :len(c)] = True
                        total += len(c)
                    qp = np.zeros((bb, qvec.shape[1]), np.float32)
                    qp[:len(pidx)] = qvec[pidx]
                    bp = np.zeros((bb,), np.float32)
                    bp[:len(pidx)] = base[pidx]
                    pv, pi = self._catalog.run_cand(qp, bp, ids, mask)
                    self._probed_ctr.increment(len(pidx))
                    self._cand_ctr.increment(total)
            with TRACER.span("topk.merge"):
                if exact_idx:
                    cvh, cih = np.asarray(cv), np.asarray(ci)
                    for r, i in enumerate(exact_idx):
                        results[i] = self._row_result(cvh[r], cih[r], ks[i])
                if pidx:
                    pvh, pih = np.asarray(pv), np.asarray(pi)
                    for r, i in enumerate(pidx):
                        results[i] = self._row_result(pvh[r], pih[r], ks[i])
        return results  # type: ignore[return-value]

    def _row_result(self, vals: np.ndarray, ids: np.ndarray,
                    k: int) -> dict:
        return {
            "items": (ids[:k].astype(np.int64) + self.item_lo).tolist(),
            # f32 carry values; .tolist() alone widens to Python floats
            "scores": vals[:k].tolist(),
        }

    def score_catalog(self, queries: Sequence) -> np.ndarray:
        """Materialized exact scores [n, n_items] — the naive-argsort
        baseline's input (bench parity pin). Shares the block score
        expression bit-for-bit with the streamed merge. Not a serving
        path; single-device engines only."""
        outs = []
        for s in range(0, len(queries), self.max_batch):
            chunk = queries[s:s + self.max_batch]
            qvec, base = self._stager.stage(chunk, self._bucket(len(chunk)))
            outs.append(self._catalog.block_scores(qvec, base)[:len(chunk)])
        return np.concatenate(outs, axis=0)

    # -- introspection -------------------------------------------------------

    def table_bytes(self) -> int:
        n = self._catalog.table_bytes
        for t in getattr(self._stager, "tables", ()):
            n += int(getattr(t, "nbytes", 0))
        return n

    def describe(self) -> dict:
        return {
            "family": self.family,
            "weights_dtype": self.weights_dtype,
            "k": self.k,
            "catalog_items": self.n_items,
            "item_range": [self.item_lo, self.item_hi],
            "block_items": self.block_items,
            "max_batch": self.max_batch,
            "candidate_cap": self.candidate_cap,
            "probe_default": self.probe_default,
            "placement": self.placement_info,
            "index": None if self.index is None else self.index.describe(),
            "table_bytes": self.table_bytes(),
        }
