"""North-star end-to-end benchmark: KDD2012-Track2-shaped CTR training to a
held-out logloss target, for train_arow AND train_fm, scored with the
scoreKDD protocol (AUC / NWMAE / WRMSE).

BASELINE.json's north star: beat the Hive-on-YARN + MixServer path on
KDD2012 Track 2 CTR at equal logloss. The actual KDD dataset cannot be
downloaded in this image (zero egress), so this generates a seeded
KDD-shaped stand-in ON DEVICE (no host->device transfer of the dataset):

- 2^22 hashed feature dims (the reference's default dense-model space is
  2^24, LearnerBaseUDTF.java:90; KDD Track 2's active dimensionality after
  hashing fits 2^22), 32 nnz/row categorical features with a log-uniform
  (heavy-tailed) id distribution like hashed CTR traffic;
- ground-truth logistic CTR model w* ~ N(0, 1.5/sqrt(32)), bias -2.0
  (mean CTR ~12%), clicks ~ Bernoulli(sigmoid(w*.x + b));
- train on `--train-rows` impressions, evaluate held-out logloss on
  `--test-rows` impressions, score AUC/NWMAE/WRMSE per the reference's
  scorer semantics (ref: resources/examples/kddtrack2/scoreKDD.py:1-40;
  vectorized in examples/score_ctr.py).

Equal-logloss protocol: the engine's minibatch path is the reference's own
documented mini-batch semantic (RegressionBaseUDTF.java:236-295) with
minibatch(1) == scan invariant-tested (tests/test_engine_invariants.py);
the achieved held-out logloss is reported next to the Bayes floor (binary
entropy of the true CTR, computable because the generator is known). The
reference wall-clock comparison is the documented JVM per-row hot-loop
anchor of 2.5e5 rows/s (BASELINE.md: the repo publishes no numbers; this is
the measured order of magnitude of a single Hive mapper on this update
family) extrapolated to the same number of row-updates. vs_baseline =
anchor_wall_clock / our_wall_clock.

Prints one JSON line per workload plus a combined summary line. Rerunnable:
    python scripts/bench_ctr_e2e.py [--train-rows N] [--epochs-fm N] ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ANCHOR_ROWS_PER_SEC = 250_000.0  # BASELINE.md JVM mapper anchor
DIMS = 1 << 22
WIDTH = 32
BATCH = 16384
BIAS = -2.0
SIGMA_W = 1.5 / np.sqrt(WIDTH)


def gen_blocks(key, n_blocks, dims, batch, width, w_true, perm=None):
    """Generate stacked CTR blocks on device: ids log-uniform over [1, dims)
    then spread hash-uniformly by `perm` (murmur-hashed features keep their
    frequency but land uniformly over the table — raw log-uniform ids would
    cluster the hot head in the first cache lines, a contiguity gift no real
    hashed data gives the host anchor; pure relabeling, the learning problem
    is identical), values 1.0 (categorical), clicks
    Bernoulli(sigmoid(w*.x + bias)).

    Returns device arrays shaped [n_blocks, batch, ...] so the epoch loop can
    be ONE jitted `lax.scan` (the framework's deployment shape — io/records.py
    prefetch + on-device epoch replay; the reference likewise replays epochs
    from its NIO buffer, FactorizationMachineUDTF.java:521)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def all_blocks(k):
        def one(_, kb):
            k1, k2 = jax.random.split(kb)
            u = jax.random.uniform(k1, (batch, width))
            idx = (jnp.exp(u * jnp.log(float(dims))).astype(jnp.int32)) % dims
            if perm is not None:
                idx = perm[idx]
            score = BIAS + jnp.sum(w_true[idx], axis=1)
            p = jax.nn.sigmoid(score)
            click = jax.random.bernoulli(k2, p).astype(jnp.float32)
            return None, (idx, click * 2.0 - 1.0, p)

        keys = jax.random.split(k, n_blocks)
        _, (idx, lab, p) = jax.lax.scan(one, None, keys)
        return idx, lab, p

    idx, lab, p = all_blocks(key)
    jax.block_until_ready(idx)
    return idx, lab, p


def eval_logloss(scores, labels01):
    import jax.numpy as jnp
    import jax

    p = jax.nn.sigmoid(scores)
    eps = 1e-7
    p = jnp.clip(p, eps, 1 - eps)
    return -jnp.mean(labels01 * jnp.log(p) + (1 - labels01) * jnp.log1p(-p)), p


def eval_held_out(score_fn, test_blocks):
    """Held-out logloss + flat (p_hat, y01) arrays over stacked test blocks;
    `score_fn(idx_block) -> scores [B]`."""
    import jax.numpy as jnp

    te_idx, te_lab, _ = test_blocks
    lls, ps, labs = [], [], []
    for b in range(te_idx.shape[0]):
        score = score_fn(te_idx[b])
        y01 = (te_lab[b] + 1.0) * 0.5
        ll, p = eval_logloss(score, y01)
        lls.append(ll)
        ps.append(p)
        labs.append(y01)
    logloss = float(jnp.mean(jnp.stack(lls)))
    return logloss, np.concatenate([np.asarray(x) for x in ps]), \
        np.concatenate([np.asarray(x) for x in labs])


def run_arow(train_blocks, test_blocks, epochs, values):
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_epoch, make_predict, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW

    fn = make_train_fn(AROW, {"r": 0.1}, mode="minibatch")
    predict = make_predict(use_covariance=True)
    tr_idx, tr_lab, _ = train_blocks
    epoch = make_epoch(lambda s, bidx, blab: fn(s, bidx, values, blab))

    # AOT-compile the epoch without executing it (donated args); the timing
    # loop calls the compiled executable directly
    warm = init_linear_state(DIMS, use_covariance=True)
    epoch_c = epoch.lower(warm, tr_idx, tr_lab).compile()
    del warm

    state = init_linear_state(DIMS, use_covariance=True)
    t0 = time.perf_counter()
    for _ in range(epochs):
        state, losses = epoch_c(state, tr_idx, tr_lab)
    # value fetch of the carried step counter: a sync that cannot return
    # before the work is done, and a check that no step was dropped
    # (runtime/benchmark.py). Explicit raise, not assert: -O must never
    # strip the sync.
    got = float(state.step)
    if got != epochs * tr_idx.shape[0] * BATCH:
        raise RuntimeError(f"step counter {got} != expected")
    train_s = time.perf_counter() - t0

    logloss, p_hat, y01 = eval_held_out(
        lambda bidx: predict(state, bidx, values)[0], test_blocks)
    return train_s, logloss, p_hat, y01


def run_fm(train_blocks, test_blocks, epochs, values):
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_epoch
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step

    hyper = FMHyper(factors=5, classification=True)
    fm_fn = make_fm_step(hyper, mode="minibatch", jit=False)
    va = jnp.zeros((BATCH,), jnp.float32)
    tr_idx, tr_lab, _ = train_blocks
    epoch = make_epoch(lambda s, bidx, blab: fm_fn(s, bidx, values, blab, va))

    warm = init_fm_state(DIMS, hyper)
    epoch_c = epoch.lower(warm, tr_idx, tr_lab).compile()
    del warm

    state = init_fm_state(DIMS, hyper)
    t0 = time.perf_counter()
    for _ in range(epochs):
        state, losses = epoch_c(state, tr_idx, tr_lab)
    # value fetch (un-fakeable sync; see runtime/benchmark.py); explicit
    # raise, not assert: -O must never strip the sync
    got = float(state.step)
    if got != epochs * tr_idx.shape[0] * BATCH:
        raise RuntimeError(f"step counter {got} != expected")
    train_s = time.perf_counter() - t0

    @jax.jit
    def fm_scores(st, idx, val):
        wg = st.w.at[idx].get(mode="fill", fill_value=0.0)
        vg = st.v.at[idx].get(mode="fill", fill_value=0.0)
        linear = st.w0 + jnp.sum(wg * val, axis=1)
        sum_vfx = jnp.einsum("bkf,bk->bf", vg, val)
        sum_v2x2 = jnp.einsum("bkf,bk->bf", vg * vg, val * val)
        return linear + 0.5 * jnp.sum(sum_vfx ** 2 - sum_v2x2, axis=1)

    logloss, p_hat, y01 = eval_held_out(
        lambda bidx: fm_scores(state, bidx, values), test_blocks)
    return train_s, logloss, p_hat, y01


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--train-rows", type=int, default=1 << 21)
    ap.add_argument("--test-rows", type=int, default=1 << 18)
    ap.add_argument("--epochs-arow", type=int, default=2)
    ap.add_argument("--epochs-fm", type=int, default=3)
    ap.add_argument("--seed", type=int, default=42)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    platform = jax.devices()[0].platform
    n_train_blocks = max(1, args.train_rows // BATCH)
    n_test_blocks = max(1, args.test_rows // BATCH)

    key = jax.random.PRNGKey(args.seed)
    kw, kd = jax.random.split(key)
    w_true = jax.random.normal(kw, (DIMS,)) * SIGMA_W
    perm = jax.random.permutation(jax.random.fold_in(kd, 2), DIMS
                                  ).astype(jnp.int32)

    t0 = time.perf_counter()
    train_blocks = gen_blocks(jax.random.fold_in(kd, 0), n_train_blocks,
                              DIMS, BATCH, WIDTH, w_true, perm)
    test_blocks = gen_blocks(jax.random.fold_in(kd, 1), n_test_blocks,
                             DIMS, BATCH, WIDTH, w_true, perm)
    gen_s = time.perf_counter() - t0

    # Measured hot-loop anchor on a host sample of the SAME data: the C
    # transliteration of the reference's per-row update (parse/boxing
    # excluded — flatters the reference; on this 260MB-L3 host the whole
    # 2^22 model is cache-resident, so this is a strict upper bound on any
    # real mapper). vs_baseline stays the r1-r4-continuity JVM-mapper
    # system anchor (BASELINE.md estimate, includes parse/ser); the
    # measured loop rides alongside as its own labeled field.
    anchors_measured = {}
    try:
        from hivemall_tpu.runtime.benchmark import measure_reference_rowloops

        n_sample = min(16, n_train_blocks)
        s_idx = np.asarray(train_blocks[0][:n_sample]).reshape(-1, WIDTH)
        s_lab = np.asarray(train_blocks[1][:n_sample]).reshape(-1)
        s_val = np.ones_like(s_idx, dtype=np.float32)
        raw = measure_reference_rowloops(s_idx, s_val, s_lab, DIMS, k=5)
        if "arow_rows_per_sec" in raw:
            anchors_measured["train_arow"] = raw["arow_rows_per_sec"]
        if "fm_rows_per_sec" in raw:
            anchors_measured["train_fm"] = raw["fm_rows_per_sec"]
    except Exception as e:  # noqa: BLE001 - anchor is auxiliary
        print(f"measured anchor unavailable: {e}", file=sys.stderr)
    values = jnp.ones((BATCH, WIDTH), jnp.float32)

    # Bayes floor: logloss of the true CTR as predictor (binary entropy)
    pe = jnp.clip(test_blocks[2], 1e-7, 1 - 1e-7)
    bayes_ll = float(-jnp.mean(pe * jnp.log(pe) + (1 - pe) * jnp.log1p(-pe)))

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples"))
    from score_ctr import score_click_auc, score_nwmae, score_wrmse

    results = {}
    for name, runner, epochs in (
        ("train_arow", run_arow, args.epochs_arow),
        ("train_fm", run_fm, args.epochs_fm),
    ):
        train_s, logloss, p_hat, y01 = runner(train_blocks, test_blocks,
                                              epochs, values)
        clicks = y01
        impressions = np.ones_like(y01)
        auc = score_click_auc(clicks, impressions, p_hat)
        nwmae = score_nwmae(clicks, impressions, p_hat)
        wrmse = score_wrmse(clicks, impressions, p_hat)
        n_updates = n_train_blocks * BATCH * epochs
        anchor_s = n_updates / ANCHOR_ROWS_PER_SEC
        rec = {
            "metric": f"ctr_e2e_{name}_wall_clock_{platform}",
            "value": round(train_s, 4),
            "unit": "sec",
            "vs_baseline": round(anchor_s / train_s, 1),
            "rows_per_sec": round(n_updates / train_s, 1),
            "held_out_logloss": round(logloss, 5),
            "bayes_logloss_floor": round(bayes_ll, 5),
            "auc": round(auc, 5),
            "nwmae": round(nwmae, 5),
            "wrmse": round(wrmse, 5),
            "train_rows": n_train_blocks * BATCH,
            "epochs": epochs,
            "anchor_wall_clock_sec": round(anchor_s, 1),
        }
        if name in anchors_measured:
            m = anchors_measured[name]
            rec["measured_hot_loop_anchor_rows_per_sec"] = round(m, 1)
            rec["vs_measured_hot_loop"] = round(
                (n_updates / m) / train_s, 3)
        results[name] = rec
        print(json.dumps(rec), flush=True)

    summary = {
        "metric": f"ctr_e2e_best_vs_anchor_{platform}",
        "value": max(r["vs_baseline"] for r in results.values()),
        "unit": "x_speedup_at_equal_logloss",
        "vs_baseline": max(r["vs_baseline"] for r in results.values()),
        "datagen_sec": round(gen_s, 2),
    }
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
