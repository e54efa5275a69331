"""MovieLens-style matrix-factorization cross-validation — the analog of the
reference's movielens example (ref: resources/examples/movielens/generate_cv.sh,
which splits the ratings file into k folds for per-fold train/test), on
synthetic MovieLens-shaped data (no dataset egress in this environment).

Pipeline per fold: train_mf_sgd / train_mf_adagrad on the train split (fold
mean mu computed from train only), mf_predict-style scoring on the held-out
fold, rmse/mae via the streaming evaluation aggregators, plus a BPR implicit
-feedback pass evaluated as held-out pairwise AUC.

Run: JAX_PLATFORMS=cpu python examples/movielens_cv.py
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from hivemall_tpu.evaluation.metrics import MAE, RMSE, auc
from hivemall_tpu.ftvec.ranking import bpr_sampling
from hivemall_tpu.models.mf import train_bprmf, train_mf_adagrad, train_mf_sgd

N_USERS, N_ITEMS, K_TRUE, N_RATINGS, FOLDS = 200, 120, 6, 8000, 3


def synth_ratings(seed: int = 42):
    """Low-rank user/item structure + noise, ratings clipped to 1..5 —
    MovieLens-shaped triples (user, item, rating)."""
    rng = np.random.RandomState(seed)
    P = rng.randn(N_USERS, K_TRUE) * 0.8
    Q = rng.randn(N_ITEMS, K_TRUE) * 0.8
    bu = rng.randn(N_USERS) * 0.3
    bi = rng.randn(N_ITEMS) * 0.3
    users = rng.randint(0, N_USERS, N_RATINGS)
    items = rng.randint(0, N_ITEMS, N_RATINGS)
    r = 3.0 + np.sum(P[users] * Q[items], axis=1) + bu[users] + bi[items] \
        + 0.2 * rng.randn(N_RATINGS)
    return users, items, np.clip(r, 1.0, 5.0).astype(np.float32)


def cv_folds(n: int, folds: int, seed: int = 7):
    """generate_cv.sh: shuffle once, slice into k folds."""
    order = np.random.RandomState(seed).permutation(n)
    return np.array_split(order, folds)


def main():
    users, items, ratings = synth_ratings()
    for name, trainer, opts_fmt in [
            ("mf_sgd", train_mf_sgd, "-k 8 -iter 50 -mu {mu:.4f} -eta 0.05 -lambda 0.03"),
            ("mf_adagrad", train_mf_adagrad,
             "-k 8 -iter 100 -mu {mu:.4f} -eta 0.3 -lambda 0.03")]:
        fold_rmse, fold_mae = [], []
        for f, test_idx in enumerate(cv_folds(N_RATINGS, FOLDS)):
            mask = np.ones(N_RATINGS, bool)
            mask[test_idx] = False
            # mu from the TRAIN split only (no test-fold statistic leaks in)
            opts = opts_fmt.format(mu=ratings[mask].mean())
            model = trainer(users[mask], items[mask], ratings[mask], opts,
                            num_users=N_USERS, num_items=N_ITEMS)
            pred = model.predict(users[test_idx], items[test_idx])
            # streaming aggregators (the UDAF iterate/terminate lifecycle)
            rmse_agg, mae_agg = RMSE(), MAE()
            for p, a in zip(pred, ratings[test_idx]):
                rmse_agg.iterate(p, a)
                mae_agg.iterate(p, a)
            fold_rmse.append(rmse_agg.terminate())
            fold_mae.append(mae_agg.terminate())
        print(f"{name}: {FOLDS}-fold CV  rmse={np.mean(fold_rmse):.3f}  "
              f"mae={np.mean(fold_mae):.3f}")
        assert np.mean(fold_rmse) < 0.65, "MF should beat the ~1.2 std baseline"

    # ranking: implicit feedback (rating >= 4 is a positive), BPR-MF.
    # Hold out ~25% of each user's positives; train only on the rest and
    # evaluate pairwise: does each HELD-OUT positive outrank the user's
    # never-interacted items? (With a 120-item catalog, held positives are
    # repeatedly drawn as training negatives, so full-catalog top-k ndcg
    # under-reads; the pairwise AUC protocol is robust to that.)
    pos_mask = ratings >= 4.0
    hold_rng = np.random.RandomState(13)
    train_items, held_items, seen = {}, {}, {}
    for u, i in zip(users, items):
        seen.setdefault(int(u), set()).add(int(i))
    for u, i in zip(users[pos_mask], items[pos_mask]):
        u, i = int(u), int(i)
        (held_items if hold_rng.rand() < 0.25 else train_items).setdefault(
            u, []).append(i)
    triples = np.array(list(bpr_sampling(train_items, N_ITEMS - 1,
                                         sampling_rate=8.0, seed=3)))
    bpr = train_bprmf(triples[:, 0], triples[:, 1], triples[:, 2],
                      "-k 8 -iter 30 -eta 0.05",
                      num_users=N_USERS, num_items=N_ITEMS)
    aucs = []
    for u, truth in held_items.items():
        if u not in train_items:
            continue
        scores = bpr.predict_bpr(np.full(N_ITEMS, u), np.arange(N_ITEMS))
        negs = [i for i in range(N_ITEMS) if i not in seen[u]]
        cand = truth + negs
        labels = [1] * len(truth) + [0] * len(negs)
        aucs.append(auc(scores[cand], labels))
    print(f"bprmf held-out pairwise auc={np.mean(aucs):.3f} "
          f"({len(aucs)} users)")
    assert np.mean(aucs) > 0.58, "BPR should beat random ranking (auc 0.5)"
    print("movielens CV example OK")


if __name__ == "__main__":
    main()
