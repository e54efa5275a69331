"""End-to-end CTR training example — the kddtrack2 pipeline shape
(ref: resources/examples/kddtrack2/*) on synthetic data:

  raw categorical rows -> feature_hashing -> add_bias -> train_arow (and
  train_fm) -> predicted CTR via sigmoid(score) -> NWMAE / WRMSE / AUC.

Run: JAX_PLATFORMS=cpu python examples/ctr_pipeline.py
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from hivemall_tpu.ftvec import add_bias, feature_hashing
from hivemall_tpu.models.classifier import train_arow
from hivemall_tpu.models.fm import train_fm
from hivemall_tpu.tools import sigmoid

from score_ctr import score_click_auc, score_nwmae, score_wrmse


def synth_ctr(n=20000, seed=0):
    """Categorical ad rows (ad, advertiser, query, position) with a
    ground-truth logistic CTR."""
    rng = np.random.RandomState(seed)
    n_ads, n_advs, n_queries = 500, 60, 1000
    ad_w = rng.randn(n_ads) * 1.2
    adv_w = rng.randn(n_advs) * 0.8
    q_w = rng.randn(n_queries) * 0.5
    pos_w = np.array([0.7, 0.0, -0.6])
    rows, clicks, imps = [], [], []
    for _ in range(n):
        ad = rng.randint(n_ads)
        adv = rng.randint(n_advs)
        q = rng.randint(n_queries)
        pos = rng.randint(3)
        logit = ad_w[ad] + adv_w[adv] + q_w[q] + pos_w[pos] - 2.0
        ctr = 1.0 / (1.0 + np.exp(-logit))
        impressions = rng.randint(1, 20)
        rows.append([f"ad#{ad}", f"adv#{adv}", f"q#{q}", f"pos#{pos}"])
        clicks.append(rng.binomial(impressions, ctr))
        imps.append(impressions)
    return rows, np.array(clicks, float), np.array(imps, float)


def main() -> None:
    rows, clicks, imps = synth_ctr()
    # expand to per-impression binary labels for online training
    feats, labels = [], []
    for r, c, m in zip(rows, clicks, imps):
        hashed = add_bias(feature_hashing(r))
        for _ in range(int(c)):
            feats.append(hashed)
            labels.append(1)
        for _ in range(int(m - c)):
            feats.append(hashed)
            labels.append(-1)
    perm = np.random.RandomState(1).permutation(len(feats))
    feats = [feats[i] for i in perm]
    labels = np.asarray(labels)[perm]

    print(f"{len(feats)} training impressions")
    model = train_arow(feats, labels, "-dims 1048576 -mini_batch 256 -iters 3 -disable_cv")
    test_feats = [add_bias(feature_hashing(r)) for r in rows]
    pred_ctr = sigmoid(model.predict(test_feats))
    print("train_arow:")
    print("  AUC  : %.4f" % score_click_auc(clicks, imps, pred_ctr))
    print("  NWMAE: %.4f" % score_nwmae(clicks, imps, pred_ctr))
    print("  WRMSE: %.4f" % score_wrmse(clicks, imps, pred_ctr))

    fm = train_fm(feats, labels,
                  "-dims 1048576 -classification -factor 4 -mini_batch 256 "
                  "-iters 3 -disable_cv")
    pred_fm = sigmoid(fm.predict(test_feats))
    print("train_fm:")
    print("  AUC  : %.4f" % score_click_auc(clicks, imps, pred_fm))


if __name__ == "__main__":
    main()
