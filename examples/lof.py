"""Local Outlier Factor — the reference's README-advertised anomaly detection.

The reference ships LOF only as example SQL on its wiki plus the
`hundred_balls` sample data (ref: resources/examples/lof/hundred_balls.txt;
no Java component exists — SURVEY.md §2.20). Here it is a first-class
function built on the batched distance kernels (knn/distance.py): one matmul
produces the full distance matrix, k-distances / reachability / lrd / LOF are
vectorized.

Run: JAX_PLATFORMS=cpu python examples/lof.py
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from hivemall_tpu.knn.distance import euclid_distance_batch


def lof(X: np.ndarray, k: int = 10) -> np.ndarray:
    """LOF scores for each row of X (score >> 1 = outlier)."""
    n = X.shape[0]
    D = np.asarray(euclid_distance_batch(X, X)).copy()
    np.fill_diagonal(D, np.inf)
    knn_idx = np.argsort(D, axis=1)[:, :k]  # [n, k]
    knn_dist = np.take_along_axis(D, knn_idx, axis=1)  # [n, k]
    k_distance = knn_dist[:, -1]  # distance to k-th neighbor
    # reachability distance: max(k_distance(neighbor), d(p, neighbor))
    reach = np.maximum(k_distance[knn_idx], knn_dist)
    lrd = k / np.maximum(reach.sum(axis=1), 1e-12)
    lof_scores = (lrd[knn_idx].sum(axis=1) / k) / np.maximum(lrd, 1e-12)
    return lof_scores


def main() -> None:
    rng = np.random.RandomState(0)
    # "hundred balls": tight cluster + a few scattered outliers
    inliers = rng.randn(100, 2) * 0.5
    outliers = np.array([[5.0, 5.0], [-6.0, 4.0], [4.0, -6.0]])
    X = np.vstack([inliers, outliers]).astype(np.float32)
    scores = lof(X, k=10)
    top = np.argsort(-scores)[:3]
    print("top-3 LOF rows:", sorted(top.tolist()))
    print("scores:", np.round(scores[top], 2).tolist())
    assert set(top.tolist()) == {100, 101, 102}, "outliers not detected"
    print("outliers detected correctly")


if __name__ == "__main__":
    main()
