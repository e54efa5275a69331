"""Forest-training benchmark: batched level-synchronous growth (grow_forest)
vs the per-tree loop (grow_tree) on the same bootstrap bags, plus a GBT
mode timing single-device vs data-parallel boosting rounds.

Usage: python scripts/bench_forest.py [N] [F] [T]
       python scripts/bench_forest.py --gbt [N] [F] [rounds]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from hivemall_tpu.models.trees.binning import bin_data, make_bins
from hivemall_tpu.models.trees.grow import grow_forest, grow_tree


def main_gbt(args):
    """Single-device vs data-parallel GBT rounds (the psum'd histogram
    build, parallel/forest_shard.train_gbt_data_parallel)."""
    import jax

    from hivemall_tpu.models.trees.forest import \
        train_gradient_tree_boosting_classifier
    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.parallel.forest_shard import train_gbt_data_parallel

    N = int(args[0]) if len(args) > 0 else 50000
    F = int(args[1]) if len(args) > 1 else 20
    rounds = int(args[2]) if len(args) > 2 else 16
    rng = np.random.RandomState(0)
    X = rng.rand(N, F)
    y = ((X[:, 0] > 0.5) ^ (X[:, 1] > 0.5) | (X[:, 2] > 0.8)).astype(int)
    opts = f"-trees {rounds} -iters {rounds} -depth 6 -seed 3"
    n_dev = len(jax.devices())
    mesh = make_mesh(n_dev)

    # warm both paths at the TIMED shapes (full N and depth — the jitted
    # histogram builders retrace per (N, S_pad), so a sliver warm-up would
    # leave compiles inside the timed region)
    warm = "-trees 2 -iters 2 -depth 6 -seed 1"
    train_gradient_tree_boosting_classifier(X, y, warm)
    train_gbt_data_parallel(X, y, warm, mesh)

    t0 = time.perf_counter()
    single = train_gradient_tree_boosting_classifier(X, y, opts)
    t_single = time.perf_counter() - t0
    t0 = time.perf_counter()
    par = train_gbt_data_parallel(X, y, opts, mesh)
    t_par = time.perf_counter() - t0
    acc_s = float(np.mean(single.predict(X) == y))
    acc_p = float(np.mean(par.predict(X) == y))
    print(json.dumps({
        "metric": f"gbt_{rounds}rounds_{N}rows_{F}feat_depth6_dataparallel_"
                  f"{jax.devices()[0].platform}",
        "value": round(t_par, 3),
        "unit": "sec",
        "single_device_sec": round(t_single, 3),
        "n_devices": n_dev,
        "speedup": round(t_single / t_par, 2),
        "train_acc_single": round(acc_s, 4),
        "train_acc_parallel": round(acc_p, 4),
    }), flush=True)


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--gbt":
        return main_gbt(sys.argv[2:])
    N = int(sys.argv[1]) if len(sys.argv) > 1 else 20000
    F = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    T = int(sys.argv[3]) if len(sys.argv) > 3 else 32
    rng = np.random.RandomState(0)
    X = rng.rand(N, F)
    y = ((X[:, 0] > 0.5) ^ (X[:, 1] > 0.5) | (X[:, 2] > 0.8)).astype(int)
    bins = make_bins(X, ["Q"] * F)
    Xb = bin_data(X, bins)
    n_bins = max(b.n_bins for b in bins)
    W = np.stack([
        np.bincount(np.random.RandomState(100 + t).randint(0, N, N),
                    minlength=N).astype(np.float32) for t in range(T)])
    kw = dict(n_bins=n_bins, classification=True, n_classes=2,
              max_depth=10, min_split=2, min_leaf=1, max_leaf_nodes=256,
              num_vars=max(1, int(np.sqrt(F))))

    def run_batched():
        return grow_forest(Xb, y, W, np.zeros(F, bool),
                           rngs=[np.random.RandomState(t) for t in range(T)],
                           strategy="batched", **kw)

    def run_per_tree():
        return [grow_tree(Xb, y, W[t], np.zeros(F, bool),
                          rng=np.random.RandomState(t), **kw)
                for t in range(T)]

    # warm up compiles on a tiny forest first
    small = dict(kw)
    grow_forest(Xb[:512], y[:512], W[:2, :512], np.zeros(F, bool),
                rngs=[np.random.RandomState(0), np.random.RandomState(1)], **small)
    grow_tree(Xb[:512], y[:512], W[0, :512], np.zeros(F, bool),
              rng=np.random.RandomState(0), **small)

    t0 = time.perf_counter()
    forest = run_batched()
    t_batched = time.perf_counter() - t0
    t0 = time.perf_counter()
    solo = run_per_tree()
    t_per_tree = time.perf_counter() - t0
    nodes = sum(t.n_nodes for t in forest)
    nodes_solo = sum(t.n_nodes for t in solo)
    print(f"rows={N} features={F} trees={T} nodes batched={nodes} per-tree={nodes_solo}")
    print(f"batched grow_forest: {t_batched:.2f}s   per-tree grow_tree loop: "
          f"{t_per_tree:.2f}s   speedup {t_per_tree / t_batched:.2f}x")
    import jax

    print(json.dumps({
        "metric": f"forest_grow_{T}trees_{N}rows_{F}feat_depth10_batched_"
                  f"{jax.devices()[0].platform}",
        "value": round(t_batched, 3),
        "unit": "sec",
        "per_tree_loop_sec": round(t_per_tree, 3),
        "batched_speedup": round(t_per_tree / t_batched, 2),
        "nodes": int(nodes),
        # grow_forest(strategy="auto") picks per_tree when unsharded — flag
        # loudly if this platform's data ever contradicts that default
        "default_strategy": "per_tree",
        "default_is_fastest": bool(t_per_tree <= t_batched),
    }), flush=True)


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
