"""End-to-end production story: distributed CTR training with periodic
crash-safe checkpoints, a simulated failure, elastic resume on a smaller
mesh, and Arrow model export for any host engine.

The reference's equivalent is a Hive job: mappers train train_arow replicas
against MIX servers, Hadoop retries failed tasks, and the model lands in a
Hive table (SURVEY.md §3.1). Here the same lifecycle is:

    MixTrainer (replicas x collectives)  ->  runtime.recovery.checkpoint
        -> [failure] -> elastic_resume on surviving devices
        -> adapters.arrow model table / IPC file

Run:  JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/elastic_ctr_training.py
"""

import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hivemall_tpu.models.classifier import AROW
from hivemall_tpu.parallel import MixConfig, make_mesh
from hivemall_tpu.runtime.recovery import checkpoint, elastic_resume

DIMS = 1 << 16
WIDTH = 16
BATCH = 64


def ctr_blocks(n_dev, k, w_true, seed):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, DIMS, size=(n_dev, k, BATCH, WIDTH)).astype(np.int32)
    val = np.ones((n_dev, k, BATCH, WIDTH), np.float32)
    score = np.sum(w_true[idx] * val, axis=-1) - 1.0
    click = (rng.rand(n_dev, k, BATCH) < 1.0 / (1.0 + np.exp(-score)))
    return idx, val, click.astype(np.float32) * 2.0 - 1.0


def holdout_auc(weights, w_true, seed=999):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, DIMS, size=(4096, WIDTH))
    score = np.sum(np.asarray(weights)[idx], axis=-1)
    truth = np.sum(w_true[idx], axis=-1) - 1.0
    y = (rng.rand(4096) < 1.0 / (1.0 + np.exp(-truth))).astype(int)
    order = np.argsort(-score)
    ys = y[order]
    pos = ys.sum()
    neg = len(ys) - pos
    # concordant pairs: for each positive (descending by score), negatives
    # ranked strictly below it
    neg_above = np.cumsum(1 - ys)
    concordant = np.sum(ys * (neg - neg_above))
    return float(concordant / max(pos * neg, 1))


def main() -> None:
    rng = np.random.RandomState(0)
    w_true = (rng.randn(DIMS) * 0.8).astype(np.float32)

    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "ctr_model.npz")

        # phase 1: 8 replicas, checkpoint every round
        trainer, state = elastic_resume(AROW, {"r": 0.1}, DIMS, ckpt,
                                        mesh=make_mesh(8),
                                        config=MixConfig(mix_every=8))
        for rnd in range(3):
            state, loss = trainer.step(
                state, *ctr_blocks(8, 8, w_true, seed=rnd))
            checkpoint(trainer, state, ckpt)
            print(f"[8 replicas] round {rnd}: loss {float(loss):.1f}")
        auc8 = holdout_auc(trainer.final_state(state).weights, w_true)
        print(f"[8 replicas] held-out AUC {auc8:.4f}")

        # "failure": half the fleet is gone. Resume from the checkpoint on
        # the 4 surviving devices — no trained work lost.
        print("-- simulated failure: resuming on 4 devices --")
        trainer, state = elastic_resume(AROW, {"r": 0.1}, DIMS, ckpt,
                                        mesh=make_mesh(4),
                                        config=MixConfig(mix_every=8))
        for rnd in range(3, 5):
            state, loss = trainer.step(
                state, *ctr_blocks(4, 8, w_true, seed=rnd))
            checkpoint(trainer, state, ckpt)
            print(f"[4 replicas] round {rnd}: loss {float(loss):.1f}")
        final = trainer.final_state(state)
        auc4 = holdout_auc(final.weights, w_true)
        print(f"[4 replicas] held-out AUC {auc4:.4f} "
              f"(total examples: {int(final.step)})")

        # export the model for any Arrow-speaking engine
        try:
            from hivemall_tpu.adapters import model_to_arrow

            class _M:  # model_to_arrow reads .state
                state = final

            table = model_to_arrow(_M)
            print(f"Arrow model table: {table.num_rows} rows, "
                  f"columns {table.column_names}")
        except ImportError:
            print("pyarrow not installed; skipping Arrow export")


if __name__ == "__main__":
    main()
