"""Factorization machines under the mini-batch rule: per lane one scalar w
and `factors` V lanes of 4 bytes each, read and written."""

from benchmark import work


def step_work(config: dict) -> dict:
    k = int(config["work_model"]["factors"])
    # vx, sum, squares (4k), grad_v and dv (6k), w terms (6)
    return work.lane_work(config["mini_batch"], work.nonzeros_per_row(config),
                          (1 + k) * 4, 10 * k + 6)
