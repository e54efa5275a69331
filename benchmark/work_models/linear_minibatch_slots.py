"""Linear learners whose rule keeps optimizer slots beside the weights
(AdaGradRDA: the weights are a function of two sums) under the mini-batch
rule: per lane the rule gathers one entry of every table and writes one
back, and the tables are of mixed width (`table_bytes`, one number a table:
bfloat16 weights beside float32 sums are [2, 4, 4]). Bandwidth bounds the
step: the arithmetic is small."""

from benchmark import work

# score 2; the hinge 1; g, s g 2; a lane's add into its feature's sum and
# that sum's square 2; deriving w from the sums (two scalings,
# |u|/t - lambda, eta t x that, a root, a divide) 9
FLOPS_PER_LANE = 16


def step_work(config: dict) -> dict:
    entry = sum(int(b) for b in config["work_model"]["table_bytes"])
    return work.lane_work(config["mini_batch"], work.nonzeros_per_row(config),
                          entry, FLOPS_PER_LANE)
