"""AROW-family linear learners under the mini-batch rule: per lane the rule
reads and writes `tables` scalar tables (weights, covariances) of
`table_bytes` each. Bandwidth bounds the step: the arithmetic is small."""

from benchmark import work

# score, variance, dw, dcov: 2 + 3 + 3 + 4, plus the mean's divide x2
FLOPS_PER_LANE = 14


def step_work(config: dict) -> dict:
    model = config["work_model"]
    entry = int(model["tables"]) * int(model["table_bytes"])
    return work.lane_work(config["mini_batch"], work.nonzeros_per_row(config),
                          entry, FLOPS_PER_LANE)
