"""AROW-family learners under the mini-batch rule on `replicas` chips with
MIX: the work of ONE block, stated over all the chips.

The per-layer readers divide one chip's mean busy or span time by ALL
replicas' blocks, so a block's required work is given as its share of the
`replicas` chips' peak: the block's own lane work (`linear_minibatch`'s, with
one more entry a lane: the pending flag the mix needs, `pending_bytes`)
divided by `replicas`, plus a round's required exchange spread over the
`mix_every x replicas` blocks between two rounds. The exchange the ALGORITHM
requires is of the entries that are due, not of `dims`: for each feature
pending on any replica, its weight, covariance and flag read once and written
once on every replica (`due_entries_per_round`: counted by the plain
reference at the cell's size, the configuration says on which seed). What a
chip must SEND for it is `mix_allreduce_roofline`'s, against the ICI's peak;
here only memory traffic counts, as in every other work model."""

from benchmark import work
from benchmark.work_models.linear_minibatch import FLOPS_PER_LANE

# per due entry and replica: 1/cov, w/cov, two sums, the divide and product
MIX_FLOPS_PER_ENTRY = 6


def entry_bytes(model: dict) -> int:
    return int(model["tables"]) * int(model["table_bytes"]) \
        + int(model["pending_bytes"])


def round_exchange_bytes(model: dict) -> int:
    """Memory traffic one round requires on ALL replicas together."""
    return int(model["replicas"]) * int(model["due_entries_per_round"]) \
        * 2 * entry_bytes(model)


def step_work(config: dict) -> dict:
    model = config["work_model"]
    replicas, mix_every = int(model["replicas"]), int(model["mix_every"])
    lane = work.lane_work(config["mini_batch"], work.nonzeros_per_row(config),
                          entry_bytes(model), FLOPS_PER_LANE)
    blocks_a_round = mix_every * replicas
    exchange = round_exchange_bytes(model) / blocks_a_round
    mix_flops = replicas * int(model["due_entries_per_round"]) \
        * MIX_FLOPS_PER_ENTRY / blocks_a_round
    return {
        "lanes": lane["lanes"],
        "gather_scatter_bytes": lane["gather_scatter_bytes"] / replicas,
        "bytes": (lane["bytes"] + exchange) / replicas,
        "flops": (lane["flops"] + mix_flops) / replicas,
    }
