"""Rating matrix factorization with AdaGrad under the mini-batch rule. Per
rating: its user and item ids and the rating; a gather of the user's P row
and AdaGrad row and of the item's Q row and AdaGrad row (`factors` lanes
of 4 bytes each) and of the two biases; a read-modify-write of each of
those six at the same ids (the ids read again); and the two touched flags.
The rows that pad a call's last block are the program's own waste and are
not counted.

Beside the keys every work model gives, `gather_bytes` and `scatter_bytes`
split `gather_scatter_bytes` into its two halves."""

ROW_TABLES = 4    # P, P_gg, Q, Q_gg: `factors` lanes each
BIAS_TABLES = 2   # Bu, Bi


def step_work(config: dict) -> dict:
    k = int(config["work_model"]["factors"])
    rows = int(config["mini_batch"])
    entries = (ROW_TABLES * k + BIAS_TABLES) * 4
    gather = rows * (2 * 4 + entries)
    scatter = rows * (2 * 4 + 2 * entries + 2)
    return {
        "ratings": rows,
        "gather_bytes": gather,
        "scatter_bytes": scatter,
        "gather_scatter_bytes": gather + scatter,
        "bytes": gather + scatter + rows * 4,   # + the rating
        # a rating: the prediction (2k + 4), both gradients (6k), both
        # accumulators' increments (4k), both AdaGrad rates (10k), both
        # steps (2k), the four row adds (4k); eta(t) (3) and the two biases'
        # steps and adds (8)
        "flops": rows * (28 * k + 15),
    }
