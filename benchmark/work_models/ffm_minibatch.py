"""Field-aware factorization machines under the mini-batch rule. Per row,
`fields` linear lanes, each reading and writing three scalars (w and FTRL's
z and n), and `fields x (fields - 1)` pair entries, each a gather and a
read-modify-write of `factors` V lanes and the AdaGrad accumulator, 4 bytes
each, plus its key and its flag. The program's padding (the 64-lane bucket,
the pair block's diagonal) is its own waste and is not counted.

Beside the keys every work model gives, `gather_bytes` and `scatter_bytes`
split `gather_scatter_bytes` into its two halves, for the readers that say
which half of the pair block holds the step back."""

LINEAR_TABLES = 3   # w, z, n


def step_work(config: dict) -> dict:
    model = config["work_model"]
    k, fields = int(model["factors"]), int(model["fields"])
    rows = int(config["mini_batch"])
    lanes = rows * fields
    pairs = lanes * (fields - 1)
    entry, linear = (k + 1) * 4, LINEAR_TABLES * 4
    # a key or id, then the entry; a write reads and writes it and sets a flag
    gather = pairs * (4 + entry) + lanes * (4 + linear)
    scatter = pairs * (4 + 2 * entry + 1) + lanes * (4 + 2 * linear + 1)
    return {
        "lanes": lanes,
        "pairs": pairs,
        "gather_bytes": gather,
        "scatter_bytes": scatter,
        "gather_scatter_bytes": gather + scatter,
        # + a lane's value and field, a row's label
        "bytes": gather + scatter + lanes * 8 + rows * 4,
        # the dot product (2k) and the gradient (about 6k) a pair entry; the
        # FTRL duals and closed form a linear lane
        "flops": pairs * 8 * k + lanes * 12,
    }
