#!/usr/bin/env python3
"""What one gather and one in-place write of a block's runs cost on the
chip, by table length, storage type and lane count, down each path the
write has: XLA's sorted scatter and the Pallas run-write kernel
(`kernels/run_write.py`). The readings behind `ops/scatter.py::WRITE_COST`
(PERF.md section 7 keeps the table). A chip tool; no benchmark cell runs it.

    chiprun -- python3 scripts/scatter_cost.py --out chiprun_out/scatter_cost.jsonl

For every storage type (`s8` written with `max` as the step's flag is,
`bf16` and `f32` with `set`), table length 2^24 ... 2^29 and lane count
16,384 / 40,960 / 65,536 (a 1,024-row block of 16, 40 and 64 lanes), ids
drawn as the benchmark's rows draw them (`block_ids`: a third of a row's
lanes on ids every row carries, the rest log-uniform by rank and placed by
a hash over the table):

- `write.full`: `table.at[sorted ids].set(values, mode="drop",
  indices_are_sorted=True)`, every lane a real id: `ops/scatter.write_runs`.
- `write.tail`: the same program, the last 3/8 of the lanes the dropped id
  `dims` (65,536 lanes then carry what `write.full` carries on 40,960: a
  dropped lane is free where the two read alike).
- `write.heads`: run heads only, under `unique_indices=True`: every lane
  that repeats its left neighbour's id gets an out-of-range id of its own.
- `write.kernel`: `write_runs_kernel` on `write.full`'s operands, its head
  sort and plan included; first held to `write.full`'s program on a random
  table, bit for bit (`matches` on the line).
- `gather.full` / `gather.tail`: `table.at[ids].get(mode="fill")` in block
  order, as the step gathers, every lane real or 3/8 of each row padding.

One program a (kind, type, length, lanes), ids as arguments; each timed as
`--iters` dispatches back to back on a donated table, ended by a value
fetch. Fails without a TPU unless `--allow-cpu` (a rehearsal: its numbers
are the CPU backend's and say nothing of the chip).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

ROWS = 1024


def block_ids(rng, dims: int, lanes: int, real: int):
    """[ROWS, lanes] ids as the benchmark's generator makes a block's
    (`benchmark/datagen.py`): the leading `real` lanes of each row carry
    features, a third of them ids that every row carries, the rest drawn
    log-uniform by rank and PLACED BY A HASH of (rank, lane), so that hot
    ids lie spread over the table as murmur-hashed names do; the lanes
    after them carry the padding id. (Until PR 39 the ranks were the ids:
    hot ids lay side by side at the table's start. XLA's writes and
    gathers read the same either way; the kernel moves a tile for every
    id that lies alone, so unplaced ranks flatter it by half: ledger,
    PR 38.)"""
    import numpy as np

    from benchmark.datagen import log_uniform_ranks, numeric_ids, place

    ids = np.full((ROWS, lanes), dims, np.int64)
    fixed = real // 3
    ids[:, :fixed] = numeric_ids(fixed, dims)
    fields = np.broadcast_to(np.arange(real - fixed), (ROWS, real - fixed))
    ids[:, fixed:real] = place(
        log_uniform_ranks(rng.random((ROWS, real - fixed)), dims), fields,
        dims)
    return ids.astype(np.int32)


def heads_only(sorted_ids, dims: int):
    """Sorted ids with every repeat replaced by a distinct id past the
    table, sorted again: what `unique_indices=True` may be promised."""
    import numpy as np

    out = sorted_ids.astype(np.int64)
    repeat = np.concatenate([[False], out[1:] == out[:-1]]) | (out >= dims)
    out[repeat] = dims + 1 + np.arange(int(repeat.sum()))
    return np.sort(out).astype(np.int32)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", default="chiprun_out/scatter_cost.jsonl")
    p.add_argument("--log2", type=int, nargs="+",
                   default=[24, 25, 26, 27, 28, 29])
    p.add_argument("--lanes", type=int, nargs="+", default=[16, 40, 64],
                   help="lanes a row; a block has 1,024 rows")
    p.add_argument("--dtypes", nargs="+", default=["s8", "bf16", "f32"])
    p.add_argument("--cases", nargs="+", default=None,
                   help="only these cases (default: all)")
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--seed", type=int, default=35)
    p.add_argument("--allow-cpu", action="store_true")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.allow_cpu:
        print(f"no TPU here (platform {dev.platform!r}): this tool times "
              "the chip", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind}
    dtypes = {"s8": jnp.int8, "bf16": jnp.bfloat16, "f32": jnp.float32}
    rng = np.random.default_rng(args.seed)

    from hivemall_tpu.kernels.run_write import write_runs_kernel

    def op_of(table):
        return "max" if table.dtype == jnp.int8 else "set"

    def write(table, ids, values, unique=False):
        return getattr(table.at[ids], op_of(table))(
            values, mode="drop", indices_are_sorted=True,
            unique_indices=unique)

    writes = {u: jax.jit(lambda t, i, v, u=u: write(t, i, v, u),
                         donate_argnums=(0,)) for u in (False, True)}
    gathers = jax.jit(
        lambda table, ids: table.at[ids].get(mode="fill", fill_value=0))
    kernel = jax.jit(
        lambda t, i, v: write_runs_kernel(
            [t], i, [v], [op_of(t)], interpret=dev.platform != "tpu")[0],
        donate_argnums=(0,))   # interpreted in the CPU rehearsal alone

    def matches(dims, dtype, ids):
        """Does the kernel leave a random table as XLA's sorted write
        leaves it, bit for bit (run values: a function of the id)."""
        key = jax.random.PRNGKey(dims % 1009)
        if dtype == jnp.int8:
            fresh = lambda: jax.random.randint(key, (dims,), -2, 3, dtype)
            values = (ids % 5 - 2).astype(dtype)
        else:
            fresh = lambda: jax.random.normal(key, (dims,), dtype)
            values = jnp.sin(ids.astype(jnp.float32)).astype(dtype)
        want = writes[False](fresh(), ids, values)
        return bool(jnp.array_equal(kernel(fresh(), ids, values), want))

    def timed(program, table, *operands):
        """Seconds a dispatch, and the table back (donated through)."""
        def run(n, carry):
            for _ in range(n):
                out = program(carry, *operands)
                if out.shape == carry.shape:   # a write: the table, donated
                    carry = out
            float(out.reshape(-1)[0])
            return carry

        table = run(3, table)
        t0 = time.perf_counter()
        table = run(args.iters, table)
        return (time.perf_counter() - t0) / args.iters, table

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    lines = []
    with open(args.out, "w", encoding="utf-8") as f:
        for name in args.dtypes:
            dtype = dtypes[name]
            for log2 in args.log2:
                dims = 1 << log2
                table = jnp.zeros((dims,), dtype)
                for k in args.lanes:
                    lanes = ROWS * k
                    full = block_ids(rng, dims, k, k)
                    tail = block_ids(rng, dims, k, k * 5 // 8)
                    values = jnp.ones((lanes,), dtype)
                    cases = [
                        ("write.full", writes[False], np.sort(full, None)),
                        ("write.tail", writes[False], np.sort(tail, None)),
                        ("write.heads", writes[True],
                         heads_only(np.sort(full, None), dims)),
                        ("write.kernel", kernel, np.sort(full, None)),
                        ("gather.full", gathers, full.reshape(-1)),
                        ("gather.tail", gathers, tail.reshape(-1)),
                    ]
                    for case, program, ids in cases:
                        if args.cases and case not in args.cases:
                            continue
                        operands = (jnp.asarray(ids),) + (
                            (values,) if case.startswith("write") else ())
                        same = None
                        if case == "write.kernel":
                            del table   # room for the two random tables
                            same = matches(dims, dtype, operands[0])
                            table = jnp.zeros((dims,), dtype)
                        sec, table = timed(program, table, *operands)
                        real = int((ids < dims).sum())
                        line = {
                            "case": case, "dtype": name, "log2_dims": log2,
                            "lanes": lanes, "real_lanes": real,
                            "ms": sec * 1e3, "ns_per_lane": sec * 1e9 / lanes,
                            # a write that streams the table moves its
                            # bytes once each way
                            "table_gbps_if_streamed":
                                2 * table.nbytes / sec / 1e9
                                if case.startswith("write") else None,
                            "matches": same, "device": device}
                        lines.append(line)
                        f.write(json.dumps(line) + "\n")
                        f.flush()
                del table

    # the table PERF.md keeps: ms by case, one row a (type, lanes), one
    # column a table length
    print("| case, type, lanes | " + " | ".join(
        f"2^{b}" for b in args.log2) + " |")
    print("|---|" + "---|" * len(args.log2))
    keys = sorted({(ln["case"], ln["dtype"], ln["lanes"]) for ln in lines})
    for key in keys:
        by_len = {ln["log2_dims"]: ln["ms"] for ln in lines
                  if (ln["case"], ln["dtype"], ln["lanes"]) == key}
        print(f"| {key[0]} {key[1]} {key[2]} | " + " | ".join(
            f"{by_len[b]:.3f}" for b in args.log2) + " |")
    print(json.dumps({"device": device, "cases": len(lines),
                      "kernel_mismatches": [
                          (ln["dtype"], ln["log2_dims"], ln["lanes"])
                          for ln in lines if ln["matches"] is False]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
