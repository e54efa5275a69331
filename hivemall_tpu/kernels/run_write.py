"""Write a block's run values into `[D]` tables by moving only the tiles of
each table that hold a touched entry: the `-mini_batch` step's write where
the tables are long against the block (`ops/scatter.py::write_path`
decides; this module is imported by a step that takes the kernel and by
nothing else, and it imports Pallas inside `pallas_modules`, the first time
such a step is traced: `from jax.experimental import pallas` is a second of
every process that makes it, PERF.md section 6, PR 39).

XLA's sorted in-place scatter streams the whole table once each way
whatever the lanes (1.6-3.2 ms per 2^28 entries on a v5e, PERF.md section
5); here the time follows the lanes. The table stays where XLA holds it,
1-D in HBM (`memory_space=pl.ANY`, aliased to the result: a `[D]` table
reshaped to `[D/128, 128]` outside the kernel would be a relayout of the
whole table, the stream again). Mosaic slices a 1-D HBM ref by whole tiles
of 1,024 entries whatever the storage type, so a copy moves one tile.

One walk of the block's ids serves all the tables of a state that it is
handed (they share their length, so an id's tile is the same tile in each):
what the walk needs is worked out before it in a few XLA ops on the
block's `[N]` lanes. `run_heads` brings the run heads to the front (a sort,
each table's values riding it; the kernel then visits each entry once), and
`chunk_plan` gives every `CHUNK` heads their tiles, listed, and each head
its tile's slot in VMEM. A grid step takes one chunk, with ids, slots, the
tile list and each table's values as scalars in SMEM and the two counts by
scalar prefetch, and runs five loops with no branch in them:

1. every tile of the chunk is copied HBM -> VMEM, one slot each in each
   table's buffer, all copies in flight at once;
2. the step waits for them (`WAIT_GROUP` tiles' bytes a wait);
3. each head patches its entry in its slot, table by table (a masked
   select on the tile; packed types widen to 32 bits and narrow back, which
   is exact);
4. every tile goes back VMEM -> HBM;
5. the step waits for its own writes.

A loop's iteration works on all the tables at once, so the scalar core
reads a head's id and slot once and has several tables' copies to issue
side by side; one kernel is lowered where a call a table lowered four
(every `train_*` call lowers its step afresh: PERF.md section 6, PR 39, has
both A/Bs). Two heads in one tile are one visit. A tile that two chunks
share is visited by both, the second after the first has finished: grid
steps run in order and a step leaves no copy in flight.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

TILE = 1024   # entries: Mosaic's 1-D HBM tile of every storage type, and
# what one copy moves
CHUNK = 1024  # heads a grid step: XLA tiles a 1-D s32 operand by 1,024, so
# an SMEM block is one tile; a table's VMEM slots hold CHUNK tiles (4 MiB
# of f32)
WAIT_GROUP = 16  # tiles one wait covers: a DMA semaphore counts bytes, so
# a wait on a ref of 16 tiles stands for 16 copies' arrivals
VMEM_BUDGET = 12 << 20  # bytes of slots one kernel call may hold (of the
# 16 MiB a Mosaic kernel gets unasked on a v5e): tables beyond it take a
# second walk

# storage type -> the 32-bit type its entries are patched in
WIDE = {jnp.dtype(jnp.float32): jnp.float32,
        jnp.dtype(jnp.bfloat16): jnp.float32,
        jnp.dtype(jnp.int8): jnp.int32}

_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret.interpret_pallas_call"


def pallas_modules():
    """(`jax.experimental.pallas`, `jax.experimental.pallas.tpu`), imported
    here and nowhere else on this kernel's path. Of the import's 0.9-1.1 s
    two thirds are Mosaic's GPU interpreter, which `pallas_call.py` imports
    unasked inside a `try: ... except ImportError` of its own; a process
    that has not imported it by now (no TPU process has a use for it) gets
    jax's own fallback for that name, and the import is 0.3 s (this
    sandbox, jax 0.9.0; PERF.md section 6, PR 39). A jax that moves the
    module imports the long way: nothing else changes."""
    if "jax.experimental.pallas" not in sys.modules \
            and _GPU_INTERPRETER not in sys.modules:
        sys.modules[_GPU_INTERPRETER] = None   # "import fails", to jax's try
        try:
            from jax.experimental import pallas  # noqa: F401
        finally:
            del sys.modules[_GPU_INTERPRETER]
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def serves(dtype, ndim: int) -> bool:
    """A 1-D table of a storage type the kernel patches."""
    return ndim == 1 and jnp.dtype(dtype) in WIDE


def run_heads(ids, dims: int):
    """The sort key that brings the run heads to the front: a head's id,
    and `dims` on every lane that repeats its left neighbour or carries a
    dropped id."""
    lax = jax.lax   # primitives by name: a fresh step lowers this each call
    before = lax.concatenate([ids[:1], ids[:-1]], 0)
    first = lax.iota(jnp.int32, ids.shape[0]) == 0
    head = (ids < dims) & (first | (before != ids))
    return lax.select(head, ids, lax.full(ids.shape, dims, jnp.int32))


def chunk_plan(hid, dims: int, tile: int = TILE):
    """Per chunk of CHUNK heads (the row form's lanes: ids repeat): each
    one's slot (its `tile`-long tile's rank in the chunk), the chunk's tile
    starts listed ascending at its front, and counts `[2 * chunks]`."""
    lax = jax.lax
    n = hid.shape[0]
    g = n // CHUNK
    at = lax.rem(lax.iota(jnp.int32, n), jnp.int32(CHUNK))
    start = hid - lax.rem(hid, jnp.int32(tile))
    before = lax.concatenate([start[:1], start[:-1]], 0)
    live = hid < dims
    opens = live & ((at == 0) | (before != start))
    opens_i = opens.astype(jnp.int32).reshape(g, CHUNK)
    slot = (lax.cumsum(opens_i, axis=1) - 1).reshape(n)
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    tiles = lax.sort(lax.select(opens, start, lax.full((n,), big, jnp.int32))
                     .reshape(g, CHUNK), dimension=1).reshape(n)
    counts = lax.concatenate([
        live.astype(jnp.int32).reshape(g, CHUNK).sum(axis=1),
        opens_i.sum(axis=1)], 0)
    return slot, tiles, counts


def _kernel(counts_ref, hid_ref, slot_ref, tiles_ref, *refs, ops, chunks: int):
    pl, pltpu = pallas_modules()
    n = len(ops)
    # then: a values block a table, the tables (aliased to the results),
    # the lane numbers; the results; a buffer a table, the semaphores
    val_refs, lane_ref = refs[:n], refs[2 * n]
    tables, bufs, sems = refs[2 * n + 1:3 * n + 1], refs[3 * n + 1:-1], refs[-1]
    g = pl.program_id(0)
    heads, tiles = counts_ref[g], counts_ref[chunks + g]

    def in_table(t, start):
        return tables[t].at[pl.ds(pl.multiple_of(start, TILE), TILE)]

    def in_vmem(t, slot, k=1):   # k tiles of table t's buffer, from `slot`
        return bufs[t].at[pl.ds(pl.multiple_of(slot * TILE, TILE), k * TILE)]

    def fetch(p, carry):
        start = tiles_ref[p]
        for t in range(n):
            pltpu.make_async_copy(in_table(t, start), in_vmem(t, p),
                                  sems.at[0, t]).start()
        return carry

    def put(p, carry):
        start = tiles_ref[p]
        for t in range(n):
            pltpu.make_async_copy(in_vmem(t, p), in_table(t, start),
                                  sems.at[1, t]).start()
        return carry

    def wait_all(way):
        # a wait is for its ref's bytes, whichever copies brought them:
        # the descriptor names the slots alone. (lax primitives by name,
        # here and in `patch`: `//`, `%`, `jnp.where` and `jnp.maximum` are
        # jitted functions whose bodies Mosaic lowers again at every use,
        # 10 ms of a step's lowering for one `//`)
        def wait(k):
            def body(_, carry):
                for t in range(n):
                    pltpu.make_async_copy(in_vmem(t, 0, k), in_vmem(t, 0, k),
                                          sems.at[way, t]).wait()
                return carry
            return body
        jax.lax.fori_loop(0, jax.lax.div(tiles, WAIT_GROUP),
                          wait(WAIT_GROUP), 0)
        jax.lax.fori_loop(0, jax.lax.rem(tiles, WAIT_GROUP), wait(1), 0)

    def patch(j, carry):
        slot = slot_ref[j]
        here = lane_ref[...] == jax.lax.rem(hid_ref[j], TILE)
        for t in range(n):
            tile = in_vmem(t, slot)
            wide = WIDE[jnp.dtype(bufs[t].dtype)]
            old = tile[...].astype(wide)
            # a flag's values come as floats with the others' (one sort
            # for all): whole numbers, exact both ways
            new = jnp.full(old.shape, val_refs[t][j].astype(wide), wide)
            if ops[t] == "max":
                new = jax.lax.max(old, new)
            tile[...] = jax.lax.select(here, new, old).astype(bufs[t].dtype)
        return carry

    jax.lax.fori_loop(0, tiles, fetch, 0)
    wait_all(0)
    jax.lax.fori_loop(0, heads, patch, 0)
    jax.lax.fori_loop(0, tiles, put, 0)
    wait_all(1)


def write_runs_kernel(tables, ids: jnp.ndarray, values, ops, *,
                      interpret: bool = False) -> list:
    """`table[ids] = values` (op `"set"`) or `max(table[ids], values)`
    (`"max"`) for each of `tables`, in place: 1-D `[D]` tables of f32, bf16
    or s8 (`serves`), all of one length, with a `values` [N] and an op
    each. `ids` [N] int32 are a block's lane ids as `reduce_block_runs`
    leaves them: ascending, in `[0, D]`, every dropped lane `== D` at the
    tail; a table's values are equal on all lanes of one id. Each result
    is `ops/scatter.py::write_runs`' on XLA's path, bit for bit.
    `interpret` runs the kernel through Pallas' interpreter (tests, on the
    CPU)."""
    tables, values, ops = list(tables), list(values), tuple(ops)
    for table in tables:
        if not serves(table.dtype, table.ndim) \
                or table.shape != tables[0].shape:
            raise ValueError(f"no run-write kernel for {table.dtype}"
                             f"{list(table.shape)} beside "
                             f"{list(tables[0].shape)}")
    if set(ops) - {"set", "max"} or not len(tables) == len(values) == len(ops):
        raise ValueError(f"ops {ops!r} for {len(tables)} tables")
    dims, n = tables[0].shape[0], ids.shape[0]
    # one rounding to the table's type, then widened exactly to a float the
    # sort carries and the kernel reads from SMEM
    vals = [v.astype(t.dtype).astype(jnp.float32)
            for t, v in zip(tables, values)]
    ids = ids.astype(jnp.int32)
    pad = -n % CHUNK   # whole chunks: the lanes added are dropped lanes
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), dims, jnp.int32)])
        vals = [jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
                for v in vals]
    whole = dims - dims % TILE   # entries in whole tiles: the kernel's
    out = tables
    if whole:
        out = list(_place(tuple(tables), ids, tuple(vals), ops=ops,
                          interpret=interpret, whole=whole,
                          walks=_walks(tables)))
    if whole < dims:
        out = _write_tails(out, ids, vals, ops, whole)
    return out


def _walks(tables):
    """The tables' positions, split so that each walk's VMEM slots stay
    under VMEM_BUDGET (a state of many f32 slots takes two)."""
    walks, room = [[]], VMEM_BUDGET
    for at, table in enumerate(tables):
        need = CHUNK * TILE * table.dtype.itemsize
        if walks[-1] and need > room:
            walks.append([])
            room = VMEM_BUDGET
        walks[-1].append(at)
        room -= need
    return tuple(map(tuple, walks))


def _place_tiles(tables, hid, plan, hvals, ops, interpret: bool):
    """One kernel call over the tables' leading entries, a whole number of
    tiles."""
    pl, pltpu = pallas_modules()
    n, chunks = len(tables), hid.shape[0] // CHUNK
    slot, tiles, counts = plan
    lanes = jax.lax.iota(jnp.int32, TILE)   # Mosaic has no 1-D iota
    scalars = pl.BlockSpec((CHUNK,), lambda g, counts: (g,),
                           memory_space=pltpu.SMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_kernel, ops=ops, chunks=chunks),
        # inside shard_map (-mix's replicas) a result varies as its table
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype,
                                        vma=jax.typeof(t).vma)
                   for t in tables],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(chunks,),
            in_specs=[scalars] * (3 + n) + [in_hbm] * n
            + [pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=[in_hbm] * n,
            scratch_shapes=[pltpu.VMEM((CHUNK * TILE,), t.dtype)
                            for t in tables]
            + [pltpu.SemaphoreType.DMA((2, n))]),
        # operand 0 is the prefetched counts, then ids, slots, tiles
        input_output_aliases={4 + n + t: t for t in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # `benchmark/xplane.classify` classes an op by the word `scatter`
        name="run_scatter_write_" + "_".join(
            jnp.dtype(t.dtype).name for t in tables),
    )(counts, hid, slot, tiles, *hvals, *tables, lanes)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "ops", "interpret", "whole", "walks"))
def _place(tables, ids, vals, *, ops, interpret, whole, walks):
    """The plan and the kernel's calls over the tables' `whole` leading
    entries, traced once a shape and a process (a fresh step traces its own
    program on every `train_*` call; this jit's trace is found again)."""
    from ..ops.scatter import _sort_columns

    key = run_heads(ids, whole)
    hid = jax.lax.sort(key, is_stable=False)
    hvals = _sort_columns(key, list(vals))
    plan = chunk_plan(hid, whole)
    out = list(tables)
    for walk in walks:
        done = _place_tiles([tables[t] for t in walk], hid, plan,
                            [hvals[t] for t in walk],
                            tuple(ops[t] for t in walk), interpret)
        for t, table in zip(walk, done):
            out[t] = table
    return out


def _write_tails(tables, ids, vals, ops, whole: int) -> list:
    """The tables' last entries, short of a tile: no copy can move them, so
    they are written where they lie, as tables of their own. Their heads
    come to the front of one more sort, under a tile of them."""
    from ..ops.scatter import _sort_columns

    dims = tables[0].shape[0]
    rest = dims - whole
    key = run_heads(ids, dims)
    key = jnp.where(key >= whole, key - whole, rest)
    tvals = _sort_columns(key, list(vals))
    key = jax.lax.sort(key, is_stable=False)[:TILE]
    out = []
    for table, op, val in zip(tables, ops, tvals):
        tail = jax.lax.dynamic_slice(table, (whole,), (rest,))
        tail = getattr(tail.at[key], op)(
            val[:TILE].astype(table.dtype), mode="drop",
            indices_are_sorted=True)
        out.append(jax.lax.dynamic_update_slice(table, tail, (whole,)))
    return out


# ---- the row form: a block's `[k]` row deltas added into a `[D, k]` table ----
#
# XLA holds a `[D, 4]` f32 table (FFM's V) as `{0,1:T(4,128)}`: the bytes of
# its `[4, D]` transpose, each (4, 128) tile (2 KiB) the four values of 128
# rows. The kernel takes that transpose, a view of the same bytes, and walks
# a block's lanes sorted by row: a grid step takes CHUNK lanes, copies every
# tile they touch into VMEM, adds each lane's four values into its row's
# column of its tile (a run's lanes one after another), and copies the
# tiles back. No carried old values: the tile is read in any case.

ROW_TILE = 128   # rows one copy moves: a (ROW_K, 128) tile of the view
ROW_K = 4        # the row width served: four f32 values a row fill the
# (4, 128) tile XLA gives the view; a wider row (FM's 16) sits in (8, 128)
# tiles of a column-major table, which this kernel does not take


def serves_rows(dtype, shape) -> bool:
    """A `[D, 4]` f32 table of whole tiles of rows."""
    return (len(shape) == 2 and shape[1] == ROW_K
            and shape[0] % ROW_TILE == 0 and shape[0] > 0
            and jnp.dtype(dtype) == jnp.float32)


def _row_kernel(counts_ref, ids_ref, slot_ref, tiles_ref, *refs, chunks: int):
    pl, pltpu = pallas_modules()
    # then: a value column a factor, the table's [k, D] view, the result
    # (aliased to it: copies go to and from the result); the tiles' buffer,
    # the semaphores
    vals, table = refs[:ROW_K], refs[ROW_K + 1]
    buf, sems = refs[ROW_K + 2], refs[ROW_K + 3]
    g = pl.program_id(0)
    lanes, tiles = counts_ref[g], counts_ref[chunks + g]

    def in_table(start):
        return table.at[:, pl.ds(pl.multiple_of(start, ROW_TILE), ROW_TILE)]

    def in_vmem(slot, k=1):   # k tiles of the buffer, from `slot`
        return buf.at[:, pl.ds(pl.multiple_of(slot * ROW_TILE, ROW_TILE),
                               k * ROW_TILE)]

    def copy(way):
        def body(p, carry):
            there, here = in_table(tiles_ref[p]), in_vmem(p)
            src, dst = (there, here) if way == 0 else (here, there)
            pltpu.make_async_copy(src, dst, sems.at[way]).start()
            return carry
        return body

    def wait_all(way):   # as `_kernel`'s: a wait stands for its ref's bytes
        def wait(k):
            def body(_, carry):
                pltpu.make_async_copy(in_vmem(0, k), in_vmem(0, k),
                                      sems.at[way]).wait()
                return carry
            return body
        jax.lax.fori_loop(0, jax.lax.div(tiles, WAIT_GROUP),
                          wait(WAIT_GROUP), 0)
        jax.lax.fori_loop(0, jax.lax.rem(tiles, WAIT_GROUP), wait(1), 0)

    shape = (ROW_K, ROW_TILE)
    factor = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    column = jax.lax.broadcasted_iota(jnp.int32, shape, 1)

    def patch(j, carry):
        tile = in_vmem(slot_ref[j])
        add = jnp.full(shape, vals[ROW_K - 1][j], jnp.float32)
        for f in range(ROW_K - 2, -1, -1):
            add = jax.lax.select(factor == f,
                                 jnp.full(shape, vals[f][j], jnp.float32), add)
        old = tile[...]
        here = column == jax.lax.rem(ids_ref[j], ROW_TILE)
        tile[...] = jax.lax.select(here, old + add, old)
        return carry

    jax.lax.fori_loop(0, tiles, copy(0), 0)
    wait_all(0)
    jax.lax.fori_loop(0, lanes, patch, 0)
    jax.lax.fori_loop(0, tiles, copy(1), 0)
    wait_all(1)


def add_rows_kernel(table, ids: jnp.ndarray, cols, *,
                    interpret: bool = False) -> jnp.ndarray:
    """`table[ids] += values` in place, lane by lane: `table` a `[D, 4]` f32
    table (`serves_rows`), `ids` [N] int32 ascending in `[0, D]` with every
    dropped lane `== D` at the tail, `cols` the lanes' values as 4 columns
    [N] in the same order. A row's lanes are added one after another in
    lane order: XLA's `.at[ids].add` to float32 rounding, in another order.
    `interpret` runs the kernel through Pallas' interpreter (tests, on the
    CPU)."""
    if not serves_rows(table.dtype, table.shape) or len(cols) != ROW_K:
        raise ValueError(f"no run-write kernel for {table.dtype}"
                         f"{list(table.shape)} with {len(cols)} columns")
    dims, n = table.shape[0], ids.shape[0]
    ids = ids.astype(jnp.int32)
    cols = [c.astype(jnp.float32) for c in cols]
    pad = -n % CHUNK   # whole chunks: the lanes added are dropped lanes
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), dims, jnp.int32)])
        cols = [jnp.concatenate([c, jnp.zeros((pad,), c.dtype)])
                for c in cols]
    return _add_rows(table, ids, tuple(cols), interpret=interpret)


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _add_rows(table, ids, cols, *, interpret):
    """The plan and the kernel's call on the table's `[4, D]` view, traced
    once a shape and a process (as `_place`)."""
    pl, pltpu = pallas_modules()
    chunks = ids.shape[0] // CHUNK
    slot, tiles, counts = chunk_plan(ids, table.shape[0], ROW_TILE)
    view = table.T   # the stored bytes: `{0,1}` of `[D, 4]` is `{1,0}` here
    scalars = pl.BlockSpec((CHUNK,), lambda g, counts: (g,),
                           memory_space=pltpu.SMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_row_kernel, chunks=chunks),
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype,
                                       vma=jax.typeof(view).vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(chunks,),
            in_specs=[scalars] * (3 + ROW_K) + [in_hbm],
            out_specs=in_hbm,
            scratch_shapes=[pltpu.VMEM((ROW_K, CHUNK * ROW_TILE),
                                       jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        # operand 0 is the prefetched counts, then ids, slots, tiles, values
        input_output_aliases={4 + ROW_K: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # `benchmark/xplane.classify` classes an op by the word `scatter`
        name="run_scatter_add_rows_float32",
    )(counts, ids, slot, tiles, *cols, view)
    return out.T
