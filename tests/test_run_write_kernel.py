"""The run-write kernel (kernels/run_write.py) against XLA's sorted in-place
write (ops/scatter.write_runs on its XLA path), bit for bit, and the rule
that chooses between them (ops/scatter.write_path).

The kernel runs through Pallas' interpreter on the CPU (its `interpret`
argument, as tests/test_pallas_kernels.py runs the scan kernel): same
copies, same waits, same patches, no chip. What the interpreter cannot show
(Mosaic's layouts of a 1-D packed table, a wait that stands for several
copies) the chip shows: `scripts/scatter_cost.py` holds every kernel case to
XLA's result there, and tests/test_minibatch_step_structure.py compiles the
cells' steps with the kernel in them for a described v5e. The step's cases
here force the kernel by patching the rule, as tests/test_lane_cut.py
patches `fillable_lanes`: there is no option.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hivemall_tpu.core.engine import make_train_fn
from hivemall_tpu.kernels.run_write import CHUNK, TILE, write_runs_kernel
from hivemall_tpu.ops import scatter
from hivemall_tpu.ops.scatter import (BlockRuns, reduce_block_runs,
                                      write_path, write_runs)
from test_minibatch_block_apply import CASES, _block, _state

DIMS = 1 << 15          # 32 tiles
RAGGED = DIMS + 24      # and 24 entries short of a 33rd
TYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "s8": jnp.int8}


def _ids(kind, rng, dims):
    """A block's lane ids as `reduce_block_runs` leaves them: ascending,
    duplicates adjacent, every dropped lane `== dims` at the tail."""
    last = dims - dims % TILE - TILE   # the last whole tile
    if kind == "all_dropped":
        ids = np.full(1500, dims)
    elif kind == "one_run_as_long_as_the_block":
        ids = np.full(CHUNK + 40, 3 * TILE + 17)
    elif kind == "heads_at_both_ends_of_a_tile_and_of_the_table":
        ids = np.array([0, 0, 1, TILE - 1, TILE, 5 * TILE - 1, 5 * TILE,
                        last, last + TILE - 1, dims - 2, dims - 1, dims - 1])
    elif kind == "dropped_tail_of_three_eighths":
        ids = np.concatenate([rng.integers(0, dims, 1000),
                              np.full(600, dims)])
    elif kind == "many_heads_in_one_tile":
        ids = 7 * TILE + rng.choice(TILE, 600, replace=False)
    elif kind == "a_tile_across_a_chunk_boundary":
        # lanes CHUNK-300 ... CHUNK+299 are 600 distinct ids of ONE tile:
        # two grid steps visit it, the second after the first has written
        ids = np.concatenate([
            rng.integers(0, 9 * TILE, CHUNK - 300),
            9 * TILE + rng.choice(TILE, 600, replace=False),
            rng.integers(10 * TILE, dims, 200)])
    elif kind == "heavy_duplicates":       # 9 ids on 1,200 lanes
        ids = rng.choice(rng.choice(dims, 9, replace=False), 1200)
    elif kind == "as_the_cells_rows":
        # a third of the lanes on ids that every row carries, the rest
        # log-uniform by rank and hash-placed, 1/40 of them dropped
        rank = np.exp(rng.random(1000) * np.log(dims)).astype(np.int64)
        ids = np.concatenate([
            np.repeat(np.arange(13) * 7919 % dims, 40),
            rank * 2654435761 % dims, np.full(40, dims)])
    return np.sort(np.asarray(ids, np.int64)).astype(np.int32)


KINDS = ["all_dropped", "one_run_as_long_as_the_block",
         "heads_at_both_ends_of_a_tile_and_of_the_table",
         "dropped_tail_of_three_eighths", "many_heads_in_one_tile",
         "a_tile_across_a_chunk_boundary", "heavy_duplicates",
         "as_the_cells_rows"]


def _table_and_values(dtype, ids, rng, dims):
    """A table in mid-training and run values that agree on all lanes of
    one id (a function of the id), signs and magnitudes mixed."""
    if dtype == jnp.int8:
        table = rng.integers(-3, 4, dims).astype(np.int8)
        by_id = rng.integers(-3, 4, dims + 1)
    else:
        table = rng.normal(size=dims).astype(np.float32)
        by_id = rng.normal(size=dims + 1).astype(np.float32) * 3
    return jnp.asarray(table, dtype), jnp.asarray(by_id[ids])


def _bits(x):
    return np.asarray(x).tobytes()


def _xla_write(table, ids, values, op):
    """`write_runs` as it is on the CPU: XLA's sorted write."""
    assert write_path(table.dtype, table.shape[0], ids.shape[0],
                      jax.default_backend()) == "xla"
    return write_runs(table, BlockRuns(jnp.asarray(ids), None, None), values,
                      op)


# every pattern on a table of whole tiles; three of them on one whose last
# entries are short of a tile
PATTERNS = [(kind, DIMS) for kind in KINDS] + [(kind, RAGGED) for kind in (
    "heads_at_both_ends_of_a_tile_and_of_the_table",
    "dropped_tail_of_three_eighths", "all_dropped")]


@pytest.mark.parametrize("kind,dims", PATTERNS, ids=[
    kind + ("" if dims == DIMS else "-ragged") for kind, dims in PATTERNS])
@pytest.mark.parametrize("op", ["set", "max"])
@pytest.mark.parametrize("name", sorted(TYPES))
def test_kernel_equals_xla_sorted_write(name, op, kind, dims):
    dtype = TYPES[name]
    rng = np.random.default_rng(len(name) * 100 + len(kind) + len(op))
    ids = _ids(kind, rng, dims)
    table, values = _table_and_values(dtype, ids, rng, dims)
    want = _xla_write(table, ids, values, op)
    (got,) = write_runs_kernel([table], jnp.asarray(ids), [values], [op],
                               interpret=True)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _bits(got) == _bits(want)
    if kind == "all_dropped":
        assert _bits(got) == _bits(table)
    elif op == "set" or kind != "one_run_as_long_as_the_block":
        # the case means something: the write changed the table (one id's
        # `max` need not)
        assert _bits(want) != _bits(table)


@pytest.mark.parametrize("name", sorted(TYPES))
def test_negative_ids_as_reduce_block_runs_maps_them(name):
    """A block's raw lane ids, negatives (counted from the table's end, as
    `.at[]` counts them) and out-of-range ones among them, through
    `reduce_block_runs` and then down each path."""
    dtype = TYPES[name]
    rng = np.random.default_rng(7)
    raw = np.concatenate([rng.integers(-DIMS, DIMS, 900),
                          [-1, -DIMS, DIMS, DIMS + 5, -DIMS - 1, 0]])
    ones = jnp.ones(raw.shape, jnp.float32)
    runs = reduce_block_runs(jnp.asarray(raw, jnp.int32), DIMS,
                             {"count": ones}, {})
    table, _ = _table_and_values(dtype, np.zeros(1, np.int32), rng, DIMS)
    values = (runs.sums["count"] % 5 - 2).astype(jnp.float32)
    op = "max" if dtype == jnp.int8 else "set"
    want = write_runs(table, runs, values, op)
    (got,) = write_runs_kernel([table], runs.ids, [values], [op],
                               interpret=True)
    assert _bits(got) == _bits(want) != _bits(table)


def _state_tables(rng, ids, dims):
    """AdaGradRDA's four and one more: (tables, values, ops)."""
    names = ["bf16", "f32", "f32", "s8", "f32"]
    pairs = [_table_and_values(TYPES[n], ids, rng, dims) for n in names]
    return ([t for t, _ in pairs], [v for _, v in pairs],
            ["max" if n == "s8" else "set" for n in names])


@pytest.mark.parametrize("dims", [DIMS, RAGGED], ids=["whole", "ragged"])
def test_one_walk_serves_tables_of_mixed_types(dims):
    """A state's tables in one kernel call: each comes out as XLA's write
    of it alone."""
    rng = np.random.default_rng(11)
    ids = _ids("a_tile_across_a_chunk_boundary", rng, dims)
    tables, values, ops = _state_tables(rng, ids, dims)
    got = write_runs_kernel(tables, jnp.asarray(ids), values, ops,
                            interpret=True)
    assert len(got) == len(tables)
    for table, vals, op, out in zip(tables, values, ops, got):
        assert out.dtype == table.dtype
        assert _bits(out) == _bits(_xla_write(table, ids, vals, op))


def test_tables_beyond_the_vmem_budget_take_a_second_walk(monkeypatch):
    from hivemall_tpu.kernels import run_write

    dims = DIMS + 2 * TILE   # a shape of its own: `_place` keeps traces
    rng = np.random.default_rng(12)
    ids = _ids("as_the_cells_rows", rng, dims)
    tables, values, ops = _state_tables(rng, ids, dims)
    assert run_write._walks(tables) == ((0, 1, 2, 3), (4,))   # 11 MiB, 4
    monkeypatch.setattr(run_write, "VMEM_BUDGET", 5 << 20)
    assert run_write._walks(tables) == ((0,), (1,), (2, 3), (4,))
    got = write_runs_kernel(tables, jnp.asarray(ids), values, ops,
                            interpret=True)
    for table, vals, op, out in zip(tables, values, ops, got):
        assert _bits(out) == _bits(_xla_write(table, ids, vals, op))


def test_tables_of_two_lengths_are_refused():
    ids = jnp.arange(8, dtype=jnp.int32)
    tables = [jnp.zeros((DIMS,), jnp.float32), jnp.zeros((RAGGED,), jnp.int8)]
    with pytest.raises(ValueError, match="no run-write kernel"):
        write_runs_kernel(tables, ids, [jnp.ones(8)] * 2, ["set", "max"],
                          interpret=True)


@pytest.mark.parametrize("dtype,shape", [
    (jnp.float16, (DIMS,)), (jnp.int32, (DIMS,)),   # types it does not patch
    (jnp.float32, (DIMS, 16)),                      # FM's V: rows
])
def test_what_the_kernel_does_not_serve_is_refused_and_stays_on_xla(dtype,
                                                                    shape):
    table = jnp.zeros(shape, dtype)
    ids = jnp.arange(64, dtype=jnp.int32)
    values = jnp.ones((64,) + shape[1:], jnp.float32)
    with pytest.raises(ValueError, match="no run-write kernel"):
        write_runs_kernel([table], ids, [values], ["set"], interpret=True)
    # however long against its block, and on a TPU
    assert len(shape) > 1 or write_path(dtype, 1 << 30, 8, "tpu") == "xla"
    out = write_runs(table, BlockRuns(ids, None, None), values)
    assert float(jnp.sum(out.astype(jnp.float32))) == values.size


# The rule as a table: (storage type, D, N, backend) -> path. The six
# cells' shapes (a 1,024-row block of Criteo's 39 features works on 40
# lanes), the lengths between, and what stays on XLA whatever its length.
WRITE_PATHS = [
    # adagrad_rda_criteo1tb: 2^29, bf16 w, two f32 sums, the s8 flag
    ("float32", 1 << 29, 40960, "tpu", "kernel"),
    ("bfloat16", 1 << 29, 40960, "tpu", "kernel"),
    ("int8", 1 << 29, 40960, "tpu", "kernel"),
    # arow_criteo1tb (replay, text) and _mix4: 2^28, bf16 w and cov, -mix's
    # f32 pending count, the s8 flag
    ("bfloat16", 1 << 28, 40960, "tpu", "kernel"),
    ("float32", 1 << 28, 40960, "tpu", "kernel"),
    ("int8", 1 << 28, 40960, "tpu", "kernel"),
    # fm_criteo1tb: w f32 and the flag at 2^23
    ("float32", 1 << 23, 40960, "tpu", "xla"),
    ("int8", 1 << 23, 40960, "tpu", "xla"),
    # ffm_criteo1tb: the linear tables at 2^23 (the 1.6 M-lane pair flag at
    # 2^28 is no `write_runs` call; by the rule it would stay too)
    ("float32", 1 << 23, 40960, "tpu", "xla"),
    ("int8", 1 << 28, 1638400, "tpu", "xla"),
    # parallel/sharded_train.py's stripes of 2^26
    ("float32", 1 << 26, 40960, "tpu", "xla"),
    ("bfloat16", 1 << 26, 40960, "tpu", "xla"),
    # an uncut 64-lane bucket
    ("float32", 1 << 28, 65536, "tpu", "kernel"),
    ("int8", 1 << 29, 65536, "tpu", "kernel"),
    # a small block over a table of middling length
    ("float32", 1 << 26, 4096, "tpu", "kernel"),
    ("float32", 1 << 20, 256, "tpu", "xla"),
    ("bfloat16", 1 << 10, 8, "tpu", "xla"),
    # types the kernel does not patch
    ("float16", 1 << 29, 40960, "tpu", "xla"),
    ("int32", 1 << 29, 40960, "tpu", "xla"),
    # every other backend, the cells' shapes among them
    ("float32", 1 << 29, 40960, "cpu", "xla"),
    ("bfloat16", 1 << 28, 40960, "cpu", "xla"),
    ("int8", 1 << 29, 40960, "gpu", "xla"),
]


@pytest.mark.parametrize("dtype,dims,lanes,backend,want", WRITE_PATHS)
def test_write_path_is_a_function_of_type_shapes_and_backend(
        dtype, dims, lanes, backend, want):
    assert write_path(jnp.dtype(dtype), dims, lanes, backend) == want


def test_the_rule_is_its_cost_model():
    """Where the stream XLA would add costs what the kernel's lanes cost
    over XLA's, the paths meet; the constants are `WRITE_COST`'s."""
    for name, cost in scatter.WRITE_COST.items():
        per_lane_ms = (cost["kernel_ns"] - cost["xla_ns"]) * 1e-6
        assert per_lane_ms > 0   # a lane is dearer down the kernel
        lanes = 40960
        meet = (scatter.KERNEL_FIXED_MS + lanes * per_lane_ms) \
            / cost["stream_ms"] * 2 ** 28
        assert write_path(name, int(meet * 1.01), lanes, "tpu") == "kernel"
        assert write_path(name, int(meet * 0.99), lanes, "tpu") == "xla"


STEP_CASES = ["arow_bf16", "arow_bf16_track_deltas", "adagrad_rda",
              "adagrad_rda_track_deltas"]


@pytest.mark.parametrize("kind", ["heavy_duplicates", "pad_lanes", "mixed"])
@pytest.mark.parametrize("case", STEP_CASES)
def test_step_with_the_kernel_equals_step_with_xla_write(monkeypatch, case,
                                                         kind):
    """`minibatch_step` with every table written through the kernel (bf16
    or f32 w and cov, f32 slots and the pending count, the s8 flag) against
    the same step on XLA's sorted write: every leaf of the state the same
    bits, and the loss."""
    rule, hyper, dtype, track, binary = CASES[case]
    state = _state(rule, dtype, track, seed=len(case))
    block = _block(kind, binary, seed=len(kind))
    # a step a path: jax finds one function's trace again
    step = lambda: jax.jit(make_train_fn(rule, hyper, mode="minibatch",
                                         track_deltas=track))
    want, want_loss = step()(state, *block)
    asked = []

    def interpreted(dtype, dims, lanes, backend):
        asked.append((jnp.dtype(dtype).name, dims, lanes))
        return "interpret"

    monkeypatch.setattr(scatter, "write_path", interpreted)
    got, got_loss = step()(state, *block)
    tables = 2 + rule.use_covariance + len(rule.slot_names) + track
    assert len(asked) == tables and {a[1] for a in asked} == {
        state.weights.shape[0]}
    assert _bits(want.weights) != _bits(state.weights)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and _bits(a) == _bits(b)
    assert float(got_loss) == float(want_loss)


@pytest.mark.parametrize("options,backend,write,tables", [
    # 2^24 entries over a [4, 8] block. On the CPU every table takes XLA's
    # write, whatever its length
    ("-dims 16777216 -mini_batch 4", None, "xla", 0),
    # the same call's record where the backend is a TPU
    ("-dims 16777216 -mini_batch 4", "tpu", "kernel:weights,covars,touched",
     3),
    ("-dims 16777216 -mini_batch 4 -mix local -mix_threshold 2", "tpu",
     "kernel:weights,covars,__delta__,touched", 4),
    # a table short against its block stays with XLA
    ("-dims 262144 -mini_batch 64", "tpu", "xla", 0),
    # the dense plan writes no runs, and the scan no block
    ("-dims 4096 -mini_batch 64", "tpu", "xla", 0),
    ("-dims 4096", "tpu", None, 0),
])
def test_train_call_says_how_its_tables_are_written(monkeypatch, options,
                                                    backend, write, tables):
    """`write` on `train.call` beside `apply`, and `train.kernel_write_lanes`
    beside `train.lanes_cut`: rows x lanes x the tables that go through the
    kernel. The record asks the rule the step asks; the test stands in for
    a TPU there (the step itself is traced on the CPU and takes XLA's
    write)."""
    from hivemall_tpu.core.engine import DELTA_SLOT
    from hivemall_tpu.models import base as mbase
    from hivemall_tpu.runtime.metrics import REGISTRY
    from hivemall_tpu.runtime.tracing import TRACER
    from hivemall_tpu.sql.registry import get_function

    if backend:
        monkeypatch.setattr(
            mbase, "kernel_written",
            lambda tables, dims, lanes, _: scatter.kernel_written(
                tables, dims, lanes, backend))
    if "-mix" in options:
        from hivemall_tpu.parallel import mix

        monkeypatch.setattr(mix, "mix_devices", lambda: jax.devices()[:2])
    rng = np.random.default_rng(0)
    rows = 128
    idx = rng.integers(0, 4096, size=(rows, 5))
    counters = {name: REGISTRY.counter("train", name)
                for name in ("kernel_write_lanes", "lanes_cut")}
    before = {name: c.value for name, c in counters.items()}
    TRACER.clear()
    get_function("train_arow")((idx, np.ones(idx.shape, np.float32)),
                               np.sign(rng.normal(size=rows)), options)
    (call,) = [sp for sp in TRACER.traces()[-1]["spans"]
               if sp["name"] == "train.call"]
    want = write and write.replace("__delta__", DELTA_SLOT)
    assert call["args"].get("write") == want
    lanes = call["args"].get("lanes", 0)       # 5 features on 8 lanes
    assert counters["kernel_write_lanes"].value \
        - before["kernel_write_lanes"] == rows * lanes * tables
    assert counters["lanes_cut"].value == before["lanes_cut"]


_IMPORT_PROBE = r"""
import json, sys
import numpy as np
from hivemall_tpu.sql.registry import get_function
from hivemall_tpu.runtime.tracing import TRACER

def pallas():
    return sorted(m for m in sys.modules if "pallas" in m)

found = {"after get_function": pallas() if get_function("train_fm") else None}
rng = np.random.default_rng(0)
idx = rng.integers(0, 4096, size=(64, 5))
val = np.ones(idx.shape, np.float32)
y = np.sign(rng.normal(size=64))
fields = np.broadcast_to(np.arange(5), idx.shape)
calls = {
    "train_fm": ((idx, val), y, "-dims 65536 -mini_batch 8 -factor 4 -c"),
    "train_ffm": ((idx, val, fields), y,
                  "-feature_hashing 12 -p 4096 -num_fields 5 -factor 2 "
                  "-mini_batch 8 -v_bits 14 -eta0_V 0.01"),
    "train_arow": ((idx, val), y, "-dims 16777216 -mini_batch 4"),
    "train_adagrad_rda": ((idx, val), y, "-dims 1048576 -mini_batch 8"),
}
writes = {}
for name, args in calls.items():
    TRACER.clear()
    get_function(name)(*args).model_rows()
    (call,) = [sp for tr in TRACER.traces() for sp in tr["spans"]
               if sp["name"] == "train.call"]
    writes[name] = call["args"].get("write")
    found["after " + name] = pallas()
print(json.dumps({"found": found, "writes": writes}))
"""


def test_no_pallas_is_imported_where_no_step_takes_the_kernel():
    """A process whose steps do not select the kernel (FM, FFM, every CPU
    run) imports nothing of Pallas: not at `hivemall_tpu`'s import, not by
    resolving a trainer, not by a whole `train_*` call. (`from
    jax.experimental import pallas` is a second of every process that
    makes it: PERF.md section 6, PR 39.) Its own process: this one has
    imported the kernel's module above."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..")]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep))}
    out = subprocess.run([sys.executable, "-c", _IMPORT_PROBE], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(report["found"]) == {
        "after get_function", "after train_fm", "after train_ffm",
        "after train_arow", "after train_adagrad_rda"}
    for where, modules in report["found"].items():
        assert modules == [], (where, modules)
    assert report["writes"] == {name: "xla" for name in report["writes"]}
    assert len(report["writes"]) == 4


# ---- the row form: a block's [4] row deltas added into a [D, 4] f32 table ----

ROWS_DIMS = 1 << 14     # 128 tiles of ROW_TILE rows


def _row_keys(kind, rng, dims):
    """A block's pair keys as `sorted_pairs` hands them to the kernel:
    ascending, every dropped lane `== dims` at the tail."""
    from hivemall_tpu.kernels.run_write import ROW_TILE as T

    if kind == "repeated_keys":             # 7 keys on 900 lanes
        ids = rng.choice(rng.choice(dims, 7, replace=False), 900)
    elif kind == "a_run_across_a_chunk":
        # lanes CHUNK-50 ... CHUNK+49 are one key: two grid steps add it
        ids = np.concatenate([rng.integers(0, 40 * T, CHUNK - 50),
                              np.full(100, 41 * T + 5),
                              rng.integers(42 * T, dims, 300)])
    elif kind == "a_tile_shared_by_two_chunks":
        # lanes CHUNK-60 ... CHUNK+59 are 120 rows of ONE tile, some twice
        ids = np.concatenate([rng.integers(0, 50 * T, CHUNK - 60),
                              50 * T + rng.integers(0, T, 120),
                              rng.integers(51 * T, dims, 200)])
    elif kind == "dropped_key_at_the_tail":
        ids = np.concatenate([rng.integers(0, dims, 1000),
                              np.full(700, dims)])
    elif kind == "keys_in_the_first_and_last_tile":
        ids = np.concatenate([[0, 0, 1, T - 1, dims - T, dims - 2, dims - 1,
                               dims - 1],
                              rng.integers(0, T, 50),
                              rng.integers(dims - T, dims, 50)])
    elif kind == "all_keys_differ":
        ids = rng.choice(dims, 3000, replace=False)
    elif kind == "as_the_cells_pairs":
        # a third of the lanes on keys every row carries, the rest hashed
        # ranks, 1/40 dropped
        rank = np.exp(rng.random(2000) * np.log(dims)).astype(np.int64)
        ids = np.concatenate([np.repeat(np.arange(13) * 7919 % dims, 80),
                              rank * 2654435761 % dims, np.full(80, dims)])
    return np.sort(np.asarray(ids, np.int64)).astype(np.int32)


ROW_KINDS = ["repeated_keys", "a_run_across_a_chunk",
             "a_tile_shared_by_two_chunks", "dropped_key_at_the_tail",
             "keys_in_the_first_and_last_tile", "all_keys_differ",
             "as_the_cells_pairs"]


def _rounding_bound(table, ids, deltas):
    """Per entry, what two float32 sums of the same terms in two orders may
    differ by: the terms' magnitude times their count times float32's
    epsilon."""
    dims = table.shape[0]
    live = ids < dims
    mag = np.abs(table).astype(np.float64)
    count = np.zeros(dims)
    np.add.at(mag, ids[live], np.abs(deltas[live]))
    np.add.at(count, ids[live], 1)
    return mag * (count[:, None] + 1) * np.finfo(np.float32).eps


@pytest.mark.parametrize("kind", ROW_KINDS)
def test_row_kernel_adds_as_xla_to_float32_rounding(kind):
    from hivemall_tpu.kernels.run_write import add_rows_kernel

    rng = np.random.default_rng(len(kind))
    ids = _row_keys(kind, rng, ROWS_DIMS)
    table = rng.normal(size=(ROWS_DIMS, 4)).astype(np.float32)
    deltas = (rng.normal(size=(ids.size, 4)) * 3).astype(np.float32)
    got = np.asarray(add_rows_kernel(
        jnp.asarray(table), jnp.asarray(ids),
        [jnp.asarray(deltas[:, f]) for f in range(4)], interpret=True))
    xla = np.asarray(jnp.asarray(table).at[ids].add(deltas, mode="drop"))
    live = ids < ROWS_DIMS
    want = table.astype(np.float64)
    np.add.at(want, ids[live], deltas[live].astype(np.float64))
    bound = _rounding_bound(table, ids, deltas)
    assert np.all(np.abs(got - want) <= bound)
    assert np.all(np.abs(xla - want) <= bound)
    # a row no lane addresses keeps its bits; one that a single lane adds
    # to is one float32 addition either way
    count = np.bincount(ids[live], minlength=ROWS_DIMS)
    assert _bits(got[count == 0]) == _bits(table[count == 0])
    assert _bits(got[count == 1]) == _bits(xla[count == 1])
    assert (count > 1).any() or kind == "all_keys_differ"


def test_row_kernel_refuses_what_it_does_not_serve():
    from hivemall_tpu.kernels.run_write import add_rows_kernel

    ids = jnp.arange(64, dtype=jnp.int32)
    cols = [jnp.ones(64)] * 4
    for table in (jnp.zeros((1 << 12, 16)),           # FM's 16-wide V
                  jnp.zeros((1 << 12, 4), jnp.bfloat16),
                  jnp.zeros((1 << 12,)),              # a [D] table
                  jnp.zeros(((1 << 12) + 64, 4))):    # a tile short
        with pytest.raises(ValueError, match="no run-write kernel"):
            add_rows_kernel(table, ids, cols[:table.shape[-1]],
                            interpret=True)


# The row rule as a table: (storage type, D, k, N, backend) -> path. The
# FFM cell's V (1,024 rows of 40 x 40 pair lanes into [2^28, 4] f32), the
# shapes between, and what stays on XLA whatever its length.
ROW_WRITE_PATHS = [
    ("float32", 1 << 28, 4, 1638400, "tpu", "kernel"),
    ("float32", 1 << 28, 4, 409600, "tpu", "kernel"),
    # every other backend
    ("float32", 1 << 28, 4, 1638400, "cpu", "xla"),
    ("float32", 1 << 28, 4, 1638400, "gpu", "xla"),
    # FM's V, [2^23, 16] (its layout is `{0,1:T(8,128)}`), and k = 8
    ("float32", 1 << 23, 16, 65536, "tpu", "xla"),
    ("float32", 1 << 28, 8, 1638400, "tpu", "xla"),
    # types the kernel does not add in, and a length short of a tile
    ("bfloat16", 1 << 28, 4, 1638400, "tpu", "xla"),
    ("float32", (1 << 28) + 64, 4, 1638400, "tpu", "xla"),
]


@pytest.mark.parametrize("dtype,dims,k,lanes,backend,want", ROW_WRITE_PATHS)
def test_row_write_path_is_a_function_of_type_shapes_and_backend(
        dtype, dims, k, lanes, backend, want):
    assert scatter.row_write_path(jnp.dtype(dtype), dims, k, lanes,
                                  backend) == want


def test_the_row_rule_is_its_cost_model():
    """The kernel is taken where its lanes and tiles cost less than XLA's
    lanes and stream; a small block into a small table, where the launch
    and the plan dominate, stays with XLA."""
    cost = scatter.ROW_WRITE_COST[("float32", 4)]
    for log2 in (20, 24, 28):
        dims = 1 << log2
        for lanes in (1 << 10, 409600, 1638400):
            xla = lanes * cost["xla_ns"] * 1e-6 \
                + cost["stream_ms"] * dims / 2 ** 28
            tiles = min(lanes * cost["tiles_per_lane"], dims / 128)
            kernel = scatter.KERNEL_FIXED_MS + 1e-6 * (
                lanes * cost["kernel_ns"] + tiles * cost["tile_ns"])
            want = "kernel" if xla > kernel else "xla"
            assert scatter.row_write_path(jnp.float32, dims, 4, lanes,
                                          "tpu") == want
    assert scatter.row_write_path(jnp.float32, 1 << 12, 4, 64, "tpu") \
        == "xla"


def _ffm_block(rng, rows, width, dims, fields):
    idx = rng.integers(0, dims, size=(rows, width)).astype(np.int32)
    idx[:, :3] = np.arange(3)             # ids every row carries
    val = np.ones((rows, width), np.float32)
    val[:, width - 2:] = 0.0              # two padding lanes a row
    idx[:, width - 2:] = dims
    fld = np.broadcast_to(np.arange(width) % fields, (rows, width))
    y = np.sign(rng.normal(size=rows)).astype(np.float32)
    return idx, val, fld.astype(np.int32), y


FFM_STEP_CASES = {
    # name -> (FFMHyper arguments, row_chunk)
    "ftrl": (dict(), None),
    "sgd_w": (dict(use_ftrl=False), None),
    "no_linear": (dict(linear_coeff=False), None),
    "row_tiles": (dict(global_bias=True), 16),
}


@pytest.mark.parametrize("case", sorted(FFM_STEP_CASES))
def test_ffm_step_with_the_row_kernel_adds_as_xla(monkeypatch, case):
    """FFM's `-mini_batch` step with V's rows added through the kernel
    (`sorted_pairs`, the rule patched to answer "interpret") against the
    same step on XLA's scatter-adds: V and gg to float32 rounding (the
    same sums in another order), the flags and the linear term as XLA's,
    the loss."""
    from hivemall_tpu.models import ffm as F

    args, row_chunk = FFM_STEP_CASES[case]
    hyper = F.FFMHyper(factors=4, num_features=1 << 10, num_fields=6,
                       v_dims=1 << 12, eta0_v=0.05, **args)
    rng = np.random.default_rng(len(case))
    block = _ffm_block(rng, 64, 8, hyper.num_features, hyper.num_fields)
    step = lambda: jax.jit(F.make_ffm_step(hyper, "minibatch", row_chunk,
                                           jit=False))
    want, want_loss = step()(F.init_ffm_state(hyper), *block)
    asked = []

    def interpreted(dtype, dims, k, lanes, backend):
        asked.append((jnp.dtype(dtype).name, dims, k, lanes))
        return "interpret"

    monkeypatch.setattr(scatter, "row_write_path", interpreted)
    got, got_loss = step()(F.init_ffm_state(hyper), *block)
    assert asked == [("float32", hyper.v_dims, 4, 64 * 8 * 8)]
    start = np.asarray(F.init_ffm_state(hyper).v)
    assert (np.asarray(want.v) != start).any()
    for name in ("v", "v_gg", "w", "z", "n", "w0"):
        np.testing.assert_allclose(np.asarray(getattr(got, name)),
                                   np.asarray(getattr(want, name)),
                                   rtol=2e-6, atol=1e-7, err_msg=name)
    for name in ("v_touched", "touched", "step"):
        assert _bits(getattr(got, name)) == _bits(getattr(want, name))
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-6)


def test_ffm_scan_and_stripe_steps_never_ask_the_row_rule(monkeypatch):
    """The scan adds a row at a time and a stripe a row group: neither
    writes a block's pair lanes, so neither asks."""
    from hivemall_tpu.models import ffm as F
    from hivemall_tpu.parallel.mesh import make_mesh
    from hivemall_tpu.parallel.sharded_train import FFMShardedTrainer

    def refused(*a):
        raise AssertionError("the row rule was asked")

    monkeypatch.setattr(scatter, "row_write_path", refused)
    hyper = F.FFMHyper(factors=4, num_features=1 << 10, num_fields=6,
                       v_dims=1 << 12, eta0_v=0.05)
    block = _ffm_block(np.random.default_rng(3), 32, 8, hyper.num_features,
                       hyper.num_fields)
    _, loss = F.make_ffm_step(hyper, "scan")(F.init_ffm_state(hyper),
                                             *block)
    assert np.isfinite(float(loss))
    tr = FFMShardedTrainer(hyper, make_mesh(2))
    _, loss = tr.step(tr.init(), *block)
    assert np.isfinite(float(loss))


@pytest.mark.parametrize("rule,write,lanes_a_row", [
    # on the CPU V is added by XLA's scatter-add
    (None, "xla", 0),
    # where the rule sends V to the kernel (here through the interpreter)
    ("interpret", "kernel:v", 8 * 8),
])
def test_train_ffm_says_how_v_is_written(monkeypatch, rule, write,
                                         lanes_a_row):
    """`write` on `train_ffm`'s `train.call`, and `train.kernel_write_lanes`
    beside `hivemall.ffm.examples`: rows x the pair lanes handed to the
    kernel."""
    from hivemall_tpu.runtime.metrics import REGISTRY
    from hivemall_tpu.runtime.tracing import TRACER
    from hivemall_tpu.sql.registry import get_function

    if rule:
        monkeypatch.setattr(scatter, "row_write_path", lambda *a: rule)
    rng = np.random.default_rng(0)
    rows = 96
    idx = rng.integers(0, 4096, size=(rows, 6))
    fields = np.broadcast_to(np.arange(6), idx.shape)
    counter = REGISTRY.counter("train", "kernel_write_lanes")
    before = counter.value
    TRACER.clear()
    get_function("train_ffm")(
        (idx, np.ones(idx.shape, np.float32), fields),
        np.sign(rng.normal(size=rows)),
        "-feature_hashing 12 -num_fields 6 -factor 4 -v_bits 12 "
        "-mini_batch 32 -eta0_V 0.01")
    (call,) = [sp for sp in TRACER.traces()[-1]["spans"]
               if sp["name"] == "train.call"]
    assert call["args"]["write"] == write
    assert counter.value - before == rows * lanes_a_row
