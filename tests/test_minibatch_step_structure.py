"""The `-mini_batch` step has no O(dims) pass where the table is long against
the block (`engine.apply_strategy` says `batch_local`): nothing it computes
is as long as the table but the in-place writes of the touched entries.

(a) on the CPU, by walking the step's jaxpr; (b) for a described v5e, by
compiling the benchmark's AROW step (2^28 dims, bfloat16 tables, a
[1024, 64] block, donated state) and reading the compiler's own account.
The FM step (models/fm.py, one plan at every shape) is held to the same, at
the benchmark's 2^23 dims and at the reference's default 2^24.
Nothing runs in (b): on-chip-measurement guide, section 2. The topology is
described inside a module-scoped fixture, never at import.

Since PR 39 a table long against the block is written by the Pallas
run-write kernel where the backend is a TPU (`ops/scatter.write_path`); (b)
also compiles two cells' steps with the kernels in them (the rule asked as
on a TPU: `jax.default_backend()` is the CPU's here) and holds them to the
same: every table aliased to its result, no copy of one, nothing as long
as a table but the kernels' calls.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from hivemall_tpu.core.engine import (DELTA_SLOT, apply_strategy,
                                      make_train_fn)
from hivemall_tpu.core.state import init_linear_state
from hivemall_tpu.models import classifier as C
from hivemall_tpu.models import fm as FM
from hivemall_tpu.models import regression as R

DIMS = 1 << 20

# name -> (rule, hyper, table dtype, track_deltas)
STEPS = {
    "arow_f32": (C.AROW, {"r": 0.1}, jnp.float32, False),
    "arow_bf16": (C.AROW, {"r": 0.1}, jnp.bfloat16, False),
    "arow_bf16_track_deltas": (C.AROW, {"r": 0.1}, jnp.bfloat16, True),
    "perceptron": (C.PERCEPTRON, {}, jnp.float32, False),
    "adagrad_regr": (R.ADAGRAD_REGR, {"eta": 1.0, "eps": 1.0, "scale": 100.0},
                     jnp.float32, False),
    "adagrad_rda": (C.ADAGRAD_RDA,
                    {"eta": 0.1, "lambda": 1e-6, "scale": 100.0},
                    jnp.float32, False),
    "pa1a_regr_globals": (R.PA1A_REGR, {"c": 1.0, "epsilon": 0.01},
                          jnp.float32, False),
}


def _state_shape(rule, dims, dtype, track=False):
    slots = tuple(rule.slot_names) + ((DELTA_SLOT,) if track else ())
    return jax.eval_shape(lambda: init_linear_state(
        dims, use_covariance=rule.use_covariance, slot_names=slots,
        global_names=rule.global_names, dtype=dtype))


def _block(rows, width):
    return (jax.ShapeDtypeStruct((rows, width), jnp.int32),
            jax.ShapeDtypeStruct((rows, width), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32))


def _table_long_equations(jaxpr, dims):
    """(primitive, shape) of every equation, nested ones too, that yields an
    array with a `dims`-long axis."""
    found = []
    for eqn in jaxpr.eqns:
        inner = [p for k, v in eqn.params.items()
                 if k != "update_jaxpr"   # a scatter's combiner is no body
                 for p in (v if isinstance(v, (list, tuple)) else (v,))
                 if hasattr(p, "eqns") or hasattr(p, "jaxpr")]
        for sub in inner:
            found += _table_long_equations(getattr(sub, "jaxpr", sub), dims)
        if inner:   # a call's or a loop's own results are its body's
            continue
        for out in eqn.outvars:
            if dims in getattr(out.aval, "shape", ()):
                found.append((eqn.primitive.name, out.aval.shape))
    return found


@pytest.mark.parametrize("name", sorted(STEPS))
def test_only_the_table_writes_are_table_long(name):
    rule, hyper, dtype, track = STEPS[name]
    step = make_train_fn(rule, hyper, mode="minibatch", track_deltas=track)
    state = _state_shape(rule, DIMS, dtype, track)
    jaxpr = jax.make_jaxpr(step)(state, *_block(32, 8))
    long = _table_long_equations(jaxpr.jaxpr, DIMS)
    # one write each: weights, touched (a max), covariances, every slot
    tables = 2 + bool(rule.use_covariance) + len(rule.slot_names) + track
    assert sorted(p for p, _ in long) \
        == ["scatter"] * (tables - 1) + ["scatter-max"], long


FM_STEPS = {
    # name -> FMHyper arguments
    "fm_k10": dict(factors=10, classification=True),
    "fm_k8_regression": dict(factors=8),
    "fm_k10_adareg": dict(factors=10, classification=True, adareg=True),
}


def _fm_args(hyper, dims, rows, width):
    return (jax.eval_shape(lambda: FM.init_fm_state(dims, hyper)),
            *_block(rows, width), jax.ShapeDtypeStruct((rows,), jnp.float32))


@pytest.mark.parametrize("name", sorted(FM_STEPS))
def test_fm_only_the_table_writes_are_table_long(name):
    """w, V (as rows) and touched are written in place; the two gathers
    read the state's own tables (operands, not results); nothing else the
    step computes has a `dims`-long axis."""
    hyper = FM.FMHyper(**FM_STEPS[name])
    step = FM.make_fm_step(hyper, "minibatch", jit=False)
    jaxpr = jax.make_jaxpr(step)(*_fm_args(hyper, DIMS, 32, 8))
    long = _table_long_equations(jaxpr.jaxpr, DIMS)
    assert sorted(long) == [
        ("scatter", (DIMS,)), ("scatter", (DIMS, hyper.padded_factors)),
        ("scatter-max", (DIMS,))], long


def test_the_walk_sees_a_dense_pass():
    """The parent's formula, as a control: the walk reports it."""
    def dense(w, idx, dw):
        total = jnp.zeros(w.shape, jnp.float32).at[idx].add(dw, mode="drop")
        return (w.astype(jnp.float32) + total).astype(w.dtype)

    jaxpr = jax.make_jaxpr(dense)(
        jax.ShapeDtypeStruct((DIMS,), jnp.bfloat16),
        jax.ShapeDtypeStruct((64,), jnp.int32),
        jax.ShapeDtypeStruct((64,), jnp.float32))
    names = [p for p, _ in _table_long_equations(jaxpr.jaxpr, DIMS)]
    assert "scatter-add" in names and "convert_element_type" in names


def test_block_local_step_carries_its_scopes_and_packs_nothing():
    from hivemall_tpu.runtime import tracing

    state = _state_shape(C.AROW, DIMS, jnp.bfloat16)
    lowered = jax.jit(make_train_fn(C.AROW, {"r": 0.1}, mode="minibatch")) \
        .lower(state, *_block(32, 8))
    scopes = set(re.findall(r"hm\.[a-z_]+", lowered.as_text(debug_info=True)))
    assert scopes == set(tracing.LINEAR_SCOPES) - {tracing.SCOPE_PACK_TABLES}
    assert not re.search(r"hm\.", lowered.as_text())   # metadata only


@pytest.mark.parametrize("options,want", [
    ("-dims 131072 -mini_batch 4", "batch_local"),
    ("-dims 4096 -mini_batch 64", "dense"),
    ("-dims 4096", None),                       # the scan has no such stage
])
def test_train_call_span_says_which_strategy_ran(options, want):
    import numpy as np

    from hivemall_tpu.models.classifier import train_arow
    from hivemall_tpu.runtime.tracing import SPAN_CALL, TRACER

    rng = np.random.default_rng(0)
    idx = rng.integers(0, 4096, size=(128, 5))
    model = train_arow((idx, np.ones(idx.shape, np.float32)),
                       np.sign(rng.normal(size=128)), options)
    call = next(sp for sp in TRACER.traces()[-1]["spans"]
                if sp["name"] == SPAN_CALL)
    assert call["args"].get("apply") == want
    if want:
        block = int(options.split()[-1]) * model.block_width
        assert apply_strategy(model.dims, block) == want


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_uncached(lowered):
    """Compiled for the described chip with the persistent cache off: an
    entry written for a device that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


# instructions that only name or pass on a table do no pass over it
PASSES_ON = {"parameter", "tuple", "get-tuple-element", "bitcast"}


def _instructions(text):
    """(result type, opcode, line) of every instruction of a compiled
    module: `%name = type[shape]{layout} opcode(operands), ...`."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\(?[^=]*?\)?) ([\w\-]+)\(",
                     line)
        if m:
            yield m.group(1), m.group(2), line.strip()


def test_compiled_cell_step_has_no_table_long_scratch(one_chip):
    dims, rows, width = 1 << 28, 1024, 64
    assert apply_strategy(dims, rows * width) == "batch_local"
    on = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    step = make_train_fn(C.AROW, {"r": 0.1}, mode="minibatch")
    compiled = _compile_uncached(jax.jit(step, donate_argnums=(0,)).lower(
        on(_state_shape(C.AROW, dims, jnp.bfloat16)),
        *on(_block(rows, width))))
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20
    long = [(opcode, line)
            for result, opcode, line in _instructions(compiled.as_text())
            if f"[{dims}]" in result and opcode not in PASSES_ON]
    assert long, "the step writes three tables"
    for opcode, line in long:
        assert opcode in ("scatter", "fusion"), line[:200]
        if opcode == "fusion":   # the fusion that holds a scatter, alone
            assert re.search(r'op_name="[^"]*/scatter(-max)?"', line), line[:200]


# name -> (rule, hyper, dims, track_deltas): cells' steps on the 40 lanes
# their rows fill, with every kind of table the kernel patches
KERNEL_CELL_STEPS = {
    "arow_mix_2^28": (C.AROW, {"r": 0.1}, 1 << 28, True),
    "adagrad_rda_2^29": (C.ADAGRAD_RDA,
                         {"eta": 0.1, "lambda": 1e-6, "scale": 100.0},
                         1 << 29, False),
}


@pytest.mark.parametrize("name", sorted(KERNEL_CELL_STEPS))
def test_compiled_cell_step_writes_in_place_through_the_kernel(
        monkeypatch, one_chip, name):
    """The cell's step compiled for the described v5e as a TPU process
    traces it: one kernel call for the tables the rule sends there, every
    table aliased to its result (the kernel's table operand is the state's own
    buffer: no copy of a table, in or out), scratch far under a table, and
    nothing else as long as a table but XLA's write of a table the rule
    keeps."""
    from hivemall_tpu.core.state import linear_tables
    from hivemall_tpu.ops import scatter

    rule, hyper, dims, track = KERNEL_CELL_STEPS[name]
    rows, width = 1024, 40
    by_rule = scatter.write_path
    monkeypatch.setattr(scatter, "write_path",
                        lambda dtype, d, n, _: by_rule(dtype, d, n, "tpu"))
    on = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    state = _state_shape(rule, dims, jnp.bfloat16, track)
    step = make_train_fn(rule, hyper, mode="minibatch", track_deltas=track)
    compiled = _compile_uncached(jax.jit(step, donate_argnums=(0,)).lower(
        on(state), *on(_block(rows, width))))
    m = compiled.memory_analysis()
    tables = linear_tables(state)
    assert m.alias_size_in_bytes >= sum(
        t.size * t.dtype.itemsize for t in tables.values())
    assert m.temp_size_in_bytes < 64 << 20
    want = {}
    for t in tables.values():
        if by_rule(t.dtype, dims, rows * width, "tpu") == "kernel":
            want[t.dtype.name] = want.get(t.dtype.name, 0) + 1
    assert sum(want.values()) >= len(tables) - 1   # at most the flag stays
    kernels, xla_writes = {}, 0
    for result, opcode, line in _instructions(compiled.as_text()):
        if f"[{dims}]" not in result or opcode in PASSES_ON:
            continue
        kernel = re.search(r"%run_scatter_write_((?:[a-z]+\d+_?)+)", line)
        if opcode == "custom-call" and kernel:   # one call, a type a table
            assert "tpu_custom_call" in line, line[:200]
            for name in kernel.group(1).rstrip("_").split("_"):
                kernels[name] = kernels.get(name, 0) + 1
        else:   # XLA's write of a table the rule keeps, alone
            assert opcode == "scatter" or (
                opcode == "fusion" and re.search(
                    r'op_name="[^"]*/scatter(-max)?"', line)), line[:200]
            xla_writes += 1
    assert kernels == want
    assert xla_writes <= 2 * (len(tables) - sum(want.values()))


def _compile_fm_cell_step(one_chip, dims):
    rows, width = 1024, 64
    hyper = FM.FMHyper(factors=10, classification=True)
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        _fm_args(hyper, dims, rows, width))
    return _compile_uncached(FM.make_fm_step(hyper, "minibatch").lower(*args))


def test_compiled_fm_cell_step_has_no_table_long_scratch(one_chip):
    """The benchmark's FM step (2^23 dims, k = 10 in 16 lanes, a [1024, 64]
    block, donated state): 5 MB of temporaries where the dense plan held
    9.16 GB, V touched by its row gather and ONE in-place scatter, never
    through the flat `[dims * 16]` view (a relayout of the whole table)."""
    dims, lanes = 1 << 23, 16
    compiled = _compile_fm_cell_step(one_chip, dims)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    assert "hm.pack_tables" not in text
    # the compiler's own moves of the [dims] w and touched tables into its
    # faster memory and back, around their scatters: 40 MiB, no pass over V
    moves = {"slice-start", "slice-done", "copy-start", "copy-done",
             "custom-call"}
    v_writes = []
    for result, opcode, line in _instructions(text):
        if opcode in PASSES_ON:
            continue
        scatter = opcode == "scatter" or (opcode == "fusion" and re.search(
            r'op_name="[^"]*/scatter(-max)?"', line))
        if f"[{dims},{lanes}]" in result or f"[{dims * lanes}]" in result:
            assert scatter, line[:200]
            v_writes.append(opcode)
        elif f"[{dims}]" in result:
            assert scatter or opcode in moves, line[:200]
    assert v_writes.count("fusion") == 1, v_writes


def test_fm_step_compiles_at_the_reference_default_dims(one_chip):
    """`train_fm -mini_batch` at 2^24 dims, the reference's default
    capacity: refused before (17.1 GiB of 15.75), now step and state with a
    spare state beside them stay under three quarters of the chip."""
    dims = 1 << 24
    m = _compile_fm_cell_step(one_chip, dims).memory_analysis()
    assert m.temp_size_in_bytes < 64 << 20
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    state_bytes = dims * (16 * 4 + 4 + 1)
    assert peak >= state_bytes
    assert peak + state_bytes <= 0.75 * 15.75 * 2 ** 30, (peak, state_bytes)


# close()'s emission at the cell's sizes (core/emission.py): the mask's
# packing and the chunked gather hold no table-long scratch either. Here,
# not in a file of their own: one worker describes the topology.
EMISSION_PROGRAMS = {
    # name -> (program, argument shapes, most temporary bytes)
    "mask_2^28": ("_pack_mask", lambda S, c: (S((1 << 28,), jnp.int8), None),
                  1 << 20),
    # filter_zero writes one flag an entry first (a pred[2^28])
    "mask_nonzero_2^28": ("_pack_mask", lambda S, c: (
        S((1 << 28,), jnp.int8), S((1 << 28,), jnp.bfloat16)),
        (256 << 20) + (1 << 20)),
    "mask_odd_dims": ("_pack_mask", lambda S, c: (
        S((100_000_003,), jnp.int8), None), 1 << 20),
    "gather_arow_bf16": ("_gather_rows", lambda S, c: (
        (S((1 << 28,), jnp.bfloat16), S((1 << 28,), jnp.bfloat16)),
        S((c,), jnp.int32)), 1 << 20),
    "gather_fm_rows": ("_gather_rows", lambda S, c: (
        (S((1 << 23,), jnp.float32), S((1 << 23, 16), jnp.float32)),
        S((c,), jnp.int32)), 1 << 20),
}


@pytest.mark.parametrize("name", sorted(EMISSION_PROGRAMS))
def test_compiled_emission_program_has_no_table_long_scratch(one_chip, name):
    from hivemall_tpu.core import emission

    program, shapes, most = EMISSION_PROGRAMS[name]
    S = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                  sharding=one_chip)
    args = shapes(S, emission.GATHER_CHUNK)
    m = _compile_uncached(
        getattr(emission, program).lower(*args)).memory_analysis()
    assert m.temp_size_in_bytes <= most
    dims = jax.tree_util.tree_leaves(args)[0].shape[0]
    if program == "_pack_mask":   # one bit an entry comes out (+ a tile)
        assert 0 <= m.output_size_in_bytes - 4 * -(-dims // 32) < 4096
    else:                         # a chunk's rows, compact
        assert m.output_size_in_bytes <= 1.07 * sum(
            emission.GATHER_CHUNK * t.dtype.itemsize
            * (t.shape[1] if t.ndim > 1 else 1) for t in args[0])
