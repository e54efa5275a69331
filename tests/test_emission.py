"""`model_rows()` on device tables (core/emission.py): the mask is packed and
the values are gathered where the tables are, and what comes out is bit for
bit what the parent's whole-table copy and host selection gave, which is
kept here as the plain reference."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from hivemall_tpu.core import emission as em  # noqa: E402
from hivemall_tpu.core.state import LinearState, model_rows  # noqa: E402
from hivemall_tpu.parallel import mix as pmix  # noqa: E402
from hivemall_tpu.runtime.metrics import REGISTRY  # noqa: E402
from hivemall_tpu.runtime.tracing import TRACER  # noqa: E402
from hivemall_tpu.sql.registry import get_function  # noqa: E402

C = 64   # the gather's chunk in these tests


@pytest.fixture(autouse=True)
def small_chunk(monkeypatch):
    monkeypatch.setattr(em, "GATHER_CHUNK", C)


def host_rows(touched, tables, filter_zero=False):
    """The parent's model_rows(): whole tables on the host, `nonzero`, one
    index pass a table."""
    touched = np.asarray(touched)
    tables = [np.asarray(t) for t in tables]
    keep = touched != 0
    if filter_zero:
        keep &= tables[0] != 0.0
    feats = np.nonzero(keep)[0].astype(np.int64)
    return (feats,) + tuple(t[feats] for t in tables)


def same_bits(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert isinstance(g, np.ndarray)
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
        assert g.tobytes() == w.tobytes()


def emitted(call):
    """(call's result, its `emit.model_rows` trace, the counters' deltas)."""
    names = ("emit.d2h_bytes", "emit.rows", "emit.gather_chunks")
    before = REGISTRY.snapshot()
    TRACER.clear()
    out = call()
    after = REGISTRY.snapshot()
    (trace,) = [t for t in TRACER.traces() if t["root"] == "emit.model_rows"]
    return out, trace, {k: after.get(k, 0) - before.get(k, 0) for k in names}


def root_args(trace):
    (root,) = [s for s in trace["spans"] if s["name"] == "emit.model_rows"]
    return root["args"]


def linear_state(dims, rows_out, dtype, covars, filter_zero, seed=0,
                 on_host=False):
    """A state that emits exactly `rows_out` rows; under `filter_zero`
    seven more entries are touched and hold a zero weight. The first and
    the last entry of the table are among the emitted ones."""
    rng = np.random.default_rng([seed, dims, rows_out])
    extra = 7 if filter_zero else 0
    ids = rng.choice(dims - 2, size=max(rows_out + extra - 2, 0),
                     replace=False) + 1
    ids = np.concatenate([[0, dims - 1], ids])[:rows_out + extra]
    w = np.zeros(dims, np.float32)
    w[ids[:rows_out]] = rng.standard_normal(rows_out) + 3.0
    cov = np.ones(dims, np.float32)
    cov[ids] = rng.random(len(ids)) + 0.25
    touched = np.zeros(dims, np.int8)
    touched[ids] = 1
    # untouched entries hold values too: they must not come out
    stray = (ids + 1) % dims
    stray = stray[touched[stray] == 0]
    w[stray], cov[stray] = 0.5, 2.0
    place = (lambda a: a) if on_host else jnp.asarray
    return LinearState(
        weights=place(w.astype(dtype)),
        covars=place(cov.astype(dtype)) if covars else None, slots={},
        touched=place(touched), step=place(np.zeros((), np.int32)),
        globals={})


@pytest.mark.parametrize("rows_out", [0, 1, C - 1, C, C + 1, 2 * C + 22])
@pytest.mark.parametrize("dims", [1 << 16, 100_003])
@pytest.mark.parametrize("filter_zero", [False, True], ids=["all", "nonzero"])
@pytest.mark.parametrize("covars", [True, False], ids=["cov", "nocov"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_device_selection_equals_the_host_selection(dtype, covars,
                                                    filter_zero, dims,
                                                    rows_out):
    st = linear_state(dims, rows_out, jnp.dtype(dtype), covars, filter_zero)
    got, trace, counted = emitted(lambda: model_rows(st, filter_zero))
    tables = [st.weights] + ([st.covars] if covars else [])
    want = host_rows(st.touched, tables, filter_zero)
    assert len(want[0]) == rows_out
    same_bits(got, want)
    args = root_args(trace)
    chunks = -(-rows_out // C)
    assert (args["select"], args["chunks"], args["rows_out"]) \
        == ("device", chunks, rows_out)
    assert args["table_dtype"] == dtype
    assert args["h2d_bytes"] == chunks * C * 4
    assert counted["emit.gather_chunks"] == chunks
    assert counted["emit.rows"] == rows_out


@pytest.mark.parametrize("dims", [1, 5, 31, 32, 33, 1000])
def test_tables_shorter_than_a_word_or_a_chunk(dims):
    """`-dims` is any integer: the last slab is short, a table under 32
    entries has fewer planes than a word has bits, and a table shorter
    than the chunk is gathered at its own length."""
    rng = np.random.default_rng(dims)
    for rows_out in sorted({0, 1, dims // 2, dims}):
        touched = np.zeros(dims, np.int8)
        touched[rng.choice(dims, rows_out, replace=False)] = 1
        w = rng.standard_normal(dims).astype(np.float32)
        st = LinearState(weights=jnp.asarray(w), covars=None, slots={},
                         touched=jnp.asarray(touched),
                         step=jnp.zeros((), jnp.int32), globals={})
        got, trace, _ = emitted(lambda: model_rows(st))
        same_bits(got, host_rows(touched, [w]))
        assert root_args(trace)["chunks"] == -(-rows_out // min(C, dims))


@pytest.mark.parametrize("segment", [1 << 20, 2, 1])
def test_mask_to_ids_on_dense_and_empty_words(monkeypatch, segment):
    """Every bit set, none set, and one word's 32 bits: the rounds take a
    word's bits lowest first and the planes come out in id order, whether
    one thread expands the mask or a thread a run of words."""
    monkeypatch.setattr(em, "SEGMENT_WORDS", segment)
    rng = np.random.default_rng(segment)
    sparse = rng.integers(0, 1 << 32, 41, dtype=np.uint64).astype(np.uint32)
    sparse[rng.random(41) < 0.7] = 0
    for words in [np.full(3, 0xFFFFFFFF, np.uint32), np.zeros(4, np.uint32),
                  np.array([0, 0x80000001, 0, 0xFFFFFFFF, 6], np.uint32),
                  sparse]:
        n = len(words)
        want = np.array(sorted(b * n + j for j in range(n) for b in range(32)
                               if int(words[j]) >> b & 1), np.int32)
        got = em.mask_to_ids(words)
        assert got.dtype == np.int32
        assert np.array_equal(got, want)


@pytest.mark.parametrize("factors", [5, 10])
def test_fm_rides_the_same_helper(factors):
    """V's lane padding (16 physical factors) is sliced on the host."""
    rng = np.random.default_rng(factors)
    idx = [rng.integers(0, 4096, 6) for _ in range(200)]
    val = [rng.random(6).astype(np.float32) for _ in idx]
    model = get_function("train_fm")(
        (idx, val), rng.choice([-1, 1], 200),
        f"-c -factor {factors} -dims 4096 -mini_batch 16")
    st = model.state
    assert st.v.shape[1] > factors
    (w0, feats, w, v), trace, counted = emitted(model.model_rows)
    rf, rw, rv = host_rows(st.touched, [st.w, st.v])
    assert len(rf) > 2 * C
    assert w0 == float(st.w0) and isinstance(w0, float)
    same_bits((feats, w), (rf, rw))
    assert v.shape == (len(rf), factors)
    assert np.array_equal(v, rv[:, :factors])
    args = root_args(trace)
    assert (args["select"], args["chunks"]) == ("device", -(-len(rf) // C))
    tables = {s["args"]["table"] for s in trace["spans"]
              if s["name"] == "emit.d2h"}
    assert tables == {"mask", "w", "v", "w0"}


def test_native_scan_models_take_the_device_path():
    """The host backends hand their tables over as device arrays."""
    rng = np.random.default_rng(3)
    idx = [rng.integers(0, 512, 5) for _ in range(300)]
    val = [rng.random(5).astype(np.float32) for _ in idx]
    model = get_function("train_arow")(
        (idx, val), rng.choice([-1, 1], 300), "-dims 512 -native_scan")
    st = model.state
    assert isinstance(st.weights, jax.Array)
    got, trace, _ = emitted(model.model_rows)
    same_bits(got, host_rows(st.touched, [st.weights, st.covars]))
    assert root_args(trace)["select"] == "device"
    assert len(got[0]) > C


@pytest.mark.parametrize("filter_zero", [False, True], ids=["all", "nonzero"])
def test_numpy_tables_are_selected_on_the_host(filter_zero):
    """A state rebuilt by hand from host arrays: nothing to copy, so the
    host selects, and says so; the rows are the device path's."""
    on_host = linear_state(1 << 12, 300, np.dtype("float32"), True,
                           filter_zero, on_host=True)
    on_device = linear_state(1 << 12, 300, jnp.float32, True, filter_zero)
    got, trace, counted = emitted(lambda: model_rows(on_host, filter_zero))
    same_bits(got, model_rows(on_device, filter_zero))
    assert len(got[0]) == 300
    args = root_args(trace)
    assert (args["select"], args["chunks"], args["d2h_bytes"],
            args["h2d_bytes"]) == ("host", 0, 0, 0)
    assert counted["emit.d2h_bytes"] == 0
    assert [s["name"] for s in trace["spans"]
            if s["name"] != "emit.model_rows"] == ["emit.select"]


def test_shapes_and_bytes_do_not_follow_the_rows():
    """Two states of equal dims whose rows differ a hundredfold: the
    second call compiles nothing, and what crosses the bus is the mask
    plus the emitted rows' own bytes, a chunk's padding at most."""
    dims = 1 << 16
    few = linear_state(dims, 30, jnp.dtype("bfloat16"), True, False, seed=1)
    many = linear_state(dims, 3000, jnp.dtype("bfloat16"), True, False,
                        seed=2)
    _, trace, counted = emitted(lambda: model_rows(few))
    sizes = (em._pack_mask._cache_size(), em._gather_rows._cache_size())
    for st, rows_out in ((many, 3000), (few, 30)):
        got, trace, counted = emitted(lambda: model_rows(st))
        assert (em._pack_mask._cache_size(),
                em._gather_rows._cache_size()) == sizes
        assert not [e for s in trace["spans"] for e in s["events"]
                    if e["name"] == "jit_recompile"]
        args = root_args(trace)
        copies = [s["args"] for s in trace["spans"] if s["name"] == "emit.d2h"]
        assert args["d2h_bytes"] == sum(c["bytes"] for c in copies) \
            == counted["emit.d2h_bytes"]
        chunks = -(-rows_out // C)
        assert args["chunks"] == counted["emit.gather_chunks"] == chunks
        assert [c["table"] for c in copies] \
            == ["mask"] + ["weights", "covars"] * chunks
        row_bytes = 2 + 2
        mask_bytes = 4 * -(-dims // 32)
        assert args["d2h_bytes"] == mask_bytes + chunks * C * row_bytes
        assert args["d2h_bytes"] <= dims // 8 + (rows_out + C) * row_bytes
        assert len(got[0]) == rows_out


@pytest.mark.parametrize("covars", [True, False], ids=["cov", "nocov"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_emission_leaves_little_under_no_span(dtype, covars):
    """A state of several chunks: the gathers' dispatch lies under
    `emit.gather` (its ids' bytes on it), the values' allocation and
    placement under `emit.assemble` with each chunk's copies as its
    children, and `emit.model_rows` keeps under a tenth of its time bare."""
    from benchmark.readers import _program_spans as ps

    dims, rows_out = 1 << 18, 200 * C + 5
    st = linear_state(dims, rows_out, jnp.dtype(dtype), covars, False)
    model_rows(st)                    # the two programs of this shape
    bare = []
    for _ in range(3):                # the least disturbed of three
        got, trace, _ = emitted(lambda: model_rows(st))
        spans = trace["spans"]
        (root,) = ps.named(spans, "emit.model_rows")
        bare.append(ps.self_ms(spans, "emit.model_rows") * 1e3
                    / root["dur_us"])
    assert min(bare) < 0.10, bare
    chunks = -(-rows_out // C)
    tables = 2 if covars else 1
    (gather,) = ps.named(spans, "emit.gather")
    (assemble,) = ps.named(spans, "emit.assemble")
    assert gather["parent_id"] == assemble["parent_id"] == root["span_id"]
    assert gather["args"] == {"chunks": chunks, "h2d_bytes": chunks * C * 4}
    assert root["args"]["h2d_bytes"] == gather["args"]["h2d_bytes"]
    assert assemble["args"] == {"bytes": sum(v.nbytes for v in got[1:])}
    copies = ps.named(spans, "emit.d2h")
    by_parent = {root["span_id"]: 0, assemble["span_id"]: 0}
    for c in copies:
        by_parent[c["parent_id"]] += 1
    assert by_parent == {root["span_id"]: 1,            # the mask
                         assemble["span_id"]: chunks * tables}
    assert root["args"]["d2h_bytes"] == sum(c["args"]["bytes"]
                                            for c in copies)
    # in order, one after the other: mask, select, gather, assemble
    kids = sorted((s for s in spans if s["parent_id"] == root["span_id"]),
                  key=lambda s: s["start_us"])
    assert [k["name"] for k in kids] == ["emit.d2h", "emit.select",
                                         "emit.gather", "emit.assemble"]
    assert 0 <= ps.self_ms(spans, "emit.assemble") * 1e3 \
        < assemble["dur_us"]


def test_mixed_model_is_gathered_on_the_device_that_holds_it(monkeypatch):
    """After `-mix` the model is replica 0's shard on its own device: the
    mask and the gathers run there, no table crosses devices or comes to
    the host whole, and the rows are the parent's."""
    devices = jax.local_devices()[2:6]
    assert len(devices) == 4, "the tests' virtual CPU mesh is missing"
    monkeypatch.setattr(pmix, "mix_devices", lambda: devices)
    dims = 1 << 14
    rng = np.random.default_rng(11)
    ids = rng.integers(0, dims, size=(600, 8))
    vals = rng.random((600, 8)).astype(np.float32)
    model = get_function("train_arow")(
        (list(ids), list(vals)), rng.integers(0, 2, 600),
        f"-dims {dims} -mini_batch 32 -mix h1,h2 -mix_threshold 3")
    st = model.state
    home = {devices[0]}
    assert st.weights.devices() == st.touched.devices() == home
    ran_on = []
    gather, pack = em._gather_rows, em._pack_mask

    def spy(fn):
        def run(*args):
            out = fn(*args)
            ran_on.extend(leaf.devices() for leaf in jax.tree.leaves(out))
            return out
        return run

    monkeypatch.setattr(em, "_gather_rows", spy(gather))
    monkeypatch.setattr(em, "_pack_mask", spy(pack))
    got, trace, _ = emitted(model.model_rows)
    same_bits(got, host_rows(st.touched, [st.weights, st.covars]))
    rows_out = len(got[0])
    chunks = -(-rows_out // C)
    assert chunks > 2 and len(ran_on) == 1 + 2 * chunks
    assert all(d == home for d in ran_on)
    args = root_args(trace)
    assert args["select"] == "device"
    assert args["d2h_bytes"] == dims // 8 + chunks * C * 8 \
        < st.weights.nbytes          # less than ONE table, of three
    assert int(model.state.step) == 600   # the state is still there to read
