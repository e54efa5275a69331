"""The native batched-apply backend (-batch B -native_apply):

- plan ABI pins: every StagedDedupPlan array handed to ctypes is host
  numpy, int32, C-contiguous, correctly ranked — property-style over
  random shapes, plus the validator's refusals (ops/scatter.py
  plan_abi_arrays, the frozen v1 ABI);
- parity: native-apply == the XLA batch backend across the supported
  rule families — integer tables (touched) EXACT, float tables
  tolerance-pinned, loss sums matching — including tails, pad lanes,
  multi-chunk blocks and warm starts;
- the refusal/fallback matrix: unsupported rule and missing .so fall
  back LOUDLY (warning naming the reason) to the XLA batch path;
  -native_apply without -batch refuses with ValueError; a
  present-but-unloadable .so is reported, never swallowed.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hivemall_tpu import native
from hivemall_tpu.core import native_batch as nb
from hivemall_tpu.core.batch_update import (make_batch_train_step,
                                            stage_block_plans)
from hivemall_tpu.core.state import init_linear_state
from hivemall_tpu.models import classifier as C
from hivemall_tpu.ops.scatter import (PLAN_ABI_VERSION, StagedDedupPlan,
                                      build_staged_plan, plan_abi_arrays)

NATIVE_RULES = [
    (C.PERCEPTRON, {}),
    (C.CW, {"phi": 1.0}),
    (C.AROW, {"r": 0.1}),
    (C.AROWH, {"r": 0.1, "c": 1.0}),
]
RULE_IDS = [r[0].name for r in NATIVE_RULES]

needs_native = pytest.mark.skipif(
    not (native.available() and native.has_batch_apply()),
    reason="native library not built (scripts/build_native.sh)")


def _data(n, k, d, seed=2, pad_frac=0.25):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, d, size=(n, k)).astype(np.int32)
    if pad_frac:
        idx[:, -1] = np.where(rng.rand(n) < pad_frac, d, idx[:, -1])
    val = rng.randn(n, k).astype(np.float32)
    val[idx >= d] = 0.0
    y = np.sign(rng.randn(n)).astype(np.float32)
    return idx, val, y


# ---------------------------------------------------------------- plan ABI

def test_plan_abi_property_pins():
    """Every plan build at random shapes satisfies the frozen ABI: int32
    dtype, C-contiguity, host numpy, the documented ranks — for single
    chunks AND the stacked block form."""
    assert PLAN_ABI_VERSION == 1
    rng = np.random.RandomState(0)
    for trial in range(12):
        n = int(rng.randint(1, 400))
        d = int(rng.randint(4, 300))
        idx = rng.randint(0, d + 1, size=n).astype(np.int64)  # incl. pads
        plan = build_staged_plan(idx, d)
        arrays = plan_abi_arrays(plan)
        assert len(arrays) == len(StagedDedupPlan._fields)
        for f, a in zip(StagedDedupPlan._fields, arrays):
            assert isinstance(a, np.ndarray), (trial, f)
            assert a.dtype == np.int32, (trial, f)
            assert a.flags["C_CONTIGUOUS"], (trial, f)
            assert a.ndim == 1, (trial, f)
    # stacked form: main plans carry the leading [nb] axis
    idx, _, _ = _data(48, 4, 64, pad_frac=0.0)
    plans = stage_block_plans(idx, 8, 64)
    stacked = plan_abi_arrays(plans.main, stacked=True)
    for f, a in zip(StagedDedupPlan._fields, stacked):
        assert a.ndim == 2 and a.dtype == np.int32
        assert a.flags["C_CONTIGUOUS"]


def test_plan_abi_refuses_wrong_dtype_rank_and_device_arrays():
    idx = np.arange(40, dtype=np.int64) % 16
    plan = build_staged_plan(idx, 16)
    # a device plan (the XLA staging path's device_put) must be refused:
    # jnp arrays have no stable ctypes buffer
    dev = jax.tree_util.tree_map(jnp.asarray, plan)
    with pytest.raises(TypeError, match="host numpy"):
        plan_abi_arrays(dev)
    # wrong dtype
    bad = plan._replace(order=plan.order.astype(np.int64))
    with pytest.raises(TypeError, match="int32"):
        plan_abi_arrays(bad)
    # non-contiguous view
    wide = np.zeros((plan.order.shape[0], 2), np.int32)
    wide[:, 0] = plan.order
    bad = plan._replace(order=wide[:, 0])
    with pytest.raises(ValueError, match="C-contiguous"):
        plan_abi_arrays(bad)
    # rank mismatch between the stacked and single-chunk forms
    with pytest.raises(ValueError, match="rank"):
        plan_abi_arrays(plan, stacked=True)


# ------------------------------------------------------------- parity pins

@needs_native
@pytest.mark.parametrize("rule,hyper", NATIVE_RULES, ids=RULE_IDS)
def test_native_apply_equals_xla_batch(rule, hyper):
    """native-apply == the XLA batch backend over a block with duplicate
    features, pad lanes and a tail chunk: float tables to tolerance,
    touched EXACT, loss sums matching."""
    d, b = 128, 8
    idx, val, y = _data(53, 4, d)
    plans = stage_block_plans(idx, b, d)
    xstep = make_batch_train_step(rule, hyper, batch_size=b, donate=False)
    s_ref, loss_ref = xstep(
        init_linear_state(d, use_covariance=rule.use_covariance),
        idx, val, y, jax.tree_util.tree_map(jax.device_put, plans))

    tables = nb.init_native_tables(d, rule.use_covariance)
    loss = nb.make_native_batch_step(rule, hyper)(tables, val, y, plans)
    st = nb.native_tables_to_state(tables, rule, len(y))

    np.testing.assert_allclose(np.asarray(st.weights),
                               np.asarray(s_ref.weights),
                               rtol=5e-5, atol=5e-6)
    if rule.use_covariance:
        np.testing.assert_allclose(np.asarray(st.covars),
                                   np.asarray(s_ref.covars),
                                   rtol=5e-5, atol=5e-6)
    # integer table: EXACT across backends
    np.testing.assert_array_equal(np.asarray(st.touched),
                                  np.asarray(s_ref.touched))
    assert loss == pytest.approx(float(loss_ref), rel=1e-4, abs=1e-4)


@needs_native
def test_native_apply_warm_start_and_b1():
    """Warm-started tables keep their touched mask (the -loadmodel
    contract), and B=1 reproduces the per-row semantics like the XLA
    backend's B=1 pin."""
    d = 64
    idx, val, y = _data(24, 4, d, seed=9, pad_frac=0.0)
    rng = np.random.RandomState(1)
    w0 = (rng.randn(d) * (rng.rand(d) < 0.2)).astype(np.float32)
    plans = stage_block_plans(idx, 1, d)
    xstep = make_batch_train_step(C.AROW, {"r": 0.1}, batch_size=1,
                                  donate=False)
    s_ref, _ = xstep(
        init_linear_state(d, use_covariance=True, initial_weights=w0),
        idx, val, y, jax.tree_util.tree_map(jax.device_put, plans))
    tables = nb.init_native_tables(d, True, initial_weights=w0)
    nb.make_native_batch_step(C.AROW, {"r": 0.1})(tables, val, y, plans)
    st = nb.native_tables_to_state(tables, C.AROW, len(y))
    np.testing.assert_allclose(np.asarray(st.weights),
                               np.asarray(s_ref.weights),
                               rtol=5e-5, atol=5e-6)
    np.testing.assert_array_equal(np.asarray(st.touched),
                                  np.asarray(s_ref.touched))


@needs_native
def test_fit_linear_native_apply_end_to_end():
    """-batch B -native_apply through the public train_* entry matches
    plain -batch B, trains across epochs with the plan cache, and
    predicts."""
    rng = np.random.RandomState(11)
    n, d = 120, 256
    idx_rows = [rng.choice(d, 5, replace=False).astype(np.int64)
                for _ in range(n)]
    val_rows = [rng.randn(5).astype(np.float32) for _ in range(n)]
    w_true = rng.randn(d).astype(np.float32)
    labels = [1.0 if v @ w_true[i] > 0 else -1.0
              for i, v in zip(idx_rows, val_rows)]
    m_nat = C.train_arow((idx_rows, val_rows), labels,
                         f"-dims {d} -batch 16 -native_apply")
    m_xla = C.train_arow((idx_rows, val_rows), labels,
                         f"-dims {d} -batch 16")
    np.testing.assert_allclose(np.asarray(m_nat.state.weights),
                               np.asarray(m_xla.state.weights),
                               rtol=5e-5, atol=5e-6)
    np.testing.assert_array_equal(np.asarray(m_nat.state.touched),
                                  np.asarray(m_xla.state.touched))
    assert int(m_nat.state.step) == int(m_xla.state.step)
    s_n = m_nat.predict((idx_rows[:8], val_rows[:8]))
    s_x = m_xla.predict((idx_rows[:8], val_rows[:8]))
    np.testing.assert_allclose(s_n, s_x, rtol=5e-4, atol=5e-5)
    # multi-epoch with shuffle restaging converges to a usable model
    m = C.train_arow((idx_rows, val_rows), labels,
                     f"-dims {d} -batch 8 -native_apply -iters 3 "
                     "-disable_cv -shuffle")
    acc = np.mean((m.predict((idx_rows, val_rows)) > 0)
                  == (np.asarray(labels) > 0))
    assert acc > 0.8


# -------------------------------------------------- refusal/fallback matrix

def _rows(n=24, d=64, seed=4):
    rng = np.random.RandomState(seed)
    idx_rows = [rng.choice(d, 4, replace=False).astype(np.int64)
                for _ in range(n)]
    val_rows = [np.ones(4, np.float32) for _ in range(n)]
    labels = [1.0 if rng.rand() > 0.5 else -1.0 for _ in range(n)]
    return idx_rows, val_rows, labels


def test_native_apply_refuses_without_batch():
    idx_rows, val_rows, labels = _rows()
    for bad in ("-native_apply",
                "-native_apply -mini_batch 4",
                "-native_apply -native_scan"):
        with pytest.raises(ValueError, match="rides the -batch backend"):
            C.train_arow((idx_rows, val_rows), labels, f"-dims 64 {bad}")
    # with -batch, the backend-exclusivity refusal covers the other flags
    with pytest.raises(ValueError, match="does not compose"):
        C.train_arow((idx_rows, val_rows), labels,
                     "-dims 64 -batch 8 -native_apply -pallas")


def test_unsupported_rule_falls_back_loudly():
    """A rule without a native closed form warns (naming the rule) and
    trains through the XLA batch path — same result as plain -batch."""
    idx_rows, val_rows, labels = _rows()
    with pytest.warns(UserWarning, match="no native batch closed form"):
        m_fb = C.train_pa1((idx_rows, val_rows), labels,
                           "-dims 64 -batch 8 -native_apply")
    m_ref = C.train_pa1((idx_rows, val_rows), labels, "-dims 64 -batch 8")
    np.testing.assert_allclose(np.asarray(m_fb.state.weights),
                               np.asarray(m_ref.state.weights),
                               rtol=1e-6, atol=1e-7)


def test_missing_library_falls_back_loudly(monkeypatch):
    """With the .so gone, -native_apply warns with the unavailability
    reason and the XLA batch path still trains."""
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(native, "load_error", lambda: "CDLL failed: boom")
    idx_rows, val_rows, labels = _rows()
    with pytest.warns(UserWarning, match="native library unavailable"):
        m = C.train_arow((idx_rows, val_rows), labels,
                         "-dims 64 -batch 8 -native_apply")
    assert np.isfinite(np.asarray(m.state.weights)).all()


def test_old_so_without_symbol_falls_back_loudly(monkeypatch):
    monkeypatch.setattr(native, "has_batch_apply", lambda: False)
    if not native.available():
        pytest.skip("needs a loadable .so to isolate the symbol probe")
    idx_rows, val_rows, labels = _rows()
    with pytest.warns(UserWarning, match="predates hm_batch_apply_block"):
        C.train_arow((idx_rows, val_rows), labels,
                     "-dims 64 -batch 8 -native_apply")


def test_bf16_storage_falls_back_loudly(monkeypatch):
    """dims > 2^24 without -disable_halffloat selects bf16 tables, which
    the native pass refuses — pinned through the reason function (a full
    2^24+1-dim train would be slow for a unit test)."""
    reason = nb.native_batch_unsupported_reason(
        C.AROW, table_dtype_is_f32=False)
    if not (native.available() and native.has_batch_apply()):
        assert reason is not None  # unavailability reported first
    else:
        assert reason is not None and "bf16" in reason


def test_unloadable_so_is_reported_not_swallowed(tmp_path, monkeypatch):
    """A PRESENT .so that cannot load on this host (the PR 11 GLIBCXX
    pathology) must warn once and surface through load_error() — the
    silent-fallback regression this pins against."""
    import hivemall_tpu.native as nat

    bad = tmp_path / "libhivemall_native.so"
    bad.write_bytes(b"\x7fELFnot-actually-an-elf")
    # pin the plain variant: under the sanitizer gate the env var would
    # redirect the loader to a (nonexistent) .asan.so and skip the warning
    monkeypatch.setenv("HIVEMALL_TPU_NATIVE_SANITIZE", "")
    monkeypatch.setattr(nat, "_LIB_PATH", str(bad))
    # this host's stamp: the case under test is a library that passes the
    # provenance check and then fails in CDLL
    monkeypatch.setattr(nat, "_stale_reason", lambda: None)
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_load_error", None)
    with pytest.warns(UserWarning, match="failed to load"):
        assert nat._load() is None
    assert nat.available() is False
    assert nat.load_error()  # the mismatch is named, queryable
    assert nat.has_batch_apply() is False
    # and the backend refuses with the recorded cause in its reason
    reason = nb.native_batch_unsupported_reason(C.AROW)
    assert reason is not None and "unavailable" in reason


@needs_native
def test_batch_apply_block_argument_validation():
    """The ctypes wrapper refuses unknown rules and wrong table dtypes
    before any native memory is touched."""
    d = 32
    idx, val, y = _data(8, 4, d, pad_frac=0.0)
    plans = stage_block_plans(idx, 4, d)
    w = np.zeros(d, np.float32)
    cov = np.ones(d, np.float32)
    touched = np.zeros(d, np.int8)
    with pytest.raises(ValueError, match="no native batch closed form"):
        native.batch_apply_block("pa1", {}, val, y, plans.main, plans.tail,
                                 d, w, cov, touched)
    with pytest.raises(ValueError, match="C-contiguous"):
        native.batch_apply_block("arow", {"r": 0.1}, val, y, plans.main,
                                 plans.tail, d, w.astype(np.float64), cov,
                                 touched)
    # a missing required hyper raises like the XLA rule's hyper[...] would
    # (phi=0 would silently freeze CW instead)
    with pytest.raises(KeyError, match="phi"):
        native.batch_apply_block("cw", {}, val, y, plans.main, plans.tail,
                                 d, w, cov, touched)
    # label/table length mismatches fail at the boundary, never in C
    with pytest.raises(ValueError, match="labels shape"):
        native.batch_apply_block("arow", {"r": 0.1}, val, y[:-1],
                                 plans.main, plans.tail, d, w, cov, touched)
    with pytest.raises(ValueError, match="rows < dims"):
        native.batch_apply_block("arow", {"r": 0.1}, val, y, plans.main,
                                 plans.tail, d, w[:d - 4], cov, touched)
