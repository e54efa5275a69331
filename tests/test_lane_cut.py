"""The `-mini_batch` steps work on the lanes a call's rows can fill
(core/batch.py::fillable_lanes: the longest row rounded up to 8, 40 of the
64-lane bucket on Criteo's 39 features), cut off inside the step before
anything is gathered (core/engine.py::make_cut_step around the step of
`fit_linear`, `-mix` and `train_fm`). A cut lane holds the padding
id in every row, so the cut changes no flag and no emitted row, and a table
only by the order of a float32 sum. Each call here is trained twice: as the
entry point builds it, and with `fillable_lanes` answering the block's whole
width, which is the step as its factory makes it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hivemall_tpu.core.batch import fillable_lanes, pad_to_bucket
from hivemall_tpu.core.engine import make_train_step, make_cut_step
from hivemall_tpu.core.state import init_linear_state
from hivemall_tpu.models import base as mbase
from hivemall_tpu.models import fm as mfm
from hivemall_tpu.models.classifier import ADAGRAD_RDA, AROW
from hivemall_tpu.parallel import mix as pmix
from hivemall_tpu.runtime.metrics import REGISTRY
from hivemall_tpu.runtime.tracing import TRACER
from hivemall_tpu.sql.registry import get_function

FEATS = 39


def criteo_like(n, dims, feats=FEATS, seed=0, repeats=True):
    """[n, feats] ids and values: a few columns that every row fills from a
    handful of ids (features that repeat within a block), the rest spread
    over the table; without `repeats`, no id twice in the whole call."""
    rng = np.random.default_rng(seed)
    if repeats:
        ids = rng.integers(0, dims, size=(n, feats))
        ids[:, 0] = 7
        ids[:, 1:4] = rng.integers(0, 16, size=(n, min(3, feats - 1)))
    else:
        ids = rng.permutation(dims)[:n * feats].reshape(n, feats)
    vals = (rng.integers(1, 8, size=(n, feats)) / 8.0).astype(np.float32)
    return ids, vals, rng.integers(0, 2, size=n)


def last_call():
    return next(s for t in reversed(TRACER.traces()) for s in t["spans"]
                if s["name"] == "train.call")


@pytest.fixture
def fit(monkeypatch):
    """fit(entry, rows, labels, options, cut) -> (model, train.call args)."""
    def run(entry, rows, labels, options, cut=True):
        with monkeypatch.context() as m:
            if not cut:
                for mod in (mbase, mfm):
                    m.setattr(mod, "fillable_lanes",
                              lambda longest, width: width)
            model = get_function(entry)(rows, labels, options)
        return model, last_call()["args"]
    return run


@pytest.fixture
def four_replicas(monkeypatch):
    devices = jax.local_devices()[:4]
    assert len(devices) == 4, "the tests' virtual CPU mesh is missing"
    monkeypatch.setattr(pmix, "mix_devices", lambda: devices)


def linear_tables_of(model):
    st = jax.device_get(model.state)
    tables = {"weights": st.weights, **st.slots}
    if st.covars is not None:
        tables["covars"] = st.covars
    return st, {k: np.asarray(v, np.float32) for k, v in tables.items()}


def assert_same_linear_model(a, b, exact=False):
    """Flags and emitted row sets equal; tables equal to the order of a
    float32 sum (the bound `tests/test_ffm.py` holds FFM's lane order to),
    or bit for bit."""
    sa, ta = linear_tables_of(a)
    sb, tb = linear_tables_of(b)
    np.testing.assert_array_equal(sa.touched, sb.touched)
    assert int(sa.step) == int(sb.step)
    ra, rb = a.model_rows(), b.model_rows()
    np.testing.assert_array_equal(ra[0], rb[0])
    assert ta.keys() == tb.keys()
    for name in ta:
        if exact:
            np.testing.assert_array_equal(ta[name], tb[name], err_msg=name)
        else:
            np.testing.assert_allclose(ta[name], tb[name], rtol=2e-5,
                                       atol=1e-7, err_msg=name)
    for x, y in zip(ra[1:], rb[1:]):
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(np.asarray(x, np.float32),
                                       np.asarray(y, np.float32),
                                       rtol=2e-5, atol=1e-7)


# dims and -mini_batch pick the arm (`apply_strategy`: dense below 256
# entries a block lane, on either lane count) and the storage (bfloat16
# above 2^24 entries)
ARMS = {
    "dense-f32": (1 << 12, 16, "dense", "float32"),
    "batch_local-f32": (1 << 19, 16, "batch_local", "float32"),
    "batch_local-bf16": (1 << 25, 16, "batch_local", "bfloat16"),
}


@pytest.mark.parametrize("arm", list(ARMS))
@pytest.mark.parametrize("entry", ["train_arow", "train_adagrad_rda"])
def test_a_cut_call_trains_the_model_of_the_uncut_call(fit, entry, arm):
    dims, b, apply, dtype = ARMS[arm]
    ids, vals, labels = criteo_like(96, dims)
    opts = f"-dims {dims} -mini_batch {b}"
    cut, args = fit(entry, (ids, vals), labels, opts)
    assert (args["width"], args["lanes"], args["apply"],
            args["table_dtype"]) == (64, 40, apply, dtype)
    whole, args = fit(entry, (ids, vals), labels, opts, cut=False)
    assert (args["width"], args["lanes"], args["apply"]) == (64, 64, apply)
    assert_same_linear_model(cut, whole)


@pytest.mark.parametrize("entry", ["train_arow", "train_adagrad_rda"])
def test_the_cut_is_exact_where_no_feature_repeats(fit, entry):
    """Every run is one lane: nothing is summed in another order."""
    dims = 1 << 19
    ids, vals, labels = criteo_like(64, dims, repeats=False)
    opts = f"-dims {dims} -mini_batch 16"
    cut, args = fit(entry, (ids, vals), labels, opts)
    assert (args["lanes"], args["apply"]) == (40, "batch_local")
    whole, _ = fit(entry, (ids, vals), labels, opts, cut=False)
    assert_same_linear_model(cut, whole, exact=True)


def test_the_shape_rule_reads_the_cut_lane_count(fit):
    """256 entries a block lane: a table between 256 x B x 40 and
    256 x B x 64 entries is passed over whole on 64 lanes and written in
    place on 40, to the same model."""
    dims = 256 * 16 * 50
    ids, vals, labels = criteo_like(64, dims)
    opts = f"-dims {dims} -mini_batch 16"
    cut, args = fit("train_arow", (ids, vals), labels, opts)
    assert (args["lanes"], args["apply"]) == (40, "batch_local")
    whole, args = fit("train_arow", (ids, vals), labels, opts, cut=False)
    assert (args["lanes"], args["apply"]) == (64, "dense")
    assert_same_linear_model(cut, whole)


def test_fm_two_epochs_cut_against_uncut(fit):
    dims = 1 << 14
    ids, vals, labels = criteo_like(96, dims)
    opts = f"-c -factor 10 -dims {dims} -mini_batch 16 -iters 2 -disable_cv"
    cut, args = fit("train_fm", (ids, vals), labels, opts)
    assert (args["width"], args["lanes"], args["epochs"]) == (64, 40, 2)
    whole, args = fit("train_fm", (ids, vals), labels, opts, cut=False)
    assert (args["width"], args["lanes"]) == (64, 64)
    a, b = jax.device_get(cut.state), jax.device_get(whole.state)
    np.testing.assert_array_equal(a.touched, b.touched)
    assert int(a.step) == int(b.step) == 2 * 96
    for name in ("w0", "w", "v"):
        np.testing.assert_allclose(getattr(a, name), getattr(b, name),
                                   rtol=2e-5, atol=1e-7, err_msg=name)
    np.testing.assert_array_equal(cut.model_rows()[1], whole.model_rows()[1])


@pytest.mark.parametrize("arm", ["dense-f32", "batch_local-f32"])
def test_mix_on_four_devices_cut_against_uncut(fit, four_replicas, arm):
    dims, b, apply, _ = ARMS[arm]
    ids, vals, labels = criteo_like(300, dims)   # shares' last blocks differ
    opts = f"-dims {dims} -mini_batch {b} -mix local -mix_threshold 2"
    cut, args = fit("train_arow", (ids, vals), labels, opts)
    assert (args["replicas"], args["width"], args["lanes"],
            args["apply"]) == (4, 64, 40, apply)
    whole, args = fit("train_arow", (ids, vals), labels, opts, cut=False)
    assert (args["lanes"], args["apply"]) == (64, apply)
    assert_same_linear_model(cut, whole)


@pytest.mark.parametrize("form", ["ragged", "text", "lists"])
def test_ragged_and_text_rows_are_cut_by_their_longest_row(fit, form):
    """39 features in the longest row among shorter ones: the parser's and
    the native packer's blocks fill from lane 0 as the array route's do."""
    dims = 1 << 19
    ids, vals, labels = criteo_like(64, dims)
    lens = np.random.default_rng(3).integers(5, FEATS + 1, size=len(ids))
    lens[11] = FEATS
    idx_rows = [r[:k] for r, k in zip(ids, lens)]
    val_rows = [r[:k] for r, k in zip(vals, lens)]
    if form == "text":
        rows = [[f"{i}:{v!r}" for i, v in zip(r, x.tolist())]
                for r, x in zip(idx_rows, val_rows)]
    elif form == "lists":
        rows = ([r.tolist() for r in idx_rows], [r.tolist() for r in val_rows])
    else:
        rows = (idx_rows, val_rows)
    opts = f"-dims {dims} -mini_batch 16"
    cut, args = fit("train_arow", rows, labels, opts)
    assert (args["width"], args["lanes"]) == (64, 40)
    whole, _ = fit("train_arow", rows, labels, opts, cut=False)
    assert_same_linear_model(cut, whole)
    # no lane past a row's own length reached the tables
    assert {int(f) for f in cut.model_rows()[0]} <= {
        int(i) for r in idx_rows for i in r}


@pytest.mark.parametrize("longest,width,lanes", [
    (3, 8, 8), (8, 8, 8), (9, 16, 16), (17, 32, 24), (39, 64, 40),
    (40, 64, 40), (41, 64, 48), (56, 64, 56), (57, 64, 64), (64, 64, 64),
    (65, 128, 72)])
def test_the_ladder_is_eight_lanes_under_the_bucket(longest, width, lanes):
    assert pad_to_bucket(longest) == width
    assert fillable_lanes(longest, width) == lanes
    assert fillable_lanes(0, 8) == 8   # rows with no feature at all


@pytest.mark.parametrize("entry", ["train_arow", "train_fm"])
@pytest.mark.parametrize("longest", [8, 40, 57, 64])
def test_a_call_reports_its_width_its_lanes_and_the_lanes_cut(fit, entry,
                                                              longest):
    dims, rows = 1 << 14, 64
    ids, vals, labels = criteo_like(rows, dims, feats=longest)
    opts = f"-dims {dims} -mini_batch 16" + (
        " -c -factor 4" if entry == "train_fm" else "")
    counter = REGISTRY.counter("train", "lanes_cut")
    before = counter.value
    _, args = fit(entry, (ids, vals), labels, opts)
    width = pad_to_bucket(longest)
    lanes = {8: 8, 40: 40, 57: 64, 64: 64}[longest]
    assert (args["width"], args["lanes"]) == (width, lanes)
    # Criteo's 39 features: 24 lanes a row
    assert counter.value - before == rows * (width - lanes)


def _lowered(step, state, width=64):
    S = jax.ShapeDtypeStruct
    return step.lower(state, S((16, width), jnp.int32),
                      S((16, width), jnp.float32),
                      S((16,), jnp.float32)).as_text()


@pytest.mark.parametrize("options", [
    "-mini_batch 16", "-mini_batch 16 -mix local -mix_threshold 2",
    "-c -factor 4 -mini_batch 16"], ids=["linear", "mix", "fm"])
@pytest.mark.parametrize("longest", [57, 64])
def test_a_call_that_fills_the_bucket_runs_the_step_as_made(
        fit, four_replicas, monkeypatch, options, longest):
    """`lanes == width`: the step is the factory's own jit, with nothing
    around it, so its module text (what the compile cache keys on) is the
    parent's; on 39 features the same call is cut."""
    cuts = []
    for mod in (mbase, mfm):
        monkeypatch.setattr(mod, "make_cut_step", lambda step, lanes: (
            cuts.append(lanes), make_cut_step(step, lanes))[1])
    entry = "train_fm" if "-factor" in options else "train_arow"
    for feats, want in ((longest, []), (FEATS, [40])):
        ids, vals, labels = criteo_like(64, 1 << 14, feats=feats)
        del cuts[:]
        _, args = fit(entry, (ids, vals), labels, f"-dims 16384 {options}")
        assert cuts == want
        assert (args["width"], args["lanes"]) == (64, want[0] if want else 64)


@pytest.mark.parametrize("rule,hyper", [
    (AROW, {"r": 0.1}),
    (ADAGRAD_RDA, {"eta": 0.1, "lambda": 1e-6, "scale": 100.0})],
    ids=["arow", "adagrad_rda"])
def test_the_cut_step_is_the_step_on_a_narrower_block(rule, hyper):
    """One program, the slice at its top: on a 64-lane block it gives what
    the step gives on the block's 40 leading lanes, bit for bit, and the
    shape rule reads the cut lane count."""
    dims = 256 * 16 * 50        # dense on 64 lanes, batch_local on 40
    def fresh():    # the cut step donates its state, as the step's jit does
        return init_linear_state(dims, use_covariance=rule.use_covariance,
                                 slot_names=rule.slot_names,
                                 dtype=jnp.float32)

    ids, vals, labels = criteo_like(16, dims)
    block = np.full((16, 64), dims, np.int32), np.zeros((16, 64), np.float32)
    block[0][:, :FEATS], block[1][:, :FEATS] = ids, vals
    y = np.where(labels > 0, 1.0, -1.0).astype(np.float32)
    step = make_train_step(rule, hyper, mode="minibatch", donate=False)
    cut, loss_cut = make_cut_step(step, 40)(fresh(), *block, y)
    narrow, loss = step(fresh(), block[0][:, :40], block[1][:, :40], y)
    for a, b in zip(jax.tree_util.tree_leaves(cut),
                    jax.tree_util.tree_leaves(narrow)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(loss_cut) == float(loss)
    shaped = jax.eval_shape(fresh)
    assert _lowered(make_cut_step(step, 40), shaped) != \
        _lowered(step, shaped)


def test_the_scan_takes_the_block_whole(fit, monkeypatch):
    """`-mini_batch 1`: the per-row scan is not cut and reports no lanes."""
    monkeypatch.setattr(mbase, "make_cut_step", None)   # never called
    ids, vals, labels = criteo_like(32, 1 << 12)
    counter = REGISTRY.counter("train", "lanes_cut")
    before = counter.value
    _, args = fit("train_arow", (ids, vals), labels, "-dims 4096")
    assert args["mode"] == "scan" and "lanes" not in args
    assert counter.value == before
