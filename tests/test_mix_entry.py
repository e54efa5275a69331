"""`train_* -mix` through the SQL entry point on the tests' virtual CPU mesh:
one replica a device, mixed into one model (models/base.py::_fit_linear_mixed,
parallel/mix.py::MixedReplicas), against the benchmark's plain reference
(benchmark/refs/arow_mix.py), the hand-driven MixTrainer, and itself."""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import compare  # noqa: E402
from benchmark.refs import arow_mix  # noqa: E402
from hivemall_tpu.models.classifier import AROW, PA1  # noqa: E402
from hivemall_tpu.models.regression import ADAGRAD_REGR  # noqa: E402
from hivemall_tpu.parallel import MixConfig, MixTrainer, make_mesh  # noqa: E402
from hivemall_tpu.parallel import mix as pmix  # noqa: E402
from hivemall_tpu.runtime.tracing import TRACER  # noqa: E402
from hivemall_tpu.sql.registry import get_function  # noqa: E402

K = 8
F32, BF16 = (1 << 12, None), (1 << 25, "bfloat16")


def rows(n, dims, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, dims, size=(n, K))
    ids[:, 0] = 7                      # a feature every row carries
    ids[:, 1] = rng.integers(0, 16, size=n)   # and a few hot ones
    vals = rng.random((n, K)).astype(np.float32)
    return ids, vals, rng.integers(0, 2, size=n)


@pytest.fixture
def replicas(monkeypatch):
    def use(r):
        devices = jax.local_devices()[:r]
        assert len(devices) == r, "the tests' virtual CPU mesh is missing"
        monkeypatch.setattr(pmix, "mix_devices", lambda: devices)
    return use


def fit(entry, ids, vals, labels, options):
    model = get_function(entry)((list(ids), list(vals)), labels, options)
    return model, next(t for t in reversed(TRACER.traces())
                       if any(s["name"] == "train.call" for s in t["spans"]))


def as_model(feats, w, cov):
    return {"feats": np.asarray(feats, np.int64), "scalars": {},
            "tables": {"w": np.asarray(w, np.float64),
                       "cov": np.asarray(cov, np.float64)}}


# R x B divides the rows or not; the threshold divides the blocks or not;
# a share's last block short, a replica's share empty
CASES = [(1, 500, 64, 3), (2, 1024, 64, 4), (2, 1000, 64, 3),
         (4, 2048, 64, 4), (4, 1000, 64, 3), (4, 777, 32, 5), (4, 70, 32, 1)]


@pytest.mark.parametrize("dims,storage", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("r,n,b,threshold", CASES)
def test_entry_point_against_the_plain_reference(replicas, r, n, b, threshold,
                                                 dims, storage):
    replicas(r)
    ids, vals, labels = rows(n, dims, seed=n + r)
    model, trace = fit("train_arow", ids, vals, labels,
                       f"-dims {dims} -mini_batch {b} -mix h1,h2 "
                       f"-mix_threshold {threshold}")
    assert int(model.state.step) == n            # every row, once
    assert str(model.state.weights.dtype) == (storage or "float32")
    feats, w, cov = model.model_rows()
    rf, rw, rc, info = arow_mix.train(
        ids, vals, labels, dims=dims, mini_batch=b, replicas=r,
        mix_every=threshold, table_dtype=storage, emitted_feats=feats,
        tau=0.01 if storage else 0.0)
    gaps = compare.model_gaps(as_model(feats, w, cov), as_model(rf, rw, rc))
    assert gaps["rows_diff"] == 0
    # float32 tables: the order of float32 sums; bfloat16: a rounding or two
    assert gaps["w_gap"] <= (0.02 if storage else 1e-5), gaps
    assert gaps["cov_gap"] <= (0.01 if storage else 1e-6), gaps
    if r > 1:
        call = next(s["args"] for s in trace["spans"] if s["name"] == "train.call")
        epoch = next(s["args"] for s in trace["spans"] if s["name"] == "train.epoch")
        assert (call["replicas"], call["mix_every"], call["reduction"]) == (
            r, threshold, "argmin_kld")
        assert epoch["mix_rounds"] == info["mix_rounds"]
        assert epoch["mix_due_entries"] == info["mix_due_entries"]
        assert epoch["mix_exchanged_entries"] == info["mix_rounds"] * dims


def test_one_device_is_the_plain_call_bit_for_bit(replicas):
    replicas(1)
    ids, vals, labels = rows(600, 1 << 12)
    mixed, trace = fit("train_arow", ids, vals, labels,
                       "-dims 4096 -mini_batch 64 -mix h1 -mix_threshold 2")
    plain, _ = fit("train_arow", ids, vals, labels, "-dims 4096 -mini_batch 64")
    assert not any(s["name"] == "train.mix" for s in trace["spans"])
    for a, b in zip(mixed.model_rows(), plain.model_rows()):
        assert np.array_equal(a, b)
    # and the exact scan stays what it was: nothing to mix with
    fit("train_arow", ids[:50], vals[:50], labels[:50], "-dims 4096 -mix h1")


@pytest.mark.parametrize("dims,storage", [F32, BF16], ids=["f32", "bf16"])
def test_replicas_are_equal_after_the_trailing_mix(replicas, monkeypatch,
                                                   dims, storage):
    """What lets `model_rows()` copy ONE model: pinned on the replicated
    state as it enters the collapse."""
    from hivemall_tpu.models import base

    replicas(4)
    seen = {}
    dispatch = base.dispatch_spanned

    def watch(span, span_args, program, *args):
        if span == "train.collapse":
            (state,) = args
            seen["w"] = np.asarray(state.weights, np.float32).reshape(4, dims)
            seen["cov"] = np.asarray(state.covars,
                                     np.float32).reshape(4, dims)
            seen["touched"] = np.asarray(state.touched).reshape(4, dims)
            seen["pending"] = np.asarray(state.slots["__delta_upd"])
            seen["step"] = np.asarray(state.step)
        return dispatch(span, span_args, program, *args)

    monkeypatch.setattr(base, "dispatch_spanned", watch)
    ids, vals, labels = rows(1000, dims, seed=3)
    model, _ = fit("train_arow", ids, vals, labels,
                   f"-dims {dims} -mini_batch 64 -mix h -mix_threshold 3")
    for name in ("w", "cov"):
        assert all(np.array_equal(seen[name][0], seen[name][r])
                   for r in range(1, 4)), name
    assert not seen["pending"].any()             # nothing left unmixed
    assert seen["step"].tolist() == [250, 250, 250, 250]
    assert len({t.tobytes() for t in seen["touched"]}) > 1   # local, until:
    assert np.array_equal(np.asarray(model.state.touched),
                          seen["touched"].max(axis=0))       # the union
    assert np.array_equal(np.asarray(model.state.weights, np.float32),
                          seen["w"][0])
    assert list(model.state.slots) == []         # the pending counts are gone
    assert {d.id for d in model.state.weights.devices()} == {
        jax.local_devices()[0].id}


def test_the_share_adds_up_to_the_model(replicas):
    """The guide's test of a share: each replica's UNMIXED model, trained
    alone as a plain call on its share, put through the reference's
    argmin-KLD once, is what the entry point returns when its only mix is
    the trailing one."""
    dims, n, b, r = 1 << 12, 1024, 64, 4
    ids, vals, labels = rows(n, dims, seed=11)
    replicas(r)
    blocks_each = n // r // b
    mixed, _ = fit("train_arow", ids, vals, labels,
                   f"-dims {dims} -mini_batch {b} -mix h -mix_threshold {blocks_each}")
    feats, w, cov = mixed.model_rows()
    ws, covs = np.zeros((r, dims)), np.ones((r, dims))
    touched = np.zeros(dims, bool)
    for i, (lo, hi) in enumerate(pmix.deal_rows(n, r)):
        alone, _ = fit("train_arow", ids[lo:hi], vals[lo:hi], labels[lo:hi],
                       f"-dims {dims} -mini_batch {b}")
        f, wi, ci = alone.model_rows()
        ws[i, f], covs[i, f] = wi, ci
        touched[f] = True
    due = np.nonzero(touched)[0]
    arow_mix.argmin_kld(ws, covs, due, lambda a: a)
    assert np.array_equal(feats, due)
    np.testing.assert_allclose(w, ws[0, due], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(cov, covs[0, due], rtol=2e-5, atol=1e-7)


@pytest.mark.parametrize("entry,rule,hyper", [
    ("train_arow", AROW, {"r": 0.1}),       # the entry points' own defaults
    ("train_pa1", PA1, {"c": 1.0}),
    ("train_adagrad_regr", ADAGRAD_REGR, {"eta": 1.0, "eps": 1.0,
                                          "scale": 100.0}),
], ids=["argmin_kld", "average", "average_with_slots"])
def test_entry_point_equals_the_hand_driven_trainer(replicas, entry, rule,
                                                    hyper):
    """Same rows, same dealing, same cadence: `-mix` is MixTrainer's mixing
    behind the normal path, and the device-side collapse is its host-side
    one (slots merged by the rule's kinds, the union of `touched`)."""
    dims, b, r, k, every = 1 << 12, 32, 4, 6, 2
    ids, vals, labels = rows(r * k * b, dims, seed=5)
    if rule.is_regression:
        labels = labels.astype(np.float32) * 0.5 + 0.1
    replicas(r)
    model, trace = fit(entry, ids, vals, labels,
                       f"-dims {dims} -mini_batch {b} -mix h -mix_threshold {every}")
    by_hand = MixTrainer(rule, hyper, dims, make_mesh(r),
                         MixConfig(mix_every=every))
    y = np.asarray(labels, np.float32)
    if not rule.is_regression:
        y = np.where(y > 0, 1.0, -1.0).astype(np.float32)
    state, _ = by_hand.step(
        by_hand.init(), ids.reshape(r, k, b, K).astype(np.int32),
        vals.reshape(r, k, b, K), y.reshape(r, k, b))
    want = by_hand.final_state(state)
    got = model.state
    np.testing.assert_allclose(np.asarray(got.weights), want.weights,
                               rtol=1e-5, atol=1e-7)
    if rule.use_covariance:
        np.testing.assert_allclose(np.asarray(got.covars), want.covars,
                                   rtol=1e-5, atol=1e-7)
    assert np.array_equal(np.asarray(got.touched), want.touched)
    assert int(got.step) == int(want.step) == r * k * b
    assert sorted(got.slots) == sorted(rule.slot_names)
    for name in rule.slot_names:
        np.testing.assert_allclose(np.asarray(got.slots[name]),
                                   want.slots[name], rtol=1e-5, atol=1e-7)
    reduction = next(s["args"]["reduction"] for s in trace["spans"]
                     if s["name"] == "train.call")
    assert reduction == by_hand.reduction


def test_programs_do_not_depend_on_the_rows_and_compile_once_a_call(replicas):
    """A two-block warm-up dispatches the shapes a long call dispatches, and
    within a call only the first dispatch of each program compiles."""
    replicas(4)
    shapes = {}

    def flags(n):
        ids, vals, labels = rows(n, 1 << 12, seed=n)
        _, trace = fit("train_arow", ids, vals, labels,
                       "-dims 4096 -mini_batch 64 -mix h -mix_threshold 2")
        spans = trace["spans"]
        shapes[n] = {(s["args"]["rows"] > 0, s["args"]["width"],
                      s["args"]["h2d_bytes"])
                     for s in spans if s["name"] == "train.data_prep"}
        return ([s["args"]["compiled"] for s in spans
                 if s["name"] == "train.compiled_step"],
                [s["args"] for s in spans if s["name"] == "train.mix"],
                [s["name"] for s in spans for e in s["events"]
                 if e["name"] == "jit_recompile"])

    steps, mixes, recompiles = flags(4 * 64 * 7)
    # each of the call's three programs on its first dispatch: the step, the
    # round, and the collapse at the call's end
    assert recompiles == ["train.compiled_step", "train.mix",
                          "train.collapse"]
    assert steps == [True] + [False] * 6
    assert [m["compiled"] for m in mixes] == [True, False, False, False]
    assert [m["trailing"] for m in mixes] == [False, False, False, True]
    assert [m["round"] for m in mixes] == [0, 1, 2, 3]
    short_steps, short_mixes, _ = flags(2 * 64)       # the harness's warm-up
    assert short_steps == [True] and len(short_mixes) == 1
    assert short_mixes[0]["trailing"] is True
    assert shapes[2 * 64] == shapes[4 * 64 * 7]       # one block shape


def test_spans_of_a_mixed_call(replicas):
    replicas(2)
    ids, vals, labels = rows(300, 1 << 12)
    _, trace = fit("train_arow", ids, vals, labels,
                   "-dims 4096 -mini_batch 64 -mix h -mix_threshold 2")
    spans = {s["span_id"]: s for s in trace["spans"]}
    names = [s["name"] for s in trace["spans"]]
    parent = lambda s: spans[s["parent_id"]]["name"]  # noqa: E731
    deal = next(s for s in trace["spans"] if s["name"] == "train.shard_rows")
    assert parent(deal) == "train.stage"
    assert deal["args"] == {"replicas": 2, "rows": 300, "rows_each": 150}
    assert all(parent(s) == "train.epoch" for s in trace["spans"]
               if s["name"] in ("train.mix", "train.compiled_step",
                                "train.data_prep", "train.sync"))
    assert names.count("train.mix") == 2 and names.count("train.sync") == 1
    sync = next(s for s in trace["spans"] if s["name"] == "train.sync")
    assert sync["args"]["fetches"] == 3 + 2     # losses and due counts, once
    # the replicas' programs are made under `train.build` (rows of 8 lanes
    # fill their bucket: no cut around the step), and the one model comes
    # out under `train.collapse`, a dispatch of a fresh jit like the others
    (build,) = [s for s in trace["spans"] if s["name"] == "train.build"]
    (collapse,) = [s for s in trace["spans"] if s["name"] == "train.collapse"]
    assert parent(build) == parent(collapse) == "train.call"
    assert build["args"] == {"replicas": 2, "jits": 3}
    assert collapse["args"] == {"compiled": True}
    order = [s["name"] for s in sorted(
        (s for s in trace["spans"] if parent_of(s, spans) == "train.call"),
        key=lambda s: s["start_us"])]
    assert order == ["train.stage", "train.build", "train.init_state",
                     "train.epoch", "train.collapse"]
    # what the call leaves bare is little beside them
    (call,) = [s for s in trace["spans"] if s["name"] == "train.call"]
    kids = [s for s in trace["spans"] if s["parent_id"] == call["span_id"]]
    assert sum(k["dur_us"] for k in kids) > 0.9 * call["dur_us"]


def parent_of(span, spans):
    return spans[span["parent_id"]]["name"] if span["parent_id"] else None


REFUSALS = [
    ("train_arow", "-dims 64 -mix h", "needs -mini_batch B > 1"),
    ("train_arow", "-dims 64 -mix h -batch 16", "does not compose with -batch"),
    ("train_arow", "-dims 64 -mix h -native_scan",
     "does not compose with -native_scan"),
    ("train_arow", "-dims 64 -mix h -mini_batch 8 -mix_threshold 0",
     "-mix_threshold in 1..127"),
    ("train_arow", "-dims 64 -mix h -mini_batch 8 -mix_threshold 128",
     "-mix_threshold in 1..127"),
    ("train_adagrad_rda", "-dims 64 -mix h -mini_batch 8",
     "is not supported for adagrad_rda"),
    ("train_pa1a_regr", "-dims 64 -mix h -mini_batch 8", "is not supported for"),
]


@pytest.mark.parametrize("entry,options,message", REFUSALS)
def test_what_cannot_be_mixed_is_refused_in_words(replicas, entry, options,
                                                  message):
    replicas(2)
    ids, vals, labels = rows(40, 64)
    with pytest.raises(ValueError, match=message) as err:
        fit(entry, ids, vals, labels, options)
    assert "-mix on 2 devices" in str(err.value)


def test_mix_reductions_sum_in_float32_whatever_the_tables_hold():
    """bfloat16 tables: 1/cov, w/cov and their sums in float32, one rounding
    at the write (a bfloat16 psum of four replicas loses the low bits)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from hivemall_tpu.runtime.jax_compat import shard_map

    mesh = make_mesh(4)
    rng = np.random.default_rng(0)
    w = rng.normal(size=(4, 256)).astype(np.float32)
    cov = rng.uniform(0.05, 1.0, size=(4, 256)).astype(np.float32)
    wb, cb = jnp.asarray(w, jnp.bfloat16), jnp.asarray(cov, jnp.bfloat16)
    delta = np.ones((4, 256), np.float32)

    def body(wv, cv, dv):
        mw, mc, _ = pmix.mix_argmin_kld(wv[0], cv[0], dv[0])
        return mw[None], mc[None]

    mw, mc = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("workers"),) * 3,
                               out_specs=(P("workers"),) * 2))(wb, cb, delta)
    assert mw.dtype == jnp.bfloat16 and mc.dtype == jnp.bfloat16
    w64, c64 = np.asarray(wb, np.float64), np.asarray(cb, np.float64)
    want_c = 1.0 / (1.0 / c64).sum(axis=0)
    want_w = want_c * (w64 / c64).sum(axis=0)
    got_w, got_c = np.asarray(mw[0], np.float64), np.asarray(mc[0], np.float64)
    # one bfloat16 rounding of the exact value: 2^-9 relative
    assert np.all(np.abs(got_c - want_c) <= 2.0 ** -8 * np.abs(want_c))
    assert np.all(np.abs(got_w - want_w) <= 2.0 ** -8 * np.abs(want_w) + 1e-6)
