"""The rating learners (`train_mf_adagrad`, `train_mf_sgd`) against the
benchmark's plain reference (`benchmark/refs/mf.py`) at a small table:
the emitted rows agree within the HugeWiki cell's limits, `-num_users` /
`-num_items` fix the state's shape and refuse an id beyond it, a call of
any length runs one step program, and `model_rows()` selects on the device
what the whole-table copy it replaced gave."""

import json
import os

import numpy as np
import pytest

from benchmark import ratings
from benchmark.ops.train_call_ratings import model_gaps
from benchmark.refs import mf as ref
from hivemall_tpu.models import mf as MF

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
U, I, K, BLOCK = 4096, 64, 10, 64
SEED = 2 ** 31 + 91


def _cell_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mf_adagrad_hugewiki.json")) as f:
        return json.load(f)


CFG = _cell_config()
LIMITS = CFG["correct"]["limits"]
ETA0 = CFG["reference_args"]["eta0"]   # the cell's -eta0
MU, MAXVAL = CFG["reference_args"]["mu"], CFG["reference_args"]["maxval"]


def _split(rows, seed=SEED):
    return ratings.make_split(CFG["data"], U, I, rows, seed, 0)


def _config(rule, bias, eta0, block=BLOCK):
    args = dict(CFG["reference_args"], rule=rule, use_bias=bias, eta0=eta0)
    return {"reference_args": args, "mini_batch": block}


def _options(rule, bias, eta0, block=BLOCK):
    opts = (f"-factor {K} -num_users {U} -num_items {I} -mini_batch {block}"
            f" -mu {MU} -maxval {MAXVAL} -eta0 {eta0}")
    return opts + ("" if bias else " -disable_bias")


@pytest.mark.parametrize("rule,bias,block", [
    ("adagrad", True, BLOCK), ("adagrad", False, BLOCK),
    ("sgd", True, BLOCK), ("sgd", False, BLOCK),
    ("adagrad", True, 1)])
def test_emitted_rows_equal_the_reference(rule, bias, block):
    """Zipf items repeat within a block (the sums the rule adds), 1,000
    ratings leave the last block short (padded), and `-mini_batch 1` is the
    row-at-a-time scan, the reference's block of one."""
    sp = _split(1000)
    held = ratings.make_heldout(CFG["data"], sp, I, 512, SEED, 0)
    train = MF.train_mf_adagrad if rule == "adagrad" else MF.train_mf_sgd
    eta0 = ETA0 if rule == "adagrad" else 0.01
    model = train(sp.users, sp.items, sp.ratings,
                  _options(rule, bias, eta0, block))
    want = ref.reference(sp, _config(rule, bias, eta0, block))
    got = model_gaps(ref.rows_of(model.model_rows()), want, ref, held)
    got["steps_diff"] = float(abs(int(model.state.step) - sp.rows))
    assert len(np.unique(sp.items[:BLOCK])) < BLOCK   # a block repeats items
    for name, limit in LIMITS.items():
        assert got[name] <= limit, (name, got[name], limit)
    if not bias:
        assert not np.any(model.model_rows()["users"][2])


def test_the_initial_rows_are_jax_random_uniform_bit_for_bit():
    import jax

    for seed, maxval in ((31, 1.0), (7, 0.5), (2 ** 31 + 5, 1.0)):
        ku, ki = jax.random.split(jax.random.PRNGKey(seed))
        mine = ref.split_key(seed)
        rows = np.array([0, 5, 299, 17])
        for key, theirs in ((mine[0], ku), (mine[1], ki)):
            full = np.asarray(jax.random.uniform(theirs, (300, K),
                                                 maxval=maxval))
            np.testing.assert_array_equal(
                ref.uniform_rows(key, rows, K, maxval), full[rows])


@pytest.mark.parametrize("side,bad", [("users", U), ("items", I),
                                      ("users", -1)])
def test_size_options_refuse_an_id_outside_the_table(side, bad):
    sp = _split(300)
    users, items = sp.users.copy(), sp.items.copy()
    (users if side == "users" else items)[7] = bad
    with pytest.raises(ValueError, match="id"):
        MF.train_mf_adagrad(users, items, sp.ratings,
                            _options("adagrad", True, ETA0))


def test_size_options_fix_the_state_and_pass_the_keywords():
    sp = _split(300)
    model = MF.train_mf_adagrad(sp.users, sp.items, sp.ratings,
                                _options("adagrad", True, ETA0))
    assert model.state.P.shape == (U, K) and model.state.Q.shape == (I, K)
    by_keyword = MF.train_mf_adagrad(
        sp.users, sp.items, sp.ratings,
        f"-factor {K} -mini_batch {BLOCK} -mu {MU} -maxval {MAXVAL}"
        f" -eta0 {ETA0}", num_users=U, num_items=I)
    np.testing.assert_array_equal(np.asarray(model.state.P),
                                  np.asarray(by_keyword.state.P))
    # without either, the call's largest ids size the tables, as before
    bare = MF.train_mf_adagrad(sp.users, sp.items, sp.ratings,
                               f"-factor {K} -mini_batch {BLOCK}")
    assert bare.state.P.shape == (int(sp.users.max()) + 1, K)


@pytest.mark.parametrize("rows", [BLOCK * 3, BLOCK * 3 + 1, BLOCK * 5 - 7])
def test_a_call_of_any_length_runs_one_step_program(rows):
    from hivemall_tpu.runtime.tracing import TRACER

    sp = _split(rows)
    MF.train_mf_adagrad(sp.users, sp.items, sp.ratings,
                        _options("adagrad", True, ETA0))
    spans = TRACER.traces()[-1]["spans"]
    steps = [s for s in spans if s["name"] == "train.compiled_step"]
    assert len(steps) == -(-rows // BLOCK)
    assert sum(bool(s["args"]["compiled"]) for s in steps) == 1
    assert not steps[-1]["args"]["compiled"]
    sync = [s for s in spans if s["name"] == "train.sync"]
    assert len(sync) == 1 and sync[0]["args"]["fetches"] == len(steps)
    prep = [s for s in spans if s["name"] == "train.data_prep"]
    assert sum(s["args"]["rows"] for s in prep) == rows


def test_stage_counts_the_repeated_item_lanes():
    from hivemall_tpu.runtime.tracing import TRACER

    users = np.arange(10, dtype=np.int32)
    # blocks of 4: [1 1 2 1] [3 3 3 4] [5 5] (+ 2 padding lanes)
    items = np.array([1, 1, 2, 1, 3, 3, 3, 4, 5, 5], np.int32)
    MF.train_mf_sgd(users, items, np.ones(10, np.float32),
                    "-factor 2 -mini_batch 4 -num_items 8")
    stage = next(s for s in TRACER.traces()[-1]["spans"]
                 if s["name"] == "train.stage")
    assert stage["args"]["item_lanes"] == 10
    assert stage["args"]["dup_item_lanes"] == 2 + 2 + 1


@pytest.mark.parametrize("train", [MF.train_mf_adagrad, MF.train_mf_sgd])
def test_model_rows_equal_the_whole_table_copy(train):
    sp = _split(700)
    model = train(sp.users, sp.items, sp.ratings,
                  f"-factor {K} -mini_batch {BLOCK} -num_users {U}"
                  f" -num_items {I} -eta0 0.01")
    st = model.state
    tu = np.nonzero(np.asarray(st.touched_u))[0]
    ti = np.nonzero(np.asarray(st.touched_i))[0]
    rows = model.model_rows()
    for (ids, table, bias), (want_ids, t, b) in (
            (rows["users"], (tu, st.P, st.Bu)),
            (rows["items"], (ti, st.Q, st.Bi))):
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(table, np.asarray(t)[want_ids])
        np.testing.assert_array_equal(bias, np.asarray(b)[want_ids])
    assert rows["mu"] == float(st.mu)
