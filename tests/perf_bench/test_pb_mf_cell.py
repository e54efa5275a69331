"""The rating cell's files (configuration, traffic, op kind, generator,
reference, work model): they resolve, the cell runs tiny through
`run.execute`, the control and the planted fault come out not correct, and
the step compiles for a described v5e at HugeWiki's table sizes with no
temporary as long as a table. New files only: the tiny cell is ADDED to a
copy of the benchmark."""

import os

import numpy as np
import pytest
from conftest import _add_cell, _dump, _load, _tiny_traffic, build_tiny_root

from benchmark import compare, manifest, ratings, run, work

CELL = "mf_adagrad_hugewiki.train_ratings"
CONFIG = "mf_adagrad_hugewiki"
TINY = "mf_tiny.ratings"
SEED = 2 ** 31 + 611
TINY_USERS, TINY_ITEMS, TINY_BLOCK, TINY_ROWS = 4096, 64, 64, 1000
STATE_BYTES = 50082603 * 2 * 16 * 4   # P, P_gg: 10 lanes held in 16 sublanes


@pytest.fixture(scope="module")
def mf_root(tmp_path_factory):
    """A copy of the benchmark with the tiny rating cell added: 4,096 users,
    64 items, blocks of 64, 1,000 ratings a call (the last block short)."""
    root = build_tiny_root(str(tmp_path_factory.mktemp("mf_root")))
    bench = os.path.join(root, "benchmark")
    man = _load(os.path.join(root, "BENCHMARK.json"))
    _tiny_traffic(bench, "train_ratings", "train_ratings_tiny", TINY_ROWS)
    c = _load(os.path.join(bench, "configs", CONFIG + ".json"))
    for old, new in (("-num_users 50082603", f"-num_users {TINY_USERS}"),
                     ("-num_items 39780", f"-num_items {TINY_ITEMS}"),
                     ("-mini_batch 1024", f"-mini_batch {TINY_BLOCK}")):
        assert old in c["options"]
        c["options"] = c["options"].replace(old, new)
    c.update(name="mf_tiny", num_features=TINY_USERS, num_users=TINY_USERS,
             num_items=TINY_ITEMS, mini_batch=TINY_BLOCK)
    _add_cell(man, bench, c, TINY, "train_ratings_tiny")
    man["configs"][-1]["reduced"] = []
    for m in man["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY)
    _dump(man, os.path.join(root, "BENCHMARK.json"))
    return root


def _execute(root, seed=SEED):
    cell = manifest.resolve(TINY, root=root)
    return cell, run.execute(cell, seed, 0.2, 0, log=open(os.devnull, "w"))


# ---- the committed files ----

def test_the_cell_is_one_chip_on_its_own_configuration_and_op_kind():
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic["op"] == "train_call_ratings"
    assert cell.traffic["rows_per_call"] == 1 << 20
    assert cell.traffic["epochs"] == 1
    cfg = cell.config
    assert cfg["entry_point"] == "train_mf_adagrad" and cfg["reference"] == "mf"
    for opt in ("-factor 10", "-num_users 50082603", "-num_items 39780",
                "-mini_batch 1024"):
        assert opt in cfg["options"]
    # HugeWiki's tables at Hivemall's width, nothing cut
    assert (cfg["num_users"], cfg["num_items"], cfg["factors"]) == (
        50082603, 39780, 10)
    assert cfg["num_features"] == cfg["num_users"] and cfg["reduced"] == []
    assert cfg["work_model"] == {"kind": "mf_minibatch", "factors": 10}
    for key in ("published", "assumed", "guarantees"):
        assert cfg[key]
    # what the options set is the reference's, and what they leave at
    # train_mf_adagrad's default is too
    args = cfg["reference_args"]
    for opt in ("mu", "maxval", "eta0"):
        assert f"-{opt} {args[opt]}" in cfg["options"]
    assert (args["factors"], args["lambda"], args["power_t"], args["eps"],
            args["scale"], args["seed"]) == (10, 0.03, 0.1, 1.0, 100.0, 31)
    limits = cfg["correct"]["limits"]
    assert set(limits) == {"rows_diff", "steps_diff", "p_gap", "q_gap",
                           "bias_gap", "rmse_gap"}
    assert set(cfg["correct"]["limits_why"]) == set(limits)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_mfu.train", "scatter_gather_roofline"} <= names


def test_the_cell_reports_the_span_metrics_and_leaves_the_last_six(
        span_metrics):
    """`train_mf_adagrad` speaks the tracer's vocabulary: the cell lists
    itself on the eight metrics that read it. The six jit and emission
    metrics that stand last list the six cells of their day, so the cell is
    on none of them, and it brings no metric of its own."""
    man = manifest.load_manifest()
    listed = [m["name"] for m in man["per_layer"]
              if CELL in m.get("workloads", [])]
    assert listed == span_metrics
    assert not any(CELL in m.get("workloads", [])
                   for m in man["per_layer"][-6:])


def test_the_reference_imports_nothing_of_the_program():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); import benchmark.refs.mf"
            "; bad = [m for m in sys.modules if m.startswith(('hivemall_tpu',"
            " 'jax'))]; assert not bad, bad" % manifest.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_work_counts_a_ratings_rows_and_biases():
    w = work.step_work(manifest.resolve(CELL).config)
    # a rating: 2 ids (8) + 4 rows of 10 lanes and 2 biases (168) gathered;
    # the ids again (8) + those 168 read and written (336) + 2 flags
    assert w["gather_bytes"] == 1024 * 176
    assert w["scatter_bytes"] == 1024 * 346
    assert w["gather_scatter_bytes"] == 1024 * 522
    assert w["bytes"] == 1024 * 526
    assert w["flops"] == 1024 * 295


def test_the_generator_has_the_sources_shape():
    cfg = manifest.resolve(CELL).config
    sp = ratings.make_split(cfg["data"], cfg["num_users"], cfg["num_items"],
                            1 << 16, SEED, 0)
    again = ratings.make_split(cfg["data"], cfg["num_users"],
                               cfg["num_items"], 1 << 16, SEED, 0)
    np.testing.assert_array_equal(sp.users, again.users)
    assert sp.users.max() < cfg["num_users"] and sp.items.max() < cfg["num_items"]
    # users: almost every one once; items: the head of a Zipf(1.0)
    _, per_user = np.unique(sp.users, return_counts=True)
    assert np.mean(per_user == 1) > 0.95
    top = np.bincount(sp.items).max() / sp.rows
    assert 0.07 < top < 0.11   # 1 / H(39,780) = 0.0895
    assert 1.0 <= sp.ratings.min() and sp.ratings.max() <= 5.0
    # the log-uniform counts' mean is HugeWiki's ratings a user
    pub = cfg["published"]
    m = cfg["data"]["user_count_max"]
    assert (m - 1) / np.log(m) == pytest.approx(
        pub["ratings"] / pub["users"], rel=1e-9)


# ---- the cell, tiny, through run.execute ----

def test_tiny_cell_runs_and_is_correct(mf_root):
    cell, line = _execute(mf_root)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert set(line["numbers"]) == set(cell.config["correct"]["limits"])
    assert line["numbers"]["steps_diff"]["value"] == 0.0
    assert line["numbers"]["rows_diff"]["value"] == 0.0
    assert line["notes"]["compared_calls"] == min(line["attempted"], 7)
    assert 0 < line["notes"]["heldout_rmse"] < 3


def test_the_control_is_not_correct(mf_root):
    """bfloat16 row tables in the program's place, where the configuration
    states float32."""
    cell = manifest.resolve(TINY, root=mf_root)
    op = run.make_op(cell, SEED + 3)
    op.setup()
    op.window(None, max_calls=2)
    checked = op.check(
        table_dtype=cell.config["correct"]["control"]["table_dtype"])
    numbers = compare.verdict(checked["numbers"],
                              cell.config["correct"]["limits"])
    assert not all(n["ok"] for n in numbers.values())


@pytest.mark.parametrize("seed", [SEED, SEED + 17])
def test_the_planted_fault_and_control_are_not_correct(mf_root, seed):
    from benchmark.tools import mf_faults

    cell = manifest.resolve(TINY, root=mf_root)
    got = mf_faults.readings(cell, seed)
    limits = cell.config["correct"]["limits"]
    for name in ("control", "dup_lane_dropped"):
        numbers = compare.verdict(dict(got[name], steps_diff=0.0), limits)
        assert not all(n["ok"] for n in numbers.values()), (name, got[name])
    # the fault is planted for the length of one reference only
    from benchmark.refs import mf as ref

    assert ref.item_lanes(np.array([1, 1])).all()


# ---- the step at HugeWiki's table sizes, for a described v5e ----

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


def test_step_compiles_in_place_with_no_table_long_temporary(one_chip):
    """A `[U, 10]` table is held with U minor: 16 sublanes of a row, 64 B.
    The block's rows are read and written by one gather and one scatter-add
    a table; a vmap of one row's read made the compiler copy P whole into
    128-lane rows (25.6 GB at HugeWiki's users), which no chip holds."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from hivemall_tpu.models.mf import MFHyper, init_mf_state, make_mf_step
    from hivemall_tpu.ops.eta import EtaEstimator

    cfg = manifest.resolve(CELL).config
    U, I, b = cfg["num_users"], cfg["num_items"], cfg["mini_batch"]
    hyper = MFHyper(factor=cfg["factors"], adagrad=True,
                    eta=EtaEstimator("invscaling",
                                        cfg["reference_args"]["eta0"]))
    state = jax.eval_shape(lambda: init_mf_state(U, I, hyper))
    on = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    block = (jax.ShapeDtypeStruct((b,), jnp.int32),
             jax.ShapeDtypeStruct((b,), jnp.int32),
             jax.ShapeDtypeStruct((b,), jnp.float32))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(make_mf_step(hyper, jit=False),
                           donate_argnums=(0,)).lower(
            on(state), *on(block)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    m = compiled.memory_analysis()
    # the user side as laid out, and the whole state over a quarter of 16 GiB
    assert STATE_BYTES <= m.argument_size_in_bytes <= 1.05 * STATE_BYTES
    assert m.argument_size_in_bytes >= 0.25 * 16 * 2 ** 30
    # every table written in place, and no temporary as long as one
    assert m.alias_size_in_bytes >= STATE_BYTES
    assert m.temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    assert "f32[50082603,10]{1,0" not in text
