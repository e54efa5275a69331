"""The FFM cell's files (configuration, traffic, op kind, reference, work
model, the two readers): they resolve, the cell runs tiny through
`run.execute` with field lanes, the control and each planted fault come out
not correct, the readers read hand-made traces, and the mini-batch step
compiles for a described v5e at the committed shapes inside its byte
bounds. New files only: the tiny cell is ADDED to a copy of the benchmark."""

import json
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import compare, manifest, run, work

CELL = "ffm_criteo1tb.train_replay"
CONFIG = "ffm_criteo1tb"
SEED = 2 ** 31 + 277
TINY = "ffm_tiny.replay"
TINY_BITS = {"-feature_hashing 23": "-feature_hashing 14",
             "-p 8388608": "-p 16384", "-v_bits 28": "-v_bits 18",
             "-mini_batch 1024": "-mini_batch 256"}
HBM_BYTES = 15.75 * 2 ** 30   # what the compiler itself reports for a v5e


def _load(path):
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def ffm_root(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("ffm_root"))
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = manifest.load_manifest()
    bench = os.path.join(dst, "benchmark")
    t = _load(os.path.join(bench, "traffic", "train_replay_fields.json"))
    t["rows_per_call"] = 1024
    _dump(t, os.path.join(bench, "traffic", "train_replay_fields_tiny.json"))
    c = _load(os.path.join(bench, "configs", CONFIG + ".json"))
    for old, new in TINY_BITS.items():
        assert old in c["options"]
        c["options"] = c["options"].replace(old, new)
    c.update(name="ffm_tiny", num_features=1 << 14, v_dims=1 << 18,
             mini_batch=256)
    _dump(c, os.path.join(bench, "configs", "ffm_tiny.json"))
    man["configs"].append({
        "name": "ffm_tiny", "source": "test", "reduced": ["num_features"],
        "file": "benchmark/configs/ffm_tiny.json", "why": "tiny"})
    man["workloads"].append({"name": TINY, "config": "ffm_tiny", "chips": 1,
                             "traffic": "train_replay_fields_tiny",
                             "why": "tiny"})
    _dump(man, os.path.join(dst, "BENCHMARK.json"))
    return dst


def _execute(root, seed=SEED):
    cell = manifest.resolve(TINY, root=root)
    return cell, run.execute(cell, seed, 0.2, 0, log=open(os.devnull, "w"))


# ---- the committed files ----

def test_the_cell_is_one_chip_on_its_own_configuration_and_op_kind():
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic["op"] == "train_call_fields"
    assert cell.traffic["rows_per_call"] == 1 << 15
    cfg = cell.config
    assert cfg["entry_point"] == "train_ffm" and cfg["reference"] == "ffm"
    for opt in ("-factor 4", "-feature_hashing 23", "-num_fields 39",
                "-v_bits 28", "-mini_batch 1024"):
        assert opt in cfg["options"]
    # no width changed: 39 fields, 38 partners a feature, k = 4, f32 tables
    assert cfg["work_model"] == {"kind": "ffm_minibatch", "factors": 4,
                                 "fields": 39}
    assert cfg["data"]["numeric_lanes"] + cfg["data"]["categorical_lanes"] == 39
    assert cfg["table_dtype"] == "float32" and list(cfg["reduced"]) == [
        "num_features"]
    assert cfg["v_dims"] == 1 << 28 and cfg["num_features"] == 1 << 23
    for key in ("published", "assumed", "guarantees", "initial_v"):
        assert cfg[key]
    # what the options leave at train_ffm's default is what the reference has
    args = cfg["reference_args"]
    assert f"-eta0_V {args['eta0_v']}" in cfg["options"]
    assert (args["eps"], args["alpha"], args["beta"], args["lambda1"],
            args["lambda2"], args["lambda0"], args["sigma"], args["seed"]) == (
                1.0, 0.1, 1.0, 0.1, 0.01, 0.01, 0.1, 31)
    # the seven unlisted per-layer metrics and the two it brings
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 9
    assert {"step_mfu.train", "scatter_gather_roofline",
            "pair_gather_roofline", "pair_scatter_roofline"} <= set(names)
    assert "stage_ms_per_krow.train" not in names   # PR 26's lists: unedited


def test_no_program_span_metric_was_added():
    """`test_pb_program_spans.py` pins the program_span and program_counter
    metrics at its eight on their three cells and may not be edited here:
    the pair-lane counters stay counters of the program (PERF.md section 7)."""
    new = [m for m in manifest.load_manifest()["per_layer"]
           if m.get("workloads") == [CELL]]
    assert sorted(m["name"] for m in new) == [
        "pair_gather_roofline", "pair_scatter_roofline"]
    assert {m["source"] for m in new} == {"device_trace"}
    assert {m["layer"] for m in new} == {"kernels"}


def test_the_reference_imports_nothing_of_the_program():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); import benchmark.refs.ffm"
            "; bad = [m for m in sys.modules if m.startswith(('hivemall_tpu',"
            " 'jax'))]; assert not bad, bad" % manifest.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_the_reference_has_the_documented_hash_and_initial_value():
    """Its own `pair_hash` and `initial_v`, from the documented formulas,
    give what the program's give, bit for bit."""
    import jax.numpy as jnp

    from benchmark.refs import ffm as ref
    from hivemall_tpu.models import ffm as prog

    rng = np.random.default_rng(3)
    feats = rng.integers(0, 1 << 23, 4096)
    fields = rng.integers(0, 39, 4096)
    np.testing.assert_array_equal(
        ref.pair_hash(feats, fields, 1 << 28),
        np.asarray(prog.pair_hash(jnp.asarray(feats), jnp.asarray(fields),
                                  1 << 28)))
    entries = rng.integers(0, 1 << 28, 4096)
    mine = ref.initial_v(entries, 4, 31, 0.1)
    np.testing.assert_array_equal(
        mine.astype(np.float32),
        np.asarray(prog.initial_v(jnp.asarray(entries), 4, 31, 0.1)))
    # a bell of standard deviation sigma around zero
    assert abs(mine.mean()) < 0.01 and abs(mine.std() - 0.1) < 0.005


def test_work_counts_real_pairs_and_splits_its_bytes():
    cfg = manifest.resolve(CELL).config
    w = work.step_work(cfg)
    lanes, pairs = 1024 * 39, 1024 * 39 * 38
    assert (w["lanes"], w["pairs"]) == (lanes, pairs)
    assert w["gather_bytes"] == pairs * (4 + 20) + lanes * (4 + 12)
    assert w["scatter_bytes"] == pairs * (4 + 40 + 1) + lanes * (4 + 24 + 1)
    assert w["gather_scatter_bytes"] == w["gather_bytes"] + w["scatter_bytes"]
    assert w["bytes"] == w["gather_scatter_bytes"] + lanes * 8 + 1024 * 4
    assert w["flops"] == pairs * 32 + lanes * 12
    # bandwidth bounds it: 105 MB a step is 0.13 ms at a v5e's peak
    peaks = work.peaks_for("TPU v5 lite")
    assert work.least_seconds(w, peaks) == w["bytes"] / peaks["bytes_per_s"]


# ---- the cell, tiny, through run.execute ----

def test_tiny_ffm_cell_runs_with_field_lanes_and_is_correct(ffm_root):
    cell, line = _execute(ffm_root)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert set(line["numbers"]) == set(cell.config["correct"]["limits"])
    assert line["numbers"]["steps_diff"]["value"] == 0.0
    assert line["numbers"]["rows_diff"]["value"] == 0.0
    assert line["notes"]["compared_calls"] == min(line["attempted"], 7)
    from benchmark import datagen

    ids, vals, fields = run.make_op(cell, SEED)._form(datagen.make_split(
        cell.config["data"], 1 << 14, 8, SEED, 0))
    assert ids.shape == vals.shape == fields.shape == (8, 39)
    assert (fields == np.arange(39)).all()


def _half_of_each_block_left_out(monkeypatch):
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.models import ffm

    real = ffm.make_ffm_step

    def make(hyper, mode="scan", **kw):
        step = real(hyper, mode, **dict(kw, jit=False))

        def half(state, indices, values, fields, labels):
            b = indices.shape[0]
            keep = (np.arange(b) < max(1, b // 2))[:, None]
            st, loss = step(state, jnp.where(keep, indices, hyper.num_features),
                            jnp.where(keep, values, 0.0), fields, labels)
            return st.replace(step=state.step + b), loss

        return jax.jit(half, donate_argnums=(0,))

    monkeypatch.setattr(ffm, "make_ffm_step", make)


def _own_field_pairs(monkeypatch):
    """FM's term under FFM's name: a lane pairs with its OWN field's entry."""
    import jax.numpy as jnp

    from hivemall_tpu.models import ffm

    def own_field(idx, fields, dv):
        k = idx.shape[0]
        return ffm.pair_hash(
            idx[:, None].astype(jnp.uint32),
            jnp.broadcast_to(fields[:, None], (k, k)).astype(jnp.uint32), dv)

    monkeypatch.setattr(ffm, "_row_pair_keys", own_field)


def _answer_altered(monkeypatch):
    from hivemall_tpu.models import ffm

    real = ffm.TrainedFFMModel.model_rows

    def model_rows(self):
        w0, feats, w, v_keys, v = real(self)
        v = np.array(v)
        v[int(np.argmax(np.abs(v).sum(axis=1)))] *= 1.01
        return w0, feats, w, v_keys, v

    monkeypatch.setattr(ffm.TrainedFFMModel, "model_rows", model_rows)


FAULTS = {"half_block": _half_of_each_block_left_out,
          "own_field": _own_field_pairs, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_ffm_path_is_not_correct(ffm_root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    _, line = _execute(ffm_root, seed=SEED + 1)
    assert line["correct"] is False
    assert not all(n["ok"] for n in line["numbers"].values())


@pytest.mark.parametrize("fault", ["half_block", "own_field"])
def test_the_reference_plants_the_same_faults(ffm_root, fault):
    """The faults as `benchmark/refs/ffm.py` plants them in its own place
    (how they are read at the cell's full size): each fails a limit."""
    from benchmark import datagen
    from benchmark.refs import ffm as ref

    cfg = manifest.resolve(TINY, root=ffm_root).config
    split = datagen.make_split(cfg["data"], cfg["num_features"], 1024, SEED, 0)
    sound = ref.reference(split, cfg, 1)[0]
    wrong = ref.reference(split, cfg, 1, fault=fault)[0]
    verdict = compare.verdict(compare.model_gaps(wrong, sound),
                              {k: v for k, v in cfg["correct"]["limits"].items()
                               if k not in ("steps_diff", "logloss_gap")})
    assert not all(n["ok"] for n in verdict.values())


def test_lower_storage_control_is_not_correct(ffm_root):
    cell = manifest.resolve(TINY, root=ffm_root)
    op = run.make_op(cell, SEED + 2)
    op.setup()
    op.window(None, max_calls=1)
    limits = cell.config["correct"]["limits"]
    sound = compare.verdict(op.check()["numbers"], limits)
    low = compare.verdict(op.check(table_dtype="bfloat16")["numbers"], limits)
    assert all(n["ok"] for n in sound.values())
    assert not all(n["ok"] for n in low.values())


# ---- the two readers, on hand-made traces ----

def _ctx(class_s, steps=64, peaks=True, config=None, device_ops=()):
    cell = manifest.resolve(CELL)
    if config is not None:
        cell.config = config
    return SimpleNamespace(
        cell=cell, result={"steps": steps},
        trace=None if class_s is None else {"class_s": class_s,
                                            "device_ops": list(device_ops)},
        peaks=work.peaks_for("TPU v5 lite") if peaks else None)


def test_the_two_readers_split_the_gather_scatter_roofline():
    from benchmark.readers import (pair_gather_roofline, pair_scatter_roofline,
                                   scatter_gather_roofline)

    w = work.step_work(manifest.resolve(CELL).config)
    ctx = _ctx({"gather": 2.0, "scatter": 6.0, "dense": 1.0})
    g, s = pair_gather_roofline.read(ctx), pair_scatter_roofline.read(ctx)
    assert g == pytest.approx(100 * w["gather_bytes"] * 64 / 819e9 / 2.0)
    assert s == pytest.approx(100 * w["scatter_bytes"] * 64 / 819e9 / 6.0)
    # the two together are the accepted metric, time-weighted
    both = scatter_gather_roofline.read(ctx)
    assert both == pytest.approx((g * 2.0 + s * 6.0) / 8.0)
    assert 0 < g < 100 and 0 < s < 100
    for reader, half in ((pair_gather_roofline, "gather"),
                         (pair_scatter_roofline, "scatter")):
        # no such op in the trace, an untraced run, no steps: left out
        assert reader.read(_ctx({half: 0.0})) is None
        assert reader.read(_ctx({})) is None
        assert reader.read(_ctx(None)) is None
        assert reader.read(_ctx({half: 1.0}, steps=0)) is None
        assert reader.read(_ctx({half: 1.0}, peaks=False)) is None
        # a work model that does not split its bytes (every other cell's),
        # or none at all: nothing is returned and nothing is raised
        fm = manifest.resolve("fm_criteo1tb.train_replay").config
        assert reader.read(_ctx({half: 1.0}, config=fm)) is None
        assert reader.read(_ctx({half: 1.0}, config={})) is None


def test_an_in_place_write_classed_gather_counts_with_the_scatters():
    """`xplane.classify` reads the flag scatter (a constant set at the keys:
    no float operand after the indices) as a gather; its result is as long
    as the V table, which no gather's is, so its seconds and its bytes meet
    in the scatter half. The ops as the chip named them (PR 32's trace)."""
    from benchmark.readers import pair_gather_roofline, pair_scatter_roofline

    w = work.step_work(manifest.resolve(CELL).config)
    ops = [["%fusion.6 fusion f32[268435456,4] [scatter]", 8.5],
           ["%while.13 while s32[] [dense]", 2.6],
           ["%fusion.75 fusion f32[409600] [gather]", 1.4],
           ["%fusion.73 fusion f32[409600,4] [gather]", 1.1],
           ["%fusion.8 fusion s8[268435456] [gather]", 0.7],
           ["%fusion.9 fusion s8[8388608] [gather]", 0.05],
           ["%fusion fusion f32[524288,4] [gather]", 0.4],
           ["%sort.2 sort s32[1638400] [dense]", 0.1]]
    ctx = _ctx({"gather": 3.75, "scatter": 9.5, "dense": 3.0}, device_ops=ops)
    g, s = pair_gather_roofline.read(ctx), pair_scatter_roofline.read(ctx)
    assert g == pytest.approx(100 * w["gather_bytes"] * 64 / 819e9 / 3.0)
    assert s == pytest.approx(100 * w["scatter_bytes"] * 64 / 819e9 / 10.25)
    # nothing but in-place writes classed gather: the gather half has no op
    only = _ctx({"gather": 0.75, "scatter": 9.5}, device_ops=ops[4:6])
    assert pair_gather_roofline.read(only) is None
    assert pair_scatter_roofline.read(only) == pytest.approx(s)


def test_every_number_with_two_readings_is_limited():
    """`logloss_gap` has a lower reading (sound runs at most 2.6e-6) and an
    upper one (the planted faults, 6.6e-3 and more): it is held to a limit
    between them, and nothing computed is left out of `correct`."""
    correct = manifest.resolve(CELL).config["correct"]
    assert set(correct) == {"limits", "control"}
    assert set(correct["limits"]) == {"rows_diff", "steps_diff", "w0_gap",
                                      "w_gap", "v_gap", "logloss_gap"}
    assert 10 * 2.58e-6 < correct["limits"]["logloss_gap"] < 6.6e-3 / 10


# ---- the step compiles for a described v5e at the cell's shapes ----

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


def _compiled(fn, args, donate=()):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def test_step_and_init_compile_compact_with_no_table_long_temporary(one_chip):
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.models.ffm import (ROW_TILE_BYTES, FFMHyper,
                                         choose_row_tile, init_ffm_state,
                                         make_ffm_step)

    cfg = manifest.resolve(CELL).config
    hyper = FFMHyper(factors=cfg["factors"], num_features=cfg["num_features"],
                     num_fields=cfg["fields"], v_dims=cfg["v_dims"],
                     eta0_v=cfg["reference_args"]["eta0_v"])
    on = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    state = jax.eval_shape(lambda: init_ffm_state(hyper))
    b, width, pair_width = cfg["mini_batch"], 64, 40
    block = (jax.ShapeDtypeStruct((b, width), jnp.int32),
             jax.ShapeDtypeStruct((b, width), jnp.float32),
             jax.ShapeDtypeStruct((b, width), jnp.int32),
             jax.ShapeDtypeStruct((b,), jnp.float32))
    step = make_ffm_step(hyper, "minibatch", pair_width=pair_width, jit=False)
    m = _compiled(step, (on(state),) + on(block), donate=(0,)).memory_analysis()
    # the compact row of ISSUE 32's table: 21 B an entry and the linear tables
    logical = cfg["v_dims"] * (4 * 4 + 4 + 1) + cfg["num_features"] * 13
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree_util.tree_leaves(state))
    assert abs(state_bytes - logical) <= 16
    args = m.argument_size_in_bytes - sum(
        s.size * s.dtype.itemsize for s in block)
    assert abs(args - logical) <= 0.05 * logical, (args, logical)
    assert m.alias_size_in_bytes >= logical     # written in place
    # nothing as long as a table: the block's deltas and keys, and a tile
    tile = choose_row_tile(b, pair_width, hyper.factors)
    assert tile * pair_width ** 2 * (hyper.factors + 1) * 4 <= ROW_TILE_BYTES
    assert m.temp_size_in_bytes <= TEMP_BOUND, m.temp_size_in_bytes
    assert m.temp_size_in_bytes < cfg["v_dims"]        # not even one byte each
    # two states and the step's scratch under three quarters of the chip
    assert 2 * args + m.temp_size_in_bytes <= 0.75 * HBM_BYTES
    # the state at least 26% of the chip's 16.9 GB (the cell's floor)
    assert args >= 0.26 * 16.9e9
    # init: one program, V written where it will live
    mi = _compiled(lambda: init_ffm_state(hyper), ()).memory_analysis()
    assert mi.temp_size_in_bytes <= 1 << 20
    assert abs(mi.output_size_in_bytes - logical) <= 0.05 * logical


# 64 MiB (the block's deltas and keys are 39 MB of it) and a tile's live
# activations; the compiler reads 65.6 MiB (my AOT run, PR 32)
TEMP_BOUND = (64 << 20) + 4 * (8 << 20)
