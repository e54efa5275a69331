"""The AdaGradRDA cell's files (configuration, reference, work model, the one
reader): they resolve, the cell runs tiny through `run.execute` with
bfloat16 weights beside float32 slots, the control and each planted fault
come out not correct, the reader reads hand-made traces, and the mini-batch
step compiles for a described v5e at the committed shapes inside its byte
bounds. New files only: the tiny cell is ADDED to a copy of the benchmark."""

import json
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, run, work

CELL = "adagrad_rda_criteo1tb.train_replay"
CONFIG = "adagrad_rda_criteo1tb"
SEED = 2 ** 31 + 341
TINY = "adagrad_rda_tiny.replay"
TINY_DIMS = 1 << 25   # over 2^24: bfloat16 weights, and the block-local arm
STATE_BYTES = 5_905_580_032
HBM_BYTES = 15.75 * 2 ** 30   # what the compiler itself reports for a v5e


def _load(path):
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def rda_root(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("rda_root"))
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = manifest.load_manifest()
    bench = os.path.join(dst, "benchmark")
    t = _load(os.path.join(bench, "traffic", "train_replay.json"))
    t["rows_per_call"] = 4096
    _dump(t, os.path.join(bench, "traffic", "train_replay_rda_tiny.json"))
    c = _load(os.path.join(bench, "configs", CONFIG + ".json"))
    assert str(c["num_features"]) in c["options"]
    c["options"] = c["options"].replace(str(c["num_features"]), str(TINY_DIMS))
    c.update(name="adagrad_rda_tiny", num_features=TINY_DIMS)
    _dump(c, os.path.join(bench, "configs", "adagrad_rda_tiny.json"))
    man["configs"].append({
        "name": "adagrad_rda_tiny", "source": "test",
        "reduced": ["num_features"],
        "file": "benchmark/configs/adagrad_rda_tiny.json", "why": "tiny"})
    man["workloads"].append({"name": TINY, "config": "adagrad_rda_tiny",
                             "chips": 1, "traffic": "train_replay_rda_tiny",
                             "why": "tiny"})
    _dump(man, os.path.join(dst, "BENCHMARK.json"))
    return dst


def _execute(root, seed=SEED):
    cell = manifest.resolve(TINY, root=root)
    return cell, run.execute(cell, seed, 0.2, 0, log=open(os.devnull, "w"))


# ---- the committed files ----

def test_the_cell_is_one_chip_on_its_own_configuration():
    cell = manifest.resolve(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.traffic_name == "train_replay"
    assert cell.traffic["op"] == "train_call"
    assert cell.traffic["rows_per_call"] == 1 << 17
    cfg = cell.config
    assert cfg["entry_point"] == "train_adagrad_rda"
    assert cfg["reference"] == "adagrad_rda"
    # Hivemall's defaults but -dims and -mini_batch
    assert cfg["options"] == "-dims 536870912 -mini_batch 1024"
    assert cfg["num_features"] == 1 << 29 and cfg["mini_batch"] == 1024
    assert cfg["reference_args"] == {"eta": 0.1, "lambda": 1e-6,
                                     "scale": 100.0, "storage": "bfloat16"}
    assert cfg["table_dtype"] == "bfloat16" and "slot_dtype" not in cfg
    assert cfg["work_model"] == {"kind": "linear_minibatch_slots",
                                 "table_bytes": [2, 4, 4]}
    # the rows are the accepted configurations' rows, byte for byte
    assert cfg["data"] == manifest.resolve(
        "arow_criteo1tb.train_replay").config["data"]
    assert list(cfg["reduced"]) == ["num_features"]
    for key in ("published", "assumed", "guarantees", "deployment"):
        assert cfg[key]
    assert set(cfg["correct"]["limits"]) == {"rows_diff", "steps_diff",
                                             "w_gap", "logloss_gap"}
    assert cfg["correct"]["limits"]["rows_diff"] == 0
    assert cfg["correct"]["control"]["table_dtype"] == "bfloat16"
    # the seven unlisted per-layer metrics and the one it brings
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 8 and names[-1] == "slot_tables_device_pct.train"
    assert {"step_mfu.train", "scatter_gather_roofline"} <= set(names)
    assert "stage_ms_per_krow.train" not in names   # PR 26's lists: unedited


def test_one_metric_was_added_and_no_program_span_metric():
    """`test_pb_program_spans.py` pins the program_span and program_counter
    metrics at its eight on their three cells and may not be edited here."""
    new = [m for m in manifest.load_manifest()["per_layer"]
           if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == ["slot_tables_device_pct.train"]
    assert new[0]["source"] == "device_trace"
    assert (new[0]["layer"], new[0]["moves"]) == ("engine step",
                                                  "train_rows_per_s")


def test_the_reference_imports_nothing_of_the_program():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.refs.adagrad_rda; "
            "bad = [m for m in sys.modules if m.startswith(('hivemall_tpu',"
            " 'jax', 'benchmark.refs.arow'))]; assert not bad, bad"
            % manifest.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_work_counts_real_lanes_of_mixed_width():
    cfg = manifest.resolve(CELL).config
    w = work.step_work(cfg)
    lanes = 1024 * 39
    assert w["lanes"] == lanes
    # id + value's gather of 10 B + id + a read-modify-write of 10 B + flag
    assert w["gather_scatter_bytes"] == lanes * 39 == 1_557_504
    assert w["bytes"] == w["gather_scatter_bytes"] + lanes * 4 + 1024 * 4
    peaks = work.peaks_for("TPU v5 lite")
    assert work.least_seconds(w, peaks) == w["bytes"] / peaks["bytes_per_s"]
    # AROW's two bfloat16 tables are 21 B a lane: the accepted model stands
    assert work.step_work(manifest.resolve(
        "arow_criteo1tb.train_replay").config)["gather_scatter_bytes"] \
        == lanes * 21


# ---- the reader, on hand-made traces ----

def _ctx(device_ops, busy_s=10.0, **cfg):
    config = dict(manifest.resolve(CELL).config, **cfg)
    return SimpleNamespace(
        cell=SimpleNamespace(config=config),
        trace={"busy_s": busy_s, "device_ops": device_ops})


def test_the_reader_counts_the_slot_tables_writes_and_gathers():
    from benchmark.readers import slot_tables_device_pct as reader

    ops = [["%fusion.4 fusion f32[536870912] [scatter]", 2.0],
           ["%fusion.5 fusion f32[536870912] [scatter]", 2.0],
           ["%fusion.3 fusion bf16[536870912] [scatter]", 1.5],   # w
           ["%fusion.6 fusion s8[536870912] [gather]", 1.0],      # the flag
           ["%fusion.1 fusion f32[65536] [gather]", 0.5],
           ["%fusion.2 fusion f32[65536] [gather]", 0.5],
           ["%fusion fusion bf16[65536] [gather]", 0.4],          # w
           ["%fusion fusion bf16[524288] [gather]", 0.3],         # emission
           ["%sort.8 sort s32[65536] [dense]", 0.1],
           ["%while.10 while s32[] [dense]", 0.1]]
    assert reader.read(_ctx(ops)) == pytest.approx(50.0)
    # a write or a gather that is not listed (fused away, renamed, under the
    # ten longest), or one too many: nothing, not a smaller share
    for gone in (0, 1, 4, 5):
        assert reader.read(_ctx(ops[:gone] + ops[gone + 1:])) is None
    assert reader.read(_ctx(
        ops + [["%fusion.9 fusion f32[536870912] [scatter]", 0.1]])) is None
    # float32 weights (their ops would read as a slot's), a work model with
    # no float32 slot, a trace with no such op, no trace: nothing, no error
    slots = lambda widths: {"kind": "linear_minibatch_slots",
                            "table_bytes": widths}
    assert reader.read(_ctx(ops, work_model=slots([4, 4, 4]))) is None
    assert reader.read(_ctx(ops, work_model=slots([2, 2]))) is None
    assert reader.read(_ctx(ops[2:4] + ops[6:])) is None
    assert reader.read(SimpleNamespace(
        cell=SimpleNamespace(config=manifest.resolve(CELL).config),
        trace=None)) is None
    arow = SimpleNamespace(
        cell=SimpleNamespace(config=manifest.resolve(
            "arow_criteo1tb.train_replay").config),
        trace={"busy_s": 1.0, "device_ops": ops})
    assert reader.read(arow) is None


# ---- the cell, tiny, through run.execute ----

def test_tiny_cell_runs_with_mixed_width_tables_and_is_correct(rda_root):
    cell, line = _execute(rda_root)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert set(line["numbers"]) == set(cell.config["correct"]["limits"])
    assert line["numbers"]["steps_diff"]["value"] == 0.0
    assert line["numbers"]["rows_diff"]["value"] == 0.0
    assert line["notes"]["compared_calls"] == min(line["attempted"], 7)


def test_the_tool_adds_heldout_accuracy_to_the_notes(rda_root):
    from benchmark.tools import heldout_run

    op = run.make_op(manifest.resolve(TINY, root=rda_root), SEED)
    op.setup()
    op.window(None, max_calls=2)
    notes = heldout_run.heldout_notes(op)
    lo, hi = notes["heldout_accuracy"]
    assert 0.0 <= lo <= hi <= 1.0
    assert 0.5 <= notes["majority_share"] < 0.6
    # four blocks teach little, but the weights are not all inside the ball
    assert 0.0 <= notes["zero_weight_share"] < 0.5


def _half_of_each_block_left_out(monkeypatch):
    import jax
    import jax.numpy as jnp

    from hivemall_tpu.core import engine
    from hivemall_tpu.models import base

    def make(rule, hyper, mode="minibatch", donate=True):
        step = engine.make_train_fn(rule, hyper, mode=mode)

        def half(state, indices, values, labels):
            b = indices.shape[0]
            keep = (np.arange(b) < max(1, b // 2))[:, None]
            st, loss = step(state,
                            jnp.where(keep, indices, state.weights.shape[0]),
                            jnp.where(keep, values, 0.0), labels)
            return st.replace(step=state.step + b), loss

        return jax.jit(half, donate_argnums=(0,))

    monkeypatch.setattr(base, "make_train_step", make)


def _slots_summed(monkeypatch):
    """The step before PR 34: every slot a plain sum of the rows' deltas, so
    G takes a block's summed squares and not its summed gradient's square."""
    import dataclasses

    from hivemall_tpu.models import classifier

    monkeypatch.setattr(classifier, "ADAGRAD_RDA", dataclasses.replace(
        classifier.ADAGRAD_RDA, block_slots=None))


def _answer_altered(monkeypatch):
    from hivemall_tpu.models import base

    real = base.TrainedLinearModel.model_rows

    def model_rows(self, filter_zero=False):
        feats, w = real(self, filter_zero)
        w = np.array(w)
        i = int(np.argmax(np.abs(w.astype(np.float32))))
        w[i] = -w[i]          # bfloat16 holds 0.4%: the sign, not 1%
        return feats, w

    monkeypatch.setattr(base.TrainedLinearModel, "model_rows", model_rows)


FAULTS = {"half_block": _half_of_each_block_left_out,
          "slots_summed": _slots_summed, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_the_slot_path_is_not_correct(rda_root, monkeypatch, fault):
    FAULTS[fault](monkeypatch)
    _, line = _execute(rda_root, seed=SEED + 1)
    assert line["correct"] is False
    assert not all(n["ok"] for n in line["numbers"].values())


@pytest.mark.parametrize("fault", ["half_block", "slots_summed"])
def test_the_reference_plants_the_same_faults(rda_root, fault):
    """`tools/ref_faults.py` reads the faults at the cell's own size
    by planting them in the reference: the same plant fails here too."""
    from benchmark import compare, datagen
    from benchmark.refs import adagrad_rda as ref
    from benchmark.tools import ref_faults

    cell = manifest.resolve(TINY, root=rda_root)
    cfg = cell.config
    sp = datagen.make_split(cfg["data"], TINY_DIMS, 4096, SEED + 2, 0)
    sound, info = ref.reference(sp, cfg, 1)
    with ref_faults.planted(ref, fault):
        faulty, _ = ref.reference(sp, cfg, 1)
    assert ref.reference(sp, cfg, 1)[0]["tables"]["w"].tobytes() == \
        sound["tables"]["w"].tobytes()      # the plant is gone again
    gaps = compare.model_gaps(faulty, sound)
    numbers = compare.verdict(dict(gaps, steps_diff=0.0, logloss_gap=0.0),
                              cfg["correct"]["limits"])
    assert not all(n["ok"] for n in numbers.values())
    assert 0 <= info["zero_weights"] < 0.5 * sound["feats"].size


def test_the_control_is_not_correct(rda_root):
    """bfloat16 SLOTS in the program's place: what upstream's half-float
    model would store, where the configuration states float32."""
    cell = manifest.resolve(TINY, root=rda_root)
    op = run.make_op(cell, SEED + 3)
    op.setup()
    op.window(None, max_calls=2)
    checked = op.check(
        table_dtype=cell.config["correct"]["control"]["table_dtype"])
    from benchmark import compare

    numbers = compare.verdict(checked["numbers"],
                              cell.config["correct"]["limits"])
    assert not all(n["ok"] for n in numbers.values())


# ---- the step at the cell's shapes, for a described v5e ----

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


def test_step_compiles_in_place_with_no_temporary_as_long_as_the_table(one_chip):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from hivemall_tpu.core.engine import apply_strategy, make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import ADAGRAD_RDA

    cfg = manifest.resolve(CELL).config
    dims, b = cfg["num_features"], cfg["mini_batch"]
    assert apply_strategy(dims, b * 64) == "batch_local"
    state = jax.eval_shape(lambda: init_linear_state(
        dims, slot_names=ADAGRAD_RDA.slot_names,
        dtype=jnp.dtype(cfg["table_dtype"])))
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree_util.tree_leaves(state))
    assert state_bytes == STATE_BYTES + 4    # 11 B an entry, and the counter
    block = (jax.ShapeDtypeStruct((b, 64), jnp.int32),
             jax.ShapeDtypeStruct((b, 64), jnp.float32),
             jax.ShapeDtypeStruct((b,), jnp.float32))
    on = lambda tree: jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    args = cfg["reference_args"]
    step = make_train_fn(ADAGRAD_RDA, {"eta": args["eta"],
                                       "lambda": args["lambda"],
                                       "scale": args["scale"]},
                         mode="minibatch")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            on(state), *on(block)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    m = compiled.memory_analysis()
    block_bytes = 2 * b * 64 * 4 + b * 4
    assert abs(m.argument_size_in_bytes - (STATE_BYTES + block_bytes)) \
        <= 0.02 * STATE_BYTES, m.argument_size_in_bytes
    # every table written in place, and nothing else as long as one
    assert m.alias_size_in_bytes >= STATE_BYTES
    assert m.temp_size_in_bytes < 8 * 2 ** 20, m.temp_size_in_bytes
    # two states alive at one reading and the scratch: under three quarters
    assert 2 * STATE_BYTES + m.temp_size_in_bytes <= 0.75 * HBM_BYTES
    # a third of the chip by the state alone: over the driver's floor
    assert STATE_BYTES >= 0.30 * 16_909_336_064
