"""The `-mix` cell's files (configuration, traffic, reference, work model,
metric readers): they resolve, the cell runs tiny through `run.execute` on
four of the CPU's virtual devices, each planted fault comes out not correct,
the readers read hand-made spans and traces, and the replicated step and the
mix compile for a described v5e:2x2 at the committed dims (upper bound
only). New files only: the tiny cell is ADDED to a copy of the benchmark."""

import json
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import manifest, run, work

CELL = "arow_criteo1tb_mix4.train_replay"
CONFIG = "arow_criteo1tb_mix4"
SEED = 2 ** 31 + 177
REPLICAS = 4
TINY = {"arow_mix_tiny.replay": 1 << 16,     # float32 tables, dense apply
        "arow_mix_bf16.replay": 1 << 25}     # bfloat16, block-local apply
F32_LIMITS = {"rows_diff": 0, "steps_diff": 0, "w_gap": 1e-4, "cov_gap": 1e-5,
              "logloss_gap": 5e-7}
HBM_BYTES = 15.75 * 2 ** 30   # what the compiler itself reports for a v5e


def _load(path):
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


@pytest.fixture(scope="module")
def mix_root(tmp_path_factory):
    dst = str(tmp_path_factory.mktemp("mix_root"))
    shutil.copytree(os.path.join(manifest.ROOT, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = manifest.load_manifest()
    bench = os.path.join(dst, "benchmark")
    t = _load(os.path.join(bench, "traffic", "train_replay_4x.json"))
    t["rows_per_call"] = 4096
    _dump(t, os.path.join(bench, "traffic", "train_replay_4x_tiny.json"))
    for name, dims in TINY.items():
        tiny = name.split(".")[0]
        c = _load(os.path.join(bench, "configs", CONFIG + ".json"))
        c["options"] = c["options"].replace(str(c["num_features"]), str(dims)) \
            .replace("-mini_batch 1024", "-mini_batch 256") \
            .replace("-mix_threshold 16", "-mix_threshold 3")
        c.update(name=tiny, num_features=dims, mini_batch=256)
        c["reference_args"]["mix_every"] = 3
        if dims <= 1 << 24:
            c["table_dtype"] = "float32"
            c["reference_args"].pop("storage")
            c["correct"]["limits"] = dict(F32_LIMITS)
        _dump(c, os.path.join(bench, "configs", tiny + ".json"))
        man["configs"].append({
            "name": tiny, "source": "test", "reduced": ["num_features"],
            "file": f"benchmark/configs/{tiny}.json", "why": "tiny"})
        man["workloads"].append({"name": name, "config": tiny, "chips": 4,
                                 "traffic": "train_replay_4x_tiny",
                                 "why": "tiny"})
    _dump(man, os.path.join(dst, "BENCHMARK.json"))
    return dst


@pytest.fixture
def four_replicas(monkeypatch):
    import jax

    from hivemall_tpu.parallel import mix

    devices = jax.local_devices()[:REPLICAS]
    assert len(devices) == REPLICAS, "the tests' virtual CPU mesh is missing"
    monkeypatch.setattr(mix, "mix_devices", lambda: devices)


def _execute(root, workload, seed=SEED):
    cell = manifest.resolve(workload, root=root)
    return cell, run.execute(cell, seed, 0.2, 0, log=open(os.devnull, "w"))


# ---- the committed files ----

def test_the_cell_is_one_four_chip_train_call_on_its_own_configuration():
    cell = manifest.resolve(CELL)
    assert cell.chips == 4 and cell.config_name == CONFIG
    assert cell.traffic["op"] == "train_call"
    assert cell.traffic["rows_per_call"] == 4 * (1 << 17)
    cfg = cell.config
    assert "-mix " in cfg["options"] and "-mix_threshold 16" in cfg["options"]
    assert cfg["reference"] == "arow_mix"
    assert cfg["reference_args"]["replicas"] == cell.chips
    assert cfg["reference_args"]["mix_every"] == cfg["work_model"]["mix_every"]
    base = _load(os.path.join(manifest.ROOT, "benchmark", "configs",
                              "arow_criteo1tb.json"))
    assert cfg["data"] == base["data"]
    assert cfg["nonzeros_per_row"] == base["nonzeros_per_row"]
    # the seven unlisted per-layer metrics and the two it brings
    names = [m["name"] for m in cell.per_layer]
    assert len(names) == 9
    assert {"step_mfu.train", "scatter_gather_roofline",
            "mix_allreduce_roofline",
            "mix_allreduce_ms_per_round.train"} <= set(names)
    assert "stage_ms_per_krow.train" not in names   # PR 26's lists: unedited


def test_at_most_a_quarter_of_the_cells_ask_for_four_chips():
    cells = manifest.load_manifest()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_the_reference_imports_nothing_of_the_program():
    import subprocess
    import sys

    code = ("import sys; sys.path.insert(0, %r); import benchmark.refs.arow_mix"
            "; bad = [m for m in sys.modules if m.startswith(('hivemall_tpu',"
            " 'jax'))]; assert not bad, bad" % manifest.ROOT)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_work_of_a_block_is_stated_over_all_the_chips():
    cfg = manifest.resolve(CELL).config
    model = cfg["work_model"]
    one = work.step_work(_load(os.path.join(
        manifest.ROOT, "benchmark", "configs", "arow_criteo1tb.json")))
    mixed = work.step_work(cfg)
    lanes = 1024 * 39
    entry = 2 * 2 + 1      # w, cov in bfloat16 and the pending flag
    assert mixed["lanes"] == one["lanes"] == lanes
    assert mixed["gather_scatter_bytes"] == lanes * (9 + 3 * entry) / 4
    exchange = 4 * model["due_entries_per_round"] * 2 * entry / (16 * 4)
    assert mixed["bytes"] == pytest.approx(
        (lanes * (9 + 3 * entry) + lanes * 4 + 1024 * 4 + exchange) / 4)
    # a share of four chips' peak: under a one-chip block's work
    assert mixed["bytes"] < one["bytes"] and mixed["flops"] < one["flops"]


# ---- the cell, tiny, through run.execute ----

@pytest.mark.parametrize("workload", sorted(TINY))
def test_tiny_mix_cell_runs_and_is_correct(mix_root, four_replicas, workload):
    cell, line = _execute(mix_root, workload)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert set(line["numbers"]) == set(cell.config["correct"]["limits"])
    assert line["numbers"]["steps_diff"]["value"] == 0.0
    assert line["numbers"]["rows_diff"]["value"] == 0.0


def _mix_skipped(monkeypatch):
    import jax

    from hivemall_tpu.parallel import mix

    monkeypatch.setattr(
        mix, "mix_linear_replica",
        lambda st, reduction, axis: (st, jax.lax.psum(0 * st.step, axis)))


def _share_trained_twice(monkeypatch):
    from hivemall_tpu.parallel import mix

    def deal_twice(n_rows, n_replicas):
        shares = mix_deal(n_rows, n_replicas)
        return [shares[0]] + shares[:1] + shares[2:]

    mix_deal = mix.deal_rows
    monkeypatch.setattr(mix, "deal_rows", deal_twice)


def _answer_altered(monkeypatch):
    from hivemall_tpu.models import base

    real = base.TrainedLinearModel.model_rows

    def model_rows(self, *a, **k):
        feats, w, cov = real(self, *a, **k)
        w = np.array(w)
        bf16 = str(self.state.weights.dtype) == "bfloat16"
        w[int(np.argmax(np.abs(w)))] *= -1.0 if bf16 else 1.01
        return feats, w, cov

    monkeypatch.setattr(base.TrainedLinearModel, "model_rows", model_rows)


FAULTS = {"mix_skipped": _mix_skipped, "share_twice": _share_trained_twice,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in sorted(TINY) for f in sorted(FAULTS)])
def test_fault_in_the_mixed_path_is_not_correct(mix_root, four_replicas,
                                                monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    _, line = _execute(mix_root, workload, seed=SEED + 1)
    assert line["correct"] is False
    assert not all(n["ok"] for n in line["numbers"].values())


def test_lower_storage_control_is_not_correct(mix_root, four_replicas):
    from benchmark import compare

    cell = manifest.resolve("arow_mix_bf16.replay", root=mix_root)
    cell.traffic["rows_per_call"] = 4 * cell.traffic["rows_per_call"]
    op = run.make_op(cell, SEED + 2)
    op.setup()
    op.window(None, max_calls=1)
    limits = cell.config["correct"]["limits"]
    sound = compare.verdict(op.check()["numbers"], limits)
    low = compare.verdict(op.check(table_dtype="float8_e4m3fn")["numbers"],
                          limits)
    assert all(n["ok"] for n in sound.values())
    assert not all(n["ok"] for n in low.values())


# ---- the readers, on hand-made spans and traces ----

def _span(name, dur_us=0.0, **args):
    return {"name": name, "start_us": 1.0, "dur_us": dur_us, "args": args,
            "span_id": name, "parent_id": None}


def _ctx(spans, trace=None):
    cell = manifest.resolve(CELL)
    ctx = SimpleNamespace(cell=cell, trace=trace, result={"calls": [{}]},
                          device={"platform": "tpu", "kind": "TPU v5 lite"})
    ctx._program_spans = spans
    return ctx


def test_no_program_span_metric_was_added():
    """`test_pb_program_spans.py` (PR 26) pins the program_span and
    program_counter metrics at its eight and at their three cells, and this
    PR may not edit it: the due share and the dealing time stay span
    arguments and counters of the program (PERF.md section 7)."""
    new = [m for m in manifest.load_manifest()["per_layer"]
           if CELL in m.get("workloads", [])]
    assert sorted(m["name"] for m in new) == [
        "mix_allreduce_ms_per_round.train", "mix_allreduce_roofline"]
    assert {m["source"] for m in new} == {"device_trace"}


def _window_trace(allreduce_s):
    """A reduced trace as `xplane.reduce` gives it: the ten longest ops of
    the traced span, seconds a chip, keyed by `xplane.short_name`."""
    ops = [["%fusion.5 fusion f32[268435456] [scatter]", 0.9],
           ["%fusion.6 fusion s8[268435456] [gather]", 0.5]]
    ops += [[f"%psum_invariant.{21 + i} all-reduce f32[268435456] [dense]", s]
            for i, s in enumerate(allreduce_s)]
    ops += [["%fusion.2 fusion f32[65536] [gather]", 0.2]]
    return {"busy_s": 4.0, "window_s": 12.0,
            "device_ops": sorted(ops, key=lambda kv: -kv[1])}


def test_the_two_device_readers_read_the_window_and_nothing_else():
    from benchmark import xplane
    from benchmark.readers import (mix_allreduce_ms_per_round,
                                   mix_allreduce_roofline)

    # the key a reader matches is the one the reduction makes on the chip
    line = ("%psum_invariant.21 = f32[268435456]{0:T(1024)} all-reduce("
            "f32[268435456]{0:T(1024)} %fusion.9), channel_id=1")
    assert " all-reduce " in xplane.short_name(line)

    # two traced calls of eight rounds: 16 rounds, 0.8 s of all-reduce a chip
    spans = [_span("train.call")] \
        + [_span("train.mix", round=i) for i in range(16)] \
        + [_span("train.epoch", mix_rounds=8, mix_due_entries=8_000_000,
                 mix_exchanged_entries=8 << 28) for _ in range(2)]
    ctx = _ctx(spans, _window_trace([0.3, 0.3, 0.2]))
    assert mix_allreduce_ms_per_round.read(ctx) == pytest.approx(50.0)
    # 16e6 due entries x 5 B x 2 * 3/4 over 200 GB/s, over the window's 0.8 s
    assert mix_allreduce_roofline.read(ctx) == pytest.approx(
        100 * (1.5 * 16e6 * 5 / 200e9) / 0.8)
    assert mix_allreduce_roofline.read(ctx) < 1.0
    # a window whose rounds were slow reads slow: nothing stands in for it
    slow = _ctx(spans, _window_trace([0.6, 0.6, 0.4]))
    assert mix_allreduce_ms_per_round.read(slow) == pytest.approx(100.0)
    for reader in (mix_allreduce_ms_per_round, mix_allreduce_roofline):
        # no all-reduce among the ten longest ops: left out, not guessed
        assert reader.read(_ctx(spans, _window_trace([]))) is None
        # the parent: no train.mix span in the window, no all-reduce op
        assert reader.read(_ctx([_span("train.call")],
                                _window_trace([]))) is None
        assert reader.read(_ctx(None, _window_trace([0.3]))) is None
        # an untraced run
        assert reader.read(_ctx(spans, None)) is None


def test_no_reader_runs_the_program():
    """A reader reads what the window left (`ctx`); it starts no call and no
    profiler session of its own."""
    import glob

    for path in glob.glob(os.path.join(manifest.ROOT, "benchmark", "readers",
                                       "*mix*.py")):
        text = open(path).read()
        for word in ("get_function", "traced_window", "start_trace",
                     "datagen"):
            assert word not in text, (path, word)


# ---- the two programs compile for a described v5e:2x2 at the cell's dims ----

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_replicated_step_and_mix_fit_with_a_spare_state(topo):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel.mix import MixedReplicas

    cfg = manifest.resolve(CELL).config
    dims, b, k = cfg["num_features"], cfg["mini_batch"], 64
    tr = MixedReplicas(AROW, {"r": cfg["reference_args"]["r"]}, dims,
                       jnp.bfloat16, topo.devices)
    assert tr.n_dev == 4 and tr.reduction == "argmin_kld"
    sh = NamedSharding(tr.mesh, P(tr.axis))

    def spread(a):   # a replica's leaf -> the replicas end to end
        shape = (4,) if a.ndim == 0 else (4 * a.shape[0],) + a.shape[1:]
        return jax.ShapeDtypeStruct(shape, a.dtype, sharding=sh)

    state = jax.tree.map(spread, jax.eval_shape(tr._init_one))
    block = tuple(jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in (
        ((4 * b, k), jnp.int32), ((4 * b, k), jnp.float32),
        ((4 * b,), jnp.float32), ((4,), jnp.int32)))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = {"step": tr.step.lower(state, *block).compile(),
                    "mix": tr.mix.lower(state).compile()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    live = sum(np.prod(a.shape) * a.dtype.itemsize
               for a in jax.tree.leaves(state)) / 4     # a chip's replica
    assert live == pytest.approx(2.25 * 2 ** 30, rel=1e-3)
    for name, c in compiled.items():
        m = c.memory_analysis()
        peak = m.argument_size_in_bytes + m.output_size_in_bytes \
            - m.alias_size_in_bytes + m.temp_size_in_bytes
        # the program, and one spare state beside it, under 3/4 of the chip
        assert peak + live <= 0.75 * HBM_BYTES, (name, peak, live)
    text = compiled["mix"].as_text()
    assert text.count(" all-reduce(") == 3 and "f32[%d]" % dims in text
    # the replicas meet only in the mix: the step holds no collective
    assert " all-reduce(" not in compiled["step"].as_text()
