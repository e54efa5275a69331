"""The harness end to end on the CPU at tiny dims, for both row forms and
both entry points; the CLI refusing to measure without a chip; every fault a
cell can have turning `correct` false; a cell and a metric added as files."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import manifest, run

CELLS = ["arow_tiny.replay", "fm_tiny.replay", "arow_tiny.text"]
SEED = 2 ** 31 + 77


def _execute(root, workload, seed=SEED, seconds=0.2):
    cell = manifest.resolve(workload, root=root)
    return cell, run.execute(cell, seed, seconds, 0, log=open(os.devnull, "w"))


@pytest.mark.parametrize("workload", CELLS + ["arow_bf16.replay"])
def test_tiny_cell_runs_and_is_correct(tiny_root, workload):
    cell, line = _execute(tiny_root, workload)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "numbers"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"train_rows_per_s", "setup_s"}
    assert line["metrics"]["train_rows_per_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert set(line["numbers"]) == set(cell.config["correct"]["limits"])
    assert line["notes"]["compared_calls"] == min(line["attempted"], 7)
    # what a reader of a slow run needs: each call's parts and CPU seconds
    assert len(line["notes"]["calls_s"]) == line["attempted"]
    assert all(len(c) == 3 and c[2] > 0 for c in line["notes"]["calls_s"])
    # the device's own counters, unmodified, beside the peak
    assert {"peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit"} <= set(
        line["device"])
    json.dumps(line)


def test_a_window_shorter_than_a_call_finishes_one(tiny_root):
    _, line = _execute(tiny_root, "arow_tiny.replay", seconds=0.001)
    assert line["attempted"] == 1 and line["correct"]


def test_same_seed_same_numbers(tiny_root):
    a = _execute(tiny_root, "arow_tiny.replay", seed=5)[1]["numbers"]
    b = _execute(tiny_root, "arow_tiny.replay", seed=5)[1]["numbers"]
    assert a == b


def test_trace_needs_the_chip(tiny_root):
    cell = manifest.resolve("arow_tiny.replay", root=tiny_root)
    with pytest.raises(RuntimeError, match="no TPU"):
        run.execute(cell, 1, 0.1, 1)


def test_added_cell_reports_the_added_metric(tiny_root):
    cell = manifest.resolve("arow_tiny.replay", root=tiny_root)
    names = [m["name"] for m in cell.per_layer]
    assert "emit_pct_again.train" in names and "step_mfu.train" in names
    other = manifest.resolve("fm_tiny.replay", root=tiny_root)
    assert "emit_pct_again.train" not in [m["name"] for m in other.per_layer]


NEW_KINDS = """
import json, os, sys
from types import SimpleNamespace
from benchmark import manifest, run, work, xplane
from benchmark.readers import step_mfu
out = {"package": os.path.dirname(manifest.__file__)}
for name in ("count_dense.count_mix", "count_plain.count_mix"):
    cell = manifest.resolve(name)
    line = run.execute(cell, 2 ** 31 + 5, 0.01, 0, log=open(os.devnull, "w"))
    # the traced run's reader of the whole step's share, on a hand-made span
    span = {"window_s": 1.0, "busy_s": 0.5, "class_s": {}}
    ctx = SimpleNamespace(cell=cell, trace=span, result={"steps": 10},
                          peaks={"flops_per_s": 1e6, "bytes_per_s": 1e6})
    out[name] = {"line": line, "work": work.step_work(cell.config),
                 "step_mfu": step_mfu.read(ctx)}
print(json.dumps(out))
"""


def test_a_new_op_kind_and_a_new_work_model_are_files_only(tiny_root):
    """A later PR's `serve_closed` or forest cell: an op module, a work-model
    module, a configuration, a traffic file and entries; no file of the
    benchmark edited. Run from the copy, so that its files are what is found."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([tiny_root, manifest.ROOT]))
    p = subprocess.run([sys.executable, "-c", NEW_KINDS], cwd=tiny_root,
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["package"] == os.path.join(tiny_root, "benchmark")
    for name in ("count_dense.count_mix", "count_plain.count_mix"):
        line = out[name]["line"]
        assert line["correct"] is True and line["attempted"] >= 1
        assert line["metrics"]["train_rows_per_s"]["value"] > 0
        assert set(line["numbers"]) == {"sum_diff"}
    # 8 rows x 100 B = 800 B a step; 10 steps at 1e6 B/s over a 1 s span
    assert out["count_dense.count_mix"]["work"]["bytes"] == 800
    assert out["count_dense.count_mix"]["step_mfu"] == pytest.approx(0.8)
    assert out["count_plain.count_mix"]["work"] is None
    assert out["count_plain.count_mix"]["step_mfu"] is None


def test_cli_refuses_to_measure_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "benchmark", "run.py"),
         "--workload", "arow_criteo1tb.train_text", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_cli_unknown_workload_exits_nonzero():
    p = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, "benchmark", "run.py"),
         "--workload", "nope", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""


# ---- the timed path broken underneath: `correct` has to come out false ----

def _state_unchanged(monkeypatch):
    import jax.numpy as jnp
    from hivemall_tpu.models import base, fm

    def frozen(state, indices, *rest):
        return state.replace(step=state.step + indices.shape[0]), jnp.zeros(())

    monkeypatch.setattr(base, "make_train_step", lambda *a, **k: frozen)
    monkeypatch.setattr(fm, "make_fm_step", lambda *a, **k: frozen)


def _half_batch(monkeypatch):
    from hivemall_tpu.models import base, fm

    def halve(make):
        def make_half(*a, **k):
            step = make(*a, **k)

            def half(state, indices, values, labels, *rest):
                h = indices.shape[0] // 2
                state, loss = step(state, indices[:h], values[:h], labels[:h],
                                   *[r[:h] for r in rest])
                return state.replace(step=state.step + h), loss

            return half

        return make_half

    monkeypatch.setattr(base, "make_train_step", halve(base.make_train_step))
    monkeypatch.setattr(fm, "make_fm_step", halve(fm.make_fm_step))


def _answer_altered(monkeypatch):
    from hivemall_tpu.models import base, fm

    def bf16(model):
        return any(str(getattr(leaf, "dtype", "")) == "bfloat16"
                   for leaf in vars(model.state).values())

    def alter(cls, table_at):
        real = cls.model_rows

        def model_rows(self, *a, **k):
            out = list(real(self, *a, **k))
            w = np.array(out[table_at])
            # one row: by one per cent where the tables are float32, by its
            # sign where they are bfloat16 (a storage that holds 0.4 per cent)
            w[int(np.argmax(np.abs(w)))] *= -1.0 if bf16(self) else 1.01
            out[table_at] = w
            return tuple(out)

        monkeypatch.setattr(cls, "model_rows", model_rows)

    alter(base.TrainedLinearModel, 1)
    alter(fm.TrainedFMModel, 2)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in CELLS for f in sorted(FAULTS)] + [
    ("arow_bf16.replay", "half_batch"), ("arow_bf16.replay", "answer_altered")])
def test_fault_in_the_timed_path_is_not_correct(tiny_root, monkeypatch,
                                                workload, fault):
    FAULTS[fault](monkeypatch)
    _, line = _execute(tiny_root, workload, seed=SEED + 1)
    assert line["correct"] is False
    assert not all(n["ok"] for n in line["numbers"].values())


@pytest.mark.parametrize("workload,dtype", [
    ("arow_tiny.replay", "bfloat16"), ("fm_tiny.replay", "bfloat16"),
    ("arow_bf16.replay", "float8_e4m3fn")])
def test_control_through_the_op_is_not_correct(tiny_root, workload, dtype):
    from benchmark import compare

    cell = manifest.resolve(workload, root=tiny_root)
    if dtype.startswith("float8"):
        cell.traffic["rows_per_call"] = 4 * cell.traffic["rows_per_call"]
    op = run.make_op(cell, SEED + 2)
    op.setup()
    op.window(None, max_calls=1)
    sound = compare.verdict(op.check()["numbers"],
                            cell.config["correct"]["limits"])
    low = compare.verdict(op.check(table_dtype=dtype)["numbers"],
                          cell.config["correct"]["limits"])
    assert all(n["ok"] for n in sound.values())
    assert not all(n["ok"] for n in low.values())
