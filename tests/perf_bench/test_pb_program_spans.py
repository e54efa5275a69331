"""The readers of the program's own spans (benchmark/readers/_program_spans.py
and the eight metrics on it) and the join of those spans with the device
trace (benchmark/tools/span_gaps.py): on hand-made spans whose numbers can be
worked out on paper, on the tiny cells run on the CPU, and on a small trace
recorded on the chip (data/)."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import manifest, run, xplane
from benchmark.readers import _program_spans as ps
from benchmark.tools import span_gaps

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = manifest.load_manifest()
NEW = [m for m in MAN["per_layer"]
       if m["source"] in ("program_span", "program_counter")]
CELLS = ["arow_criteo1tb.train_replay", "fm_criteo1tb.train_replay",
         "arow_criteo1tb.train_text"]
MS = 1e3  # us


def _reader(metric_name):
    with open(os.path.join(manifest.ROOT, "benchmark", "metrics",
                           metric_name + ".json")) as f:
        spec = json.load(f)
    return importlib.import_module("benchmark.readers." + spec["reader"])


def _read_all(ctx):
    return {m["name"]: _reader(m["name"]).read(ctx) for m in NEW}


def test_eight_metrics_on_the_three_train_call_cells():
    assert len(NEW) == 8
    for m in NEW:
        assert m["workloads"] == CELLS and m["moves"] == "train_rows_per_s"
    # the seven older entries stand as they were, in front
    assert [m["name"] for m in MAN["per_layer"][:7]] == [
        "device_idle_pct.train", "step_device_ms.train",
        "dense_pass_device_pct.train", "scatter_gather_roofline",
        "step_mfu.train", "host_gap_ms_per_step.train",
        "emit_pct_of_call.train"]
    assert all("workloads" not in m for m in MAN["per_layer"][:7])


# ---- by hand ----

def _span(name, sid, parent, start_ms, dur_ms, **args):
    return {"name": name, "span_id": sid, "parent_id": parent,
            "start_us": start_ms * MS, "dur_us": dur_ms * MS, "args": args,
            "events": []}


def hand_spans():
    """One call of 100 ms: 10 ms staging 2000 rows, 5 ms state, one epoch of
    80 ms with four steps (each 1 ms of packing 500 rows, 258,000 bytes; a
    first dispatch of 20 ms, then 2 ms each; one 12 ms wait at the end), then
    an emission of 50 ms: three copies of 10 ms and 3000 bytes each, 15 ms
    of selecting 10 rows."""
    spans = [_span("train.call", "c", None, 0, 100, rows=2000),
             _span("train.stage", "st", "c", 2, 10, rows=2000, nnz=9000),
             _span("train.init_state", "i", "c", 12, 5, state_bytes=64),
             _span("train.epoch", "e", "c", 18, 80, epoch=0, steps=4)]
    t = 18.0
    for k in range(4):
        spans.append(_span("train.data_prep", f"p{k}", "e", t, 1, rows=500,
                           width=64, h2d_bytes=258000))
        d = 20 if k == 0 else 2
        spans.append(_span("train.compiled_step", f"d{k}", "e", t + 1, d,
                           step=k, compiled=k == 0))
        t += 1 + d
    spans.append(_span("train.sync", "s", "e", t, 12, fetches=4))
    spans.append(_span("emit.model_rows", "m", None, 101, 50, rows_out=10,
                       d2h_bytes=9000))
    for k in range(3):
        spans.append(_span("emit.d2h", f"h{k}", "m", 102 + 10 * k, 10,
                           table=f"t{k}", bytes=3000))
    spans.append(_span("emit.select", "sel", "m", 133, 15, rows_out=10))
    return spans


def test_readers_by_hand():
    ctx = SimpleNamespace(result={"calls": []}, _program_spans=hand_spans())
    assert _read_all(ctx) == pytest.approx({
        "stage_ms_per_krow.train": 10 / 2.0,
        "pack_ms_per_step.train": 1.0,
        "dispatch_ms_per_step.train": 2.0,
        "first_dispatch_ms.train": 20.0,
        "sync_ms_per_step.train": 12 / 4,
        "h2d_bytes_per_row.train": 516.0,
        "emit_d2h_bytes_per_row.train": 900.0,
        "emit_d2h_pct_of_emit.train": 60.0,
    })
    spans = hand_spans()
    assert ps.self_ms(spans, "train.call") == pytest.approx(100 - 95)
    assert ps.self_ms(spans, "train.epoch") == pytest.approx(80 - 4 - 26 - 12)
    assert ps.self_ms(spans, "emit.model_rows") == pytest.approx(50 - 45)
    assert ps.self_ms(spans, "train.sync") == pytest.approx(12)
    # children that overlap count once; one that sticks out is clipped
    # (the wait runs from 48 to 60 ms)
    spans += [_span("x", "x1", "s", 50, 8), _span("x", "x2", "s", 56, 100)]
    assert ps.self_ms(spans, "train.sync") == pytest.approx(12 - 8 - 2)


def test_a_window_with_no_train_call_reads_nothing(tiny_root):
    """An op kind that is no `train_*` call (the `count_op` a later PR might
    bring, as tests/perf_bench/conftest.py writes it) has no `calls` in its
    result; a window in which the program opened no `train.call` (the parent
    commit) has none in the tracer either."""
    ns = {}
    with open(os.path.join(tiny_root, "benchmark", "ops", "count_op.py")) as f:
        exec(f.read(), ns)
    op = ns["Op"](SimpleNamespace(traffic={"n": 1000}), 3)
    ctx = SimpleNamespace(result=op.window(None, max_calls=2))
    assert set(_read_all(ctx).values()) == {None}
    far = {"calls": [{"t0": -2.0, "t1": -1.0}]}   # no span starts there
    assert set(_read_all(SimpleNamespace(result=far)).values()) == {None}


def test_a_disabled_tracer_reads_nothing_and_says_so(monkeypatch, capsys):
    from hivemall_tpu.runtime.tracing import TRACER

    monkeypatch.setattr(TRACER, "enabled", False)
    ctx = SimpleNamespace(result={"calls": [{"t0": 0.0, "t1": 1e12}]})
    assert set(_read_all(ctx).values()) == {None}
    assert capsys.readouterr().err.count("tracer is disabled") == 1


# ---- the tiny cells on the CPU: result["calls"] and the tracer are enough ----

@pytest.fixture(scope="module", params=["arow_tiny.replay", "fm_tiny.replay",
                                        "arow_tiny.text"])
def tiny_window(request, tiny_root):
    from hivemall_tpu.runtime.tracing import TRACER

    cell = manifest.resolve(request.param, root=tiny_root)
    op = run.make_op(cell, 2 ** 31 + 26)
    op.setup()     # its warm-up call's traces lie before the window
    TRACER.clear()
    result = op.window(None, max_calls=2)
    return cell, result


def test_all_eight_read_positive_on_a_tiny_cell(tiny_window):
    cell, result = tiny_window
    values = _read_all(SimpleNamespace(result=result, cell=cell))
    assert all(v is not None and v > 0 for v in values.values()), values
    fm = cell.config["entry_point"] == "train_fm"
    # [mini_batch, 64] ids and values, the labels, FM's mask
    assert values["h2d_bytes_per_row.train"] == 64 * 8 + 4 + (4 if fm else 0)
    assert 0 < values["emit_d2h_pct_of_emit.train"] < 100
    spans = ps.window_spans(SimpleNamespace(result=result))
    assert len(ps.named(spans, "train.call")) == 2
    assert len(ps.named(spans, "emit.model_rows")) == 2
    steps = result["steps"]
    assert len(ps.named(spans, "train.compiled_step")) == steps
    assert len(ps.named(spans, "train.data_prep")) == steps
    # the tracer's clock is the benchmark's: the root spans lie inside the
    # calls' own t0..t1 and take nearly all of train_s and emit_s
    table = span_gaps.span_table(result)
    clocks = table["clocks"]
    assert clocks["train.call_s"] <= clocks["bench_train_s"]
    assert clocks["emit.model_rows_s"] <= clocks["bench_emit_s"]
    assert clocks["train.call_s"] > 0.9 * clocks["bench_train_s"]
    assert table["spans"]["train.call"]["count"] == 2
    text = cell.traffic["row_form"] == "text"
    assert ("train.parse" in table["spans"]) == text


# ---- the join with the device trace ----

NS = 1e6  # ms in ns


def hand_trace():
    """test_pb_xplane's hand-made trace (one call of 10 ms, two steps of
    4 ms: gather 1, dense 2, scatter 1; 1 ms before the first launch, 0.5 ms
    between, emission over the last 1 ms of which 0.5 ms idle), with the
    program's spans laid over it and a loop's body inside the dense op."""
    ops, modules = [], []
    for t in (1.0, 5.5):
        modules.append(["jit_minibatch_step", t * NS, 4 * NS])
        ops += [["%g", t * NS, 1 * NS, "jit_minibatch_step"],
                ["%f", (t + 1) * NS, 2 * NS, "jit_minibatch_step"],
                ["%body_op", (t + 1.5) * NS, 1 * NS, "jit_minibatch_step"],
                ["%s", (t + 3) * NS, 1 * NS, "jit_minibatch_step"]]
    spans = [["bench:call", 0.0, 10 * NS], ["bench:emit", 9 * NS, 1 * NS],
             ["train.call", 0.1 * NS, 8.8 * NS],
             ["train.stage", 0.2 * NS, 0.7 * NS],
             ["train.parse", 0.3 * NS, 0.5 * NS],
             ["train.epoch", 0.95 * NS, 7.9 * NS],
             ["train.compiled_step", 5.1 * NS, 0.3 * NS],
             ["emit.model_rows", 9.05 * NS, 0.9 * NS],
             ["emit.d2h", 9.1 * NS, 0.7 * NS]]
    return {"devices": {"0": {"ops": ops, "modules": modules}},
            "spans": spans}


HAND_TEXT = """HloModule jit_minibatch_step

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %neg = f32[8]{0} negate(%p), metadata={op_name="jit(minibatch_step)/vmap(hm.gather)/neg"}
}

%body (arg: f32[8]) -> f32[8] {
  %arg = f32[8]{0} parameter(0)
  ROOT %body_op = f32[8]{0} add(%arg, %arg)
}

ENTRY %main (w: f32[8]) -> f32[8] {
  %w = f32[8]{0} parameter(0), metadata={op_name="state.weights"}
  %zeros = f32[8]{0} broadcast(%c), dimensions={}
  %g = f32[8]{0} fusion(%w), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(minibatch_step)/vmap(hm.gather)/gather"}
  %f = f32[8]{0} while(%g), condition=%cond, body=%body
  %s = f32[8]{0} scatter(%zeros, %f), metadata={op_name="jit(minibatch_step)/hm.reduce/scatter-add"}
  ROOT %out = f32[8]{0} add(%w, %s), metadata={op_name="jit(minibatch_step)/hm.apply/add"}
}
"""


def test_scopes_of_by_hand():
    scopes = span_gaps.scopes_of(HAND_TEXT)
    assert scopes["%g"] == ["hm.gather", "own"]
    assert scopes["%neg"] == ["hm.gather", "own"]
    assert scopes["%s"] == ["hm.reduce", "own"]
    assert scopes["%out"] == ["hm.apply", "own"]
    # the compiler's own: a zero fill goes with the scatter it feeds, a loop
    # with the one op that reads it, the loop's body with the loop
    assert scopes["%zeros"] == ["hm.reduce", "user"]
    assert scopes["%f"] == ["hm.reduce", "user"]
    assert scopes["%body_op"] == ["hm.reduce", "caller"]
    # a table that one stage reads and another writes belongs to neither
    assert scopes["%w"] == [span_gaps.UNSCOPED, "none"]


def test_exclusive_time_by_hand():
    got = span_gaps.exclusive_ns([(0, 10, "a"), (2, 5, "b"), (3, 4, "c"),
                                  (12, 14, "d"), (13, 16, "e")])
    assert got == {"a": 7, "b": 2, "c": 1, "d": 1, "e": 3}


def test_reduce_by_hand():
    scopes = {"jit_minibatch_step": span_gaps.scopes_of(HAND_TEXT)}
    r = span_gaps.reduce(hand_trace(), scopes)
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(8e-3)
    assert r["calls"] == 1
    # %f runs 2 ms a step, 1 ms of it under its body's op: both hm.reduce
    assert r["busy_by_scope_s"] == pytest.approx(
        {"hm.gather": 2e-3, "hm.reduce": 6e-3})
    assert sum(r["busy_by_scope_s"].values()) == pytest.approx(r["busy_s"])
    assert r["inherited_s"] == pytest.approx({"hm.reduce": 4e-3})
    assert r["top_ops"][0] == ["jit_minibatch_step", "%g", "hm.gather", "own",
                               pytest.approx(2e-3)]
    idle = r["idle_by_phase_s"]
    # the same phases and seconds as the ledger's reduction of this trace
    assert {k: v["total"] for k, v in idle.items()} == pytest.approx({
        "bench:call/before_first_launch": 1e-3,
        "bench:call/between_launches": 0.5e-3, "bench:emit": 0.5e-3})
    assert idle["bench:call/before_first_launch"]["by_span"] == pytest.approx({
        "bench:call": 0.1e-3, "train.call": 0.1e-3 + 0.05e-3,
        "train.stage": 0.1e-3 + 0.1e-3, "train.parse": 0.5e-3,
        "train.epoch": 0.05e-3})
    assert idle["bench:call/between_launches"]["by_span"] == pytest.approx({
        "train.epoch": 0.2e-3, "train.compiled_step": 0.3e-3})
    assert idle["bench:emit"]["by_span"] == pytest.approx({
        "emit.d2h": 0.3e-3, "emit.model_rows": 0.15e-3, "bench:emit": 0.05e-3})
    # ops of a program with no text go by the program's name
    r = span_gaps.reduce(hand_trace())
    assert r["busy_by_scope_s"] == pytest.approx(
        {"program:jit_minibatch_step": 8e-3})
    assert span_gaps.reduce({"devices": {}, "spans": []}) is None


def _same(got, want):
    """Equal, numbers to rounding, through dicts and lists."""
    if isinstance(want, dict):
        assert set(got) == set(want)
        for k in want:
            _same(got[k], want[k])
    elif isinstance(want, list):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            _same(g, w)
    elif isinstance(want, float):
        assert got == pytest.approx(want)
    else:
        assert got == want


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "v5e_arow_tiny_spans.json")) as f:
        return json.load(f)


def test_reduce_on_a_recorded_trace(recorded):
    """One call of 4 steps at 2^16 dims recorded on a v5e: the file holds
    `span_gaps.load`'s output, the compiled step's scopes and what `reduce`
    made of them there."""
    loaded, scopes = recorded["loaded"], recorded["step_scopes"]
    r = span_gaps.reduce(loaded, scopes)
    _same(r, recorded["reduced"])
    names = {s[0] for s in loaded["spans"]}
    assert {"bench:call", "bench:emit", "train.call", "train.stage",
            "train.init_state", "train.epoch", "train.data_prep",
            "train.compiled_step", "train.sync", "emit.model_rows",
            "emit.d2h", "emit.select"} <= names
    # every instant of busy time has one scope, and the step's are all there
    assert sum(r["busy_by_scope_s"].values()) == pytest.approx(r["busy_s"])
    assert {"hm.pack_tables", "hm.gather", "hm.reduce", "hm.apply",
            "hm.touched"} <= set(r["busy_by_scope_s"])
    step = sum(v for k, v in r["busy_by_scope_s"].items()
               if k.startswith("hm."))
    assert step > 0.95 * r["busy_s"]
    # idle and busy make up the span; phases are the ledger's own
    idle = sum(v["total"] for v in r["idle_by_phase_s"].values())
    assert idle + r["busy_s"] == pytest.approx(r["window_s"])
    for v in r["idle_by_phase_s"].values():
        assert sum(v["by_span"].values()) == pytest.approx(v["total"])
    as_ledger = xplane.reduce({
        "devices": {k: {"ops": [[o[0], o[1], o[2], "dense"] for o in d["ops"]],
                        "modules": d["modules"]}
                    for k, d in loaded["devices"].items()},
        "marks": [s for s in loaded["spans"] if s[0].startswith("bench:")]})
    assert as_ledger["busy_s"] == pytest.approx(r["busy_s"])
    assert dict(as_ledger["idle_gaps"]) == pytest.approx(
        {k: v["total"] for k, v in r["idle_by_phase_s"].items()})
    # the program's spans name what the ledger's phases could not
    before = r["idle_by_phase_s"]["bench:call/before_first_launch"]["by_span"]
    assert max(before, key=before.get) == "train.stage"
    emit = r["idle_by_phase_s"]["bench:emit"]["by_span"]
    assert set(emit) <= {"emit.d2h", "emit.select", "emit.model_rows",
                         "bench:emit"}
