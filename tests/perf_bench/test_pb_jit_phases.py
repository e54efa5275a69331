"""The six readers of the spans PR 37 put into the program (a fresh jit's
`train.jit_trace` / `train.jit_lower` / `train.jit_compile`, emission's
`emit.gather` / `emit.assemble`, and `emit.select`, which no metric read):
on hand-made spans whose numbers can be worked out on paper, and on the tiny
CPU cell of each committed cell's kind that test_pb_program_spans.py runs."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest
from test_pb_program_spans import (SPEAKING, _span, hand_spans,  # noqa: F401
                                   tiny_window)

from benchmark import manifest
from benchmark.readers import _program_spans as ps

MAN = manifest.load_manifest()
JIT = ["jit_trace_ms_per_call.train", "jit_lower_ms_per_call.train",
       "jit_compile_ms_per_call.train"]
SIX = JIT + ["compile_cache_hit_pct.train", "emit_select_pct_of_emit.train",
             "emit_assemble_pct_of_emit.train"]


def _read(name, spans):
    with open(os.path.join(manifest.ROOT, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(SimpleNamespace(result={"calls": []},
                                       _program_spans=spans))


def _read_six(spans):
    return {name: _read(name, spans) for name in SIX}


def test_the_six_are_listed_last_on_the_six_cells():
    entries = MAN["per_layer"][-6:]
    assert [m["name"] for m in entries] == SIX
    for m in entries:
        assert m["workloads"] == SPEAKING
        assert m["moves"] == "train_rows_per_s"
        assert m["layer"] == ("emission" if m["name"].startswith("emit_")
                              else "compile cache")
        assert m["source"] == ("program_counter" if "hit_pct" in m["name"]
                               else "program_span")
    # the emission shares beside the one that was there
    d2h = next(m for m in MAN["per_layer"]
               if m["name"] == "emit_d2h_pct_of_emit.train")
    assert d2h["layer"] == "emission"


# ---- by hand ----

def phases(parent, start_ms, trace, lower, compile_, cache="hit", tag=""):
    """The three phases one after the other from `start_ms`."""
    args = {"cache": cache}
    if cache == "hit":
        args["retrieval_ms"] = compile_ * 0.9
    return [
        _span("train.jit_trace", f"t{tag}", parent, start_ms, trace, fn="step"),
        _span("train.jit_lower", f"l{tag}", parent, start_ms + trace, lower,
              fn="jit(step)"),
        _span("train.jit_compile", f"c{tag}", parent,
              start_ms + trace + lower, compile_, fn="jit(step)", **args)]


def emission(root, start_ms):
    """An emission of 100 ms: the mask's copy 10, the host's pass 20, the
    gathers' dispatch 8, then 50 of assembly of which two copies take 15 and
    20; 12 under no child."""
    return [
        _span("emit.model_rows", root, None, start_ms, 100, rows_out=10),
        _span("emit.d2h", root + "m", root, start_ms + 1, 10, table="mask"),
        _span("emit.select", root + "s", root, start_ms + 12, 20),
        _span("emit.gather", root + "g", root, start_ms + 33, 8, chunks=2,
              h2d_bytes=512),
        _span("emit.assemble", root + "a", root, start_ms + 42, 50, bytes=80),
        _span("emit.d2h", root + "a0", root + "a", start_ms + 45, 15,
              table="w"),
        _span("emit.d2h", root + "a1", root + "a", start_ms + 62, 20,
              table="w")]


def one_call():
    """test_pb_program_spans' call of 100 ms, whose first dispatch (`d0`,
    20 ms from 19 ms on) is 5 ms of trace, 4 of lowering, 6 of the cache's
    read and 5 of the rest; its emission replaced by the one above."""
    spans = [s for s in hand_spans() if not s["name"].startswith("emit.")]
    return spans + phases("d0", 19.5, 5, 4, 6) + emission("m", 101)


def mixed_call():
    """A `-mix` call beside it: 3 ms of traces under `train.build`
    (`eval_shape`), a step's first dispatch of 10 + 8 + 12 in 40 ms, a round's
    of 2 + 3 + 30 in 40 ms whose executable the cache did not hold, and the
    collapse's of 1 + 2 + 3 in 8 ms."""
    spans = [_span("train.call", "C", None, 300, 200, rows=2000),
             _span("train.build", "B", "C", 301, 5, replicas=4, jits=4),
             _span("train.jit_trace", "tb", "B", 302, 3, fn="_init_one"),
             _span("train.epoch", "E", "C", 310, 170, epoch=0, steps=2),
             _span("train.compiled_step", "D0", "E", 311, 40, step=0,
                   compiled=True),
             _span("train.compiled_step", "D1", "E", 352, 3, step=1,
                   compiled=False),
             _span("train.mix", "M0", "E", 356, 40, round=0, trailing=True,
                   compiled=True),
             _span("train.collapse", "K", "C", 485, 8, compiled=True)]
    return spans + phases("D0", 312, 10, 8, 12, tag="D") \
        + phases("M0", 357, 2, 3, 30, cache="miss", tag="M") \
        + phases("K", 485.5, 1, 2, 3, tag="K")


def test_readers_by_hand_one_call():
    got = _read_six(one_call())
    assert got == pytest.approx({
        "jit_trace_ms_per_call.train": 5.0,
        "jit_lower_ms_per_call.train": 4.0,
        "jit_compile_ms_per_call.train": 6.0,
        "compile_cache_hit_pct.train": 100.0,
        "emit_select_pct_of_emit.train": 20.0,
        # the gathers' 8 and the assembly's own 50 - 15 - 20
        "emit_assemble_pct_of_emit.train": 23.0})
    # with the copies' share the three add to 100 less what is still bare
    d2h = _read("emit_d2h_pct_of_emit.train", one_call())
    assert d2h == pytest.approx(45.0)
    spans = one_call()
    bare = ps.self_ms(spans, "emit.model_rows")
    assert bare == pytest.approx(12.0)
    assert got["emit_select_pct_of_emit.train"] \
        + got["emit_assemble_pct_of_emit.train"] + d2h \
        == pytest.approx(100.0 - bare)
    # the first dispatch is its three phases and a remainder
    first = _read("first_dispatch_ms.train", spans)
    assert first - sum(got[name] for name in JIT) == pytest.approx(5.0)


def test_readers_by_hand_two_fresh_dispatches_and_a_collapse():
    both = one_call() + mixed_call()
    got = _read_six(both)
    # two calls; the build's trace and the collapse's phases count with the
    # dispatches': every `train.jit_*` span of the window
    assert got["jit_trace_ms_per_call.train"] == pytest.approx(
        (5 + 3 + 10 + 2 + 1) / 2)
    assert got["jit_lower_ms_per_call.train"] == pytest.approx(
        (4 + 8 + 3 + 2) / 2)
    assert got["jit_compile_ms_per_call.train"] == pytest.approx(
        (6 + 12 + 30 + 3) / 2)
    # four compiles, one of them XLA's own
    assert got["compile_cache_hit_pct.train"] == pytest.approx(75.0)
    # the phases are no more than the dispatches that hold them and what the
    # build and the collapse compiled
    first = _read("first_dispatch_ms.train", both)
    assert first == pytest.approx((20 + 40 + 40) / 2)
    held = first + (5 + 8) / 2
    assert sum(got[name] for name in JIT) <= held
    # one emission in the window: the shares are the first call's
    assert got["emit_select_pct_of_emit.train"] == pytest.approx(20.0)


@pytest.mark.parametrize("cache,want", [("hit", 100.0), ("miss", 0.0),
                                        ("off", 0.0)])
def test_hit_share_by_the_compile_spans_cache_argument(cache, want):
    spans = [s for s in hand_spans() if not s["name"].startswith("emit.")]
    spans += phases("d0", 19.5, 5, 4, 6, cache=cache)
    assert _read("compile_cache_hit_pct.train", spans) == want


def test_a_window_that_compiled_nothing_and_a_parents_emission():
    """A memoised step (no dispatch compiled, no phase span): the four
    compile metrics read nothing, not 0. A parent commit's emission has no
    `emit.assemble`: that share reads nothing, `emit.select`'s reads what
    PR 29's span says."""
    warm = [dict(s, args=dict(s["args"], compiled=False))
            if "compiled" in s["args"] else s for s in hand_spans()]
    got = _read_six(warm)
    for name in JIT + ["compile_cache_hit_pct.train"]:
        assert got[name] is None
    assert got["emit_assemble_pct_of_emit.train"] is None
    assert got["emit_select_pct_of_emit.train"] == pytest.approx(30.0)
    # no `train.call` in the window at all: nothing of anything
    assert set(_read_six(None).values()) == {None}
    # tables on the host: `emit.select` alone under the root
    host = [_span("train.call", "c", None, 0, 10),
            _span("emit.model_rows", "m", None, 11, 10, select="host"),
            _span("emit.select", "s", "m", 12, 8)]
    got = _read_six(host)
    assert got["emit_select_pct_of_emit.train"] == pytest.approx(80.0)
    assert got["emit_assemble_pct_of_emit.train"] is None


# ---- the tiny cells on the CPU ----

def test_all_six_read_a_number_on_a_tiny_cell(tiny_window):
    cell, result = tiny_window
    spans = ps.window_spans(SimpleNamespace(result=result))
    got = _read_six(spans)
    assert all(v is not None for v in got.values()), got
    for name in JIT:
        assert got[name] > 0
    # no persistent cache on a CPU process (runtime/compile_cache.py leaves
    # it alone): every compile of the window reads `off`
    compiles = ps.named(spans, "train.jit_compile")
    assert {s["args"]["cache"] for s in compiles} == {"off"}
    assert got["compile_cache_hit_pct.train"] == 0.0
    # every call's fresh jits: the step's, and with `-mix` the round's and
    # the collapse's
    mixed = "-mix " in cell.config["options"]
    fresh = [s for s in spans if s["args"].get("compiled")]
    assert len(fresh) == 2 * (3 if mixed else 1)
    for d in fresh:
        kids = [s["name"] for s in sorted(
            (s for s in spans if s["parent_id"] == d["span_id"]
             and s["name"].startswith("train.jit_")
             and "reshape" not in s["args"]["fn"]),
            key=lambda s: s["start_us"])]
        assert kids == ["train.jit_trace", "train.jit_lower",
                        "train.jit_compile"], d
    assert len(ps.named(spans, "train.build")) == (2 if mixed else 0)
    assert len(ps.named(spans, "train.collapse")) == (2 if mixed else 0)
    # the phases lie inside the spans that hold them
    first = _read("first_dispatch_ms.train", spans)
    around = sum(ps.total_ms(ps.named(spans, name)) for name in (
        "train.init_state", "train.build", "train.collapse")) / 2
    assert sum(got[name] for name in JIT) <= first + around
    # emission: three shares of one span
    select = got["emit_select_pct_of_emit.train"]
    assemble = got["emit_assemble_pct_of_emit.train"]
    d2h = _read("emit_d2h_pct_of_emit.train", spans)
    assert select > 0 and assemble > 0 and d2h > 0
    bare = 100.0 * ps.self_ms(spans, "emit.model_rows") \
        / ps.total_ms(ps.named(spans, "emit.model_rows"))
    assert select + assemble + d2h == pytest.approx(100.0 - bare)
