"""The trace reduction: on a hand-made trace whose numbers can be worked out
on paper, and on a small trace recorded on the chip (data/)."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import work, xplane
from benchmark.readers import (dense_pass_device_pct, device_idle_pct,
                               host_gap_ms_per_step, scatter_gather_roofline,
                               step_device_ms, step_mfu)

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6  # ns


def hand_trace():
    """One device, one call of 10 ms with 2 steps; per step: a gather of
    1 ms, a dense fusion of 2 ms, a scatter of 1 ms, back to back. 1 ms of
    staging before the first step, 0.5 ms between the steps, then 0.5 ms
    until a 1 ms emission ends the call."""
    ops, modules = [], []
    for k, t in enumerate((1.0, 5.5)):
        modules.append(["jit_minibatch_step", t * MS, 4 * MS])
        ops += [["gather.1", t * MS, 1 * MS, "gather"],
                ["fusion.7", (t + 1) * MS, 2 * MS, "dense"],
                ["fusion.9", (t + 3) * MS, 1 * MS, "scatter"]]
    marks = [["bench:call", 0.0, 10 * MS], ["bench:emit", 9 * MS, 1 * MS]]
    return {"devices": {"0": {"ops": ops, "modules": modules}}, "marks": marks}


def test_reduce_by_hand():
    r = xplane.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(8e-3)
    assert r["idle_in_calls_s"] == pytest.approx(2e-3)
    assert r["class_s"] == pytest.approx(
        {"gather": 2e-3, "scatter": 2e-3, "dense": 4e-3})
    assert r["device_ops"][0] == ["fusion.7 [dense]", pytest.approx(4e-3)]
    gaps = dict((k, v) for k, v in r["idle_gaps"])
    assert gaps["bench:call/before_first_launch"] == pytest.approx(1e-3)
    assert gaps["bench:call/between_launches"] == pytest.approx(0.5e-3)
    assert gaps["bench:emit"] == pytest.approx(0.5e-3)
    assert r["calls"] == 1


def cell_of(rows, nnz, model=None):
    """As much of a cell as the readers look at."""
    return SimpleNamespace(config={
        "mini_batch": rows, "work_model": model or {
            "kind": "linear_minibatch", "tables": 2, "table_bytes": 4},
        "data": {"numeric_lanes": 1, "categorical_lanes": nnz - 1}})


def test_readers_by_hand():
    peaks = {"flops_per_s": 1e12, "bytes_per_s": 1e9}
    # 5 rows x 6 lanes x 33 B = 990 B of gather and scatter; + values and
    # labels: 990 + 30 * 4 + 5 * 4 = 1130 B a step
    ctx = SimpleNamespace(trace=xplane.reduce(hand_trace()), cell=cell_of(5, 6),
                          result={"steps": 2}, peaks=peaks)
    assert device_idle_pct.read(ctx) == pytest.approx(20.0)
    assert step_device_ms.read(ctx) == pytest.approx(4.0)
    assert dense_pass_device_pct.read(ctx) == pytest.approx(50.0)
    # 2 steps x 990 B at 1e9 B/s = 1.98 us over 4 ms of gather + scatter
    assert scatter_gather_roofline.read(ctx) == pytest.approx(0.0495)
    # 2 steps x 1130 B = 2.26 us over the 10 ms span
    assert step_mfu.read(ctx) == pytest.approx(0.0226)
    assert host_gap_ms_per_step.read(ctx) == pytest.approx(1.0)


def test_readers_return_nothing_without_something_to_read():
    ctx = SimpleNamespace(trace=None, result={}, peaks=None, cell=cell_of(1, 1))
    for reader in (device_idle_pct, step_device_ms, dense_pass_device_pct,
                   scatter_gather_roofline, step_mfu, host_gap_ms_per_step):
        assert reader.read(ctx) is None
    no_gs = hand_trace()
    for op in no_gs["devices"]["0"]["ops"]:
        op[3] = "dense"
    peaks = {"flops_per_s": 1.0, "bytes_per_s": 1.0}
    ctx = SimpleNamespace(trace=xplane.reduce(no_gs), result={"steps": 2},
                          peaks=peaks, cell=cell_of(1, 1))
    assert scatter_gather_roofline.read(ctx) is None   # never 0
    # a configuration that states no work model: no share of a peak to give
    ctx = SimpleNamespace(trace=xplane.reduce(hand_trace()), peaks=peaks,
                          result={"steps": 2}, cell=SimpleNamespace(config={}))
    assert scatter_gather_roofline.read(ctx) is None
    assert step_mfu.read(ctx) is None
    assert step_device_ms.read(ctx) == pytest.approx(4.0)


def test_no_device_op_reduces_to_nothing():
    assert xplane.reduce({"devices": {}, "marks": []}) is None
    empty = {"devices": {"0": {"ops": [], "modules": []}}, "marks": []}
    assert xplane.reduce(empty) is None


def test_overlapping_ops_count_once_in_busy():
    t = hand_trace()
    t["devices"]["0"]["ops"].append(["copy.2", 1.5 * MS, 1 * MS, "dense"])
    r = xplane.reduce(t)
    assert r["busy_s"] == pytest.approx(8e-3)
    assert r["class_s"]["dense"] == pytest.approx(5e-3)


@pytest.mark.parametrize("name,text,cls", [
    ("fusion.3", "kind=kCustom scatter", "scatter"),
    ("gather.2", "", "gather"),
    ("fusion.1", "hlo_category: data formatting", "dense"),
    ("copy.4", "", "dense"),
    ("Scatter-fusion", "", "scatter"),
    # what the v5e's compiler really prints (PR 24's first trace): gathers and
    # scatters are custom fusions told apart by their operands' order
    ("%fusion = f32[65536,2]{0,1:T(2,128)S(1)} fusion(f32[268435456,2]{0,1:T(2,128)}"
     " %pad_maximum_fusion, s32[65536]{0:T(1024)S(1)} %copy-done.9), kind=kCustom,"
     " calls=%fused_computation", "", "gather"),
    ("%fusion.3 = f32[268435456]{0:T(1024)} fusion(f32[268435456]{0:T(1024)}"
     " %broadcast.4.clone, s32[65536]{0:T(1024)S(1)} %copy-done.2, f32[65536]"
     "{0:T(1024)S(1)} %copy-done.8), kind=kCustom, calls=%fused_computation.3",
     "", "scatter"),
    ("%fusion.2 = f32[134217728]{0:T(1024)} fusion(s32[720896]{0:T(1024)S(1)}"
     " %get-tuple-element.54, f32[720896]{0:T(1024)S(1)} %get-tuple-element.55,"
     " f32[]{:T(128)} %constant.27), kind=kCustom, calls=%fused_computation.5",
     "", "scatter"),
    ("%divide_add_fusion = (f32[268435456]{0:T(1024)}, f32[268435456]{0:T(1024)})"
     " fusion(f32[268435456]{0:T(1024)} %state_covars.1, f32[268435456]{0:T(1024)}"
     " %fusion.3), kind=kLoop, calls=%fused_computation.5", "", "dense"),
    ("%fusion.4 = s8[268435456]{0:T(1024)(128)(4,1)} fusion(s8[268435456]"
     "{0:T(1024)(128)(4,1)} %state_touched.1, f32[268435456]{0:T(1024)} %fusion.1),"
     " kind=kLoop, calls=%fused_computation.8", "", "dense"),
])
def test_classify(name, text, cls):
    assert xplane.classify(name, text) == cls


def test_short_name():
    line = ("%divide_add_fusion = (f32[268435456]{0:T(1024)}, f32[268435456]"
            "{0:T(1024)}) fusion(f32[268435456]{0:T(1024)} %state_covars.1),"
            " kind=kLoop, calls=%fused_computation.5")
    assert xplane.short_name(line) == "%divide_add_fusion fusion f32[268435456]"
    assert xplane.short_name("plain") == "plain"
    assert len(xplane.short_name("x" * 500)) == 96


def test_union_intervals():
    assert xplane.union_intervals([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


RECORDED = os.path.join(HERE, "data", "v5e_arow_tiny_trace.json")


@pytest.mark.skipif(not os.path.isfile(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_reduces():
    with open(RECORDED) as f:
        rec = json.load(f)
    r = xplane.reduce(rec["loaded"])
    assert r is not None and r["calls"] == rec["result"]["attempted"]
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(rec["reduced"]["busy_s"])
    assert r["class_s"]["gather"] + r["class_s"]["scatter"] > 0
    assert r["class_s"]["dense"] > 0
    peaks = work.peaks_for("TPU v5 lite")
    ctx = SimpleNamespace(trace=r, result={"steps": rec["result"]["steps"]},
                          peaks=peaks, cell=cell_of(1024, 39))
    assert 0 < scatter_gather_roofline.read(ctx) < 100
    assert 0 < step_mfu.read(ctx) < 100
    assert 0 < device_idle_pct.read(ctx) < 100
