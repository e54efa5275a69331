"""The byte/op functions against hand-worked small cases, and the peaks."""

import pytest

from benchmark import work

LINEAR = {"kind": "linear_minibatch", "tables": 2, "table_bytes": 4}
FM = {"kind": "fm_minibatch", "factors": 10}


def config(model, rows, nnz):
    """As much of a configuration's file as a work model reads."""
    return {"work_model": model, "mini_batch": rows,
            "data": {"numeric_lanes": 1, "categorical_lanes": nnz - 1}}


def test_linear_step_by_hand():
    # 2 rows x 3 lanes = 6 lanes; a lane: id 4 + gather 2*4 + id 4 +
    # scatter 2*(2*4) + touched 1 = 33 bytes; + value 4; + 2 labels * 4
    w = work.step_work(config(LINEAR, rows=2, nnz=3))
    assert w["lanes"] == 6
    assert w["gather_scatter_bytes"] == 6 * 33 == 198
    assert w["bytes"] == 198 + 6 * 4 + 2 * 4 == 230
    assert w["flops"] == 6 * 14


def test_linear_bf16_tables_halve_the_table_traffic():
    half = dict(LINEAR, table_bytes=2)
    w = work.step_work(config(half, rows=1, nnz=1))
    assert w["gather_scatter_bytes"] == 4 + 4 + 4 + 8 + 1


def test_fm_step_by_hand():
    # entry = (1 + 10) * 4 = 44; lane = 4 + 44 + 4 + 88 + 1 = 141
    w = work.step_work(config(FM, rows=1024, nnz=39))
    assert w["lanes"] == 39936
    assert w["gather_scatter_bytes"] == 39936 * 141
    assert w["flops"] == 39936 * 106


def test_required_work_does_not_depend_on_the_table_size():
    a = work.step_work(config(LINEAR, 1024, 39))
    big = dict(config(LINEAR, 1024, 39), num_features=1 << 28)
    assert a == work.step_work(big)


def test_least_seconds_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert work.least_seconds({"flops": 100, "bytes": 50}, peaks) == 5.0
    assert work.least_seconds({"flops": 1000, "bytes": 50}, peaks) == 10.0


def test_v5e_peaks_and_unknown_device():
    p = work.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("cpu")


def test_unknown_work_model_is_an_error_and_none_is_nothing():
    with pytest.raises(KeyError, match="work_models/nope.py"):
        work.step_work(config({"kind": "nope"}, 1, 1))
    assert work.step_work({"mini_batch": 1}) is None


def test_every_configuration_names_a_work_model_that_is_a_file():
    import json
    import os

    from benchmark import manifest

    for c in manifest.load_manifest()["configs"]:
        cfg = json.load(open(os.path.join(manifest.ROOT, c["file"])))
        w = work.step_work(cfg)
        assert w["bytes"] >= w["gather_scatter_bytes"] > 0 and w["flops"] > 0
