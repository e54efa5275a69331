"""BENCHMARK.json <-> files: every entry resolves, every name is legal."""

import json
import os
import re

import pytest

from benchmark import manifest

REPO = manifest.ROOT
MAN = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
FILE = re.compile(r"^[A-Za-z0-9_.\-/]+$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ALL_METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) < 64 * 1024
    assert MAN["command"][1].startswith(tuple(p + "/" for p in MAN["paths"]))
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("path", MAN["paths"])
def test_paths_exist_and_hold_legal_file_names(path):
    assert os.path.isdir(os.path.join(REPO, path))
    for base, dirs, files in os.walk(os.path.join(REPO, path)):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), REPO)
            assert FILE.match(rel), rel


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_workload_resolves(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = manifest.resolve(w["name"])
    assert cell.config["num_features"] > 0
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "ops", cell.traffic["op"] + ".py"))
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "refs", cell.config["reference"] + ".py"))
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    # every limit the cell is held to is a number the reference produces
    assert set(cell.config["correct"]["limits"]) >= {"rows_diff", "steps_diff"}


@pytest.mark.parametrize("c", MAN["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and 1 <= len(c["source"]) <= 200
    assert c["file"].startswith("benchmark/") and FILE.match(c["file"])
    cfg = json.load(open(os.path.join(REPO, c["file"])))
    assert cfg["name"] == c["name"]
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for key in c["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank")), "a width may not be reduced"
    assert str(cfg["num_features"]) in cfg["options"]
    assert any(w["config"] == c["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("m", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry_matches_its_file(m):
    e2e = m in MAN["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    spec = json.load(open(os.path.join(
        REPO, "benchmark", "metrics", m["name"] + ".json")))
    for key in ("unit", "better", "source"):
        assert spec[key] == m[key], key
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "readers", spec["reader"] + ".py"))
    cells = m.get("workloads", [w["name"] for w in MAN["workloads"]])
    assert set(cells) <= {w["name"] for w in MAN["workloads"]}
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        moved = next(x for x in MAN["end_to_end"] if x["name"] == m["moves"])
        reporting = moved.get("workloads", [w["name"] for w in MAN["workloads"]])
        assert set(cells) <= set(reporting)
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_step_share_of_peak_beside_the_kernels_roofline():
    names = [m["name"] for m in MAN["per_layer"]]
    assert any(re.search(r"(^|[_.])mfu([_.]|$)", n) for n in names)
    assert any(n.endswith("_roofline") for n in names)
