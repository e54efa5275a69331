"""Hive TRANSFORM streaming bridge: subprocess round trips over the real
stdin/stdout TSV contract (adapters/hive_transform.py; ref: the UDTF surface
`hivemall/UDTFWithOptions.java:48` + define-all.hive:27-28 — this is the
JVM-free execution path a Hive cluster drives via `TRANSFORM ... USING`)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ITEM_SEP = "\x02"


def run_bridge(args, stdin_text, check=True):
    proc = subprocess.run(
        [sys.executable, "-m", "hivemall_tpu.adapters.hive_transform", *args],
        input=stdin_text, capture_output=True, text=True, timeout=600,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if check:
        assert proc.returncode == 0, proc.stderr
    return proc


def _dataset(n=400, dims=64, seed=0):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(dims)
    rows = []
    for _ in range(n):
        idx = rng.choice(dims, size=6, replace=False)
        y = 1.0 if w_true[idx].sum() > 0 else -1.0
        rows.append((idx, y))
    return w_true, rows


def test_train_arow_roundtrip_and_predict_linear(tmp_path):
    _, rows = _dataset()
    # Hive array<string> framing: \x02-joined tokens
    stdin_text = "".join(
        ITEM_SEP.join(f"{j}:1" for j in idx) + f"\t{y}\n" for idx, y in rows)
    proc = run_bridge(["train_arow", "-dims", "64"], stdin_text)
    model_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert all(len(r) == 3 for r in model_rows)  # feature, weight, covar
    feats = {int(r[0]) for r in model_rows}
    assert feats <= set(range(64)) and len(feats) > 30

    # emitted rows == the framework's own model rows for the same input
    from hivemall_tpu.core.state import model_rows as fw_rows
    from hivemall_tpu.models.classifier import train_arow

    fw = train_arow([[f"{j}:1" for j in idx] for idx, _ in rows],
                    [y for _, y in rows], "-dims 64")
    f0, w0, c0 = fw_rows(fw.state)
    got = {int(r[0]): (float(r[1]), float(r[2])) for r in model_rows}
    want = {int(f): (float(w), float(c)) for f, w, c in zip(f0, w0, c0)}
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-5, atol=1e-6)

    # predict_linear over the emitted model file (ADD FILE pattern)
    model_file = tmp_path / "model.tsv"
    model_file.write_text(proc.stdout)
    test_in = "".join(
        f"r{i}\t" + ITEM_SEP.join(f"{j}:1" for j in idx) + "\n"
        for i, (idx, _) in enumerate(rows[:80]))
    pred = run_bridge(
        ["predict_linear", "-loadmodel", str(model_file), "-sigmoid"],
        test_in)
    scored = [line.split("\t") for line in pred.stdout.splitlines()]
    assert [r[0] for r in scored] == [f"r{i}" for i in range(80)]
    probs = np.array([float(r[1]) for r in scored])
    assert np.all((probs >= 0) & (probs <= 1))
    acc = np.mean([(p > 0.5) == (y > 0)
                   for p, (_, y) in zip(probs, rows[:80])])
    assert acc > 0.9, acc


def test_space_joined_string_features_and_null_rows():
    _, rows = _dataset(n=200, seed=1)
    lines = ["\\N\t1.0", "0:1 1:1\t\\N"]  # NULL feature / NULL label: skip
    lines += [" ".join(f"{j}:1" for j in idx) + f"\t{y}" for idx, y in rows]
    proc = run_bridge(["train_perceptron", "-dims", "64"],
                      "\n".join(lines) + "\n")
    model_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert all(len(r) == 2 for r in model_rows)  # no covariance
    assert len(model_rows) > 20


def test_train_fm_and_predict_fm_roundtrip(tmp_path):
    _, rows = _dataset(n=300, dims=32, seed=2)
    stdin_text = "".join(
        ITEM_SEP.join(f"{j}:1" for j in idx) + f"\t{y}\n" for idx, y in rows)
    proc = run_bridge(
        ["train_fm", "-dims", "32", "-factors", "4", "-classification",
         "-iters", "2"], stdin_text)
    out_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert out_rows[0][0] == "-1" and out_rows[0][2] == "\\N"  # w0 row
    for r in out_rows[1:]:
        assert len(json.loads(r[2])) == 4  # k factors

    model_file = tmp_path / "fm.tsv"
    model_file.write_text(proc.stdout)
    test_in = "".join(
        f"{i}\t" + ITEM_SEP.join(f"{j}:1" for j in idx) + "\n"
        for i, (idx, _) in enumerate(rows[:50]))
    pred = run_bridge(["predict_fm", "-loadmodel", str(model_file)], test_in)
    scores = np.array([float(line.split("\t")[1])
                       for line in pred.stdout.splitlines()])

    # parity with the framework's own predict
    from hivemall_tpu.models.fm import train_fm

    fw = train_fm([[f"{j}:1" for j in idx] for idx, _ in rows],
                  [y for _, y in rows],
                  "-dims 32 -factors 4 -classification -iters 2")
    fw_scores = np.asarray(fw.predict(
        [[f"{j}:1" for j in idx] for idx, _ in rows[:50]]))
    if isinstance(fw_scores, tuple):
        fw_scores = fw_scores[0]
    np.testing.assert_allclose(scores, fw_scores[:50], rtol=1e-4, atol=1e-5)


def test_multiclass_emission():
    rng = np.random.RandomState(3)
    rows, labels = [], []
    for _ in range(240):
        c = rng.randint(3)
        idx = [c * 8 + int(j) for j in rng.choice(8, size=3, replace=False)]
        rows.append(ITEM_SEP.join(f"{j}:1" for j in idx))
        labels.append(f"class{c}")
    stdin_text = "".join(f"{r}\t{lab}\n" for r, lab in zip(rows, labels))
    proc = run_bridge(["train_multiclass_perceptron", "-dims", "24"],
                      stdin_text)
    out_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert {r[0] for r in out_rows} == {"class0", "class1", "class2"}
    assert all(len(r) == 3 for r in out_rows)  # label, feature, weight


def test_forest_emission_votes():
    rng = np.random.RandomState(4)
    X = rng.rand(240, 5)
    y = (X[:, 0] > 0.5).astype(int)
    stdin_text = "".join(
        ITEM_SEP.join(f"{v:.6f}" for v in X[i]) + f"\t{int(y[i])}\n"
        for i in range(len(y)))
    proc = run_bridge(["train_randomforest_classifier", "-trees", "6",
                       "-seed", "11"], stdin_text)
    out_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert len(out_rows) == 6
    assert all(len(r) == 6 for r in out_rows)
    # each emitted tree evaluates through the framework's own evaluator
    from hivemall_tpu.models.trees import tree_predict

    votes = [int(tree_predict(r[1], r[2], X[0], classification=True))
             for r in out_rows]
    assert set(votes) <= {0, 1}


def test_mf_emission():
    rng = np.random.RandomState(5)
    users = rng.randint(0, 20, size=300)
    items = rng.randint(0, 15, size=300)
    ratings = rng.rand(300) * 5
    stdin_text = "".join(f"{u}\t{i}\t{r:.4f}\n"
                         for u, i, r in zip(users, items, ratings))
    proc = run_bridge(["train_mf_sgd", "-factor", "4", "-iterations", "3"],
                      stdin_text)
    out_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert all(len(r) == 6 for r in out_rows)
    pu_rows = [r for r in out_rows if r[1] != "\\N"]
    qi_rows = [r for r in out_rows if r[2] != "\\N"]
    assert pu_rows and qi_rows
    assert len(json.loads(pu_rows[0][1])) == 4


def test_train_ffm_blob_row_and_predict_ffm(tmp_path):
    """train_ffm's emission carries the complete model as a base91 blob
    row (feature -2); predict_ffm scores the full pairwise model from it
    with framework parity."""
    rng = np.random.RandomState(11)
    rows, labels = [], []
    for _ in range(200):
        idx = rng.choice(32, size=5, replace=False)
        rows.append(ITEM_SEP.join(f"{j % 4}:{j}:1" for j in idx))
        labels.append(1.0 if idx.sum() > 75 else -1.0)
    train_in = "".join(f"{r}\t{y}\n" for r, y in zip(rows, labels))
    proc = run_bridge(["train_ffm", "-feature_hashing", "8", "-factors",
                       "3"], train_in)
    out_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert all(len(r) == 3 for r in out_rows)
    blob_rows = [r for r in out_rows if r[0] == "-2"]
    assert len(blob_rows) == 1 and blob_rows[0][2] != "\\N"

    model_file = tmp_path / "ffm.tsv"
    model_file.write_text(proc.stdout)
    test_in = "".join(f"{i}\t{r}\n" for i, r in enumerate(rows[:40]))
    pred = run_bridge(["predict_ffm", "-loadmodel", str(model_file)],
                      test_in)
    scores = np.array([float(line.split("\t")[1])
                       for line in pred.stdout.splitlines()])

    from hivemall_tpu.models.ffm import train_ffm

    fw = train_ffm([r.split(ITEM_SEP) for r in rows], labels,
                   "-feature_hashing 8 -factors 3")
    fw_scores = np.asarray(fw.predict([r.split(ITEM_SEP)
                                       for r in rows[:40]]))
    # blob values are half-float compressed (the reference's recipe)
    np.testing.assert_allclose(scores, fw_scores, rtol=5e-3, atol=5e-3)


def test_predict_multiclass_roundtrip(tmp_path):
    rng = np.random.RandomState(6)
    rows, labels = [], []
    for _ in range(300):
        c = rng.randint(3)
        idx = [c * 8 + int(j) for j in rng.choice(8, size=3, replace=False)]
        rows.append(ITEM_SEP.join(f"{j}:1" for j in idx))
        labels.append(f"class{c}")
    train_in = "".join(f"{r}\t{lab}\n" for r, lab in zip(rows, labels))
    proc = run_bridge(["train_multiclass_perceptron", "-dims", "24"],
                      train_in)
    model_file = tmp_path / "mc.tsv"
    model_file.write_text(proc.stdout)
    test_in = "".join(f"r{i}\t{r}\n" for i, r in enumerate(rows[:60]))
    pred = run_bridge(["predict_multiclass", "-loadmodel", str(model_file)],
                      test_in)
    scored = [line.split("\t") for line in pred.stdout.splitlines()]
    assert len(scored) == 60 and all(len(r) == 3 for r in scored)
    acc = np.mean([r[1] == lab for r, lab in zip(scored, labels[:60])])
    assert acc > 0.9, acc


def test_predict_forest_roundtrip(tmp_path):
    rng = np.random.RandomState(8)
    X = rng.rand(300, 5)
    y = (X[:, 0] > 0.5).astype(int)
    train_in = "".join(
        ITEM_SEP.join(f"{v:.6f}" for v in X[i]) + f"\t{int(y[i])}\n"
        for i in range(len(y)))
    proc = run_bridge(["train_randomforest_classifier", "-trees", "8",
                       "-seed", "3"], train_in)
    model_file = tmp_path / "rf.tsv"
    model_file.write_text(proc.stdout)
    test_in = "".join(
        f"r{i}\t" + ITEM_SEP.join(f"{v:.6f}" for v in X[i]) + "\n"
        for i in range(100))
    pred = run_bridge(["predict_forest", "-loadmodel", str(model_file)],
                      test_in)
    scored = [line.split("\t") for line in pred.stdout.splitlines()]
    votes = np.array([int(r[1]) for r in scored])
    assert np.mean(votes == y[:100]) > 0.9


def test_train_arow_native_scan_through_bridge(tmp_path):
    """The host fast path drives end to end through the TRANSFORM framing."""
    from hivemall_tpu import native

    if not native.available():
        import pytest as _pytest

        _pytest.skip("native lib not built")
    _, rows = _dataset(n=200, seed=9)
    stdin_text = "".join(
        ITEM_SEP.join(f"{j}:1" for j in idx) + f"\t{y}\n" for idx, y in rows)
    fast = run_bridge(["train_arow", "-dims", "64", "-native_scan"],
                      stdin_text)
    plain = run_bridge(["train_arow", "-dims", "64"], stdin_text)
    got = {r.split("\t")[0]: float(r.split("\t")[1])
           for r in fast.stdout.splitlines()}
    want = {r.split("\t")[0]: float(r.split("\t")[1])
            for r in plain.stdout.splitlines()}
    assert got.keys() == want.keys()
    for k in want:
        assert abs(got[k] - want[k]) < 1e-4


def test_gbt_emission_and_unknown_subcommand():
    rng = np.random.RandomState(12)
    X = rng.rand(200, 4)
    y = (X[:, 0] > 0.5).astype(int)
    stdin_text = "".join(
        ITEM_SEP.join(f"{v:.6f}" for v in X[i]) + f"\t{int(y[i])}\n"
        for i in range(len(y)))
    proc = run_bridge(["train_gradient_tree_boosting_classifier", "-trees",
                       "4", "-iters", "4", "-seed", "2"], stdin_text)
    out_rows = [line.split("\t") for line in proc.stdout.splitlines()]
    assert len(out_rows) == 4  # one row per binary boosting round
    assert all(len(r) == 9 for r in out_rows)
    assert [r[0] for r in out_rows] == ["1", "2", "3", "4"]
    assert json.loads(out_rows[0][8]) == [0, 1]  # label vocabulary

    proc = run_bridge(["sigmoid"], "", check=False)
    assert proc.returncode == 2
    assert "unknown subcommand" in proc.stderr


def test_predict_gbt_roundtrip(tmp_path):
    """GBT trained through the bridge scores through predict_gbt with
    framework decision parity — with {-1, 1} labels, so the classes
    vocabulary mapping is exercised (advisor-caught: without it the
    bridge emitted class INDICES, silently diverging from the
    framework's labels)."""
    rng = np.random.RandomState(14)
    X = rng.rand(240, 4)
    y = np.where(X[:, 0] > 0.5, 1, -1)
    train_in = "".join(
        ITEM_SEP.join(f"{v:.6f}" for v in X[i]) + f"\t{int(y[i])}\n"
        for i in range(len(y)))
    proc = run_bridge(["train_gradient_tree_boosting_classifier", "-trees",
                       "6", "-iters", "6", "-seed", "5"], train_in)
    model_file = tmp_path / "gbt.tsv"
    model_file.write_text(proc.stdout)
    test_in = "".join(
        f"r{i}\t" + ITEM_SEP.join(f"{v:.6f}" for v in X[i]) + "\n"
        for i in range(80))
    pred = run_bridge(["predict_gbt", "-loadmodel", str(model_file)],
                      test_in)
    scored = [line.split("\t") for line in pred.stdout.splitlines()]
    assert len(scored) == 80 and all(len(r) == 3 for r in scored)

    from hivemall_tpu.models.trees.forest import \
        train_gradient_tree_boosting_classifier

    fw = train_gradient_tree_boosting_classifier(
        X, y, "-trees 6 -iters 6 -seed 5")
    fw_pred = fw.predict(X[:80])
    fw_scores = fw.decision_function(X[:80])[:, 0]
    # the bridge parses TSV labels as floats, so its vocabulary is
    # [-1.0, 1.0] where the direct int-label call yields [-1, 1]
    got_labels = np.array([int(float(r[1])) for r in scored])
    got_scores = np.array([float(r[2]) for r in scored])
    np.testing.assert_array_equal(got_labels, fw_pred)
    np.testing.assert_allclose(got_scores, fw_scores, rtol=1e-5, atol=1e-6)


def test_bin_shim_exists_and_is_executable():
    shim = os.path.join(REPO, "bin", "hivemall-tpu")
    assert os.path.exists(shim)
    assert os.access(shim, os.X_OK)
