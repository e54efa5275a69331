"""Smoke tests keeping the fast runnable examples green (the slower CTR /
MovieLens examples are exercised manually; these complete in seconds)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(example: str, timeout: int = 240) -> str:
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", example)],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=REPO, capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout


def test_sql_session_example():
    out = _run("sql_session.py")
    assert "entirely through SQL" in out


def test_lof_example():
    out = _run("lof.py")
    assert "outliers detected correctly" in out


def test_text_classification_ja_example():
    out = _run("text_classification_ja.py")
    assert "tokenize_ja_bulk -> tf -> feature_hashing" in out


def test_serve_ctr_example():
    out = _run("serve_ctr.py")
    assert "train -> freeze -> deploy -> predict -> hot swap: done" in out
