"""Build-stamp staleness (scripts/build_native.sh) and sanitizer-variant
selection (hivemall_tpu/native loader, HIVEMALL_TPU_NATIVE_SANITIZE).

tests/test_native.py gates on a PRESENT library (module-wide skip);
these tests pin the build/load machinery itself, so they run — and the
skip paths stay named — even when the .so or the compiler is absent.
"""

import os
import shutil
import subprocess

import pytest

import hivemall_tpu.native as nat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "build_native.sh")
SO = os.path.join(REPO, "hivemall_tpu", "native", "libhivemall_native.so")
STAMP = SO + ".stamp"


def _build(*args):
    return subprocess.run(["bash", SCRIPT, *args], cwd=REPO,
                          capture_output=True, text=True)


def test_if_stale_is_idempotent_and_stamped():
    """Two --if-stale runs in a row: the second must be a no-op (stamp
    match) or the named no-compiler skip — never an unconditional
    rebuild, never a silent failure."""
    first = _build("--if-stale")
    assert first.returncode == 0, first.stdout + first.stderr
    second = _build("--if-stale")
    assert second.returncode == 0, second.stdout + second.stderr
    if shutil.which("g++"):
        assert "fresh" in second.stdout, second.stdout + second.stderr
        assert os.path.isfile(STAMP), "build must leave a stamp"
        with open(STAMP, encoding="utf-8") as fh:
            stamp = fh.read()
        # compiler identity + flags + target CPU + source hash: the four
        # staleness axes
        assert "compiler:" in stamp and "flags:" in stamp \
            and "cpu:" in stamp and "source:" in stamp
    else:
        assert "no g++" in second.stdout + second.stderr


@pytest.mark.skipif(shutil.which("g++") is None,
                    reason="no g++: stamp-mismatch rebuild not exercisable")
def test_flag_drift_in_stamp_forces_rebuild():
    """A stamp recording different flags (the pre-v16 pathology: a
    sanitizer/-O0 build mistaken for the optimized one) must force a
    rebuild even though the .so is newer than its source."""
    _build("--if-stale")  # ensure .so + stamp exist
    with open(STAMP, encoding="utf-8") as fh:
        good = fh.read()
    try:
        with open(STAMP, "w", encoding="utf-8") as fh:
            fh.write(good.replace("flags: ", "flags: -O0 "))
        proc = _build("--if-stale")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "built" in proc.stdout, (
            "flag drift must rebuild:\n" + proc.stdout + proc.stderr)
        with open(STAMP, encoding="utf-8") as fh:
            assert fh.read() == good, "rebuild must restore the true stamp"
    finally:
        if os.path.isfile(SO) and open(STAMP).read() != good:
            with open(STAMP, "w", encoding="utf-8") as fh:
                fh.write(good)


def _foreign_cpu_stamp():
    """Rewrite the stamp's cpu line as another host's; returns the true
    stamp text for restoring."""
    _build("--if-stale")
    with open(STAMP, encoding="utf-8") as fh:
        good = fh.read()
    with open(STAMP, "w", encoding="utf-8") as fh:
        fh.write(good.replace("cpu: ", "cpu: elsewhere-"))
    return good


def _fresh_loader(monkeypatch):
    monkeypatch.setenv("HIVEMALL_TPU_NATIVE_SANITIZE", "")
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_load_error", None)
    monkeypatch.setattr(nat, "_built_because", None)


@pytest.mark.skipif(shutil.which("g++") is None,
                    reason="no g++: foreign-CPU rebuild not exercisable")
def test_foreign_cpu_build_is_rebuilt_not_loaded(monkeypatch):
    """A library stamped by another CPU (the tree was copied between hosts;
    -march=native makes it a SIGILL hazard — it killed hm_pack_block on the
    TPU VM) must be rebuilt by --if-stale AND by the loader's first use;
    the loader's stamp derivation must agree with the script's."""
    good = _foreign_cpu_stamp()
    try:
        proc = _build("--if-stale")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "built" in proc.stdout, proc.stdout + proc.stderr
        with open(STAMP, encoding="utf-8") as fh:
            assert fh.read() == good
        assert nat._stale_reason() is None  # python id == script id

        _foreign_cpu_stamp()
        _fresh_loader(monkeypatch)
        assert "another CPU" in nat._stale_reason()
        assert nat._load() is not None
        info = nat.build_info()
        assert "another CPU" in info["built_at_first_use"]
        assert info["stamp"]["cpu"] == info["host_cpu"]
    finally:
        _build("--if-stale")


def test_foreign_cpu_build_without_compiler_is_refused(monkeypatch):
    """No compiler to rebuild with: the foreign-CPU library stays on disk
    but is NOT loaded, and the cause is named."""
    if not os.path.isfile(SO):
        pytest.skip("no library on disk and none buildable")
    good = _foreign_cpu_stamp()
    try:
        _fresh_loader(monkeypatch)
        monkeypatch.setattr(nat.shutil, "which", lambda _name: None)
        with pytest.warns(UserWarning, match="another CPU"):
            assert nat._load() is None
        assert "no g++" in nat.load_error()
    finally:
        with open(STAMP, "w", encoding="utf-8") as fh:
            fh.write(good)


def test_unknown_sanitize_mode_is_a_hard_error():
    proc = _build("--sanitize=bogus")
    assert proc.returncode == 2
    assert "unknown --sanitize mode" in proc.stderr


def test_sanitize_env_selects_suffixed_variant(monkeypatch):
    """The loader maps HIVEMALL_TPU_NATIVE_SANITIZE to the suffixed .so
    the sanitizer build produces — and never the plain library."""
    monkeypatch.setattr(nat, "_load_error", None)
    monkeypatch.setenv("HIVEMALL_TPU_NATIVE_SANITIZE", "")
    assert nat._so_path() == nat._LIB_PATH
    monkeypatch.setenv("HIVEMALL_TPU_NATIVE_SANITIZE", "asan")
    assert nat._so_path().endswith("libhivemall_native.asan.so")
    monkeypatch.setenv("HIVEMALL_TPU_NATIVE_SANITIZE", "tsan")
    assert nat._so_path().endswith("libhivemall_native.tsan.so")
    assert nat._load_error is None  # known values never poison the loader


def test_unknown_sanitize_env_refuses_loudly(monkeypatch):
    """A typo'd sanitizer name must disable the native backend with a
    named error — silently loading the UNinstrumented .so would make a
    sanitizer CI lane vacuously green."""
    monkeypatch.setattr(nat, "_lib", None)
    monkeypatch.setattr(nat, "_load_error", None)
    monkeypatch.setenv("HIVEMALL_TPU_NATIVE_SANITIZE", "addres")  # typo
    with pytest.warns(UserWarning, match="unknown HIVEMALL_TPU_NATIVE"):
        assert nat._load() is None
    assert nat._load_error is not None
    assert "addres" in nat._load_error
