"""chip_smoke.py on the CPU: its stage functions at toy sizes, its refusal to
pass without a TPU, and the compile-cache helper it relies on.

The script itself only ever passes on the chip (ROADMAP tier-1 runs on the
CPU), so the stages are imported and driven directly here — the same code
the chip runs at 2^24 dims, at 2^12."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from hivemall_tpu.runtime import compile_cache  # noqa: E402

DIMS, WIDTH, MINI_BATCH = 1 << 12, 8, 64


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


@pytest.fixture(scope="module")
def trained(meter):
    # 256 rows cannot learn 4096 planted weights: the chance-level floor is
    # a property of the full size, so the toy run lowers it
    return chip_smoke.stage_train(meter, DIMS, 256, 128, WIDTH, MINI_BATCH,
                                  iters=2, min_accuracy=0.5)


def test_train_stage_checks_hold(trained):
    report, model, _data = trained
    assert report["steps"] == 8 and report["rows"] == 512
    assert report["state_platform"] == ["cpu"]
    # same backend, same seed: the reference run is bit-identical here
    assert report["holdout_accuracy"] == report["cpu_holdout_accuracy"]
    assert report["max_abs_score_delta_vs_cpu"] == 0.0
    assert report["fit"]["wall_s"] >= report["fit"]["compile_s"] > 0.0
    assert int(model.state.step) == 512


def test_serve_stage_checks_hold(meter, trained):
    _report, model, data = trained
    # 2 batch buckets x 2 width buckets
    rep = chip_smoke.stage_serve(meter, model, data, DIMS, MINI_BATCH,
                                 max_batch=16, max_width=16,
                                 request_shapes=((1, 3), (5, 8), (20, 6)))
    assert rep["warmed_buckets"] == 4
    assert rep["post_warmup_recompiles"] == 0.0
    assert len(rep["requests"]) == 6  # 3 on v1 + 3 on v2 after the swap
    assert rep["deploy"]["compile_s"] > 0.0


def test_kernels_stage_checks_hold():
    rep = chip_smoke.stage_kernels(DIMS, 1 << 10, WIDTH,
                                   pallas_interpret=True)
    assert rep["pallas"]["matches_scan"] and rep["pallas"]["interpret"]
    # at the real smoke width the kernel is refused in words, off-chip too
    from hivemall_tpu.kernels.linear_scan import vmem_resident_reason
    from hivemall_tpu.models.classifier import AROW

    assert "MiB of VMEM" in vmem_resident_reason(
        AROW, chip_smoke.FULL["dims"])


def test_mesh_stage_checks_hold(monkeypatch):
    """Four of the conftest's eight virtual devices (`-mix` trains a replica
    on every local device: the test hands it the same four)."""
    import jax

    from hivemall_tpu.parallel import mix

    monkeypatch.setattr(mix, "mix_devices", lambda: jax.local_devices()[:4])
    rep = chip_smoke.stage_mesh(DIMS, WIDTH, MINI_BATCH, n_devices=4,
                                max_batch=16, max_width=16,
                                catalog_items=512)
    assert rep["devices"] == 4
    assert rep["mix"]["entry_point_matches"]
    assert rep["sharded"]["matches_single_device"]
    assert rep["sharded2d"]["mesh"] == [2, 2]
    assert rep["serving"]["placement"]["model_shards"] == 4
    assert rep["serving"]["topk_matches"]
    # the CPU backend reports no memory stats: said, not skipped silently
    assert rep["mix_bytes_in_use"] == "not reported by this backend"


def test_script_refuses_to_pass_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout.strip() == "", "no result line without a chip"


def test_last_stdout_line_is_the_verdict_and_nothing_else(capsys):
    """The driver reads the last line: exactly {"ok", "device"} with
    exactly {"platform", "kind", "count"}; the measurements ride the line
    before it."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke.emit_result(device, {"stages_passed": ["train"]})
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert json.loads(lines[-2]) == {"report": {"stages_passed": ["train"]}}
    assert len(lines) == 2


# --- the compile-cache helper -------------------------------------------------

_CACHE_PROBE = (
    "import jax\n"
    "jax.default_backend = lambda: 'tpu'\n"  # the helper's only backend read
    "from hivemall_tpu.runtime.compile_cache import enable_compile_cache\n"
    "print(enable_compile_cache())\n"
    "print(enable_compile_cache())\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
    "print(jax.config.jax_persistent_cache_min_compile_time_secs)\n")


def _cache_probe(env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k not in (compile_cache.CACHE_DIR_ENV,
                        compile_cache.MIN_COMPILE_SECS_ENV)}
    proc = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=REPO,
                          env={**env, "JAX_PLATFORMS": "cpu",
                               **env_overrides},
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()


def test_cache_dir_is_fixed_in_checkout_when_env_is_unset():
    """Unset: the fixed in-checkout path — the same on every call and in
    every process (never a tempfile, pid or timestamp) — and the
    persistence threshold drops so small programs are written."""
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.DEFAULT_CACHE_DIR == want
    first, second = _cache_probe({}), _cache_probe({})
    assert first == second == [want, want, want, "0.0"]


def test_cache_dir_from_env_is_left_alone(tmp_path):
    """Set from outside: jax reads the variable itself; the helper sets no
    directory (and leaves a threshold the environment chose)."""
    outside = str(tmp_path / "placed_from_outside")
    out = _cache_probe({compile_cache.CACHE_DIR_ENV: outside,
                        compile_cache.MIN_COMPILE_SECS_ENV: "0.5"})
    assert out == [outside, outside, outside, "0.5"]


def test_cache_stays_off_on_a_cpu_only_process(monkeypatch):
    """The tier-1 process is CPU-only: the helper changes no config here
    (see the module docstring for why)."""
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
