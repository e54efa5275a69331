"""Deployment-layer tests: runtime/launch.py + bin/hivemall_tpu_daemon.sh —
the ops tier (L7) that boots SPMD workers the way the reference boots its
MIX fleet (ref: bin/mixserv_cluster.sh:44-56, bin/mixserv_daemon.sh start
branch: pid file + rotated log + nohup'd server process)."""

import json
import os
import socket
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHILD = os.path.join(REPO, "tests", "_launch_child.py")

# jax <= 0.4.x has no cross-process collective transport on CPU: the
# cluster joins, then the first collective dies with this message. The
# multi-process tests skip on it — the capability, not the version, is
# what they need (runtime/jax_compat.py covers the API surface only).
CPU_MP_UNSUPPORTED = \
    "Multiprocess computations aren't implemented on the CPU backend"


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env(**extra):
    return {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        **extra,
    }


def test_launch_single_process(tmp_path):
    out = tmp_path / "single.json"
    r = subprocess.run(
        [sys.executable, "-m", "hivemall_tpu.runtime.launch",
         CHILD, str(out), "pass-through-arg"],
        env=_env(), cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "LAUNCH CHILD 0 OK" in r.stdout
    assert "single-process" in r.stderr
    rec = json.loads(out.read_text())
    assert rec["process_count"] == 1
    assert rec["argv_extra"] == "pass-through-arg"


def test_launch_two_process_cluster(tmp_path):
    """Two launcher processes join over a loopback coordinator and see one
    global 4-device view — the mixserv_cluster start analog."""
    port = _free_port()
    procs, outs = [], []
    for pid in range(2):
        out = tmp_path / f"launch{pid}.json"
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "hivemall_tpu.runtime.launch",
             "--coordinator", f"127.0.0.1:{port}",
             "--num-procs", "2", "--proc-id", str(pid),
             CHILD, str(out)],
            env=_env(), cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    logs = []
    for p in procs:
        try:
            log, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("launch child timed out")
        logs.append(log)
    if any(CPU_MP_UNSUPPORTED in log for log in logs):
        pytest.skip("installed jax cannot run cross-process collectives "
                    "on the CPU backend")
    for pid, (p, log) in enumerate(zip(procs, logs)):
        assert p.returncode == 0, f"proc {pid}:\n{log}"
        assert f"LAUNCH CHILD {pid} OK" in log
    recs = [json.loads(o.read_text()) for o in outs]
    for pid, rec in enumerate(recs):
        assert rec["process_index"] == pid
        assert rec["process_count"] == 2
        assert rec["local_devices"] == 2
        assert rec["global_devices"] == 4
        # the global psum proves cross-process communication, not just a join
        assert rec["collective"] == 4


def test_launch_mix_option_maps_to_coordinator():
    """--mix 'host1:port,host2' (the reference's client option syntax) must
    resolve its first entry as the coordinator address."""
    from hivemall_tpu.runtime.launch import build_parser
    from hivemall_tpu.runtime.cluster import parse_mix_option

    args = build_parser().parse_args(
        ["--mix", "10.0.0.5:7777,10.0.0.6", "--num-procs", "2",
         "--proc-id", "0", "prog.py"])
    host, port = parse_mix_option(args.mix)
    assert (host, port) == ("10.0.0.5", 7777)
    assert args.prog == "prog.py"


def test_daemon_lifecycle(tmp_path):
    """start -> status -> stop on localhost without ssh: pid file, log file,
    and a clean double-start refusal (mixserv_daemon.sh semantics)."""
    daemon = os.path.join(REPO, "bin", "hivemall_tpu_daemon.sh")
    pid_file = tmp_path / "worker.pid"
    # a worker program that stays alive long enough to probe status
    sleeper = tmp_path / "sleeper.py"
    sleeper.write_text("import time; time.sleep(30)\n")
    env = _env(
        HIVEMALL_TPU_HOME=REPO,
        HIVEMALL_TPU_PID_FILE=str(pid_file),
        HIVEMALL_TPU_LOG_DIR=str(tmp_path / "logs"),
        HIVEMALL_TPU_APP=str(sleeper),
        HIVEMALL_TPU_PYTHON=sys.executable,
    )

    r = subprocess.run(["bash", daemon, "start", "127.0.0.1:1", "1", "0"],
                       env=env, capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    assert pid_file.exists()
    try:
        # double start refuses while alive
        r2 = subprocess.run(["bash", daemon, "start", "127.0.0.1:1", "1", "0"],
                            env=env, capture_output=True, text=True, timeout=60)
        assert "already running" in r2.stdout

        r3 = subprocess.run(["bash", daemon, "status"], env=env,
                            capture_output=True, text=True, timeout=60)
        assert r3.returncode == 0 and "running as pid" in r3.stdout

        logs = list((tmp_path / "logs").iterdir())
        assert logs, "daemon wrote no log file"
    finally:
        r4 = subprocess.run(["bash", daemon, "stop"], env=env,
                            capture_output=True, text=True, timeout=60)
    assert "stopped pid" in r4.stdout
    assert not pid_file.exists()
    r5 = subprocess.run(["bash", daemon, "status"], env=env,
                        capture_output=True, text=True, timeout=60)
    assert r5.returncode == 1
