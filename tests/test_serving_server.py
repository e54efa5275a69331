"""Registry + /predict endpoint pins (serving/server.py): wire format,
error codes, and the acceptance property — an in-flight v1 -> v2 hot swap
completes with ZERO failed requests."""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from hivemall_tpu.models.classifier import train_arow, train_perceptron
from hivemall_tpu.serving import ModelRegistry, serve

ROWS = [[f"{i % 13}:1.0", f"{(i * 7) % 13}:0.5"] for i in range(40)]
LABELS = [1 if i % 2 else -1 for i in range(40)]

ENGINE_KW = {"max_batch": 32, "max_width": 16}


def _post(port, payload, timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


@pytest.fixture()
def stack():
    registry = ModelRegistry(max_batch=32, max_delay_ms=1.0,
                             engine_kwargs=ENGINE_KW)
    server = serve(registry)
    yield registry, server.server_address[1]
    server.shutdown()
    registry.shutdown()


def test_predict_wire_format(stack):
    registry, port = stack
    model = train_arow(ROWS, LABELS, "-dims 256")
    registry.deploy("ctr", model, version="1")

    out = _post(port, {"model": "ctr", "instances": ROWS[:5]})
    assert out["model"] == "ctr"
    assert out["version"] == "1"
    assert len(out["predictions"]) == 5
    # served over the wire == live model scores
    assert np.allclose(out["predictions"], model.predict(ROWS[:5]))

    # single deployed model: "model" may be omitted
    out2 = _post(port, {"instances": ROWS[:2]})
    assert out2["model"] == "ctr" and len(out2["predictions"]) == 2


def test_error_codes(stack):
    registry, port = stack
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, {"model": "nope", "instances": ROWS[:1]})
    assert e.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, {"model": "nope"})  # no instances
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(
            urllib.request.Request(f"http://127.0.0.1:{port}/predict",
                                   data=b"not json"), timeout=10)
    assert e.value.code == 400


def test_models_listing_and_metrics(stack):
    registry, port = stack
    registry.deploy("ctr", train_perceptron(ROWS, LABELS, "-dims 128"),
                    version="7")
    models = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/models", timeout=10).read())["models"]
    assert models[0]["name"] == "ctr"
    assert models[0]["version"] == "7"
    assert models[0]["family"] == "linear"
    _post(port, {"instances": ROWS[:3]})
    metrics = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
    assert "# TYPE hivemall_tpu_serving_ctr_batch_occupancy histogram" \
        in metrics
    assert "hivemall_tpu_serving_ctr_batch_occupancy_bucket" in metrics
    assert "# TYPE hivemall_tpu_serving_ctr_rows counter" in metrics


def test_hot_swap_under_load_zero_failures(stack):
    """The acceptance pin: requests hammer /predict from several threads
    while v1 is swapped for v2; every request succeeds and both versions
    are observed."""
    registry, port = stack
    v1 = train_arow(ROWS, LABELS, "-dims 256")
    v2 = train_arow(ROWS, LABELS, "-dims 256 -iters 3")
    registry.deploy("ctr", v1, version="1")

    failures, versions = [], set()
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            try:
                out = _post(port, {"model": "ctr", "instances": ROWS[:3]})
                versions.add(out["version"])
            except Exception as e:  # any failed request fails the test
                failures.append(repr(e))
                return

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    # let v1 serve some traffic, then swap in-flight
    for _ in range(3):
        _post(port, {"model": "ctr", "instances": ROWS[:2]})
    registry.deploy("ctr", v2, version="2")
    # post-swap requests serve v2's weights — observed while the hammer
    # threads are still running
    out = _post(port, {"model": "ctr", "instances": ROWS[:5]})
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert failures == []
    assert "1" in versions, "hammer never saw v1 traffic"
    assert out["version"] == "2"
    assert np.allclose(out["predictions"], v2.predict(ROWS[:5]))


def test_registry_submit_retries_across_swap(stack):
    """The deterministic version of the swap race: a caller holding the OLD
    entry gets BatcherClosed from its drained batcher, but registry.submit
    re-resolves and lands on the new version."""
    from hivemall_tpu.serving import BatcherClosed

    registry, _ = stack
    v1 = train_perceptron(ROWS, LABELS, "-dims 128")
    v2 = train_arow(ROWS, LABELS, "-dims 128")
    old_entry = registry.deploy("ctr", v1, version="1")
    registry.deploy("ctr", v2, version="2")
    # the stale handle fails hard...
    with pytest.raises(BatcherClosed):
        old_entry.batcher.submit(ROWS[:1])
    # ...but the registry path serves v2
    entry, fut = registry.submit("ctr", ROWS[:2])
    assert entry.version == "2"
    assert len(fut.result(timeout=10)) == 2


def test_undeploy(stack):
    registry, port = stack
    registry.deploy("ctr", train_perceptron(ROWS, LABELS, "-dims 128"))
    assert registry.undeploy("ctr") is True
    assert registry.undeploy("ctr") is False
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, {"model": "ctr", "instances": ROWS[:1]})
    assert e.value.code == 404


def test_bench_serving_http_mode_smoke(tmp_path):
    """scripts/bench_serving.py --http drives POST /predict end-to-end
    (ROADMAP open item): same BENCH-style JSON, zero steady-state
    recompiles, a zero-failure hot swap at the HTTP surface, and the
    tracing artifact — a Chrome trace covering >= 4 request-path stages
    plus the per-stage breakdown embedded in the BENCH JSON."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trace_path = str(tmp_path / "serving_trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "scripts/bench_serving.py", "--http", "--smoke",
         "--requests", "80", "--train-rows", "150", "--concurrency", "2",
         "--trace-out", trace_path],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("{")][-1]
    result = json.loads(line)
    assert result["methodology"] == "http_post_predict_closed_loop"
    assert result["unit"] == "req/s" and result["value"] > 0
    assert result["steady_state_recompiles"] == 0
    assert result["hot_swap"]["failed_requests"] == 0
    assert set(result["hot_swap"]["versions_observed"]) == {"1", "2"}
    assert result["request_errors"] == 0
    assert {m["metric"] for m in result["extra_metrics"]} == {
        "http_p50_ms", "http_p95_ms", "http_p99_ms"}
    # the tracing block: per-stage breakdown + slowest traces in the
    # artifact, and the exported Chrome trace loads with the full request
    # stage vocabulary (server/queue/pad/dispatch/block)
    tr = result["tracing"]
    assert len(set(tr["distinct_stages"]) & {
        "server.predict", "queue.wait", "engine.pad", "engine.dispatch",
        "engine.block"}) >= 4
    assert tr["slowest_traces"] and tr["slowest_traces"][0]["stages_ms"]
    assert tr["stage_breakdown_ms"]["queue.wait"]["count"] > 0
    doc = json.load(open(trace_path))
    assert {e["name"] for e in doc["traceEvents"]} >= set(
        tr["distinct_stages"])


def test_multi_model_registry(stack):
    registry, port = stack
    registry.deploy("a", train_perceptron(ROWS, LABELS, "-dims 128"))
    registry.deploy("b", train_arow(ROWS, LABELS, "-dims 128"))
    assert {m["name"] for m in registry.list_models()} == {"a", "b"}
    out = _post(port, {"model": "b", "instances": ROWS[:2]})
    assert out["model"] == "b"
    # ambiguous: two models, no name -> 404
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, {"instances": ROWS[:1]})
    assert e.value.code == 404


def test_per_priority_latency_histograms_and_slo_healthz(stack):
    """PR 20 observability satellites on the serving port: every
    successful /predict lands in BOTH the overall latency histogram and
    its priority class's own (high/normal/low on /metrics), /healthz
    carries the SLO block, and GET /slo + /debug/bundle are served with
    the registry's models described."""
    registry, port = stack
    registry.deploy("ctr", train_arow(ROWS, LABELS, "-dims 256"),
                    version="1")

    def counts():
        # the metrics registry is process-wide, so pin DELTAS, not totals
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        out = {}
        for line in text.splitlines():
            if line.startswith("hivemall_tpu_serving_http_latency_seconds") \
                    and "_count " in line:
                key, val = line.rsplit(" ", 1)
                out[key] = float(val)
        return text, out

    metrics, before = counts()
    for prio in ("high", "normal", "low"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"model": "ctr",
                             "instances": ROWS[:2]}).encode(),
            headers={"Content-Type": "application/json",
                     "x-priority": prio})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert json.loads(r.read())["model"] == "ctr"
    metrics, after = counts()
    for prio in ("high", "normal", "low"):
        name = f"hivemall_tpu_serving_http_latency_seconds_{prio}"
        assert f"# TYPE {name} histogram" in metrics
        key = f"{name}_count"
        assert after[key] - before.get(key, 0.0) == 1.0, \
            f"{prio} class must record exactly its 1 request"
    # the overall histogram saw all three
    overall = "hivemall_tpu_serving_http_latency_seconds_count"
    assert after[overall] - before.get(overall, 0.0) == 3.0

    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=10).read())
    assert "slo" in health
    assert set(health["slo"]) == {"worst_state", "paging", "warning",
                                  "evaluated"}
    # no objective is paging here, so SLO burn must not degrade health
    assert health["slo"]["paging"] == []

    slo_doc = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/slo", timeout=10).read())
    assert "slos" in slo_doc and "worst_state" in slo_doc
    bundle = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/debug/bundle?n=5", timeout=10).read())
    # the serving server carries its registry: models are described
    assert any(m.get("name") == "ctr" for m in bundle["models"])
    assert bundle["health"] is not None
