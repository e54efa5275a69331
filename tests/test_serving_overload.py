"""Overload-grade serving pins (serving/admission.py + batcher.py +
server.py): strict-priority drain with a bounded starvation escape,
admission quotas with lowest-first shedding, in-queue deadline expiry that
never reaches dispatch, the AIMD adaptive-batching controller, the
express high-priority lane, the one-lock-acquisition admission decision
under concurrent submits, and the HTTP overload contract (x-priority /
x-deadline-ms, 504, Retry-After, concurrency door, degraded /healthz,
per-model quota isolation)."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from hivemall_tpu.models.classifier import train_arow, train_perceptron
from hivemall_tpu.runtime.metrics import REGISTRY
from hivemall_tpu.serving import (AIMDController, DeadlineExpired,
                                  DynamicBatcher, ModelRegistry, QueueFull,
                                  ShedLowPriority, priority_class, serve)

ROWS = [[f"{i % 13}:1.0", f"{(i * 7) % 13}:0.5"] for i in range(40)]
LABELS = [1 if i % 2 else -1 for i in range(40)]
ENGINE_KW = {"max_batch": 32, "max_width": 16}


def _blocked_batcher(name, **kw):
    """A batcher whose worker can be parked inside predict: the first
    submitted request enters predict and blocks until `release` is set.
    Calls (the dispatched row lists) are recorded in order."""
    started = threading.Event()
    release = threading.Event()
    calls = []

    def predict(rows):
        calls.append(list(rows))
        started.set()
        release.wait(timeout=10)
        return rows

    b = DynamicBatcher(predict, name=name, **kw)
    return b, calls, started, release


# -- priority classes ---------------------------------------------------------

def test_priority_class_normalization():
    assert priority_class("high") == 0
    assert priority_class("NORMAL") == 1
    assert priority_class(2) == 2
    assert priority_class("1") == 1
    for bad in ("urgent", 3, -1, True, None, 1.5):
        with pytest.raises(ValueError):
            priority_class(bad)


def test_strict_priority_drain_single_class_batches():
    """With the worker parked, queued high work dispatches before queued
    low work, and batches never mix classes."""
    b, calls, started, release = _blocked_batcher(
        "ovl_strict", max_batch=8, max_delay_ms=0.5)
    try:
        first = b.submit(["park"])
        started.wait(timeout=5)
        f_low = [b.submit([f"low{i}"], priority="low") for i in range(2)]
        f_high = [b.submit([f"high{i}"], priority="high") for i in range(2)]
        release.set()
        for f in f_high + f_low + [first]:
            f.result(timeout=5)
        # call 0 is the parked request; highs land strictly before lows
        flat = [r for c in calls[1:] for r in c]
        assert flat.index("high0") < flat.index("low0")
        assert flat.index("high1") < flat.index("low0")
        for c in calls[1:]:
            kinds = {r[:3] for r in c}
            assert len(kinds) == 1, f"mixed-class batch: {c}"
    finally:
        release.set()
        b.close()


def test_starvation_bound_forces_low_batch():
    """A low request skipped `starvation_limit` consecutive batches while
    queued anchors the next batch — bounded progress under a sustained
    high flood."""
    b, calls, started, release = _blocked_batcher(
        "ovl_starve", max_batch=1, max_delay_ms=0.2, starvation_limit=3)
    try:
        first = b.submit(["park"])
        started.wait(timeout=5)
        f_low = b.submit(["low"], priority="low")
        f_high = [b.submit([f"high{i}"], priority="high") for i in range(8)]
        release.set()
        for f in f_high + [f_low, first]:
            f.result(timeout=5)
        order = [c[0] for c in calls[1:]]
        # the low request dispatched after at most starvation_limit
        # high batches, with highs still queued behind it
        low_at = order.index("low")
        assert low_at <= 3, f"low starved past the bound: {order}"
        assert any(r.startswith("high") for r in order[low_at + 1:])
    finally:
        release.set()
        b.close()


# -- deadlines ----------------------------------------------------------------

def test_inqueue_expiry_never_reaches_dispatch():
    b, calls, started, release = _blocked_batcher(
        "ovl_expire", max_batch=4, max_delay_ms=0.2)
    try:
        before = REGISTRY.counter(
            "serving", "ovl_expire.batcher.expired.normal").value
        first = b.submit(["park"])
        started.wait(timeout=5)
        doomed = b.submit(["doomed"], deadline_ms=30)
        time.sleep(0.08)  # the deadline elapses while the worker is parked
        release.set()
        with pytest.raises(DeadlineExpired):
            doomed.result(timeout=5)
        assert first.result(timeout=5) == ["park"]
        # a follow-up proves the worker moved on; "doomed" never dispatched
        assert b.submit(["after"]).result(timeout=5) == ["after"]
        assert not any("doomed" in c for c in calls)
        assert REGISTRY.counter(
            "serving", "ovl_expire.batcher.expired.normal").value \
            == before + 1
    finally:
        release.set()
        b.close()


def test_submit_rejects_nonpositive_deadline():
    b, _, _, release = _blocked_batcher("ovl_badddl", max_batch=2)
    try:
        with pytest.raises(ValueError):
            b.submit(["x"], deadline_ms=0)
        with pytest.raises(ValueError):
            b.submit(["x"], deadline_ms=-5)
    finally:
        release.set()
        b.close()


# -- quotas + shedding --------------------------------------------------------

def test_quota_rejects_low_while_high_has_headroom():
    b, _, started, release = _blocked_batcher(
        "ovl_quota", max_batch=2, max_delay_ms=0.1, max_queue_rows=8,
        priority_quota_fracs=(1.0, 0.75, 0.5))
    try:
        first = b.submit(["park"])
        started.wait(timeout=5)
        b.submit(["n1", "n2", "n3", "n4"])  # depth 4 = the low quota
        with pytest.raises(QueueFull) as e:
            b.submit(["l1"], priority="low")  # 4+1 > 8*0.5
        assert e.value.reason == "quota"
        assert e.value.retry_after_s >= 1.0
        b.submit(["n5", "n6"])  # 4+2 <= 6: normal still admitted
        with pytest.raises(QueueFull):
            b.submit(["n7"])  # 6+1 > 8*0.75
        f_high = b.submit(["h1", "h2"], priority="high")  # to the full cap
        release.set()
        assert f_high.result(timeout=5) == ["h1", "h2"]
        assert first.result(timeout=5) == ["park"]
        st = b.overload_state()
        assert st["quota_rejected"]["low"] >= 1
        assert st["quota_rejected"]["normal"] >= 1
        assert st["quota_rejected"]["high"] == 0
    finally:
        release.set()
        b.close()


def test_shed_evicts_newest_lowest_priority_for_high():
    b, _, started, release = _blocked_batcher(
        "ovl_shed", max_batch=2, max_delay_ms=0.1, max_queue_rows=4)
    try:
        first = b.submit(["park"])
        started.wait(timeout=5)
        low_old = b.submit(["lo1", "lo2"], priority="low")
        low_new = b.submit(["ln1", "ln2"], priority="low")
        f_high = b.submit(["h1"], priority="high")  # evicts the NEWEST low
        with pytest.raises(ShedLowPriority) as e:
            low_new.result(timeout=5)
        assert e.value.reason == "shed"
        release.set()
        assert f_high.result(timeout=5) == ["h1"]
        assert low_old.result(timeout=5) == ["lo1", "lo2"]
        assert b.overload_state()["shed"]["low"] >= 1
    finally:
        release.set()
        b.close()


def test_no_shed_when_shedding_cannot_admit():
    """Eviction only happens when the lower classes actually hold enough
    rows to admit the trigger — shedding someone and STILL rejecting
    would destroy accepted work for nothing."""
    b, _, started, release = _blocked_batcher(
        "ovl_noshed", max_batch=2, max_delay_ms=0.1, max_queue_rows=4)
    try:
        first = b.submit(["park"])
        started.wait(timeout=5)
        f_hi = b.submit(["h1", "h2", "h3"], priority="high")
        f_low = b.submit(["l1"], priority="low")  # depth 4 = cap
        with pytest.raises(QueueFull) as e:
            # needs 2 rows freed but the lower classes hold only 1
            b.submit(["x1", "x2"], priority="high")
        assert e.value.reason == "quota"
        release.set()
        assert f_low.result(timeout=5) == ["l1"]  # survived: no futile shed
        assert f_hi.result(timeout=5) == ["h1", "h2", "h3"]
        first.result(timeout=5)
        assert b.overload_state()["shed"]["low"] == 0
    finally:
        release.set()
        b.close()


def test_concurrent_submit_admission_is_atomic():
    """The satellite race pin: quota checks, queue append and counters
    happen under ONE lock acquisition — hammering submit from many
    threads leaves counters exactly consistent with the futures'
    outcomes (no check-then-act window)."""
    b, _, started, release = _blocked_batcher(
        "ovl_race", max_batch=4, max_delay_ms=0.2, max_queue_rows=32,
        priority_quota_fracs=(1.0, 0.75, 0.5))
    names = ("high", "normal", "low")
    futures, quota_rejected = [], []
    lock = threading.Lock()
    try:
        first = b.submit(["park"])
        started.wait(timeout=5)
        base = {k: [REGISTRY.counter(
            "serving", f"ovl_race.batcher.{k}.{p}").value for p in names]
            for k in ("accepted", "quota_rejected", "shed")}
        barrier = threading.Barrier(12)

        def hammer(i):
            barrier.wait()
            for j in range(20):
                pri = names[(i + j) % 3]
                try:
                    f = b.submit([f"r{i}_{j}", f"s{i}_{j}"], priority=pri)
                    with lock:
                        futures.append(f)
                except ShedLowPriority:
                    raise AssertionError("submit() itself never sheds")
                except QueueFull:
                    with lock:
                        quota_rejected.append(pri)

        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        release.set()
        outcomes = {"ok": 0, "shed": 0, "expired": 0}
        for f in futures:
            try:
                f.result(timeout=10)
                outcomes["ok"] += 1
            except ShedLowPriority:
                outcomes["shed"] += 1
            except DeadlineExpired:
                outcomes["expired"] += 1
        first.result(timeout=10)
        delta = {k: sum(REGISTRY.counter(
            "serving", f"ovl_race.batcher.{k}.{p}").value - base[k][c]
            for c, p in enumerate(names))
            for k in ("accepted", "quota_rejected", "shed")}
        # every submit resolved exactly one way, and the counters agree
        assert delta["accepted"] == len(futures)
        assert delta["quota_rejected"] == len(quota_rejected)
        assert delta["shed"] == outcomes["shed"]
        assert outcomes["ok"] + outcomes["shed"] + outcomes["expired"] \
            == len(futures)
        assert b.overload_state()["depth_rows"] == 0
    finally:
        release.set()
        b.close()


# -- adaptive batching --------------------------------------------------------

def test_aimd_controller_grows_under_load_and_decays_idle():
    c = AIMDController(base_delay_s=0.002, cap_delay_s=0.02,
                       base_batch=32, cap_batch=128)
    assert c.adaptive
    for _ in range(64):
        c.on_take(depth_rows_after=1000)  # persistent backlog
    assert c.delay_s == 0.02 and c.batch_rows == 128  # pinned at caps
    for _ in range(16):
        c.on_idle()
    assert c.delay_s == 0.002 and c.batch_rows == 32  # back at base
    # fixed-window defaults: caps equal bases, controller is inert
    fixed = AIMDController(base_delay_s=0.002, cap_delay_s=0.002,
                           base_batch=32, cap_batch=32)
    fixed.on_take(depth_rows_after=1000)
    assert not fixed.adaptive and fixed.delay_s == 0.002 \
        and fixed.batch_rows == 32


def test_batcher_widens_under_backlog_then_decays():
    def predict(rows):
        time.sleep(0.002)
        return rows

    b = DynamicBatcher(predict, name="ovl_aimd", max_batch=4,
                       max_delay_ms=0.5, max_delay_ms_cap=8.0,
                       max_batch_cap=16, max_queue_rows=4096)
    try:
        futs = [b.submit([i, i + 1]) for i in range(100)]  # deep backlog
        for f in futs:
            f.result(timeout=30)
        widened = b.overload_state()["controller"]
        assert widened["delay_ms"] > 0.5 or widened["batch_rows"] > 4
        # idle wake-ups decay the window back toward base
        for i in range(6):
            b.submit([i]).result(timeout=5)
            time.sleep(0.01)
        decayed = b.overload_state()["controller"]
        assert decayed["delay_ms"] <= widened["delay_ms"]
        assert decayed["batch_rows"] <= max(4, widened["batch_rows"])
    finally:
        b.close()


def test_express_lane_serves_high_while_general_lane_is_busy():
    """The express lane: with the GENERAL worker parked inside a normal
    batch's predict, a high-priority submit still completes — high never
    waits out a lower class's dispatch quantum."""
    release = threading.Event()
    started = threading.Event()

    def predict(rows):
        if any("slow" in str(r) for r in rows):
            started.set()
            release.wait(timeout=10)
        return rows

    b = DynamicBatcher(predict, name="ovl_express", max_batch=4,
                       max_delay_ms=0.2, express_high=True)
    try:
        slow = b.submit(["slow"])  # general lane parks in predict
        started.wait(timeout=5)
        fast = b.submit(["hi"], priority="high")
        assert fast.result(timeout=5) == ["hi"]  # while normal in flight
        assert not slow.done()
        release.set()
        assert slow.result(timeout=5) == ["slow"]
    finally:
        release.set()
        b.close()


# -- HTTP overload contract ---------------------------------------------------

def _post_raw(port, payload, headers=(), timeout=10):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **dict(headers)})
    return urllib.request.urlopen(req, timeout=timeout)


def _post(port, payload, headers=(), timeout=10):
    with _post_raw(port, payload, headers, timeout) as r:
        return json.loads(r.read()), dict(r.headers)


@pytest.fixture()
def stack():
    registry = ModelRegistry(max_batch=32, max_delay_ms=1.0,
                             max_queue_rows=8, engine_kwargs=ENGINE_KW)
    server = serve(registry)
    yield registry, server.server_address[1]
    server.shutdown()
    registry.shutdown()


def _park_entry(registry, name):
    """Swap the deployed entry's predict_fn for one whose FIRST call
    parks until released (later calls — e.g. the express lane's — run
    through); returns (entry, started, release)."""
    entry = registry.get(name)
    started, release = threading.Event(), threading.Event()
    real = entry.batcher.predict_fn
    first = threading.Event()

    def blocked(rows):
        if not first.is_set():
            first.set()
            started.set()
            release.wait(timeout=10)
        return real(rows)

    entry.batcher.predict_fn = blocked
    return entry, started, release


def test_priority_and_deadline_headers_and_504(stack):
    registry, port = stack
    registry.deploy("ctr", train_arow(ROWS, LABELS, "-dims 256"))
    out, _ = _post(port, {"instances": ROWS[:2]},
                   headers={"x-priority": "high"})
    assert len(out["predictions"]) == 2
    # park the worker; a deadlined request expires IN the queue -> 504
    # (delivered once the worker cycles — collect the response async)
    entry, started, release = _park_entry(registry, "ctr")
    doomed: list = []

    def post_doomed():
        try:
            _post(port, {"instances": ROWS[:1]},
                  headers={"x-deadline-ms": "40"}, timeout=30)
            doomed.append(("ok", None))
        except urllib.error.HTTPError as e:
            doomed.append((e.code, json.loads(e.read())))

    try:
        bg = threading.Thread(
            target=lambda: _post(port, {"instances": ROWS[:1]}, timeout=30))
        bg.start()
        started.wait(timeout=5)
        t = threading.Thread(target=post_doomed)
        t.start()
        time.sleep(0.15)  # the 40 ms budget elapses while parked
    finally:
        release.set()
        bg.join(timeout=10)
    t.join(timeout=10)
    assert doomed and doomed[0][0] == 504
    assert doomed[0][1]["reason"] == "deadline"
    # invalid header values are a 400, not a silent default
    for hdr in ({"x-priority": "urgent"}, {"x-deadline-ms": "-3"},
                {"x-deadline-ms": "nan"}):
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(port, {"instances": ROWS[:1]}, headers=hdr)
        assert e.value.code == 400


def test_quota_503_carries_retry_after_and_isolation(stack):
    """One model's flood 503s with Retry-After + reason while a second
    model keeps serving — per-model quotas are per-model batchers."""
    registry, port = stack
    registry.deploy("a", train_arow(ROWS, LABELS, "-dims 256"))
    registry.deploy("b", train_perceptron(ROWS, LABELS, "-dims 128"))
    entry, started, release = _park_entry(registry, "a")
    try:
        bg = threading.Thread(
            target=lambda: _post(port, {"model": "a",
                                        "instances": ROWS[:1]}, timeout=30))
        bg.start()
        started.wait(timeout=5)
        # fill model a's queue to its normal-class quota (0.85 * 8 = 6)
        entry.batcher.submit(ROWS[:6])
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(port, {"model": "a", "instances": ROWS[:2]})
        assert e.value.code == 503
        assert int(e.value.headers["Retry-After"]) >= 1
        assert json.loads(e.value.read())["reason"] == "quota"
        # model b is untouched by a's flood
        out, _ = _post(port, {"model": "b", "instances": ROWS[:3]})
        assert len(out["predictions"]) == 3
    finally:
        release.set()
        bg.join(timeout=10)


def test_healthz_reports_degraded_before_dead(stack):
    registry, port = stack
    registry.deploy("ctr", train_arow(ROWS, LABELS, "-dims 256"))

    def healthz():
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                    timeout=10) as r:
            assert r.status == 200  # alive either way — that's the point
            return json.loads(r.read())

    assert healthz()["status"] == "ok"
    entry, started, release = _park_entry(registry, "ctr")
    try:
        bg = threading.Thread(
            target=lambda: _post(port, {"instances": ROWS[:1]}, timeout=30))
        bg.start()
        started.wait(timeout=5)
        entry.batcher.submit(ROWS[:6])  # 6/8 rows = the 0.75 threshold
        info = healthz()
        assert info["status"] == "degraded"
        assert info["models"]["ctr"]["depth_fraction"] >= 0.75
        assert "controller" in info["models"]["ctr"]
    finally:
        release.set()
        bg.join(timeout=10)
    for _ in range(50):  # drains fast once released
        if healthz()["status"] == "ok":
            break
        time.sleep(0.05)
    assert healthz()["status"] == "ok"


def test_concurrency_door_rejects_cheap_and_reserves_high():
    registry = ModelRegistry(max_batch=32, max_delay_ms=1.0,
                             engine_kwargs=ENGINE_KW)
    server = serve(registry, max_concurrent_requests=1)
    port = server.server_address[1]
    try:
        registry.deploy("ctr", train_arow(ROWS, LABELS, "-dims 256"))
        entry, started, release = _park_entry(registry, "ctr")
        bg = threading.Thread(
            target=lambda: _post(port, {"instances": ROWS[:1]}, timeout=30))
        bg.start()
        started.wait(timeout=5)
        # the single in-flight slot is taken: a normal request is refused
        # at the door, before its body is parsed
        t0 = time.perf_counter()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(port, {"instances": ROWS[:1]})
        assert e.value.code == 503
        assert json.loads(e.value.read())["reason"] == "concurrency"
        assert time.perf_counter() - t0 < 2.0
        # a high-priority HEADER request enters through the reserve
        out, _ = _post(port, {"instances": ROWS[:2]},
                       headers={"x-priority": "high"}, timeout=30)
        release.set()
        assert len(out["predictions"]) == 2
        bg.join(timeout=10)
    finally:
        release.set()
        server.shutdown()
        registry.shutdown()


def test_traceparent_adopted_and_echoed(stack):
    registry, port = stack
    registry.deploy("ctr", train_arow(ROWS, LABELS, "-dims 256"))
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    hdr = f"00-{tid}-00f067aa0ba902b7-01"
    _, headers = _post(port, {"instances": ROWS[:1]},
                       headers={"traceparent": hdr})
    echoed = headers["traceparent"]
    ver, e_tid, e_sid, flags = echoed.split("-")
    assert (ver, e_tid) == ("00", tid)  # adopted trace id, echoed back
    assert e_sid != "00f067aa0ba902b7" and len(e_sid) == 16  # OUR root span
    from hivemall_tpu.runtime.tracing import TRACER

    # the root span commits in the handler thread AFTER the response body
    # is flushed — the client can observe the response before the trace
    # lands in the ring; poll briefly instead of racing that window
    committed = []
    for _ in range(100):
        committed = [t for t in TRACER.traces() if t["trace_id"] == tid]
        if committed:
            break
        time.sleep(0.01)
    assert committed, "adopted trace never committed"
    root = [s for s in committed[-1]["spans"]
            if s["name"] == "server.predict"][0]
    assert root["parent_id"] == "00f067aa0ba902b7"  # client span = parent
    # malformed headers fall back to a fresh trace (and still echo)
    for bad in ("ff-" + hdr[3:], "00-" + "0" * 32 + "-00f067aa0ba902b7-01",
                "nonsense", "00-zz-yy-01"):
        _, headers = _post(port, {"instances": ROWS[:1]},
                           headers={"traceparent": bad})
        assert headers["traceparent"].split("-")[1] != tid


def test_models_listing_exposes_admission_state(stack):
    registry, port = stack
    registry.deploy("ctr", train_arow(ROWS, LABELS, "-dims 256"))
    models = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/models", timeout=10).read())["models"]
    adm = models[0]["admission"]
    assert adm["max_queue_rows"] == 8
    assert adm["quota_fracs"] == {"high": 1.0, "normal": 0.85, "low": 0.6}
    assert adm["controller"]["base_batch"] == 32
    assert set(adm["shed"]) == {"high", "normal", "low"}


@pytest.mark.slow  # the REAL smoke runs as tier-1 gate 7 in scripts/test.sh
def test_bench_serving_overload_smoke(tmp_path):
    """scripts/bench_serving.py --overload end-to-end (tier-1 gate 7
    shape, scaled down): the BENCH JSON carries the goodput curve,
    consistent shed counters, and zero steady-state recompiles. The
    retention gate itself is disabled here (--goodput-retention-min 0):
    at this tiny scale inside a loaded test run it measures host noise —
    gate 7 runs the real thing at smoke scale."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "scripts/bench_serving.py", "--overload",
         "--smoke", "--dims", "512", "--train-rows", "120",
         "--calib-requests", "30", "--step-seconds", "1.2",
         "--instances-per-request", "64", "--max-batch", "32",
         "--concurrency", "4", "--goodput-retention-min", "0",
         "--trace-out", str(tmp_path / "overload_trace.json")],
        cwd=repo, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][-1]
    result = json.loads(line)
    assert result["methodology"] == "http_open_loop_stepped_offered_load"
    assert result["retention_x"] > 0
    assert result["steady_state_recompiles"] == 0
    assert [s["offered_x"] for s in result["steps"]] == [0.25, 1.0, 2.0]
    for s in result["steps"]:
        assert set(s["by_priority"]) == {"high", "normal", "low"}
    assert all(v["ok_"] for v in result["consistency"].values()
               if isinstance(v, dict) and "ok_" in v)
    assert result["consistency"]["transport_errors"] == 0
    assert set(result["counters"]) == {"accepted", "quota_rejected",
                                       "shed", "expired"}
    assert result["admission"]["max_concurrent_requests"] >= 12
    assert result["high_priority_p99"]["bound_ms"] > 0
