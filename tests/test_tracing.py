"""End-to-end tracing tests (runtime/tracing.py + the serving/training
wiring): span nesting and the batcher thread hop, ring-buffer eviction,
seeded sampling determinism, Chrome-export schema, the /trace endpoint,
recompile instant events, per-step training timelines, and the tracer's
hot-path overhead bound."""

import json
import time
import urllib.request

import numpy as np
import pytest

from hivemall_tpu.runtime.tracing import TRACER, Tracer, step_span


def _make_model(dims=256, n=120, seed=0):
    from hivemall_tpu.models.classifier import train_arow

    rng = np.random.RandomState(seed)
    rows = [[f"{rng.randint(dims)}:{rng.rand():.3f}"
             for _ in range(rng.randint(3, 8))] for _ in range(n)]
    labels = rng.choice([-1, 1], n)
    return train_arow(rows, labels, f"-dims {dims}"), rows


# -- core span mechanics -----------------------------------------------------

def test_span_nesting_and_parenting():
    t = Tracer(seed=1)
    with t.span("root", args={"k": 1}) as root:
        assert t.current() is root
        with t.span("child") as child:
            assert child.trace_id == root.trace_id
            with t.span("grandchild") as gc:
                pass
    assert t.current() is None
    (trace,) = t.traces()
    by_name = {s["name"]: s for s in trace["spans"]}
    assert trace["root"] == "root"
    assert by_name["child"]["parent_id"] == by_name["root"]["span_id"]
    assert by_name["grandchild"]["parent_id"] == by_name["child"]["span_id"]
    assert by_name["root"]["parent_id"] is None
    assert by_name["root"]["args"] == {"k": 1}
    assert trace["duration_ms"] >= by_name["child"]["dur_us"] / 1e3


def test_sibling_roots_are_separate_traces():
    t = Tracer(seed=1)
    with t.span("a"):
        pass
    with t.span("b"):
        pass
    ids = [tr["trace_id"] for tr in t.traces()]
    assert len(ids) == 2 and ids[0] != ids[1]


def test_disabled_tracer_is_a_noop():
    t = Tracer(enabled=False)
    with t.span("x") as s:
        assert not s.recording
        s.set(a=1)
        s.event("e")
    assert t.traces() == []
    assert t.current() is None


def test_traces_n_zero_returns_none_not_all():
    """out[-0:] is the whole list — n<=0 must mean 'none', including via
    GET /trace?n=0."""
    t = Tracer(seed=0)
    for i in range(3):
        with t.span(f"r{i}"):
            pass
    assert t.traces(n=0) == []
    assert t.traces(n=-2) == []
    assert len(t.traces(n=2)) == 2


def test_ring_buffer_eviction_order():
    """The ring holds the LAST `capacity` committed traces, oldest first —
    FIFO eviction, no reordering."""
    t = Tracer(capacity=3, seed=0)
    for i in range(7):
        with t.span(f"r{i}"):
            pass
    assert [tr["root"] for tr in t.traces()] == ["r4", "r5", "r6"]
    assert [tr["root"] for tr in t.traces(n=2)] == ["r5", "r6"]
    t.clear()
    assert t.traces() == []


def test_sampling_determinism_with_seeded_sampler():
    """Same seed -> the same commit/drop decision sequence (roots draw
    from a seeded RNG); child spans inherit the root's decision."""
    def decisions(seed):
        t = Tracer(sample_rate=0.4, seed=seed)
        out = []
        for i in range(32):
            with t.span(f"r{i}") as root:
                with t.span("child"):
                    pass
                out.append(root.sampled)
        # committed traces == sampled roots, in order
        assert [tr["root"] for tr in t.traces()] == \
            [f"r{i}" for i, s in enumerate(out) if s]
        return out

    a, b = decisions(1234), decisions(1234)
    assert a == b
    assert 0 < sum(a) < 32  # actually sampling, not all-or-nothing
    assert decisions(99) != a  # seed matters


def test_always_sample_on_slow():
    """An unsampled root slower than slow_ms commits anyway — the tail is
    never invisible; fast unsampled roots count as dropped."""
    t = Tracer(sample_rate=0.0, slow_ms=5.0, seed=0)
    with t.span("fast"):
        pass
    with t.span("slow"):
        time.sleep(0.02)
    roots = [tr["root"] for tr in t.traces()]
    assert roots == ["slow"]
    assert t.traces()[0]["sampled"] is False
    assert t.dropped == 1


def test_exemplar_id_respects_sampling_and_slow_escape():
    """Exemplars link only to traces that can land in the ring: sampled
    roots always; unsampled roots only when slow_ms makes the slow escape
    possible (the tail is exactly what an exemplar should reach)."""
    t = Tracer(sample_rate=0.0, seed=0)
    with t.span("r") as root:
        assert t.exemplar_id(root) is None  # can never commit
    t_slow = Tracer(sample_rate=0.0, slow_ms=1.0, seed=0)
    with t_slow.span("r") as root:
        assert t_slow.exemplar_id(root) == root.trace_id
        time.sleep(0.002)
    assert [tr["trace_id"] for tr in t_slow.traces()] == [root.trace_id]
    t_on = Tracer(sample_rate=1.0, seed=0)
    with t_on.span("r") as root:
        assert t_on.exemplar_id() == root.trace_id  # defaults to current
    assert t_on.exemplar_id() is None  # outside any span


def test_instant_events_and_retro_spans():
    t = Tracer(seed=0)
    with t.span("root") as root:
        t0 = time.perf_counter_ns()
        time.sleep(0.001)
        t.instant("marker", {"x": 1})
        t.add_span("retro", root, t0, time.perf_counter_ns(),
                   args={"rows": 3})
    (trace,) = t.traces()
    by_name = {s["name"]: s for s in trace["spans"]}
    assert by_name["root"]["events"][0]["name"] == "marker"
    assert by_name["root"]["events"][0]["args"] == {"x": 1}
    assert by_name["retro"]["parent_id"] == by_name["root"]["span_id"]
    assert by_name["retro"]["dur_us"] >= 1000
    assert by_name["retro"]["args"] == {"rows": 3}


def test_chrome_export_schema(tmp_path):
    """The export is Chrome trace_event JSON: a traceEvents list of "X"
    complete events (ts/dur in microseconds) and "i" instant events, each
    carrying pid/tid and the trace/span ids in args — the shape
    ui.perfetto.dev and chrome://tracing load."""
    t = Tracer(seed=0)
    with t.span("root", args={"rows": 4}):
        with t.span("child"):
            t.instant("blip", {"n": 1})
    path = str(tmp_path / "trace.json")
    doc = t.export_chrome(path)
    on_disk = json.load(open(path))
    assert on_disk == doc
    assert set(doc) >= {"traceEvents", "displayTimeUnit"}
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    instants = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert {e["name"] for e in xs} == {"root", "child"}
    assert [e["name"] for e in instants] == ["blip"]
    for e in xs:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["cat"] == "hivemall_tpu"
        assert "trace_id" in e["args"] and "span_id" in e["args"]
    (blip,) = instants
    assert blip["s"] == "t"
    root = next(e for e in xs if e["name"] == "root")
    child = next(e for e in xs if e["name"] == "child")
    assert child["args"]["parent_id"] == root["args"]["span_id"]
    # spans nest in time: child inside [root.ts, root.ts + root.dur]
    assert root["ts"] <= child["ts"]
    assert child["ts"] + child["dur"] <= root["ts"] + root["dur"] + 1e-3


def test_stage_breakdown_and_slowest():
    t = Tracer(seed=0)
    for ms in (1, 5):
        with t.span("request"):
            with t.span("work"):
                time.sleep(ms / 1000)
    br = t.stage_breakdown()
    assert br["work"]["count"] == 2
    assert br["work"]["total_ms"] >= 5.0
    assert br["work"]["max_ms"] >= br["work"]["mean_ms"]
    slowest = t.slowest(1)
    assert len(slowest) == 1
    assert slowest[0]["duration_ms"] >= 5.0
    assert slowest[0]["stages_ms"]["work"] >= 5.0


def test_every_span_is_a_profiler_annotation(monkeypatch):
    """The bridge has no switch: each span's extent is a
    jax.profiler.TraceAnnotation of the same name, opened after the span
    and closed before it, so a profile being taken holds the program's
    spans on its own clock; a disabled tracer opens none."""
    from hivemall_tpu.runtime import tracing

    seen = []

    class Mark:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("open", self.name))

        def __exit__(self, *exc):
            seen.append(("close", self.name))

    monkeypatch.setattr(tracing, "_ANNOTATION", Mark)
    t = Tracer(seed=0)
    with t.span("annotated"):
        with t.span("inner"):
            pass
    assert seen == [("open", "annotated"), ("open", "inner"),
                    ("close", "inner"), ("close", "annotated")]
    (trace,) = t.traces()
    assert {s["name"] for s in trace["spans"]} == {"annotated", "inner"}
    with Tracer(enabled=False).span("off"):
        pass
    assert len(seen) == 4
    assert not hasattr(t, "jax_annotations")


# -- serving-path wiring -----------------------------------------------------

def test_batcher_thread_hop_parenting():
    """A request submitted under an ambient span crosses to the worker
    thread carrying it: queue.wait and batch.predict land in the SAME
    trace, parented under the submit-side span."""
    from hivemall_tpu.serving import DynamicBatcher

    TRACER.clear()
    batcher = DynamicBatcher(lambda rows: [0.0] * len(rows),
                             name="hop_test", max_delay_ms=1.0)
    try:
        with TRACER.span("server.predict") as root:
            fut = batcher.submit([["1:1.0"], ["2:1.0"]])
            assert fut.result(timeout=10) == [0.0, 0.0]
    finally:
        batcher.close()
    trace = next(t for t in TRACER.traces()
                 if t["root"] == "server.predict")
    by_name = {s["name"]: s for s in trace["spans"]}
    assert {"server.predict", "queue.wait", "batch.predict"} <= set(by_name)
    root_id = by_name["server.predict"]["span_id"]
    assert by_name["queue.wait"]["parent_id"] == root_id
    assert by_name["batch.predict"]["parent_id"] == root_id
    # the hop is real: worker spans ran on a different thread
    assert by_name["batch.predict"]["tid"] != by_name["server.predict"]["tid"]
    assert by_name["queue.wait"]["args"]["rows"] == 2


def test_batch_rep_prefers_sampled_request():
    """Under sampling < 1, the batch's device-side spans must land in a
    trace that will actually COMMIT: an unsampled first request must not
    absorb batch.predict into a dropped trace while the sampled request
    commits stage-less (regression: rep selection ignored sampling)."""
    import hivemall_tpu.serving.batcher as batcher_mod
    from hivemall_tpu.serving import DynamicBatcher

    t = Tracer(sample_rate=0.5, seed=7)
    # find a (drop, keep) decision pair so request 0 is unsampled
    probe = Tracer(sample_rate=0.5, seed=7)
    decisions = [probe._sample() for _ in range(8)]
    assert False in decisions and True in decisions
    orig = batcher_mod.TRACER
    batcher_mod.TRACER = t
    try:
        b = DynamicBatcher(lambda rows: [0.0] * len(rows),
                           name="rep_test", max_batch=64,
                           max_delay_ms=50.0)
        # stall the worker so all submits merge into one batch
        gate = b.submit([["0:1.0"]])
        futs = [b.submit([[f"{i}:1.0"]]) for i in range(1, 8)]
        for f in [gate] + futs:
            f.result(timeout=10)
        time.sleep(0.1)  # done-callbacks commit the owned roots
        b.close()
    finally:
        batcher_mod.TRACER = orig
    committed = t.traces()
    assert committed, "sampling 0.5 over 8 requests must commit some"
    # every committed multi-request batch trace that carries the device
    # call carries it fully; and at least one committed trace has it
    assert any(any(s["name"] == "batch.predict" for s in tr["spans"])
               for tr in committed)
    for tr in committed:
        names = [s["name"] for s in tr["spans"]]
        # a committed request trace either owns the batch dispatch or
        # links to the trace that does — never silently stage-less
        if "batch.predict" not in names:
            events = [e for s in tr["spans"] for e in s["events"]]
            assert any(e["name"] == "batched" for e in events)


def test_batcher_owns_root_when_no_ambient_span():
    """submit() with no open span starts its own serving.request root and
    the future's done-callback ends it — direct batcher users get traces
    too."""
    from hivemall_tpu.serving import DynamicBatcher

    TRACER.clear()
    batcher = DynamicBatcher(lambda rows: [1.0] * len(rows),
                             name="own_root", max_delay_ms=1.0)
    try:
        batcher.submit([["1:1.0"]]).result(timeout=10)
        deadline = time.time() + 5
        while not TRACER.traces() and time.time() < deadline:
            time.sleep(0.005)  # done-callback commits just after result()
    finally:
        batcher.close()
    trace = next(t for t in TRACER.traces()
                 if t["root"] == "serving.request")
    names = {s["name"] for s in trace["spans"]}
    assert {"serving.request", "queue.wait", "batch.predict"} <= names


def test_engine_stage_spans_and_latency_exemplar():
    """engine.predict emits the bucket/pad/dispatch/block stages under its
    umbrella span, and its latency histogram observation carries the
    trace_id as an exemplar."""
    from hivemall_tpu.runtime.metrics import REGISTRY
    from hivemall_tpu.serving import ServingEngine

    model, rows = _make_model()
    engine = ServingEngine(model, name="trace_eng", max_batch=16,
                           max_width=16)
    engine.warmup()
    TRACER.clear()
    engine.predict(rows[:4])
    trace = next(t for t in TRACER.traces()
                 if t["root"] == "engine.predict")
    by_name = {s["name"]: s for s in trace["spans"]}
    assert {"engine.predict", "engine.bucket", "engine.pad",
            "engine.dispatch", "engine.block"} <= set(by_name)
    umbrella = by_name["engine.predict"]["span_id"]
    for stage in ("engine.bucket", "engine.pad"):
        assert by_name[stage]["parent_id"] == umbrella
    assert by_name["engine.bucket"]["args"]["b_pad"] == 8
    ex = REGISTRY.histogram("serving.trace_eng.predict_seconds").exemplars()
    assert any(e["trace_id"] == trace["trace_id"] for e in ex.values())


def test_recompile_instant_event_lands_inside_span():
    """A jit cache miss under recompile_guard inside an open span surfaces
    as a jit_recompile instant event in that trace — the recompile shows
    up inside the request/step that paid for it."""
    import jax

    from hivemall_tpu.runtime.metrics import recompile_guard

    fresh = jax.jit(lambda x: x * 3 + 1)
    t_local = TRACER
    t_local.clear()
    with t_local.span("request"):
        with recompile_guard("tracing_test_compile", fresh):
            fresh(np.float32(2.0))
    trace = next(t for t in t_local.traces() if t["root"] == "request")
    events = [e for s in trace["spans"] for e in s["events"]]
    assert any(e["name"] == "jit_recompile"
               and e["args"]["guard"] == "tracing_test_compile"
               and e["args"]["compiles"] >= 1 for e in events)


def test_trace_endpoint_smoke():
    """GET /trace?n= serves the ring as Chrome JSON on the metrics port
    (and the serving server inherits it)."""
    from hivemall_tpu.runtime.metrics_http import serve_metrics

    TRACER.clear()
    with TRACER.span("endpoint.root"):
        with TRACER.span("endpoint.child"):
            pass
    server = serve_metrics(port=0)
    try:
        port = server.server_address[1]
        doc = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace?n=5", timeout=10).read())
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"endpoint.root", "endpoint.child"} <= names
        # bad n falls back instead of erroring
        doc2 = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/trace?n=bogus", timeout=10).read())
        assert "traceEvents" in doc2
    finally:
        server.shutdown()


def test_http_predict_root_span_end_to_end():
    """POST /predict produces one trace whose stages cover the whole path:
    server root + parse, queue wait, batched dispatch, engine stages —
    the >= 4 distinct-stage acceptance shape."""
    from hivemall_tpu.serving import ModelRegistry
    from hivemall_tpu.serving.server import serve

    model, rows = _make_model(seed=3)
    registry = ModelRegistry(max_delay_ms=1.0,
                             engine_kwargs={"max_batch": 16,
                                            "max_width": 16})
    registry.deploy("m", model, version="1")
    server = serve(registry)
    try:
        port = server.server_address[1]
        TRACER.clear()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict",
            data=json.dumps({"model": "m",
                             "instances": rows[:3]}).encode(),
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert len(out["predictions"]) == 3
    finally:
        server.shutdown()
        registry.shutdown()
    trace = next(t for t in TRACER.traces()
                 if t["root"] == "server.predict")
    names = {s["name"] for s in trace["spans"]}
    assert len(names & {"server.predict", "queue.wait", "engine.pad",
                        "engine.dispatch", "engine.block"}) >= 4
    root = next(s for s in trace["spans"] if s["name"] == "server.predict")
    assert root["args"]["status"] == 200
    assert root["args"]["instances"] == 3


# -- training wiring ---------------------------------------------------------

def test_step_span_times_training_phases():
    """The per-step training timeline: step_span root, trainer dispatch as
    train.compiled_step, host block building as train.data_prep, the
    loop's wait as train.sync — all one trace per step."""
    import jax

    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel import MixConfig, MixTrainer, make_mesh

    tr = MixTrainer(AROW, {"r": 0.1}, 512, make_mesh(2), MixConfig())
    state = tr.init()
    rng = np.random.RandomState(0)
    idx = rng.randint(0, 512, (2, 8, 4)).astype(np.int32)
    val = np.ones((2, 8, 4), np.float32)
    lab = np.sign(rng.randn(2, 8)).astype(np.float32)
    TRACER.clear()
    for i in range(2):
        with step_span("mix_dp", step=i):
            blocks = tr.shard_blocks(idx, val, lab)
            state, loss = tr.step(state, *blocks)
            with TRACER.span("train.sync"):
                jax.block_until_ready(loss)
    steps = [t for t in TRACER.traces() if t["root"] == "train.step"]
    assert len(steps) == 2
    for want_step, trace in enumerate(steps):
        by_name = {s["name"]: s for s in trace["spans"]}
        assert {"train.step", "train.data_prep", "train.compiled_step",
                "train.sync"} <= set(by_name)
        root = by_name["train.step"]
        assert root["args"] == {"trainer": "mix_dp", "step": want_step}
        for child in ("train.data_prep", "train.compiled_step",
                      "train.sync"):
            assert by_name[child]["parent_id"] == root["span_id"]
        assert by_name["train.compiled_step"]["args"]["trainer"] == "mix_dp"


def test_sharded_trainer_step_is_spanned():
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.parallel import make_mesh
    from hivemall_tpu.parallel.sharded_train import ShardedTrainer

    tr = ShardedTrainer(AROW, {"r": 0.1}, 600, make_mesh(2))
    state = tr.init()
    idx = np.zeros((8, 4), np.int32)
    val = np.ones((8, 4), np.float32)
    lab = np.ones(8, np.float32)
    TRACER.clear()
    with step_span("sharded_1d", step=0):
        state, _ = tr.step(state, idx, val, lab)
    tr.final_state(state)  # train.sync, its own root outside the step
    roots = [t["root"] for t in TRACER.traces()]
    assert "train.step" in roots and "train.sync" in roots
    step_trace = next(t for t in TRACER.traces()
                      if t["root"] == "train.step")
    names = {s["name"] for s in step_trace["spans"]}
    assert "train.compiled_step" in names


# -- overhead ----------------------------------------------------------------

def test_tracer_overhead_under_5_percent():
    """Closed-loop throughput with full tracing (sampling 1.0, the
    serving span shape: root + 3 children per iteration) must stay within
    5% of tracing disabled. The workload is a ~2 ms spin — comparable to
    a real padded CPU dispatch and large enough that per-iteration span
    cost (a few microseconds) is far below the 5% bound; best-of
    interleaved trials absorbs scheduler noise."""
    def spin():  # deterministic CPU-bound work, no syscalls
        acc = 0
        for i in range(60000):
            acc += i * i
        return acc

    def run(tracer, iters=60):
        t0 = time.perf_counter()
        for _ in range(iters):
            with tracer.span("request"):
                with tracer.span("stage_a"):
                    spin()
                with tracer.span("stage_b"):
                    spin()
                with tracer.span("stage_c"):
                    spin()
        return iters / (time.perf_counter() - t0)

    on = Tracer(capacity=64, sample_rate=1.0, seed=0)
    off = Tracer(enabled=False)
    run(on, iters=10), run(off, iters=10)  # warm caches
    # PAIRED back-to-back trials, alternating order to cancel drift; the
    # verdict is the least-noisy pair's delta. This box's inter-trial
    # throughput swings far exceed 5% (shared cores), so unpaired
    # medians/bests flake — but a genuinely slow tracer (say 20%
    # overhead) shows >5% in EVERY pair, which still fails.
    deltas = []
    for trial in range(6):
        if trial % 2 == 0:
            r_on, r_off = run(on), run(off)
        else:
            r_off, r_on = run(off), run(on)
        deltas.append((r_off - r_on) / r_off)
    delta = min(deltas)
    assert delta < 0.05, (f"tracing overhead {delta:.1%} in the best "
                          f"pairing (all pairs: "
                          f"{[f'{d:.1%}' for d in deltas]})")


# -- W3C traceparent (client-supplied trace context) -------------------------

def test_parse_traceparent_valid_and_malformed():
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    sid = "00f067aa0ba902b7"
    assert Tracer.parse_traceparent(f"00-{tid}-{sid}-01") == (tid, sid, True)
    assert Tracer.parse_traceparent(f"00-{tid}-{sid}-00") == (tid, sid, False)
    # uppercase hex normalizes; surrounding whitespace is tolerated
    assert Tracer.parse_traceparent(f"  00-{tid.upper()}-{sid}-01 ") \
        == (tid, sid, True)
    # a version-00 parser accepts FUTURE versions with appended fields...
    assert Tracer.parse_traceparent(f"01-{tid}-{sid}-01-extra.data") \
        == (tid, sid, True)
    for bad in (None, "", "nonsense", f"00-{tid}-{sid}",  # missing field
                f"ff-{tid}-{sid}-01",                     # version 0xff
                f"00-{'0' * 32}-{sid}-01",                # all-zero trace
                f"00-{tid}-{'0' * 16}-01",                # all-zero span
                f"00-{tid[:-1]}-{sid}-01",                # short trace id
                f"00-{tid}-{sid}-01-extra",               # ...but 00 is
                f"00-{tid}-{sid}-zz"):                    # exactly four
        assert Tracer.parse_traceparent(bad) is None


def test_remote_parent_adopts_trace_and_echo_format():
    t = Tracer(sample_rate=0.0, seed=0)  # sampled only via the flag
    tid = "4bf92f3577b34da6a3ce929d0e0e4736"
    remote = Tracer.parse_traceparent(f"00-{tid}-00f067aa0ba902b7-01")
    with t.span("server.predict", remote=remote) as root:
        assert root.trace_id == tid           # client's trace id adopted
        assert root.parent_id == "00f067aa0ba902b7"
        assert root.sampled is True           # the flag is a vote
        echo = t.format_traceparent(root)
    ver, e_tid, e_sid, flags = echo.split("-")
    assert (ver, e_tid, flags) == ("00", tid, "01")
    assert len(e_sid) == 16 and int(e_sid, 16) > 0  # OUR span, W3C shaped
    assert [tr["trace_id"] for tr in t.traces()] == [tid]
    # remote applies only to roots: a nested span keeps the local parent
    with t.span("outer", remote=remote) as outer:
        with t.span("inner", remote=remote) as inner:
            assert inner.parent_id == outer.span_id
    # unsampled-flag remote with sampling off: timed but not committed
    t2 = Tracer(sample_rate=0.0, seed=0)
    with t2.span("r", remote=Tracer.parse_traceparent(
            f"00-{tid}-00f067aa0ba902b7-00")):
        pass
    assert t2.traces() == [] and t2.dropped == 1


def test_format_traceparent_internal_ids_and_nullspan():
    t = Tracer(sample_rate=1.0, seed=0)
    with t.span("r") as root:
        echo = t.format_traceparent(root)
    ver, e_tid, e_sid, flags = echo.split("-")
    assert (ver, flags) == ("00", "01")
    assert len(e_tid) == 32 and int(e_tid, 16) > 0
    assert len(e_sid) == 16
    assert t.format_traceparent(None) is None
    off = Tracer(enabled=False)
    with off.span("r") as nullspan:
        assert off.format_traceparent(nullspan) is None


# -- slow-trace retention (reserved ring fraction) ---------------------------

def test_slow_traces_survive_fast_flood():
    """PR 5 leftover: with slow_ms set, a fraction of the ring is reserved
    for slow-qualified traces — a flood of fast sampled traces must not
    FIFO-evict the slow outliers (exactly the traces overload debugging
    needs)."""
    t = Tracer(capacity=8, sample_rate=1.0, slow_ms=5.0, seed=0,
               slow_reserve=0.25)
    assert t.slow_reserved == 2
    with t.span("slow_one"):
        time.sleep(0.012)
    for i in range(30):
        with t.span(f"fast{i}"):
            pass
    roots = [tr["root"] for tr in t.traces()]
    assert "slow_one" in roots, "fast flood evicted the slow outlier"
    assert len(roots) <= 8  # total capacity unchanged: reserve is carved out
    # commit order is preserved across the merged rings
    assert roots[0] == "slow_one"
    assert roots[1:] == [f"fast{i}" for i in range(24, 30)]
    # slowest() sees the retained outlier
    assert t.slowest(1)[0]["root"] == "slow_one"
    t.clear()
    assert t.traces() == []


def test_slow_reserve_is_a_floor_not_a_partition():
    t = Tracer(capacity=8, sample_rate=1.0, slow_ms=5.0, seed=0,
               slow_reserve=0.25)
    # more slow traces than reserved slots: the overflow competes in the
    # general ring, so an all-slow workload retains up to full capacity
    for i in range(4):
        with t.span(f"slow{i}"):
            time.sleep(0.008)
    slow_roots = [tr["root"] for tr in t.traces() if tr["root"].startswith("slow")]
    assert slow_roots == ["slow0", "slow1", "slow2", "slow3"]
    # a fast flood can evict the overflowed slow traces but never the
    # newest `reserved` ones
    for i in range(20):
        with t.span(f"fast{i}"):
            pass
    kept = [tr["root"] for tr in t.traces() if tr["root"].startswith("slow")]
    assert kept == ["slow2", "slow3"]
    # no slow_ms -> no reserve: legacy FIFO semantics bit-for-bit
    plain = Tracer(capacity=3, seed=0)
    assert plain.slow_reserved == 0
    for i in range(5):
        with plain.span(f"r{i}"):
            pass
    assert [tr["root"] for tr in plain.traces()] == ["r2", "r3", "r4"]


# -- the training call's vocabulary ------------------------------------------
# One train_* UDTF call is one `train.call` trace and its model_rows() one
# `emit.model_rows` trace: names, parents, counts and counters as
# docs/observability.md ("The training call's timeline") lists them.

CALL_PARENTS = {
    "train.call": None,
    "train.stage": "train.call",
    "train.parse": "train.stage",
    "train.init_state": "train.call",
    "train.epoch": "train.call",
    "train.data_prep": "train.epoch",
    "train.compiled_step": "train.epoch",
    # the call's own fresh jit of its step, taken apart on its first dispatch
    "train.jit_trace": "train.compiled_step",
    "train.jit_lower": "train.compiled_step",
    "train.jit_compile": "train.compiled_step",
    "train.sync": "train.epoch",
}
# the mask's copy (and FM's w0) lies under the root, a chunk's under
# `emit.assemble`
EMIT_PARENTS = {("emit.model_rows", None), ("emit.d2h", "emit.model_rows"),
                ("emit.select", "emit.model_rows"),
                ("emit.gather", "emit.model_rows"),
                ("emit.assemble", "emit.model_rows"),
                ("emit.d2h", "emit.assemble")}
JIT_PHASES = ("train.jit_trace", "train.jit_lower", "train.jit_compile")
# spans under which a process compiles ONCE (the state's eager fills,
# emission's two module-level programs, the collapse's scalar reshape): their
# compile phases are there or not by what ran before in the process
ONCE_A_PROCESS = ("train.init_state", "emit.model_rows", "emit.gather",
                  "train.collapse")
CALL_COUNTERS = ("train.h2d_bytes", "train.parse_tokens", "train.jit_compiles",
                 "train.slot_bytes", "emit.d2h_bytes", "emit.rows")
# a linear entry's rule: its name on `train.call`, its slots, derived weights
LINEAR_RULES = {"train_arow": ("arow", [], False),
                "train_adagrad_rda": ("adagrad_rda",
                                      ["sum_grad", "sum_sqgrad"], True)}


def _rows(form, n=64, dims=256, seed=0):
    rng = np.random.RandomState(seed)
    idx = [rng.randint(0, dims, rng.randint(3, 8)) for _ in range(n)]
    val = [rng.rand(len(r)).astype(np.float32) for r in idx]
    labels = rng.choice([-1, 1], n)
    if form == "text":
        return [[f"{i}:{v:.4f}" for i, v in zip(r, x)]
                for r, x in zip(idx, val)], labels, idx
    return (idx, val), labels, idx


def _counters():
    from hivemall_tpu.runtime.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    return {k: snap.get(k, 0.0) for k in CALL_COUNTERS}


def _traced_call(entry, options, form, **kw):
    """(train.call trace, emit.model_rows trace, emitted rows, counter
    deltas, staged id rows) of one call and its model_rows()."""
    from hivemall_tpu.sql.registry import get_function

    rows, labels, idx = _rows(form, **kw)
    before = _counters()
    TRACER.clear()
    model = get_function(entry)(rows, labels, options)
    emitted = model.model_rows()
    after = _counters()
    call, emit = TRACER.traces()
    assert (call["root"], emit["root"]) == ("train.call", "emit.model_rows")
    return call, emit, emitted, \
        {k: after[k] - before[k] for k in CALL_COUNTERS}, idx


def _parents(trace):
    """(name, parent's name) of every span, but the compile phases of what a
    process compiles once."""
    names = {s["span_id"]: s["name"] for s in trace["spans"]}
    pairs = {(s["name"], names.get(s["parent_id"])) for s in trace["spans"]}
    return {(name, parent) for name, parent in pairs
            if not (name in JIT_PHASES and parent in ONCE_A_PROCESS)}


def _sum(trace, name, key):
    return sum(s["args"][key] for s in trace["spans"] if s["name"] == name)


@pytest.mark.parametrize("form", ["text", "arrays"])
@pytest.mark.parametrize("entry,options,epochs,feats_at", [
    ("train_arow", "-dims 256 -mini_batch 16", 1, 0),
    ("train_fm", "-c -factor 10 -dims 256 -mini_batch 16 -iters 2 -disable_cv",
     2, 1),
    # a slot-carrying rule with derived weights: `slots`, `derive_w`, the
    # state's bytes by table and the `train.slot_bytes` counter
    ("train_adagrad_rda", "-dims 256 -mini_batch 16", 1, 0),
])
def test_train_call_commits_the_vocabulary(entry, options, epochs, feats_at,
                                           form):
    call, emit, emitted, counted, idx = _traced_call(entry, options, form)
    want = dict(CALL_PARENTS)
    if form == "arrays":
        want.pop("train.parse")   # no parser on pre-hashed rows
    assert _parents(call) == set(want.items())
    assert _parents(emit) == EMIT_PARENTS
    by_name = {}
    for s in call["spans"] + emit["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    steps = epochs * 64 // 16
    for per_step in ("train.data_prep", "train.compiled_step"):
        assert len(by_name[per_step]) == steps
    assert [s["args"]["step"] for s in by_name["train.compiled_step"]] \
        == list(range(steps))
    assert len(by_name["train.epoch"]) == epochs
    assert _sum(call, "train.epoch", "steps") == steps
    # fit_linear waits once an epoch for every loss; train_fm every step
    fm = entry == "train_fm"
    assert len(by_name["train.sync"]) == (steps if fm else epochs)
    assert _sum(call, "train.sync", "fetches") == steps
    (root,) = by_name["train.call"]
    rule_name, slots, derived = ("fm", None, None) if fm \
        else LINEAR_RULES[entry]
    want_args = {
        "entry": rule_name, "dims": 256, "rows": 64,
        "mini_batch": 16, "epochs": epochs, "mode": "minibatch",
        "table_dtype": "float32",
        # the step's way with a block: a 256-entry table is small for the
        # linear step's shape rule; FM has one plan at every shape
        "apply": "batch_local" if fm else "dense",
        # which tables' runs the Pallas kernel writes: none off a TPU
        "write": "xla",
        # rows of 3 to 7 features: the 8-lane bucket, and nothing to cut
        "width": 8, "lanes": 8}
    if not fm:   # fit_linear says what the rule keeps beside its weights
        want_args.update(slots=slots, derive_w=derived)
    assert root["args"] == want_args
    nnz = sum(len(r) for r in idx)
    (stage,) = by_name["train.stage"]
    # ragged rows and text take the row walk: `layout: rect` is a 2-D pair's
    assert stage["args"] == {"form": form, "layout": "rows", "rows": 64,
                             "nnz": nnz}
    if form == "text":
        (parse,) = by_name["train.parse"]
        assert parse["args"]["tokens"] == nnz
    init_args = by_name["train.init_state"][0]["args"]
    assert init_args["state_bytes"] > 256 * 4
    if fm:
        assert "state_bytes_by_table" not in init_args
        assert counted["train.slot_bytes"] == 0
    else:
        # float32 tables of 256 entries, the int8 flags; AROW's covariances
        tables = ["weights"] + (["covars"] if entry == "train_arow" else []) \
            + slots
        assert init_args["state_bytes_by_table"] == dict(
            {t: 1024 for t in tables}, touched=256)
        assert counted["train.slot_bytes"] == 1024 * len(slots)
        # all of the state but its two scalars' few bytes
        assert 0 <= init_args["state_bytes"] - sum(
            init_args["state_bytes_by_table"].values()) <= 16
    # h2d_bytes is the nbytes of what each step was handed: [16, 8] int32
    # ids and float32 values, 16 labels, and FM's 16-row validation mask
    block = 16 * 8 * 4 * 2 + 16 * 4 + (16 * 4 if fm else 0)
    assert {s["args"]["h2d_bytes"] for s in by_name["train.data_prep"]} \
        == {block}
    assert _sum(call, "train.data_prep", "rows") == 64 * epochs
    # the counters are the args' sums, made at the same place
    assert counted["train.h2d_bytes"] == steps * block
    assert counted["train.parse_tokens"] == (nnz if form == "text" else 0)
    assert counted["train.jit_compiles"] == sum(
        s["args"]["compiled"] for s in by_name["train.compiled_step"])
    feats = emitted[feats_at]
    assert len(feats) and set(feats) <= {i for r in idx for i in r}
    (emit_root,) = by_name["emit.model_rows"]
    assert emit_root["args"]["rows_out"] == len(feats)
    assert by_name["emit.select"][0]["args"]["rows_out"] == len(feats)
    assert counted["emit.rows"] == len(feats)
    d2h = _sum(emit, "emit.d2h", "bytes")
    assert emit_root["args"]["d2h_bytes"] == d2h == counted["emit.d2h_bytes"]
    # the mask comes over, then the emitted entries' values a chunk at a
    # time (core/emission.py): never a whole table
    tables = {s["args"]["table"] for s in by_name["emit.d2h"]}
    # close() emits the weights, and the covariances where the rule has
    # them: a slot-carrying rule's sums stay on the device
    cov = entry == "train_arow"
    assert tables == ({"mask", "w", "v", "w0"} if fm
                      else {"mask", "weights", "covars"} if cov
                      else {"mask", "weights"})
    assert emit_root["args"]["select"] == "device"
    assert emit_root["args"]["chunks"] == 1
    assert emit_root["args"]["h2d_bytes"] == 256 * 4
    row = 4 + 16 * 4 if fm else 4 + 4 if cov else 4
    assert d2h == 256 // 8 + 256 * row + (4 if fm else 0)
    # the one chunk's ids go up under `emit.gather`; its values are fetched
    # and placed under `emit.assemble`, as many bytes as rows came out
    assert [s["args"] for s in by_name["emit.gather"]] \
        == [{"chunks": 1, "h2d_bytes": 256 * 4}]
    assert [s["args"] for s in by_name["emit.assemble"]] \
        == [{"bytes": len(feats) * row}]
    _phases_lie_in_their_dispatch(call)


def _ffm_rows(form, n=64, seed=0):
    rng = np.random.RandomState(seed)
    idx = rng.randint(0, 256, (n, 6))
    val = rng.rand(n, 6).astype(np.float32) + 0.5
    fld = np.broadcast_to(np.arange(6), idx.shape)
    labels = rng.choice([-1, 1], n)
    if form == "text":
        return [[f"{f}:{i}:{v!r}" for f, i, v in zip(fr, ir, vr.tolist())]
                for fr, ir, vr in zip(fld, idx, val)], labels, idx
    return (idx, val, fld), labels, idx


@pytest.mark.parametrize("form", ["text", "arrays"])
def test_train_ffm_commits_the_vocabulary(form):
    """`entry: ffm` speaks fit_linear's and train_fm's vocabulary, with the
    pair block's own args and counters and a second key space emitted."""
    from hivemall_tpu.runtime.metrics import REGISTRY
    from hivemall_tpu.sql.registry import get_function

    rows, labels, idx = _ffm_rows(form)
    names = CALL_COUNTERS + ("train.pair_lanes", "train.pair_lanes_padded")
    read = lambda: {k: REGISTRY.snapshot().get(k, 0.0) for k in names}
    before = read()
    TRACER.clear()
    model = get_function("train_ffm")(
        rows, labels, "-factor 4 -feature_hashing 18 -num_fields 6 -v_bits 12 "
        "-mini_batch 16 -iters 2 -disable_cv")
    w0, feats, w, v_keys, v = model.model_rows()
    counted = {k: v_ - before[k] for k, v_ in read().items()}
    call, emit = TRACER.traces()
    want = dict(CALL_PARENTS)
    if form == "arrays":
        want.pop("train.parse")
    assert _parents(call) == set(want.items())
    assert _parents(emit) == EMIT_PARENTS
    by_name = {}
    for s in call["spans"] + emit["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    steps = 2 * 64 // 16
    for per_step in ("train.data_prep", "train.compiled_step"):
        assert len(by_name[per_step]) == steps
    # the loss is fetched once an epoch, as fit_linear's is
    assert len(by_name["train.sync"]) == 2
    assert _sum(call, "train.sync", "fetches") == steps
    (root,) = by_name["train.call"]
    assert root["args"] == {
        "entry": "ffm", "dims": 1 << 18, "rows": 64, "mini_batch": 16,
        "epochs": 2, "mode": "minibatch", "table_dtype": "float32",
        "fields": 6, "pairs_per_row": 30, "v_dims": 1 << 12,
        "apply": "batch_local", "write": "xla", "row_tile": 16}
    (stage,) = by_name["train.stage"]
    # the three [64, 6] arrays stay arrays; the parser returns lists
    assert stage["args"] == {
        "form": form, "layout": "rect" if form == "arrays" else "rows",
        "rows": 64, "nnz": 64 * 6}
    if form == "text":
        assert by_name["train.parse"][0]["args"] == {"tokens": 64 * 6,
                                                     "native": False}
    # a step is handed [16, 8] ids, values and fields and 16 labels
    block = 16 * 8 * 4 * 3 + 16 * 4
    assert {s["args"]["h2d_bytes"] for s in by_name["train.data_prep"]} \
        == {block}
    assert counted["train.h2d_bytes"] == steps * block
    assert counted["train.parse_tokens"] == (64 * 6 if form == "text" else 0)
    assert counted["train.jit_compiles"] == 1
    # 30 real pair lanes a row; the step gathers the 8 x 8 block of its lanes
    assert counted["train.pair_lanes"] == 2 * 64 * 30
    assert counted["train.pair_lanes_padded"] == 2 * 64 * 64
    assert [(s["args"]["pair_lanes"], s["args"]["pair_lanes_padded"])
            for s in by_name["train.epoch"]] == [(64 * 30, 64 * 64)] * 2
    # emission: the linear rows, then the V entries, each selected on the
    # device from its own flags
    assert set(feats) == {i for r in idx for i in r}
    (emit_root,) = by_name["emit.model_rows"]
    assert emit_root["args"]["rows_out"] == len(feats) + len(v_keys)
    assert emit_root["args"]["v_rows_out"] == len(v_keys) > 0
    assert [s["args"]["rows_out"] for s in by_name["emit.select"]] \
        == [len(feats), len(v_keys)]
    assert counted["emit.rows"] == len(feats) + len(v_keys)
    d2h = _sum(emit, "emit.d2h", "bytes")
    assert emit_root["args"]["d2h_bytes"] == d2h == counted["emit.d2h_bytes"]
    assert [s["args"]["table"] for s in by_name["emit.d2h"]] \
        == ["mask", "w", "mask", "v", "w0"]
    assert emit_root["args"]["select"] == "device"
    # both masks, one gather chunk a key space (a chunk is the table's
    # length where that is under 2^19), and w0
    assert d2h == (1 << 18) // 8 + (1 << 12) // 8 \
        + (1 << 18) * 4 + (1 << 12) * 16 + 4
    # a key space at a time: its gather, then its values
    assert [s["args"] for s in by_name["emit.gather"]] == [
        {"chunks": 1, "h2d_bytes": (1 << 18) * 4},
        {"chunks": 1, "h2d_bytes": (1 << 12) * 4}]
    assert [s["args"] for s in by_name["emit.assemble"]] \
        == [{"bytes": len(feats) * 4}, {"bytes": len(v_keys) * 16}]
    _phases_lie_in_their_dispatch(call)


def test_ffm_step_carries_every_scope_and_nothing_as_long_as_a_table():
    import re

    import jax.numpy as jnp

    from hivemall_tpu.models.ffm import (FFMHyper, init_ffm_state,
                                         make_ffm_step)
    from hivemall_tpu.runtime import tracing

    hyper = FFMHyper(factors=4, num_features=512, num_fields=8, v_dims=4096)
    block = (jnp.zeros((16, 8), jnp.int32), jnp.ones((16, 8), jnp.float32),
             jnp.zeros((16, 8), jnp.int32), jnp.ones((16,), jnp.float32))
    lowered = make_ffm_step(hyper, "minibatch").lower(
        init_ffm_state(hyper), *block)
    text = lowered.as_text(debug_info=True)
    assert set(re.findall(r"hm\.[a-z_]+", text)) == set(tracing.FM_SCOPES)
    assert not re.search(r"hm\.", lowered.as_text())   # metadata only
    # the pair block: V, gg and w gathered once; z and n beside them for the
    # linear lanes; each table written once, in place
    assert (len(re.findall('"stablehlo.gather"', text)),
            len(re.findall('"stablehlo.scatter"', text))) == (6, 7)
    # no tensor of the V table's length but the tables themselves
    assert not re.search(r"tensor<4096x[0-9]+xf32>", text.replace(
        "tensor<4096x4xf32>", ""))


def test_root_self_time_is_what_the_children_leave():
    """Self time by the rule of benchmark/readers/_program_spans.py: a
    span's duration less the union of its children's intervals. The
    children of train.call and train.epoch lie inside them, one after the
    other, so self time is also duration less the children's sum."""
    from benchmark.readers import _program_spans as ps

    call, emit, _, _, _ = _traced_call(
        "train_arow", "-dims 256 -mini_batch 16", "text")
    for trace, roots in ((call, ("train.call", "train.epoch")),
                         (emit, ("emit.model_rows",))):
        spans = trace["spans"]
        for name in roots:
            (root,) = ps.named(spans, name)
            kids = [s for s in spans if s["parent_id"] == root["span_id"]]
            assert kids
            lo = root["start_us"]
            for k in sorted(kids, key=lambda s: s["start_us"]):
                assert lo <= k["start_us"]
                lo = k["start_us"] + k["dur_us"]
            assert lo <= root["start_us"] + root["dur_us"]
            left = root["dur_us"] - sum(k["dur_us"] for k in kids)
            assert ps.self_ms(spans, name) == pytest.approx(left / 1e3)
            assert 0 <= left < root["dur_us"]


def test_each_call_compiles_on_its_first_step_and_says_so():
    """make_train_step returns a fresh jax.jit a call, so a second call of
    the same shapes traces and lowers its step again: `compiled` on step 0
    alone, with the `jit_recompile` instant recompile_guard also emits."""
    for _ in range(2):
        call, _, _, counted, _ = _traced_call(
            "train_arow", "-dims 256 -mini_batch 16 -iters 2 -disable_cv",
            "arrays")
        steps = [s for s in call["spans"]
                 if s["name"] == "train.compiled_step"]
        assert [s["args"]["compiled"] for s in steps] == [True] + [False] * 7
        assert [[e["name"] for e in s["events"]] for s in steps] \
            == [["jit_recompile"]] + [[]] * 7
        assert steps[0]["events"][0]["args"] == {"guard": "train.call",
                                                 "compiles": 1}
        assert counted["train.jit_compiles"] == 1


# -- a fresh jit's compile, taken apart ----------------------------------------
# jax.monitoring's callbacks open and close `train.jit_trace`,
# `train.jit_lower` and `train.jit_compile` under the span that dispatched the
# fresh jit (runtime/tracing.py::_bind_compile_listeners).

DISPATCH_SPANS = ("train.compiled_step", "train.mix", "train.collapse")


def _phases_lie_in_their_dispatch(call):
    """Every dispatch of a fresh jit (`compiled` true) holds the three
    phases once each, in order, one after the other, inside it; a dispatch
    that compiled nothing holds none."""
    spans = call["spans"]
    fresh = 0
    for d in (s for s in spans if s["name"] in DISPATCH_SPANS):
        kids = sorted((s for s in spans if s["parent_id"] == d["span_id"]
                       and s["name"] in JIT_PHASES),
                      key=lambda s: s["start_us"])
        if not d["args"]["compiled"]:
            assert kids == [], d
            continue
        fresh += 1
        # but the collapse's scalars, reshaped by an eager op once a process
        own = [k for k in kids if "reshape" not in k["args"]["fn"]]
        assert [k["name"] for k in own] == list(JIT_PHASES), d
        edge = d["start_us"]
        for k in own:
            assert edge <= k["start_us"]
            edge = k["start_us"] + k["dur_us"]
        assert edge <= d["start_us"] + d["dur_us"]
        assert own[2]["args"]["cache"] in ("hit", "miss", "off")
    assert fresh >= 1
    return fresh


class _Callbacks:
    """Every jax.monitoring callback of its extent, counted."""

    def __enter__(self):
        from jax import monitoring

        self.count = 0
        self._regs = (
            (monitoring.register_scalar_listener,
             monitoring.unregister_scalar_listener),
            (monitoring.register_event_duration_secs_listener,
             monitoring.unregister_event_duration_listener),
            (monitoring.register_event_listener,
             monitoring.unregister_event_listener))
        for register, _ in self._regs:
            register(self._seen)
        return self

    def _seen(self, *args, **kw):
        self.count += 1

    def __exit__(self, *exc):
        for _, unregister in self._regs:
            unregister(self._seen)


def _fresh_jit():
    """A fresh closure over functions that are jitted themselves (`jnp.where`,
    `jnp.linalg.norm`, ...): their traces fire the trace event too."""
    import jax
    import jax.numpy as jnp

    def nested(x):
        y = jnp.where(x > 0, jnp.sin(x), jnp.cos(x))
        return jnp.sum(y) + jnp.linalg.norm(x) + jnp.max(jnp.cumsum(x))

    return jax.jit(nested), jnp.ones((64,), jnp.float32)


def _phase_spans(trace):
    return sorted((s for s in trace["spans"] if s["name"] in JIT_PHASES),
                  key=lambda s: s["start_us"])


def test_a_fresh_jit_under_a_span_yields_the_three_phases_once():
    fn, x = _fresh_jit()
    t = Tracer(seed=0)
    with _Callbacks() as first, t.span("outer"):
        with t.span("dispatch") as d:
            fn(x)
    # the trace event alone fires for every inner jitted function
    assert first.count > 3 * 2
    with _Callbacks() as again, t.span("outer"):
        with t.span("dispatch"):
            for _ in range(100):
                fn(x)
    assert again.count == 0          # a warm jit runs no listener
    cold, warm = t.traces()
    assert _phase_spans(warm) == []
    phases = _phase_spans(cold)
    assert [p["name"] for p in phases] == list(JIT_PHASES)
    (parent,) = [s for s in cold["spans"] if s["name"] == "dispatch"]
    assert {p["parent_id"] for p in phases} == {parent["span_id"]}
    assert [p["args"]["fn"] for p in phases] \
        == ["nested", "jit(nested)", "jit(nested)"]
    edge = parent["start_us"]
    for p in phases:                 # one after the other, inside the parent
        assert edge <= p["start_us"] and p["dur_us"] > 0
        edge = p["start_us"] + p["dur_us"]
    assert edge <= parent["start_us"] + parent["dur_us"]
    assert sum(p["dur_us"] for p in phases) <= parent["dur_us"]
    assert d.span_id == parent["span_id"]


@pytest.mark.parametrize("how", ["no_span", "disabled", "unsampled_null"])
def test_a_compile_outside_a_recording_span_yields_nothing(how):
    from hivemall_tpu.runtime import tracing

    fn, x = _fresh_jit()
    t = Tracer(seed=0, enabled=how != "disabled")
    before = len(TRACER.traces())
    if how == "no_span":
        fn(x)
    elif how == "disabled":
        with t.span("dispatch"):
            fn(x)
    else:
        # a span another tracer's disabled state handed out: not recording
        with t.span("outer"):
            token = tracing._current.set(tracing.NULL_SPAN)
            try:
                fn(x)
            finally:
                tracing._current.reset(token)
    assert tracing._PHASE.span is None
    assert all(_phase_spans(tr) == [] for tr in t.traces())
    assert len(TRACER.traces()) == before


@pytest.mark.parametrize("case", ["nested", "foreign_inside", "end_alone",
                                  "unknown_event"])
def test_phase_callbacks_by_hand(case):
    """The listeners on hand-fed events: nested events of the open phase
    make one span, another phase's events inside it make none (an eager op
    compiling inside a trace), an end with no start is ignored."""
    from hivemall_tpu.runtime import tracing

    tr, lo, be = tracing._PHASE_SPANS
    t = Tracer(seed=0)
    with t.span("dispatch"):
        if case == "nested":
            tracing._phase_started(tr, 0.0, fun_name="outer")
            tracing._phase_started(tr, 0.0, fun_name="inner")
            tracing._phase_ended(tr, 0.1, fun_name="inner")
            assert tracing._PHASE.span is not None
            tracing._phase_ended(tr, 0.2, fun_name="outer")
            want = [("train.jit_trace", "outer")]
        elif case == "foreign_inside":
            tracing._phase_started(tr, 0.0, fun_name="outer")
            for event in (tr, lo, be):
                tracing._phase_started(event, 0.0, fun_name="eager")
                tracing._cache_event("/jax/compilation_cache/cache_hits")
                tracing._phase_ended(event, 0.1, fun_name="eager")
            tracing._phase_ended(tr, 0.2, fun_name="outer")
            want = [("train.jit_trace", "outer")]
        elif case == "end_alone":
            tracing._phase_ended(be, 0.1, fun_name="late")
            tracing._phase_ended(tracing._CACHE_RETRIEVAL, 0.1)
            tracing._cache_event("/jax/compilation_cache/cache_hits")
            want = []
        else:
            tracing._phase_started("/jax/other/event", 1.0)
            tracing._phase_ended("/jax/other/event", 1.0)
            tracing._cache_event("/jax/other/event")
            want = []
        assert tracing._PHASE.span is None
    (trace,) = t.traces()
    assert [(s["name"], s["args"]["fn"]) for s in _phase_spans(trace)] == want
    assert all("cache" not in s["args"] for s in _phase_spans(trace))


def _cache_counters():
    from hivemall_tpu.runtime.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    return (snap.get("train.compile_cache_hits", 0.0),
            snap.get("train.compile_cache_misses", 0.0))


def test_compile_span_says_what_the_persistent_cache_did(tmp_path):
    """`cache` reads `off` with no directory, `miss` on a directory's first
    compile of a module and `hit` on its next fresh jit; the two counters
    follow; a hit carries the read's time."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    def compile_once():
        fn, x = _fresh_jit()
        t = Tracer(seed=0)
        with t.span("dispatch"):
            fn(x)
        (trace,) = t.traces()
        (span,) = [s for s in trace["spans"]
                   if s["name"] == "train.jit_compile"]
        return span["args"]

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    start = _cache_counters()
    try:
        jax.config.update(keys[0], None)
        cc.reset_cache()
        off = compile_once()
        assert off["cache"] == "off" and "retrieval_ms" not in off
        assert _cache_counters() == start
        jax.config.update(keys[0], str(tmp_path))
        jax.config.update(keys[1], 0.0)
        jax.config.update(keys[2], 0)
        cc.reset_cache()
        miss = compile_once()
        assert miss["cache"] == "miss" and "retrieval_ms" not in miss
        assert _cache_counters() == (start[0], start[1] + 1)
        hit = compile_once()
        assert hit["cache"] == "hit" and hit["retrieval_ms"] > 0
        assert _cache_counters() == (start[0] + 1, start[1] + 1)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_disabled_tracer_registers_no_listener():
    """`HIVEMALL_TPU_TRACE=0`: a whole `train_*` call and its emission bind
    nothing to jax.monitoring; the default binds the three, once."""
    import os
    import subprocess
    import sys

    code = (
        "import numpy as np\n"
        "from jax._src import monitoring as m\n"
        "from hivemall_tpu.models.classifier import train_arow\n"
        "count = lambda: (len(m.get_scalar_listeners()),"
        " len(m.get_event_duration_listeners()), len(m.get_event_listeners()))\n"
        "before = count()\n"
        "for _ in range(2):\n"
        "    train_arow([['1:1.0', '2:0.5']] * 8, np.ones(8, int),"
        " '-dims 16').model_rows()\n"
        "print([b - a for a, b in zip(before, count())])\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for switch, want in (("0", "[0, 0, 0]"), ("1", "[1, 1, 1]")):
        env = dict(os.environ, HIVEMALL_TPU_TRACE=switch, JAX_PLATFORMS="cpu",
                   PYTHONPATH=root)
        out = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.strip().splitlines()[-1] == want


@pytest.mark.parametrize("entry,options", [
    ("train_arow", "-dims 256 -mini_batch 16 -iters 2 -disable_cv"),
    ("train_adagrad_rda", "-dims 256 -mini_batch 16"),
    ("train_fm", "-c -factor 4 -dims 256 -mini_batch 16 -iters 2 "
                 "-disable_cv"),
    ("train_ffm", "-factor 4 -feature_hashing 18 -num_fields 6 -v_bits 12 "
                  "-mini_batch 16 -iters 2 -disable_cv -eta0_V 0.1"),
    ("train_arow", "-dims 256 -mini_batch 16 -mix h -mix_threshold 2"),
])
def test_every_fresh_dispatch_of_an_entry_has_the_three_phases(
        entry, options, monkeypatch):
    import jax

    from hivemall_tpu.parallel import mix as pmix
    from hivemall_tpu.sql.registry import get_function

    mixed = "-mix " in options
    if mixed:
        monkeypatch.setattr(pmix, "mix_devices",
                            lambda: jax.local_devices()[:2])
    rows, labels, _ = _ffm_rows("arrays") if entry == "train_ffm" \
        else _rows("arrays")
    TRACER.clear()
    with _Callbacks() as seen:
        get_function(entry)(rows, labels, options)
    assert seen.count > 0
    (call,) = [t for t in TRACER.traces() if t["root"] == "train.call"]
    # the step's jit; with `-mix` the round's and the collapse's too
    assert _phases_lie_in_their_dispatch(call) == (3 if mixed else 1)
    names = {s["span_id"]: s["name"] for s in call["spans"]}
    under = {(s["name"], names[s["parent_id"]]) for s in call["spans"]
             if s["name"] in ("train.build", "train.collapse")}
    assert under == ({("train.build", "train.call"),
                      ("train.collapse", "train.call")} if mixed else set())


# gather and scatter ops in each step's lowered text: the linear steps' at
# the commit before any scope (61d9a95), the FM step's at the commit that
# made it block-local (three in-place writes). A scope is metadata and adds
# no op
PARENT_OP_COUNTS = {"arow_minibatch": (2, 6), "arow_scan": (4, 6),
                    "fm_minibatch": (4, 6)}


@pytest.mark.parametrize("family", sorted(PARENT_OP_COUNTS))
def test_steps_carry_every_scope_and_no_new_op(family):
    import re

    import jax.numpy as jnp

    from hivemall_tpu.core.engine import make_train_step
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step
    from hivemall_tpu.runtime import tracing

    block = (jnp.zeros((16, 8), jnp.int32), jnp.ones((16, 8), jnp.float32),
             jnp.ones((16,), jnp.float32))
    if family == "fm_minibatch":
        hyper = FMHyper(factors=10, classification=True)
        lowered = make_fm_step(hyper, "minibatch").lower(
            init_fm_state(512, hyper), *block, jnp.zeros((16,), jnp.float32))
        want = set(tracing.FM_SCOPES)
    else:
        mode = family.split("_")[1]
        lowered = make_train_step(AROW, {"r": 0.1}, mode=mode).lower(
            init_linear_state(512, use_covariance=True), *block)
        want = set(tracing.LINEAR_SCOPES)
        if mode == "scan":   # per-row: nothing is stacked, nothing reduced
            want -= {tracing.SCOPE_PACK_TABLES, tracing.SCOPE_REDUCE}
    text = lowered.as_text(debug_info=True)
    assert set(re.findall(r"hm\.[a-z_]+", text)) == want
    assert not re.search(r"hm\.", lowered.as_text())   # metadata only
    assert (len(re.findall("stablehlo.gather", text)),
            len(re.findall("stablehlo.scatter", text))) \
        == PARENT_OP_COUNTS[family]
