"""Observability: counters, stopwatch, throughput sampling, profiler hooks.

Mirrors the reference's observability surface (SURVEY.md §5):
- StopWatch elapsed-time logging (ref: utils/datetime/StopWatch.java, used in
  model load LearnerBaseUDTF.java:217-234)
- Hadoop Reporter/Counters for progress + iteration counts
  (ref: UDTFWithOptions.java:59-88, FM iteration counter
  FactorizationMachineUDTF.java:529-543)
- the MIX server's ThroughputCounter msgs/sec sampling + JMX MBean registry
  (ref: mixserv/.../metrics/ThroughputCounter.java:34, MetricsRegistry.java)

Stage timing lives in runtime/tracing.py, whose spans are also the JAX
profiler's host marks; `recompile_guard` here is the jit-cache witness.
"""

from __future__ import annotations

import logging
import re
import threading
import time
from collections import defaultdict
from typing import Dict, Optional


class StopWatch:
    def __init__(self, label: str = "") -> None:
        self.label = label
        self._start = time.perf_counter()

    def restart(self) -> None:
        self._start = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self._start

    def __str__(self) -> str:
        return f"{self.label} {self.elapsed() * 1000:.1f} ms"


class Counter:
    """A named monotonic counter (Hadoop Counter analog)."""

    def __init__(self, group: str, name: str) -> None:
        self.group = group
        self.name = name
        self.value = 0
        self._lock = threading.Lock()

    def increment(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class ThroughputCounter:
    """Events/sec sampled over a sliding window (ThroughputCounter analog)."""

    def __init__(self, window_sec: float = 5.0) -> None:
        self.window = window_sec
        self._events: list = []
        self._lock = threading.Lock()
        self.last_reads_per_sec = 0.0

    def record(self, n: int = 1) -> None:
        now = time.monotonic()
        with self._lock:
            self._events.append((now, n))
            cutoff = now - self.window
            while self._events and self._events[0][0] < cutoff:
                self._events.pop(0)
            span = max(1e-9, now - (self._events[0][0] if self._events else now))
            self.last_reads_per_sec = sum(c for _, c in self._events) / max(span, 1e-9)


class Histogram:
    """Fixed-bucket cumulative histogram (the Prometheus histogram shape).

    `buckets` are upper bounds in ascending order; an implicit +Inf bucket
    catches the tail. observe() is lock-guarded and O(len(buckets)) — cheap
    enough for per-request latency recording on the serving path
    (serving/engine.py, serving/batcher.py), and usable next to any
    existing meter (e.g. per-block step walltime).
    """

    # Latency-shaped default: 500us .. 10s, roughly log-spaced (seconds).
    DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                       0.1, 0.25, 0.5, 1.0, 2.5, 10.0)

    def __init__(self, name: str, buckets=DEFAULT_BUCKETS) -> None:
        self.name = name
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # [+Inf] is last
        self.sum = 0.0
        self.count = 0
        # bucket index -> (value, trace_id, unix_ts): the last sampled
        # observation that landed there (OpenMetrics exemplar shape) — a
        # bad p99 bucket links straight to a trace in runtime/tracing.py
        self._exemplars: Dict[int, tuple] = {}
        self._lock = threading.Lock()

    def observe(self, value: float, trace_id: Optional[str] = None) -> None:
        v = float(value)
        i = 0
        for i, ub in enumerate(self.buckets):
            if v <= ub:
                break
        else:
            i = len(self.buckets)
        now = time.time() if trace_id is not None else 0.0
        with self._lock:
            self._counts[i] += 1
            self.sum += v
            self.count += 1
            if trace_id is not None:
                self._exemplars[i] = (v, trace_id, now)

    def exemplars(self) -> dict:
        """{bucket_upper_bound: {"value", "trace_id", "unix"}} for buckets
        that have one (the +Inf overflow keys as inf)."""
        with self._lock:
            items = dict(self._exemplars)
        bounds = self.buckets + (float("inf"),)
        return {bounds[i]: {"value": v, "trace_id": tid, "unix": ts}
                for i, (v, tid, ts) in items.items()}

    def snapshot(self) -> dict:
        """{"buckets": [(upper_bound, cumulative_count)...], "sum", "count"}
        with the trailing +Inf bucket included (cumulative == count)."""
        with self._lock:
            counts = list(self._counts)
            total, s = self.count, self.sum
        cum, out = 0, []
        for ub, c in zip(self.buckets, counts):
            cum += c
            out.append((ub, cum))
        out.append((float("inf"), total))
        return {"buckets": out, "sum": s, "count": total}

    def quantile(self, q: float) -> float:
        """Quantile estimate with LINEAR INTERPOLATION inside the holding
        bucket (the Prometheus histogram_quantile formula): the q-th rank
        is located in its cumulative bucket, then placed proportionally
        between the bucket's lower and upper bound — a p50 of values
        clustered near a bucket's floor no longer over-reports as the
        bucket's ceiling. For dashboards/logs; benches that need exact
        percentiles keep raw samples. Ranks landing in the +Inf overflow
        clamp to the largest finite bound (the histogram_quantile
        convention — and inf would break strict JSON)."""
        snap = self.snapshot()
        if not snap["count"] or not self.buckets:
            return 0.0
        rank = q * snap["count"]
        prev_cum, lo = 0, 0.0
        for ub, cum in snap["buckets"]:
            if cum >= rank:
                if ub == float("inf"):
                    return self.buckets[-1]
                in_bucket = cum - prev_cum
                if in_bucket <= 0:
                    return ub
                return lo + (ub - lo) * (rank - prev_cum) / in_bucket
            prev_cum, lo = cum, ub
        return self.buckets[-1]


class MetricsRegistry:
    """Process-wide registry (the JMX MBean registry analog); exportable as a
    plain dict for scraping."""

    def __init__(self) -> None:
        self.counters: Dict[str, Counter] = {}
        self.throughput: Dict[str, ThroughputCounter] = {}
        self.gauges: Dict[str, float] = {}
        self.histograms: Dict[str, Histogram] = {}
        # registration and snapshot share one lock: the HTTP scrape thread
        # (runtime/metrics_http.py) iterates while the training thread may
        # be registering new keys
        self._lock = threading.Lock()

    def counter(self, group: str, name: str) -> Counter:
        key = f"{group}.{name}"
        with self._lock:
            if key not in self.counters:
                self.counters[key] = Counter(group, name)
            return self.counters[key]

    def meter(self, name: str) -> ThroughputCounter:
        with self._lock:
            if name not in self.throughput:
                self.throughput[name] = ThroughputCounter()
            return self.throughput[name]

    def histogram(self, name: str, buckets=None) -> Histogram:
        with self._lock:
            if name not in self.histograms:
                self.histograms[name] = Histogram(
                    name, buckets if buckets is not None
                    else Histogram.DEFAULT_BUCKETS)
            return self.histograms[name]

    def set_gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = value

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            out: Dict[str, float] = dict(self.gauges)
            for key, c in self.counters.items():
                out[key] = float(c.value)
            for name, t in self.throughput.items():
                out[f"{name}.per_sec"] = t.last_reads_per_sec
            hists = list(self.histograms.items())
        # histogram locks are taken outside the registry lock (fixed order:
        # registry -> histogram; nothing takes them in reverse)
        for name, h in hists:
            snap = h.snapshot()
            out[f"{name}.count"] = float(snap["count"])
            out[f"{name}.sum"] = float(snap["sum"])
        return out

    def typed_snapshot(self) -> dict:
        """Snapshot keeping metric kinds apart — the Prometheus exposition
        (runtime/metrics_http.py) needs # TYPE per family."""
        with self._lock:
            counters = {k: float(c.value) for k, c in self.counters.items()}
            gauges = dict(self.gauges)
            meters = {f"{n}.per_sec": t.last_reads_per_sec
                      for n, t in self.throughput.items()}
            hists = list(self.histograms.items())
        return {
            "counters": counters,
            "gauges": gauges,
            "meters": meters,
            "histograms": {n: {**h.snapshot(), "exemplars": h.exemplars()}
                           for n, h in hists},
        }


REGISTRY = MetricsRegistry()


def _jit_cache_size(fn) -> int:
    """Compile-cache entry count of a jax.jit product (0 when unknown)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return 0
    try:
        return int(probe())
    except Exception:  # graftcheck: disable=G028 (jax-internal probe: 0 is the documented unknown)
        return 0


# jax logs every XLA compile at DEBUG as "Compiling jit(<fn>) with global
# shapes and types (ShapedArray(...),). Argument mapping: ...": the module
# name is the function name wrapped in the API that compiled it (jit, pmap).
# The capture anchors on the sentence structure, NOT a bracket match —
# shapes like float32[4] contain `]`, so a lazy `\[.*?\]` truncates
# mid-list.
_COMPILE_LOG_RE = re.compile(
    r"Compiling \w+\((\S+)\) with global shapes and types (.*?)\. "
    r"Argument mapping")

# the module that owns the "Compiling ..." log line; if a jax upgrade moves
# or rewords it, attribution goes empty (counters are unaffected) and
# tests/test_graftcheck.py's retrace-attribution tests fail — they are the
# alarm for this parse
_COMPILE_LOGGER = "jax._src.interpreters.pxla"

# fn name -> shape signature of its LAST compile, process-wide: lets a later
# guard label a recompile as a shape delta vs a fresh-identity churn
_LAST_COMPILED_SHAPES: Dict[str, str] = {}


class _CompileLogCapture(logging.Handler):
    """DEBUG tap on the ``jax`` logger: names the function being compiled
    and the abstract shapes that missed the cache — attribution a
    cache-size probe cannot give. A fresh ``jax.jit`` wrapper built per
    call compiles every iteration while every *named* probe stays flat
    (G032's counter blind spot); the compile log still names the wrapped
    function each time."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.events: list = []

    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_LOG_RE.search(record.getMessage())
        except Exception:  # graftcheck: disable=G028,G029 (a malformed log record must never break the guarded step; nothing to degrade to — the event is simply not attributed)
            return
        if m:
            self.events.append((m.group(1), m.group(2)))


class recompile_guard:
    """Count jit cache misses per named step function — the runtime witness
    for graftcheck's G001 recompile-hazard rule (hivemall_tpu/analysis).

    Wrap the steady-state section of a training loop::

        step = make_train_step(rule, hyper)
        with recompile_guard("arow_minibatch", step) as g:
            for block in blocks:
                state, loss = step(state, *block)
        g.compiles  # cache misses INSIDE the block; 0 after warmup

    Every exit increments the process-wide counter
    ``graftcheck.recompiles.<name>`` and sets the gauge
    ``<name>.jit_cache_entries`` to the functions' total cache size, so the
    /metrics endpoint (runtime/metrics_http.py) exposes

        hivemall_tpu_graftcheck_recompiles_<name>
        hivemall_tpu_<name>_jit_cache_entries

    and a static G001 finding can be confirmed on hardware: a step function
    recompiling per invocation shows a recompile counter growing linearly
    with steps (the recompilation-count production metric of the ads-infra
    paper, PAPERS.md). ``expect_stable=True`` raises on any miss — used by
    tests to pin the steady state.

    Every guard also taps the jax compile log (``_CompileLogCapture``) and
    records one attribution per compile in ``guard.attributions``:
    ``{"fn": <jitted fn name>, "shapes": <abstract arg shapes>, "prev":
    <that fn's previous shapes or None>, "delta": <bool>}``. This closes
    the counter's blind spot — a fresh wrapper identity (G032) compiles
    per call while every named probe stays flat, but the log still names
    the function — and lets the static finding and the live counter point
    at the same line. Each attribution is also emitted as a
    ``jit_retrace_attrib`` trace instant next to ``jit_recompile``.
    """

    def __init__(self, name: str, *jitted_fns, registry: "MetricsRegistry" = None,
                 expect_stable: bool = False) -> None:
        self.name = name
        self.fns = jitted_fns
        self.registry = registry if registry is not None else REGISTRY
        self.expect_stable = expect_stable
        self.compiles = 0
        self.attributions: list = []
        self._start: list = []
        self._log_tap: Optional[_CompileLogCapture] = None
        self._prior_level = logging.NOTSET

    def __enter__(self) -> "recompile_guard":
        if self.expect_stable and self.fns and not any(
                getattr(f, "_cache_size", None) is not None
                for f in self.fns):
            # a guard that cannot observe the cache must not certify
            # stability — fail fast instead of silently reporting 0 misses
            raise RuntimeError(
                f"recompile_guard({self.name!r}, expect_stable=True): none "
                f"of the guarded functions expose a jit cache-size probe "
                f"(_cache_size) — pass jax.jit products")
        self._start = [_jit_cache_size(f) for f in self.fns]
        self._log_tap = _CompileLogCapture()
        logger = logging.getLogger(_COMPILE_LOGGER)
        self._prior_level = logger.level
        self._prior_propagate = logger.propagate
        logger.addHandler(self._log_tap)
        if logger.getEffectiveLevel() > logging.DEBUG:
            # debug logging is off: lower just the compile logger and stop
            # propagation so the capture stays silent on the console; when
            # the user already runs jax at DEBUG, touch nothing
            logger.setLevel(logging.DEBUG)
            logger.propagate = False
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        logger = logging.getLogger(_COMPILE_LOGGER)
        logger.removeHandler(self._log_tap)
        logger.setLevel(self._prior_level)
        logger.propagate = self._prior_propagate
        for fn_name, shapes in self._log_tap.events:
            prev = _LAST_COMPILED_SHAPES.get(fn_name)
            _LAST_COMPILED_SHAPES[fn_name] = shapes
            self.attributions.append({
                "fn": fn_name, "shapes": shapes, "prev": prev,
                "delta": prev is not None and prev != shapes})
        sizes = [_jit_cache_size(f) for f in self.fns]
        self.compiles = sum(max(0, now - was)
                            for was, now in zip(self._start, sizes))
        self.registry.counter("graftcheck",
                              f"recompiles.{self.name}").increment(
            self.compiles)
        if self.compiles or self.attributions:
            # a cache miss inside an active trace span shows up INSIDE the
            # request/step that paid for it (late import: tracing is a
            # leaf module; this path only runs on the cold compile)
            from .tracing import TRACER

            if self.compiles:
                TRACER.instant("jit_recompile", {"guard": self.name,
                                                 "compiles": self.compiles})
            for a in self.attributions:
                TRACER.instant("jit_retrace_attrib",
                               {"guard": self.name, "fn": a["fn"],
                                "shapes": a["shapes"],
                                "prev": a["prev"] or "",
                                "shape_delta": a["delta"]})
        self.registry.set_gauge(f"{self.name}.jit_cache_entries",
                                float(sum(sizes)))
        if exc_type is None and self.expect_stable and self.compiles:
            attrib = "; ".join(
                f"{a['fn']} {a['shapes']}"
                + (" [shape delta]" if a["delta"] else "")
                for a in self.attributions) \
                or "no compile-log attribution captured"
            raise RuntimeError(
                f"recompile_guard({self.name!r}): {self.compiles} jit cache "
                f"miss(es) in a section expected steady — a G001-class "
                f"hazard is retracing the step function ({attrib})")
