"""Multi-model registry with atomic hot-swap + the /predict HTTP endpoint.

The reference swaps models by overwriting a Hive table between batch scoring
runs; an online server must swap under live load. The registry keeps one
``(engine, batcher)`` pair per model name; ``deploy()`` builds and WARMS the
new version off to the side, then publishes it with one dict assignment
(atomic under the GIL — readers see either the old or the new entry, never
a partial one) and drains the old batcher so every request admitted before
the swap still completes: an in-flight v1 -> v2 swap fails zero requests
(tests/test_serving_server.py pins this).

HTTP surface (layered on runtime/metrics_http.py — same process, one port):

- ``POST /predict``  body ``{"model": name?, "instances": [...]}`` ->
  ``{"model", "version", "predictions": [...]}``. Overload contract
  (docs/serving.md "Overload behavior"): requests may carry an
  ``x-priority`` header (high/normal/low, or body key ``priority``) and
  an ``x-deadline-ms`` budget (or body key ``deadline_ms``); a request
  that expires in the queue gets **504** (``reason: deadline``), an
  over-quota or shed request gets **503 + Retry-After** priced from the
  live drain-rate estimate (``reason: quota`` / ``shed``); 404 unknown
  model, 400 bad payload. A client ``traceparent`` header (W3C) is
  adopted as the request trace's root parent and echoed back on every
  response; malformed headers fall back to a fresh trace;
- ``POST /topk``     body ``{"model": name?, "queries": [...], "k"?,
  "probe"?}`` -> ``{"model", "version", "k", "results": [{"items",
  "scores"}, ...]}``. The top-K retrieval surface (serving/retrieval.py)
  — deploy() must have been given ``retrieval=`` options for the model
  (400 otherwise). Same priority/deadline/traceparent contract and error
  mapping as /predict, through the model's SEPARATE retrieval batcher;
- ``GET /models``    registry listing (name, version, family, admission
  and placement state, counters);
- ``GET /healthz``   overload-aware: reports ``degraded`` (still 200 —
  alive, shedding predictably) when any model's queue passes the depth
  threshold OR any registered SLO is paging on its burn rate
  (runtime/slo.py — the ``slo`` block carries the detail), BEFORE the
  process ever looks dead;
- ``GET /slo`` / ``GET /debug/bundle`` — inherited from metrics_http:
  per-objective multi-window burn rates + alert states, and the
  flight-recorder snapshot (models, metrics + time-series history,
  traces, recompile attributions) in one JSON document
  (docs/observability.md "SLOs & burn rates", "Flight recorder");
- ``GET /metrics`` / ``GET /trace?n=`` — inherited from metrics_http:
  the serving latency/occupancy/queue histograms, per-priority
  shed/expiry/quota counters and live controller state (with trace
  exemplars under ``?exemplars=1``), and the last n request traces as
  Chrome/Perfetto JSON (docs/observability.md).
"""

from __future__ import annotations

import json
import math
import threading
import time
from http.server import ThreadingHTTPServer
from typing import Dict, Optional

import numpy as np

from ..runtime import metrics_http
from ..runtime.compile_cache import enable_compile_cache
from ..runtime.metrics import REGISTRY
from ..runtime.tracing import TRACER
from .admission import (PRIORITY_NAMES, DeadlineExpired, priority_class,
                        priority_name)
from .batcher import BatcherClosed, DynamicBatcher, QueueFull
from .engine import ServingEngine


class ModelEntry:
    """One deployed model version: engine + its batching front."""

    def __init__(self, name: str, version: str, engine: ServingEngine,
                 batcher: DynamicBatcher,
                 lineage: Optional[list] = None, cache=None,
                 retrieval_engine=None,
                 retrieval_batcher: Optional[DynamicBatcher] = None) -> None:
        self.name = name
        self.version = version
        self.engine = engine
        self.batcher = batcher
        # the hot-row score cache this entry's batcher fronts with —
        # owned by the REGISTRY and shared across this name's versions
        # (the version lives in the key; serving/cache.py). None = off.
        self.cache = cache
        # the top-K retrieval surface (serving/retrieval.py): present only
        # when deploy() was given ``retrieval=`` options and the family is
        # MF/FM. Its batcher is separate from the pointwise one — a /topk
        # flood cannot starve /predict of dispatch slots, and vice versa.
        self.retrieval_engine = retrieval_engine
        self.retrieval_batcher = retrieval_batcher
        self.deployed_unix = time.time()
        # version lineage: the publisher's recent gate decisions (publish /
        # refusal / rollback records — hivemall_tpu/pipeline) surfaced on
        # /models, so "why is v7 serving and where did v6 go" is answerable
        # from the serving endpoint alone. Immutable after deploy.
        self.lineage = list(lineage or [])

    def describe(self) -> dict:
        return {
            "name": self.name,
            "version": self.version,
            "family": self.engine.family,
            "deployed_unix": self.deployed_unix,
            "max_batch": self.engine.max_batch,
            "max_width": self.engine.max_width,
            # the precision surface: what dtype the tables serve at and the
            # resident bytes a request's gathers read (bf16/int8 artifacts
            # shrink this 2-4x; also gauges serving.<name>.table_bytes /
            # .weights_bits on /metrics)
            "weights_dtype": self.engine.weights_dtype,
            "table_bytes": self.engine.table_bytes,
            # where those bytes live: single-device, replicated, or
            # NamedSharding-striped over a (batch, model) mesh — including
            # mesh shape, stripe grids and per-device resident bytes
            # (docs/serving.md "Sharded serving")
            "placement": self.engine.placement,
            # the overload surface: queue depth per priority class,
            # quota fractions, live AIMD controller window, drain-rate
            # estimate and shed/expiry/quota-reject counters
            "admission": self.batcher.overload_state(),
            # the hot-row cache surface: budget, resident bytes, hit/miss/
            # coalesced/evicted counters and the live hit ratio
            # (docs/serving.md "Score caching & coalescing")
            "cache": self.cache.stats() if self.cache is not None
            else {"enabled": False},
            # publisher lineage: recent gate decisions for this model's
            # version sequence (empty for hand-deployed models)
            "lineage": [dict(d) for d in self.lineage],
            # the top-K retrieval surface: catalog size, block/K geometry,
            # sharding and LSH index state (docs/serving.md "Top-K
            # retrieval"). {"enabled": False} = /topk 400s for this model.
            "retrieval": {"enabled": True,
                          **self.retrieval_engine.describe()}
            if self.retrieval_engine is not None else {"enabled": False},
        }


class ModelRegistry:
    """name -> ModelEntry with atomic version swap.

    Reads (`get`) are lock-free dict lookups; writes serialize on a lock.
    A handler thread holds the ENTRY it resolved, not the name, so a swap
    never invalidates an in-flight request — the old batcher drains.
    """

    # serving-grade admission defaults: every model's batcher gets the
    # full overload posture unless a deploy overrides it — low-priority
    # work quota-sheds at 60% queue fill, normal at 85%, high keeps
    # headroom to the cap (docs/serving.md "Overload behavior"); adaptive
    # caps stay equal to the bases (off) unless configured, so light-load
    # latency semantics are identical to the fixed-window batcher.
    DEFAULT_QUOTA_FRACS = (1.0, 0.85, 0.6)

    def __init__(self, *, max_batch: int = 256, max_delay_ms: float = 2.0,
                 max_queue_rows: int = 4096, warmup: bool = True,
                 engine_kwargs: Optional[dict] = None,
                 max_delay_ms_cap: Optional[float] = None,
                 max_batch_cap: Optional[int] = None,
                 priority_quota_fracs: Optional[tuple] = None,
                 starvation_limit: int = 8,
                 express_high: bool = True,
                 degraded_depth_fraction: float = 0.75,
                 score_cache_bytes: Optional[int] = None) -> None:
        # every deploy compiles a bucket ladder (42 programs per engine by
        # default): let a restarted server find them in the persistent
        # compilation cache instead of recompiling
        enable_compile_cache()
        self._entries: Dict[str, ModelEntry] = {}
        # hot-row score caches, one per model NAME, shared across that
        # name's versions (the version is in every key, so a hot-swap
        # invalidates atomically and old-version entries age out of the
        # byte budget — serving/cache.py). ``score_cache_bytes`` is the
        # registry-wide default budget; None/0 leaves caching OFF (the
        # conservative default: admission counters then mean exactly what
        # PR 10 pinned), a deploy can override per model.
        self._caches: Dict[str, object] = {}
        self.score_cache_bytes = score_cache_bytes
        self._lock = threading.Lock()
        self.max_batch = max_batch
        self.max_delay_ms = max_delay_ms
        self.max_queue_rows = max_queue_rows
        self.warmup = warmup
        self.engine_kwargs = dict(engine_kwargs or {})
        self.max_delay_ms_cap = max_delay_ms_cap
        self.max_batch_cap = max_batch_cap
        self.priority_quota_fracs = tuple(
            priority_quota_fracs or self.DEFAULT_QUOTA_FRACS)
        self.starvation_limit = starvation_limit
        # high-priority requests get a dedicated drain lane by default —
        # they never wait behind an in-flight lower-class dispatch
        # (serving/batcher.py "express lane")
        self.express_high = express_high
        # /healthz flips to "degraded" when any model's queue fills past
        # this fraction — overload is reported while the process is still
        # very much alive and shedding predictably
        self.degraded_depth_fraction = float(degraded_depth_fraction)
        self._swaps = REGISTRY.counter("serving", "registry.swaps")

    def deploy(self, name: str, source, version: Optional[str] = None,
               batcher_overrides: Optional[dict] = None,
               lineage: Optional[list] = None,
               score_cache_bytes: Optional[int] = None,
               retrieval: Optional[dict] = None,
               **engine_overrides) -> ModelEntry:
        """Deploy `source` (artifact dir path, Artifact, or trained model)
        as `name`; replaces any current version atomically AFTER the new
        engine is fully warmed (no cold-cache window under load). The
        version defaults to the artifact's manifest version (so /predict
        responses correlate with the frozen directory, rollbacks included);
        bare model objects auto-increment. ``batcher_overrides`` tunes
        this model's admission posture (max_queue_rows, quota fractions,
        adaptive caps, starvation limit) over the registry defaults —
        per-model quotas are per-model BATCHERS: each model owns its
        queue, so one model's flood can never 503 another. ``lineage``
        attaches the publisher's gate-decision records to the entry
        (surfaced on /models — the continuous-training pipeline passes its
        recent publish/refusal/rollback history here).
        ``score_cache_bytes`` overrides the registry's hot-row cache
        budget for this model (None inherits the registry default — or,
        failing that, whatever cache an earlier deploy enabled for this
        name; an explicit 0 disables); the cache OBJECT persists across
        this name's versions — swap invalidation is the version key, not
        a flush (docs/serving.md "Score caching & coalescing").
        ``retrieval`` (a dict of RetrievalEngine kwargs, ``{}`` for the
        defaults) additionally stands up the top-K catalog-scoring surface
        for this model — MF/FM only — behind its OWN DynamicBatcher, so
        ``POST /topk`` rides the same admission/priority/deadline
        machinery without sharing dispatch slots with /predict
        (docs/serving.md "Top-K retrieval"). Opt-in: None (default) means
        /topk answers 400 for this model."""
        from .artifact import Artifact, load as load_artifact

        if isinstance(source, str):
            source = load_artifact(source)
        if version is None and isinstance(source, Artifact):
            version = source.manifest.get("version")
        kw = dict(self.engine_kwargs)
        kw.update(engine_overrides)
        kw.setdefault("max_batch", self.max_batch)
        engine = ServingEngine(source, name=name, **kw)
        if version is None:
            with self._lock:
                old = self._entries.get(name)
            version = str(int(old.version) + 1) if old is not None \
                and old.version.isdigit() else "1"
        if self.warmup:
            engine.warmup()
        bkw = dict(max_batch=engine.max_batch,
                   max_delay_ms=self.max_delay_ms,
                   max_queue_rows=self.max_queue_rows,
                   max_delay_ms_cap=self.max_delay_ms_cap,
                   max_batch_cap=self.max_batch_cap,
                   priority_quota_fracs=self.priority_quota_fracs,
                   starvation_limit=self.starvation_limit,
                   express_high=self.express_high)
        bkw.update(batcher_overrides or {})
        cache_bytes = self.score_cache_bytes if score_cache_bytes is None \
            else score_cache_bytes
        cache = None
        if cache_bytes:
            from .cache import ScoreCache

            with self._lock:
                cache = self._caches.get(name)
                if cache is None or cache.max_bytes != int(cache_bytes):
                    cache = ScoreCache(int(cache_bytes), name=name)
                    self._caches[name] = cache
        elif score_cache_bytes is not None:
            with self._lock:  # explicit 0: caching OFF for this name
                self._caches.pop(name, None)
        else:
            # no override and no registry default: a cache an earlier
            # deploy enabled for this name SURVIVES the redeploy — the
            # object persisting across versions is the hot-swap story
            # (old-version entries age out of the byte budget)
            with self._lock:
                cache = self._caches.get(name)
        r_engine = r_batcher = None
        if retrieval is not None:
            from .retrieval import RetrievalEngine

            rkw = dict(retrieval)
            # the catalog shards wherever the pointwise tables do unless
            # the retrieval options say otherwise
            if kw.get("placement") is not None:
                rkw.setdefault("placement", kw.get("placement"))
            r_engine = RetrievalEngine(source, name=name, **rkw)
            if self.warmup:
                r_engine.warmup()
            rbkw = dict(max_batch=r_engine.max_batch,
                        max_delay_ms=self.max_delay_ms,
                        max_queue_rows=self.max_queue_rows,
                        max_delay_ms_cap=self.max_delay_ms_cap,
                        max_batch_cap=self.max_batch_cap,
                        priority_quota_fracs=self.priority_quota_fracs,
                        starvation_limit=self.starvation_limit,
                        express_high=self.express_high)
            rbkw.update(batcher_overrides or {})
            rbkw["max_batch"] = r_engine.max_batch
            # no score cache / row keys: a top-K row is (query, k, probe)
            # and the result is a ranking, not a scalar — the hot-row
            # cache's single-score contract doesn't apply
            r_batcher = DynamicBatcher(r_engine.topk_batch,
                                       name=f"{name}.topk", **rbkw)
        batcher = DynamicBatcher(engine.predict, name=name, cache=cache,
                                 cache_version=str(version),
                                 row_key_fn=engine.row_keys, **bkw)
        entry = ModelEntry(name, str(version), engine, batcher,
                           lineage=lineage, cache=cache,
                           retrieval_engine=r_engine,
                           retrieval_batcher=r_batcher)
        with self._lock:
            old = self._entries.get(name)
            self._entries[name] = entry  # the atomic publish
        if old is not None:
            self._swaps.increment()
            # outside the lock: draining can take max_delay + a batch
            old.batcher.close(drain=True)
            if old.retrieval_batcher is not None:
                old.retrieval_batcher.close(drain=True)
        REGISTRY.set_gauge(f"serving.{name}.deployed_version",
                           float(version) if str(version).isdigit() else 0.0)
        return entry

    def get(self, name: Optional[str] = None) -> Optional[ModelEntry]:
        """Resolve a model by name; with one deployed model, name may be
        omitted (the single-model convenience every demo uses)."""
        if name is not None:
            # designed lock-free read: a single dict .get() is atomic under
            # the GIL and deploy() publishes entries with one assignment —
            # readers see the old or new entry, never a partial one
            return self._entries.get(name)  # graftcheck: disable=G012 (reviewed lock-free read)
        with self._lock:  # a concurrent first deploy mutates the dict
            entries = list(self._entries.values())
        if len(entries) == 1:
            return entries[0]
        return None

    # each BatcherClosed means a full deploy landed between resolve and
    # submit; needing this many consecutive swaps inside one submit window
    # is not a reachable steady state
    _SWAP_RETRIES = 8

    def submit(self, name: Optional[str], instances, *,
               priority="normal", deadline_ms: Optional[float] = None):
        """Resolve + enqueue, retrying across hot swaps: a caller that
        resolved the OLD entry right before deploy() published the new one
        sees BatcherClosed from the draining batcher — re-resolving gets
        the new version, so a swap fails zero requests. Returns
        (entry, future); (None, None) means the name is genuinely unknown
        (never deployed, or undeployed). QueueFull propagates (backpressure
        is the caller's 503); BatcherClosed escapes only after
        _SWAP_RETRIES consecutive swap collisions (retryable, also 503).
        ``priority``/``deadline_ms`` thread through to the batcher's
        admission decision (serving/batcher.py)."""
        for _ in range(self._SWAP_RETRIES):
            entry = self.get(name)
            if entry is None:
                return None, None
            try:
                return entry, entry.batcher.submit(
                    instances, priority=priority, deadline_ms=deadline_ms)
            except BatcherClosed:  # graftcheck: disable=G031 (retry rebinds to the NEW batcher; waiting adds only latency)
                continue
        raise BatcherClosed(
            f"model {name!r}: {self._SWAP_RETRIES} consecutive version "
            f"swaps collided with this submit — retry")

    def submit_topk(self, name: Optional[str], rows, *,
                    priority="normal", deadline_ms: Optional[float] = None):
        """submit(), but into the model's RETRIEVAL batcher. ``rows`` is a
        list of ``(query, k, probe)`` tuples (serving/retrieval.py
        ``topk_batch``). Returns (entry, future); (None, None) means the
        name is unknown; (entry, None) means the model is deployed but
        without a retrieval surface (deploy() had no ``retrieval=`` — the
        caller's 400). Swap-retry semantics match submit()."""
        for _ in range(self._SWAP_RETRIES):
            entry = self.get(name)
            if entry is None:
                return None, None
            if entry.retrieval_batcher is None:
                return entry, None
            try:
                return entry, entry.retrieval_batcher.submit(
                    rows, priority=priority, deadline_ms=deadline_ms)
            except BatcherClosed:  # graftcheck: disable=G031 (retry rebinds to the NEW batcher; waiting adds only latency)
                continue
        raise BatcherClosed(
            f"model {name!r}: {self._SWAP_RETRIES} consecutive version "
            f"swaps collided with this submit — retry")

    def health(self) -> dict:
        """Overload-aware health: ``degraded`` (still alive — shedding
        predictably) when any model's queue fills past
        ``degraded_depth_fraction``; the status a load balancer should
        read BEFORE the process ever looks dead."""
        with self._lock:
            entries = list(self._entries.values())
        models, worst = {}, 0.0
        for e in entries:
            st = e.batcher.overload_state()
            worst = max(worst, st["depth_fraction"])
            models[e.name] = {
                "depth_fraction": st["depth_fraction"],
                "depth_rows": st["depth_rows"],
                "controller": st["controller"],
                "shed": st["shed"], "expired": st["expired"],
                "quota_rejected": st["quota_rejected"],
            }
        info = {
            "status": "degraded" if worst >= self.degraded_depth_fraction
            else "ok",
            "degraded_depth_fraction": self.degraded_depth_fraction,
            "worst_depth_fraction": round(worst, 4),
            "models": models,
        }
        try:
            import jax

            info["process_index"] = jax.process_index()
            info["local_devices"] = len(jax.local_devices())
        except Exception:  # graftcheck: disable=G029 (probe: jax absent means health omits device fields)
            pass
        return info

    def undeploy(self, name: str) -> bool:
        with self._lock:
            entry = self._entries.pop(name, None)
            self._caches.pop(name, None)
        if entry is None:
            return False
        entry.batcher.close(drain=True)
        if entry.retrieval_batcher is not None:
            entry.retrieval_batcher.close(drain=True)
        return True

    def list_models(self):
        with self._lock:  # a first deploy of a new name mutates the dict
            entries = list(self._entries.values())
        return [e.describe() for e in entries]

    def shutdown(self) -> None:
        with self._lock:
            entries = list(self._entries.values())
            self._entries = {}
            self._caches = {}
        for e in entries:
            e.batcher.close(drain=True)
            if e.retrieval_batcher is not None:
                e.retrieval_batcher.close(drain=True)


class _ServingHandler(metrics_http._Handler):
    """Extends the metrics handler with /predict, /models and the
    overload-aware /healthz. The registry rides on the server object
    (see serve())."""

    # persistent connections: the overload bench (and any real client)
    # reuses sockets instead of burning an ephemeral port per request;
    # every response carries Content-Length, so keep-alive is safe
    protocol_version = "HTTP/1.1"

    predict_timeout = 30.0

    def _send_json(self, code: int, payload: dict, extra_headers=()) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        for k, v in extra_headers:
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802 - http.server API
        path = self.path.split("?")[0]
        if path == "/models":
            self._send_json(200, {"models": self.server.registry.list_models()})
            return
        if path == "/healthz":
            # overload-aware liveness: "degraded" reports a server that is
            # alive and shedding predictably BEFORE it ever looks dead.
            # Queue depth is the instantaneous signal; the SLO engine's
            # burn state (runtime/slo.py) is the over-time one — a paging
            # objective degrades health even while the queue happens to
            # look shallow, so a front door routing on /healthz sees
            # both (ROADMAP fleet-serving: per-replica health a router
            # can trust)
            from ..runtime.slo import ENGINE

            info = self.server.registry.health()
            slo_block = ENGINE.health_block()
            info["slo"] = slo_block
            if slo_block["paging"]:
                info["status"] = "degraded"
            self._send_json(200, info)
            return
        super().do_GET()

    def _drain_body(self) -> None:
        """Read and discard the request body so the keep-alive connection
        stays in sync on paths that never parse it (the door 503, the
        POST 404)."""
        try:
            length = int(self.headers.get("Content-Length", 0))
        except ValueError:  # garbage header: nothing trustworthy to drain
            length = 0
        self.rfile.read(length)

    def do_POST(self):  # noqa: N802 - http.server API
        route = self.path.split("?")[0]
        if route not in ("/predict", "/topk"):
            self._drain_body()
            self._send_json(404, {"error": "not found"})
            return
        # concurrency admission, at the door: past the in-flight limit the
        # request is refused BEFORE its body is parsed — under overload
        # the handler threads' own parse work would otherwise starve the
        # batcher worker of the very CPU that IS the service capacity.
        # The body is still drained so the keep-alive connection stays
        # usable; 503s are deliberately cheap.
        sem = getattr(self.server, "inflight", None)
        held = None
        if sem is not None:
            if sem.acquire(blocking=False):
                held = sem
            else:
                # the door must not undo the priority classes: requests
                # whose x-priority HEADER says "high" may still enter
                # through the reserved slots (body-priority requests
                # cannot — the point of the door is deciding before the
                # body is parsed)
                hdr = (self.headers.get("x-priority") or "").strip().lower()
                reserve = getattr(self.server, "inflight_reserve", None)
                if hdr in ("high", "0") and reserve is not None \
                        and reserve.acquire(blocking=False):
                    held = reserve
            if held is None:
                self._drain_body()
                self.server.concurrency_rejected.increment()
                self._send_json(503,
                                {"error": "too many in-flight requests",
                                 "reason": "concurrency"},
                                extra_headers=(("Retry-After", "1"),))
                return
        try:
            self._topk() if route == "/topk" else self._predict()
        finally:
            if held is not None:
                held.release()

    def _predict(self) -> None:
        # the request's ROOT span: HTTP parse, queue wait, batched device
        # dispatch and the response write all land under it; the latency
        # histogram observation carries its trace_id as an exemplar. A
        # client W3C traceparent is adopted as the root's parent (PR 5
        # leftover) and echoed back with OUR root span as the new parent;
        # a malformed header parses to None — a fresh trace.
        remote = TRACER.parse_traceparent(self.headers.get("traceparent"))
        with TRACER.span("server.predict", remote=remote) as root:
            tp = TRACER.format_traceparent(root)
            tp_hdr = (("traceparent", tp),) if tp else ()
            with TRACER.span("server.parse"):
                close_hdr = ()
                try:
                    try:
                        length = int(self.headers.get("Content-Length", 0))
                    except ValueError:
                        # body length unknowable: the socket cannot be
                        # drained back into sync — close it with the 400
                        close_hdr = (("Connection", "close"),)
                        raise
                    payload = json.loads(self.rfile.read(length) or b"{}")
                    instances = payload["instances"]
                    if not isinstance(instances, list):
                        raise TypeError("instances must be a list")
                    # priority class + deadline budget: body keys win over
                    # the x-priority / x-deadline-ms headers
                    cls = priority_class(
                        payload.get("priority",
                                    self.headers.get("x-priority")
                                    or "normal"))
                    deadline_ms = payload.get(
                        "deadline_ms", self.headers.get("x-deadline-ms"))
                    if deadline_ms is not None:
                        deadline_ms = float(deadline_ms)
                        if not math.isfinite(deadline_ms) \
                                or deadline_ms <= 0:
                            raise ValueError(
                                f"deadline_ms must be a positive number, "
                                f"got {deadline_ms}")
                except (KeyError, TypeError, ValueError) as e:
                    self._send_json(400, {"error": f"bad request: {e}"},
                                    extra_headers=tp_hdr + close_hdr)
                    root.set(status=400)
                    return
            root.set(instances=len(instances),
                     model=payload.get("model") or "",
                     priority=priority_name(cls),
                     **({"deadline_ms": deadline_ms}
                        if deadline_ms is not None else {}))
            t0 = time.perf_counter()
            try:
                # registry.submit retries across a hot swap, so a v1->v2
                # deploy never fails a request; only an unknown name /
                # undeploy 404s
                entry, future = self.server.registry.submit(
                    payload.get("model"), instances,
                    priority=cls, deadline_ms=deadline_ms)
                if entry is None:
                    self._send_json(404,
                                    {"error": f"unknown model "
                                              f"{payload.get('model')!r}"},
                                    extra_headers=tp_hdr)
                    root.set(status=404)
                    return
                preds = future.result(timeout=self.predict_timeout)
            except DeadlineExpired as e:
                # expired IN the queue: no dispatch slot was spent on it
                self._send_json(504, {"error": str(e),
                                      "reason": "deadline"},
                                extra_headers=tp_hdr)
                root.set(status=504)
                return
            except (QueueFull, BatcherClosed) as e:
                # quota refusal, low-priority shed, or a swap-collision
                # storm — all retryable; Retry-After is priced from the
                # live drain-rate estimate so clients back off usefully
                ra = getattr(e, "retry_after_s", None) or 1.0
                self._send_json(
                    503, {"error": str(e),
                          "reason": getattr(e, "reason", "busy")},
                    extra_headers=tp_hdr + (
                        ("Retry-After", str(int(math.ceil(ra)))),))
                root.set(status=503)
                return
            except Exception as e:  # scoring bug — surface, don't hang
                self._send_json(500, {"error": f"{type(e).__name__}: {e}"},
                                extra_headers=tp_hdr)
                root.set(status=500)
                return
            dt = time.perf_counter() - t0
            self.server.latency.observe(
                dt, trace_id=TRACER.exemplar_id(root))
            # per-priority-class twin of the aggregate histogram: the
            # class rides the metric name (serving.http.latency_seconds.
            # high/normal/low — the counter convention), so /metrics can
            # answer "is the high class actually protected" and the SLO
            # engine can target one class (docs/serving.md)
            self.server.latency_by_class[cls].observe(dt)
            root.set(status=200, version=entry.version)
            self._send_json(200, {
                "model": entry.name,
                "version": entry.version,
                "predictions": [_jsonable(p) for p in preds],
            }, extra_headers=tp_hdr)

    def _topk(self) -> None:
        # /predict's twin for the retrieval surface: same root-span /
        # traceparent / priority / deadline / error-mapping contract, but
        # the rows are (query, k, probe) tuples into the model's SEPARATE
        # retrieval batcher and the answer is a ranking per query
        # (docs/serving.md "Top-K retrieval")
        remote = TRACER.parse_traceparent(self.headers.get("traceparent"))
        with TRACER.span("server.topk", remote=remote) as root:
            tp = TRACER.format_traceparent(root)
            tp_hdr = (("traceparent", tp),) if tp else ()
            with TRACER.span("server.parse"):
                close_hdr = ()
                try:
                    try:
                        length = int(self.headers.get("Content-Length", 0))
                    except ValueError:
                        close_hdr = (("Connection", "close"),)
                        raise
                    payload = json.loads(self.rfile.read(length) or b"{}")
                    queries = payload["queries"]
                    if not isinstance(queries, list):
                        raise TypeError("queries must be a list")
                    k = payload.get("k")
                    if k is not None:
                        k = int(k)
                        if k < 1:
                            raise ValueError(f"k must be >= 1, got {k}")
                    probe = payload.get("probe")
                    if probe is not None:
                        probe = bool(probe)
                    cls = priority_class(
                        payload.get("priority",
                                    self.headers.get("x-priority")
                                    or "normal"))
                    deadline_ms = payload.get(
                        "deadline_ms", self.headers.get("x-deadline-ms"))
                    if deadline_ms is not None:
                        deadline_ms = float(deadline_ms)
                        if not math.isfinite(deadline_ms) \
                                or deadline_ms <= 0:
                            raise ValueError(
                                f"deadline_ms must be a positive number, "
                                f"got {deadline_ms}")
                except (KeyError, TypeError, ValueError) as e:
                    self._send_json(400, {"error": f"bad request: {e}"},
                                    extra_headers=tp_hdr + close_hdr)
                    root.set(status=400)
                    return
            root.set(queries=len(queries),
                     model=payload.get("model") or "",
                     priority=priority_name(cls),
                     **({"k": k} if k is not None else {}),
                     **({"deadline_ms": deadline_ms}
                        if deadline_ms is not None else {}))
            t0 = time.perf_counter()
            try:
                rows = [(q, k, probe) for q in queries]
                entry, future = self.server.registry.submit_topk(
                    payload.get("model"), rows,
                    priority=cls, deadline_ms=deadline_ms)
                if entry is None:
                    self._send_json(404,
                                    {"error": f"unknown model "
                                              f"{payload.get('model')!r}"},
                                    extra_headers=tp_hdr)
                    root.set(status=404)
                    return
                if future is None:
                    # deployed, but deploy() stood up no retrieval surface
                    self._send_json(
                        400, {"error": f"model {entry.name!r} has no "
                                       f"retrieval surface (deploy with "
                                       f"retrieval= to enable /topk)"},
                        extra_headers=tp_hdr)
                    root.set(status=400)
                    return
                results = future.result(timeout=self.predict_timeout)
            except DeadlineExpired as e:
                self._send_json(504, {"error": str(e),
                                      "reason": "deadline"},
                                extra_headers=tp_hdr)
                root.set(status=504)
                return
            except (QueueFull, BatcherClosed) as e:
                ra = getattr(e, "retry_after_s", None) or 1.0
                self._send_json(
                    503, {"error": str(e),
                          "reason": getattr(e, "reason", "busy")},
                    extra_headers=tp_hdr + (
                        ("Retry-After", str(int(math.ceil(ra)))),))
                root.set(status=503)
                return
            except Exception as e:  # scoring bug — surface, don't hang
                self._send_json(500, {"error": f"{type(e).__name__}: {e}"},
                                extra_headers=tp_hdr)
                root.set(status=500)
                return
            dt = time.perf_counter() - t0
            self.server.latency.observe(
                dt, trace_id=TRACER.exemplar_id(root))
            self.server.latency_by_class[cls].observe(dt)
            root.set(status=200, version=entry.version)
            self._send_json(200, {
                "model": entry.name,
                "version": entry.version,
                "k": k if k is not None else entry.retrieval_engine.k,
                "results": list(results),
            }, extra_headers=tp_hdr)


def _jsonable(p):
    if isinstance(p, (np.generic,)):
        return p.item()
    if isinstance(p, np.ndarray):
        return p.tolist()
    return p


def serve(registry: ModelRegistry, port: int = 0, host: str = "127.0.0.1",
          max_concurrent_requests: Optional[int] = None
          ) -> ThreadingHTTPServer:
    """Start the serving endpoint on a daemon thread (stdlib only, the
    serve_metrics recipe); ``server.server_address[1]`` is the bound port.
    The same server answers /predict, /models, /metrics and /healthz.

    ``max_concurrent_requests`` bounds in-flight /predict handlers: past
    the limit requests get an immediate cheap 503 (``reason:
    concurrency``) before their body is parsed — the third admission
    dimension next to queue-row quotas and deadlines (docs/serving.md
    "Overload behavior"). A quarter of the limit again is reserved for
    requests whose ``x-priority`` header says high, so the door cannot
    undo the priority classes. None (default) leaves it unbounded."""
    server = ThreadingHTTPServer((host, port), _ServingHandler)
    server.registry = registry
    server.latency = REGISTRY.histogram("serving.http.latency_seconds")
    # the per-priority-class split of the same histogram (indexed by the
    # admission class int): multi-tenancy per-tenant counters will ride
    # this shape
    server.latency_by_class = tuple(
        REGISTRY.histogram(f"serving.http.latency_seconds.{p}")
        for p in PRIORITY_NAMES)
    if max_concurrent_requests is None:
        server.inflight = server.inflight_reserve = None
    else:
        n = int(max_concurrent_requests)
        server.inflight = threading.BoundedSemaphore(n)
        server.inflight_reserve = threading.BoundedSemaphore(
            max(2, n // 4))
    server.concurrency_rejected = REGISTRY.counter(
        "serving", "http.concurrency_rejected")
    t = threading.Thread(target=server.serve_forever, daemon=True,
                         name="hivemall-tpu-serving")
    t.start()
    return server
