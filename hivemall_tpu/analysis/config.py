"""Codebase-specific knobs for the graftcheck rules.

graftcheck is purpose-built for this repo's JAX idioms: the hot-path module
list, the mesh axis registry, and the jitted-factory naming convention live
here rather than being rediscovered per rule.
"""

from __future__ import annotations

import re

# --- G002: modules whose loops are per-step hot paths -----------------------
# The per-step loops of these modules drive every benchmark; an implicit
# device->host sync there serializes dispatch (the rounds 1-5 regressions).
HOT_LOOP_MODULES = (
    "hivemall_tpu/core/engine.py",
    "hivemall_tpu/parallel/sharded_train.py",
    "hivemall_tpu/parallel/mix.py",
    "hivemall_tpu/models/trees/grow.py",
    # the epoch/convergence driver that loops the engine's jitted steps
    "hivemall_tpu/models/base.py",
)

# Methods with these names receive device state / blocks by contract, so
# their parameters are treated as device values even outside a loop.
HOT_FN_RE = re.compile(r"^(step|_step|train_step|epoch)$")

# Calls that force an implicit device->host transfer when applied to a
# device value. jax.device_get is handled separately (it is the explicit,
# batched boundary idiom — flagged only when used per-element).
SYNC_CALLS = ("float", "int", "bool")
SYNC_NP_CALLS = ("asarray", "array")
SYNC_METHODS = ("item", "tolist")

# --- taint: factories returning jitted callables ----------------------------
# `step = make_train_step(...)` / `predict = make_predict(...)`: calling the
# result yields device arrays. Matched against the callee name.
JITTED_FACTORY_RE = re.compile(
    r"^make_\w*(step|epoch|predict|train_fn|mix|fn)\w*$")

# Attribute callees whose results are device values (trainer convention).
JITTED_ATTR_CALLEES = ("_step", "step")

# Transforms whose function argument is traced when called.
TRACING_TRANSFORMS = (
    "jit", "vmap", "pmap", "shard_map", "scan", "cond", "while_loop",
    "fori_loop", "checkpoint", "remat", "grad", "value_and_grad", "custom_vjp",
)

# Calls whose RESULT is host data even when arguments are device values.
UNTAINT_CALLS = ("device_get", "shape", "len", "range", "eval_shape",
                 "tree_structure")

# --- G003: dtype-sensitive scopes ------------------------------------------
# Modules whose math feeds weight updates: bare literals / float64 here can
# silently upcast the bf16-above-2^24 storage policy (models/base.py).
DTYPE_MODULE_PREFIXES = (
    "hivemall_tpu/ops/",
    "hivemall_tpu/core/",
    "hivemall_tpu/models/",
    "hivemall_tpu/kernels/",
)
# Update-math modules where even host-side helper functions are checked for
# unpinned float literals (their outputs flow straight into rule updates).
DTYPE_MATH_MODULES = (
    "hivemall_tpu/ops/eta.py",
    "hivemall_tpu/ops/losses.py",
)

# --- G004: mesh axis registry ----------------------------------------------
# Fallback when parallel/mesh.py is outside the scanned path set. When it IS
# scanned, its module-level string constants and Mesh(...) literals extend
# this set.
MESH_FILE = "hivemall_tpu/parallel/mesh.py"
DEFAULT_AXIS_NAMES = frozenset({"workers", "shards"})
COLLECTIVE_CALLS = ("psum", "pmean", "pmax", "pmin", "all_gather",
                    "axis_index", "ppermute", "psum_scatter", "pcast")

# --- G012-G016: concurrency / serving safety --------------------------------
# Constructors whose result is a lock object; the kind decides reentrancy
# (plain Lock is non-reentrant; Condition() wraps an RLock by default).
LOCK_CONSTRUCTOR_KINDS = {
    "Lock": "lock",
    "RLock": "rlock",
    "Condition": "condition",
    "Semaphore": "lock",
    "BoundedSemaphore": "lock",
}

# Method calls on a field that mutate the underlying container — counted as
# WRITES by the guarded-by inference (`self._q.append(x)` races exactly like
# `self._q = ...`).
MUTATOR_METHODS = ("append", "appendleft", "extend", "insert", "add",
                   "discard", "remove", "clear", "update", "setdefault",
                   "pop", "popleft", "popitem", "sort")

# G013 scope: the serving hot path — a blocking call under a lock here stalls
# every in-flight request at once (the hot-swap-stall failure mode). Modules
# outside the list opt in with the marker comment. The continuous-training
# pipeline is in scope by prefix: its worker thread shares the registry with
# request handlers, so a freeze/gate/deploy under its lock would stall
# every concurrent status()/lineage read exactly when a swap is in flight.
# The observability stack (metrics registry + endpoint, time-series
# sampler, SLO engine) is hot the same way: the sampler thread, ring
# listeners and HTTP scrape handlers all take its locks concurrently with
# request handlers, so a registry snapshot or listener callback under a
# ring/engine lock stalls both the sampler AND every /metrics scrape.
CONCURRENCY_HOT_PREFIXES = ("hivemall_tpu/serving/",
                            "hivemall_tpu/pipeline/",
                            "hivemall_tpu/runtime/metrics",
                            "hivemall_tpu/runtime/timeseries",
                            "hivemall_tpu/runtime/slo")
CONCURRENCY_MARKER = "# graftcheck: serving-module"

# Blocking-call classification for G013 (tails of dotted callees).
BLOCKING_DEVICE_TAILS = ("device_get", "block_until_ready")
BLOCKING_IO_TAILS = ("sleep", "urlopen", "connect", "accept", "recv",
                     "sendall", "getaddrinfo", "fsync")
# Future/thread rendezvous: .result() blocks on completion; set_result /
# set_exception run done-callbacks synchronously on the calling thread.
BLOCKING_FUTURE_TAILS = ("result", "set_result", "set_exception", "join",
                         "wait")
# jit dispatch / compile triggers: a cold bucket compiles under the lock.
BLOCKING_JIT_TAILS = ("warmup", "predict", "predict_fn")
# Roots whose methods share tails with the blocking list but never block
# (os.path.join, np ops, json/re parsing).
BLOCKING_SAFE_ROOTS = ("os", "np", "numpy", "json", "re", "posixpath",
                       "ntpath", "shutil", "sys", "math")

# --- G017-G021: dtype / precision flow (v4) ---------------------------------
# Hot-path scopes for the dtype-flow rules: a silent widening here doubles
# HBM traffic on every step/request (the dequant-free serving contract the
# quantized-artifact work depends on). The kernel/op packages and the
# serving score path are always hot; elsewhere in the dtype-sensitive
# packages only traced / step-shaped functions are (dtypeflow.in_hot_scope).
DTYPEFLOW_HOT_PREFIXES = (
    "hivemall_tpu/ops/",
    "hivemall_tpu/kernels/",
)
# serving/engine.py carries the dequant-free score path (the _q8_* scorers
# and every gathered-window cast); io/checkpoint.py carries the shared
# quantization pack/unpack helpers (quantize_int8 / bf16_pack_raw) — both
# are always hot for G017/G019 so a widened full-table copy or a silent
# promotion in the quant plumbing fails tier-1 (scripts/lint.sh) before a
# benchmark ever runs.
DTYPEFLOW_HOT_MODULES = ("hivemall_tpu/serving/engine.py",
                         # the hot-row score cache (the serving L0 fast
                         # path): cached values ARE the engine's computed
                         # predictions — a silent widening or f64 leak in
                         # the cache plumbing would break the cached ==
                         # computed bit-parity gate the skew bench pins.
                         # (G012-G016 concurrency scope is the serving/
                         # prefix — CONCURRENCY_HOT_PREFIXES above — so
                         # cache.py's lock discipline is gated the same
                         # way as batcher.py's.)
                         "hivemall_tpu/serving/cache.py",
                         # the sharded score path: per-window widens only
                         # (G019) and f32 accumulation (G021), same
                         # contract as the single-device _q8_* scorers
                         "hivemall_tpu/serving/sharded.py",
                         # the top-K retrieval path: the blocked catalog
                         # scorers carry the same dequant-free contract
                         # (int8 window widen + scale fold, f32
                         # accumulation) at catalog scale — a full-table
                         # dequant here costs N_items, not a window
                         "hivemall_tpu/serving/retrieval.py",
                         "hivemall_tpu/io/checkpoint.py",
                         # the segment-sum batched trainer: the CPU hot
                         # path — gathered [U]-window widens only, f32
                         # delta accumulation, one cast at each table
                         # write; a full-table promotion here would hand
                         # back the bandwidth the compact plan bought
                         "hivemall_tpu/core/batch_update.py",
                         # the native-apply staging layer (-native_apply):
                         # host f32 tables + plan marshalling feeding the
                         # ctypes ABI — a silent widening or float64
                         # temporary here doubles the very traffic the
                         # native pass exists to cut, and an unpinned
                         # dtype would cross the ABI as garbage
                         "hivemall_tpu/core/native_batch.py")
HOT_MARKER = "# graftcheck: hot-module"

# G018 scope: the serving/request path plus checkpoint IO — np.float64 (or a
# float64-by-default numpy constructor) here silently doubles payload and
# table bandwidth. Modules outside opt in with the serving-module marker
# (shared with G013 — both guard the same request path).
DTYPEFLOW_SERVING_PREFIXES = (
    "hivemall_tpu/serving/",
    "hivemall_tpu/io/",
)

# G020 scope: artifact/checkpoint save->load modules whose reloads must pin
# the manifest dtype (a bf16 table widened to f32 at rest must narrow back
# on load, not silently serve wide).
ARTIFACT_IO_MODULES = (
    "hivemall_tpu/io/checkpoint.py",
    "hivemall_tpu/serving/artifact.py",
    "hivemall_tpu/serving/engine.py",
    # the sharded load path re-places reloaded tables; its dtype pins live
    # in host_score_tables but an unpinned asarray HERE would undo them
    "hivemall_tpu/serving/sharded.py",
)
ARTIFACT_MARKER = "# graftcheck: artifact-io"

# --- G022-G026: FFI boundary (v5) ------------------------------------------
# Exported symbols of the native library (native/hivemall_native.cpp) all
# share this prefix; any dotted call whose tail matches is a foreign call.
FFI_SYMBOL_PREFIXES = ("hm_",)
# Callees whose results are sanctioned pointer sources: they raise on any
# dtype/rank/contiguity violation, so arrays unpacked from them are
# ABI-proven (ops/scatter.py plan_abi_arrays — the frozen plan ABI's gate).
FFI_SANCTIONING_VALIDATORS = ("plan_abi_arrays",)
# numpy constructors whose result is always freshly allocated C-contiguous;
# with an explicit dtype they fully validate a pointer source.
FFI_FRESH_CTORS = ("empty", "zeros", "ones", "full", "frombuffer")
# The Python-side plan ABI version constant (ops/scatter.py) checked by
# G025 against the C side's HM_PLAN_ABI_VERSION literal.
FFI_ABI_VERSION_CONSTANT = "PLAN_ABI_VERSION"
# C source of the native library for the G025 cross-language check; the
# env var overrides the default repo-root-relative location (tests seed
# deliberate drift through a tempdir copy).
FFI_NATIVE_CPP_ENV = "GRAFTCHECK_NATIVE_CPP"
FFI_NATIVE_CPP_DEFAULT = "native/hivemall_native.cpp"

# --- G027-G031: exception flow / failure paths (v6) --------------------------
# Failure-path scope: the serving request path, the continuous-training
# pipeline, and the whole runtime package (recovery driver, fault injector,
# tracing, metrics, cluster shims) — the code whose exception paths the
# reliability fronts depend on. A Future leaked on an unwind here hangs a
# client forever; a silent fallback hides a degradation until a bench
# regresses. Modules outside the prefixes opt in with the marker comment.
EXCEPTION_HOT_PREFIXES = (
    "hivemall_tpu/serving/",
    "hivemall_tpu/pipeline/",
    "hivemall_tpu/runtime/",
)
EXCEPTION_MARKER = "# graftcheck: failure-path-module"

# Handler calls that count as a LOUD surface for G028: the fallback names
# its reason somewhere an operator can see (warnings / logging / the trace
# ring / the metrics registry).
LOUD_CALL_TAILS = ("warn", "warning", "warn_explicit", "error", "exception",
                   "critical", "fatal", "instant", "increment")
LOUD_CALL_ROOTS = ("warnings", "logging")

# Handler types whose silent fallback is the sanctioned API-probing idiom
# (compat shims, optional native libraries) — a handler catching ONLY these
# is never a G028 degraded path.
PROBE_EXCEPTION_TYPES = frozenset({
    "ImportError", "ModuleNotFoundError", "AttributeError",
})

# Retry backoff classification for G031 (tails of dotted callees).
# cv.wait(timeout) counts: blocking on a condition variable IS the
# well-behaved form of waiting between attempts.
BACKOFF_CALL_TAILS = ("sleep", "wait")

# --- G005: donation --------------------------------------------------------
# jit-wrapped functions whose name looks step-shaped should donate their
# model-state argument; otherwise every hot-loop step copies the tables.
STEP_NAME_RE = re.compile(r"(step|epoch|train)", re.IGNORECASE)

# --- G006: host side effects -----------------------------------------------
SIDE_EFFECT_CALLS = ("print",)
SIDE_EFFECT_ATTR_ROOTS = ("time", "logging")
SIDE_EFFECT_METHODS = ("increment", "set_gauge", "record")
SIDE_EFFECT_NP_RANDOM = ("random",)

# --- v7 traceflow (G032-G036) ----------------------------------------------
# Modules whose jit call graphs the trace-time rules sweep by default: the
# serving dispatch stack and the kernel/op layers every jitted scorer and
# step funnels through. The zero-recompile contract is a property of these
# modules first; anything else opts in with the marker comment.
TRACEFLOW_HOT_PREFIXES = (
    "hivemall_tpu/ops/",
    "hivemall_tpu/kernels/",
)
TRACEFLOW_HOT_MODULES = (
    "hivemall_tpu/serving/engine.py",
    "hivemall_tpu/serving/retrieval.py",
    "hivemall_tpu/serving/sharded.py",
)
TRACEFLOW_MARKER = "# graftcheck: jit-hot-module"

# Module-level dicts recognized as sanctioned jit memos (the _SHARDED_JIT /
# _RETRIEVAL_JIT / _QUANT_JIT get-or-build idiom): a function that both
# reads and writes one of these is a memo helper, and jit wrappers built
# under it are constructed once per key, not once per call.
TRACEFLOW_MEMO_NAME_RE = re.compile(r"^_[A-Z0-9_]*JIT[A-Z0-9_]*$")

# Function names sanctioned to construct jit wrappers per CALL of the
# factory: builders invoked once at setup (make_*/build_*) and __init__.
# Calling one of these per hot-loop iteration is still churn (G032c).
TRACEFLOW_FACTORY_RE = re.compile(r"^_?(make|build)_\w+")

# Calls that canonicalize an array's shape onto the bucket ladder before it
# reaches a jitted callable (G034). pad_to_bucket is the width calculator
# (slicing/padding to its result IS bucket routing); bucket_rows and
# pad_rows_to_multiple are the array-level canonicalizers; a bare pad is
# trusted as deliberate shape control.
SHAPE_CANONICALIZERS = ("pad_to_bucket", "bucket_rows", "pad_rows_to_multiple",
                        "pad")

# Callee names that declare themselves host-sync boundaries (G036): a
# helper named like one of these performs its device_get on purpose, as the
# loop's sanctioned whole-value boundary read.
TRACEFLOW_SYNC_NAME_RE = re.compile(
    r"(sync|block_until|device_get|to_host|fetch|drain|gather_host)",
    re.IGNORECASE)

# Call tails inside a callee body that constitute an unconditional device
# sync for the G036 summary walk (taint-free: these block by name).
TRACEFLOW_SYNC_CALL_TAILS = ("device_get", "block_until_ready")
