"""ctypes bindings for the native host ops (native/hivemall_native.cpp).

The C++ library accelerates the host-side input pipeline: bulk murmur3 feature
hashing and padded-CSR block packing (the [native-equiv] substrate pieces from
SURVEY.md §2.17). The .so is a build product, not a tracked file: the first
load compiles it from native/hivemall_native.cpp through
scripts/build_native.sh when it is missing or its build stamp names another
CPU or source (the plain build is -march=native, so a library copied from
another host is a SIGILL waiting in hm_pack_block). Python/numpy fallbacks
are used when no compiler is available."""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import shutil
import subprocess
from typing import List, Optional, Sequence, Tuple

import numpy as np

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(os.path.dirname(_PKG_DIR))
_LIB_PATH = os.path.join(_PKG_DIR, "libhivemall_native.so")
_SRC_PATH = os.path.join(_REPO_ROOT, "native", "hivemall_native.cpp")
_BUILD_SCRIPT = os.path.join(_REPO_ROOT, "scripts", "build_native.sh")
_lib: Optional[ctypes.CDLL] = None


_load_error: Optional[str] = None
# why this process (re)built the library at first use, or None when the
# library on disk was already this host's build — reported by build_info()
_built_because: Optional[str] = None

# HIVEMALL_TPU_NATIVE_SANITIZE selects a sanitizer-instrumented .so variant
# built by `scripts/build_native.sh --sanitize=...` (suffixed so the
# build-stamp machinery never confuses it with the optimized build):
#   ""     -> libhivemall_native.so       (the optimized default)
#   "asan" -> libhivemall_native.asan.so  (ASan+UBSan, halt_on_error gate)
#   "tsan" -> libhivemall_native.tsan.so  (TSan — armed for the threaded
#                                          native apply)
# Sanitizer runtimes are not linked into a -shared .so: the test harness
# LD_PRELOADs libasan/libubsan (scripts/test.sh gate 11).
_SANITIZE_ENV = "HIVEMALL_TPU_NATIVE_SANITIZE"
_SANITIZE_SUFFIX = {"": "", "asan": ".asan", "tsan": ".tsan"}


def _so_path() -> Optional[str]:
    """The .so variant selected by the sanitizer env var, or None (with
    ``_load_error`` recorded) for an unknown value — a typo'd sanitizer
    name must refuse loudly, never silently load the uninstrumented .so."""
    global _load_error
    variant = os.environ.get(_SANITIZE_ENV, "").strip().lower()
    suffix = _SANITIZE_SUFFIX.get(variant)
    if suffix is None:
        _load_error = (f"unknown {_SANITIZE_ENV}={variant!r} "
                       f"(expected one of: "
                       f"{', '.join(repr(k) for k in _SANITIZE_SUFFIX)})")
        import warnings

        warnings.warn(f"hivemall_tpu.native: {_load_error}; native "
                      f"backend disabled, using Python fallbacks")
        return None
    if not suffix:
        return _LIB_PATH
    base, ext = os.path.splitext(_LIB_PATH)
    return base + suffix + ext


def _host_cpu_id() -> str:
    """Machine + sha256 of the kernel's ISA-flags line — the same derivation
    as scripts/build_native.sh::stamp_content's ``cpu:`` line (keep the two
    identical). Two hosts with the same id accept the same -march=native
    instruction set."""
    line = b""
    try:
        with open("/proc/cpuinfo", "rb") as fh:
            for raw in fh:
                if raw.startswith((b"flags", b"Features")):
                    line = raw
                    break
    except OSError:
        pass
    return f"{platform.machine()} {hashlib.sha256(line).hexdigest()}"


def _read_stamp() -> dict:
    """The plain library's build stamp as {key: value}; {} when absent."""
    try:
        with open(_LIB_PATH + ".stamp", encoding="utf-8") as fh:
            return dict(ln.rstrip("\n").split(": ", 1) for ln in fh
                        if ": " in ln)
    except OSError:
        return {}


def _stale_reason() -> Optional[str]:
    """Why the plain .so on disk must not be loaded as it stands, or None
    when its stamp says it was built on this CPU from the current source.
    Compiler/flag drift is scripts/build_native.sh --if-stale's job
    (scripts/test.sh runs it); the loader checks only what makes a load
    unsafe (foreign CPU) or wrong (other source)."""
    if not os.path.exists(_LIB_PATH):
        return "library not built yet"
    stamp = _read_stamp()
    if not stamp:
        return "library has no build stamp"
    host_cpu = _host_cpu_id()
    if stamp.get("cpu") != host_cpu:
        return (f"library was built for another CPU ({stamp.get('cpu')}; "
                f"this host is {host_cpu})")
    if os.path.exists(_SRC_PATH):
        with open(_SRC_PATH, "rb") as fh:
            digest = hashlib.sha256(fh.read()).hexdigest()
        if stamp.get("source") != digest:
            return "library predates native/hivemall_native.cpp"
    return None


def _build(reason: str) -> bool:
    """Build the plain library for this host (first use). False, with
    ``_load_error`` naming why, when there is no compiler/build script or
    the build fails."""
    global _load_error, _built_because
    import warnings

    have_toolchain = shutil.which("g++") and os.path.exists(_BUILD_SCRIPT) \
        and os.path.exists(_SRC_PATH)
    if have_toolchain:
        proc = subprocess.run(["bash", _BUILD_SCRIPT], cwd=_REPO_ROOT,
                              capture_output=True, text=True)
        if proc.returncode == 0:
            _built_because = reason
            return True
        detail = f"scripts/build_native.sh failed: {proc.stderr.strip()[-300:]}"
    else:
        detail = "no g++ / build script to rebuild it"
    _load_error = f"{reason}; {detail}"
    if have_toolchain or os.path.exists(_LIB_PATH):
        # loud unless this is the quiet pure-Python deployment (nothing to
        # load and nothing to build with)
        warnings.warn(f"hivemall_tpu.native: {_LIB_PATH} not loaded "
                      f"({_load_error}); using Python fallbacks")
    return False


def build_info() -> dict:
    """Which library this process uses and how it came to be — the
    provenance chip_smoke.py reports: path, whether it loaded, why it was
    (re)built at first use (None: the stamp already matched this host),
    and the stamp (compiler, flags, cpu, source hash)."""
    loaded = _load() is not None
    return {"path": _so_path(), "loaded": loaded,
            "built_at_first_use": _built_because,
            "load_error": _load_error, "stamp": _read_stamp(),
            "host_cpu": _host_cpu_id()}


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        return None
    path = _so_path()
    if path is None:
        return None
    if path == _LIB_PATH:
        stale = _stale_reason()
        if stale is not None and not _build(stale):
            return None
    elif not os.path.exists(path):
        return None
    try:
        lib = ctypes.CDLL(path)
        _bind_core(lib)
    except (OSError, AttributeError) as e:
        # a built .so that cannot load on THIS host (toolchain/libstdc++
        # mismatch — OSError) or that predates a core symbol
        # (AttributeError from the prototype binding, including a stale
        # build without hm_plan_abi_version) is the same situation as an
        # unbuilt one: fall back to the Python implementations
        # (identical semantics), once, loudly
        _load_error = str(e)
        import warnings

        warnings.warn(f"hivemall_tpu.native: {path} failed to load "
                      f"({e}); using Python fallbacks — rebuild with "
                      f"scripts/build_native.sh")
        return None
    # runtime half of the frozen-ABI contract (G025 is the static half):
    # a .so compiled against a different plan layout must never serve
    from ..ops.scatter import PLAN_ABI_VERSION

    native_ver = int(lib.hm_plan_abi_version())
    if native_ver != PLAN_ABI_VERSION:
        _load_error = (f"plan ABI version mismatch: .so compiled with "
                       f"{native_ver}, Python expects {PLAN_ABI_VERSION}")
        import warnings

        warnings.warn(f"hivemall_tpu.native: {path} failed to load "
                      f"({_load_error}); using Python fallbacks — rebuild "
                      f"with scripts/build_native.sh")
        return None
    _bind_optional(lib)
    _lib = lib
    return lib


def _bind_core(lib: ctypes.CDLL) -> None:
    # the ABI handshake symbol: absent => stale pre-v16 build, and the
    # AttributeError here routes through _load's loud-fallback path
    lib.hm_plan_abi_version.restype = ctypes.c_int64
    lib.hm_plan_abi_version.argtypes = []
    lib.hm_murmur3_x86_32.restype = ctypes.c_int32
    lib.hm_murmur3_x86_32.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                      ctypes.c_uint32]
    lib.hm_murmur3_bulk.restype = None
    lib.hm_murmur3_bulk.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32,
        ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.hm_pack_block.restype = None
    lib.hm_pack_block.argtypes = [ctypes.c_void_p] * 3 + [
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.hm_decode_records.restype = ctypes.c_int64
    lib.hm_decode_records.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.hm_encode_records_bound.restype = ctypes.c_int64
    lib.hm_encode_records_bound.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.hm_encode_records.restype = ctypes.c_int64
    lib.hm_encode_records.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.hm_zigzag_leb128_encode.restype = ctypes.c_int64
    lib.hm_zigzag_leb128_encode.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
    ]
    lib.hm_zigzag_leb128_decode.restype = ctypes.c_int64
    lib.hm_zigzag_leb128_decode.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p,
    ]
    lib.hm_forest_eval.restype = ctypes.c_int64
    lib.hm_forest_eval.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p,
    ]


def _bind_optional(lib: ctypes.CDLL) -> None:
    """Per-symbol guards: these entry points may be absent from older .so
    builds without invalidating the core library. hasattr probes (not
    try/except around the whole block) so every PRESENT symbol gets its
    full prototype declared at load time — no call ever runs on ctypes'
    guessed signature (graftcheck G024's contract)."""
    if hasattr(lib, "hm_lattice_tokenize_bulk"):
        lib.hm_lattice_tokenize_bulk.restype = ctypes.c_int64
        lib.hm_lattice_tokenize_bulk.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
    if hasattr(lib, "hm_arow_reference_rowloop"):
        lib.hm_arow_reference_rowloop.restype = ctypes.c_int64
        lib.hm_arow_reference_rowloop.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_float,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
        ]
    if hasattr(lib, "hm_fm_reference_rowloop"):
        lib.hm_fm_reference_rowloop.restype = ctypes.c_int64
        lib.hm_fm_reference_rowloop.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p,
        ]
    if hasattr(lib, "hm_batch_apply_block"):
        lib.hm_batch_apply_block.restype = ctypes.c_int64
        lib.hm_batch_apply_block.argtypes = [
            ctypes.c_int32, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int32, ctypes.c_void_p,
        ]
    if hasattr(lib, "hm_parse_features_batch"):
        lib.hm_parse_features_batch.restype = ctypes.c_int64
        lib.hm_parse_features_batch.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p,
        ]


def available() -> bool:
    return _load() is not None


def load_error() -> Optional[str]:
    """Why the library is not in use (unbuildable, foreign-CPU build with no
    compiler, toolchain mismatch at load — the PR 11 GLIBCXX pathology), or
    None. Callers that refuse or fall back on unavailability report this so
    the cause is named, never swallowed."""
    _load()
    return _load_error


def has_batch_apply() -> bool:
    """True when the loaded .so exports the batched-apply entry point
    (hm_batch_apply_block) — the -native_apply execution backend's probe."""
    lib = _load()
    return lib is not None and hasattr(lib, "hm_batch_apply_block")


# rule-family ids of hm_batch_apply_block's native closed forms — the ABI's
# rule enum, mirrored (native/hivemall_native.cpp HM_BATCH_RULE_*)
BATCH_APPLY_RULES = {"perceptron": 0, "cw": 1, "arow": 2, "arowh": 3}
# hyperparameters each native form REQUIRES: a missing one must raise like
# the XLA rule's hyper["..."] KeyError would, never default to a silently
# degenerate 0.0 (phi=0 freezes CW entirely)
_BATCH_APPLY_REQUIRED_HYPER = {"perceptron": (), "cw": ("phi",),
                               "arow": ("r",), "arowh": ("r", "c")}


def batch_apply_block(rule_name: str, hyper: dict, values: np.ndarray,
                      labels: np.ndarray, main_plan, tail_plan, dims: int,
                      weights: np.ndarray, covars: Optional[np.ndarray],
                      touched: Optional[np.ndarray]) -> Optional[float]:
    """Apply one staged block through hm_batch_apply_block: the whole
    gather -> batch closed form -> segment-reduce -> scatter-back pass in
    one native call, mutating the host-resident f32 tables in place.

    `main_plan` is the block's stacked StagedDedupPlan ([nb, ...] leading
    axis, core/batch_update.py::BlockPlans.main) or None; `tail_plan` the
    remainder chunk's plan or None. Plans must satisfy the frozen ctypes
    ABI (ops/scatter.py::plan_abi_arrays — int32, C-contiguous); values
    [n_rows, width] f32, labels [n_rows] f32. Returns the block's loss sum,
    or None when the library (or the symbol) is unavailable. Raises on a
    rule outside BATCH_APPLY_RULES or malformed plan/table arguments."""
    lib = _load()
    if lib is None or not hasattr(lib, "hm_batch_apply_block"):
        return None
    if rule_name not in BATCH_APPLY_RULES:
        raise ValueError(f"no native batch closed form for rule "
                         f"{rule_name!r} (supported: "
                         f"{sorted(BATCH_APPLY_RULES)})")
    missing = [h for h in _BATCH_APPLY_REQUIRED_HYPER[rule_name]
               if h not in hyper]
    if missing:
        raise KeyError(f"rule {rule_name!r} requires hyperparameter(s) "
                       f"{missing} — same contract as the XLA rule's "
                       f"hyper[...] access")
    from ..ops.scatter import plan_abi_arrays

    values = np.ascontiguousarray(values, np.float32)
    labels = np.ascontiguousarray(labels, np.float32)
    n_rows, width = values.shape
    if labels.shape != (n_rows,):
        raise ValueError(f"labels shape {labels.shape} != ({n_rows},) for "
                         f"values {values.shape}")
    as_p = lambda a: (a.ctypes.data_as(ctypes.c_void_p)  # noqa: E731
                      if a is not None else None)
    nb = bsz = slots_u = 0
    mo = mls = mrep = mst = men = None
    if main_plan is not None:
        mo, mls, mrep, mst, men = plan_abi_arrays(main_plan, stacked=True)
        nb, lanes = mo.shape
        slots_u = mrep.shape[1]
        bsz = lanes // width
    tail_rows = tail_u = 0
    to = tls = trep = tst = ten = None
    if tail_plan is not None:
        to, tls, trep, tst, ten = plan_abi_arrays(tail_plan)
        tail_rows = to.shape[0] // width
        tail_u = trep.shape[0]
    for name, t, dt in (("weights", weights, np.float32),
                        ("covars", covars, np.float32),
                        ("touched", touched, np.int8)):
        if t is None:
            continue
        if t.dtype != dt or not t.flags["C_CONTIGUOUS"]:
            raise ValueError(f"native batch apply needs C-contiguous "
                             f"{np.dtype(dt).name} {name} table, got "
                             f"{t.dtype}")
        if t.shape[0] < dims:
            # the C pass writes any rp < dims: a short table would be
            # heap corruption, not a drop — fail at the boundary
            raise ValueError(f"{name} table has {t.shape[0]} rows < dims "
                             f"{dims}")
    loss = ctypes.c_double(0.0)
    rc = lib.hm_batch_apply_block(
        BATCH_APPLY_RULES[rule_name],
        ctypes.c_float(float(hyper.get("r", 0.0))),
        ctypes.c_float(float(hyper.get("c", 0.0))),
        ctypes.c_float(float(hyper.get("phi", 0.0))),
        as_p(values), as_p(labels), n_rows, width,
        nb, bsz, slots_u, as_p(mo), as_p(mls), as_p(mrep), as_p(mst),
        as_p(men), tail_rows, tail_u, as_p(to), as_p(tls), as_p(trep),
        as_p(tst), as_p(ten), dims, as_p(weights), as_p(covars),
        as_p(touched), 1,  # the frozen ABI's averaging flag: always on
        ctypes.byref(loss))
    if rc != 0:
        raise ValueError("hm_batch_apply_block rejected its arguments "
                         f"(rc={rc}): rule/plan/table mismatch")
    return float(loss.value)


def murmur3(data: bytes, seed: int = 0x9747B28C) -> Optional[int]:
    lib = _load()
    if lib is None:
        return None
    return int(lib.hm_murmur3_x86_32(data, len(data), seed))


def _pack_bytes(items: Sequence[bytes]):
    """Concatenate byte strings into (ctypes buffer, int64 offsets[n+1]) —
    the marshalling shape every bulk string entry point shares."""
    n = len(items)
    offsets = np.zeros(n + 1, dtype=np.int64)
    for i, s in enumerate(items):
        offsets[i + 1] = offsets[i] + len(s)
    buf = b"".join(items)
    return ctypes.create_string_buffer(buf, len(buf) or 1), offsets


def murmur3_bulk(strings: Sequence[bytes], num_features: int,
                 seed: int = 0x9747B28C) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    n = len(strings)
    cbuf, offsets = _pack_bytes(strings)
    out = np.empty(n, dtype=np.int64)
    lib.hm_murmur3_bulk(
        ctypes.cast(cbuf, ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p), n, seed, num_features,
        out.ctypes.data_as(ctypes.c_void_p))
    return out


def decode_records(body: bytes, n_rows: int):
    """Decode a HMTR1 shard body -> (row_offsets, indices, values, labels),
    or None without the library."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(body, dtype=np.uint8)
    total = lib.hm_decode_records(buf.ctypes.data_as(ctypes.c_void_p), len(body),
                                  n_rows, None, None, None, None)
    if total < 0:
        raise ValueError("corrupt record shard")
    offsets = np.empty(n_rows + 1, np.int64)
    indices = np.empty(total, np.int64)
    values = np.empty(total, np.float32)
    labels = np.empty(n_rows, np.float32)
    out = lib.hm_decode_records(
        buf.ctypes.data_as(ctypes.c_void_p), len(body), n_rows,
        offsets.ctypes.data_as(ctypes.c_void_p),
        indices.ctypes.data_as(ctypes.c_void_p),
        values.ctypes.data_as(ctypes.c_void_p),
        labels.ctypes.data_as(ctypes.c_void_p))
    if out != total:
        raise ValueError("corrupt record shard")
    return offsets, indices, values, labels


def encode_records(idx_rows: Sequence[np.ndarray],
                   val_rows: Sequence[np.ndarray],
                   labels: np.ndarray) -> Optional[bytes]:
    """Encode rows to an HMTR1 shard body (sorting each row by feature id),
    or None without the library. Raises on nnz > 255 / negative ids."""
    lib = _load()
    if lib is None:
        return None
    n = len(idx_rows)
    if len(val_rows) != n or len(labels) != n:
        raise ValueError("idx_rows/val_rows/labels length mismatch")
    offsets = np.zeros(n + 1, dtype=np.int64)
    for i, r in enumerate(idx_rows):
        if len(val_rows[i]) != len(r):
            raise ValueError(f"row {i}: {len(r)} indices vs "
                             f"{len(val_rows[i])} values")
        offsets[i + 1] = offsets[i] + len(r)
    indices = (np.ascontiguousarray(
        np.concatenate(idx_rows).astype(np.int64, copy=False)) if n else
        np.zeros(0, np.int64))
    values = (np.ascontiguousarray(
        np.concatenate(val_rows).astype(np.float32, copy=False)) if n else
        np.zeros(0, np.float32))
    labs = np.ascontiguousarray(labels, dtype=np.float32)
    cap = int(lib.hm_encode_records_bound(
        offsets.ctypes.data_as(ctypes.c_void_p), n))
    out = np.empty(max(cap, 1), dtype=np.uint8)
    written = lib.hm_encode_records(
        indices.ctypes.data_as(ctypes.c_void_p),
        values.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p),
        labs.ctypes.data_as(ctypes.c_void_p), n,
        out.ctypes.data_as(ctypes.c_void_p), cap)
    if written < 0:
        raise ValueError("row nnz > 255 or negative feature id")
    return out[:written].tobytes()


def zigzag_leb128_encode(values: np.ndarray) -> Optional[bytes]:
    lib = _load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(values, dtype=np.int64)
    cap = 10 * len(vals)
    out = np.empty(max(cap, 1), dtype=np.uint8)
    written = lib.hm_zigzag_leb128_encode(
        vals.ctypes.data_as(ctypes.c_void_p), len(vals),
        out.ctypes.data_as(ctypes.c_void_p), cap)
    if written < 0:
        raise ValueError("zigzag-leb128 encode overflow")
    return out[:written].tobytes()


def zigzag_leb128_decode(buf: bytes, n: int) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    data = np.frombuffer(buf, dtype=np.uint8)
    out = np.empty(max(n, 1), dtype=np.int64)
    consumed = lib.hm_zigzag_leb128_decode(
        data.ctypes.data_as(ctypes.c_void_p), len(data), n,
        out.ctypes.data_as(ctypes.c_void_p))
    if consumed < 0:
        raise ValueError("corrupt zigzag-leb128 stream")
    return out[:n]


def pack_block(idx_rows: Sequence[np.ndarray], val_rows: Sequence[np.ndarray],
               width: int, dims: int
               ) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    lib = _load()
    if lib is None:
        return None
    n = len(idx_rows)
    offsets = np.zeros(n + 1, dtype=np.int64)
    for i, r in enumerate(idx_rows):
        offsets[i + 1] = offsets[i] + len(r)
    indices = (np.concatenate(idx_rows).astype(np.int64) if n else
               np.zeros(0, np.int64))
    values = (np.concatenate(val_rows).astype(np.float32) if n else
              np.zeros(0, np.float32))
    out_idx = np.empty((n, width), dtype=np.int32)
    out_val = np.empty((n, width), dtype=np.float32)
    out_nnz = np.empty(n, dtype=np.int32)
    lib.hm_pack_block(
        indices.ctypes.data_as(ctypes.c_void_p),
        values.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p), n, width, dims,
        out_idx.ctypes.data_as(ctypes.c_void_p),
        out_val.ctypes.data_as(ctypes.c_void_p),
        out_nnz.ctypes.data_as(ctypes.c_void_p))
    return out_idx, out_val, out_nnz


def forest_eval(programs: Sequence[Tuple[np.ndarray, np.ndarray, np.ndarray]],
                X: np.ndarray) -> Optional[np.ndarray]:
    """Evaluate T compiled opcode programs (vm.compile_script_arrays output)
    over X [N, F] raw rows -> [T, N] leaf values, or None without the
    library. Raises on a malformed program."""
    lib = _load()
    if lib is None:
        return None
    T = len(programs)
    X = np.ascontiguousarray(X, dtype=np.float64)
    N, F = X.shape
    offsets = np.zeros(T + 1, np.int64)
    for t, (ops, _, _) in enumerate(programs):
        offsets[t + 1] = offsets[t] + len(ops)
    ops = np.ascontiguousarray(np.concatenate([p[0] for p in programs]),
                               dtype=np.int8)
    argi = np.ascontiguousarray(np.concatenate([p[1] for p in programs]),
                                dtype=np.int32)
    argf = np.ascontiguousarray(np.concatenate([p[2] for p in programs]),
                                dtype=np.float64)
    out = np.empty((T, N), np.float64)
    rc = lib.hm_forest_eval(
        ops.ctypes.data_as(ctypes.c_void_p), argi.ctypes.data_as(ctypes.c_void_p),
        argf.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p), T,
        X.ctypes.data_as(ctypes.c_void_p), N, F,
        out.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        raise ValueError("malformed opcode program")
    return out


def parse_features_bulk(rows: Sequence[Sequence[str]], num_features: int
                        ) -> Optional[Tuple[List[np.ndarray], List[np.ndarray]]]:
    """Bulk-parse rows of "name[:value]" tokens through the C parser
    (hm_parse_features_batch): one concatenated buffer in, flat idx/val
    arrays out, re-split per row. Returns None when the .so is absent or a
    token falls outside the canonical grammar (caller uses the Python
    parser, keeping error behavior and exotic-literal handling identical)."""
    lib = _load()
    if lib is None or not hasattr(lib, "hm_parse_features_batch"):
        return None
    toks: List[bytes] = []
    row_lens = np.empty(len(rows), dtype=np.int64)
    for r, row in enumerate(rows):
        row_lens[r] = len(row)
        for t in row:
            if type(t) is not str:
                return None  # (name, value) tuples etc. -> Python path
            if not t.isascii():
                # the C scan can't see Unicode-NUMERIC names that Python's
                # int() would direct-index (e.g. Arabic-Indic digits, nbsp
                # + digits); decline those precisely — ordinary non-ASCII
                # names (no decimals/whitespace) stay on the fast path
                name = t.split(":", 1)[0]
                if any(ch.isdecimal() or ch.isspace() for ch in name):
                    return None
            toks.append(t.encode("utf-8"))
    n = len(toks)
    cbuf, offsets = _pack_bytes(toks)
    out_idx = np.empty(n, dtype=np.int64)
    out_val = np.empty(n, dtype=np.float32)
    rc = lib.hm_parse_features_batch(
        ctypes.cast(cbuf, ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p), n, num_features,
        out_idx.ctypes.data_as(ctypes.c_void_p),
        out_val.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        return None
    bounds = np.zeros(len(rows) + 1, dtype=np.int64)
    np.cumsum(row_lens, out=bounds[1:])
    idx_rows = [out_idx[bounds[r]:bounds[r + 1]] for r in range(len(rows))]
    val_rows = [out_val[bounds[r]:bounds[r + 1]] for r in range(len(rows))]
    return idx_rows, val_rows


def arow_reference_rowloop(idx: np.ndarray, val: np.ndarray,
                           labels: np.ndarray, dims: int, r: float = 0.1,
                           state: Optional[dict] = None,
                           track_touched: bool = False) -> Optional[int]:
    """Run the reference's per-row AROW hot loop (C transliteration of
    AROWClassifierUDTF.java:99-150 + DenseModel.java:193-201 set
    bookkeeping) over [n_rows, width] gathered blocks. This is the MEASURED
    anchor for vs_baseline (VERDICT r3 missing #2): one sequential mapper's
    row loop with the JVM's parse/boxing costs excluded (flattering the
    reference). Mutates/allocates flat model arrays in `state` (reused
    across calls when passed); returns margin-violation count, or None
    without the library.

    `track_touched`: maintain a monotone uint8 `state["touch"]` was-ever-
    set flag per feature — the -native_scan backend's model-emission mask
    (clocks/deltas wrap like the reference's short/byte counters and can
    NOT serve as touched). Anchor measurements leave it off so the timed
    loop stays the pure reference transliteration."""
    lib = _load()
    if lib is None or not hasattr(lib, "hm_arow_reference_rowloop"):
        return None
    n_rows, width = idx.shape
    if state is None:
        state = {}
    if "w" not in state:
        state["w"] = np.zeros(dims, np.float32)
        state["cov"] = np.ones(dims, np.float32)
        state["clocks"] = np.zeros(dims, np.int16)
        state["deltas"] = np.zeros(dims, np.int8)
    if track_touched and "touch" not in state:
        state["touch"] = np.zeros(dims, np.uint8)
    idx = np.ascontiguousarray(idx, np.int32)
    val = np.ascontiguousarray(val, np.float32)
    labels = np.ascontiguousarray(labels, np.float32)
    as_p = lambda a: a.ctypes.data_as(ctypes.c_void_p)  # noqa: E731
    return int(lib.hm_arow_reference_rowloop(
        as_p(idx), as_p(val), as_p(labels), n_rows, width,
        ctypes.c_float(r), as_p(state["w"]), as_p(state["cov"]),
        as_p(state["clocks"]), as_p(state["deltas"]),
        as_p(state["touch"]) if track_touched else None))


def fm_reference_rowloop(idx: np.ndarray, val: np.ndarray,
                         labels: np.ndarray, dims: int, k: int = 5,
                         eta: float = 0.05, lam: float = 0.01,
                         state: Optional[dict] = None,
                         track_touched: bool = False) -> Optional[int]:
    """Run the reference's per-row train_fm (classification) hot loop (C
    transliteration of FactorizationMachineUDTF.java:369-393 trainTheta;
    fixed eta, defaults eta0=0.05 lambda=0.01 per FMHyperParameters.java:
    30-70) — the measured train_fm anchor, and (with `track_touched`) the
    -native_scan FM backend body. Returns sign-error count, or None
    without the library."""
    lib = _load()
    if lib is None or not hasattr(lib, "hm_fm_reference_rowloop"):
        return None
    n_rows, width = idx.shape
    if state is None:
        state = {}
    if "w" not in state:
        rng = np.random.RandomState(42)
        state["w0"] = np.zeros(1, np.float32)
        state["w"] = np.zeros(dims, np.float32)
        # sigma=0.1 gaussian rankinit like the reference default
        state["V"] = (0.1 * rng.randn(dims, k)).astype(np.float32)
    if track_touched and "touch" not in state:
        state["touch"] = np.zeros(dims, np.uint8)
    idx = np.ascontiguousarray(idx, np.int32)
    val = np.ascontiguousarray(val, np.float32)
    labels = np.ascontiguousarray(labels, np.float32)
    as_p = lambda a: a.ctypes.data_as(ctypes.c_void_p)  # noqa: E731
    rc = int(lib.hm_fm_reference_rowloop(
        as_p(idx), as_p(val), as_p(labels), n_rows, width, k,
        ctypes.c_float(eta), ctypes.c_float(lam),
        as_p(state["w0"]), as_p(state["w"]), as_p(state["V"]),
        as_p(state["touch"]) if track_touched else None))
    if rc < 0:
        raise ValueError("fm reference rowloop: k > 64 unsupported")
    return rc


def lattice_tokenize_bulk(cps: np.ndarray, classes: np.ndarray,
                          text_offsets: np.ndarray,
                          surf_buf: np.ndarray, surf_offsets: np.ndarray,
                          entry_offsets: np.ndarray, entry_pos: np.ndarray,
                          entry_cost: np.ndarray, max_word: int,
                          conn: np.ndarray,
                          unk_base: np.ndarray, unk_per: np.ndarray,
                          unk_pos: np.ndarray):
    """Bulk lattice Viterbi (hm_lattice_tokenize_bulk); all marshalling is
    done by the caller (nlp/lattice.py, which owns the lexicon encoding).
    Returns (starts, lens, pos_ids, counts) or None when unavailable."""
    lib = _load()
    if lib is None or not hasattr(lib, "hm_lattice_tokenize_bulk"):
        return None
    # pin every caller-marshalled buffer to the ABI dtype + C order: the
    # native pass reads these at fixed widths, so a strided or
    # wrong-width array here is silent corruption, not an exception
    cps = np.ascontiguousarray(cps, np.uint32)
    classes = np.ascontiguousarray(classes, np.uint8)
    text_offsets = np.ascontiguousarray(text_offsets, np.int64)
    surf_buf = np.ascontiguousarray(surf_buf, np.uint32)
    surf_offsets = np.ascontiguousarray(surf_offsets, np.int64)
    entry_offsets = np.ascontiguousarray(entry_offsets, np.int64)
    entry_pos = np.ascontiguousarray(entry_pos, np.int16)
    entry_cost = np.ascontiguousarray(entry_cost, np.int32)
    conn = np.ascontiguousarray(conn, np.int32)
    unk_base = np.ascontiguousarray(unk_base, np.int32)
    unk_per = np.ascontiguousarray(unk_per, np.int32)
    unk_pos = np.ascontiguousarray(unk_pos, np.int16)
    n_texts = len(text_offsets) - 1
    total_chars = int(text_offsets[-1])
    out_start = np.empty(max(total_chars, 1), np.int32)
    out_len = np.empty(max(total_chars, 1), np.int32)
    out_pos = np.empty(max(total_chars, 1), np.int16)
    out_counts = np.empty(max(n_texts, 1), np.int64)
    as_p = lambda a: a.ctypes.data_as(ctypes.c_void_p)  # noqa: E731
    rc = lib.hm_lattice_tokenize_bulk(
        as_p(cps), as_p(classes), as_p(text_offsets), n_texts,
        as_p(surf_buf), as_p(surf_offsets), as_p(entry_offsets),
        as_p(entry_pos), as_p(entry_cost), len(surf_offsets) - 1,
        int(max_word), as_p(conn), conn.shape[0],
        as_p(unk_base), as_p(unk_per), as_p(unk_pos),
        as_p(out_start), as_p(out_len), as_p(out_pos), as_p(out_counts))
    if rc < 0:
        return None
    return out_start[:rc], out_len[:rc], out_pos[:rc], out_counts
