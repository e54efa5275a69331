#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run is one process: it makes the cell's inputs from the seed, warms up
every shape (set-up), measures for `--seconds`, reads the device's peak
memory, checks what the timed calls produced against the plain reference,
and prints one JSON object as the last line of standard output. With
`--trace 1` the profiler records the window's first ops, as many as the op
kind says (`trace_max_ops`; the window ends there), and the line carries the
per-layer metrics.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for. It never falls back to the CPU: tests drive
`execute()` directly at tiny sizes, which returns the line without printing.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, to the interpreter's own few ms

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import traceback
from types import SimpleNamespace
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, devmem, manifest, work, xplane  # noqa: E402

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
WORK_DIR = os.path.join(ROOT, ".bench_work")


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless the
    environment names one. The path is part of the cache's key."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(jax.config.jax_compilation_cache_dir)


def device_info() -> dict:
    import jax

    devs = jax.local_devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(cell, dev: dict) -> None:
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        sys.stderr.write(
            f"benchmark/run.py measures on a TPU only: JAX reports "
            f"{dev['count']} x {dev['platform']} ({dev['kind']}), the cell "
            f"{cell.name} needs {cell.chips} TPU chip(s). No result.\n")
        raise SystemExit(3)


def make_op(cell, seed: int):
    """The cell's op kind, found by the traffic file's `op` key."""
    mod = importlib.import_module("benchmark.ops." + cell.traffic["op"])
    return mod.Op(cell, seed)


def traced_window(op, seconds, max_calls: int, inspect=None):
    """(window result, xplane.load's trace, inspect(xplane path)) of a window
    run under the profiler; the trace's files live under .bench_work/ only
    while they are read."""
    import jax

    os.makedirs(WORK_DIR, exist_ok=True)
    tdir = tempfile.mkdtemp(prefix="trace_", dir=WORK_DIR)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # a 2^17-row Python loop would flood it
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            result = op.window(seconds, max_calls=max_calls)
        finally:
            jax.profiler.stop_trace()
        seen = inspect(xplane.find_xplane(tdir)) if inspect else None
        return result, xplane.load(tdir), seen
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _read_metrics(entries, cell, ctx) -> dict:
    out = {}
    for m in entries:
        spec = cell.metric_files[m["name"]]
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(cell, seed: int, seconds: float, trace: int, *,
            t0: Optional[float] = None, dev: Optional[dict] = None,
            log=sys.stderr) -> dict:
    """One run, from set-up to the result line (returned, not printed)."""
    t0 = time.perf_counter() if t0 is None else t0
    dev = dev or device_info()
    on_chip = dev["platform"] == "tpu"
    if trace and not on_chip:
        raise RuntimeError("--trace 1 reads the device's trace: no TPU here")
    op = make_op(cell, seed)
    op.setup()
    setup_s = time.perf_counter() - t0
    log.write(f"[bench] set-up {setup_s:.2f} s; after warm-up "
              f"{json.dumps(devmem.figures())}\n")

    reduced = None
    if trace:
        result, loaded, _ = traced_window(op, seconds, op.trace_max_ops)
        reduced = xplane.reduce(loaded)
        if reduced is None:
            raise RuntimeError("the traced span holds no device operation")
    else:
        result = op.window(seconds)
    mem = devmem.figures()
    log.write(f"[bench] window {result['wall_s']:.2f} s, {result['attempted']}"
              f" ops; {json.dumps(mem)}\n")

    checked = op.check()
    limits = cell.config["correct"]["limits"]
    numbers = compare.verdict(checked["numbers"], limits)
    correct = all(n["ok"] for n in numbers.values()) and result["failed"] == 0

    ctx = SimpleNamespace(
        cell=cell, result=result, setup_s=setup_s, trace=reduced, device=dev,
        peaks=work.peaks_for(dev["kind"]) if on_chip else None)
    metrics = _read_metrics(cell.per_layer if trace else cell.end_to_end,
                            cell, ctx)
    device = dict(dev, **{k: mem[k] for k in (
        "memory_peak_bytes", "peak_bytes_in_use", "peak_bytes_reserved",
        "bytes_limit")})
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["notes"] = dict(checked["notes"], window_s=result["wall_s"],
                         memory_read_together=mem["read_together"],
                         **result.get("notes", {}))
    line["numbers"] = numbers
    return line


def print_numbers(numbers: dict, stream) -> None:
    for name, n in numbers.items():
        stream.write(f"compared {name} = {n['value']!r} limit {n['limit']!r} "
                     f"{'ok' if n['ok'] else 'NOT OK'}\n")


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = manifest.resolve(args.workload)
    try:
        dev = device_info()
        require_chips(cell, dev)
        cache = enable_compile_cache()
        sys.stderr.write(f"[bench] {args.workload} seed {args.seed} seconds "
                         f"{args.seconds} trace {args.trace}; compile cache "
                         f"{cache}\n")
        line = execute(cell, args.seed, args.seconds, args.trace, t0=_T0,
                       dev=dev)
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        sys.stderr.write(f"[bench] FAILED; arguments {vars(args)}\n")
        try:
            sys.stderr.write(f"[bench] device memory {devmem.figures()}\n")
        except Exception:  # the device may be what failed
            sys.stderr.write("[bench] device memory not readable\n")
        sys.stderr.flush()
        return 1
    sys.stderr.flush()
    print_numbers(line["numbers"], sys.stderr)
    sys.stderr.flush()
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
