"""Reduction from a profiler trace (`*.xplane.pb`) to the numbers the readers
use. Two stages, so that the second can be checked on a small recorded trace:

1. `load(trace_dir)`: planes -> plain lists. Device planes are those named
   `/device:TPU:<n>`; their `XLA Ops` line holds one event per executed HLO
   op, their `XLA Modules` line one per executed program. Host marks are the
   benchmark's own `TraceAnnotation`s (names starting `bench:`), found on any
   host line. All times are nanoseconds on the profiler's one clock.
2. `reduce(trace)`: busy union, idle gaps attributed to the host mark they
   fall in, op time by class, top ops.

Op classes. The learners' steps are gathers, scatters and full-table passes.
The TPU compiler names an op event by its whole HLO line and emits gathers
and scatters as `kind=kCustom` fusions with no such word in them, so an op is
classed by what its line says (`classify`): the words, or else the operands.
HLO orders a gather's operands (table, indices) and a scatter's (table,
indices, updates), and a scatter into zeros keeps (indices, updates): a
custom fusion that takes an integer array is a `scatter` if a non-scalar
float operand FOLLOWS the integer one, else a `gather`. Everything else on
the device is `dense`: the elementwise fusions, copies, broadcasts, slices
and zero-fills whose cost grows with the table, not with the batch. Scope
names inside the program would replace this reading (PERF.md, section 7).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench:"
# container ops whose interval covers their children's
_CONTAINERS = re.compile(r"^(while|conditional|call)(\.|$)")


_OPERAND = re.compile(r"\b(pred|[suf]\d+|bf16)\[([0-9,]*)\]")


def _operands(line: str):
    """(type, is_scalar) of each array operand in an HLO op's argument list."""
    start = line.find("(", line.find(" = ") + 3)
    # a tuple-typed result opens with "(": the call's "(" follows the op word
    m = re.search(r"\)?\s([a-z][\w\-]*)\(", line[line.find(" = "):])
    if m:
        start = line.find(" = ") + m.end() - 1
    depth, end = 0, len(line)
    for i in range(start, len(line)):
        depth += line[i] == "("
        depth -= line[i] == ")"
        if depth == 0:
            end = i
            break
    return [(t, dims == "") for t, dims in _OPERAND.findall(line[start:end])]


def classify(name: str, text: str = "") -> str:
    hay = (name + " " + text).lower()
    if "scatter" in hay:
        return "scatter"
    if "gather" in hay:
        return "gather"
    if "kind=kcustom" in hay and " fusion(" in name:
        ops = _operands(name)
        ints = [i for i, (t, scalar) in enumerate(ops)
                if t[0] in "su" and not scalar]
        if ints:
            after = ops[ints[0] + 1:]
            if any(t[0] in "fb" and not scalar for t, scalar in after):
                return "scatter"
            return "gather"
    return "dense"


def short_name(name: str, limit: int = 96) -> str:
    """`%fusion.3 fusion f32[268435456]` from an op event's whole HLO line."""
    m = re.match(r"^(%?[\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])[^ ]* .*?"
                 r"\b([a-z][\w\-]*)\(", name)
    out = f"{m.group(1)} {m.group(3)} {m.group(2).lstrip('(')}" if m else name
    return out[:limit]


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load(trace_dir: str) -> dict:
    """{"devices": {ordinal: {"ops": [[name, start, dur, cls]], "modules":
    [[name, start, dur]]}}, "marks": [[name, start, dur]]}"""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    marks: List[list] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        if _CONTAINERS.match(ev.name):
                            continue
                        dev["ops"].append([ev.name, float(ev.start_ns),
                                           float(ev.duration_ns),
                                           classify(ev.name)])
                elif line.name == MODULES_LINE:
                    for ev in line.events:
                        dev["modules"].append([ev.name, float(ev.start_ns),
                                               float(ev.duration_ns)])
            devices[m.group(1)] = dev
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(MARK_PREFIX):
                        marks.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    marks.sort(key=lambda e: e[1])
    return {"devices": devices, "marks": marks}


def union_intervals(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if min(e, t1) > max(s, t0)]


def _mark_at(marks, t: float) -> str:
    """Innermost benchmark mark that covers time t."""
    best = None
    for name, s, d in marks:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "bench:outside_calls"


def reduce(trace: dict) -> Optional[dict]:
    """Numbers of the traced span: from the start of the first `bench:call`
    mark to the end of the last. None where no device op ran in it."""
    calls = [m for m in trace["marks"] if m[0] == MARK_PREFIX + "call"]
    devs = trace["devices"]
    if not devs or not any(d["ops"] for d in devs.values()):
        return None
    if calls:
        t0 = calls[0][1]
        t1 = max(s + d for _, s, d in calls)
    else:
        t0 = min(o[1] for d in devs.values() for o in d["ops"])
        t1 = max(o[1] + o[2] for d in devs.values() for o in d["ops"])
    window = t1 - t0
    busy_each, class_ns, op_ns = [], {"gather": 0.0, "scatter": 0.0,
                                      "dense": 0.0}, {}
    gaps_by_mark: Dict[str, float] = {}
    idle_in_calls = 0.0
    for dev in devs.values():
        iv = _clip([(s, s + d) for _, s, d, _ in dev["ops"]], t0, t1)
        merged = union_intervals(iv)
        busy_each.append(sum(e - s for s, e in merged))
        for name, s, d, cls in dev["ops"]:
            if s + d <= t0 or s >= t1:
                continue
            class_ns[cls] += d
            key = f"{short_name(name)} [{cls}]"
            op_ns[key] = op_ns.get(key, 0.0) + d
        # idle gaps lie between the merged busy intervals; each is cut at
        # every mark's edges and at each call's first and last launch, and a
        # piece goes to the innermost mark over it, `bench:call` split into
        # before the first launch, between launches and after the last
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        step_starts = sorted(s for n, s, d in dev["modules"])
        cuts = sorted({x for _, s, d in trace["marks"] for x in (s, s + d)}
                      | {x for c in calls for x in _first_last(c, step_starts)})
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 <= g0:
                continue
            pts = [g0] + [c for c in cuts if g0 < c < g1] + [g1]
            for p0, p1 in zip(pts[:-1], pts[1:]):
                mid = 0.5 * (p0 + p1)
                mark = _mark_at(trace["marks"], mid)
                if mark == MARK_PREFIX + "call":
                    call = next((c for c in calls
                                 if c[1] <= mid <= c[1] + c[2]), None)
                    mark = _call_phase(call, step_starts, mid)
                if mark != "bench:outside_calls":
                    idle_in_calls += p1 - p0
                gaps_by_mark[mark] = gaps_by_mark.get(mark, 0.0) + (p1 - p0)
    n = max(len(busy_each), 1)
    busy = sum(busy_each) / n
    top_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(gaps_by_mark.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window / 1e9,
        "busy_s": busy / 1e9,
        "idle_in_calls_s": idle_in_calls / n / 1e9,
        "class_s": {k: v / n / 1e9 for k, v in class_ns.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v / n / 1e9] for k, v in top_gaps],
        "calls": len(calls),
    }


def _first_last(call, step_starts):
    inside = [s for s in step_starts if call[1] <= s <= call[1] + call[2]]
    return (inside[0], inside[-1]) if inside else ()


def _call_phase(call, step_starts, t: float) -> str:
    """Which part of a `bench:call` the time t lies in, by the program's
    launches inside it: staging before the first, feeding between them,
    draining after the last."""
    if call is None:
        return "bench:call"
    inside = [s for s in step_starts if call[1] <= s <= call[1] + call[2]]
    if not inside or t < inside[0]:
        return "bench:call/before_first_launch"
    if t > inside[-1]:
        return "bench:call/after_last_launch"
    return "bench:call/between_launches"
