"""What the readers of the program's own spans share: the spans that
`hivemall_tpu.runtime.tracing.TRACER` committed inside the window, and sums
over them. No metric file names this module.

The window is `[calls[0].t0, calls[-1].t1]` of `result["calls"]`, on
`time.perf_counter`, which is the clock the tracer stamps its spans with (the
profiler's clock holds the same spans as `TraceAnnotation`s; that join is
`benchmark/tools/span_gaps.py`'s). A program without the vocabulary (a parent
commit, another op kind) has no `train.call` span there: every reader then
returns nothing.
"""

from __future__ import annotations

import sys
from typing import Callable, List, Optional

CALL = "train.call"
_KEY = "_program_spans"


def window_spans(ctx) -> Optional[List[dict]]:
    """Every committed span that starts inside the window, or None where the
    window holds no `train.call`. Kept on `ctx`, so eight readers walk the
    tracer's ring once."""
    if hasattr(ctx, _KEY):
        return getattr(ctx, _KEY)
    spans = None
    calls = ctx.result.get("calls")
    if calls:
        from hivemall_tpu.runtime.tracing import TRACER

        if not TRACER.enabled:
            sys.stderr.write("[bench] the program's tracer is disabled "
                             "(HIVEMALL_TPU_TRACE=0): no program_span or "
                             "program_counter metric is read\n")
        else:
            t0, t1 = calls[0]["t0"] * 1e6, calls[-1]["t1"] * 1e6
            inside = [s for t in TRACER.traces() for s in t["spans"]
                      if t0 <= s["start_us"] <= t1]
            if any(s["name"] == CALL for s in inside):
                spans = inside
    setattr(ctx, _KEY, spans)
    return spans


def named(spans: List[dict], name: str,
          where: Optional[Callable[[dict], bool]] = None) -> List[dict]:
    return [s for s in spans
            if s["name"] == name and (where is None or where(s["args"]))]


def total_ms(spans: List[dict]) -> float:
    return sum(s["dur_us"] for s in spans) / 1e3


def arg_sum(spans: List[dict], key: str) -> float:
    return float(sum(s["args"].get(key, 0) for s in spans))


def self_ms(spans: List[dict], name: str) -> float:
    """Summed self time of the spans called `name`: each one's duration less
    the part of its interval that its child spans cover (their union)."""
    out = 0.0
    for s in named(spans, name):
        lo, hi = s["start_us"], s["start_us"] + s["dur_us"]
        covered, edge = 0.0, lo
        for c in sorted((c for c in spans if c["parent_id"] == s["span_id"]),
                        key=lambda c: c["start_us"]):
            a = max(c["start_us"], edge)
            b = min(c["start_us"] + c["dur_us"], hi)
            if b > a:
                covered += b - a
                edge = b
        out += s["dur_us"] - covered
    return out / 1e3


def ratio(num: float, den: float) -> Optional[float]:
    return num / den if den > 0 else None
