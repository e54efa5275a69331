#!/usr/bin/env python3
"""Join the program's own spans with the device trace, for one cell:

    python3 benchmark/tools/span_gaps.py --workload <cell> --seed 1 \
        --calls 2 --out chiprun_out/gaps.json [--dims N --rows N]

Every `Tracer.span` of the program is also a `TraceAnnotation`, so a profile
holds the vocabulary of `hivemall_tpu/runtime/tracing.py` on the profiler's
clock beside the device ops. This tool runs a cell's set-up and a few traced
calls (as `trace_dump.py` does) and reduces the trace to

- device-idle seconds by the ledger's phase (`benchmark/xplane.py`: before
  the first launch, between launches, `bench:emit`, ...) and, inside each,
  by the innermost span that covers them: a program span, else the
  benchmark's own mark;
- device-busy seconds by `hm.*` scope, each instant given to the op that
  started last (ops nest: the sum is the busy time). The profiler's op
  events carry no scope on this chip (their stats are offsets and durations
  only), and their names are the HLO instruction's line without its
  metadata. So an op is joined by its instruction name against the text of
  the same step compiled ahead of time (`lower(...).compile().as_text()`,
  whose lines carry `op_name="jit(minibatch_step)/hm.gather/..."`). What the
  compiler made itself (zero fills, layout copies, the loops that stack
  bf16 tables) carries no `op_name`: such an instruction takes the scope its
  users agree on, else its operands', else its caller's (`scopes_of`), and
  its seconds are also summed apart (`inherited`). The compile cache's key
  leaves metadata out: where the cache holds an executable built before the
  scopes existed, that is the one that runs and the one whose text is read,
  and every op reads `(unscoped)`. Run with a fresh
  `JAX_COMPILATION_CACHE_DIR` to see the scopes;
- per span name, from the tracer's own records of the same calls: count,
  total and self seconds (duration less what child spans cover), and how
  `train.call` / `emit.model_rows` compare with the benchmark's `train_s` /
  `emit_s` (the two clocks).

`reduce()` is plain arithmetic on plain lists; `tests/perf_bench/` checks it
on a hand-made trace and on one recorded with `--dims 65536 --rows 4096`.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from types import SimpleNamespace
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest, run, xplane  # noqa: E402
from benchmark.readers import _program_spans as ps  # noqa: E402

SPAN_PREFIXES = ("train.", "emit.")
SCOPE = re.compile(r"\bhm\.[a-z_]+")
UNSCOPED = "(unscoped)"
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply)=(%[\w.\-]+)")
_REF = re.compile(r"%[\w.\-]+")


# ---- the trace, as plain lists ----

def load(path: str) -> dict:
    """{"devices": {ordinal: {"ops": [[instruction, start, dur, module]],
    "modules": [[name, start, dur]]}}, "spans": [[name, start, dur]]}: the
    device's ops (each with the program it ran in) and the host events that
    are program spans or the benchmark's marks. Nanoseconds, one clock."""
    from jax.profiler import ProfileData

    devices: Dict[str, dict] = {}
    spans: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == xplane.MODULES_LINE:
                    modules = [[ev.name.split("(")[0], float(ev.start_ns),
                                float(ev.duration_ns)] for ev in line.events]
                elif line.name == xplane.OPS_LINE:
                    # loops stay in: `exclusive_ns` gives a loop only the
                    # time no op of its body covers
                    ops = [[ev.name.split(" = ")[0], float(ev.start_ns),
                            float(ev.duration_ns)] for ev in line.events]
            modules.sort(key=lambda e: e[1])
            for op in ops:
                op.append(_module_at(modules, op[1]))
            devices[m.group(1)] = {"ops": ops, "modules": modules}
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES + (xplane.MARK_PREFIX,)):
                        spans.append([ev.name, float(ev.start_ns),
                                      float(ev.duration_ns)])
    spans.sort(key=lambda e: (e[1], -e[2]))
    return {"devices": devices, "spans": spans}


def _module_at(modules: List[list], t: float) -> str:
    for name, s, d in modules:
        if s <= t <= s + d:
            return name
    return "(no program)"


def scopes_of(hlo_text: str) -> Dict[str, List[str]]:
    """{instruction name: [scope, how]} from a compiled step's text. `how`
    is `own` where the instruction's `op_name` holds an `hm.*` scope; else
    the scope is inherited, to a fixed point: `user` (its users all have one
    scope), `caller` (of the instruction that calls the computation it is
    in), `operand` (its operands all have one scope); else `(unscoped)`."""
    comp_of, operands, scope, caller = {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.group(1), line[m.end():]
        callees = _CALLEE.findall(rest)
        for c in callees:
            caller[c] = name
        comp_of[name] = comp
        operands[name] = [r for r in _REF.findall(rest.split(", metadata=")[0])
                          if r not in callees]
        op_name = _OP_NAME.search(rest)
        own = SCOPE.search(op_name.group(1)) if op_name else None
        if own:
            scope[name] = [own.group(0), "own"]
    users: Dict[str, List[str]] = {}
    for name, ops in operands.items():
        for o in ops:
            users.setdefault(o, []).append(name)

    def agreed(near) -> Optional[str]:
        """The one scope of `near`, if each of them has one."""
        near = [n for n in near if n in comp_of]
        seen = {scope[n][0] for n in near if n in scope}
        full = near and all(n in scope for n in near)
        return seen.pop() if full and len(seen) == 1 else None

    # a table is read by one stage and written by another, so a rule waits
    # until every neighbour it looks at has a scope: no guess from a part
    rules = (("user", lambda n: users.get(n, ())),
             ("caller", lambda n: [caller.get(comp_of[n])]),
             ("operand", lambda n: operands[n]))
    changed = True
    while changed:
        changed = False
        for how, near in rules:
            for name in comp_of:
                found = None if name in scope else agreed(near(name))
                if found:
                    scope[name] = [found, how]
                    changed = True
            if changed:
                break   # the earlier rule again before a later one
    return {name: scope.get(name, [UNSCOPED, "none"]) for name in comp_of}


def exclusive_ns(ops: List[tuple]) -> Dict[object, float]:
    """{key: nanoseconds} of `(start, end, key)` intervals, each instant
    given to the interval that started last among those open: nested ops
    (a loop's body inside the loop) count once."""
    out: Dict[object, float] = {}
    stack: List[tuple] = []
    t = 0.0
    for s, e, key in sorted(ops, key=lambda o: o[:2]) + [(float("inf"),) * 3]:
        while stack and t < s:
            _, top_e, top_key = stack[-1]
            upto = min(top_e, s)
            if upto > t:
                out[top_key] = out.get(top_key, 0.0) + upto - t
                t = upto
            if top_e <= t:
                stack.pop()
        t = max(t, s)
        stack.append((s, e, key))
    return out


# ---- the reduction ----

def _innermost(spans: List[list], t: float) -> str:
    best = None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "bench:outside_calls"


def reduce(trace: dict, step_scopes: Optional[Dict[str, dict]] = None,
           top: int = 16) -> Optional[dict]:
    """Idle seconds by ledger phase and innermost span, busy seconds by
    scope, over the span from the first `bench:call` mark's start to the
    last one's end (`xplane.reduce`'s span). `step_scopes` maps a program's
    name to `scopes_of` its text; an op of a program not in it goes under
    `program:<name>`."""
    step_scopes = step_scopes or {}
    marks = [s for s in trace["spans"] if s[0].startswith(xplane.MARK_PREFIX)]
    calls = [m for m in marks if m[0] == xplane.MARK_PREFIX + "call"]
    devs = trace["devices"]
    if not calls or not any(d["ops"] for d in devs.values()):
        return None
    t0, t1 = calls[0][1], max(s + d for _, s, d in calls)
    n = len(devs)
    busy = 0.0
    by_op: Dict[tuple, float] = {}
    by_phase: Dict[str, Dict[str, float]] = {}
    for dev in devs.values():
        clipped = [(max(s, t0), min(s + d, t1), (module, name))
                   for name, s, d, module in dev["ops"]
                   if s + d > t0 and s < t1]
        merged = xplane.union_intervals([c[:2] for c in clipped])
        busy += sum(e - s for s, e in merged)
        for key, ns in exclusive_ns(clipped).items():
            by_op[key] = by_op.get(key, 0.0) + ns
        launches = sorted(s for _, s, _ in dev["modules"])
        cuts = sorted({x for _, s, d in trace["spans"] for x in (s, s + d)}
                      | {x for c in calls
                         for x in xplane._first_last(c, launches)})
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            pts = [g0] + [c for c in cuts if g0 < c < g1] + [g1]
            for p0, p1 in zip(pts[:-1], pts[1:]):
                mid = 0.5 * (p0 + p1)
                phase = xplane._mark_at(marks, mid)
                if phase == xplane.MARK_PREFIX + "call":
                    call = next(c for c in calls if c[1] <= mid <= c[1] + c[2])
                    phase = xplane._call_phase(call, launches, mid)
                inner = by_phase.setdefault(phase, {})
                span = _innermost(trace["spans"], mid)
                inner[span] = inner.get(span, 0.0) + (p1 - p0)
    sec = lambda ns: ns / n / 1e9
    by_scope: Dict[str, float] = {}
    inherited: Dict[str, float] = {}
    ops_out = []
    for (module, name), ns in sorted(by_op.items(), key=lambda kv: -kv[1]):
        scope, how = (step_scopes[module].get(name, [UNSCOPED, "none"])
                      if module in step_scopes
                      else ["program:" + module, "own"])
        by_scope[scope] = by_scope.get(scope, 0.0) + ns
        if how not in ("own", "none"):
            inherited[scope] = inherited.get(scope, 0.0) + ns
        if len(ops_out) < top:
            ops_out.append([module, name, scope, how, sec(ns)])
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sec(busy),
        "calls": len(calls),
        "busy_by_scope_s": {k: sec(v) for k, v in by_scope.items()},
        "inherited_s": {k: sec(v) for k, v in inherited.items()},
        "top_ops": ops_out,
        "idle_by_phase_s": {
            phase: {"total": sec(sum(inner.values())),
                    "by_span": {k: sec(v) for k, v in sorted(
                        inner.items(), key=lambda kv: -kv[1])}}
            for phase, inner in sorted(
                by_phase.items(), key=lambda kv: -sum(kv[1].values()))},
    }


def span_table(result: dict) -> Optional[dict]:
    """Per span name, from the tracer's records of the window's calls:
    count, total and self seconds; and the tracer's `train.call` and
    `emit.model_rows` totals beside the benchmark's own clock."""
    spans = ps.window_spans(SimpleNamespace(result=result))
    if spans is None:
        return None
    names = sorted({s["name"] for s in spans})
    table = {name: {"count": len(ps.named(spans, name)),
                    "total_s": ps.total_ms(ps.named(spans, name)) / 1e3,
                    "self_s": ps.self_ms(spans, name) / 1e3}
             for name in names}
    calls = result["calls"]
    return {"spans": table, "clocks": {
        "train.call_s": table["train.call"]["total_s"],
        "bench_train_s": sum(c["train_s"] for c in calls),
        "emit.model_rows_s": table.get("emit.model_rows", {}).get("total_s"),
        "bench_emit_s": sum(c["emit_s"] for c in calls)}}


# ---- the step's text, compiled ahead of time at the cell's shapes ----

def _arow_step(cfg: dict, dims: int):
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.core.engine import make_train_step
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW

    # fit_linear's own rule for the tables' storage
    dtype = jnp.bfloat16 if dims > (1 << 24) else jnp.float32
    state = jax.eval_shape(lambda: init_linear_state(
        dims, use_covariance=True, dtype=dtype))
    return make_train_step(AROW, {"r": cfg["reference_args"]["r"]}), state, ()


def _fm_step(cfg: dict, dims: int):
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step

    hyper = FMHyper(factors=cfg["factors"], classification=True)
    state = jax.eval_shape(lambda: init_fm_state(dims, hyper))
    va = jax.ShapeDtypeStruct((cfg["mini_batch"],), jnp.float32)
    return make_fm_step(hyper, "minibatch"), state, (va,)


STEPS = {"train_arow": _arow_step, "train_fm": _fm_step}


def step_scopes(cfg: dict, width: int = 64) -> Dict[str, dict]:
    """{"jit_<step>": scopes_of(its compiled text)} for the cell's entry
    point, or {} (said on stderr) for one this tool cannot rebuild."""
    import jax
    import jax.numpy as jnp

    build = STEPS.get(cfg["entry_point"])
    if build is None:
        sys.stderr.write(f"[span_gaps] no step builder for "
                         f"{cfg['entry_point']}: ops go by program only\n")
        return {}
    step, state, extra = build(cfg, int(cfg["num_features"]))
    b = int(cfg["mini_batch"])
    block = (jax.ShapeDtypeStruct((b, width), jnp.int32),
             jax.ShapeDtypeStruct((b, width), jnp.float32),
             jax.ShapeDtypeStruct((b,), jnp.float32)) + extra
    text = step.lower(state, *block).compile().as_text()
    return {"jit_" + step.__name__: scopes_of(text)}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--calls", type=int, default=2)
    p.add_argument("--out", required=True)
    p.add_argument("--dims", type=int)
    p.add_argument("--rows", type=int)
    args = p.parse_args()
    cell = manifest.resolve(args.workload)
    if args.dims:
        cell.config["options"] = cell.config["options"].replace(
            str(cell.config["num_features"]), str(args.dims))
        cell.config["num_features"] = args.dims
    if args.rows:
        cell.traffic["rows_per_call"] = args.rows
    run.require_chips(cell, run.device_info())
    run.enable_compile_cache()
    op = run.make_op(cell, args.seed)
    op.setup()
    result, _, loaded = run.traced_window(op, None, args.calls, inspect=load)
    scopes = step_scopes(cell.config)
    out = {"what": " ".join(sys.argv), "device": run.device_info(),
           "result": {k: v for k, v in result.items() if k != "calls"},
           "calls": result["calls"],
           "reduced": reduce(loaded, scopes), "program": span_table(result),
           "step_scopes": scopes}
    if args.dims:   # small enough to keep: a recorded trace for the tests
        out["loaded"] = loaded
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(out, f)
    print(json.dumps({k: out[k] for k in ("what", "reduced", "program")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
