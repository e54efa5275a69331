"""G019 cast-inside-loop / materializing-dequant: full-array casts per step.

Two advisory shapes of the same waste, scoped to the hot-path modules
(analysis/dtypeflow.in_hot_scope):

- **cast-inside-loop**: an ``x.astype(...)`` whose receiver no statement
  in the enclosing Python loop rebinds — the cast re-materializes the
  same array every iteration. Hoist it above the loop, or reuse a
  precomputed plan the way ``ops/scatter.py`` builds its sort/segment
  structure once per block and amortizes it over every table.
- **materializing dequant**: an ``astype`` whose receiver is *provably*
  reduced-precision (bf16/f16/int8) and whose target is f32/f64 — a
  full widened copy of a quantized array. The dequant-free serving
  contract wants the cast fused per-tile/per-window inside the consuming
  loop (widen the gathered window only), not a whole-table
  materialization that erases the bandwidth the quantization bought.

Both are warnings: widening can be the right call (an f32 accumulator),
and the fix is structural — suppress with a rationale where the copy is
deliberate.
"""

from __future__ import annotations

from typing import List, Set

from ..dtypeflow import get_model, in_hot_scope
from ..findings import Finding, Severity
from ..program import ProgramModel

RULE_ID = "G019"


def check_program(program: ProgramModel, scanned: Set[str]
                  ) -> List[Finding]:
    findings: List[Finding] = []
    flow = get_model(program)
    for path in sorted(scanned):
        model = program.modules.get(path)
        if model is None:
            continue
        seen: Set[int] = set()
        for fn in model.functions:
            if not in_hot_scope(path, model, fn):
                continue
            for site in flow.facts(path, fn).casts:
                if site.node.lineno in seen:
                    continue
                if site.loop is not None and site.loop_invariant:
                    seen.add(site.node.lineno)
                    findings.append(Finding(
                        path, site.node.lineno, RULE_ID, Severity.WARNING,
                        "astype of a loop-invariant array inside a Python "
                        "loop — the cast re-materializes the full array "
                        "every iteration; hoist it, or build a reusable "
                        "plan once per block (ops/scatter.py amortizes its "
                        "sort/segment plan over every table exactly this "
                        "way)",
                        model.snippet(site.node.lineno)))
                elif site.receiver_dt is not None \
                        and site.receiver_dt.reduced_float \
                        and site.target_dt is not None \
                        and site.target_dt.wide_float:
                    seen.add(site.node.lineno)
                    findings.append(Finding(
                        path, site.node.lineno, RULE_ID, Severity.WARNING,
                        f"materializing dequant: astype("
                        f"{site.target_dt.name}) of a "
                        f"{site.receiver_dt.name} array copies the whole "
                        f"table widened — cast per-tile/per-window inside "
                        f"the consuming loop (widen the gathered window "
                        f"only) to keep the bandwidth the reduced dtype "
                        f"bought",
                        model.snippet(site.node.lineno)))
    return findings
