"""graftcheck — a JAX/TPU-aware static analysis pass for this codebase.

The first five bench rounds in a row lost throughput to
*silent* Python-side hazards — retracing, implicit device->host syncs,
accidental float64 promotion — never to kernel bugs. graftcheck is the gate:
an AST analyzer purpose-built for the repo's JAX idioms, runnable as

    python -m hivemall_tpu.analysis [paths]

and wired into tier-1 CI (scripts/lint.sh, tests/test_graftcheck.py).

Rules (see docs/static_analysis.md for the full contract):

- G001 recompile-hazard     — Python control flow on traced values,
                              shape-derived f-strings/keys in jitted fns,
                              jax.jit built inside hot loops, non-literal
                              static_argnums.
- G002 host-sync-in-hot-loop — .item()/float()/int()/np.asarray/.tolist()
                              on device values inside the per-step loops of
                              the hot-path modules; per-element device_get.
- G003 dtype-drift          — np.float64 and bare float literals in update
                              math (the bf16-above-2^24 policy of
                              models/base.py must not silently upcast).
- G004 axis-name-mismatch   — psum/pmean/all_gather axis names checked
                              against the mesh axes of parallel/mesh.py.
- G005 donation-misuse      — step-shaped jit wrappers missing
                              donate_argnums; reads of a donated argument
                              after the donating call.
- G006 untraced-side-effect — print/metrics/time/np.random and free-variable
                              mutation inside traced functions.

Suppress a single line with `# graftcheck: disable=G00X[,G00Y]` (or
`disable=all`); accepted pre-existing findings live in
``hivemall_tpu/analysis/baseline.json`` and are refreshed with
``python -m hivemall_tpu.analysis --update-baseline``.

Runtime companion: ``hivemall_tpu.runtime.metrics.recompile_guard`` counts
jit cache misses per named step function and exports them on ``/metrics``,
so G001 claims are verifiable on hardware.
"""

from .findings import Finding, Severity
from .runner import analyze_paths, analyze_source
from .baseline import load_baseline, diff_against_baseline, write_baseline

__all__ = [
    "Finding",
    "Severity",
    "analyze_paths",
    "analyze_source",
    "load_baseline",
    "diff_against_baseline",
    "write_baseline",
]
