"""The native batched-apply execution backend (`-batch B -native_apply`).

The segment-sum batch backend (core/batch_update.py) removed the sort and
compacted the scatter, but its last scatter still runs through XLA:CPU's
element-at-a-time scatter engine (~15 M elt/s measured on the bench
host) — a per-element cost the hardware doesn't require. This backend
hands the SAME `StagedDedupPlan` (verbatim — the frozen ctypes ABI in
ops/scatter.py::plan_abi_arrays) to one vectorized C++ pass per block
(native/hivemall_native.cpp::hm_batch_apply_block): gather the U unique
rows from host-resident f32 tables, evaluate the rule's batch closed form
with margin/violation masks computed natively, segment-reduce the B*K
lanes, and scatter-add back — plain contiguous loops the compiler
vectorizes, with the table walk sequential (plan reps ascend). This is
the terascale-system play (PAPERS.md, Agarwal et al.): eliminate
per-element host overhead on the sparse-update hot loop.

Semantics are the batch backend's exactly (the engine's minibatch
accumulate-then-apply, count-averaged): float tables equal up to
reduction order (tolerance-pinned by tests/test_native_batch.py),
touched EXACT. Supported rule families are the native closed forms —
perceptron / CW / AROW / AROWh (native.BATCH_APPLY_RULES); everything
else, a missing .so, or bf16 table storage falls back LOUDLY to the XLA
batch path (models/base.py warns with the reason — never silently).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .. import native
from .batch_update import BlockPlans
from .engine import Rule

# rule capabilities the native pass implements; anything beyond
# (optimizer slots, derive_w recomputation, scalar globals, DELTA_SLOT
# tracking) has no native form and must fall back to the XLA batch path
_NATIVE_RULE_NAMES = frozenset(native.BATCH_APPLY_RULES)


def native_batch_unsupported_reason(rule: Rule,
                                    table_dtype_is_f32: bool = True,
                                    track_deltas: bool = False
                                    ) -> Optional[str]:
    """Why `-native_apply` cannot serve this configuration, or None when
    it can. The reason string is what models/base.py puts in its fallback
    warning — a mismatch is always REPORTED, never swallowed."""
    if not native.available():
        err = native.load_error()
        return ("native library unavailable"
                + (f" ({err})" if err else " (not built)")
                + " — bash scripts/build_native.sh")
    if not native.has_batch_apply():
        return ("libhivemall_native.so predates hm_batch_apply_block — "
                "rebuild with scripts/build_native.sh")
    if rule.name not in _NATIVE_RULE_NAMES:
        return (f"rule {rule.name!r} has no native batch closed form "
                f"(supported: {sorted(_NATIVE_RULE_NAMES)})")
    if rule.slot_names or rule.derive_w is not None or rule.global_names \
            or rule.pre_batch is not None or rule.pre_row is not None:
        return (f"rule {rule.name!r} carries optimizer slots/globals the "
                "native pass does not implement")
    if track_deltas:
        return "DELTA_SLOT tracking has no native form"
    if not table_dtype_is_f32:
        return ("bf16 table storage (dims > 2^24 without "
                "-disable_halffloat) has no native form; tables must be "
                "f32")
    return None


def init_native_tables(dims: int, use_covariance: bool,
                       initial_weights: Optional[np.ndarray] = None,
                       initial_covars: Optional[np.ndarray] = None) -> dict:
    """Host-resident f32 tables the native pass mutates in place — the
    LinearState analog (weights 0, covars 1, touched 0; warm starts seed
    touched from nonzero weights like init_linear_state)."""
    t = {
        "w": (np.ascontiguousarray(initial_weights, np.float32).copy()
              if initial_weights is not None
              else np.zeros(dims, np.float32)),
        "cov": None,
        "touched": np.zeros(dims, np.int8),
    }
    if initial_weights is not None:
        t["touched"][np.asarray(initial_weights) != 0] = 1
    if use_covariance:
        t["cov"] = (np.ascontiguousarray(initial_covars, np.float32).copy()
                    if initial_covars is not None
                    else np.ones(dims, np.float32))
    return t


def make_native_batch_step(rule: Rule, hyper: dict):
    """`step(tables, values, labels, plans) -> loss_sum` applying one
    staged block through the native pass. `plans` is the block's
    stage_block_plans output, HOST-side (the plan ABI forbids device
    arrays); `tables` is init_native_tables' dict, mutated in place.
    Raises RuntimeError when the backend is unavailable — callers decide
    support FIRST via native_batch_unsupported_reason (the loud-fallback
    contract)."""
    reason = native_batch_unsupported_reason(rule)
    if reason is not None:
        raise RuntimeError(f"-native_apply unavailable: {reason}")

    def step(tables: dict, values, labels, plans: BlockPlans) -> float:
        loss = native.batch_apply_block(
            rule.name, hyper, values, labels, plans.main, plans.tail,
            tables["w"].shape[0], tables["w"], tables["cov"],
            tables["touched"])
        if loss is None:  # the .so vanished between probe and call
            raise RuntimeError("native batch apply became unavailable "
                               f"mid-run: {native.load_error()}")
        return loss

    return step


def native_tables_to_state(tables: dict, rule: Rule, n_examples: int):
    """Collapse the host tables into a LinearState (the fit_linear return
    convention — model emission reads touched, serving freezes weights)."""
    import jax.numpy as jnp

    from .state import init_linear_state

    state = init_linear_state(
        tables["w"].shape[0], use_covariance=rule.use_covariance,
        initial_weights=tables["w"], initial_covars=tables["cov"])
    return state.replace(
        touched=jnp.asarray(tables["touched"]),
        step=jnp.asarray(np.int32(n_examples)))
