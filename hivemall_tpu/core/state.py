"""Model state: the TPU-resident "parameter store".

Mirrors the reference model layer (ref: core/.../model/DenseModel.java:36-52):
a dense weight table plus optional covariance and optimizer slot arrays, all
fixed-shape HBM-resident device arrays in a pytree — DenseModel's
struct-of-arrays layout maps 1:1. The `touched` bitmap reproduces the close()
behavior of emitting only weights actually updated
(ref: BinaryOnlineClassifierUDTF.java:249-298).

Sparse/string models (SparseModel, SpaceEfficientDenseModel) are subsumed by
feature hashing into this dense space (the reference's own default is hashed
2^24 dims) plus optional bf16 storage in place of the half-float codec.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np
from flax import struct

from ..runtime.metrics import REGISTRY
from ..runtime.tracing import SPAN_EMIT, TRACER
from .emission import select_rows


@struct.dataclass
class LinearState:
    """State for all hashed-feature linear learners (binary + regression)."""

    weights: jnp.ndarray  # [D] float32
    covars: Optional[jnp.ndarray]  # [D] float32, init 1.0 (covariance learners)
    slots: Dict[str, jnp.ndarray]  # per-feature optimizer aux, init 0.0
    touched: jnp.ndarray  # [D] int8 — 1 where an update landed
    step: jnp.ndarray  # [] int32 — 1-based processed-example counter
    globals: Dict[str, jnp.ndarray]  # scalar running stats (e.g. target stddev,
    # ref: common/OnlineVariance.java used by PA1a/PA2a/AROWe2 regressors)

    @property
    def dims(self) -> int:
        return self.weights.shape[0]


def init_linear_state(
    dims: int,
    use_covariance: bool = False,
    slot_names: tuple = (),
    global_names: tuple = (),
    dtype=jnp.float32,
    initial_weights: Optional[np.ndarray] = None,
    initial_covars: Optional[np.ndarray] = None,
) -> LinearState:
    """Create a zeroed model (covariance initialized to 1.0, the implicit
    default for absent entries in the reference, ref: AROWClassifierUDTF.java:140).

    `initial_weights`/`initial_covars` support warm start, mirroring
    `-loadmodel` (ref: LearnerBaseUDTF.java:215-333).
    """
    weights = (
        jnp.asarray(initial_weights, dtype=dtype)
        if initial_weights is not None
        else jnp.zeros((dims,), dtype=dtype)
    )
    covars = None
    if use_covariance:
        covars = (
            jnp.asarray(initial_covars, dtype=dtype)
            if initial_covars is not None
            else jnp.ones((dims,), dtype=dtype)
        )
    slots = {name: jnp.zeros((dims,), dtype=jnp.float32) for name in slot_names}
    touched = jnp.zeros((dims,), dtype=jnp.int8)
    if initial_weights is not None:
        touched = (jnp.asarray(initial_weights) != 0).astype(jnp.int8)
    return LinearState(
        weights=weights,
        covars=covars,
        slots=slots,
        touched=touched,
        step=jnp.zeros((), dtype=jnp.int32),
        globals={name: jnp.zeros((), dtype=jnp.float32) for name in global_names},
    )


def linear_tables(state: LinearState) -> Dict[str, jnp.ndarray]:
    """The state's `[D]` tables by name: `weights`, `covars` where the rule
    has them, each optimizer slot, `touched`."""
    tables = {"weights": state.weights}
    if state.covars is not None:
        tables["covars"] = state.covars
    return {**tables, **state.slots, "touched": state.touched}


def model_rows(state: LinearState, filter_zero: bool = False):
    """Dump the model as (feature, weight[, covar]) arrays over touched
    entries — the close() model emission (ref: BinaryOnlineClassifierUDTF.java:254-291).
    Device tables are selected on the device and only the emitted entries
    cross to the host (core/emission.py).
    """
    with TRACER.span(SPAN_EMIT, args={
            "table_dtype": str(state.weights.dtype)}) as emit:
        tables = [("weights", state.weights)]
        if state.covars is not None:
            tables.append(("covars", state.covars))
        feats, values, stats = select_rows(
            state.touched, tables, state.weights if filter_zero else None)
        emit.set(rows_out=len(feats), **stats)
    REGISTRY.counter("emit", "rows").increment(len(feats))
    return (feats, *values)
