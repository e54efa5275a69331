"""Shared trainer driver for the linear-learner family.

Mirrors LearnerBaseUDTF + BinaryOnlineClassifierUDTF / RegressionBaseUDTF
(ref: core/.../hivemall/LearnerBaseUDTF.java:61-343,
BinaryOnlineClassifierUDTF.java:51-298, regression/RegressionBaseUDTF.java:58-295):
option parsing, model creation, the training loop, and model emission — with
rows staged into fixed-shape FeatureBlocks and the update rules executed as
jitted TPU kernels (core/engine.py).

Execution modes:
- default (`-mini_batch 1`): scan mode — per-row sequential semantics,
  reference-exact.
- `-mini_batch B` > 1: minibatch mode — the reference's accumulate-then-
  apply-average semantics, the TPU hot path.
- `-iters N` + `-cv_rate`: multi-epoch with convergence checking; the epoch
  replay that FM/MF do via NioStatefullSegment disk spills is simply re-running
  the staged blocks (host RAM / HBM resident).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from ..constants import DEFAULT_NUM_FEATURES
from ..core.batch import (fillable_lanes, is_rect, iter_blocks, longest_row,
                          pack_rows, pad_to_bucket, shuffle_rows)
from ..ops.scatter import kernel_written
from ..core.engine import (Rule, apply_strategy, make_predict,
                           make_train_step, make_cut_step)
from ..core.state import (LinearState, init_linear_state, linear_tables,
                          model_rows)
from ..ops.convergence import ConversionState
from ..runtime.metrics import REGISTRY, _jit_cache_size
from ..runtime.tracing import (SPAN_BUILD, SPAN_CALL, SPAN_COLLAPSE,
                               SPAN_COMPILED_STEP, SPAN_DATA_PREP, SPAN_EPOCH,
                               SPAN_INIT_STATE, SPAN_MIX, SPAN_SHARD_ROWS,
                               SPAN_STAGE, SPAN_SYNC, TRACER)
from ..utils.feature import parse_features_batch
from ..utils.options import CommandLine, Options


def base_options() -> Options:
    """Options shared by all linear learners (ref: LearnerBaseUDTF.java:85-103)."""
    o = Options()
    o.add("dense", "densemodel", False, "Use dense model or not (always dense on TPU)")
    o.add("dims", "feature_dimensions", True,
          "The dimension of model [default: 2^24 hashed space]", default=None, type=int)
    o.add("disable_halffloat", None, False, "(accepted for parity; TPU uses fp32/bf16)")
    o.add("loadmodel", None, True,
          "Warm-start from a saved model-rows table (ref: LearnerBaseUDTF.java:215-333)")
    # MIX (ref: LearnerBaseUDTF.java:92-103). Upstream, mappers exchange
    # per-feature partial results with a server fleet while they train; here
    # the mappers are this host's devices and the exchange is a collective
    # (parallel/mix.py::MixedReplicas; docs/distributed_training.md)
    o.add("mix", "mix_servers", True,
          "Train one replica on each local device, each on its contiguous "
          "share of the call's rows, and mix them into one model while they "
          "train (argmin-KLD for covariance learners, delta-weighted average "
          "else). The server list itself is accepted for parity only; with "
          "one device this is the plain call. Needs -mini_batch B > 1")
    o.add("mix_session", "mix_session_name", True, "(parity) MIX session name")
    o.add("mix_threshold", None, True,
          "With -mix: the replicas mix after every this many blocks (a block "
          "is one averaged update of each feature it carries) and once more "
          "when the rows end [default: 3, range 1-127]. Every feature with a "
          "pending update on any replica is mixed; upstream's per-feature "
          "push gate is not reproduced", type=int)
    o.add("mix_cancel", "enable_mix_canceling", False, "(parity) no-op under sync SPMD")
    o.add("ssl", None, False, "(parity) TLS handled by the deployment, not the library")
    o.add("mini_batch", "mini_batch_size", True,
          "Mini batch size [default: 1 = exact per-row scan]", default=1, type=int)
    o.add("iters", "iterations", True, "Number of epochs [default: 1]", default=1, type=int)
    o.add("disable_cv", "disable_cvtest", False, "Disable convergence check")
    o.add("cv_rate", "convergence_rate", True, "Convergence rate [default: 0.005]",
          default=0.005, type=float)
    # TPU-native extensions
    o.add("block_size", None, True, "Rows per staged device block [default: 4096]",
          default=4096, type=int)
    o.add("shuffle", None, False, "Shuffle rows between epochs")
    o.add("seed", None, True, "Shuffle seed", default=31, type=int)
    o.add("pallas", None, False,
          "Use the VMEM-resident Pallas backend for exact scan mode "
          "(models that fit on-chip; kernels/linear_scan.py)")
    o.add("native_scan", None, False,
          "Run exact scan epochs through the native C row loop — the "
          "host fast path for accelerator-less mappers (train_arow: any "
          "options; train_fm: -classification with a fixed -eta)")
    o.add("batch", "batch_backend", True,
          "Segment-sum batched backend: apply minibatches of B rows "
          "through one host-staged dedup plan (core/batch_update.py) — "
          "the CPU hot path; same mini-batch semantics as -mini_batch B "
          "(docs/execution_backends.md)", type=int)
    o.add("native_apply", None, False,
          "With -batch B: apply the staged dedup plans through one "
          "vectorized C++ pass per block (core/native_batch.py) instead "
          "of the XLA segment-sum step — same mini-batch semantics, "
          "host-resident f32 tables; falls back LOUDLY to the XLA batch "
          "path when the .so or the rule's native form is missing")
    return o


# the pre-hashed row form: (idx_rows, val_rows), each a list of one array a
# row, or each ONE `[n, lanes]` array where every row has as many lanes (a
# rectangular pair: it is staged, dealt, shuffled and packed as an array)
ArrayRows = Union[Tuple[List[np.ndarray], List[np.ndarray]],
                  Tuple[np.ndarray, np.ndarray]]
# a call's rows: `"<id>:<value>"` strings a row, or the pre-hashed form
FeatureRows = Union[Sequence[Sequence[str]], ArrayRows]


def _is_array_rows(features: FeatureRows) -> bool:
    return isinstance(features, tuple) and len(features) == 2


def _stage_rows(features: FeatureRows, dims: int) -> ArrayRows:
    """(idx_rows, val_rows): ids int64 in [0, dims) by NumPy's floored
    modulo, values float32. A rectangular pair comes back as two arrays
    (the caller's own where they are int64 ids in range and float32 values:
    nothing downstream writes to staged rows), any other input as two lists
    of a row's arrays."""
    if _is_array_rows(features):
        idx, val = features
        if is_rect(idx) and is_rect(val) and idx.shape == val.shape:
            idx = idx.astype(np.int64, copy=False)
            # two reductions cost a tenth of the division they mostly save
            if idx.size and (idx.min() < 0 or idx.max() >= dims):
                idx = np.remainder(idx, dims)
            return idx, np.asarray(val, dtype=np.float32)
        idx_rows = [np.asarray(r, dtype=np.int64) % dims for r in features[0]]
        val_rows = [np.asarray(v, dtype=np.float32) for v in features[1]]
        return idx_rows, val_rows
    return parse_features_batch(features, dims)


def stage_training_rows(features: FeatureRows, dims: int, replicas: int = 1,
                        stage=None):
    """(idx_rows, val_rows, block width) of a training call's rows, under a
    `train.stage` span (text rows open `train.parse` inside it). `stage`
    takes `_stage_rows`' place where a family's text rows are not
    `"<id>:<value>"` (FFM's carry a field). With
    `replicas` > 1 (`-mix`) the rows are dealt inside it, under
    `train.shard_rows`: idx_rows and val_rows are then one list a replica,
    its contiguous share (parallel/mix.py::deal_rows). Rows that stayed
    arrays (`layout: rect`, counted by `train.rows_staged_rect`) give their
    lengths by shape and their shares as views."""
    with TRACER.span(SPAN_STAGE, args={
            "form": "arrays" if _is_array_rows(features) else "text"}) as sp:
        idx_rows, val_rows = (stage or _stage_rows)(features, dims)
        rect = is_rect(idx_rows) and is_rect(val_rows)
        if rect:
            rows, nnz = len(idx_rows), idx_rows.size
            longest = longest_row(idx_rows)
            REGISTRY.counter("train", "rows_staged_rect").increment(rows)
        else:
            lens = [len(r) for r in idx_rows]
            rows, nnz, longest = len(lens), sum(lens), max(lens, default=1)
        sp.set(layout="rect" if rect else "rows", rows=rows, nnz=nnz)
        if replicas > 1:
            from ..parallel.mix import deal_rows

            with TRACER.span(SPAN_SHARD_ROWS,
                             args={"replicas": replicas}) as deal:
                shares = deal_rows(rows, replicas)
                idx_rows = [idx_rows[lo:hi] for lo, hi in shares]
                val_rows = [val_rows[lo:hi] for lo, hi in shares]
                deal.set(rows=rows, rows_each=shares[0][1] - shares[0][0])
    return idx_rows, val_rows, pad_to_bucket(longest)


def init_state_spanned(init, *args, **kw):
    """`init(*args, **kw)` under a `train.init_state` span that carries the
    new state's bytes; a `LinearState`'s also by table
    (`state_bytes_by_table`: `weights`, `covars`, each slot, `touched`), and
    its slot tables' bytes go to the `train.slot_bytes` counter."""
    with TRACER.span(SPAN_INIT_STATE) as sp:
        state = init(*args, **kw)
        sp.set(state_bytes=sum(x.nbytes
                               for x in jax.tree_util.tree_leaves(state)))
        if isinstance(state, LinearState):
            by_table = {name: int(table.nbytes)
                        for name, table in linear_tables(state).items()}
            sp.set(state_bytes_by_table=by_table)
            REGISTRY.counter("train", "slot_bytes").increment(
                sum(by_table[k] for k in state.slots))
    return state


def prepared_blocks(idx_rows, val_rows, labels, dims, block_size, width,
                    extra=None):
    """The arrays of each training step, `(indices, values, labels) +
    extra(block)`, each packed under a `train.data_prep` span that carries
    the bytes handed to the step (`h2d_bytes`, also the `train.h2d_bytes`
    counter)."""
    blocks = iter_blocks(idx_rows, val_rows, labels, dims, block_size, width)
    h2d_counter = REGISTRY.counter("train", "h2d_bytes")
    for _ in range(-(-len(idx_rows) // block_size)):
        with TRACER.span(SPAN_DATA_PREP) as sp:
            block = next(blocks)
            arrays = (block.indices, block.values, block.labels)
            if extra is not None:
                arrays += extra(block)
            h2d = sum(a.nbytes for a in arrays)
            sp.set(rows=block.batch_size, width=block.width, h2d_bytes=h2d)
        h2d_counter.increment(h2d)
        yield arrays


def prepared_replica_blocks(idx_shares, val_shares, label_shares, dims,
                            block_size, width):
    """`prepared_blocks` for `-mix`: step j's arrays hold block j of every
    replica's share end to end, `(indices [R*B, K], values, labels [R*B],
    real rows [R])`. A share's last block is padded with empty rows up to the
    block's shape, so that every call dispatches one shape; a replica whose
    share has run out sends a block of padding."""
    n_blocks = max(1, max(-(-len(s) // block_size) for s in idx_shares))
    h2d_counter = REGISTRY.counter("train", "h2d_bytes")
    for j in range(n_blocks):
        with TRACER.span(SPAN_DATA_PREP) as sp:
            lo, hi = j * block_size, (j + 1) * block_size
            blocks = [pack_rows(i[lo:hi], v[lo:hi], y[lo:hi], dims,
                                width=width, batch_size=block_size)
                      for i, v, y in zip(idx_shares, val_shares, label_shares)]
            real = np.asarray([len(i[lo:hi]) for i in idx_shares], np.int32)
            arrays = (np.concatenate([b.indices for b in blocks]),
                      np.concatenate([b.values for b in blocks]),
                      np.concatenate([b.labels for b in blocks]), real)
            h2d = sum(a.nbytes for a in arrays)
            sp.set(rows=int(real.sum()), width=width, h2d_bytes=h2d)
        h2d_counter.increment(h2d)
        yield arrays


def table_dtype(dims: int, cl: CommandLine):
    """The tables' storage. SpaceEfficientDenseModel analog: above 2^24 dims
    the reference switches to half-float storage unless -disable_halffloat
    (ref: LearnerBaseUDTF.java:172-175); TPU-native that is bf16."""
    import jax.numpy as jnp

    half = dims > (1 << 24) and not cl.has("disable_halffloat")
    return jnp.bfloat16 if half else jnp.float32


def dispatch_step(step, step_no: int, *args):
    """One call of the jitted `step` under a `train.compiled_step` span.
    `compiled` is true where the jit's cache grew across the call: the span
    then holds the trace, the lowering and the compile (or the persistent
    cache's read), and says so by a `jit_recompile` instant."""
    return dispatch_spanned(SPAN_COMPILED_STEP, {"step": step_no}, step, *args)


def dispatch_spanned(span: str, span_args: dict, program, *args):
    """`dispatch_step` under any span of the vocabulary (`-mix` dispatches
    its mix rounds under `train.mix` and its collapse under
    `train.collapse`). Where the jit was fresh, the tracer's compile
    listeners put the `train.jit_trace`, `train.jit_lower` and
    `train.jit_compile` child spans inside."""
    with TRACER.span(span, args=span_args) as sp:
        before = _jit_cache_size(program)
        out = program(*args)
        grew = _jit_cache_size(program) - before
        sp.set(compiled=grew > 0)
        if grew > 0:
            sp.event("jit_recompile", guard=SPAN_CALL, compiles=grew)
            REGISTRY.counter("train", "jit_compiles").increment(grew)
    return out


def record_write_path(call, tables, dims: int, lanes: int,
                      rows: Sequence[str] = ()) -> int:
    """Put on `train.call` how a `-mini_batch` step writes the `[dims]`
    tables it writes at a block's runs (`tables`, name -> table; none under
    the `dense` plan), `lanes` lanes a block: `write` is `xla`, or `kernel:`
    and the names of the tables that go through the run-write kernel
    (`ops/scatter.py::write_path`, the test the step makes when it is
    traced), after `rows`, the row tables whose adds go through it (FFM's
    V). Returns how many of `tables` those are, for
    `train.kernel_write_lanes`."""
    through = kernel_written(tables, dims, lanes, jax.default_backend())
    names = [*rows, *through]
    call.set(write="kernel:" + ",".join(names) if names else "xla")
    return len(through)


@dataclass
class TrainedLinearModel:
    """A fitted model: holds device state + the jitted predictor."""

    state: LinearState
    rule: Rule
    dims: int
    block_width: int

    def predict(self, features: FeatureRows, return_variance: bool = False):
        """Batched scoring — the SQL join+sum inference path collapsed into one
        gather-dot kernel (ref: SURVEY.md §3.5; tools/math/SigmoidGenericUDF.java)."""
        idx_rows, val_rows = _stage_rows(features, self.dims)
        n = len(idx_rows)
        width = pad_to_bucket(longest_row(idx_rows))
        want_var = return_variance and self.rule.use_covariance
        predict = make_predict(use_covariance=want_var)
        # keep per-block outputs on device so dispatch stays async across
        # blocks; ONE batched transfer at the end (graftcheck G002)
        scores, variances = [], []
        for block in iter_blocks(idx_rows, val_rows, np.zeros(n), self.dims, 4096, width):
            out = predict(self.state, block.indices, block.values)
            if want_var:
                scores.append(out[0])
                variances.append(out[1])
            else:
                scores.append(out)
        if want_var:
            scores, variances = jax.device_get((scores, variances))
            return np.concatenate(scores)[:n], np.concatenate(variances)[:n]
        return np.concatenate(jax.device_get(scores))[:n]

    def model_rows(self, filter_zero: bool = False):
        return model_rows(self.state, filter_zero)


def _fit_native_scan(rule, hyper, cl, dims, idx_rows, val_rows, labels,
                     width, block_size, initial_weights, initial_covars
                     ) -> "TrainedLinearModel":
    """`-native_scan`: exact sequential AROW epochs through the C row loop
    (native/hivemall_native.cpp::hm_arow_reference_rowloop — the same code
    measured as the bench anchor, shipped as an execution backend). This is
    the host fast path for accelerator-less workers: a Hive TRANSFORM
    mapper training through the bridge runs at the reference JVM's
    theoretical-best speed with zero JAX dispatch. Semantics = engine scan
    mode (per-row sequential, AROWClassifierUDTF.java:99-150), parity-
    tested; epoch 'loss' for -iters convergence is the margin-violation
    count (the reference's own AROW loss() is the sign-error count — close
    but not identical, documented here)."""
    from .. import native

    if rule.name != "arow":
        raise ValueError(
            "-native_scan supports train_arow only (the C row loop "
            f"implements AROW's closed form); {rule.name} has no native "
            "path — drop the flag")
    # state arrays get one extra sentinel slot: block padding uses
    # index == dims with value 0, so pad lanes read/write the sentinel
    # and contribute nothing to real features
    st = {
        "w": np.zeros(dims + 1, np.float32),
        "cov": np.ones(dims + 1, np.float32),
        "clocks": np.zeros(dims + 1, np.int16),
        "deltas": np.zeros(dims + 1, np.int8),
    }
    if initial_weights is not None:
        st["w"][:dims] = np.asarray(initial_weights, np.float32)
    if initial_covars is not None:
        st["cov"][:dims] = np.asarray(initial_covars, np.float32)
    # zero-row probe: availability check that cannot touch the state
    # (AROW's updates happen to confine to the sentinel slot under a fake
    # row, but only by accident of x=0 scaling — don't rely on it)
    probe = native.arow_reference_rowloop(
        np.zeros((0, 1), np.int32), np.zeros((0, 1), np.float32),
        np.zeros(0, np.float32), dims + 1, r=hyper.get("r", 0.1), state=st,
        track_touched=True)
    if probe is None:
        raise RuntimeError("-native_scan requires the native library "
                           "(bash scripts/build_native.sh)")

    iters = cl.get_int("iters", 1)
    n = len(idx_rows)
    conv = ConversionState(not cl.has("disable_cv"),
                           cl.get_float("cv_rate", 0.005))
    row_counter = REGISTRY.counter("hivemall", f"{rule.name}.examples")
    iter_counter = REGISTRY.counter("hivemall", f"{rule.name}.iterations")
    r = hyper.get("r", 0.1)
    for it in range(max(1, iters)):
        if cl.has("shuffle") and it > 0:
            idx_rows, val_rows, labels = shuffle_rows(
                idx_rows, val_rows, labels, cl.get_int("seed", 31) + it)
        epoch_violations = 0
        for block in iter_blocks(idx_rows, val_rows, labels, dims,
                                 block_size, width):
            epoch_violations += native.arow_reference_rowloop(
                block.indices, block.values, block.labels, dims + 1,
                r=r, state=st, track_touched=True)
            row_counter.increment(block.batch_size)
        iter_counter.increment()
        conv.incr_loss(float(epoch_violations))
        if iters > 1 and conv.is_converged(n):
            break

    import jax.numpy as jnp

    state = init_linear_state(dims, use_covariance=True,
                              initial_weights=st["w"][:dims],
                              initial_covars=st["cov"][:dims])
    # monotone C-loop touch flags OR the warm-start mask — exactly the
    # engine's semantics (init seeds touched from initial_weights != 0 and
    # the kernel only max-updates it); the wrap-prone clocks/deltas never
    # feed model emission
    touched = st["touch"][:dims] != 0
    if initial_weights is not None:
        touched |= np.asarray(initial_weights) != 0
    state = state.replace(
        touched=jnp.asarray(touched.astype(np.int8)),
        step=jnp.asarray(np.int32(n * (it + 1))))
    return TrainedLinearModel(state=state, rule=rule, dims=dims,
                              block_width=width)


def _fit_native_batch(rule, hyper, cl, dims, idx_rows, val_rows, labels,
                      width, block_size, batch_b, initial_weights,
                      initial_covars) -> "TrainedLinearModel":
    """`-batch B -native_apply`: the staged-plan batch backend executed by
    one native C++ pass per block (core/native_batch.py). Plans are built
    host-side exactly like the XLA batch path and REUSED across epochs
    (cleared when -shuffle re-deals the rows); tables stay host-resident
    f32 and collapse to a LinearState at the end."""
    from ..core.batch_update import stage_block_plans
    from ..core.native_batch import (init_native_tables,
                                     make_native_batch_step,
                                     native_tables_to_state)
    step = make_native_batch_step(rule, hyper)
    tables = init_native_tables(dims, rule.use_covariance,
                                initial_weights, initial_covars)
    iters = cl.get_int("iters", 1)
    n = len(idx_rows)
    conv = ConversionState(not cl.has("disable_cv"),
                           cl.get_float("cv_rate", 0.005))
    row_counter = REGISTRY.counter("hivemall", f"{rule.name}.examples")
    iter_counter = REGISTRY.counter("hivemall", f"{rule.name}.iterations")
    plan_cache: list = []
    for it in range(max(1, iters)):
        if cl.has("shuffle") and it > 0:
            idx_rows, val_rows, labels = shuffle_rows(
                idx_rows, val_rows, labels, cl.get_int("seed", 31) + it)
            plan_cache = []
        epoch_loss = 0.0
        for bi, block in enumerate(iter_blocks(idx_rows, val_rows, labels,
                                               dims, block_size, width)):
            if bi >= len(plan_cache):
                plan_cache.append(
                    stage_block_plans(block.indices, batch_b, dims))
            epoch_loss += step(tables, block.values, block.labels,
                               plan_cache[bi])
            row_counter.increment(block.batch_size)
        iter_counter.increment()
        conv.incr_loss(epoch_loss)
        if iters > 1 and conv.is_converged(n):
            break
    state = native_tables_to_state(tables, rule, n * (it + 1))
    return TrainedLinearModel(state=state, rule=rule, dims=dims,
                              block_width=width)


def fit_linear(
    rule: Rule,
    hyper: dict,
    cl: CommandLine,
    features: FeatureRows,
    labels: Sequence[float],
    label_map: Callable[[np.ndarray], np.ndarray] = None,
    initial_weights: Optional[np.ndarray] = None,
    initial_covars: Optional[np.ndarray] = None,
    default_dims: int = DEFAULT_NUM_FEATURES,
    pallas_interpret: bool = False,
) -> TrainedLinearModel:
    """The generic fit loop used by every classifier/regressor `train_*`.

    `pallas_interpret` runs the `-pallas` kernel in the Pallas interpreter
    instead of compiling it — for tests that check the kernel's semantics
    off-chip. It is a Python argument on purpose, never an option string:
    a user's `-pallas` either compiles for the TPU or is refused.

    The whole call is one `train.call` span (docs/observability.md): option
    reads and backend refusals are its self time, everything else a child."""
    with TRACER.span(SPAN_CALL, args={
            "entry": rule.name, "slots": list(rule.slot_names),
            "derive_w": rule.derive_w is not None}) as call:
        return _fit_linear(call, rule, hyper, cl, features, labels,
                           label_map, initial_weights, initial_covars,
                           default_dims, pallas_interpret)


def _fit_linear(call, rule, hyper, cl, features, labels, label_map,
                initial_weights, initial_covars, default_dims,
                pallas_interpret) -> TrainedLinearModel:
    dims = cl.get_int("dims") or default_dims
    mini_batch = cl.get_int("mini_batch", 1)
    iters = cl.get_int("iters", 1)
    block_size = cl.get_int("block_size", 4096)
    labels = np.asarray(labels, dtype=np.float32)
    if label_map is not None:
        labels = label_map(labels)

    if cl.has("loadmodel") and initial_weights is None:
        from ..io.checkpoint import dense_from_rows, load_model_rows

        feats0, w0, c0 = load_model_rows(cl.get("loadmodel"))
        initial_weights, initial_covars = dense_from_rows(dims, feats0, w0, c0)

    # -mix: one replica a local device; with one device, the plain call
    replicas = 1
    if cl.has("mix"):
        from ..parallel.mix import mix_devices

        replicas = len(mix_devices())
    if replicas > 1:
        reason = _mix_unsupported_reason(rule, cl, mini_batch)
        if reason:
            raise ValueError(f"-mix on {replicas} devices {reason}")
        return _fit_linear_mixed(call, rule, hyper, cl, features, labels, dims,
                                 mini_batch, iters, replicas, initial_weights,
                                 initial_covars)

    idx_rows, val_rows, width = stage_training_rows(features, dims)
    n = len(idx_rows)
    if n == 0:
        raise ValueError("no training rows")

    batch_b = cl.get_int("batch", 0) if cl.has("batch") else 0
    mode = "minibatch" if mini_batch > 1 else "scan"
    lanes = width   # only the -mini_batch step cuts a block
    if cl.has("batch"):
        if batch_b < 1:
            raise ValueError(f"-batch must be >= 1: {batch_b}")
        if mini_batch > 1:
            raise ValueError("-batch IS the mini-batch backend; drop "
                             "-mini_batch (its size becomes -batch's B)")
        if cl.has("native_scan") or cl.has("pallas"):
            raise ValueError("-batch does not compose with -native_scan/"
                             "-pallas; pick one execution backend "
                             "(docs/execution_backends.md)")
        mode = "batch"
    if cl.has("native_apply") and mode != "batch":
        # -native_apply is a modifier of the batch backend, not a backend
        # of its own — and it never composes with the other execution
        # flags (the -pallas/-native_scan combos land here or in the
        # -batch refusal above)
        raise ValueError("-native_apply rides the -batch backend; add "
                         "-batch B (docs/execution_backends.md)")
    if mode == "minibatch":
        block_size = mini_batch
    call.set(dims=dims, rows=n, mini_batch=mini_batch, mode=mode)
    if mode == "batch":
        # a staged block must hold whole minibatches: round the block up
        # to a multiple of B (only the dataset's final partial block
        # stages a tail chunk)
        block_size = -(-max(block_size, batch_b) // batch_b) * batch_b
    if cl.has("native_scan"):
        if mode != "scan":
            raise ValueError("-native_scan is the exact per-row path; "
                             "drop -mini_batch or drop -native_scan")
        return _fit_native_scan(rule, hyper, cl, dims, idx_rows, val_rows,
                                labels, width, block_size,
                                initial_weights, initial_covars)
    if mode == "batch" and cl.has("native_apply"):
        from ..core.native_batch import native_batch_unsupported_reason

        reason = native_batch_unsupported_reason(
            rule, table_dtype_is_f32=np.dtype(table_dtype(dims, cl)) == np.float32)
        if reason is None:
            return _fit_native_batch(rule, hyper, cl, dims, idx_rows,
                                     val_rows, labels, width, block_size,
                                     batch_b, initial_weights,
                                     initial_covars)
        # loud fallback, never silent: the XLA batch path has identical
        # semantics, so training proceeds — but the operator asked for
        # the native pass and must learn why they didn't get it
        import warnings

        warnings.warn(f"-native_apply unavailable ({reason}); falling "
                      "back to the XLA batch backend", stacklevel=2)
    if mode == "batch":
        from ..core.batch_update import make_batch_train_step

        step = make_batch_train_step(rule, hyper, batch_size=batch_b)
    elif cl.has("pallas") and mode == "scan":
        from ..kernels.linear_scan import make_pallas_scan_step

        platform = jax.devices()[0].platform
        if platform != "tpu" and not pallas_interpret:
            raise ValueError(
                f"-pallas compiles a TPU (Mosaic) kernel and jax is on "
                f"{platform!r}; drop the flag — the default scan backend "
                f"has the same per-row semantics")
        # dims that cannot be VMEM-resident are refused by the kernel's own
        # guard when the first block is traced (vmem_resident_reason)
        step = make_pallas_scan_step(rule, hyper, interpret=pallas_interpret)
    else:
        if mode == "minibatch":
            # the step works on the lanes this call's rows can fill; which
            # way it applies a block is the same static test of shapes the
            # step makes when it is traced
            lanes = fillable_lanes(longest_row(idx_rows), width)
            call.set(apply=apply_strategy(dims, block_size * lanes),
                     width=width, lanes=lanes)
        step = make_train_step(rule, hyper, mode=mode)
        if lanes < width:
            step = make_cut_step(step, lanes)
    state = init_state_spanned(
        init_linear_state,
        dims,
        use_covariance=rule.use_covariance,
        slot_names=rule.slot_names,
        global_names=rule.global_names,
        dtype=table_dtype(dims, cl),
        initial_weights=initial_weights,
        initial_covars=initial_covars,
    )
    call.set(table_dtype=str(state.weights.dtype))
    kernel_tables = 0
    if mode == "minibatch":
        by_runs = apply_strategy(dims, block_size * lanes) == "batch_local"
        kernel_tables = record_write_path(
            call, linear_tables(state) if by_runs else {}, dims,
            block_size * lanes)

    conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
    # progress counters, the Hadoop Reporter/Counter analog
    # (ref: UDTFWithOptions.java:59-88, FM iteration counter :529-543)
    iter_counter = REGISTRY.counter("hivemall", f"{rule.name}.iterations")
    row_counter = REGISTRY.counter("hivemall", f"{rule.name}.examples")
    cut_counter = REGISTRY.counter("train", "lanes_cut")
    kernel_counter = REGISTRY.counter("train", "kernel_write_lanes")
    # -batch: plans are a pure function of each block's indices, so they
    # are staged on the host once and replayed every epoch (cleared when
    # -shuffle re-deals the rows)
    plan_cache: list = []
    step_no = 0
    for it in range(max(1, iters)):
        with TRACER.span(SPAN_EPOCH, args={"epoch": it}) as epoch:
            if cl.has("shuffle") and it > 0:
                idx_rows, val_rows, labels = shuffle_rows(
                    idx_rows, val_rows, labels, cl.get_int("seed", 31) + it
                )
                plan_cache = []
            # losses stay on device through the epoch — a float() per block
            # would sync the dispatch stream every step; the convergence
            # check only needs the epoch total, fetched in ONE batched
            # device_get at the epoch boundary (graftcheck G002)
            epoch_losses = []
            for bi, block in enumerate(
                    prepared_blocks(idx_rows, val_rows, labels, dims,
                                    block_size, width)):
                if mode == "batch":
                    from ..core.batch_update import stage_block_plans

                    if bi >= len(plan_cache):
                        # device_put once at staging: replayed epochs must
                        # not re-upload the plan arrays every block
                        plan_cache.append(jax.tree_util.tree_map(
                            jax.device_put,
                            stage_block_plans(block[0], batch_b, dims)))
                    block += (plan_cache[bi],)
                state, loss = dispatch_step(step, step_no, state, *block)
                step_no += 1
                epoch_losses.append(loss)
                row_counter.increment(block[0].shape[0])
                cut_counter.increment(block[0].shape[0] * (width - lanes))
                kernel_counter.increment(
                    block[0].shape[0] * lanes * kernel_tables)
            iter_counter.increment()
            with TRACER.span(SPAN_SYNC,
                             args={"fetches": len(epoch_losses)}):
                epoch_loss = float(np.sum(jax.device_get(epoch_losses)))
            epoch.set(steps=len(epoch_losses))
            call.set(epochs=it + 1)
            conv.incr_loss(epoch_loss)
            if iters > 1 and conv.is_converged(n):
                break
    return TrainedLinearModel(state=state, rule=rule, dims=dims, block_width=width)


def _mix_unsupported_reason(rule: Rule, cl: CommandLine,
                            mini_batch: int) -> Optional[str]:
    """Why this call cannot train mixed replicas, or None. Stated, not
    silent: each is a path nobody has needed mixed yet."""
    backends = [f"-{o}" for o in ("batch", "native_scan", "native_apply",
                                  "pallas") if cl.has(o)]
    if backends:
        return (f"does not compose with {'/'.join(backends)}: the replicas "
                "run the XLA -mini_batch step; drop one of the two")
    if mini_batch <= 1:
        return ("needs -mini_batch B > 1: the replicas mix between blocks, "
                "and the exact per-row scan (-mini_batch 1) has none; add "
                "-mini_batch 1024 or drop -mix")
    if rule.global_names or rule.derive_w is not None:
        return (f"is not supported for {rule.name}: its running label "
                "statistics or derived weights count the rows that pad a "
                "replica's last block; train without -mix, or drive "
                "parallel.MixTrainer by hand")
    n = cl.get_int("mix_threshold", 3)
    if not 1 <= n <= 127:
        return f"needs -mix_threshold in 1..127 (blocks between mixes): {n}"
    return None


def _fit_linear_mixed(call, rule, hyper, cl, features, labels, dims,
                      mini_batch, iters, replicas, initial_weights,
                      initial_covars) -> TrainedLinearModel:
    """`_fit_linear` with `-mix` on `replicas` devices: each trains its
    contiguous share of the rows in blocks of `-mini_batch`, all mix after
    every `-mix_threshold` blocks and once more when the rows end, and one
    model comes back (parallel/mix.py::MixedReplicas)."""
    from ..parallel.mix import MixedReplicas, deal_rows, mix_devices

    mix_every = cl.get_int("mix_threshold", 3)
    idx_shares, val_shares, width = stage_training_rows(features, dims, replicas)
    n = sum(len(s) for s in idx_shares)
    if n == 0:
        raise ValueError("no training rows")
    label_shares = [labels[lo:hi] for lo, hi in deal_rows(n, replicas)]
    lanes = fillable_lanes(max(longest_row(s) for s in idx_shares), width)
    # the replicas' programs, each a fresh jit: the step, the mix round and
    # the collapse, and the cut around the step
    with TRACER.span(SPAN_BUILD, args={"replicas": replicas}) as build:
        trainer = MixedReplicas(rule, hyper, dims, table_dtype(dims, cl),
                                mix_devices())
        # the replicas' blocks lie end to end along the rows: one cut for all
        step = make_cut_step(trainer.step, lanes) if lanes < width \
            else trainer.step
        build.set(jits=3 + (lanes < width))
    call.set(dims=dims, rows=n, mini_batch=mini_batch, mode="minibatch",
             apply=apply_strategy(dims, mini_batch * lanes), width=width,
             lanes=lanes, replicas=replicas, mix_every=mix_every,
             reduction=trainer.reduction)
    state = init_state_spanned(trainer.init, initial_weights, initial_covars)
    call.set(table_dtype=str(state.weights.dtype))
    # a replica's step writes a replica's tables, `mini_batch` rows a block
    by_runs = apply_strategy(dims, mini_batch * lanes) == "batch_local"
    kernel_tables = record_write_path(
        call, linear_tables(state) if by_runs else {}, dims,
        mini_batch * lanes)

    conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
    iter_counter = REGISTRY.counter("hivemall", f"{rule.name}.iterations")
    row_counter = REGISTRY.counter("hivemall", f"{rule.name}.examples")
    cut_counter = REGISTRY.counter("train", "lanes_cut")
    kernel_counter = REGISTRY.counter("train", "kernel_write_lanes")
    step_no = round_no = 0
    for it in range(max(1, iters)):
        with TRACER.span(SPAN_EPOCH, args={"epoch": it}) as epoch:
            if cl.has("shuffle") and it > 0:
                # a row stays on its mapper: each share is shuffled alone
                dealt = [shuffle_rows(i, v, y, cl.get_int("seed", 31) + it)
                         for i, v, y in zip(idx_shares, val_shares, label_shares)]
                idx_shares, val_shares, label_shares = map(list, zip(*dealt))
            # losses and due counts stay on the device through the epoch and
            # come back in its ONE device_get, as in _fit_linear
            losses, dues = [], []

            def mix_round(state, trailing):
                state, due = dispatch_spanned(
                    SPAN_MIX, {"round": round_no + len(dues),
                               "trailing": trailing}, trainer.mix, state)
                dues.append(due)
                return state

            pending = 0
            for block in prepared_replica_blocks(
                    idx_shares, val_shares, label_shares, dims, mini_batch,
                    width):
                state, loss = dispatch_step(step, step_no, state, *block)
                step_no += 1
                losses.append(loss)
                real_rows = int(block[3].sum())
                row_counter.increment(real_rows)
                cut_counter.increment(real_rows * (width - lanes))
                kernel_counter.increment(real_rows * lanes * kernel_tables)
                pending = (pending + 1) % mix_every
                if not pending:
                    state = mix_round(state, trailing=False)
            if pending:
                # the rows have ended short of a full group
                state = mix_round(state, trailing=True)
            iter_counter.increment()
            with TRACER.span(SPAN_SYNC,
                             args={"fetches": len(losses) + len(dues)}):
                losses, dues = jax.device_get((losses, dues))
            round_no += len(dues)
            mixed = {"mix_rounds": len(dues),
                     "mix_exchanged_entries": len(dues) * dims,
                     "mix_due_entries": int(np.sum(dues, dtype=np.int64))}
            for name, count in mixed.items():
                REGISTRY.counter("train", name).increment(count)
            epoch.set(steps=len(losses), **mixed)
            call.set(epochs=it + 1)
            conv.incr_loss(float(np.sum(losses)))
            if iters > 1 and conv.is_converged(n):
                break
    # every epoch ended in a mix: weights and covariances are equal on every
    # replica, and one of them is the model
    state = dispatch_spanned(SPAN_COLLAPSE, {}, trainer.collapse, state)
    return TrainedLinearModel(state=state, rule=rule, dims=dims,
                              block_width=width)


def binary_label_map(labels: np.ndarray) -> np.ndarray:
    """int labels -> {-1, +1} (ref: BinaryOnlineClassifierUDTF train: y = label > 0 ? 1 : -1)."""
    return np.where(labels > 0, 1.0, -1.0).astype(np.float32)
