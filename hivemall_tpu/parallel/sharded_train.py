"""Feature-dimension sharded TRAINING — model-parallel linear learners.

The reference trains against a parameter store sharded across MIX servers by
feature hash: every update routes to `hash(feature) mod numNodes`
(ref: mix/client/MixRequestRouter.java:56-60), so no single node holds the
whole 2^24-dim model. TPU-native, the same capability is the model pytree
sharded along the feature dimension over the mesh: each device holds a [D/n]
stripe of weights / covars / optimizer slots, and a training step is

    gather:  each device gathers its stripe's hits (lanes it does not own are
             masked to zero),
    reduce:  per-row score / squared-norm / variance partials psum over ICI —
             after the psum every device knows the full-row scalars,
    update:  the rule's closed form runs lane-wise on every device with the
             *global* scalars, and deltas scatter into the local stripe only.

The step body is the ordinary engine step built with
`make_train_fn(..., feature_shard=(axis, stripe))` (core/engine.py) — one
copy of the update-application logic, sharded or not. Parity vs the
single-device engine is exact up to psum summation order
(tests/test_sharded_train.py).

Arbitrary dims: when dims is not divisible by the stripe count the tables
pad up to `stripe * n_shards`. The padding slots are safe by the engine's
own protocol: data pad lanes carry value 0, every linear rule's lane deltas
are proportional to the lane value (so they vanish), and the only writes that
can land in a padding slot are the touched/delta-count marks — slots past
`dims` that no predict or export ever reads (final states slice back to
[:dims]).

Two trainers:
- `ShardedTrainer` — 1-D mesh, ONE model too big for one chip's HBM (e.g.
  covariance + optimizer slots at 2^24+ dims); blocks replicated.
- `Sharded2DTrainer` — 2-D (replicas x stripes) mesh: each replica holds a
  feature-sharded model and trains its own data shard; every `mix_every`
  blocks the replicas delta-weighted-average along the replica axis. This is
  the reference's actual production topology: N mapper clients training
  concurrently against M feature-sharded MIX servers
  (ref: MixRequestRouter.java:56-60 + MixServerHandler.java:118-158,
  MixServerTest.java:122-151 five concurrent clients).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core.engine import DELTA_SLOT, Rule, make_train_fn
from ..core.state import LinearState, init_linear_state
from ..core.striping import restripe
from .mesh import SHARD_AXIS, WORKER_AXIS, make_mesh, make_mesh_2d
from .mix import (MixConfig, add_replica_base, collapse_linear_replicas,
                  grouped_mix_scan, make_linear_mix, replicate_state,
                  split_replica_blocks, strip_replica_base)
from .sharded import stripe_score
from ..runtime.jax_compat import shard_map
from ..runtime.tracing import (SPAN_COMPILED_STEP, SPAN_DATA_PREP, SPAN_SYNC,
                               TRACER)


def _resolve_1d_mesh(mesh: Optional[Mesh], who: str):
    """Shared striping scaffold: validate/construct the 1-D mesh and return
    (mesh, axis_name, n_devices)."""
    mesh = mesh if mesh is not None else make_mesh()
    if len(mesh.axis_names) != 1:
        raise ValueError(f"{who} needs a 1-D mesh, got axes {mesh.axis_names}")
    return mesh, mesh.axis_names[0], mesh.devices.size


def _born_sharded(init_fn, mesh: Mesh, specs):
    """jit the state constructor with out_shardings so the full tables are
    never materialized on one device (sharded trainers exist because they
    wouldn't fit)."""
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)
    return jax.jit(init_fn, out_shardings=shardings)()


def _unpad_state(host, dims: int, dims_padded: int, specs, axis_name: str):
    """Slice the dims padding back off every leaf — shared final_state tail
    of all sharded trainers. The padded axis is read from each leaf's
    PartitionSpec (the position named `axis_name`), never guessed from
    sizes, so a non-feature axis that coincidentally equals dims_padded can
    not be mis-sliced."""
    if dims == dims_padded:
        return host

    def unpad(x, spec):
        if getattr(x, "ndim", 0) >= 1:
            for ax, name in enumerate(tuple(spec)):
                if name == axis_name:
                    sl = [slice(None)] * x.ndim
                    sl[ax] = slice(0, dims)
                    return x[tuple(sl)]
        return x

    return jax.tree.map(unpad, host, specs)


def _align_linear_host(host: LinearState, dims: int, use_covariance: bool,
                       slot_names: tuple, global_names: tuple) -> LinearState:
    """Normalize a checkpointed host LinearState to THIS trainer's field
    structure before re-striping: slots/globals the rule expects but the
    checkpoint lacks fill with zeros (e.g. the 2-D trainer's mix delta
    counter resuming from a plain sharded checkpoint); extras drop; a
    covariance learner resuming a covariance-free checkpoint starts its
    covariance at the init value 1.0. This is what makes resume
    cross-family: any collapsed linear checkpoint seeds any linear
    trainer."""
    host = jax.device_get(host)
    slots = dict(host.slots or {})
    covars = host.covars
    if use_covariance and covars is None:
        covars = np.ones(dims, np.asarray(host.weights).dtype)
    elif not use_covariance:
        covars = None
    return host.replace(
        covars=covars,
        slots={name: np.asarray(slots[name]) if name in slots
               else np.zeros(dims, np.float32) for name in slot_names},
        globals={name: np.asarray((host.globals or {}).get(name, 0.0),
                                  np.float32) for name in global_names},
    )


def _pad_initial(arr, dims_padded, fill=0.0):
    """Pad a user-provided [dims] warm-start array up to the sharded table
    size. Weights pad with 0; covariances pad with 1.0 (their init value) —
    the argminKLD mix reads 1/cov on every slot, so a zero-padded covariance
    would put inf/NaN in the padding lanes."""
    arr = np.asarray(arr)
    if arr.shape[0] == dims_padded:
        return arr
    return np.pad(arr, (0, dims_padded - arr.shape[0]),
                  constant_values=fill)


class ShardedTrainer:
    """Train a single feature-sharded model across the mesh.

    The state returned by `init()` / threaded through `step()` is a
    padded-dims LinearState whose [D] leaves carry a NamedSharding along the
    feature dim — each device materializes only its [D/n] stripe in HBM.
    Blocks are replicated (every device sees every row; the model, not the
    data, is what doesn't fit).
    """

    def __init__(self, rule: Rule, hyper: dict, dims: int,
                 mesh: Optional[Mesh] = None, mode: str = "minibatch",
                 dtype=None):
        self.rule = rule
        self.hyper = hyper
        self.dims = dims
        self.mesh, self.axis, n = _resolve_1d_mesh(mesh, "ShardedTrainer")
        self.stripe = -(-dims // n)  # ceil: arbitrary dims pad up
        self.dims_padded = self.stripe * n
        # SpaceEfficientDenseModel analog, same policy as models/base.py
        # fit_linear: above the reference's default 2^24 dims, tables store
        # bf16 (ref: LearnerBaseUDTF.java:172-175 switches to half-float
        # there); pass dtype=jnp.float32 for the -disable_halffloat analog
        if dtype is None:
            dtype = jnp.bfloat16 if dims > (1 << 24) else jnp.float32
        self.dtype = dtype

        body_fn = make_train_fn(rule, hyper, mode=mode,
                                feature_shard=(self.axis, self.stripe))
        state_shape = jax.eval_shape(self._init_one)
        # [D] leaves stripe along the feature dim; scalars replicate
        specs = jax.tree.map(
            lambda leaf: P(self.axis) if leaf.ndim == 1 else P(), state_shape)
        self._specs = specs
        self._step = jax.jit(
            shard_map(
                body_fn,
                mesh=self.mesh,
                in_specs=(specs, P(), P(), P()),
                out_specs=(specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def _init_one(self, **kwargs) -> LinearState:
        kwargs.setdefault("dtype", self.dtype)
        return init_linear_state(
            self.dims_padded,
            use_covariance=self.rule.use_covariance,
            slot_names=tuple(self.rule.slot_names),
            global_names=self.rule.global_names,
            **kwargs,
        )

    def init(self, from_state: Optional[LinearState] = None,
             **kwargs) -> LinearState:
        """Initial state with [D] leaves placed feature-sharded on the mesh —
        each device allocates only its stripe. kwargs pass through to
        init_linear_state (initial_weights/initial_covars = -loadmodel warm
        start, ref: LearnerBaseUDTF.java:215-333); [dims] arrays pad up to
        the sharded table size.

        ``from_state`` is the elastic-resume path: a COLLAPSED host
        LinearState (a final_state() / checkpoint load) re-stripes onto
        THIS mesh through core.striping.restripe — unpad at the old grid,
        re-pad at this mesh's ``stripe * n``, place with NamedSharding —
        so a run checkpointed under N devices resumes under M≠N with the
        full optimizer state (slots, step, Welford globals) intact."""
        if from_state is not None:
            if kwargs:
                raise ValueError("pass either from_state or init kwargs")
            host = _align_linear_host(from_state, self.dims,
                                      self.rule.use_covariance,
                                      tuple(self.rule.slot_names),
                                      tuple(self.rule.global_names))
            return restripe(host, self._specs, self.mesh, self.axis,
                            self.dims, self.dims_padded,
                            fills={"covars": 1.0})
        if not kwargs:
            return _born_sharded(self._init_one, self.mesh, self._specs)
        for key, fill in (("initial_weights", 0.0), ("initial_covars", 1.0)):
            if kwargs.get(key) is not None:
                kwargs[key] = _pad_initial(kwargs[key], self.dims_padded, fill)
        state = self._init_one(**kwargs)
        return jax.tree.map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(self.mesh, spec)), state, self._specs)

    def step(self, state: LinearState, indices, values, labels):
        """One sharded train step. indices/values: [B, K]; labels: [B]
        (replicated to every device — the model is what's sharded). The
        dispatch runs under a ``train.compiled_step`` span: inside a
        driver's ``tracing.step_span`` it becomes the per-step timeline's
        compiled-step stage (data-prep and sync are the caller's stages —
        see runtime/tracing.py)."""
        with TRACER.span(SPAN_COMPILED_STEP,
                         args={"trainer": "sharded_1d"}):
            return self._step(state, indices, values, labels)

    def final_state(self, state: LinearState) -> LinearState:
        """Host-side copy with the padding sliced back off — a plain [dims]
        model for export / warm start / init_linear_state round trips."""
        with TRACER.span(SPAN_SYNC, args={"trainer": "sharded_1d"}):
            host = jax.device_get(state)
        return _unpad_state(host, self.dims,
                            self.dims_padded, self._specs, self.axis)

    def make_predict(self):
        """Jitted scoring that consumes the TRAINED sharded state directly —
        same mesh, same stripe placement, same stripe_score body as
        parallel/sharded.make_sharded_predict, so a model trained sharded
        serves sharded with no re-placement step."""
        fn = shard_map(
            stripe_score(self.axis, self.stripe),
            mesh=self.mesh,
            in_specs=(P(self.axis), P(), P()),
            out_specs=P(),
        )
        jfn = jax.jit(fn)

        def predict(state: LinearState, indices, values):
            return jfn(state.weights, indices, values)

        return predict


class FMShardedTrainer:
    """Feature-dim sharded FM training — the V table is the framework's
    largest model state ([2^24, k] + optimizer does not fit one chip), so w
    and V stripe [D/S] / [D/S, k] across the mesh exactly like the linear
    ShardedTrainer: per row, each device gathers its owned lanes, the three
    prediction partials (linear, sumVfX, sumV2X2) psum over ICI, and lane
    updates scatter locally (models/fm.py make_fm_step feature_shard).
    Blocks replicate (the model, not the data, is what doesn't fit).
    Arbitrary dims pad up to stripe * n_devices."""

    def __init__(self, hyper, dims: int, mesh: Optional[Mesh] = None,
                 mode: str = "minibatch"):
        from ..models.fm import FMHyper, init_fm_state, make_fm_step

        assert isinstance(hyper, FMHyper)
        self.hyper = hyper
        self.dims = dims
        self.mesh, self.axis, n = _resolve_1d_mesh(mesh, "FMShardedTrainer")
        self.stripe = -(-dims // n)
        self.dims_padded = self.stripe * n
        self._init_fn = lambda: init_fm_state(self.dims_padded, hyper)

        body = make_fm_step(hyper, mode,
                            feature_shard=(self.axis, self.stripe))
        state_shape = jax.eval_shape(self._init_fn)
        dp = self.dims_padded
        specs = jax.tree.map(
            lambda leaf: P(*((self.axis,) + (None,) * (leaf.ndim - 1)))
            if leaf.ndim >= 1 and leaf.shape[0] == dp else P(), state_shape)
        self._specs = specs
        self._step = jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(specs, P(), P(), P(), P()),
                out_specs=(specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def init(self, from_state=None):
        """Default: born sharded (fresh V draw at the padded shape). With
        ``from_state`` — a collapsed host FMState from final_state() or an
        elastic checkpoint — every table re-stripes onto THIS mesh
        (core.striping.restripe): w/touched unpad+re-pad along dim 0, the
        [D, k] V table re-pads its row axis (pad rows are never gathered —
        no data id reaches a slot past dims — so zero-fill is exact), and
        scalars replicate. A 4-device run resumes on 2 or 8."""
        if from_state is None:
            return _born_sharded(self._init_fn, self.mesh, self._specs)
        return restripe(from_state, self._specs, self.mesh, self.axis,
                        self.dims, self.dims_padded)

    def step(self, state, indices, values, labels, va=None):
        """indices/values: [B, K]; labels: [B] (replicated)."""
        if va is None:
            # np.shape reads the .shape attribute — no device->host copy of
            # the labels block on the per-step path (graftcheck G002)
            va = np.zeros(np.shape(labels), np.float32)
        with TRACER.span(SPAN_COMPILED_STEP,
                         args={"trainer": "fm_sharded"}):
            return self._step(state, indices, values, labels, va)

    def final_state(self, state):
        """Host-side copy with the padding sliced back off."""
        with TRACER.span(SPAN_SYNC, args={"trainer": "fm_sharded"}):
            host = jax.device_get(state)
        return _unpad_state(host, self.dims,
                            self.dims_padded, self._specs, self.axis)

    def make_predict(self):
        """Serve the trained sharded state directly: the SAME
        sharded_gather_predict body the train step uses (models/fm.py), so
        train-time and serve-time predictions cannot drift."""
        from ..models.fm import sharded_gather_predict

        stripe, axis = self.stripe, self.axis

        def local_scores(w, v, w0, idx, val):
            _, _, _, _, p, _ = sharded_gather_predict(
                w, v, w0, idx, val, axis, stripe)
            return p

        fn = shard_map(
            local_scores,
            mesh=self.mesh,
            in_specs=(P(self.axis), P(self.axis, None), P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        jfn = jax.jit(fn)

        def predict(state, indices, values):
            return jfn(state.w, state.v, state.w0, indices, values)

        return predict


class FFMShardedTrainer:
    """Feature-dim sharded FFM training: the linear tables ([num_features])
    and the hashed pairwise V tables ([v_dims, k] + gg) stripe across the
    mesh with independent stripe sizes. A row's [K, K, k] pairwise block is
    reconstructed on every device with one psum of the owner-gathered
    entries (models/ffm.py make_ffm_step feature_shard), updates scatter
    back owned entries only, and keys hash with the ORIGINAL v_dims so the
    sharded model computes the same function as the unsharded one. Supports
    row_chunk tiling on top (the two compose: the psum moves [C, K, K, k]
    per chunk). Blocks replicate; both tables pad to their stripe grids.

    `init(from_state=...)` seeds from an (unsharded) host FFMState — the
    parity/warm-start path; the default init starts V at `initial_v`, the
    entry's own value as `init_ffm_state` has it (the padding too, which
    nothing addresses), so `final_state` is a state that `TrainedFFMModel`
    emits, encodes and decodes like `train_ffm`'s."""

    def __init__(self, hyper, mesh: Optional[Mesh] = None,
                 mode: str = "minibatch", row_chunk: Optional[int] = None):
        from ..models.ffm import (FFMHyper, FFMState, initial_v,
                                  make_ffm_step)

        assert isinstance(hyper, FFMHyper)
        self.hyper = hyper
        self.mesh, self.axis, n = _resolve_1d_mesh(mesh, "FFMShardedTrainer")
        self.stripe_w = -(-hyper.num_features // n)
        self.stripe_v = -(-hyper.v_dims // n)
        self.nf_padded = self.stripe_w * n
        self.dv_padded = self.stripe_v * n

        def init_one() -> FFMState:
            return FFMState(
                w0=jnp.zeros(()),
                w=jnp.zeros((self.nf_padded,)),
                z=jnp.zeros((self.nf_padded,)),
                n=jnp.zeros((self.nf_padded,)),
                v=initial_v(jnp.arange(self.dv_padded, dtype=jnp.uint32),
                            hyper.factors, hyper.seed, hyper.sigma),
                v_gg=jnp.zeros((self.dv_padded,)),
                touched=jnp.zeros((self.nf_padded,), jnp.int8),
                v_touched=jnp.zeros((self.dv_padded,), jnp.int8),
                step=jnp.zeros((), jnp.int32),
            )

        self._init_fn = init_one
        body = make_ffm_step(hyper, mode, row_chunk=row_chunk,
                             feature_shard=(self.axis, self.stripe_w,
                                            self.stripe_v))
        state_shape = jax.eval_shape(init_one)
        striped = {self.nf_padded, self.dv_padded}
        specs = jax.tree.map(
            lambda leaf: P(*((self.axis,) + (None,) * (leaf.ndim - 1)))
            if leaf.ndim >= 1 and leaf.shape[0] in striped else P(),
            state_shape)
        self._specs = specs
        self._step = jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(specs, P(), P(), P(), P()),
                out_specs=(specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def init(self, from_state=None):
        if from_state is None:
            return _born_sharded(self._init_fn, self.mesh, self._specs)
        host = jax.device_get(from_state)
        nf, dv = self.hyper.num_features, self.hyper.v_dims
        padded = host.replace(
            w=_pad_initial(np.asarray(host.w), self.nf_padded),
            z=_pad_initial(np.asarray(host.z), self.nf_padded),
            n=_pad_initial(np.asarray(host.n), self.nf_padded),
            v=np.pad(np.asarray(host.v),
                     ((0, self.dv_padded - dv), (0, 0))),
            v_gg=_pad_initial(np.asarray(host.v_gg), self.dv_padded),
            touched=np.pad(np.asarray(host.touched),
                           (0, self.nf_padded - nf)),
            v_touched=np.pad(np.asarray(host.v_touched),
                             (0, self.dv_padded - dv)),
        )
        return jax.tree.map(
            lambda leaf, spec: jax.device_put(
                leaf, NamedSharding(self.mesh, spec)), padded, self._specs)

    def step(self, state, indices, values, fields, labels):
        """indices/values/fields: [B, K]; labels: [B] (replicated)."""
        with TRACER.span(SPAN_COMPILED_STEP,
                         args={"trainer": "ffm_sharded"}):
            return self._step(state, indices, values, fields, labels)

    def make_predict(self):
        """Serve the trained sharded state directly — the SAME
        sharded_ffm_gather body the train step uses, vmapped over the
        batch, so serving never materializes the full V table."""
        from ..models.ffm import sharded_ffm_gather

        hyper, axis = self.hyper, self.axis
        sw, sv = self.stripe_w, self.stripe_v

        def local_scores(st, idx, val, fld):
            def one(i, v, f):
                p, *_ = sharded_ffm_gather(st, i, v, f, hyper, axis, sw, sv)
                return p

            return jax.vmap(one)(idx, val, fld)

        fn = shard_map(
            local_scores,
            mesh=self.mesh,
            in_specs=(self._specs, P(), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        jfn = jax.jit(fn)

        def predict(state, indices, values, fields):
            return jfn(state, indices, values, fields)

        return predict

    def final_state(self, state):
        """Host-side copy with both paddings sliced back off. FFM carries
        TWO independently padded table families (linear at num_features, V
        at v_dims), so the unpad is field-wise rather than the shared
        spec-driven helper (which assumes one padded extent)."""
        with TRACER.span(SPAN_SYNC, args={"trainer": "ffm_sharded"}):
            host = jax.device_get(state)
        nf, dv = self.hyper.num_features, self.hyper.v_dims
        return host.replace(
            w=np.asarray(host.w)[: nf],
            z=np.asarray(host.z)[: nf],
            n=np.asarray(host.n)[: nf],
            touched=np.asarray(host.touched)[: nf],
            v=np.asarray(host.v)[: dv],
            v_gg=np.asarray(host.v_gg)[: dv],
            v_touched=np.asarray(host.v_touched)[: dv],
        )


class MCShardedTrainer:
    """Feature-dim sharded MULTICLASS training: the stacked [L, D] weight
    (and covariance) tensor stripes along the feature dim — [L, D/S] per
    device. Per row, the per-label score/variance partials psum over the
    stripe axis (models/multiclass.py _row_quantities_sharded), the margin
    and closed-form alpha/beta are computed from the global scalars, and
    the correct/missed row updates scatter into the local stripe. An
    L-label covariance model at 2^24 dims is 2L full tables — this is what
    makes it fit. Blocks replicate; arbitrary dims pad up."""

    def __init__(self, rule, hyper: dict, num_labels: int, dims: int,
                 mesh: Optional[Mesh] = None, mode: str = "minibatch"):
        from ..models.multiclass import (MCRule, MulticlassState,
                                         make_mc_train_step)

        assert isinstance(rule, MCRule)
        self.rule = rule
        self.num_labels = num_labels
        self.dims = dims
        self.mesh, self.axis, n = _resolve_1d_mesh(mesh, "MCShardedTrainer")
        self.stripe = -(-dims // n)
        self.dims_padded = self.stripe * n
        dp = self.dims_padded
        L = num_labels

        def init_one() -> MulticlassState:
            return MulticlassState(
                weights=jnp.zeros((L, dp), jnp.float32),
                covars=jnp.ones((L, dp), jnp.float32)
                if rule.use_covariance else None,
                touched=jnp.zeros((L, dp), jnp.int8),
                step=jnp.zeros((), jnp.int32),
            )

        self._init_fn = init_one
        mc_body = make_mc_train_step(rule, hyper, mode,
                                     feature_shard=(self.axis, self.stripe))

        def body(state, indices, values, labels):
            # labels cast on device (no host round trip on the hot path)
            return mc_body(state, indices, values, labels.astype(jnp.int32))
        state_shape = jax.eval_shape(init_one)
        specs = jax.tree.map(
            lambda leaf: P(None, self.axis)
            if leaf.ndim == 2 and leaf.shape[-1] == dp else P(), state_shape)
        self._specs = specs
        self._step = jax.jit(
            shard_map(
                body,
                mesh=self.mesh,
                in_specs=(specs, P(), P(), P()),
                out_specs=(specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def init(self):
        return _born_sharded(self._init_fn, self.mesh, self._specs)

    def step(self, state, indices, values, labels):
        """indices/values: [B, K]; labels: [B] int (replicated)."""
        with TRACER.span(SPAN_COMPILED_STEP,
                         args={"trainer": "mc_sharded"}):
            return self._step(state, indices, values, labels)

    def final_state(self, state):
        """Host-side copy with the padding sliced back off."""
        with TRACER.span(SPAN_SYNC, args={"trainer": "mc_sharded"}):
            host = jax.device_get(state)
        return _unpad_state(host, self.dims,
                            self.dims_padded, self._specs, self.axis)

    def make_predict(self):
        """Per-label scores from the sharded state: local [L, K] gather +
        one psum over the stripe axis."""
        stripe, axis = self.stripe, self.axis

        from ..core.striping import translate_to_stripe

        def local_scores(weights, idx, val):
            lidx, vmask = translate_to_stripe(idx, val, axis, stripe)
            W = jnp.take(weights, lidx, axis=1, mode="fill",
                         fill_value=0.0)  # [L, B, K]
            return jax.lax.psum(jnp.einsum("lbk,bk->bl", W, vmask), axis)

        fn = shard_map(
            local_scores,
            mesh=self.mesh,
            in_specs=(P(None, self.axis), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        jfn = jax.jit(fn)

        def predict(state, indices, values):
            return jfn(state.weights, indices, values)

        return predict


class Sharded2DTrainer:
    """Replicas x feature stripes: R data-parallel model replicas, each
    feature-sharded over S devices. Per-row score/norm/variance partials
    psum along the stripe axis (every device of a replica sees the global
    row scalars); every `config.mix_every` blocks the replicas mix along the
    replica axis with the delta-weighted average / argminKLD reduction —
    stripe-local, no cross-stripe traffic.

    Blocks: [R, k, B, K] — replica r trains its own k blocks (data
    parallelism), every stripe of a replica sees all of that replica's rows.

    Cadence note: for covariance learners the argminKLD mix SHRINKS the
    mixed covariance (1/sum(1/cov)) every time it fires — mixing after every
    block freezes the learner early. The reference gates server replies at
    syncThreshold=30 clock ticks (MixServerHandler.java:142-148); pick
    mix_every accordingly (tens of blocks), not 1.
    """

    def __init__(self, rule: Rule, hyper: dict, dims: int,
                 mesh: Optional[Mesh] = None,
                 n_replicas: Optional[int] = None,
                 n_shards: Optional[int] = None,
                 config: MixConfig = MixConfig(), mode: str = "minibatch"):
        self.rule = rule
        self.hyper = hyper
        self.dims = dims
        if mesh is None:
            if n_replicas is None or n_shards is None:
                raise ValueError(
                    "pass either a 2-D mesh or both n_replicas and n_shards")
            mesh = make_mesh_2d(n_replicas, n_shards)
        if len(mesh.axis_names) != 2:
            raise ValueError(
                f"Sharded2DTrainer needs a 2-D mesh, got axes {mesh.axis_names}")
        self.mesh = mesh
        self.replica_axis, self.shard_axis = mesh.axis_names
        self.n_replicas = mesh.shape[self.replica_axis]
        self.n_shards = mesh.shape[self.shard_axis]
        self.config = config
        self.stripe = -(-dims // self.n_shards)
        self.dims_padded = self.stripe * self.n_shards
        self._resume_base = None  # set by init(from_state=...) on warm restart
        reduction = config.reduction
        if reduction == "auto":
            reduction = "argmin_kld" if rule.use_covariance else "average"
        self.reduction = reduction

        local_fn = make_train_fn(rule, hyper, mode=mode,
                                 track_deltas=True,
                                 feature_shard=(self.shard_axis, self.stripe))
        mix = make_linear_mix(self.reduction, self.replica_axis)
        mix_every = config.mix_every

        def device_step(state: LinearState, indices, values, labels):
            # leaves carry a leading [1] replica axis inside shard_map
            st = jax.tree.map(lambda x: x[0], state)

            def body(s, blk):
                s, loss = local_fn(s, *blk)
                return s, loss

            st, loss = grouped_mix_scan(
                body, mix, st, (indices[0], values[0], labels[0]), mix_every)
            # loss is identical on every stripe (computed from psummed row
            # scalars); sum it over the replicas
            loss_sum = jax.lax.psum(loss, self.replica_axis)
            return jax.tree.map(lambda x: x[None], st), loss_sum

        state_shape = jax.eval_shape(self._init_one)
        # replica axis leads every leaf; [D] leaves additionally stripe
        specs = jax.tree.map(
            lambda leaf: P(self.replica_axis, self.shard_axis)
            if leaf.ndim == 1 else P(self.replica_axis), state_shape)
        self._specs = specs
        blk = P(self.replica_axis)
        self._step = jax.jit(
            shard_map(
                device_step,
                mesh=self.mesh,
                in_specs=(specs, blk, blk, blk),
                out_specs=(specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def _init_one(self, **kwargs) -> LinearState:
        return init_linear_state(
            self.dims_padded,
            use_covariance=self.rule.use_covariance,
            slot_names=tuple(self.rule.slot_names) + (DELTA_SLOT,),
            global_names=self.rule.global_names,
            **kwargs,
        )

    def init(self, from_state: Optional[LinearState] = None,
             **kwargs) -> LinearState:
        """Replicated-then-striped initial state: every leaf gains a leading
        [R] replica axis; [D] leaves additionally shard into [D/S] stripes —
        each device allocates [1, stripe].

        ``from_state`` seeds every replica from a collapsed checkpoint (the
        elastic-restart path over BOTH mesh axes at once: the table
        re-stripes to this mesh's stripe grid AND re-replicates to its
        replica count). Exactly like MixTrainer, the seeded base is
        remembered so final_state() strips it from each replica's ADDITIVE
        statistics (step, sum-kind slots, Welford globals) before the
        collapse and restores it once after — nothing is counted
        n_replicas times, no matter how many checkpoint/resume cycles
        stack."""
        self._resume_base = None
        if from_state is not None:
            if kwargs:
                raise ValueError("pass either from_state or init kwargs")
            host = _align_linear_host(
                from_state, self.dims, self.rule.use_covariance,
                tuple(self.rule.slot_names) + (DELTA_SLOT,),
                tuple(self.rule.global_names))
            dp = self.dims_padded
            padded = host.replace(
                weights=_pad_initial(np.asarray(host.weights), dp),
                covars=_pad_initial(np.asarray(host.covars), dp, 1.0)
                if host.covars is not None else None,
                slots={k: _pad_initial(np.asarray(v), dp)
                       for k, v in host.slots.items()},
                touched=_pad_initial(np.asarray(host.touched), dp),
            )
            self._resume_base = padded
            return replicate_state(padded, self.n_replicas, self.mesh,
                                   specs=self._specs, axis=self.replica_axis)
        for key, fill in (("initial_weights", 0.0), ("initial_covars", 1.0)):
            if kwargs.get(key) is not None:
                kwargs[key] = _pad_initial(kwargs[key], self.dims_padded, fill)
        return replicate_state(self._init_one(**kwargs), self.n_replicas,
                               self.mesh, specs=self._specs,
                               axis=self.replica_axis)

    def step(self, state: LinearState, indices, values, labels):
        """indices/values: [R, k, B, K]; labels: [R, k, B] — replica r's k
        blocks. Each group of mix_every blocks trains locally, then the
        replicas mix."""
        with TRACER.span(SPAN_COMPILED_STEP,
                         args={"trainer": "sharded_2d"}):
            return self._step(state, indices, values, labels)

    def shard_blocks(self, indices, values, labels):
        """Host helper: split [R * k, B, ...] blocks into [R, k, B, ...]."""
        with TRACER.span(SPAN_DATA_PREP, args={"trainer": "sharded_2d"}):
            return split_replica_blocks(self.n_replicas, indices, values,
                                        labels)

    def final_state(self, state: LinearState) -> LinearState:
        """Collapse the replica axis (collapse_linear_replicas: trailing-mix
        weights, touched union, slot merge, Welford merge) and slice the
        padding back off, returning a plain [dims] model. A warm-started
        run (init(from_state=...)) strips the seeded base from each
        replica's additive statistics before the merge and restores it
        once after — see strip_replica_base/add_replica_base."""
        with TRACER.span(SPAN_SYNC, args={"trainer": "sharded_2d"}):
            host = jax.device_get(state)
        kinds = dict(self.rule.slot_merge)
        base = self._resume_base
        if base is not None:
            host = strip_replica_base(host, base, kinds)
        merged = collapse_linear_replicas(host, kinds)
        if base is not None:
            merged = add_replica_base(merged, base, kinds)
        # collapsed leaves lost the leading replica axis: strip it from the
        # specs too, then slice the stripe axis they name
        collapsed_specs = jax.tree.map(lambda s: P(*tuple(s)[1:]), self._specs)
        return _unpad_state(merged, self.dims, self.dims_padded,
                            collapsed_specs, self.shard_axis)

    def make_predict(self):
        """Serve the trained 2-D state without re-placement: replica 0's
        stripes already lay [D/S] per device; score with the shared
        stripe_score body, psum over the stripe axis."""
        def local_score(w_local, indices, values):
            # w_local: [1, stripe] (replica-axis leading)
            return stripe_score(self.shard_axis, self.stripe)(
                w_local[0], indices, values)

        fn = shard_map(
            local_score,
            mesh=self.mesh,
            in_specs=(P(self.replica_axis, self.shard_axis), P(), P()),
            out_specs=P(),
            check_vma=False,
        )
        jfn = jax.jit(fn)

        def predict(state: LinearState, indices, values):
            return jfn(state.weights, indices, values)

        return predict
