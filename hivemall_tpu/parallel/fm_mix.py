"""Data-parallel FM training with collective mixing.

The north-star workload trains AROW *and* FM across workers (BASELINE.json).
For FM the mixable state is (w0, w[D], V[D,k]): replicas train on their data
shards and mix every k blocks —

- w: delta-weighted average over per-feature update counts (every FM row
  updates all its features, so counts = touch counts), like PartialAverage;
- V: averaged with the same per-feature weights broadcast over factors;
- w0: plain mean (every row updates it);
- AdaGrad-style slots are NOT mixed (device-local, like the reference where
  optimizer state never crossed the MIX wire — only weights did,
  ref: MixMessage carries weight/covar only, mix/MixMessage.java:26-95).

Mix cadence is MixConfig.mix_every, uniform with MixTrainer: the default (1)
mixes after every block; pass mix_every=k to train k blocks locally between
collectives (the syncThreshold analog, MixServerHandler.java:142-148).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models.fm import FMHyper, FMState, init_fm_state, make_fm_step
from .mesh import WORKER_AXIS, make_mesh
from .mix import MixConfig, grouped_mix_scan, replicate_state
from ..runtime.jax_compat import pcast, shard_map


class FMMixTrainer:
    def __init__(self, hyper: FMHyper, dims: int, mesh: Optional[Mesh] = None,
                 mode: str = "minibatch", config: MixConfig = MixConfig()):
        self.hyper = hyper
        self.dims = dims
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = self.mesh.devices.size
        self.config = config
        self.axis = config.axis_name

        local_step = make_fm_step(hyper, mode)
        # make_fm_step returns a jitted fn; jitted fns compose fine inside
        # shard_map (they inline at trace time)

        def mix(st: FMState) -> FMState:
            counts = st.touched.astype(jnp.float32)
            total = jax.lax.psum(counts, self.axis)
            w = jnp.where(total > 0,
                          jax.lax.psum(st.w * counts, self.axis)
                          / jnp.maximum(total, 1.0), st.w)
            v = jnp.where(total[:, None] > 0,
                          jax.lax.psum(st.v * counts[:, None], self.axis)
                          / jnp.maximum(total, 1.0)[:, None], st.v)
            # pcast re-tags the device-invariant pmean result as mesh-varying
            # so the grouped-scan carry type stays consistent
            w0 = pcast(jax.lax.pmean(st.w0, self.axis), self.axis, to="varying")
            return st.replace(w=w, v=v, w0=w0)

        def device_step(state: FMState, indices, values, labels, va):
            st = jax.tree.map(lambda x: x[0], state)

            def body(s, blk):
                s, loss = local_step(s, *blk)
                return s, loss

            st, loss = grouped_mix_scan(
                body, mix, st, (indices[0], values[0], labels[0], va[0]),
                config.mix_every)
            return jax.tree.map(lambda x: x[None], st), jax.lax.psum(
                loss, self.axis)

        spec_state = jax.tree.map(lambda _: P(self.axis),
                                  jax.eval_shape(lambda: init_fm_state(dims, hyper)))
        self._step = jax.jit(
            shard_map(
                device_step,
                mesh=self.mesh,
                in_specs=(spec_state, P(self.axis), P(self.axis), P(self.axis),
                          P(self.axis)),
                out_specs=(spec_state, P()),
            ),
            donate_argnums=(0,),
        )

    def init(self) -> FMState:
        return replicate_state(init_fm_state(self.dims, self.hyper),
                               self.n_dev, self.mesh, axis=self.axis)

    def step(self, state: FMState, indices, values, labels, va=None):
        """indices/values/labels: [n_dev, k, B, ...]."""
        if va is None:
            va = np.zeros(labels.shape, np.float32)
        return self._step(state, indices, values, labels, va)

    def final_state(self, state: FMState) -> FMState:
        """Collapse the device axis: w0/w/v are identical across replicas
        after the trailing mix; touched unions; the adaptive-regularization
        lambdas (data-derived scalars, ref: FactorizationMachineModel
        updateLambda* :253-300) average across replicas."""
        host = jax.device_get(state)
        merged = jax.tree.map(lambda x: x[0], host)
        step_all = np.asarray(host.step)
        return merged.replace(
            touched=np.max(np.asarray(host.touched), axis=0),
            lambda_w0=np.asarray(host.lambda_w0).mean(axis=0),
            lambda_w=np.asarray(host.lambda_w).mean(axis=0),
            lambda_v=np.asarray(host.lambda_v).mean(axis=0),
            step=step_all.sum().astype(step_all.dtype),
        )
