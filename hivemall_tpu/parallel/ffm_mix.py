"""Data-parallel FFM training with collective mixing.

Same contract as fm_mix.py: replicas train on shards, weights cross the
"wire", optimizer state stays local. Mixable FFM state: w0 (pmean), w
(touch-weighted average), V (plain pmean — the hashed (feature,field) table
has no per-entry touch mask; entries untouched everywhere are identical
across replicas so the mean is a no-op for them). FTRL z/n and AdaGrad gg
stay device-local.

Mix cadence is MixConfig.mix_every, uniform with MixTrainer: the default (1)
mixes after every block; mix_every=k trains k blocks locally per collective.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models.ffm import FFMHyper, FFMState, init_ffm_state, make_ffm_step
from .mesh import WORKER_AXIS, make_mesh
from .mix import MixConfig, grouped_mix_scan, replicate_state
from ..runtime.jax_compat import pcast, shard_map


class FFMMixTrainer:
    def __init__(self, hyper: FFMHyper, mesh: Optional[Mesh] = None,
                 mode: str = "minibatch", config: MixConfig = MixConfig()):
        self.hyper = hyper
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n_dev = self.mesh.devices.size
        self.config = config
        self.axis = config.axis_name
        local_step = make_ffm_step(hyper, mode)

        def mix(st: FFMState) -> FFMState:
            counts = st.touched.astype(jnp.float32)
            total = jax.lax.psum(counts, self.axis)

            def touch_avg(x):
                return jnp.where(total > 0,
                                 jax.lax.psum(x * counts, self.axis)
                                 / jnp.maximum(total, 1.0), x)

            # FTRL derives w from the duals at the next update of a feature
            # (w_updates in models/ffm.py), so mixing w alone would be
            # overwritten — the duals z/n mix with the same touch-weighted
            # average, keeping the mixed linear term effective. w is mixed
            # too: it is read directly by predict for features not updated
            # again.
            # pcast re-tags device-invariant pmean results as mesh-varying so
            # the grouped-scan carry type stays consistent
            revary = lambda x: pcast(x, self.axis, to="varying")
            return st.replace(
                w=touch_avg(st.w),
                z=touch_avg(st.z),
                n=touch_avg(st.n),
                v=revary(jax.lax.pmean(st.v, self.axis)),
                w0=revary(jax.lax.pmean(st.w0, self.axis)),
            )

        def device_step(state: FFMState, indices, values, fields, labels):
            st = jax.tree.map(lambda x: x[0], state)

            def body(s, blk):
                s, loss = local_step(s, *blk)
                return s, loss

            st, loss = grouped_mix_scan(
                body, mix, st,
                (indices[0], values[0], fields[0], labels[0]),
                config.mix_every)
            return jax.tree.map(lambda x: x[None], st), jax.lax.psum(
                loss, self.axis)

        spec_state = jax.tree.map(lambda _: P(self.axis),
                                  jax.eval_shape(lambda: init_ffm_state(hyper)))
        self._step = jax.jit(
            shard_map(
                device_step,
                mesh=self.mesh,
                in_specs=(spec_state,) + (P(self.axis),) * 4,
                out_specs=(spec_state, P()),
            ),
            donate_argnums=(0,),
        )

    def init(self) -> FFMState:
        return replicate_state(init_ffm_state(self.hyper), self.n_dev,
                               self.mesh, axis=self.axis)

    def step(self, state, indices, values, fields, labels):
        return self._step(state, indices, values, fields, labels)

    def final_state(self, state) -> FFMState:
        """Collapse the device axis: w/z/n/v/w0 are identical across replicas
        after the trailing mix; touched and v_touched union; the AdaGrad-V accumulator
        v_gg — an additive sum of squared gradients over each replica's
        disjoint shard — merges by summing (the union stream's total), so a
        warm restart resumes with the full-stream curvature instead of one
        replica's."""
        host = jax.device_get(state)
        merged = jax.tree.map(lambda x: x[0], host)
        step_all = np.asarray(host.step)
        return merged.replace(
            touched=np.max(np.asarray(host.touched), axis=0),
            v_touched=np.max(np.asarray(host.v_touched), axis=0),
            v_gg=np.asarray(host.v_gg).sum(axis=0),
            step=step_all.sum().astype(step_all.dtype),
        )
