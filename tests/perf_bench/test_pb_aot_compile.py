"""The two mini-batch steps compile for a described v5e at the benchmark's
sizes, and step plus one spare state stay under three quarters of the chip.

Nothing runs: the TPU compiler is installed here and compiles for a chip
that is described, not attached (on-chip-measurement guide, section 2). The
topology is described inside a module-scoped fixture, never at import. The
file asserts nothing about the sizes the compiler refuses today."""

import json
import os

import pytest

from benchmark import manifest

HBM_BYTES = 15.75 * 2 ** 30   # what the compiler itself reports for a v5e
SHARE = 0.75


def _config(name):
    with open(os.path.join(manifest.ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    import jax

    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _compile(step, state, block, sharding):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        compiled = jax.jit(step, donate_argnums=(0,)).lower(
            _on(state, sharding), *_on(block, sharding)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    m = compiled.memory_analysis()
    peak = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    state_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree_util.tree_leaves(state))
    return peak, state_bytes


def _block(rows, width=64):
    import jax
    import jax.numpy as jnp

    return (jax.ShapeDtypeStruct((rows, width), jnp.int32),
            jax.ShapeDtypeStruct((rows, width), jnp.float32),
            jax.ShapeDtypeStruct((rows,), jnp.float32))


def test_arow_step_fits_with_a_spare_state(one_chip):
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.core.engine import make_train_fn
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models.classifier import AROW

    cfg = _config("arow_criteo1tb")
    dtype = jnp.dtype(cfg["table_dtype"])
    state = jax.eval_shape(lambda: init_linear_state(
        cfg["num_features"], use_covariance=True, dtype=dtype))
    step = make_train_fn(AROW, {"r": cfg["reference_args"]["r"]},
                         mode="minibatch")
    peak, state_bytes = _compile(step, state, _block(cfg["mini_batch"]), one_chip)
    assert state_bytes >= 2 * cfg["num_features"] * dtype.itemsize
    assert peak + state_bytes <= SHARE * HBM_BYTES, (peak, state_bytes)
    assert peak >= 0.25 * HBM_BYTES, "the cell would be under the memory floor"


def test_fm_step_fits_with_a_spare_state(one_chip):
    import jax
    import jax.numpy as jnp
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step

    cfg = _config("fm_criteo1tb")
    hyper = FMHyper(factors=cfg["factors"], classification=True)
    state = jax.eval_shape(lambda: init_fm_state(cfg["num_features"], hyper))
    step = make_fm_step(hyper, "minibatch", jit=False)
    block = _block(cfg["mini_batch"]) + (
        jax.ShapeDtypeStruct((cfg["mini_batch"],), jnp.float32),)
    peak, state_bytes = _compile(step, state, block, one_chip)
    assert peak + state_bytes <= SHARE * HBM_BYTES, (peak, state_bytes)
    assert peak >= 0.25 * HBM_BYTES, "the cell would be under the memory floor"
