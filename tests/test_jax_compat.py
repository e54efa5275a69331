"""runtime/jax_compat: the one sanctioned shard_map surface.

The smoke test runs in a fresh subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` so it proves the
documented zero-config recipe (a 2-device CPU psum through the compat
shard_map) independent of the 8-device conftest mesh."""

import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SMOKE = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from hivemall_tpu.runtime.jax_compat import pcast, shard_map

    devices = jax.devices()
    assert len(devices) == 2, devices
    mesh = Mesh(np.asarray(devices), ("workers",))

    def body(x):
        total = jax.lax.psum(jnp.sum(x), "workers")
        # pcast only re-tags the value as mesh-varying; the number survives
        return pcast(total, "workers", to="varying")[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("workers"),
                           out_specs=P("workers"), check_vma=False))
    out = np.asarray(fn(np.arange(8, dtype=np.float32)))
    np.testing.assert_allclose(out, np.asarray([28.0, 28.0]))
    print("SMOKE_OK")
""")


def test_two_device_psum_smoke():
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
    }
    proc = subprocess.run([sys.executable, "-c", _SMOKE], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SMOKE_OK" in proc.stdout


def test_check_vma_kwarg_accepted_both_ways():
    """Both check_vma values trace on the installed jax."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from hivemall_tpu.runtime.jax_compat import shard_map

    mesh = Mesh(np.asarray(jax.devices()), ("workers",))

    def body(x):
        return jax.lax.psum(jnp.sum(x), "workers")[None]

    n = len(jax.devices())
    x = np.arange(n * 2, dtype=np.float32)
    for check_vma in (False, True):
        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("workers"),
                               out_specs=P("workers"), check_vma=check_vma))
        np.testing.assert_allclose(np.asarray(fn(x)).sum(),
                                   x.sum() * n)


def test_decorator_style():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from hivemall_tpu.runtime.jax_compat import shard_map

    mesh = Mesh(np.asarray(jax.devices()), ("workers",))

    @shard_map(mesh=mesh, in_specs=P("workers"), out_specs=P())
    def total(x):
        return jax.lax.psum(jnp.sum(x), "workers")

    x = np.arange(len(jax.devices()) * 2, dtype=np.float32)
    np.testing.assert_allclose(float(jax.jit(total)(x)), x.sum())


def test_threefry_alignment_shape_prefix_stable():
    """jax's partitionable threefry makes random bits a pure function of
    (key, flat index), so a padded table's prefix equals the unpadded one —
    the property every padded-sharded-vs-single-device parity test rests
    on."""
    import jax

    key = jax.random.PRNGKey(7)
    a = np.asarray(jax.random.normal(key, (1003, 4)))
    b = np.asarray(jax.random.normal(key, (1008, 4)))
    np.testing.assert_allclose(a, b[:1003])
