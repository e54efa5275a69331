"""ops/scatter.py — `scatter_rows_flat`, FM's and FFM's row scatter through
the flat scalar view (the block-run reduction is pinned by
tests/test_minibatch_block_apply.py, the staged plans by
tests/test_batch_update.py and tests/test_native_batch.py)."""

import numpy as np
import jax.numpy as jnp

from hivemall_tpu.ops.scatter import scatter_rows_flat


def test_scatter_rows_flat_both_branches():
    """scatter_rows_flat == the [N,k]-row scatter form, on the flat-index
    fast path AND the int32-overflow fallback (forced via _flat_limit),
    including pad-key drops and logical-lane slicing (kl < k)."""
    rng = np.random.RandomState(3)
    e, k, kl, n = 37, 8, 5, 256
    table = jnp.asarray(rng.randn(e, k).astype(np.float32))
    keys = rng.randint(0, e, size=n).astype(np.int32)
    keys[rng.rand(n) < 0.15] = e  # pad protocol: out-of-range drops
    keys = jnp.asarray(keys)
    upd = jnp.asarray(rng.randn(n, kl).astype(np.float32))

    # reference: row-form scatter of the zero-padded update
    upd_full = jnp.concatenate(
        [upd, jnp.zeros((n, k - kl), jnp.float32)], axis=1)
    want = table.at[keys].add(upd_full, mode="drop")

    got_fast = scatter_rows_flat(table, keys, upd)
    got_fallback = scatter_rows_flat(table, keys, upd, _flat_limit=1)
    np.testing.assert_allclose(np.asarray(got_fast), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_fallback), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # pad lanes (kl..k) of every row receive nothing on either path
    np.testing.assert_array_equal(
        np.asarray(got_fast[:, kl:]), np.asarray(table[:, kl:]))
