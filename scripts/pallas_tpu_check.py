import sys, os; sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
"""Hardware validation of the Pallas scan backend: compile (interpret=False)
on the attached TPU, compare against the engine's reference-exact scan mode
for every rule family, and time Pallas-vs-engine sequential throughput."""
import time

import numpy as np
import jax

from hivemall_tpu.core.engine import make_train_step
from hivemall_tpu.core.state import init_linear_state
from hivemall_tpu.kernels.linear_scan import make_pallas_scan_step
from hivemall_tpu.models.classifier import AROW


from tests.pallas_cases import generic_rules as rules
from tests.pallas_cases import make_block_data as data


def main():
    platform = jax.devices()[0].platform
    assert platform == "tpu", f"need the TPU chip, got {platform}"

    D = 256
    idx, val, y = data(D=D)
    state = init_linear_state(D, use_covariance=True)
    step = make_train_step(AROW, {"r": 0.1}, mode="scan", donate=False)
    ref_state, _ = step(state, idx, val, y)
    got_state, _ = make_pallas_scan_step(AROW, {"r": 0.1})(
        init_linear_state(D, use_covariance=True), idx, val, y)
    np.testing.assert_allclose(np.asarray(got_state.weights),
                               np.asarray(ref_state.weights),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_state.covars),
                               np.asarray(ref_state.covars),
                               rtol=1e-4, atol=1e-5)
    print("AROW via generic pallas backend: compiled, matches engine scan")

    for i, (rule, hyper, binary) in enumerate(rules()):
        idx, val, y = data(B=48, K=8, D=128, seed=i)
        if not binary:
            y = (y * 0.3).astype(np.float32)
        kw = dict(use_covariance=rule.use_covariance,
                  slot_names=rule.slot_names, global_names=rule.global_names)
        ref, ref_loss = make_train_step(rule, hyper, mode="scan", donate=False)(
            init_linear_state(128, **kw), idx, val, y)
        got, got_loss = make_pallas_scan_step(rule, hyper)(
            init_linear_state(128, **kw), idx, val, y)
        np.testing.assert_allclose(np.asarray(got.weights),
                                   np.asarray(ref.weights), rtol=1e-4, atol=1e-5)
        assert abs(float(got_loss) - float(ref_loss)) < 1e-3 + 1e-4 * abs(float(ref_loss))
        print(f"{rule.name}: compiled, matches engine scan")
        n_verified = i + 2  # + the AROW case above

    # the correctness result is published before the (longer) timing runs
    import json
    print(json.dumps({
        "metric": "pallas_rule_families_hardware_verified_tpu",
        "value": n_verified, "unit": "rule_families",
    }), flush=True)

    # throughput: sequential semantics, Pallas VMEM kernel vs engine HBM scan
    B, K, Dbig = 4096, 16, 1 << 18
    rng = np.random.RandomState(0)
    import jax.numpy as jnp
    idx = jnp.asarray((rng.zipf(1.3, size=(B, K)) % Dbig).astype(np.int32))
    val = jnp.ones((B, K), np.float32)
    y = jnp.asarray(np.sign(rng.randn(B)).astype(np.float32))

    def timeit(step, st):
        # verified sync: end every timed window with a VALUE FETCH of a
        # scalar carried through the step chain (runtime/benchmark.py)
        st2, loss = step(st, idx, val, y)
        float(loss)
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            st2, loss = step(st2, idx, val, y)
        float(loss)
        return (time.perf_counter() - t0) / n

    eng = timeit(make_train_step(AROW, {"r": 0.1}, mode="scan", donate=False),
                 init_linear_state(Dbig, use_covariance=True))
    print(json.dumps({
        "metric": "engine_scan_arow_seq_4096x16_2^18_tpu",
        "value": round(B / eng, 1), "unit": "rows/sec",
        "ms_per_block": round(eng * 1e3, 3),
    }), flush=True)
    pal = timeit(make_pallas_scan_step(AROW, {"r": 0.1}),
                 init_linear_state(Dbig, use_covariance=True))
    print(f"sequential AROW [B={B},K={K},D=2^18]: engine scan "
          f"{eng*1e3:.1f} ms/block ({B/eng:,.0f} rows/s), pallas "
          f"{pal*1e3:.1f} ms/block ({B/pal:,.0f} rows/s), "
          f"speedup {eng/pal:.1f}x")
    print(json.dumps({
        "metric": "pallas_vmem_scan_arow_seq_4096x16_2^18_tpu",
        "value": round(B / pal, 1), "unit": "rows/sec",
        "engine_scan_rows_per_sec": round(B / eng, 1),
        "speedup_vs_engine_scan": round(eng / pal, 2),
        "ms_per_block": round(pal * 1e3, 3),
    }), flush=True)


if __name__ == "__main__":
    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
