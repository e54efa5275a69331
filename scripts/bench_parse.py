"""Host-staging benchmark: the native bulk feature parser vs the Python
parser over a CTR-shaped token batch (mixed int ids / "id:value" pairs /
hashed string names). Rerunnable source of docs/perf_history.md's parser row.

Run: python scripts/bench_parse.py [n_rows] [width]
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _time(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def main() -> None:
    n_rows = int(sys.argv[1]) if len(sys.argv) > 1 else 20000
    width = int(sys.argv[2]) if len(sys.argv) > 2 else 16

    import hivemall_tpu.native as native
    from hivemall_tpu.utils.feature import parse_features_batch

    rng = np.random.RandomState(0)
    rows = []
    for _ in range(n_rows):
        row = []
        for k in range(width):
            if k % 3 == 0:
                row.append(f"cat{rng.randint(1000)}:1")
            elif k % 3 == 1:
                row.append(str(rng.randint(1 << 22)))
            else:
                row.append(f"{rng.randint(1 << 22)}:{rng.rand():.4f}")
        rows.append(row)

    fast = native.parse_features_bulk(rows, 1 << 22) \
        if native.available() else None
    if fast is None:
        # covers both no-.so and an older .so without the parser symbol
        print(json.dumps({"metric": "parse_features_native_speedup",
                          "value": 0.0, "unit": "x",
                          "note": "native parser unavailable"}))
        return

    # best-of-3 per side so the published speedup is stable on a shared host
    t_native = min(_time(lambda: native.parse_features_bulk(rows, 1 << 22))
                   for _ in range(3))
    real = native.parse_features_bulk
    try:
        native.parse_features_bulk = lambda *a: None  # force the Python path
        t_python = min(_time(lambda: parse_features_batch(rows, 1 << 22))
                       for _ in range(3))
        py = parse_features_batch(rows, 1 << 22)
    finally:
        native.parse_features_bulk = real

    for a, b in zip(fast[0], py[0]):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(fast[1], py[1]):
        # strtof vs float() may differ by 1 ulp on decimal literals
        np.testing.assert_allclose(a, b, rtol=1e-6)

    n_tokens = n_rows * width
    print(json.dumps({
        "metric": "parse_features_native_speedup",
        "value": round(t_python / t_native, 2),
        "unit": "x",
        "native_ms": round(t_native * 1e3, 1),
        "python_ms": round(t_python * 1e3, 1),
        "native_tokens_per_sec": round(n_tokens / t_native, 0),
        "n_tokens": n_tokens,
    }))


if __name__ == "__main__":
    main()
