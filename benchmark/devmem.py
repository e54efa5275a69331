"""The device's own memory counters, and the peak the result line reports.

A TPU counts live buffers (`bytes_in_use`: the model state, staged blocks)
and the scratch of running programs (`bytes_reserved`: XLA's temporaries,
3 GiB of a 2^28-dim AROW step) apart, and `peak_bytes_in_use` never sees the
scratch (PR 24, chip: 2.43 GB for a step the compiler sizes at 6.25 GiB).
What the chip holds at one instant is the two together, so the line's
`memory_peak_bytes` is the larger of `peak_bytes_in_use` and the largest
`bytes_in_use + bytes_reserved` that ONE reading of the counters showed: a
`Sampler` reads them every millisecond while the warm-up op runs (set-up, not
the window: the same programs on the same state), and an op kind may add
readings of its own (`snapshot()`). Nothing is added across readings, so the
figure is a lower bound of the true peak that the device itself reported.
The line carries the raw counters beside it.
"""

from __future__ import annotations

import threading
from typing import Optional

PERIOD_S = 0.001
_seen = {"together": 0, "in_use": 0, "reserved": 0, "readings": 0}


def _stats(device) -> dict:
    return {k: int(v) for k, v in (device.memory_stats() or {}).items()}


def snapshot() -> int:
    """One reading of every chip's counters; returns (and remembers, with
    its two parts) the largest `bytes_in_use + bytes_reserved` of a chip."""
    import jax

    best = 0
    _seen["readings"] += 1
    for d in jax.local_devices():
        s = _stats(d)
        in_use, reserved = s.get("bytes_in_use", 0), s.get("bytes_reserved", 0)
        best = max(best, in_use + reserved)
        if in_use + reserved > _seen["together"]:
            _seen.update(together=in_use + reserved, in_use=in_use,
                         reserved=reserved)
    return best


class Sampler:
    """`with Sampler():` takes a snapshot every PERIOD_S from a thread."""

    def __init__(self):
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self) -> None:
        while not self._stop.wait(PERIOD_S):
            snapshot()

    def __enter__(self) -> "Sampler":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        snapshot()


def figures() -> dict:
    """The fullest chip's peak counters as the device gives them, the
    largest single reading of in-use plus reserved, and the line's peak."""
    import jax

    best: dict = {}
    for d in jax.local_devices():
        s = _stats(d)
        if s.get("peak_bytes_in_use", 0) >= best.get("peak_bytes_in_use", -1):
            best = s
    in_use = best.get("peak_bytes_in_use", 0)
    return {"memory_peak_bytes": max(in_use, _seen["together"]),
            "peak_bytes_in_use": in_use,
            "peak_bytes_reserved": best.get("peak_bytes_reserved", 0),
            "bytes_limit": best.get("bytes_limit", 0),
            "read_together": dict(_seen)}
