"""The line's memory peak: the device's own counters, read together."""

import pytest

from benchmark import devmem

GB = 10 ** 9


@pytest.fixture
def counters(monkeypatch):
    """A fake chip whose counters a test sets; a fresh record of readings."""
    now = {}
    monkeypatch.setattr(devmem, "_stats", lambda device: dict(now))
    monkeypatch.setattr(devmem, "_seen", {"together": 0, "in_use": 0,
                                          "reserved": 0, "readings": 0})
    return now


def test_peak_is_the_largest_single_reading_never_a_sum_of_two(counters):
    # state alone, then a step's scratch beside it, then scratch gone and a
    # second buffer live: the two peaks never fell together
    for in_use, reserved in ((2 * GB, 0), (2 * GB, 3 * GB), (4 * GB, 0)):
        counters.update(bytes_in_use=in_use, bytes_reserved=reserved,
                        peak_bytes_in_use=4 * GB, peak_bytes_reserved=3 * GB,
                        bytes_limit=16 * GB)
        devmem.snapshot()
    f = devmem.figures()
    assert f["memory_peak_bytes"] == 5 * GB          # not 4 + 3
    assert f["read_together"] == {"together": 5 * GB, "in_use": 2 * GB,
                                  "reserved": 3 * GB, "readings": 3}
    assert (f["peak_bytes_in_use"], f["peak_bytes_reserved"],
            f["bytes_limit"]) == (4 * GB, 3 * GB, 16 * GB)


def test_peak_in_use_stands_where_no_reading_caught_more(counters):
    counters.update(bytes_in_use=GB, bytes_reserved=0, peak_bytes_in_use=6 * GB,
                    peak_bytes_reserved=9 * GB, bytes_limit=16 * GB)
    devmem.snapshot()
    assert devmem.figures()["memory_peak_bytes"] == 6 * GB


def test_sampler_reads_while_the_block_runs(counters):
    import time

    counters.update(bytes_in_use=GB, bytes_reserved=GB, peak_bytes_in_use=GB)
    with devmem.Sampler():
        time.sleep(0.05)
    assert devmem._seen["readings"] >= 5 and devmem._seen["together"] == 2 * GB
