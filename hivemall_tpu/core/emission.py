"""close()'s emission for tables that live on a device: the touched entries
are selected where the tables are, and only the selected entries cross to
the host.

Three steps (`select_rows`), shared by `core.state.model_rows` and
`TrainedFMModel.model_rows`:

1. `_pack_mask` (device, dense): `touched != 0` packed to one bit an entry,
   in slab order: with `n = ceil(dims / 32)`, bit `b` of word `j` is entry
   `b * n + j`, so every operand is a 1-D slab of the table and plane `b`
   holds the ids `b * n ..` in ascending order.
2. `mask_to_ids` (host, sparse-aware): the words come over, the non-zero
   ones are expanded to the ascending ids, a thread a run of words.
3. `_gather_rows` (device): each table read at the sorted ids, a chunk of
   `GATHER_CHUNK` ids a dispatch, every chunk dispatched before the first
   is fetched (`emit.gather`), then each fetched and placed in the values
   (`emit.assemble`, the copies its `emit.d2h` children).

Shapes depend on `dims`, the tables' dtypes and `GATHER_CHUNK`, never on
how many rows come out, so the first `model_rows()` of a process compiles
what every later one of that table size runs. Values keep their storage
type until they are on the host.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime.metrics import REGISTRY
from ..runtime.tracing import (SPAN_EMIT_ASSEMBLE, SPAN_EMIT_D2H,
                               SPAN_EMIT_GATHER, SPAN_EMIT_SELECT, TRACER)

MASK_BITS = 32
# Ids a gather dispatch. A sorted gather is 14 ns a lane on a v5e, so the
# padding of the last chunk costs 3.7 ms a table at most, and a dispatch
# with its two copies a fraction of a millisecond: 2^19 keeps both under a
# hundredth of what emitting a million rows takes. A table shorter than
# that takes its own length.
GATHER_CHUNK = 1 << 19
# Mask words a host thread expands (2^25 table entries): eight threads at
# 2^28 dims, none under 2^25.
SEGMENT_WORDS = 1 << 20

# log2 of a one-bit uint32 `x`: _BIT_OF[(x * 0x077CB531) >> 27] (de Bruijn)
_BIT_OF = np.array([0, 1, 28, 2, 29, 14, 24, 3, 30, 22, 20, 15, 25, 17, 4, 8,
                    31, 27, 13, 23, 21, 19, 16, 7, 26, 12, 18, 6, 11, 5, 10,
                    9], np.uint8)


def table_to_host(table, name: str, stats: dict) -> np.ndarray:
    """One device array copied to the host under an `emit.d2h` span; its
    bytes go to the `emit.d2h_bytes` counter and to `stats["d2h_bytes"]`,
    which the caller hands to its `emit.model_rows` span."""
    with TRACER.span(SPAN_EMIT_D2H, args={"table": name}) as sp:
        out = np.asarray(table)
        sp.set(bytes=out.nbytes)
    REGISTRY.counter("emit", "d2h_bytes").increment(out.nbytes)
    stats["d2h_bytes"] += out.nbytes
    return out


@jax.jit
def _pack_mask(touched, nonzero_of):
    """uint32 `[ceil(dims / 32)]`: bit `b` of word `j` says that entry
    `b * n + j` is emitted. `nonzero_of` (a table or None) is `filter_zero`:
    its zeros are left out. Every operand is a 1-D slab, so nothing sits in
    padded tiles: one fusion and no temporary at 2^28 entries (with
    `nonzero_of`, one flag an entry is written first, or the 64 slabs would
    each be copied out)."""
    dims = touched.shape[0]
    n = -(-dims // MASK_BITS)
    flags = touched if nonzero_of is None \
        else (touched != 0) & (nonzero_of != 0)
    words = jnp.zeros((n,), jnp.uint32)
    for b in range(-(-dims // n)):
        lo, hi = b * n, min(dims, (b + 1) * n)
        # the last slab is short where 32 does not divide dims
        plane = jnp.pad(flags[lo:hi].astype(bool), (0, n - (hi - lo)))
        words |= plane.astype(jnp.uint32) << b
    return words


@jax.jit
def _gather_rows(tables, ids):
    """Each table's entries (rows, for a `[D, k]` table) at the ascending
    `ids`, in the table's own type."""
    return tuple(t.at[ids].get(indices_are_sorted=True,
                               mode="promise_in_bounds") for t in tables)


def _segment_ids(words: np.ndarray, first: int, n: int):
    """The set bits of `words` (the mask's words `first ..`) as int32 ids,
    plane by plane, and how many each plane holds. Only the non-zero words
    are expanded: each round takes every remaining word's lowest bit, so
    the work is a few passes over the emitted rows, and a stable (counting)
    sort by plane puts them in id order."""
    j = np.flatnonzero(words).astype(np.int32)
    left = words[j]
    count = np.bitwise_count(left)
    at = np.cumsum(count, dtype=np.int32) - count   # a word's first row
    ids = np.repeat(j + np.int32(first), count)
    plane = np.empty(len(ids), np.uint8)
    while len(left):
        low = left & (~left + np.uint32(1))
        plane[at] = _BIT_OF[(low * np.uint32(0x077CB531)) >> np.uint32(27)]
        left = left ^ low
        more = np.flatnonzero(left)
        left, at = left[more], at[more] + 1
    ids += plane.astype(np.int32) * np.int32(n)
    return (ids[np.argsort(plane, kind="stable")],
            np.bincount(plane, minlength=MASK_BITS))


def mask_to_ids(words: np.ndarray) -> np.ndarray:
    """The ascending int32 ids of `_pack_mask`'s set bits. A long mask is
    cut into runs of `SEGMENT_WORDS` words, a thread each (numpy lets go of
    the interpreter lock in the passes that cost), and plane `b` of the
    answer is the runs' planes `b` end to end."""
    n = len(words)
    cuts = list(range(0, n, SEGMENT_WORDS)) + [n]
    work = lambda lo, hi: _segment_ids(words[lo:hi], lo, n)
    if len(cuts) == 2:
        return work(0, n)[0]
    with ThreadPoolExecutor(min(len(cuts) - 1, 8)) as pool:
        parts = list(pool.map(work, cuts[:-1], cuts[1:]))
    ends = [np.cumsum(held) for _, held in parts]
    return np.concatenate([ids[end[b] - held[b]:end[b]]
                           for b in range(MASK_BITS)
                           for (ids, held), end in zip(parts, ends)])


def select_rows(touched, tables: Sequence[Tuple[str, object]],
                nonzero_of=None):
    """`(feats, values, stats)`: the ascending int64 ids whose `touched`
    flag is set (and whose `nonzero_of` entry is not zero), each named
    table's entries at those ids as numpy arrays of the table's type, and
    the `emit.model_rows` span's account of the copies: `select`,
    `chunks`, `d2h_bytes`, `h2d_bytes`. Runs inside that span.

    Device tables are selected on their device: no table is copied, moved
    or resharded, the ids go to where the tables are. Tables that are numpy
    arrays already (a state rebuilt by hand) are selected on the host:
    there is no copy to save."""
    if not isinstance(touched, jax.Array):
        stats = {"select": "host", "chunks": 0, "d2h_bytes": 0,
                 "h2d_bytes": 0}
        with TRACER.span(SPAN_EMIT_SELECT) as select:
            keep = np.asarray(touched) != 0
            if nonzero_of is not None:
                keep &= np.asarray(nonzero_of) != 0
            feats = np.nonzero(keep)[0].astype(np.int64)
            values = [np.asarray(t)[feats] for _, t in tables]
            select.set(rows_out=len(feats))
        return feats, values, stats

    stats = {"select": "device", "d2h_bytes": 0}
    words = table_to_host(_pack_mask(touched, nonzero_of), "mask", stats)
    with TRACER.span(SPAN_EMIT_SELECT) as select:
        ids = mask_to_ids(words)
        feats = ids.astype(np.int64)
        select.set(rows_out=len(feats))
    rows = len(feats)
    chunk = min(GATHER_CHUNK, touched.shape[0])
    arrays = tuple(t for _, t in tables)

    def ids_of(lo):   # the last chunk padded with its last id: in range, sorted
        part = ids[lo:lo + chunk]
        return np.pad(part, (0, chunk - len(part)), mode="edge")

    # every chunk is dispatched before the first is fetched, so that the
    # copies overlap the gathers
    with TRACER.span(SPAN_EMIT_GATHER) as gather:
        pieces = [_gather_rows(arrays, ids_of(lo))
                  for lo in range(0, rows, chunk)]
        for piece in pieces:
            for value in piece:
                value.copy_to_host_async()
        h2d = len(pieces) * chunk * 4
        gather.set(chunks=len(pieces), h2d_bytes=h2d)
    with TRACER.span(SPAN_EMIT_ASSEMBLE) as assemble:
        values = [np.empty((rows,) + t.shape[1:], t.dtype) for t in arrays]
        for k, piece in enumerate(pieces):
            lo = k * chunk
            for (name, _), out, value in zip(tables, values, piece):
                out[lo:lo + chunk] = table_to_host(value, name,
                                                   stats)[:rows - lo]
        assemble.set(bytes=sum(v.nbytes for v in values))
    REGISTRY.counter("emit", "gather_chunks").increment(len(pieces))
    stats.update(chunks=len(pieces), h2d_bytes=h2d)
    return feats, values, stats
