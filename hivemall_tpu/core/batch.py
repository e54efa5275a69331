"""Feature blocks: the on-device batch format for all hashed-feature learners.

The reference processes one Hive row at a time (`process(Object[])`,
BinaryOnlineClassifierUDTF.java:111). TPU-first, rows are staged into HBM as
fixed-shape padded blocks:

    indices [B, K] int32  — hashed feature ids, padded with `dims` (out of range)
    values  [B, K] f32    — feature values, padded with 0
    labels  [B]    f32    — ±1 for classifiers, y for regressors

Padding with an OUT-OF-RANGE index (== dims) instead of a mask array lets every
gather use mode='fill' (reads 0 / neutral) and every scatter use mode='drop'
(padding lanes vanish), so kernels never multiply by a mask and XLA sees static
shapes. K is bucketed to powers of two to bound recompilation
(SURVEY.md §7 hard part (b)).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import numpy as np


class FeatureBlock(NamedTuple):
    indices: np.ndarray  # [B, K] int32 (device or host)
    values: np.ndarray  # [B, K] float32
    labels: np.ndarray  # [B] float32
    nnz: np.ndarray  # [B] int32 — true row lengths (for norms the pad lanes
    # already contribute 0, so this is informational/debug)

    @property
    def batch_size(self) -> int:
        return self.indices.shape[0]

    @property
    def width(self) -> int:
        return self.indices.shape[1]


def pad_to_bucket(k: int, min_width: int = 8) -> int:
    """Round row width up to a power of two >= min_width (bounds the number of
    distinct compiled shapes)."""
    w = min_width
    while w < k:
        w <<= 1
    return w


def fillable_lanes(longest: int, width: int) -> int:
    """The leading lanes of a `width`-lane block that a call's rows can
    fill: the longest row rounded up to a multiple of 8 (`train_ffm`'s
    `pair_width` ladder). `pack_rows` fills a row from lane 0, so the lanes
    beyond hold the padding id in every row, and a `-mini_batch` step built
    with this count cuts them off before it gathers (39 features on the
    64-lane bucket: 40)."""
    return min(width, max(8, -(-longest // 8) * 8))


def bucket_rows(x, min_rows: int = 8):
    """Pad an array's leading (row) axis up to the bucket ladder
    (``pad_to_bucket``): the shape canonicalizer for feeding a
    variable-length batch to a jitted callable without forking one compile
    per novel length (graftcheck G034 rewrites unrouted dispatch sites to
    ``scorer(bucket_rows(batch))[:batch.shape[0]]``). Pad rows are zeros —
    callers slice the result back to the true row count."""
    n = x.shape[0]
    b = pad_to_bucket(max(n, 1), min_width=min_rows)
    if b == n:
        return x
    pad_shape = (b - n,) + tuple(x.shape[1:])
    return np.concatenate([np.asarray(x), np.zeros(pad_shape, x.dtype)])


def is_rect(rows) -> bool:
    """Rows that are one 2-D array: every row of one length. Staging and
    packing keep such rows an array (no Python-level work per row)."""
    return isinstance(rows, np.ndarray) and rows.ndim == 2


def longest_row(rows) -> int:
    """The longest row's length (1 for no rows): a shape where the rows are
    one array."""
    if is_rect(rows):
        return rows.shape[1] if rows.shape[0] else 1
    return max((len(r) for r in rows), default=1)


def pack_rows(
    idx_rows: Sequence[np.ndarray],
    val_rows: Sequence[np.ndarray],
    labels: Sequence[float],
    dims: int,
    width: Optional[int] = None,
    batch_size: Optional[int] = None,
) -> FeatureBlock:
    """Pack variable-length hashed rows into one padded FeatureBlock.

    Rows longer than `width` are truncated (callers should pick width >= max
    nnz; `pad_to_bucket(max_nnz)` is the default). If `batch_size` is given,
    the block is padded with empty rows up to it (their labels are 0 and all
    lanes are dropped, so they are true no-ops in every learner).

    Rows given as two 2-D arrays (`is_rect`) are packed by one slice
    assignment each, to the same block as the same rows in a list.
    """
    n = len(idx_rows)
    if width is None:
        width = pad_to_bucket(longest_row(idx_rows))
    b = batch_size if batch_size is not None else n
    rect = is_rect(idx_rows) and is_rect(val_rows)
    if b == n and n > 0 and not rect:
        from .. import native

        packed = native.pack_block(idx_rows, val_rows, width, dims)
        if packed is not None:
            out_idx, out_val, out_nnz = packed
            return FeatureBlock(out_idx, out_val,
                                np.asarray(labels, dtype=np.float32), out_nnz)
    indices = np.full((b, width), dims, dtype=np.int32)
    values = np.zeros((b, width), dtype=np.float32)
    labs = np.zeros((b,), dtype=np.float32)
    nnz = np.zeros((b,), dtype=np.int32)
    if rect:
        k = min(idx_rows.shape[1], width)
        indices[:n, :k] = idx_rows[:, :k]
        values[:n, :k] = val_rows[:, :k]
        labs[:n] = labels
        nnz[:n] = k
    else:
        for i in range(n):
            k = min(len(idx_rows[i]), width)
            indices[i, :k] = idx_rows[i][:k]
            values[i, :k] = val_rows[i][:k]
            labs[i] = labels[i]
            nnz[i] = k
    return FeatureBlock(indices, values, labs, nnz)


def iter_blocks(
    idx_rows: Sequence[np.ndarray],
    val_rows: Sequence[np.ndarray],
    labels: Sequence[float],
    dims: int,
    batch_size: int,
    width: Optional[int] = None,
):
    """Yield fixed-shape FeatureBlocks over a dataset.

    The final partial block is emitted at its true size (one extra compiled
    shape) rather than padded with fake rows — fake rows would corrupt global
    scalars (w0, running target stats) and the example counter `t`.
    """
    n = len(idx_rows)
    if width is None:
        width = pad_to_bucket(longest_row(idx_rows))
    for start in range(0, n, batch_size):
        end = min(start + batch_size, n)
        yield pack_rows(
            idx_rows[start:end],
            val_rows[start:end],
            labels[start:end],
            dims,
            width=width,
            batch_size=end - start,
        )


def pad_rows_to_multiple(indices, values, labels, multiple: int, dims: int):
    """Pad a staged block's rows up to a multiple of `multiple` with
    sentinel rows (every lane the out-of-range pad index ``dims``, value 0,
    label 0) — the fixed-chunk scan shape shared by the chunked device
    backends (kernels/linear_scan.py's SMEM chunking; the batch backend
    stages a tail plan instead, core/batch_update.py). Sentinel rows are
    dead weight only: backends that carry global scalars or the example
    counter must mask by the TRUE row count (linear_scan's live_rows
    meta) — a sentinel row is not a no-op for running scalar stats."""
    import jax.numpy as jnp

    b, k = indices.shape
    b_pad = (b + multiple - 1) // multiple * multiple
    if b_pad == b:
        return indices, values, labels
    pad = b_pad - b
    return (
        jnp.concatenate([indices, jnp.full((pad, k), dims, indices.dtype)]),
        jnp.concatenate([values, jnp.zeros((pad, k), values.dtype)]),
        jnp.concatenate([labels, jnp.zeros((pad,), labels.dtype)]),
    )


def shuffle_rows(
    idx_rows: List[np.ndarray],
    val_rows: List[np.ndarray],
    labels: np.ndarray,
    seed: int,
):
    """Host-side shuffle between epochs (the reference's rand_amplify /
    epoch-replay analog, ref: ftvec/amplify/RandomAmplifierUDTF.java:43-66)."""
    rng = np.random.RandomState(seed)
    perm = rng.permutation(len(idx_rows))
    if is_rect(idx_rows) and is_rect(val_rows):
        return idx_rows[perm], val_rows[perm], np.asarray(labels)[perm]
    return (
        [idx_rows[i] for i in perm],
        [val_rows[i] for i in perm],
        np.asarray(labels)[perm],
    )
