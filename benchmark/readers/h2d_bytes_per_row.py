"""Bytes handed to the steps (`h2d_bytes` of each `train.data_prep`, the
blocks' `nbytes`) over the row-visits in them."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    prep = ps.named(spans, "train.data_prep")
    return ps.ratio(ps.arg_sum(prep, "h2d_bytes"), ps.arg_sum(prep, "rows"))
