"""`train.data_prep` time per step: `pack_rows` of one block, and FM's
validation mask."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    prep = ps.named(spans, "train.data_prep")
    return ps.ratio(ps.total_ms(prep), len(prep))
