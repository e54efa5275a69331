"""`train.stage` time of the window's calls per 1000 rows staged: the Python
walk over pre-hashed rows, or the parser (`train.parse`, inside it)."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    stage = ps.named(spans, "train.stage")
    return ps.ratio(ps.total_ms(stage), ps.arg_sum(stage, "rows") / 1e3)
