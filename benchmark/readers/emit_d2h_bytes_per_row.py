"""Bytes `model_rows()` copied to the host (`d2h_bytes` of each
`emit.model_rows`: whole tables) over the rows it emitted."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    emit = ps.named(spans, "emit.model_rows")
    return ps.ratio(ps.arg_sum(emit, "d2h_bytes"),
                    ps.arg_sum(emit, "rows_out"))
