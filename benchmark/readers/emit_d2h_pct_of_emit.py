"""`emit.d2h` time over `emit.model_rows` time: the share of emission that
is the copy of whole tables to the host; the rest is the host's own passes
(`emit.select`)."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    share = ps.ratio(ps.total_ms(ps.named(spans, "emit.d2h")),
                     ps.total_ms(ps.named(spans, "emit.model_rows")))
    return None if share is None else 100.0 * share
