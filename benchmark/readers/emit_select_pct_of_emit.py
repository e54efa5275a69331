"""`emit.select` time over `emit.model_rows` time: the host's pass over
the mask's words (`core/emission.py::mask_to_ids`) as a share of emission."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    share = ps.ratio(ps.total_ms(ps.named(spans, "emit.select")),
                     ps.total_ms(ps.named(spans, "emit.model_rows")))
    return None if share is None else 100.0 * share
