"""`emit.gather` time plus `emit.assemble`'s self time (its duration less
its `emit.d2h` children: the copies are `emit_d2h_pct_of_emit.train`'s) over
`emit.model_rows` time: the chunk gathers' dispatch with their ids sent up,
the values' allocation and each chunk's placement. Nothing where the
program opens no `emit.assemble` (a parent commit, tables on the host)."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None or not ps.named(spans, "emit.assemble"):
        return None
    own = ps.total_ms(ps.named(spans, "emit.gather")) \
        + ps.self_ms(spans, "emit.assemble")
    share = ps.ratio(own, ps.total_ms(ps.named(spans, "emit.model_rows")))
    return None if share is None else 100.0 * share
