"""`train.sync` time per step: the host's waits for loss values. FM waits
out every step; `fit_linear` waits once an epoch for all it ran ahead of."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    steps = len(ps.named(spans, "train.compiled_step"))
    return ps.ratio(ps.total_ms(ps.named(spans, "train.sync")), steps)
