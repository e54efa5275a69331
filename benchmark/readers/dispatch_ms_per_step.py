"""`train.compiled_step` time per step, each call's first step left out
(that one holds the trace, the lowering and the compile cache's read:
`first_dispatch_ms.train`). What is left is the host's hand-over of one step
to the runtime, and whatever the runtime makes it wait."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    later = ps.named(spans, "train.compiled_step", lambda a: a.get("step"))
    return ps.ratio(ps.total_ms(later), len(later))
