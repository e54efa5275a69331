"""Per call, the summed time of the window's `train.jit_trace` spans: the
Python trace of each fresh jit (the step's, a mix round's, the collapse's,
the state's), as the tracer's compile listeners open and close it on
`jax.monitoring`'s callbacks, outermost trace only. Nothing where no such
span lies in the window: nothing was traced (a memoised step), or the
program has no listeners (a parent commit)."""

from benchmark.readers import _program_spans as ps


def per_call_ms(ctx, name: str):
    """The summed time of the window's spans called `name` over its
    `train.call`s; None where it has none."""
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    phases = ps.named(spans, name)
    if not phases:
        return None
    return ps.ratio(ps.total_ms(phases), len(ps.named(spans, ps.CALL)))


def read(ctx):
    return per_call_ms(ctx, "train.jit_trace")
