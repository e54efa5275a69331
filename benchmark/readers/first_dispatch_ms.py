"""Mean `train.compiled_step` time of each call's first step. Every call
builds a fresh `jax.jit` of its step, so this holds the Python trace, the
lowering and the read of the executable from the compile cache."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    first = ps.named(spans, "train.compiled_step",
                     lambda a: a.get("step") == 0)
    return ps.ratio(ps.total_ms(first), len(first))
