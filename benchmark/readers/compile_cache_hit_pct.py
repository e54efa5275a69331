"""Of the window's `train.jit_compile` spans, the share whose `cache` is
`hit`: the persistent compile cache served the executable. Under 100, an
XLA compile ran inside the timed window (`miss`), or no cache was read
(`off`). Nothing where nothing compiled."""

from benchmark.readers import _program_spans as ps


def read(ctx):
    spans = ps.window_spans(ctx)
    if spans is None:
        return None
    compiles = ps.named(spans, "train.jit_compile")
    hits = ps.named(spans, "train.jit_compile",
                    lambda args: args.get("cache") == "hit")
    share = ps.ratio(len(hits), len(compiles))
    return None if share is None else 100.0 * share
