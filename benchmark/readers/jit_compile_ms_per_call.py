"""Per call, the summed time of the window's `train.jit_compile` spans:
the backend's phase of each fresh jit. On a warm persistent cache that is
the module's cache key, the entry's read, its deserialisation and the
executable's load; on a miss, XLA's compile. Nothing where the window has
none (`jit_trace_ms_per_call.py`)."""

from benchmark.readers.jit_trace_ms_per_call import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "train.jit_compile")
