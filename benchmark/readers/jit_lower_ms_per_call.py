"""Per call, the summed time of the window's `train.jit_lower` spans: each
fresh jit's jaxpr lowered to an MLIR module. Nothing where the window has
none (`jit_trace_ms_per_call.py`)."""

from benchmark.readers.jit_trace_ms_per_call import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "train.jit_lower")
