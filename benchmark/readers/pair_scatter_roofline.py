"""`scatter_gather_roofline`'s scatter half: the work model's `scatter_bytes`
(the pair block's entries and the linear lanes) over the chip's peak bytes/s,
over the summed duration of the scatter ops in the traced span."""

from benchmark.readers import _pair_half


def read(ctx):
    return _pair_half.read(ctx, "scatter")
