"""`scatter_gather_roofline`'s gather half: the work model's `gather_bytes`
(the pair block's entries and the linear lanes) over the chip's peak bytes/s,
over the summed duration of the gather ops in the traced span."""

from benchmark.readers import _pair_half


def read(ctx):
    return _pair_half.read(ctx, "gather")
