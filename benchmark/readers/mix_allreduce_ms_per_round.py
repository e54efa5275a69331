"""Device time of the `all-reduce` ops of one mix round, per chip: the traced
window's own all-reduce seconds over its own `train.mix` rounds
(`_mix_window`: where both come from, and what falls outside them)."""

from benchmark.readers import _mix_window as mw


def read(ctx):
    seconds = mw.allreduce_s(ctx)
    return None if seconds is None else 1e3 * seconds / mw.rounds(ctx)
