"""What the two device-trace readers of the mix share: the traced window's
own all-reduce time and its own rounds. No metric file names this module.

`run.execute` hands a reader the REDUCED trace of the window (`ctx.trace`),
whose `device_ops` are the ten longest ops of the traced span, seconds a chip,
each keyed `<instruction> <op word> <type[shape]> [class]`
(`xplane.short_name`). The mix's collectives are the ops whose word is
`all-reduce`; the rounds are the window's `train.mix` spans, which cover the
same calls (`_program_spans`). Both numbers are the window's, so a slow or
contended round moves them, and a mix that exchanges less reads less.

What this cannot see. An all-reduce shorter than the tenth-longest op is not
in the list: where none is, nothing is read and the metric is left out (so it
is once the mix exchanges the due entries alone and its collectives take
microseconds; PERF.md section 7 names the edit to `run.execute` that hands a
reader the window's events). Where some are listed and others not, the sum
leaves out at most the tenth entry's seconds for each one missing. The step
holds no collective, and the scalar ones (the due count, the collapse's) take
microseconds. A program without `-mix` (the parent commit) has neither span
nor op: nothing is read.
"""

from __future__ import annotations

from typing import Optional

from benchmark.readers import _program_spans as ps

ALLREDUCE = " all-reduce "
MIX = "train.mix"


def rounds(ctx) -> int:
    """Mix rounds dispatched in the traced calls (`train.mix` spans)."""
    spans = ps.window_spans(ctx)
    return len(ps.named(spans, MIX)) if spans else 0


def allreduce_s(ctx) -> Optional[float]:
    """Seconds a chip spent in the listed all-reduce ops over the traced
    span; None where the span has no mix round or lists no all-reduce."""
    if not ctx.trace or not rounds(ctx):
        return None
    found = [s for key, s in ctx.trace["device_ops"] if ALLREDUCE in key]
    return sum(found) if found else None
