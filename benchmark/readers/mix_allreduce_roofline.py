"""The least time a chip's interconnect could take to all-reduce what the
traced window's rounds REQUIRED, over the time its all-reduce ops took
(`_mix_window`). Both are the same calls': the due entries are the
`mix_due_entries` of the window's `train.epoch` spans.

Required: of each entry that was due (pending on some replica) its weight,
covariance and pending flag (`work_model`'s entry bytes), all-reduced
bandwidth-optimally over R chips: each chip sends 2 (R - 1) / R of the
payload. Peak: 1,600 Gbit/s = 200 GB/s of chip-to-chip interconnect a v5e
chip (Google Cloud documentation, "TPU v5e"; held here because
`benchmark/peaks.json` has no such column). On a 2x2 host not every one of a
chip's links is wired, so the share a perfect all-reduce could reach there is
under 100%; today's mix sends whole tables, so it reads far under 1%. A sum
that misses an all-reduce too short for the window's list overstates the
share; it cannot carry it past 100% while a listed op lasts longer than the
least time of all the rounds (today 0.2 s against 1 ms)."""

from benchmark.readers import _mix_window as mw
from benchmark.readers import _program_spans as ps
from benchmark.work_models import linear_minibatch_mix as wm

ICI_BYTES_PER_S = {"TPU v5 lite": 200e9}


def read(ctx):
    model = ctx.cell.config.get("work_model") or {}
    peak = ICI_BYTES_PER_S.get(ctx.device.get("kind"))
    seconds = mw.allreduce_s(ctx)
    if seconds is None or peak is None or "replicas" not in model:
        return None
    r = int(model["replicas"])
    due = ps.arg_sum(ps.named(ps.window_spans(ctx), "train.epoch"),
                     "mix_due_entries")
    least = 2.0 * (r - 1) / r * due * wm.entry_bytes(model) / peak
    return 100.0 * least / seconds
