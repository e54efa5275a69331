"""The benchmark's own host-clock span around `model_rows()` over the calls'
wall time, in the traced span."""


def read(ctx):
    r = ctx.result
    if r["call_s"] <= 0:
        return None
    return 100.0 * r["emit_s"] / r["call_s"]
