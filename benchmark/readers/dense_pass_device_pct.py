"""Share of device-op time in ops that are neither a gather nor a scatter:
the full-table fusions, copies, broadcasts and zero-fills (xplane.classify)."""


def read(ctx):
    t = ctx.trace
    if not t:
        return None
    total = sum(t["class_s"].values())
    if total <= 0:
        return None
    return 100.0 * t["class_s"]["dense"] / total
