"""Per-layer metric readers: one module each, found by the metric file's
`reader` key. `read(ctx) -> float | None`; None leaves the metric out."""
