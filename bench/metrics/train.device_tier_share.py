"""Streaming trainer: share of the window's φ̂ rows that the steps read from
the store's device row tier (``StepMetrics.tier_rows`` over
``StepMetrics.rows``), in %.  A program whose steps lack the fields reports
nothing."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    vals = [(getattr(m, "tier_rows", None), getattr(m, "rows", None))
            for m in steps or ()]
    if not vals or any(None in v for v in vals):
        return None
    used = sum(r for _, r in vals)
    return 100.0 * sum(t for t, _ in vals) / used if used else None
