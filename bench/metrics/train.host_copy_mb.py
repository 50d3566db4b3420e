"""Streaming trainer: mean bytes copied between host and device per step
(``StepMetrics.h2d_bytes`` + ``d2h_bytes``: the host arrays passed into the
step program and the arrays ``device_get`` returns), in MB (10^6 bytes).
A program whose steps lack the fields reports nothing."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    vals = [(getattr(m, "h2d_bytes", None), getattr(m, "d2h_bytes", None))
            for m in steps or ()]
    if not vals or any(None in v for v in vals):
        return None
    return sum(h + d for h, d in vals) / len(vals) / 1e6
