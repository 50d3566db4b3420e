"""Whole step: the least time the chip's peaks allow for all sweeps the
window ran (the benchmark's own count of their work, ``foembench.work``),
over the window's wall time, in %.  The E-step is bandwidth-bound: the
bytes term bounds it at these shapes."""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx.get("work"):
        return None
    total = ctx["work"][0]
    for w in ctx["work"][1:]:
        total = total + w
    return 100.0 * total.least_seconds(ctx["peaks"]) / ctx["window_s"]
