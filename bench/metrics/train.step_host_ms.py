"""Streaming trainer: mean host time per step on the trainer thread
(``StepMetrics.host_seconds``: span ``foem.step`` minus
``foem.device_wait``), in ms.  A program whose steps lack the field
reports nothing."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    vals = [getattr(m, "host_seconds", None) for m in steps or ()]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
