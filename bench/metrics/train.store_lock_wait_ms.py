"""Streaming trainer: mean time per step the trainer thread was blocked on
the ``ParameterStore`` lock (``StepMetrics.lock_wait_seconds``, the step's
``store.lock_wait`` spans on that thread), in ms.  A program whose steps
lack the field reports nothing."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    vals = [getattr(m, "lock_wait_seconds", None) for m in steps or ()]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
