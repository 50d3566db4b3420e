"""Streaming trainer: mean time per step to fetch the step's φ̂ rows from
the ``ParameterStore`` (``StepMetrics.fetch_seconds``, span ``foem.fetch``
on the prefetch worker, any store lock wait included), in ms.  A program
whose steps lack the field reports nothing."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    vals = [getattr(m, "fetch_seconds", None) for m in steps or ()]
    if not vals or None in vals:
        return None
    return 1e3 * sum(vals) / len(vals)
