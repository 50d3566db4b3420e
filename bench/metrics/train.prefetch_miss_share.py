"""Streaming trainer: share of the window's steps whose φ̂ rows were not
staged by the prefetcher before the step needed them
(1 − mean ``StepMetrics.prefetch_hit``), in %."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    if not steps:
        return None
    return 100.0 * (1.0 - sum(bool(m.prefetch_hit) for m in steps) / len(steps))
