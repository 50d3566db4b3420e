"""FOEM algorithm: mean sweeps per minibatch over the window's steps
(``StepMetrics.sweeps``: warm-up plus scheduled sweeps until the stop rule
or the cap)."""


def read(ctx):
    steps = ctx.get("steps") if ctx.get("kind") == "train" else None
    if not steps:
        return None
    return sum(int(m.sweeps) for m in steps) / len(steps)
