"""Device: share of the traced slice of the training window in which no
operation ran on the chip, 1 − (union of busy intervals) / slice, in %."""


def read(ctx):
    red = ctx.get("trace")
    if ctx.get("kind") != "train" or red is None:
        return None
    idle = red.idle_share()
    return None if idle is None else 100.0 * idle
