"""Replica balancer: how far the busiest replica's share of the window's
batches lies above an even share, max over replicas of batches dispatched
over the mean per replica, minus 1, in % (``ctx["dispatch"]``: the
window's launches by the ``ReplicaPool`` replica that served each).  A cell
served by one engine has no dispatch and reports nothing."""


def read(ctx):
    dispatch = ctx.get("dispatch") if ctx.get("kind") == "serve" else None
    if not dispatch:
        return None
    counts = list(dispatch.values())
    mean = sum(counts) / len(counts)
    if mean <= 0:
        return None
    return 100.0 * (max(counts) / mean - 1.0)
