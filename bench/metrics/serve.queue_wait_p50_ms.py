"""Serving front: the median (nearest rank) over every request of the
window's launches of its queue wait, launch start minus submit
(``queue_wait_s`` of each ``batch_log`` record), in ms.  The median and
not a tail: the traced run stalls when its profiler stops, and the requests
queued through that stall and its backlog set the tail.  A program whose
records lack the field reports nothing."""

import math


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log or any("queue_wait_s" not in b for b in log):
        return None
    waits = sorted(w for b in log for w in b["queue_wait_s"])
    if not waits:
        return None
    return 1e3 * waits[max(0, math.ceil(0.5 * len(waits)) - 1)]
