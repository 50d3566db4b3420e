"""Serving front, ``TopicServer`` host path: mean bytes a launch copies
from host to device (``h2d_bytes`` of each ``batch_log`` record: the
localized word ids, counts, padded φ̂ rows and topic totals), in MB
(10^6 bytes).  A program whose records lack the field reports nothing."""


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log or any("h2d_bytes" not in b for b in log):
        return None
    return sum(b["h2d_bytes"] for b in log) / len(log) / 1e6
