"""Serving front, ``TopicServer`` host path: mean ``device_wait_seconds``
of the window's launches (span ``serve.device_wait``: the launcher waits
for the sweep count and copies θ back), in ms.  A program whose records
lack the field reports nothing."""


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log or any("device_wait_seconds" not in b for b in log):
        return None
    return 1e3 * sum(b["device_wait_seconds"] for b in log) / len(log)
