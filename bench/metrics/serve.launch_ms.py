"""Serving front, ``TopicServer`` host path and launch: mean
``launch_seconds`` of the window's launches (row gather, padding, copy
in, the inference program, θ back to the host), in ms."""


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log:
        return None
    return 1e3 * sum(b["launch_seconds"] for b in log) / len(log)
