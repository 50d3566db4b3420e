"""Serving front, ``TopicServer`` host path: mean ``stage_in_seconds`` of
the window's launches (span ``serve.stage_in``: the copies in and the call
of the inference program), in ms.  A program whose records lack the field
reports nothing."""


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log or any("stage_in_seconds" not in b for b in log):
        return None
    return 1e3 * sum(b["stage_in_seconds"] for b in log) / len(log)
