"""Serving front, ``TopicServer`` host path: mean ``prep_seconds`` of the
window's launches (spans ``serve.localize``, ``serve.gather_rows`` and
``serve.pad_rows``: the work before the copies in), in ms.  A program
whose records lack the field reports nothing."""


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log or any("prep_seconds" not in b for b in log):
        return None
    return 1e3 * sum(b["prep_seconds"] for b in log) / len(log)
