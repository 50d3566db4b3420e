"""Serving front, admission: mean ``filled / capacity`` of the launches the
window made (``AdmissionRouter.batch_log``), in %."""


def read(ctx):
    log = ctx.get("batch_log") if ctx.get("kind") == "serve" else None
    if not log:
        return None
    return 100.0 * sum(b["filled"] / b["capacity"] for b in log) / len(log)
