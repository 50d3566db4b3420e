"""Kernels: device time per launch of the inference operations in the
traced slice (the ``theta_sweep`` kernel, or the portable inference's XLA
operations; ``INFER_OPS`` names them), in ms."""

#: device operations of one inference launch
INFER_OPS = [r"^%?theta_sweep_pallas"]


def read(ctx):
    red = ctx.get("trace")
    launches = ctx.get("trace_launches")
    if ctx.get("kind") != "serve" or red is None or not launches:
        return None
    busy = red.time_s(INFER_OPS)
    if busy <= 0:
        return None
    return 1e3 * busy / launches
