"""Kernels: the least time the chip's peaks allow for the sweeps of the
traced steps (the benchmark's own work count, so the same work whatever
implements it), over the device time of what implemented them, in %.

What implemented them is read from the program's dispatch decisions
(``ctx["sweep_paths"]``), not guessed from the names the trace holds.
Where every sweep took the Pallas kernels, the denominator is their device
time (``SWEEP_KERNELS``).  Where any sweep took the portable path, which
has no operation of its own (its scans sit inside the trainer's step
program among the scheduler updates), it is that whole program's device
time (``STEP_PROGRAM``), and the share is a lower bound.  Where the chosen
path's operations are not in the trace, nothing is reported.  The bytes
term bounds the sweep at every cell's shapes.
"""

#: the Pallas sweep kernels, by their operation names in the trace
SWEEP_KERNELS = [r"^%?(gs|scheduled)_sweep_pallas"]
#: the trainer's jitted step (``FOEMTrainer._local_step_fn``'s ``run``)
STEP_PROGRAM = [r"^jit_run\b"]


def read(ctx):
    red = ctx.get("trace")
    paths = ctx.get("sweep_paths")
    if (ctx.get("kind") != "train" or red is None or not paths
            or not ctx.get("trace_work")):
        return None
    if paths == {"pallas"}:
        busy = red.time_s(SWEEP_KERNELS)
    else:
        busy = red.module_time_s(STEP_PROGRAM)
    if busy <= 0:
        return None
    total = ctx["trace_work"][0]
    for w in ctx["trace_work"][1:]:
        total = total + w
    return 100.0 * total.least_seconds(ctx["peaks"]) / busy
