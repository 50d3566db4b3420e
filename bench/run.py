#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload kos_k100.train --seed 7 --seconds 51 \
        --trace 0

Run it from the root of a checkout.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` reports its per-layer metrics from a
profiler trace of a slice of the window.  The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` when traced, and last ``checks``: each number
compared with the reference beside its limit); the same checks are the last
lines of standard error.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = os.path.dirname(HERE)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(root, "src"))
    from foembench import device, runner, spec

    try:
        cell = spec.load_cell(args.workload)
        result = runner.run_cell(cell, args.seed, args.seconds,
                                 bool(args.trace),
                                 say=lambda s: print(s, flush=True))
    except (device.NoChip, spec.SpecError, ImportError) as e:
        print(f"bench/run.py: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    runner.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
