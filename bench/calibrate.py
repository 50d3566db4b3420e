#!/usr/bin/env python3
"""Measurements that set the benchmark's numbers, run once on the chip in
one process each (the benchmark's own runs never run these):

    python3 bench/calibrate.py knee --workload kos_k100.serve_poisson \
        --rates 1000,2000,4000 --seconds 10
        # completed rate, p99 and generator lateness at each offered rate
    python3 bench/calibrate.py readings --workload kos_k100.train \
        --seeds 1,2,3 [--control] [--seconds 8]
        # the compared numbers of the program (or of the control: the
        # reference in bfloat16 for training, the program's bfloat16 phi
        # path for serving; or of the program with a --fault planted) on
        # each seed, one JSON line each

A traced run's breakdown is ``bench/run.py --trace 1``'s.

Each writes its lines to standard output and to ``--out`` (default
``chiprun_out/calibrate.jsonl`` under the checkout).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("knee", "readings"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None,
                    help="plant a fault of foembench.faults under the timed "
                         "path (readings)")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "calibrate.jsonl"))
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from foembench import device, faults, runner, spec

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    sink = open(args.out, "a")

    def record(obj):
        obj = dict(obj, what=args.what, workload=args.workload,
                   at=time.strftime("%H:%M:%S"))
        line = json.dumps(obj)
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    cell = spec.load_cell(args.workload)
    say = lambda s: print(s, flush=True)
    if args.what == "knee":
        device.enable_compile_cache()
        devices = device.require_chips(cell.chips)
        env = runner.Env(devices, device.CompileCounter(),
                         os.path.join(HERE, "out"), say)
        for rate in [float(r) for r in args.rates.split(",")]:
            out = cell.runner.run(cell, env, args.seed, args.seconds, False,
                                  rate=rate)
            for line in out["notes"]:
                say(line)
            record({"rate": rate, **out["e2e"], "failed": out["failed"],
                    "checks": out["checks"]})
    else:
        serving = cell.traffic["kind"] == "open_loop"
        kw = {} if serving else {"window_on": False}
        if args.fault:
            kw["fault"] = (faults.SERVE if serving else faults.TRAIN)[args.fault]
        for seed in [int(s) for s in args.seeds.split(",")]:
            t = time.perf_counter()
            res = runner.run_cell(cell, seed, args.seconds, False,
                                  control=args.control, say=say, **kw)
            record({"seed": seed, "control": args.control, "fault": args.fault,
                    "correct": res["correct"], "checks": res["checks"],
                    "metrics": res["metrics"],
                    "seconds": time.perf_counter() - t})
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
