"""One run of one cell: find the chip, run the cell's runner, read its
metrics, decide ``correct``, and build the result line."""
from __future__ import annotations

import dataclasses
import math
import os
import sys
from typing import Callable, Dict, List, Optional

from foembench import checks, device
from foembench.spec import BENCH_DIR, Cell


@dataclasses.dataclass
class Env:
    """What a cell's run is given besides its cell and seed."""

    devices: List
    counter: "device.CompileCounter"
    out_dir: str
    say: Callable[[str], None]

    def memory_peak(self) -> int:
        return device.memory_peak_bytes(self.devices)


def read_per_layer(cell: Cell, ctx: Dict) -> Dict[str, Dict]:
    """Each per-layer metric its reader finds something for."""
    out = {}
    for m in cell.per_layer:
        value = cell.readers[m.name](ctx)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, peaks: Optional[Dict] = None,
             control: bool = False, fault=None,
             out_dir: str = os.path.join(BENCH_DIR, "out"),
             say: Callable[[str], None] = print, **kw) -> Dict:
    """Run ``cell`` once and return its result line (a dict).

    ``require_tpu=False`` (tests only) skips the look for a chip and runs
    on whatever JAX has, with the given ``peaks``."""
    import jax

    if require_tpu:
        device.enable_compile_cache()
        devices = device.require_chips(cell.chips)
        peaks = device.peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()[:cell.chips]
    env = Env(devices, device.CompileCounter(), out_dir, say)
    os.makedirs(out_dir, exist_ok=True)
    out = cell.runner.run(cell, env, seed, seconds, trace, control=control,
                          fault=fault, **kw)
    for line in out["notes"]:
        say(line)
    record = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(out.get("memory_peak_bytes", 0))}
    result = {"correct": checks.all_pass(out["checks"]),
              "attempted": int(out.get("attempted", 0)),
              "failed": int(out.get("failed", 0))}
    if "e2e" not in out:            # readings only: no window ran
        result["metrics"] = {}
    elif not trace:
        result["metrics"] = {
            m.name: {"value": float(out["e2e"][m.name]), "unit": m.unit}
            for m in cell.end_to_end}
    else:
        ctx = dict(out["ctx"], peaks=peaks, cell=cell.name)
        result["metrics"] = read_per_layer(cell, ctx)
        red = ctx.get("trace")
        if red is not None:
            record["busy_s"] = red.busy_s()
            record["window_s"] = red.window_s
            result["breakdown"] = red.breakdown()
    result["device"] = record
    result["checks"] = out["checks"]
    return result


def emit(result: Dict) -> None:
    """Print the compared numbers beside their limits as the last lines of
    standard error, and the result as the last line of standard output."""
    import json

    sys.stdout.flush()
    for name, c in result["checks"].items():
        verdict = "ok" if (math.isfinite(c["value"])
                           and c["value"] <= c["limit"]) else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
