"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell (workload), its configuration and its
traffic mix, and each metric.  Everything that belongs to one of them lives
in a file of its own under the benchmark directory, found by that name:

* ``BENCHMARK.json`` ``configs[].file`` — the configuration's sizes;
* ``traffic/<mix>.json`` — the parameters of a traffic mix, read by the one
  general generator in ``foembench.traffic``;
* ``metrics/<metric>.py`` — the reader of one per-layer metric, a module
  with ``read(ctx) -> float | None``.

So a later change adds a configuration, a mix or a metric by adding files and
entries, never by editing a file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

#: the benchmark's own directory (``bench/``) and the checkout root above it
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` that no file answers to, or a bad file."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    """One workload: a configuration under a traffic mix, with the metrics
    it reports in a plain (``end_to_end``) and a traced (``per_layer``) run."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]
    readers: Dict[str, Callable]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SpecError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"])


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_reader(name: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``metrics/<name>.py``'s ``read`` function."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"per-layer metric {name!r} has no reader at {path}")
    mod_name = "foembench_metric_" + "".join(
        ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod.read


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    path = os.path.join(bench_dir, "traffic", f"{name}.json")
    if not os.path.exists(path):
        raise SpecError(f"traffic mix {name!r} has no file at {path}")
    mix = load_json(path)
    mix.setdefault("name", name)
    return mix


def load_cell(workload: str, bench: Optional[dict] = None,
              root: str = ROOT, bench_dir: str = BENCH_DIR) -> Cell:
    """Everything a run of ``workload`` needs, found by name."""
    bench = load_benchmark(root) if bench is None else bench
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no workload {workload!r} (have: {names})")
    conf = next((c for c in bench["configs"] if c["name"] == entry["config"]),
                None)
    if conf is None:
        raise SpecError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    config = load_json(os.path.join(root, conf["file"]))
    config.setdefault("name", conf["name"])
    e2e = [_metric(m) for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [_metric(m) for m in bench["per_layer"] if _applies(m, workload)]
    readers = {m.name: load_reader(m.name, bench_dir) for m in layer}
    return Cell(name=workload, chips=int(entry["chips"]), config=config,
                traffic=load_traffic(entry["traffic"], bench_dir),
                end_to_end=e2e, per_layer=layer, readers=readers)
