"""Find a cell's parts by name.

``BENCHMARK.json`` names each cell (workload), its configuration and its
traffic mix, and each metric.  Everything that belongs to one of them lives
in a file of its own under the benchmark directory, found by that name:

* ``BENCHMARK.json`` ``configs[].file`` — the configuration's sizes, with
  an optional ``"reference": "<module>"`` (default ``"reference"``): the
  plain reference in ``foembench/<module>.py`` that its cells compare with;
* ``traffic/<mix>.json`` — the parameters of a traffic mix, read by the one
  general generator in ``foembench.traffic``, with an optional
  ``"runner": "<module>"``: the code in ``foembench/<module>.py`` that runs
  a cell of this mix (default by ``kind``: ``"stream"`` → ``train_cell``,
  ``"open_loop"`` → ``serve_cell``);
* ``metrics/<metric>.py`` — the reader of one per-layer metric, a module
  with ``read(ctx) -> float | None``.

So a later change adds a configuration (with its own reference), a mix
(with its own runner) or a metric by adding files and entries, never by
editing a file that is there.

A reference module gives what the runners call:

* ``StepInput(key, vocab_pos, word_ids, counts, sweeps)`` and
  ``foem_steps(steps, view_rows, K, *, vocab, alpha_m1, beta_m1, warmup,
  check_every, active, dtype=float32)`` -> per step ``(rows (view_rows, K),
  totals (K,), train_ppl)``: consecutive FOEM minibatches from an empty φ̂;
* ``heldout_perplexity(docs, row_of, phi_rows, phi_k, *, vocab, alpha_m1,
  beta_m1, split_seed)`` -> float: eq. 21 on held-out documents;
* ``normalized_phi(phi, phi_k, vocab, beta_m1)`` -> (W, K) eq. 10, and
  ``infer_theta(docs, keys, phi_n, *, alpha_m1, sweeps, bucket)`` -> (n, K)
  θ of each ``(words, counts)`` request from its own key.

A runner module gives ``run(cell, env, seed, seconds, trace, *, control,
fault, **kw) -> dict`` (``runner.Env`` is ``env``) with ``notes`` (lines to
print) and ``checks`` (``checks.Check`` by name); a run with a window adds
``e2e`` (each end-to-end metric by name), ``ctx`` (what the per-layer
readers read), ``attempted``, ``failed`` and ``memory_peak_bytes``.

A serving mix may also carry ``replicas`` (default 1: one
``ServingEngine``; N > 1: a thread ``ReplicaPool`` of N ``TopicServer``s,
one chip each, so the cell's ``chips`` must equal it) and
``knee_docs_per_s`` (default: the configuration's ``serve`` knee), the
measured knee of that deployment that ``load`` is a share of.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional

#: the benchmark's own directory (``bench/``) and the checkout root above it
PACKAGE_DIR = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(PACKAGE_DIR)
ROOT = os.path.dirname(BENCH_DIR)

#: the runner of a mix that names none, by its ``kind``
RUNNER_OF_KIND = {"stream": "train_cell", "open_loop": "serve_cell"}


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
    reference: ModuleType
    runner: ModuleType


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


def load_module(name: str, what: str, bench_dir: str = BENCH_DIR
                ) -> ModuleType:
    """``foembench/<name>.py`` under ``bench_dir``: this package's own
    module when the file is in it, else the file loaded on its own."""
    if not re.fullmatch(r"[A-Za-z_][A-Za-z0-9_]*", name):
        raise SpecError(f"{what} {name!r} is not a module name")
    path = os.path.join(bench_dir, "foembench", f"{name}.py")
    if not os.path.exists(path):
        raise SpecError(f"{what} {name!r} has no module at {path}")
    if os.path.samefile(os.path.dirname(path), PACKAGE_DIR):
        return importlib.import_module(f"foembench.{name}")
    mod_name = "foembench_" + re.sub(r"\W", "_", os.path.abspath(path))
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[mod_name]


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
    mix = load_traffic(entry["traffic"], bench_dir)
    runner = mix.get("runner") or RUNNER_OF_KIND.get(mix.get("kind"))
    if runner is None:
        raise SpecError(f"traffic mix {mix['name']!r} names no runner and "
                        f"has unknown kind {mix.get('kind')!r}")
    chips = int(entry["chips"])
    replicas = int(mix.get("replicas", 1))
    if mix.get("kind") == "open_loop" and chips != replicas:
        raise SpecError(f"workload {workload!r} asks for {chips} chips, but "
                        f"its mix {mix['name']!r} serves {replicas} "
                        "replicas, one chip each")
    e2e = [_metric(m) for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [_metric(m) for m in bench["per_layer"] if _applies(m, workload)]
    readers = {m.name: load_reader(m.name, bench_dir) for m in layer}
    return Cell(name=workload, chips=chips, config=config, traffic=mix,
                end_to_end=e2e, per_layer=layer, readers=readers,
                reference=load_module(config.get("reference", "reference"),
                                      "reference", bench_dir),
                runner=load_module(runner, "runner", bench_dir))
