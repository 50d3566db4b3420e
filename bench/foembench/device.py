"""The chip: finding it, its published peaks, its memory, and compiles.

Nothing here touches JAX at import time; a run calls :func:`require_chips`
first, which fails (no fallback to the CPU) when JAX finds no TPU.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Dict, List

from foembench.spec import BENCH_DIR


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class UnknownDevice(KeyError):
    """A ``device_kind`` that ``peaks.json`` has no row for."""


def enable_compile_cache(bench_dir: str = BENCH_DIR) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (``bench/.jax_cache``), unless ``JAX_COMPILATION_CACHE_DIR`` names one.

    Every program is cached, however quickly it compiled, so a second run of
    a cell loads everything and compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        bench_dir, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int) -> List:
    """The first ``n`` TPU devices; :class:`NoChip` otherwise."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(
            f"JAX found no TPU (platform {devs[0].platform if devs else None!r});"
            " this benchmark measures the chip and never falls back to the CPU")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips; JAX sees {len(devs)}")
    return devs[:n]


def load_peaks(path: str = os.path.join(BENCH_DIR, "peaks.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(kind: str, table: Dict = None) -> Dict:
    """Published peaks of one chip of ``kind``: ``flops_per_s``,
    ``hbm_bytes_per_s``, ``hbm_bytes``.  An unknown kind is an error."""
    table = load_peaks() if table is None else table
    row = table.get("devices", {}).get(kind)
    if row is None:
        raise UnknownDevice(
            f"no peaks for device_kind {kind!r} in peaks.json "
            f"(have: {sorted(table.get('devices', {}))})")
    return row


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileCounter:
    """Counts, while armed, the programs JAX lowered (a new trace, whether
    the persistent cache then had it or not) and the XLA backend
    compilations (cache misses), through JAX's monitoring events."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lowered = 0
        self.compiled = 0
        self.armed = False
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if not self.armed or event not in (self._LOWER, self._BACKEND):
            return
        with self._lock:
            if event == self._LOWER:
                self.lowered += 1
            else:
                self.compiled += 1
