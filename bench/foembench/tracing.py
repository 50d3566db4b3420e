"""Profiler capture and the reduction from a trace to numbers.

A traced run opens one :class:`Capture` over a steady slice of its window.
The slice is marked by a host span named ``bench.trace_window``, so the
reduction measures on the trace's own clock.  The reduction works on plain
:class:`Event` lists, so tests feed it small synthetic traces:

* device busy time: the union of the intervals of device operations;
* time by name pattern: summed device time of the operations whose name
  matches one of a metric's regular expressions;
* the breakdown: the device operations that took most time, and the
  longest idle gaps, each named by what the host was doing in it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"

#: device line that holds one event per executed operation (a while loop
#: or conditional is an event that contains its body's operations)
DEVICE_OPS_LINE = "XLA Ops"
#: device line that holds one event per executed program
DEVICE_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    plane: str = ""
    line: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") or name.startswith("/device:GPU:")


def split_events(events: Iterable[Event]
                 ) -> Tuple[List[Event], List[Event], List[Event]]:
    """(device operations, host spans, device program executions)."""
    dev, host, modules = [], [], []
    for e in events:
        if is_device_plane(e.plane):
            if e.line == DEVICE_OPS_LINE:
                dev.append(e)
            elif e.line == DEVICE_MODULES_LINE:
                modules.append(e)
        elif e.plane.startswith("/host:") and e.dur_ns > 0:
            host.append(e)
    return dev, host, modules


def short_name(name: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


def leaves(dev: Sequence[Event]) -> List[Event]:
    """The operations that contain no other operation (a loop's or a
    conditional's event spans its body's events on the same device)."""
    out = []
    for plane in sorted({e.plane for e in dev}):
        evs = sorted((e for e in dev if e.plane == plane),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        for e, nxt in zip(evs, evs[1:] + [None]):
            if nxt is None or nxt.start_ns >= e.end_ns:
                out.append(e)
    return out


def load_xplane(path: str) -> List[Event]:
    """Every event of a ``.xplane.pb`` file, read with JAX's own reader."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns), plane.name, line.name))
    return out


def find_window(host: Sequence[Event]) -> Optional[Tuple[float, float]]:
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        return None
    e = max(spans, key=lambda s: s.dur_ns)
    return e.start_ns, e.end_ns


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            out.append(dataclasses.replace(e, start_ns=s, dur_ns=t - s))
    return out


def union_ns(events: Iterable[Event]) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(events, key=lambda e: e.start_ns):
        if cur_e is None or e.start_ns > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start_ns, e.end_ns
        else:
            cur_e = max(cur_e, e.end_ns)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_ns_per_device(dev: Sequence[Event]) -> Dict[str, float]:
    by = {}
    for e in dev:
        by.setdefault(e.plane, []).append(e)
    return {p: union_ns(evs) for p, evs in by.items()}


def matching(dev: Iterable[Event], patterns: Sequence[str]) -> List[Event]:
    rx = [re.compile(p) for p in patterns]
    return [e for e in dev if any(r.search(e.name) for r in rx)]


def time_ns(dev: Iterable[Event], patterns: Sequence[str]) -> float:
    """Summed device time of the operations matching ``patterns``."""
    return sum(e.dur_ns for e in matching(dev, patterns))


def top_ops(dev: Sequence[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` leaf operations that took most device time, by name."""
    by: Dict[str, float] = {}
    for e in leaves(dev):
        k = short_name(e.name)
        by[k] = by.get(k, 0.0) + e.dur_ns
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def gaps(dev: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of one device's operations inside [lo, hi]."""
    out, cur = [], lo
    for e in sorted(dev, key=lambda e: e.start_ns):
        if e.start_ns > cur:
            out.append((cur, e.start_ns))
        cur = max(cur, e.end_ns)
    if hi > cur:
        out.append((cur, hi))
    return out


def name_gap(lo: float, hi: float, host: Sequence[Event]) -> str:
    """What the host was doing in an idle gap: the shortest host span that
    covers at least half the gap, else the one overlapping it most."""
    best, best_key = "host idle", None
    length = hi - lo
    for e in host:
        if e.name == WINDOW_SPAN:
            continue
        ov = min(hi, e.end_ns) - max(lo, e.start_ns)
        if ov <= 0:
            continue
        key = (0, e.dur_ns) if ov >= 0.5 * length else (1, -ov)
        if best_key is None or key < best_key:
            best, best_key = e.name, key
    return best


def idle_gaps(dev: Sequence[Event], host: Sequence[Event], lo: float,
              hi: float, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of the first device, named."""
    planes = sorted({e.plane for e in dev})
    first = [e for e in dev if planes and e.plane == planes[0]]
    longest = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[name_gap(s, t, host), (t - s) * 1e-9] for s, t in longest]


@dataclasses.dataclass
class Reduced:
    """A trace reduced to what the per-layer readers take."""

    device_ops: List[Event]     # clipped to the window
    host: List[Event]
    window_ns: Tuple[float, float]
    modules: List[Event] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices that ran operations."""
        per = busy_ns_per_device(self.device_ops)
        return (sum(per.values()) / len(per)) * 1e-9 if per else 0.0

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not self.device_ops:
            return None
        return max(0.0, 1.0 - self.busy_s() / self.window_s)

    def time_s(self, patterns: Sequence[str]) -> float:
        return time_ns(self.device_ops, patterns) * 1e-9

    def module_time_s(self, patterns: Sequence[str]) -> float:
        """Device time of the programs whose name matches ``patterns``."""
        return time_ns(self.modules, patterns) * 1e-9

    def count(self, patterns: Sequence[str]) -> int:
        return len(matching(self.device_ops, patterns))

    def breakdown(self) -> Dict:
        lo, hi = self.window_ns
        return {"device_ops": top_ops(self.device_ops),
                "idle_gaps": idle_gaps(self.device_ops, self.host, lo, hi)}


def reduce_events(events: Iterable[Event]) -> Reduced:
    dev, host, modules = split_events(events)
    window = find_window(host)
    if window is None:
        if not dev:
            raise ValueError("the trace holds no device operation and no "
                             f"{WINDOW_SPAN} span")
        window = (min(e.start_ns for e in dev), max(e.end_ns for e in dev))
    return Reduced(clip(dev, *window), host, window, clip(modules, *window))


class Capture:
    """One profiler session over a slice of the window, written under
    ``out_dir`` (which is emptied first).  ``python`` turns on the
    profiler's Python tracer, whose spans name the idle gaps."""

    def __init__(self, out_dir: str, python: bool = True):
        self.out_dir = out_dir
        self.python = python
        self._span = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1 if self.python else 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self) -> Reduced:
        files = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return reduce_events(load_xplane(max(files, key=os.path.getmtime)))
