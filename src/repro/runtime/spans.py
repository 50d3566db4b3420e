"""Named host stages: one helper both times a stage into the record of
its training step or serving launch and names it in the profiler's trace.

``with span("foem.fetch", rec, "fetch_seconds"):`` adds the block's
``time.perf_counter()`` duration to ``rec["fetch_seconds"]`` (and keeps it
as ``.seconds``), and opens ``jax.profiler.TraceAnnotation`` of the same
name, so the stage sits in the profiler's host plane on the device
operations' clock.  The annotation is inert when no profiler session is
active; the timing is always on.  Span names are stable: trace readers
match them.
"""
from __future__ import annotations

import time
from typing import Optional

from jax.profiler import TraceAnnotation


class span:
    """Time one host stage and annotate it for the profiler."""

    __slots__ = ("_ann", "_rec", "_key", "_t0", "seconds")

    def __init__(self, name: str, rec: Optional[dict] = None,
                 key: Optional[str] = None):
        self._ann = TraceAnnotation(name)
        self._rec = rec
        self._key = key
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        if self._rec is not None:
            self._rec[self._key] = self._rec.get(self._key, 0.0) + self.seconds
