"""The work a sweep needs, counted from its shapes in f32, and the least
time the chip could take for it.

Per token with a nonzero count and per topic lane it updates (K for a dense
sweep, the A active topics for a scheduled one), a column-serial E-step
(eq. 13 with exclusion, normalisation, eq. 36 residual, the three folds)
takes ``FLOPS_PER_LANE`` operations and must move ``BYTES_PER_LANE``
bytes of μ (read the old, write the new) and residual.  The working φ̂
rows and θ̂ of those lanes are read and written once per sweep.  Nothing
else is counted: this is the least the sweep needs, whatever implements
it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

#: exclusion 3, clamps 2, smoothing adds 2, product 1, quotient 1,
#: normaliser sum 1 and divide 1, residual 3, delta 2, folds 3
FLOPS_PER_LANE = 19
#: μ read 4 + μ write 4 + residual write 4 (f32)
BYTES_PER_LANE = 12
#: a φ̂ or θ̂ row entry read and written once per sweep (f32)
BYTES_PER_STATE_ENTRY = 8


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_seconds(self, peaks: Dict) -> float:
        return max(self.flops / peaks["flops_per_s"],
                   self.bytes / peaks["hbm_bytes_per_s"])

    def bound(self, peaks: Dict) -> str:
        return ("compute" if self.flops / peaks["flops_per_s"]
                >= self.bytes / peaks["hbm_bytes_per_s"] else "bandwidth")


def sweep(tokens: int, lanes: int, words: int, docs: int) -> Work:
    """One sweep over ``tokens`` nonzero (doc, word) entries, ``lanes``
    topics each, with ``words`` φ̂ rows and ``docs`` θ̂ rows of that many
    lanes."""
    return Work(float(FLOPS_PER_LANE) * tokens * lanes,
                float(BYTES_PER_LANE) * tokens * lanes
                + float(BYTES_PER_STATE_ENTRY) * (words + docs) * lanes)


def minibatch(tokens: int, words: int, docs: int, *, topics: int,
              active: int, sweeps: int, warmup: int) -> Work:
    """FOEM's sweeps on one minibatch: ``warmup`` dense sweeps over all K
    topics, the rest scheduled over the A active topics."""
    dense = min(sweeps, warmup)
    full = sweep(tokens, topics, words, docs)
    part = sweep(tokens, active, words, docs)
    return Work(dense * full.flops + (sweeps - dense) * part.flops,
                dense * full.bytes + (sweeps - dense) * part.bytes)
