"""Shared TPU launch budget model for the Pallas sweep engine.

One accounting of on-chip memory for every registered kernel launch, so the
runtime dispatch heuristics (``ops.sweep``/``ops.infer`` deciding fused
kernel vs. portable scan) and the static analyzer (``analysis.check_all``)
can never disagree — both call into this module.  Before this module each
kernel carried its own hand-derived byte formula (``gs_sweep.fits_vmem``,
``theta_sweep.theta_fits_vmem``, …); those entry points remain but now
delegate to the contract registry built on this model.

The model (see ``docs/ARCHITECTURE.md`` §"Kernel contracts & static
analysis" for the per-kernel instantiations):

* **VMEM** (~16 MB per core).  Every BlockSpec block is padded to the f32
  tile — sublanes to a multiple of 8, lanes to a multiple of 128 — and
  counted once if its index map is constant over the grid (a *carried*
  block: Pallas fetches it once and holds it), twice if the index map
  varies (the pipeline double-buffers it).  Aliased carried outputs are
  separate VMEM blocks from their donated inputs, so a carried in/out pair
  costs 2×.  Scratch allocations count once.  The default launch budget is
  12 MB — ¾ of a core, leaving headroom for pipeline bookkeeping and the
  compiler's own temporaries.
* **SMEM**.  Scalar-prefetch operands (``PrefetchScalarGridSpec``) live in
  scalar memory, which is far smaller than VMEM.  A 2-D table is padded to
  the (8, 128) tile, so the kernels pass the (W_s, A) active-topic table
  flattened: ~512 KB for W_s=8k, A=16, the dominant consumer.  The default
  budget is 1 MB.
* **Tile sizing** for the grid-over-token-blocks kernels
  (``foem_estep``/``topk_estep``) uses ``ESTEP_TILE_BUDGET`` (two thirds of
  the launch budget): the block-token count BT is chosen so the six live
  (BT, K) tiles fit.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Mapping, Optional, Tuple

VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
#: Default per-launch VMEM budget (bytes): ~3/4 of a core.
DEFAULT_VMEM_BUDGET = 12 * 1024 * 1024
#: Default scalar-prefetch (SMEM) budget per launch (bytes).
DEFAULT_SMEM_BUDGET = 1024 * 1024
#: Tile-sizing budget for the token-block E-step kernels (bytes).
ESTEP_TILE_BUDGET = DEFAULT_VMEM_BUDGET * 2 // 3

SUBLANE = 8      # f32 second-minor tile extent
LANE = 128       # minor (lane) tile extent


def round_up(n: int, m: int) -> int:
    """Round ``n`` up to a multiple of ``m`` (identity for ``m <= 1``)."""
    if m <= 1:
        return n
    return n + (-n) % m


@dataclasses.dataclass(frozen=True)
class Cell:
    """One static launch shape: the axes every sweep kernel is sized by.

    ``D`` documents (sublane-padded to 8 by the wrappers), ``L`` token
    columns, ``K`` topics (lane-padded to ``lane_align``), ``W_s`` live
    vocabulary rows, ``A`` active topics per word (0 = dense-only cell).
    """

    D: int
    L: int
    K: int
    W_s: int
    A: int = 0

    def padded(self, lane_align: int = LANE) -> Tuple[int, int]:
        """(Dp, Kp) at the wrapper's padding for ``lane_align``."""
        return round_up(self.D, SUBLANE), round_up(self.K, lane_align)

    def label(self) -> str:
        base = f"D={self.D} L={self.L} K={self.K} W_s={self.W_s}"
        return base + (f" A={self.A}" if self.A else "")


@dataclasses.dataclass(frozen=True)
class Block:
    """One BlockSpec operand of a launch, as the budget model sees it.

    ``block_shape`` is the VMEM block; ``full_shape`` the HBM operand it
    tiles; ``max_index`` the largest block index the index map emits over
    the whole grid (checked against ``full_shape``).  ``carried=True``
    marks a constant index map — fetched once, not double-buffered.
    """

    name: str
    block_shape: Tuple[int, ...]
    full_shape: Tuple[int, ...]
    max_index: Tuple[int, ...]
    carried: bool = False
    dtype: str = "float32"
    dtype_bytes: int = 4

    def vmem_bytes(self) -> int:
        return vmem_block_bytes(self.block_shape, self.dtype_bytes)

    def live_bytes(self) -> int:
        """VMEM bytes held live: ×2 when the pipeline double-buffers."""
        return self.vmem_bytes() * (1 if self.carried else 2)


@dataclasses.dataclass(frozen=True)
class Scalar:
    """One scalar-prefetch operand (lives in SMEM for the whole launch)."""

    name: str
    shape: Tuple[int, ...]
    dtype: str = "int32"
    dtype_bytes: int = 4

    def smem_bytes(self) -> int:
        """Scalar-memory bytes: a 2-D table pads its minor dim to 128 words
        and its second-minor to 8 rows, so a (W_s, 16) table costs 8x its
        data; the kernels flatten large tables to 1-D."""
        shape = self.shape
        if len(shape) >= 2:
            shape = (*shape[:-2], round_up(shape[-2], SUBLANE),
                     round_up(shape[-1], LANE))
        return math.prod(shape) * self.dtype_bytes


@dataclasses.dataclass(frozen=True)
class LaunchSpec:
    """A fully instantiated launch at one :class:`Cell`.

    Flat operand numbering (what ``input_output_aliases`` keys refer to)
    is ``scalars + inputs``; ``aliases`` maps flat input index → output
    index, mirroring the kernel's ``pl.pallas_call`` call site exactly.
    """

    kernel: str
    grid: Tuple[int, ...]
    scalars: Tuple[Scalar, ...]
    inputs: Tuple[Block, ...]
    outputs: Tuple[Block, ...]
    scratch: Tuple[Block, ...]
    aliases: Mapping[int, int]

    @property
    def num_scalar_prefetch(self) -> int:
        return len(self.scalars)

    def flat_input(self, idx: int) -> Optional[Block]:
        """The input Block at flat operand index ``idx`` (None = scalar)."""
        n = len(self.scalars)
        if idx < n:
            return None
        return self.inputs[idx - n]


def vmem_block_bytes(shape: Tuple[int, ...], dtype_bytes: int = 4) -> int:
    """Physical VMEM footprint of one block: tile-padded to (8, 128).

    A 1-wide minor dim still occupies a full 128-lane tile row (this is
    why the (D, 1) per-column operands cost D·128 floats, not D), and the
    second-minor dim rounds to the 8-sublane f32 tile.
    """
    if not shape:
        shape = (1, 1)
    elif len(shape) == 1:
        shape = (1,) + tuple(shape)
    lead = math.prod(shape[:-2]) if len(shape) > 2 else 1
    return (
        lead
        * round_up(shape[-2], SUBLANE)
        * round_up(shape[-1], LANE)
        * dtype_bytes
    )


def vmem_terms(spec: LaunchSpec) -> Dict[str, int]:
    """Itemised VMEM live-set bytes per operand of one launch."""
    terms: Dict[str, int] = {}
    for b in spec.inputs + spec.outputs:
        terms[b.name] = terms.get(b.name, 0) + b.live_bytes()
    for b in spec.scratch:
        terms[b.name] = terms.get(b.name, 0) + b.vmem_bytes()
    return terms


def vmem_total(spec: LaunchSpec) -> int:
    return sum(vmem_terms(spec).values())


def smem_total(spec: LaunchSpec) -> int:
    return sum(s.smem_bytes() for s in spec.scalars)


def dominating_term(spec: LaunchSpec) -> Tuple[str, int]:
    """(operand name, bytes) of the largest VMEM consumer."""
    terms = vmem_terms(spec)
    name = max(terms, key=lambda k: terms[k])
    return name, terms[name]


def estep_token_block(num_topics: int,
                      budget: int = ESTEP_TILE_BUDGET) -> int:
    """Largest multiple-of-8 token block with 6 live (BT, K) f32 tiles.

    The tile-sizing rule of the token-block E-step kernels
    (``foem_estep``/``topk_estep``): θ̂/φ̂/exclude/μ_old in, μ_new/residual
    out — six (BT, K) tiles live at once — capped at 1024 tokens.
    """
    per_token = 6 * num_topics * 4
    bt = max(8, (budget // per_token) // 8 * 8)
    return int(min(bt, 1024))
