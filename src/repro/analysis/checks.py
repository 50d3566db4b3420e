"""Static checks of the kernel launch contracts against the budget model.

``check_all`` sweeps every registered :class:`LaunchContract` over a grid
of (D, L, K, W_s, A) cells — always including the BENCH_* reference cells
and the ROADMAP's W_s=8k/K=128 target — and reports, per (kernel, cell):

* VMEM live-set fit (carried + scratch + double-buffered per-column
  blocks) with the dominating operand,
* SMEM scalar-prefetch fit,
* lane/sublane alignment of every block (each of the last two dims a
  multiple of the (8, 128) tile or equal to the operand's dim),
* ``input_output_aliases`` shape/dtype consistency and donation coverage
  (every VMEM-carried output must be donated — a carried output without
  an alias would silently double the HBM footprint),
* index-map bounds vs. grid extents (the block index range each index
  map emits must stay inside the full operand).

A cell "fits" only if the byte budgets hold AND no structural errors were
found.  ``assert_reference_cells`` is the CI gate.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from repro.analysis import budget as bm
from repro.analysis.budget import Cell, LaunchSpec
from repro.analysis.contracts import KERNEL_CONTRACTS

#: Named reference cells: every BENCH_* pinned shape plus the ROADMAP
#: target.  (The serving benchmark's cell coincides with the sweep
#: benchmark's full cell; both labels are kept for provenance.)
REFERENCE_CELLS: Tuple[Tuple[str, Cell], ...] = (
    ("BENCH_sweep full", Cell(D=256, L=64, K=128, W_s=8192, A=16)),
    ("BENCH_sweep quick", Cell(D=32, L=16, K=32, W_s=512, A=8)),
    ("BENCH_serve", Cell(D=256, L=64, K=128, W_s=8192, A=16)),
    ("ROADMAP W_s=8k/K=128", Cell(D=256, L=64, K=128, W_s=8192, A=16)),
)

#: Quantized-serving showcase cells, checked ONLY against the quantized
#: theta_sweep contracts: at W_s=32768 the f32 φ block alone is 16 MiB
#: (over the 12 MiB VMEM budget), while bf16 (~8 MiB) and int8 (~4 MiB +
#: a 128 KiB SMEM scale vector) still fit — the concrete "halving VMEM
#: doubles the servable W_s×K" cell pinned by BENCH_serve's quant suite.
#: A=0 keeps the (W_s, A) schedule table out of SMEM so the comparison
#: isolates the φ footprint.
QUANT_KERNELS: Tuple[str, ...] = ("theta_sweep_bf16", "theta_sweep_int8")
QUANT_REFERENCE_CELLS: Tuple[Tuple[str, Cell], ...] = (
    ("BENCH_serve quant W_s=16k", Cell(D=256, L=64, K=128, W_s=16384, A=0)),
    ("BENCH_serve quant W_s=32k", Cell(D=256, L=64, K=128, W_s=32768, A=0)),
)

#: Default exploration grid for ``check_all`` (beyond the reference cells):
#: where does the single-launch working set stop fitting?
DEFAULT_GRID_D = (64, 256, 1024)
DEFAULT_GRID_K = (64, 128, 256)
DEFAULT_GRID_W = (2048, 8192, 16384, 32768)


@dataclasses.dataclass(frozen=True)
class CheckReport:
    """The analyzer's verdict for one (kernel, cell) pair."""

    kernel: str
    label: str
    cell: Cell
    vmem_bytes: int
    vmem_budget: int
    smem_bytes: int
    smem_budget: int
    dominating: Tuple[str, int]
    errors: Tuple[str, ...]

    @property
    def fits_vmem(self) -> bool:
        return self.vmem_bytes <= self.vmem_budget

    @property
    def fits_smem(self) -> bool:
        return self.smem_bytes <= self.smem_budget

    @property
    def ok(self) -> bool:
        return self.fits_vmem and self.fits_smem and not self.errors

    def reason(self) -> str:
        if self.errors:
            return self.errors[0]
        if not self.fits_vmem:
            name, nbytes = self.dominating
            return (
                f"VMEM {self.vmem_bytes / 2**20:.2f} MiB > "
                f"{self.vmem_budget / 2**20:.2f} MiB "
                f"(dominated by {name}: {nbytes / 2**20:.2f} MiB)"
            )
        if not self.fits_smem:
            return (
                f"SMEM {self.smem_bytes / 2**10:.0f} KiB > "
                f"{self.smem_budget / 2**10:.0f} KiB"
            )
        return "ok"


def _alignment_errors(spec: LaunchSpec, lane_align: int = bm.LANE) -> List[str]:
    """The TPU block tiling rule for every BlockSpec operand.

    Each of a block's last two dims must be a multiple of the tile — 128
    lanes, 8 sublanes — or equal the operand's own dim there.  A minor dim
    of 1 is legal only when the operand's minor dim is 1 too: a (D, 1)
    column block over a (D, L) array is refused by the compiler.
    """
    if lane_align <= 1:
        # interpret-mode layout: blocks are plain arrays, no (8, 128)
        # tiling exists, so lane/sublane residues are meaningless
        return []
    errs = []
    for b in spec.inputs + spec.outputs:
        shape, full = tuple(b.block_shape), tuple(b.full_shape)
        if len(shape) < 2:
            shape, full = (1,) + shape, (1,) + full
        for axis, tile, what in ((-1, bm.LANE, "minor"),
                                 (-2, bm.SUBLANE, "second-minor")):
            dim = shape[axis]
            if dim % tile and dim != full[axis]:
                errs.append(
                    f"{spec.kernel}/{b.name}: {what} block dim {dim} is "
                    f"neither a multiple of the {tile}-"
                    f"{'lane' if tile == bm.LANE else 'sublane'} tile nor "
                    f"the operand's dim {full[axis]}"
                )
    return errs


def _alias_errors(spec: LaunchSpec) -> List[str]:
    errs = []
    donated_outputs = set()
    for flat_idx, out_idx in spec.aliases.items():
        if out_idx >= len(spec.outputs):
            errs.append(
                f"{spec.kernel}: alias target {out_idx} out of range"
            )
            continue
        out = spec.outputs[out_idx]
        inp = spec.flat_input(flat_idx)
        if inp is None:
            errs.append(
                f"{spec.kernel}: alias source {flat_idx} is a "
                "scalar-prefetch operand (cannot be donated)"
            )
            continue
        donated_outputs.add(out_idx)
        if tuple(inp.full_shape) != tuple(out.full_shape):
            errs.append(
                f"{spec.kernel}: aliased {inp.name}->{out.name} shape "
                f"mismatch {inp.full_shape} vs {out.full_shape}"
            )
        if inp.dtype != out.dtype:
            errs.append(
                f"{spec.kernel}: aliased {inp.name}->{out.name} dtype "
                f"mismatch {inp.dtype} vs {out.dtype}"
            )
    for i, out in enumerate(spec.outputs):
        if out.carried and i not in donated_outputs:
            errs.append(
                f"{spec.kernel}: carried output {out.name} is not donated "
                "(input_output_aliases must cover every VMEM-carried "
                "output or its HBM footprint doubles)"
            )
    return errs


def _index_map_errors(spec: LaunchSpec) -> List[str]:
    errs = []
    for b in spec.inputs + spec.outputs:
        if len(b.max_index) != len(b.block_shape) or (
            len(b.full_shape) != len(b.block_shape)
        ):
            errs.append(
                f"{spec.kernel}/{b.name}: rank mismatch between block "
                f"{b.block_shape}, operand {b.full_shape} and index "
                f"range {b.max_index}"
            )
            continue
        for axis, (idx, blk, full) in enumerate(
            zip(b.max_index, b.block_shape, b.full_shape)
        ):
            if (idx + 1) * blk > full:
                errs.append(
                    f"{spec.kernel}/{b.name}: index map reaches block "
                    f"{idx} on axis {axis} — {(idx + 1) * blk} exceeds "
                    f"the operand extent {full}"
                )
    return errs


def check_spec(
    spec: LaunchSpec,
    *,
    label: str = "",
    cell: Optional[Cell] = None,
    lane_align: int = bm.LANE,
    vmem_budget: int = bm.DEFAULT_VMEM_BUDGET,
    smem_budget: int = bm.DEFAULT_SMEM_BUDGET,
) -> CheckReport:
    """Run every static check on one instantiated launch spec."""
    errors = (
        _alignment_errors(spec, lane_align)
        + _alias_errors(spec)
        + _index_map_errors(spec)
    )
    return CheckReport(
        kernel=spec.kernel,
        label=label,
        cell=cell if cell is not None else Cell(0, 0, 0, 0),
        vmem_bytes=bm.vmem_total(spec),
        vmem_budget=vmem_budget,
        smem_bytes=bm.smem_total(spec),
        smem_budget=smem_budget,
        dominating=bm.dominating_term(spec),
        errors=tuple(errors),
    )


def check_cell(
    cell: Cell,
    *,
    label: str = "",
    kernels: Optional[Sequence[str]] = None,
    lane_align: int = bm.LANE,
    vmem_budget: int = bm.DEFAULT_VMEM_BUDGET,
    smem_budget: int = bm.DEFAULT_SMEM_BUDGET,
) -> List[CheckReport]:
    """Check every (or the named) registered kernel contract at one cell."""
    names = kernels if kernels is not None else sorted(KERNEL_CONTRACTS)
    out = []
    for name in names:
        spec = KERNEL_CONTRACTS[name].spec(cell, lane_align)
        out.append(
            check_spec(
                spec, label=label or cell.label(), cell=cell,
                lane_align=lane_align,
                vmem_budget=vmem_budget, smem_budget=smem_budget,
            )
        )
    return out


def default_cells() -> List[Tuple[str, Cell]]:
    """The reference cells plus the default exploration grid."""
    cells: List[Tuple[str, Cell]] = list(REFERENCE_CELLS)
    for d in DEFAULT_GRID_D:
        for k in DEFAULT_GRID_K:
            for w in DEFAULT_GRID_W:
                c = Cell(D=d, L=64, K=k, W_s=w, A=16)
                cells.append((c.label(), c))
    return cells


def check_all(
    cells: Optional[Iterable[Tuple[str, Cell]]] = None,
    *,
    lane_align: int = bm.LANE,
    vmem_budget: int = bm.DEFAULT_VMEM_BUDGET,
    smem_budget: int = bm.DEFAULT_SMEM_BUDGET,
) -> List[CheckReport]:
    """Sweep every registered contract over a grid of launch cells.

    ``cells`` defaults to :func:`default_cells` — the BENCH_* reference
    cells and the ROADMAP target, plus the exploration grid.  Returns one
    :class:`CheckReport` per (kernel, cell); a report with ``ok=False``
    carries the dominating VMEM term or the structural error.
    """
    reports = []
    for label, cell in (cells if cells is not None else default_cells()):
        reports.extend(
            check_cell(
                cell, label=label, lane_align=lane_align,
                vmem_budget=vmem_budget, smem_budget=smem_budget,
            )
        )
    return reports


def assert_reference_cells(lane_align: int = bm.LANE) -> List[CheckReport]:
    """CI gate: every kernel contract must verify at every reference cell.

    Raises ``AssertionError`` naming the first failing (kernel, cell) if
    any reference launch does not fit; returns the reports otherwise.

    The quantized showcase cells (:data:`QUANT_REFERENCE_CELLS`) are
    checked only against the quantized theta_sweep contracts — the f32
    kernel is *expected* not to fit there; that gap is the point.
    """
    reports = check_all(REFERENCE_CELLS, lane_align=lane_align)
    for label, cell in QUANT_REFERENCE_CELLS:
        reports.extend(
            check_cell(
                cell, label=label, kernels=QUANT_KERNELS,
                lane_align=lane_align,
            )
        )
    bad = [r for r in reports if not r.ok]
    if bad:
        lines = "\n".join(
            f"  {r.kernel} @ {r.label}: {r.reason()}" for r in bad
        )
        raise AssertionError(
            f"{len(bad)} reference launch contract(s) failed:\n{lines}"
        )
    return reports


def kernel_fits_vmem(
    kernel: str,
    num_rows: int,
    num_docs: int,
    num_topics: int,
    budget: int = bm.DEFAULT_VMEM_BUDGET,
) -> bool:
    """Dispatch-facing VMEM-fit query against the registered contract.

    The runtime heuristics (``ops.sweep``/``ops.infer`` choosing fused
    kernel vs. portable scan) call this, so dispatch and static analysis
    share one byte model by construction.  The live set is independent of
    L (per-column blocks don't scale with it) and of A (the active-topic
    table lives in SMEM), so only (W_s, D, K) are needed.
    """
    cell = Cell(D=num_docs, L=1, K=num_topics, W_s=num_rows, A=16)
    spec = KERNEL_CONTRACTS[kernel].spec(cell)
    return bm.vmem_total(spec) <= budget


def format_reports(reports: Sequence[CheckReport]) -> str:
    """Render reports as the fixed-width table the CLI and docs use."""
    header = (
        f"{'kernel':<16} {'cell':<28} {'VMEM':>10} {'SMEM':>9} "
        f"{'fit':<4} note"
    )
    lines = [header, "-" * len(header)]
    for r in reports:
        note = "" if r.ok else r.reason()
        if r.ok:
            name, nbytes = r.dominating
            note = f"dominant: {name} {nbytes / 2**20:.2f} MiB"
        lines.append(
            f"{r.kernel:<16} {r.label:<28} "
            f"{r.vmem_bytes / 2**20:>8.2f}Mi {r.smem_bytes / 2**10:>7.0f}Ki "
            f"{'ok' if r.ok else 'FAIL':<4} {note}"
        )
    return "\n".join(lines)


def summarize(reports: Sequence[CheckReport]) -> Dict[str, int]:
    ok = sum(1 for r in reports if r.ok)
    return {"total": len(reports), "ok": ok, "fail": len(reports) - ok}
