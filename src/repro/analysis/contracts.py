"""Declarative launch contracts for every registered Pallas kernel.

Each :class:`LaunchContract` reproduces, as data, exactly what its kernel's
``pl.pallas_call`` site does at a given :class:`~repro.analysis.budget.Cell`:
the grid arithmetic, every BlockSpec (block shape, full operand shape, the
range of the index map over the grid, whether the block is VMEM-carried),
the scalar-prefetch operands, dtypes, and ``input_output_aliases`` in the
kernel's flat operand numbering.  ``analysis.checks`` verifies the spec
against the shared budget model; ``tests/test_analysis.py`` verifies the
spec against the kernel itself (shapes of a real interpret-mode launch).

Contracts are registered at the launch's **high-water** static
configuration — ``emit_loglik=True``, ``double_buffer=True``, the
scheduled variant where one exists — because that is the configuration the
budget must hold for.

This module is import-light on purpose (no jax): the repo lint and the
``python -m repro.analysis`` CLI load it without touching a backend.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

from repro.analysis.budget import (
    LANE,
    Block,
    Cell,
    LaunchSpec,
    Scalar,
    estep_token_block,
    round_up,
)


@dataclasses.dataclass(frozen=True)
class LaunchContract:
    """One kernel's declarative launch contract.

    ``build(cell, lane_align)`` instantiates the :class:`LaunchSpec` at a
    static shape; ``module``/``entry`` name the Python call site the
    contract mirrors; ``equations`` the paper equations the kernel
    implements (the lint checks the module documents them).
    """

    name: str
    module: str
    entry: str
    equations: Tuple[str, ...]
    description: str
    build: Callable[..., LaunchSpec]

    def spec(self, cell: Cell, lane_align: int = LANE) -> LaunchSpec:
        return self.build(cell, lane_align)


def _pads(cell: Cell, lane_align: int) -> Tuple[int, int]:
    return cell.padded(lane_align)


def _column(name: str, Dp: int, L: int) -> Block:
    """A per-token (D, L) operand laid out by column, (L, Dp, 1), walked
    one (1, Dp, 1) block per grid column (``gs_sweep.column_major``)."""
    return Block(name, (1, Dp, 1), (L, Dp, 1), (L - 1, 0, 0))


# ---------------------------------------------------------------------------
# gs_sweep — fused dense column-serial Gauss-Seidel sweep
# ---------------------------------------------------------------------------

def _gs_sweep_spec(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
    Dp, Kp = _pads(cell, lane_align)
    L, W = cell.L, cell.W_s
    carried_in = dict(carried=True)
    return LaunchSpec(
        kernel="gs_sweep",
        grid=(2 * L,),                      # emit_loglik high-water mark
        scalars=(
            Scalar("word_ids", (Dp, L)),
            Scalar("wb", (1,), dtype="float32"),
        ),
        inputs=(
            _column("counts", Dp, L),
            Block("mu_in", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("theta_in", (Dp, Kp), (Dp, Kp), (0, 0), **carried_in),
            Block("phi_in", (W, Kp), (W, Kp), (0, 0), **carried_in),
            Block("ptot_in", (1, Kp), (1, Kp), (0, 0), **carried_in),
        ),
        outputs=(
            Block("theta_out", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
            Block("phi_out", (W, Kp), (W, Kp), (0, 0), carried=True),
            Block("ptot_out", (1, Kp), (1, Kp), (0, 0), carried=True),
            Block("mu_out", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("res_out", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            _column("loglik", Dp, L),
        ),
        scratch=(
            Block("rows_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            Block("delta_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
        ),
        # flat operands: wid(0) wb(1) counts(2) mu(3) theta(4) phi(5) ptot(6)
        aliases={4: 0, 5: 1, 6: 2},
    )


# ---------------------------------------------------------------------------
# scheduled_sweep — fused §3.1 scheduled sparse sweep
# ---------------------------------------------------------------------------

def _scheduled_sweep_spec(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
    Dp, Kp = _pads(cell, lane_align)
    L, W, A = cell.L, cell.W_s, max(cell.A, 1)
    return LaunchSpec(
        kernel="scheduled_sweep",
        grid=(2 * L,),
        scalars=(
            Scalar("word_ids", (Dp, L)),
            Scalar("word_topics", (W * A,)),
            Scalar("wb", (1,), dtype="float32"),
        ),
        inputs=(
            _column("counts", Dp, L),
            _column("token_active", Dp, L),
            Block("mu_in", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("theta_in", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
            Block("phi_in", (W, Kp), (W, Kp), (0, 0), carried=True),
            Block("ptot_in", (1, Kp), (1, Kp), (0, 0), carried=True),
        ),
        outputs=(
            Block("theta_out", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
            Block("phi_out", (W, Kp), (W, Kp), (0, 0), carried=True),
            Block("ptot_out", (1, Kp), (1, Kp), (0, 0), carried=True),
            Block("mu_out", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("res_out", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            _column("loglik", Dp, L),
        ),
        scratch=(
            Block("rows_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            Block("mask_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            Block("delta_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
        ),
        # flat: wid(0) wtop(1) wb(2) counts(3) act(4) mu(5) theta(6) phi(7)
        #       ptot(8)
        aliases={6: 0, 7: 1, 8: 2},
    )


# ---------------------------------------------------------------------------
# sharded_sweep — two-phase probe + fold (scheduled variant = high water)
# ---------------------------------------------------------------------------

def _sharded_probe_spec(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
    Dp, Kp = _pads(cell, lane_align)
    L, W, A = cell.L, cell.W_s, max(cell.A, 1)
    return LaunchSpec(
        kernel="sharded_probe",
        grid=(L,),
        scalars=(
            Scalar("word_ids", (Dp, L)),
            Scalar("word_topics", (W * A,)),
            Scalar("wb", (1,), dtype="float32"),
        ),
        inputs=(
            _column("counts", Dp, L),
            _column("token_active", Dp, L),
            Block("mu_in", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("theta_in", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
            Block("phi_in", (W, Kp), (W, Kp), (0, 0), carried=True),
            Block("ptot_in", (1, Kp), (1, Kp), (0, 0), carried=True),
        ),
        outputs=(
            _column("s_out", Dp, L),
            _column("pm_out", Dp, L),
        ),
        scratch=(
            Block("rows_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            Block("mask_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
        ),
        aliases={},
    )


def _sharded_fold_spec(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
    Dp, Kp = _pads(cell, lane_align)
    L, W, A = cell.L, cell.W_s, max(cell.A, 1)
    return LaunchSpec(
        kernel="sharded_fold",
        grid=(2 * L,),                      # emit_loglik high-water mark
        scalars=(
            Scalar("word_ids", (Dp, L)),
            Scalar("word_topics", (W * A,)),
            Scalar("wb", (1,), dtype="float32"),
        ),
        inputs=(
            _column("counts", Dp, L),
            _column("token_active", Dp, L),
            _column("remainder", Dp, L),
            _column("prev_mass", Dp, L),
            Block("mu_in", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("theta_in", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
            Block("phi_in", (W, Kp), (W, Kp), (0, 0), carried=True),
            Block("ptot_in", (1, Kp), (1, Kp), (0, 0), carried=True),
        ),
        outputs=(
            Block("theta_out", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
            Block("phi_out", (W, Kp), (W, Kp), (0, 0), carried=True),
            Block("ptot_out", (1, Kp), (1, Kp), (0, 0), carried=True),
            Block("mu_out", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            Block("res_out", (1, Dp, Kp), (L, Dp, Kp), (L - 1, 0, 0)),
            _column("live_mass", Dp, L),
            _column("loglik_u", Dp, L),
        ),
        scratch=(
            Block("rows_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            Block("delta_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            Block("mask_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
        ),
        # flat: wid(0) wtop(1) wb(2) counts(3) act(4) rem(5) pm(6) mu(7)
        #       theta(8) phi(9) ptot(10)
        aliases={8: 0, 9: 1, 10: 2},
    )


# ---------------------------------------------------------------------------
# theta_sweep — fused frozen-φ inference (θ-only fixed point)
# ---------------------------------------------------------------------------

#: Chunk length ops.infer launches between stop-rule checks (grid sizing
#: only; the VMEM live set is independent of the sweep count — §2.4).
THETA_CHUNK_SWEEPS = 10


#: Serving φ storage dtypes: (itemsize, min sublane tile) per variant.
#: bf16 halves and int8 quarters the dominant (W_s, K) φ block — the
#: "halving VMEM doubles the servable W_s×K per launch" lever — at the
#: price of a larger Mosaic sublane tile on W_s (16/32 rows instead of 8)
#: and, for int8, a (W_s,) f32 per-row scale vector in SMEM.
PHI_STORAGE = {
    "float32": (4, 8),
    "bfloat16": (2, 16),
    "int8": (1, 32),
}


def _theta_sweep_spec_for(phi_dtype: str):
    """Build the theta_sweep contract at one serving φ storage dtype.

    The f32 instantiation reproduces the original contract exactly; the
    quantized variants change ONLY the φ block's dtype/footprint, its
    sublane-tile rounding of W_s, and (int8) add the scalar-prefetched
    per-row scale vector — mirroring ``theta_sweep_pallas``'s quantized
    operand list.
    """
    phi_bytes, phi_tile = PHI_STORAGE[phi_dtype]

    def build(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
        Dp, Kp = _pads(cell, lane_align)
        L, A = cell.L, max(cell.A, 1)
        W = round_up(cell.W_s, phi_tile) if phi_dtype != "float32" \
            else cell.W_s
        scalars = [
            Scalar("word_ids", (Dp, L)),
            Scalar("word_topics", (W * A,)),
        ]
        if phi_dtype == "int8":
            scalars.append(Scalar("phi_scale", (W,), dtype="float32"))
        n_scal = len(scalars)
        return LaunchSpec(
            kernel=(
                "theta_sweep" if phi_dtype == "float32"
                else f"theta_sweep_{'bf16' if phi_dtype == 'bfloat16' else 'int8'}"
            ),
            grid=((THETA_CHUNK_SWEEPS + 1) * L,),  # sweeps + eq. 21 columns
            scalars=tuple(scalars),
            inputs=(
                _column("est_counts", Dp, L),
                _column("ev_counts", Dp, L),
                Block("theta_in", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
                Block("phi_norm", (W, Kp), (W, Kp), (0, 0), carried=True,
                      dtype=phi_dtype, dtype_bytes=phi_bytes),
            ),
            outputs=(
                Block("theta_out", (Dp, Kp), (Dp, Kp), (0, 0), carried=True),
                _column("est_ll", Dp, L),
                _column("ev_ll", Dp, L),
            ),
            scratch=(
                Block("rows_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
                Block("acc_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
                Block("mask_scratch", (Dp, Kp), (Dp, Kp), (0, 0)),
            ),
            # flat: wid(0) wtop(1) [scale] est ev theta phi — θ̂ donated
            aliases={n_scal + 2: 0},
        )

    return build


_theta_sweep_spec = _theta_sweep_spec_for("float32")


# ---------------------------------------------------------------------------
# foem_estep / topk_estep — token-block E-step tiles
# ---------------------------------------------------------------------------

def _foem_estep_spec(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
    Kp = round_up(cell.K, lane_align)
    T = cell.D * cell.L                 # standalone worst case: all tokens
    BT = min(estep_token_block(Kp), round_up(T, 8))
    Tp = round_up(T, BT)
    tile = dict(block_shape=(BT, Kp), full_shape=(Tp, Kp),
                max_index=(Tp // BT - 1, 0))
    col = dict(block_shape=(BT, 1), full_shape=(Tp, 1),
               max_index=(Tp // BT - 1, 0))
    return LaunchSpec(
        kernel="foem_estep",
        grid=(Tp // BT,),
        scalars=(),
        inputs=(
            Block("theta_rows", **tile),
            Block("phi_rows", **tile),
            Block("phi_tot", (1, Kp), (1, Kp), (0, 0), carried=True),
            Block("exclude", **tile),
            Block("mu_old", **tile),
            Block("counts", **col),
            Block("wb", (1, 1), (1, 1), (0, 0), carried=True),
        ),
        outputs=(
            Block("mu_new", **tile),
            Block("residual", **tile),
        ),
        scratch=(),
        aliases={},
    )


def _topk_estep_spec(cell: Cell, lane_align: int = LANE) -> LaunchSpec:
    # A active lanes, padded to the lane boundary by the wrapper (ops.py)
    Ap = round_up(max(cell.A, 1), lane_align)
    T = cell.D * cell.L
    BT = min(256, round_up(T, 8))
    Tp = round_up(T, BT)
    tile = dict(block_shape=(BT, Ap), full_shape=(Tp, Ap),
                max_index=(Tp // BT - 1, 0))
    col = dict(block_shape=(BT, 1), full_shape=(Tp, 1),
               max_index=(Tp // BT - 1, 0))
    return LaunchSpec(
        kernel="topk_estep",
        grid=(Tp // BT,),
        scalars=(),
        inputs=(
            Block("theta_a", **tile),
            Block("phi_a", **tile),
            Block("ptot_a", **tile),
            Block("mu_prev_a", **tile),
            Block("counts", **col),
            Block("active", **col),
            Block("wb", (1, 1), (1, 1), (0, 0), carried=True),
        ),
        outputs=(
            Block("mu_new", **tile),
            Block("delta", **tile),
        ),
        scratch=(),
        aliases={},
    )


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

KERNEL_CONTRACTS: Dict[str, LaunchContract] = {
    c.name: c
    for c in (
        LaunchContract(
            name="gs_sweep",
            module="repro.kernels.gs_sweep",
            entry="gs_sweep_pallas",
            equations=("eq. 13", "eq. 36", "eq. 3"),
            description="fused dense column-serial Gauss-Seidel sweep",
            build=_gs_sweep_spec,
        ),
        LaunchContract(
            name="scheduled_sweep",
            module="repro.kernels.scheduled_sweep",
            entry="scheduled_sweep_pallas",
            equations=("eq. 13", "eq. 38", "eq. 36", "eq. 3"),
            description="fused scheduled sparse sweep (§3.1 active sets)",
            build=_scheduled_sweep_spec,
        ),
        LaunchContract(
            name="sharded_probe",
            module="repro.kernels.sharded_sweep",
            entry="sharded_probe_pallas",
            equations=("eq. 13", "eq. 38"),
            description="two-phase sharded sweep, phase A (normaliser probe)",
            build=_sharded_probe_spec,
        ),
        LaunchContract(
            name="sharded_fold",
            module="repro.kernels.sharded_sweep",
            entry="sharded_fold_pallas",
            equations=("eq. 13", "eq. 38", "eq. 36", "eq. 3"),
            description="two-phase sharded sweep, phase C (Gauss-Seidel fold)",
            build=_sharded_fold_spec,
        ),
        LaunchContract(
            name="theta_sweep",
            module="repro.kernels.theta_sweep",
            entry="theta_sweep_pallas",
            equations=("eq. 11", "eq. 21"),
            description="fused frozen-φ inference fixed point (§2.4)",
            build=_theta_sweep_spec,
        ),
        LaunchContract(
            name="theta_sweep_bf16",
            module="repro.kernels.theta_sweep",
            entry="theta_sweep_pallas",
            equations=("eq. 11", "eq. 21"),
            description="frozen-φ inference, bf16 serving φ (dequant-on-read)",
            build=_theta_sweep_spec_for("bfloat16"),
        ),
        LaunchContract(
            name="theta_sweep_int8",
            module="repro.kernels.theta_sweep",
            entry="theta_sweep_pallas",
            equations=("eq. 11", "eq. 21"),
            description="frozen-φ inference, int8 serving φ + per-row scales",
            build=_theta_sweep_spec_for("int8"),
        ),
        LaunchContract(
            name="foem_estep",
            module="repro.kernels.foem_estep",
            entry="fused_estep_pallas",
            equations=("eq. 11", "eq. 13", "eq. 36"),
            description="fused dense E-step token-block tile",
            build=_foem_estep_spec,
        ),
        LaunchContract(
            name="topk_estep",
            module="repro.kernels.topk_estep",
            entry="topk_estep_pallas",
            equations=("eq. 38",),
            description="scheduled sparse E-step token-block tile",
            build=_topk_estep_spec,
        ),
    )
}

#: Modules allowed to contain ``pl.BlockSpec`` literals (the lint's
#: blockspec-registry rule): exactly the registered kernel modules.
CONTRACT_MODULES = tuple(sorted({c.module for c in KERNEL_CONTRACTS.values()}))
