"""Static kernel-contract analyzer + repo hygiene gates.

Covers the three static layers of ``repro.analysis``:
  * budget model — monotonicity, the E-step tile rule, fit boundaries;
  * contract registry — every registered (module, entry) names a real
    kernel, every reference cell verifies under both layouts, corrupted
    specs are caught by the alias/alignment/index-map checks;
  * dispatch-boundary validation — ``ops.sweep``/``ops.infer`` raise
    ``ContractError`` eagerly (no tracing) on malformed arguments;
  * repo lint + module graph — the tree is clean and the rules fire on
    synthetic violations.
"""
import ast
import dataclasses
import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    ContractError,
    KERNEL_CONTRACTS,
    REFERENCE_CELLS,
    assert_reference_cells,
    check_all,
    kernel_fits_vmem,
)
from repro.analysis import budget as bm
from repro.analysis.checks import check_spec
from repro.analysis.modules import (
    QUARANTINED_MODULES,
    ROOTS,
    build_import_graph,
    check_module_graph,
    default_src_root,
    reachable_from,
)
from repro.core.types import SweepPlan
from repro.kernels import ops as kops

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
REF_CELL = REFERENCE_CELLS[0][1]     # BENCH_sweep full


# ---------------------------------------------------------------------------
# Reference cells — the CI gate
# ---------------------------------------------------------------------------

def test_reference_cells_fit_compiled():
    from repro.analysis import QUANT_KERNELS, QUANT_REFERENCE_CELLS

    reports = assert_reference_cells()          # raises on any failure
    assert {r.kernel for r in reports} == set(KERNEL_CONTRACTS)
    assert len(reports) == (
        len(KERNEL_CONTRACTS) * len(REFERENCE_CELLS)
        + len(QUANT_KERNELS) * len(QUANT_REFERENCE_CELLS)
    )
    # the ROADMAP W_s=8k/K=128 target is among the gated cells
    assert any("8k" in r.label or r.cell.W_s == 8192 for r in reports)


def test_reference_cells_fit_interpret_layout():
    for r in assert_reference_cells(lane_align=1):
        assert r.ok, (r.kernel, r.label, r.reason())


def test_registry_names_real_kernels():
    """Every contract's (module, entry) resolves to an importable callable
    — the registry cannot drift from the actual kernel surface."""
    for c in KERNEL_CONTRACTS.values():
        mod = importlib.import_module(c.module)
        assert callable(getattr(mod, c.entry)), (c.name, c.module, c.entry)
        assert c.equations, c.name


# ---------------------------------------------------------------------------
# Budget model
# ---------------------------------------------------------------------------

def test_vmem_monotone_in_problem_size():
    def vmem(kernel, **kw):
        cell = dataclasses.replace(REF_CELL, **kw)
        spec = KERNEL_CONTRACTS[kernel].spec(cell)
        return bm.vmem_total(spec)

    for kernel in ("gs_sweep", "scheduled_sweep", "theta_sweep"):
        assert vmem(kernel, W_s=16384) > vmem(kernel)
        assert vmem(kernel, K=256) > vmem(kernel)
        assert vmem(kernel, D=1024) > vmem(kernel)


def test_fit_boundary_matches_legacy_heuristics():
    """The unified model preserves the dispatch boundary the kernels'
    deleted ad-hoc formulas enforced at the ROADMAP cell."""
    from repro.kernels.gs_sweep import fits_vmem
    from repro.kernels.scheduled_sweep import sched_fits_vmem
    from repro.kernels.theta_sweep import theta_fits_vmem

    assert fits_vmem(8192, 256, 128)
    assert not fits_vmem(32768, 256, 128)
    assert sched_fits_vmem(8192, 256, 128)
    assert theta_fits_vmem(8192, 256, 128)
    assert fits_vmem(8192, 256, 128) == kernel_fits_vmem(
        "gs_sweep", 8192, 256, 128
    )


def test_quantized_phi_extends_fit_boundary():
    """The quantized-serving showcase: at W_s=32k/D=256/K=128 the f32 φ
    block alone blows the VMEM budget, while bf16 and int8 storage fit —
    the static model certifies the 'halving VMEM doubles servable W_s×K'
    claim before any kernel runs."""
    from repro.kernels.theta_sweep import theta_fits_vmem

    assert not kernel_fits_vmem("theta_sweep", 32768, 256, 128)
    assert kernel_fits_vmem("theta_sweep_bf16", 32768, 256, 128)
    assert kernel_fits_vmem("theta_sweep_int8", 32768, 256, 128)
    for dt in ("float32", "bfloat16", "int8"):
        assert theta_fits_vmem(32768, 256, 128, phi_dtype=dt) == (
            dt != "float32"
        )


def test_estep_token_block_rule():
    from repro.kernels.foem_estep import token_block_for

    assert token_block_for(128) == bm.estep_token_block(128) == 1024
    assert token_block_for(16384) == 16
    for k in (32, 128, 1024, 16384):
        bt = token_block_for(k)
        assert bt % 8 == 0 and 8 <= bt <= 1024
    assert token_block_for(1 << 22) == 8        # floor, never 0


def test_smem_counts_scalar_prefetch_bytes():
    spec = KERNEL_CONTRACTS["scheduled_sweep"].spec(REF_CELL)
    assert spec.num_scalar_prefetch == 3
    expect = sum(s.smem_bytes() for s in spec.scalars)
    assert bm.smem_total(spec) == expect > 0
    # wtop dominates: (W_s, A) int32
    assert expect >= REF_CELL.W_s * REF_CELL.A * 4


# ---------------------------------------------------------------------------
# Structural checks on corrupted specs
# ---------------------------------------------------------------------------

def _gs_spec():
    return KERNEL_CONTRACTS["gs_sweep"].spec(REF_CELL)


def test_alias_target_out_of_range_caught():
    spec = _gs_spec()
    bad = dataclasses.replace(spec, aliases={**spec.aliases, 3: 99})
    rep = check_spec(bad)
    assert any("out of range" in e for e in rep.errors)
    assert not rep.ok


def test_alias_shape_dtype_mismatch_caught():
    spec = _gs_spec()
    (inp_idx, out_idx), *_ = spec.aliases.items()
    out = spec.outputs[out_idx]
    bad_out = dataclasses.replace(out, dtype="bfloat16", dtype_bytes=2)
    outputs = tuple(
        bad_out if i == out_idx else o for i, o in enumerate(spec.outputs)
    )
    rep = check_spec(dataclasses.replace(spec, outputs=outputs))
    assert any("alias" in e and "dtype" in e for e in rep.errors)


def test_uncovered_donation_caught():
    """Every carried output must be aliased — dropping an alias entry is a
    silent extra VMEM buffer and must fail the check."""
    spec = _gs_spec()
    aliases = dict(spec.aliases)
    aliases.popitem()
    rep = check_spec(dataclasses.replace(spec, aliases=aliases))
    assert any("alias" in e.lower() or "donat" in e.lower()
               for e in rep.errors)


def test_index_map_overrun_caught():
    spec = _gs_spec()
    blk = spec.inputs[0]
    bad_blk = dataclasses.replace(
        blk, max_index=tuple(m + 10 for m in blk.max_index)
    )
    inputs = (bad_blk,) + tuple(spec.inputs[1:])
    rep = check_spec(dataclasses.replace(spec, inputs=inputs))
    assert any("exceed" in e or "bound" in e or "outside" in e
               for e in rep.errors), rep.errors


def test_lane_misalignment_caught():
    spec = _gs_spec()
    blk = spec.inputs[0]
    shape = tuple(blk.block_shape[:-1]) + (blk.block_shape[-1] + 3,)
    bad_blk = dataclasses.replace(blk, block_shape=shape)
    inputs = (bad_blk,) + tuple(spec.inputs[1:])
    rep = check_spec(dataclasses.replace(spec, inputs=inputs))
    assert any("lane" in e for e in rep.errors)


def test_column_block_over_token_matrix_is_misaligned():
    """The per-column (Dp, 1) block over a (Dp, L) operand — the layout the
    TPU compiler refused — is an alignment error; the column-major
    (1, Dp, 1) block over (L, Dp, 1) the kernels use is not."""
    spec = _gs_spec()
    col = spec.inputs[0]
    L, Dp = col.full_shape[0], col.full_shape[1]
    old = dataclasses.replace(col, block_shape=(Dp, 1), full_shape=(Dp, L),
                              max_index=(0, L - 1))
    rep = check_spec(dataclasses.replace(spec, inputs=(old,) + spec.inputs[1:]))
    assert any("minor block dim 1" in e for e in rep.errors), rep.errors
    assert check_spec(spec).errors == ()


def test_smem_pads_two_dimensional_tables():
    """A 2-D scalar-prefetch table pads to the (8, 128) tile: the (W_s, A)
    active-topic table would need 4 MiB of the 1 MiB scalar memory at the
    reference cell, its flattened form 512 KiB."""
    table = bm.Scalar("word_topics", (8192, 16))
    flat = bm.Scalar("word_topics", (8192 * 16,))
    assert table.smem_bytes() == 8192 * 128 * 4 > bm.DEFAULT_SMEM_BUDGET
    assert flat.smem_bytes() == 8192 * 16 * 4 < bm.DEFAULT_SMEM_BUDGET


def test_check_all_reports_dominating_term():
    big = bm.Cell(D=1024, L=64, K=256, W_s=32768, A=16)
    reports = check_all([("big", big)])
    failing = [r for r in reports if not r.fits_vmem]
    assert failing
    for r in failing:
        name, nbytes = r.dominating
        assert nbytes > 0 and name
        assert "dominated by" in r.reason()


# ---------------------------------------------------------------------------
# Eager ContractError at the ops dispatch boundary (no tracing involved)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sweep_args():
    rng = np.random.default_rng(3)
    D, L, K, W = 6, 10, 8, 32
    wid = jnp.asarray(rng.integers(0, W, (D, L)).astype(np.int32))
    cnt = jnp.asarray(rng.integers(0, 5, (D, L)).astype(np.float32))
    mu = jnp.asarray(rng.dirichlet(np.ones(K), (D, L)).astype(np.float32))
    theta = jnp.einsum("dlk,dl->dk", mu, cnt)
    phi = jax.ops.segment_sum(
        (cnt[..., None] * mu).reshape(D * L, K), wid.reshape(-1),
        num_segments=W,
    )
    return wid, cnt, mu, theta, phi, phi.sum(0)


KW = dict(alpha_m1=0.01, beta_m1=0.01, wb=0.32)


def test_bad_plan_axis_raises_eagerly(sweep_args):
    wid, cnt, mu, theta, phi, ptot = sweep_args
    with pytest.raises(ContractError, match="axis_name"):
        kops.sweep(wid, cnt, mu, theta, phi, ptot, **KW,
                   plan=SweepPlan(axis_name=""))


def test_mismatched_donated_dtypes_raise(sweep_args):
    wid, cnt, mu, theta, phi, ptot = sweep_args
    with pytest.raises(ContractError, match="donated"):
        kops.sweep(wid, cnt, mu, theta.astype(jnp.bfloat16), phi, ptot,
                   **KW)


def test_ragged_rows_forced_pallas_raise(sweep_args):
    wid, cnt, mu, theta, phi, ptot = sweep_args
    with pytest.raises(ContractError, match="sublane"):
        kops.sweep(wid, cnt, mu, theta, phi[:31], ptot, **KW,
                   use_pallas=True)
    # ... including via a plan that forces the compiled path
    with pytest.raises(ContractError, match="sublane"):
        kops.sweep(wid, cnt, mu, theta, phi[:31], ptot, **KW,
                   plan=SweepPlan(impl="pallas"))
    # auto dispatch simply stays portable — no error
    r = kops.sweep(wid, cnt, mu, theta, phi[:31], ptot, **KW)
    assert r.mu.shape == mu.shape


def test_shape_mismatches_raise(sweep_args):
    wid, cnt, mu, theta, phi, ptot = sweep_args
    with pytest.raises(ContractError, match="counts"):
        kops.sweep(wid, cnt[:, :4], mu, theta, phi, ptot, **KW)
    with pytest.raises(ContractError, match="theta"):
        kops.sweep(wid, cnt, mu, theta[:3], phi, ptot, **KW)
    with pytest.raises(ContractError, match="phi_k"):
        kops.sweep(wid, cnt, mu, theta, phi, ptot[:4], **KW)
    with pytest.raises(ContractError, match="word_topics"):
        kops.sweep(wid, cnt, mu, theta, phi, ptot, **KW,
                   word_topics=jnp.zeros((5, 2), jnp.int32))


def test_infer_contracts_raise(sweep_args):
    wid, cnt, mu, theta, phi, ptot = sweep_args
    phin = phi / jnp.maximum(phi.sum(0, keepdims=True), 1e-30)
    with pytest.raises(ContractError, match="theta0"):
        kops.infer(wid, cnt, theta[:3], phin, alpha_m1=0.01)
    with pytest.raises(ContractError, match="ev_counts"):
        kops.infer(wid, cnt, theta, phin, alpha_m1=0.01,
                   ev_counts=cnt[:, :4])
    with pytest.raises(ContractError, match="sublane"):
        kops.infer(wid, cnt, theta, phin[:31], alpha_m1=0.01,
                   use_pallas=True)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_reference_gate(capsys):
    from repro.analysis.__main__ import main

    assert main(["--reference"]) == 0
    out = capsys.readouterr().out
    assert "gs_sweep" in out and "ROADMAP" in out


# ---------------------------------------------------------------------------
# Repo lint + module graph
# ---------------------------------------------------------------------------

def _lint():
    sys.path.insert(0, TOOLS)
    try:
        import lint_repro
    finally:
        sys.path.remove(TOOLS)
    return lint_repro


def test_lint_tree_clean():
    assert _lint().run_lint() == []


@pytest.mark.parametrize("src,rule,tag", [
    ("import numpy as np\nx = np.zeros((3,), np.float64)\n",
     "check_f64", "f64"),
    ("def f(x, acc=[]):\n    return acc\n",
     "check_mutable_defaults", "mutable-default"),
    ("try:\n    pass\nexcept:\n    pass\n",
     "check_bare_except", "bare-except"),
])
def test_lint_rules_fire(src, rule, tag):
    lint = _lint()
    tree = ast.parse(src)
    hits = getattr(lint, rule)("/x/y.py", "repro.fake", src, tree)
    assert hits and all(f"[{tag}]" in h for h in hits)


def test_lint_f64_annotation_accepted():
    lint = _lint()
    src = "import numpy as np\nx = np.float64(0)  # lint: host-f64\n"
    assert lint.check_f64("/x/y.py", "repro.fake", src, ast.parse(src)) == []


def test_lint_blockspec_outside_contracts_fires():
    lint = _lint()
    src = "import jax.experimental.pallas as pl\ns = pl.BlockSpec((8, 128), None)\n"
    hits = lint.check_blockspec("/x/y.py", "repro.fake", src, ast.parse(src))
    assert hits and "[blockspec]" in hits[0]
    # ...but not inside a registered contract module
    assert lint.check_blockspec(
        "/x/y.py", "repro.kernels.gs_sweep", src, ast.parse(src)
    ) == []


def test_module_graph_clean():
    violations, dead = check_module_graph()
    assert violations == []
    assert dead == set(QUARANTINED_MODULES)


def test_quarantine_is_not_reachable():
    graph = build_import_graph(default_src_root())
    live = reachable_from(graph, ROOTS)
    leaked = live & QUARANTINED_MODULES
    assert not leaked, f"quarantined modules linked into the repro: {leaked}"


def test_module_graph_flags_unquarantined_dead_module():
    graph = {"repro.a": {"repro.b"}, "repro.b": set(), "repro.dead": set()}
    live = reachable_from(graph, ("repro.a",))
    assert live == {"repro.a", "repro.b"}
    assert set(graph) - live == {"repro.dead"}
