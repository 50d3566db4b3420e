"""Pallas TPU kernel: fused column-serial Gauss-Seidel IEM sweep.

The paper's inner loop (Fig. 2, adapted to TPU as ``em.blocked_iem_sweep``
with B = L) is a *sequential* scan over token columns: E-step with eq. 13
self-exclusion for the column's D documents, then an immediate fold of the
Δ-statistics into θ̂ and φ̂ so the next column sees them (Gauss-Seidel).
Expressed as ``lax.scan`` + ``segment_sum`` that is L kernel launches per
sweep, each paying a full-matrix φ̂ round trip; expressed here it is ONE
launch:

  * the grid is the column index — Pallas grids execute sequentially on a
    TPU core, which is exactly the Gauss-Seidel ordering we need;
  * θ̂ (D, K), φ̂ (W_s, K) and φ̂(k) are carried in VMEM across grid steps:
    their block index maps are constant, so Pallas neither re-fetches nor
    writes them back until the last column — the fold is on-chip;
  * the HBM buffers for θ̂/φ̂/φ̂(k) are donated via ``input_output_aliases``
    (no second (W_s, K) allocation), with the gmm-style first-visit copy
    initialising the output blocks;
  * the word ids are a scalar-prefetch operand (``PrefetchScalarGridSpec``)
    so the kernel can issue the per-document dynamic row gather/scatter on
    φ̂ without materialising one-hot matrices;
  * the per-column φ̂-row gather is *double-buffered*: column l+1's D rows
    are issued as async copies right after column l's scatter (the earliest
    consistent point) and waited only where column l+1 first needs them —
    the copies fly while the exclusion/θ̂-side arithmetic runs, taking the
    serial gather off the critical path (``double_buffer=False`` keeps the
    synchronous gather for bitwise comparison);
  * the per-column residual counts·|Δμ| (paper eq. 36) is emitted as a
    second (D, L, K) output, which makes the post-warm-up
    ``scheduling.full_sweep_residuals`` re-measurement free;
  * with ``emit_loglik=True`` the grid is extended by L stop-rule steps
    that re-walk the columns against the *final* carried θ̂/φ̂/φ̂(k) and
    emit per-column partial sums of the eq. 3 data log-likelihood — the
    training-perplexity stop rule without a separate (D, L, K)
    gather+einsum pass (the stats never leave VMEM).

Per column the kernel touches O(D·K) values of φ̂ (the D gathered rows)
instead of the O(W_s·K) full-matrix scatter of the scan formulation — the
sweep becomes arithmetic-bound, not launch/HBM-bound.

VMEM budget: 2·(W_s + D)·K·4 B for the carried φ̂/θ̂ pairs plus the small
per-column blocks; W_s ≤ ~8k at K = 128 fits comfortably.  The dispatch
layer (``ops.sweep``) falls back to the delta-compacted portable path
when the working set is larger or the backend is not TPU.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis.budget import DEFAULT_VMEM_BUDGET
from repro.analysis.checks import kernel_fits_vmem


def fits_vmem(num_rows: int, num_docs: int, num_topics: int,
              budget: int = DEFAULT_VMEM_BUDGET) -> bool:
    """Can the kernel's live VMEM set fit for one launch?

    Delegates to the ``gs_sweep`` contract in ``repro.analysis`` — the
    same budget model the static analyzer checks, so dispatch and
    analysis can never disagree about what fits.
    """
    return kernel_fits_vmem("gs_sweep", num_rows, num_docs, num_topics,
                            budget)


def loglik_partial(cnt, theta, ptot, rows, wb, *, alpha_m1: float,
                   beta_m1: float, k_actual: int):
    """One column's eq. 3 data-loglik partials against the carried stats.

    The stop-rule arithmetic shared by the dense and scheduled sweep
    kernels' loglik phases: eq. 9/10 normalisation, padded topic lanes
    masked out, padded documents inert via their zero counts.  Mirrors
    ``em.map_log_likelihood`` / ``training_perplexity`` term for term.
    Returns the per-document ``(D, 1)`` column ``x·log lik``; the wrapper
    sums the emitted (L, D, 1) partials.
    """
    D, K = theta.shape
    th_den = theta.sum(-1, keepdims=True) + k_actual * alpha_m1
    th_n = (theta + alpha_m1) / jnp.maximum(th_den, 1e-30)
    ph_n = (rows + beta_m1) / jnp.maximum(ptot + wb, 1e-30)
    prod = th_n * ph_n
    if k_actual != K:
        lane = jax.lax.broadcasted_iota(jnp.int32, (D, K), 1)
        prod = jnp.where(lane < k_actual, prod, 0.0)
    lik = jnp.maximum(prod.sum(-1, keepdims=True), 1e-30)
    return cnt * jnp.log(lik)


def scatter_rows(wid_ref, col, phi_ref, delta_ref, num_docs: int):
    """Fold the staged (D, K) Δ rows into φ̂ at the column's word rows.

    Δ goes through a VMEM ref so each document's row is a dynamic
    ``pl.ds`` load — the TPU compiler has no value-level dynamic slice.
    """
    def go(d, _):
        w = wid_ref[d, col]
        phi_ref[pl.ds(w, 1), :] = (
            phi_ref[pl.ds(w, 1), :] + delta_ref[pl.ds(d, 1), :]
        )
        return 0
    jax.lax.fori_loop(0, num_docs, go, 0)


def _make_gs_kernel(*, alpha_m1: float, beta_m1: float, k_actual: int,
                    num_cols: int, emit_loglik: bool, double_buffer: bool):
    """Build the kernel body for a static (loglik, buffering) configuration.

    Ref order: scalar prefetch (wid, wb), inputs (counts column, μ column,
    θ̂, φ̂, φ̂(k)), outputs (θ̂, φ̂, φ̂(k) carried; μ, residual columns;
    loglik partial columns when emitted), scratch (rows buffer, Δ buffer;
    DMA semaphore when double-buffered).
    """

    def kernel(wid_ref, wb_ref, counts_ref, mu_in_ref, theta_in_ref,
               phi_in_ref, ptot_in_ref, *rest):
        n_out = 6 if emit_loglik else 5
        theta_ref, phi_ref, ptot_ref, mu_ref, res_ref = rest[:5]
        ll_ref = rest[5] if emit_loglik else None
        scratch = rest[n_out:]
        rows_ref, delta_ref = scratch[:2]
        sem = scratch[2] if double_buffer else None

        l = pl.program_id(0)
        D, K = theta_ref.shape
        wb = wb_ref[0]

        def gather_sync(col):
            def go(d, _):
                w = wid_ref[d, col]
                rows_ref[pl.ds(d, 1), :] = phi_ref[pl.ds(w, 1), :]
                return 0
            jax.lax.fori_loop(0, D, go, 0)

        def prefetch(col, start):
            # The start/wait pair reconstruct identical copy descriptors;
            # one semaphore tracks all D row copies of a column.
            def go(d, _):
                w = wid_ref[d, col]
                cp = pltpu.make_async_copy(
                    phi_ref.at[pl.ds(w, 1), :],
                    rows_ref.at[pl.ds(d, 1), :],
                    sem,
                )
                if start:
                    cp.start()
                else:
                    cp.wait()
                return 0
            jax.lax.fori_loop(0, D, go, 0)

        # First column: bring the carried stats into the output blocks (they
        # are aliased with the inputs in HBM but the VMEM out block starts
        # undefined), then stage column 0's φ̂ rows.
        @pl.when(l == 0)
        def _():
            theta_ref[...] = theta_in_ref[...]
            phi_ref[...] = phi_in_ref[...]
            ptot_ref[...] = ptot_in_ref[...]
            if double_buffer:
                prefetch(0, start=True)

        def sweep_col():
            cnt = counts_ref[0]                     # (D, 1)
            mu_old = mu_in_ref[0]                   # (D, K)
            theta = theta_ref[...]
            ptot = ptot_ref[...]                    # (1, K)

            # ---- θ̂-side exclusion arithmetic (no φ̂ rows needed yet; the
            # column's row copies issued by the previous step fly here) ----
            ex = cnt * mu_old
            th = jnp.maximum(theta - ex, 0.0)
            pt = ptot - ex

            if double_buffer:
                prefetch(l, start=False)            # first use: wait here
            else:
                gather_sync(l)
            phi_rows = rows_ref[...]

            # ---- fused E-step: eq. 13 exclusion + responsibility + norm ----
            ph = jnp.maximum(phi_rows - ex, 0.0)
            num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb)
            if k_actual != K:
                # padded topic lanes carry zero stats; keep them out
                lane = jax.lax.broadcasted_iota(jnp.int32, (D, K), 1)
                num = jnp.where(lane < k_actual, num, 0.0)
            denom = jnp.maximum(num.sum(-1, keepdims=True), 1e-30)
            mu_new = num / denom
            delta = cnt * mu_new - ex               # (D, K)

            # ---- Gauss-Seidel fold: θ̂/φ̂/φ̂(k) updated before next col ----
            theta_ref[...] = theta + delta
            ptot_ref[...] = ptot + delta.sum(0, keepdims=True)
            delta_ref[...] = delta
            scatter_rows(wid_ref, l, phi_ref, delta_ref, D)

            if double_buffer:
                # earliest consistent point: the scatter above is what the
                # next column's rows must reflect
                @pl.when(l + 1 < num_cols)
                def _():
                    prefetch(l + 1, start=True)

            mu_ref[0] = mu_new
            res_ref[0] = cnt * jnp.abs(mu_new - mu_old)

        def ppl_col():
            # Stop-rule phase: per-column eq. 3 data-loglik partials against
            # the FINAL carried stats (phase runs after the last fold).
            gather_sync(l - num_cols)
            ll_ref[0] = loglik_partial(
                counts_ref[0], theta_ref[...], ptot_ref[...], rows_ref[...],
                wb, alpha_m1=alpha_m1, beta_m1=beta_m1, k_actual=k_actual,
            )

        if emit_loglik:
            @pl.when(l < num_cols)
            def _():
                sweep_col()

            @pl.when(l >= num_cols)
            def _():
                ppl_col()
        else:
            sweep_col()

    return kernel


def compiler_params():
    """Mosaic parameters shared by the column-serial launches: the column
    grid is sequential (Gauss-Seidel order, VMEM-carried statistics)."""
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def column_index_maps(num_cols: int, emit_loglik: bool):
    """Block-index maps over a sweep grid of ``num_cols`` (+ ``num_cols``
    stop-rule steps when ``emit_loglik``).

    Returns ``(col_of, pin_of, ll_of)``: ``col_of`` re-walks the per-column
    inputs in the stop-rule phase, ``pin_of`` keeps the μ/residual output
    blocks on the last column there (no re-flush of written output), and
    ``ll_of`` holds the loglik output on block 0 through the sweep phase,
    which never writes it, so each loglik block is flushed once, after the
    stop-rule phase has written it.
    """
    L = num_cols
    if not emit_loglik:
        ident = lambda l: l
        return ident, ident, ident
    return (
        lambda l: jax.lax.rem(l, L),
        lambda l: jnp.minimum(l, L - 1),
        lambda l: jnp.maximum(l - L, 0),
    )


def column_major(x: jax.Array) -> jax.Array:
    """(D, L) per-token values -> (L, D, 1): one (1, D, 1) block per column.

    A per-column block of a (D, L) array would be (D, 1), which breaks the
    TPU tiling rule (the minor block dim must be a multiple of 128 or the
    whole array dim); stacked by column the block's last two dims are the
    array's own.
    """
    return x.T[:, :, None]


def from_column_major(x: jax.Array) -> jax.Array:
    """Inverse of :func:`column_major`: (L, D, 1) -> (D, L)."""
    return x[..., 0].T


@functools.partial(
    jax.jit,
    static_argnames=("alpha_m1", "beta_m1", "lane_align", "emit_loglik",
                     "double_buffer", "interpret"),
)
def gs_sweep_pallas(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_wk
    counts: jax.Array,         # (D, L) float32
    mu: jax.Array,             # (D, L, K)
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: jax.Array | float,     # W·(β−1), with the *global* W; may be traced
    lane_align: int = 1,       # pad K to this multiple (128 for compiled TPU)
    emit_loglik: bool = False,
    double_buffer: bool = True,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
           Optional[jax.Array]]:
    """One fused column-serial Gauss-Seidel sweep in a single launch.

    Returns ``(mu_new (D,L,K), residual (D,L,K), theta (D,K),
    phi_wk (W_s,K), phi_k (K,), loglik)`` — the same stats the scan
    formulation produces, plus the eq. 36 residuals measured for free and,
    when ``emit_loglik``, the post-sweep eq. 3 data log-likelihood summed
    from in-kernel per-column partials (None otherwise).

    Documents are padded to the 8-sublane boundary with zero-count slots
    (zero counts ⇒ zero Δ, so padding is exact); ``lane_align`` pads the
    topic axis, with padded lanes masked out of the renormalisation and
    the loglik.
    """
    D, L = word_ids.shape
    K = mu.shape[-1]
    Wrows = phi_wk.shape[0]

    pad_d = (-D) % 8
    pad_k = (-K) % lane_align if lane_align > 1 else 0
    Dp, Kp = D + pad_d, K + pad_k
    if pad_d or pad_k:
        word_ids = jnp.pad(word_ids, ((0, pad_d), (0, 0)))
        counts = jnp.pad(counts, ((0, pad_d), (0, 0)))
        mu = jnp.pad(mu, ((0, pad_d), (0, 0), (0, pad_k)))
        theta = jnp.pad(theta, ((0, pad_d), (0, pad_k)))
        phi_wk = jnp.pad(phi_wk, ((0, 0), (0, pad_k)))
        phi_k = jnp.pad(phi_k, ((0, pad_k),))

    mu_cols = mu.transpose(1, 0, 2)             # (L, Dp, Kp) column-major

    kernel = _make_gs_kernel(
        alpha_m1=alpha_m1, beta_m1=beta_m1, k_actual=K, num_cols=L,
        emit_loglik=emit_loglik, double_buffer=double_buffer,
    )
    wb_arr = jnp.reshape(jnp.asarray(wb, mu.dtype), (1,))
    col_of, pin_of, ll_of = column_index_maps(L, emit_loglik)
    grid_len = 2 * L if emit_loglik else L

    out_specs = [
        pl.BlockSpec((Dp, Kp), lambda l, wid, wb: (0, 0)),
        # named VMEM: the double-buffered gather DMAs from this block
        pl.BlockSpec((Wrows, Kp), lambda l, wid, wb: (0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, Kp), lambda l, wid, wb: (0, 0)),
        pl.BlockSpec((1, Dp, Kp), lambda l, wid, wb: (pin_of(l), 0, 0)),
        pl.BlockSpec((1, Dp, Kp), lambda l, wid, wb: (pin_of(l), 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Dp, Kp), theta.dtype),
        jax.ShapeDtypeStruct((Wrows, Kp), phi_wk.dtype),
        jax.ShapeDtypeStruct((1, Kp), phi_k.dtype),
        jax.ShapeDtypeStruct((L, Dp, Kp), mu.dtype),
        jax.ShapeDtypeStruct((L, Dp, Kp), mu.dtype),
    ]
    if emit_loglik:
        out_specs.append(
            pl.BlockSpec((1, Dp, 1), lambda l, wid, wb: (ll_of(l), 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((L, Dp, 1), mu.dtype))

    scratch_shapes = [
        pltpu.VMEM((Dp, Kp), mu.dtype),        # gathered φ̂ rows
        pltpu.VMEM((Dp, Kp), mu.dtype),        # staged Δ rows
    ]
    if double_buffer:
        scratch_shapes.append(pltpu.SemaphoreType.DMA)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(grid_len,),
        in_specs=[
            pl.BlockSpec((1, Dp, 1), lambda l, wid, wb: (col_of(l), 0, 0)),
            pl.BlockSpec((1, Dp, Kp), lambda l, wid, wb: (pin_of(l), 0, 0)),
            pl.BlockSpec((Dp, Kp), lambda l, wid, wb: (0, 0)),
            pl.BlockSpec((Wrows, Kp), lambda l, wid, wb: (0, 0)),
            pl.BlockSpec((1, Kp), lambda l, wid, wb: (0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # flat operands: wid(0) wb(1) counts(2) mu(3) theta(4) phi(5) ptot(6)
        input_output_aliases={4: 0, 5: 1, 6: 2},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(word_ids, wb_arr, column_major(counts), mu_cols, theta, phi_wk,
      phi_k[None, :])

    theta_out, phi_out, ptot_out, mu_out, res_out = outs[:5]
    loglik = outs[5].sum() if emit_loglik else None

    mu_new = mu_out.transpose(1, 0, 2)[:D, :, :K]
    res = res_out.transpose(1, 0, 2)[:D, :, :K]
    return (
        mu_new, res, theta_out[:D, :K], phi_out[:, :K], ptot_out[0, :K],
        loglik,
    )
