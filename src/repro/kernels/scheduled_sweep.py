"""Pallas TPU kernel: single-launch scheduled (sparse) Gauss-Seidel sweep.

Dynamic scheduling (paper §3.1) restricts each post-warm-up sweep to the
top-λ_k·K active topics per vocabulary word, with the eq. 38 partial
renormalisation preserving the inactive topics' mass and the λ_w word
threshold skipping settled words entirely.  The scan formulation
(``foem.scheduled_iem_sweep``) pays per column: a (D, A) three-way
gather, the ``topk_estep`` launch, three 2-D scatters into the full
(W_s, K)/(D, K) matrices and a ``put_along_axis`` — so the *sparse* sweep
that should be the fastest path launches and moves more data than the
dense fused sweep.  Here it is ONE launch, structured like
``gs_sweep_pallas``:

  * the grid is the column index (sequential on a TPU core = the
    Gauss-Seidel ordering); θ̂ (D, K), φ̂ (W_s, K), φ̂(k) are carried in
    VMEM with ``input_output_aliases`` donation;
  * BOTH the word ids (D, L) and the per-word active-topic ids (W_s, A)
    are scalar-prefetched (``PrefetchScalarGridSpec``): the word id drives
    the dynamic φ̂ row gather/scatter, and the word's active-topic ids are
    expanded in the same serial loop into a (D, K) lane mask — the TPU
    adaptation of the active set (A ≤ 128 active lanes out of a 128-lane
    vector register cost the same arithmetic as a dense row, so masking
    beats an (A,)-gather and keeps every store row-contiguous);
  * the eq. 38 partial renormalisation and the λ_w active-word masking are
    fused in-kernel (subsuming ``topk_estep`` for this path): the active
    mask zeroes the numerator off the active set, the renorm rescales to
    the active set's previous mass, and inactive lanes/rows keep μ_old;
  * the eq. 36 residual *replacement* values — counts·|Δμ|, non-zero only
    on the touched (word, topic) entries — come out as a by-product, so
    the scheduler refresh is one segment-sum instead of a re-measurement;
  * with ``emit_loglik=True`` the grid is extended by L stop-rule steps
    emitting per-column eq. 3 data-loglik partials against the final
    carried stats — ``foem_minibatch``'s while-loop stop rule needs no
    separate (D, L, K) gather+einsum perplexity pass.

VMEM adds one (D, K) mask scratch over ``gs_sweep``'s budget; the
dispatch layer (``ops.sweep``) falls back to the delta-compacted portable
scan when the working set is larger or the backend is not TPU.
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
from repro.kernels.gs_sweep import (
    column_index_maps,
    column_major,
    compiler_params,
    loglik_partial,
    scatter_rows,
)


def sched_fits_vmem(num_rows: int, num_docs: int, num_topics: int,
                    budget: int = DEFAULT_VMEM_BUDGET) -> bool:
    """Like ``gs_sweep.fits_vmem`` plus the (D, K) active-mask scratch.

    Delegates to the ``scheduled_sweep`` contract in ``repro.analysis``
    (the shared budget model).
    """
    return kernel_fits_vmem("scheduled_sweep", num_rows, num_docs,
                            num_topics, budget)


def expand_lane_mask(wtop_ref, w, active_topics: int, lane, dtype):
    """One word's (1, K) {0, 1} lane mask from its active-topic ids.

    ``wtop_ref`` is the flattened (W_s·A,) scalar-prefetched table: a 2-D
    (W_s, A) SMEM operand pads its minor dim to 128 words, which at
    W_s = 8k is 4 MiB — four times the scalar memory.
    """
    base = w * active_topics
    m = jnp.zeros(lane.shape, dtype)
    for a in range(active_topics):              # static unroll, A ≈ 16
        m = jnp.maximum(m, (lane == wtop_ref[base + a]).astype(dtype))
    return m


def _make_sched_kernel(*, alpha_m1: float, beta_m1: float, k_actual: int,
                       num_cols: int, active_topics: int, emit_loglik: bool):
    """Kernel body for a static (A, loglik) configuration.

    Ref order: scalar prefetch (wid, flat word-topics, wb), inputs (counts
    column, active-word column, μ column, θ̂, φ̂, φ̂(k)), outputs (θ̂, φ̂,
    φ̂(k) carried; μ, residual columns; loglik partial columns when
    emitted), scratch (gathered rows, lane mask, staged Δ).
    """

    def kernel(wid_ref, wtop_ref, wb_ref, counts_ref, act_ref, mu_in_ref,
               theta_in_ref, phi_in_ref, ptot_in_ref, *rest):
        theta_ref, phi_ref, ptot_ref, mu_ref, res_ref = rest[:5]
        ll_ref = rest[5] if emit_loglik else None
        rows_ref, mask_ref, delta_ref = rest[6:] if emit_loglik else rest[5:]

        l = pl.program_id(0)
        D, K = theta_ref.shape
        wb = wb_ref[0]

        @pl.when(l == 0)
        def _():
            theta_ref[...] = theta_in_ref[...]
            phi_ref[...] = phi_in_ref[...]
            ptot_ref[...] = ptot_in_ref[...]

        def sweep_col():
            cnt = counts_ref[0]                     # (D, 1)
            act = act_ref[0]                        # (D, 1) ∈ {0, 1}
            mu_old = mu_in_ref[0]                   # (D, K)
            theta = theta_ref[...]
            ptot = ptot_ref[...]                    # (1, K)
            lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

            # ---- serial gather: the word's φ̂ row AND its active-topic
            # lane mask, expanded from the prefetched word-topic table ----
            def gather(d, _):
                w = wid_ref[d, l]
                rows_ref[pl.ds(d, 1), :] = phi_ref[pl.ds(w, 1), :]
                mask_ref[pl.ds(d, 1), :] = expand_lane_mask(
                    wtop_ref, w, active_topics, lane, mu_old.dtype
                )
                return 0
            jax.lax.fori_loop(0, D, gather, 0)

            # λ_w word mask folds into the lane mask: a skipped word's row
            # has an all-zero mask, so μ_new = μ_old and Δ = 0 below.
            mask = mask_ref[...] * act              # (D, K)

            # ---- fused sparse E-step: eq. 13 on the active set only ----
            ex = cnt * mu_old * mask
            th = jnp.maximum(theta - ex, 0.0)
            ph = jnp.maximum(rows_ref[...] - ex, 0.0)
            pt = ptot - ex
            num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb) * mask
            # eq. 38 partial renorm: preserve the active set's prev mass
            prev_mass = (mu_old * mask).sum(-1, keepdims=True)
            denom = jnp.maximum(num.sum(-1, keepdims=True), 1e-30)
            mu_new = mask * (num / denom * prev_mass) + (1.0 - mask) * mu_old
            delta = cnt * (mu_new - mu_old)         # zero off the active set

            # ---- Gauss-Seidel fold before the next column ----
            theta_ref[...] = theta + delta
            ptot_ref[...] = ptot + delta.sum(0, keepdims=True)
            delta_ref[...] = delta
            scatter_rows(wid_ref, l, phi_ref, delta_ref, D)

            mu_ref[0] = mu_new
            res_ref[0] = jnp.abs(delta)             # eq. 36 replacement value

        def ppl_col():
            # Stop-rule phase against the FINAL carried stats — shared
            # arithmetic with the dense kernel (gs_sweep.loglik_partial).
            def gather(d, _):
                w = wid_ref[d, l - num_cols]
                rows_ref[pl.ds(d, 1), :] = phi_ref[pl.ds(w, 1), :]
                return 0
            jax.lax.fori_loop(0, D, gather, 0)
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


@functools.partial(
    jax.jit,
    static_argnames=("alpha_m1", "beta_m1", "lane_align", "emit_loglik",
                     "interpret"),
)
def scheduled_sweep_pallas(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_wk
    counts: jax.Array,         # (D, L) float32
    mu: jax.Array,             # (D, L, K)
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    word_topics: jax.Array,    # (W_s, A) int32 — active topic ids per word
    token_active: jax.Array,   # (D, L) bool — λ_w word mask per token
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: jax.Array | float,     # W·(β−1), global W; may be traced
    lane_align: int = 1,       # pad K to this multiple (128 for compiled TPU)
    emit_loglik: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
           Optional[jax.Array]]:
    """One fused scheduled sparse sweep in a single launch.

    Returns ``(mu_new (D,L,K), residual (D,L,K), theta (D,K),
    phi_wk (W_s,K), phi_k (K,), loglik)``, the ``SweepResult`` field set:
    inactive (token, topic) entries keep μ_old and carry zero residual,
    matching the ``scheduled_iem_sweep`` scan semantics; ``loglik`` is the
    post-sweep eq. 3 data log-likelihood (None unless ``emit_loglik``).

    Document rows are padded to the 8-sublane boundary with zero-count,
    inactive slots; ``lane_align`` pads the topic axis (padded lanes can
    never enter an active set, so the mask excludes them for free).
    """
    D, L = word_ids.shape
    K = mu.shape[-1]
    A = word_topics.shape[-1]
    Wrows = phi_wk.shape[0]

    pad_d = (-D) % 8
    pad_k = (-K) % lane_align if lane_align > 1 else 0
    Dp, Kp = D + pad_d, K + pad_k
    if pad_d or pad_k:
        word_ids = jnp.pad(word_ids, ((0, pad_d), (0, 0)))
        counts = jnp.pad(counts, ((0, pad_d), (0, 0)))
        token_active = jnp.pad(token_active, ((0, pad_d), (0, 0)))
        mu = jnp.pad(mu, ((0, pad_d), (0, 0), (0, pad_k)))
        theta = jnp.pad(theta, ((0, pad_d), (0, pad_k)))
        phi_wk = jnp.pad(phi_wk, ((0, 0), (0, pad_k)))
        phi_k = jnp.pad(phi_k, ((0, pad_k),))

    mu_cols = mu.transpose(1, 0, 2)             # (L, Dp, Kp) column-major
    act = token_active.astype(mu.dtype)

    kernel = _make_sched_kernel(
        alpha_m1=alpha_m1, beta_m1=beta_m1, k_actual=K, num_cols=L,
        active_topics=A, emit_loglik=emit_loglik,
    )
    wb_arr = jnp.reshape(jnp.asarray(wb, mu.dtype), (1,))
    col_of, pin_of, ll_of = column_index_maps(L, emit_loglik)
    grid_len = 2 * L if emit_loglik else L

    out_specs = [
        pl.BlockSpec((Dp, Kp), lambda l, wid, wt, wb: (0, 0)),
        pl.BlockSpec((Wrows, Kp), lambda l, wid, wt, wb: (0, 0)),
        pl.BlockSpec((1, Kp), lambda l, wid, wt, wb: (0, 0)),
        pl.BlockSpec((1, Dp, Kp), lambda l, wid, wt, wb: (pin_of(l), 0, 0)),
        pl.BlockSpec((1, Dp, Kp), lambda l, wid, wt, wb: (pin_of(l), 0, 0)),
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
            pl.BlockSpec((1, Dp, 1), lambda l, wid, wt, wb: (ll_of(l), 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((L, Dp, 1), mu.dtype))

    col = pl.BlockSpec((1, Dp, 1), lambda l, wid, wt, wb: (col_of(l), 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(grid_len,),
        in_specs=[
            col,                                 # counts column
            col,                                 # active-word column
            pl.BlockSpec((1, Dp, Kp), lambda l, wid, wt, wb: (pin_of(l), 0, 0)),
            pl.BlockSpec((Dp, Kp), lambda l, wid, wt, wb: (0, 0)),
            pl.BlockSpec((Wrows, Kp), lambda l, wid, wt, wb: (0, 0)),
            pl.BlockSpec((1, Kp), lambda l, wid, wt, wb: (0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Dp, Kp), mu.dtype),      # gathered φ̂ rows
            pltpu.VMEM((Dp, Kp), mu.dtype),      # active-topic lane mask
            pltpu.VMEM((Dp, Kp), mu.dtype),      # staged Δ rows
        ],
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        # flat operands: wid(0) wtop(1) wb(2) counts(3) act(4) mu(5)
        #                theta(6) phi(7) ptot(8)
        input_output_aliases={6: 0, 7: 1, 8: 2},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(word_ids, word_topics.reshape(-1), wb_arr, column_major(counts),
      column_major(act), mu_cols, theta, phi_wk, phi_k[None, :])

    theta_out, phi_out, ptot_out, mu_out, res_out = outs[:5]
    loglik = outs[5].sum() if emit_loglik else None

    mu_new = mu_out.transpose(1, 0, 2)[:D, :, :K]
    res = res_out.transpose(1, 0, 2)[:D, :, :K]
    return (
        mu_new, res, theta_out[:D, :K], phi_out[:, :K], ptot_out[0, :K],
        loglik,
    )
