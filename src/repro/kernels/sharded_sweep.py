"""Pallas TPU kernels: the two-phase shard-local Gauss-Seidel sweep.

``foem_sharded`` runs the paper's inner loop with the topic axis sharded
over the mesh's ``model`` axis: each shard owns φ̂ (W_s, K/mp), θ̂
(D, K/mp) and μ (D, L, K/mp), and the only cross-shard quantities in the
E-step are the per-token normalisers — the eq. 11/13 denominator (dense)
and the eq. 38 renormalisation mass pair (scheduled).  The fused
single-launch sweeps (``gs_sweep.py`` / ``scheduled_sweep.py``) cannot
serve that path directly because a collective cannot cross a Pallas kernel
boundary; the portable fallback pays L tiny psums and L scan steps per
sweep instead of one launch.

This module splits the fused sweep into the **two-phase** launch structure
(dispatched by ``ops.sweep`` under a ``SweepPlan`` with ``axis_name``):

  * **phase A — probe** (``sharded_probe_pallas``): a shard-local launch
    over the column grid that computes, for every column against the
    *sweep-start* statistics (Jacobi — no fold, φ̂ stays read-only in
    VMEM), the shard's partial normalisers: the local-lane numerator sums
    s^m (D, L) and, for the scheduled sweep, the local eq. 38 previous
    active mass p^m (D, L).  These small per-shard buffers are the only
    phase output.
  * **phase B — reduce** (in ``ops.sweep``): ONE ``lax.psum`` of the
    stacked probe buffers over the model axis, fused with nothing else on
    the wire — O(D·L) per sweep instead of L separate (D,)-psums.
  * **phase C — fold** (``sharded_fold_pallas``): a shard-local launch
    that re-runs the column grid as a true Gauss-Seidel sweep — θ̂, φ̂ and
    φ̂(k) carried in VMEM with ``input_output_aliases`` donation, exactly
    like the single-shard kernels — consuming the reduced normalisers.
    The shard's OWN contribution to each column's denominator is kept
    *live* (recomputed from the carried stats); only the other shards'
    contributions come from the probe (one-phase-stale).  With one shard
    the remainder is zero and the fold degenerates to the single-shard
    kernels' arithmetic.  The launch additionally emits the live local
    masses m^m (D, L) and, with ``emit_loglik``, per-token *pre-log*
    eq. 3 partials u^m (D, L) against the final carried stats (the log
    must happen after the cross-shard psum, so unlike the single-shard
    kernels the stop-rule output here is per token, not per column).
  * **phase D — correct** (in ``ops.sweep``): a second (D, L) psum of the
    live masses and one vectorized renormalisation μ̂ = μ·(target/​mass)
    folded into the statistics, which restores *exact* global
    normalisation (dense: Σ_k μ̂ = 1; scheduled: eq. 38's preserved
    active mass) — so total-mass conservation holds to fp round-off even
    though the in-sweep denominators carried stale cross-shard terms.

The staleness is confined to the *other shards'* share of the denominator
for the duration of one sweep, and the exact renorm is applied between
phases — precisely the stochastic-approximation perturbation Cappé &
Moulines's online-EM analysis (arXiv:1011.1745) tolerates, and the same
"shard-local state, reduce only the normalisers" structure Towards Big
Topic Modeling (arXiv:1311.4150) uses across machines.  See
``docs/ARCHITECTURE.md`` for the launch diagram.

VMEM: the probe carries the same working set as the fold minus the output
aliases; the fold adds only two (D, 1) column blocks over
``scheduled_sweep``'s budget.  ``sharded_fits_vmem`` sizes both.
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
    from_column_major,
    scatter_rows,
)
from repro.kernels.scheduled_sweep import expand_lane_mask


def sharded_fits_vmem(num_rows: int, num_docs: int, num_topics: int,
                      scheduled: bool = True,
                      budget: int = DEFAULT_VMEM_BUDGET) -> bool:
    """Can one two-phase launch's live VMEM set fit?

    Delegates to the ``sharded_fold`` contract in ``repro.analysis`` —
    the fold phase is the high-water mark (carried φ̂/θ̂/φ̂(k) in/out
    pairs, per-column μ blocks, rows + lane-mask scratch, plus the
    (D, 1) normaliser columns the two-phase structure adds), and the
    registered contract is the scheduled variant, which dominates the
    dense one — so one query covers both.
    """
    del scheduled  # the registered high-water contract covers both variants
    return kernel_fits_vmem("sharded_fold", num_rows, num_docs, num_topics,
                            budget)


def _expand_mask(wid_ref, wtop_ref, mask_ref, l, D, K, active_topics, dtype):
    """Serial per-document expansion of the prefetched (flattened) active-
    topic table into the (D, K) lane mask (shared by probe and fold)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def go(d, _):
        mask_ref[pl.ds(d, 1), :] = expand_lane_mask(
            wtop_ref, wid_ref[d, l], active_topics, lane, dtype
        )
        return 0
    jax.lax.fori_loop(0, D, go, 0)


def _lane_guard(x, k_actual):
    """Zero the padded topic lanes (they carry no statistics)."""
    D, K = x.shape
    if k_actual == K:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, (D, K), 1)
    return jnp.where(lane < k_actual, x, 0.0)


# ---------------------------------------------------------------------------
# Phase A — probe
# ---------------------------------------------------------------------------

def _make_probe_kernel(*, alpha_m1: float, beta_m1: float, k_actual: int,
                       active_topics: int, scheduled: bool):
    """Kernel body computing one column's partial normalisers (no fold).

    Ref order: scalar prefetch (wid[, word-topics], wb), inputs (counts[,
    active column], μ column, θ̂, φ̂, φ̂(k)), outputs (s partials[, prev-mass
    partials]), scratch (gathered rows[, lane mask]).
    """

    def kernel(wid_ref, *rest):
        if scheduled:
            (wtop_ref, wb_ref, counts_ref, act_ref, mu_in_ref, theta_ref,
             phi_ref, ptot_ref, s_ref, pm_ref, rows_ref, mask_ref) = rest
        else:
            (wb_ref, counts_ref, mu_in_ref, theta_ref, phi_ref, ptot_ref,
             s_ref, rows_ref) = rest
        l = pl.program_id(0)
        D, K = theta_ref.shape
        wb = wb_ref[0]
        cnt = counts_ref[0]                     # (D, 1)
        mu_old = mu_in_ref[0]                   # (D, K)

        def gather(d, _):
            w = wid_ref[d, l]
            rows_ref[pl.ds(d, 1), :] = phi_ref[pl.ds(w, 1), :]
            return 0
        jax.lax.fori_loop(0, D, gather, 0)

        if scheduled:
            _expand_mask(wid_ref, wtop_ref, mask_ref, l, D, K,
                         active_topics, mu_old.dtype)
            mask = mask_ref[...] * act_ref[0]
            ex = cnt * mu_old * mask
        else:
            mask = None
            ex = cnt * mu_old

        th = jnp.maximum(theta_ref[...] - ex, 0.0)
        ph = jnp.maximum(rows_ref[...] - ex, 0.0)
        pt = ptot_ref[...] - ex
        num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb)
        if scheduled:
            num = num * mask
        num = _lane_guard(num, k_actual)
        s_ref[0] = num.sum(-1, keepdims=True)
        if scheduled:
            pm_ref[0] = _lane_guard(mu_old * mask, k_actual).sum(
                -1, keepdims=True
            )

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("alpha_m1", "beta_m1", "lane_align", "interpret"),
)
def sharded_probe_pallas(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_wk
    counts: jax.Array,         # (D, L) float32
    mu: jax.Array,             # (D, L, K) shard-local topic lanes
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    word_topics: Optional[jax.Array] = None,   # (W_s, A) int32 (scheduled)
    token_active: Optional[jax.Array] = None,  # (D, L) bool (scheduled)
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: jax.Array | float,
    lane_align: int = 1,
    interpret: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Phase A of the two-phase sharded sweep: per-column partial normalisers.

    Returns ``(s (D, L), prev_mass (D, L) | None)`` — the shard's local-lane
    numerator sums against the sweep-start statistics and, when scheduled,
    the local eq. 38 previous active mass.  ``lax.psum`` of these over the
    model axis gives the cross-shard normalisers phase C consumes.
    """
    D, L = word_ids.shape
    K = mu.shape[-1]
    Wrows = phi_wk.shape[0]
    scheduled = word_topics is not None
    A = word_topics.shape[-1] if scheduled else 0

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
        if scheduled:
            token_active = jnp.pad(token_active, ((0, pad_d), (0, 0)))

    mu_cols = mu.transpose(1, 0, 2)             # (L, Dp, Kp)
    wb_arr = jnp.reshape(jnp.asarray(wb, mu.dtype), (1,))
    kernel = _make_probe_kernel(
        alpha_m1=alpha_m1, beta_m1=beta_m1, k_actual=K, active_topics=A,
        scheduled=scheduled,
    )

    col = pl.BlockSpec((1, Dp, 1), lambda l, *p: (l, 0, 0))
    mu_spec = pl.BlockSpec((1, Dp, Kp), lambda l, *p: (l, 0, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda l, *p: (0,) * len(shape))

    in_specs = [col]                            # counts
    operands = [column_major(counts)]
    if scheduled:
        in_specs.append(col)                    # active column
        operands.append(column_major(token_active.astype(mu.dtype)))
    in_specs += [mu_spec, full((Dp, Kp)), full((Wrows, Kp)), full((1, Kp))]
    operands += [mu_cols, theta, phi_wk, phi_k[None, :]]

    out_specs = [col]
    out_shape = [jax.ShapeDtypeStruct((L, Dp, 1), mu.dtype)]
    if scheduled:
        out_specs.append(col)
        out_shape.append(jax.ShapeDtypeStruct((L, Dp, 1), mu.dtype))

    scratch_shapes = [pltpu.VMEM((Dp, Kp), mu.dtype)]        # gathered rows
    if scheduled:
        scratch_shapes.append(pltpu.VMEM((Dp, Kp), mu.dtype))  # lane mask

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 if scheduled else 2,
        grid=(L,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    prefetch = (word_ids, word_topics.reshape(-1), wb_arr) if scheduled else (
        word_ids, wb_arr
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*prefetch, *operands)
    s = from_column_major(outs[0])[:D]
    pm = from_column_major(outs[1])[:D] if scheduled else None
    return s, pm


# ---------------------------------------------------------------------------
# Phase C — fold
# ---------------------------------------------------------------------------

def _make_fold_kernel(*, alpha_m1: float, beta_m1: float, k_actual: int,
                      num_cols: int, active_topics: int, scheduled: bool,
                      emit_loglik: bool):
    """Kernel body for the shard-local Gauss-Seidel fold phase.

    Ref order: scalar prefetch (wid[, word-topics], wb), inputs (counts[,
    active column], remainder column, [prev-mass column,] μ column, θ̂, φ̂,
    φ̂(k)), outputs (θ̂, φ̂, φ̂(k) carried; μ, residual columns; live-mass
    column; loglik-partial column when emitted), scratch (rows[, mask]).
    """

    def kernel(wid_ref, *rest):
        i = 0
        if scheduled:
            wtop_ref = rest[i]; i += 1
        wb_ref = rest[i]; i += 1
        counts_ref = rest[i]; i += 1
        if scheduled:
            act_ref = rest[i]; i += 1
        rem_ref = rest[i]; i += 1
        if scheduled:
            pm_ref = rest[i]; i += 1
        mu_in_ref, theta_in_ref, phi_in_ref, ptot_in_ref = rest[i:i + 4]
        i += 4
        theta_ref, phi_ref, ptot_ref, mu_ref, res_ref, m_ref = rest[i:i + 6]
        i += 6
        ll_ref = None
        if emit_loglik:
            ll_ref = rest[i]; i += 1
        rows_ref, delta_ref = rest[i:i + 2]; i += 2
        mask_ref = rest[i] if scheduled else None

        l = pl.program_id(0)
        D, K = theta_ref.shape
        wb = wb_ref[0]

        @pl.when(l == 0)
        def _():
            theta_ref[...] = theta_in_ref[...]
            phi_ref[...] = phi_in_ref[...]
            ptot_ref[...] = ptot_in_ref[...]

        def gather(col, with_mask):
            def go(d, _):
                w = wid_ref[d, col]
                rows_ref[pl.ds(d, 1), :] = phi_ref[pl.ds(w, 1), :]
                return 0
            jax.lax.fori_loop(0, D, go, 0)
            if with_mask:
                _expand_mask(wid_ref, wtop_ref, mask_ref, col, D, K,
                             active_topics, rows_ref.dtype)

        def sweep_col():
            cnt = counts_ref[0]                     # (D, 1)
            rem = rem_ref[0]                        # (D, 1) other shards' Σnum
            mu_old = mu_in_ref[0]                   # (D, K)
            theta = theta_ref[...]
            ptot = ptot_ref[...]                    # (1, K)

            gather(l, scheduled)
            if scheduled:
                mask = mask_ref[...] * act_ref[0]
                ex = cnt * mu_old * mask
            else:
                ex = cnt * mu_old

            # ---- E-step numerator from the LIVE carried stats ----
            th = jnp.maximum(theta - ex, 0.0)
            ph = jnp.maximum(rows_ref[...] - ex, 0.0)
            pt = ptot - ex
            num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb)
            if scheduled:
                num = num * mask
            num = _lane_guard(num, k_actual)

            # ---- normaliser: own lanes live + other shards from phase B ----
            denom = jnp.maximum(rem + num.sum(-1, keepdims=True), 1e-30)
            if scheduled:
                # eq. 38 renorm to the GLOBAL previous active mass
                mu_new = mask * (num / denom * pm_ref[0]) + (
                    1.0 - mask
                ) * mu_old
                delta = cnt * (mu_new - mu_old)     # zero off the active set
                live = _lane_guard(mu_new * mask, k_actual)
            else:
                mu_new = num / denom
                delta = cnt * mu_new - ex
                live = _lane_guard(mu_new, k_actual)
            m_ref[0] = live.sum(-1, keepdims=True)

            # ---- Gauss-Seidel fold before the next column ----
            theta_ref[...] = theta + delta
            ptot_ref[...] = ptot + delta.sum(0, keepdims=True)
            delta_ref[...] = delta
            scatter_rows(wid_ref, l, phi_ref, delta_ref, D)

            mu_ref[0] = mu_new
            res_ref[0] = jnp.abs(delta) if scheduled else (
                cnt * jnp.abs(mu_new - mu_old)
            )

        def ppl_col():
            # Stop-rule phase against the FINAL carried stats.  Unlike the
            # single-shard kernels this emits PRE-LOG per-token partials:
            # u = Σ_{k local} (θ̂+α)(φ̂_w+β)/(φ̂(k)+wb) — the log (and the
            # θ̂-normaliser division) must wait for the cross-shard psum.
            gather(l - num_cols, False)
            th_n = theta_ref[...] + alpha_m1
            ph_n = (rows_ref[...] + beta_m1) / jnp.maximum(
                ptot_ref[...] + wb, 1e-30
            )
            ll_ref[0] = _lane_guard(th_n * ph_n, k_actual).sum(
                -1, keepdims=True
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
def sharded_fold_pallas(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_wk
    counts: jax.Array,         # (D, L) float32
    mu: jax.Array,             # (D, L, K) shard-local topic lanes
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    remainder: jax.Array,      # (D, L) other shards' numerator sums (phase B)
    prev_mass: Optional[jax.Array] = None,     # (D, L) global eq. 38 mass
    word_topics: Optional[jax.Array] = None,   # (W_s, A) int32 (scheduled)
    token_active: Optional[jax.Array] = None,  # (D, L) bool (scheduled)
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: jax.Array | float,
    lane_align: int = 1,
    emit_loglik: bool = False,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
           jax.Array, Optional[jax.Array]]:
    """Phase C of the two-phase sharded sweep: the shard-local GS fold.

    One launch over the column grid, θ̂/φ̂/φ̂(k) carried in VMEM and donated
    exactly like ``gs_sweep_pallas``/``scheduled_sweep_pallas``; per column
    the denominator is the live own-lane numerator sum plus ``remainder``
    (the psum'd probe sums minus the shard's own probe contribution).  With
    ``remainder == 0`` (and ``prev_mass`` the local mass) this reproduces
    the single-shard kernels' arithmetic.

    Returns ``(mu_new (D,L,K), residual (D,L,K), theta (D,K),
    phi_wk (W_s,K), phi_k (K,), live_mass (D,L), loglik_u (D,L) | None)``
    where ``live_mass`` feeds the phase D exact renorm psum and
    ``loglik_u`` the stop rule's pre-log partial psum.
    """
    D, L = word_ids.shape
    K = mu.shape[-1]
    Wrows = phi_wk.shape[0]
    scheduled = word_topics is not None
    A = word_topics.shape[-1] if scheduled else 0

    pad_d = (-D) % 8
    pad_k = (-K) % lane_align if lane_align > 1 else 0
    Dp, Kp = D + pad_d, K + pad_k
    if pad_d or pad_k:
        word_ids = jnp.pad(word_ids, ((0, pad_d), (0, 0)))
        counts = jnp.pad(counts, ((0, pad_d), (0, 0)))
        remainder = jnp.pad(remainder, ((0, pad_d), (0, 0)))
        mu = jnp.pad(mu, ((0, pad_d), (0, 0), (0, pad_k)))
        theta = jnp.pad(theta, ((0, pad_d), (0, pad_k)))
        phi_wk = jnp.pad(phi_wk, ((0, 0), (0, pad_k)))
        phi_k = jnp.pad(phi_k, ((0, pad_k),))
        if scheduled:
            prev_mass = jnp.pad(prev_mass, ((0, pad_d), (0, 0)))
            token_active = jnp.pad(token_active, ((0, pad_d), (0, 0)))

    mu_cols = mu.transpose(1, 0, 2)             # (L, Dp, Kp)
    wb_arr = jnp.reshape(jnp.asarray(wb, mu.dtype), (1,))
    kernel = _make_fold_kernel(
        alpha_m1=alpha_m1, beta_m1=beta_m1, k_actual=K, num_cols=L,
        active_topics=A, scheduled=scheduled, emit_loglik=emit_loglik,
    )

    grid_len = 2 * L if emit_loglik else L
    col_of, pin_of, ll_of = column_index_maps(L, emit_loglik)

    col = pl.BlockSpec((1, Dp, 1), lambda l, *p: (col_of(l), 0, 0))
    col_pin = pl.BlockSpec((1, Dp, 1), lambda l, *p: (pin_of(l), 0, 0))
    mu_spec = pl.BlockSpec((1, Dp, Kp), lambda l, *p: (pin_of(l), 0, 0))
    full = lambda shape: pl.BlockSpec(shape, lambda l, *p: (0,) * len(shape))

    in_specs = [col]                            # counts
    operands = [column_major(counts)]
    if scheduled:
        in_specs.append(col)                    # active column
        operands.append(column_major(token_active.astype(mu.dtype)))
    in_specs.append(col)                        # remainder column
    operands.append(column_major(remainder.astype(mu.dtype)))
    if scheduled:
        in_specs.append(col)                    # global prev-mass column
        operands.append(column_major(prev_mass.astype(mu.dtype)))
    in_specs += [mu_spec, full((Dp, Kp)), full((Wrows, Kp)), full((1, Kp))]
    operands += [mu_cols, theta, phi_wk, phi_k[None, :]]

    out_specs = [
        full((Dp, Kp)),                                     # θ̂ carried
        full((Wrows, Kp)),                                  # φ̂ carried
        full((1, Kp)),                                      # φ̂(k) carried
        pl.BlockSpec((1, Dp, Kp), lambda l, *p: (pin_of(l), 0, 0)),  # μ
        pl.BlockSpec((1, Dp, Kp), lambda l, *p: (pin_of(l), 0, 0)),  # resid
        col_pin,                                            # live mass
    ]
    out_shape = [
        jax.ShapeDtypeStruct((Dp, Kp), theta.dtype),
        jax.ShapeDtypeStruct((Wrows, Kp), phi_wk.dtype),
        jax.ShapeDtypeStruct((1, Kp), phi_k.dtype),
        jax.ShapeDtypeStruct((L, Dp, Kp), mu.dtype),
        jax.ShapeDtypeStruct((L, Dp, Kp), mu.dtype),
        jax.ShapeDtypeStruct((L, Dp, 1), mu.dtype),
    ]
    if emit_loglik:
        out_specs.append(                                   # pre-log partials
            pl.BlockSpec((1, Dp, 1), lambda l, *p: (ll_of(l), 0, 0))
        )
        out_shape.append(jax.ShapeDtypeStruct((L, Dp, 1), mu.dtype))

    scratch_shapes = [
        pltpu.VMEM((Dp, Kp), mu.dtype),                     # gathered rows
        pltpu.VMEM((Dp, Kp), mu.dtype),                     # staged Δ rows
    ]
    if scheduled:
        scratch_shapes.append(pltpu.VMEM((Dp, Kp), mu.dtype))  # lane mask

    num_prefetch = 3 if scheduled else 2
    # flat operand index of the θ̂ input (aliased with output 0): prefetch
    # args + counts [+ act] + rem [+ pm] + μ, then θ̂ φ̂ φ̂(k)
    theta_idx = num_prefetch + (5 if scheduled else 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_prefetch,
        grid=(grid_len,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch_shapes,
    )
    prefetch = (word_ids, word_topics.reshape(-1), wb_arr) if scheduled else (
        word_ids, wb_arr
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases={theta_idx: 0, theta_idx + 1: 1,
                              theta_idx + 2: 2},
        compiler_params=compiler_params(),
        interpret=interpret,
    )(*prefetch, *operands)

    theta_out, phi_out, ptot_out, mu_out, res_out, m_out = outs[:6]
    u = from_column_major(outs[6])[:D] if emit_loglik else None

    mu_new = mu_out.transpose(1, 0, 2)[:D, :, :K]
    res = res_out.transpose(1, 0, 2)[:D, :, :K]
    return (
        mu_new, res, theta_out[:D, :K], phi_out[:, :K], ptot_out[0, :K],
        from_column_major(m_out)[:D], u,
    )
