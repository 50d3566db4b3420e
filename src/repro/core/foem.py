"""FOEM — Fast Online EM for LDA (paper Fig. 4).

FOEM = SEM's minibatch stream (outer loop) with the inner batch-EM replaced by
the *time-efficient IEM*: blocked incremental sweeps restricted, after a first
full sweep, to the top-``λ_k K`` topics per vocabulary word and the top-
``λ_w W_s`` words, ranked by responsibility residuals (dynamic scheduling,
§3.1), with the eq. 38 partial renormalisation.  Global topic-word statistics
accumulate with the implicit 1/s learning rate (eq. 33, ``rho_mode=
"accumulate"``) or the explicit stepwise interpolation (eq. 20,
``rho_mode="stepwise"``).

Everything here is jit-compilable with static shapes; the parameter-streaming
tier (host/disk residency of φ̂, §3.2) lives in ``core/streaming.py`` and the
trainer that stitches them together in ``core/trainer.py``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import em
from repro.core import scheduling as sched_lib
from repro.kernels import ops as kops
from repro.core.types import (
    GlobalStats,
    LDAConfig,
    LocalState,
    MinibatchData,
    SchedulerState,
    SweepPlan,
    uniform_responsibilities,
)


class FOEMDiagnostics(NamedTuple):
    sweeps_run: jax.Array       # () int32 — inner sweeps actually executed
    final_train_ppl: jax.Array  # () float32
    residual_mass: jax.Array    # () float32 — Σ r_w at exit


class FOEMMinibatchResult(NamedTuple):
    local: LocalState
    phi_wk: jax.Array           # working copy WITH this minibatch folded in
    phi_k: jax.Array
    scheduler: SchedulerState
    diag: FOEMDiagnostics


# ---------------------------------------------------------------------------
# Scheduled (sparse) blocked-IEM sweep
# ---------------------------------------------------------------------------

def scheduled_iem_sweep(
    batch: MinibatchData,
    local: LocalState,
    phi_wk: jax.Array,          # (Wv, K) working stats (minibatch folded in)
    phi_k: jax.Array,           # (K,)
    scheduler: SchedulerState,
    cfg: LDAConfig,
    *,
    vocab_size: Optional[int] = None,
    compute_loglik: bool = False,
    plan: Optional[SweepPlan] = None,
    num_words: Optional[jax.Array] = None,
) -> Tuple[LocalState, jax.Array, jax.Array, SchedulerState,
           Optional[jax.Array]]:
    """One dynamic-scheduling sweep: update only active (word, topic) entries.

    Work per sweep is O(NNZ_s · λ_k K + W_s · K log K) — the paper's
    'time-efficient IEM' bound — instead of O(NNZ_s · 2K).

    The column-serial case (B = L, ``cfg.sweep_impl == "fused"``) routes
    through ``kernels.ops.sweep``: one launch on the kernel path, the
    delta-compacted portable scan elsewhere, with the eq. 36 replacement
    residuals and (``compute_loglik``) the stop-rule log-likelihood emitted
    by the sweep itself.  A coarse block count keeps the legacy blocked
    scan over ``kops.topk_estep``.

    Under a sharded ``plan`` (``foem_sharded``: topic lanes K/mp per
    shard, ``cfg.topk_shards == mp``) the selection runs on the shard's
    *local* residual slice — top-(A/mp) local ids, whose union across
    shards is the balanced size-A active set — and the sweep always takes
    the unified dispatch (the legacy blocked scan has no sharded form).

    ``num_words`` is the real W_s when ``phi_wk``'s trailing rows pad a
    jit-shape bucket (the λ_w word ranking must not count them).

    Returns ``(local, phi, ptot, scheduler, loglik-or-None)``.
    """
    A = cfg.active_topics
    assert A > 0, "scheduled_iem_sweep requires cfg.active_topics > 0"
    D, L = batch.word_ids.shape
    K = cfg.K
    W = vocab_size if vocab_size is not None else cfg.W
    Wrows = phi_wk.shape[0]
    sharded = plan is not None and plan.axis_name is not None

    # ---- selection (the lax.top_k partial sort; paper's insertion sort) ----
    if sharded:
        # scheduler.r_wk is the (W_s, K/mp) local slice: a plain local
        # top-(A/mp) IS the shard's group of the grouped selection
        word_topics = sched_lib.select_active_topics(
            scheduler, max(1, A // max(1, cfg.topk_shards))
        )                                                          # (Wv, A/mp)
    else:
        word_topics = sched_lib.select_active_topics(
            scheduler, A, cfg.topk_shards
        )                                                          # (Wv, A)
    if sharded and cfg.active_words_frac < 1.0:
        # the λ_w word ranking needs the GLOBAL eq. 37 residual: a
        # shard-local threshold would freeze a word on one shard and not
        # another, making the cross-shard normaliser masks inconsistent.
        # One (W_s,)-psum; every shard then derives the identical mask.
        r_w = jax.lax.psum(scheduler.r_w, plan.axis_name)
        word_thresh = sched_lib.select_active_words_threshold(
            sched_lib.SchedulerState(r_wk=scheduler.r_wk, r_w=r_w),
            cfg.active_words_frac,
        )
    else:
        r_w = scheduler.r_w
        word_thresh = sched_lib.select_active_words_threshold(
            scheduler, cfg.active_words_frac, num_words
        )
    token_active = (
        jnp.take(r_w, batch.word_ids, axis=0) >= word_thresh
    ) & (batch.counts > 0)                                         # (D, L)

    # ---- blocked Gauss-Seidel over token columns (0 = column-serial) ----
    B = cfg.resolve_blocks(L)
    if sharded or (B == L and cfg.sweep_impl == "fused"):
        r = kops.sweep(
            batch.word_ids, batch.counts, local.mu, local.theta_dk,
            phi_wk, phi_k,
            alpha_m1=cfg.alpha_m1, beta_m1=cfg.beta_m1,
            wb=W * cfg.beta_m1,
            word_topics=word_topics, token_active=token_active,
            compute_loglik=compute_loglik, unroll=cfg.sweep_unroll,
            plan=plan, debug_checks=cfg.debug_checks,
        )
        scheduler = sched_lib.scheduler_update_from_sweep(
            scheduler, r.residual, batch.word_ids, word_topics
        )
        return (
            LocalState(mu=r.mu, theta_dk=r.theta), r.phi_wk, r.phi_k,
            scheduler, r.loglik,
        )
    token_topics = jnp.take(word_topics, batch.word_ids, axis=0)   # (D, L, A)
    pad = (-L) % B

    def _pad(x, fill=0):
        if not pad:
            return x
        cfgpad = [(0, 0)] * x.ndim
        cfgpad[1] = (0, pad)
        return jnp.pad(x, cfgpad, constant_values=fill)

    wid = _pad(batch.word_ids)
    cnt = _pad(batch.counts)
    mu = _pad(local.mu)
    ttop = _pad(token_topics)
    tact = _pad(token_active, fill=False)
    Lp = L + pad
    blk = Lp // B

    def blkview(x):
        return x.reshape((D, B, blk) + x.shape[2:]).transpose(
            (1, 0, 2) + tuple(range(3, x.ndim + 1))
        )

    w_b, c_b, mu_b, tt_b, ta_b = map(blkview, (wid, cnt, mu, ttop, tact))
    drows = jnp.arange(D)[:, None, None]

    def body(carry, xs):
        theta, phi, ptot = carry
        wid_b, cnt_b, mu_old, top_b, act_b = xs
        # Gather the active slices only — O(A), not O(K).
        mu_prev_a = jnp.take_along_axis(mu_old, top_b, axis=-1)     # (D,blk,A)
        theta_a = theta[drows, top_b]                               # (D,blk,A)
        phi_a = phi[wid_b[..., None], top_b]                        # (D,blk,A)
        ptot_a = ptot[top_b]                                        # (D,blk,A)
        # fused exclusion + eq. 13 + eq. 38 renorm + mask + delta — the
        # kernels/topk_estep Pallas kernel on TPU, its jnp oracle elsewhere
        blkD, blkL, A_ = mu_prev_a.shape
        T = blkD * blkL
        mu_new_flat, delta_flat = kops.topk_estep(
            theta_a.reshape(T, A_), phi_a.reshape(T, A_),
            ptot_a.reshape(T, A_), mu_prev_a.reshape(T, A_),
            cnt_b.reshape(T), act_b.reshape(T),
            alpha_m1=cfg.alpha_m1, beta_m1=cfg.beta_m1,
            wb=W * cfg.beta_m1,
        )
        mu_new_a = mu_new_flat.reshape(blkD, blkL, A_)
        delta = delta_flat.reshape(blkD, blkL, A_)                  # (D,blk,A)

        # fold θ̂ : 2-D scatter over (doc, topic)
        theta = theta.at[
            jnp.broadcast_to(drows, top_b.shape), top_b
        ].add(delta)
        # fold φ̂ : 2-D scatter over (word, topic) — flattening W·K would
        # overflow int32 in the big-model regime
        phi = phi.at[
            jnp.broadcast_to(wid_b[..., None], top_b.shape), top_b
        ].add(delta)
        ptot = ptot.at[top_b.reshape(-1)].add(delta.reshape(-1))
        mu_out = jnp.put_along_axis(
            mu_old, top_b, mu_new_a, axis=-1, inplace=False
        )
        abs_delta = jnp.abs(delta)
        return (theta, phi, ptot), (mu_out, abs_delta)

    (theta, phi, ptot), (mu_out_b, absdelta_b) = jax.lax.scan(
        body, (local.theta_dk, phi_wk, phi_k), (w_b, c_b, mu_b, tt_b, ta_b),
        unroll=max(1, min(cfg.sweep_unroll, B)),
    )

    def unblk(x):
        return x.transpose((1, 0, 2) + tuple(range(3, x.ndim))).reshape(
            (D, Lp) + x.shape[3:]
        )[:, :L]

    mu_out = unblk(mu_out_b)
    abs_delta = unblk(absdelta_b)

    # ---- residual refresh (replace touched, keep the rest) — §3.1 ----
    r_new, touched = sched_lib.scatter_residuals(
        abs_delta, batch.word_ids, token_topics, Wrows, K
    )
    scheduler = sched_lib.update_residuals(scheduler, r_new, touched)
    loglik = None
    if compute_loglik:
        loglik = em.map_log_likelihood(
            batch, theta, phi, ptot, cfg, vocab_size=W
        )
    return LocalState(mu=mu_out, theta_dk=theta), phi, ptot, scheduler, loglik


# ---------------------------------------------------------------------------
# Per-minibatch FOEM inner loop
# ---------------------------------------------------------------------------

def foem_minibatch(
    key: jax.Array,
    batch: MinibatchData,
    phi_wk_in: jax.Array,       # (Wv, K) global stats view (minibatch NOT folded)
    phi_k_in: jax.Array,        # (K,)    global topic totals
    cfg: LDAConfig,
    *,
    vocab_size: Optional[int] = None,
    num_words: Optional[jax.Array] = None,
) -> FOEMMinibatchResult:
    """Run FOEM's inner loop on one minibatch (paper Fig. 4 lines 2-18).

    1. init μ, θ̂; fold the minibatch's initial contribution into the working φ̂
    2. one full blocked-IEM sweep (initialises residuals)
    3. scheduled sparse sweeps until the training-perplexity delta < tol
       (checked every ``ppl_check_every`` sweeps) or ``max_sweeps``.

    Every sweep — warm-up, dense and scheduled — routes through the unified
    ``kernels.ops.sweep`` dispatch when column-serial (``sweep_impl ==
    "fused"``); on check iterations that sweep also emits the stop rule's
    log-likelihood (in-kernel per-column partials on the kernel path), so
    the while-loop needs no standalone (D, L, K) perplexity pass.  Coarse
    block counts or ``sweep_impl == "scan"`` keep the legacy blocked scans
    and the separate ``em.training_perplexity`` check.

    ``num_words`` is the real W_s when ``phi_wk_in`` is padded with zero
    rows to a jit-shape bucket (``FOEMTrainer``); it keeps the λ_w word
    ranking over the real words only.
    """
    D, L = batch.word_ids.shape
    K = cfg.K
    W = vocab_size if vocab_size is not None else cfg.W

    mu0 = uniform_responsibilities(key, (D, L, K), cfg.dtype)
    theta0 = em.fold_theta(mu0, batch.counts)
    d_wk, d_k = em.fold_phi(mu0, batch.counts, batch.word_ids, phi_wk_in.shape[0])
    phi = phi_wk_in + d_wk      # working copy: global + this minibatch (line 3)
    ptot = phi_k_in + d_k
    local = LocalState(mu=mu0, theta_dk=theta0)

    ntok = jnp.maximum(batch.counts.sum(), 1.0)
    use_fused = cfg.sweep_impl == "fused" and cfg.resolve_blocks(L) == L
    use_sched = cfg.active_topics > 0

    # ---- warm-up full sweeps (paper Fig. 4's unscheduled first iteration);
    # the last sweep initialises the residual matrices ----
    warm = max(1, cfg.warmup_sweeps)
    if use_fused:
        # fused Gauss-Seidel sweep: residuals come out of the sweep itself
        # (init costs one scatter, no re-measurement) and the last warm-up
        # sweep also emits the stop rule's baseline log-likelihood
        r = None
        for i in range(warm):
            r = em.gs_sweep_with_residuals(
                batch, local, phi, ptot, cfg, vocab_size=W,
                compute_loglik=(i == warm - 1),
            )
            local = LocalState(mu=r.mu, theta_dk=r.theta)
            phi, ptot = r.phi_wk, r.phi_k
        scheduler = sched_lib.residuals_from_sweep(
            r.residual, batch.word_ids, phi.shape[0]
        )
        ppl0 = jnp.exp(-r.loglik / ntok)
    else:
        for _ in range(warm):
            prev_mu = local.mu
            local, dd_wk, dd_k = em.blocked_iem_sweep(
                batch, local, phi, ptot, cfg, vocab_size=W
            )
            phi = phi + dd_wk
            ptot = ptot + dd_k
        scheduler = sched_lib.full_sweep_residuals(
            local.mu, prev_mu, batch.counts, batch.word_ids, phi.shape[0]
        )
        ppl0 = em.training_perplexity(
            batch, local.theta_dk, phi, ptot, cfg, vocab_size=W
        )

    def sweep_once(local, phi, ptot, scheduler, compute_loglik):
        """One inner sweep via the unified dispatch: (..., loglik-or-None)."""
        if use_sched:
            return scheduled_iem_sweep(
                batch, local, phi, ptot, scheduler, cfg, vocab_size=W,
                compute_loglik=compute_loglik, num_words=num_words,
            )
        if use_fused:
            # working-copy form: skip the delta round trip entirely
            r = em.gs_sweep_with_residuals(
                batch, local, phi, ptot, cfg, vocab_size=W,
                compute_loglik=compute_loglik,
            )
            return (
                LocalState(mu=r.mu, theta_dk=r.theta), r.phi_wk, r.phi_k,
                scheduler, r.loglik,
            )
        new_local, dwk, dk = em.blocked_iem_sweep(
            batch, local, phi, ptot, cfg, vocab_size=W
        )
        return new_local, phi + dwk, ptot + dk, scheduler, None

    # The fused dispatch provides the stop-rule loglik from inside the
    # sweep; only the legacy scan paths still pay a standalone pass.
    in_sweep_ppl = use_fused

    def cond(state):
        t, done, *_ = state
        return (t < cfg.max_sweeps) & jnp.logical_not(done)

    def step(state):
        t, done, local, phi, ptot, scheduler, last_ppl = state
        check = (t + 1) % cfg.ppl_check_every == 0
        if in_sweep_ppl:
            def checked(local, phi, ptot, scheduler):
                local, phi, ptot, scheduler, ll = sweep_once(
                    local, phi, ptot, scheduler, True
                )
                return local, phi, ptot, scheduler, jnp.exp(-ll / ntok)

            def unchecked(local, phi, ptot, scheduler):
                local, phi, ptot, scheduler, _ = sweep_once(
                    local, phi, ptot, scheduler, False
                )
                return local, phi, ptot, scheduler, last_ppl

            local, phi, ptot, scheduler, ppl = jax.lax.cond(
                check, checked, unchecked, local, phi, ptot, scheduler
            )
        else:
            local, phi, ptot, scheduler, _ = sweep_once(
                local, phi, ptot, scheduler, False
            )
            ppl = jax.lax.cond(
                check,
                lambda: em.training_perplexity(
                    batch, local.theta_dk, phi, ptot, cfg, vocab_size=W
                ),
                lambda: last_ppl,
            )
        done = check & (
            jnp.abs(last_ppl - ppl) < cfg.ppl_rel_tol * jnp.abs(ppl)
        )
        return (t + 1, done, local, phi, ptot, scheduler, ppl)

    state = (jnp.int32(warm), jnp.bool_(False), local, phi, ptot, scheduler,
             ppl0)
    t, done, local, phi, ptot, scheduler, ppl = jax.lax.while_loop(
        cond, step, state
    )
    diag = FOEMDiagnostics(
        sweeps_run=t, final_train_ppl=ppl, residual_mass=scheduler.r_w.sum()
    )
    return FOEMMinibatchResult(local, phi, ptot, scheduler, diag)


# ---------------------------------------------------------------------------
# Stream-level merge (eq. 33 accumulate / eq. 20 stepwise)
# ---------------------------------------------------------------------------

def merge_minibatch(
    stats: GlobalStats,
    result_phi_wk: jax.Array,
    result_phi_k: jax.Array,
    minibatch_phi_wk: jax.Array,  # Σ_d x μ of this minibatch alone
    minibatch_phi_k: jax.Array,
    cfg: LDAConfig,
    stream_scale: float = 1.0,    # S = D/D_s for stepwise mode
) -> GlobalStats:
    """Fold a finished minibatch into the stream-lifetime statistics."""
    s = stats.step + 1
    if cfg.rho_mode == "accumulate":
        # eq. 33 with ρ_s = 1/s: plain accumulation of sufficient statistics.
        return GlobalStats(
            phi_wk=result_phi_wk, phi_k=result_phi_k, step=s
        )
    rho = (cfg.tau0 + s.astype(jnp.float32)) ** (-cfg.kappa)      # eq. 18
    phi_wk = (1.0 - rho) * stats.phi_wk + rho * stream_scale * minibatch_phi_wk
    phi_k = (1.0 - rho) * stats.phi_k + rho * stream_scale * minibatch_phi_k
    return GlobalStats(phi_wk=phi_wk, phi_k=phi_k, step=s)


@functools.partial(jax.jit, static_argnames=("cfg", "stream_scale"))
def foem_step(
    key: jax.Array,
    batch: MinibatchData,
    stats: GlobalStats,
    cfg: LDAConfig,
    stream_scale: float = 1.0,
) -> Tuple[GlobalStats, LocalState, FOEMDiagnostics]:
    """Whole-vocabulary FOEM step (φ̂ device-resident): the pjit training step."""
    res = foem_minibatch(key, batch, stats.phi_wk, stats.phi_k, cfg)
    mb_wk = res.phi_wk - stats.phi_wk
    mb_k = res.phi_k - stats.phi_k
    new_stats = merge_minibatch(
        stats, res.phi_wk, res.phi_k, mb_wk, mb_k, cfg, stream_scale
    )
    return new_stats, res.local, res.diag
