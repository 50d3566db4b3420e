"""Jit'd dispatch layer over the Pallas kernels.

On TPU backends the Pallas kernels are compiled natively; elsewhere the
caller chooses between ``interpret=True`` (kernel-body semantics, used by the
correctness tests) and the pure-jnp reference (fast on CPU, used by the
models and the dry-run, whose lowering must stay backend-portable).

The column-serial Gauss-Seidel sweeps — dense (full-K IEM) and scheduled
(active-set, §3.1) — share ONE entry point, ``sweep(...) -> SweepResult``:
the single-launch Pallas kernels (``gs_sweep_pallas`` /
``scheduled_sweep_pallas``) on TPU when the carried working set fits VMEM,
and the delta-compacted portable scans elsewhere.  Every caller
(``em.blocked_iem_sweep``, ``foem`` warm-up and scheduled sweeps,
``foem_sharded``'s shard-local sweeps, the streaming trainer through
``foem_minibatch``) routes through it.

Test-time (frozen φ̂) inference has its own entry point,
``infer(...) -> InferResult``: the §2.4 θ-only fixed point as chunked
single-launch ``theta_sweep_pallas`` calls (dense or active-set
scheduled), convergence-stopped on the estimation-split perplexity, with
the eq. 21 held-out log-predictive partials emitted in-kernel.  Every
serving/evaluation consumer (``perplexity.fit_theta_fixed_phi`` /
``predictive_perplexity``, ``launch.serve.TopicServer``,
``foem_sharded.heldout_perplexity_sharded``) routes through it.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import threading
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.analysis.budget import SUBLANE
from repro.analysis.validate import validate_infer_args, validate_sweep_args
from repro.core.types import InferPlan, InferResult, SweepPlan, SweepResult
from repro.kernels import ref
from repro.kernels.foem_estep import fused_estep_pallas
from repro.kernels.gs_sweep import fits_vmem, gs_sweep_pallas
from repro.kernels.scheduled_sweep import sched_fits_vmem, scheduled_sweep_pallas
from repro.kernels.sharded_sweep import (
    sharded_fits_vmem,
    sharded_fold_pallas,
    sharded_probe_pallas,
)
from repro.kernels.theta_sweep import (
    PHI_SUBLANE,
    dequantize_phi,
    quantize_phi,
    theta_fits_vmem,
    theta_sweep_pallas,
)
from repro.kernels.topk_estep import topk_estep_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class Dispatch:
    """One trace-time dispatch decision of ``sweep`` or ``infer``.

    ``path`` is what ran: ``"pallas"`` (compiled kernel), ``"interpret"``
    (kernel body on the CPU) or ``"portable"`` (the jnp mirror);
    ``reason`` says why — ``"auto"`` when the kernel was eligible,
    ``"VMEM"`` / ``"sublane"`` / ``"no TPU"`` / ``"psum hooks"`` when the
    auto path fell back, ``"forced"`` or ``"plan"`` when the caller chose.
    ``shape`` is ``(D, L, K, W_s)``.  Decisions are made while tracing, so
    a jit cache hit records nothing.
    """

    seq: int
    entry: str
    path: str
    reason: str
    shape: Tuple[int, int, int, int]

    def __str__(self) -> str:
        return f"{self.entry} {self.path}: {self.reason}"


_DISPATCH_LOG: collections.deque = collections.deque(maxlen=1024)
_DISPATCH_SEQ = itertools.count()
_DISPATCH_LOCK = threading.Lock()


def _record_dispatch(entry, path, reason, shape) -> None:
    with _DISPATCH_LOCK:
        _DISPATCH_LOG.append(
            Dispatch(next(_DISPATCH_SEQ), entry, path, reason,
                     tuple(int(x) for x in shape))
        )


def dispatch_log(since: int = -1) -> List[Dispatch]:
    """The recorded dispatch decisions with ``seq > since`` (the newest
    1024), oldest first — how a caller sees whether a kernel or the
    portable path ran, and why."""
    with _DISPATCH_LOCK:
        return [d for d in _DISPATCH_LOG if d.seq > since]


def _auto_path(*, fits: bool, rows: int, sublane: int,
               hooked: bool = False) -> Tuple[bool, str]:
    """The auto rule shared by ``sweep`` and ``infer``: (use kernel, reason)."""
    if hooked:
        return False, "psum hooks"
    if not on_tpu():
        return False, "no TPU"
    if not fits:
        return False, "VMEM"
    if rows % sublane:
        return False, "sublane"
    return True, "auto"


# ---------------------------------------------------------------------------

def fused_estep(
    theta_rows: jax.Array,
    phi_rows: jax.Array,
    phi_tot: jax.Array,
    exclude: Optional[jax.Array],
    mu_old: jax.Array,
    counts: jax.Array,
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: float,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Fused FOEM E-step: (mu_new, residual)."""
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if use_pallas or interpret:
        return fused_estep_pallas(
            theta_rows, phi_rows, phi_tot, exclude, mu_old, counts,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
            use_exclude=exclude is not None, interpret=interpret,
        )
    return ref.fused_estep_ref(
        theta_rows, phi_rows, phi_tot, exclude, mu_old, counts,
        alpha_m1, beta_m1, wb,
    )


def topk_estep(
    theta_a, phi_a, ptot_a, mu_prev_a, counts, active,
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: float,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Scheduled sparse E-step on active topics: (mu_new_a, delta)."""
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if use_pallas or interpret:
        return topk_estep_pallas(
            theta_a, phi_a, ptot_a, mu_prev_a, counts, active,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb, interpret=interpret,
        )
    return ref.topk_estep_ref(
        theta_a, phi_a, ptot_a, mu_prev_a, counts, active,
        alpha_m1, beta_m1, wb,
    )


# ---------------------------------------------------------------------------
# Column-serial Gauss-Seidel sweeps — unified dispatch
# ---------------------------------------------------------------------------

def _map_loglik(
    word_ids, counts, theta, phi_wk, phi_k, *, alpha_m1, beta_m1, wb,
):
    """Eq. 3 data log-likelihood of the given stats (mirrors
    ``em.map_log_likelihood`` without the config plumbing — the portable
    sweeps' post-hoc stop-rule value; the kernels emit the same quantity
    from per-column partials)."""
    K = theta.shape[-1]
    th_den = theta.sum(-1, keepdims=True) + K * alpha_m1
    theta_n = (theta + alpha_m1) / jnp.maximum(th_den, 1e-30)
    phi_n = (phi_wk + beta_m1) / jnp.maximum(phi_k + wb, 1e-30)[None, :]
    rows = jnp.take(phi_n, word_ids, axis=0)               # (D, L, K)
    lik = jnp.maximum(jnp.einsum("dlk,dk->dl", rows, theta_n), 1e-30)
    return (counts * jnp.log(lik)).sum()


def _gs_sweep_portable(
    word_ids: jax.Array,       # (D, L) int32
    counts: jax.Array,         # (D, L)
    mu: jax.Array,             # (D, L, K)
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: float,
    unroll: int = 8,
    use_pallas: bool = False,
    interpret: bool = False,
    norm_psum: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """Delta-compacted column-serial Gauss-Seidel sweep — portable jnp path.

    The legacy formulation folded each column with a full-(W_s, K)
    ``segment_sum``; here the fold touches only the D gathered rows
    (``.at[wid].add``), columns are chunked into unrolled scan tiles, and
    the E-step arithmetic routes through ``fused_estep`` (the Pallas
    kernel's jnp oracle on CPU, the kernel itself on TPU).

    ``norm_psum`` hooks the E-step normaliser (shard_map over a topic-
    sharded φ̂: the denominator is a psum over the model axis — see
    ``foem_sharded``); when set the arithmetic is inlined, since a
    collective cannot cross a kernel boundary.
    """
    L = word_ids.shape[1]

    def col(carry, xs):
        theta, phi, ptot = carry
        wid, cnt, mu_old = xs                       # (D,) (D,) (D, K)
        ex = cnt[:, None] * mu_old
        rows = jnp.take(phi, wid, axis=0)           # gather D rows only
        if norm_psum is None:
            mu_new, res = fused_estep(
                theta, rows, ptot, ex, mu_old, cnt,
                alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
                use_pallas=use_pallas, interpret=interpret,
            )
        else:
            th = jnp.maximum(theta - ex, 0.0)
            ph = jnp.maximum(rows - ex, 0.0)
            pt = ptot[None, :] - ex
            num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb)
            denom = norm_psum(num.sum(-1, keepdims=True))
            mu_new = num / jnp.maximum(denom, 1e-30)
            res = cnt[:, None] * jnp.abs(mu_new - mu_old)
        delta = cnt[:, None] * mu_new - ex
        carry = (
            theta + delta,
            phi.at[wid].add(delta),                 # scatter D rows only
            ptot + delta.sum(0),
        )
        return carry, (mu_new, res)

    (theta, phi, ptot), (mu_cols, res_cols) = jax.lax.scan(
        col,
        (theta, phi_wk, phi_k),
        (word_ids.T, counts.T, mu.transpose(1, 0, 2)),
        unroll=max(1, min(unroll, L)),
    )
    return (
        mu_cols.transpose(1, 0, 2), res_cols.transpose(1, 0, 2),
        theta, phi, ptot,
    )


def _sched_sweep_portable(
    word_ids: jax.Array,       # (D, L) int32
    counts: jax.Array,         # (D, L)
    mu: jax.Array,             # (D, L, K)
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    word_topics: jax.Array,    # (W_s, A) int32
    token_active: jax.Array,   # (D, L) bool
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: float,
    unroll: int = 8,
    renorm_psum: Optional[Callable[[jax.Array], jax.Array]] = None,
):
    """Delta-compacted scheduled sweep — the portable oracle mirroring
    ``_gs_sweep_portable`` (and the kernel's arithmetic exactly).

    The active set is expanded ONCE per sweep into a (W_s, K) *word* lane
    mask (active sets are per word, so one W_s·A-update scatter covers
    every token); each column gathers its D mask rows next to its D φ̂
    rows, runs the masked full-K E-step — eq. 13 with exclusion confined
    to the active lanes, eq. 38 renorm to the active set's previous mass,
    λ_w folded into the mask — and folds with *dense* adds plus a single
    D-row φ̂ scatter.  This deliberately trades O(D·K) elementwise work
    for the scan formulation's three 2-D scatters per column: on CPU an
    XLA scatter costs ~65 ns *per scalar update* regardless of operand
    size, so the per-column D·A-update scatters dominated the sweep;
    masked-dense arithmetic is vector work.

    ``renorm_psum`` hooks the eq. 38 mass/denominator reductions for the
    topic-sharded shard_map path (union active set across shards).
    """
    D, L = word_ids.shape
    word_masks = jnp.put_along_axis(
        jnp.zeros_like(phi_wk), word_topics, 1.0, axis=-1, inplace=False
    )                                                       # (W_s, K)

    def col(carry, xs):
        theta, phi, ptot = carry
        wid, cnt, mu_old, act = xs          # (D,) (D,) (D,K) (D,)
        mask = jnp.take(word_masks, wid, axis=0) * act[:, None]
        ex = cnt[:, None] * mu_old * mask
        rows = jnp.take(phi, wid, axis=0)           # gather D rows only
        th = jnp.maximum(theta - ex, 0.0)
        ph = jnp.maximum(rows - ex, 0.0)
        pt = ptot[None, :] - ex
        num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb) * mask
        prev_mass = (mu_old * mask).sum(-1, keepdims=True)
        new_sum = num.sum(-1, keepdims=True)
        if renorm_psum is not None:
            # eq. 38 over the UNION active set (topic-sharded shard_map)
            prev_mass = renorm_psum(prev_mass)
            new_sum = renorm_psum(new_sum)
        mu_new = mask * (num / jnp.maximum(new_sum, 1e-30) * prev_mass) + (
            1.0 - mask
        ) * mu_old
        delta = cnt[:, None] * (mu_new - mu_old)    # zero off the active set
        carry = (
            theta + delta,
            phi.at[wid].add(delta),                 # scatter D rows only
            ptot + delta.sum(0),
        )
        return carry, (mu_new, jnp.abs(delta))

    (theta, phi, ptot), (mu_cols, res_cols) = jax.lax.scan(
        col,
        (theta, phi_wk, phi_k),
        (word_ids.T, counts.T, mu.transpose(1, 0, 2),
         token_active.T.astype(mu.dtype)),
        unroll=max(1, min(unroll, L)),
    )
    return (
        mu_cols.transpose(1, 0, 2), res_cols.transpose(1, 0, 2),
        theta, phi, ptot,
    )


# ---------------------------------------------------------------------------
# Two-phase sharded sweep (probe → reduce → fold → correct)
# ---------------------------------------------------------------------------

def _word_lane_masks(phi_wk, word_topics):
    """(W_s, A) active-topic ids → (W_s, K) {0,1} lane masks (one build per
    sweep; per-token masks are row gathers of this)."""
    return jnp.put_along_axis(
        jnp.zeros_like(phi_wk), word_topics, 1.0, axis=-1, inplace=False
    )


def _probe_portable(
    word_ids, counts, mu, theta, phi_wk, phi_k, word_masks, token_active,
    *, alpha_m1, beta_m1, wb,
):
    """Phase A, pure-jnp: partial normalisers against the sweep-start stats.

    Jacobi — no fold, so the whole (D, L) batch vectorizes in one pass.
    Mirrors ``sharded_sweep._make_probe_kernel`` term for term.
    """
    rows = jnp.take(phi_wk, word_ids, axis=0)              # (D, L, K)
    if word_masks is not None:
        mask = jnp.take(word_masks, word_ids, axis=0) * (
            token_active.astype(mu.dtype)[..., None]
        )
        ex = counts[..., None] * mu * mask
    else:
        mask = None
        ex = counts[..., None] * mu
    th = jnp.maximum(theta[:, None, :] - ex, 0.0)
    ph = jnp.maximum(rows - ex, 0.0)
    pt = phi_k[None, None, :] - ex
    num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb)
    if mask is not None:
        num = num * mask
        return num.sum(-1), (mu * mask).sum(-1)
    return num.sum(-1), None


def _fold_portable(
    word_ids, counts, mu, theta, phi_wk, phi_k, remainder, prev_mass,
    word_masks, token_active, *, alpha_m1, beta_m1, wb, unroll,
):
    """Phase C, pure-jnp: the column-serial GS fold consuming the reduced
    normalisers — the delta-compacted scan with the shard's own numerator
    sum live and the cross-shard remainder injected per column.  Mirrors
    ``sharded_sweep._make_fold_kernel`` term for term.
    """
    scheduled = word_masks is not None
    L = word_ids.shape[1]

    def col(carry, xs):
        theta, phi, ptot = carry
        if scheduled:
            wid, cnt, mu_old, rem, pm, act = xs
            mask = jnp.take(word_masks, wid, axis=0) * act[:, None]
            ex = cnt[:, None] * mu_old * mask
        else:
            wid, cnt, mu_old, rem = xs
            ex = cnt[:, None] * mu_old
        rows = jnp.take(phi, wid, axis=0)           # gather D rows only
        th = jnp.maximum(theta - ex, 0.0)
        ph = jnp.maximum(rows - ex, 0.0)
        pt = ptot[None, :] - ex
        num = (th + alpha_m1) * (ph + beta_m1) / (pt + wb)
        if scheduled:
            num = num * mask
        denom = jnp.maximum(
            rem[:, None] + num.sum(-1, keepdims=True), 1e-30
        )
        if scheduled:
            mu_new = mask * (num / denom * pm[:, None]) + (1.0 - mask) * mu_old
            delta = cnt[:, None] * (mu_new - mu_old)
            res = jnp.abs(delta)
            live = (mu_new * mask).sum(-1)
        else:
            mu_new = num / denom
            delta = cnt[:, None] * mu_new - ex
            res = cnt[:, None] * jnp.abs(mu_new - mu_old)
            live = mu_new.sum(-1)
        carry = (
            theta + delta,
            phi.at[wid].add(delta),                 # scatter D rows only
            ptot + delta.sum(0),
        )
        return carry, (mu_new, res, live)

    xs = [word_ids.T, counts.T, mu.transpose(1, 0, 2), remainder.T]
    if scheduled:
        xs += [prev_mass.T, token_active.T.astype(mu.dtype)]
    (theta, phi, ptot), (mu_cols, res_cols, live_cols) = jax.lax.scan(
        col, (theta, phi_wk, phi_k), tuple(xs),
        unroll=max(1, min(unroll, L)),
    )
    return (
        mu_cols.transpose(1, 0, 2), res_cols.transpose(1, 0, 2),
        theta, phi, ptot, live_cols.T,
    )


def _loglik_partials(word_ids, theta, phi_wk, phi_k, *, alpha_m1, beta_m1,
                     wb):
    """Per-token PRE-LOG eq. 3 partials over the shard's topic lanes:
    u = Σ_k (θ̂+α)(φ̂_w+β)/(φ̂(k)+wb) — (D, L).  After a model-axis psum
    and division by the global θ̂ normaliser this is the token likelihood
    (``_map_loglik`` factorises exactly this way)."""
    rows = jnp.take(phi_wk, word_ids, axis=0)              # (D, L, K)
    ph_n = (rows + beta_m1) / jnp.maximum(phi_k + wb, 1e-30)[None, None, :]
    return ((theta[:, None, :] + alpha_m1) * ph_n).sum(-1)


def _assemble_sharded_loglik(counts, u_glob, th_den):
    """Finish the stop-rule value from psum'd pieces: log AFTER the
    cross-shard reduction, counts-weighted sum over the shard's tokens."""
    lik = jnp.maximum(u_glob / th_den[:, None], 1e-30)
    return (counts * jnp.log(lik)).sum()


def _sweep_two_phase(
    word_ids, counts, mu, theta, phi_wk, phi_k, word_topics, token_active,
    *, alpha_m1, beta_m1, wb, axis_name, compute_loglik, how, unroll,
) -> SweepResult:
    """The two-phase sharded sweep engine (see ``kernels/sharded_sweep.py``).

      A. shard-local probe launch → partial normalisers (D, L) per shard
      B. ONE ``lax.psum`` of the stacked partials over ``axis_name``
      C. shard-local Gauss-Seidel fold launch consuming the reduced
         normalisers (own contribution live, peers' one-phase stale),
         θ̂/φ̂/φ̂(k) VMEM-carried across the column grid
      D. one more (D, L) psum of the live masses + a vectorized exact
         renormalisation folded into the stats — global normalisation and
         total-mass conservation hold to fp round-off

    ``how`` ∈ {"pallas", "interpret", "portable"} picks compiled kernels,
    interpret-mode kernel bodies (CPU tests) or the pure-jnp mirror; all
    three share this orchestration, so kernel-vs-portable parity is a
    same-collective comparison.
    """
    scheduled = word_topics is not None
    kernels = how in ("pallas", "interpret")
    interpret = how == "interpret"
    K = mu.shape[-1]
    D, L = word_ids.shape
    psum = functools.partial(lax.psum, axis_name=axis_name)
    word_masks = _word_lane_masks(phi_wk, word_topics) if scheduled else None

    # ---- phase A: probe (Jacobi, sweep-start stats) ----
    if kernels:
        s, pm = sharded_probe_pallas(
            word_ids, counts, mu, theta, phi_wk, phi_k,
            word_topics, token_active,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb, interpret=interpret,
        )
    else:
        s, pm = _probe_portable(
            word_ids, counts, mu, theta, phi_wk, phi_k, word_masks,
            token_active, alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
        )

    # ---- phase B: one fused reduction of the K-normaliser partials ----
    if scheduled:
        s_glob, pm_glob = psum((s, pm))
    else:
        s_glob, pm_glob = psum(s), None
    remainder = s_glob - s          # peers' share; own share stays live

    # ---- phase C: shard-local Gauss-Seidel fold ----
    if kernels:
        mu_new, res, theta_o, phi_o, ptot_o, live, u = sharded_fold_pallas(
            word_ids, counts, mu, theta, phi_wk, phi_k, remainder, pm_glob,
            word_topics, token_active,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
            emit_loglik=compute_loglik, interpret=interpret,
        )
    else:
        mu_new, res, theta_o, phi_o, ptot_o, live = _fold_portable(
            word_ids, counts, mu, theta, phi_wk, phi_k, remainder, pm_glob,
            word_masks, token_active,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb, unroll=unroll,
        )
        u = None
        if compute_loglik:
            u = _loglik_partials(
                word_ids, theta_o, phi_o, ptot_o,
                alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
            )

    # ---- phase D: exact renorm + stop-rule assembly (one psum) ----
    if compute_loglik:
        th_den = theta_o.sum(-1) + K * alpha_m1    # psum → global Σθ̂ + Kα
        live_glob, u_glob, th_den = psum((live, u, th_den))
        ll = _assemble_sharded_loglik(counts, u_glob, th_den)
    else:
        live_glob = psum(live)
        ll = None

    if scheduled:
        # rescale the active-lane mass to eq. 38's exact global target
        scale = pm_glob / jnp.maximum(live_glob, 1e-30)    # (D, L)
        mask = jnp.take(word_masks, word_ids, axis=0) * (
            token_active.astype(mu.dtype)[..., None]
        )
        mu_corr = mu_new + mask * mu_new * (scale[..., None] - 1.0)
    else:
        scale = 1.0 / jnp.maximum(live_glob, 1e-30)
        mu_corr = mu_new * scale[..., None]
    delta = counts[..., None] * (mu_corr - mu_new)
    theta_o = theta_o + delta.sum(1)
    d_flat = delta.reshape(D * L, K)
    phi_o = phi_o + jax.ops.segment_sum(
        d_flat, word_ids.reshape(D * L), num_segments=phi_wk.shape[0]
    )
    ptot_o = ptot_o + d_flat.sum(0)
    return SweepResult(mu_corr, theta_o, phi_o, ptot_o, res, ll)


def sweep(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_wk
    counts: jax.Array,         # (D, L)
    mu: jax.Array,             # (D, L, K)
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: jax.Array | float,
    word_topics: Optional[jax.Array] = None,   # (W_s, A): scheduled sweep
    token_active: Optional[jax.Array] = None,  # (D, L) λ_w mask (scheduled)
    compute_loglik: bool = False,
    unroll: int = 8,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    norm_psum: Optional[Callable] = None,      # dense E-step normaliser hook
    renorm_psum: Optional[Callable] = None,    # eq. 38 mass hook (scheduled)
    plan: Optional[SweepPlan] = None,          # execution plan (mesh axis etc.)
    debug_checks: bool = False,                # numerical-invariant sanitizer
) -> SweepResult:
    """One column-serial Gauss-Seidel sweep — THE sweep entry point.

    Every sweep in the library (``em.blocked_iem_sweep``, ``foem`` warm-up
    and scheduled sweeps, ``foem_sharded``'s shard-local sweeps, the
    streaming trainer through ``foem_minibatch``) routes through this
    function; it owns kernel dispatch AND — under a sharded plan — the
    cross-shard collectives, so algorithm code never touches either.

    * ``word_topics is None`` → dense full-K IEM sweep (paper Fig. 2 at
      B = L); otherwise the §3.1 scheduled sparse sweep on the per-word
      active sets with eq. 38 renormalisation and the ``token_active``
      λ_w word mask (default ``counts > 0``).
    * ``compute_loglik`` additionally returns the post-sweep eq. 3 data
      log-likelihood (the training-perplexity stop rule): emitted from
      in-kernel per-column partials on the kernel paths, one jnp pass on
      the portable paths.  Under a sharded plan the emitted partials are
      pre-log per-token values and ``sweep`` finishes them with one psum
      (log strictly after the cross-shard reduction).
    * ``plan`` (``core.types.SweepPlan``) selects the execution plan.
      With ``plan.axis_name`` set the call must be inside ``shard_map``
      with the topic axis sharded over that mesh axis; ``sweep`` then runs
      the two-phase engine (probe launch → one psum of the (D, L)
      normaliser partials → shard-local VMEM-carried fold launch → exact
      renorm psum; ``kernels/sharded_sweep.py``) or, with
      ``plan.two_phase=False``, the legacy per-column psum hooks on the
      portable scan.  Without a plan (or ``axis_name=None``) the plan's
      ``impl`` maps onto ``use_pallas``/``interpret`` below.
    * Dispatch: the single-launch Pallas kernel on TPU whenever the
      carried (W_s + D, K) working set fits VMEM; otherwise the
      delta-compacted portable scan (whose dense E-step still routes
      through the fused kernel on TPU).  ``interpret=True`` forces the
      kernel body on CPU (tests); ``use_pallas=False`` forces the pure-jnp
      oracle.  Each choice is recorded with its reason in
      :func:`dispatch_log`.
    * ``norm_psum`` / ``renorm_psum`` are the raw reduction hooks the
      sharded plan's legacy mode is built on, kept public for tests and
      custom meshes: ``norm_psum`` reduces the dense E-step normaliser
      (eq. 11/13 denominator), ``renorm_psum`` the scheduled sweep's
      eq. 38 mass/denominator pair, each a callable mapping a shard-local
      ``(D, 1)`` column to its cross-shard sum.  Hooks imply the portable
      path — a collective cannot cross a Pallas kernel boundary — and are
      mutually exclusive with a sharded ``plan``.
    * Argument contracts (shapes, dtypes of the donated stats, plan axis,
      sublane layout of a forced compiled launch) are validated eagerly at
      this boundary — ``repro.analysis.validate`` raises ``ContractError``
      before any tracing.  ``debug_checks=True`` (``cfg.debug_checks``)
      additionally runs the ``repro.analysis.sanitizer`` numerical
      invariants on the result via ``checkify`` — eager calls raise
      immediately, jitted callers wrap with ``checkify.checkify``.
    """
    # Seeded fault injection (runtime/faults.py): eager calls consult the
    # process-wide plan at the pre-probe boundary.  Skipped under tracing —
    # a fault must never be staged into a jit cache — and free (one None
    # check) when no plan is active.  Lazy import: faults lives above the
    # kernel layer.
    if not isinstance(word_ids, jax.core.Tracer):
        from repro.runtime import faults as _faults

        _faults.fire_active(_faults.PRE_PROBE)
    forced_pallas = use_pallas is True or (
        plan is not None and plan.axis_name is None and plan.impl == "pallas"
    )
    validate_sweep_args(
        word_ids, counts, mu, theta, phi_wk, phi_k,
        word_topics=word_topics, token_active=token_active, plan=plan,
        use_pallas=True if forced_pallas else use_pallas,
        interpret=interpret,
    )
    scheduled = word_topics is not None
    if scheduled and token_active is None:
        token_active = counts > 0
    result = _sweep_impl(
        word_ids, counts, mu, theta, phi_wk, phi_k,
        alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
        word_topics=word_topics, token_active=token_active,
        compute_loglik=compute_loglik, unroll=unroll,
        use_pallas=use_pallas, interpret=interpret,
        norm_psum=norm_psum, renorm_psum=renorm_psum, plan=plan,
    )
    if debug_checks:
        from repro.analysis import sanitizer

        sanitizer.sweep_invariants(
            result, counts=counts, mu_before=mu,
            phi_wk_before=phi_wk, phi_k_before=phi_k,
            word_topics=word_topics, token_active=token_active,
            word_ids=word_ids,
            axis_name=plan.axis_name if plan is not None else None,
        )
    return result


def _sweep_impl(
    word_ids, counts, mu, theta, phi_wk, phi_k,
    *,
    alpha_m1, beta_m1, wb,
    word_topics=None, token_active=None,
    compute_loglik=False, unroll=8,
    use_pallas=None, interpret=False,
    norm_psum=None, renorm_psum=None, plan=None,
) -> SweepResult:
    D, L = word_ids.shape
    K = mu.shape[-1]
    scheduled = word_topics is not None
    if scheduled and token_active is None:
        token_active = counts > 0

    if plan is not None and plan.axis_name is not None:
        if norm_psum is not None or renorm_psum is not None:
            raise ValueError(
                "pass EITHER a sharded SweepPlan OR raw psum hooks, not both"
            )
        how, reason = plan.impl, "plan"
        if how == "auto":
            # hooks mode is portable-only, so auto resolves to a kernel
            # path only for the two-phase engine
            kernel, reason = _auto_path(
                fits=sharded_fits_vmem(phi_wk.shape[0], D, K, scheduled),
                rows=phi_wk.shape[0], sublane=SUBLANE,
                hooked=not plan.two_phase,
            )
            how = "pallas" if kernel else "portable"
        if plan.two_phase:
            _record_dispatch("sweep", how, f"two-phase {reason}",
                             (D, L, K, phi_wk.shape[0]))
            return _sweep_two_phase(
                word_ids, counts, mu, theta, phi_wk, phi_k,
                word_topics, token_active,
                alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
                axis_name=plan.axis_name, compute_loglik=compute_loglik,
                how=how, unroll=unroll,
            )
        if how in ("pallas", "interpret"):
            raise ValueError(
                "two_phase=False (per-column psum hooks) requires the "
                "portable path; a collective cannot cross a kernel boundary"
            )
        hook = lambda x: lax.psum(x, plan.axis_name)
        r = _sweep_impl(
            word_ids, counts, mu, theta, phi_wk, phi_k,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
            word_topics=word_topics, token_active=token_active,
            unroll=unroll,
            norm_psum=None if scheduled else hook,
            renorm_psum=hook if scheduled else None,
        )
        if compute_loglik:
            u = _loglik_partials(
                word_ids, r.theta, r.phi_wk, r.phi_k,
                alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
            )
            u_glob, th_den = lax.psum(
                (u, r.theta.sum(-1) + K * alpha_m1), plan.axis_name
            )
            r = r._replace(
                loglik=_assemble_sharded_loglik(counts, u_glob, th_den)
            )
        return r
    reason = "forced"
    if plan is not None and plan.impl != "auto":
        reason = "plan"
        if plan.impl == "pallas":
            use_pallas = True
        elif plan.impl == "interpret":
            interpret = True
        elif plan.impl == "portable":
            use_pallas = False

    hooked = norm_psum is not None or renorm_psum is not None

    auto = use_pallas is None
    if use_pallas is False:
        interpret = False       # explicit False wins: pure-jnp oracle
    elif auto:
        # a ragged W_s violates the compiled kernels' sublane layout
        # (ContractError when forced); auto simply stays portable
        use_pallas, auto_reason = _auto_path(
            fits=(sched_fits_vmem if scheduled else fits_vmem)(
                phi_wk.shape[0], D, K
            ),
            rows=phi_wk.shape[0], sublane=SUBLANE, hooked=hooked,
        )
        if not interpret:
            reason = auto_reason
    _record_dispatch(
        "sweep",
        "interpret" if interpret else ("pallas" if use_pallas else "portable"),
        reason, (D, L, K, phi_wk.shape[0]),
    )
    if hooked and (use_pallas or interpret):
        # refuse rather than silently downgrade: a collective cannot cross
        # a kernel boundary, and a parity test passing a hook would
        # otherwise compare the oracle to itself
        raise ValueError(
            "norm_psum/renorm_psum require the portable path; drop the "
            "hook or the explicit use_pallas/interpret request"
        )

    if (use_pallas or interpret) and not hooked:
        lane_align = 128 if (use_pallas and not interpret) else 1
        if scheduled:
            mu_new, res, theta_o, phi_o, ptot_o, ll = scheduled_sweep_pallas(
                word_ids, counts, mu, theta, phi_wk, phi_k,
                word_topics, token_active,
                alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
                lane_align=lane_align, emit_loglik=compute_loglik,
                interpret=interpret,
            )
        else:
            mu_new, res, theta_o, phi_o, ptot_o, ll = gs_sweep_pallas(
                word_ids, counts, mu, theta, phi_wk, phi_k,
                alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
                lane_align=lane_align, emit_loglik=compute_loglik,
                interpret=interpret,
            )
        return SweepResult(mu_new, theta_o, phi_o, ptot_o, res, ll)

    if scheduled:
        mu_new, res, theta_o, phi_o, ptot_o = _sched_sweep_portable(
            word_ids, counts, mu, theta, phi_wk, phi_k,
            word_topics, token_active,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb, unroll=unroll,
            renorm_psum=renorm_psum,
        )
    else:
        # an explicit use_pallas=False means NO kernels at all (pure-jnp
        # oracle for tests); only the auto path lets the inner E-step use
        # the fused kernel
        mu_new, res, theta_o, phi_o, ptot_o = _gs_sweep_portable(
            word_ids, counts, mu, theta, phi_wk, phi_k,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb, unroll=unroll,
            use_pallas=on_tpu() if auto else False,
            norm_psum=norm_psum,
        )
    ll = None
    if compute_loglik:
        ll = _map_loglik(
            word_ids, counts, theta_o, phi_o, ptot_o,
            alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
        )
    return SweepResult(mu_new, theta_o, phi_o, ptot_o, res, ll)


# ---------------------------------------------------------------------------
# Frozen-φ inference (θ-only fixed point) — unified dispatch
# ---------------------------------------------------------------------------

def _infer_chunk_portable(
    word_ids, est_counts, ev_counts, theta, phi_norm, word_masks,
    *, alpha_m1, k_alpha, num_sweeps, axis_name=None,
):
    """``num_sweeps`` frozen-φ Jacobi sweeps + the eq. 21 phase — pure jnp.

    The portable mirror of ``theta_sweep_pallas`` (and, at ``rel_tol=0``,
    of the legacy ``fit_theta_fixed_phi`` 50-sweep scan): gather the φ rows
    once, scan the fixed point, measure both splits' per-token
    log-predictive partials against the final θ̂.  ``axis_name`` wraps the
    two per-token reductions (the μ normaliser and the eq. 21 likelihood)
    plus the θ̂ normaliser in ``lax.psum`` for the topic-sharded shard_map
    path — inference is Jacobi, so unlike training sweeps no two-phase
    launch restructuring is needed.
    """
    psum = (
        (lambda x: lax.psum(x, axis_name)) if axis_name else (lambda x: x)
    )
    rows = jnp.take(phi_norm, word_ids, axis=0)            # (D, L, K)
    if word_masks is not None:
        rows_fit = rows * jnp.take(word_masks, word_ids, axis=0)
    else:
        rows_fit = rows

    def normalize(theta):
        den = psum(theta.sum(-1, keepdims=True)) + k_alpha
        return (theta + alpha_m1) / jnp.maximum(den, 1e-30)

    def one(theta, _):
        num = normalize(theta)[:, None, :] * rows_fit      # (D, L, K)
        denom = psum(num.sum(-1, keepdims=True))
        mu = num / jnp.maximum(denom, 1e-30)
        return jnp.einsum("dlk,dl->dk", mu, est_counts), None

    theta, _ = lax.scan(one, theta, None, length=num_sweeps)
    lik = psum(jnp.einsum("dlk,dk->dl", rows, normalize(theta)))
    ll = jnp.log(jnp.maximum(lik, 1e-30))                  # full support
    return theta, est_counts * ll, ev_counts * ll


def infer(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_norm
    est_counts: jax.Array,     # (D, L) estimation (80%) split counts
    theta0: jax.Array,         # (D, K) initial θ̂ statistics
    phi_norm: jax.Array,       # (W_s, K) NORMALISED φ (eq. 10), frozen
    *,
    alpha_m1: float,
    ev_counts: Optional[jax.Array] = None,     # (D, L) evaluation (20%) split
    word_topics: Optional[jax.Array] = None,   # (W_s, A): scheduled fit
    max_sweeps: int = 50,
    check_every: int = 10,
    rel_tol: jax.Array | float = 0.0,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
    plan: Optional[SweepPlan | InferPlan] = None,  # execution plan
    debug_checks: bool = False,                # numerical-invariant sanitizer
) -> InferResult:
    """Frozen-φ inference for unseen documents — THE serving entry point.

    The test-time sibling of ``sweep``: every frozen-φ consumer
    (``perplexity.fit_theta_fixed_phi``, ``predictive_perplexity``,
    ``launch.serve.TopicServer``, ``foem_sharded.heldout_perplexity_sharded``)
    routes through this function, which owns kernel dispatch, the
    convergence stop and — under a sharded plan — the cross-shard
    collectives.  Paper §2.4: fit θ̂ on the estimation split by the
    fixed-point E-step with φ̂ frozen (eq. 11 without the φ M-step), then
    score the evaluation split with eq. 21.

    * The fixed point runs in ``check_every``-sweep chunks inside a
      ``lax.while_loop``; after each chunk the estimation-split perplexity
      ``exp(−est_loglik/ntokens)`` is compared to the previous chunk's and
      the loop stops when the relative change drops below ``rel_tol`` (the
      training stop rule of §2.4 applied at test time), or after
      ``max_sweeps`` total.  ``rel_tol=0`` never triggers, reproducing the
      legacy fixed-``max_sweeps`` behaviour exactly; ``max_sweeps`` must be
      a multiple of ``check_every``.
    * ``ev_counts`` is the 20% evaluation split of the same documents
      (identical ``word_ids`` layout — ``perplexity.split_heldout_counts``'
      binomial thinning preserves it); its eq. 21 per-token partials are
      measured inside the same chunk launch, so held-out perplexity costs
      no standalone (D, L, K) pass.  ``None`` scores nothing (serving).
    * ``word_topics`` restricts the *fit* to each word's (W_s, A) active
      topic set — the §3.1 machinery reused at serving time (see
      ``perplexity.serving_active_topics``); the eq. 21 evaluation always
      uses the full support.
    * Dispatch: the single-launch Pallas kernel per chunk on TPU whenever
      the (W_s + D, K) working set fits VMEM; the pure-jnp mirror
      elsewhere.  ``interpret=True`` forces the kernel body on CPU
      (tests); ``use_pallas=False`` forces the oracle.  Each choice is
      recorded with its reason in :func:`dispatch_log`.
    * ``plan`` (``core.types.SweepPlan``) with ``axis_name`` set runs the
      fixed point *inside* ``shard_map`` with the topic axis sharded over
      that mesh axis: the per-token normalisers, the θ̂ normaliser and the
      pre-log eq. 21 likelihood are psum'd over the axis (inference is
      Jacobi, so one reduction per sweep suffices — no two-phase
      restructuring).  Sharded plans imply the portable path (a collective
      cannot cross a Pallas kernel boundary); the returned ``theta`` is
      the shard's topic slice, the logliks are already globally reduced.
    * ``plan`` may also be an :class:`~repro.core.types.InferPlan`, whose
      ``phi_dtype`` selects the serving *storage* dtype of the frozen φ
      block: ``"bfloat16"``/``"int8"`` quantize once up front
      (``theta_sweep.quantize_phi`` — per-row scales for int8) and the
      kernel dequantizes each gathered row on read, shrinking the VMEM φ
      block 2×/4×.  The portable mirror dequantizes the same values, so
      kernel/portable parity is preserved under quantization; with the
      default ``"float32"`` the dispatch is bitwise-identical to a
      plan-less call.
    * Argument contracts are validated eagerly (``ContractError``);
      ``debug_checks=True`` runs the ``repro.analysis.sanitizer``
      invariants on the result (jitted callers wrap with
      ``checkify.checkify``).
    """
    phi_dtype = getattr(plan, "phi_dtype", "float32") if plan else "float32"
    forced_pallas = use_pallas is True or (
        plan is not None and plan.axis_name is None and plan.impl == "pallas"
    )
    validate_infer_args(
        word_ids, est_counts, theta0, phi_norm,
        ev_counts=ev_counts, word_topics=word_topics, plan=plan,
        use_pallas=True if forced_pallas else use_pallas,
        interpret=interpret, phi_dtype=phi_dtype,
    )
    D, L = word_ids.shape
    K = theta0.shape[-1]
    check_every = max(1, min(check_every, max_sweeps))
    if max_sweeps % check_every:
        raise ValueError(
            f"max_sweeps ({max_sweeps}) must be a multiple of "
            f"check_every ({check_every}) — the fixed point runs in "
            "check_every-sweep chunks"
        )
    n_chunks = max_sweeps // check_every
    ev = jnp.zeros_like(est_counts) if ev_counts is None else ev_counts

    axis_name = None
    reason = "forced"
    if plan is not None and plan.axis_name is not None:
        if plan.impl in ("pallas", "interpret"):
            raise ValueError(
                "a sharded infer plan requires the portable path; a "
                "collective cannot cross a Pallas kernel boundary"
            )
        axis_name = plan.axis_name
        k_alpha = (K * lax.psum(1, axis_name)) * alpha_m1   # global K·(α−1)
        use_pallas, interpret, reason = False, False, "sharded plan"
    else:
        if plan is not None and plan.impl != "auto":
            reason = "plan"
            if plan.impl == "pallas":
                use_pallas = True
            elif plan.impl == "interpret":
                interpret = True
            elif plan.impl == "portable":
                use_pallas = False
        k_alpha = K * alpha_m1
        if use_pallas is False:
            interpret = False           # explicit False wins: pure-jnp oracle
        elif use_pallas is None:
            use_pallas, auto_reason = _auto_path(
                fits=theta_fits_vmem(phi_norm.shape[0], D, K,
                                     phi_dtype=phi_dtype),
                rows=phi_norm.shape[0], sublane=PHI_SUBLANE[phi_dtype],
            )
            if not interpret:
                reason = auto_reason
    _record_dispatch(
        "infer",
        "interpret" if interpret else ("pallas" if use_pallas else "portable"),
        reason, (D, L, K, phi_norm.shape[0]),
    )

    # Quantize the frozen φ block ONCE, outside the while_loop: both paths
    # then read the same stored values, so kernel/portable parity holds
    # under quantization.  The f32 path never touches phi_norm.
    phi_store, phi_scale = phi_norm, None
    if phi_dtype != "float32":
        phi_store, phi_scale = quantize_phi(phi_norm, phi_dtype)

    if use_pallas or interpret:
        lane_align = 128 if (use_pallas and not interpret) else 1

        def chunk(theta):
            return theta_sweep_pallas(
                word_ids, est_counts, ev, theta, phi_store, word_topics,
                phi_scale,
                alpha_m1=alpha_m1, num_sweeps=check_every,
                lane_align=lane_align, interpret=interpret,
            )
    else:
        phi_read = (
            phi_norm if phi_dtype == "float32"
            else dequantize_phi(phi_store, phi_scale)
        )
        word_masks = (
            _word_lane_masks(phi_read, word_topics)
            if word_topics is not None else None
        )

        def chunk(theta):
            return _infer_chunk_portable(
                word_ids, est_counts, ev, theta, phi_read, word_masks,
                alpha_m1=alpha_m1, k_alpha=k_alpha, num_sweeps=check_every,
                axis_name=axis_name,
            )

    ntok_est = jnp.maximum(est_counts.sum(), 1.0)
    dtype = theta0.dtype

    def cond(state):
        c, done, *_ = state
        return (c < n_chunks) & jnp.logical_not(done)

    def body(state):
        c, done, theta, _, _, last_ppl = state
        theta, est_ll_tok, ev_ll_tok = chunk(theta)
        est_ll = est_ll_tok.sum()
        ppl = jnp.exp(-est_ll / ntok_est)
        done = jnp.abs(last_ppl - ppl) < rel_tol * ppl
        return c + 1, done, theta, est_ll, ev_ll_tok, ppl

    c, _, theta, est_ll, ev_ll_tok, _ = lax.while_loop(
        cond, body,
        (jnp.int32(0), jnp.bool_(False), theta0,
         jnp.zeros((), dtype), jnp.zeros((D, L), dtype),
         jnp.asarray(jnp.inf, dtype)),
    )
    result = InferResult(
        theta=theta,
        sweeps=c * check_every,
        est_loglik=est_ll,
        ev_loglik=ev_ll_tok.sum(),
        ev_loglik_doc=ev_ll_tok.sum(-1),
    )
    if debug_checks:
        from repro.analysis import sanitizer

        sanitizer.infer_invariants(
            result, est_counts=est_counts, axis_name=axis_name,
        )
    return result


def gs_sweep(
    word_ids: jax.Array,       # (D, L) int32 — rows into phi_wk
    counts: jax.Array,         # (D, L)
    mu: jax.Array,             # (D, L, K)
    theta: jax.Array,          # (D, K)
    phi_wk: jax.Array,         # (W_s, K)
    phi_k: jax.Array,          # (K,)
    *,
    alpha_m1: float,
    beta_m1: float,
    wb: float,
    unroll: int = 8,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Legacy tuple form of the dense sweep (see ``sweep``).

    Returns ``(mu_new, residual, theta, phi_wk, phi_k)``.
    """
    r = sweep(
        word_ids, counts, mu, theta, phi_wk, phi_k,
        alpha_m1=alpha_m1, beta_m1=beta_m1, wb=wb,
        unroll=unroll, use_pallas=use_pallas, interpret=interpret,
    )
    return r.mu, r.residual, r.theta, r.phi_wk, r.phi_k


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
) -> jax.Array:
    """Grouped-query attention over (BH, S, d) flattened head layout."""
    use_pallas = on_tpu() if use_pallas is None else use_pallas
    if use_pallas or interpret:
        # lazy: flash_attention is quarantined LM-template code
        # (analysis.modules), not part of the LDA reproduction graph
        from repro.kernels.flash_attention import flash_attention as _flash_pallas

        return _flash_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            interpret=interpret,
        )
    return ref.mha_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)
