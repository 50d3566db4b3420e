"""Residual-based dynamic scheduling — paper §3.1 (eqs. 34-38).

The paper keeps, per vocabulary word w, accumulated responsibility residuals
    r_w(k) = Σ_d x_{w,d} |μ^t_{w,d}(k) − μ^{t−1}_{w,d}(k)|      (eq. 36)
    r_w    = Σ_k r_w(k)                                          (eq. 37)
and each inner sweep updates only the λ_k·K topics with the largest r_w(k)
(per word) and the λ_w·W_s words with the largest r_w.  Inactive entries keep
their previous residual estimate (priority-queue semantics); active entries
are *replaced* with the freshly measured residual.

TPU adaptation: the insertion/partial sort becomes ``jax.lax.top_k`` over the
(W_s, K) residual matrix — one partial sort per sweep,
O(W_s · K log K) as in the paper's complexity accounting.  The per-token
active set is the token's *word's* active set, gathered by word id.

The partial renormalisation (eq. 38) preserves the inactive topics' mass:
    μ̂^t(k) = μ^t(k) / Σ_{k∈A} μ^t(k) · Σ_{k∈A} μ̂^{t−1}(k),  k ∈ A.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import LDAConfig, SchedulerState


def init_scheduler(num_words: int, cfg: LDAConfig) -> SchedulerState:
    """Fresh residual state; +inf-like init so every entry is visited once."""
    big = jnp.full((num_words, cfg.K), jnp.finfo(cfg.dtype).max / 4, cfg.dtype)
    return SchedulerState(r_wk=big, r_w=big.sum(-1))


def select_active_topics(
    sched: SchedulerState, active_topics: int, topk_shards: int = 0
) -> jax.Array:
    """Top-λ_kK topic ids per vocabulary word: (W_s, K) -> (W_s, A) int32.

    ``topk_shards > 0`` selects A/topk_shards winners within each contiguous
    K/topk_shards topic group instead of a global top-A.  When the groups
    align with the mesh's model-axis sharding of the topic dimension, the
    partial sort becomes shard-local — no all-gather of the (W_s, K)
    residual matrix (the §Perf lever for the K-sharded LDA step).  The
    union is still a valid size-A active set; per-group balance only
    re-orders WHICH near-top entries are refreshed first (priority-queue
    semantics are preserved since untouched residuals persist).
    """
    K = sched.r_wk.shape[1]
    if topk_shards and topk_shards > 1:
        assert K % topk_shards == 0 and active_topics % topk_shards == 0, (
            K, active_topics, topk_shards,
        )
        g = K // topk_shards
        a = active_topics // topk_shards
        r = sched.r_wk.reshape(-1, topk_shards, g)
        _, idx = jax.lax.top_k(r, a)                     # local per group
        offs = (jnp.arange(topk_shards) * g)[None, :, None]
        return (idx + offs).reshape(-1, active_topics).astype(jnp.int32)
    _, idx = jax.lax.top_k(sched.r_wk, active_topics)
    return idx.astype(jnp.int32)


def select_active_words_threshold(
    sched: SchedulerState, frac: float,
    num_words: Optional[jax.Array] = None,
) -> jax.Array:
    """Residual threshold t such that ~frac·W_s words satisfy r_w >= t.

    Returned as a scalar; tokens are masked by ``r_w[word_id] >= t``.  With
    frac == 1.0 the threshold is -inf (all words active).  ``num_words``
    (traced) is the real W_s when the rows past it are zero padding of a
    jit-shape bucket: residuals are non-negative and padding rows stay 0,
    so the k-th largest of the padded vector is the k-th largest of the
    real words.
    """
    if frac >= 1.0:
        return jnp.array(-jnp.inf, sched.r_w.dtype)
    if num_words is None:
        n = sched.r_w.shape[0]
        k = max(1, int(round(frac * n)))
        vals, _ = jax.lax.top_k(sched.r_w, k)
        return vals[-1]
    k = jnp.maximum(1, jnp.round(frac * num_words).astype(jnp.int32))
    return jnp.sort(sched.r_w)[::-1][k - 1]


def sparse_estep_renorm(
    mu_active_new: jax.Array,   # (D, L, A) unnormalised responsibilities on A
    mu_prev_active: jax.Array,  # (D, L, A) previous *normalised* μ on A
) -> jax.Array:
    """eq. (38): renormalise over the active set, preserving inactive mass."""
    prev_mass = mu_prev_active.sum(-1, keepdims=True)
    new_sum = jnp.maximum(mu_active_new.sum(-1, keepdims=True), 1e-30)
    return mu_active_new / new_sum * prev_mass


def update_residuals(
    sched: SchedulerState,
    delta_r_wk: jax.Array,      # (W_s, K) freshly measured Σ_d x|Δμ| (active-only rows/cols non-zero)
    touched_wk: jax.Array,      # (W_s, K) bool — True where the entry was updated this sweep
) -> SchedulerState:
    """Replace residuals for touched entries, keep estimates elsewhere."""
    r_wk = jnp.where(touched_wk, delta_r_wk, sched.r_wk)
    return SchedulerState(r_wk=r_wk, r_w=r_wk.sum(-1))


def scatter_residuals(
    abs_delta: jax.Array,   # (D, L, A) x|Δμ| per token over its active topics
    word_ids: jax.Array,    # (D, L)
    topic_ids: jax.Array,   # (D, L, A) the active topic ids per token
    num_words: int,
    num_topics: int,
) -> Tuple[jax.Array, jax.Array]:
    """Accumulate eq. (36) residuals into (W_s, K); also return touched mask.

    Implemented as a single segment-sum over the flattened (word, topic) pair
    index — one scatter, matching the 'negligible cost' claim in §3.1.
    """
    D, L, A = abs_delta.shape
    # 2-D scatter (never flatten the (word, topic) pair: W·K overflows int32
    # in the big-model regime, paper §1 task 2)
    widx = jnp.broadcast_to(word_ids[..., None], topic_ids.shape)
    summed = jnp.zeros((num_words, num_topics), abs_delta.dtype).at[
        widx, topic_ids
    ].add(abs_delta)
    touched = jnp.zeros((num_words, num_topics), jnp.bool_).at[
        widx, topic_ids
    ].set(True)
    return summed, touched


def scheduler_update_from_sweep(
    sched: SchedulerState,
    residual: jax.Array,     # (D, L, K) counts·|Δμ| emitted by the fused sweep
    word_ids: jax.Array,     # (D, L)
    word_topics: jax.Array,  # (W_s, A) the active topic ids per word
) -> SchedulerState:
    """Replace-touched residual refresh from a fused scheduled sweep.

    The single-launch scheduled sweep emits the eq. 36 replacement values
    full-K (zeros off each token's active set), so the refresh is ONE
    segment-sum over the vocab axis — equal to ``scatter_residuals`` +
    ``update_residuals`` on the compact (D, L, A) values, since entries
    outside a token's active set contribute exactly zero.  The touched mask
    (an active entry whose fresh residual is 0 must *replace* the old
    estimate, not keep it) is per word — the batch's words, each with its
    active set — so it needs no per-token scatter at all: one W_s·A mask
    build and a presence vector.
    """
    D, L, K = residual.shape
    num_words = sched.r_wk.shape[0]
    r_meas = jax.ops.segment_sum(
        residual.reshape(D * L, K), word_ids.reshape(D * L),
        num_segments=num_words,
    )
    present = jnp.zeros((num_words,), jnp.bool_).at[
        word_ids.reshape(-1)
    ].set(True)
    active = jnp.put_along_axis(
        jnp.zeros((num_words, K), jnp.bool_), word_topics, True, axis=-1,
        inplace=False,
    )
    return update_residuals(sched, r_meas, active & present[:, None])


def residuals_from_sweep(
    residual: jax.Array,    # (D, L, K) counts·|Δμ| emitted by the fused sweep
    word_ids: jax.Array,    # (D, L)
    num_words: int,
) -> SchedulerState:
    """Build the residual state from the fused sweep's emitted residuals.

    The fused Gauss-Seidel sweep (``kernels.ops.gs_sweep``) measures
    counts·|μ_new − μ_old| per token as a by-product of the E-step, so the
    post-warm-up init (``full_sweep_residuals``) needs only this one
    scatter — no re-measurement pass over (D, L, K)."""
    D, L, K = residual.shape
    r_wk = jax.ops.segment_sum(
        residual.reshape(D * L, K), word_ids.reshape(D * L),
        num_segments=num_words,
    )
    return SchedulerState(r_wk=r_wk, r_w=r_wk.sum(-1))


def full_sweep_residuals(
    mu_new: jax.Array,      # (D, L, K)
    mu_old: jax.Array,      # (D, L, K)
    counts: jax.Array,      # (D, L)
    word_ids: jax.Array,    # (D, L)
    num_words: int,
) -> SchedulerState:
    """Residual init after a full (unscheduled) sweep — paper Fig. 4 ('In the
    first iteration FOEM ... scans the entire non-zero elements and topics,
    which also initializes and updates the residual matrices').

    Measures counts·|Δμ| post hoc; the fused sweep emits the same quantity
    for free, in which case use ``residuals_from_sweep`` directly."""
    return residuals_from_sweep(
        counts[..., None] * jnp.abs(mu_new - mu_old), word_ids, num_words
    )


# ---------------------------------------------------------------------------
# Topic-shift detection — lifelong-stream drift over eq. 36 / eq. 21 signals
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShiftEvent:
    """One detected stream event, surfaced through ``StepMetrics``."""

    step: int
    kind: str        # "residual-shift" | "ppl-shift" | "topic-birth" | "topic-death"
    value: float     # signal magnitude (deviation, share, ...)
    topic: int = -1  # topic id for birth/death events


class ShiftDetector:
    """EWMA drift detector over the trainer's per-step stream signals.

    Lifelong streams are non-stationary: when the document distribution
    shifts, the eq. 36 replacement-residual mass (how much of μ the sweep
    rewrote) and the eq. 21 train perplexity both jump relative to their
    recent history.  This detector keeps an exponentially weighted mean and
    mean-absolute-deviation per signal; a point farther than
    ``threshold × dev`` from the mean (after ``warmup`` observations) fires
    a shift event and re-arms the estimator at the new level.  A fired
    shift latches ``consume_refresh()`` so the trainer can grant the next
    step extra warm-up (full, unscheduled) sweeps — the Fig. 4 residual
    re-initialisation applied mid-stream instead of only at t=0.

    Topic birth/death tracks the normalized φ_k mass shares: a topic whose
    share crosses ``topic_floor_frac / K`` (a fraction of the uniform
    share) in either direction emits one event at the crossing.

    Single-writer: ``update`` must be called from the trainer thread only
    (readers consume the returned events; there is no internal locking).
    """

    def __init__(self, *, alpha: float = 0.25, threshold: float = 6.0,
                 warmup: int = 8, topic_floor_frac: float = 0.05):
        self.alpha = float(alpha)
        self.threshold = float(threshold)
        self.warmup = int(warmup)
        self.topic_floor_frac = float(topic_floor_frac)
        self._sig: dict = {}          # name -> [ewma_mean, ewma_dev, n_obs]
        self._alive = None            # (K,) bool from the last update
        self._refresh = False
        self.events: list = []        # full event history, oldest first

    def _drift(self, name: str, x: float, step: int) -> Optional[ShiftEvent]:
        st = self._sig.setdefault(name, [0.0, 0.0, 0])
        mean, dev, n = st
        if n == 0:
            st[:] = [x, 0.0, 1]
            return None
        d = abs(x - mean)
        if n >= self.warmup and d > self.threshold * max(dev, 1e-12):
            # re-arm at the new level; keep dev so a noisy regime doesn't
            # look calm the moment after a shift
            st[:] = [x, dev, 1]
            return ShiftEvent(step=step, kind=f"{name}-shift", value=d)
        st[0] = mean + self.alpha * (x - mean)
        st[1] = dev + self.alpha * (d - dev)
        st[2] = n + 1
        return None

    def update(self, *, step: int, residual_mass: float = float("nan"),
               perplexity: float = float("nan"), phi_k=None) -> list:
        """Feed one trainer step's signals; returns the events it fired."""
        evs = []
        if residual_mass == residual_mass:        # not NaN
            ev = self._drift("residual", float(residual_mass), step)
            if ev is not None:
                evs.append(ev)
        if perplexity == perplexity:
            ev = self._drift("ppl", float(perplexity), step)
            if ev is not None:
                evs.append(ev)
        if phi_k is not None:
            pk = np.asarray(phi_k, np.float64)    # lint: host-f64
            tot = pk.sum()
            if tot > 0:
                shares = pk / tot
                floor = self.topic_floor_frac / len(pk)
                alive = shares >= floor
                if self._alive is not None:
                    for k in np.flatnonzero(alive & ~self._alive):
                        evs.append(ShiftEvent(step=step, kind="topic-birth",
                                              value=float(shares[k]),
                                              topic=int(k)))
                    for k in np.flatnonzero(self._alive & ~alive):
                        evs.append(ShiftEvent(step=step, kind="topic-death",
                                              value=float(shares[k]),
                                              topic=int(k)))
                self._alive = alive
        if any(ev.kind.endswith("-shift") for ev in evs):
            self._refresh = True
        self.events.extend(evs)
        return evs

    def consume_refresh(self) -> bool:
        """Latched 'grant extra warm-up sweeps' flag; cleared on read."""
        out = self._refresh
        self._refresh = False
        return out
