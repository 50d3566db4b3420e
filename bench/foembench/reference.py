"""The plain reference: LDA under MAP-EM, written from the paper in
straightforward ``jax.numpy``, f32 unless told otherwise.  It imports
nothing of the program under test and takes nothing the program made.

* :func:`foem_steps` — FOEM's per-minibatch inner loop (paper Fig. 4):
  random-normalised μ init, the minibatch's initial fold into the working
  φ̂, ``warmup`` full column-serial Gauss–Seidel sweeps (eq. 13 with the
  token's own contribution excluded), then scheduled sweeps over each word's
  top-A topics by eq. 36 residual, with the eq. 38 renormalisation and
  replace-on-touch residual refresh (§3.1); the training perplexity of the
  last check (every ``check_every`` sweeps).  The number of sweeps is
  given, so the reference follows the same stop.
* :func:`infer_theta` — the frozen-φ θ fixed point (§2.4, eq. 11 without
  the φ M-step) from the request's own random-normalised μ init.
* :func:`heldout_perplexity` — eq. 21 on an 80/20 split of held-out
  documents, θ fitted on the 80% with φ̂ frozen.

Column-serial means: within one token column every document reads the
state left by the previous column; the updates of one column's documents
are applied together.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

TINY = 1e-30


def _uniform_mu(key, shape):
    g = jax.random.uniform(key, shape, jnp.float32, minval=0.5, maxval=1.5)
    return g / g.sum(-1, keepdims=True)


def _dense_sweep(wid, cnt, mu, theta, phi, ptot, a, b, wb):
    """One full sweep.  ``mu`` is column-major (L, D, K).  Returns the new
    μ, θ̂, φ̂, φ̂(k) and the eq. 36 residual Σ x|Δμ| per (word, topic)."""
    r0 = jnp.zeros_like(phi)

    def col(carry, xs):
        theta, phi, ptot, r = carry
        w, c, m_old = xs
        ex = c[:, None] * m_old
        th = jnp.maximum(theta - ex, 0.0)
        ph = jnp.maximum(phi[w] - ex, 0.0)
        pt = ptot[None, :] - ex
        num = (th + a) * (ph + b) / (pt + wb)
        m_new = num / jnp.maximum(num.sum(-1, keepdims=True), TINY)
        delta = c[:, None] * m_new - ex
        r = r.at[w].add(c[:, None] * jnp.abs(m_new - m_old))
        return (theta + delta, phi.at[w].add(delta), ptot + delta.sum(0),
                r), m_new

    (theta, phi, ptot, r), mu = jax.lax.scan(
        col, (theta, phi, ptot, r0), (wid.T, cnt.T, mu))
    return mu, theta, phi, ptot, r


def _sched_sweep(wid, cnt, mu, theta, phi, ptot, word_topics, a, b, wb):
    """One scheduled sweep over each word's active topics (eq. 38)."""
    Ws, K = phi.shape
    lanes = jnp.zeros((Ws, K), mu.dtype).at[
        jnp.arange(Ws)[:, None], word_topics].set(1)
    r0 = jnp.zeros_like(phi)

    def col(carry, xs):
        theta, phi, ptot, r = carry
        w, c, m_old = xs
        mask = lanes[w] * (c > 0).astype(mu.dtype)[:, None]
        ex = c[:, None] * m_old * mask
        th = jnp.maximum(theta - ex, 0.0)
        ph = jnp.maximum(phi[w] - ex, 0.0)
        pt = ptot[None, :] - ex
        num = (th + a) * (ph + b) / (pt + wb) * mask
        prev = (m_old * mask).sum(-1, keepdims=True)
        new = jnp.maximum(num.sum(-1, keepdims=True), TINY)
        m_new = mask * (num / new * prev) + (1 - mask) * m_old
        delta = c[:, None] * (m_new - m_old)
        r = r.at[w].add(jnp.abs(delta))
        return (theta + delta, phi.at[w].add(delta), ptot + delta.sum(0),
                r), m_new

    (theta, phi, ptot, r), mu = jax.lax.scan(
        col, (theta, phi, ptot, r0), (wid.T, cnt.T, mu))
    present = jnp.zeros((Ws,), bool).at[wid.reshape(-1)].set(True)
    touched = (lanes > 0) & present[:, None]
    return mu, theta, phi, ptot, r, touched


def _perplexity(wid, cnt, theta, phi, ptot, a, b, wb):
    K = theta.shape[-1]
    th = (theta + a) / jnp.maximum(theta.sum(-1, keepdims=True) + K * a, TINY)
    ph = (phi + b) / jnp.maximum(ptot + wb, TINY)[None, :]
    lik = (ph[wid] * th[:, None, :]).sum(-1)
    ll = (cnt * jnp.log(jnp.maximum(lik, TINY))).sum()
    return jnp.exp(-ll / jnp.maximum(cnt.sum(), 1.0))


@functools.partial(jax.jit, static_argnames=(
    "warmup", "check_every", "active", "dtype"))
def foem_minibatch(key, wid, cnt, rows, phi_k, sweeps, *, vocab, alpha_m1,
                   beta_m1, warmup, check_every, active, dtype=jnp.float32):
    """One minibatch: ``rows`` (W_s, K) and ``phi_k`` (K,) before it,
    ``wid`` (D, L) ids into ``rows``, ``cnt`` (D, L), ``sweeps`` in all.
    Returns the rows and totals after it and the training perplexity of the
    last check."""
    D, L = wid.shape
    K = rows.shape[1]
    a, b = jnp.asarray(alpha_m1, dtype), jnp.asarray(beta_m1, dtype)
    wb = jnp.asarray(vocab * beta_m1, dtype)
    cnt = cnt.astype(dtype)
    mu = _uniform_mu(key, (D, L, K)).astype(dtype)
    weighted = mu * cnt[..., None]
    theta = weighted.sum(1)
    phi = rows.astype(dtype).at[wid].add(weighted)
    ptot = phi_k.astype(dtype) + weighted.sum((0, 1))
    mu = mu.transpose(1, 0, 2)
    del weighted
    for _ in range(warmup):
        mu, theta, phi, ptot, r = _dense_sweep(wid, cnt, mu, theta, phi,
                                               ptot, a, b, wb)

    def scheduled(t, state):
        mu, theta, phi, ptot, r, ppl = state
        _, word_topics = jax.lax.top_k(r.astype(jnp.float32), active)
        mu, theta, phi, ptot, r_new, touched = _sched_sweep(
            wid, cnt, mu, theta, phi, ptot, word_topics, a, b, wb)
        r = jnp.where(touched, r_new, r)
        ppl = jax.lax.cond(
            (t + 1) % check_every == 0,
            lambda: _perplexity(wid, cnt, theta, phi, ptot, a, b,
                                wb).astype(jnp.float32),
            lambda: ppl)
        return mu, theta, phi, ptot, r, ppl

    state = (mu, theta, phi, ptot, r, jnp.asarray(jnp.nan, jnp.float32))
    _, _, phi, ptot, _, ppl = jax.lax.fori_loop(warmup, sweeps, scheduled,
                                                state)
    return phi.astype(jnp.float32), ptot.astype(jnp.float32), ppl


class StepInput(NamedTuple):
    key: jax.Array          # the PRNG key the step's μ init draws from
    vocab_pos: np.ndarray   # (W_s,) positions of the step's words in the view
    word_ids: np.ndarray    # (D, L) ids into the step's W_s words
    counts: np.ndarray      # (D, L)
    sweeps: int             # sweeps the step ran


def foem_steps(steps: Sequence[StepInput], view_rows: int, K: int, *,
               vocab: int, alpha_m1: float, beta_m1: float, warmup: int,
               check_every: int, active: int, dtype=jnp.float32,
               row_bucket: int = 512):
    """Run consecutive minibatches from an empty φ̂ over a view of
    ``view_rows`` words (the union of the steps' vocabularies).  Returns,
    per step, ``(phi_view (view_rows, K), phi_k (K,), train_ppl)`` on the
    host, as float32.  A step's rows are padded with zero rows to a
    multiple of ``row_bucket`` (never indexed), so steps share compiles."""
    phi = np.zeros((view_rows, K), np.float32)
    phi_k = np.zeros((K,), np.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for s in steps:
            n = len(s.vocab_pos)
            rows = np.zeros((-(-n // row_bucket) * row_bucket, K), np.float32)
            rows[:n] = phi[s.vocab_pos]
            rows, phi_k_new, ppl = foem_minibatch(
                s.key, jnp.asarray(s.word_ids), jnp.asarray(s.counts),
                jnp.asarray(rows), jnp.asarray(phi_k),
                jnp.int32(s.sweeps), vocab=vocab, alpha_m1=alpha_m1,
                beta_m1=beta_m1, warmup=warmup, check_every=check_every,
                active=active, dtype=dtype)
            phi = phi.copy()
            phi[s.vocab_pos] = np.asarray(rows)[:n]
            phi_k = np.asarray(phi_k_new)
            out.append((phi, phi_k, float(ppl)))
    return out


# ---------------------------------------------------------------------------
# frozen-φ inference
# ---------------------------------------------------------------------------

def normalized_phi(phi: np.ndarray, phi_k: np.ndarray, vocab: int,
                   beta_m1: float) -> jax.Array:
    """eq. 10 over the full (W, K) statistics."""
    phi = jnp.asarray(phi, jnp.float32)
    den = jnp.asarray(phi_k, jnp.float32) + vocab * beta_m1
    return (phi + beta_m1) / jnp.maximum(den, TINY)[None, :]


@functools.partial(jax.jit, static_argnames=("sweeps",))
def _infer_group(keys, words, counts, phi_n, alpha_m1, sweeps):
    n, L = words.shape
    K = phi_n.shape[1]
    mu0 = jax.vmap(lambda k: _uniform_mu(k, (L, K)))(keys)
    theta = (mu0 * counts[..., None]).sum(1)
    rows = phi_n[words]                                   # (n, L, K)

    def norm(theta):
        return (theta + alpha_m1) / jnp.maximum(
            theta.sum(-1, keepdims=True) + K * alpha_m1, TINY)

    def one(theta, _):
        num = norm(theta)[:, None, :] * rows
        mu = num / jnp.maximum(num.sum(-1, keepdims=True), TINY)
        return (mu * counts[..., None]).sum(1), None

    theta, _ = jax.lax.scan(one, theta, None, length=sweeps)
    return norm(theta)


def infer_theta(docs: Sequence, keys: np.ndarray, phi_n: jax.Array, *,
                alpha_m1: float, sweeps: int, bucket: int,
                block: int = 256) -> np.ndarray:
    """θ (n, K) of each ``(words, counts)`` request.  A request's μ init is
    drawn over its length bucket (distinct words rounded up to ``bucket``),
    as the serving path pads it, from its own key."""
    out: List = [None] * len(docs)
    by_len = {}
    for i, (w, _) in enumerate(docs):
        L = max(bucket, -(-len(w) // bucket) * bucket)
        by_len.setdefault(L, []).append(i)
    with jax.default_matmul_precision("highest"):
        for L, idx in sorted(by_len.items()):
            for lo in range(0, len(idx), block):
                part = idx[lo: lo + block]
                words = np.zeros((len(part), L), np.int32)
                counts = np.zeros((len(part), L), np.float32)
                for j, i in enumerate(part):
                    w, c = docs[i]
                    words[j, :len(w)] = w
                    counts[j, :len(c)] = c
                theta = np.asarray(_infer_group(
                    jnp.asarray(keys[part], jnp.uint32), jnp.asarray(words),
                    jnp.asarray(counts), phi_n, alpha_m1, sweeps))
                for j, i in enumerate(part):
                    out[i] = theta[j]
    return np.stack(out)


# ---------------------------------------------------------------------------
# held-out perplexity (eq. 21)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("sweeps",))
def _heldout_ll(rows, est, ev, alpha_m1, sweeps):
    K = rows.shape[-1]

    def norm(theta):
        return (theta + alpha_m1) / jnp.maximum(
            theta.sum(-1, keepdims=True) + K * alpha_m1, TINY)

    def one(theta, _):
        num = norm(theta)[:, None, :] * rows
        mu = num / jnp.maximum(num.sum(-1, keepdims=True), TINY)
        return (mu * est[..., None]).sum(1), None

    theta0 = jnp.broadcast_to(est.sum(1, keepdims=True) / K,
                              (est.shape[0], K))
    theta, _ = jax.lax.scan(one, theta0, None, length=sweeps)
    lik = (rows * norm(theta)[:, None, :]).sum(-1)
    return (ev * jnp.log(jnp.maximum(lik, TINY))).sum()


def heldout_perplexity(docs: Sequence, row_of: dict, phi_rows: np.ndarray,
                       phi_k: np.ndarray, *, vocab: int, alpha_m1: float,
                       beta_m1: float, split_seed: int, sweeps: int = 50,
                       block_bytes: float = 5e8) -> float:
    """eq. 21 over ``docs`` (``(words, counts)`` with global word ids).
    ``phi_rows`` holds the φ̂ rows of the held-out vocabulary, ``row_of``
    maps a global word id to its row.  Each count is split 80/20 by a
    binomial draw from ``split_seed``; θ is fitted on the 80% with φ̂
    frozen, and the 20% is scored."""
    rng = np.random.default_rng(split_seed)
    K = phi_rows.shape[1]
    phi_n = np.asarray(normalized_phi(phi_rows, phi_k, vocab, beta_m1))
    L = max(len(w) for w, _ in docs)
    per_doc = L * K * 4
    block = max(1, int(block_bytes // per_doc))
    ll, ntok = 0.0, 0.0
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(docs), block):
            part = docs[lo: lo + block]
            words = np.zeros((len(part), L), np.int64)
            est = np.zeros((len(part), L), np.float32)
            ev = np.zeros((len(part), L), np.float32)
            for j, (w, c) in enumerate(part):
                e = rng.binomial(c.astype(np.int64), 0.8).astype(np.float32)
                words[j, :len(w)] = [row_of[int(x)] for x in w]
                est[j, :len(w)] = e
                ev[j, :len(w)] = c - e
            rows = jnp.asarray(phi_n[words])
            ll += float(_heldout_ll(rows, jnp.asarray(est), jnp.asarray(ev),
                                    alpha_m1, sweeps))
            ntok += float(ev.sum())
    return float(np.exp(-ll / max(ntok, 1.0)))
