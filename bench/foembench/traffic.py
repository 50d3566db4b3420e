"""The one general traffic generator.  A mix is a data file
(``traffic/<mix>.json``) of parameters; its ``kind`` picks one of two shapes:

* ``"stream"`` — a closed training stream: documents drawn from LDA's
  generative process (Dirichlet topic mixtures over topics that are sparse
  Dirichlet draws under a Zipf word envelope), cut into the cell's
  minibatches and cycled, plus a fixed held-out set;
* ``"open_loop"`` — serving requests: bag-of-words documents with
  Zipf-skewed words and uniform token counts, sent at Poisson arrival times
  at a fixed rate (a share ``load`` of the measured knee: the mix's own
  ``knee_docs_per_s`` where it has one, else the configuration's).

Documents, sizes and arrival gaps are drawn from the mix's ``base_seed``
and the configuration; the run's seed puts them in another order (and keys
the program's random initialisation).  So every seed gets the same set of
documents, but a training stream's seed also decides which documents share
a minibatch, and that changes how many sweeps the program's stop rule runs
a step: training seeds do not do the same work.

A document set is CSR: ``indptr`` (n+1,), ``words`` (nnz,) int32 sorted
within each document, ``counts`` (nnz,) float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import numpy as np


@dataclasses.dataclass
class Docs:
    indptr: np.ndarray
    words: np.ndarray
    counts: np.ndarray

    @property
    def n(self) -> int:
        return len(self.indptr) - 1

    def doc(self, i: int) -> Tuple[np.ndarray, np.ndarray]:
        s, e = self.indptr[i], self.indptr[i + 1]
        return self.words[s:e], self.counts[s:e]

    def take(self, order: np.ndarray) -> "Docs":
        lens = np.diff(self.indptr)[order]
        indptr = np.concatenate([[0], np.cumsum(lens)]).astype(np.int64)
        # position of each output entry in the source arrays
        idx = (np.arange(indptr[-1]) - np.repeat(indptr[:-1], lens)
               + np.repeat(self.indptr[:-1][order], lens))
        return Docs(indptr, self.words[idx], self.counts[idx])


def _bags(doc_of_token: np.ndarray, word_of_token: np.ndarray, n_docs: int,
          vocab: int) -> Docs:
    """Tokens -> per-document sorted (word, count) bags."""
    key = doc_of_token.astype(np.int64) * vocab + word_of_token
    uniq, counts = np.unique(key, return_counts=True)
    docs = uniq // vocab
    indptr = np.concatenate([[0], np.cumsum(np.bincount(docs, minlength=n_docs))])
    return Docs(indptr.astype(np.int64), (uniq % vocab).astype(np.int32),
                counts.astype(np.float32))


def zipf_envelope(rng: np.random.Generator, vocab: int, exponent: float):
    """Word probabilities ∝ rank^-exponent over a seeded permutation of the
    vocabulary (popular words are not the low ids)."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    p /= p.sum()
    out = np.empty(vocab)
    out[rng.permutation(vocab)] = p
    return out


def _sample_rows(rng, cum: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Inverse-CDF draw of one column index per entry of ``rows`` from the
    row-wise cumulative distributions ``cum`` (each row ends at 1)."""
    R, C = cum.shape
    flat = (cum + np.arange(R)[:, None]).ravel()
    u = rows + rng.random(len(rows)) * (1.0 - 1e-12)
    idx = np.searchsorted(flat, u, side="right")
    return np.minimum(idx - rows * C, C - 1)


# ---------------------------------------------------------------------------
# "stream": LDA documents for the trainer
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Topics:
    words: np.ndarray    # (T, S) support word ids of each true topic
    probs: np.ndarray    # (T, S) their probabilities


def true_topics(cfg: dict, gen: dict) -> Topics:
    """The generator's true topics for configuration ``cfg``.

    ``gen`` holds ``base_seed``; ``true_topics`` (capped at the
    configuration's K); ``topic_support`` words per topic, drawn with
    replacement under a Zipf(``word_zipf``) envelope, so frequent words sit
    in many topics; and ``topic_dirichlet``, the Dirichlet shape of the
    weights on the support (small: a few words carry each topic)."""
    rng = np.random.default_rng(int(gen["base_seed"]))
    W = int(cfg["vocab_size"])
    T = min(int(gen["true_topics"]), int(cfg["num_topics"]))
    S = int(gen["topic_support"])
    env = zipf_envelope(rng, W, gen["word_zipf"])
    support = rng.choice(W, size=(T, S), p=env)
    probs = rng.gamma(float(gen["topic_dirichlet"]), size=(T, S))
    probs /= probs.sum(1, keepdims=True)
    return Topics(support, probs)


@dataclasses.dataclass
class Corpus:
    train: Docs          # the cycle's documents, in the seed's order
    heldout: Docs        # fixed held-out documents (same for every seed)


def lda_corpus(cfg: dict, mix: dict, seed: int) -> Corpus:
    """Training and held-out documents for configuration ``cfg``.

    ``mix["topics"]`` sets the true topics (:func:`true_topics`);
    ``doc_dirichlet`` the documents' topic mixtures,
    ``max_cycle_minibatches`` and ``heldout_docs`` how many documents.
    Document lengths are Poisson around the configuration's
    ``mean_doc_tokens``."""
    gen = mix["topics"]
    topics = true_topics(cfg, gen)
    T = topics.words.shape[0]
    cum = np.cumsum(topics.probs, 1)
    cum[:, -1] = 1.0
    rng = np.random.default_rng(int(gen["base_seed"]) + 1)
    W, D_s = int(cfg["vocab_size"]), int(cfg["minibatch_docs"])
    n_heldout = int(mix["heldout_docs"])
    cycles = min(int(mix["max_cycle_minibatches"]),
                 (int(cfg["num_docs"]) - n_heldout) // D_s)
    n = cycles * D_s + n_heldout
    lens = np.maximum(rng.poisson(float(cfg["mean_doc_tokens"]), n), 4)
    theta = rng.dirichlet(np.full(T, float(mix["doc_dirichlet"])), size=n)
    tcum = np.cumsum(theta, 1)
    tcum[:, -1] = 1.0
    doc_of_tok = np.repeat(np.arange(n), lens)
    topic = _sample_rows(rng, tcum, doc_of_tok)
    word = topics.words[topic, _sample_rows(rng, cum, topic)]
    docs = _bags(doc_of_tok, word, n, W)
    train = docs.take(np.arange(cycles * D_s))
    heldout = docs.take(np.arange(cycles * D_s, n))
    order = np.random.default_rng(seed).permutation(train.n)
    return Corpus(train.take(order), heldout)


def topic_word_stats(cfg: dict, mix: dict) -> np.ndarray:
    """A trained-looking (W, K) φ̂ of pseudo-counts from the generator's true
    topics (``mix["topics"]``): the corpus's ``num_tokens`` shared evenly by
    the K topics, each spread by its word probabilities (topics past the
    true count repeat)."""
    topics = true_topics(cfg, mix["topics"])
    W, K = int(cfg["vocab_size"]), int(cfg["num_topics"])
    T = topics.words.shape[0]
    per_topic = float(cfg["num_tokens"]) / K
    phi = np.zeros((W, K), np.float32)
    for k in range(K):
        np.add.at(phi[:, k], topics.words[k % T],
                  per_topic * topics.probs[k % T])
    return phi


# ---------------------------------------------------------------------------
# "open_loop": serving requests
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Requests:
    due: np.ndarray      # (N,) seconds after the window opens, ascending
    docs: Docs           # N request documents, in send order
    keys: np.ndarray     # (N, 2) uint32 per-request PRNG keys
    rate: float          # offered docs/s


def offered_rate(cfg: dict, mix: dict) -> float:
    """``load`` times the knee of the mix's deployment (its own
    ``knee_docs_per_s``, else the configuration's)."""
    knee = mix.get("knee_docs_per_s")
    if knee is None:
        knee = cfg["serve"]["knee_docs_per_s"]
    return float(mix["load"]) * float(knee)


def open_loop(cfg: dict, mix: dict, seed: int, seconds: float,
              rate: float = None) -> Requests:
    """Requests due in the first ``seconds`` of the window (and a margin),
    at ``rate`` docs/s (default: :func:`offered_rate`)."""
    rate = offered_rate(cfg, mix) if rate is None else float(rate)
    W = int(cfg["vocab_size"])
    lo, hi = (int(x) for x in mix["doc_tokens"])
    n = int(math.ceil(rate * seconds * 1.2)) + 64
    base = np.random.default_rng(int(mix["base_seed"]))
    env = zipf_envelope(base, W, mix["word_zipf"])
    cum = np.cumsum(env)
    cum[-1] = 1.0
    lens = base.integers(lo, hi + 1, n)
    gaps = base.exponential(1.0 / rate, n)
    doc_of_tok = np.repeat(np.arange(n), lens)
    word = np.minimum(np.searchsorted(cum, base.random(len(doc_of_tok)),
                                      side="right"), W - 1)
    docs = _bags(doc_of_tok, word, n, W)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)
    due = np.cumsum(gaps[rng.permutation(n)])
    keys = np.stack([np.full(n, seed % (1 << 32), np.uint32),
                     np.arange(n, dtype=np.uint32)], axis=1)
    return Requests(due, docs.take(order), keys, rate)
