"""End-to-end behaviour: the full FOEM system learns real topic structure."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FOEMTrainer,
    GlobalStats,
    LDAConfig,
    MinibatchData,
    ParameterStore,
    em,
    foem,
)
from repro.core.perplexity import predictive_perplexity, split_heldout_counts
from repro.data import synthetic_lda_corpus
from repro.sparse import MinibatchStream
from repro.sparse.docword import bucketize


def test_end_to_end_topic_recovery(tmp_path):
    """Train streaming FOEM on a synthetic corpus with known topics; the
    learned φ must (a) beat the untrained model on held-out perplexity by a
    wide margin and (b) align with the true topics (greedy cosine match)."""
    K, W = 8, 400
    cfg = LDAConfig(num_topics=K, vocab_size=W, max_sweeps=16,
                    iem_blocks=4, active_topics=4)
    corpus, true_phi = synthetic_lda_corpus(
        360, W, K, mean_doc_len=80, seed=11
    )
    rng = np.random.default_rng(0)
    train, test = corpus.split_train_test(40, rng)

    store = ParameterStore(str(tmp_path), num_topics=K, vocab_capacity=W,
                           buffer_rows=128)
    trainer = FOEMTrainer(cfg, store, checkpoint_every=4)
    trainer.fit_stream(
        iter(MinibatchStream(train, 64, seed=0, epochs=6)), max_steps=18
    )

    ids = list(range(test.num_docs))
    w, c = bucketize(test, ids)
    est, ev = split_heldout_counts(c, rng)
    est_b = MinibatchData(jnp.asarray(w), jnp.asarray(est))
    ev_b = MinibatchData(jnp.asarray(w), jnp.asarray(ev))

    phi = jnp.asarray(store.dense_phi())
    if phi.shape[0] < W:
        phi = jnp.pad(phi, ((0, W - phi.shape[0]), (0, 0)))
    ppl_trained = float(predictive_perplexity(
        jax.random.PRNGKey(0), est_b, ev_b, phi,
        jnp.asarray(store.phi_k, jnp.float32), cfg,
    ))
    ppl_untrained = float(predictive_perplexity(
        jax.random.PRNGKey(0), est_b, ev_b,
        jnp.ones((W, K)) / W, jnp.ones((K,)), cfg,
    ))
    assert ppl_trained < 0.7 * ppl_untrained, (ppl_trained, ppl_untrained)

    # greedy topic matching against ground truth
    learned = np.asarray(em.normalize_phi(
        phi, jnp.asarray(store.phi_k, jnp.float32), cfg
    )).T                                      # (K, W)
    truth = true_phi.T                        # (K, W)
    sims = learned @ truth.T / (
        np.linalg.norm(learned, axis=1)[:, None]
        * np.linalg.norm(truth, axis=1)[None] + 1e-12
    )
    matched = []
    s = sims.copy()
    for _ in range(K):
        i, j = np.unravel_index(np.argmax(s), s.shape)
        matched.append(s[i, j])
        s[i, :] = -1
        s[:, j] = -1
    assert np.mean(matched) > 0.5, f"topic match cosines: {matched}"


def test_foem_matches_sem_quality_with_less_work(tiny_corpus):
    """The paper's core claim at minibatch granularity: FOEM (scheduled,
    λ_kK≈3) reaches comparable training perplexity to SEM (full BEM inner
    loop) on the same stream while touching ~λ_k of the topic space."""
    from repro.core import sem

    corpus, _ = tiny_corpus
    base = LDAConfig(num_topics=6, vocab_size=240, max_sweeps=12,
                     iem_blocks=4)
    cfg_foem = dataclasses.replace(base, active_topics=3)
    cfg_sem = dataclasses.replace(base, rho_mode="stepwise")

    def run(step_fn, cfg):
        stats = GlobalStats.zeros(cfg)
        key = jax.random.PRNGKey(0)
        last = None
        for i, mb in enumerate(MinibatchStream(corpus, 32, seed=5, epochs=3)):
            if i >= 5:
                break
            batch = MinibatchData(jnp.asarray(mb.word_ids),
                                  jnp.asarray(mb.counts))
            key, sub = jax.random.split(key)
            stats, _, diag = step_fn(sub, batch, stats, cfg)
            last = float(diag.final_train_ppl)
        return last

    p_foem = run(foem.foem_step, cfg_foem)
    p_sem = run(sem.sem_step, cfg_sem)
    assert p_foem < p_sem * 1.3, (p_foem, p_sem)


def test_compile_cache_env_wins_else_checkout_dir(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX untouched; unset, the
    entry points cache at the fixed ``<checkout>/.jax_cache``."""
    import os

    from repro.runtime.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        path = enable_compile_cache()
        assert path == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
