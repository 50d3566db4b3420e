"""Async parameter-streaming pipeline: prefetch determinism + reconciliation.

The contract under test (§3.2 + this repo's pipeline): overlapping the next
minibatch's φ̂-row fetch with the current device step must be *semantically
invisible* — bitwise-identical φ̂/φ̂(k) with prefetching on or off — because
the trainer patches staged rows against any write-back the fetch raced.
"""
import numpy as np
import pytest

from repro.core import FOEMTrainer, LDAConfig, ParameterStore
from repro.core.streaming import StreamPrefetcher
from repro.data import synthetic_lda_corpus
from repro.sparse import MinibatchStream, prefetch_iterator


def _run(tmp_path, depth, *, buffer_rows=64, steps=6, tag="",
         sweep_impl="fused"):
    corpus, _ = synthetic_lda_corpus(120, 150, 5, mean_doc_len=30, seed=11)
    # vocab (150) << corpus tokens: consecutive minibatches overlap heavily,
    # so staged fetches always race the previous write-back — the
    # reconciliation path is exercised on every step.
    cfg = LDAConfig(num_topics=5, vocab_size=150, max_sweeps=4,
                    sweep_impl=sweep_impl)
    store = ParameterStore(
        str(tmp_path / f"d{depth}{tag}"), num_topics=5, vocab_capacity=150,
        buffer_rows=buffer_rows,
    )
    tr = FOEMTrainer(cfg, store, seed=0, prefetch_depth=depth)
    ms = tr.fit_stream(
        iter(MinibatchStream(corpus, 40, seed=0, epochs=None)),
        max_steps=steps,
    )
    return store.dense_phi().copy(), np.array(store.phi_k), ms


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("sweep_impl", ["fused", "scan"])
def test_prefetch_is_bitwise_deterministic(tmp_path, depth, sweep_impl):
    """Prefetch on/off must be invisible with either sweep implementation
    (the fused Gauss-Seidel sweep and the legacy scan)."""
    phi_sync, phi_k_sync, _ = _run(tmp_path, 0, sweep_impl=sweep_impl)
    phi_pf, phi_k_pf, ms = _run(tmp_path, depth, sweep_impl=sweep_impl)
    np.testing.assert_array_equal(phi_sync, phi_pf)
    np.testing.assert_array_equal(phi_k_sync, phi_k_pf)
    assert len(ms) == 6


def test_prefetch_is_deterministic_unbuffered(tmp_path):
    """No hot buffer: every staged fetch reads the backing store the
    write-back scatters into — the hardest race for reconciliation."""
    a = _run(tmp_path, 0, buffer_rows=0, tag="a")
    b = _run(tmp_path, 1, buffer_rows=0, tag="b")
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])


def test_prefetch_counters_populated(tmp_path):
    _, _, ms = _run(tmp_path, 1, tag="c")
    # steady state: staged fetches land while the device computes
    assert sum(m.prefetch_hit for m in ms) >= len(ms) - 2
    assert all(m.overlap_seconds >= 0.0 for m in ms)


def test_stream_prefetcher_reconciliation_token(tmp_path):
    """A staged fetch that raced a write must carry an older version so the
    consumer knows to patch it."""
    store = ParameterStore(str(tmp_path), num_topics=4, vocab_capacity=32,
                           buffer_rows=8)

    class _MB:   # minimal Minibatch stand-in
        def __init__(self, ids):
            self.local_vocab = np.asarray(ids, np.int64)

    pf = StreamPrefetcher(store, [_MB([1, 2, 3])], depth=1)
    try:
        (staged,) = list(pf)
    finally:
        pf.close()
    v_after = store.write_rows(np.array([2]), np.ones((1, 4), np.float32))
    assert staged.version < v_after
    # the patch the trainer would apply:
    _, ia, ib = np.intersect1d(
        staged.minibatch.local_vocab, np.array([2]),
        assume_unique=True, return_indices=True,
    )
    staged.phi_rows[ia] = np.ones((1, 4), np.float32)[ib]
    np.testing.assert_array_equal(
        staged.phi_rows, store.fetch_rows(np.array([1, 2, 3]))
    )


def test_stream_prefetcher_close_unblocks_worker(tmp_path):
    """Abandoning the pipeline mid-stream (max_steps) must not hang even
    with an infinite source."""
    store = ParameterStore(str(tmp_path), num_topics=2, vocab_capacity=16,
                           buffer_rows=4)

    def infinite():
        i = 0
        while True:
            class _MB:
                local_vocab = np.array([i % 16], np.int64)
            yield _MB()
            i += 1

    pf = StreamPrefetcher(store, infinite(), depth=1)
    it = iter(pf)
    next(it)
    pf.close()          # must return promptly (joins the worker)
    import threading
    assert not any(t.name == "minibatch-prefetch" and t.is_alive()
                   for t in threading.enumerate())


def test_prefetch_iterator_order_and_errors():
    assert list(prefetch_iterator(iter(range(50)), depth=3)) == list(range(50))

    def boom():
        yield 1
        raise RuntimeError("producer failed")

    it = prefetch_iterator(boom(), depth=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="producer failed"):
        next(it)


def test_prefetch_iterator_abandonment_stops_worker():
    """Breaking out of a prefetched infinite stream must stop the worker
    thread (generator close), not leave it blocked on a full queue."""
    import itertools
    import threading

    it = prefetch_iterator(itertools.count(), depth=1)
    assert next(it) == 0
    it.close()
    import time as _time
    deadline = _time.time() + 5.0
    while _time.time() < deadline:
        if not any(t.name == "minibatch-prefetch" and t.is_alive()
                   for t in threading.enumerate()):
            break
        _time.sleep(0.05)
    assert not any(t.name == "minibatch-prefetch" and t.is_alive()
                   for t in threading.enumerate())


# ---------------------------------------------------------------------------
# W_s bucketing: the trainer pads the streamed rows to a jit-shape bucket
# ---------------------------------------------------------------------------

def test_padded_trainer_step_matches_unpadded_bitwise(tmp_path):
    """The trainer pads the (W_s, K) rows with zero rows to a multiple of
    ``docword.VOCAB_BUCKET``; on the portable path the step it writes back
    is bitwise the unpadded ``foem_minibatch`` step: same φ̂ rows, same
    φ̂(k), same train ppl (λ_w < 1 also exercises the word ranking)."""
    import jax
    import jax.numpy as jnp

    from repro.core import foem
    from repro.core.types import MinibatchData
    from repro.sparse.docword import VOCAB_BUCKET

    corpus, _ = synthetic_lda_corpus(80, 150, 5, mean_doc_len=30, seed=3)
    cfg = LDAConfig(num_topics=5, vocab_size=150, max_sweeps=6,
                    active_topics=2, active_words_frac=0.7,
                    ppl_check_every=2)
    store = ParameterStore(str(tmp_path / "pad"), num_topics=5,
                           vocab_capacity=150)
    tr = FOEMTrainer(cfg, store, seed=0, prefetch_depth=0)
    stream = iter(MinibatchStream(corpus, 40, seed=0, epochs=None))
    tr.step(next(stream))                 # a non-trivial φ̂ for step two
    mb = next(stream)
    assert len(mb.local_vocab) % VOCAB_BUCKET    # the step really pads

    rows = store.fetch_rows(mb.local_vocab)
    phi_k = store.phi_k.astype(np.float32)
    _, sub = jax.random.split(tr.key)

    @jax.jit
    def unpadded(key, batch, rows, phi_k, live_w):
        res = foem.foem_minibatch(key, batch, rows, phi_k, cfg,
                                  vocab_size=live_w)
        return res.phi_wk, res.phi_k, res.diag.final_train_ppl

    want_rows, want_k, want_ppl = unpadded(
        sub, MinibatchData(jnp.asarray(mb.local_word_ids),
                           jnp.asarray(mb.counts)),
        jnp.asarray(rows), jnp.asarray(phi_k), max(store.live_vocab, cfg.W),
    )
    m = tr.step(mb)
    np.testing.assert_array_equal(store.fetch_rows(mb.local_vocab),
                                  np.asarray(want_rows))
    np.testing.assert_array_equal(store.phi_k.astype(np.float32),
                                  np.asarray(want_k))
    assert m.train_ppl == float(want_ppl)


def test_varying_ws_stream_compiles_once_per_bucket(tmp_path):
    """Minibatches with different unique-vocabulary sizes share one
    compiled step per W_s bucket instead of compiling per step."""
    from repro.sparse.docword import VOCAB_BUCKET

    corpus, _ = synthetic_lda_corpus(160, 900, 5, mean_doc_len=30, seed=5)
    cfg = LDAConfig(num_topics=5, vocab_size=900, max_sweeps=2)
    store = ParameterStore(str(tmp_path / "buckets"), num_topics=5,
                           vocab_capacity=900)
    tr = FOEMTrainer(cfg, store, seed=0, prefetch_depth=0)
    sizes = []

    def stream():
        for mb in MinibatchStream(corpus, 20, seed=0, epochs=None):
            sizes.append(len(mb.local_vocab))
            yield mb

    tr.fit_stream(stream(), max_steps=6)
    buckets = {-(-n // VOCAB_BUCKET) for n in sizes}
    assert len(set(sizes)) > len(buckets)        # W_s varied within buckets
    compiled = sum(fn._cache_size() for fn in tr._jit_cache.values())
    assert compiled == len(buckets)
