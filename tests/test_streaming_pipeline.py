"""Async parameter-streaming pipeline: prefetch determinism + reconciliation,
and the device row tier.

The contract under test (§3.2 + this repo's pipeline): overlapping the next
minibatch's φ̂-row fetch with the current device step must be *semantically
invisible* — bitwise-identical φ̂/φ̂(k) with prefetching on or off — because
the trainer patches staged rows against any write-back the fetch raced.
Holding φ̂ in the store's device tier must be just as invisible: the same
rows, totals, sweeps and perplexities as streaming them, and every read of
the store sees what the tier holds.  The fit rule decides the regime; the
tests force its answer by patching ``trainer.device_tier_fits``.
"""
import logging
from unittest import mock

import jax
import numpy as np
import pytest

import repro.core.trainer as trainer_mod
from repro.core import (FOEMTrainer, LDAConfig, ParameterStore,
                        SnapshotPublisher)
from repro.core.streaming import StreamPrefetcher
from repro.data import synthetic_lda_corpus
from repro.runtime import faults
from repro.sparse import MinibatchStream, prefetch_iterator
from repro.sparse.docword import VOCAB_BUCKET


def _fit_rule(answer):
    """Patch the fit rule: a fixed answer, or a rule of its own."""
    rule = answer if callable(answer) else (lambda *a: answer)
    return mock.patch.object(trainer_mod, "device_tier_fits", rule)


def _run(tmp_path, depth, *, buffer_rows=64, steps=6, tag="",
         sweep_impl="fused"):
    """A streamed run: these tests pin the host pipeline's reconciliation."""
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
    with _fit_rule(False):
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


# ---------------------------------------------------------------------------
# Device row tier: φ̂ held on the device, the host store behind it
# ---------------------------------------------------------------------------

TK, TV = 5, 2000


def _tier_stream():
    """Minibatches whose W_s straddles one bucket (about 490–525 words, so
    buckets 512 and 1024 both occur, the first minibatches in the smaller)
    over a vocabulary they share heavily."""
    corpus, _ = synthetic_lda_corpus(240, TV, 20, mean_doc_len=40, seed=11)
    return MinibatchStream(corpus, 48, seed=0, epochs=None)


def _bucket(n):
    return -(-n // VOCAB_BUCKET)


def _tier_run(path, depth, fits, *, steps=8, watch=None, faults=None,
              publish_every=0):
    """Train ``steps`` minibatches with the fit rule's answer forced;
    ``watch(store)`` gives the step callback.  Returns the store, the step
    metrics, the published snapshots, the minibatches and the trainer."""
    cfg = LDAConfig(num_topics=TK, vocab_size=TV, max_sweeps=4)
    store = ParameterStore(str(path), num_topics=TK, vocab_capacity=TV,
                           buffer_rows=256)
    pub = SnapshotPublisher(store, retain=steps) if publish_every else None
    tr = FOEMTrainer(cfg, store, seed=0, prefetch_depth=depth, faults=faults,
                     publisher=pub, publish_every=publish_every)
    mbs = []

    def feed():
        for mb in _tier_stream():
            mbs.append(mb)
            yield mb

    with _fit_rule(fits):
        ms = tr.fit_stream(feed(), max_steps=steps,
                           callback=watch(store) if watch else None)
    return store, ms, (pub._snaps if pub else []), mbs, tr


def _assert_same_training(a, b):
    (sa, ma), (sb, mb) = a[:2], b[:2]
    np.testing.assert_array_equal(sa.dense_phi(), sb.dense_phi())
    np.testing.assert_array_equal(sa.phi_k, sb.phi_k)
    assert [m.sweeps for m in ma] == [m.sweeps for m in mb]
    np.testing.assert_array_equal([m.train_ppl for m in ma],   # nan: dropped
                                  [m.train_ppl for m in mb])


def _assert_same_reads(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("depth", [0, 1])
def test_tier_matches_streamed_bitwise(tmp_path, depth):
    """Rows in the tier or streamed from the host store: bitwise the same
    rows, totals, sweeps and train perplexities, over two W_s buckets; the
    tier's steps copy only ids, counts and totals."""
    tier = _tier_run(tmp_path / "tier", depth, True)
    streamed = _tier_run(tmp_path / "streamed", depth, False)
    _assert_same_training(tier, streamed)
    store, ms, _, mbs, _ = tier
    assert len({_bucket(m.rows) for m in ms}) == 2
    assert all(m.tier_rows == m.rows > 0 for m in ms)
    assert sum(m.tier_uploads for m in ms) == 0      # a new store: no copy
    assert all(m.tier_rows == 0 for m in streamed[1])
    assert not store.has_tier                        # fit_stream detached it
    for m, mb in zip(ms, mbs):
        w_pad = _bucket(m.rows) * VOCAB_BUCKET
        assert m.h2d_bytes == 2 * mb.word_ids.size * 4 + w_pad * 4 + TK * 4
        assert m.d2h_bytes == TK * 4 + 3 * 4


@pytest.mark.parametrize("depth", [0, 1])
def test_tier_reads_are_coherent(tmp_path, depth):
    """``fetch_rows``, ``flush`` (read back through a reopened store),
    ``dense_phi`` and ``publish`` (its rows and ``changed_ids``) read what
    the tier holds, step by step; a resumed store fills the tier again."""
    ids = np.arange(TV)
    runs = []
    for name, fits in (("tier", True), ("streamed", False)):
        reads = []

        def watch(store, path=tmp_path / name, reads=reads):
            def cb(m):
                reads.append(store.fetch_rows(ids, promote=False))
                if m.step == 4:
                    store.flush()
                    back = ParameterStore.attach(str(path), TK, TV)
                    reads.extend([np.int64(back.step), back.dense_phi(),
                                  back.phi_k])
                if m.step == 6:
                    reads.append(store.dense_phi())
            return cb

        runs.append((_tier_run(tmp_path / name, depth, fits, watch=watch,
                               publish_every=2), reads))
    (tier, t_reads), (streamed, s_reads) = runs
    _assert_same_training(tier, streamed)
    _assert_same_reads(t_reads, s_reads)
    assert len(t_reads) == 8 + 3 + 1 and t_reads[4] == 4
    assert [s.version for s in tier[2]] == [1, 2, 3, 4]
    for a, b in zip(tier[2], streamed[2]):
        np.testing.assert_array_equal(a.phi, b.phi)
        np.testing.assert_array_equal(a.changed_ids, b.changed_ids)
        assert len(a.changed_ids) > 0

    # resume on the reopened stores: the tier is filled from the host tier
    cfg = LDAConfig(num_topics=TK, vocab_size=TV, max_sweeps=4)
    out = []
    for name, fits in (("tier", True), ("streamed", False)):
        store = ParameterStore(str(tmp_path / name), TK, TV, buffer_rows=256)
        tr = FOEMTrainer(cfg, store, seed=1, prefetch_depth=depth)
        assert tr.resume_step() == 8
        with _fit_rule(fits):
            ms = tr.fit_stream(iter(_tier_stream()), max_steps=2)
        out.append((store, ms))
    _assert_same_training(*out)
    assert [m.tier_uploads for m in out[0][1]] == [TV, 0]


@pytest.mark.parametrize("depth", [0, 1])
def test_post_fold_drop_leaves_tier_untouched(tmp_path, depth):
    """A ``POST_FOLD`` drop discards the step's fold: the tier's rows and
    the totals read as before the step, and the run matches the streamed
    run with the same drop."""
    ids = np.arange(TV)
    runs = []
    for name, fits in (("tier", True), ("streamed", False)):
        plan = faults.FaultPlan([faults.FaultSpec(faults.POST_FOLD, "drop",
                                                  step=3)])
        reads = []

        def watch(store, reads=reads):
            def cb(m):
                reads.append((m.sweeps, store.fetch_rows(ids, promote=False),
                              store.phi_k.copy()))
            return cb

        runs.append((_tier_run(tmp_path / name, depth, fits, steps=6,
                               watch=watch, faults=plan), reads))
    (tier, t_reads), (streamed, s_reads) = runs
    _assert_same_training(tier, streamed)
    assert [r[0] == 0 for r in t_reads] == [False] * 3 + [True] + [False] * 2
    _assert_same_reads(t_reads[3][1:], t_reads[2][1:])
    for a, b in zip(t_reads, s_reads):
        _assert_same_reads(a[1:], b[1:])
    assert tier[1][3].tier_rows == 0


@pytest.mark.parametrize("outcome", ["no_fit", "later_bucket"])
def test_fit_rule_outcomes(tmp_path, caplog, outcome):
    """No fit: the trainer streams rows and never attaches the tier.  A
    later, larger W_s bucket that does not fit (by the compiled step's
    memory): the tier detaches with its rows written back, the trainer
    streams from then on, and the run stays bitwise the streamed one.
    Reading the step's memory compiles the program the steps then run,
    on the tier and streamed alike: one compile per bucket."""
    asked = []

    def later_bucket(device, table_bytes, step_bytes):
        asked.append(step_bytes())
        return asked[-1] <= asked[0]

    attached = []

    def watch(store):
        return lambda m: attached.append(store.has_tier)

    rule = False if outcome == "no_fit" else later_bucket
    with jax.log_compiles(True), caplog.at_level(logging.WARNING, "jax"):
        run = _tier_run(tmp_path / "run", 1, rule, watch=watch)
    steps_compiled = [r for r in caplog.records
                      if r.getMessage().startswith("Compiling jit(run)")]
    streamed = _tier_run(tmp_path / "streamed", 1, False)
    _assert_same_training(run, streamed)
    ms = run[1]
    assert len(steps_compiled) == len({_bucket(m.rows) for m in ms}) == 2
    if outcome == "no_fit":
        assert not any(attached)
        assert all(m.tier_rows == 0 for m in ms)
        return
    first_big = [_bucket(m.rows) for m in ms].index(2)
    assert first_big > 0 and len(asked) == 2 and asked[1] > asked[0]
    assert attached == [True] * first_big + [False] * (len(ms) - first_big)
    assert [m.tier_rows > 0 for m in ms] == attached


def test_device_tier_fits_rule():
    """The fit rule reads the step's bytes only where the device states a
    limit, and leaves ``TIER_MARGIN`` of it free."""
    class Dev:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    def never():
        raise AssertionError("no limit: the step's memory is not read")

    fits = trainer_mod.device_tier_fits
    assert fits(Dev(None), 10 ** 12, never)
    assert fits(Dev({"bytes_in_use": 0}), 10 ** 12, never)
    limit = 16 * 10 ** 9
    room = int(limit * (1 - trainer_mod.TIER_MARGIN))
    assert fits(Dev({"bytes_limit": limit}), room - 100, lambda: 100)
    assert not fits(Dev({"bytes_limit": limit}), room - 100, lambda: 101)
