"""ParameterStore: the paper's §3.2 parameter streaming + fault tolerance."""
import os

import numpy as np
import pytest

from repro.core.streaming import ParameterStore


def _mk(tmp_path, buffer_rows=0, K=8, W=100):
    return ParameterStore(str(tmp_path), num_topics=K, vocab_capacity=W,
                          buffer_rows=buffer_rows)


def test_roundtrip_unbuffered(tmp_path):
    st = _mk(tmp_path)
    ids = np.array([3, 7, 42])
    rows = np.arange(24, dtype=np.float32).reshape(3, 8)
    st.write_rows(ids, rows)
    out = st.fetch_rows(ids)
    np.testing.assert_allclose(out, rows)
    assert st.stats.disk_writes == 3 and st.stats.disk_reads == 3


def test_buffer_hits_and_eviction(tmp_path):
    st = _mk(tmp_path, buffer_rows=2)
    ids = np.array([1, 2, 3])                  # 3 rows through a 2-row buffer
    st.write_rows(ids, np.ones((3, 8), np.float32))
    assert st.stats.evictions == 1             # LRU evicted row 1
    st.stats.reset()
    st.fetch_rows(np.array([2, 3]))            # both still buffered
    assert st.stats.buffer_hits == 2 and st.stats.disk_reads == 0
    st.fetch_rows(np.array([1]))               # evicted -> disk
    assert st.stats.disk_reads == 1


def test_io_decreases_with_buffer(tmp_path):
    """Table 5's invariant: bigger buffer ⇒ fewer backing-store accesses."""
    rng = np.random.default_rng(0)
    seq = [rng.choice(60, size=20, replace=False) for _ in range(12)]
    totals = {}
    for buf in (0, 16, 64):
        st = ParameterStore(str(tmp_path / f"b{buf}"), num_topics=4,
                            vocab_capacity=64, buffer_rows=buf)
        for ids in seq:
            rows = st.fetch_rows(ids)
            st.write_rows(ids, rows + 1)
        totals[buf] = st.stats.disk_reads + st.stats.disk_writes
    assert totals[0] > totals[16] > totals[64]
    assert totals[64] <= 64 * 2   # at most one read per distinct row (+ none written yet)


def test_flush_restart_restores_state(tmp_path):
    st = _mk(tmp_path, buffer_rows=4)
    ids = np.array([5, 6])
    st.write_rows(ids, np.full((2, 8), 3.0, np.float32))
    st.phi_k = np.full(8, 1.5)
    st.step = 17
    st.ensure_vocab(6)
    st.flush()
    st2 = _mk(tmp_path, buffer_rows=4)
    np.testing.assert_allclose(st2.fetch_rows(ids), 3.0)
    np.testing.assert_allclose(st2.phi_k, 1.5)
    assert st2.step == 17 and st2.live_vocab == 7


def test_dirty_rows_survive_crash_after_flush(tmp_path):
    st = _mk(tmp_path, buffer_rows=8)
    st.write_rows(np.array([1]), np.full((1, 8), 9.0, np.float32))
    st.flush()
    del st                                      # simulated crash
    st2 = _mk(tmp_path, buffer_rows=0)
    np.testing.assert_allclose(st2.fetch_rows(np.array([1])), 9.0)


@pytest.mark.parametrize("buffer_rows", [0, 4])
def test_device_tier_is_one_store_with_the_host_tier(tmp_path, buffer_rows):
    """The device row tier and the host tier read as one store: attach
    fills the tier from the host (no copy for a store never written), a
    tier write reaches the host only through a read or flush, a host write
    reaches the tier, and detach writes back what is left."""
    import jax.numpy as jnp

    from repro.core.streaming import tier_ids

    K, W = 8, 100
    fresh = _mk(tmp_path / "fresh", buffer_rows, K, W)
    assert fresh.attach_tier() == 0
    fresh.detach_tier()

    st = _mk(tmp_path / "st", buffer_rows, K, W)
    rng = np.random.default_rng(0)
    base = rng.random((W, K), dtype=np.float32)
    st.write_rows(np.arange(W), base)
    assert st.attach_tier() == W and st.has_tier
    ids = np.array([5, 17, 3, 60, 9, 88])
    dev_ids = jnp.asarray(tier_ids(ids, W))
    got = np.asarray(st.tier_gather(dev_ids))
    np.testing.assert_array_equal(got[: len(ids)], base[ids])
    assert not got[len(ids):].any()                  # padding reads zeros

    new = rng.random((len(dev_ids), K), dtype=np.float32)
    st.take_changed()
    v = st.write_version
    assert st.tier_write(ids, dev_ids, jnp.asarray(new)) == v + 1
    np.testing.assert_array_equal(st._read_rows(ids), base[ids])   # behind
    np.testing.assert_array_equal(st.take_changed(), np.sort(ids))
    # a read writes back the dirty rows it covers, and only those
    np.testing.assert_array_equal(st.fetch_rows(ids[:2]), new[:2])
    np.testing.assert_array_equal(st._read_rows(ids[2:]), base[ids[2:]])
    # a host write reaches the tier and is not overwritten by it
    st.write_rows(ids[2:4], np.full((2, K), 7.0, np.float32))
    np.testing.assert_array_equal(
        np.asarray(st.tier_gather(dev_ids))[2:4], np.full((2, K), 7.0))
    want = base.copy()
    want[ids] = new[: len(ids)]
    want[ids[2:4]] = 7.0
    st.flush()
    back = ParameterStore.attach(str(tmp_path / "st"), K, W)
    np.testing.assert_array_equal(back._read_rows(np.arange(W)), want)
    st.tier_write(ids, dev_ids, jnp.asarray(new + 1.0))
    st.detach_tier()
    assert not st.has_tier
    want[ids] = new[: len(ids)] + 1.0
    np.testing.assert_array_equal(st._read_rows(np.arange(W)), want)


def test_vocab_watermark_and_capacity(tmp_path):
    st = _mk(tmp_path)
    st.ensure_vocab(50)
    assert st.live_vocab == 51
    with pytest.raises(ValueError):
        st.ensure_vocab(100)                    # beyond capacity

def test_rows_for_bytes():
    assert ParameterStore.rows_for_bytes(1000, 4_000_000) == 1000


# ---------------------------------------------------------------------------
# Vectorized-store specifics: per-row equivalence, batched LRU order,
# insert-on-read, prefetch pipeline.
# ---------------------------------------------------------------------------


class _PerRowReference:
    """Per-row LRU oracle for the vectorized store: ordered-dict recency,
    write-back dirty eviction, insert-on-read promotion.  A batch is atomic
    ("up to batching"): residents are looked up / bumped first, then the
    batch's new rows are inserted row by row — so a row never gets evicted
    by its own batch before being served."""

    def __init__(self, K, cap, buffer_rows):
        from collections import OrderedDict

        self.K, self.buffer_rows = K, buffer_rows
        self.disk = np.zeros((cap, K), np.float32)
        self.buf = OrderedDict()          # id -> (row, dirty)
        self.reads = self.writes = self.hits = self.evict = 0

    def _insert(self, w, row, dirty):
        assert w not in self.buf
        self.buf[w] = (row.copy(), dirty)
        if len(self.buf) > self.buffer_rows:
            wv, (r, d) = self.buf.popitem(last=False)
            if d:
                self.disk[wv] = r
                self.writes += 1
            self.evict += 1

    def fetch(self, ids):
        out = np.empty((len(ids), self.K), np.float32)
        missed = []
        for i, w in enumerate(ids):
            w = int(w)
            if w in self.buf:
                out[i] = self.buf[w][0]
                self.buf.move_to_end(w)
                self.hits += 1
            else:
                out[i] = self.disk[w]
                self.reads += 1
                missed.append((w, out[i]))
        if self.buffer_rows:
            for w, row in missed:
                self._insert(w, row, dirty=False)
        return out

    def write(self, ids, rows):
        if not self.buffer_rows:
            for i, w in enumerate(ids):
                self.disk[int(w)] = rows[i]
                self.writes += 1
            return
        fresh = []
        for i, w in enumerate(ids):
            w = int(w)
            if w in self.buf:
                self.buf[w] = (np.asarray(rows[i]).copy(), True)
                self.buf.move_to_end(w)
            else:
                fresh.append((w, np.asarray(rows[i])))
        for w, row in fresh:
            self._insert(w, row, dirty=True)

    def dense(self):
        for w, (r, d) in self.buf.items():
            if d:
                self.disk[w] = r
                self.writes += 1
        return self.disk


@pytest.mark.parametrize("buf", [0, 7, 32])
def test_vectorized_matches_perrow_reference(tmp_path, buf):
    """Random mixed fetch/write workload: values, stats and final state of
    the batched store must equal the per-row LRU reference exactly."""
    K, W = 4, 64
    rng = np.random.default_rng(buf + 1)
    st = ParameterStore(str(tmp_path / f"v{buf}"), num_topics=K,
                        vocab_capacity=W, buffer_rows=buf)
    ref = _PerRowReference(K, W, buf)
    for it in range(25):
        ids = np.unique(rng.choice(W, rng.integers(1, 20), replace=False))
        got = st.fetch_rows(ids)
        want = ref.fetch(ids)
        np.testing.assert_array_equal(got, want)
        new = rng.normal(size=(len(ids), K)).astype(np.float32)
        st.write_rows(ids, new)
        ref.write(ids, new)
    assert st.stats.disk_reads == ref.reads
    assert st.stats.buffer_hits == ref.hits
    assert st.stats.evictions == ref.evict
    assert st.stats.disk_writes == ref.writes
    np.testing.assert_array_equal(st.dense_phi(), ref.dense()[:st.live_vocab or 1])


def test_lru_eviction_order_batched(tmp_path):
    """Batched access must preserve per-row LRU recency: within a batch,
    later ids are more recent; a hit refreshes recency."""
    st = _mk(tmp_path, buffer_rows=3)
    st.write_rows(np.array([1, 2, 3]), np.ones((3, 8), np.float32))
    st.fetch_rows(np.array([1]))               # bump 1 → LRU order now 2,3,1
    st.write_rows(np.array([4]), np.ones((1, 8), np.float32))  # evicts 2
    st.stats.reset()
    st.fetch_rows(np.array([1, 3, 4]))
    assert st.stats.buffer_hits == 3 and st.stats.disk_reads == 0
    st.fetch_rows(np.array([2]))
    assert st.stats.disk_reads == 1            # 2 was the evicted one


def test_insert_on_read_promotes_rows(tmp_path):
    """satellite: a read-heavy stream must accumulate buffer hits — rows
    read from disk are promoted into the hot buffer (clean)."""
    st = _mk(tmp_path, buffer_rows=8)
    ids = np.array([3, 9, 27])
    st.fetch_rows(ids)                          # cold: all disk
    assert st.stats.disk_reads == 3 and st.stats.buffer_hits == 0
    st.stats.reset()
    for _ in range(5):
        st.fetch_rows(ids)                      # warm: all buffer
    assert st.stats.buffer_hits == 15 and st.stats.disk_reads == 0
    # promoted rows are clean: eviction must not write them back
    st.write_rows(np.arange(8, dtype=np.int64) + 40,
                  np.ones((8, 8), np.float32))  # flood the buffer
    assert st.stats.disk_writes == 0            # only clean rows evicted


def test_fetch_write_roundtrip_large_batch_through_small_buffer(tmp_path):
    """Batch larger than W*: overflow spills to disk; values survive."""
    st = _mk(tmp_path, buffer_rows=4)
    ids = np.arange(20, dtype=np.int64)
    rows = np.arange(20 * 8, dtype=np.float32).reshape(20, 8)
    st.write_rows(ids, rows)
    np.testing.assert_array_equal(st.fetch_rows(ids), rows)
    st.flush()
    st2 = _mk(tmp_path, buffer_rows=0)          # restart: values on disk
    np.testing.assert_array_equal(st2.fetch_rows(ids), rows)


def test_versioned_fetch_orders_writes(tmp_path):
    st = _mk(tmp_path, buffer_rows=4)
    _, v0 = st.fetch_rows_versioned(np.array([1]))
    v1 = st.write_rows(np.array([1]), np.ones((1, 8), np.float32))
    _, v2 = st.fetch_rows_versioned(np.array([1]))
    assert v0 < v1 <= v2


def test_fetch_beyond_capacity_raises_explanatory_error(tmp_path):
    st = _mk(tmp_path, buffer_rows=4)
    with pytest.raises(ValueError, match="exceeds store capacity"):
        st.fetch_rows(np.array([150]))          # capacity is 100


def test_promotion_counter_and_stats_window(tmp_path):
    """satellite: promotions are counted once per disk-read row, and
    stats_window() gives a reset-able per-batch view without disturbing
    the cumulative counters."""
    st = _mk(tmp_path, buffer_rows=8)
    st.fetch_rows(np.array([1, 2, 3]))           # cold: 3 promotions
    assert st.stats.promotions == 3
    win = st.stats_window(reset=True)
    assert win.promotions == 3 and win.disk_reads == 3
    st.fetch_rows(np.array([1, 2, 3]))           # warm: no promotion
    win = st.stats_window(reset=True)
    assert win.promotions == 0 and win.buffer_hits == 3
    # the window reset did not zero anything mid-flight: counters add up
    assert st.stats_window().buffer_hits == 0


def test_fetch_rows_promote_false_reads_without_caching(tmp_path):
    """satellite fix: serving reads (promote=False) must not insert into
    the LRU buffer — the old insert-on-read double-counted rows already
    held by the serving-side hot cache."""
    st = _mk(tmp_path, buffer_rows=8)
    vals = st.fetch_rows(np.array([5, 6]), promote=False)
    assert st.stats.promotions == 0
    st.stats.reset()
    st.fetch_rows(np.array([5, 6]), promote=False)
    assert st.stats.disk_reads == 2              # still cold: never cached
    assert st.stats.buffer_hits == 0
    # versioned variant honours the flag too
    _, ver = st.fetch_rows_versioned(np.array([5, 6]), promote=False)
    assert st.stats.promotions == 0 and ver == st.write_version
    np.testing.assert_array_equal(vals, st.fetch_rows(np.array([5, 6])))
    assert st.stats.promotions == 2              # default path still promotes


# ---------------------------------------------------------------------------
# Readonly attach: the replica-pool workers' view of a store they don't own.
# Attach must read the committed state (including a committed-but-unretired
# WAL, overlaid in memory only) and must never mutate the backing files.
# ---------------------------------------------------------------------------


def test_attach_reads_flushed_state_readonly(tmp_path):
    st = ParameterStore(str(tmp_path), num_topics=4, vocab_capacity=32,
                        buffer_rows=8)
    ids = np.arange(10, dtype=np.int32)
    rows = np.random.default_rng(0).random((10, 4)).astype(np.float32)
    st.ensure_vocab(9)
    st.write_rows(ids, rows)
    st.flush()

    ro = ParameterStore.attach(str(tmp_path), num_topics=4,
                               vocab_capacity=32)
    assert ro.readonly and ro.live_vocab == 10
    np.testing.assert_allclose(ro.fetch_rows(ids), rows)
    dp = ro.dense_phi()
    assert dp.shape == (10, 4)
    np.testing.assert_allclose(dp, rows)
    # every mutator is fenced off
    with pytest.raises(PermissionError):
        ro.write_rows(ids[:1], rows[:1])
    with pytest.raises(PermissionError):
        ro.flush()


def test_attach_overlays_committed_wal_without_touching_disk(tmp_path):
    """A committed-but-unretired WAL (owner crashed between COMMIT and
    apply) must be visible to an attached reader — overlaid in memory:
    the memmap bytes and the WAL file itself stay untouched, so the
    owner's own crash recovery still replays it later."""
    from repro.core import streaming as streaming_mod

    st = ParameterStore(str(tmp_path), num_topics=4, vocab_capacity=32,
                        buffer_rows=8)
    ids = np.arange(10, dtype=np.int32)
    rows = np.random.default_rng(0).random((10, 4)).astype(np.float32)
    st.ensure_vocab(9)
    st.write_rows(ids, rows)
    st.flush()

    # stage a second version up to (and including) the WAL COMMIT rename,
    # but crash before the memmap apply: flush steps 1-2 only
    rows2 = (rows + 1.0).astype(np.float32)
    st.write_rows(ids, rows2)
    with st._lock:
        dirty = np.flatnonzero(st._buf_dirty)
        d_ids = st._buf_ids[dirty]
        order = np.argsort(d_ids)
        d_ids = d_ids[order]
        d_rows = st._buf[dirty[order]]
        streaming_mod._write_record(
            st._wal_path() + ".tmp",
            {"ids": d_ids, "rows": d_rows, "phi_k": st.phi_k},
            st._manifest_payload(version=st.flush_version + 1))
        os.replace(st._wal_path() + ".tmp", st._wal_path())

    mmap_path = str(tmp_path / "phi_wk.mmap")
    with open(mmap_path, "rb") as f:
        pre = f.read()

    ro = ParameterStore.attach(str(tmp_path), num_topics=4,
                               vocab_capacity=32)
    assert ro.recovered_from_wal
    np.testing.assert_allclose(ro.fetch_rows(d_ids), d_rows)
    # the overlay is memory-only: WAL still present, memmap bit-identical
    assert os.path.exists(st._wal_path())
    with open(mmap_path, "rb") as f:
        assert f.read() == pre


# ---------------------------------------------------------------------------
# Concurrency: windowed-stats races, and hypothesis property tests for the
# versioning protocol (write_version monotonicity, versioned reconciliation,
# epoch cache coherence).
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st_
    HAVE_HYPOTHESIS = True
except ImportError:                               # CI installs it; local
    HAVE_HYPOTHESIS = False                       # runs skip gracefully

    def given(**_kw):                             # no-op stand-ins so the
        return lambda f: f                        # decorated tests still

    def settings(**_kw):                          # collect (and then skip)
        return lambda f: f

    class st_:                                    # noqa: N801
        @staticmethod
        def none():
            return None

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")


def test_stats_window_reset_is_race_free(tmp_path):
    """Regression for the windowed-stats race: a fetcher thread hammering
    fetch_rows while the main thread drains stats_window(reset=True) must
    conserve every access — the drained windows plus the final window sum
    to exactly one count per fetched row (reads + hits, no loss, no
    double-count from the read-modify-reset)."""
    import sys
    import threading

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        stc = _mk(tmp_path, buffer_rows=8)
        n_fetches, batch = 400, 5
        done = threading.Event()

        def fetcher():
            rng = np.random.default_rng(0)
            for _ in range(n_fetches):
                stc.fetch_rows(rng.integers(0, 50, batch).astype(np.int64))
            done.set()

        th = threading.Thread(target=fetcher)
        reads = hits = 0
        th.start()
        while not done.is_set():
            win = stc.stats_window(reset=True)
            reads += win.disk_reads
            hits += win.buffer_hits
        th.join()
        win = stc.stats_window(reset=True)
        reads += win.disk_reads
        hits += win.buffer_hits
        assert reads + hits == n_fetches * batch
    finally:
        sys.setswitchinterval(old_interval)


def test_hot_row_cache_window_stats_race_free(tmp_path):
    """Same conservation law for HotRowCache's windowed CacheStats."""
    import sys
    import threading

    from repro.core import HotRowCache

    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        stc = _mk(tmp_path, buffer_rows=0)
        stc.write_rows(np.arange(50), np.ones((50, 8), np.float32))
        cache = HotRowCache(stc, capacity=16)
        n_fetches, batch = 400, 5
        done = threading.Event()

        def fetcher():
            rng = np.random.default_rng(1)
            for _ in range(n_fetches):
                cache.fetch(rng.integers(0, 50, batch).astype(np.int64))
            done.set()

        th = threading.Thread(target=fetcher)
        total = 0
        th.start()
        while not done.is_set():
            win = cache.window_stats(reset=True)
            total += win.hits + win.misses
        th.join()
        win = cache.window_stats(reset=True)
        total += win.hits + win.misses
        assert total == n_fetches * batch
        cache.reset_stats()
        assert cache.stats.hits == 0 and cache.stats.misses == 0
    finally:
        sys.setswitchinterval(old_interval)


if HAVE_HYPOTHESIS:
    _ids_st = st_.lists(st_.integers(0, 39), min_size=1, max_size=8,
                        unique=True)
    _ops_st = st_.lists(
        st_.tuples(st_.booleans(), _ids_st), min_size=1, max_size=24)
    _rounds_st = st_.lists(_ids_st, min_size=1, max_size=10)


@needs_hypothesis
@settings(max_examples=30, deadline=None)
@given(ops=_ops_st if HAVE_HYPOTHESIS else st_.none())
def test_write_version_monotone_and_counts_writes(ops):
    """write_version is monotone nondecreasing, bumps on every write_rows
    (exactly once per call), and never moves on a read."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        stc = ParameterStore(os.path.join(d, "p"), num_topics=4,
                             vocab_capacity=40, buffer_rows=4)
        last = stc.write_version
        writes = 0
        for is_write, ids in ops:
            a = np.asarray(ids, np.int64)
            if is_write:
                v = stc.write_rows(a, np.ones((len(a), 4), np.float32))
                writes += 1
                assert v > last
            else:
                _, v = stc.fetch_rows_versioned(a)
                assert v == last
            assert v >= last
            last = v
        assert stc.write_version == writes


@needs_hypothesis
@settings(max_examples=30, deadline=None)
@given(ops=_ops_st if HAVE_HYPOTHESIS else st_.none())
def test_versioned_fetch_reconciles_to_fresh_state(ops):
    """The reconciliation protocol: take a versioned fetch, apply every
    LATER write on top of it, and the patched view must equal a fresh
    fetch — the version totally orders writes against reads."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        stc = ParameterStore(os.path.join(d, "p"), num_topics=4,
                             vocab_capacity=40, buffer_rows=4)
        base_ids = np.arange(40, dtype=np.int64)
        snap, v0 = stc.fetch_rows_versioned(base_ids)
        view = snap.copy()
        for i, (is_write, ids) in enumerate(ops):
            a = np.asarray(ids, np.int64)
            if is_write:
                rows = np.full((len(a), 4), float(i + 1), np.float32)
                v = stc.write_rows(a, rows)
                assert v > v0          # later write: must patch the view
                view[a] = rows
            else:
                stc.fetch_rows(a)      # reads don't perturb the protocol
        np.testing.assert_array_equal(view, stc.fetch_rows(base_ids))


@needs_hypothesis
@settings(max_examples=25, deadline=None)
@given(rounds=_rounds_st if HAVE_HYPOTHESIS else st_.none())
def test_epoch_cache_never_serves_stale_rows(rounds):
    """Per-version epoch invalidation: interleave writes, publishes and
    cached fetches arbitrarily — a version-pinned fetch through the cache
    must ALWAYS equal the snapshot's own rows, never a stale resident."""
    import tempfile

    from repro.core import HotRowCache, SnapshotPublisher

    with tempfile.TemporaryDirectory() as d:
        stc = ParameterStore(os.path.join(d, "p"), num_topics=4,
                             vocab_capacity=40, buffer_rows=0)
        stc.write_rows(np.arange(40),
                       np.zeros((40, 4), np.float32))
        pub = SnapshotPublisher(stc, retain=2)
        snap = pub.publish()
        cache = HotRowCache(stc, capacity=8)
        cache.install_version(snap.version, changed_ids=snap.changed_ids)
        for i, ids in enumerate(rounds):
            a = np.asarray(ids, np.int64)
            if i % 2 == 1:             # odd rounds mutate + republish
                stc.write_rows(a, np.full((len(a), 4), float(i),
                                          np.float32))
                snap = pub.publish()
                cache.install_version(snap.version,
                                      changed_ids=snap.changed_ids)
            got = cache.fetch(a, source=snap, version=snap.version)
            np.testing.assert_array_equal(got, snap.fetch_rows(a))
            # and the cache's residents agree with the snapshot wholesale
            resident = np.arange(40, dtype=np.int64)
            np.testing.assert_array_equal(
                cache.fetch(resident, source=snap, version=snap.version),
                snap.fetch_rows(resident))
