"""Replica-grade tests for the multi-replica serving pool (PR 10).

Three layers, cheapest first:

* **Balancer properties** — the pure :class:`ReplicaBalancer` accounting
  under hypothesis-generated op interleavings: in-flight never negative,
  the per-replica cap is respected, acquire is least-loaded with
  smallest-id tie-break, and φ version notes are monotone.
* **Cross-replica determinism** (thread backend) — the same document
  resolves to a bitwise-identical θ̂ whether it lands on replica 0,
  replica 3, or a single-replica :class:`ServingEngine`, because
  per-document PRNG keys make placement semantically invisible at
  ``rel_tol=0``.
* **Replica-kill chaos** (process backend, marked ``slow``) — Zipf
  traffic into a pool whose :class:`FaultPlan` SIGKILLs a worker
  mid-flight: every Future still resolves, re-issued batches match the
  unfaulted run bitwise, and the pool respawns back to strength.
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.core import LDAConfig, ParameterStore
from repro.core.streaming import SnapshotPublisher
from repro.launch.replica import ReplicaBalancer, ReplicaPool, ReplicaSpec
from repro.launch.serve import ServingEngine, TopicServer, TrafficGenerator
from repro.runtime.faults import FaultSpec, REPLICA_KILL

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


# ---------------------------------------------------------------------------
# ReplicaBalancer: deterministic unit tests
# ---------------------------------------------------------------------------


def test_balancer_least_loaded_with_smallest_id_ties():
    b = ReplicaBalancer(cap=2)
    for rid in (3, 1, 7):
        b.add(rid)
    assert b.acquire() == 1            # all tied at 0 -> smallest id
    assert b.acquire() == 3            # 1 now loaded, next smallest
    assert b.acquire() == 7
    assert b.acquire() == 1            # round 2, still least-loaded order
    b.complete(7)
    assert b.acquire() == 7            # 7 dropped back below the others


def test_balancer_cap_and_negative_accounting():
    b = ReplicaBalancer(cap=1)
    b.add(0)
    assert b.acquire() == 0
    assert b.acquire() is None         # at cap: caller must wait
    assert not b.acquire_specific(0)
    b.complete(0)
    with pytest.raises(ValueError):    # idle replica: would go negative
        b.complete(0)
    with pytest.raises(KeyError):
        b.complete(99)
    with pytest.raises(ValueError):
        b.add(0)                       # double registration


def test_balancer_remove_returns_orphans_and_respawn_keeps_version_floor():
    b = ReplicaBalancer(cap=4)
    b.add(0)
    b.add(1)
    for _ in range(3):
        b.acquire_specific(1)
    b.note_version(1, 5)
    assert b.remove(1) == 3            # three in-flight batches orphaned
    assert b.replicas() == [0]
    b.add(1)                           # respawned replacement
    assert b.inflight(1) == 0
    with pytest.raises(ValueError):    # version floor survives the respawn:
        b.note_version(1, 4)           # the replacement swaps to latest first
    b.note_version(1, 5)               # equal is fine (idempotent swap ack)
    b.note_version(1, 6)


def test_balancer_version_ledger():
    b = ReplicaBalancer(cap=2)
    b.add(0)
    b.add(1)
    assert b.min_version() == -1
    b.note_version(0, 3)
    assert b.versions() == {0: 3, 1: -1}
    assert b.min_version() == -1
    b.note_version(1, 2)
    assert b.min_version() == 2
    with pytest.raises(ValueError):
        b.note_version(0, 1)


# ---------------------------------------------------------------------------
# ReplicaBalancer: hypothesis property tests
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    # op stream over a small id space: (op, rid) with op in
    # add / acquire / acquire_specific / complete / remove
    _ops_st = st_.lists(
        st_.tuples(st_.sampled_from(["add", "acq", "acq_at", "done", "rm"]),
                   st_.integers(0, 4)),
        min_size=1, max_size=60)
    _caps_st = st_.integers(1, 3)
    _notes_st = st_.lists(
        st_.tuples(st_.integers(0, 3), st_.integers(0, 20)),
        min_size=1, max_size=40)


@needs_hypothesis
@settings(max_examples=60, deadline=None)
@given(cap=_caps_st if HAVE_HYPOTHESIS else st_.none(),
       ops=_ops_st if HAVE_HYPOTHESIS else st_.none())
def test_balancer_inflight_bounded_and_least_loaded(cap, ops):
    """Under any interleaving of membership/dispatch ops the balancer
    keeps every in-flight count in ``[0, cap]``, acquire only ever
    returns a least-loaded registered replica strictly under the cap,
    and the shadow model never diverges from the balancer's ledger."""
    b = ReplicaBalancer(cap=cap)
    model = {}                          # rid -> in-flight (shadow)
    for op, rid in ops:
        if op == "add":
            if rid in model:
                with pytest.raises(ValueError):
                    b.add(rid)
            else:
                b.add(rid)
                model[rid] = 0
        elif op == "acq":
            got = b.acquire()
            free = {r: n for r, n in model.items() if n < cap}
            if not free:
                assert got is None
            else:
                lo = min(free.values())
                assert got in free and free[got] == lo
                assert got == min(r for r, n in free.items() if n == lo)
                model[got] += 1
        elif op == "acq_at":
            ok = b.acquire_specific(rid)
            assert ok == (model.get(rid, cap) < cap)
            if ok:
                model[rid] += 1
        elif op == "done":
            if model.get(rid, 0) > 0:
                b.complete(rid)
                model[rid] -= 1
            elif rid in model:
                with pytest.raises(ValueError):
                    b.complete(rid)
            else:
                with pytest.raises(KeyError):
                    b.complete(rid)
        elif op == "rm":
            if rid in model:
                assert b.remove(rid) == model.pop(rid)
        # ledger never diverges, counts never escape [0, cap]
        assert b.replicas() == sorted(model)
        for r, n in model.items():
            assert 0 <= n <= cap
            assert b.inflight(r) == n
        assert b.total_inflight() == sum(model.values())


@needs_hypothesis
@settings(max_examples=60, deadline=None)
@given(notes=_notes_st if HAVE_HYPOTHESIS else st_.none())
def test_balancer_version_notes_monotone(notes):
    """φ version notes are accepted iff nondecreasing per replica; the
    ledger always holds the running per-replica maximum."""
    b = ReplicaBalancer(cap=2)
    high = {}
    for rid in range(4):
        b.add(rid)
        high[rid] = -1
    for rid, v in notes:
        if v < high[rid]:
            with pytest.raises(ValueError):
                b.note_version(rid, v)
        else:
            b.note_version(rid, v)
            high[rid] = v
        assert b.versions() == high
        assert b.min_version() == min(high.values())


# ---------------------------------------------------------------------------
# Serving fixtures: a small trained store shared across the pool tests
# ---------------------------------------------------------------------------

K, W = 8, 96


def _make_store(d: str) -> ParameterStore:
    store = ParameterStore(d, num_topics=K, vocab_capacity=W, buffer_rows=0)
    rng = np.random.default_rng(0)
    store.ensure_vocab(W - 1)
    store.write_rows(np.arange(W, dtype=np.int64),
                     rng.random((W, K)).astype(np.float32) + 0.1)
    store.phi_k[:] = store.dense_phi().sum(0)
    store.flush()
    return store


@pytest.fixture(scope="module")
def pool_store(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("replica_store"))
    _make_store(d)
    return d


@pytest.fixture(scope="module")
def pool_docs():
    rng = np.random.default_rng(42)
    docs = []
    for _ in range(24):
        n = int(rng.integers(4, 12))
        w = rng.choice(W, size=n, replace=False).astype(np.int32)
        c = rng.integers(1, 4, size=n).astype(np.float32)
        docs.append((w, c))
    return docs


def _spec(store_path, **kw):
    return ReplicaSpec(
        store_path=store_path, cfg=LDAConfig(num_topics=K, vocab_size=W),
        vocab_capacity=W, fit_sweeps=10, rel_tol=0.0, check_every=10,
        vocab_pad=32, hot_rows=16, **kw)


@pytest.fixture(scope="module")
def engine_ref(pool_store, pool_docs):
    """Single-replica ServingEngine reference answers (router seed 0)."""
    store = ParameterStore.attach(pool_store, num_topics=K, vocab_capacity=W)
    server = TopicServer(store, LDAConfig(num_topics=K, vocab_size=W), 10,
                         rel_tol=0.0, check_every=10, vocab_pad=32,
                         hot_rows=16)
    eng = ServingEngine(server, max_batch=8, max_delay_ms=2.0, max_len=64,
                        seed=0)
    try:
        futs = [eng.submit(w, c) for w, c in pool_docs]
        ref = [np.asarray(f.result(timeout=60)) for f in futs]
        eng.drain()
    finally:
        eng.close()
    return ref


# ---------------------------------------------------------------------------
# Cross-replica determinism (thread backend: device-mesh degenerate case)
# ---------------------------------------------------------------------------


def test_thread_pool_bitwise_matches_single_engine(pool_store, pool_docs,
                                                   engine_ref):
    """Least-loaded placement across 2 replicas is semantically invisible:
    every θ̂ is bitwise identical to the single-replica engine's answer
    (same router seed -> same per-document seq-XOR keys)."""
    with ReplicaPool(_spec(pool_store), replicas=2, backend="thread",
                     max_batch=8, max_delay_ms=2.0, max_len=64,
                     seed=0) as pool:
        pool.wait_ready(60)
        futs = [pool.submit(w, c) for w, c in pool_docs]
        got = [np.asarray(f.result(timeout=60)) for f in futs]
        pool.drain()
        m = pool.metrics()
    assert m["requests"] == len(pool_docs)
    assert m["replicas"] == 2 and m["deaths"] == 0
    assert sum(m["dispatch"].values()) == m["batches"]
    for i, (a, b) in enumerate(zip(engine_ref, got)):
        np.testing.assert_array_equal(a, b, err_msg=f"doc {i}")


def test_pinned_placement_parity_replica0_vs_replica3(pool_store, pool_docs,
                                                      engine_ref):
    """The same document pinned to replica 0 or to replica 3 of a
    4-replica pool resolves bitwise identically (and identically to the
    engine): placement carries no semantic content at rel_tol=0."""
    answers = {}
    for pin in (0, 3):
        with ReplicaPool(_spec(pool_store), replicas=4, backend="thread",
                         max_batch=8, max_delay_ms=2.0, max_len=64,
                         seed=0) as pool:
            pool.wait_ready(60)
            pool.pin_replica = pin
            futs = [pool.submit(w, c) for w, c in pool_docs]
            answers[pin] = [np.asarray(f.result(timeout=60)) for f in futs]
            pool.drain()
            m = pool.metrics()
        # pin actually forced placement: only `pin` got any batches
        assert {r for r, n in m["dispatch"].items() if n > 0} == {pin}
    for i in range(len(pool_docs)):
        np.testing.assert_array_equal(answers[0][i], answers[3][i],
                                      err_msg=f"doc {i} r0 vs r3")
        np.testing.assert_array_equal(answers[0][i], engine_ref[i],
                                      err_msg=f"doc {i} vs engine")


def test_thread_pool_hot_swap_versions_are_monotone(tmp_path, pool_docs):
    """Publishing φ versions mid-traffic hot-swaps every replica; the
    responses' version stamps only ever move forward and the pool's
    version ledger converges to the published version."""
    d = str(tmp_path / "swap_store")
    store = _make_store(d)
    pub = SnapshotPublisher(store)
    pub.publish()
    with ReplicaPool(_spec(d), replicas=2, backend="thread",
                     max_batch=4, max_delay_ms=1.0, max_len=64,
                     seed=0) as pool:
        pool.wait_ready(60)
        pool.subscribe(pub, refresh=True)
        seen = []
        for _round in range(3):
            futs = [pool.submit(w, c) for w, c in pool_docs[:8]]
            seen += [f.result(timeout=60).version for f in futs]
            pool.drain()
            pub.publish()
            deadline = time.monotonic() + 30
            while (min(pool.balancer.versions().values()) < pub.version
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        assert pool.balancer.versions() == {0: pub.version, 1: pub.version}
    # per-submission order isn't globally serialized across replicas, but
    # versions never exceed what was published and never precede the
    # subscribe-time snapshot
    assert all(1 <= v <= pub.version for v in seen)


# ---------------------------------------------------------------------------
# Engine close()/drain() idempotency under the pool's usage pattern
# ---------------------------------------------------------------------------


def test_pool_close_idempotent_and_concurrent(pool_store, pool_docs):
    """close() from many threads at once: all return, workers joined,
    and a submit afterwards raises the router's closed error."""
    pool = ReplicaPool(_spec(pool_store), replicas=2, backend="thread",
                       max_batch=8, max_delay_ms=2.0, max_len=64, seed=0)
    pool.wait_ready(60)
    futs = [pool.submit(w, c) for w, c in pool_docs[:6]]
    errs = []

    def closer():
        try:
            pool.close()
        except Exception as e:          # pragma: no cover - the assertion
            errs.append(e)

    threads = [threading.Thread(target=closer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert not errs
    for f in futs:                      # close resolves everything admitted
        assert np.asarray(f.result(timeout=1)).shape == (K,)
    with pytest.raises(RuntimeError, match="closed"):
        pool.submit(pool_docs[0][0], pool_docs[0][1])
    pool.close()                        # idempotent second (fifth) close


# ---------------------------------------------------------------------------
# Replica-kill chaos (process backend) — slow: ~2s/worker spawn cost
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_process_pool_kill_reissue_bitwise_parity(pool_store):
    """SIGKILL a worker mid-flight under Zipf/Poisson traffic at a
    4-replica process pool: every Future resolves, the dead replica's
    in-flight batches are re-issued bitwise-identically (same padded
    payload, same per-document keys), the pool respawns back to 4, and
    post-kill throughput recovers (requests keep resolving after the
    death at a nonzero rate)."""
    gen = TrafficGenerator(W, doc_len=(4, 12), seed=7)
    trace = gen.trace([(500.0, 48)])

    def run(fault_specs):
        spec = _spec(pool_store, fault_specs=fault_specs)
        with ReplicaPool(spec, replicas=4, backend="process", max_batch=8,
                         max_delay_ms=2.0, max_len=64, seed=0) as pool:
            pool.wait_ready(180)
            futs = TrafficGenerator.replay(trace, pool.submit, pace=True)
            got = [np.asarray(f.result(timeout=240)) for f in futs]
            pool.drain()
            m = pool.metrics()
        return got, m

    ref, m0 = run(())
    assert m0["deaths"] == 0 and m0["respawns"] == 0

    kill = (FaultSpec(point=REPLICA_KILL, kind="kill", step=0, shard=0,
                      hard=True),)
    got, m1 = run(kill)

    # zero dropped futures: every request resolved to a (K,) θ̂
    assert len(got) == len(trace) and all(g.shape == (K,) for g in got)
    assert m1["requests"] == len(trace)
    # the worker actually died and was replaced
    assert m1["deaths"] == 1 and m1["respawns"] == 1
    assert m1["replicas"] == 4
    # QPS recovery: survivors + the respawn kept serving after the death
    # (work landed on replicas other than the one that died and respawned)
    assert sum(m1["dispatch"].values()) >= m1["batches"]
    assert sum(n for rid, n in m1["dispatch"].items() if rid != 0) > 0
    # re-issued results match the unfaulted run bitwise
    for i, (a, b) in enumerate(zip(ref, got)):
        np.testing.assert_array_equal(a, b, err_msg=f"doc {i}")


@pytest.mark.slow
def test_process_pool_soft_kill_reissue(pool_store, pool_docs):
    """A soft (raised, not SIGKILL) replica death exercises the same
    orphan re-issue path through a clean worker exit."""
    kill = (FaultSpec(point=REPLICA_KILL, kind="kill", step=0, shard=1,
                      hard=False),)
    with ReplicaPool(_spec(pool_store, fault_specs=kill), replicas=2,
                     backend="process", max_batch=8, max_delay_ms=2.0,
                     max_len=64, seed=0) as pool:
        pool.wait_ready(180)
        futs = [pool.submit(w, c) for w, c in pool_docs]
        got = [np.asarray(f.result(timeout=240)) for f in futs]
        pool.drain()
        m = pool.metrics()
    assert len(got) == len(pool_docs)
    assert m["deaths"] == 1 and m["respawns"] == 1


def test_process_backend_refuses_tpu_host(monkeypatch, pool_store):
    """On a TPU host the parent process holds the chips, so the process
    backend refuses to start and names the thread backend instead."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match='backend="thread"'):
        ReplicaPool(_spec(pool_store), replicas=2, backend="process")
