"""Named host stages of the trainer step and the serving launch.

Each stage runs under ``repro.runtime.spans.span``: its seconds land in the
step's ``StepMetrics`` or the launch's ``batch_log`` record, and its name in
the profiler's host trace.  These tests pin the fields on both training
paths, the per-thread store-lock account, the span names and their nesting
in a real ``.xplane.pb``, the serving records of the engine and the thread
replica pool, and the step program's module name.
"""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

import repro.core.trainer as trainer_mod
from repro.core import FOEMTrainer, LDAConfig, ParameterStore
from repro.data import synthetic_lda_corpus
from repro.launch.replica import ReplicaPool
from repro.launch.serve import ServingEngine, TopicServer
from repro.runtime.spans import span
from repro.sparse import MinibatchStream
from repro.sparse.docword import VOCAB_BUCKET

K, W, D = 5, 150, 40


def _trainer(tmp_path, depth, tag=""):
    cfg = LDAConfig(num_topics=K, vocab_size=W, max_sweeps=4)
    store = ParameterStore(str(tmp_path / f"s{depth}{tag}"), num_topics=K,
                           vocab_capacity=W, buffer_rows=64)
    return FOEMTrainer(cfg, store, seed=0, prefetch_depth=depth)


def _stream():
    corpus, _ = synthetic_lda_corpus(120, W, K, mean_doc_len=30, seed=11)
    return MinibatchStream(corpus, D, seed=0, epochs=None)


@pytest.fixture
def streamed(monkeypatch):
    """Rows streamed from the host store: the fit rule says no."""
    monkeypatch.setattr(trainer_mod, "device_tier_fits", lambda *a: False)


def test_span_adds_to_record_and_keeps_seconds():
    rec = {}
    with span("test.stage", rec, "t") as a:
        time.sleep(0.01)
    with span("test.stage", rec, "t") as b:
        pass
    assert a.seconds >= 0.009
    assert rec["t"] == pytest.approx(a.seconds + b.seconds)
    with span("test.unrecorded") as c:
        pass
    assert c.seconds >= 0.0


def _three_steps(tr):
    stream = _stream()
    mbs = []

    def feed():
        for mb in stream:
            mbs.append(mb)
            yield mb

    ms = tr.fit_stream(feed(), max_steps=3)
    assert len(ms) == 3
    return zip(ms, mbs)


@pytest.mark.parametrize("depth", [0, 1])
def test_step_metrics_host_fields(tmp_path, depth, streamed):
    """Both training paths fill the host-stage fields; the bytes are what
    the shapes give."""
    for m, mb in _three_steps(_trainer(tmp_path, depth)):
        w_pad = -(-len(mb.local_vocab) // VOCAB_BUCKET) * VOCAB_BUCKET
        L = mb.word_ids.shape[1]
        assert m.h2d_bytes == D * L * 4 + D * L * 4 + w_pad * K * 4 + K * 4
        assert m.d2h_bytes == w_pad * K * 4 + K * 4 + 3 * 4
        assert m.fetch_seconds > 0.0
        assert 0.0 < m.host_seconds < m.seconds
        assert m.lock_wait_seconds >= 0.0
        assert m.tier_rows == 0 and m.rows == len(mb.local_vocab)


@pytest.mark.parametrize("depth", [0, 1])
def test_step_metrics_tier_fields(tmp_path, depth):
    """On the device tier no row crosses: ids, counts, the padded row ids
    and the totals go in, the totals and three scalars come back, and every
    row is served from the tier."""
    for m, mb in _three_steps(_trainer(tmp_path, depth)):
        w_pad = -(-len(mb.local_vocab) // VOCAB_BUCKET) * VOCAB_BUCKET
        L = mb.word_ids.shape[1]
        assert m.h2d_bytes == D * L * 4 + D * L * 4 + w_pad * 4 + K * 4
        assert m.d2h_bytes == K * 4 + 3 * 4
        assert m.tier_rows == m.rows == len(mb.local_vocab)
        assert m.tier_uploads == 0
        assert m.fetch_seconds >= 0.0
        assert 0.0 < m.host_seconds < m.seconds


def test_dropped_step_reads_zero(tmp_path):
    from repro.runtime.faults import PRE_PROBE, FaultPlan, FaultSpec

    tr = _trainer(tmp_path, 0)
    tr.faults = FaultPlan([FaultSpec(PRE_PROBE, "drop", step=0)])
    m = tr.fit_stream(iter(_stream()), max_steps=1)[0]
    assert m.sweeps == 0
    assert (m.fetch_seconds, m.lock_wait_seconds, m.host_seconds,
            m.h2d_bytes, m.d2h_bytes) == (0.0, 0.0, 0.0, 0, 0)


def test_lock_wait_is_charged_to_the_waiting_thread(tmp_path):
    """A thread holding the store lock for 50 ms makes the trainer's step
    read the wait; the holder's own account stays 0."""
    tr = _trainer(tmp_path, 0)
    mb = next(iter(_stream()))
    tr.step(mb)                          # compile outside the measurement
    held = threading.Event()
    holder = {}

    def hold():
        with tr.store._lock:
            held.set()
            time.sleep(0.05)
        holder["wait"] = tr.store.lock_wait_seconds()

    t = threading.Thread(target=hold)
    t.start()
    held.wait()
    m = tr.step(mb)
    t.join()
    assert m.lock_wait_seconds >= 0.04
    assert holder["wait"] == 0.0


def _host_events(log_dir):
    """{(plane, line index): [(name, start, end)]} of the profile's host
    planes: one line per thread (Python threads all share the line name
    ``python``, so a line is told apart by its position)."""
    from jax.profiler import ProfileData

    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            lines[(plane.name, i)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]
    return lines


def _inside(child, parents):
    return any(s <= child[1] and child[2] <= t for _, s, t in parents)


TRAIN_CHILDREN = ["foem.wait_staged", "foem.reconcile", "foem.pad_rows",
                  "foem.stage_in", "foem.device_wait", "foem.write_back"]
TIER_CHILDREN = ["foem.wait_staged", "foem.stage_in", "foem.device_wait",
                 "foem.write_back"]
WORKER_SPANS = ["foem.next_minibatch", "foem.fetch"]
SERVE_CHILDREN = ["serve.localize", "serve.gather_rows", "serve.pad_rows",
                  "serve.stage_in", "serve.device_wait"]


def _by_name(events, names):
    return {n: [e for e in events if e[0] == n] for n in names}


def _traced_steps(tmp_path, children):
    """Two traced steps: each stage of ``children`` once a step, inside it
    and in the order listed; the worker's spans on a line of their own."""
    tr = _trainer(tmp_path, 1)
    tr.fit_stream(iter(_stream()), max_steps=1)          # compile first
    log_dir = str(tmp_path / "trace")
    with jax.profiler.trace(log_dir):
        tr.fit_stream(iter(_stream()), max_steps=2)
    lines = _host_events(log_dir)
    step_line = next(evs for evs in lines.values()
                     if any(e[0] == "foem.step" for e in evs))
    steps = [e for e in step_line if e[0] == "foem.step"]
    assert len(steps) == 2
    kids = _by_name(step_line, children)
    for name in children:                # each stage once a step, inside it
        assert len(kids[name]) == 2, name
        assert all(_inside(e, steps) for e in kids[name]), name
    for st in steps:                     # in the order listed
        starts = [next(e[1] for e in kids[n] if _inside(e, [st]))
                  for n in children]
        assert starts == sorted(starts)
    worker = next(evs for evs in lines.values()
                  if any(e[0] == "foem.fetch" for e in evs))
    assert worker is not step_line
    for name in WORKER_SPANS:
        assert any(e[0] == name for e in worker), name
    return step_line


def test_training_spans_in_the_profile(tmp_path, streamed):
    _traced_steps(tmp_path, TRAIN_CHILDREN)


def test_training_spans_on_the_device_tier(tmp_path):
    """The tier's step has no reconcile or padding stage; the write-back
    of the tier's rows when ``fit_stream`` ends runs as ``store.sync``."""
    step_line = _traced_steps(tmp_path, TIER_CHILDREN)
    names = {e[0] for e in step_line}
    assert not names & {"foem.reconcile", "foem.pad_rows"}
    assert "store.sync" in names


def _server(tmp_path):
    rng = np.random.default_rng(0)
    phi = rng.gamma(1.0, 1.0, (96, 8)).astype(np.float32) * 1e4
    store = ParameterStore(str(tmp_path / "phi"), num_topics=8,
                           vocab_capacity=96, buffer_rows=0)
    store.write_rows(np.arange(96), phi)
    store.phi_k[:] = phi.sum(0)
    return TopicServer(store, LDAConfig(num_topics=8, vocab_size=96),
                       fit_sweeps=10, rel_tol=0.0, check_every=10,
                       vocab_pad=32)


def _docs(n=6):
    rng = np.random.default_rng(3)
    out = []
    for i in range(n):
        w = rng.choice(96, size=4 + i, replace=False).astype(np.int32)
        out.append((w, rng.integers(1, 5, len(w)).astype(np.float32)))
    return out


def test_serving_spans_in_the_profile(tmp_path):
    server = _server(tmp_path)
    eng = ServingEngine(server, max_batch=8, bucket_multiple=16,
                        max_delay_ms=5.0, max_len=16)
    eng.prewarm()
    log_dir = str(tmp_path / "trace")
    with jax.profiler.trace(log_dir):
        for _ in range(2):               # the second waits in next_batch
            for f in [eng.submit(w, c) for w, c in _docs()]:
                f.result(timeout=30)
        # a future resolves inside its launch: join the launcher so every
        # serve.launch span has ended before the profile stops
        eng.close()
    lines = _host_events(log_dir)
    launcher = next(evs for evs in lines.values()
                    if any(e[0] == "serve.launch" for e in evs))
    launches = [e for e in launcher if e[0] == "serve.launch"]
    kids = _by_name(launcher, SERVE_CHILDREN)
    for name in SERVE_CHILDREN:
        assert kids[name] and all(_inside(e, launches) for e in kids[name])
    assert any(e[0] == "serve.next_batch" for e in launcher)
    assert any(e[0] == "serve.flush" for evs in lines.values() for e in evs
               if evs is not launcher)


def _check_records(log, n_requests):
    assert sum(b["filled"] for b in log) == n_requests
    for b in log:
        assert b["sweeps"] > 0
        assert b["prep_seconds"] > 0.0
        assert b["stage_in_seconds"] > 0.0 and b["device_wait_seconds"] > 0.0
        assert b["h2d_bytes"] > 0
        assert len(b["queue_wait_s"]) == b["filled"]
        assert all(q >= 0.0 for q in b["queue_wait_s"])


@pytest.mark.parametrize("front", ["engine", "thread_pool"])
def test_launch_records(tmp_path, front):
    """The engine and a thread-backend pool both log the server's record:
    sweeps, stage times, bytes and one queue wait per request."""
    docs = _docs()
    server = _server(tmp_path)
    kw = dict(max_batch=8, bucket_multiple=16, max_delay_ms=5.0, max_len=16)
    if front == "engine":
        ctx = ServingEngine(server, **kw)
    else:
        ctx = ReplicaPool(replicas=1, backend="thread", servers=[server], **kw)
    with ctx as front_end:
        for f in [front_end.submit(w, c) for w, c in docs]:
            f.result(timeout=60)
        front_end.drain()
        log = list(front_end.router.batch_log)
    _check_records(log, len(docs))


def test_launch_record_matches_shapes(tmp_path):
    server = _server(tmp_path)
    w = np.arange(4 * 16, dtype=np.int32).reshape(4, 16) % 40
    c = np.ones((4, 16), np.float32)
    keys = np.zeros((4, 2), np.uint32)
    theta, rec = server.launch(w, c, key=keys)
    assert theta.shape == (4, 8)
    # local ids, counts, eval counts, 64 padded rows, totals, keys
    assert rec["h2d_bytes"] == 3 * 4 * 16 * 4 + 64 * 8 * 4 + 8 * 4 + 4 * 2 * 4
    assert rec["launch_seconds"] >= (rec["prep_seconds"]
                                     + rec["stage_in_seconds"]
                                     + rec["device_wait_seconds"])
    assert not hasattr(server, "last_sweeps")


def test_step_program_module_is_jit_run(tmp_path):
    """Trace readers match the step program as ``jit_run``."""
    tr = _trainer(tmp_path, 0)
    mb = next(iter(_stream()))
    tr.step(mb)
    (fn,) = tr._jit_cache.values()
    import jax.numpy as jnp

    from repro.core.types import MinibatchData

    batch = MinibatchData(word_ids=jnp.asarray(mb.local_word_ids),
                          counts=jnp.asarray(mb.counts))
    text = fn.lower(jax.random.PRNGKey(0), batch,
                    jnp.zeros((VOCAB_BUCKET, K), jnp.float32),
                    jnp.zeros((K,), jnp.float32), W,
                    jnp.int32(len(mb.local_vocab))).as_text()
    assert "module @jit_run" in text
