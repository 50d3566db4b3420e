"""Serving driver — topic inference for unseen documents (the paper's
deployment mode) and LM decode on reduced configs.

LDA serving = the E-step with FROZEN φ̂ (§2.4): per request batch, fit θ̂
only — the θ-only fixed point of eq. 11 with the φ M-step switched off —
and return the per-document topic mixture (eq. 9).  Requests stream
against the same disk-backed parameter access as training
(``ParameterStore``), and the fit routes through the fused inference
dispatch (``kernels.ops.infer``): convergence-stopped chunks of the
single-launch θ sweep kernel on TPU, the jnp mirror elsewhere, with the
eq. 21 log-predictive partials available in the same launch for
lifelong held-out evaluation.

The high-throughput path is :class:`ServingEngine` — continuous batching
over :class:`TopicServer`'s fixed jit shapes::

      submit() ──► admission queue (per-L-bucket in-flight slots)
                      │  collector thread: flush when a bucket fills
                      │  or its oldest request hits max_delay_ms
                      ▼
      bounded launch queue ──► launcher thread
                      │  localize_vocab → fetch φ̂ rows (HotRowCache →
                      │  ParameterStore) → pad to the (D, L, W_s) bucket
                      │  → one `_infer_local` launch (pre-warmed traces)
                      ▼
      per-request futures resolve with (θ_d, latency)

Admission never blocks on compute: while the launcher executes batch *s*,
the collector keeps admitting and assembling batch *s+1* (the launch
queue is the only backpressure).  Per-document PRNG keys make results
independent of how requests were packed into batches, so continuous
batching is semantically invisible.  ``phi_dtype`` serves a quantized
(bf16/int8) read-only φ block through the same launches; the
:class:`TrafficGenerator` drives the stack with Zipf word mixes and
Poisson arrivals for the BENCH_serve SLO cells.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import queue
import threading
import time
from concurrent.futures import Future
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCHS, LDA_ARCH
from repro.core import (
    HotRowCache,
    LDAConfig,
    ParameterStore,
    PhiSnapshot,
    SnapshotPublisher,
)
from repro.core import em
from repro.core.perplexity import init_theta, serving_active_topics
from repro.core.types import InferPlan, MinibatchData, uniform_responsibilities
from repro.data import synthetic_lda_corpus
from repro.kernels import ops as kops
from repro.models import build
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.spans import span
from repro.sparse.docword import (
    VOCAB_BUCKET,
    DocWordMatrix,
    bucketize,
    localize_vocab,
    pad_vocab_rows,
)


def _round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


class ThetaResult(np.ndarray):
    """A (K,) θ mixture stamped with the committed φ snapshot version that
    produced it (−1 when serving straight from the store, i.e. not
    subscribed to a publisher).  Behaves exactly like the plain ndarray the
    engine used to resolve — the version tag rides along as an attribute."""

    version: int = -1

    @staticmethod
    def wrap(theta: np.ndarray, version: int) -> "ThetaResult":
        out = np.asarray(theta).view(ThetaResult)
        out.version = int(version)
        return out


@dataclasses.dataclass(frozen=True)
class _ServingVersion:
    """One pinned, immutable φ epoch the server launches against.

    Holds the snapshot plus its (possibly quantized) serving storage —
    built once at hot-swap (`TopicServer.refresh`) and shared by every
    launch on this version.  In-flight launches keep their reference, so a
    concurrent swap never tears a batch: rows and ``phi_k`` always come
    from the same epoch.
    """

    snapshot: PhiSnapshot
    version: int
    phi_k: np.ndarray                  # (K,) float32
    values: np.ndarray                 # (capacity, K) f32/bf16/int8 storage
    scale: Optional[np.ndarray]        # (capacity,) f32 int8 scales, or None

    def fetch_rows(self, word_ids: np.ndarray) -> np.ndarray:
        """Dequantized f32 rows of THIS version (never the live store)."""
        ids = np.asarray(word_ids, np.int64)
        rows = np.asarray(self.values[ids], np.float32)
        if self.scale is not None:
            rows = rows * self.scale[ids][:, None]
        return rows


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "fit_sweeps", "check_every", "active_topics",
                     "use_pallas", "interpret", "phi_dtype"),
)
def _infer_local(key, word_ids, counts, ev_counts, rows, phi_k, cfg,
                 fit_sweeps, check_every, rel_tol, active_topics,
                 use_pallas, interpret, phi_dtype="float32"):
    """One jitted request batch: normalise the streamed (W_s, K) view
    (eq. 10 with the *global* W smoothing mass), fit θ̂ through
    ``ops.infer`` and return the eq. 9 mixtures + diagnostics.

    ``key`` is either one batch key (legacy: one init stream folded over
    the whole (D, L, K) block — a document's init then depends on its slot
    in the batch) or a (D, 2) *per-document* key stack: each document's
    θ̂ init draws from its own stream, so the result is invariant to how
    the continuous-batching engine packed requests into slots.
    """
    if key.ndim == 2:        # per-document keys: slot-invariant init
        L = word_ids.shape[1]
        mu0 = jax.vmap(
            lambda k: uniform_responsibilities(k, (L, cfg.K), cfg.dtype)
        )(key)
        theta0 = em.fold_theta(mu0, counts)
    else:
        theta0 = init_theta(key, MinibatchData(word_ids, counts), cfg)
    phi_norm = em.normalize_phi(rows, phi_k, cfg, vocab_size=cfg.W)
    res = kops.infer(
        word_ids, counts, theta0, phi_norm,
        alpha_m1=cfg.alpha_m1, ev_counts=ev_counts,
        word_topics=(
            serving_active_topics(phi_norm, active_topics)
            if active_topics else None
        ),
        max_sweeps=fit_sweeps, check_every=check_every, rel_tol=rel_tol,
        use_pallas=use_pallas, interpret=interpret,
        plan=InferPlan(phi_dtype=phi_dtype),
        debug_checks=cfg.debug_checks,
    )
    return em.normalize_theta(res.theta, cfg), res.sweeps, res.ev_loglik


class TopicServer:
    """Batched topic-mixture inference against a (possibly disk-backed) φ̂.

    The paper's deployment mode (§2.4): per request batch, stream exactly
    the W_s touched φ̂ rows from the store, fit θ̂ with φ̂ frozen through
    the fused dispatch (``ops.infer`` — convergence-stopped instead of a
    fixed sweep budget), and return the eq. 9 topic mixtures.  Identical
    requests are deterministic: the fixed-point init key defaults to a
    fixed key and can be passed explicitly per request (it is never
    advanced by the server).

    Knobs: ``fit_sweeps`` caps the fixed point, ``rel_tol``/``check_every``
    are the §2.4 relative stop rule (defaults from the config),
    ``active_topics > 0`` restricts each word's fit support to its top-A
    topics by φ mass (the §3.1 machinery at serving time), and
    ``use_pallas``/``interpret`` force the kernel/oracle dispatch.

    Serving-specific knobs: ``phi_dtype`` stores the frozen φ block in
    bf16/int8 inside the fused kernel (dequantize-on-read; f32 results
    bitwise-unchanged by default) and ``hot_rows > 0`` layers a read-only
    hot-word row LRU (:class:`~repro.core.streaming.HotRowCache`) over
    the store, sized for the Zipf head of request traffic.
    """

    def __init__(self, store: ParameterStore, cfg: LDAConfig,
                 fit_sweeps: int = 50, *,
                 rel_tol: Optional[float] = None,
                 check_every: Optional[int] = None,
                 active_topics: int = 0,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False,
                 vocab_pad: int = VOCAB_BUCKET,
                 phi_dtype: str = "float32",
                 hot_rows: int = 0):
        self.store = store
        self.cfg = cfg
        self.fit_sweeps = fit_sweeps
        self.rel_tol = cfg.ppl_rel_tol if rel_tol is None else rel_tol
        self.check_every = (
            cfg.ppl_check_every if check_every is None else check_every
        )
        self.active_topics = active_topics
        self.use_pallas = use_pallas
        self.interpret = interpret
        self.vocab_pad = max(1, vocab_pad)   # W_s bucketing for jit reuse
        self.phi_dtype = phi_dtype
        self.hot_cache = (
            HotRowCache(store, hot_rows) if hot_rows > 0 else None
        )
        # --- lifelong publish/subscribe state ---
        self._publisher: Optional[SnapshotPublisher] = None
        self._active: Optional[_ServingVersion] = None   # pinned epoch
        self.swap_log: List[dict] = []       # one record per hot-swap
        self.last_version = -1               # version the last launch used
        self._launching = threading.local()  # the thread's open launch record

    # -------------------------------------------------- lifelong hot-swap

    def subscribe(self, publisher: SnapshotPublisher,
                  refresh: bool = True) -> None:
        """Serve committed φ snapshot versions from ``publisher`` instead
        of the live store — the lifelong train-while-serve mode.  Once
        subscribed, launches never read store rows again: a concurrent
        trainer can write freely and the server only moves at
        ``refresh()`` (called between launches by the engine)."""
        self._publisher = publisher
        if refresh:
            self.refresh()

    def refresh(self) -> bool:
        """Hot-swap to the newest published version, if any.  Verifies the
        snapshot's crc manifest, (re)builds the quantized serving storage,
        installs the new epoch in the hot-row cache (dropping only the
        rows the publish changed), and atomically replaces the pinned
        epoch.  Zero downtime: in-flight launches finish on the old
        version they captured.  Returns True iff a swap happened."""
        pub = self._publisher
        if pub is None:
            return False
        snap = pub.latest()
        if snap is None:
            return False
        cur = self._active
        if cur is not None and cur.version == snap.version:
            return False
        t0 = time.perf_counter()
        if not snap.verify():
            raise RuntimeError(
                f"φ snapshot v{snap.version} fails its crc manifest — "
                "torn or mutated publish; refusing to swap"
            )
        values, scale = snap.quantize(self.phi_dtype)   # re-quantize on swap
        if self.hot_cache is not None:
            self.hot_cache.install_version(
                snap.version, changed_ids=snap.changed_ids
            )
        sv = _ServingVersion(
            snapshot=snap,
            version=snap.version,
            phi_k=np.asarray(snap.phi_k, np.float32),
            values=values,
            scale=scale,
        )
        self._active = sv                    # the atomic swap point
        self.swap_log.append({
            "version": snap.version,
            "seconds": time.perf_counter() - t0,
            "changed_rows": int(len(snap.changed_ids)),
        })
        return True

    # ------------------------------------------------------------ inference

    def _fetch_rows(self, uniq: np.ndarray,
                    active: Optional[_ServingVersion] = None) -> np.ndarray:
        if self.hot_cache is not None:
            if active is not None:
                return self.hot_cache.fetch(
                    uniq, source=active, version=active.version
                )
            return self.hot_cache.fetch(uniq)
        if active is not None:
            return active.fetch_rows(uniq)
        return self.store.fetch_rows(uniq)

    def _run(self, word_ids: np.ndarray, counts: np.ndarray,
             ev_counts: Optional[np.ndarray], key: Optional[jax.Array]):
        """One launch; its stages fill the record :meth:`launch` opened on
        this thread (a throwaway one outside ``launch``)."""
        rec = getattr(self._launching, "rec", None)
        if rec is None:
            rec = {}
        if key is None:
            key = jax.random.PRNGKey(0)      # deterministic by default
        # pin ONE epoch for the whole launch: rows and phi_k below both come
        # from `active`, so a concurrent refresh() can never tear the batch
        active = self._active
        with span("serve.localize", rec, "prep_seconds"):
            uniq, local = localize_vocab(word_ids)
        with span("serve.gather_rows", rec, "prep_seconds"):
            rows = self._fetch_rows(uniq, active)          # streamed φ̂
        # pad the local vocab to a bucket boundary so jit traces are reused
        # across requests (padded rows are never indexed by `local`)
        with span("serve.pad_rows", rec, "prep_seconds"):
            rows = pad_vocab_rows(rows, self.vocab_pad)
        with span("serve.stage_in", rec, "stage_in_seconds"):
            host_in = (
                local, np.asarray(counts),
                np.asarray(ev_counts if ev_counts is not None
                           else np.zeros_like(counts)),
                rows,
                np.asarray(active.phi_k if active is not None
                           else self.store.phi_k, np.float32),
            )
            rec["h2d_bytes"] = sum(a.nbytes for a in host_in) + (
                key.nbytes if isinstance(key, np.ndarray) else 0)
            args = (jnp.asarray(key), *map(jnp.asarray, host_in),
                    self.cfg, self.fit_sweeps, self.check_every,
                    self.rel_tol, self.active_topics, self.use_pallas,
                    self.interpret, self.phi_dtype)
            if self.cfg.debug_checks:
                # functionalize the sanitizer checks through the jitted batch
                from jax.experimental import checkify

                err, (theta, sweeps, ev_ll) = checkify.checkify(
                    _infer_local)(*args)
                err.throw()
            else:
                theta, sweeps, ev_ll = _infer_local(*args)
        with span("serve.device_wait", rec, "device_wait_seconds"):
            rec["sweeps"] = int(sweeps)
            theta = np.asarray(theta)
        self.last_version = active.version if active is not None else -1
        return theta, ev_ll

    def launch(self, word_ids: np.ndarray, counts: np.ndarray,
               key: Optional[jax.Array] = None) -> Tuple[np.ndarray, dict]:
        """``infer`` plus the launch's record, the entry ``batch_log``
        keeps: ``start`` and ``launch_seconds`` (``perf_counter`` around
        the ``infer`` call), ``prep_seconds`` (spans ``serve.localize``,
        ``serve.gather_rows``, ``serve.pad_rows``), ``stage_in_seconds``
        (``serve.stage_in``: the copies in and the call),
        ``device_wait_seconds`` (``serve.device_wait``: the sweep count
        and θ back), ``sweeps``, ``h2d_bytes`` (host arrays the launch
        copies in), the hot-row cache's counts since the last launch,
        ``version`` and ``published_version``.  The launch goes through
        ``self.infer``, so a wrapper installed on the instance wraps it
        too."""
        rec = {"prep_seconds": 0.0, "stage_in_seconds": 0.0,
               "device_wait_seconds": 0.0, "sweeps": 0, "h2d_bytes": 0}
        self._launching.rec = rec
        try:
            rec["start"] = time.perf_counter()
            theta = self.infer(word_ids, counts, key=key)
            rec["launch_seconds"] = time.perf_counter() - rec["start"]
        finally:
            self._launching.rec = None
        cache = self.hot_cache
        cw = cache.window_stats() if cache is not None else None
        pub = self._publisher
        rec.update(
            cache_hits=cw.hits if cw else 0,
            cache_misses=cw.misses if cw else 0,
            # staleness audit trail: the version this launch served vs
            # the newest committed version at launch time
            version=self.last_version,
            published_version=pub.version if pub is not None else -1,
        )
        return theta, rec

    def infer(self, word_ids: np.ndarray, counts: np.ndarray,
              key: Optional[jax.Array] = None) -> np.ndarray:
        """(B, L) docs -> (B, K) normalized topic mixtures θ (eq. 9)."""
        return self._run(word_ids, counts, None, key)[0]

    def evaluate(self, word_ids: np.ndarray, est_counts: np.ndarray,
                 ev_counts: np.ndarray,
                 key: Optional[jax.Array] = None
                 ) -> Tuple[np.ndarray, float]:
        """Lifelong held-out evaluation: fit θ̂ on ``est_counts``, score
        ``ev_counts`` with eq. 21 in the same launch.  Returns
        ``(theta (B, K), predictive perplexity)``."""
        theta, ev_ll = self._run(word_ids, est_counts, ev_counts, key)
        ppl = float(np.exp(-float(ev_ll) / max(float(ev_counts.sum()), 1.0)))
        return theta, ppl

    def infer_stream(
        self, corpus: DocWordMatrix, doc_ids: Sequence[int],
        batch_size: int, key: Optional[jax.Array] = None,
        bucket_multiple: int = 16,
        records: Optional[List[dict]] = None,
    ) -> Iterator[Tuple[Sequence[int], np.ndarray]]:
        """Batched/bucketized streaming inference over a request stream.

        Packs ``doc_ids`` into fixed-size (batch_size, L) buckets
        (``sparse.docword.bucketize``; L rounds up to ``bucket_multiple``
        and short tail batches pad with empty documents, so jit traces are
        reused across the stream), derives a per-batch key from ``key``
        (``fold_in`` by batch index — the stream is deterministic end to
        end) and yields ``(chunk_doc_ids, theta (len(chunk), K))``.
        Each launch's record (:meth:`launch`) is appended to ``records``
        when given.
        """
        base = jax.random.PRNGKey(0) if key is None else key
        ids = list(doc_ids)
        for i, lo in enumerate(range(0, len(ids), batch_size)):
            chunk = ids[lo: lo + batch_size]
            w, c = bucketize(corpus, chunk, pad_multiple=bucket_multiple)
            if len(chunk) < batch_size:      # tail: pad with empty docs
                padding = batch_size - len(chunk)
                w = np.concatenate([w, np.zeros((padding, w.shape[1]),
                                                w.dtype)])
                c = np.concatenate([c, np.zeros((padding, c.shape[1]),
                                                c.dtype)])
            theta, rec = self.launch(w, c, key=jax.random.fold_in(base, i))
            if records is not None:
                records.append(rec)
            yield chunk, theta[: len(chunk)]


# ---------------------------------------------------------------------------
# Continuous batching — the high-throughput serving engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Request:
    """One admitted document, waiting in an in-flight slot."""

    seq: int
    word_ids: np.ndarray         # (n,) token word ids (unpadded)
    counts: np.ndarray           # (n,) token counts
    key: np.ndarray              # (2,) uint32 per-document PRNG key
    future: Future
    t_submit: float


def pad_batch(L: int, reqs: Sequence[_Request], max_batch: int
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a flushed bucket to its ``(max_batch, L)`` jit shape.

    Tail slots are empty documents (exactly like ``infer_stream``'s tail
    padding).  The padded arrays — not the request list — are the unit of
    replica dispatch: re-issuing the identical payload after a worker
    loss reproduces the launch bitwise.  Under ``rel_tol > 0`` the
    convergence stop is batch-global, so re-issue parity REQUIRES
    resending the same padded batch, never repacking the survivors.
    """
    w = np.zeros((max_batch, L), np.int32)
    c = np.zeros((max_batch, L), np.float32)
    keys = np.zeros((max_batch, 2), np.uint32)
    for i, r in enumerate(reqs):
        w[i, : len(r.word_ids)] = r.word_ids
        c[i, : len(r.counts)] = r.counts
        keys[i] = r.key
    return w, c, keys


class AdmissionRouter:
    """Deadline-aware admission front: in-flight slots, collector thread
    and a bounded flush queue, decoupled from whatever runs the batches.

    PR 8 built this machinery inside :class:`ServingEngine`; it now
    stands alone so the multi-replica pool
    (:class:`repro.launch.replica.ReplicaPool`) can put the *identical*
    admission semantics in front of N workers:

    * ``submit`` (caller thread) appends the request to the in-flight
      slots of its document-length bucket — O(1) under a lock — stamps a
      per-document PRNG key, and returns a Future;
    * the *collector* thread flushes a bucket into the bounded queue when
      it fills ``max_batch`` slots, or when its **oldest** request has
      waited ``max_delay_ms`` (deadline-aware: a straggling slot never
      holds a full bucket hostage, a lone request never waits more than
      the deadline);
    * the single consumer (the engine's launcher thread, or the pool's
      dispatcher) pulls ``(L, reqs)`` items with :meth:`next_batch` and
      reports outcomes through :meth:`resolve_batch` /
      :meth:`fail_batch`, which keep the resolved/latency/batch
      accounting that :meth:`drain` and :meth:`metrics` read.

    ``close()`` is idempotent and safe under concurrent callers: every
    caller blocks until the collector is joined, so nobody can observe a
    half-stopped router.
    """

    def __init__(self, *, max_batch: int = 64, bucket_multiple: int = 16,
                 max_delay_ms: float = 5.0, max_len: int = 256,
                 queue_depth: int = 4, seed: int = 0):
        self.max_batch = int(max_batch)
        self.bucket_multiple = int(bucket_multiple)
        self.max_delay = float(max_delay_ms) / 1e3
        self.max_len = int(max_len)
        self.queue_depth = int(queue_depth)
        self._base_key = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        self._pending: dict = {}             # L bucket -> list[_Request]
        self._seq = 0
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: "queue.Queue" = queue.Queue(maxsize=self.queue_depth)
        self._stop = False
        self._resolved = 0                   # futures resolved (ok or error)
        self.latencies: List[float] = []     # per request, submit -> resolve
        self.batch_log: List[dict] = []      # per launched batch
        self._collector = threading.Thread(
            target=self._collect_loop, name="serve-collector", daemon=True
        )
        self._collector.start()

    # ------------------------------------------------------------- admission

    def _bucket(self, n: int) -> int:
        return _round_up(max(n, 1), self.bucket_multiple)

    def submit(self, word_ids: np.ndarray,
               counts: Optional[np.ndarray] = None,
               key: Optional[np.ndarray] = None) -> Future:
        """Admit one document; resolves to its (K,) normalized θ (eq. 9)."""
        w = np.asarray(word_ids, np.int32).ravel()
        c = (np.ones(len(w), np.float32) if counts is None
             else np.asarray(counts, np.float32).ravel())
        if len(w) > self.max_len:
            raise ValueError(
                f"document has {len(w)} tokens > engine max_len "
                f"{self.max_len}; raise max_len at construction"
            )
        fut: Future = Future()
        with self._cond:
            if self._stop:
                raise RuntimeError("admission router is closed")
            seq = self._seq
            self._seq += 1
            if key is None:
                # distinct per-request stream, no per-request jax dispatch
                key = self._base_key.copy()
                key[1] ^= np.uint32(seq)
            req = _Request(seq, w, c, np.asarray(key, np.uint32), fut,
                           time.perf_counter())
            self._pending.setdefault(self._bucket(len(w)), []).append(req)
            self._cond.notify()
        return fut

    # ------------------------------------------------------------- collector

    def _collect_loop(self) -> None:
        while True:
            flush: List[Tuple[int, List[_Request]]] = []
            with self._cond:
                while True:
                    if self._stop and not self._pending:
                        break
                    now = time.perf_counter()
                    deadline = None
                    for L, reqs in self._pending.items():
                        if len(reqs) >= self.max_batch or self._stop:
                            flush.append((L, reqs[: self.max_batch]))
                            rest = reqs[self.max_batch:]
                            self._pending[L] = rest
                            continue
                        age_out = reqs[0].t_submit + self.max_delay
                        if age_out <= now:
                            flush.append((L, reqs))
                            self._pending[L] = []
                        elif deadline is None or age_out < deadline:
                            deadline = age_out
                    self._pending = {
                        L: r for L, r in self._pending.items() if r
                    }
                    if flush or (self._stop and not self._pending):
                        break
                    self._cond.wait(
                        timeout=None if deadline is None else deadline - now
                    )
                stopping = self._stop and not self._pending
            # bounded put OUTSIDE the lock: backpressure must not stall
            # submit()
            for item in flush:
                with span("serve.flush"):
                    self._queue.put(item)
            if stopping and not flush:
                self._queue.put(None)
                return

    # -------------------------------------------------------------- consumer

    def next_batch(self) -> Optional[Tuple[int, List[_Request]]]:
        """Block for the next flushed ``(L, reqs)`` bucket.  ``None`` is
        the shutdown sentinel: admission stopped and every pending slot
        has been flushed ahead of it."""
        return self._queue.get()

    def resolve_batch(self, reqs: Sequence[_Request], thetas,
                      version: int, rec: dict) -> None:
        """Resolve a launched bucket and commit its accounting (batch
        record + per-request latencies).  The record gains ``queue_wait_s``:
        per request, its launch's ``start`` minus its submit time.
        Resolutions are counted one by one: if ``set_result`` ever raises
        mid-loop (e.g. a cancelled future), the already-resolved prefix
        must still reach ``_resolved`` or ``drain()`` hangs forever on the
        lost counts."""
        t1 = time.perf_counter()
        rec["queue_wait_s"] = [rec["start"] - r.t_submit for r in reqs]
        ok = 0
        try:
            for i, r in enumerate(reqs):
                r.future.set_result(
                    ThetaResult.wrap(np.array(thetas[i]), version)
                )
                ok += 1
        finally:
            with self._lock:
                self._resolved += ok
                self.batch_log.append(rec)
                self.latencies.extend(t1 - r.t_submit for r in reqs)

    def fail_batch(self, reqs: Sequence[_Request],
                   exc: BaseException) -> None:
        """Resolve a failed bucket with ``exc`` — never hang the callers."""
        n_err = 0
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(exc)
                n_err += 1
        with self._lock:
            self._resolved += n_err

    # ------------------------------------------------------------ accounting

    def metrics(self, reset: bool = False) -> dict:
        """Latency/throughput/cache summary over the recorded window."""
        with self._lock:
            lats = np.asarray(self.latencies, np.float64)  # lint: host-f64
            log = list(self.batch_log)
            if reset:
                self.latencies = []
                self.batch_log = []
        out = {
            "requests": int(lats.size),
            "batches": len(log),
            "mean_fill": (
                float(np.mean([b["filled"] for b in log])) if log else 0.0
            ),
            "cache_hits": int(sum(b["cache_hits"] for b in log)),
            "cache_misses": int(sum(b["cache_misses"] for b in log)),
        }
        # staleness bound actually observed: how many committed versions
        # behind the newest publish each launch served (lifelong mode only)
        stale = [
            b["published_version"] - b["version"]
            for b in log
            if b.get("version", -1) >= 0 and b.get("published_version", -1) >= 0
        ]
        if stale:
            out["max_staleness_versions"] = int(max(stale))
        if lats.size:
            out.update(
                p50_ms=float(np.percentile(lats, 50) * 1e3),
                p99_ms=float(np.percentile(lats, 99) * 1e3),
                mean_ms=float(lats.mean() * 1e3),
            )
        return out

    def drain(self) -> None:
        """Block until every admitted request has resolved."""
        while True:
            with self._lock:
                idle = not self._pending and self._queue.empty()
                resolved, admitted = self._resolved, self._seq
            if idle and resolved >= admitted:
                return
            time.sleep(0.001)

    def close(self) -> None:
        """Stop admission, flush the remaining slots, join the collector.

        Idempotent AND safe under concurrent callers: every caller blocks
        on the join (``Thread.join`` is multi-caller safe), so no caller
        returns while the collector is still flushing.
        """
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._collector.join()


def prewarm_server(srv: TopicServer, *, max_batch: int,
                   bucket_multiple: int, max_len: int,
                   lengths: Optional[Sequence[int]] = None,
                   vocab_sizes: Optional[Sequence[int]] = None) -> int:
    """Compile one server's (L-bucket × W_s-bucket) trace grid.

    Shared by ``ServingEngine.prewarm`` and each pool replica — a worker
    process owns its own jit cache, so the replica pool prewarms per
    worker with exactly these launches.  Returns the launch count and
    resets the cache/store stat windows so warm-up traffic doesn't
    pollute the serving counters (both resets take their owner's lock —
    a concurrent launcher fetch never observes a half-replaced stats
    object).
    """
    if lengths is None:
        lengths = range(bucket_multiple, max_len + 1, bucket_multiple)
    count = 0
    for L in lengths:
        Lb = _round_up(max(L, 1), bucket_multiple)
        if Lb != L:
            continue
        vs = vocab_sizes
        if vs is None:
            reach = min(srv.cfg.W, max_batch * Lb)
            vs = range(srv.vocab_pad,
                       _round_up(reach, srv.vocab_pad) + 1,
                       srv.vocab_pad)
        for ws in vs:
            n = min(ws, srv.cfg.W, max_batch * Lb)
            if _round_up(n, srv.vocab_pad) != ws:
                continue              # bucket not reachable at this (D, L)
            w = (np.arange(max_batch * Lb, dtype=np.int64) % n)
            w = w.reshape(max_batch, Lb).astype(np.int32)
            c = np.ones_like(w, np.float32)
            keys = np.zeros((max_batch, 2), np.uint32)
            srv.infer(w, c, key=jnp.asarray(keys))
            count += 1
    if srv.hot_cache is not None:
        srv.hot_cache.reset_stats()
    srv.store.stats_window(reset=True)
    return count


class ServingEngine:
    """Continuous batching over :class:`TopicServer`'s fixed jit shapes.

    Admission (in-flight slots, deadline-aware collector, bounded launch
    queue, per-document PRNG keys) is an :class:`AdmissionRouter`; the
    engine adds the single *launcher* thread that consumes flushed
    buckets, pads each to its (``max_batch``, L-bucket) jit shape
    (:func:`pad_batch`) and runs one ``_infer_local`` launch per bucket.
    Admission never blocks on compute: the bounded queue is the only
    backpressure.

    Every request gets a *per-document* PRNG key, so a document's θ is
    independent of which slot/batch the collector packed it into —
    continuous batching is semantically invisible (bitwise, under
    ``rel_tol=0``).  ``prewarm()`` compiles the whole (L-bucket ×
    W_s-bucket) trace grid up front; ``compile_count()`` exposes the
    jit-cache size so benches can assert no recompilation under traffic.
    """

    def __init__(self, server: TopicServer, *,
                 max_batch: int = 64,
                 bucket_multiple: int = 16,
                 max_delay_ms: float = 5.0,
                 max_len: int = 256,
                 queue_depth: int = 4,
                 seed: int = 0):
        self.server = server
        self.router = AdmissionRouter(
            max_batch=max_batch, bucket_multiple=bucket_multiple,
            max_delay_ms=max_delay_ms, max_len=max_len,
            queue_depth=queue_depth, seed=seed,
        )
        self.max_batch = self.router.max_batch
        self.bucket_multiple = self.router.bucket_multiple
        self.max_delay = self.router.max_delay
        self.max_len = self.router.max_len
        self.queue_depth = self.router.queue_depth
        self._launcher = threading.Thread(
            target=self._launch_loop, name="serve-launcher", daemon=True
        )
        self._launcher.start()

    # ------------------------------------------------------------- admission

    # Accounting lives on the router; these delegations keep the PR-8
    # test/bench surface (eng._resolved, eng._seq, eng.batch_log,
    # eng.latencies) stable.

    @property
    def _resolved(self) -> int:
        return self.router._resolved

    @property
    def _seq(self) -> int:
        return self.router._seq

    @property
    def batch_log(self) -> List[dict]:
        return self.router.batch_log

    @property
    def latencies(self) -> List[float]:
        return self.router.latencies

    def _bucket(self, n: int) -> int:
        return self.router._bucket(n)

    def submit(self, word_ids: np.ndarray, counts: Optional[np.ndarray] = None,
               key: Optional[np.ndarray] = None) -> Future:
        """Admit one document; resolves to its (K,) normalized θ (eq. 9)."""
        return self.router.submit(word_ids, counts, key)

    # -------------------------------------------------------------- launcher

    def _launch_loop(self) -> None:
        while True:
            with span("serve.next_batch"):
                item = self.router.next_batch()
            if item is None:
                return
            L, reqs = item
            with span("serve.launch"):
                try:
                    # hot-swap point: the launcher is the only thread that
                    # launches, so swapping BETWEEN launches gives zero
                    # downtime — no launch ever straddles two versions
                    self.server.refresh()
                    self._launch(L, reqs)
                except BaseException as e:  # resolve, never hang the callers
                    self.router.fail_batch(reqs, e)

    def _launch(self, L: int, reqs: List[_Request]) -> None:
        w, c, keys = pad_batch(L, reqs, self.max_batch)
        theta, rec = self.server.launch(w, c, key=jnp.asarray(keys))
        rec.update(L=L, filled=len(reqs), capacity=self.max_batch)
        self.router.resolve_batch(reqs, theta, rec["version"], rec)

    # -------------------------------------------------------------- plumbing

    def prewarm(self, lengths: Optional[Sequence[int]] = None,
                vocab_sizes: Optional[Sequence[int]] = None) -> int:
        """Compile the (L-bucket × W_s-bucket) trace grid up front.

        Defaults cover every shape the admission path can produce: L
        buckets are the ``bucket_multiple`` grid up to ``max_len``; W_s
        buckets are the ``vocab_pad`` grid up to the largest unique vocab
        a full batch can touch (min(W, max_batch·L)).  Returns the jit
        cache size afterwards — under subsequent traffic
        ``compile_count()`` must not move past it.
        """
        prewarm_server(self.server, max_batch=self.max_batch,
                       bucket_multiple=self.bucket_multiple,
                       max_len=self.max_len, lengths=lengths,
                       vocab_sizes=vocab_sizes)
        return self.compile_count()

    @staticmethod
    def compile_count() -> int:
        """Size of ``_infer_local``'s jit cache — the recompilation probe."""
        return _infer_local._cache_size()

    def metrics(self, reset: bool = False) -> dict:
        """Latency/throughput/cache summary over the recorded window."""
        return self.router.metrics(reset=reset)

    def drain(self) -> None:
        """Block until every admitted request has resolved."""
        self.router.drain()

    def close(self) -> None:
        """Flush remaining slots, stop both threads.

        Idempotent AND safe under concurrent callers: every caller blocks
        until both the collector and the launcher are joined.  (The PR-8
        version let a second closer return as soon as it saw the stop
        flag, while the first was still joining — double-close by
        thread-join luck; the threaded regression test in
        ``test_serving_engine.py`` pins the fix.)
        """
        self.router.close()
        self._launcher.join()

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# Synthetic traffic — Zipf word mix, Poisson arrivals, QPS ramps
# ---------------------------------------------------------------------------


class TrafficGenerator:
    """Deterministic synthetic request traffic for the serving bench.

    Documents draw their tokens from a Zipf(``zipf_exponent``) word
    distribution over a seeded permutation of the vocabulary (the
    realistic skew the hot-row cache exploits); arrivals are Poisson —
    i.i.d. exponential gaps at each stage's rate — with ``stages`` giving
    a QPS ramp as ``(qps, num_requests)`` segments.  ``trace`` precomputes
    everything (sampling never runs inside the timed loop);
    ``replay`` submits a trace either paced (latency measurement) or
    back-to-back (sustained-throughput measurement).
    """

    def __init__(self, vocab_size: int, *,
                 zipf_exponent: float = 1.1,
                 doc_len: Tuple[int, int] = (16, 64),
                 seed: int = 0):
        self.vocab = int(vocab_size)
        self.doc_len = doc_len
        self.rng = np.random.default_rng(seed)
        ranks = np.arange(1, self.vocab + 1, dtype=np.float64)  # lint: host-f64
        p = ranks ** -float(zipf_exponent)
        self._p = p / p.sum()
        self._word_of_rank = self.rng.permutation(self.vocab)

    def document(self) -> Tuple[np.ndarray, np.ndarray]:
        """One bag-of-words request: (unique word ids, counts)."""
        lo, hi = self.doc_len
        n_tokens = int(self.rng.integers(lo, hi + 1))
        ranks = self.rng.choice(self.vocab, size=n_tokens, p=self._p)
        uniq, counts = np.unique(self._word_of_rank[ranks],
                                 return_counts=True)
        return uniq.astype(np.int32), counts.astype(np.float32)

    def trace(self, stages: Sequence[Tuple[float, int]]
              ) -> List[Tuple[float, np.ndarray, np.ndarray]]:
        """Precompute ``(arrival_seconds, word_ids, counts)`` requests for
        a QPS ramp of ``(qps, num_requests)`` stages."""
        out = []
        t = 0.0
        for qps, n in stages:
            gaps = self.rng.exponential(1.0 / float(qps), int(n))
            for g in gaps:
                t += float(g)
                w, c = self.document()
                out.append((t, w, c))
        return out

    @staticmethod
    def replay(trace, submit, pace: bool = True) -> List[Future]:
        """Drive ``submit(word_ids, counts)`` with a precomputed trace.

        ``pace=True`` honours the arrival timestamps (open-loop latency
        measurement: late arrivals are submitted immediately, queueing
        delay counts against the server); ``pace=False`` submits
        back-to-back (closed-loop sustained-QPS measurement).
        """
        futures = []
        t0 = time.perf_counter()
        for t_arr, w, c in trace:
            if pace:
                delay = t0 + t_arr - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
            futures.append(submit(w, c))
        return futures


def serve_traffic(args, server: TopicServer) -> None:
    """Drive the continuous-batching engine — or, with ``--replicas N``,
    the multi-replica pool — with synthetic Zipf/Poisson traffic and
    report the SLO numbers (p50/p99 latency, QPS, cache)."""
    gen = TrafficGenerator(args.vocab, seed=123)
    trace = gen.trace([(args.qps, args.requests)])
    replicas = int(getattr(args, "replicas", 1) or 1)
    if replicas > 1:
        # imported lazily: replica.py imports this module
        from repro.launch.replica import ReplicaPool, ReplicaSpec

        spec = ReplicaSpec(
            store_path=args.workdir, cfg=server.cfg,
            vocab_capacity=args.vocab, fit_sweeps=server.fit_sweeps,
            rel_tol=server.rel_tol, check_every=server.check_every,
            active_topics=server.active_topics, vocab_pad=server.vocab_pad,
            phi_dtype=server.phi_dtype, hot_rows=args.hot_rows,
        )
        backend = getattr(args, "replica_backend", "thread")
        with ReplicaPool(spec, replicas=replicas, backend=backend,
                         max_batch=args.batch,
                         max_delay_ms=args.max_delay_ms,
                         max_len=_round_up(gen.doc_len[1], 16)) as pool:
            pool.wait_ready()
            t0 = time.time()
            futs = TrafficGenerator.replay(trace, pool.submit,
                                           pace=args.pace)
            for f in futs:
                f.result()
            dt = time.time() - t0
            m = pool.metrics()
        print(f"served {m['requests']} requests in {dt:.2f}s over "
              f"{replicas} {backend} replicas "
              f"({m['requests']/dt:.1f} QPS sustained, target {args.qps})")
        print(f"  latency p50 {m.get('p50_ms', 0):.1f}ms  "
              f"p99 {m.get('p99_ms', 0):.1f}ms  "
              f"batches {m['batches']} (mean fill {m['mean_fill']:.1f}); "
              f"dispatch {m['dispatch']}, deaths {m['deaths']}, "
              f"respawns {m['respawns']}")
        return
    with ServingEngine(server, max_batch=args.batch,
                       max_delay_ms=args.max_delay_ms,
                       max_len=_round_up(gen.doc_len[1], 16)) as eng:
        compiled = eng.prewarm()
        t0 = time.time()
        futs = TrafficGenerator.replay(trace, eng.submit, pace=args.pace)
        for f in futs:
            f.result()
        dt = time.time() - t0
        m = eng.metrics()
        assert eng.compile_count() == compiled, "recompiled under traffic!"
    print(f"served {m['requests']} requests in {dt:.2f}s "
          f"({m['requests']/dt:.1f} QPS sustained, target {args.qps})")
    print(f"  latency p50 {m.get('p50_ms', 0):.1f}ms  "
          f"p99 {m.get('p99_ms', 0):.1f}ms  "
          f"batches {m['batches']} (mean fill {m['mean_fill']:.1f})")
    if server.hot_cache is not None:
        s = server.hot_cache.stats
        print(f"  hot-row cache: {s.hits} hits / {s.misses} misses "
              f"({100 * s.hit_rate:.1f}%)")


def serve_lda(args) -> None:
    cfg = LDAConfig(num_topics=args.topics, vocab_size=args.vocab)
    store = ParameterStore(args.workdir, num_topics=args.topics,
                           vocab_capacity=args.vocab,
                           buffer_rows=args.buffer_rows)
    if store.phi_k.sum() == 0:
        raise SystemExit(
            f"no trained φ̂ under {args.workdir}; run launch/train.py first"
        )
    server = TopicServer(store, cfg, active_topics=args.active_topics,
                         phi_dtype=args.phi_dtype, hot_rows=args.hot_rows)
    if args.traffic:
        serve_traffic(args, server)
        return
    corpus, _ = synthetic_lda_corpus(args.requests, args.vocab,
                                     args.topics, seed=123)
    ids = list(range(corpus.num_docs))
    records: List[dict] = []
    t0 = time.time()
    for chunk, theta in server.infer_stream(corpus, ids, args.batch,
                                            records=records):
        top = np.argsort(-theta, axis=1)[:, :3]
        if chunk[0] == ids[0]:
            for d in range(min(4, len(chunk))):
                mix = ", ".join(
                    f"k{int(k)}:{theta[d, k]:.2f}" for k in top[d]
                )
                print(f"  doc{chunk[d]:4d} top topics: {mix}")
    dt = time.time() - t0
    print(f"served {len(ids)} docs in {dt:.2f}s "
          f"({len(ids)/dt:.1f} docs/s, batch={args.batch}, "
          f"{records[-1]['sweeps']} fixed-point sweeps on the last batch)")


def serve_lm(args) -> None:
    cfg = ARCHS[args.arch].reduced()
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    B, prompt_len, gen = args.batch, 16, args.gen_tokens

    batch = {"tokens": jnp.ones((B, prompt_len), jnp.int32)}
    if cfg.frontend == "image_patches":
        batch["image_embeds"] = jnp.ones(
            (B, cfg.image_tokens, cfg.d_model), jnp.float32) * 0.01
    logits, pre_caches = model.prefill(params, batch)
    cache = model.init_cache(B, prompt_len + gen)
    cache = jax.tree.map(
        lambda dst, src: jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), (0,) * dst.ndim
        ) if dst.ndim == src.ndim else dst,
        cache, pre_caches,
    )

    @jax.jit
    def step(params, cache, tok, pos):
        b = {"tokens": tok}
        if cfg.frontend == "image_patches":
            b["image_embeds"] = batch["image_embeds"]
        lg, cache = model.decode_step(params, cache, b, pos)
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        return nxt[:, None], cache

    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    out: List[np.ndarray] = [np.asarray(tok)]
    t0 = time.time()
    for i in range(gen):
        tok, cache = step(params, cache, tok, jnp.int32(prompt_len + i))
        out.append(np.asarray(tok))
    dt = time.time() - t0
    print(f"{args.arch}: generated {gen}×{B} tokens in {dt:.2f}s "
          f"({B*gen/dt:.1f} tok/s); sample: {np.concatenate(out,1)[0][:16]}")


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=LDA_ARCH)
    ap.add_argument("--workdir", default="/tmp/repro_train")
    ap.add_argument("--topics", type=int, default=100)
    ap.add_argument("--vocab", type=int, default=5000)
    ap.add_argument("--buffer-rows", type=int, default=2048)
    ap.add_argument("--active-topics", type=int, default=0,
                    help="restrict each word's fit support to its top-A "
                         "topics by trained φ mass (0 = dense fit)")
    ap.add_argument("--requests", type=int, default=512)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--gen-tokens", type=int, default=32)
    ap.add_argument("--traffic", action="store_true",
                    help="drive the continuous-batching engine with "
                         "synthetic Zipf/Poisson traffic and report "
                         "p50/p99 latency + sustained QPS")
    ap.add_argument("--qps", type=float, default=200.0,
                    help="offered request rate for --traffic")
    ap.add_argument("--pace", action="store_true",
                    help="honour arrival timestamps (open-loop latency "
                         "run) instead of submitting back-to-back")
    ap.add_argument("--max-delay-ms", type=float, default=5.0,
                    help="continuous-batching flush deadline")
    ap.add_argument("--phi-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8"),
                    help="serving storage dtype of the frozen φ block")
    ap.add_argument("--hot-rows", type=int, default=0,
                    help="capacity of the serving hot-word φ-row cache "
                         "(0 = disabled)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve --traffic through a ReplicaPool of N "
                         "data-parallel workers (1 = the single-replica "
                         "engine)")
    ap.add_argument("--replica-backend", default="thread",
                    choices=("process", "thread"),
                    help="replica isolation: in-process threads, one "
                         "device each (the only backend on a TPU host, "
                         "where one process holds the chips) or one "
                         "spawned process per replica (CPU hosts)")
    args = ap.parse_args()
    if args.arch == LDA_ARCH:
        serve_lda(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
