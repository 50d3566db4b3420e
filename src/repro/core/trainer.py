"""FOEMTrainer — the single-host lifelong-learning runtime (paper Fig. 4 + §3.2).

Per minibatch:
  1. vocab-major reorganisation (``localize_vocab``) → W_s unique words;
  2. fetch exactly those φ̂ rows from the ParameterStore (disk/host tier,
     LRU-buffered) — parameter streaming;
  3. run the jitted FOEM inner loop on the (W_s, K) local view;
  4. write the updated rows back, update the (K,) topic totals, advance the
     stream cursor, optionally checkpoint (fault-tolerant restart point).

With ``prefetch_depth > 0``, stages 1-2 for minibatch s+1 run on a background
thread while the device executes minibatch s, and stage 4's write-back is
reconciled against in-flight fetches (see ``streaming.StreamPrefetcher``) —
the pipelined step costs ≈ max(device compute, host I/O) instead of their
sum, with bitwise-identical results.

Two memory regimes, one algorithm.  Where the whole φ̂ table fits on the
device beside the compiled step (``device_tier_fits``), the store holds it
there as a device row tier: stage 2 is a gather on the device, stage 4 a
scatter, and no row crosses to the host until someone reads the store.  The
device then holds W_cap·K for φ̂ plus the step's O(K·(D_s + NNZ_s + W_s)).
Otherwise rows stream from the host store, and the device never holds more
than O(K·(D_s + NNZ_s + W_s)) — the paper's space bound with W* =
buffer_rows.  Both regimes give bitwise-identical results.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import em, foem, sem
from repro.core.streaming import ParameterStore, StreamPrefetcher, tier_ids
from repro.core.types import GlobalStats, LDAConfig, MinibatchData
from repro.runtime import faults as fault_lib
from repro.runtime.spans import span
from repro.sparse.docword import VOCAB_BUCKET, pad_vocab_rows
from repro.sparse.minibatch import Minibatch, MinibatchStream

#: Share of the device's memory the fit rule leaves free beside the tier and
#: the compiled step: the tier's gather and scatter, the allocator's
#: fragmentation and whatever else the process keeps on the device.
TIER_MARGIN = 1 / 16


def device_tier_fits(device, table_bytes: int,
                     step_bytes: Callable[[], int]) -> bool:
    """The fit rule of the device row tier: the table's bytes, the compiled
    step's (``step_bytes()``, read only when there is a limit) and a margin
    of ``TIER_MARGIN`` fit in the device's ``bytes_limit``.  A device that
    states no limit (the CPU backend) fits."""
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        return True
    return table_bytes + step_bytes() + TIER_MARGIN * limit <= limit


@dataclasses.dataclass
class StepMetrics:
    """One step's record.  ``seconds`` is span ``foem.step``; the host-stage
    fields are 0 for a dropped step."""

    step: int
    sweeps: int
    train_ppl: float
    seconds: float
    disk_reads: int
    disk_writes: int
    buffer_hits: int
    prefetch_hit: bool = False      # rows were staged before we needed them
    overlap_seconds: float = 0.0    # host I/O hidden behind device compute
    residual_mass: float = float("nan")  # eq. 36 Σ r_w at sweep exit (foem)
    published_version: int = -1     # φ snapshot published at this step (-1: none)
    shift_events: Tuple = ()        # ShiftEvents the detector fired this step
    fetch_seconds: float = 0.0      # foem.fetch of this step's rows (any thread)
    lock_wait_seconds: float = 0.0  # trainer thread blocked on the store lock
    host_seconds: float = 0.0       # foem.step minus foem.device_wait
    h2d_bytes: int = 0              # host arrays passed into the step program
    d2h_bytes: int = 0              # arrays device_get returned
    rows: int = 0                   # the step's φ̂ rows (W_s)
    tier_rows: int = 0              # of them, served from the device tier
    tier_uploads: int = 0           # rows uploaded to the tier for this step


class FOEMTrainer:
    """Streaming FOEM with disk-backed parameters (the paper's full system)."""

    def __init__(
        self,
        cfg: LDAConfig,
        store: ParameterStore,
        *,
        seed: int = 0,
        checkpoint_every: int = 0,
        algorithm: str = "foem",   # "foem" | "sem"
        prefetch_depth: int = 1,   # 0 = fully synchronous host I/O
        faults: Optional[fault_lib.FaultPlan] = None,
        publisher=None,            # streaming.SnapshotPublisher | None
        publish_every: int = 0,    # publish a φ snapshot every N steps
        shift_detector=None,       # scheduling.ShiftDetector | None
        refresh_extra_sweeps: int = 2,  # extra warm-ups on a detected shift
    ):
        if store.K != cfg.K:
            raise ValueError("store/config topic count mismatch")
        self.cfg = cfg
        self.store = store
        self.key = jax.random.PRNGKey(seed)
        self.checkpoint_every = checkpoint_every
        self.algorithm = algorithm
        self.prefetch_depth = int(prefetch_depth)
        self.faults = faults
        self.publisher = publisher
        self.publish_every = int(publish_every)
        self.shift_detector = shift_detector
        self.refresh_extra_sweeps = int(refresh_extra_sweeps)
        # steps whose contribution a seeded "drop" fault discarded — the
        # re-issue queue a driver replays through MinibatchStream
        self.dropped_steps: List[int] = []
        self.history: List[StepMetrics] = []
        # snapshot of cumulative store I/O counters at the last step boundary
        # (read under the store lock — a concurrent stats_window(reset) from
        # the serving side must not observe a torn triple)
        self._stats_base = store.bump_pipeline_stats()
        # jit cache keyed by the (D_s, L) batch shape and the W_s bucket:
        # rows are zero-padded to ``docword.VOCAB_BUCKET`` (a multiple of
        # the sublane tile, so the sweep kernels are eligible) and a
        # stream of varying W_s compiles once per bucket
        self._jit_cache: Dict = {}
        # device row tier: the fit rule's answer per step shape; once it
        # says no, the trainer streams rows for good (no attach/detach
        # thrash).  Staged fetches older than _fetch_floor predate a
        # detach and miss the tier's writes: they are fetched again.
        self._device = jax.devices()[0]
        self._tier_fit: Dict = {}
        self._tier_off = store.readonly
        self._tier_uploads = 0
        self._fetch_floor = 0

    # ------------------------------------------------------------------

    def _local_step_fn(self, algorithm: str, cfg: Optional[LDAConfig] = None):
        if cfg is None:
            cfg = self.cfg

        if algorithm == "foem":
            def run(key, batch, phi_rows, phi_k, live_w, num_words):
                res = foem.foem_minibatch(
                    key, batch, phi_rows, phi_k, cfg, vocab_size=live_w,
                    num_words=num_words,
                )
                return (
                    res.phi_wk,
                    res.phi_k,
                    res.diag.sweeps_run,
                    res.diag.final_train_ppl,
                    res.diag.residual_mass,
                )
        elif algorithm == "sem":
            def run(key, batch, phi_rows, phi_k, live_w, num_words):
                stats = GlobalStats(phi_wk=phi_rows, phi_k=phi_k, step=jnp.int32(0))
                new_stats, local, diag = sem.sem_step(
                    key, batch, stats, cfg, vocab_size=live_w
                )
                return (
                    new_stats.phi_wk,
                    new_stats.phi_k,
                    diag.sweeps_run,
                    diag.final_train_ppl,
                    jnp.float32(float("nan")),   # no residual scheduler
                )
        else:
            raise ValueError(f"unknown algorithm {algorithm!r}")
        # Donate the (W_s, K) rows and (K,) totals: the inner loop rewrites
        # both wholesale, so the device can update them in place instead of
        # copying per step.  (CPU has no donation; skip the warning there.)
        donate = () if jax.default_backend() == "cpu" else (2, 3)
        fn = jax.jit(run, donate_argnums=donate)
        if not cfg.debug_checks:
            return fn
        # checkify functionalizes the sanitizer's checks through the jitted
        # inner loop (checkify.check cannot be staged bare); a fired
        # invariant surfaces as JaxRuntimeError at the step boundary
        from jax.experimental import checkify

        checked = checkify.checkify(fn)

        def run_checked(*args):
            err, out = checked(*args)
            err.throw()
            return out

        run_checked.jitted = fn      # what the fit rule reads the memory of
        return run_checked

    def _get_step_fn(self, shapes, refresh: bool = False):
        key = (self.algorithm, shapes, refresh)
        fn = self._jit_cache.get(key)
        if fn is None:
            cfg = self.cfg
            if refresh:
                # a detected topic shift grants the step extra full
                # (unscheduled) warm-up sweeps — the Fig. 4 residual
                # re-initialisation applied mid-stream
                cfg = dataclasses.replace(
                    cfg,
                    warmup_sweeps=min(
                        cfg.max_sweeps,
                        cfg.warmup_sweeps + self.refresh_extra_sweeps,
                    ),
                )
            fn = self._local_step_fn(self.algorithm, cfg)
            self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------

    def _shapes(self, mb: Minibatch):
        """The step program's cache key: (D_s, L) and the padded rows."""
        w_pad = -(-len(mb.local_vocab) // VOCAB_BUCKET) * VOCAB_BUCKET
        return (mb.local_word_ids.shape, (w_pad, self.cfg.K))

    def _step_bytes(self, mb: Minibatch, shapes) -> int:
        """Device bytes of the compiled step for ``shapes``: arguments +
        outputs + temporaries − donated, from the jitted program that
        ``_local_step_fn`` built (a planted fault may wrap what
        ``_get_step_fn`` returns).  The call that follows reuses this
        compile."""
        self._get_step_fn(shapes)
        fn = self._jit_cache[(self.algorithm, shapes, False)]
        fn = getattr(fn, "jitted", fn)
        batch = MinibatchData(word_ids=jnp.asarray(mb.local_word_ids),
                              counts=jnp.asarray(mb.counts))
        mem = fn.lower(
            self.key, batch, jax.ShapeDtypeStruct(shapes[1], self.store.dtype),
            jax.ShapeDtypeStruct((self.cfg.K,), jnp.float32),
            max(self.store.live_vocab, self.cfg.W), jnp.int32(0),
        ).compile().memory_analysis()
        return (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    def _use_tier(self, mb: Minibatch) -> bool:
        """Whether this step's rows live in the store's device tier.

        The fit rule answers once per step shape.  A yes attaches the tier
        (if it is not); the first no detaches it, writing its dirty rows
        back, and the trainer streams rows from then on.
        """
        if self._tier_off:
            return False
        shapes = self._shapes(mb)
        fits = self._tier_fit.get(shapes)
        if fits is None:
            fits = self._tier_fit[shapes] = device_tier_fits(
                self._device, self.store.tier_bytes(),
                lambda: self._step_bytes(mb, shapes))
        if not fits:
            self._tier_off = True
            self.store.detach_tier()
            self._fetch_floor = self.store.write_version
            return False
        if not self.store.has_tier:
            self._tier_uploads += self.store.attach_tier()
        return True

    def step(self, mb: Minibatch) -> StepMetrics:
        """Synchronous step: fetch → compute → write back."""
        lock0 = self.store.lock_wait_seconds()
        with span("foem.step") as whole:
            tier = self._use_tier(mb)
            with span("foem.fetch") as fetch:
                phi_rows = (None if tier                       # (W_s, K)
                            else self.store.fetch_rows(mb.local_vocab))
            m, _, waited = self._step_with_rows(mb, phi_rows)
        return self._timed(m, whole.seconds, waited, fetch.seconds, lock0)

    def _step_with_rows(
        self,
        mb: Minibatch,
        phi_rows: Optional[np.ndarray],
        *,
        prefetch_hit: bool = False,
        overlap_seconds: float = 0.0,
    ) -> Tuple[StepMetrics, Optional[np.ndarray], float]:
        """Run the jitted inner loop on pre-fetched rows and write back;
        ``phi_rows=None`` gathers and scatters them in the device tier.

        Returns ``(metrics, new_rows, device_wait_seconds)`` — new_rows
        (None on the tier) feed the prefetch reconciliation log.  It runs
        inside the caller's
        ``foem.step`` span, which sets the timings with :meth:`_timed`.
        I/O counters are per-step deltas of the store's cumulative stats;
        in the pipelined path a step's delta includes the *next*
        minibatch's background fetch (sums over the run are exact either
        way).
        """
        cfg = self.cfg
        # pre-probe: a "kill" raises before any state is touched; a "drop"
        # skips this minibatch entirely (contribution lost → re-issue queue)
        if self.faults is not None and self.faults.fire(
            fault_lib.PRE_PROBE, step=self.store.step
        ):
            return self._dropped_step(), phi_rows, 0.0
        self.store.ensure_vocab(int(mb.local_vocab.max(initial=0)))
        phi_k = self.store.phi_k.astype(np.float32)                # (K,)
        self.key, sub = jax.random.split(self.key)
        refresh = (
            self.shift_detector.consume_refresh()
            if self.shift_detector is not None else False
        )
        tier = phi_rows is None
        num_words = len(mb.local_vocab)
        if tier:
            ids = tier_ids(mb.local_vocab, self.store.capacity)
        else:
            with span("foem.pad_rows"):
                padded = pad_vocab_rows(phi_rows)
        with span("foem.stage_in"):
            host_in = (mb.local_word_ids, mb.counts,
                       ids if tier else padded, phi_k)
            batch = MinibatchData(
                word_ids=jnp.asarray(mb.local_word_ids),
                counts=jnp.asarray(mb.counts),
            )
            if tier:
                ids = jnp.asarray(ids)
                rows_in = self.store.tier_gather(ids)
            else:
                rows_in = jnp.asarray(padded)
            step_fn = self._get_step_fn(self._shapes(mb), refresh=refresh)
            live_w = max(self.store.live_vocab, cfg.W)
            out = step_fn(
                sub, batch, rows_in, jnp.asarray(phi_k), live_w,
                jnp.int32(num_words),
            )
        # One transfer for rows, totals AND the diagnostic scalars: fetching
        # int(sweeps)/float(ppl) separately would stall the prefetch pipeline
        # with two extra device syncs after the row sync.  On the tier the
        # rows stay on the device.
        with span("foem.device_wait") as waited:
            fetched = jax.device_get(out[1:] if tier else out)
        if tier:
            new_rows = out[0]
            new_phi_k, sweeps, ppl, res_mass = fetched
        else:
            new_rows, new_phi_k, sweeps, ppl, res_mass = fetched
            new_rows = new_rows[:num_words]     # drop the bucket padding
        new_phi_k = np.asarray(new_phi_k, np.float64)  # lint: host-f64 — RAM accumulator

        # post-fold: the local fold is complete but unpublished — a "kill"
        # here loses exactly this minibatch (the paper's restart unit); a
        # "drop" discards the fold without touching the store.
        if self.faults is not None and self.faults.fire(
            fault_lib.POST_FOLD, step=self.store.step
        ):
            return self._dropped_step(), phi_rows, 0.0

        with span("foem.write_back"):
            if tier:
                self.store.tier_write(mb.local_vocab, ids, new_rows)
                new_rows = None
            else:
                self.store.write_rows(mb.local_vocab, new_rows)
            self.store.phi_k = new_phi_k
            self.store.step += 1
            if self.checkpoint_every and self.store.step % self.checkpoint_every == 0:
                self.store.flush()

        # --- lifelong: publish a committed φ snapshot on the cadence ---
        published = -1
        if (
            self.publisher is not None
            and self.publish_every
            and self.store.step % self.publish_every == 0
        ):
            with span("foem.publish"):
                published = self.publisher.publish().version

        # --- topic-shift detection over this step's stream signals ---
        events: Tuple = ()
        if self.shift_detector is not None:
            with span("foem.shift"):
                events = tuple(self.shift_detector.update(
                    step=self.store.step,
                    residual_mass=float(res_mass),
                    perplexity=float(ppl),
                    phi_k=new_phi_k,
                ))

        base = self._stats_base
        self._stats_base = self.store.bump_pipeline_stats(
            overlap_seconds=overlap_seconds, prefetch_hit=prefetch_hit
        )
        m = StepMetrics(
            step=self.store.step,
            sweeps=int(sweeps),
            train_ppl=float(ppl),
            seconds=0.0,                        # set by _timed
            disk_reads=self._stats_base[0] - base[0],
            disk_writes=self._stats_base[1] - base[1],
            buffer_hits=self._stats_base[2] - base[2],
            prefetch_hit=prefetch_hit,
            overlap_seconds=overlap_seconds,
            residual_mass=float(res_mass),
            published_version=published,
            shift_events=events,
            h2d_bytes=sum(a.nbytes for a in host_in),
            d2h_bytes=sum(np.asarray(a).nbytes for a in fetched),
            rows=num_words,
        )
        if tier:
            m.tier_rows, m.tier_uploads = num_words, self._tier_uploads
            self._tier_uploads = 0
        self.history.append(m)
        return m, new_rows, waited.seconds

    def _dropped_step(self) -> StepMetrics:
        """Account for a minibatch whose contribution a fault discarded.

        The store is untouched and the cursor still advances (the stream
        consumed the minibatch); the step index lands in
        ``dropped_steps`` so a driver can re-issue it.  Metrics carry
        ``sweeps=0`` / ``ppl=nan`` and zero host-stage fields — a
        visibly-dropped cell, not a fake convergence point.
        """
        self.store.step += 1
        self.dropped_steps.append(self.store.step)
        base = self._stats_base
        self._stats_base = self.store.bump_pipeline_stats()
        m = StepMetrics(
            step=self.store.step,
            sweeps=0,
            train_ppl=float("nan"),
            seconds=0.0,                        # set by _timed
            disk_reads=self._stats_base[0] - base[0],
            disk_writes=self._stats_base[1] - base[1],
            buffer_hits=self._stats_base[2] - base[2],
        )
        self.history.append(m)
        return m

    def _timed(self, m: StepMetrics, seconds: float, device_wait: float,
               fetch_seconds: float, lock0: float) -> StepMetrics:
        """Set a step's timings once its ``foem.step`` span has closed.
        ``lock0`` is the trainer thread's store lock wait when the step
        began; a dropped step (``sweeps == 0``) keeps its host-stage fields
        at 0."""
        m.seconds = seconds
        if m.sweeps:
            m.fetch_seconds = fetch_seconds
            m.lock_wait_seconds = self.store.lock_wait_seconds() - lock0
            m.host_seconds = seconds - device_wait
        return m

    # ------------------------------------------------------------------

    def fit_stream(
        self,
        stream: Iterator[Minibatch],
        max_steps: Optional[int] = None,
        callback: Optional[Callable[[StepMetrics], None]] = None,
    ) -> List[StepMetrics]:
        """Train on ``stream``; the store is flushed at its end.  The call
        leaves φ̂ in the host store: it detaches the device tier when it
        returns or raises."""
        try:
            if self.prefetch_depth > 0:
                return self._fit_stream_prefetched(stream, max_steps, callback)
            out = []
            for mb in stream:
                if max_steps is not None and len(out) >= max_steps:
                    break
                m = self.step(mb)
                out.append(m)
                if callback:
                    callback(m)
            self.store.flush()
            return out
        finally:
            self.store.detach_tier()

    def _fit_stream_prefetched(
        self,
        stream: Iterator[Minibatch],
        max_steps: Optional[int],
        callback: Optional[Callable[[StepMetrics], None]],
    ) -> List[StepMetrics]:
        """Pipelined loop: the worker fetches minibatch s+1's rows (and runs
        the stream's bucketize/localize) while the device computes on s.

        A staged fetch may predate recent write-backs; every write is logged
        with its ``write_version`` and patched into newer-versioned fetches
        before compute — results are bitwise-identical to the sync path.
        On the device tier the worker stages no rows and nothing is
        reconciled; a batch staged without rows, or before a detach, is
        fetched again on the trainer thread.  A step's ``foem.step`` span
        starts before the queue handover, so the step pays its (residual)
        I/O wait.
        """
        out: List[StepMetrics] = []
        pf = StreamPrefetcher(self.store, stream, depth=self.prefetch_depth)
        # (version, ids, rows) of recent write-backs; a staged fetch can be
        # at most depth+1 writes behind.
        writes: deque = deque(maxlen=self.prefetch_depth + 2)
        it = iter(pf)
        try:
            while max_steps is None or len(out) < max_steps:
                lock0 = self.store.lock_wait_seconds()
                with span("foem.step") as whole:
                    with span("foem.wait_staged") as wait:
                        staged = next(it, None)
                    if staged is None:
                        break
                    mb, rows = staged.minibatch, staged.phi_rows
                    fetch_seconds = staged.fetch_seconds
                    if self._use_tier(mb):
                        rows = None
                    elif rows is None or staged.version < self._fetch_floor:
                        # staged while the tier held the rows
                        with span("foem.fetch") as fetch:
                            rows = self.store.fetch_rows(mb.local_vocab)
                        fetch_seconds += fetch.seconds
                    else:
                        with span("foem.reconcile"):
                            for ver, w_ids, w_rows in writes:
                                if ver > staged.version:
                                    _, ia, ib = np.intersect1d(
                                        mb.local_vocab, w_ids,
                                        assume_unique=True,
                                        return_indices=True,
                                    )
                                    rows[ia] = w_rows[ib]
                    # a hit means the rows were already staged when we
                    # arrived (wait ≈ queue overhead); blocking is a miss
                    overlap = max(0.0, staged.fetch_seconds - wait.seconds)
                    m, new_rows, waited = self._step_with_rows(
                        mb, rows,
                        prefetch_hit=wait.seconds < 1e-3,
                        overlap_seconds=overlap,
                    )
                self._timed(m, whole.seconds, waited, fetch_seconds, lock0)
                if new_rows is not None:
                    writes.append(
                        (self.store.write_version, mb.local_vocab, new_rows)
                    )
                out.append(m)
                if callback:
                    callback(m)
        finally:
            pf.close()
        self.store.flush()
        return out

    # ------------------------------------------------------------------

    def resume_step(self) -> int:
        """Restart point: minibatches already consumed (fault tolerance)."""
        return self.store.step
