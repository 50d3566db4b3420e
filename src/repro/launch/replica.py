"""Multi-replica data-parallel serving: a :class:`ReplicaPool` of N
serving workers behind one admission router.

The single-replica engine (PR 8) is throughput-capped by one GIL-bound
launcher thread; `Towards Big Topic Modeling` (PAPERS.md) motivates
scaling the same frozen-φ model over data-parallel workers, and Cappé's
online-EM argument is why placement is *free*: per-document PRNG keys and
a pinned φ snapshot make replica assignment semantically invisible — the
same document resolves to the bitwise-identical θ̂ on any replica (at
``rel_tol = 0``; see ``pad_batch`` for the ``rel_tol > 0`` caveat).

::

    submit() ──► AdmissionRouter (PR 8's slots + deadline collector)
                     │ dispatcher thread: least-loaded pick under the
                     │ per-replica in-flight cap (ReplicaBalancer)
                     ▼
       per-replica task queues ──► N replica workers
         "process" backend: one spawned process per replica, its own
           TopicServer + HotRowCache over a READONLY store attach
           (multiprocessing scales the launcher past the GIL)
         "thread" backend: one thread per replica (the device-mesh
           degenerate case — each replica pins a local jax device)
                     │ shared result queue
                     ▼
       results thread resolves futures (ThetaResult.version intact)

Fault handling reuses the PR 7 machinery: a seeded
:class:`~repro.runtime.faults.FaultPlan` in a worker fires the
``REPLICA_KILL`` point between receiving a batch and launching it
(``hard=True`` SIGKILLs the worker mid-flight).  The monitor thread
detects the loss, re-issues the dead worker's in-flight batches to
survivors — the *identical padded payload*, so re-issued results match an
unfaulted run bitwise — and respawns (or downsizes) the pool.  No
submitted Future is ever dropped.

Hot-swaps stay version-consistent across replicas: the pool subscribes
every worker to the PR 9 :class:`~repro.core.SnapshotPublisher` by
broadcasting each published snapshot (full payload + crc manifest)
through the task queues; responses carry ``ThetaResult.version``, and
pool-level ``max_staleness_versions`` is the max over replicas' launches.
"""
from __future__ import annotations

import dataclasses
import multiprocessing
import queue
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import LDAConfig, ParameterStore, PhiSnapshot
from repro.launch import serve as serve_mod
from repro.launch.serve import AdmissionRouter, TopicServer, pad_batch
from repro.runtime import faults as fault_lib


class ReplicaBalancer:
    """Pure least-loaded dispatch accounting — no threads, no I/O.

    The pool's dispatcher drives one instance under its own lock; the
    hypothesis property suite drives it directly with arbitrary
    interleavings of add / acquire / complete / remove / version notes.

    Invariants (raised on violation, never silently repaired):

    * per-replica in-flight count never goes negative — completing an
      idle replica raises;
    * :meth:`acquire` only returns a replica strictly under ``cap``, and
      always a least-loaded one (ties break to the smallest id);
    * per-replica φ version notes are monotone — a replica reporting an
      older version than it already served is a protocol violation.
    """

    def __init__(self, cap: int = 2):
        if cap < 1:
            raise ValueError("per-replica in-flight cap must be >= 1")
        self.cap = int(cap)
        self._inflight: Dict[int, int] = {}
        self._version: Dict[int, int] = {}

    # -------------------------------------------------------- membership

    def add(self, rid: int) -> None:
        if rid in self._inflight:
            raise ValueError(f"replica {rid} already registered")
        self._inflight[rid] = 0
        # a respawned rid keeps its version floor: the replacement is
        # sent the latest snapshot first, so monotonicity still holds
        self._version.setdefault(rid, -1)

    def remove(self, rid: int) -> int:
        """Deregister a (dead) replica; returns the in-flight count it
        held — the orphans the pool must re-issue."""
        orphans = self._inflight.pop(rid)
        return orphans

    def replicas(self) -> List[int]:
        return sorted(self._inflight)

    # ---------------------------------------------------------- dispatch

    def acquire(self) -> Optional[int]:
        """Least-loaded replica strictly under the cap (ties -> smallest
        id), with its in-flight count bumped; ``None`` when every replica
        is at the cap (the caller waits for a completion)."""
        free = [(n, rid) for rid, n in self._inflight.items()
                if n < self.cap]
        if not free:
            return None
        _, rid = min(free)
        self._inflight[rid] += 1
        return rid

    def acquire_specific(self, rid: int) -> bool:
        """Pin-path acquire: bump ``rid`` iff it is registered and under
        the cap (the placement-parity tests force placement with this)."""
        if self._inflight.get(rid, self.cap) >= self.cap:
            return False
        self._inflight[rid] += 1
        return True

    def complete(self, rid: int) -> None:
        if rid not in self._inflight:
            raise KeyError(f"completion for unregistered replica {rid}")
        if self._inflight[rid] <= 0:
            raise ValueError(
                f"replica {rid} completion with zero in-flight — "
                "accounting would go negative"
            )
        self._inflight[rid] -= 1

    def inflight(self, rid: int) -> int:
        return self._inflight[rid]

    def total_inflight(self) -> int:
        return sum(self._inflight.values())

    # ---------------------------------------------------------- versions

    def note_version(self, rid: int, version: int) -> None:
        old = self._version.get(rid, -1)
        if version < old:
            raise ValueError(
                f"replica {rid} φ version moved backwards "
                f"({old} -> {version}); hot-swaps must be monotone"
            )
        self._version[rid] = version

    def versions(self) -> Dict[int, int]:
        return {rid: self._version.get(rid, -1) for rid in self._inflight}

    def min_version(self) -> int:
        if not self._inflight:
            return -1
        return min(self._version.get(rid, -1) for rid in self._inflight)


@dataclasses.dataclass(frozen=True)
class ReplicaSpec:
    """Everything a worker process needs to rebuild its serving stack.

    Picklable and shipped once at spawn (the pool uses the ``spawn``
    context: a forked child would inherit jax's internal threads
    mid-state).  The worker attaches the trained store READONLY
    (:meth:`ParameterStore.attach`) — serving processes never write
    through the store; φ updates arrive via the snapshot broadcast.

    ``sim_service_ms > 0`` replaces the launch with a sleep of that
    duration (a device-model worker: the launcher waits as an async
    accelerator would run).  Used only by the ``router_saturation`` bench
    cell, where replica scaling must measure the router/dispatch path
    rather than host-core arithmetic; results are uniform θ placeholders.

    ``fault_specs`` seed a per-worker :class:`FaultPlan` that fires the
    ``REPLICA_KILL`` point (``shard`` = replica id, ``step`` = the
    worker's batch counter) between receiving a batch and launching it.
    """

    store_path: str
    cfg: LDAConfig
    vocab_capacity: int
    fit_sweeps: int = 50
    rel_tol: Optional[float] = None
    check_every: Optional[int] = None
    active_topics: int = 0
    use_pallas: Optional[bool] = None
    interpret: bool = False
    vocab_pad: int = 512
    phi_dtype: str = "float32"
    hot_rows: int = 0
    buffer_rows: int = 0
    sim_service_ms: float = 0.0
    fault_specs: Tuple[fault_lib.FaultSpec, ...] = ()

    def build_server(self) -> TopicServer:
        store = ParameterStore.attach(
            self.store_path, num_topics=self.cfg.num_topics,
            vocab_capacity=self.vocab_capacity,
            buffer_rows=self.buffer_rows,
        )
        return TopicServer(
            store, self.cfg, self.fit_sweeps,
            rel_tol=self.rel_tol, check_every=self.check_every,
            active_topics=self.active_topics, use_pallas=self.use_pallas,
            interpret=self.interpret, vocab_pad=self.vocab_pad,
            phi_dtype=self.phi_dtype, hot_rows=self.hot_rows,
        )


def snapshot_payload(snap: PhiSnapshot) -> dict:
    """Pickle-ready swap broadcast: the full φ epoch + its crc manifest.

    The worker rebuilds a :class:`PhiSnapshot` from these arrays and
    compares the recomputed crc against the publisher's — corruption
    crossing the process boundary fails loudly instead of serving
    garbage (the same contract ``TopicServer.refresh`` enforces
    in-process).
    """
    return {
        "version": snap.version,
        "phi": np.asarray(snap.phi),
        "phi_k": np.asarray(snap.phi_k),
        "step": snap.step,
        "live_vocab": snap.live_vocab,
        "write_version": snap.write_version,
        "flush_version": snap.flush_version,
        "changed_ids": np.asarray(snap.changed_ids),
        "crc": snap.crc,
    }


class _SwapMailbox:
    """A one-snapshot ``SnapshotPublisher`` stand-in inside a replica.

    ``TopicServer.subscribe``/``refresh`` only need ``latest()`` and
    ``version``; the parent's swap broadcast fills the box.  Because the
    task queue is FIFO, every batch enqueued after a swap broadcast is
    served on (at least) that version — the pool-wide staleness bound.
    """

    def __init__(self):
        self._snap: Optional[PhiSnapshot] = None
        self.version = 0

    def install(self, payload: dict) -> PhiSnapshot:
        snap = PhiSnapshot(
            version=payload["version"], phi=payload["phi"],
            phi_k=payload["phi_k"], step=payload["step"],
            live_vocab=payload["live_vocab"],
            write_version=payload["write_version"],
            flush_version=payload["flush_version"],
            changed_ids=payload["changed_ids"],
        )
        if snap.crc != payload["crc"]:
            raise RuntimeError(
                f"φ snapshot v{snap.version} failed its crc manifest "
                "crossing the process boundary — refusing to install"
            )
        self._snap = snap
        self.version = snap.version
        return snap

    def latest(self) -> Optional[PhiSnapshot]:
        return self._snap


def _serve_loop(rid: int, server: TopicServer, mailbox: _SwapMailbox,
                plan: Optional[fault_lib.FaultPlan], sim_service_ms: float,
                num_topics: int, task_q, result_q, device=None) -> None:
    """The replica message loop — identical for both backends.

    Messages in: ``("swap", payload)``, ``("prewarm", dims)``,
    ``("batch", batch_id, L, w, c, keys, filled)``, ``("stop",)``.
    Messages out: ``("ready"|"swapped"|"prewarmed"|"done"|"error"|
    "fault"|"bye", rid, ...)``.

    A ``hard=True`` kill at ``REPLICA_KILL`` SIGKILLs the process with
    the batch in flight — it is never acked, and the parent re-issues it.
    A soft kill raises :class:`InjectedFault` here: the replica reports
    and exits its loop (the thread-backend equivalent of dying).
    """
    import contextlib

    import jax

    ctx = (jax.default_device(device) if device is not None
           else contextlib.nullcontext())
    n_batches = 0
    result_q.put(("ready", rid))
    while True:
        msg = task_q.get()
        kind = msg[0]
        if kind == "stop":
            result_q.put(("bye", rid))
            return
        if kind == "swap":
            mailbox.install(msg[1])
            server.refresh()                 # between batches by FIFO order
            result_q.put(("swapped", rid, mailbox.version))
            continue
        if kind == "prewarm":
            with ctx:
                n = serve_mod.prewarm_server(server, **msg[1])
            result_q.put(("prewarmed", rid, n))
            continue
        _, batch_id, L, w, c, keys, filled = msg
        try:
            if plan is not None:
                plan.fire(fault_lib.REPLICA_KILL, shard=rid, step=n_batches)
            n_batches += 1
            if sim_service_ms > 0.0:
                # device-model service: no launch, so a record of its time
                t0 = time.perf_counter()
                time.sleep(sim_service_ms / 1e3)
                theta = np.full((w.shape[0], num_topics),
                                1.0 / num_topics, np.float32)
                rec = {"start": t0,
                       "launch_seconds": time.perf_counter() - t0,
                       "cache_hits": 0, "cache_misses": 0,
                       "version": (mailbox.version if mailbox.version > 0
                                   else -1)}
            else:
                with ctx:
                    theta, rec = server.launch(w, c, key=keys)
            result_q.put(("done", rid, batch_id,
                          np.asarray(theta[:filled]), rec))
        except fault_lib.InjectedFault as e:
            result_q.put(("fault", rid, str(e)))
            return                            # soft replica death
        except BaseException as e:            # deterministic failure: no
            result_q.put(("error", rid, batch_id, repr(e)))  # re-issue loop


def _replica_worker(rid: int, spec: ReplicaSpec, task_q, result_q) -> None:
    """Process-backend entry point (module-level for the spawn context)."""
    try:
        server = spec.build_server()
    except BaseException as e:
        result_q.put(("error", rid, -1, repr(e)))
        raise
    mailbox = _SwapMailbox()
    server.subscribe(mailbox, refresh=False)
    plan = (fault_lib.FaultPlan(spec.fault_specs)
            if spec.fault_specs else None)
    _serve_loop(rid, server, mailbox, plan, spec.sim_service_ms,
                spec.cfg.num_topics, task_q, result_q)


class _ProcessReplica:
    """Handle on one spawned worker process + its task queue."""

    backend = "process"

    def __init__(self, rid: int, spec: ReplicaSpec, result_q, ctx):
        self.rid = rid
        self.task_q = ctx.Queue()
        self.proc = ctx.Process(
            target=_replica_worker, args=(rid, spec, self.task_q, result_q),
            name=f"replica-{rid}", daemon=True,
        )
        self.proc.start()

    def send(self, msg) -> None:
        self.task_q.put(msg)

    def alive(self) -> bool:
        return self.proc.is_alive()

    def join(self, timeout: Optional[float] = None) -> None:
        self.proc.join(timeout)

    def kill(self) -> None:
        self.proc.kill()

    @property
    def exitcode(self):
        return self.proc.exitcode


class _ThreadReplica:
    """Handle on one in-process replica thread (device-mesh degenerate
    case: each replica optionally pins a local jax device)."""

    backend = "thread"

    def __init__(self, rid: int, server: TopicServer,
                 plan: Optional[fault_lib.FaultPlan], sim_service_ms: float,
                 num_topics: int, result_q, device=None):
        self.rid = rid
        self.task_q: "queue.Queue" = queue.Queue()
        mailbox = _SwapMailbox()
        server.subscribe(mailbox, refresh=False)
        self.thread = threading.Thread(
            target=_serve_loop,
            args=(rid, server, mailbox, plan, sim_service_ms, num_topics,
                  self.task_q, result_q, device),
            name=f"replica-{rid}", daemon=True,
        )
        self.thread.start()

    def send(self, msg) -> None:
        self.task_q.put(msg)

    def alive(self) -> bool:
        return self.thread.is_alive()

    def join(self, timeout: Optional[float] = None) -> None:
        self.thread.join(timeout)

    def kill(self) -> None:
        pass                                  # threads die via soft faults

    @property
    def exitcode(self):
        return None


class ReplicaPool:
    """N serving replicas behind one :class:`AdmissionRouter`.

    ``submit`` / ``drain`` / ``metrics`` / ``close`` mirror the
    single-replica :class:`ServingEngine` surface, so benches and callers
    swap between them freely.  See the module docstring for the thread
    and fault architecture.

    Parameters beyond the router's: ``backend`` ("process" spawns one
    worker process per replica and refuses to start on a TPU host, where
    the parent holds the chips; "thread" runs in-process replicas, one
    device each), ``max_inflight`` (per-replica dispatch
    cap — the balancer's least-loaded window), ``respawn`` (replace dead
    workers; ``False`` downsizes instead), and ``servers`` (thread
    backend only: prebuilt ``TopicServer``s, e.g. sharing the owning
    process's store for the placement-parity tests).
    """

    def __init__(self, spec: Optional[ReplicaSpec] = None, *,
                 replicas: int = 2, backend: str = "process",
                 servers: Optional[Sequence[TopicServer]] = None,
                 max_batch: int = 64, bucket_multiple: int = 16,
                 max_delay_ms: float = 5.0, max_len: int = 256,
                 queue_depth: int = 4, seed: int = 0,
                 max_inflight: int = 2, respawn: bool = True):
        if backend not in ("process", "thread"):
            raise ValueError(f"unknown replica backend {backend!r}")
        if backend == "process" and spec is None:
            raise ValueError("process backend needs a picklable ReplicaSpec")
        if backend == "process":
            import jax

            if jax.default_backend() == "tpu":
                # this process now holds the chips; a spawned worker that
                # initialises JAX would fail or hang waiting for them
                raise RuntimeError(
                    "the process replica backend cannot run on a TPU host: "
                    "the parent process holds the chips and spawned "
                    "workers cannot open them. Use backend=\"thread\" "
                    "(serve CLI: --replica-backend thread), which pins one "
                    "device per replica in this process."
                )
        if servers is not None and backend != "thread":
            raise ValueError("prebuilt servers are thread-backend only")
        if servers is not None and len(servers) != replicas:
            raise ValueError("need exactly one prebuilt server per replica")
        self.spec = spec
        self.backend = backend
        self.respawn = bool(respawn)
        self.router = AdmissionRouter(
            max_batch=max_batch, bucket_multiple=bucket_multiple,
            max_delay_ms=max_delay_ms, max_len=max_len,
            queue_depth=queue_depth, seed=seed,
        )
        self.balancer = ReplicaBalancer(cap=max_inflight)
        #: test hook — force every dispatch onto one replica id (the
        #: placement-parity tests compare pinned placements bitwise)
        self.pin_replica: Optional[int] = None
        self.respawns = 0
        self.deaths: List[dict] = []
        self._soft_faults: Dict[int, str] = {}  # rid -> injected-fault detail
        self._state_lock = threading.Lock()
        self._state_cond = threading.Condition(self._state_lock)
        self._replicas: Dict[int, object] = {}
        self._inflight: Dict[int, dict] = {}   # batch_id -> dispatch info
        self._dispatched: Dict[int, int] = {}  # rid -> batches sent
        self._next_batch_id = 0
        self._ready: set = set()
        self._prewarm_acks = 0
        self._publisher = None
        self._last_swap: Optional[dict] = None
        self._swap_version = 0
        self._closing = False
        self._closed = False
        self._close_lock = threading.Lock()
        self._results_stop = threading.Event()
        self._monitor_stop = threading.Event()

        if backend == "process":
            self._ctx = multiprocessing.get_context("spawn")
            self._result_q = self._ctx.Queue()
        else:
            self._ctx = None
            self._result_q = queue.Queue()

        with self._state_cond:
            for rid in range(int(replicas)):
                server = servers[rid] if servers is not None else None
                self._replicas[rid] = self._spawn(rid, server)
                self.balancer.add(rid)
                self._dispatched[rid] = 0

        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="pool-dispatcher", daemon=True)
        self._results = threading.Thread(
            target=self._results_loop, name="pool-results", daemon=True)
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="pool-monitor", daemon=True)
        self._dispatcher.start()
        self._results.start()
        self._monitor.start()

    # --------------------------------------------------------------- spawn

    def _spawn(self, rid: int, server: Optional[TopicServer] = None,
               clean: bool = False):
        """Build one replica handle.  ``clean=True`` strips the fault
        specs: the seeded chaos belongs to the original cohort, a
        respawned worker must not replay it (its batch counter restarts,
        so a concrete-step kill would fire again and again)."""
        if self.backend == "process":
            spec = self.spec
            if clean and spec.fault_specs:
                spec = dataclasses.replace(spec, fault_specs=())
            return _ProcessReplica(rid, spec, self._result_q, self._ctx)
        if server is None:
            server = self.spec.build_server()
        plan = None
        if not clean and self.spec is not None and self.spec.fault_specs:
            plan = fault_lib.FaultPlan(self.spec.fault_specs)
        sim = self.spec.sim_service_ms if self.spec is not None else 0.0
        K = (self.spec.cfg.num_topics if self.spec is not None
             else server.cfg.num_topics)
        import jax
        devs = jax.local_devices()
        device = devs[rid % len(devs)] if len(devs) > 1 else None
        return _ThreadReplica(rid, server, plan, sim, K,
                              self._result_q, device)

    def wait_ready(self, timeout: float = 120.0) -> None:
        """Block until every current replica has built its server (spawn
        + jax import ≈ 1s per process worker)."""
        deadline = time.monotonic() + timeout
        with self._state_cond:
            while not self._state_cond.wait_for(
                    lambda: self._ready >= set(self._replicas),
                    timeout=min(1.0, max(0.0, deadline - time.monotonic()))):
                if time.monotonic() >= deadline:
                    missing = set(self._replicas) - self._ready
                    raise TimeoutError(
                        f"replicas {sorted(missing)} not ready "
                        f"after {timeout}s")

    # ----------------------------------------------------------- admission

    def submit(self, word_ids: np.ndarray,
               counts: Optional[np.ndarray] = None,
               key: Optional[np.ndarray] = None) -> Future:
        """Admit one document; resolves to its (K,) θ̂ stamped with the φ
        version that produced it — same contract as the engine."""
        return self.router.submit(word_ids, counts, key)

    # ----------------------------------------------------------- lifelong

    def subscribe(self, publisher, refresh: bool = True) -> None:
        """Subscribe every replica to a :class:`SnapshotPublisher`: each
        publish is broadcast (full payload + crc) through the task
        queues.  The watcher thread picks up later publishes within its
        poll interval; per-replica swap acks feed the balancer's
        monotone version ledger."""
        self._publisher = publisher
        if refresh:
            snap = publisher.latest()
            if snap is not None:
                self._broadcast_swap(snap)
        watcher = threading.Thread(
            target=self._watch_loop, name="pool-version-watcher", daemon=True)
        watcher.start()
        self._watcher = watcher

    def _broadcast_swap(self, snap) -> None:
        payload = snapshot_payload(snap)
        with self._state_cond:
            if payload["version"] <= self._swap_version:
                return
            self._last_swap = payload
            self._swap_version = payload["version"]
            handles = list(self._replicas.values())
        for h in handles:
            h.send(("swap", payload))

    def _watch_loop(self) -> None:
        while not self._results_stop.is_set():
            pub = self._publisher
            if pub is not None and pub.version > self._swap_version:
                snap = pub.latest()
                if snap is not None:
                    self._broadcast_swap(snap)
            time.sleep(0.02)

    # ------------------------------------------------------------ dispatch

    def _choose(self) -> Optional[int]:
        pin = self.pin_replica
        if pin is not None:
            if pin in self._replicas and self.balancer.acquire_specific(pin):
                return pin
            return None
        return self.balancer.acquire()

    def _dispatch(self, L: int, reqs, w, c, keys,
                  batch_id: Optional[int] = None) -> None:
        """Assign a padded batch to a least-loaded replica (blocking while
        every replica is at its in-flight cap).  Re-issue passes the
        original ``batch_id`` and the *identical* padded arrays — the
        bitwise-parity contract."""
        with self._state_cond:
            while True:
                rid = self._choose()
                if rid is not None:
                    break
                if not self._replicas:
                    # pool fully dead and not respawning: fail, don't hang
                    if batch_id is not None:
                        self._inflight.pop(batch_id, None)
                    exc = RuntimeError(
                        "replica pool has no live replicas left")
                    self._state_cond.release()
                    try:
                        self.router.fail_batch(reqs, exc)
                    finally:
                        self._state_cond.acquire()
                    return
                self._state_cond.wait(timeout=0.05)
            if batch_id is None:
                batch_id = self._next_batch_id
                self._next_batch_id += 1
            self._inflight[batch_id] = {
                "rid": rid, "L": L, "reqs": reqs,
                "w": w, "c": c, "keys": keys, "filled": len(reqs),
            }
            self._dispatched[rid] = self._dispatched.get(rid, 0) + 1
            handle = self._replicas[rid]
        handle.send(("batch", batch_id, L, w, c, keys, len(reqs)))

    def _dispatch_loop(self) -> None:
        while True:
            item = self.router.next_batch()
            if item is None:
                return
            L, reqs = item
            w, c, keys = pad_batch(L, reqs, self.router.max_batch)
            self._dispatch(L, reqs, w, c, keys)

    # ------------------------------------------------------------- results

    def _results_loop(self) -> None:
        while True:
            try:
                msg = self._result_q.get(timeout=0.05)
            except queue.Empty:
                if self._results_stop.is_set():
                    return
                continue
            kind = msg[0]
            if kind == "done":
                _, rid, bid, theta, rec = msg
                version = rec["version"]
                with self._state_cond:
                    info = self._inflight.pop(bid, None)
                    if info is not None:
                        self._account_completion(info["rid"], version)
                        self._state_cond.notify_all()
                if info is None:
                    continue   # duplicate after a re-issue: drop
                pub = self._publisher
                rec.update(
                    L=info["L"], filled=info["filled"],
                    capacity=self.router.max_batch, replica=rid,
                    published_version=(
                        pub.version if pub is not None else -1),
                )
                self.router.resolve_batch(info["reqs"], theta, version, rec)
            elif kind == "error":
                _, rid, bid, err = msg
                with self._state_cond:
                    info = self._inflight.pop(bid, None)
                    if info is not None:
                        self._account_completion(info["rid"], None)
                        self._state_cond.notify_all()
                if info is not None:
                    self.router.fail_batch(
                        info["reqs"],
                        RuntimeError(f"replica {rid} launch failed: {err}"))
            elif kind == "ready":
                with self._state_cond:
                    self._ready.add(msg[1])
                    self._state_cond.notify_all()
            elif kind == "swapped":
                _, rid, version = msg
                with self._state_cond:
                    try:
                        self.balancer.note_version(rid, version)
                    except KeyError:
                        pass                  # raced a removal
            elif kind == "prewarmed":
                with self._state_cond:
                    self._prewarm_acks += 1
                    self._state_cond.notify_all()
            elif kind == "fault":
                # a soft kill also exits the worker loop: stash the detail
                # and let the monitor's death detection record the single
                # death event (otherwise one loss counts twice)
                with self._state_cond:
                    self._soft_faults[msg[1]] = msg[2]
            # "bye": clean shutdown ack — nothing to account

    def _account_completion(self, rid: int, version: Optional[int]) -> None:
        """Balancer bookkeeping for one finished batch, tolerant of the
        replica having been removed while the result was in the queue."""
        try:
            self.balancer.complete(rid)
        except (KeyError, ValueError):
            pass
        if version is not None and version >= 0:
            try:
                self.balancer.note_version(rid, version)
            except KeyError:
                pass

    # ------------------------------------------------------------- monitor

    def _monitor_loop(self) -> None:
        while not self._monitor_stop.is_set():
            time.sleep(0.05)
            if self._closing:
                continue
            dead = []
            with self._state_cond:
                for rid, h in list(self._replicas.items()):
                    if not h.alive():
                        dead.append((rid, h))
                        del self._replicas[rid]
                        try:
                            self.balancer.remove(rid)
                        except KeyError:
                            pass
                if dead:
                    self._ready -= {rid for rid, _ in dead}
                    self._state_cond.notify_all()
            for rid, h in dead:
                self._handle_death(rid, h)

    def _handle_death(self, rid: int, handle) -> None:
        """PR 7 semantics at the pool level: record the loss, respawn (or
        downsize), then re-issue the dead worker's in-flight batches —
        identical padded payloads — so every submitted Future resolves."""
        with self._state_cond:
            detail = self._soft_faults.pop(rid, None)
        rec = {"rid": rid, "kind": "soft" if detail else "hard",
               "exitcode": handle.exitcode}
        if detail:
            rec["detail"] = detail
        self.deaths.append(rec)
        with self._state_cond:
            orphans = [(bid, info) for bid, info in self._inflight.items()
                       if info["rid"] == rid]
            respawn = self.respawn and not self._closing
            if respawn:
                self._replicas[rid] = self._spawn(rid, clean=True)
                self.balancer.add(rid)
                self.respawns += 1
                swap = self._last_swap
                self._state_cond.notify_all()
            survivors = bool(self._replicas)
        if respawn and swap is not None:
            self._replicas[rid].send(("swap", swap))
        if not survivors:
            with self._state_cond:
                for bid, info in orphans:
                    self._inflight.pop(bid, None)
            for _, info in orphans:
                self.router.fail_batch(
                    info["reqs"],
                    RuntimeError(f"replica {rid} died with no survivors"))
            return
        for bid, info in orphans:
            self._dispatch(info["L"], info["reqs"], info["w"], info["c"],
                           info["keys"], batch_id=bid)

    # ------------------------------------------------------------ plumbing

    def prewarm(self, lengths: Optional[Sequence[int]] = None,
                vocab_sizes: Optional[Sequence[int]] = None,
                timeout: float = 600.0) -> int:
        """Broadcast the (L × W_s) trace-grid compile to every replica and
        wait for the acks (each worker process owns its own jit cache)."""
        dims = {
            "max_batch": self.router.max_batch,
            "bucket_multiple": self.router.bucket_multiple,
            "max_len": self.router.max_len,
            "lengths": None if lengths is None else list(lengths),
            "vocab_sizes": (None if vocab_sizes is None
                            else list(vocab_sizes)),
        }
        with self._state_cond:
            self._prewarm_acks = 0
            handles = list(self._replicas.values())
        for h in handles:
            h.send(("prewarm", dims))
        deadline = time.monotonic() + timeout
        with self._state_cond:
            ok = self._state_cond.wait_for(
                lambda: self._prewarm_acks >= len(handles),
                timeout=deadline - time.monotonic())
        if not ok:
            raise TimeoutError("replica prewarm did not ack in time")
        return len(handles)

    def metrics(self, reset: bool = False) -> dict:
        """Router window metrics + pool aggregation: per-replica dispatch
        counts, deaths/respawns, and the balancer's version ledger
        (pool-level staleness = max over replicas, already folded into
        ``max_staleness_versions`` by the per-batch records)."""
        out = self.router.metrics(reset=reset)
        with self._state_cond:
            out.update(
                replicas=len(self._replicas),
                backend=self.backend,
                dispatch={rid: n for rid, n in sorted(
                    self._dispatched.items())},
                deaths=len(self.deaths),
                respawns=self.respawns,
                replica_versions=self.balancer.versions(),
            )
        return out

    def drain(self) -> None:
        """Block until every admitted request has resolved (including
        batches in flight at the workers — the router counts resolutions,
        not launches)."""
        self.router.drain()

    def close(self, timeout: float = 60.0) -> None:
        """Flush, dispatch, and resolve everything, then stop the world.

        Idempotent and safe under concurrent callers (the close lock
        serializes them; every caller returns only after the threads and
        workers are joined).  Order matters: the router closes first so
        the dispatcher drains every flushed bucket; worker stop messages
        go out only after the in-flight map empties, so no batch is ever
        abandoned by shutdown.
        """
        with self._close_lock:
            if self._closed:
                return
            self.router.close()
            self._dispatcher.join()
            deadline = time.monotonic() + timeout
            with self._state_cond:
                self._state_cond.wait_for(
                    lambda: not self._inflight,
                    timeout=max(0.0, deadline - time.monotonic()))
                leftovers = list(self._inflight.items())
                self._inflight.clear()
                self._closing = True
                handles = list(self._replicas.values())
            for _, info in leftovers:         # timeout path: never hang callers
                self.router.fail_batch(
                    info["reqs"],
                    RuntimeError("replica pool closed with the batch "
                                 "still in flight"))
            for h in handles:
                h.send(("stop",))
            for h in handles:
                h.join(timeout=10.0)
                if h.alive():
                    h.kill()
            self._results_stop.set()
            self._monitor_stop.set()
            self._results.join()
            self._monitor.join()
            if self.backend == "process":
                self._result_q.close()
                self._result_q.join_thread()
            self._closed = True

    def __enter__(self) -> "ReplicaPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
