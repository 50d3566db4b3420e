"""Parameter streaming — paper §3.2: the 'big model' tier.

The global topic-word matrix φ̂_{W×K} lives in *external storage* (here a
memory-mapped file standing in for the paper's HDF5 store); only

  * the rows of the current minibatch's vocabulary W_s, and
  * a hot-word LRU buffer of ``W*`` rows ("Replace most frequent vocabulary
    word-topic parameter matrix ... in buffer memory", Fig. 4 line 2)

are resident.  Rows are read/written once per minibatch (vocab-major layout).
Because the canonical state is externalised, training is fault tolerant by
construction: a crash loses at most the current minibatch (§3.2 "Fault
tolerance is also assured because the global topic-word matrix is stored in
hard disk for restarting the online learning").

Architecture of the host-I/O path (this PR's pipeline)::

      MinibatchStream ──► StreamPrefetcher (worker thread)
                             │  bucketize + localize_vocab
                             │  ParameterStore.fetch_rows  ← vectorized:
                             │     one fancy-indexed memmap gather per
                             │     minibatch + array-backed LRU hit/miss
                             ▼
      queue (depth = prefetch_depth) ──► FOEMTrainer.step
                             │             reconcile vs. recent write-backs
                             │             jitted foem_step  (device)
                             ▼
      ParameterStore.write_rows  ← coalesced scatter of W_s dirty rows

    While the device executes minibatch *s*, the worker fetches minibatch
    *s+1*'s φ̂ rows — disk/host I/O overlaps device compute end-to-end, so a
    step costs ≈ max(compute, I/O) instead of their sum.  The fetch of *s+1*
    may race the write-back of *s*; ``write_version`` orders the two and the
    trainer patches the (tiny) overlap from the freshly computed host rows,
    making results bitwise-identical with prefetching on or off.

All LRU state is arrays (contiguous ``(W*, K)`` row buffer + id/clock/dirty
vectors + a word→slot map), so a whole minibatch's hit partition, clock
bump, insertion and batched eviction are NumPy ops — no per-row Python loop
anywhere on the hot path.

How the knobs map to the paper's Table 5: ``buffer_rows`` is W* (0 = the
0.0GB row: every access hits the backing store; ``rows_for_bytes`` converts
a byte budget), ``W_s`` is the per-minibatch unique vocabulary, and
``prefetch_depth`` is the number of minibatches fetched ahead (1 = double
buffering, the Fig. 4 "while GPU computes, CPU fetches" overlap).

Where the whole φ̂ and the step fit on the device, the trainer keeps φ̂ there
instead (the store's device row tier): rows are gathered and scattered on
the device, and this host path becomes the overflow and persistence tier,
written back only when someone reads the store.

At pod scale the same role is played by sharding φ̂ over the ``model`` mesh
axis (see ``parallel/sharding.py``); this module is the single-host tier and
the checkpoint substrate.
"""
from __future__ import annotations

import dataclasses
import functools
import io
import json
import os
import struct
import threading
import time
import zlib
from typing import Iterable, Iterator, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import faults as fault_lib
from repro.runtime.spans import span
from repro.sparse.docword import VOCAB_BUCKET


class StoreCorruptionError(RuntimeError):
    """The on-disk store state is not recoverable to a consistent version
    (externally corrupted manifest with no valid WAL to rebuild from)."""


_WAL_MAGIC = b"FOEMWAL1"


def _fsync_dir(path: str) -> None:
    """Best-effort directory fsync (durability of renames on POSIX)."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_record(path: str, arrays: dict, meta: dict) -> None:
    """Shadow-write a checksummed record file (fsync'd, NOT renamed —
    the caller owns the atomic-rename commit point)."""
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    payload = buf.getvalue()
    meta_bytes = json.dumps(meta, sort_keys=True).encode()
    body = struct.pack("<II", len(meta_bytes), len(payload)) + meta_bytes + payload
    with open(path, "wb") as f:
        f.write(_WAL_MAGIC)
        f.write(struct.pack("<I", zlib.crc32(body)))
        f.write(body)
        f.flush()
        os.fsync(f.fileno())


def _read_record(path: str) -> Optional[Tuple[dict, dict]]:
    """Read a record written by ``_write_record``; ``None`` when torn or
    corrupt (bad magic / truncated / checksum mismatch)."""
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    hdr = len(_WAL_MAGIC) + 4
    if len(raw) < hdr + 8 or raw[: len(_WAL_MAGIC)] != _WAL_MAGIC:
        return None
    (crc,) = struct.unpack_from("<I", raw, len(_WAL_MAGIC))
    body = raw[hdr:]
    if zlib.crc32(body) != crc:
        return None
    meta_len, payload_len = struct.unpack_from("<II", body, 0)
    if len(body) != 8 + meta_len + payload_len:
        return None
    meta = json.loads(body[8 : 8 + meta_len].decode())
    with np.load(io.BytesIO(body[8 + meta_len :])) as z:
        arrays = {k: z[k] for k in z.files}
    return arrays, meta


@dataclasses.dataclass
class StoreStats:
    """I/O accounting used by the Table-5 benchmark and the serving bench."""

    disk_reads: int = 0      # rows read from the backing store
    disk_writes: int = 0     # rows written to the backing store
    buffer_hits: int = 0     # rows served from the hot buffer
    evictions: int = 0
    promotions: int = 0      # rows promoted into the buffer by insert-on-read
    prefetch_hits: int = 0   # minibatches whose rows were already staged
    overlap_seconds: float = 0.0  # host I/O time hidden behind device compute

    def reset(self) -> None:
        self.disk_reads = self.disk_writes = 0
        self.buffer_hits = self.evictions = self.promotions = 0
        self.prefetch_hits = 0
        self.overlap_seconds = 0.0

    def snapshot(self) -> "StoreStats":
        return dataclasses.replace(self)


class _TimedLock:
    """A re-entrant lock whose contended acquires are timed.  Only an
    acquire that has to block reads the clock: it runs under span
    ``store.lock_wait`` and its seconds go to the waiting thread's
    ``wait_seconds()``."""

    def __init__(self):
        self._lock = threading.RLock()
        self._waits = threading.local()

    def __enter__(self) -> None:
        if not self._lock.acquire(blocking=False):
            with span("store.lock_wait", self._waits.__dict__, "seconds"):
                self._lock.acquire()

    def __exit__(self, *exc) -> None:
        self._lock.release()

    def wait_seconds(self) -> float:
        return getattr(self._waits, "seconds", 0.0)


#: Rows one device-tier transfer moves at most (a multiple of the W_s
#: bucket): an upload or a whole-table sync holds one such block on the host.
TIER_CHUNK = 16 * VOCAB_BUCKET
#: The device tier's rows are padded to a multiple of the TPU's 128 lanes:
#: a row gather or scatter on a table whose minor dimension is not lane
#: aligned copies the whole table first (6 GB of temporaries at PubMed's
#: K = 10^4, by the TPU compiler's memory analysis).
TIER_LANES = 128


@functools.partial(jax.jit, static_argnums=2)
def _tier_gather(table, ids, k):
    """Rows ``ids`` of the device tier, first ``k`` lanes; an id past the
    table reads zeros."""
    return table.at[ids].get(mode="fill", fill_value=0)[:, :k]


@functools.partial(jax.jit, donate_argnums=0)
def _tier_scatter(table, ids, rows):
    """The tier with rows ``ids`` replaced (``rows`` zero-padded to the
    lanes); an id past the table is dropped.  Whole rows: the TPU compiler
    turns a scatter into part of each row into a loop over the ids."""
    pad = table.shape[1] - rows.shape[1]
    return table.at[ids].set(jnp.pad(rows, ((0, 0), (0, pad))), mode="drop")


def tier_ids(word_ids: np.ndarray, capacity: int) -> np.ndarray:
    """``word_ids`` as int32, padded up to the W_s bucket with ``capacity``
    — an id past the table, which the tier's gather reads as a zero row and
    its scatter drops (the padding of ``pad_vocab_rows``)."""
    n = len(word_ids)
    out = np.full((-(-n // VOCAB_BUCKET) * VOCAB_BUCKET,), capacity, np.int32)
    out[:n] = word_ids
    return out


class ParameterStore:
    """Disk-backed φ̂_{W×K} with a write-back LRU hot-word buffer.

    All row I/O is *vectorized*: a minibatch's W_s rows move as one
    fancy-indexed gather/scatter against the memmap and one partitioned
    gather against the hot buffer.  The LRU itself is array-backed — a
    contiguous ``(W*, K)`` row buffer plus id/clock/dirty vectors and a
    word→slot index — so hit partitioning, recency bumps and batched
    eviction are O(W_s) NumPy work instead of O(W_s) interpreter work.

    Thread safety: every public mutator takes ``_lock`` so a background
    prefetcher (``StreamPrefetcher``) can fetch while the trainer writes
    back.  A contended acquire is timed (span ``store.lock_wait``) and
    charged to the thread that waited: ``lock_wait_seconds()``.
    ``write_version`` increments on every value-changing write; a fetch
    tagged with an older version may miss those writes and must be
    reconciled by the caller (see ``fetch_rows_versioned``).

    Row ids within one ``fetch_rows``/``write_rows`` call must be unique —
    they are a minibatch's (deduplicated) local vocabulary.

    Device row tier: ``attach_tier`` puts the whole ``(capacity, K)`` table
    on a device, where the trainer gathers and scatters its steps' rows
    (``tier_gather``/``tier_write``); the host buffer and memmap become the
    overflow and persistence tier.  Rows the tier holds newer than the host
    copy are dirty, and every host read writes back the dirty rows it covers
    first (``fetch_rows``, ``flush`` and so ``dense_phi`` and
    ``SnapshotPublisher.publish``; span ``store.sync``), so readers on any
    thread see what the tier holds.  ``detach_tier`` writes back the rest.

    Parameters
    ----------
    path:            directory for the backing file + manifest.
    num_topics:      K.
    vocab_capacity:  pre-allocated W capacity (the paper's W←W+1 growth is
                     realised as a high-watermark within this capacity; the
                     file is extended in chunks when exceeded).
    buffer_rows:     W* — max rows resident in the hot buffer (0 = unbuffered,
                     every access hits the backing store: Table 5's 0.0GB row).
    readonly:        attach to an existing store without taking ownership:
                     the memmap opens mode "r", recovery never rewrites disk
                     state (a committed-but-unapplied WAL is overlaid on
                     reads in memory instead of replayed), and every mutator
                     raises.  This is the multi-process serving contract —
                     a :class:`~repro.launch.replica.ReplicaPool` worker in
                     another process must never race the owning trainer's
                     WAL commit, so it attaches instead of opening.
    """

    MANIFEST = "store.json"
    BACKING = "phi_wk.mmap"
    WAL = "store.wal"

    def __init__(
        self,
        path: str,
        num_topics: int,
        vocab_capacity: int,
        buffer_rows: int = 0,
        dtype=np.float32,
        faults: Optional[fault_lib.FaultPlan] = None,
        readonly: bool = False,
    ):
        self.path = path
        self.K = int(num_topics)
        self.capacity = int(vocab_capacity)
        self.buffer_rows = int(buffer_rows)
        self.dtype = np.dtype(dtype)
        self.live_vocab = 0                      # W high-watermark
        self.phi_k = np.zeros((self.K,), np.float64)  # lint: host-f64 — RAM accumulator
        self.step = 0                            # minibatch cursor (restart point)
        self.stats = StoreStats()
        self.write_version = 0                   # bumps on every write_rows
        self.flush_version = 0                   # bumps on every committed flush
        # rows written since the last take_changed() — the publish delta a
        # SnapshotPublisher turns into per-version cache epoch invalidation
        self._changed = np.zeros((int(vocab_capacity),), bool)
        self.faults = faults                     # seeded fault-injection plan
        self.recovered_from_wal = False          # last open replayed a WAL
        self.readonly = bool(readonly)
        # readonly attach: committed-but-unapplied WAL rows, overlaid on
        # fetches in memory (sorted ids + rows) — disk is never touched
        self._overlay: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._lock = _TimedLock()
        # ---- array-backed LRU (empty slots carry id == -1) ----
        W_star = self.buffer_rows
        self._buf = np.zeros((W_star, self.K), self.dtype)
        self._buf_ids = np.full((W_star,), -1, np.int64)
        self._buf_clock = np.zeros((W_star,), np.int64)
        self._buf_dirty = np.zeros((W_star,), bool)
        self._slot_of = np.full((self.capacity,), -1, np.int64)
        self._clock = 0
        # ---- device row tier (None: detached) ----
        self._tier = None
        self._tier_dirty = np.zeros((self.capacity,), bool)
        backing = os.path.join(path, self.BACKING)
        if self.readonly:
            if not os.path.exists(backing):
                raise FileNotFoundError(
                    f"no store to attach to under {path} (missing "
                    f"{self.BACKING}); readonly attach never creates one"
                )
            self._mm = np.memmap(
                backing, dtype=self.dtype, mode="r",
                shape=(self.capacity, self.K),
            )
            self._arr = np.asarray(self._mm)
            self._attach()
            return
        os.makedirs(path, exist_ok=True)
        mode = "r+" if os.path.exists(backing) else "w+"
        self._mm = np.memmap(
            backing, dtype=self.dtype, mode=mode, shape=(self.capacity, self.K)
        )
        # Plain ndarray view of the same mapping: fancy gathers/scatters on it
        # skip np.memmap.__getitem__'s subclass overhead (~4x on 4096-row
        # blocks); durability still goes through self._mm.flush().
        self._arr = np.asarray(self._mm)
        # a new backing file holds zeros until the first row write
        self._pristine = mode == "w+"
        if mode == "r+":
            self._recover()

    # -------------------------------------------------- readonly attach

    @classmethod
    def attach(cls, path: str, num_topics: int, vocab_capacity: int,
               buffer_rows: int = 0, dtype=np.float32) -> "ParameterStore":
        """Open an existing store read-only, without taking ownership.

        The serving-process entry point: no recovery writes, no WAL
        replay (a committed WAL is overlaid on reads in memory), and all
        mutators raise.  Concurrent with the owner's flushes this reads a
        consistent manifest version; under the replica pool the swap
        payloads carry the authoritative φ bytes anyway.
        """
        return cls(path, num_topics, vocab_capacity,
                   buffer_rows=buffer_rows, dtype=dtype, readonly=True)

    def _attach(self) -> None:
        """Readonly recovery scan: load the manifest, overlay (in memory)
        any committed-but-unapplied WAL — never write a byte to disk."""
        wal = self._wal_path()
        if os.path.exists(wal):
            rec = _read_record(wal)
            if rec is not None:          # committed: newer than the memmap
                arrays, meta = rec
                ids = arrays["ids"].astype(np.int64)
                order = np.argsort(ids)
                self._overlay = (
                    ids[order], arrays["rows"].astype(self.dtype)[order]
                )
                self._apply_manifest(
                    {**meta, "phi_k": arrays["phi_k"].tolist()}
                )
                self.recovered_from_wal = True
                return
        self._load_manifest()

    def lock_wait_seconds(self) -> float:
        """Seconds the calling thread has spent blocked on the store lock
        (cumulative; a step reads the difference)."""
        return self._lock.wait_seconds()

    def _check_writable(self) -> None:
        if self.readonly:
            raise PermissionError(
                "ParameterStore opened readonly (attach): serving "
                "processes never write through the store — swaps arrive "
                "via the snapshot publish protocol"
            )

    def _read_backing(self, ids: np.ndarray) -> np.ndarray:
        """Backing-store gather, patched with the readonly WAL overlay."""
        rows = self._arr[ids]
        if self._overlay is not None:
            o_ids, o_rows = self._overlay
            pos = np.searchsorted(o_ids, ids)
            pos = np.minimum(pos, len(o_ids) - 1)
            hit = o_ids[pos] == ids
            if hit.any():
                rows = np.array(rows)          # un-alias the memmap view
                rows[hit] = o_rows[pos[hit]]
        return rows

    # ------------------------------------------------------------------ I/O

    def fetch_rows(
        self, word_ids: np.ndarray, promote: bool = True
    ) -> np.ndarray:
        """Read φ̂ rows for a minibatch's unique vocabulary — one block I/O.

        Buffer hits are gathered from the hot buffer, misses from the memmap
        with a single fancy-indexed read; missed rows are then *promoted*
        into the buffer (insert-on-read, clean) so a read-heavy stream still
        accumulates hits under the same LRU eviction policy as writes.

        ``promote=False`` skips that insert-on-read: a layered read cache
        (``HotRowCache``) that already retains the miss must not *also*
        promote it here, or every serving miss would be double-cached —
        once in the serving cache and once in the training buffer, evicting
        genuinely training-hot rows and double-counting the promotion.
        """
        return self.fetch_rows_versioned(word_ids, promote=promote)[0]

    def fetch_rows_versioned(
        self, word_ids: np.ndarray, promote: bool = True
    ) -> Tuple[np.ndarray, int]:
        """``fetch_rows`` plus the ``write_version`` the read is consistent
        with — the prefetch pipeline's reconciliation token."""
        with self._lock:
            ids = np.asarray(word_ids, np.int64)
            if len(ids) and int(ids.max()) >= self.capacity:
                raise ValueError(
                    f"word id {int(ids.max())} exceeds store capacity "
                    f"{self.capacity}; grow capacity at construction "
                    "(static allocation for XLA)"
                )
            self._sync_tier(ids)
            if self.buffer_rows == 0:
                out = self._read_backing(ids)
                self.stats.disk_reads += len(ids)
                return out, self.write_version
            slots = self._slot_of[ids]
            hit = slots >= 0
            n_hit = int(hit.sum())
            if n_hit == len(ids):                 # warm stream fast path
                out = self._buf[slots]
                self._touch(slots)
                self.stats.buffer_hits += n_hit
                return out, self.write_version
            if n_hit == 0:                        # cold stream fast path
                out = self._read_backing(ids)
                self.stats.disk_reads += len(ids)
                if promote:
                    self.stats.promotions += len(ids)
                    self._insert(ids, out, dirty=False)
                return out, self.write_version
            out = np.empty((len(ids), self.K), self.dtype)
            hit_idx = np.flatnonzero(hit)
            miss_idx = np.flatnonzero(~hit)
            hit_slots = slots[hit_idx]
            out[hit_idx] = self._buf[hit_slots]
            self._touch(hit_slots)
            self.stats.buffer_hits += n_hit
            miss_ids = ids[miss_idx]
            rows = self._read_backing(miss_ids)
            out[miss_idx] = rows
            self.stats.disk_reads += len(miss_ids)
            if promote:
                self.stats.promotions += len(miss_ids)
                self._insert(miss_ids, rows, dirty=False)
            return out, self.write_version

    def write_rows(self, word_ids: np.ndarray, rows: np.ndarray) -> int:
        """Write updated rows back (coalesced) — buffered words stay dirty
        until eviction.  Returns the new ``write_version``."""
        self._check_writable()
        with self._lock:
            ids = np.asarray(word_ids, np.int64)
            rows = np.asarray(rows, self.dtype)
            self._changed[ids] = True
            self._put_rows(ids, rows)
            if self._tier is not None:            # the tier holds them too
                self._tier = self._upload(self._tier, ids, rows)
                self._tier_dirty[ids] = False
            self.write_version += 1
            return self.write_version

    def _put_rows(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Host-tier write: into the hot buffer (dirty), or a sorted
        scatter into the backing store when unbuffered."""
        self._pristine = False
        if self.buffer_rows > 0:
            self._insert(ids, rows, dirty=True)
        else:
            order = np.argsort(ids)           # sorted scatter: sequential I/O
            self._arr[ids[order]] = rows[order]
            self.stats.disk_writes += len(ids)

    def _read_rows(self, ids: np.ndarray) -> np.ndarray:
        """Host-tier read with no promotion and no I/O accounting."""
        rows = np.array(self._read_backing(ids))
        if self.buffer_rows > 0:
            slots = self._slot_of[ids]
            hit = slots >= 0
            rows[hit] = self._buf[slots[hit]]
        return rows

    # ---------------------------------------------------- device row tier

    @property
    def has_tier(self) -> bool:
        return self._tier is not None

    def tier_bytes(self) -> int:
        """Device bytes of the tier: the whole table, K lane-padded."""
        return self.capacity * self._tier_lanes() * self.dtype.itemsize

    def _tier_lanes(self) -> int:
        return -(-self.K // TIER_LANES) * TIER_LANES

    def attach_tier(self) -> int:
        """Hold the whole table on the default device (its rows padded to
        ``TIER_LANES``), filled from the host tier (a store never written is
        allocated as zeros there, with no copy).  Returns the rows uploaded.

        The tier's arrays are not committed to the device, so the rows it
        gathers reach the step program as the streamed path's rows do: one
        compiled program serves both."""
        self._check_writable()
        with self._lock:
            if self._tier is not None:
                return 0
            table = jnp.zeros((self.capacity, self._tier_lanes()), self.dtype)
            uploaded = 0
            if not self._pristine:
                for lo in range(0, self.capacity, TIER_CHUNK):
                    ids = np.arange(lo, min(lo + TIER_CHUNK, self.capacity))
                    table = self._upload(table, ids, self._read_rows(ids))
                uploaded = self.capacity
            self._tier = table
            self._tier_dirty[:] = False
            return uploaded

    def detach_tier(self) -> None:
        """Write the tier's dirty rows back to the host tier and free it."""
        with self._lock:
            if self._tier is not None:
                self._sync_tier()
                self._tier = None

    def tier_gather(self, ids: jax.Array) -> jax.Array:
        """The rows ``ids`` (from :func:`tier_ids`) on the device; padding
        rows are zeros."""
        with self._lock:
            return _tier_gather(self._tier, ids, self.K)

    def tier_write(self, word_ids: np.ndarray, ids: jax.Array,
                   rows: jax.Array) -> int:
        """A step's write-back into the tier: rows ``ids`` (from
        :func:`tier_ids` of ``word_ids``) replaced by ``rows``, in place.
        Like ``write_rows`` it marks the rows changed and returns the new
        ``write_version``; the host copy stays behind until a read."""
        with self._lock:
            self._tier = _tier_scatter(self._tier, ids, rows)
            self._tier_dirty[word_ids] = True
            self._changed[word_ids] = True
            self.write_version += 1
            return self.write_version

    def _upload(self, table, ids: np.ndarray, rows: np.ndarray):
        """``table`` with rows ``ids`` set from host ``rows``."""
        pad = tier_ids(ids, self.capacity)
        block = np.zeros((len(pad), self.K), self.dtype)
        block[: len(ids)] = rows
        return _tier_scatter(table, jnp.asarray(pad), jnp.asarray(block))

    def _sync_tier(self, ids: Optional[np.ndarray] = None) -> None:
        """Write back the tier's dirty rows among ``ids`` (all when None)
        to the host tier.  The caller holds ``_lock``."""
        if self._tier is None:
            return
        dirty = self._tier_dirty
        which = np.flatnonzero(dirty) if ids is None else ids[dirty[ids]]
        if not len(which):
            return
        with span("store.sync"):
            for lo in range(0, len(which), TIER_CHUNK):
                part = which[lo: lo + TIER_CHUNK]
                rows = _tier_gather(
                    self._tier, jnp.asarray(tier_ids(part, self.capacity)),
                    self.K)
                self._put_rows(part, np.asarray(rows)[: len(part)])
            dirty[which] = False

    # ----------------------------------------------------- LRU internals

    def _touch(self, slots: np.ndarray) -> None:
        """Recency bump: later position in the batch == more recent (matches
        per-row ``move_to_end`` order; clocks stay unique)."""
        n = len(slots)
        if n:
            self._buf_clock[slots] = np.arange(self._clock, self._clock + n)
            self._clock += n

    def _insert(self, ids: np.ndarray, rows: np.ndarray, dirty: bool) -> None:
        """Vectorized buffer insertion with batched LRU eviction.

        Semantically equivalent to inserting ``ids`` one by one (in order)
        into the old OrderedDict LRU: the final residents, eviction count and
        dirty write-backs match the per-row implementation.
        """
        W_star = self.buffer_rows
        slots = self._slot_of[ids]
        have = slots >= 0
        n_have = int(have.sum())
        if n_have == len(ids):                    # pure overwrite (write-back)
            self._buf[slots] = rows
            if dirty:
                self._buf_dirty[slots] = True
            self._touch(slots)
            return
        if n_have:
            have_idx = np.flatnonzero(have)
            have_slots = slots[have_idx]
            self._buf[have_slots] = rows[have_idx]
            if dirty:
                self._buf_dirty[have_slots] = True
            # Bump residents now so batched eviction can never pick them.
            self._touch(have_slots)
            new_idx = np.flatnonzero(~have)
            new_ids, new_rows = ids[new_idx], rows[new_idx]
        else:
            new_ids, new_rows = ids, rows
        n_new = len(new_ids)
        if n_new > W_star:
            # The leading n_new - W* fresh rows would be inserted then
            # immediately evicted by the per-row LRU — spill them straight to
            # the store (write back if dirty, count the pass-through evictions).
            head = n_new - W_star
            if dirty:
                order = np.argsort(new_ids[:head])
                self._arr[new_ids[:head][order]] = new_rows[:head][order]
                self.stats.disk_writes += head
            self.stats.evictions += head
            new_ids, new_rows = new_ids[head:], new_rows[head:]
            n_new = W_star
        free = np.flatnonzero(self._buf_ids < 0)
        need = n_new - len(free)
        if need > 0:
            occupied = np.flatnonzero(self._buf_ids >= 0)
            oldest = occupied[
                np.argpartition(self._buf_clock[occupied], need - 1)[:need]
            ]
            self._evict_slots(oldest)
            free = np.concatenate([free, oldest])
        tgt = free[:n_new]
        self._buf[tgt] = new_rows
        self._buf_ids[tgt] = new_ids
        self._buf_dirty[tgt] = dirty
        self._slot_of[new_ids] = tgt
        self._touch(tgt)

    def _evict_slots(self, slots: np.ndarray) -> None:
        """Batched eviction: one sorted scatter writes back the dirty rows."""
        vict_ids = self._buf_ids[slots]
        dirty = self._buf_dirty[slots]
        if dirty.any():
            d_ids = vict_ids[dirty]
            d_slots = slots[dirty]
            order = np.argsort(d_ids)       # sorted scatter, single gather pass
            self._arr[d_ids[order]] = self._buf[d_slots[order]]
            self.stats.disk_writes += len(d_ids)
        self.stats.evictions += len(slots)
        self._slot_of[vict_ids] = -1
        self._buf_ids[slots] = -1
        self._buf_dirty[slots] = False

    # -------------------------------------------------------------- vocab

    def ensure_vocab(self, max_word_id: int) -> None:
        """Watermark growth: the paper's W ← W + 1 on unseen words."""
        if max_word_id >= self.capacity:
            raise ValueError(
                f"word id {max_word_id} exceeds store capacity {self.capacity}; "
                "grow capacity at construction (static allocation for XLA)"
            )
        self.live_vocab = max(self.live_vocab, max_word_id + 1)

    # ---------------------------------------------------------- persistence

    def _fire(self, point: str) -> None:
        if self.faults is not None:
            self.faults.fire(point, step=self.step)

    def flush(self) -> None:
        """Crash-consistent flush: WAL-committed write-back of all dirty
        buffer rows + memmap + manifest.

        Protocol (every on-disk transition is shadow-write → fsync →
        atomic rename, so a SIGKILL at ANY point leaves the store
        recoverable to a consistent version — see ``_recover``):

          1. snapshot the dirty rows + scalars into ``store.wal.tmp``
             (checksummed, fsync'd);                       [kill → old version]
          2. rename to ``store.wal`` — the COMMIT point;   [kill → new version]
          3. apply the rows to the memmap and msync;       [kill → new version]
          4. atomically replace the manifest;              [kill → new version]
          5. retire the WAL.

        The seeded fault points: ``mid-flush`` fires between 1 and 2
        (pre-commit), ``pre-publish`` between 3 and 4 (post-apply,
        pre-manifest) — the two sides of the commit the chaos tests kill
        at.
        """
        self._check_writable()
        with self._lock:
            self._sync_tier()
            dirty_slots = np.flatnonzero(self._buf_dirty)
            d_ids = self._buf_ids[dirty_slots]
            order = np.argsort(d_ids)
            d_ids = d_ids[order]
            d_rows = self._buf[dirty_slots[order]]
            wal = self._wal_path()
            _write_record(
                wal + ".tmp",
                {"ids": d_ids, "rows": d_rows, "phi_k": self.phi_k},
                self._manifest_payload(version=self.flush_version + 1),
            )
            self._fire(fault_lib.MID_FLUSH)
            os.replace(wal + ".tmp", wal)              # ---- COMMIT ----
            _fsync_dir(self.path)
            if len(d_ids):
                self._arr[d_ids] = d_rows
                self.stats.disk_writes += len(d_ids)
                self._buf_dirty[dirty_slots] = False
            self._mm.flush()
            self._fire(fault_lib.PRE_PUBLISH)
            self.flush_version += 1
            self._save_manifest()
            os.unlink(wal)

    def _manifest_path(self) -> str:
        return os.path.join(self.path, self.MANIFEST)

    def _wal_path(self) -> str:
        return os.path.join(self.path, self.WAL)

    def _manifest_payload(self, version: Optional[int] = None) -> dict:
        return {
            "K": self.K,
            "capacity": self.capacity,
            "live_vocab": self.live_vocab,
            "step": self.step,
            "phi_k": self.phi_k.tolist(),
            "dtype": self.dtype.name,
            "version": self.flush_version if version is None else version,
        }

    def _save_manifest(self) -> None:
        tmp = self._manifest_path() + ".tmp"
        payload = self._manifest_payload()
        payload["crc"] = zlib.crc32(
            json.dumps(payload, sort_keys=True).encode()
        )
        with open(tmp, "w") as f:
            json.dump(payload, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self._manifest_path())   # atomic rename
        _fsync_dir(self.path)

    def _apply_manifest(self, payload: dict) -> None:
        assert payload["K"] == self.K, "topic count mismatch on restart"
        self.live_vocab = int(payload["live_vocab"])
        self.step = int(payload["step"])
        self.phi_k = np.asarray(payload["phi_k"], np.float64)  # lint: host-f64
        self.flush_version = int(payload.get("version", 0))

    def _recover(self) -> None:
        """Recovery scan on open: roll the store to its last consistent
        version.

        * stale ``*.tmp`` shadows (a kill before a commit rename) are
          deleted;
        * a valid committed WAL is replayed — rows into the memmap,
          scalars into the manifest — and retired (idempotent: replaying
          an already-applied WAL rewrites identical bytes), repairing both
          a missing/stale manifest and a partially applied memmap write;
        * a torn/corrupt WAL means the flush never committed: it is
          discarded and the previous manifest version stands;
        * a corrupt manifest with no WAL to rebuild from raises
          ``StoreCorruptionError`` (external damage, not a crash artifact
          — every crash window above leaves a recoverable state).
        """
        self.recovered_from_wal = False
        for stale in (self._wal_path() + ".tmp",
                      self._manifest_path() + ".tmp"):
            if os.path.exists(stale):
                os.unlink(stale)
        wal = self._wal_path()
        if os.path.exists(wal):
            rec = _read_record(wal)
            if rec is None:                      # torn: never committed
                os.unlink(wal)
            else:
                arrays, meta = rec
                ids = arrays["ids"].astype(np.int64)
                if len(ids):
                    self._arr[ids] = arrays["rows"].astype(self.dtype)
                self._mm.flush()
                self._apply_manifest(
                    {**meta, "phi_k": arrays["phi_k"].tolist()}
                )
                self._save_manifest()
                os.unlink(wal)
                self.recovered_from_wal = True
                return
        self._load_manifest()

    def _load_manifest(self) -> None:
        p = self._manifest_path()
        if not os.path.exists(p):
            return
        try:
            with open(p) as f:
                payload = json.load(f)
            crc = payload.pop("crc", None)
        except (OSError, ValueError) as e:
            raise StoreCorruptionError(
                f"unreadable store manifest {p} and no WAL to rebuild from"
            ) from e
        if crc is not None and crc != zlib.crc32(
            json.dumps(payload, sort_keys=True).encode()
        ):
            raise StoreCorruptionError(
                f"store manifest {p} fails its checksum and no WAL exists"
            )
        self._apply_manifest(payload)

    # ------------------------------------------------------------- helpers

    def stats_window(self, reset: bool = True) -> StoreStats:
        """Snapshot the I/O counters, optionally zeroing them — the serving
        engine samples per-request-window hit/miss/promotion rates with
        this instead of differencing cumulative totals."""
        with self._lock:
            snap = self.stats.snapshot()
            if reset:
                self.stats.reset()
            return snap

    def bump_pipeline_stats(
        self, overlap_seconds: float = 0.0, prefetch_hit: bool = False
    ) -> Tuple[int, int, int]:
        """Credit the prefetch pipeline's counters and return the current
        ``(disk_reads, disk_writes, buffer_hits)`` totals — one locked
        read-modify-read so a concurrent ``stats_window(reset=True)`` can
        neither lose the bump nor observe a torn delta (the trainer used
        to ``+=`` these fields without the lock)."""
        with self._lock:
            self.stats.overlap_seconds += overlap_seconds
            if prefetch_hit:
                self.stats.prefetch_hits += 1
            return (
                self.stats.disk_reads,
                self.stats.disk_writes,
                self.stats.buffer_hits,
            )

    def take_changed(self, reset: bool = True) -> np.ndarray:
        """Row ids written since the last take — the delta one φ publish
        covers.  ``SnapshotPublisher.publish`` drains this under the store
        lock so per-version cache invalidation drops exactly the rows that
        changed instead of the whole cache."""
        with self._lock:
            ids = np.flatnonzero(self._changed)
            if reset:
                self._changed[ids] = False
            return ids

    def dense_phi(self) -> np.ndarray:
        """Materialise the live (W, K) matrix (tests / small corpora only)."""
        if self.readonly:
            n = max(self.live_vocab, 1)
            return np.asarray(self._read_backing(np.arange(n)))
        self.flush()
        return np.asarray(self._mm[: max(self.live_vocab, 1)])

    def resident_rows(self) -> int:
        return int((self._buf_ids >= 0).sum())

    def buffer_bytes(self) -> int:
        return self.resident_rows() * self.K * self.dtype.itemsize

    @staticmethod
    def rows_for_bytes(num_topics: int, nbytes: float, dtype=np.float32) -> int:
        """Translate a Table-5 style buffer size in bytes into W* rows."""
        return int(nbytes // (num_topics * np.dtype(dtype).itemsize))


# ---------------------------------------------------------------------------
# Versioned φ snapshots — the lifelong train-while-serve publish protocol
# ---------------------------------------------------------------------------


def _host_quantize_rows(
    phi: np.ndarray, phi_dtype: str
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Host-side mirror of ``kernels.theta_sweep.quantize_phi`` for snapshot
    storage: bf16 cast (exact f32 round-trip for serving reads) or symmetric
    per-row int8 (``scale_w = max_k |φ_w(k)| / 127``, 1.0 for all-zero rows).
    Falls back to f32 storage when ``ml_dtypes`` is unavailable — a memory
    regression, never a correctness one."""
    if phi_dtype in (None, "float32"):
        return phi, None
    if phi_dtype == "bfloat16":
        try:
            import ml_dtypes
        except ImportError:
            return phi, None
        return phi.astype(ml_dtypes.bfloat16), None
    if phi_dtype == "int8":
        amax = np.abs(phi).max(axis=-1)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.round(phi / scale[:, None]), -127, 127)
        return q.astype(np.int8), scale
    raise ValueError(
        f"unknown phi_dtype {phi_dtype!r}; expected float32/bfloat16/int8"
    )


class PhiSnapshot:
    """One immutable, crc-manifested φ version — the publish unit of the
    lifelong train-while-serve protocol.

    A snapshot owns read-only copies of the full (capacity, K) φ̂ block and
    the (K,) topic totals as of one committed flush, stamped with the
    publish ``version`` (the subscriber-facing epoch), the store's
    ``write_version``/``flush_version`` it captured, and the row ids the
    publish changed (``changed_ids`` — what per-version cache invalidation
    drops).  ``crc`` is computed over the copied bytes at publish;
    ``verify()`` recomputes it, so a reader holding a torn or mutated φ
    fails loudly instead of serving garbage.

    Readers *pin* a version by simply holding the reference: nothing the
    trainer does after publish can change these arrays, so an in-flight
    request batch is consistent end to end.  ``quantize`` memoizes the
    bf16/int8 serving storage per dtype — built once per version at
    hot-swap time, shared by every subsequent launch on this version.
    """

    def __init__(self, *, version: int, phi: np.ndarray, phi_k: np.ndarray,
                 step: int, live_vocab: int, write_version: int,
                 flush_version: int, changed_ids: np.ndarray):
        phi = np.ascontiguousarray(phi)
        phi.setflags(write=False)
        phi_k = np.ascontiguousarray(phi_k)
        phi_k.setflags(write=False)
        changed_ids = np.ascontiguousarray(np.asarray(changed_ids, np.int64))
        changed_ids.setflags(write=False)
        self.version = int(version)
        self.phi = phi                 # (capacity, K) read-only
        self.phi_k = phi_k             # (K,) read-only
        self.step = int(step)
        self.live_vocab = int(live_vocab)
        self.write_version = int(write_version)
        self.flush_version = int(flush_version)
        self.changed_ids = changed_ids
        self.crc = self._crc()
        self._quant: dict = {}
        self._quant_lock = threading.Lock()

    @property
    def K(self) -> int:
        return self.phi.shape[1]

    def _crc(self) -> int:
        crc = zlib.crc32(self.phi)
        crc = zlib.crc32(self.phi_k, crc)
        header = f"{self.version}:{self.step}:{self.write_version}".encode()
        return zlib.crc32(header, crc)

    def verify(self) -> bool:
        """Recompute the manifest crc — a torn/mutated φ fails here."""
        return self._crc() == self.crc

    def fetch_rows(self, word_ids: np.ndarray) -> np.ndarray:
        """Gather (len(ids), K) f32 rows — always from THIS version."""
        return np.asarray(
            self.phi[np.asarray(word_ids, np.int64)], np.float32
        )

    def quantize(
        self, phi_dtype: str
    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Memoized ``(values, scale)`` serving storage of this version
        (thread-safe: the first caller builds, everyone else shares)."""
        key = phi_dtype or "float32"
        with self._quant_lock:
            got = self._quant.get(key)
            if got is None:
                got = _host_quantize_rows(self.phi, key)
                self._quant[key] = got
            return got


class SnapshotPublisher:
    """Versioned φ publish/subscribe over a :class:`ParameterStore`.

    ``publish()`` is the trainer-side commit: under the store lock it
    drives the WAL-committed ``ParameterStore.flush()`` (the durable
    commit point — a crash mid-publish recovers to a consistent version
    by the PR-7 protocol), captures an immutable :class:`PhiSnapshot` of
    the post-flush state, drains the store's changed-row delta, and
    stamps the next monotonically increasing snapshot version.  The last
    ``retain`` versions stay referenced so readers pinned to an older
    epoch finish their in-flight batches before the arrays are dropped;
    the staleness bound of any launch is therefore ≤ ``retain`` versions
    by construction.

    Readers never block writers: ``latest()`` is one lock-protected list
    read, ``wait_for(version)`` parks on a condition until the trainer
    catches up.  Generalizes the PR-1 prefetcher's ``write_version``
    reconciliation from row-level to whole-φ epochs.
    """

    def __init__(self, store: ParameterStore, retain: int = 2):
        if retain < 1:
            raise ValueError("retain must be >= 1")
        self.store = store
        self.retain = int(retain)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._snaps: List[PhiSnapshot] = []
        self.version = 0                  # last published version (0 = none)
        self.publish_log: List[dict] = []

    def publish(self) -> PhiSnapshot:
        """Commit the current φ (WAL flush) and publish it as a snapshot."""
        t0 = time.perf_counter()
        with self._cond:                      # serialize publishers
            with self.store._lock:            # atomic wrt trainer writes
                self.store.flush()            # ---- the COMMIT point ----
                snap = PhiSnapshot(
                    version=self.version + 1,
                    phi=self.store._arr.copy(),
                    phi_k=self.store.phi_k.copy(),
                    step=self.store.step,
                    live_vocab=self.store.live_vocab,
                    write_version=self.store.write_version,
                    flush_version=self.store.flush_version,
                    changed_ids=self.store.take_changed(reset=True),
                )
            self.version = snap.version
            self._snaps.append(snap)
            del self._snaps[: -self.retain]
            self.publish_log.append({
                "version": snap.version,
                "step": snap.step,
                "changed_rows": int(len(snap.changed_ids)),
                "seconds": time.perf_counter() - t0,
            })
            self._cond.notify_all()
        return snap

    def latest(self) -> Optional[PhiSnapshot]:
        with self._lock:
            return self._snaps[-1] if self._snaps else None

    def get(self, version: int) -> Optional[PhiSnapshot]:
        """A still-retained snapshot by version (None once aged out)."""
        with self._lock:
            for snap in self._snaps:
                if snap.version == version:
                    return snap
            return None

    def wait_for(self, version: int,
                 timeout: Optional[float] = None) -> Optional[PhiSnapshot]:
        """Block until ``version`` (or newer) is published; None on timeout."""
        with self._cond:
            ok = self._cond.wait_for(
                lambda: self.version >= version, timeout=timeout
            )
            return self._snaps[-1] if ok else None


# ---------------------------------------------------------------------------
# Serving-side hot-word row cache — read-only LRU above the store
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CacheStats:
    """Hit/miss accounting for one :class:`HotRowCache` window."""

    hits: int = 0            # rows served from the cache
    misses: int = 0          # rows fetched through the store
    invalidations: int = 0   # epoch installs / whole-cache drops
    rows_dropped: int = 0    # resident rows evicted by invalidation

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0


class HotRowCache:
    """Read-only hot-word φ̂-row LRU layered over a :class:`ParameterStore`.

    Serving traffic is Zipf-skewed: a few hundred head words dominate every
    request batch, but each ``TopicServer`` request localizes its own
    vocabulary, so the store's training buffer — tuned for minibatch
    streams and shared with the write-back path — sees the same head rows
    re-requested under lock contention with training I/O.  This cache keeps
    those rows in a serving-owned, read-only buffer:

    * misses fall through with ``store.fetch_rows(..., promote=False)`` so
      a serving miss is cached exactly once (here), never double-promoted
      into the training LRU;
    * unpinned caches invalidate whole when ``store.write_version`` moves —
      the frozen-φ serving contract means version changes are rare (model
      refresh), so correctness costs one bulk drop instead of per-row
      coherence;
    * under the lifelong publish protocol the server instead calls
      ``install_version(v, changed_ids)`` at each hot-swap: only the rows
      the publish actually changed are dropped (per-version *epoch*
      invalidation), so the Zipf head survives a publish and the hit rate
      doesn't reset to zero every cadence; fetches then pass the pinned
      epoch + snapshot source so a straggler launch on an older version
      bypasses the cache instead of mixing epochs;
    * hit/miss counters are windowed (``window_stats``) so the engine can
      report per-request-batch rates.

    Same array-backed LRU discipline as the store buffer (ids/clock/slot
    vectors, batched eviction); rows within one ``fetch`` must be unique —
    they are a request batch's deduplicated local vocabulary.
    """

    def __init__(self, store: ParameterStore, capacity: int):
        self.store = store
        self.capacity = int(capacity)
        self.K = store.K
        self._version = store.write_version
        self._lock = threading.Lock()
        self._buf = np.zeros((self.capacity, self.K), store.dtype)
        self._ids = np.full((self.capacity,), -1, np.int64)
        self._clock_v = np.zeros((self.capacity,), np.int64)
        self._slot_of = np.full((store.capacity,), -1, np.int64)
        self._clock = 0
        self._pinned = False             # True once install_version() ran
        self.stats = CacheStats()        # cumulative
        self._window = CacheStats()      # since last window_stats(reset=True)

    def _count(self, hits: int = 0, misses: int = 0, inval: int = 0,
               rows_dropped: int = 0) -> None:
        for s in (self.stats, self._window):
            s.hits += hits
            s.misses += misses
            s.invalidations += inval
            s.rows_dropped += rows_dropped

    def _invalidate(self) -> None:
        dropped = int((self._ids >= 0).sum())
        self._ids.fill(-1)
        self._slot_of.fill(-1)
        self._count(inval=1, rows_dropped=dropped)

    def install_version(self, version: int,
                        changed_ids: Optional[np.ndarray] = None) -> int:
        """Pin the cache to a published φ epoch, dropping only the rows the
        publish changed.  ``changed_ids=None`` drops everything (the
        conservative fallback).  Returns the number of rows dropped; after
        the first call the cache stops auto-invalidating on raw
        ``store.write_version`` movement — the publish protocol owns epoch
        transitions."""
        with self._lock:
            if changed_ids is None:
                dropped = int((self._ids >= 0).sum())
                self._ids.fill(-1)
                self._slot_of.fill(-1)
            else:
                ids = np.asarray(changed_ids, np.int64)
                ids = ids[ids < len(self._slot_of)]
                slots = self._slot_of[ids]
                res = slots >= 0
                dropped = int(res.sum())
                if dropped:
                    s = slots[res]
                    self._slot_of[self._ids[s]] = -1
                    self._ids[s] = -1
            self._pinned = True
            self._version = int(version)
            self._count(inval=1, rows_dropped=dropped)
            return dropped

    def reset_stats(self) -> None:
        """Zero both counters under the lock (prewarm discards warm-up
        traffic without racing a concurrent launcher fetch)."""
        with self._lock:
            self.stats = CacheStats()
            self._window = CacheStats()

    def fetch(self, word_ids: np.ndarray, source=None,
              version: Optional[int] = None) -> np.ndarray:
        """Gather φ̂ rows for a request batch's unique vocabulary.

        ``source`` (anything with ``fetch_rows(ids) -> (n, K) f32``, e.g. a
        pinned snapshot view) replaces the store as the miss path;
        ``version`` is the caller's pinned epoch — if it differs from the
        cache's installed epoch the fetch bypasses the cache entirely (a
        straggler on an old version must not pollute the new epoch, and
        must not read rows cached from it)."""
        ids = np.asarray(word_ids, np.int64)
        if source is not None:
            fill = source.fetch_rows
        else:
            def fill(miss):
                return self.store.fetch_rows(miss, promote=False)
        if self.capacity == 0:
            with self._lock:
                self._count(misses=len(ids))
            return fill(ids)
        with self._lock:
            if version is not None and int(version) != self._version:
                self._count(misses=len(ids))
                return fill(ids)
            if not self._pinned and self.store.write_version != self._version:
                self._invalidate()
                self._version = self.store.write_version
            slots = self._slot_of[ids]
            hit = slots >= 0
            n_hit = int(hit.sum())
            if n_hit == len(ids):                 # head-word fast path
                out = self._buf[slots]
                self._touch(slots)
                self._count(hits=n_hit)
                return out
            miss_idx = np.flatnonzero(~hit)
            miss_ids = ids[miss_idx]
            rows = fill(miss_ids)
            if n_hit == 0:
                out = rows
            else:
                out = np.empty((len(ids), self.K), self._buf.dtype)
                hit_idx = np.flatnonzero(hit)
                hit_slots = slots[hit_idx]
                out[hit_idx] = self._buf[hit_slots]
                self._touch(hit_slots)
                out[miss_idx] = rows
            self._count(hits=n_hit, misses=len(miss_ids))
            self._insert(miss_ids, rows)
            return out

    def _touch(self, slots: np.ndarray) -> None:
        n = len(slots)
        if n:
            self._clock_v[slots] = np.arange(self._clock, self._clock + n)
            self._clock += n

    def _insert(self, ids: np.ndarray, rows: np.ndarray) -> None:
        n_new = len(ids)
        if n_new > self.capacity:                 # keep the batch's tail
            ids, rows = ids[-self.capacity:], rows[-self.capacity:]
            n_new = self.capacity
        if n_new == 0:
            return
        free = np.flatnonzero(self._ids < 0)
        need = n_new - len(free)
        if need > 0:
            occupied = np.flatnonzero(self._ids >= 0)
            oldest = occupied[
                np.argpartition(self._clock_v[occupied], need - 1)[:need]
            ]
            self._slot_of[self._ids[oldest]] = -1
            self._ids[oldest] = -1
            free = np.concatenate([free, oldest])
        tgt = free[:n_new]
        self._buf[tgt] = rows
        self._ids[tgt] = ids
        self._slot_of[ids] = tgt
        self._touch(tgt)

    def resident_rows(self) -> int:
        return int((self._ids >= 0).sum())

    def window_stats(self, reset: bool = True) -> CacheStats:
        """Hit/miss counters since the last window; the engine calls this
        once per flushed batch to surface per-batch cache rates."""
        with self._lock:
            snap = dataclasses.replace(self._window)
            if reset:
                self._window = CacheStats()
            return snap


# ---------------------------------------------------------------------------
# Asynchronous prefetch — double-buffered fetch stage of the pipeline
# ---------------------------------------------------------------------------


class PrefetchedBatch(NamedTuple):
    """A minibatch staged by the worker: its φ̂ rows (None while the store
    has a device tier, which holds them), the store version the fetch is
    consistent with, and how long the host I/O took (span ``foem.fetch``,
    any store lock wait included)."""

    minibatch: object            # sparse.minibatch.Minibatch
    phi_rows: Optional[np.ndarray]  # (W_s, K)
    version: int                 # store.write_version at fetch time
    fetch_seconds: float


class StreamPrefetcher:
    """Background fetch of upcoming minibatches' φ̂ rows (double buffering).

    A worker thread (``sparse.minibatch.prefetch_iterator``) drains
    ``stream`` — so bucketization and ``localize_vocab`` also run off the
    critical path — fetches each minibatch's rows, and stages
    ``PrefetchedBatch`` items in a bounded queue.  With ``depth=1`` the
    worker is fetching minibatch s+1 while the consumer computes on
    minibatch s.

    Because a staged fetch may predate the consumer's most recent
    ``write_rows``, each item carries the store ``write_version`` it saw;
    the consumer patches rows overlapping any newer write-back (the
    trainer keeps the last few write sets) — that reconciliation is what
    makes prefetched and sequential execution bitwise-identical.

    The worker's stages are spans ``foem.next_minibatch`` (the stream's
    bucketize and localize) and ``foem.fetch``.  While the store has a
    device tier the worker fetches no rows: the trainer gathers them there.
    """

    def __init__(self, store: ParameterStore, stream: Iterable, depth: int = 1):
        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        # local import: core.streaming is imported by repro.core's package
        # init, which sparse must not depend on at module load
        from repro.sparse.minibatch import prefetch_iterator

        def staged() -> Iterator[PrefetchedBatch]:
            it = iter(stream)
            while True:
                with span("foem.next_minibatch"):
                    mb = next(it, None)
                if mb is None:
                    return
                with span("foem.fetch") as fetch:
                    if store.has_tier:
                        rows, version = None, store.write_version
                    else:
                        rows, version = store.fetch_rows_versioned(
                            mb.local_vocab)
                yield PrefetchedBatch(mb, rows, version, fetch.seconds)

        self._inner = prefetch_iterator(staged(), depth=depth)

    def __iter__(self) -> Iterator[PrefetchedBatch]:
        """Yields the staged batches in stream order (the consumer times
        its own wait on the queue)."""
        return self._inner

    def close(self) -> None:
        """Stop the worker and release the source (safe to call repeatedly)."""
        self._inner.close()
