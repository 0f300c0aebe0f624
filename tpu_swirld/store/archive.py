"""Append-only host-side column archive of decided ancestry rows.

The streaming driver retires (spills) every event below the decided
frontier here; the device keeps only the undecided window resident.  Each
archived row ``e`` is the event's **full global ancestry bitmap** over
columns ``[0, e]`` (reflexive, topo order ⇒ nothing newer is an ancestor),
stored as a zlib-compressed ``np.packbits`` blob — gossip-DAG ancestry rows
are almost-all-ones below a recent horizon, so they compress to a few
percent of the raw ``N²/8`` bytes.

Rows arrive in two shapes:

- :meth:`spill` — *window rows* from the live driver, covering only the
  retained columns ``[lo, hi)``.  The prefix ``[0, lo)`` was pruned from
  the device slab earlier; it is reconstructed exactly from the parents'
  archived rows (``anc(e) ∩ [0, lo) = (anc(p1) ∪ anc(p2)) ∩ [0, lo)``,
  since ``e ≥ lo``) — the rows are appended in topo order, so parents are
  always already archived or earlier in the same batch.
- :meth:`spill_full` — full-width rows straight from a batch rebase's
  ``bool[N, N]`` slab (no reconstruction needed).

Sees rows are **not** archived: ``sees(e, j) = anc(e, j) & ~forkseen(e,
c(j))`` is derived on :meth:`fetch` from the archived ancestry row plus
the global fork-pair ledger (the packer keeps every pair forever, and a
pair discovered after ``e`` was archived cannot poison ``e`` — its second
member is newer than ``e``, so ``e`` never descends from it).  Archiving
one slab instead of two halves the archive.

Background packing
------------------

Packing (device pull + prefix reconstruction + ``packbits`` + zlib) runs
on a **background worker thread** behind a *bounded* spill queue, so the
streaming driver's critical path pays only an enqueue: while the device
extends the window for chunk ``k``, the worker compresses chunk ``k−1``'s
retired rows.  Exactness is preserved by a **drain barrier**: every read
of archived bytes (:meth:`fetch`, :meth:`digest`, :meth:`save`) first
waits for the queue to empty, so the visible archive is always the one a
synchronous spiller would have built — same rows, same blob stream, same
digest.  ``n_rows`` counts *accepted* rows (committed + queued), which is
the contiguity frontier the spiller and the widening rebase reason about.
A full queue blocks the spiller (backpressure, counted in
``stall_seconds``); a worker failure is re-raised on the next archive
operation rather than swallowed.  ``async_spill=False`` (or
``SWIRLD_ARCHIVE_ASYNC=0``) degrades to the fully synchronous behavior —
bit-identical output either way.

The streaming driver's **decode-overlap** worker
(:meth:`tpu_swirld.store.streaming.StreamingConsensus._chunked_deltas`)
is this protocol's ingest-side mirror: a bounded queue of pure
`prepare_events` jobs ahead of the device, a drain barrier at every
handoff (which re-raises worker failures), and a sync fallback that is
bit-identical by construction.  Audit changes to either against both.

Rows decompressed for parent-prefix reconstruction or fetches are kept in
a bounded LRU cache (parents of spilled rows are almost always recent, so
the hit rate is high), and :meth:`prefetch` warms that cache in the
background so a widening rebase's re-fetch overlaps the device pulls that
precede it.

The archive is checkpointable (:meth:`save` / :meth:`load`, no pickle)
and carries a running BLAKE2b digest of the appended blobs; ``load``
verifies it, so a corrupt archive fails loudly at restore time instead of
poisoning a later widening rebase.
"""

from __future__ import annotations

import collections
import queue
import struct
import threading
import time
import zlib
from typing import List, Optional

import numpy as np

from tpu_swirld import crypto, obs
from tpu_swirld.config import resolve_archive_settings

#: LRU capacity (decompressed rows) for the reconstruction/fetch cache
_ROW_CACHE_ENTRIES = 1024

# Schedule-fuzz seam: tpu_swirld.analysis.races installs a yield injector
# here to perturb client/worker interleavings at the tagged points below.
# None in production — each point costs one global read + None check.
_injector = None


def set_injector(inj) -> None:
    global _injector
    _injector = inj


def _yp(tag: str) -> None:
    inj = _injector
    if inj is not None:
        inj.point(tag)


class SlabArchive:
    """Append-only archive of decided ancestry rows (see module doc)."""

    #: archive format version (bump on layout changes)
    FORMAT_VERSION = 1

    #: every mutable attribute the pack worker shares with the client
    #: thread (SW006 lock-discipline): the spill queue itself, the blob
    #: list / byte counter / row cache it packs into behind the drain
    #: barrier, the failure slot, and the busy-time counter.  Audit any
    #: addition here against the queue/barrier protocol in the module doc.
    GUARDED_ATTRS = frozenset({
        "_q", "_rows", "_cache", "_committed_bytes", "_worker_err",
        "busy_seconds",
    })

    def __init__(
        self,
        compress_level: Optional[int] = None,
        *,
        queue_depth: Optional[int] = None,
        async_spill: Optional[bool] = None,
        config=None,
    ):
        s = resolve_archive_settings(config)
        self._rows: List[bytes] = []       # zlib(packbits(row over [0, e]))
        self._rounds: List[tuple] = []     # retired-round ledger
        self._level = (
            compress_level if compress_level is not None
            else s["compress_level"]
        )
        self.queue_depth = (
            queue_depth if queue_depth is not None else s["queue_depth"]
        )
        self._async = (
            async_spill if async_spill is not None else s["async_spill"]
        )
        self.spills = 0                    # spill batches accepted
        self.fetches = 0                   # fetch calls served
        self.spilled_rows = 0              # rows newly archived (accepted)
        self.fetched_rows = 0              # rows decompressed for callers
        self.skipped_rows = 0              # re-spills of already-archived rows
        self._n_accepted = 0               # committed + queued rows
        self._committed_bytes = 0
        self._cache: "collections.OrderedDict[int, np.ndarray]" = (
            collections.OrderedDict()
        )
        # background packing worker (lazily started on first async spill)
        self._q: Optional[queue.Queue] = None
        self._worker: Optional[threading.Thread] = None
        self._worker_err: Optional[BaseException] = None
        self.busy_seconds = 0.0            # worker time spent packing
        self.stall_seconds = 0.0           # caller time blocked on the queue
        self.max_queue_depth = 0           # high-water mark of queued batches

    # ------------------------------------------------------------- basics

    @property
    def n_rows(self) -> int:
        """Archived prefix length: rows ``[0, n_rows)`` are archived (or
        accepted into the spill queue — the drain barrier makes the
        distinction unobservable to readers)."""
        return self._n_accepted

    @property
    def committed_rows(self) -> int:
        """Rows physically packed (``n_rows`` minus the queue backlog)."""
        return len(self._rows)

    @property
    def archive_bytes(self) -> int:
        """Total compressed payload bytes currently committed (queued
        batches land here once the worker packs them)."""
        return self._committed_bytes

    @property
    def pending_batches(self) -> int:
        return self._q.qsize() if self._q is not None else 0

    @property
    def queue_full(self) -> bool:
        """True while a spill would block on the full queue."""
        return self._q is not None and self._q.full()

    def _row_bool(self, e: int) -> np.ndarray:
        """Decompress row ``e`` to a bool[e + 1] ancestry bitmap (LRU
        cached — parents of spilled rows and widening re-fetches are
        heavily repeated)."""
        cached = self._cache.get(e)
        if cached is not None:
            self._cache.move_to_end(e)
            return cached
        _yp("archive.cache.miss")
        raw = np.frombuffer(zlib.decompress(self._rows[e]), dtype=np.uint8)
        row = np.unpackbits(raw, count=e + 1).astype(bool)
        row.flags.writeable = False
        self._cache[e] = row
        if len(self._cache) > _ROW_CACHE_ENTRIES:
            self._cache.popitem(last=False)
        return row

    def _append_bool(self, row: np.ndarray) -> None:
        _yp("archive.append")
        blob = zlib.compress(np.packbits(row).tobytes(), self._level)
        self._rows.append(blob)
        self._committed_bytes += len(blob)

    # ------------------------------------------------- background worker

    def _make_queue(self, maxsize: int) -> queue.Queue:
        """Seam for analysis.races: the sanitized subclass returns a queue
        whose internal lock participates in the lock-order graph."""
        return queue.Queue(maxsize=maxsize)

    def _ensure_worker(self) -> queue.Queue:
        if self._q is None:
            self._q = self._make_queue(max(1, int(self.queue_depth)))
            self._worker = threading.Thread(
                target=self._worker_loop, name="slab-archive-pack",
                daemon=True,
            )
            self._worker.start()
        return self._q

    def _worker_loop(self) -> None:
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                _yp("archive.worker.item")
                t0 = time.perf_counter()
                kind, args = item
                if kind == "spill":
                    self._pack_window_rows(*args)
                elif kind == "spill_full":
                    self._pack_full_rows(*args)
                elif kind == "prefetch":
                    lo, hi = args
                    for e in range(max(0, lo), min(hi, len(self._rows))):
                        self._row_bool(e)
                self.busy_seconds += time.perf_counter() - t0
            except BaseException as exc:  # re-raised at the next barrier
                if self._worker_err is None:
                    self._worker_err = exc
            finally:
                self._q.task_done()

    def _drain(self) -> None:
        """Barrier: wait until every queued batch is packed, then re-raise
        any worker failure.  All reads of archived content go through
        here, so async and sync spilling are observationally identical."""
        _yp("archive.drain")
        if self._q is not None and (
            self._q.unfinished_tasks or not self._q.empty()
        ):
            t0 = time.perf_counter()
            self._q.join()
            self.stall_seconds += time.perf_counter() - t0
        if self._worker_err is not None:
            err, self._worker_err = self._worker_err, None
            raise RuntimeError("archive pack worker failed") from err

    def _enqueue(self, item) -> None:
        q = self._ensure_worker()
        _yp("archive.enqueue")
        self.max_queue_depth = max(self.max_queue_depth, q.qsize() + 1)
        o = obs.current()
        if o is not None:
            o.registry.gauge("store_spill_queue_depth").set(q.qsize() + 1)
        if q.full():
            t0 = time.perf_counter()
            q.put(item)
            self.stall_seconds += time.perf_counter() - t0
        else:
            q.put(item)

    def close(self) -> None:
        """Stop the worker after packing everything queued (idempotent)."""
        if self._q is not None:
            self._drain()
            self._q.put(None)
            self._worker.join()
            self._q = None
            self._worker = None

    # -------------------------------------------------------------- spill

    def spill(self, lo: int, parents, rows) -> int:
        """Archive window rows for global events ``[lo, lo + d)``.

        ``rows`` is bool[d, w] over retained columns ``[lo, lo + w)``
        (numpy or a lazily-materialized device array — async mode pulls it
        on the worker, off the caller's critical path); ``parents`` is the
        int32[d, 2] *global* parent indices of those events (-1 genesis).
        Rows already archived (``e < n_rows`` — possible after a widening
        rebase re-admitted them) are skipped: ancestry is a pure DAG
        function, so the archived copy is already the exact value.
        Returns the number of rows newly accepted.
        """
        d = int(rows.shape[0])
        if lo + d <= self.n_rows or d == 0:
            self.skipped_rows += d
            return 0
        if lo > self.n_rows:
            raise ValueError(
                f"non-contiguous spill: rows [{lo}, {lo + d}) after "
                f"{self.n_rows}"
            )
        added = lo + d - self.n_rows
        self.skipped_rows += d - added
        self._n_accepted = lo + d
        if self._async:
            self._enqueue(("spill", (lo, np.asarray(parents), rows)))
        else:
            self._pack_window_rows(lo, np.asarray(parents), rows)
        self.spills += 1
        self.spilled_rows += added
        self._record_gauges()
        return added

    def _pack_window_rows(self, lo: int, parents: np.ndarray, rows) -> None:
        rows = np.asarray(rows)
        for i in range(rows.shape[0]):
            e = lo + i
            if e < len(self._rows):
                continue
            if e != len(self._rows):
                raise ValueError(
                    f"non-contiguous spill: row {e} after {len(self._rows)}"
                )
            full = np.zeros(e + 1, dtype=bool)
            # pruned-prefix columns [0, lo) come from the parents' rows
            # (earlier-archived, or appended earlier in this same batch);
            # retained columns [lo, e] come straight from the device slab,
            # which already includes the parent closure there
            for p in parents[i]:
                p = int(p)
                if p < 0:
                    continue
                cut = min(p + 1, lo)
                if cut > 0:
                    full[:cut] |= self._row_bool(p)[:cut]
            full[lo : e + 1] = rows[i, : e - lo + 1]
            self._append_bool(full)

    def spill_full(self, start: int, rows) -> int:
        """Archive full-width rows for global events ``[start, start+d)``
        from a batch slab (bool[d, n] over global columns ``[0, n)``)."""
        d = int(rows.shape[0])
        if start + d <= self.n_rows or d == 0:
            self.skipped_rows += d
            return 0
        if start > self.n_rows:
            raise ValueError(
                f"non-contiguous spill: rows [{start}, {start + d}) after "
                f"{self.n_rows}"
            )
        added = start + d - self.n_rows
        self.skipped_rows += d - added
        self._n_accepted = start + d
        if self._async:
            self._enqueue(("spill_full", (start, rows)))
        else:
            self._pack_full_rows(start, rows)
        self.spills += 1
        self.spilled_rows += added
        self._record_gauges()
        return added

    def _pack_full_rows(self, start: int, rows) -> None:
        rows = np.asarray(rows)
        for i in range(rows.shape[0]):
            e = start + i
            if e < len(self._rows):
                continue
            if e != len(self._rows):
                raise ValueError(
                    f"non-contiguous spill: row {e} after {len(self._rows)}"
                )
            self._append_bool(rows[i, : e + 1])

    # -------------------------------------------------------------- fetch

    def prefetch(self, lo: int, hi: int) -> None:
        """Warm the decompressed-row cache for rows ``[lo, hi)`` in the
        background (best-effort: a no-op in sync mode or beyond the
        committed prefix).  A widening rebase calls this before its device
        pulls so decompression overlaps them."""
        if not self._async or hi <= lo:
            return
        lo = max(lo, hi - _ROW_CACHE_ENTRIES)   # cache-bounded window
        self._enqueue(("prefetch", (lo, hi)))
        o = obs.current()
        if o is not None:
            o.registry.counter("store_prefetches_total").inc()

    def fetch(
        self, lo: int, hi: int, col_lo: int, col_hi: int,
        out: "np.ndarray" = None,
    ) -> np.ndarray:
        """Re-admit archived ancestry rows ``[lo, hi)`` over columns
        ``[col_lo, col_hi)`` as a dense bool matrix (zero beyond each
        row's own index — topo order).  Drains the spill queue first.
        ``out`` decompresses straight into a caller buffer (e.g. the
        widening rebase's assembled slab, which ``slab_put`` then
        scatters to the mesh) instead of allocating an intermediate —
        must be bool, ``(hi - lo, col_hi - col_lo)``, zero-filled."""
        if hi > self.n_rows:
            raise ValueError(
                f"fetch [{lo}, {hi}) exceeds archived prefix {self.n_rows}"
            )
        self._drain()
        o = obs.current()
        span = (
            o.tracer.span("store.archive_fetch") if o is not None
            else _NULL_CTX
        )
        with span:
            if out is None:
                out = np.zeros((hi - lo, col_hi - col_lo), dtype=bool)
            elif out.shape != (hi - lo, col_hi - col_lo):
                raise ValueError(
                    f"out shape {out.shape} != "
                    f"{(hi - lo, col_hi - col_lo)}"
                )
            for i, e in enumerate(range(lo, hi)):
                row = self._row_bool(e)
                a = min(col_hi, e + 1)
                if a > col_lo:
                    out[i, : a - col_lo] = row[col_lo:a]
        self.fetches += 1
        self.fetched_rows += hi - lo
        if o is not None:
            o.registry.counter("store_fetches_total").inc()
            o.registry.counter("store_fetched_rows_total").inc(hi - lo)
        return out

    @staticmethod
    def derive_sees(
        anc_rows: np.ndarray,
        col_lo: int,
        creator: np.ndarray,
        fork_pairs: np.ndarray,
        n_members: int,
    ) -> np.ndarray:
        """Fork-aware visibility for fetched rows: ``sees = anc &
        ~forkseen[:, creator(col)]``.

        ``anc_rows`` is bool[d, c] over global columns ``[col_lo, col_lo +
        c)``; ``creator`` the global creator indices of those columns;
        ``fork_pairs`` the **global** int32[G, 3] ledger.  Pairs with a
        member outside the column span cannot poison these rows (the
        fetched rows never descend from anything outside ``[0, col_lo +
        c)``, and members below ``col_lo`` were below every archived
        row's own pruned prefix — the packer pins pairs above the prune
        boundary, so the span always covers every applicable pair).
        """
        d, c = anc_rows.shape
        fseen = np.zeros((d, n_members), dtype=bool)
        for m, a, b in fork_pairs:
            a, b = int(a) - col_lo, int(b) - col_lo
            if 0 <= a < c and 0 <= b < c:
                fseen[:, int(m)] |= anc_rows[:, a] & anc_rows[:, b]
        return anc_rows & ~fseen[:, creator]

    # ------------------------------------------------------- round ledger

    # The witness-round ledger mirrors the visibility archive at round
    # granularity: when the driver rolls a fame-complete round out of its
    # retained window, the row lands here (global round, witness event
    # indices in registration order, famous flags, decided_at).  It is
    # report/checkpoint metadata — the widening rebase never re-votes
    # committed rounds (a straggler below the frozen horizon takes the
    # full-rebase path instead).

    def retire_round(
        self, rnd: int, events, famous, decided_at
    ) -> None:
        self._rounds.append(
            (int(rnd), list(map(int, events)), list(map(int, famous)),
             list(map(int, decided_at)))
        )

    @property
    def retired_rounds(self) -> int:
        return len(self._rounds)

    # --------------------------------------------------------- checkpoint

    def digest(self) -> str:
        """BLAKE2b over the blob stream (order-sensitive).  Drains the
        spill queue first so the digest covers every accepted row."""
        self._drain()
        h = b""
        for b in self._rows:
            h = crypto.hash_bytes(h + crypto.hash_bytes(b))
        return h.hex()

    def save(self, path: str) -> None:
        """Single ``.npz``, no pickle: length-prefixed blob stream +
        round ledger + digest.  Drains the spill queue first (a
        checkpoint taken while spills are in flight persists them)."""
        self._drain()
        blob = b"".join(
            struct.pack("<I", len(b)) + b for b in self._rows
        )
        rounds = self._rounds
        rmeta = []
        rflat: List[int] = []
        for rnd, evs, fam, dec in rounds:
            rmeta.append((rnd, len(evs)))
            for e, f, dc in zip(evs, fam, dec):
                rflat.extend((e, f, dc))
        # write through a file object: np.savez_compressed appends ".npz"
        # to bare string paths, which would break save(p)/load(p) round
        # trips for any other suffix
        with open(path, "wb") as f:
            np.savez_compressed(
                f,
                format_version=self.FORMAT_VERSION,
                n_rows=len(self._rows),
                blobs=np.frombuffer(blob, dtype=np.uint8),
                round_meta=np.asarray(rmeta, dtype=np.int64).reshape(-1, 2),
                round_flat=np.asarray(rflat, dtype=np.int64),
                digest=np.frombuffer(self.digest().encode(), dtype=np.uint8),
            )

    @classmethod
    def load(cls, path: str) -> "SlabArchive":
        """Restore and **verify**: a digest mismatch (tampered or corrupt
        archive) raises ``ValueError`` instead of silently feeding wrong
        ancestry into a later widening rebase."""
        z = np.load(path)
        if int(z["format_version"]) != cls.FORMAT_VERSION:
            raise ValueError(
                f"unsupported archive version {int(z['format_version'])}"
            )
        arch = cls()
        blob = z["blobs"].tobytes()
        off = 0
        while off < len(blob):
            (ln,) = struct.unpack_from("<I", blob, off)
            off += 4
            arch._rows.append(blob[off : off + ln])
            off += ln
        arch._n_accepted = len(arch._rows)
        arch._committed_bytes = sum(len(b) for b in arch._rows)
        if arch.n_rows != int(z["n_rows"]):
            raise ValueError(
                f"archive truncated: {arch.n_rows} rows, header says "
                f"{int(z['n_rows'])}"
            )
        want = z["digest"].tobytes().decode()
        got = arch.digest()
        if got != want:
            raise ValueError(
                "archive digest mismatch (corrupt or tampered checkpoint)"
            )
        rmeta = z["round_meta"]
        rflat = z["round_flat"]
        pos = 0
        for rnd, cnt in rmeta:
            evs, fam, dec = [], [], []
            for _ in range(int(cnt)):
                e, f, dc = rflat[pos : pos + 3]
                evs.append(int(e))
                fam.append(int(f))
                dec.append(int(dc))
                pos += 3
            arch.retire_round(int(rnd), evs, fam, dec)
        return arch

    # ---------------------------------------------------------------- obs

    def _record_gauges(self) -> None:
        o = obs.current()
        if o is None:
            return
        g = o.registry
        g.gauge("store_archived_rows").set(self.n_rows)
        g.counter("store_spills_total").inc()


class _NullCtx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


_NULL_CTX = _NullCtx()
