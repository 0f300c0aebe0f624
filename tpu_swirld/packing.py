"""Dense packing: hash-addressed event DAG -> index arrays for the device.

SURVEY.md §7 step 2 / BASELINE.json north star: events and their parent
pointers are packed into a dense ``(N, 2)`` int32 index array plus creator /
seq / timestamp / coin-bit vectors in topological (insertion) order.  The
packer is append-only and incremental: gossip-sync deltas append to the same
:class:`Packer`, and :meth:`Packer.pack` snapshots the arrays the pipeline
consumes (``tpu_swirld.tpu.pipeline``).

Everything here is host-side numpy — the device never touches hashes.  The
hash <-> index mapping (``ids``) and the raw signatures (``sigs``, for the
order-extraction whitening hash) stay on the host.

Fork bookkeeping: the oracle detects forks per ``(creator, seq)`` group
(minimal fork pairs always share them — see the spec block in
``tpu_swirld.oracle.node``).  The packer mirrors that: every unordered pair
of distinct events by one creator at one seq becomes a ``fork_pairs`` row
``(member, idx_a, idx_b)``; the device computes ``forkseen[x, m]`` as an OR
of ``anc[x, a] & anc[x, b]`` over that member's rows.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from tpu_swirld import obs
from tpu_swirld.oracle.event import Event


@dataclasses.dataclass
class PackedDAG:
    """Snapshot of a packed event DAG (topo order, genesis parents = -1)."""

    n: int                     # number of events
    n_members: int
    parents: np.ndarray        # int32[N, 2]; -1 for genesis
    creator: np.ndarray        # int32[N]; member index
    seq: np.ndarray            # int32[N]; self-chain height
    t: np.ndarray              # int64[N]; creation timestamps
    coin: np.ndarray           # uint8[N]; signature middle bit (coin rounds)
    stake: np.ndarray          # int32[M]
    fork_pairs: np.ndarray     # int32[G, 3]: (member, idx_a, idx_b)
    member_table: np.ndarray   # int32[M, K]: event idx per member, -1 pad
    ids: List[bytes]           # event id per index (host only)
    sigs: List[bytes]          # signature per index (host only)

    @property
    def max_events_per_member(self) -> int:
        return self.member_table.shape[1]

    def index_of(self, eid: bytes) -> int:
        return self.ids.index(eid)


class Packer:
    """Append-only incremental packer (one per consensus engine instance).

    Columns live in amortized-doubling numpy buffers written in place by
    :meth:`append`, so :meth:`pack` is O(1) in the already-packed prefix:
    it snapshots read-only *views* of the buffers instead of rebuilding
    every slab from the python lists (the old behaviour made each steady-
    state repack O(N)).  Appends only ever write *past* the snapshotted
    length and buffer growth reallocates rather than resizing in place, so
    earlier snapshots stay valid forever.
    """

    _INIT_CAP = 256

    def __init__(self, members: Sequence[bytes], stake: Sequence[int]):
        if len(members) != len(stake):
            raise ValueError("members and stake length mismatch")
        self.members: List[bytes] = list(members)
        self.member_index: Dict[bytes, int] = {m: i for i, m in enumerate(members)}
        self.stake = np.asarray(stake, dtype=np.int32)
        self.idx: Dict[bytes, int] = {}         # event id -> index
        self._n = 0
        cap = self._INIT_CAP
        self._parents = np.full((cap, 2), -1, dtype=np.int32)
        self._creator = np.zeros((cap,), dtype=np.int32)
        self._seq = np.zeros((cap,), dtype=np.int32)
        self._t = np.zeros((cap,), dtype=np.int64)
        self._coin = np.zeros((cap,), dtype=np.uint8)
        self._ids: List[bytes] = []
        self._sigs: List[bytes] = []
        self._member_counts = np.zeros((len(members),), dtype=np.int32)
        self._by_seq: List[Dict[int, List[int]]] = [{} for _ in members]
        self._k = 1                              # member_table column capacity
        self._member_table = np.full((len(members), self._k), -1, dtype=np.int32)
        self._fork_pairs = np.zeros((0, 3), dtype=np.int32)
        self._n_fork_pairs = 0
        self.packs = 0                           # observability: pack() calls

    def __len__(self) -> int:
        return self._n

    # ---- dynamic membership (epoch repack seam: membership.repack)

    def add_member(self, pk: bytes) -> int:
        """Append one member row (a decided JOIN): the member axis only
        ever *extends*, so existing event indices, fork pairs, and every
        snapshot stay valid.  Returns the new member index."""
        if pk in self.member_index:
            return self.member_index[pk]
        i = len(self.members)
        self.members.append(pk)
        self.member_index[pk] = i
        counts = np.zeros((i + 1,), dtype=np.int32)
        counts[:i] = self._member_counts
        self._member_counts = counts
        self._by_seq.append({})
        table = np.full((i + 1, self._k), -1, dtype=np.int32)
        table[:i] = self._member_table
        self._member_table = table
        stake = np.zeros((i + 1,), dtype=np.int32)
        stake[:i] = self.stake
        self.stake = stake
        return i

    def set_stake(self, stake: Sequence[int]) -> None:
        """Swap the stake vector (a decided LEAVE/RESTAKE or an epoch
        activation).  Length must match the member axis."""
        if len(stake) != len(self.members):
            raise ValueError("stake length != member count")
        self.stake = np.asarray(stake, dtype=np.int32)

    def _grow(self, need: int) -> None:
        cap = self._parents.shape[0]
        if need <= cap:
            return
        new_cap = max(cap * 2, need)

        def regrow(a, fill):
            out = np.full((new_cap,) + a.shape[1:], fill, a.dtype)
            out[: self._n] = a[: self._n]
            return out

        self._parents = regrow(self._parents, -1)
        self._creator = regrow(self._creator, 0)
        self._seq = regrow(self._seq, 0)
        self._t = regrow(self._t, 0)
        self._coin = regrow(self._coin, 0)

    def _grow_member_table(self, k: int) -> None:
        if k <= self._k:
            return
        new_k = max(self._k * 2, k)
        out = np.full((len(self.members), new_k), -1, dtype=np.int32)
        out[:, : self._k] = self._member_table
        self._member_table = out
        self._k = new_k

    def _push_fork_pair(self, row: Tuple[int, int, int]) -> None:
        g = self._n_fork_pairs
        if g >= self._fork_pairs.shape[0]:
            new_cap = max(8, self._fork_pairs.shape[0] * 2)
            out = np.full((new_cap, 3), -1, dtype=np.int32)
            out[:g] = self._fork_pairs[:g]
            self._fork_pairs = out
        self._fork_pairs[g] = row
        self._n_fork_pairs = g + 1

    def append(self, ev: Event) -> int:
        """Pack one event (parents must already be packed).  Idempotent."""
        return self.append_prepared(ev, ev.id)

    def append_prepared(self, ev: Event, eid: bytes) -> int:
        """:meth:`append` with the event id already computed — the
        decode-overlap worker hashes ids off-thread (``prepare_events``)
        and the main thread packs here without re-hashing.  All packer
        mutation stays on the calling thread."""
        existing = self.idx.get(eid)
        if existing is not None:
            return existing
        ci = self.member_index.get(ev.c)
        if ci is None:
            raise ValueError("unknown creator")
        i = self._n
        self._grow(i + 1)
        if ev.p:
            sp = self.idx.get(ev.p[0])
            op = self.idx.get(ev.p[1])
            if sp is None or op is None:
                raise ValueError("parent not packed (append in topo order)")
            seq = int(self._seq[sp]) + 1
            self._parents[i] = (sp, op)
        else:
            seq = 0
            self._parents[i] = (-1, -1)
        self.idx[eid] = i
        self._creator[i] = ci
        self._seq[i] = seq
        self._t[i] = int(ev.t)
        self._coin[i] = ev.coin_bit() & 1
        self._ids.append(eid)
        self._sigs.append(ev.s)
        slot = int(self._member_counts[ci])
        self._grow_member_table(slot + 1)
        self._member_table[ci, slot] = i
        self._member_counts[ci] = slot + 1
        group = self._by_seq[ci].setdefault(seq, [])
        for other in group:            # every prior same-(creator, seq) event
            self._push_fork_pair((ci, other, i))
        group.append(i)
        # publish last: every row/side-table write above used the local
        # index, so a concurrent len()/pack() reader (telemetry, the
        # decode-overlap driver's invariant checks) never observes a
        # half-written event at position _n - 1
        self._n = i + 1
        return i

    def extend(self, events: Iterable[Event]) -> List[int]:
        return [self.append(ev) for ev in events]

    def extend_prepared(self, pairs: Iterable[Tuple[Event, bytes]]) -> List[int]:
        """Pack a pre-decoded delta: ``pairs`` as produced by
        :func:`prepare_events` (typically on a worker thread)."""
        return [self.append_prepared(ev, eid) for ev, eid in pairs]

    # ---- bounded read-only views (the incremental driver's surface:
    # keeps the buffer layout private to this file; same freeze contract
    # as pack())

    def window_view(self, start: int, stop: Optional[int] = None):
        """Read-only ``(parents, creator, coin, t)`` column views for the
        packed events [start, stop) — an ingest delta."""
        stop = self._n if stop is None else stop
        return (
            self._ro(self._parents[start:stop]),
            self._ro(self._creator[start:stop]),
            self._ro(self._coin[start:stop]),
            self._ro(self._t[start:stop]),
        )

    @property
    def n_fork_pairs(self) -> int:
        return self._n_fork_pairs

    def fork_pairs_view(self, start: int = 0) -> np.ndarray:
        """Read-only fork-pair rows [start, n_fork_pairs)."""
        return self._ro(self._fork_pairs[start : self._n_fork_pairs])

    def sig(self, i: int) -> bytes:
        return self._sigs[i]

    def event_id(self, i: int) -> bytes:
        return self._ids[i]

    @staticmethod
    def _ro(view: np.ndarray) -> np.ndarray:
        """Freeze a buffer view: snapshots share memory with the live
        packer, so in-place mutation by a consumer must be an error, not
        silent corruption of every other outstanding snapshot."""
        view = view[:]
        view.flags.writeable = False
        return view

    def pack(self) -> PackedDAG:
        n = self._n
        m = len(self.members)
        k = max(int(self._member_counts.max(initial=0)), 1)
        self.packs += 1
        return PackedDAG(
            n=n,
            n_members=m,
            parents=self._ro(self._parents[:n]),
            creator=self._ro(self._creator[:n]),
            seq=self._ro(self._seq[:n]),
            t=self._ro(self._t[:n]),
            coin=self._ro(self._coin[:n]),
            stake=self.stake.copy(),
            # the member table is the one slab a future append may write
            # *inside* (a member's next slot can sit below another member's
            # column high-water mark), so it is copied; it is O(N/M * M) =
            # O(N) int32 but tiny next to the O(N) views above being free
            fork_pairs=self._fork_pairs[: self._n_fork_pairs].copy(),
            member_table=self._member_table[:, :k].copy(),
            ids=list(self._ids),
            sigs=list(self._sigs),
        )


def chunk_slices(n: int, chunk: int) -> List[Tuple[int, int]]:
    """Chunk-aligned ``[start, stop)`` slices covering ``[0, n)``.

    Every piece except the last is exactly ``chunk`` long, so a consumer
    that pads each piece to a ``chunk`` multiple (the device scan stages)
    wastes padding on at most one piece per delta.  Any split of a
    topologically ordered stream is itself topologically valid, so the
    slices can be ingested independently.
    """
    if chunk <= 0:
        raise ValueError("chunk must be positive")
    return [(s, min(n, s + chunk)) for s in range(0, n, chunk)]


def prepare_events(events: Sequence[Event]) -> List[Tuple[Event, bytes]]:
    """Gossip decode for a delta: compute each event's id (a content
    hash — the dominant host cost of packing) without touching any
    shared state.  Pure function of the events, so it can run on the
    streaming driver's decode worker while the device executes the
    previous chunk; the main thread packs the result with
    :meth:`Packer.extend_prepared`."""
    return [(ev, ev.id) for ev in events]


def pack_events(
    events: Sequence[Event],
    members: Sequence[bytes],
    stake: Optional[Sequence[int]] = None,
) -> PackedDAG:
    """Pack a topologically ordered event sequence in one shot: one
    ``swirld.pack`` record on the engine recorder
    (:func:`tpu_swirld.obs.recorder`)."""
    with obs.call_span(obs.recorder(), "swirld.pack") as sp:
        if stake is None:
            stake = [1] * len(members)
        p = Packer(members, stake)
        p.extend(events)
        sp.args["events"] = len(p)
        return p.pack()


def pack_node(node) -> PackedDAG:
    """Pack an oracle :class:`~tpu_swirld.oracle.node.Node`'s full DAG in its
    insertion (topo) order — the order its own consensus state was built in,
    which the parity tests compare against."""
    events = [node.hg[eid] for eid in node.order_added]
    stake = [node.stake[m] for m in node.members]
    return pack_events(events, node.members, stake)
