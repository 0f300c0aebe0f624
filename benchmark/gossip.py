"""The benchmark's own gossip-DAG generator.

A copy of the shape of ``tpu_swirld.sim.generate_gossip_dag`` /
``stream_gossip_dag`` (per-member self-chains stitched by a random other
parent taken from a random member's head), kept here so that no program
change can move the yardstick.  It builds plain columns (creator, parent
indices, timestamps, ids, signatures) that the reference reads, and the
program's own ``Event`` records only where the engine is fed.

Event bytes follow the program's wire layout (``Event.body``): one parent
count byte, the parent ids, ``<q`` timestamp, ``<I``-prefixed creator key
and payload.  The id is BLAKE2b-256 of the body.  Signatures are the
64-byte keyed-hash scheme of the program's ``sim`` crypto backend over the
``EVNT:`` domain: the engines never verify a signature, they read its
middle bit for coin rounds and XOR it into the order's tiebreak, so a hash
gives the same bits at a fraction of an Ed25519 signature's set-up cost.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
import struct
from typing import List

import numpy as np

DOMAIN_EVENT = b"EVNT:"


def _blake(data: bytes, size: int) -> bytes:
    return hashlib.blake2b(data, digest_size=size).digest()


def keypair(seed: int, i: int):
    sk = _blake(b"sk" + b"dag-%d-%d" % (seed, i), 32)
    return _blake(b"pk" + sk, 32), sk


@dataclasses.dataclass
class History:
    """A generated, topologically ordered history (index = position)."""

    members: List[bytes]           # public keys, member index order
    stake: np.ndarray              # int64[M]
    creator: np.ndarray            # int32[N]
    self_parent: np.ndarray        # int32[N], -1 for genesis
    other_parent: np.ndarray       # int32[N], -1 for genesis
    t: np.ndarray                  # int64[N]
    payload: List[bytes]
    ids: List[bytes]
    sigs: List[bytes]

    @property
    def n(self) -> int:
        return len(self.ids)

    def prefix(self, n: int) -> "History":
        return History(
            self.members, self.stake, self.creator[:n],
            self.self_parent[:n], self.other_parent[:n], self.t[:n],
            self.payload[:n], self.ids[:n], self.sigs[:n],
        )


def generate(n_members: int, n_events: int, seed: int, stake,
             dag_seed: int) -> History:
    """An honest random-gossip history of ``n_events`` events.

    Genesis events first, one per member; then each event picks a random
    creator, a random other member and that member's head as other parent
    (the RNG call pattern of the program's generator with no forkers).

    The shape of the DAG is drawn from ``dag_seed``; ``seed`` relabels the
    members (a permutation) and gives their keys, so every seed gets the
    same amount of work.  ``stake`` None is one each."""
    rng = random.Random(dag_seed)
    label = list(range(n_members))
    random.Random(seed).shuffle(label)
    keys = [keypair(seed, i) for i in range(n_members)]
    members = [pk for pk, _ in keys]
    stake = np.asarray(
        [1] * n_members if stake is None else stake, dtype=np.int64
    )
    creator = np.zeros(n_events, np.int32)
    sp = np.full(n_events, -1, np.int32)
    op = np.full(n_events, -1, np.int32)
    t = np.zeros(n_events, np.int64)
    payload: List[bytes] = []
    ids: List[bytes] = []
    sigs: List[bytes] = []
    head = [0] * n_members

    def emit(i, c, d, parents):
        pk = members[c]
        body = b"".join((
            struct.pack("<B", len(parents)), *parents,
            struct.pack("<q", i + 1), struct.pack("<I", len(pk)), pk,
            struct.pack("<I", len(d)), d,
        ))
        ids.append(_blake(body, 32))
        sigs.append(_blake(pk + DOMAIN_EVENT + body, 64))
        payload.append(d)
        creator[i] = c
        t[i] = i + 1
        head[c] = i

    for c in range(min(n_members, n_events)):
        emit(c, label[c], b"", ())
    for i in range(n_members, n_events):
        c = rng.randrange(n_members)
        p = rng.randrange(n_members - 1)
        if p >= c:
            p += 1
        c, p = label[c], label[p]
        # one branch per honest member: the branch draws of the program's
        # generator always return its single head
        other = head[p]
        rng.randrange(1)
        rng.randrange(1)
        sp[i], op[i] = head[c], other
        emit(i, c, b"tx:%d" % i, (ids[head[c]], ids[other]))
    return History(members, stake, creator, sp, op, t, payload, ids, sigs)


def program_events(hist: History, start: int = 0, stop=None):
    """The history as the program's ``Event`` records (its input format)."""
    from tpu_swirld.oracle.event import Event

    stop = hist.n if stop is None else stop
    out = []
    for i in range(start, stop):
        s, o = int(hist.self_parent[i]), int(hist.other_parent[i])
        p = () if s < 0 else (hist.ids[s], hist.ids[o])
        out.append(Event(
            d=hist.payload[i], p=p, t=int(hist.t[i]),
            c=hist.members[hist.creator[i]], s=hist.sigs[i],
        ))
    return out
