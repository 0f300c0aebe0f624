"""The benchmark's own gossip-DAG generator.

A copy of the shape of ``tpu_swirld.sim.generate_gossip_dag`` /
``stream_gossip_dag`` (per-member self-chains, or branches where a member
forks, stitched by a random other parent taken from a random member's
branch head), kept here so that no program change can move the
yardstick.  It builds plain columns (creator, parent indices,
timestamps, ids, signatures) that the reference reads, and the program's
own ``Event`` records only where the engine is fed.

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
             dag_seed: int, forkers: int = 0,
             fork_prob: float = 0.05) -> History:
    """A random-gossip history of ``n_events`` events.

    Genesis events first, one per member; then each event picks a random
    creator, a random other member, a random branch head of that member
    as other parent and a random branch head of its creator to extend:
    the RNG call pattern of the program's generator.  A member keeps one
    branch unless it forks.  The first ``forkers`` members (before the
    relabelling) fork: when such a member's chosen head is not its
    genesis, with probability ``fork_prob`` the new event is a sibling of
    that head (the same self-parent, payload ``fork:<index>``) and opens a
    new branch.  With no forkers no ``random()`` is drawn, so the history
    is the honest one draw for draw.

    The shape of the DAG, forks included, is drawn from ``dag_seed``;
    ``seed`` relabels the members (a permutation) and gives their keys, so
    every seed gets the same amount of work.  ``stake`` None is one
    each."""
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
    branches: List[List[int]] = []       # pre-label member -> branch heads

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

    for c in range(min(n_members, n_events)):
        emit(c, label[c], b"", ())
        branches.append([c])
    for i in range(n_members, n_events):
        c = rng.randrange(n_members)
        p = rng.randrange(n_members - 1)
        if p >= c:
            p += 1
        other = branches[p][rng.randrange(len(branches[p]))]
        b = rng.randrange(len(branches[c]))
        head = branches[c][b]
        if c < forkers and sp[head] >= 0 and rng.random() < fork_prob:
            sp[i], op[i] = sp[head], other
            emit(i, label[c], b"fork:%d" % i, (ids[sp[head]], ids[other]))
            branches[c].append(i)
        else:
            sp[i], op[i] = head, other
            emit(i, label[c], b"tx:%d" % i, (ids[head], ids[other]))
            branches[c][b] = i
    return History(members, stake, creator, sp, op, t, payload, ids, sigs)


def from_config(cfg, n_events: int, seed: int, dag_seed: int) -> History:
    """The history of a configuration file: its members, stake and
    forkers (``forkers`` 0 and ``fork_prob`` 0.05 where it states none)."""
    return generate(int(cfg["members"]), n_events, seed, cfg["stake"],
                    dag_seed, int(cfg.get("forkers", 0)),
                    float(cfg.get("fork_prob", 0.05)))


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
