"""Plain reference consensus: rounds, witnesses, fame, round received,
consensus timestamps and total order of a history.

Independent of the program: it reads only a :class:`benchmark.gossip.
History` and follows Baird's algorithm (SWIRLDS-TR-2016-01) with the
oracle's conventions: reflexive ancestry, the ∃-z strongly-sees rule, a
round-``r`` event promoted when it strongly sees round-``r`` witnesses of
more than ``num/den`` of the stake (2/3, strict, exact integers), fame
decided at the first non-coin round where a voter's tally reaches that
supermajority, coin votes from the signature's middle bit every
``coin_period`` rounds, round received by all unique famous witnesses,
the lower-median consensus timestamp and the BLAKE2b(whiten || id)
tiebreak.

With no forks, "sees" is ancestry, and ancestry is carried as two
integer tables instead of an N x N bitmap:

- ``last[x, m]``: the highest self-chain position of member ``m`` among
  ``x``'s ancestors (-1 for none), so ``y`` is an ancestor of ``x`` iff
  ``last[x, c(y)] >= seq(y)``;
- ``first[y, m]``: the lowest position on ``m``'s chain whose event has
  ``y`` as an ancestor (``len(chain)`` for none).

``x`` strongly sees ``w`` iff the stake of the members ``m`` with
``last[x, m] >= first[w, m]`` is a supermajority.

A history in which some member forks (two of its events at one
self-chain position: neither is a self-ancestor of the other) takes
:func:`consensus_forked`, which departs from the honest path where the
tables no longer hold:

- ancestry is a bitmap, one row per event (the OR of its parents' rows),
  its columns grouped by creator, each creator's group a whole number of
  bytes;
- ``forkseen[x, m]``: ``x`` has two events of one ``(m, seq)`` group
  among its ancestors, which is how the oracle detects a fork pair;
- ``x`` sees ``y`` iff ``y`` is an ancestor of ``x`` and not
  ``forkseen[x, c(y)]``: a first-round vote is "sees", not ancestry;
- ``x`` strongly sees ``w`` iff the stake of the members ``m`` with some
  event ``z`` by ``m`` such that ``x`` sees ``z`` and ``z`` sees ``w`` is
  a supermajority.  The events that see ``w`` are one packed row, so the
  per-member "any z" is a byte OR over each creator's group;
- a creator may have several witnesses in a round: promotion counts each
  creator's stake once, and a fame tally counts it for "yes" if any of
  its strongly-seen witnesses votes yes and for "no" likewise;
- the unique famous witnesses of a round are the famous witnesses whose
  creator has exactly one famous witness in it;
- a consensus timestamp takes, per unique famous witness, the earliest
  event on that witness's own self-parent walk with the event as an
  ancestor (a forked creator has no single chain).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Reference:
    round: np.ndarray                 # int32[N]
    is_witness: np.ndarray            # bool[N]
    famous: Dict[int, Optional[bool]]  # witness index -> fame
    round_received: np.ndarray        # int32[N], -1 not received
    consensus_ts: np.ndarray          # int64[N], valid where received
    order: List[int]
    max_round: int

    @property
    def n(self) -> int:
        return len(self.round)


def _tables(hist):
    n, m = hist.n, len(hist.members)
    creator, sp, op = hist.creator, hist.self_parent, hist.other_parent
    seq = np.zeros(n, np.int32)
    chains: List[List[int]] = [[] for _ in range(m)]
    last = np.full((n, m), -1, np.int32)
    for x in range(n):
        c = int(creator[x])
        s = int(sp[x])
        if s >= 0:
            seq[x] = seq[s] + 1
            np.maximum(last[s], last[op[x]], out=last[x])
        last[x, c] = seq[x]
        chains[c].append(x)
    chains_np = [np.asarray(ch, np.int64) for ch in chains]
    first = np.zeros((n, m), np.int32)
    for mm in range(m):
        rows = last[chains_np[mm]]                 # monotone down the chain
        for c in range(m):
            ch = chains_np[c]
            first[ch, mm] = np.searchsorted(
                rows[:, c], np.arange(len(ch)), side="left"
            )
    return seq, chains_np, last, first


def consensus(hist, coin_period: int = 6, num: int = 2,
              den: int = 3) -> Reference:
    """The reference run.  ``num/den`` is the supermajority share: the
    configuration states 2/3; the control passes another.  A history with
    a fork takes :func:`consensus_forked`."""
    if has_forks(hist):
        return consensus_forked(hist, coin_period, num, den)
    n = hist.n
    stake = np.asarray(hist.stake, np.int64)
    tot = int(stake.sum())
    creator = hist.creator
    sig_coin = np.fromiter(
        (s[len(s) // 2] & 1 for s in hist.sigs), np.int8, n
    ).astype(bool)

    def supermajority(a):
        return den * a > num * tot

    seq, chains, last, first = _tables(hist)

    # ---- rounds and witnesses (topological order)
    rnd = np.zeros(n, np.int32)
    wit = np.zeros(n, bool)
    wits: List[List[int]] = []            # round -> witnesses, index order
    wfirst: List[np.ndarray] = []         # round -> first[] of its witnesses
    for x in range(n):
        s = int(hist.self_parent[x])
        if s < 0:
            r = 0
        else:
            r = max(int(rnd[s]), int(rnd[hist.other_parent[x]]))
            if r < len(wits) and wits[r]:
                if len(wfirst[r]) != len(wits[r]):
                    wfirst[r] = first[wits[r]]
                hits = (last[x][None, :] >= wfirst[r]) @ stake
                seen = np.asarray(wits[r])[supermajority(hits)]
                if supermajority(int(stake[creator[seen]].sum())):
                    r += 1
        rnd[x] = r
        if s < 0 or rnd[s] < r:
            wit[x] = True
            while len(wits) <= r:
                wits.append([])
                wfirst.append(np.zeros((0, first.shape[1]), np.int32))
            wits[r].append(x)
    max_round = int(rnd.max()) if n else 0
    wits_np = [np.asarray(w, np.int64) for w in wits]

    def ssm(rows, cols):
        """strongly-sees of witness lists rows x cols, as 0/1 int64."""
        out = np.zeros((len(rows), len(cols)), np.int64)
        fc = first[cols][None, :, :]
        for b in range(0, len(rows), 16):
            hits = (last[rows[b:b + 16]][:, None, :] >= fc) @ stake
            out[b:b + 16] = supermajority(hits)
        return out

    # ---- fame
    famous: Dict[int, Optional[bool]] = {int(w): None for w in np.flatnonzero(wit)}
    ssm_cache: Dict[int, np.ndarray] = {}
    for rx in range(max_round + 1):
        xs = wits_np[rx] if rx < len(wits_np) else np.zeros(0, np.int64)
        if not len(xs) or rx + 1 > max_round:
            continue
        ys = wits_np[rx + 1]
        vote = last[ys][:, creator[xs]] >= seq[xs][None, :]   # direct votes
        undecided = np.ones(len(xs), bool)
        for ry in range(rx + 2, max_round + 1):
            d = ry - rx
            prev, ys = ys, wits_np[ry]
            if ry not in ssm_cache:
                ssm_cache[ry] = ssm(ys, prev)
            weighted = ssm_cache[ry] * stake[creator[prev]][None, :]
            yes = weighted @ vote.astype(np.int64)
            no = weighted @ (~vote).astype(np.int64)
            strong = supermajority(np.maximum(yes, no))
            v = yes >= no
            if d % coin_period:
                for j in np.flatnonzero(undecided & strong.any(0)):
                    y = int(np.argmax(strong[:, j]))        # first voter
                    famous[int(xs[j])] = bool(v[y, j])
                    undecided[j] = False
            else:
                v = np.where(strong, v, sig_coin[ys][:, None])
            vote = v
            if not undecided.any():
                break

    # ---- order
    rr = np.full(n, -1, np.int32)
    cts = np.zeros(n, np.int64)
    order: List[int] = []
    chain_t = [hist.t[ch] for ch in chains]
    by_round = np.argsort(rnd, kind="stable")
    bound = np.searchsorted(rnd[by_round], np.arange(max_round + 2), "right")
    tbd = np.zeros(0, np.int64)
    for r in range(max_round - 1):
        ws = wits_np[r]
        if any(famous[int(w)] is None for w in ws):
            break
        # events of round r join the candidates; an event received at r
        # is an ancestor of a round-r witness, so its round is at most r
        tbd = np.sort(np.concatenate([tbd, by_round[bound[r - 1] if r else 0:bound[r]]]))
        ufw = [int(w) for w in ws if famous[int(w)]]
        if not ufw:
            continue
        ufw = np.asarray(ufw, np.int64)
        got = (last[ufw][:, creator[tbd]] >= seq[tbd][None, :]).all(0)
        rec, tbd = tbd[got], tbd[~got]
        ts = np.stack([
            chain_t[creator[w]][first[rec, creator[w]]] for w in ufw
        ])
        ts.sort(axis=0)
        med = ts[(len(ufw) - 1) // 2]
        rr[rec] = r
        cts[rec] = med
        order.extend(_tiebroken(hist, ufw, rec, med))
    return Reference(rnd, wit, famous, rr, cts, order, max_round)


def _tiebroken(hist, ufw, rec, med) -> List[int]:
    """The events received in one round, in consensus order: by
    timestamp, then BLAKE2b(whiten || id), ``whiten`` the XOR of the
    unique famous witnesses' signatures."""
    acc = 0
    for w in ufw:
        acc ^= int.from_bytes(hist.sigs[w], "big")
    whiten = acc.to_bytes(64, "big")
    keys = [
        (int(med[k]), hashlib.blake2b(whiten + hist.ids[x],
                                      digest_size=32).digest(), int(x))
        for k, x in enumerate(rec)
    ]
    keys.sort()
    return [x for _, _, x in keys]


def has_forks(hist) -> bool:
    """Two events of one creator at one self-chain position: two genesis
    events of one member, or two events with one self-parent."""
    sp = np.asarray(hist.self_parent)
    genesis = np.asarray(hist.creator)[sp < 0]
    kids = sp[sp >= 0]
    return (len(np.unique(genesis)) < len(genesis)
            or len(np.unique(kids)) < len(kids))


class _Visibility:
    """Packed ancestry and ``forkseen`` of a history (module doc)."""

    def __init__(self, hist):
        n, m = hist.n, len(hist.members)
        creator = np.asarray(hist.creator, np.int64)
        sp, op = hist.self_parent, hist.other_parent
        count = np.bincount(creator, minlength=m)
        group = np.maximum(1, (count + 7) // 8)          # bytes per creator
        self.group_start = np.concatenate(([0], np.cumsum(group)[:-1]))
        rank = np.empty(n, np.int64)                     # within its creator
        rank[np.argsort(creator, kind="stable")] = np.arange(n) - np.repeat(
            np.cumsum(count) - count, count)
        self.col = 8 * self.group_start[creator] + rank
        self.byte = self.col >> 3
        self.bit = np.left_shift(1, self.col & 7).astype(np.uint8)
        self.width = 8 * int(group.sum())
        self.anc = anc = np.zeros((n, int(group.sum())), np.uint8)
        seq = np.zeros(n, np.int64)
        for x in range(n):
            s = sp[x]
            if s >= 0:
                np.bitwise_or(anc[s], anc[op[x]], out=anc[x])
                seq[x] = seq[s] + 1
            anc[x, self.byte[x]] |= self.bit[x]
        self.forkseen = np.zeros((n, m), bool)
        key = creator * (n + 1) + seq
        by_key = np.argsort(key, kind="stable")
        k = key[by_key]
        starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
        ends = np.r_[starts[1:], n]
        for a, b in zip(starts, ends):
            if b - a > 1:
                evs = by_key[a:b]
                self.forkseen[:, creator[evs[0]]] |= (
                    self.has(slice(None), evs).sum(1) >= 2)

    def has(self, rows, cols) -> np.ndarray:
        """``anc[rows, cols]`` as bools: is each ``col`` an ancestor of
        each ``row``."""
        return (self.anc[rows][:, self.byte[cols]] & self.bit[cols]) != 0

    def seen_by(self, w: int, cw: int) -> np.ndarray:
        """The packed row of the events that see ``w`` (creator ``cw``)."""
        bits = np.zeros(self.width, bool)
        bits[self.col] = self.has(slice(None), [w])[:, 0] \
            & ~self.forkseen[:, cw]
        return np.packbits(bits, bitorder="little")

    def strongly(self, xs, seen, stake, supermajority) -> np.ndarray:
        """``bool[len(xs), len(seen)]``: each ``x`` strongly sees the
        event whose :meth:`seen_by` row is ``seen[j]``."""
        out = np.zeros((len(xs), len(seen)), bool)
        for b in range(0, len(xs), 16):
            rows = xs[b:b + 16]
            hit = self.anc[rows][:, None, :] & seen[None, :, :]
            by_member = np.bitwise_or.reduceat(
                hit, self.group_start, axis=2) != 0
            by_member &= ~self.forkseen[rows][:, None, :]
            out[b:b + 16] = supermajority(by_member @ stake)
        return out


def consensus_forked(hist, coin_period: int = 6, num: int = 2,
                     den: int = 3) -> Reference:
    """The reference run on the fork-aware path (module doc); it gives
    the honest path's answers on a history without forks."""
    n = hist.n
    stake = np.asarray(hist.stake, np.int64)
    tot = int(stake.sum())
    creator = np.asarray(hist.creator, np.int64)
    sp = hist.self_parent
    sig_coin = np.fromiter(
        (s[len(s) // 2] & 1 for s in hist.sigs), np.int8, n
    ).astype(bool)

    def supermajority(a):
        return den * a > num * tot

    vis = _Visibility(hist)

    # ---- rounds and witnesses (topological order)
    rnd = np.zeros(n, np.int32)
    wit = np.zeros(n, bool)
    wits: List[List[int]] = []            # round -> witnesses, index order
    seen: List[List[np.ndarray]] = []     # round -> seen_by rows
    stacked: Dict[int, np.ndarray] = {}
    for x in range(n):
        s = int(sp[x])
        if s < 0:
            r = 0
        else:
            r = max(int(rnd[s]), int(rnd[hist.other_parent[x]]))
            if r < len(wits) and wits[r]:
                if len(stacked.get(r, ())) != len(wits[r]):
                    stacked[r] = np.stack(seen[r])
                hit = vis.strongly([x], stacked[r], stake, supermajority)[0]
                cs = np.unique(creator[np.asarray(wits[r])[hit]])
                if supermajority(int(stake[cs].sum())):
                    r += 1
        rnd[x] = r
        if s < 0 or rnd[s] < r:
            wit[x] = True
            while len(wits) <= r:
                wits.append([])
                seen.append([])
            wits[r].append(x)
            seen[r].append(vis.seen_by(x, creator[x]))
    max_round = int(rnd.max()) if n else 0
    wits_np = [np.asarray(w, np.int64) for w in wits]

    # ---- fame
    famous: Dict[int, Optional[bool]] = {
        int(w): None for w in np.flatnonzero(wit)}
    ssm_cache: Dict[int, np.ndarray] = {}
    for rx in range(max_round + 1):
        xs = wits_np[rx] if rx < len(wits_np) else np.zeros(0, np.int64)
        if not len(xs) or rx + 1 > max_round:
            continue
        ys = wits_np[rx + 1]
        # first-round votes: y sees x
        vote = vis.has(ys, xs) & ~vis.forkseen[ys][:, creator[xs]]
        undecided = np.ones(len(xs), bool)
        for ry in range(rx + 2, max_round + 1):
            d = ry - rx
            prev, ys = ys, wits_np[ry]
            if ry not in ssm_cache:
                ssm_cache[ry] = vis.strongly(
                    ys, np.stack(seen[ry - 1]), stake, supermajority)
            # per creator of prev: any strongly-seen witness voting yes / no
            by_c = np.argsort(creator[prev], kind="stable")
            cs = creator[prev][by_c]
            first = np.flatnonzero(np.r_[True, cs[1:] != cs[:-1]])
            strong_seen = ssm_cache[ry][:, by_c, None]
            yes = np.logical_or.reduceat(
                strong_seen & vote[by_c][None], first, axis=1)
            no = np.logical_or.reduceat(
                strong_seen & ~vote[by_c][None], first, axis=1)
            w_c = stake[cs[first]][None, :, None]
            yes, no = (yes * w_c).sum(1), (no * w_c).sum(1)
            strong = supermajority(np.maximum(yes, no))
            v = yes >= no
            if d % coin_period:
                for j in np.flatnonzero(undecided & strong.any(0)):
                    y = int(np.argmax(strong[:, j]))        # first voter
                    famous[int(xs[j])] = bool(v[y, j])
                    undecided[j] = False
            else:
                v = np.where(strong, v, sig_coin[ys][:, None])
            vote = v
            if not undecided.any():
                break

    # ---- order
    rr = np.full(n, -1, np.int32)
    cts = np.zeros(n, np.int64)
    order: List[int] = []
    by_round = np.argsort(rnd, kind="stable")
    bound = np.searchsorted(rnd[by_round], np.arange(max_round + 2), "right")
    tbd = np.zeros(0, np.int64)
    for r in range(max_round - 1):
        ws = wits_np[r]
        if any(famous[int(w)] is None for w in ws):
            break
        tbd = np.sort(np.concatenate(
            [tbd, by_round[bound[r - 1] if r else 0:bound[r]]]))
        fam = [int(w) for w in ws if famous[int(w)]]
        per_creator = np.bincount(creator[fam], minlength=len(stake))
        ufw = np.asarray([w for w in fam if per_creator[creator[w]] == 1],
                         np.int64)
        if not len(ufw):
            continue
        got = vis.has(ufw, tbd).all(0)
        rec, tbd = tbd[got], tbd[~got]
        ts = []
        for w in ufw:
            walk = [int(w)]
            while sp[walk[-1]] >= 0:
                walk.append(int(sp[walk[-1]]))
            walk = np.asarray(walk[::-1], np.int64)       # genesis first
            ts.append(hist.t[walk[vis.has(walk, rec).argmax(0)]])
        ts = np.stack(ts)
        ts.sort(axis=0)
        med = ts[(len(ufw) - 1) // 2]
        rr[rec] = r
        cts[rec] = med
        order.extend(_tiebroken(hist, ufw, rec, med))
    return Reference(rnd, wit, famous, rr, cts, order, max_round)
