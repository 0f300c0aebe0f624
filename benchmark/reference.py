"""Plain reference consensus: rounds, witnesses, fame, round received,
consensus timestamps and total order of an honest history.

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
            if chains[c][-1] != s:
                raise ValueError(f"event {x} forks member {c}'s chain")
            seq[x] = seq[s] + 1
            np.maximum(last[s], last[op[x]], out=last[x])
        elif chains[c]:
            raise ValueError(f"member {c} has two genesis events")
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
    configuration states 2/3; the control passes another."""
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
        rr[rec] = r
        cts[rec] = med
        order.extend(x for _, _, x in keys)
    return Reference(rnd, wit, famous, rr, cts, order, max_round)
