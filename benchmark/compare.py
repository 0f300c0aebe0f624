"""The comparison that decides ``correct``: every stage's output, as the
timed path produced it, against the plain reference over the same events.

Each number is a count of disagreeing entries and its limit is 0: the
configuration states bit-identical rounds, witnesses, fame, round
received, consensus timestamps and order.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

FIELDS = ("round", "witness", "fame", "received", "timestamp", "order")


def mismatches(res, ref, n: int) -> Dict[str, int]:
    """Disagreements of an engine result over the first ``n`` events with
    the reference over the same ``n`` events; an event the result lacks
    disagrees in round, witness and round received."""
    out = {}
    k = min(int(res.n), n)
    out["round"] = int(np.count_nonzero(
        np.asarray(res.round[:k]) != ref.round[:k])) + (n - k)
    out["witness"] = int(np.count_nonzero(
        np.asarray(res.is_witness[:k], bool) != ref.is_witness[:k])) + (n - k)
    keys = set(res.famous) | set(ref.famous)
    out["fame"] = sum(
        res.famous.get(w, "absent") != ref.famous.get(w, "absent")
        for w in keys
    )
    rr = np.asarray(res.round_received[:k])
    out["received"] = int(np.count_nonzero(rr != ref.round_received[:k])) \
        + (n - k)
    got = ref.round_received[:k] >= 0
    out["timestamp"] = int(np.count_nonzero(
        np.asarray(res.consensus_ts[:k])[got] != ref.consensus_ts[:k][got]))
    a, b = list(res.order), list(ref.order)
    out["order"] = sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))
    return out


def prefix_mismatches(order, ref) -> int:
    """Disagreements of an order emitted pass by pass, which must be a
    prefix of the reference's order (consensus order is final once
    emitted)."""
    return sum(x != y for x, y in zip(order, ref.order)) + max(
        0, len(order) - len(ref.order))
