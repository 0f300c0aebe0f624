"""Share of the witness slots per round that the rounds scan carried and
filled: 100 x the sum of ``witness_slots_used`` (the most witnesses in
any round of a rounds phase) over the sum of ``rounds_slots`` (the slots
per round that phase carried), over the engine calls of the traced
window.  The program's own counters, read from each call's record with
no alignment to the trace (benchmark/trace/program.py ``counted``); None
where no record carries them.  Logs each call's ``fork_pairs``,
``rounds_slots`` and ``witness_slots_used``."""

import json
import sys

from benchmark.trace import program

KEYS = ("fork_pairs", "rounds_slots", "witness_slots_used")


def read(ctx):
    records = [r for r in program.counted(ctx) if "rounds_slots" in r]
    log = {"per_call": KEYS, "calls": [[r[k] for k in KEYS] for r in records]}
    print(f"[slots] {json.dumps(log)}", file=sys.stderr, flush=True)
    slots = sum(r["rounds_slots"] for r in records)
    if not slots:
        return None
    return 100.0 * sum(r["witness_slots_used"] for r in records) / slots
