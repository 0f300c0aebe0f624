"""Median host wall time of one ingest call in the live window, in ms.
The benchmark's own span around the call ends when the order is on the
host."""

import statistics


def read(ctx):
    passes = ctx.counters.get("passes")
    return statistics.median(passes) if passes else None
