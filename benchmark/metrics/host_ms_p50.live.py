"""Median host milliseconds of a live ingest call: the call's
``swirld.stream_ingest`` span less the union of the device waits
(``swirld.wait{on=device}``) inside it, over the calls that
``pass_ms_p50.live`` times (the window's, before the drain's).  The
program's own spans, aligned by benchmark/trace/program.py."""

import statistics

from benchmark.trace import program


def read(ctx):
    prog = program.read(ctx)
    n = len(ctx.counters.get("passes") or [])
    if prog is None or not n or len(prog.calls) < n:
        return None
    return 1e3 * statistics.median(
        program.host_seconds(prog, c) for c in prog.calls[:n])
