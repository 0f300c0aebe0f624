"""Rounds-scan dispatches (probes) per accepted chunk or fused span,
summed over the engine calls of the traced window: a failed probe
re-runs its whole span once the missing witness columns are added.  The
program's own counters, read from each call's record with no alignment
to the trace (benchmark/trace/program.py ``counted``)."""

from benchmark.trace import program


def read(ctx):
    return program.probes_per_unit(program.counted(ctx))
