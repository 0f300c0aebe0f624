"""Rounds-scan dispatches (probes) per accepted chunk or fused span,
summed over the engine calls of the traced window: a failed probe
re-runs its whole span once the missing witness columns are added.  The
program's own counters (benchmark/trace/program.py)."""

from benchmark.trace import program


def read(ctx):
    prog = program.read(ctx)
    return None if prog is None else program.probes_per_unit(prog)
