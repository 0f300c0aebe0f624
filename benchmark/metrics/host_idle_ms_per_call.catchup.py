"""Device-idle milliseconds per ingest call of the steady window in
which the innermost program span is a host phase (any ``swirld.*`` span
but a wait).  The program's own spans, aligned to the device trace by
benchmark/trace/program.py."""

from benchmark.trace import program


def read(ctx):
    prog = program.read(ctx)
    if prog is None:
        return None
    lo, hi = ctx.trace.window()
    calls = [s for s in ctx.trace.calls() if lo <= s < hi]
    idle = program.idle_by_span(prog, ctx.trace)
    host = sum(v for k, v in idle.items() if program.is_host_phase(k))
    return 1e3 * host / len(calls) if calls else None
