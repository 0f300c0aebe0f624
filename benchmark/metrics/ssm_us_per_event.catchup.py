"""Device microseconds of the strongly-sees programs per event ordered in
the steady part of the traced window (benchmark/trace/reduce.py).  The
programs are named in ssm_programs.json."""

import os

from benchmark.trace import reduce

PROGRAMS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ssm_programs.json")


def read(ctx):
    if ctx.trace is None:
        return None
    ordered = reduce.ordered_in_window(ctx.trace, ctx.counters.get("calls", []))
    seconds = reduce.matching_seconds(ctx.trace, PROGRAMS)
    return 1e6 * seconds / ordered if ordered and seconds > 0 else None
