"""Device idle share of the catch-up window, in percent (profiler trace)."""

from benchmark.trace import reduce


def read(ctx):
    share = None if ctx.trace is None else reduce.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
