"""Seconds of lowering and compiling during set-up, from JAX's own
monitoring events (benchmark/clock.py)."""


def read(ctx):
    return ctx.counters.get("setup_compile_s")
