"""Test-only per-layer metric: events ordered in the window."""


def read(ctx):
    return ctx.counters.get("ordered") or None
