"""Host-side arithmetic of the benchmark: JAX's compile clock and
percentiles.

Copies, not imports, of the program's sound pieces, so that no program
change moves the yardstick: the compile clock is ``chip_smoke.py``'s
``_CompileClock`` (seconds from JAX's own monitoring events), and
:func:`percentile` is the nearest-rank percentile of
``tpu_swirld/obs/finality.py``.
"""

from __future__ import annotations

import math
import time
from typing import Sequence

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileClock:
    """Seconds JAX spent lowering and compiling, and how many times it
    lowered, compiled and missed the persistent cache.  Tracing is left
    out: nested jits trace inside their caller, so its events overlap."""

    def __init__(self):
        self.seconds = 0.0
        self.lowerings = 0
        self.backend_compiles = 0
        self.cache_misses = 0
        self.lowered: list = []          # names of the programs lowered
        self.lowered_at: list = []       # host time of each lowering

    def on_duration(self, event, duration, **kw):
        if event == LOWERING:
            self.seconds += duration
            self.lowerings += 1
            self.lowered.append(kw.get("fun_name", "?"))
            self.lowered_at.append(time.perf_counter())
        elif event == BACKEND_COMPILE:
            self.seconds += duration
            self.backend_compiles += 1

    def on_event(self, event, **_kw):
        if event == CACHE_MISS:
            self.cache_misses += 1

    def install(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        jax.monitoring.register_event_listener(self.on_event)
        return self

    def snapshot(self):
        return (self.seconds, self.lowerings, self.backend_compiles,
                self.cache_misses)


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in (0, 1]) of the samples."""
    s = sorted(samples)
    if not s:
        raise ValueError("no samples")
    rank = max(1, min(len(s), math.ceil(q * len(s))))
    return s[rank - 1]
