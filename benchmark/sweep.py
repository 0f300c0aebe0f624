"""The sweep that found the live mix's knee (run once, on the chip).

    python3 benchmark/sweep.py --workload council64.live --seed 1 \
        --seconds 15 --rates 16 18 20 22 24 26

One pre-run over a stream long enough for the highest rate compiles
every shape, as a run's warm-up does; then, for each offered rate (syncs
per second, lowest first), a fresh engine ingests the warm-up part and
runs one open-loop segment of ``--seconds`` on the same stream position
as a run's window.  Each segment prints the offered and taken rates and
the backlog at its close: every sync due by then and not yet taken.  A
rate is sustained when that backlog is under one second of arrivals; the
knee is the highest rate sustained with every lower rate sustained too,
and the live mix offers 80 % of it.  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import drivers, spec  # noqa: E402
from benchmark.clock import CompileClock, percentile  # noqa: E402
from benchmark.run import place_compile_cache, require_chips  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    import jax

    require_chips(jax, cell.chips)
    place_compile_cache(jax)
    clock = CompileClock().install()
    cell.traffic = dict(cell.traffic, rate_syncs_per_s=max(args.rates))
    drv = drivers.OpenLoopDriver(
        cell, args.seed, drivers.Program(cell.config),
        drivers.annotate(False), args.seconds)
    drv.warm()
    drv.inc.store.close()
    knee, below = None, True
    for rate in sorted(args.rates):
        drv.restart()
        c0 = clock.snapshot()
        rec = drv.run_schedule(rate, args.seconds, 0.0)
        taken = len(rec["passes"])           # calls begun before the close
        backlog_end = rec["offered"] - taken
        sustained = backlog_end < rate
        below &= sustained
        knee = rate if below else knee
        lat = [1e3 * x for x in rec["sync_latency"] if x == x]
        print(json.dumps({
            "rate_syncs_per_s": rate,
            "offered_syncs": rec["offered"], "taken_syncs": taken,
            "backlog_end": backlog_end, "sustained": sustained,
            "sync_p50_ms": percentile(lat, 0.5) if lat else None,
            "sync_p95_ms": percentile(lat, 0.95) if lat else None,
            "pass_p50_ms": 1e3 * float(np.median(rec["passes"])),
            "pass_max_ms": 1e3 * max(rec["passes"]),
            "lowerings": clock.snapshot()[1] - c0[1],
        }), flush=True)
        drv.inc.store.close()
    print(json.dumps({"knee_syncs_per_s": knee,
                      "rate_80pct": None if knee is None else 0.8 * knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
