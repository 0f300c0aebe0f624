"""The control of the comparison that decides ``correct``.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3 [--seconds S]

The control is the plain reference put in the program's place with one
guarantee of the configuration broken: fame, rounds and strongly-sees
decided by a simple stake majority (more than 1/2) instead of the strict
2/3 supermajority.  For each seed it prints the numbers the benchmark
compares, for the control against the true reference, on the history
that a run of the cell with that seed generates (for an open-loop cell,
the whole stream of a run of ``--seconds``).  Every seed has to fail at least one of
them.  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, drivers, gossip, reference, spec  # noqa: E402


def control_readings(cell, seed: int, seconds: float):
    """The compared numbers, control against reference, on the history a
    run of ``seconds`` with this seed generates."""
    cfg, mix = cell.config, cell.traffic
    n = int(cfg["history_events"])
    if mix["driver"] == "open_loop":
        n = drivers.stream_syncs(mix, seconds) * int(mix["sync_events"])
    hist = gossip.from_config(cfg, n, seed, int(mix["dag_seed"]))
    period = int(cfg["coin_period"])
    ref = reference.consensus(hist, period)
    ctl = reference.consensus(hist, period, num=1, den=2)
    bad = compare.mismatches(ctl, ref, hist.n)
    bad["emitted_order"] = compare.prefix_mismatches(ctl.order, ref) + abs(
        len(ctl.order) - len(ref.order))
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    failed_all = True
    for seed in args.seeds:
        t = time.perf_counter()
        bad = control_readings(cell, seed, args.seconds)
        failed_all &= any(bad.values())
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": bad,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
