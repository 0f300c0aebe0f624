"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In order: check that JAX holds a TPU with the chips the cell asks for
(otherwise exit non-zero with no result); place JAX's persistent compile
cache in the checkout and have it keep every program; generate the cell's
data from the seed; warm up the cell's own shapes; measure for
``--seconds``; read the device's peak memory; free the program's state;
compare what the window produced with the plain reference; print detail
on standard error, the compared numbers with their limits last, and one
JSON result line as the last line of standard output.

With ``--trace 1`` the window runs under the JAX profiler and the result
carries the cell's per-layer metrics, the device's busy and window
seconds and a breakdown; otherwise it carries the end-to-end metrics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import drivers, reference, spec  # noqa: E402
from benchmark.clock import CompileClock  # noqa: E402


def log(*parts):
    print(*parts, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(jax, chips: int):
    """The devices the cell runs on; exits when they are not TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"benchmark: JAX holds {len(devices)} {devices[0].platform} "
            f"device(s); the cell needs {chips} TPU chip(s) - no result"
        )
    return devices[:chips]


def place_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), keeping every program however
    short its compile, so that only a cell's first run compiles."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader reads."""
    trace: Optional[object]
    counters: Dict


def main(argv=None, *, root: str = spec.ROOT, devices_fn=None,
         program_fn=drivers.Program) -> int:
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    import jax

    devices = (devices_fn or functools.partial(require_chips, jax))(cell.chips)
    log(f"[env] devices {devices} compile cache {place_compile_cache(jax)}")
    clock = CompileClock().install()
    span = drivers.annotate(bool(args.trace))
    drv = drivers.driver(cell, args.seed, program_fn(cell.config), span,
                         args.seconds)
    drv.warm()
    if getattr(drv, "prerun_ends", None):
        # the pre-run call during which the last program was lowered
        last = bisect.bisect_left(drv.prerun_ends,
                                  max(clock.lowered_at, default=0.0))
        after = sum(t > drv.prerun_ends[-1] for t in clock.lowered_at)
        log(f"[warm] pre-run of {len(drv.prerun_ends)} calls: last lowering "
            f"in call {last}; {after} lowerings after it")
    setup_compile = clock.snapshot()
    setup_s = time.perf_counter() - T_START
    log(f"[setup] setup_s={setup_s} compile_s={setup_compile[0]} "
        f"lowerings={setup_compile[1]} "
        f"backend_compiles={setup_compile[2]} "
        f"cache_misses={setup_compile[3]}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    profiler = contextlib.nullcontext()
    if trace_dir:
        # device programs and the benchmark's spans; no Python tracer
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        profiler = jax.profiler.trace(trace_dir, profiler_options=opts)
    with profiler:
        with span("window"):
            e2e = drv.measure(args.seconds)
    window = [b - a for a, b in zip(setup_compile, clock.snapshot())]
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    log(f"[window] {json.dumps(dict(e2e, **_summary(drv.counters)))}")
    log(f"[window] compiles inside: lowerings={window[1]} "
        f"backend_compiles={window[2]} cache_misses={window[3]} "
        f"compile_s={window[0]} "
        f"programs={clock.lowered[setup_compile[1]:]}")
    log(f"[device] peak_bytes_in_use={peak}")

    drv.release()
    t_check = time.perf_counter()
    checks = drv.checks(functools.partial(
        reference.consensus, coin_period=int(cell.config["coin_period"])))
    log(f"[check] reference and comparison took "
        f"{time.perf_counter() - t_check} s")
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": int(peak),
    }
    out: Dict = {
        "correct": not any(checks.values()),
        "attempted": int(drv.attempted), "failed": int(drv.failed),
    }
    if trace_dir:
        from benchmark.trace import reduce

        t_load = time.perf_counter()
        trace = reduce.load(trace_dir)
        log(f"[trace] read in {time.perf_counter() - t_load} s, "
            f"{_disk_bytes(trace_dir)} bytes; "
            f"{json.dumps(reduce.coverage(trace))}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        counters = dict(drv.counters, setup_compile_s=setup_compile[0])
        ctx = Context(trace, counters)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = trace.window()
        device.update(busy_s=reduce.busy_seconds(trace), window_s=hi - lo)
        out.update(metrics=metrics, device=device,
                   breakdown=reduce.breakdown(trace))
    else:
        values = dict(e2e, setup_s=setup_s)
        out.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }, device=device)
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} {v} limit 0")
    print(json.dumps(out), flush=True)
    return 0


def _disk_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(path) for f in fs)


def _summary(counters: Dict) -> Dict:
    """Counters for the log: a list as its count, median and maximum."""
    out = {}
    for k, v in counters.items():
        if isinstance(v, list):
            s = sorted(v)
            v = {"n": len(s), "p50": s[len(s) // 2] if s else None,
                 "max": s[-1] if s else None}
        out[k] = v
    return out


if __name__ == "__main__":
    sys.exit(main())
