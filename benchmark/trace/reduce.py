"""The reduction from a profiler trace to the benchmark's numbers.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler.trace`` writes
into a small plain form (:class:`Trace`): per device, the intervals in
which it executed an XLA program (the ``XLA Modules`` line: one event per
program run, inside which the chip runs the program's ops back to back),
and the host spans that the benchmark itself wrote with
``TraceAnnotation``.  The per-op line is not read: it holds hundreds of
thousands of events per second of window.  Everything after that is
arithmetic on intervals, tested on a small committed trace.

The window read is the steady part of the ``window`` span: it opens with
the first ``ingest`` call that starts once the device trace shows a
program, and closes with the span, so every number below leaves out a
stretch before the first traced program.  Stretches that the trace loses
later are not left out; :func:`coverage` counts the calls in which no
program shows (PERF.md, section 3).

- busy time: the union of a device's program intervals inside the window;
- idle share: 1 - busy / window, averaged over the devices used;
- per-program seconds: module durations inside the window, by name;
- idle gaps: the stretches of the window with no op running, each named
  by the innermost benchmark span it fell in.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]        # start s, end s, name

#: the benchmark's own host spans (benchmark/drivers.py and run.py)
SPANS = ("window", "generate", "pack", "ingest", "result", "replay_start",
         "wait")


@dataclasses.dataclass
class Trace:
    modules: List[List[Interval]]          # per device: program runs
    host: List[Interval]                   # benchmark spans

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls([[tuple(x) for x in m] for m in d["modules"]],
                   [tuple(x) for x in d["host"]])

    def opened(self) -> Tuple[float, float]:
        """The ``window`` span as the host wrote it."""
        spans = [(s, e) for s, e, n in self.host if n == "window"]
        if not spans:
            raise ValueError("the trace holds no window span")
        return spans[0]

    def calls(self) -> List[float]:
        """Start of every ``ingest`` call inside the ``window`` span."""
        lo, hi = self.opened()
        return [s for s, e, n in self.host if n == "ingest" and lo <= s < hi]

    def first_call(self) -> Optional[int]:
        """Index of the first call that starts once every traced device
        has shown a program in the window; None where none does."""
        lo, hi = self.opened()
        firsts = [min((s for s, e, _ in m if e > lo), default=hi)
                  for m in self.modules]
        start = max(firsts, default=hi)
        return next((k for k, s in enumerate(self.calls()) if s >= start),
                    None)

    def window(self) -> Tuple[float, float]:
        """The steady part of the window (module doc)."""
        lo, hi = self.opened()
        k = self.first_call()
        return (hi, hi) if k is None else (self.calls()[k], hi)


def load(directory: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``directory``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(paths[-1])
    modules, host = [], []
    for plane in data.planes:
        if re.match(r"/device:TPU:\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            modules.append(_events(lines.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [iv for iv in _events(ln) if iv[2] in SPANS]
    host.sort()
    return Trace(modules, host)


def _events(line) -> List[Interval]:
    if line is None:
        return []
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events]


def union(intervals: Sequence[Interval], lo: float, hi: float):
    """Merged ``[start, end)`` pairs of the intervals, clipped to
    ``[lo, hi)``."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: Trace) -> float:
    """Seconds in which a program ran, averaged over the devices traced."""
    lo, hi = trace.window()
    if not trace.modules:
        return 0.0
    return sum(
        sum(e - s for s, e in union(m, lo, hi)) for m in trace.modules
    ) / len(trace.modules)


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window; None where the trace holds no device or no
    steady window."""
    lo, hi = trace.window()
    if not trace.modules or hi <= lo:
        return None
    return 1.0 - busy_seconds(trace) / (hi - lo)


def ordered_in_window(trace: Trace, per_call: Sequence[int]):
    """Events ordered by the calls of the steady window, from the count of
    each call of the whole window in call order; None where the calls
    traced are not the calls counted."""
    k = trace.first_call()
    if k is None or len(per_call) != len(trace.calls()):
        return None
    return sum(per_call[k:])


def coverage(trace: Trace) -> Dict:
    """For the log: how much of the ``window`` span was left out, the
    calls of the steady window in which device 0 shows no program (a
    trace that drops programs mid-window), and the programs traced after
    the span closed (a clock offset)."""
    lo0, hi = trace.opened()
    lo, _ = trace.window()
    spans = [(s, e) for s, e, n in trace.host
             if n == "ingest" and lo <= s < hi]
    mods = trace.modules[0] if trace.modules else []
    busy = union(mods, lo, hi)
    ends = [e for _, e in busy]

    def shows_program(s, e):
        k = bisect.bisect_right(ends, s)     # first busy stretch ending > s
        return k < len(busy) and busy[k][0] < e

    return {
        "left_out_s": lo - lo0,
        "calls": len(spans),
        "calls_without_program": sum(
            not shows_program(s, e) for s, e in spans),
        "programs_after_close": sum(ms >= hi for ms, _, _ in mods),
    }


def program_name(module: str) -> str:
    """``jit_ssm_block_stage(123)`` -> ``jit_ssm_block_stage``."""
    return re.sub(r"\(\d+\)$", "", module)


def program_seconds(trace: Trace) -> Dict[str, float]:
    """Device seconds of each program inside the window, over devices."""
    lo, hi = trace.window()
    out: Dict[str, float] = {}
    for mods in trace.modules:
        for s, e, name in mods:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = program_name(name)
                out[key] = out.get(key, 0.0) + d
    return out


def idle_gaps(trace: Trace, device: int = 0) -> List[Tuple[str, float]]:
    """Every stretch of the window with no program on ``device``, longest
    first, named by the latest-starting benchmark span that covers its
    middle (``idle`` where none does)."""
    lo, hi = trace.window()
    busy = union(trace.modules[device], lo, hi) if trace.modules else []
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < hi:
        gaps.append((t, hi))
    named = []
    inner = sorted(iv for iv in trace.host if iv[2] != "window")
    starts = [iv[0] for iv in inner]
    for s, e in gaps:
        mid = (s + e) / 2
        k = bisect.bisect_right(starts, mid) - 1
        name = inner[k][2] if k >= 0 and inner[k][1] > mid else "idle"
        named.append((name, e - s))
    named.sort(key=lambda g: -g[1])
    return named


def breakdown(trace: Trace, top: int = 10) -> Dict:
    progs = sorted(program_seconds(trace).items(), key=lambda kv: -kv[1])
    return {
        "device_ops": [[n, s] for n, s in progs[:top]],
        "idle_gaps": [[n, s] for n, s in idle_gaps(trace)[:top]],
    }


def matching_seconds(trace: Trace, data_file: str) -> float:
    """Device seconds of the programs a data file of names lists."""
    with open(data_file) as f:
        names = set(json.load(f)["programs"])
    return sum(s for p, s in program_seconds(trace).items() if p in names)
