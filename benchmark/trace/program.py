"""The program's own spans and counters, aligned to the device trace.

While a JAX profiler session runs, the program records its engine phases
(``swirld.*`` spans, each engine call's counters in its ``swirld.pass`` /
``swirld.batch`` record) in a process-wide recorder,
``tpu_swirld.obs.profile_recorder()``.  :func:`read` takes that recorder
after a traced window and puts its spans on the trace's clock (seconds
since the profile started): the k-th outermost program span of the window
(``swirld.stream_ingest``, or ``swirld.batch`` on the batch path) is
paired with the k-th benchmark ``ingest`` span, and the program's clock is
shifted by the median difference of their ends.  The ends, not the
starts: the batch call packs its events before ``run_consensus`` starts,
and the benchmark's span closes as the engine call returns.

It reads nothing (None) where the program keeps no such recorder (a
checkout from before it), where the counts differ, where the ends spread
by more than :data:`MAX_SPREAD_S`, or where the recorder dropped events.
Every call logs one ``[program]`` line on standard error: why the spans
could not be aligned (``unaligned``: ``dropped``, ``count`` or
``spread``), or else the offset and its spread, the device-idle seconds
of the steady window split by the innermost program span (``outside``
where none is open) against ``window_s - busy_s``, and, over the calls of
the steady window, the ``*_stage`` programs the program dispatched
against the ``*_stage`` modules the trace holds.

The counters need none of that: :func:`counted` reads each engine call's
record as it is, with no pairing and no complete recorder, since each
record holds its own call's counts.  The recorder is fresh per profiler
session, and the session wraps the window only.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from benchmark.trace import reduce

#: the outermost span of one engine call, by path
OUTER = ("swirld.stream_ingest", "swirld.batch")
#: widest spread of the paired end differences that still aligns
MAX_SPREAD_S = 1e-3
#: the key the idle split gives device-idle time no program span covers
OUTSIDE = "outside"


@dataclasses.dataclass
class Span:
    start: float                    # seconds, on the trace's clock
    end: float
    name: str
    depth: int
    args: Dict

    @property
    def label(self) -> str:
        """The span's name, with the wait's cause where it has one."""
        on = self.args.get("on")
        return f"{self.name}{{on={on}}}" if on else self.name


@dataclasses.dataclass
class Program:
    spans: List[Span]               # every span, in start order
    calls: List[Span]               # the outermost span of each call
    offset_s: float                 # trace clock - program clock
    spread_s: float                 # max - min of the paired differences
    log: Dict                       # the [program] line
    starts: List[float] = dataclasses.field(init=False, repr=False)

    def __post_init__(self):
        self.starts = [s.start for s in self.spans]

    def inside(self, call: Span) -> List[Span]:
        """The spans that start and end within ``call``."""
        a = bisect.bisect_left(self.starts, call.start)
        b = bisect.bisect_right(self.starts, call.end)
        return [s for s in self.spans[a:b] if s.end <= call.end]


def recorder():
    """The program's profiler-gated recorder, or None where it has none."""
    try:
        from tpu_swirld import obs
    except ImportError:
        return None
    get = getattr(obs, "profile_recorder", None)
    return get() if get is not None else None


def spans_of(events: Sequence[Dict], offset_s: float = 0.0) -> List[Span]:
    """The complete (``ph: X``) events of a recorder as spans, shifted
    from the recorder's clock (microseconds since its epoch) by
    ``offset_s``."""
    out = [
        Span(offset_s + 1e-6 * e["ts"],
             offset_s + 1e-6 * (e["ts"] + e["dur"]),
             e["name"], int(e["args"].get("depth", 0)), e["args"])
        for e in events if e.get("ph") == "X"
    ]
    out.sort(key=lambda s: (s.start, s.depth))
    return out


def ingest_spans(trace) -> List[Tuple[float, float]]:
    """The benchmark's ``ingest`` spans inside its ``window`` span."""
    lo, hi = trace.opened()
    return [(s, e) for s, e, n in trace.host
            if n == "ingest" and lo <= s < hi]


def read(ctx, rec=None) -> Optional[Program]:
    """The aligned program spans of a traced run (module doc), computed
    once per run; logs the ``[program]`` line on every call."""
    prog = getattr(ctx, "_program", None)
    if prog is None:
        prog = _align(ctx.trace, recorder() if rec is None else rec)
        ctx._program = prog
    print(f"[program] {json.dumps(prog.log)}", file=sys.stderr, flush=True)
    return prog if prog.calls else None


def counted(ctx, rec=None) -> List[Dict]:
    """The counters of every engine call the recorder holds: the args of
    each ``swirld.pass`` / ``swirld.batch`` record (they do not nest),
    whether or not the spans align.  Logs the ``[program]`` line too."""
    rec = recorder() if rec is None else rec
    read(ctx, rec)
    if rec is None:
        return []
    return [e["args"] for e in rec.events
            if e.get("ph") == "X" and "rounds_probes" in e["args"]]


def _align(trace, rec) -> Program:
    empty = Program([], [], 0.0, 0.0, {})
    if rec is None or trace is None:
        empty.log = {"recorder": None}
        return empty
    spans = spans_of(rec.events)
    calls = [s for s in spans if s.name in OUTER and s.depth == 0]
    ingest = ingest_spans(trace)
    empty.log = {"spans": len(spans), "dropped": int(rec.dropped),
                 "calls": len(calls), "ingest_calls": len(ingest)}
    if rec.dropped or not calls or len(calls) != len(ingest):
        empty.log["unaligned"] = "dropped" if rec.dropped else "count"
        return empty
    diffs = [e - c.end for (_, e), c in zip(ingest, calls)]
    offset = statistics.median(diffs)
    spread = max(diffs) - min(diffs)
    empty.log.update(offset_s=offset, spread_ms=1e3 * spread)
    if spread > MAX_SPREAD_S:
        empty.log["unaligned"] = "spread"
        return empty
    for s in spans:
        s.start += offset
        s.end += offset
    calls = [s for s in spans if s.name in OUTER and s.depth == 0]
    prog = Program(spans, calls, offset, spread, dict(empty.log))
    lo, hi = trace.window()
    idle = idle_by_span(prog, trace)
    busy = sum(e - s for s, e in reduce.union(
        trace.modules[0], lo, hi)) if trace.modules else 0.0
    longest = sorted(idle_pieces(prog, trace), key=lambda p: -p[1])[:5]
    starts = [c.start for c in calls]
    prog.log.update(
        outside_ingest_max_us=1e6 * max(
            max(a - c.start, c.end - b, 0.0)
            for (a, b), c in zip(ingest, calls)),
        idle_s=idle, idle_sum_s=sum(idle.values()),
        # [label, seconds, call index] of the longest idle stretches
        idle_longest=[[key, d, bisect.bisect_right(starts, t) - 1]
                      for t, d, key in longest],
        window_minus_busy_s=(hi - lo) - busy,
        stage_programs=stage_coverage(prog, trace),
    )
    return prog


def idle_by_span(prog: Program, trace, device: int = 0) -> Dict[str, float]:
    """Device-idle seconds of the steady window, split by the innermost
    program span open at each instant (:data:`OUTSIDE` where none is)."""
    out: Dict[str, float] = {}
    for _, d, key in idle_pieces(prog, trace, device):
        out[key] = out.get(key, 0.0) + d
    return out


def idle_pieces(prog: Program, trace, device: int = 0):
    """``(start, seconds, label)`` of each stretch of the steady window in
    which the device idles under one innermost program span."""
    lo, hi = trace.window()
    if hi <= lo or not trace.modules:
        return
    gaps, t = [], lo
    for s, e in reduce.union(trace.modules[device], lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if t < hi:
        gaps.append((t, hi))
    k = 0
    for a, b, span in innermost_segments(prog.spans, lo, hi):
        key = span.label if span is not None else OUTSIDE
        while k < len(gaps) and gaps[k][1] <= a:
            k += 1
        j = k
        while j < len(gaps) and gaps[j][0] < b:
            s0 = max(a, gaps[j][0])
            d = min(b, gaps[j][1]) - s0
            if d > 0:
                yield s0, d, key
            j += 1


def innermost_segments(spans: Sequence[Span], lo: float, hi: float):
    """``(start, end, span)`` pieces covering ``[lo, hi)``, each with the
    innermost span open over it (None where none is).  Spans of one
    thread nest, so a stack sweep over their ends finds it."""
    marks = sorted(
        [(s.start, 1, s.depth, k) for k, s in enumerate(spans)]
        + [(s.end, 0, -s.depth, k) for k, s in enumerate(spans)]
    )
    stack: List[int] = []
    t = lo
    for when, opens, _, k in marks:
        when = min(max(when, lo), hi)
        if when > t:
            yield t, when, spans[stack[-1]] if stack else None
            t = when
        if opens:
            stack.append(k)
        elif k in stack:
            stack.remove(k)
    if t < hi:
        yield t, hi, spans[stack[-1]] if stack else None


def is_host_phase(label: str) -> bool:
    """A program phase in which the host works: any ``swirld.*`` span
    but a wait."""
    return label.startswith("swirld.") and not label.startswith("swirld.wait")


def host_seconds(prog: Program, call: Span) -> float:
    """A call's duration less the union of the device waits inside it."""
    waits = [(s.start, s.end, s.name) for s in prog.inside(call)
             if s.name == "swirld.wait" and s.args.get("on") == "device"]
    return (call.end - call.start) - sum(
        e - s for s, e in reduce.union(waits, call.start, call.end))


def probes_per_unit(records: Sequence[Dict]) -> Optional[float]:
    """Rounds-scan dispatches per accepted chunk or fused span, over the
    counters of :func:`counted`."""
    units = sum(r["rounds_units"] for r in records)
    return sum(r["rounds_probes"] for r in records) / units if units else None


def stage_coverage(prog: Program, trace, device: int = 0) -> Dict:
    """Over the calls of the steady window: the stage programs each call
    dispatched (its records' ``dispatches``) against the ``*_stage``
    modules the trace holds from its start to the next call's start."""
    lo, hi = trace.window()
    mods = sorted(s for s, _, name in (trace.modules[device]
                                       if trace.modules else ())
                  if reduce.program_name(name).endswith("_stage"))
    starts = [c.start for c in prog.calls]
    out = {"calls": 0, "dispatched": 0, "traced": 0, "calls_short": 0,
           "calls_over": 0}
    for k, c in enumerate(prog.calls):
        if not lo <= c.start < hi:
            continue
        nxt = starts[k + 1] if k + 1 < len(starts) else float("inf")
        sent = sum(s.args.get("dispatches", 0) for s in prog.inside(c))
        seen = bisect.bisect_left(mods, nxt) - bisect.bisect_left(
            mods, c.start)
        out["calls"] += 1
        out["dispatched"] += sent
        out["traced"] += seen
        out["calls_short"] += seen < sent
        out["calls_over"] += seen > sent
    return out
