"""Observability subsystem: structured spans + protocol gauges (SURVEY §5).

SURVEY §5 lists metrics/telemetry among the aux subsystems the reference
never had ("no logging, no metrics, no persistence — state dies with the
process"); this package is the real implementation the ad-hoc
``tpu_swirld.metrics`` counters grew into.  Three pieces:

- :mod:`tpu_swirld.obs.tracer` — a nested-span tracer with wall-clock +
  monotonic timestamps and JSONL export in Chrome trace-event form
  (``chrome://tracing`` / Perfetto compatible after ``[...]`` wrapping).
- :mod:`tpu_swirld.obs.registry` — counters / gauges / histograms with
  Prometheus-text and JSON exporters.
- :mod:`tpu_swirld.obs.report` — the ``python -m tpu_swirld.obs report``
  CLI rendering a phase-breakdown table + protocol gauges from a trace.
- :mod:`tpu_swirld.obs.finality` — per-event lifecycle tracking:
  rounds-to-decision / time-to-finality histograms, decided watermarks,
  gossip-propagation latency (``finality_*`` metric families).
- :mod:`tpu_swirld.obs.flightrec` — the black-box flight recorder:
  bounded per-node rings of recent activity, dumped as self-contained
  post-mortem JSON when a verdict fails / breaker opens / overflow heals
  / rebase storm triggers (``flightrec_*`` metric families).

Instrumented layers: oracle phases (``oracle/node.py::consensus_pass``),
gossip (sync round-trips / payload bytes / events-per-sync / fork
detections), the device pipeline stages (``tpu/pipeline.py`` — per-stage
compile-vs-execute time, pad waste, strongly-sees column and chunk-scan
counts), the mesh path (``parallel.py``), and the engines' phases (below).

Enabling
--------

Everything is **disabled by default with near-zero overhead**: the hot
paths check a module global (``obs.current() is None``) and touch neither
tracer nor registry when it is unset.  Enable around a region::

    from tpu_swirld import obs

    with obs.enabled() as o:                 # or o = obs.enable()
        run_consensus(packed, config)
    o.save("/tmp/swirld.trace.jsonl")        # spans + registry snapshot
    print(o.registry.to_prometheus_text())

then render with ``python -m tpu_swirld.obs report /tmp/swirld.trace.jsonl``.

Engine spans
------------

Each engine entry (``StreamingConsensus.ingest``,
``IncrementalConsensus.ingest``, ``run_consensus``, ``pack_events``)
reads the gate once (:func:`recorder`) and installs what it finds for the
call (:func:`call_span`); every span, tally and pull inside reads that
one recorder.  The recorder is the ambient ``Obs``'s tracer when
``enable()`` was called; otherwise, while a JAX profiler session runs
(``jax.profiler.TraceAnnotation.is_enabled()``), a process-wide tracer
(:func:`profile_recorder`) that is cleared when a new session started by
``jax.profiler.trace`` / ``start_trace`` is first seen, holds at most
:data:`PROFILE_MAX_EVENTS` events (``dropped`` counts the rest) and
enters a ``TraceAnnotation`` for each span it records, so the phases show
in the device trace an operator opens in Perfetto or TensorBoard;
otherwise none, and every span site gets one shared no-op span.  Spans
are phase-grained and named ``swirld.*``: ``stream_ingest``, ``batch``,
``pass``, ``pack``, ``plan``, ``rounds``, ``fame``, ``order``,
``retire``, ``rebase`` and ``wait`` (``on``: ``device``, ``decode`` or
``spill``).  Each ``swirld.pass`` / ``swirld.batch`` record counts
:data:`TALLIES`; under an enabled ``Obs`` those counts also go to the
registry.  Under the profiler gate alone :func:`stage_call` does not
block: device time per stage is the device trace's.  Under an enabled
``Obs`` it blocks on every stage, so a pull there waits for nothing and
is counted but not spanned.  Span times are on the tracer's epoch
(``Tracer.epoch``): ``epoch_ns + 1000 * ts`` is the profiler's clock.

Per-node oracle counters remain opt-in via ``node.metrics = Metrics()``
(now a thin shim over :class:`Registry`) and ``node.tracer = Tracer()``;
``sim.make_simulation(..., metrics=..., tracer=...)`` wires whole
simulations.
"""

from __future__ import annotations

import contextlib
import contextvars
import sys
import time
from typing import Optional

import numpy as np

from tpu_swirld.obs.finality import (  # noqa: F401
    FinalityTracker, record_batch_result,
)
from tpu_swirld.obs.flightrec import (  # noqa: F401
    FlightRecorder, load_dump,
)
from tpu_swirld.obs.memory import (  # noqa: F401
    MemoryMonitor, device_live_bytes,
)
from tpu_swirld.obs.registry import (  # noqa: F401
    Counter, Gauge, Histogram, Registry,
)
from tpu_swirld.obs.tracer import (  # noqa: F401
    NULL_SPAN, NULL_TRACER, NullTracer, Tracer, load_trace,
)


class Obs:
    """A tracer + registry bundle — the unit ``enable()`` installs."""

    def __init__(
        self,
        tracer: Optional[Tracer] = None,
        registry: Optional[Registry] = None,
    ):
        self.tracer = tracer if tracer is not None else Tracer()
        self.registry = registry if registry is not None else Registry()

    def save(self, path: str) -> None:
        """Write the trace plus the registry snapshot (as Chrome counter
        samples) so one file carries both timing and gauges.  The tracer
        itself is not mutated — repeated saves snapshot fresh values
        instead of accumulating stale duplicates."""
        import json as _json

        from tpu_swirld.obs.registry import Histogram as _H, _num

        events = list(self.tracer.events)
        for m in self.registry.metrics():
            labels = {k: v for k, v in m.labels}
            if isinstance(m, _H):
                events.append(
                    self.tracer.counter_event(
                        m.name + "_count", m.count, labels
                    )
                )
                events.append(
                    self.tracer.counter_event(
                        m.name + "_sum", round(m.sum, 9), labels
                    )
                )
            else:
                events.append(
                    self.tracer.counter_event(m.name, _num(m.value), labels)
                )
        with open(path, "w") as f:
            for e in events:
                f.write(_json.dumps(e) + "\n")


_current: Optional[Obs] = None


def current() -> Optional[Obs]:
    """The ambient Obs, or None when observability is disabled (default).

    Hot paths gate on this: ``o = obs.current(); if o is not None: ...`` —
    one global read on the disabled path, nothing else.
    """
    return _current


def enable(obs: Optional[Obs] = None) -> Obs:
    """Install (and return) the ambient Obs."""
    global _current
    _current = obs if obs is not None else Obs()
    return _current


def disable() -> Optional[Obs]:
    """Clear the ambient Obs; returns the one that was active."""
    global _current
    prev, _current = _current, None
    return prev


@contextlib.contextmanager
def enabled(obs: Optional[Obs] = None):
    """Scoped enable: ``with obs.enabled() as o: ...`` (restores the
    previous ambient Obs on exit, so scopes nest)."""
    global _current
    prev = _current
    o = obs if obs is not None else Obs()
    _current = o
    try:
        yield o
    finally:
        _current = prev


@contextlib.contextmanager
def phase_scope(metrics, tracer, name: str):
    """Combined per-phase scope: times into ``metrics`` (a
    :class:`tpu_swirld.metrics.Metrics`) and/or spans into ``tracer``,
    either of which may be None.  The all-None case is never constructed
    by callers (they branch first), but stays correct."""
    if tracer is not None and metrics is not None:
        with tracer.span(name), metrics.phase(name):
            yield
    elif tracer is not None:
        with tracer.span(name):
            yield
    elif metrics is not None:
        with metrics.phase(name):
            yield
    else:
        yield


# ------------------------------------------------------ engine recorder

#: the counters each ``swirld.pass`` / ``swirld.batch`` record carries:
#: stage dispatches, blocking device->host pulls, rounds-scan dispatches
#: (probes), accepted chunks or fused spans (units), witness columns the
#: scan found missing and added, and per rounds phase the fork-pair rows
#: it ran with, the witness slots per round it carried, the most
#: witnesses any of its rounds holds and the times it grew its slots in
#: place
TALLIES = ("dispatches", "pulls", "rounds_probes", "rounds_units",
           "columns_added", "fork_pairs", "rounds_slots",
           "witness_slots_used", "rounds_slot_grows")

#: registry counters that mirror the tallies under an enabled Obs (the
#: dispatches are stage_call's per-stage ``pipeline_stage_calls``)
_TALLY_COUNTERS = {
    "pulls": "pipeline_host_pulls_total",
    "rounds_probes": "pipeline_chunk_scans_total",
    "rounds_units": "pipeline_rounds_units_total",
    "columns_added": "pipeline_scan_columns_total",
    "fork_pairs": "pipeline_rounds_fork_pairs_total",
    "rounds_slots": "pipeline_rounds_slots_total",
    "witness_slots_used": "pipeline_witness_slots_used_total",
    "rounds_slot_grows": "pipeline_rounds_slot_grows_total",
}

#: events the profiler-gated recorder keeps per session; the rest are
#: counted in its ``dropped``
PROFILE_MAX_EVENTS = 250_000

#: the recorder of the engine call running in this thread / context
_active: contextvars.ContextVar = contextvars.ContextVar(
    "swirld_recorder", default=None)
_profile_tracer: Optional[Tracer] = None
_profile_session = None
_annotation = None                    # jax.profiler.TraceAnnotation


def recorder() -> Optional[Tracer]:
    """The gate an engine entry reads once per call: the ambient Obs's
    tracer, else the process-wide profiler recorder while a JAX profiler
    session runs, else None (module doc)."""
    o = _current
    if o is not None:
        return o.tracer
    ann = _annotation if _annotation is not None else _bind_annotation()
    if ann is None or not ann.is_enabled():
        return None
    return _session_recorder(ann)


def _bind_annotation():
    """``jax.profiler.TraceAnnotation`` once JAX is loaded (a process
    without JAX runs no profiler session, so the gate stays shut)."""
    global _annotation
    prof = sys.modules.get("jax.profiler")
    if prof is not None:
        _annotation = prof.TraceAnnotation
    return _annotation


def _session_recorder(annotation) -> Tracer:
    global _profile_tracer, _profile_session
    # the session object jax.profiler.trace / start_trace holds while it
    # runs (None for a session started through the profiler server)
    state = getattr(sys.modules.get("jax._src.profiler"), "_profile_state",
                    None)
    session = getattr(state, "profile_session", None)
    if _profile_tracer is None or session is not _profile_session:
        _profile_session = session
        _profile_tracer = Tracer(max_events=PROFILE_MAX_EVENTS,
                                 annotation=annotation)
    return _profile_tracer


def profile_recorder() -> Optional[Tracer]:
    """The process-wide recorder of the newest profiler session (None
    before the first): what a trace reader aligns with the device trace."""
    return _profile_tracer


class _CallSpan:
    """The outermost span of one engine call; installs its recorder as
    the one every nested span, tally and pull reads."""

    __slots__ = ("_rec", "_ctx", "_token")

    def __init__(self, rec: Tracer, ctx):
        self._rec, self._ctx, self._token = rec, ctx, None

    def __enter__(self):
        self._token = _active.set(self._rec)
        return self._ctx.__enter__()

    def __exit__(self, *exc):
        _active.reset(self._token)
        return self._ctx.__exit__(*exc)


def call_span(rec: Optional[Tracer], name: str, *, tally: bool = False,
              **args):
    """An engine call's span on ``rec`` (from :func:`recorder`), holding
    the :data:`TALLIES` at zero when ``tally``; the shared no-op span when
    ``rec`` is None."""
    if rec is None:
        return NULL_SPAN
    if tally:
        args.update(dict.fromkeys(TALLIES, 0))
    return _CallSpan(rec, rec.span(name, **args))


def span(name: str, **args):
    """A phase span on the engine call's recorder (no-op without one)."""
    rec = _active.get()
    if rec is None:
        return NULL_SPAN
    return rec.span(name, **args)


def tally(key: str, n: int = 1) -> None:
    """Count ``n`` into the innermost ``swirld.pass`` / ``swirld.batch``
    record and, under an enabled Obs, its registry counter."""
    rec = _active.get()
    if rec is not None:
        rec.tally(key, n)
    o = _current
    if o is not None:
        o.registry.counter(_TALLY_COUNTERS[key]).inc(n)


# Audit seam: tpu_swirld.analysis.jit_audit installs a callback here to
# record every stage call's abstract signature (shape/dtype/weak_type per
# arg) without touching values.  None in production — one global read.
_stage_observer = None


def set_stage_observer(cb) -> None:
    """Install (or clear, with None) the stage-call observer: called as
    ``cb(name, fn, args, kw)`` before every observed stage dispatch."""
    global _stage_observer
    _stage_observer = cb


def stage_call(name: str, fn, *args, **kw):
    """Run a jitted stage, counted as a dispatch of the engine call.
    Under the ambient Obs it also spans the call, blocks on the result so
    the span measures device completion, and classifies the call as
    ``compile`` vs ``execute`` by watching the jit cache grow.

    Enabling observability therefore synchronizes stage boundaries —
    that's the point (per-stage attribution); leave it disabled for
    maximum-overlap production runs.  The profiler-gated recorder never
    blocks: the device trace holds each stage's time.
    """
    so = _stage_observer
    if so is not None:
        so(name, fn, args, kw)
    count_dispatch()
    o = _current
    if o is None:
        return fn(*args, **kw)
    import jax

    c0 = _jit_cache_size(fn)
    t0 = time.perf_counter()
    with o.tracer.span(name) as sp:
        out = fn(*args, **kw)
        out = jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        kind = "execute"
        if c0 >= 0 and _jit_cache_size(fn) > c0:
            kind = "compile"
        sp.args["kind"] = kind   # inside the span: lands in the event
    reg = o.registry
    reg.counter("pipeline_stage_seconds", {"stage": name, "kind": kind}).inc(dt)
    reg.counter("pipeline_stage_calls", {"stage": name, "kind": kind}).inc()
    return out


def count_dispatch() -> None:
    """Count one stage dispatch into the engine call's record (every
    :func:`stage_call`, and a stage the caller dispatches directly)."""
    rec = _active.get()
    if rec is not None:
        rec.tally("dispatches")


def to_host(x, copy: bool = False):
    """Pull a (device) array to host numpy: the engines' blocking pulls
    route through here, so each is counted and, on the profiler-gated
    recorder, spanned as ``swirld.wait{on=device}`` (under an enabled Obs
    :func:`stage_call` has blocked already, so a pull waits for nothing).
    ``copy=True`` forces a mutable owned copy (``np.array`` semantics for
    mirrors mutated in place)."""
    rec = _active.get()
    if rec is None and _current is None:
        return np.array(x) if copy else np.asarray(x)
    tally("pulls")
    if _current is not None:
        return np.array(x) if copy else np.asarray(x)
    with rec.span("swirld.wait", on="device"):
        return np.array(x) if copy else np.asarray(x)


def _jit_cache_size(fn) -> int:
    try:
        return fn._cache_size()
    except Exception:
        return -1


def compile_counts(registry) -> dict:
    """Per-stage count of jit-cache-growing calls recorded by
    :func:`stage_call` (``kind == "compile"``) in ``registry``.

    The steady-state recompile regression test wraps a warm loop in a
    fresh Obs and asserts this comes back empty — i.e. the loop added
    zero new entries to any stage's jit cache.
    """
    out: dict = {}
    for m in registry.metrics():
        if m.name != "pipeline_stage_calls":
            continue
        labels = dict(m.labels)
        if labels.get("kind") == "compile":
            stage = labels.get("stage", "?")
            out[stage] = out.get(stage, 0) + int(m.value)
    return out
