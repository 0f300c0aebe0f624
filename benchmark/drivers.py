"""The two traffic drivers, read from a mix's data file.

- ``replay`` (closed loop): a node that restarts or rejoins replays a
  saved history from genesis, as fast as the engine takes it.  The window
  holds back-to-back replays of the same history, each by a fresh engine,
  and closes at the end of the first replay that ends ``seconds`` or more
  after it opened: every replay in it is whole, so each window does a
  whole number of replays' work and the check covers all of each.
- ``open_loop``: syncs of ``sync_events`` events, drawn in order from one
  generated stream, fall due as a Poisson process drawn from the mix's
  ``arrival_seed``.  Each ingest
  call takes the oldest sync that is due.  A sync is timed from when it
  was due; after the window the schedule keeps feeding until every event
  due in the window is ordered (the drain).

Both take the program's entry points from :class:`Program`, which is the
one place the benchmark touches the system under test.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Dict, List

import numpy as np

from benchmark import compare, gossip
from benchmark.clock import percentile

now = time.perf_counter


class Program:
    """The system under test: its engines at their defaults."""

    def __init__(self, config: Dict):
        from tpu_swirld.config import SwirldConfig
        from tpu_swirld.packing import pack_events
        from tpu_swirld.store import StreamingConsensus
        from tpu_swirld.tpu.pipeline import run_consensus

        self.stake = [int(s) for s in config["stake"]]
        self.swirld = SwirldConfig(
            n_members=int(config["members"]), stake=tuple(self.stake),
            coin_period=int(config["coin_period"]),
        )
        self._pack, self._run = pack_events, run_consensus
        self._streaming = StreamingConsensus

    def batch(self, events, members):
        return self._run(self._pack(events, members, self.stake), self.swirld)

    def streaming(self, members):
        return self._streaming(members, self.stake, self.swirld)


def annotate(on: bool):
    """``TraceAnnotation`` spans in a traced run, nothing otherwise."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Replay:
    order: List[int] = dataclasses.field(default_factory=list)
    result: object = None          # the engine's result once complete


class ReplayDriver:
    """Closed-loop catch-up over one saved history."""

    def __init__(self, cell, seed: int, program, span):
        cfg, mix = cell.config, cell.traffic
        self.program, self.span = program, span
        self.engine = mix["engine"]
        self.sync = int(mix.get("sync_events", 0))
        with span("generate"):
            self.hist = gossip.from_config(
                cfg, int(cfg["history_events"]), seed, int(mix["dag_seed"]))
            self.events = gossip.program_events(self.hist)
        self.replays: List[Replay] = []
        self.counters: Dict = {}

    def _calls(self, rep: Replay):
        """One replay as a series of engine calls; yields after each
        ``ingest`` call the number of events it placed in consensus
        order."""
        hist, span = self.hist, self.span
        if self.engine == "batch":
            with span("ingest"):
                res = self.program.batch(self.events, hist.members)
            rep.order, rep.result = list(res.order), res
            yield len(res.order)
            return
        with span("replay_start"):
            inc = self.program.streaming(hist.members)
        try:
            for s in range(0, hist.n, self.sync):
                with span("ingest"):
                    st = inc.ingest(self.events[s:s + self.sync])
                rep.order.extend(st["ordered"])
                yield len(st["ordered"])
            with span("result"):
                rep.result = inc.result()
        finally:
            inc.store.close()

    def warm(self):
        """One whole replay: every shape the window uses."""
        rep = Replay()
        for _ in self._calls(rep):
            pass
        self.warm_replay = rep

    def measure(self, seconds: float) -> Dict:
        t0 = now()
        calls, t = [], t0
        while t - t0 < seconds:
            rep = Replay()
            self.replays.append(rep)
            calls.extend(self._calls(rep))
            t = now()
        ordered = sum(calls)
        self.counters.update(window_s=t - t0, ordered=ordered, calls=calls,
                             replays=len(self.replays))
        return {"events_per_s": ordered / (t - t0)}

    def release(self):
        self.events = None

    def checks(self, reference) -> Dict[str, int]:
        """Worst disagreement of any replay with the reference."""
        ref = reference(self.hist)
        worst = dict.fromkeys(compare.FIELDS, 0)
        worst["emitted_order"] = 0
        self.failed = 0
        for rep in [self.warm_replay] + self.replays:
            bad = compare.mismatches(rep.result, ref, self.hist.n)
            # the order as the calls returned it, pass by pass
            bad["emitted_order"] = compare.prefix_mismatches(
                rep.order, ref) + abs(len(rep.order) - len(ref.order))
            self.failed += rep is not self.warm_replay and any(bad.values())
            for k, v in bad.items():
                worst[k] = max(worst[k], v)
        self.attempted = len(self.replays)
        return worst


def stream_syncs(mix: Dict, seconds: float) -> int:
    """Syncs in an open-loop run's stream: the warm-up part, the window's
    and the drain that orders the window's last events."""
    return int(mix["warmup_syncs"]) + int(mix["drain_syncs"]) + math.ceil(
        float(mix["rate_syncs_per_s"]) * seconds)


class OpenLoopDriver:
    """Open-loop live sync over one generated stream.

    The node takes one sync per ingest call, oldest first; syncs that fall
    due meanwhile wait.  So the engine sees the same deltas whatever the
    timing, and set-up can compile every shape the window will use: a
    first engine ingests the whole stream that the run can reach (the
    shapes depend on the engine's state, which only ingesting reaches),
    then a fresh one ingests the warm-up part and the window runs on
    it."""

    def __init__(self, cell, seed: int, program, span, seconds: float):
        cfg, mix = cell.config, cell.traffic
        self.program, self.span, self.mix = program, span, mix
        self.sync = int(mix["sync_events"])
        self.rate = float(mix["rate_syncs_per_s"])
        self.warm_syncs = int(mix["warmup_syncs"])
        with span("generate"):
            self.hist = gossip.from_config(
                cfg, stream_syncs(mix, seconds) * self.sync, seed,
                int(mix["dag_seed"]))
            self.events = gossip.program_events(self.hist)
        self.pos = 0                     # events ingested so far
        self.emitted: List[int] = []     # order as the calls returned it
        self.prerun_ends: List[float] = []   # host time each pre-run call ended
        self.counters: Dict = {}
        self.inc = None

    def _ingest(self):
        stop = self.pos + self.sync
        with self.span("ingest"):
            st = self.inc.ingest(self.events[self.pos:stop])
        self.pos = stop
        self.emitted.extend(st["ordered"])
        return st["ordered"]

    def warm(self):
        """Every delta of the stream through a first engine, then the
        warm-up part through the engine the window runs on."""
        with self.span("replay_start"):
            self.inc = self.program.streaming(self.hist.members)
        try:
            while self.pos < self.hist.n:
                self._ingest()
                self.prerun_ends.append(now())
        finally:
            self.inc.store.close()
        self.restart()

    def restart(self):
        """A fresh engine that has ingested the warm-up part."""
        self.pos, self.emitted = 0, []
        with self.span("replay_start"):
            self.inc = self.program.streaming(self.hist.members)
        while self.pos < self.warm_syncs * self.sync:
            self._ingest()

    def gaps(self, rate: float, n: int, seconds: float) -> np.ndarray:
        """Seconds between due times, drawn from the mix's
        ``arrival_seed`` alone, so that every seed offers the same load at
        the same times.  The window's ``rate * seconds`` gaps are scaled so
        that exactly that many syncs fall due in it (a Poisson process
        given its count)."""
        rng = np.random.default_rng(int(self.mix["arrival_seed"]))
        gaps = rng.exponential(1.0 / rate, n)
        head = min(n, int(rate * seconds))
        gaps[:head] *= (seconds - 0.5 / rate) / gaps[:head].sum()
        return gaps

    def run_schedule(self, rate: float, seconds: float, drain_s: float):
        """Offer syncs at ``rate`` per second for ``seconds``, then keep
        the schedule running up to ``drain_s`` more until every event due
        in the window is ordered (or the stream ends).  Returns the
        per-sync and per-event records of the window."""
        e0 = self.pos
        n_syncs = (self.hist.n - e0) // self.sync
        gaps = self.gaps(rate, n_syncs, seconds)
        t0 = now()
        due = t0 + np.cumsum(gaps)
        n_win = int(np.searchsorted(due, t0 + seconds))   # due in window
        e1 = e0 + n_win * self.sync
        done = np.full(self.hist.n, np.nan)
        took = np.full(n_syncs, np.nan)
        backlog, passes, late = [], [], []
        k = 0                                   # next sync to ingest
        while k < n_syncs:
            t = now()
            if t > t0 + seconds + drain_s:
                break
            if k >= n_win and not np.isnan(done[e0:e1]).any():
                break
            if due[k] > t:
                with self.span("wait"):
                    time.sleep(due[k] - t)
                late.append(now() - due[k])
                continue
            backlog.append((t - t0, int(np.searchsorted(due, t, "right")) - k))
            ordered = self._ingest()
            t_end = now()
            if t < t0 + seconds:
                passes.append(t_end - t)
            took[k] = t_end
            done[ordered] = t_end
            k += 1
        syncs_due = due[:n_win]
        ev_due = np.repeat(syncs_due, self.sync)
        return {
            "sync_latency": (took[:n_win] - syncs_due),
            "event_latency": done[e0:e1] - ev_due,
            "passes": passes, "late": late, "backlog": backlog,
            "offered": n_win, "t0": t0,
        }

    def measure(self, seconds: float) -> Dict:
        rec = self.run_schedule(
            self.rate, seconds, float(self.mix["drain_max_s"]))
        sync_lat, ev_lat = rec["sync_latency"], rec["event_latency"]
        self.attempted = int(rec["offered"])
        # a sync never taken, or one with an event never ordered by the
        # end of the drain, misses every latency limit
        self.failed = int(np.count_nonzero(
            np.isnan(sync_lat)
            | np.isnan(ev_lat).reshape(-1, self.sync).any(axis=1)))
        inf = float("inf")
        sync_ms = [1e3 * x if x == x else inf for x in sync_lat]
        ev_ms = [1e3 * x if x == x else inf for x in ev_lat]
        self.counters.update(
            passes=[1e3 * p for p in rec["passes"]],
            late_ms=[1e3 * x for x in rec["late"]],
            backlog_max=max((b for _, b in rec["backlog"]), default=0),
            syncs=len(sync_ms), events=len(ev_ms),
            ordered=int(np.count_nonzero(~np.isnan(ev_lat))),
        )
        return {
            "sync_p95_ms": percentile(sync_ms, 0.95),
            "finality_p95_ms": percentile(ev_ms, 0.95),
        }

    def release(self):
        self.result = self.inc.result()
        self.inc.store.close()
        self.inc = self.events = None

    def checks(self, reference) -> Dict[str, int]:
        ref = reference(self.hist.prefix(self.pos))
        bad = compare.mismatches(self.result, ref, self.pos)
        bad["emitted_order"] = compare.prefix_mismatches(self.emitted, ref) \
            + abs(len(self.emitted) - len(ref.order))
        return bad


def driver(cell, seed: int, program, span, seconds: float):
    if cell.traffic["driver"] == "open_loop":
        return OpenLoopDriver(cell, seed, program, span, seconds)
    return ReplayDriver(cell, seed, program, span)
