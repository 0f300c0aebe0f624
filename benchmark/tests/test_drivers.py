"""The drivers' arithmetic on a fake clock: the replay rate over whole
replays, tails timed from the due time over all syncs, and the seeded
Poisson schedule."""

import contextlib

import numpy as np
import pytest

from benchmark import drivers, spec
from benchmark.clock import percentile

from .fakes import Clock, FakeProgram


def no_span(name):
    return contextlib.nullcontext()


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(drivers, "now", c)
    monkeypatch.setattr(drivers.time, "sleep", c.sleep)
    return c


def test_replay_window_holds_whole_replays(toy_root, clock):
    cell = spec.load_cell("toy8.stream", toy_root)      # 1,500 events, 256
    prog = FakeProgram(clock, 0.125)
    drv = drivers.ReplayDriver(cell, 5, prog, no_span)
    drv.warm()
    out = drv.measure(1.1)
    # each replay: 6 ingest calls (0.75 s, 1,500 ordered) and result();
    # replay 2 ends at 1.5 s, the first end at or after 1.1 s
    assert drv.counters["ordered"] == 2 * 1500
    assert drv.counters["window_s"] == pytest.approx(1.5)
    assert out["events_per_s"] == pytest.approx(3000 / 1.5)
    assert drv.counters["replays"] == 2
    assert all(len(r.order) == 1500 for r in drv.replays)
    # one count per ingest call, in call order, for the trace's readers
    assert drv.counters["calls"] == [256] * 5 + [220] + [256] * 5 + [220]


def test_batch_replay_is_one_call(toy_root, clock):
    cell = spec.load_cell("toy8.catchup", toy_root)
    drv = drivers.ReplayDriver(cell, 5, FakeProgram(clock, 0.5), no_span)
    drv.warm()
    out = drv.measure(1.2)           # calls end at 0.5, 1.0, 1.5
    assert drv.counters["replays"] == 3
    assert out["events_per_s"] == pytest.approx(3 * 1500 / 1.5)


def simulate(due, service):
    """Independent FIFO single-server queue, one sync per call of
    ``service`` seconds; returns each sync's end."""
    end, t = np.zeros(len(due)), due[0]
    for k, d in enumerate(due):
        t = max(t, d) + service
        end[k] = t
    return end


def test_open_loop_tails_from_due_time_over_all_syncs(toy_root, clock):
    cell = spec.load_cell("toy8.live", toy_root)
    prog = FakeProgram(clock, 0.12)
    drv = drivers.OpenLoopDriver(cell, 9, prog, no_span, 4.0)
    drv.warm()
    gaps = drv.gaps(drv.rate, (drv.hist.n - drv.pos) // drv.sync, 4.0)
    t0 = clock.t
    out = drv.measure(4.0)
    due = t0 + np.cumsum(gaps)
    n_win = int(np.searchsorted(due, t0 + 4.0))
    end = simulate(due, 0.12)
    want = (end[:n_win] - due[:n_win]) * 1e3
    assert drv.attempted == n_win and drv.failed == 0
    assert out["sync_p95_ms"] == pytest.approx(percentile(list(want), 0.95))
    # the fake orders every event when its sync is taken
    assert out["finality_p95_ms"] == pytest.approx(
        percentile(list(np.repeat(want, drv.sync)), 0.95))
    assert max(drv.counters["late_ms"]) == pytest.approx(0.0, abs=1e-6)


def test_open_loop_counts_syncs_never_taken(toy_root, clock):
    cell = spec.load_cell("toy8.live", toy_root)
    prog = FakeProgram(clock, 30.0)     # one call outlasts window + drain
    drv = drivers.OpenLoopDriver(cell, 9, prog, no_span, 4.0)
    drv.warm()
    out = drv.measure(2.0)
    assert drv.failed > 0 and out["sync_p95_ms"] == float("inf")


def test_poisson_schedule_comes_from_arrival_seed(toy_root):
    cell = spec.load_cell("toy8.live", toy_root)
    a = drivers.OpenLoopDriver(cell, 2**31 + 7, None, no_span, 4.0)
    b = drivers.OpenLoopDriver(cell, 2**31 + 7, None, no_span, 4.0)
    c = drivers.OpenLoopDriver(cell, 2**31 + 8, None, no_span, 4.0)
    ga, gb, gc = (d.gaps(10.0, 50, 4.0) for d in (a, b, c))
    # arrival_seed alone draws the gaps: every seed offers the same load
    # at the same times, and exactly rate * seconds syncs fall due in the
    # window
    assert np.array_equal(ga, gb) and np.array_equal(ga, gc)
    assert np.searchsorted(np.cumsum(ga), 4.0) == 40
    cell.traffic["arrival_seed"] += 1
    assert not np.array_equal(ga, a.gaps(10.0, 50, 4.0))


def test_dag_seed_fixes_the_work_not_the_inputs():
    from benchmark import gossip, reference

    a = gossip.generate(8, 900, 11, None, 1)
    b = gossip.generate(8, 900, 12, None, 1)
    assert a.ids != b.ids and not np.array_equal(a.creator, b.creator)
    assert np.array_equal(a.self_parent, b.self_parent)
    assert len(reference.consensus(a).order) == len(
        reference.consensus(b).order)
