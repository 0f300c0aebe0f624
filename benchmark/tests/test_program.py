"""The program-span readers' arithmetic on a small committed run: the
reduced trace of small_trace.json plus the program recorder of the same
window (data/program_trace.json)."""

import copy
import json
import os
import types

import pytest

from benchmark import spec
from benchmark.trace import program, reduce

from .conftest import ROOT

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture
def run():
    with open(os.path.join(DATA, "program_trace.json")) as f:
        return json.load(f)


def ctx_of(run, **counters):
    rec = types.SimpleNamespace(**run["recorder"])
    ctx = types.SimpleNamespace(trace=reduce.Trace.from_json(run["trace"]),
                                counters=counters)
    return ctx, rec


def aligned(run):
    ctx, rec = ctx_of(run)
    return program.read(ctx, rec), ctx


def reader(metric):
    return spec.Cell("x", 1, {}, {}, [], [], ROOT).reader(metric)


def test_pairing_shifts_by_the_median_of_the_end_differences(run):
    prog, _ = aligned(run)
    # ends 10 and 30 us before the ingest ends: median 20 us, spread 20 us
    assert prog.offset_s == pytest.approx(9.5 + 20e-6, abs=1e-9)
    assert prog.spread_s == pytest.approx(20e-6, abs=1e-9)
    assert [c.name for c in prog.calls] == ["swirld.stream_ingest"] * 2
    assert prog.calls[0].end == pytest.approx(10.50001, abs=1e-9)
    assert prog.log["outside_ingest_max_us"] == pytest.approx(10, abs=1e-3)


def test_idle_split_by_innermost_span_adds_up_to_idle(run):
    prog, ctx = aligned(run)
    idle = program.idle_by_span(prog, ctx.trace)
    # gaps [10.2, 10.3) and [10.45, 10.8); the spans sit 20 us later
    want = {"swirld.wait{on=device}": 0.08, "swirld.rounds": 0.03,
            "swirld.retire": 0.03, "swirld.pass": 0.03,
            "swirld.stream_ingest": 0.01, "swirld.order": 0.02,
            "outside": 0.25}
    assert set(idle) == set(want)
    for k, v in want.items():
        assert idle[k] == pytest.approx(v, abs=1e-4), k
    assert sum(idle.values()) == pytest.approx(1 - reduce.busy_seconds(
        ctx.trace))
    assert prog.log["idle_sum_s"] == pytest.approx(
        prog.log["window_minus_busy_s"])


def test_readers(run, monkeypatch):
    ctx, rec = ctx_of(run, passes=[500.0, 250.0])
    monkeypatch.setattr(program, "recorder", lambda: rec)
    ctx._program = program._align(ctx.trace, rec)
    # host phases 0.12 s of idle over the 2 calls of the steady window
    assert reader("host_idle_ms_per_call.catchup")(ctx) == pytest.approx(
        60.0, abs=0.1)
    # (0.49998 - 0.08) and 0.24996 s: the median of two is their mean
    assert reader("host_ms_p50.live")(ctx) == pytest.approx(
        1e3 * (0.49998 - 0.08 + 0.24996) / 2, abs=1e-6)
    for m in ("rounds_probes_per_span.catchup",
              "rounds_probes_per_span.live"):
        assert reader(m)(ctx) == pytest.approx(4 / 3)


def test_stage_programs_dispatched_against_traced(run):
    prog, ctx = aligned(run)
    # call 1 dispatched 3 and the trace shows 1 (the one at 9.9 s started
    # before it); call 2 dispatched 1 and shows 1; jit_copy is no stage
    assert program.stage_coverage(prog, ctx.trace) == {
        "calls": 2, "dispatched": 4, "traced": 2, "calls_short": 1,
        "calls_over": 0}


@pytest.mark.parametrize("fault", ["count", "spread", "dropped", "absent"])
def test_nothing_is_read_where_the_run_cannot_be_aligned(run, fault,
                                                         capsys,
                                                         monkeypatch):
    run = copy.deepcopy(run)
    events = run["recorder"]["events"]
    if fault == "count":
        run["recorder"]["events"] = [
            e for e in events if not (e["name"] == "swirld.stream_ingest"
                                      and e["ts"] > 1e6)]
    elif fault == "spread":
        last = max((e for e in events if e["name"] ==
                    "swirld.stream_ingest"), key=lambda e: e["ts"])
        last["dur"] -= 2000.0           # ends 2 ms early
    elif fault == "dropped":
        run["recorder"]["dropped"] = 1
    ctx, rec = ctx_of(run, passes=[1.0])
    if fault == "absent":
        rec = None
        ctx._program = program._align(ctx.trace, rec)
    monkeypatch.setattr(program, "recorder", lambda: rec)
    assert program.read(ctx, rec) is None
    for m in ("host_ms_p50.live", "host_idle_ms_per_call.catchup"):
        assert reader(m)(ctx) is None
    log = capsys.readouterr().err
    assert "[program]" in log
    if fault != "absent":
        assert f'"unaligned": "{fault}"' in log


@pytest.mark.parametrize("fault", ["count", "spread", "dropped", "absent"])
def test_counters_are_read_without_the_alignment(run, fault, monkeypatch,
                                                 capsys):
    """Each call's record holds its own counts: the probes per span read
    the same whether or not the spans align, and nothing without a
    recorder."""
    run = copy.deepcopy(run)
    events = run["recorder"]["events"]
    if fault == "count":
        # a call's outermost span missing: its pass record still counts
        run["recorder"]["events"] = [
            e for e in events if not (e["name"] == "swirld.stream_ingest"
                                      and e["ts"] > 1e6)]
    elif fault == "spread":
        last = max((e for e in events if e["name"] ==
                    "swirld.stream_ingest"), key=lambda e: e["ts"])
        last["dur"] -= 2000.0
    elif fault == "dropped":
        run["recorder"]["dropped"] = 10
    ctx, rec = ctx_of(run)
    if fault == "absent":
        rec = None
    monkeypatch.setattr(program, "recorder", lambda: rec)
    want = None if fault == "absent" else pytest.approx(4 / 3)
    for m in ("rounds_probes_per_span.catchup",
              "rounds_probes_per_span.live"):
        assert reader(m)(ctx) == want
    assert "[program]" in capsys.readouterr().err


def test_the_parent_program_reads_nothing(monkeypatch):
    """A program without the recorder (the parent of this benchmark
    file) gives None, not an error."""
    from tpu_swirld import obs

    monkeypatch.delattr(obs, "profile_recorder")
    assert program.recorder() is None
