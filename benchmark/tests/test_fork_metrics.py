"""The forked council's metric reader on synthetic engine records, and
the repo's benchmark files as the forked cell leaves them."""

import json
import types

import pytest

from benchmark import spec
from benchmark.trace import program

from .conftest import ROOT

METRIC = "witness_slot_fill.forked"


def record(name="swirld.batch", **counters):
    args = dict(dispatches=9, pulls=4, rounds_probes=3, rounds_units=2,
                columns_added=1, depth=0, **counters)
    return {"name": name, "ph": "X", "ts": 0.0, "dur": 1.0, "args": args}


def read(monkeypatch, events):
    rec = types.SimpleNamespace(events=events, dropped=0)
    monkeypatch.setattr(program, "recorder", lambda: rec)
    ctx = types.SimpleNamespace(trace=None, counters={})
    return spec.Cell("x", 1, {}, {}, [], [], ROOT).reader(METRIC)(ctx)


def test_fill_is_used_over_carried_slots(monkeypatch, capsys):
    calls = [record(fork_pairs=4517, rounds_slots=2019,
                    witness_slots_used=192) for _ in range(3)]
    # a phase span holds no counters and is not read
    calls.append({"name": "swirld.rounds", "ph": "X", "ts": 0.0, "dur": 1.0,
                  "args": {"slots": 2019, "forked": True, "depth": 1}})
    assert read(monkeypatch, calls) == pytest.approx(100 * 192 / 2019)
    assert round(100 * 192 / 2019, 2) == 9.51
    log = capsys.readouterr().err
    line = next(s for s in log.splitlines() if s.startswith("[slots] "))
    assert json.loads(line[len("[slots] "):])["calls"] == [
        [4517, 2019, 192]] * 3


def test_fill_sums_over_the_calls_of_the_window(monkeypatch):
    calls = [record("swirld.pass", fork_pairs=0, rounds_slots=65,
                    witness_slots_used=64),
             record("swirld.pass", fork_pairs=12, rounds_slots=135,
                    witness_slots_used=36)]
    assert read(monkeypatch, calls) == pytest.approx(50.0)


@pytest.mark.parametrize("events", [
    [record(), record("swirld.pass")],      # a program without the counters
    [],                                     # no engine call in the window
], ids=["parent", "empty"])
def test_nothing_is_read_without_the_counters(monkeypatch, events):
    assert read(monkeypatch, events) is None


def test_nothing_is_read_without_a_recorder(monkeypatch):
    monkeypatch.setattr(program, "recorder", lambda: None)
    ctx = types.SimpleNamespace(trace=None, counters={})
    reader = spec.Cell("x", 1, {}, {}, [], [], ROOT).reader(METRIC)
    assert reader(ctx) is None


def test_the_repo_benchmark_is_sound():
    assert spec.validate() == []


def test_the_forked_cell_reads_its_config():
    cell = spec.load_cell("council64f21.catchup")
    cfg = cell.config
    assert (cfg["members"], cfg["forkers"], cfg["fork_prob"]) == (64, 21, 0.05)
    assert cfg["stake"] == [1] * 64 and spec.fork_faults(cfg) == []
    assert cell.traffic["driver"] == "replay"
    assert cell.traffic["engine"] == "batch"
    assert {m["name"] for m in cell.end_to_end} == {"events_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "compile_s", "rounds_probes_per_span.catchup", METRIC}
