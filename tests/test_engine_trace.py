"""The engines' phase spans and counters (tpu_swirld.obs engine recorder).

With no profiler and no ambient Obs an engine call records nothing and
enters no annotation.  Under a JAX profiler session the process-wide
recorder holds ``swirld.*`` phase spans nested in each call's
``swirld.pass`` / ``swirld.batch`` record, on the profiler's clock, with
per-call counters that repeat run to run (they count the algorithm, not
time).
"""

import glob
import json
import os

import jax
import pytest

from benchmark.trace import reduce
from tpu_swirld import obs
from tpu_swirld.config import SwirldConfig
from tpu_swirld.packing import pack_events
from tpu_swirld.sim import generate_gossip_dag
from tpu_swirld.store import StreamingConsensus
from tpu_swirld.tpu.pipeline import IncrementalConsensus, run_consensus

MEMBERS = 6


@pytest.fixture(scope="module")
def dag():
    members, stake, events, _keys = generate_gossip_dag(MEMBERS, 360,
                                                        seed=3)
    return members, stake, events


@pytest.fixture(autouse=True)
def fresh_recorder(monkeypatch):
    """Each test starts with no recorder from an earlier session."""
    monkeypatch.setattr(obs, "_profile_tracer", None)
    monkeypatch.setattr(obs, "_profile_session", None)


def run_batch(dag):
    members, stake, events = dag
    return run_consensus(pack_events(events, members, stake), block=64)


def run_incremental(dag):
    members, stake, events = dag
    inc = IncrementalConsensus(members, stake, SwirldConfig(n_members=MEMBERS),
                               chunk=32, window_bucket=256, prune_min=32)
    for s in range(0, len(events), 60):
        inc.ingest(events[s:s + 60])
    return inc.result()


def run_streaming(dag):
    members, stake, events = dag
    inc = StreamingConsensus(members, stake, SwirldConfig(n_members=MEMBERS),
                             chunk=32, window_bucket=256, prune_min=32,
                             ingest_chunk=64)
    try:
        for s in range(0, len(events), 180):
            inc.ingest(events[s:s + 180])
        return inc.result()
    finally:
        inc.store.close()


RUNS = {"batch": run_batch, "incremental": run_incremental,
        "streaming": run_streaming}


def profiled(fn, dag, path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(path), profiler_options=opts):
        fn(dag)
    return obs.profile_recorder()


def spans(rec):
    return [e for e in rec.events if e["ph"] == "X"]


def tallies(rec):
    return [e["args"] for e in spans(rec) if "rounds_probes" in e["args"]]


def test_engine_calls_record_nothing_without_profiler_or_obs(dag,
                                                             monkeypatch):
    made = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(obs, "_annotation", Counting)
    assert obs.current() is None and not Counting.is_enabled()
    for run in RUNS.values():
        run(dag)
    assert obs.recorder() is None
    assert obs.profile_recorder() is None
    assert obs._active.get() is None
    assert made == []


@pytest.mark.parametrize("path", ["batch", "incremental"])
def test_profiler_session_records_nested_phase_spans(dag, tmp_path, path):
    rec = profiled(RUNS[path], dag, tmp_path)
    events = spans(rec)
    names = {e["name"] for e in events}
    assert all(n.startswith("swirld.") for n in names)
    assert not names & set(reduce.SPANS)
    outer = "swirld.batch" if path == "batch" else "swirld.pass"
    want = {"swirld.plan", "swirld.rounds", "swirld.fame", "swirld.order",
            "swirld.wait", "swirld.pack", outer}
    if path == "incremental":
        want |= {"swirld.retire", "swirld.rebase"}
    assert want <= names
    # every phase nests inside one engine call's record
    calls = [e for e in events if e["name"] == outer]
    for e in events:
        if e["name"] in (outer, "swirld.pack") and e["args"]["depth"] == 0:
            continue
        assert e["args"]["depth"] >= 1, e["name"]
        assert any(c["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= c["ts"] + c["dur"] + 1e-3 for c in calls), e["name"]
    for t in tallies(rec):
        assert t["dispatches"] > 0 and t["pulls"] > 0
    assert sum(t["rounds_probes"] for t in tallies(rec)) >= sum(
        t["rounds_units"] for t in tallies(rec)) > 0


def test_streaming_call_holds_its_passes_and_decode_waits(dag, tmp_path):
    rec = profiled(run_streaming, dag, tmp_path)
    events = spans(rec)
    calls = [e for e in events if e["name"] == "swirld.stream_ingest"]
    passes = [e for e in events if e["name"] == "swirld.pass"]
    assert len(calls) == 2 and all(c["args"]["depth"] == 0 for c in calls)
    assert sum(c["args"]["passes"] for c in calls) == len(passes) == 6
    waits = {e["args"]["on"] for e in events if e["name"] == "swirld.wait"}
    assert {"device", "decode"} <= waits


def test_recorded_span_lands_on_the_profiler_clock(dag, tmp_path):
    from jax.profiler import ProfileData

    rec = profiled(run_batch, dag, tmp_path)
    mine = next(e for e in spans(rec) if e["name"] == "swirld.batch")
    start_ns = rec.epoch()["epoch_ns"] + 1e3 * mine["ts"]
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    data = ProfileData.from_file(path)
    t0 = next(dict(p.stats)["profile_start_time"] for p in data.planes
              if p.name == "Task Environment")
    theirs = [ev.start_ns for p in data.planes if p.name.startswith("/host")
              for ln in p.lines for ev in ln.events
              if ev.name == "swirld.batch"]
    assert len(theirs) == 1
    assert abs(t0 + theirs[0] - start_ns) < 1e6


def test_batch_rounds_probes_are_the_registry_chunk_scans(dag):
    with obs.enabled() as o:
        res = run_batch(dag)
    (batch,) = [e["args"] for e in o.tracer.spans()
                if e["name"] == "swirld.batch"]
    scans = o.registry.value("pipeline_chunk_scans_total")
    assert batch["rounds_probes"] == scans == res.timings[
        "ssm_col_iterations"] > 0
    assert o.registry.value("pipeline_rounds_units_total") == batch[
        "rounds_units"]
    assert o.registry.value("pipeline_host_pulls_total") == batch["pulls"]


@pytest.mark.parametrize("path", ["batch", "incremental"])
def test_rounds_counts_repeat_on_a_second_run(dag, tmp_path, path):
    first = profiled(RUNS[path], dag, tmp_path / "a")
    counts = [(t["rounds_probes"], t["rounds_units"], t["columns_added"])
              for t in tallies(first)]
    second = profiled(RUNS[path], dag, tmp_path / "b")
    # a new session starts a fresh recorder
    assert second is not first
    assert [(t["rounds_probes"], t["rounds_units"], t["columns_added"])
            for t in tallies(second)] == counts


def test_profile_recorder_is_bounded_and_counts_drops(dag, tmp_path,
                                                      monkeypatch):
    monkeypatch.setattr(obs, "PROFILE_MAX_EVENTS", 10)
    rec = profiled(run_incremental, dag, tmp_path)
    assert len(rec.events) == 10 and rec.dropped > 0


def test_stage_call_blocks_only_under_an_enabled_obs(dag, tmp_path,
                                                     monkeypatch):
    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: blocked.append(1) or real(x))
    profiled(run_batch, dag, tmp_path)
    assert blocked == []
    with obs.enabled():
        run_batch(dag)
    assert blocked


def test_tracer_exports_its_epoch(tmp_path):
    t = obs.Tracer()
    with t.span("swirld.x"):
        pass
    epoch = t.epoch()
    assert set(epoch) == {"epoch_ns", "epoch_perf_ns"}
    assert abs(epoch["epoch_ns"] * 1e-9 - t.events[0]["args"]["wall_s"]) < 1.0
    path = tmp_path / "t.json"
    t.save_chrome(str(path))
    assert json.loads(path.read_text())["otherData"] == epoch
