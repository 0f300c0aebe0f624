"""The observability subsystem (tpu_swirld.obs): spans, registry, exporters,
pipeline/gossip instrumentation, disabled-mode overhead, report CLI."""

import json
import os
import subprocess
import sys

import pytest

from tpu_swirld import obs, viz
from tpu_swirld.metrics import Metrics, node_gauges
from tpu_swirld.obs.registry import Registry
from tpu_swirld.obs.report import aggregate_spans, gauge_rows, render_report
from tpu_swirld.obs.tracer import NULL_TRACER, Tracer, load_trace
from tpu_swirld.packing import pack_events
from tpu_swirld.sim import generate_gossip_dag, make_simulation


# ------------------------------------------------------------------ tracer


def test_span_nesting_and_jsonl_roundtrip(tmp_path):
    tr = Tracer()
    with tr.span("outer", n=1) as sp:
        with tr.span("inner"):
            pass
        sp.args["extra"] = "x"
    tr.instant("marker", k=2)
    events = tr.events
    # inner closes first, with depth 1; outer has depth 0 and the args
    inner, outer, marker = events
    assert inner["name"] == "inner" and inner["args"]["depth"] == 1
    assert outer["name"] == "outer" and outer["args"]["depth"] == 0
    assert outer["args"]["n"] == 1 and outer["args"]["extra"] == "x"
    assert outer["dur"] >= inner["dur"] >= 0
    assert outer["ts"] <= inner["ts"]          # outer started first
    assert outer["args"]["wall_s"] > 0          # wall clock recorded
    assert marker["ph"] == "i"
    # JSONL round-trip preserves every event
    p = str(tmp_path / "t.jsonl")
    tr.save(p)
    with open(p) as f:
        lines = [l for l in f.read().splitlines() if l]
    assert len(lines) == len(events)
    assert load_trace(p) == events
    # Chrome-wrapped form loads identically
    pc = str(tmp_path / "t.chrome.json")
    tr.save_chrome(pc)
    assert load_trace(pc) == events


def test_phase_seconds_aggregates_depth0():
    tr = Tracer()
    for _ in range(3):
        with tr.span("a"):
            with tr.span("b"):
                pass
    agg = tr.phase_seconds()
    assert set(agg) == {"a"}
    assert agg["a"] > 0


def test_null_tracer_allocates_nothing():
    # the disabled tracer hands out ONE shared no-op span: no per-call
    # allocation, no recorded events
    s1 = NULL_TRACER.span("x", k=1)
    s2 = NULL_TRACER.span("y")
    assert s1 is s2
    with s1:
        pass
    assert NULL_TRACER.events == []


# ---------------------------------------------------------------- registry


def test_registry_prometheus_text_format():
    reg = Registry()
    reg.counter("syncs").inc(3)
    reg.gauge("lag", {"node": "0"}).set(2.5)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus_text()
    assert "# TYPE syncs counter" in text
    assert "syncs 3" in text
    assert "# TYPE lag gauge" in text
    assert 'lag{node="0"} 2.5' in text
    # histogram: cumulative buckets + +Inf + sum/count
    assert 'lat_bucket{le="0.1"} 1' in text
    assert 'lat_bucket{le="1.0"} 2' in text
    assert 'lat_bucket{le="+Inf"} 3' in text
    assert "lat_count 3" in text
    assert "lat_sum 5.55" in text


def test_registry_json_and_identity():
    reg = Registry()
    c1 = reg.counter("n", {"a": "1"})
    c2 = reg.counter("n", {"a": "1"})
    assert c1 is c2                    # same (name, labels) -> same object
    c1.inc(2)
    assert reg.value("n", {"a": "1"}) == 2
    assert reg.value("missing", default=-1) == -1
    with pytest.raises(TypeError):
        reg.gauge("n", {"a": "1"})     # kind mismatch is an error
    d = json.loads(reg.to_json())
    assert d['n{a="1"}'] == {"kind": "counter", "value": 2}


def test_counter_rejects_decrease():
    reg = Registry()
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)


# ----------------------------------------------- pipeline instrumentation


def _small_packed(n_events=300, n_members=6, seed=4):
    members, stake, events, _keys = generate_gossip_dag(
        n_members, n_events, seed=seed
    )
    return pack_events(events, members, stake)


def test_disabled_mode_pipeline_touches_nothing():
    """Acceptance pin: with tracing off, the pipeline must not touch any
    registry or tracer — zero per-event (and even per-stage) obs work."""
    from tpu_swirld.tpu.pipeline import run_consensus

    packed = _small_packed()
    bystander = obs.Obs()              # exists but is never enabled
    assert obs.current() is None
    res = run_consensus(packed, block=64)
    assert len(res.order) > 0
    assert obs.current() is None       # nothing installed an ambient Obs
    assert len(bystander.registry) == 0
    assert bystander.tracer.events == []


def test_enabled_pipeline_records_stages_and_pad_waste():
    from tpu_swirld.tpu.pipeline import run_consensus

    packed = _small_packed()
    with obs.enabled() as o:
        run_consensus(packed, block=64)
    reg = o.registry
    n_pad = ((packed.n + 63) // 64) * 64
    assert reg.value("pipeline_events") == packed.n
    assert reg.value("pipeline_pad_events") == n_pad - packed.n
    assert reg.value("pipeline_ssm_columns_total") > 0
    assert reg.value("pipeline_chunk_scans_total") > 0
    # per-stage seconds with compile/execute attribution exist
    stages = reg.collect("pipeline_stage_seconds")
    names = {dict(k)["stage"] for k in stages}
    assert "pipeline.visibility_stage" in names
    assert "pipeline.rounds_chunk_stage" in names
    assert "pipeline.fame_order_cols_stage" in names
    spans = {e["name"] for e in o.tracer.spans()}
    assert "swirld.order" in spans


def test_enabled_pipeline_span_count_is_stage_granular():
    """Spans scale with stages/chunks, never with events: 4x the events
    must cost far fewer than 4x-minus-stages extra spans (no per-event
    Python-level span overhead even when ENABLED)."""
    from tpu_swirld.tpu.pipeline import run_consensus

    small = _small_packed(n_events=128, n_members=4, seed=7)
    big = _small_packed(n_events=512, n_members=4, seed=7)
    with obs.enabled() as o1:
        run_consensus(small, block=64)
    with obs.enabled() as o2:
        run_consensus(big, block=64)
    n1 = len(o1.tracer.spans())
    n2 = len(o2.tracer.spans())
    # chunked scanning adds ~(N/chunk) spans; per-event spans would add >384
    assert n2 - n1 < 64
    assert n2 < big.n / 4


def test_obs_save_is_repeatable_without_duplicates(tmp_path):
    o = obs.Obs()
    with o.tracer.span("s"):
        pass
    o.registry.counter("c").inc(1)
    p1, p2 = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    o.save(p1)
    o.registry.counter("c").inc(1)
    o.save(p2)
    # each file: 1 span + 1 counter sample, and the second has fresh values
    e1, e2 = load_trace(p1), load_trace(p2)
    assert len(e1) == 2 and len(e2) == 2
    assert [e["args"]["value"] for e in e2 if e["ph"] == "C"] == [2]
    assert o.tracer.events == [e1[0]]          # tracer itself not mutated


def test_obs_enabled_scope_nests_and_restores():
    assert obs.current() is None
    with obs.enabled() as outer:
        assert obs.current() is outer
        with obs.enabled() as inner:
            assert obs.current() is inner
        assert obs.current() is outer
    assert obs.current() is None


# -------------------------------------------------- gossip + sim plumbing


def test_make_simulation_plumbs_shared_metrics_and_tracer():
    shared = Metrics()
    tr = Tracer()
    sim = make_simulation(4, seed=11, metrics=shared, tracer=tr)
    for n in sim.nodes:
        assert n.metrics is shared
        assert n.tracer is tr
    sim.run(60)
    counts = shared.counts
    assert counts["gossip_syncs"] == 60
    assert counts["gossip_bytes_in"] > 0
    assert counts["gossip_bytes_out"] > 0
    assert counts["gossip_events_received"] > 0
    # oracle phase spans recorded (3 per consensus pass)
    assert len(tr.spans()) == 3 * 60
    # the shim snapshot still has the legacy shape on top of gossip counters
    snap = shared.snapshot()
    assert "s_divide_rounds" in snap and "n_gossip_syncs" in snap


def test_make_simulation_per_node_metrics():
    sim = make_simulation(3, seed=12, metrics=True)
    assert all(n.metrics is not None for n in sim.nodes)
    ms = {id(n.metrics) for n in sim.nodes}
    assert len(ms) == 3                # fresh Metrics per node
    sim.run(30)
    total = sum(n.metrics.counts.get("gossip_syncs", 0) for n in sim.nodes)
    assert total == 30


def test_forker_sims_plumb_metrics():
    from tpu_swirld.sim import run_with_divergent_forkers, run_with_forkers

    shared = Metrics()
    sim = run_with_forkers(5, 1, 80, seed=3, fork_every=5, metrics=shared)
    assert sim.nodes[1].metrics is shared
    assert shared.counts["gossip_syncs"] == 80
    # consistent-order forks propagate through honest gossip -> detections
    assert shared.counts.get("gossip_fork_pairs_detected", 0) > 0

    shared2 = Metrics()
    dsim = run_with_divergent_forkers(5, 1, 60, seed=3, metrics=shared2)
    assert all(n.metrics is shared2 for n in dsim.nodes)
    assert shared2.counts.get("gossip_fork_pairs_detected", 0) > 0


def test_node_gauges_tolerates_partial_nodes():
    class Husk:                        # checkpoint-/backend-shaped stub
        famous = {}

    g = node_gauges(Husk())
    assert g["events"] == 0 and g["orphans_parked"] == 0
    assert g["forks_detected"] == 0 and g["late_witnesses"] == 0
    assert g["horizon_violations"] == 0

    sim = make_simulation(4, seed=2)
    sim.run(60)
    reg = Registry()
    g = node_gauges(sim.nodes[0], registry=reg)
    assert g["events"] == len(sim.nodes[0].hg)
    lab = {"node": sim.nodes[0].pk[:4].hex()}
    assert reg.value("node_events", lab) == g["events"]
    assert g["orphans_parked"] == sim.nodes[0].orphans_parked
    # a shared registry keeps every node distinct (default pk-prefix label)
    for n in sim.nodes[1:]:
        node_gauges(n, registry=reg)
    variants = reg.collect("node_events")
    assert len(variants) == 4


# ----------------------------------------------------------- viz gauges


def test_viz_fame_gauges_annotate_and_register():
    sim = make_simulation(4, seed=5)
    sim.run(100)
    node = sim.nodes[0]
    reg = Registry()
    lanes = viz.ascii_lanes(node=node, registry=reg)
    assert "fame decided/witnesses per round:" in lanes
    dot = viz.to_dot(node=node)
    assert dot.startswith("digraph")
    assert "fame per round:" in dot
    rows = viz.export_state(node=node)
    gauges = viz.fame_gauges(rows)
    # every round with witnesses appears; counts match the export
    wit_rounds = {r["round"] for r in rows if r["witness"]}
    assert set(gauges) == wit_rounds
    r0_decided = sum(
        1 for r in rows
        if r["witness"] and r["round"] == 0 and r["famous"] is not None
    )
    assert gauges[0][0] == r0_decided
    assert reg.value("round_fame_decided", {"round": "0"}) == r0_decided


# ------------------------------------------------------------- report CLI


def test_report_aggregation_pure():
    events = [
        {"name": "a", "ph": "X", "ts": 0, "dur": 1000, "args": {"depth": 0}},
        {"name": "a", "ph": "X", "ts": 2000, "dur": 3000, "args": {"depth": 0}},
        {"name": "b", "ph": "X", "ts": 100, "dur": 500, "args": {"depth": 1}},
        {"name": "g", "ph": "C", "ts": 0, "args": {"value": 7, "round": "1"}},
    ]
    rows = aggregate_spans(events)
    a = next(r for r in rows if r["name"] == "a")
    assert a["calls"] == 2 and a["total_ms"] == 4.0 and a["max_ms"] == 3.0
    g = gauge_rows(events)
    assert g == [{"name": "g", "value": 7, "labels": {"round": "1"}}]
    text = render_report(events)
    assert "phase breakdown" in text and "g{round=1}  7" in text


@pytest.mark.smoke
def test_report_cli_smoke(tmp_path):
    """End-to-end: generate a real trace (sim + pipeline under obs), then
    run the actual `python -m tpu_swirld.obs report` CLI on it."""
    from tpu_swirld.tpu.pipeline import run_consensus

    with obs.enabled() as o:
        sim = make_simulation(4, seed=6, metrics=Metrics(registry=o.registry),
                              tracer=o.tracer)
        sim.run(40)
        from tpu_swirld.packing import pack_node

        run_consensus(pack_node(sim.nodes[0]), sim.config, block=64)
        viz.fame_gauges(
            viz.export_state(node=sim.nodes[0]), registry=o.registry
        )
    path = str(tmp_path / "trace.jsonl")
    o.save(path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "tpu_swirld.obs", "report", path],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert r.returncode == 0, r.stderr
    assert "phase breakdown" in r.stdout
    assert "divide_rounds" in r.stdout          # oracle spans made it
    assert "pipeline.visibility_stage" in r.stdout
    assert "gossip_syncs" in r.stdout           # registry snapshot made it
    assert "round_fame_decided" in r.stdout     # viz gauges made it


# --------------------------------- telemetry plane (PR 16): trace identity


def test_pack_unpack_context_roundtrip_and_errors():
    from tpu_swirld.obs.tracer import (
        TRACE_CTX_LEN, pack_context, unpack_context,
    )

    ctx = pack_context(b"8bytesid", 0xDEADBEEF01)
    assert len(ctx) == TRACE_CTX_LEN
    assert unpack_context(ctx) == (b"8bytesid", 0xDEADBEEF01)
    with pytest.raises(ValueError):
        pack_context(b"short", 1)
    with pytest.raises(ValueError):
        unpack_context(ctx + b"x")
    with pytest.raises(ValueError):
        unpack_context(b"")


def test_span_ids_are_process_unique_and_parenting_crosses_processes():
    """The cluster-trace identity model: every enabled span gets a
    pid-folded unique id; span_under parents a local span beneath a
    remote one via the 16-byte wire context; active_context exports the
    innermost traced span for the transport to stamp."""
    from tpu_swirld.obs.tracer import pack_context, unpack_context

    client = Tracer(pid=1000)
    node = Tracer(pid=3)
    root_ctx = pack_context(b"trace-00", 0)   # parent 0 = trace root
    with client.span_under("client.submit", root_ctx) as root:
        wire = client.active_context()
        assert wire is not None
        tid, parent = unpack_context(wire)
        assert tid == b"trace-00" and parent == root.span_id
    # "another process": a different tracer parents under the wire bytes
    with node.span_under("node.submit", wire) as child:
        inner_wire = node.active_context()
        with node.span("node.inner"):   # plain child inherits the trace
            pass
    ev_root = client.events[-1]
    ev_inner, ev_child = node.events[-2], node.events[-1]
    assert ev_root["args"]["span_id"] == root.span_id
    assert ev_root["args"]["trace"] == b"trace-00".hex()
    assert "parent_span_id" not in ev_root["args"]   # root of the trace
    assert ev_child["args"]["parent_span_id"] == root.span_id
    assert ev_child["args"]["trace"] == ev_root["args"]["trace"]
    assert ev_inner["args"]["parent_span_id"] == child.span_id
    assert ev_inner["args"]["trace"] == ev_root["args"]["trace"]
    # ids never collide across processes: pid lives in the upper bits
    assert root.span_id >> 32 == (1000 & 0xFFFF) + 1
    assert child.span_id >> 32 == 3 + 1
    # outside any span there is nothing to stamp
    assert client.active_context() is None
    assert unpack_context(inner_wire)[1] == child.span_id


def test_tracer_event_cap_counts_drops():
    t = Tracer(max_events=2)
    for i in range(5):
        with t.span("s%d" % i):
            pass
    assert len(t.events) == 2 and t.dropped == 3


def test_untraced_spans_carry_no_trace_keys():
    """The pre-PR span shape is preserved: spans outside any trace emit
    span_id (new, additive) but neither trace nor parent-pointer keys
    beyond the local parent."""
    t = Tracer()
    with t.span("plain_outer"):
        with t.span("plain_inner"):
            pass
    inner, outer = t.events
    assert "trace" not in outer["args"] and "trace" not in inner["args"]
    assert "parent_span_id" not in outer["args"]
    assert inner["args"]["parent_span_id"] == outer["args"]["span_id"]
    assert t.active_context() is None


# --------------------------------------- telemetry plane: shard merging


def _shard_event(name, pid, ts, wall_s, span_id, trace=None, parent=None):
    args = {"depth": 0, "wall_s": wall_s, "span_id": span_id}
    if trace is not None:
        args["trace"] = trace
    if parent is not None:
        args["parent_span_id"] = parent
    return {"name": name, "ph": "X", "pid": pid, "tid": 0,
            "ts": ts, "dur": 500.0, "args": args}


def test_cluster_trace_merge_rebases_and_links_cross_process(tmp_path):
    from tpu_swirld.obs import cluster_trace

    trace = "aabbccdd00112233"
    # client shard: epoch ~= wall 100.0, root span of the trace
    client = [_shard_event("client.submit", 1000, 0.0, 100.0, 7,
                           trace=trace)]
    # node shard: different epoch (ts 5000 at wall 100.001) — the merger
    # must rebase both onto one timebase before comparing ts
    node = [
        _shard_event("node.submit", 3, 5000.0, 100.001, 99,
                     trace=trace, parent=7),
        _shard_event("node.local", 3, 6000.0, 100.002, 100, parent=99),
    ]
    (tmp_path / "client.trace.jsonl").write_text(
        "\n".join(json.dumps(e) for e in client) + "\n")
    (tmp_path / "node-0.trace.jsonl").write_text(
        "\n".join(json.dumps(e) for e in node) + "\n")
    out_path = str(tmp_path / "merged.trace.json")
    summary = cluster_trace.merge_dir(str(tmp_path), out_path=out_path)
    assert summary["shards"] == [
        str(tmp_path / "client.trace.jsonl"),
        str(tmp_path / "node-0.trace.jsonl"),
    ]
    assert summary["traces"] == 1
    assert summary["cross_process_traces"] == 1
    assert summary["cross_process_trace_ids"] == [trace]
    info = summary["per_trace"][trace]
    assert info["spans"] == 2 and info["pids"] == [0, 1]
    assert info["edges"] == 1 and info["cross_process_edges"] == 1
    with open(out_path) as f:
        merged = json.load(f)["traceEvents"]
    # shard labels became process_name metadata on renumbered pids
    names = {e["pid"]: e["args"]["name"]
             for e in merged if e.get("ph") == "M"}
    assert names == {0: "client", 1: "n0"}
    # rebasing: node.submit lands ~1000us after client.submit, not -5000
    by_name = {e["name"]: e for e in merged if e.get("ph") == "X"}
    delta = by_name["node.submit"]["ts"] - by_name["client.submit"]["ts"]
    assert delta == pytest.approx(1000.0, abs=1.0)
    # the cross-process edge became a flow arrow pair (s on the parent's
    # pid/ts, f on the child's)
    flows = [e for e in merged if e.get("ph") in ("s", "f")]
    assert [(e["ph"], e["pid"]) for e in flows] == [("s", 0), ("f", 1)]
    assert flows[0]["id"] == flows[1]["id"]


def test_cluster_trace_merge_is_pure_and_empty_dir_ok(tmp_path):
    from tpu_swirld.obs import cluster_trace

    s1 = cluster_trace.merge_dir(str(tmp_path))
    assert s1["events"] == 0 and s1["traces"] == 0
    assert s1["cross_process_traces"] == 0


# ------------------------------- telemetry plane: registry sample plane


def test_registry_samples_roundtrip_merge_and_rollup():
    from tpu_swirld.obs.registry import (
        Registry, merge_node_samples, rollup_node_samples,
    )

    def make(node_scale):
        r = Registry()
        r.counter("tx_accepted").inc(10 * node_scale)
        r.gauge("pending_txs").set(3 * node_scale)
        h = r.histogram("ttf_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5 * node_scale)
        return r

    per_node = {
        "n0": make(1).to_samples(),
        "n1": make(2).to_samples(),
    }
    # load_samples round-trips a registry through its sample form
    r2 = Registry()
    r2.load_samples(per_node["n0"])
    assert r2.to_samples() == per_node["n0"]
    # merged exposition: one family, node label per sample
    text = merge_node_samples(per_node).to_prometheus_text()
    assert 'tx_accepted{node="n0"} 10' in text
    assert 'tx_accepted{node="n1"} 20' in text
    assert 'pending_txs{node="n1"} 6' in text
    # cluster rollup: counters and gauges sum, histograms roll count
    rollup = rollup_node_samples(per_node)
    assert rollup["tx_accepted"] == 30
    assert rollup["pending_txs"] == 9
    assert rollup["ttf_seconds_count"] == 4


# ----------------------------------- telemetry plane: report CLI modes


def test_report_degrades_gracefully_on_bench_artifact(tmp_path, capsys):
    """An old BENCH_*.json (plain result doc, pretty-printed) renders
    n/a sections and exits 0 instead of crashing the CLI."""
    from tpu_swirld.obs.report import main as report_main

    path = str(tmp_path / "BENCH_r99.json")
    with open(path, "w") as f:
        json.dump({
            "n": 1, "cmd": "python bench.py", "rc": 0,
            "parsed": {"metric": "events/sec", "value": 123.0,
                       "unit": "events/s"},
        }, f, indent=2)
    rc = report_main(["report", path])
    out = capsys.readouterr().out
    assert rc == 0
    assert "n/a" in out and "bench artifact" in out
    assert "events/sec: 123.0 events/s" in out
    # a real (single-line JSONL) trace still renders the normal report
    tpath = str(tmp_path / "t.trace.jsonl")
    t = Tracer()
    with t.span("alpha"):
        pass
    t.save(tpath)
    rc = report_main(["report", tpath])
    out = capsys.readouterr().out
    assert rc == 0 and "alpha" in out and "bench artifact" not in out


def test_report_cluster_dir_renders_fleet_with_na_for_old_reports(
    tmp_path, capsys,
):
    from tpu_swirld.obs.report import main as report_main

    # node-0: a current-shape report; node-1: an old report missing the
    # PR 16 keys (trace_events, finality) — must render n/a, not raise
    with open(tmp_path / "node-0.report.json", "w") as f:
        json.dump({
            "node": "n0", "events": 10, "decided": ["aa"], "decided_tx": 4,
            "unclean_start": False, "trace_events": 12, "trace_dropped": 0,
            "finality": {"decided": 1, "rtd_p50": 3.0, "undecided": 2},
            "counters": {"tx_accepted": 4, "tx_shed_pool": 1,
                         "wal_torn_tail_recovered": 0,
                         "node_circuit_opens": 0},
        }, f)
    with open(tmp_path / "node-1.report.json", "w") as f:
        json.dump({"node": "n1", "events": 8, "decided": [],
                   "counters": {}}, f)
    with open(tmp_path / "metrics.json", "w") as f:
        json.dump({"polls": 2, "nodes": {"n0": [], "n1": []},
                   "rollup": {"tx_accepted": 4.0}}, f)
    rc = report_main(["report", "--cluster-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "cluster fleet (2 node reports)" in out
    assert "n/a" in out                          # node-1's missing keys
    assert "tx_accepted" in out and "polls=2" in out
    assert "shed / backpressure" in out
    assert "WAL recovery" in out
    assert "circuit breaker / retries" in out
    assert "merged cross-process trace" in out   # n/a pointer section
    # an empty dir still renders (all n/a) and exits 0
    empty = tmp_path / "empty"
    empty.mkdir()
    rc = report_main(["report", "--cluster-dir", str(empty)])
    out = capsys.readouterr().out
    assert rc == 0 and "no node-*.report.json" in out


# --------------------------------- telemetry plane: lint scope coverage


def test_lint_scopes_cover_new_obs_modules():
    """obs/cluster_trace.py sits inside the SW002 and SW003 scopes; the
    SW003 note scope (here soak.py) takes only a justified suppression."""
    from tpu_swirld.analysis.lint import check_source

    set_iter = "def f(s):\n    for x in {1, 2}:\n        pass\n"
    clock = "import time\n\ndef f():\n    return time.perf_counter(){}\n"
    assert any(
        f.rule == "SW002"
        for f in check_source(set_iter, module_path="obs/cluster_trace.py",
                              rules=["SW002"])
    )
    assert any(
        f.rule == "SW003"
        for f in check_source(
            clock.format(""), module_path="obs/cluster_trace.py",
            rules=["SW003"],
        )
    )
    # note scope: a bare disable is NOT enough in soak.py...
    assert check_source(
        clock.format("   # swirld-lint: disable=SW003"),
        module_path="soak.py", rules=["SW003"],
    )
    # ...a justified one is
    assert check_source(
        clock.format("   # swirld-lint: disable=SW003 -- wall schedule"),
        module_path="soak.py", rules=["SW003"],
    ) == []
    # and the shipped module itself passes the full rule set
    import tpu_swirld.obs as obspkg
    from tpu_swirld.analysis.lint import lint_paths

    base = os.path.dirname(obspkg.__file__)
    findings = lint_paths([os.path.join(base, "cluster_trace.py")])
    assert findings == [], [str(f) for f in findings]
