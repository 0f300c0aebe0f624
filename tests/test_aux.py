"""Aux subsystems: checkpoint/resume, metrics, viz export (SURVEY §5)."""

import json
import struct

import pytest

from tpu_swirld.checkpoint import (
    RETIRED_CONFIG_KEYS, load_node, load_packed, save_node, save_packed,
)
from tpu_swirld.metrics import Metrics, node_gauges
from tpu_swirld.packing import pack_node
from tpu_swirld.sim import make_simulation
from tpu_swirld import viz


def test_packed_checkpoint_roundtrip(tmp_path):
    sim = make_simulation(4, seed=3)
    sim.run(100)
    packed = pack_node(sim.nodes[0])
    p = str(tmp_path / "dag.npz")
    save_packed(p, packed)
    got = load_packed(p)
    for field in (
        "parents", "creator", "seq", "t", "coin", "stake",
        "fork_pairs", "member_table",
    ):
        assert (getattr(got, field) == getattr(packed, field)).all()
    assert got.ids == packed.ids
    assert got.sigs == packed.sigs


def test_node_checkpoint_resume_and_continue(tmp_path):
    sim = make_simulation(4, seed=8)
    sim.run(150)
    node = sim.nodes[1]
    p = str(tmp_path / "node.swck")
    save_node(p, node)
    restored = load_node(
        p, sk=node.sk, pk=node.pk, network=sim.network,
        network_want={m: n.ask_events for m, n in zip(sim.members, sim.nodes)},
    )
    # bit-identical consensus state after replay
    assert restored.consensus == node.consensus
    assert restored.round == node.round
    assert restored.is_witness == node.is_witness
    assert restored.famous == node.famous
    assert restored.consensus_ts == node.consensus_ts
    # and the restored node keeps working: gossip + consensus continue
    peer = next(m for m in sim.members if m != node.pk)
    new_ids = restored.sync(peer, b"resumed")
    restored.consensus_pass(new_ids)
    assert restored.head in restored.hg


def _add_config_keys(path, **extra):
    """Rewrite a node checkpoint's header with extra config keys."""
    with open(path, "rb") as f:
        data = f.read()
    (hlen,) = struct.unpack_from("<I", data, 4)
    meta = json.loads(data[8 : 8 + hlen].decode())
    meta["config"].update(extra)
    header = json.dumps(meta).encode()
    with open(path, "wb") as f:
        f.write(b"SWCK" + struct.pack("<I", len(header)) + header
                + data[8 + hlen :])


def test_node_checkpoint_header_with_retired_config_keys(tmp_path):
    """Headers written while SwirldConfig still had the rounds-span and
    decode settings carry them: they load, and any other unknown config
    key is still refused."""
    sim = make_simulation(4, seed=8)
    sim.run(150)
    node = sim.nodes[1]
    p = str(tmp_path / "node.swck")
    save_node(p, node)
    _add_config_keys(p, fuse_chunks=8, decode_overlap=True,
                     decode_queue_depth=2)
    assert RETIRED_CONFIG_KEYS == (
        "fuse_chunks", "decode_overlap", "decode_queue_depth",
    )
    restored = load_node(p, sk=node.sk, pk=node.pk, network=sim.network)
    assert restored.consensus == node.consensus
    assert restored.famous == node.famous
    _add_config_keys(p, not_a_setting=1)
    with pytest.raises(TypeError, match="not_a_setting"):
        load_node(p, sk=node.sk, pk=node.pk, network=sim.network)


def test_metrics_counters():
    sim = make_simulation(4, seed=2)
    node = sim.nodes[0]
    node.metrics = Metrics()
    sim.run(120)
    snap = node.metrics.snapshot()
    assert snap["n_events_processed"] > 0
    assert snap["s_divide_rounds"] > 0
    assert snap["s_decide_fame"] >= 0
    if node.consensus:
        assert snap["n_events_ordered"] == len(node.consensus)
        assert snap["events_per_sec_to_consensus"] > 0
    g = node_gauges(node)
    assert g["events"] == len(node.hg)
    assert g["decided_round_lag"] >= 0


def test_viz_exports_agree_across_backends():
    from tpu_swirld.tpu.pipeline import run_consensus

    sim = make_simulation(4, seed=5)
    sim.run(150)
    node = sim.nodes[0]
    packed = pack_node(node)
    result = run_consensus(packed, node.config, block=64)
    a = viz.export_state(node=node)
    b = viz.export_state(packed=packed, result=result)
    assert a == b
    # serialized forms render without error
    s = viz.to_json(node=node)
    assert json.loads(s)[0]["creator"] == 0
    dot = viz.to_dot(node=node)
    assert dot.startswith("digraph") and "->" in dot
    lanes = viz.ascii_lanes(node=node)
    assert "m0" in lanes and "height" in lanes


def test_bench_compare_tool(tmp_path):
    """scripts/bench_compare.py: ok within threshold, nonzero on >10%
    throughput regression (opt-in check wiring)."""
    import subprocess
    import sys

    sa = {"envelope": "baseline", "clean": True, "findings": 0}
    old = {"value": 1000.0, "phases": {"pipeline": 1.0},
           "incremental": {"steady_evps": 2000.0}}
    good = {"value": 950.0, "phases": {"pipeline": 1.1},
            "incremental": {"steady_evps": 2100.0}, "scale_audit": sa}
    bad = {"value": 800.0, "phases": {},
           "incremental": {"steady_evps": 2100.0}, "scale_audit": sa}
    po, pg, pb = tmp_path / "o.json", tmp_path / "g.json", tmp_path / "b.json"
    po.write_text(json.dumps(old))
    pg.write_text(json.dumps(good))
    pb.write_text(json.dumps(bad))
    r = subprocess.run(
        [sys.executable, "scripts/bench_compare.py", str(po), str(pg)],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(
        [sys.executable, "scripts/bench_compare.py", str(po), str(pb)],
        capture_output=True, text=True,
    )
    assert r.returncode == 1
    assert "REGRESSION" in r.stdout
    # regression in the incremental metric alone must also fail
    bad_inc = {"value": 1000.0, "phases": {},
               "incremental": {"steady_evps": 1500.0}, "scale_audit": sa}
    pbi = tmp_path / "bi.json"
    pbi.write_text(json.dumps(bad_inc))
    r = subprocess.run(
        [sys.executable, "scripts/bench_compare.py", str(po), str(pbi)],
        capture_output=True, text=True,
    )
    assert r.returncode == 1
