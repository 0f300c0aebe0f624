"""The plain reference against the program's oracle, and the control.

The reference imports nothing of the program; these tests may."""

import random

import pytest

from benchmark import compare, gossip, reference, spec
from benchmark.control import control_readings


@pytest.fixture(scope="module")
def sim_crypto():
    from tpu_swirld import crypto

    before = crypto.backend_name()
    crypto.set_backend("sim")
    yield
    crypto.set_backend(before)


@pytest.mark.parametrize("members,events,seed", [
    (4, 300, 1), (8, 1000, 2), (16, 2000, 3), (5, 800, 2**33 + 5),
])
def test_reference_equals_the_oracle(sim_crypto, members, events, seed):
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.sim import generate_gossip_dag

    hist = gossip.generate(members, events, seed, None, seed)
    mem, _stake, evs, _keys = generate_gossip_dag(members, events, seed=seed)
    # the copy draws the program's own DAG, with the members relabelled
    label = list(range(members))
    random.Random(seed).shuffle(label)
    pos = {e.id: i for i, e in enumerate(evs)}
    assert hist.creator.tolist() == [label[mem.index(e.c)] for e in evs]
    assert hist.self_parent.tolist() == [
        pos[e.p[0]] if e.p else -1 for e in evs]
    assert hist.other_parent.tolist() == [
        pos[e.p[1]] if e.p else -1 for e in evs]
    evs = gossip.program_events(hist)
    assert [e.id for e in evs] == hist.ids
    ref = reference.consensus(hist)
    _pk, sk = gossip.keypair(seed, 0)
    node = Node(sk=sk, pk=hist.members[0], network={}, members=hist.members,
                clock=lambda: 0, create_genesis=False)
    node.consensus_pass([e.id for e in evs if node.add_event(e)])
    pos = {e: i for i, e in enumerate(hist.ids)}
    assert [hist.ids[i] for i in ref.order] == node.consensus
    assert len(node.consensus) > 0
    for e in node.order_added:
        assert ref.round[pos[e]] == node.round[e]
        assert ref.is_witness[pos[e]] == bool(node.is_witness[e])
    assert ref.famous == {pos[w]: node.famous[w]
                          for ws in node.wit_list.values() for w in ws}
    for e, r in node.round_received.items():
        assert ref.round_received[pos[e]] == r
        assert ref.consensus_ts[pos[e]] == node.consensus_ts[e]
    assert int((ref.round_received >= 0).sum()) == len(node.round_received)


@pytest.mark.parametrize("engine", ["batch", "streaming"])
def test_engines_match_the_reference(engine):
    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.packing import pack_events
    from tpu_swirld.store import StreamingConsensus
    from tpu_swirld.tpu.pipeline import run_consensus

    hist = gossip.generate(8, 1200, 3, None, 3)
    events = gossip.program_events(hist)
    cfg = SwirldConfig(n_members=8)
    if engine == "batch":
        res = run_consensus(pack_events(events, hist.members), cfg)
    else:
        inc = StreamingConsensus(hist.members, [1] * 8, cfg)
        for s in range(0, hist.n, 256):
            inc.ingest(events[s:s + 256])
        res = inc.result()
        inc.store.close()
    bad = compare.mismatches(res, reference.consensus(hist), hist.n)
    assert not any(bad.values()), bad


def test_reference_refuses_a_forked_history():
    hist = gossip.generate(4, 40, 1, None, 1)
    hist.self_parent[30] = hist.self_parent[hist.self_parent[30]]
    with pytest.raises(ValueError):
        reference.consensus(hist)


@pytest.mark.parametrize("cell_name,seed", [
    ("toy8.catchup", 1), ("toy8.catchup", 2), ("toy8.live", 3)])
def test_control_fails_the_comparison(toy_root, cell_name, seed):
    cell = spec.load_cell(cell_name, toy_root)
    bad = control_readings(cell, seed, 2.0)
    assert any(bad.values()), bad
