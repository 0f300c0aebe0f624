"""The plain reference against the program's oracle, and the control.

The reference imports nothing of the program; these tests may."""

import random

import numpy as np
import pytest

from benchmark import compare, gossip, reference, spec
from benchmark.control import control_readings


def same_shape(hist, members, events, dag_seed, seed, **forks):
    """The copy draws the program's own DAG, with the members relabelled."""
    from tpu_swirld.sim import generate_gossip_dag

    mem, _stake, evs, _keys = generate_gossip_dag(
        members, events, seed=dag_seed, **forks)
    label = list(range(members))
    random.Random(seed).shuffle(label)
    pos = {e.id: i for i, e in enumerate(evs)}
    assert hist.creator.tolist() == [label[mem.index(e.c)] for e in evs]
    assert hist.self_parent.tolist() == [
        pos[e.p[0]] if e.p else -1 for e in evs]
    assert hist.other_parent.tolist() == [
        pos[e.p[1]] if e.p else -1 for e in evs]
    assert hist.payload == [e.d for e in evs]


def fork_pairs(hist) -> int:
    """Pairs of events of one creator at one self-chain position."""
    seq = np.zeros(hist.n, np.int64)
    for x, s in enumerate(hist.self_parent):
        if s >= 0:
            seq[x] = seq[s] + 1
    _, n = np.unique(np.stack([hist.creator, seq]), axis=1,
                     return_counts=True)
    return int((n * (n - 1) // 2).sum())


def equals_the_oracle(hist, seed):
    from tpu_swirld.oracle.node import Node

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


@pytest.mark.parametrize("members,events,seed", [
    (4, 300, 1), (8, 1000, 2), (16, 2000, 3), (5, 800, 2**33 + 5),
])
def test_reference_equals_the_oracle(sim_crypto, members, events, seed):
    hist = gossip.generate(members, events, seed, None, seed)
    same_shape(hist, members, events, seed, seed)
    assert not reference.has_forks(hist)
    equals_the_oracle(hist, seed)


# (members, events, forkers, fork_prob, seed); the DAG's seed is the
# seed's low 31 bits
FORKED = [
    (4, 300, 1, 0.3, 1), (4, 300, 1, 0.3, 2**31 + 2),
    (7, 600, 2, 0.2, 3), (7, 600, 2, 0.2, 2**31 + 9),
    (16, 2000, 5, 0.05, 3), (16, 2000, 5, 0.05, 2**31 + 10),
]


@pytest.mark.parametrize("members,events,forkers,fork_prob,seed", FORKED)
def test_forked_reference_equals_the_oracle(sim_crypto, members, events,
                                            forkers, fork_prob, seed):
    hist = gossip.generate(members, events, seed, None, seed % 2**31,
                           forkers=forkers, fork_prob=fork_prob)
    assert fork_pairs(hist) >= 1 and reference.has_forks(hist)
    equals_the_oracle(hist, seed)


@pytest.mark.parametrize("members,events,forkers,fork_prob,seed",
                         FORKED[1::2])
def test_forked_history_has_the_programs_shape(sim_crypto, members, events,
                                               forkers, fork_prob, seed):
    hist = gossip.generate(members, events, seed, None, seed % 2**31,
                           forkers=forkers, fork_prob=fork_prob)
    same_shape(hist, members, events, seed % 2**31, seed,
               n_forkers=forkers, fork_prob=fork_prob)
    # the forkers are the first members before the relabelling, so every
    # seed forks the same branches
    other = gossip.generate(members, events, seed + 1, None, seed % 2**31,
                            forkers=forkers, fork_prob=fork_prob)
    assert np.array_equal(hist.self_parent, other.self_parent)
    assert np.array_equal(hist.other_parent, other.other_parent)


@pytest.mark.parametrize("members,events,seed", [
    (4, 300, 1), (8, 1000, 2), (16, 2000, 2**31 + 3), (64, 3000, 4),
])
def test_forked_path_gives_the_honest_answers(members, events, seed):
    hist = gossip.generate(members, events, seed, None, seed % 2**31)
    a = reference.consensus(hist)
    b = reference.consensus_forked(hist)
    assert len(a.order) > 0
    assert not any(compare.mismatches(b, a, hist.n).values())
    assert a.order == b.order and a.famous == b.famous
    assert a.max_round == b.max_round


@pytest.mark.parametrize("engine", ["batch", "streaming"])
@pytest.mark.parametrize("forkers", [0, 2])
def test_engines_match_the_reference(engine, forkers):
    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.packing import pack_events
    from tpu_swirld.store import StreamingConsensus
    from tpu_swirld.tpu.pipeline import run_consensus

    hist = gossip.generate(8, 1200, 3, None, 3, forkers=forkers,
                           fork_prob=0.1)
    assert reference.has_forks(hist) == bool(forkers)
    events = gossip.program_events(hist)
    cfg = SwirldConfig(n_members=8)
    if engine == "batch":
        packed = pack_events(events, hist.members)
        assert len(packed.fork_pairs) == fork_pairs(hist)
        res = run_consensus(packed, cfg)
    else:
        inc = StreamingConsensus(hist.members, [1] * 8, cfg)
        for s in range(0, hist.n, 256):
            inc.ingest(events[s:s + 256])
        res = inc.result()
        inc.store.close()
    bad = compare.mismatches(res, reference.consensus(hist), hist.n)
    assert not any(bad.values()), bad


@pytest.mark.parametrize("cell_name,seed", [
    ("toy8.catchup", 1), ("toy8.catchup", 2), ("toy8.live", 3),
    ("toy8f2.catchup", 1), ("toy8f2.catchup", 2**31 + 5)])
def test_control_fails_the_comparison(toy_root, cell_name, seed):
    cell = spec.load_cell(cell_name, toy_root)
    bad = control_readings(cell, seed, 2.0)
    assert any(bad.values()), bad
