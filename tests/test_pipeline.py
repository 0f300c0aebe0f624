"""Bit-parity: the device pipeline must match the oracle exactly.

BASELINE.json north star: identical ``round`` / ``witness`` / ``famous`` /
consensus order.  Each test packs a seeded oracle sim and compares every
output, no tolerance.
"""

import pytest

from tpu_swirld.packing import pack_node
from tpu_swirld.sim import make_simulation, run_with_forkers
from tpu_swirld.tpu.pipeline import run_consensus


def assert_parity(node, packed, result):
    # No history precondition: the deterministic expiry horizon registers
    # straggler witnesses identically on the live oracle and the batch
    # replay, so parity is promised for EVERY history (the old
    # quarantine-free precondition is gone).
    # rounds + witness flags, every event
    for i, eid in enumerate(node.order_added):
        assert result.round[i] == node.round[eid], (
            f"round mismatch at {i}: {result.round[i]} != {node.round[eid]}"
        )
        assert bool(result.is_witness[i]) == bool(node.is_witness[eid]), (
            f"witness mismatch at {i}"
        )
    # fame: over all registered witnesses
    oracle_famous = {
        node.idx[w]: node.famous[w]
        for r, ws in node.wit_list.items()
        for w in ws
    }
    assert result.famous == oracle_famous
    # round received + consensus timestamps for ordered events
    for pos, eid in enumerate(node.consensus):
        i = node.idx[eid]
        assert result.round_received[i] == node.round_received[eid]
        assert result.consensus_ts[i] == node.consensus_ts[eid]
    # the total order itself
    got = [packed.ids[i] for i in result.order]
    assert got == node.consensus


def run_parity(sim_nodes, turns, seed, forkers=0):
    if forkers:
        sim = run_with_forkers(sim_nodes, forkers, turns, seed=seed)
    else:
        sim = make_simulation(sim_nodes, seed=seed)
        sim.run(turns)
    node = sim.nodes[0]
    packed = pack_node(node)
    result = run_consensus(packed, node.config, block=64)
    assert_parity(node, packed, result)
    assert len(node.consensus) > 0, "test must exercise a non-trivial order"
    return sim, node, result


def test_parity_config1_small():
    """BASELINE config 1 shape: 4-member reference sim."""
    run_parity(4, 200, seed=0)


def test_parity_config1_other_seeds():
    run_parity(4, 250, seed=7)
    run_parity(5, 250, seed=11)


def test_parity_16_members():
    """BASELINE config 2 shape (16 members), reduced turns for CI speed."""
    sim, node, result = run_parity(16, 400, seed=2)
    assert result.max_round >= 2


def test_parity_with_forkers():
    """Fork-aware pipeline: parity on a DAG containing real fork pairs."""
    sim = run_with_forkers(n_nodes=7, n_forkers=2, n_turns=300, seed=9)
    node = next(
        n for n in sim.nodes if any(n.has_fork[m] for m in sim.members)
    )
    packed = pack_node(node)
    assert len(packed.fork_pairs) > 0
    result = run_consensus(packed, node.config, block=64)
    assert_parity(node, packed, result)


def test_parity_weighted_stake():
    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.sim import make_simulation

    cfg = SwirldConfig(n_members=5, stake=(3, 1, 1, 1, 1), seed=4)
    sim = make_simulation(5, seed=4, config=cfg)
    sim.run(250)
    node = sim.nodes[0]
    packed = pack_node(node)
    result = run_consensus(packed, node.config, block=64)
    assert_parity(node, packed, result)


@pytest.mark.slow
def test_parity_config2_full():
    """Full BASELINE config 2: 16 members / 2k events."""
    sim = make_simulation(16, seed=2)
    sim.run_until_events(2000)
    node = max(sim.nodes, key=lambda n: len(n.hg))
    packed = pack_node(node)
    result = run_consensus(packed, node.config, block=128)
    assert_parity(node, packed, result)


def test_parity_config4_shape_small():
    """Config-4 adversary shape at reduced scale (12 members, 4 forkers):
    fork trees deep enough to exercise fame + ordering parity."""
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.packing import pack_events
    from tpu_swirld.sim import generate_gossip_dag

    members, stake, events, keys = generate_gossip_dag(
        12, 1200, seed=4, n_forkers=4
    )
    packed = pack_events(events, members, stake)
    assert len(packed.fork_pairs) > 0
    node = Node(
        sk=keys[0][1], pk=members[0], network={}, members=members,
        clock=lambda: 0, create_genesis=False,
    )
    new_ids = [ev.id for ev in events if node.add_event(ev)]
    node.consensus_pass(new_ids)
    assert len(node.consensus) > 0, "fame/order must be exercised"
    assert sum(node.has_fork[m] for m in members) > 0
    result = run_consensus(packed, node.config)
    assert_parity(node, packed, result)


@pytest.mark.slow
def test_parity_config4_64m_f21():
    """BASELINE config 4: 64 members, f=21 forkers — fork-detection parity
    at scale (reduced event count: the pure-Python oracle is the limiter)."""
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.packing import pack_events
    from tpu_swirld.sim import generate_gossip_dag

    members, stake, events, keys = generate_gossip_dag(
        64, 4000, seed=4, n_forkers=21
    )
    packed = pack_events(events, members, stake)
    assert len(packed.fork_pairs) > 100
    node = Node(
        sk=keys[0][1], pk=members[0], network={}, members=members,
        clock=lambda: 0, create_genesis=False,
    )
    new_ids = [ev.id for ev in events if node.add_event(ev)]
    node.consensus_pass(new_ids)
    result = run_consensus(packed, node.config)
    assert_parity(node, packed, result)
    assert sum(node.has_fork[m] for m in members) >= 15


def test_columns_mode_matches_full():
    """The column-restricted strongly-sees path must equal the full-matrix
    path exactly (and both equal the oracle)."""
    sim = make_simulation(6, seed=19)
    sim.run(300)
    node = sim.nodes[0]
    packed = pack_node(node)
    a = run_consensus(packed, node.config, block=64, ssm_mode="full")
    b = run_consensus(packed, node.config, block=64, ssm_mode="columns")
    assert (a.round == b.round).all()
    assert (a.is_witness == b.is_witness).all()
    assert a.famous == b.famous
    assert a.order == b.order
    assert (a.round_received == b.round_received).all()
    assert (a.consensus_ts == b.consensus_ts).all()
    assert_parity(node, packed, b)
    assert b.timings["ssm_col_iterations"] < 64, "column loop must converge"


def test_columns_mode_dense_two_member_dag():
    """Degenerate round-per-event DAG (2-member alternating gossip): the
    column loop's retry bound must cover one-round-per-chunk-row density
    (review regression: cap of 64 crashed legal DAGs)."""
    sim = make_simulation(2, seed=0)
    for t in range(400):
        sim.step(t % 2)
    node = sim.nodes[0]
    packed = pack_node(node)
    a = run_consensus(packed, node.config, ssm_mode="full")
    b = run_consensus(packed, node.config, ssm_mode="columns")
    assert a.order == b.order and (a.round == b.round).all()
    assert_parity(node, packed, b)


def test_ssm_mode_validated():
    import pytest as _pytest

    sim = make_simulation(4, seed=1)
    sim.run(40)
    packed = pack_node(sim.nodes[0])
    with _pytest.raises(ValueError):
        run_consensus(packed, ssm_mode="colums")


def test_parity_huge_stake_exact_tally():
    """tot_stake >= 2^24 forces the exact int32 per-creator fame tally
    (the fast f32 path would round) — parity must hold."""
    from tpu_swirld.config import SwirldConfig

    big = 1 << 23
    cfg = SwirldConfig(n_members=4, stake=(big, big, big, big), seed=2)
    sim = make_simulation(4, seed=2, config=cfg)
    sim.run(200)
    node = sim.nodes[0]
    packed = pack_node(node)
    assert int(packed.stake.sum()) >= (1 << 24)
    result = run_consensus(packed, node.config, block=64)
    assert_parity(node, packed, result)
    assert len(node.consensus) > 0


def test_parity_three_members_supermajority_edge():
    """n=3: supermajority needs all... 3*2 > 2*3 means 2-of-3 suffices;
    the smallest population where consensus can advance."""
    sim = make_simulation(3, seed=8)
    sim.run(200)
    node = sim.nodes[0]
    packed = pack_node(node)
    result = run_consensus(packed, node.config, block=64)
    assert_parity(node, packed, result)
    assert len(node.consensus) > 0


def test_pipeline_trivial_dags():
    """Geneses-only and single-member DAGs must not crash either backend."""
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.packing import pack_events
    from tpu_swirld.sim import generate_gossip_dag

    members, stake, events, keys = generate_gossip_dag(4, 4, seed=0)
    packed = pack_events(events, members, stake)   # geneses only
    result = run_consensus(packed, block=64)
    assert list(result.round) == [0, 0, 0, 0]
    assert result.is_witness.all()
    assert result.order == []


def test_parity_with_late_straggler_witness():
    """The killer case for the old node-local quarantine: a straggler
    witness landing in a fame-complete round.  The deterministic expiry
    horizon registers it on every engine, so the live node that received
    it LATE must stay bit-identical to a batch replay AND to a fresh
    observer that ingested the whole DAG at once."""
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.sim import make_straggler_event

    sim = make_simulation(4, seed=0)
    sim.run(220)
    node = sim.nodes[0]
    frozen = node._frozen_round
    assert frozen >= 2, "history must have a committed frontier"
    pk, sk = sim.nodes[1].pk, sim.nodes[1].sk
    ev = make_straggler_event(node, pk, sk, at_round=1)
    assert node.add_event(ev)
    node.consensus_pass([ev.id])
    assert node.round[ev.id] <= frozen
    assert node.is_witness[ev.id]
    assert ev.id in node.late_witnesses, "scenario must exercise the corner"
    assert ev.id in node.wit_slot, "late witness must be fully registered"
    assert node.famous[ev.id] is False, "a true straggler is not famous"
    assert node.horizon_violations == 0
    # batch replay of the same insertion order: bit-identical
    packed = pack_node(node)
    result = run_consensus(packed, node.config, block=64)
    assert_parity(node, packed, result)
    # a fresh observer ingesting everything at once agrees too (arrival
    # order cannot influence the horizon)
    observer = Node(
        sk=node.sk, pk=node.pk, network={}, members=node.members,
        config=node.config, clock=lambda: 0, create_genesis=False,
    )
    new_ids = [e for e in node.order_added if observer.add_event(node.hg[e])]
    observer.consensus_pass(new_ids)
    assert observer.consensus == node.consensus
    assert all(observer.round[e] == node.round[e] for e in node.order_added)
    assert {w: node.famous[w] for w in node.wit_slot} == {
        w: observer.famous[w] for w in observer.wit_slot
    }


def test_overflow_selfheal_fork_storm_smax():
    """A fork-heavy DAG under an under-provisioned witness-slot capacity
    previously died with RuntimeError("witness table overflow"); the
    self-healing grow must double s_max and finish with full parity, with
    the order and rounds of a run whose slots never overflow."""
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.packing import pack_events
    from tpu_swirld.sim import generate_gossip_dag

    members, stake, events, keys = generate_gossip_dag(
        8, 500, seed=4, n_forkers=3, fork_prob=0.4
    )
    packed = pack_events(events, members, stake)
    assert len(packed.fork_pairs) > 0
    node = Node(
        sk=keys[0][1], pk=members[0], network={}, members=members,
        clock=lambda: 0, create_genesis=False,
    )
    new_ids = [ev.id for ev in events if node.add_event(ev)]
    node.consensus_pass(new_ids)
    result = run_consensus(
        packed, node.config, block=64, s_max=len(members) + 1
    )
    assert result.timings["overflow_retries"] >= 1
    assert_parity(node, packed, result)
    ample = run_consensus(packed, node.config, block=64, s_max=packed.n)
    assert ample.timings["overflow_retries"] == 0
    assert result.order == ample.order
    assert (result.round == ample.round).all()


def test_overflow_selfheal_round_clamp():
    """An under-provisioned round window (the chain-clamp failure shape)
    must retry unclamped at config.max_rounds instead of fail-stopping,
    on both the columns and the full-matrix paths.

    Why the clamp itself cannot be beaten naturally (so an explicit tight
    r_max is the honest way to drive this path): every promoted round
    needs witnesses from creators holding > 2/3 of stake, so
    sum_m stake_m * W_m > (2/3) * total * R — some member witnesses at
    least ~2/3 of all R rounds — and strongly-seeing each round's last
    witness forces extra "echo" events per round (~2s-2 events per round
    for an s-member quorum), pushing the LONGEST self-chain to >= R for
    every achievable schedule.  Empirically (3-member rotation attempt):
    max_round 74 vs chain 102.  The heal makes the clamp safe even where
    that argument has gaps (weighted stakes, byzantine shapes)."""
    from tpu_swirld.config import SwirldConfig

    cfg = SwirldConfig(n_members=5, stake=(3, 2, 2, 1, 1), seed=4)
    sim = make_simulation(5, seed=4, config=cfg)
    sim.run(320)
    node = sim.nodes[0]
    packed = pack_node(node)
    assert node.max_round >= 8
    a = run_consensus(packed, node.config, block=64, r_max=4)
    assert a.timings["overflow_retries"] >= 1
    assert_parity(node, packed, a)
    b = run_consensus(
        packed, node.config, block=64, r_max=4, ssm_mode="full"
    )
    assert b.timings["overflow_retries"] >= 1
    assert a.order == b.order and (a.round == b.round).all()


def test_overflow_exhausted_raises_corrected_error():
    """When config.max_rounds itself is too small the error must name the
    genuinely exhausted capacity and the knob that raises it."""
    from tpu_swirld.config import SwirldConfig

    cfg = SwirldConfig(n_members=5, max_rounds=4, seed=4)
    sim = make_simulation(5, seed=4)
    sim.run(320)
    node = sim.nodes[0]
    packed = pack_node(node)
    assert node.max_round >= 4
    with pytest.raises(RuntimeError, match="max_rounds"):
        run_consensus(packed, cfg, block=64)


def test_parity_small_coin_period():
    """coin_period=2 makes every even vote distance a coin round, so the
    signature coin-bit override constantly feeds the tallies — pinning the
    coin-vote path's parity (rarely reached with the default C=6)."""
    from tpu_swirld.config import SwirldConfig

    for seed in (6, 13):
        cfg = SwirldConfig(n_members=5, coin_period=2, seed=seed)
        sim = make_simulation(5, seed=seed, config=cfg)
        sim.run(350)
        node = sim.nodes[0]
        packed = pack_node(node)
        result = run_consensus(packed, node.config, block=64)
        assert_parity(node, packed, result)
        assert len(node.consensus) > 0
