"""The engines against the plain reference on forked councils, and the
slot counters of the fork-aware rounds scan.

Seeded forked histories come from the benchmark's generator
(``benchmark.gossip``), at the shape of its forked council cut small:
16 members of which 5 fork, ``fork_prob`` 0.05, 1,200 events.  Each goes
through the batch engine (``pack_events`` + ``run_consensus``) and the
streaming engine (64-event syncs, then ``result()``) and is compared with
``benchmark.reference.consensus`` (its fork-aware path) stage by stage.
Both calls run under a JAX profiler session, so their records carry the
counters the benchmark reads: ``fork_pairs``, ``rounds_slots`` and
``witness_slots_used``, and ``rounds_slot_grows``, the in-place slot
grows of the forked batch scan.

Two reference conventions are reached by no generated history at the
benchmark's coin period; :data:`FORKED_COIN2` is a literal DAG that
reaches the second (module doc of ``benchmark.reference``):

- a creator's stake counts once when an event is promoted.  No event can
  strongly see two witnesses of one creator in one round (the members
  through which it sees one of them never see the other), so counting
  each witness instead gives the same rounds on every DAG; the test pins
  that on a DAG whose forker has several witnesses in a round;
- the unique famous witnesses of a round leave out every famous witness
  of a creator with more than one.  On :data:`FORKED_COIN2` the forker
  has two famous witnesses in round 1, and a program that kept them
  disagrees with the reference's order.
"""

import hashlib
import struct

import jax
import numpy as np
import pytest

from benchmark import compare, gossip, reference
from tpu_swirld import crypto, obs
from tpu_swirld.config import SwirldConfig
from tpu_swirld.packing import pack_events
from tpu_swirld.store import StreamingConsensus
from tpu_swirld.tpu import pipeline

MEMBERS, FORKERS, EVENTS, SYNC = 16, 5, 1200, 64

# 4 members, member 3 forks; coin rounds every 2 rounds.  Per event:
# creator, self-parent, other-parent (-1 for a genesis event).
FORKED_COIN2 = dict(
    coin_period=2,
    creator=[
        3, 0, 2, 1, 2, 1, 2, 2, 1, 1, 2, 1, 0, 1, 0, 2, 3, 3, 0, 0, 1, 1, 3,
        3, 3, 1, 3, 3, 1, 3, 3, 2, 1, 0, 3, 0, 3, 1, 3, 1, 3, 1, 0, 2, 1, 0,
        2, 3, 1, 1, 0, 3, 1, 0, 2, 2, 0, 1, 3, 1, 1, 3, 2, 0, 3, 2, 2, 3, 1,
        2, 0, 3, 0, 2, 1, 0, 3, 2],
    self_parent=[
        -1, -1, -1, -1, 2, 3, 4, 6, 5, 8, 7, 9, 1, 11, 12, 10, 0, 0, 14,
        18, 13, 20, 0, 22, 16, 21, 24, 17, 25, 22, 27, 15, 28, 19, 26, 33,
        30, 32, 34, 37, 36, 39, 35, 31, 41, 42, 43, 23, 44, 48, 45, 40, 49,
        50, 46, 54, 53, 52, 23, 57, 59, 51, 55, 56, 58, 62, 65, 38, 60, 66,
        63, 29, 70, 69, 68, 72, 38, 73],
    other_parent=[
        -1, -1, -1, -1, 0, 4, 1, 1, 1, 7, 1, 1, 10, 12, 0, 13, 13, 13, 15,
        13, 19, 15, 19, 15, 21, 19, 15, 19, 15, 15, 15, 26, 23, 23, 31, 23,
        31, 23, 35, 35, 35, 31, 31, 41, 43, 23, 45, 45, 46, 46, 46, 49, 46,
        46, 38, 53, 29, 55, 57, 55, 56, 60, 38, 58, 63, 63, 63, 66, 63, 68,
        69, 70, 61, 72, 67, 47, 74, 74],
)


@pytest.fixture(scope="module")
def sim_crypto():
    """The sim signature scheme the histories' signatures follow, so that
    the oracle accepts their events."""
    before = crypto.backend_name()
    crypto.set_backend("sim")
    yield
    crypto.set_backend(before)


def council(dag_seed):
    return gossip.generate(MEMBERS, EVENTS, 7, None, dag_seed,
                           forkers=FORKERS, fork_prob=0.05)


def literal_history(creator, self_parent, other_parent, seed=1):
    """A history from its parent columns, with the generator's keys, event
    bytes and signatures: event ``i`` has timestamp ``i + 1`` and payload
    ``fork:i`` where it is the second child of its self-parent."""
    m = max(creator) + 1
    members = [gossip.keypair(seed, i)[0] for i in range(m)]
    ids, sigs, payload, children = [], [], [], set()
    for i, (c, s, o) in enumerate(zip(creator, self_parent, other_parent)):
        parents = () if s < 0 else (ids[s], ids[o])
        d = b"" if s < 0 else (b"fork:%d" if s in children else b"tx:%d") % i
        children.add(s)
        pk = members[c]
        body = b"".join((
            struct.pack("<B", len(parents)), *parents,
            struct.pack("<q", i + 1), struct.pack("<I", len(pk)), pk,
            struct.pack("<I", len(d)), d,
        ))
        ids.append(hashlib.blake2b(body, digest_size=32).digest())
        sigs.append(hashlib.blake2b(pk + gossip.DOMAIN_EVENT + body,
                                    digest_size=64).digest())
        payload.append(d)
    return gossip.History(
        members, np.ones(m, np.int64), np.asarray(creator, np.int32),
        np.asarray(self_parent, np.int32), np.asarray(other_parent, np.int32),
        np.arange(1, len(ids) + 1, dtype=np.int64), payload, ids, sigs)


def profiled(fn, path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(path), profiler_options=opts):
        out = fn()
    rec = obs.profile_recorder()
    return out, [e["args"] for e in rec.events
                 if e.get("ph") == "X" and "rounds_probes" in e["args"]], [
        e["args"] for e in rec.events
        if e.get("ph") == "X" and e["name"] == "swirld.rounds"]


def run_batch(hist, config):
    return pipeline.run_consensus(
        pack_events(gossip.program_events(hist), hist.members), config)


def run_streaming(hist, config, sync=SYNC):
    events = gossip.program_events(hist)
    inc = StreamingConsensus(hist.members, [1] * len(hist.members), config)
    emitted = []
    try:
        for s in range(0, hist.n, sync):
            emitted += inc.ingest(events[s:s + sync])["ordered"]
        res = inc.result()
    finally:
        inc.store.close()
    return res, emitted


def fork_pair_count(hist) -> int:
    """Pairs of events of one creator at one self-chain position."""
    seq = np.zeros(hist.n, np.int64)
    for x, s in enumerate(hist.self_parent):
        if s >= 0:
            seq[x] = seq[s] + 1
    _, k = np.unique(np.stack([hist.creator, seq]), axis=1,
                     return_counts=True)
    return int((k * (k - 1) // 2).sum())


def most_witnesses(ref) -> int:
    return int(np.bincount(ref.round[ref.is_witness]).max())


def agrees(res, ref, n, emitted=None):
    bad = compare.mismatches(res, ref, n)
    if emitted is not None:
        bad["emitted_order"] = compare.prefix_mismatches(emitted, ref)
    return not any(bad.values()), bad


@pytest.mark.parametrize("dag_seed", [1, 2])
@pytest.mark.parametrize("engine", ["batch", "streaming"])
def test_engines_match_the_reference_on_a_forked_council(engine, dag_seed,
                                                         tmp_path):
    hist = council(dag_seed)
    pairs = fork_pair_count(hist)
    assert pairs > 0
    ref = reference.consensus(hist)
    assert len(ref.order) > 0
    cfg = SwirldConfig(n_members=MEMBERS)
    if engine == "batch":
        res, records, rounds = profiled(lambda: run_batch(hist, cfg),
                                        tmp_path)
        ok, bad = agrees(res, ref, hist.n)
        assert ok, bad
        (rec,) = records
        # one rounds phase: the packed fork pairs, every pair of the
        # history, and the reference's busiest round
        assert rec["fork_pairs"] == pairs
        assert rec["witness_slots_used"] == most_witnesses(ref)
        assert rec["rounds_slots"] >= rec["witness_slots_used"]
        assert rounds == [dict(rounds[0], slots=rec["rounds_slots"],
                               forked=True)]
    else:
        (res, emitted), records, rounds = profiled(
            lambda: run_streaming(hist, cfg), tmp_path)
        ok, bad = agrees(res, ref, hist.n, emitted)
        assert ok, bad
        # one record per pass; a pass that rebases runs two rounds phases
        assert len(records) == -(-hist.n // SYNC) <= len(rounds)
        for rec in records:
            assert 0 <= rec["fork_pairs"] <= 2 * pairs
            assert rec["rounds_slots"] >= rec["witness_slots_used"]
            assert rec["witness_slots_used"] <= 2 * most_witnesses(ref)
        assert records[-1]["fork_pairs"] > 0
        assert sum(rec["rounds_slots"] for rec in records) <= sum(
            r["slots"] for r in rounds)
        assert rounds[-1]["forked"]


@pytest.mark.parametrize("s_max", [None, 2], ids=["default", "tiny"])
def test_forked_batch_grows_witness_slots_in_place(s_max, tmp_path):
    """The forked batch scan starts below the worst-case slot count (the
    honest bound, or an explicit tiny one), grows in place on each slot
    overflow and re-runs only the overflowing chunk: the same answers, the
    same chunks and columns as a run at the worst case, plus one dropped
    probe per grow."""
    hist = council(1)
    ref = reference.consensus(hist)
    cfg = SwirldConfig(n_members=MEMBERS)
    packed = pack_events(gossip.program_events(hist), hist.members)
    worst = pipeline.prepare_inputs(packed, cfg)[1]["s_max"]
    res, (rec,), rounds = profiled(
        lambda: pipeline.run_consensus(packed, cfg, s_max=s_max),
        tmp_path / "grown")
    _, (base,), _ = profiled(
        lambda: pipeline.run_consensus(packed, cfg, s_max=worst),
        tmp_path / "worst")
    ok, bad = agrees(res, ref, hist.n)
    assert ok, bad
    assert base["rounds_slot_grows"] == 0 and base["rounds_slots"] == worst
    assert rec["rounds_slot_grows"] >= 1
    assert rec["witness_slots_used"] <= rec["rounds_slots"] < worst
    assert [r["slots"] for r in rounds] == [rec["rounds_slots"]]
    # every chunk accepted once: the scan never restarted
    assert rec["rounds_units"] == base["rounds_units"] == -(-hist.n // 128)
    assert rec["columns_added"] == base["columns_added"]
    assert rec["rounds_probes"] == (base["rounds_probes"]
                                    + rec["rounds_slot_grows"])


@pytest.mark.parametrize("engine", ["batch", "streaming"])
def test_an_honest_history_tallies_no_fork_pairs(engine, tmp_path):
    hist = gossip.generate(8, 400, 5, None, 5)
    assert not reference.has_forks(hist)
    cfg = SwirldConfig(n_members=8)
    run = run_batch if engine == "batch" else run_streaming
    _, records, rounds = profiled(lambda: run(hist, cfg), tmp_path)
    assert records and all(r["fork_pairs"] == 0 for r in records)
    assert all(r["rounds_slots"] >= r["witness_slots_used"] > 0
               for r in records)
    assert rounds and not any(r["forked"] for r in rounds)
    # no slot overflow: every rounds phase carries the honest bound
    assert all(r["rounds_slot_grows"] == 0 for r in records)
    assert all(r["slots"] == 8 + 1 for r in rounds)


def oracle(hist, config):
    from tpu_swirld.oracle.node import Node

    evs = gossip.program_events(hist)
    _pk, sk = gossip.keypair(1, 0)
    node = Node(sk=sk, pk=hist.members[0], network={}, members=hist.members,
                config=config, clock=lambda: 0, create_genesis=False)
    node.consensus_pass([e.id for e in evs if node.add_event(e)])
    return node


def oracle_agrees(node, hist, ref):
    pos = {e: i for i, e in enumerate(hist.ids)}
    assert [hist.ids[i] for i in ref.order] == node.consensus
    for e in node.order_added:
        assert ref.round[pos[e]] == node.round[e]
        assert ref.is_witness[pos[e]] == bool(node.is_witness[e])
    assert ref.famous == {pos[w]: node.famous[w]
                          for ws in node.wit_list.values() for w in ws}
    for e, r in node.round_received.items():
        assert ref.round_received[pos[e]] == r
        assert ref.consensus_ts[pos[e]] == node.consensus_ts[e]


def same_creator_witnesses(hist, ref, famous_only=False):
    """Rounds in which one creator has two or more (famous) witnesses."""
    by = {}
    for w in np.flatnonzero(ref.is_witness):
        if not famous_only or ref.famous[int(w)]:
            key = (int(ref.round[w]), int(hist.creator[w]))
            by.setdefault(key, []).append(int(w))
    return {k: v for k, v in by.items() if len(v) > 1}


def keep_every_famous_witness(fam_events, creators):
    return sorted(int(e) for e in fam_events)


@pytest.mark.parametrize("convention", ["stake_once", "unique_famous"])
def test_reference_conventions_on_a_hand_built_dag(convention, sim_crypto,
                                                   monkeypatch):
    dag = dict(FORKED_COIN2)
    cfg = SwirldConfig(n_members=4, coin_period=dag.pop("coin_period"))
    hist = literal_history(**dag)
    assert reference.has_forks(hist)
    ref = reference.consensus(hist, cfg.coin_period)
    assert len(ref.order) > 0
    oracle_agrees(oracle(hist, cfg), hist, ref)
    ok, bad = agrees(run_batch(hist, cfg), ref, hist.n)
    assert ok, bad
    res, emitted = run_streaming(hist, cfg, sync=8)
    ok, bad = agrees(res, ref, hist.n, emitted)
    assert ok, bad
    if convention == "stake_once":
        several = same_creator_witnesses(hist, ref)
        assert several
        vis = reference._Visibility(hist)

        def supermajority(stake):
            return 3 * stake > 2 * len(hist.members)

        for (r, c), ws in several.items():
            later = np.flatnonzero(ref.round > r)
            seen = np.stack([vis.seen_by(w, c) for w in ws])
            hit = vis.strongly(later, seen, hist.stake, supermajority)
            # where no event strongly sees two of them, stake counted per
            # creator and per witness promote alike
            assert hit.sum(1).max() <= 1
    else:
        assert same_creator_witnesses(hist, ref, famous_only=True)
        monkeypatch.setattr(pipeline, "_unique_famous",
                            keep_every_famous_witness)
        ok, bad = agrees(run_batch(hist, cfg), ref, hist.n)
        assert not ok and bad["order"] > 0
        res, emitted = run_streaming(hist, cfg, sync=8)
        assert not agrees(res, ref, hist.n, emitted)[0]
