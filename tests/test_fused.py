"""Fused rounds-span dispatch + decode overlap: exactness pins.

The incremental rounds scan (``pipeline.rounds_span_stage``) batches up
to ``SPAN_CHUNKS`` packed chunks per dispatch behind a fixpoint
witness-column probe, and the streaming driver's decode worker
pre-hashes the next delta's event ids off-thread behind a drain barrier.
Both are pure latency plays: every output must be bit-identical to one
batch :func:`run_consensus` pass, and to decoding on the ingest thread,
over ANY chunking, fork pattern, rebase, or ragged span tail — commit
boundaries and thread scheduling never influence consensus outputs.
"""

import random

import pytest

from tpu_swirld import obs
from tpu_swirld.config import SwirldConfig
from tpu_swirld.oracle.event import Event
from tpu_swirld.packing import chunk_slices, pack_events
from tpu_swirld.sim import generate_gossip_dag, make_simulation, \
    make_straggler_event
from tpu_swirld.store import StreamingConsensus
from tpu_swirld.tpu.pipeline import (
    SPAN_CHUNKS,
    IncrementalConsensus,
    run_consensus,
)

from tests.test_incremental import assert_same_result


def drive(members, stake, config, chunks, **kw):
    inc = StreamingConsensus(members, stake, config, **kw)
    for chunk in chunks:
        inc.ingest(chunk)
    return inc


def random_chunks(events, seed, sizes=(2, 30, 90, 200)):
    rng = random.Random(seed)
    out, i = [], 0
    while i < len(events):
        c = rng.choice(sizes)
        out.append(events[i : i + c])
        i += c
    return out


class _SpanRecorder:
    """Stage observer: every dispatch's name, and each span's
    ``k_chunks``."""

    def __init__(self):
        self.names = []
        self.spans = []

    def __call__(self, name, fn, args, kw):
        self.names.append(name)
        if name == "pipeline.rounds_span_stage":
            self.spans.append(kw["k_chunks"])

    def __enter__(self):
        obs.set_stage_observer(self)
        return self

    def __exit__(self, *exc):
        obs.set_stage_observer(None)


# ------------------------------------------------------- fused == batch


@pytest.mark.parametrize("ingest_chunk", [512, 576])
def test_fused_vs_batch_random_chunks_with_forks(ingest_chunk):
    """Fused span dispatch vs one batch pass over forked history with
    randomly sized ingest deltas: bit-identical.  With 64-event scan
    chunks, 512 makes each split pass one whole span of SPAN_CHUNKS and
    576 a whole span plus a ragged tail of one chunk."""
    members, stake, events, _keys = generate_gossip_dag(
        12, 1400, seed=4, n_forkers=4
    )
    packed = pack_events(events, members, stake)
    assert len(packed.fork_pairs) > 0
    cfg = SwirldConfig(n_members=12)
    chunks = random_chunks(events, 7, sizes=(2, 30, 90, 200, 1100))
    with _SpanRecorder() as rec:
        fused = drive(
            members, stake, cfg, chunks,
            chunk=64, window_bucket=512, prune_min=128,
            ingest_chunk=ingest_chunk,
        )
    assert SPAN_CHUNKS in rec.spans
    assert_same_result(fused.result(), run_consensus(packed, cfg))


def test_fused_ragged_span_tail():
    """ingest_chunk = SPAN_CHUNKS + 1 scan chunks: after a one-chunk
    cold start, the full delta dispatches one whole span plus a ragged
    tail span of one chunk and the last delta a ragged span of six,
    each with its own static trip count — outputs identical to batch."""
    members, stake, events, _keys = generate_gossip_dag(8, 1000, seed=9)
    cfg = SwirldConfig(n_members=8)
    step = 64 * (SPAN_CHUNKS + 1)
    chunks = [events[:64]] + [
        events[i : i + step] for i in range(64, len(events), step)
    ]
    with _SpanRecorder() as rec:
        fused = drive(
            members, stake, cfg, chunks,
            chunk=64, window_bucket=512, prune_min=128, ingest_chunk=step,
        )
    assert SPAN_CHUNKS in rec.spans and 1 in rec.spans
    assert_same_result(
        fused.result(),
        run_consensus(pack_events(events, members, stake), cfg),
    )


def test_fused_widening_rebase_mid_stream():
    """A stale-view sync referencing long-pruned history: the widening
    rebase re-fetches archived tiles and the fused re-extension over the
    widened window stays bit-identical.  200-event deltas in 64-event
    chunks keep every span ragged (4 chunks)."""
    members, stake, events, keys = generate_gossip_dag(8, 2000, seed=11)
    cfg = SwirldConfig(n_members=8)
    inc = StreamingConsensus(
        members, stake, cfg, chunk=64, window_bucket=256, prune_min=64,
        ingest_chunk=256,
    )
    for i in range(0, len(events), 200):
        inc.ingest(events[i : i + 200])
    assert inc.pruned_prefix > 500
    pk3, sk3 = keys[3]
    head3 = [ev for ev in events if ev.c == pk3][-1]
    old0 = events[100]            # long received, long pruned
    strag = Event(
        d=b"stale-sync", p=(head3.id, old0.id), t=events[-1].t + 1, c=pk3
    ).signed(sk3)
    inc.ingest([strag])
    assert inc.widen_rebases == 1
    packed = pack_events(events + [strag], members, stake)
    assert_same_result(inc.result(), run_consensus(packed, cfg))


def test_fused_full_rebase_straggler_witness():
    """A forged straggler witness below the frozen vote horizon routes
    through the exact full-batch fallback; 50-event deltas in 32-event
    chunks keep every span ragged (2 chunks)."""
    sim = make_simulation(5, seed=23)
    sim.run(260)
    node = sim.nodes[0]
    events = [node.hg[e] for e in node.order_added]
    stake = [node.stake[m] for m in node.members]
    lag = sim.nodes[-1]
    strag = make_straggler_event(node, lag.pk, lag.sk, at_round=1)
    inc = drive(
        node.members, stake, node.config,
        [events[i : i + 50] for i in range(0, len(events), 50)] + [[strag]],
        block=64, chunk=32, window_bucket=256, prune_min=64,
    )
    assert inc.full_rebases >= 1
    packed = pack_events(events + [strag], node.members, stake)
    assert_same_result(
        inc.result(), run_consensus(packed, node.config, block=64)
    )


def test_incremental_pass_dispatches_only_rounds_spans():
    """An incremental pass that does not rebase runs its rounds scan as
    spans of at most SPAN_CHUNKS chunks and never dispatches the batch
    path's per-chunk stage."""
    members, stake, events, _keys = generate_gossip_dag(8, 1200, seed=3)
    cfg = SwirldConfig(n_members=8)
    inc = IncrementalConsensus(
        members, stake, cfg, chunk=64, window_bucket=512, prune_min=128,
    )
    inc.ingest(events[:300])                  # cold start: a batch rebase
    with _SpanRecorder() as rec:
        st = inc.ingest(events[300:])         # 900 events: 15 chunks
    assert not st["rebased"]
    assert "pipeline.rounds_span_stage" in rec.names
    assert "pipeline.rounds_chunk_stage" not in rec.names
    assert max(rec.spans) == SPAN_CHUNKS
    assert min(rec.spans) < SPAN_CHUNKS       # the ragged tail
    assert_same_result(
        inc.result(), run_consensus(pack_events(events, members, stake), cfg)
    )


# -------------------------------------------------- decode overlap parity


def test_async_decode_equals_sync_decode_digest():
    """Worker-thread pre-decode vs decoding on the ingest thread:
    identical consensus outputs AND identical archive blob digests (the
    spill stream is a function of consensus state only, so thread
    scheduling must not reorder or alter a single blob).  The sync side
    feeds the same deltas pre-split into ingest-chunk slices, one ingest
    call each: the same sequence of passes, none handed to the worker."""
    members, stake, events, _keys = generate_gossip_dag(
        10, 1200, seed=2, n_forkers=2
    )
    chunks = random_chunks(events, 5)
    ingest_chunk = 128                        # a multiple of the scan chunk
    kw = dict(chunk=64, window_bucket=512, prune_min=128,
              ingest_chunk=ingest_chunk)
    cfg = SwirldConfig(n_members=10)
    a = drive(members, stake, cfg, chunks, **kw)
    sliced = [
        chunk[s:e]
        for chunk in chunks
        for s, e in chunk_slices(len(chunk), ingest_chunk)
    ]
    b = drive(members, stake, cfg, sliced, **kw)
    assert a.decoded_off_thread > 0       # the worker actually decoded
    assert b.decoded_off_thread == 0
    assert_same_result(a.result(), b.result())
    a.store.close()
    b.store.close()
    assert a.store.archive.digest() == b.store.archive.digest()


class _PoisonEvent:
    """Stand-in whose id computation fails on the decode worker."""

    @property
    def id(self):
        raise RuntimeError("poison id")


def test_decode_worker_failure_reraised_at_barrier():
    """A failure inside the worker's prepare_events surfaces on the
    ingest thread at the drain barrier (future.result()), not as a
    swallowed exception or a hang."""
    members, stake, events, _keys = generate_gossip_dag(8, 400, seed=6)
    inc = StreamingConsensus(
        members, stake, SwirldConfig(n_members=8),
        chunk=64, window_bucket=256, prune_min=64, ingest_chunk=64,
    )
    poisoned = events[:128] + [_PoisonEvent()]
    with pytest.raises(RuntimeError, match="poison id"):
        inc.ingest(poisoned)
