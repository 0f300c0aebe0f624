"""Pallas SSM kernel: interpret-mode parity with the XLA ssm_matrix."""

import jax
import jax.numpy as jnp
import numpy as np

from tpu_swirld.packing import pack_node
from tpu_swirld.sim import make_simulation, run_with_forkers
from tpu_swirld.tpu.pallas_kernels import ssm_matrix_pallas
from tpu_swirld.tpu.pipeline import (
    ancestry, forkseen_matrix, sees_matrix, ssm_matrix,
)

INTERPRET = jax.default_backend() != "tpu"


def _sees_from_sim(n_nodes, turns, seed, forkers=0):
    if forkers:
        sim = run_with_forkers(n_nodes, forkers, turns, seed=seed)
    else:
        sim = make_simulation(n_nodes, seed=seed)
        sim.run(turns)
    node = sim.nodes[0]
    packed = pack_node(node)
    n = packed.n
    n_pad = ((n + 127) // 128) * 128
    parents = np.concatenate(
        [packed.parents, np.full((n_pad - n, 2), -1, np.int32)]
    )
    anc = ancestry(jnp.asarray(parents), block=128, matmul_dtype=jnp.float32)
    creator = np.concatenate(
        [packed.creator, np.zeros((n_pad - n,), np.int32)]
    )
    fseen = forkseen_matrix(
        anc, jnp.asarray(packed.fork_pairs), packed.n_members, jnp.float32
    )
    sees = sees_matrix(anc, fseen, jnp.asarray(creator))
    return packed, sees


def test_pallas_ssm_matches_xla():
    packed, sees = _sees_from_sim(5, 220, seed=3)
    tot = int(packed.stake.sum())
    want = ssm_matrix(
        sees, jnp.asarray(packed.member_table), jnp.asarray(packed.stake),
        tot, jnp.float32,
    )
    got = ssm_matrix_pallas(
        sees, jnp.asarray(packed.member_table), jnp.asarray(packed.stake),
        tot, jnp.float32, tile_m=128, tile_n=128, interpret=INTERPRET,
    )
    assert (np.asarray(got) == np.asarray(want)).all()


def test_pallas_ssm_matches_xla_with_forks_and_stake():
    packed, sees = _sees_from_sim(7, 260, seed=9, forkers=2)
    assert len(packed.fork_pairs) > 0
    tot = int(packed.stake.sum())
    want = ssm_matrix(
        sees, jnp.asarray(packed.member_table), jnp.asarray(packed.stake),
        tot, jnp.float32,
    )
    got = ssm_matrix_pallas(
        sees, jnp.asarray(packed.member_table), jnp.asarray(packed.stake),
        tot, jnp.float32, tile_m=128, tile_n=128, interpret=INTERPRET,
    )
    assert (np.asarray(got) == np.asarray(want)).all()


def test_full_pipeline_with_pallas_ssm_parity():
    """End-to-end: run_consensus with the Pallas SSM seam, oracle parity."""
    from tpu_swirld.tpu.pipeline import run_consensus
    from tests.test_pipeline import assert_parity

    sim = make_simulation(5, seed=17)
    sim.run(250)
    node = sim.nodes[0]
    packed = pack_node(node)
    result = run_consensus(
        packed, node.config, block=128, use_pallas_ssm=True
    )
    assert_parity(node, packed, result)


def test_pallas_ssm_block_matches_xla_block():
    """The Pallas block kernel must equal the XLA ssm_block_stage exactly
    — same sees-slab gathers, same member hops — at ragged edge shapes:
    a row suffix that is not tile-aligned, a single-column batch, and a
    full-height block."""
    from tpu_swirld.tpu.pallas_kernels import ssm_block_pallas
    from tpu_swirld.tpu.pipeline import ssm_block_stage

    packed, sees = _sees_from_sim(5, 220, seed=3)
    tot = int(packed.stake.sum())
    n = sees.shape[0]
    mt = jnp.asarray(packed.member_table)
    stake = jnp.asarray(packed.stake)
    picks = np.linspace(0, packed.n - 1, 100).astype(np.int32)
    cases = [
        (0, n, np.concatenate([picks, np.full(28, -1, np.int32)])),
        (n - 128, 128, picks[:16]),            # suffix block
        (n - 64, 64, picks[:16]),              # sub-tile suffix
        # odd offset + the driver's minimum column batch (one real column
        # bucketed to 16 — the single-event-chunk shape)
        (32, 96, np.concatenate([picks[:1], np.full(15, -1, np.int32)])),
    ]
    for row0, rows, cols in cases:
        want = ssm_block_stage(
            sees, mt, stake, jnp.asarray(cols), np.int32(row0), rows=rows,
            tot_stake=tot, matmul_dtype_name="float32",
        )
        got = ssm_block_pallas(
            sees, mt, stake, jnp.asarray(cols), np.int32(row0), rows=rows,
            tot_stake=tot, matmul_dtype_name="float32",
            tile_m=128, tile_n=128, interpret=INTERPRET,
        )
        assert (np.asarray(got) == np.asarray(want)).all(), (row0, rows)


def test_pallas_bmm_matches_xla():
    """The tiled boolean-matmul hop (ancestry extension) is exact against
    the straight XLA matmul, including a non-128 contraction axis."""
    from tpu_swirld.tpu.pallas_kernels import bmm_or_pallas
    from tpu_swirld.tpu.pipeline import _bmm

    rng = np.random.default_rng(5)
    for p, q, r in [(128, 128, 256), (64, 96, 128), (128, 64, 512)]:
        a = jnp.asarray(rng.random((p, q)) < 0.1)
        b = jnp.asarray(rng.random((q, r)) < 0.1)
        want = _bmm(a, b, jnp.float32)
        got = bmm_or_pallas(a, b, jnp.float32, interpret=INTERPRET)
        assert (np.asarray(got) == np.asarray(want)).all(), (p, q, r)


def test_bmm_or_pallas_counts_xla_fallback():
    """A shape the grid cannot tile takes the XLA matmul, and says so in
    the ambient registry (chip_smoke phase D fails on any such count)."""
    from tpu_swirld import obs as obslib
    from tpu_swirld.tpu.pallas_kernels import bmm_or_pallas
    from tpu_swirld.tpu.pipeline import _bmm

    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.random((64, 40)) < 0.3)
    b = jnp.asarray(rng.random((40, 5)) < 0.3)
    with obslib.enabled() as o:
        got = bmm_or_pallas(a, b, jnp.float32, interpret=INTERPRET)
        bmm_or_pallas(a, a.T, jnp.float32, interpret=INTERPRET)
    assert (np.asarray(got) == np.asarray(_bmm(a, b, jnp.float32))).all()
    counts = {
        dict(m.labels)["shape"]: m.value
        for m in o.registry.metrics() if m.name == "pallas_bmm_fallback"
    }
    assert counts == {"64x40x5": 1}


def test_incremental_with_pallas_block_parity():
    """IncrementalConsensus with the full Pallas extension-kernel bundle
    (ancestry bmm hop + strongly-sees block) as its hot-path backend:
    bit-parity with full recompute."""
    from tpu_swirld.tpu.pallas_kernels import make_extension_kernels
    from tpu_swirld.tpu.pipeline import IncrementalConsensus, run_consensus

    # 5 members + a forker: the forked fused stage's one-hot hop is only
    # n_members wide, which the bmm grid cannot tile — it must fall back
    # to the XLA matmul instead of crashing (small-network regression)
    sim = run_with_forkers(5, 1, 220, seed=17)
    node = sim.nodes[0]
    packed = pack_node(node)
    assert len(packed.fork_pairs) > 0
    events = [node.hg[e] for e in node.order_added]
    stake = [node.stake[m] for m in node.members]
    inc = IncrementalConsensus(
        node.members, stake, node.config, block=64, chunk=64,
        window_bucket=256, prune_min=64,
        extension_kernels=make_extension_kernels(
            interpret=INTERPRET, tile_m=128, tile_n=128
        ),
    )
    for i in range(0, len(events), 80):
        inc.ingest(events[i : i + 80])
    res = inc.result()
    ref = run_consensus(packed, node.config, block=64)
    assert res.order == ref.order
    assert res.famous == ref.famous
    assert (res.round == ref.round).all()
