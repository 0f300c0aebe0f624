"""The strongly-sees block stages against a plain numpy ∃-z tally.

The b-side ("member slot z sees column w") is built from two slice
gathers (:func:`tpu_swirld.tpu.pipeline.member_cols_block`); these cases
pin both block stages, the fused ``k == 1`` GEMM and the looped member
hops, bit for bit, on a fork-free ancestry slab and on a forked window
whose sees slab differs from its ancestry.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from tpu_swirld.config import SwirldConfig
from tpu_swirld.packing import pack_events
from tpu_swirld.sim import generate_gossip_dag
from tpu_swirld.tpu.pipeline import (
    member_cols_block, prepare_inputs, ssm_block_from_rows_stage,
    ssm_block_stage, ssm_gather_rows_stage, visibility_stage,
)

M = 8
STAKE = np.asarray([1, 2, 3, 1, 2, 3, 1, 5], np.int32)
TOT = int(STAKE.sum())


@functools.lru_cache(maxsize=None)
def _window(kind):
    """(sees slab, member table with extra -1 columns, events packed)."""
    forkers = 2 if kind == "forked" else 0
    members, stake, events, _keys = generate_gossip_dag(
        M, 300, seed=5, n_forkers=forkers, fork_prob=0.25
    )
    packed = pack_events(events, members, stake)
    arrays, statics, _ = prepare_inputs(
        packed, SwirldConfig(n_members=M), block=64,
        matmul_dtype_name="float32",
    )
    anc, sees = visibility_stage(
        jnp.asarray(arrays["parents"]), jnp.asarray(arrays["creator"]),
        jnp.asarray(packed.fork_pairs), n_members=M, block=64,
        matmul_dtype_name="float32",
    )
    anc, sees = np.asarray(anc), np.asarray(sees)
    # a forked window must hold rows whose sees row is not their ancestry
    assert (anc != sees).any() == bool(forkers)
    k0 = packed.member_table.shape[1]
    mt = np.full((M, k0 + 3), -1, np.int32)
    mt[:, :k0] = packed.member_table
    return sees, mt, packed.n


def _table(mt, path):
    if path == "looped":
        return mt
    # one slot per member, one of them empty: the fused single-GEMM path
    mt1 = mt[:, [mt.shape[1] // 4]].copy()
    mt1[3, 0] = -1
    return mt1


def _cols(n_events):
    picks = np.linspace(0, n_events - 1, 21).astype(np.int32)
    return np.concatenate([[-1], picks[::2], [-1, -1], picks[1::2], [-1]])


def _ref_block(sees, mt, cols, row0, rows):
    """Plain ∃-z rule: x strongly sees w iff the stake of the members
    with some event z (x sees z, z sees w) is over two thirds."""
    n = sees.shape[0]
    cv = cols >= 0
    tally = np.zeros((rows, cols.shape[0]), np.int64)
    for m in range(mt.shape[0]):
        z = mt[m][mt[m] >= 0]
        a = sees[row0:row0 + rows][:, z].astype(np.int64)
        b = (sees[z][:, np.clip(cols, 0, n - 1)] & cv[None, :])
        tally += STAKE[m] * ((a @ b.astype(np.int64)) > 0)
    return (3 * tally > 2 * TOT) & cv[None, :]


@pytest.mark.parametrize("kind", ["chain", "forked"])
@pytest.mark.parametrize("path", ["fused_k1", "looped"])
@pytest.mark.parametrize("row0", [0, 192])
def test_ssm_block_stage_matches_numpy(kind, path, row0):
    sees, mt, n_events = _window(kind)
    mt = _table(mt, path)
    cols = _cols(n_events)
    rows = 128
    got = ssm_block_stage(
        jnp.asarray(sees), jnp.asarray(mt), jnp.asarray(STAKE),
        jnp.asarray(cols), np.int32(row0), rows=rows, tot_stake=TOT,
        matmul_dtype_name="float32",
    )
    want = _ref_block(sees, mt, cols, row0, rows)
    np.testing.assert_array_equal(np.asarray(got), want)
    if path == "looped" and row0:
        assert want.any() and not want.all()


@pytest.mark.parametrize("kind", ["chain", "forked"])
@pytest.mark.parametrize("path", ["fused_k1", "looped"])
@pytest.mark.parametrize("row_off", [0, 96])
def test_ssm_block_from_rows_stage_matches_numpy(kind, path, row_off):
    sees, mt, n_events = _window(kind)
    mt = _table(mt, path)
    cols = _cols(n_events)
    row0, gathered, rows = 64, 256, 128
    a_r3 = ssm_gather_rows_stage(
        jnp.asarray(sees), jnp.asarray(mt), np.int32(row0), rows=gathered
    )
    got = ssm_block_from_rows_stage(
        a_r3, jnp.asarray(sees), jnp.asarray(mt), jnp.asarray(STAKE),
        jnp.asarray(cols), np.int32(row_off), rows=rows, tot_stake=TOT,
        matmul_dtype_name="float32",
    )
    want = _ref_block(sees, mt, cols, row0 + row_off, rows)
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kind", ["chain", "forked", "random"])
def test_member_cols_block_equals_element_gather(kind):
    if kind == "random":
        rng = np.random.default_rng(11)
        sees = rng.random((320, 320)) < 0.5
        mt = rng.integers(-80, 320, (M, 12)).astype(np.int32)
        mt[mt < 0] = -1
        cols = np.sort(rng.integers(-8, 320, 40)).astype(np.int32)
        cols[cols < 0] = -1
    else:
        sees, mt, n_events = _window(kind)
        cols = _cols(n_events)
    n = sees.shape[0]
    idx = mt.reshape(-1)
    idxc = np.clip(idx, 0, n - 1)
    want = (
        sees[idxc[:, None], np.clip(cols, 0, n - 1)[None, :]]
        & (idx >= 0)[:, None] & (cols >= 0)[None, :]
    )
    got = member_cols_block(
        jnp.asarray(sees), jnp.asarray(idxc), jnp.asarray(idx >= 0),
        jnp.asarray(cols),
    )
    np.testing.assert_array_equal(np.asarray(got), want)
