"""The batched device consensus pipeline (JAX / XLA).

This is the TPU-native replacement for the oracle's per-event recursion
(``Node.divide_rounds`` / ``decide_fame`` / ``find_order`` — SURVEY.md §2
#6-8, BASELINE.json north star).  It consumes a :class:`~tpu_swirld.packing.
PackedDAG` and produces **bit-identical** ``round`` / ``is_witness`` /
``famous`` / ``(round_received, consensus_ts)`` outputs; the final total
order additionally applies the signature-whitened hash tiebreak, which is a
host-side byte operation (``run_consensus``).

Phase structure (each phase a pure jittable function; ``consensus_arrays``
fuses them into one jit for the end-to-end device step):

1. ``ancestry`` — reflexive-transitive parent closure as a *blockwise*
   boolean matmul: events are processed in topological blocks; each block's
   internal closure is log2(B) squarings of a B×B adjacency (MXU), then one
   (B×B)@(B×N) matmul propagates the external parent rows.  This is the
   "tiled boolean matrix-power reachability" kernel of SURVEY §5.
2. ``forkseen_matrix`` / ``sees_matrix`` — fork-aware visibility.  Fork
   pairs (same creator+seq, packed on host) poison descendants: ``sees(x,y)
   = anc(x,y) & ~forkseen(x, creator(y))``.
3. ``ssm_matrix`` — strongly-sees via the ∃-z member hop: per member m,
   ``hit_m = (S[:, events_m] @ S[events_m, :]) > 0``; stake-weighted count
   of hitting members crosses the strict-2/3 integer threshold.  Exactly
   the oracle's ``strongly_sees`` (∃-z rule).
4. ``rounds_scan`` — ``lax.scan`` over events in topo order carrying the
   round->witness-slot table: round = max(parent rounds) + promotion,
   witness = first-of-creator-in-round.
5. ``fame_scan`` — ``lax.scan`` over rounds carrying the previous round's
   vote matrix: direct votes at distance 1, stake tallies over strongly-
   seen previous-round witnesses (per-creator OR when forks exist), coin
   rounds take the packed signature middle bit; fame is decided by the
   chronologically first supermajority in a non-coin round.
6. ``order_scan`` — per fame-complete round: unique famous witnesses, the
   all-UFW ancestry test for round-received, and a self-parent chain walk
   producing each UFW's earliest-seeing timestamp; consensus timestamp is
   the lower median.

Expiry horizon: the batch pipeline needs no special handling for
"ancient" straggler witnesses — the deterministic rule (expired iff below
the fame-complete frontier of the event's OWN ancestry, which provably
never fires; see :mod:`tpu_swirld.oracle.node`) means every witness simply
registers in scan order, exactly as the oracle registers it in arrival
order.  That shared rule is what makes live-oracle state and batch replays
bit-identical for EVERY history, stragglers included.

Self-healing: the rounds scan reports witness-table overflow as an
``OVF_ROUND | OVF_SLOT`` bitmask and the host orchestrators grow the
flagged capacity (``_healed_capacities``) — a fork storm or a deeper DAG
than the chain-derived ``r_max`` clamp degrades to a slower pass, never a
``RuntimeError``.  The column path's chunked scan grows a slot overflow
in place and re-runs only the overflowing chunk, so the forked batch path
starts from the honest slot bound and carries what its rounds use.

All supermajorities are exact integer tests ``3*amount > 2*total``.  The
device stays int32-pure: int64 timestamps are dense-ranked on the host
(equal timestamps -> equal ranks, so lower-median selection is exact) and
the median *rank* is mapped back to the int64 value after the kernel.  Bool
matmuls run in ``matmul_dtype`` (bfloat16 on TPU — products are 0/1 and the
MXU accumulates in f32, so counts below 2^24 are exact; float32 on CPU) and
threshold at 0.5.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from tpu_swirld import crypto, obs
from tpu_swirld.config import SwirldConfig
from tpu_swirld.oracle.node import xor_bytes
from tpu_swirld.packing import PackedDAG, Packer

INT32_MAX = np.iinfo(np.int32).max

# Witness-table overflow bitmask (the rounds scan's self-diagnosis, so the
# host can heal the RIGHT capacity instead of fail-stopping): a witness
# landed outside the retained round window (OVF_ROUND) / a round's witness
# slots were exhausted (OVF_SLOT).
OVF_ROUND = 1
OVF_SLOT = 2

# Scan chunks one incremental rounds dispatch covers (rounds_span_stage):
# a pass of n chunks issues ceil(n / SPAN_CHUNKS) spans, the last one
# ragged.  The ragged-tail trip counts are compiled shapes, so changing
# this changes what warm-up lowers.
SPAN_CHUNKS = 8


def _record_shapes(o, *, n: int, n_pad: int, statics: Dict) -> None:
    """Pad-waste + static-shape gauges for one pipeline invocation."""
    g = o.registry
    g.gauge("pipeline_events").set(n)
    g.gauge("pipeline_pad_events").set(n_pad - n)
    g.gauge("pipeline_pad_waste_frac").set(
        round((n_pad - n) / max(n_pad, 1), 6)
    )
    g.gauge("pipeline_s_max").set(statics["s_max"])
    g.gauge("pipeline_block").set(statics["block"])
    # pipeline_r_max is set later, once the chain-trimmed effective bound
    # (the one the witness table actually uses) is known


def default_matmul_dtype():
    return jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16


def _bucket(v: int, m: int) -> int:
    """Round up to a multiple of m (recompile hygiene for static shapes)."""
    return ((max(v, 1) + m - 1) // m) * m


class ShapeContractError(ValueError):
    """A device-kernel shape precondition was violated by the caller.

    Raised explicitly (never ``assert`` — asserts vanish under
    ``python -O``, silently disabling the guard in optimized
    deployments; lint rule SW007) and counted in
    :data:`shape_guard_trips` so harnesses can surface how often the
    guard fired."""


#: lifetime count of ShapeContractError raises in this process (a plain
#: module counter: the guard is load-bearing, the count is observability)
shape_guard_trips = 0


def _shape_guard(ok: bool, message: str) -> None:
    if not ok:
        global shape_guard_trips
        shape_guard_trips += 1
        raise ShapeContractError(message)


def _bmm(a: jnp.ndarray, b: jnp.ndarray, dtype) -> jnp.ndarray:
    """Boolean matmul: OR over products of 0/1 values (exact: f32 accum)."""
    return (
        jnp.matmul(
            a.astype(dtype), b.astype(dtype), preferred_element_type=jnp.float32
        )
        > 0.5
    )


# --------------------------------------------------------------- phase 1


def ancestry(parents: jnp.ndarray, *, block: int, matmul_dtype) -> jnp.ndarray:
    """Reflexive-transitive closure of the parent relation.

    ``parents`` int32[N, 2] with -1 for genesis, topologically ordered
    (parents strictly below), N a multiple of ``block``.  Returns bool[N, N]
    with ``anc[i, j]`` = "j is an ancestor of i" (reflexive).
    """
    n = parents.shape[0]
    _shape_guard(
        n % block == 0,
        f"ancestry: N={n} must be padded to a multiple of block={block}",
    )
    n_blocks = n // block
    n_sq = max(1, math.ceil(math.log2(block)))

    eye = jnp.eye(block, dtype=bool)
    jj = jnp.arange(block)

    def body(k, r):
        s = k * block
        pb = lax.dynamic_slice(parents, (s, 0), (block, 2))      # B,2
        local = pb - s                                           # in-block offset
        adj = (local[:, 0:1] == jj[None, :]) | (local[:, 1:2] == jj[None, :])
        lc = adj | eye
        for _ in range(n_sq):                                    # static unroll
            lc = lc | _bmm(lc, lc, matmul_dtype)
        pc = jnp.clip(pb, 0, n - 1)
        ext = pb >= 0                                            # external iff < s,
        ext = ext & (pb < s)                                     # in-block handled by lc
        g = (r[pc[:, 0]] & ext[:, 0:1]) | (r[pc[:, 1]] & ext[:, 1:2])   # B,N
        rows = _bmm(lc, g, matmul_dtype)                         # B,N
        diag = lax.dynamic_slice(rows, (0, s), (block, block)) | lc
        rows = lax.dynamic_update_slice(rows, diag, (0, s))
        return lax.dynamic_update_slice(r, rows, (s, 0))

    r0 = jnp.zeros((n, n), dtype=bool)
    return lax.fori_loop(0, n_blocks, body, r0)


# --------------------------------------------------------------- phase 2


def forkseen_matrix(
    anc: jnp.ndarray, fork_pairs: jnp.ndarray, n_members: int, matmul_dtype
) -> jnp.ndarray:
    """bool[N, M]: does x have a fork pair by member m among its ancestors?

    ``fork_pairs`` int32[G, 3] rows (member, idx_a, idx_b); G may include
    padding rows with member = -1.
    """
    n = anc.shape[0]
    if fork_pairs.shape[0] == 0:
        return jnp.zeros((n, n_members), dtype=bool)
    mcol = fork_pairs[:, 0]
    a = jnp.clip(fork_pairs[:, 1], 0, n - 1)
    b = jnp.clip(fork_pairs[:, 2], 0, n - 1)
    hit = anc[:, a] & anc[:, b] & (mcol >= 0)[None, :]           # N,G
    onehot = mcol[:, None] == jnp.arange(n_members)[None, :]     # G,M
    return _bmm(hit, onehot, matmul_dtype)


def sees_matrix(
    anc: jnp.ndarray, forkseen: jnp.ndarray, creator: jnp.ndarray
) -> jnp.ndarray:
    """Fork-aware visibility: sees(x, y) = anc(x, y) & ~forkseen(x, c(y))."""
    return anc & ~forkseen[:, creator]


# --------------------------------------------------------------- phase 3


def ssm_matrix(
    sees: jnp.ndarray,
    member_table: jnp.ndarray,
    stake: jnp.ndarray,
    tot_stake: int,
    matmul_dtype,
) -> jnp.ndarray:
    """Strongly-sees matrix (∃-z rule): bool[N, N].

    ``ssm[x, w]`` = members holding a strict 2/3 stake supermajority each
    have an event z with sees(x, z) and sees(z, w).
    """
    n = sees.shape[0]
    n_members, k = member_table.shape

    def body(m, acc):
        idx = member_table[m]                        # K
        valid = idx >= 0
        idxc = jnp.clip(idx, 0, n - 1)
        a = sees[:, idxc] & valid[None, :]           # N,K  (x sees z)
        b = sees[idxc, :] & valid[:, None]           # K,N  (z sees w)
        hit = _bmm(a, b, matmul_dtype)               # N,N
        return acc + stake[m] * hit.astype(jnp.int32)

    acc = lax.fori_loop(0, n_members, body, jnp.zeros((n, n), dtype=jnp.int32))
    return 3 * acc > 2 * tot_stake
# --------------------------------------------------------------- phase 4


def rounds_scan(
    parents: jnp.ndarray,
    ssm: jnp.ndarray,
    creator: jnp.ndarray,
    stake: jnp.ndarray,
    tot_stake: int,
    n_valid: jnp.ndarray,
    *,
    r_max: int,
    s_max: int,
    has_forks: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Round assignment + witness registration (topo-order scan).

    Returns (round int32[N], is_witness bool[N], wit_table int32[r_max,
    s_max], wit_count int32[r_max], overflow int32[] — an OVF_ROUND /
    OVF_SLOT bitmask so the orchestrator can grow the right
    capacity).  Slot order within a round is registration (= topo) order,
    as in the oracle.  (The column-restricted variant runs via
    ``rounds_chunk_stage`` / ``_make_rounds_step`` with a ``col_pos``
    map.)
    """
    step = _make_rounds_step(
        parents, ssm, creator, stake, tot_stake, n_valid,
        jnp.zeros((), dtype=jnp.int32),
        r_max=r_max, s_max=s_max, has_forks=has_forks, col_pos=None,
    )
    n = parents.shape[0]
    carry0 = (
        jnp.zeros((n,), dtype=jnp.int32),
        jnp.zeros((n,), dtype=bool),
        jnp.full((r_max, s_max), -1, dtype=jnp.int32),
        jnp.zeros((r_max,), dtype=jnp.int32),
        jnp.zeros((), dtype=jnp.int32),
    )
    (rnd, wits, tab, cnt, overflow), _ = lax.scan(
        step, carry0, jnp.arange(n)
    )
    return rnd, wits, tab, cnt, overflow


def _make_rounds_step(parents, ssm, creator, stake, tot_stake, n_valid,
                      r_base, *, r_max, s_max, has_forks, col_pos):
    """The shared per-event body of the rounds scan.  Carry:
    (rnd[N], wits[N], wit_table, wit_count, overflow).

    ``rnd`` holds *global* round values; the witness table holds only the
    retained round window — row ``k`` is global round ``r_base + k``
    (``r_base`` a traced scalar so window shifts never retrace).  The
    batch path passes ``r_base = 0``.  ``overflow`` is an int32 OVF_ROUND
    / OVF_SLOT bitmask: an event landing outside the window (including a
    straggler below ``r_base`` in the incremental path) sets OVF_ROUND, a
    full slot row sets OVF_SLOT; the batch orchestrators self-heal by
    growing the flagged capacity, the incremental driver rebases.  The
    body reads ``s_max`` only as the slot bound and the gather width, and
    a ``-1`` slot is invalid, so a table padded with ``-1`` columns scans
    exactly as the narrower one.
    """
    n = parents.shape[0]
    n_members = stake.shape[0]
    marange = jnp.arange(n_members)

    def step(carry, i):
        rnd, wits, tab, cnt, overflow = carry
        p1 = parents[i, 0]
        p2 = parents[i, 1]
        genesis = p1 < 0
        p1c = jnp.maximum(p1, 0)
        p2c = jnp.maximum(p2, 0)
        r0 = jnp.maximum(rnd[p1c], rnd[p2c])
        r0w = r0 - r_base                                   # window row
        r0c = jnp.clip(r0w, 0, r_max - 1)
        widx = tab[r0c]                                     # S
        wvalid = (widx >= 0) & (r0w >= 0) & (r0w < r_max)
        widxc = jnp.clip(widx, 0, n - 1)
        if col_pos is None:
            ss = ssm[i, widxc] & wvalid                     # S
        else:
            wpos = col_pos[widxc]                           # S (-1 = absent)
            ss = (
                ssm[i, jnp.clip(wpos, 0, ssm.shape[1] - 1)]
                & (wpos >= 0)
                & wvalid
            )
        if has_forks:
            wcre = creator[widxc]
            contrib = ((wcre[:, None] == marange[None, :]) & ss[:, None]).any(0)
            amount = jnp.sum(stake * contrib)
        else:
            # no forks packed -> at most one witness per (creator, round)
            amount = jnp.sum(stake[creator[widxc]] * ss)
        promoted = 3 * amount > 2 * tot_stake
        r = jnp.where(genesis, 0, r0 + promoted)
        rw = r - r_base
        is_wit = (genesis | (r > rnd[p1c])) & (i < n_valid)
        overflow = overflow | jnp.where(
            is_wit & ((rw >= r_max) | (rw < 0)), OVF_ROUND, 0
        )
        rc = jnp.clip(rw, 0, r_max - 1)
        slot = cnt[rc]
        overflow = overflow | jnp.where(is_wit & (slot >= s_max), OVF_SLOT, 0)
        do = is_wit & (slot < s_max) & (rw < r_max) & (rw >= 0)
        slotc = jnp.clip(slot, 0, s_max - 1)
        tab = tab.at[rc, slotc].set(jnp.where(do, i, tab[rc, slotc]))
        cnt = cnt.at[rc].add(do.astype(jnp.int32))
        rnd = rnd.at[i].set(jnp.where(i < n_valid, r, 0))
        wits = wits.at[i].set(is_wit)
        return (rnd, wits, tab, cnt, overflow), None

    return step


# --------------------------------------------------------------- phase 5


def fame_scan(
    wit_table: jnp.ndarray,
    sees: jnp.ndarray,
    ssm: jnp.ndarray,
    creator: jnp.ndarray,
    coin: jnp.ndarray,
    stake: jnp.ndarray,
    tot_stake: int,
    coin_period: int,
    matmul_dtype,
    *,
    has_forks: bool,
    col_pos: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Virtual fame voting.  Returns ``(famous, decided_at)``: famous
    int8[r_max*s_max] over witness slots (row-major (round, slot)) — 1
    famous, 0 not, -1 undecided — and decided_at int32[r_max*s_max], the
    (table-local) round index whose tally first decided each slot (-1 for
    undecided slots).  ``decided_at`` lets the incremental driver freeze a
    vote horizon: a decision is final iff no witness later registers in a
    round below it.

    With ``col_pos``, ``ssm`` is column-restricted (every queried column is
    a witness, so the map is total here — guaranteed by the host loop).
    """
    r_max, s_max = wit_table.shape
    n = sees.shape[0]
    n_members = stake.shape[0]
    w_max = r_max * s_max
    # The fast tally multiplies stake values into a float32 matmul; that is
    # exact only while every sum stays below 2^24.  Forks additionally need
    # the per-creator OR.  Otherwise take the int32 per-creator path.
    exact_tally = has_forks or tot_stake >= (1 << 24)

    x_event = wit_table.reshape(-1)                     # W
    x_valid = x_event >= 0
    xe = jnp.clip(x_event, 0, n - 1)
    x_round = jnp.arange(w_max, dtype=jnp.int32) // s_max
    marange = jnp.arange(n_members)

    def step(carry, ry):
        v_prev, famous, dec_at = carry                  # bool[S,W], int8[W]
        y_idx = wit_table[ry]                           # S
        y_valid = y_idx >= 0
        ye = jnp.clip(y_idx, 0, n - 1)
        d = ry - x_round                                # W
        sees_yx = sees[ye][:, xe] & y_valid[:, None] & x_valid[None, :]
        p_idx = wit_table[ry - 1]
        p_valid = p_idx >= 0
        pe = jnp.clip(p_idx, 0, n - 1)
        if col_pos is None:
            ssy = ssm[ye][:, pe]                        # S,S
        else:
            ppos = col_pos[pe]
            ssy = (
                ssm[ye][:, jnp.clip(ppos, 0, ssm.shape[1] - 1)]
                & (ppos >= 0)[None, :]
            )
        ssy = ssy & y_valid[:, None] & p_valid[None, :]
        pcre = creator[pe]                              # S
        pstake = jnp.where(p_valid, stake[pcre], 0)
        if exact_tally:
            # per-creator OR before stake-weighting (forked creators may
            # have several witnesses in round ry-1)
            onehot = (pcre[:, None] == marange[None, :]) & p_valid[:, None]
            w1 = (ssy[:, None, :] & onehot.T[None, :, :]).reshape(
                s_max * n_members, s_max
            )                                           # (S*M),S
            yes_c = _bmm(w1, v_prev, matmul_dtype).reshape(
                s_max, n_members, w_max
            )
            no_c = _bmm(w1, ~v_prev & p_valid[:, None], matmul_dtype).reshape(
                s_max, n_members, w_max
            )
            yes = jnp.sum(yes_c * stake[None, :, None], axis=1)     # S,W int32
            no = jnp.sum(no_c * stake[None, :, None], axis=1)
        else:
            sw = ssy * pstake[None, :]                  # S,S int32
            yes = jnp.matmul(
                sw.astype(jnp.float32),
                v_prev.astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
            no = jnp.matmul(
                sw.astype(jnp.float32),
                (~v_prev & p_valid[:, None]).astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ).astype(jnp.int32)
        v_tally = yes >= no                             # S,W
        super_ = 3 * jnp.maximum(yes, no) > 2 * tot_stake
        is_coin = (d % coin_period) == 0                # W
        coin_y = (coin[ye] > 0)[:, None]                # S,1
        vote = jnp.where(
            (d == 1)[None, :],
            sees_yx,
            jnp.where(is_coin[None, :], jnp.where(super_, v_tally, coin_y), v_tally),
        )
        vote = vote & y_valid[:, None] & x_valid[None, :] & (d >= 1)[None, :]
        eligible = (
            super_
            & y_valid[:, None]
            & (x_valid & (d >= 2) & ~is_coin)[None, :]
        )
        any_dec = eligible.any(0)                       # W
        first_y = jnp.argmax(eligible, axis=0)          # W
        val = v_tally[first_y, jnp.arange(w_max)]
        newly = (famous < 0) & any_dec
        famous = jnp.where(newly, val.astype(jnp.int8), famous)
        dec_at = jnp.where(newly, ry, dec_at)
        return (vote, famous, dec_at), None

    carry0 = (
        jnp.zeros((s_max, w_max), dtype=bool),
        jnp.full((w_max,), -1, dtype=jnp.int8),
        jnp.full((w_max,), -1, dtype=jnp.int32),
    )
    (v_last, famous, dec_at), _ = lax.scan(
        step, carry0, jnp.arange(1, r_max, dtype=jnp.int32)
    )
    return famous, dec_at


# --------------------------------------------------------------- phase 6


def order_scan(
    anc: jnp.ndarray,
    wit_table: jnp.ndarray,
    wit_count: jnp.ndarray,
    famous: jnp.ndarray,
    creator: jnp.ndarray,
    self_parent: jnp.ndarray,
    t_rank: jnp.ndarray,
    max_round: jnp.ndarray,
    n_valid: jnp.ndarray,
    *,
    chain: int,
    received0: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Round-received + consensus timestamp ranks.

    Processes the maximal fame-complete prefix of rounds in ascending
    order; an event is received in the first round whose unique famous
    witnesses all have it as an ancestor; its consensus timestamp is the
    lower median of the UFWs' earliest-seeing self-ancestor timestamps
    (as dense ranks — the host maps ranks back to int64 values).
    Returns (round_received int32[N] (-1 = not received), ts_rank int32[N],
    received bool[N]).

    ``received0`` carries already-received flags from earlier incremental
    passes (those events are skipped; the round indices in the outputs are
    then relative to the carried window's ``r_base``).  ``max_round`` must
    be in the same (local) round frame as the witness table rows.
    """
    r_max, s_max = wit_table.shape
    n = anc.shape[0]
    famous_grid = famous.reshape(r_max, s_max)

    wvalid = wit_table >= 0
    decided = (famous_grid >= 0) | ~wvalid
    complete = decided.all(axis=1) & (
        max_round >= jnp.arange(r_max) + 2
    ) & (wit_count > 0)
    # maximal prefix of fame-complete rounds (cumulative AND)
    prefix = jnp.cumprod(complete.astype(jnp.int32)) > 0

    ev_valid = jnp.arange(n) < n_valid

    def step(carry, r):
        received, rr_out, ts_out = carry
        widx = wit_table[r]
        valid = widx >= 0
        we = jnp.clip(widx, 0, n - 1)
        fam = (famous_grid[r] == 1) & valid             # S
        wcre = creator[we]
        # count famous witnesses per creator via pairwise same-creator sum
        same = (wcre[:, None] == wcre[None, :]) & valid[:, None] & valid[None, :]
        cnt_same = jnp.sum(same & fam[None, :], axis=1)  # S: per slot, count of
        ufw = fam & (cnt_same == 1)                      # famous by same creator
        has = ufw.any()

        # The ancestry test + chain walk + median are by far the scan's
        # dominant cost (O(chain * S * N) gathers); rounds that cannot
        # receive anything — outside the fame-complete prefix, or with no
        # unique famous witness — skip them entirely.  Exact: ``newly``
        # was masked by ``prefix[r] & has`` anyway, so the skipped rounds
        # contributed nothing to the carry.
        def receive_round(c2):
            received, rr_out, ts_out = c2
            anc_rows = anc[we]                           # S,N
            all_see = (anc_rows | ~ufw[:, None]).all(0)  # N
            newly = all_see & ~received & ev_valid

            # earliest-seeing timestamps via self-chain walk (w -> genesis)
            def walk(c3, _):
                cur, tsw = c3
                an = anc[cur]                            # S,N
                tsw = jnp.where(an, t_rank[cur][:, None], tsw)
                nxt = self_parent[cur]
                cur = jnp.where(nxt >= 0, nxt, cur)
                return (cur, tsw), None

            ts0 = jnp.full((s_max, n), INT32_MAX, dtype=jnp.int32)
            (cur, tsw), _ = lax.scan(walk, (we, ts0), None, length=chain)
            tsw = jnp.where(ufw[:, None], tsw, INT32_MAX)  # swirld-lint: disable=SW011 -- masking non-UFW rows TO the sort sentinel is the point: they sort last, and med_i < nv keeps the median strictly below any masked row (the packer bounds live timestamps under INT32_MAX)
            ts_sorted = jnp.sort(tsw, axis=0)            # S,N ascending
            nv = jnp.sum(ufw)
            med_i = jnp.clip((nv - 1) // 2, 0, s_max - 1)
            med = ts_sorted[med_i]                       # N
            return (
                received | newly,
                jnp.where(newly, r, rr_out),
                jnp.where(newly, med, ts_out),
            )

        carry = lax.cond(
            prefix[r] & has, receive_round, lambda c2: c2,
            (received, rr_out, ts_out),
        )
        return carry, None

    carry0 = (
        received0 if received0 is not None else jnp.zeros((n,), dtype=bool),
        jnp.full((n,), -1, dtype=jnp.int32),
        jnp.zeros((n,), dtype=jnp.int32),
    )
    (received, rr_out, ts_out), _ = lax.scan(
        step, carry0, jnp.arange(r_max, dtype=jnp.int32)
    )
    return rr_out, ts_out, received


# ----------------------------------------------------------- fused kernel


def rounds_body(
    parents, creator, stake, fork_pairs, member_table, n_valid, *,
    tot_stake, block, r_max, s_max, has_forks, matmul_dtype_name,
    ssm_fn=None,
):
    """Stage A: ancestry -> sees -> strongly-sees -> rounds/witness scan.

    ``ssm_fn`` overrides the strongly-sees kernel (the FLOP bottleneck) —
    ``tpu_swirld.parallel`` passes the mesh-sharded version.  Jittable.
    """
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    n_members = stake.shape[0]
    anc = ancestry(parents, block=block, matmul_dtype=dt)
    fseen = forkseen_matrix(anc, fork_pairs, n_members, dt)
    sees = sees_matrix(anc, fseen, creator)
    if ssm_fn is None:
        ssm = ssm_matrix(sees, member_table, stake, tot_stake, dt)
    else:
        ssm = ssm_fn(sees, member_table, stake, tot_stake, dt)
    rnd, wits, tab, cnt, overflow = rounds_scan(
        parents, ssm, creator, stake, tot_stake, n_valid,
        r_max=r_max, s_max=s_max, has_forks=has_forks,
    )
    max_round = jnp.max(jnp.where(jnp.arange(rnd.shape[0]) < n_valid, rnd, 0))
    return {
        "anc": anc, "sees": sees, "ssm": ssm, "round": rnd,
        "is_witness": wits, "wit_table": tab, "wit_count": cnt,
        "overflow": overflow, "max_round": max_round,
    }


def fame_order_body(
    anc, sees, ssm, wit_table, wit_count, creator, coin, stake, self_parent,
    t_rank, max_round, n_valid, *,
    tot_stake, coin_period, r_max, s_max, chain, has_forks,
    matmul_dtype_name,
):
    """Stage B: fame fixed point + order extraction over rounds [0, r_max)."""
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    tab = wit_table[:r_max]
    cnt = wit_count[:r_max]
    famous, decided_at = fame_scan(
        tab, sees, ssm, creator, coin, stake, tot_stake, coin_period, dt,
        has_forks=has_forks,
    )
    rr, cts_rank, _received = order_scan(
        anc, tab, cnt, famous, creator, self_parent, t_rank, max_round,
        n_valid, chain=chain,
    )
    return {
        "famous": famous, "fame_decided_at": decided_at,
        "round_received": rr, "consensus_ts_rank": cts_rank,
    }


def consensus_body(
    parents,
    creator,
    t_rank,
    coin,
    stake,
    fork_pairs,
    member_table,
    n_valid,
    *,
    tot_stake: int,
    coin_period: int,
    block: int,
    r_max: int,
    s_max: int,
    chain: int,
    has_forks: bool,
    matmul_dtype_name: str,
    ssm_fn=None,
):
    """End-to-end device consensus: packed arrays -> all consensus outputs.

    Composes :func:`rounds_body` + :func:`fame_order_body` in one trace —
    the fused single-jit form used by the graft entry and the mesh path.
    ``run_consensus`` instead runs the two stages as separate jits so the
    second can be re-bound with a tight ``r_max``.
    """
    a = rounds_body(
        parents, creator, stake, fork_pairs, member_table, n_valid,
        tot_stake=tot_stake, block=block, r_max=r_max, s_max=s_max,
        has_forks=has_forks, matmul_dtype_name=matmul_dtype_name,
        ssm_fn=ssm_fn,
    )
    b = fame_order_body(
        a["anc"], a["sees"], a["ssm"], a["wit_table"], a["wit_count"],
        creator, coin, stake, parents[:, 0], t_rank, a["max_round"], n_valid,
        tot_stake=tot_stake, coin_period=coin_period, r_max=r_max,
        s_max=s_max, chain=chain, has_forks=has_forks,
        matmul_dtype_name=matmul_dtype_name,
    )
    return {
        "round": a["round"],
        "is_witness": a["is_witness"],
        "wit_table": a["wit_table"],
        "wit_count": a["wit_count"],
        "overflow": a["overflow"],
        "max_round": a["max_round"],
        **b,
    }


consensus_arrays = functools.partial(
    jax.jit,
    static_argnames=(
        "tot_stake",
        "coin_period",
        "block",
        "r_max",
        "s_max",
        "chain",
        "has_forks",
        "matmul_dtype_name",
    ),
)(consensus_body)

rounds_stage = functools.partial(
    jax.jit,
    static_argnames=(
        "tot_stake", "block", "r_max", "s_max", "has_forks",
        "matmul_dtype_name",
    ),
)(rounds_body)


# --- column-restricted strongly-sees path (default single-host execution):
# visibility once, then an iterated {ssm columns -> rounds scan} loop on the
# host until every registered witness has a column (exactness certificate),
# then fame/order with the position-mapped restricted matrix.


@functools.partial(
    jax.jit, static_argnames=("n_members", "block", "matmul_dtype_name")
)
def visibility_stage(parents, creator, fork_pairs, *, n_members, block,
                     matmul_dtype_name):
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    anc = ancestry(parents, block=block, matmul_dtype=dt)
    fseen = forkseen_matrix(anc, fork_pairs, n_members, dt)
    sees = sees_matrix(anc, fseen, creator)
    return anc, sees


@functools.partial(jax.jit, static_argnames=("block", "matmul_dtype_name"))
def ancestry_stage(parents, *, block, matmul_dtype_name):
    """Ancestry only — the fork-free visibility fast path: with no fork
    pairs packed, ``sees == anc`` (a pair can only exist once its SECOND
    member is packed, and nothing already packed can descend from it), so
    the sees slab is an *alias* of the ancestry slab and is neither
    computed nor stored."""
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    return ancestry(parents, block=block, matmul_dtype=dt)


def member_cols_block(sees, idxc, valid, cols):
    """The b-side of a strongly-sees block: "member slot z sees column
    w", ``(M*K, C)``, masked by ``valid`` (the slot holds an event) and
    ``cols >= 0``.  ``idxc`` are the slots' clipped window rows.

    Two slice gathers: the C column slices of the slab, then the M*K row
    slices of that ``(W, C)`` strip.  The one element gather
    ``sees[idxc[:, None], cols[None, :]]`` gives the same bits, but XLA
    lowers it to a scalar gather fed by an ``(M*K, C, 2)`` index array:
    at W=18432, M*K=40960, C=512 it took 265 ms on a TPU v5e, the two
    slice gathers 2.4 ms (the column gather transposes the slab)."""
    n = sees.shape[0]
    strip = sees[:, jnp.clip(cols, 0, n - 1)]                # W,C
    return strip[idxc] & valid[:, None] & (cols >= 0)[None, :]


@functools.partial(
    jax.jit,
    static_argnames=("rows", "tot_stake", "matmul_dtype_name"),
)
def ssm_block_stage(sees, member_table, stake, cols, row0, *, rows,
                    tot_stake, matmul_dtype_name):
    """Strongly-sees block for window rows ``[row0, row0 + rows)`` against
    the column events ``cols``, gathered **directly from the sees slab**:
    per member one (rows, K) @ (K, C) ∃-z hop, int32 stake tally,
    strict-2/3 threshold.

    This is the single strongly-sees kernel of the windowed drivers — the
    row-extension pass (new rows × every live column) and the witness-
    column adds (suffix rows × new columns) are the same computation at
    different offsets, so one kernel serves both and the old per-member
    gather slabs (``a3``/``b3``, ~2×M·W·K resident bools) no longer exist:
    the gathers here read tiles of the one sees slab the store budgets.

    Callers exploit structure to keep ``rows``/``C`` tight: rows *below* a
    witness column can never strongly-see it (z would need to be both
    above the row and below the column), so column adds pass only the
    suffix ``[min(cols), hi)``, and the untouched slab region is already
    the exact value (zero).
    """
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    n = sees.shape[0]
    n_members, k = member_table.shape
    idx = member_table.reshape(-1)
    valid = idx >= 0
    idxc = jnp.clip(idx, 0, n - 1)
    col_valid = cols >= 0
    sees_rows = lax.dynamic_slice(sees, (row0, 0), (rows, n))
    a_flat = sees_rows[:, idxc] & valid[None, :]             # rows,M*K
    b_flat = member_cols_block(sees, idxc, valid, cols)      # M*K,C
    if k == 1 and tot_stake < (1 << 24):
        # one member row each: the per-member ∃-z indicator IS the 0/1
        # product, so the whole stake tally collapses into a single
        # (rows, M) @ (M, C) matmul with stake folded into the b-side —
        # exact in f32 while the tally stays below 2^24 (same bound the
        # fame tally relies on), and it replaces M accumulator sweeps
        # over the (rows, C) block with one GEMM.
        acc = jnp.matmul(
            a_flat.astype(jnp.float32),
            b_flat.astype(jnp.float32) * stake[:, None].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
    else:
        a_r3 = a_flat.reshape(rows, n_members, k).transpose(1, 0, 2)
        b_cols = b_flat.reshape(n_members, k, cols.shape[0])

        def body(m, acc):                   # per-member hop; the (rows, C)
            hit = _bmm(a_r3[m], b_cols[m], dt)  # tally stays in the block
            return acc + stake[m] * hit.astype(jnp.int32)

        acc = lax.fori_loop(
            0, n_members, body,
            jnp.zeros((rows, cols.shape[0]), dtype=jnp.int32),
        )
    return (3 * acc > 2 * tot_stake) & col_valid[None, :]


@functools.partial(jax.jit, donate_argnums=(0,))
def update_block_stage(ssm_c, part, row0, col0):
    """Write one computed block into the donated column store."""
    return lax.dynamic_update_slice(ssm_c, part, (row0, col0))


@functools.partial(jax.jit, static_argnames=("rows",))
def ssm_gather_rows_stage(sees, member_table, row0, *, rows):
    """The a-side gather of :func:`ssm_block_stage` alone: per-member
    "x sees z" rows for window rows ``[row0, row0 + rows)``.  The sees
    slab is frozen between a pass's extension and its prune, so the
    incremental driver gathers this ONCE per pass and reuses it across
    every witness-column add of the pass (the gather, not the matmul,
    dominates small column batches)."""
    n = sees.shape[0]
    n_members, k = member_table.shape
    idx = member_table.reshape(-1)
    valid = idx >= 0
    idxc = jnp.clip(idx, 0, n - 1)
    sees_rows = lax.dynamic_slice(sees, (row0, 0), (rows, n))
    return (
        (sees_rows[:, idxc] & valid[None, :])
        .reshape(rows, n_members, k).transpose(1, 0, 2)
    )                                                        # M,rows,K


@functools.partial(
    jax.jit, static_argnames=("rows", "tot_stake", "matmul_dtype_name")
)
def ssm_block_from_rows_stage(a_r3, sees, member_table, stake, cols,
                              row_off, *, rows, tot_stake,
                              matmul_dtype_name):
    """:func:`ssm_block_stage` resumed from a pre-gathered a-side
    (:func:`ssm_gather_rows_stage`): b-side gather + member hops only,
    over the cached rows ``[row_off, row_off + rows)`` (the caller's
    suffix cut — the slice fuses into the member loop, nothing
    re-materializes)."""
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    n = sees.shape[0]
    n_members, k = member_table.shape
    idx = member_table.reshape(-1)
    valid = idx >= 0
    idxc = jnp.clip(idx, 0, n - 1)
    col_valid = cols >= 0
    b_cols = member_cols_block(sees, idxc, valid, cols).reshape(
        n_members, k, cols.shape[0]
    )
    if k == 1 and tot_stake < (1 << 24):
        # fused single-GEMM stake tally (see ssm_block_stage): with one
        # gathered row per member the ∃-z hop is the 0/1 product itself
        a2 = lax.dynamic_slice(
            a_r3, (0, row_off, 0), (n_members, rows, 1)
        ).reshape(n_members, rows)
        acc = jnp.matmul(
            a2.T.astype(jnp.float32),
            b_cols.reshape(n_members, cols.shape[0]).astype(jnp.float32)
            * stake[:, None].astype(jnp.float32),
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)
        return (3 * acc > 2 * tot_stake) & col_valid[None, :]

    def body(m, acc):
        a_m = lax.dynamic_slice(a_r3[m], (row_off, 0), (rows, k))
        hit = _bmm(a_m, b_cols[m], dt)
        return acc + stake[m] * hit.astype(jnp.int32)

    acc = lax.fori_loop(
        0, n_members, body,
        jnp.zeros((rows, cols.shape[0]), dtype=jnp.int32),
    )
    return (3 * acc > 2 * tot_stake) & col_valid[None, :]


def _suffix_rows(row_hi: int, row_lo: int, cap: int):
    """Pick the static suffix-row count for an ssm block: the smallest
    power-of-two ≥ 256 covering ``[row_lo, row_hi)``, clamped to ``cap``
    — a small, session-bounded shape family, so the jit cache stays warm.
    Returns ``(row0, rows)`` with ``row0 ≤ row_lo``."""
    need = max(row_hi - row_lo, 1)
    rows = 256
    while rows < need:
        rows *= 2
    rows = min(rows, cap)
    return max(0, row_hi - rows), rows


def _resume_rounds(parents, ssm_c, col_pos, creator, stake, n_valid,
                   carry, start, r_base, *, tot_stake, r_max, s_max,
                   has_forks, length):
    """The rounds scan resumed from ``carry`` over events
    [start, start+length): the one body both jitted rounds stages
    trace."""
    step = _make_rounds_step(
        parents, ssm_c, creator, stake, tot_stake, n_valid, r_base,
        r_max=r_max, s_max=s_max, has_forks=has_forks, col_pos=col_pos,
    )
    carry, _ = lax.scan(step, carry, start + jnp.arange(length))
    return carry


@functools.partial(
    jax.jit,
    static_argnames=("tot_stake", "r_max", "s_max", "has_forks", "chunk"),
)
def rounds_chunk_stage(parents, ssm_c, col_pos, creator, stake, n_valid,
                       rnd, wits, tab, cnt, overflow, start, r_base, *,
                       tot_stake, r_max, s_max, has_forks, chunk):
    """One chunk of the rounds scan: events [start, start+chunk) resume
    from the carried (rnd, wits, tab, cnt, overflow) state.  Shares the
    per-event body with rounds_scan — used by the column-restricted
    batch path (``_columns_pass``, which the incremental rebase runs
    too).  ``r_base`` (traced) maps global rounds to witness-table rows
    (0 on the batch path)."""
    return _resume_rounds(
        parents, ssm_c, col_pos, creator, stake, n_valid,
        (rnd, wits, tab, cnt, overflow), start, r_base,
        tot_stake=tot_stake, r_max=r_max, s_max=s_max,
        has_forks=has_forks, length=chunk,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tot_stake", "r_max", "s_max", "has_forks", "chunk", "k_chunks",
    ),
    donate_argnums=(6, 7, 8, 9, 10),
)
def rounds_span_stage(parents, ssm_c, col_pos, creator, stake, n_valid,
                      rnd, wits, tab, cnt, overflow, start, r_base, *,
                      tot_stake, r_max, s_max, has_forks, chunk, k_chunks):
    """``k_chunks`` packed chunks of the rounds scan in ONE dispatch —
    the fused megakernel.  Same per-event body as rounds_chunk_stage,
    scan length ``chunk * k_chunks`` (one compiled body either way; the
    trip count is static).  The carry slabs (rnd/wits/tab/cnt/overflow,
    positions 6-10) are donated: callers re-upload the host-mirror carry
    before every probe, so the witness-column fixpoint retry never reads
    a buffer this dispatch consumed."""
    return _resume_rounds(
        parents, ssm_c, col_pos, creator, stake, n_valid,
        (rnd, wits, tab, cnt, overflow), start, r_base,
        tot_stake=tot_stake, r_max=r_max, s_max=s_max,
        has_forks=has_forks, length=chunk * k_chunks,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "tot_stake", "coin_period", "r_max", "s_max", "chain", "has_forks",
        "matmul_dtype_name",
    ),
)
def fame_order_cols_stage(
    anc, sees, ssm_c, col_pos, wit_table, wit_count, creator, coin, stake,
    self_parent, t_rank, max_round, n_valid, *,
    tot_stake, coin_period, r_max, s_max, chain, has_forks,
    matmul_dtype_name,
):
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    tab = wit_table[:r_max]
    cnt = wit_count[:r_max]
    famous, decided_at = fame_scan(
        tab, sees, ssm_c, creator, coin, stake, tot_stake, coin_period, dt,
        has_forks=has_forks, col_pos=col_pos,
    )
    rr, cts_rank, _received = order_scan(
        anc, tab, cnt, famous, creator, self_parent, t_rank, max_round,
        n_valid, chain=chain,
    )
    return {
        "famous": famous, "fame_decided_at": decided_at,
        "round_received": rr, "consensus_ts_rank": cts_rank,
    }

_pallas_rounds_stages = {}


def rounds_stage_pallas(interpret: bool):
    """rounds_stage with the strongly-sees phase as the Pallas kernel."""
    fn = _pallas_rounds_stages.get(interpret)
    if fn is None:
        from tpu_swirld.tpu.pallas_kernels import make_ssm_fn

        fn = functools.partial(
            jax.jit,
            static_argnames=(
                "tot_stake", "block", "r_max", "s_max", "has_forks",
                "matmul_dtype_name",
            ),
        )(functools.partial(rounds_body, ssm_fn=make_ssm_fn(interpret=interpret)))
        _pallas_rounds_stages[interpret] = fn
    return fn

fame_order_stage = functools.partial(
    jax.jit,
    static_argnames=(
        "tot_stake", "coin_period", "r_max", "s_max", "chain", "has_forks",
        "matmul_dtype_name",
    ),
)(fame_order_body)


# ------------------------------------------------------- host orchestration


@dataclasses.dataclass
class ConsensusResult:
    """Host-side view of the device outputs (indices into the PackedDAG)."""

    n: int
    round: np.ndarray            # int32[n]
    is_witness: np.ndarray       # bool[n]
    famous: Dict[int, Optional[bool]]   # witness idx -> fame (None undecided)
    round_received: np.ndarray   # int32[n] (-1 not received)
    consensus_ts: np.ndarray     # int64[n]
    order: List[int]             # final total order (packed indices)
    max_round: int
    timings: Dict[str, float] = dataclasses.field(default_factory=dict)


def _pad_packed(packed: PackedDAG, block: int):
    n = packed.n
    n_pad = ((n + block - 1) // block) * block
    pad = n_pad - n

    def padi(a, fill):
        if pad == 0:
            return a
        shape = (pad,) + a.shape[1:]
        return np.concatenate([a, np.full(shape, fill, a.dtype)], axis=0)

    parents = padi(packed.parents, -1)
    creator = padi(packed.creator, 0)
    seq = padi(packed.seq, 0)
    t = padi(packed.t, 0)
    coin = padi(packed.coin, 0)
    return n_pad, parents, creator, seq, t, coin


def prepare_inputs(
    packed: PackedDAG,
    config: Optional[SwirldConfig] = None,
    *,
    block: int = 128,
    r_max: Optional[int] = None,
    s_max: Optional[int] = None,
    matmul_dtype_name: Optional[str] = None,
):
    """Host prep shared by :func:`run_consensus` and the graft entry:
    block padding, dense timestamp ranks, and the static shape parameters.

    Returns ``(arrays, statics, ts_unique)`` where ``arrays`` holds the
    numpy kernel inputs (keys match the kernel's positional order:
    parents, creator, t_rank, coin, stake, fork_pairs, member_table,
    n_valid) and ``statics`` the keyword shape parameters.
    """
    config = config or SwirldConfig(n_members=packed.n_members)
    if matmul_dtype_name is None:
        matmul_dtype_name = (
            "float32" if jax.default_backend() == "cpu" else "bfloat16"
        )
    n = packed.n
    _n_pad, parents, creator, _seq, t, coin = _pad_packed(packed, block)
    extras = (
        len(set(packed.fork_pairs[:, 2].tolist()))
        if len(packed.fork_pairs)
        else 0
    )
    if s_max is None:
        s_max = packed.n_members + extras + 1
    if r_max is None:
        r_max = int(config.max_rounds)
    chain = int(packed.seq.max()) + 1 if n else 1
    # dense-rank timestamps so the device stays int32-pure (see module doc)
    ts_unique, t_rank = np.unique(t, return_inverse=True)
    t_rank = t_rank.astype(np.int32).reshape(t.shape)
    arrays = {
        "parents": parents,
        "creator": creator,
        "t_rank": t_rank,
        "coin": coin,
        "stake": packed.stake,
        "fork_pairs": packed.fork_pairs,
        "member_table": packed.member_table,
        "n_valid": np.int32(n),
    }
    statics = {
        "tot_stake": int(packed.stake.sum()),
        "coin_period": config.coin_period,
        "block": block,
        "r_max": r_max,
        "s_max": s_max,
        "chain": chain,
        "has_forks": bool(len(packed.fork_pairs)),
        "matmul_dtype_name": matmul_dtype_name,
    }
    return arrays, statics, ts_unique


def _tally_rounds(fork_pairs: int, slots: int, used: int) -> None:
    """Count one rounds phase into the engine call's record: the fork-pair
    rows it ran with, the witness slots per round it carried and the most
    witnesses any of its rounds holds, all host values the caller has."""
    obs.tally("fork_pairs", fork_pairs)
    obs.tally("rounds_slots", slots)
    obs.tally("witness_slots_used", used)


def _fame_slots(wit_count, r_tight: int, s_max: int, fork_pairs: int) -> int:
    """Witness slots per round for the fame/order stage: the most any of
    its ``r_tight`` rounds holds, bucketed.  Slots fill from 0, so the
    cut drops only empty slots.  The forked fame tally costs
    O(S^2 * members * rounds): at the worst-case capacity of config 4
    (S = 2019) that is tens of GB, at the observed count it is small.
    The same pull gives the rounds phase's counters
    (:func:`_tally_rounds`, ``s_max`` the slots the scan carried)."""
    used = int(np.max(obs.to_host(wit_count[:r_tight]), initial=0))
    _tally_rounds(fork_pairs, s_max, used)
    return min(s_max, _bucket(max(used, 1), 8))


def _healed_capacities(ovf: int, *, r_eff: int, r_cap: int, s_eff: int,
                       s_cap: int) -> Tuple[int, int]:
    """Translate a rounds-scan overflow bitmask into grown capacities.

    The self-healing contract (no fail-stop on recoverable capacity
    misses): OVF_ROUND unclamps the witness-table round window straight to
    ``r_cap`` (``config.max_rounds`` — the chain-derived clamp is a
    heuristic, not a theorem the pipeline should die on), OVF_SLOT doubles
    the per-round slot capacity (power-of-two growth keeps the static
    shapes on the existing bucket discipline).  Raises the *corrected*
    error — naming the capacity that is genuinely exhausted and the knob
    that raises it — only when the flagged capacity is already at its hard
    bound.
    """
    r_new, s_new = r_eff, s_eff
    if ovf & OVF_ROUND:
        if r_eff >= r_cap:
            raise RuntimeError(
                f"consensus rounds exceed the round-window capacity "
                f"{r_cap} (the larger of config.max_rounds and any "
                f"explicit r_max); raise SwirldConfig.max_rounds beyond "
                f"{r_cap}"
            )
        r_new = r_cap
    if ovf & OVF_SLOT:
        if s_eff >= s_cap:
            raise RuntimeError(
                f"witness slots per round exceed the padded event count "
                f"({s_cap}) — impossible for a valid DAG; this indicates "
                "packing corruption"
            )
        s_new = min(max(2 * s_eff, 1), s_cap)
    if (r_new, s_new) == (r_eff, s_eff):
        raise RuntimeError(f"unhealable overflow mask {ovf}")
    o = obs.current()
    if o is not None:
        o.registry.counter("pipeline_overflow_retries_total").inc()
        o.registry.gauge("pipeline_r_max").set(r_new)
        o.registry.gauge("pipeline_s_max").set(s_new)
    return r_new, s_new


def run_consensus(
    packed: PackedDAG,
    config: Optional[SwirldConfig] = None,
    *,
    block: int = 128,
    r_max: Optional[int] = None,
    s_max: Optional[int] = None,
    matmul_dtype_name: Optional[str] = None,
    mesh=None,
    use_pallas_ssm: bool = False,
    ssm_mode: Optional[str] = None,
) -> ConsensusResult:
    """Run the full pipeline on a packed DAG and extract the final order.

    The device computes everything except the tiebreak hash; the host
    applies the oracle's exact sort key (round received, consensus ts,
    BLAKE2b(whiten || id)) to produce the total order.  With ``mesh`` (a
    1-D member-axis ``jax.sharding.Mesh``), the strongly-sees phase is
    sharded over the mesh with psum stake aggregation
    (:mod:`tpu_swirld.parallel`).  The call is one ``swirld.batch``
    record on the engine recorder (:func:`tpu_swirld.obs.recorder`).
    """
    with obs.call_span(obs.recorder(), "swirld.batch", tally=True):
        return _run_consensus(
            packed, config, block=block, r_max=r_max, s_max=s_max,
            matmul_dtype_name=matmul_dtype_name, mesh=mesh,
            use_pallas_ssm=use_pallas_ssm, ssm_mode=ssm_mode,
        )


def _run_consensus(packed, config, *, block, r_max, s_max,
                   matmul_dtype_name, mesh, use_pallas_ssm, ssm_mode):
    # the column path's rounds scan starts from the honest bound and grows
    # in place; an explicit s_max is where it starts
    s_start = packed.n_members + 1 if s_max is None else s_max
    with obs.span("swirld.plan"):
        arrays, statics, ts_unique = prepare_inputs(
            packed, config, block=block, r_max=r_max, s_max=s_max,
            matmul_dtype_name=matmul_dtype_name,
        )
    config = config or SwirldConfig(n_members=packed.n_members)
    n = packed.n
    o = obs.current()
    if o is not None:
        _record_shapes(
            o, n=n, n_pad=arrays["parents"].shape[0], statics=statics
        )
    parents, creator, t_rank, coin = (
        arrays["parents"], arrays["creator"], arrays["t_rank"], arrays["coin"]
    )
    member_table, stake = arrays["member_table"], arrays["stake"]
    r_max, s_max = statics["r_max"], statics["s_max"]
    chain = statics["chain"]
    tot = statics["tot_stake"]
    matmul_dtype_name = statics["matmul_dtype_name"]
    if ssm_mode not in (None, "columns", "full"):
        raise ValueError(f"unknown ssm_mode {ssm_mode!r}")
    if mesh is not None and use_pallas_ssm:
        raise NotImplementedError(
            "use_pallas_ssm is not yet routed through the sharded (mesh) "
            "path; run one or the other"
        )
    if ssm_mode == "columns" and (mesh is not None or use_pallas_ssm):
        raise NotImplementedError(
            "ssm_mode='columns' is not routed through the mesh/pallas "
            "paths yet; those run the full-matrix kernel"
        )
    if ssm_mode is None:
        # auto: column-restricted on the plain single-host path, full
        # matrix for the fused mesh / pallas kernels
        ssm_mode = "full" if (mesh is not None or use_pallas_ssm) else "columns"
    if mesh is not None:
        from tpu_swirld.parallel import consensus_fn_for_mesh, pad_members

        member_table, stake = pad_members(
            member_table, stake, mesh.devices.size
        )
        kernel = consensus_fn_for_mesh(mesh)
        if o is not None:
            o.registry.gauge("mesh_devices").set(int(mesh.devices.size))
        # the longest self-chain bounds max_round for honest-shaped DAGs;
        # use it as the witness-table clamp, backed by the self-healing
        # retry (an under-provisioned table grows, never crashes)
        r_eff = min(r_max, _bucket(chain + 1, 32))
        r_cap = max(int(config.max_rounds), r_max)
        if o is not None:
            o.registry.gauge("pipeline_r_max").set(r_eff)
        t_dev0 = time.perf_counter()
        retries = 0
        while True:
            out = obs.stage_call(
                "pipeline.mesh_consensus",
                kernel,
                jnp.asarray(parents),
                jnp.asarray(creator),
                jnp.asarray(t_rank),
                jnp.asarray(coin),
                jnp.asarray(stake),
                jnp.asarray(packed.fork_pairs),
                jnp.asarray(member_table),
                jnp.asarray(n, dtype=jnp.int32),
                tot_stake=tot,
                coin_period=config.coin_period,
                block=block,
                r_max=r_eff,
                s_max=s_max,
                chain=chain,
                has_forks=bool(len(packed.fork_pairs)),
                matmul_dtype_name=matmul_dtype_name,
            )
            out = jax.tree.map(obs.to_host, out)  # blocks on the device
            ovf = int(out["overflow"])
            if not ovf:
                break
            r_eff, s_max = _healed_capacities(
                ovf, r_eff=r_eff, r_cap=r_cap, s_eff=s_max,
                s_cap=parents.shape[0],
            )
            retries += 1
        _tally_rounds(len(packed.fork_pairs), s_max,
                      int(np.max(out["wit_count"], initial=0)))
        t_device = time.perf_counter() - t_dev0
        t_fin0 = time.perf_counter()
        result = _finalize_spanned(packed, out, ts_unique)
        result.timings = {
            "device_and_dispatch": round(t_device, 6),
            "finalize_host": round(time.perf_counter() - t_fin0, 6),
            "overflow_retries": retries,
        }
        return result

    # single-host path: two stages with a tight fame/order r_max.  The
    # longest self-chain bounds max_round for honest-shaped DAGs (a
    # member's round rises at most once per own event); the clamp is a
    # recompile-hygiene heuristic backed by the self-healing retry, so an
    # under-provisioned r_max or s_max grows instead of fail-stopping.
    r_rounds = min(r_max, _bucket(chain + 1, 32))
    r_cap = max(int(config.max_rounds), r_max)
    if o is not None:
        o.registry.gauge("pipeline_r_max").set(r_rounds)
    if ssm_mode == "columns" and not use_pallas_ssm:
        return _run_consensus_columns(
            packed, config, parents, creator, t_rank, coin, stake,
            member_table, ts_unique, n=n, tot=tot, block=block,
            r_rounds=r_rounds, r_cap=r_cap, s_max=s_max, s_start=s_start,
            chain=chain, matmul_dtype_name=matmul_dtype_name,
        )
    stage_a_fn = rounds_stage
    if use_pallas_ssm:
        from tpu_swirld.tpu.pallas_kernels import resolve_interpret

        stage_a_fn = rounds_stage_pallas(interpret=resolve_interpret())
    t_dev0 = time.perf_counter()
    retries = 0
    while True:
        stage_a = obs.stage_call(
            "pipeline.rounds_stage",
            stage_a_fn,
            jnp.asarray(parents),
            jnp.asarray(creator),
            jnp.asarray(stake),
            jnp.asarray(packed.fork_pairs),
            jnp.asarray(member_table),
            jnp.asarray(n, dtype=jnp.int32),
            tot_stake=tot,
            block=block,
            r_max=r_rounds,
            s_max=s_max,
            has_forks=bool(len(packed.fork_pairs)),
            matmul_dtype_name=matmul_dtype_name,
        )
        ovf = int(obs.to_host(stage_a["overflow"]))
        if not ovf:
            break
        r_rounds, s_max = _healed_capacities(
            ovf, r_eff=r_rounds, r_cap=r_cap, s_eff=s_max,
            s_cap=parents.shape[0],
        )
        retries += 1
    max_round = int(obs.to_host(stage_a["max_round"]))
    r_tight = min(r_rounds, _bucket(max_round + 3, 8))
    s_tight = _fame_slots(stage_a["wit_count"], r_tight, s_max,
                          len(packed.fork_pairs))
    tab_b = stage_a["wit_table"][:r_tight, :s_tight]
    stage_b = obs.stage_call(
        "pipeline.fame_order_stage",
        fame_order_stage,
        stage_a["anc"],
        stage_a["sees"],
        stage_a["ssm"],
        tab_b,
        stage_a["wit_count"],
        jnp.asarray(creator),
        jnp.asarray(coin),
        jnp.asarray(stake),
        jnp.asarray(parents[:, 0]),
        jnp.asarray(t_rank),
        stage_a["max_round"],
        jnp.asarray(n, dtype=jnp.int32),
        tot_stake=tot,
        coin_period=config.coin_period,
        r_max=r_tight,
        s_max=s_tight,
        chain=chain,
        has_forks=bool(len(packed.fork_pairs)),
        matmul_dtype_name=matmul_dtype_name,
    )
    out = {
        "round": stage_a["round"],
        "is_witness": stage_a["is_witness"],
        "wit_table": tab_b,
        "wit_count": stage_a["wit_count"][:r_tight],
        "max_round": stage_a["max_round"],
        **stage_b,
    }
    out = jax.tree.map(obs.to_host, out)      # blocks on the device
    t_device = time.perf_counter() - t_dev0
    t_fin0 = time.perf_counter()
    result = _finalize_spanned(packed, out, ts_unique)
    result.timings = {
        "device_and_dispatch": round(t_device, 6),
        "finalize_host": round(time.perf_counter() - t_fin0, 6),
        "overflow_retries": retries,
    }
    return result


def _run_consensus_columns(
    packed, config, parents, creator, t_rank, coin, stake, member_table,
    ts_unique, *, n, tot, block, r_rounds, r_cap, s_max, s_start, chain,
    matmul_dtype_name,
):
    """Column-restricted strongly-sees execution (the default path) —
    :func:`_columns_pass` plus host order extraction and timings."""
    o = obs.current()
    t_dev0 = time.perf_counter()
    out, aux = _columns_pass(
        packed, config, parents, creator, t_rank, coin, stake, member_table,
        n=n, tot=tot, block=block, r_rounds=r_rounds, r_cap=r_cap,
        s_max=s_max, s_start=s_start, chain=chain,
        matmul_dtype_name=matmul_dtype_name,
    )
    t_device = time.perf_counter() - t_dev0
    t_fin0 = time.perf_counter()
    result = _finalize_spanned(packed, out, ts_unique)
    if o is not None:
        o.registry.counter("pipeline_ssm_columns_total").inc(aux["n_cols"])
    result.timings = {
        "device_and_dispatch": round(t_device, 6),
        "finalize_host": round(time.perf_counter() - t_fin0, 6),
        "ssm_columns": aux["n_cols"],
        "ssm_col_iterations": aux["n_scans"],
        "overflow_retries": aux["overflow_retries"],
    }
    return result


def _columns_pass(
    packed, config, parents, creator, t_rank, coin, stake, member_table,
    *, n, tot, block, r_rounds, s_max, chain, matmul_dtype_name,
    r_cap=None, ssm_block_fn=None, s_start=None,
):
    """Column-restricted strongly-sees execution core.

    Strongly-see columns are pure DAG functions (round-independent), and
    the rounds scan only queries *witness* columns, so instead of the full
    Θ(N³) matrix we compute columns only as witnesses are discovered: the
    scan runs in chunks carrying its state; when a chunk registers a
    witness that has no column yet, the column is computed and just that
    chunk re-runs (exact, because columns don't depend on rounds).  Every
    query in the final pass over each chunk was answered exactly, so the
    result is bit-identical to the full-matrix scan at Θ(N²·W) cost
    (W ≈ 10% of N in gossip DAGs).  Columns are additionally computed
    only over their *suffix rows* (a row below a witness can never
    strongly-see it, and the untouched slab region is already zero — the
    exact value), which cuts the column work by the witness's depth.

    Returns ``(out, aux)``: ``out`` the numpy consensus outputs (for
    :func:`finalize_order`) and ``aux`` the live device intermediates
    (visibility slabs and the column store) that
    :class:`IncrementalConsensus` lifts into its carried state on a cold
    start or rebase.  On a fork-free history ``aux["sees"]`` *is*
    ``aux["anc"]`` (alias — see :func:`ancestry_stage`).  ``ssm_block_fn``
    overrides the strongly-sees block kernel (signature of
    :func:`ssm_block_stage`) — the mesh and Pallas backends plug in here.
    ``s_max`` sizes the column store; the rounds scan starts with
    ``s_start`` witness slots per round (default ``s_max``) and grows them
    in place on a slot overflow (``aux["s_max"]`` is where it ended).
    """
    n_pad = parents.shape[0]
    has_forks = bool(len(packed.fork_pairs))
    use_gather_cache = ssm_block_fn is None
    if ssm_block_fn is None:
        ssm_block_fn = functools.partial(
            obs.stage_call, "pipeline.ssm_block_stage", ssm_block_stage
        )
    parents_d = jnp.asarray(parents)
    creator_d = jnp.asarray(creator)
    stake_d = jnp.asarray(stake)
    mt_d = jnp.asarray(member_table)
    n_d = jnp.asarray(n, dtype=jnp.int32)
    if has_forks:
        anc, sees = obs.stage_call(
            "pipeline.visibility_stage",
            visibility_stage,
            parents_d, creator_d, jnp.asarray(packed.fork_pairs),
            n_members=int(stake.shape[0]), block=block,
            matmul_dtype_name=matmul_dtype_name,
        )
    else:
        anc = obs.stage_call(
            "pipeline.visibility_stage", ancestry_stage,
            parents_d, block=block, matmul_dtype_name=matmul_dtype_name,
        )
        sees = anc          # alias: no fork pair packed -> sees == anc

    # the sees slab is frozen for the rest of the pass, so gather the
    # a-side member rows ONCE and serve every witness-column add from it
    # (same one-time cost profile as the old precomputed member slabs,
    # but transient — freed with the pass).  A custom ssm_block_fn
    # (mesh / Pallas backend) keeps the per-call path: the cache is an
    # XLA-host optimization, not part of the kernel seam.
    a_r3_full = None
    if use_gather_cache:
        a_r3_full = obs.stage_call(
            "pipeline.ssm_gather_rows", ssm_gather_rows_stage,
            sees, mt_d, np.int32(0), rows=n_pad,
        )

    # incremental column store: a preallocated (N, W_CAP) buffer written
    # in place so the scan's input shape stays stable (W_CAP grows in
    # 1024-buckets only); positions tracked host-side.  Every column is
    # exact regardless of round state.
    col_pos = np.full((n_pad,), -1, dtype=np.int32)
    n_cols = 0
    w_cap = min(_bucket(max(s_max * 8, 256), 256), n_pad)
    ssm_c = jnp.zeros((n_pad, w_cap), dtype=bool)
    n_scans = 0

    def add_columns(events):
        nonlocal n_cols, ssm_c, w_cap
        # bucket only the matmul batch and the buffer CAPACITY; occupancy
        # advances by the real count so padding slots are reused.  The
        # grain is deliberately coarse: every distinct padded width is a
        # fresh jit signature for the block kernel and the donated update,
        # and compile time — not matmul width — dominates the column path
        batch = _bucket(len(events), 64)
        if n_cols + batch > w_cap:
            w_cap = _bucket(
                max(n_cols + batch, min(w_cap * 2, n_pad)), 256
            )
            ssm_c = jnp.pad(ssm_c, ((0, 0), (0, w_cap - ssm_c.shape[1])))
        cols_arr = np.full((batch,), -1, dtype=np.int32)
        cols_arr[: len(events)] = events
        row0, rows_eff = _suffix_rows(n_pad, min(events), n_pad)
        if a_r3_full is not None:
            part = obs.stage_call(
                "pipeline.ssm_block_from_rows", ssm_block_from_rows_stage,
                a_r3_full, sees, mt_d, stake_d, jnp.asarray(cols_arr),
                np.int32(row0), rows=rows_eff, tot_stake=tot,
                matmul_dtype_name=matmul_dtype_name,
            )
        else:
            part = ssm_block_fn(
                sees, mt_d, stake_d, jnp.asarray(cols_arr), np.int32(row0),
                rows=rows_eff, tot_stake=tot,
                matmul_dtype_name=matmul_dtype_name,
            )
        for j, e in enumerate(events):
            col_pos[e] = n_cols + j
        obs.count_dispatch()
        ssm_c = update_block_stage(
            ssm_c, part, np.int32(row0), np.int32(n_cols)
        )
        n_cols += len(events)

    add_columns([int(i) for i in np.where(packed.parents[:, 0] < 0)[0]])

    # chunked scan: resume from the carried state; when a chunk registers
    # a witness whose column is missing AND a later event in the chunk
    # queried that witness's round, compute the column and re-run just
    # that chunk (columns are round-independent, so the re-run is exact);
    # otherwise the chunk's outputs are already exact and the new columns
    # only serve future chunks.  Witness-table overflow self-heals (the
    # column store survives either way — columns never depend on the
    # table shape), so an under-provisioned r_max/s_max degrades to a
    # slower pass, never a crash.  A slot overflow alone grows the slots
    # in place: the chunk's output is dropped, the carried table padded
    # with empty slots, and the same chunk re-run from its pre-chunk
    # state, which no overflow touched.  A round overflow restarts the
    # scan with the window grown.
    chunk_size = min(128, n_pad)
    while n_pad % chunk_size:
        chunk_size //= 2
    parents_np = parents
    if r_cap is None:
        r_cap = max(int(config.max_rounds), r_rounds)
    s_scan = s_max if s_start is None else s_start
    overflow_retries = slot_grows = 0
    with obs.span("swirld.rounds", slots=s_scan, forked=has_forks) as rsp:
        while True:
            state = (
                jnp.zeros((n_pad,), dtype=jnp.int32),
                jnp.zeros((n_pad,), dtype=bool),
                jnp.full((r_rounds, s_scan), -1, dtype=jnp.int32),
                jnp.zeros((r_rounds,), dtype=jnp.int32),
                jnp.zeros((), dtype=jnp.int32),
            )
            for start in range(0, n_pad, chunk_size):
                start_d = jnp.asarray(start, dtype=jnp.int32)
                # each failed attempt adds at least one column, and a chunk
                # can register at most chunk_size witnesses, so this bound
                # is safe even for degenerate one-round-per-event DAGs
                # (2-member gossip); slot grows re-run inside an attempt
                for _attempt in range(chunk_size + 1):
                    while True:
                        out = obs.stage_call(
                            "pipeline.rounds_chunk_stage",
                            rounds_chunk_stage,
                            parents_d, ssm_c, jnp.asarray(col_pos),
                            creator_d, stake_d, n_d, *state, start_d,
                            jnp.zeros((), dtype=jnp.int32),
                            tot_stake=tot, r_max=r_rounds, s_max=s_scan,
                            has_forks=has_forks, chunk=chunk_size,
                        )
                        n_scans += 1
                        obs.tally("rounds_probes")
                        tab = obs.to_host(out[2])
                        # a slot overflow leaves some round's last slot
                        # filled, so only then is the flag worth a pull
                        if not ((tab[:, -1] >= 0).any()
                                and int(obs.to_host(out[4])) == OVF_SLOT):
                            break
                        _, s_new = _healed_capacities(
                            OVF_SLOT, r_eff=r_rounds, r_cap=r_cap,
                            s_eff=s_scan, s_cap=n_pad,
                        )
                        state = (*state[:2], jnp.pad(
                            state[2], ((0, 0), (0, s_new - s_scan)),
                            constant_values=-1), *state[3:])
                        s_scan = s_new
                        slot_grows += 1
                        overflow_retries += 1
                        rsp.args["slots"] = s_scan
                    registered = np.unique(tab[tab >= 0])
                    missing = registered[col_pos[registered] < 0]
                    if missing.size == 0:
                        state = out
                        obs.tally("rounds_units")
                        break
                    rnd_np = obs.to_host(out[0])
                    # was a missing witness's round queried later in this
                    # chunk?
                    ce = np.arange(start, start + chunk_size, dtype=np.int64)
                    p = parents_np[ce]
                    r0 = np.where(
                        p[:, 0] < 0,
                        -1,
                        np.maximum(rnd_np[np.maximum(p[:, 0], 0)],
                                   rnd_np[np.maximum(p[:, 1], 0)]),
                    )
                    affected = False
                    for w in missing:
                        if w < start:   # registered in an earlier chunk?
                            affected = True  # (shouldn't happen; be safe)
                            break
                        later = ce > w
                        if np.any(later & (r0 == rnd_np[w])):
                            affected = True
                            break
                    add_columns([int(e) for e in missing])
                    obs.tally("columns_added", len(missing))
                    if not affected:
                        state = out
                        obs.tally("rounds_units")
                        break
                else:
                    raise RuntimeError(
                        "witness-column chunk did not converge")
                if int(obs.to_host(state[4])):
                    break               # overflow: stop scanning, grow, retry
            ovf = int(obs.to_host(state[4]))
            if not ovf:
                break
            r_rounds, s_scan = _healed_capacities(
                ovf, r_eff=r_rounds, r_cap=r_cap, s_eff=s_scan,
                s_cap=n_pad,
            )
            overflow_retries += 1
            rsp.args["slots"] = s_scan
    obs.tally("rounds_slot_grows", slot_grows)
    rnd_a, wits_a, tab_a, cnt_a, _overflow_a = state
    with obs.span("swirld.fame"):
        max_round_d = jnp.max(jnp.where(jnp.arange(n_pad) < n_d, rnd_a, 0))
        max_round = int(obs.to_host(max_round_d))
        r_tight = min(r_rounds, _bucket(max_round + 3, 8))
        s_tight = _fame_slots(cnt_a, r_tight, s_scan, len(packed.fork_pairs))
        tab_b = tab_a[:r_tight, :s_tight]
        stage_b = obs.stage_call(
            "pipeline.fame_order_cols_stage",
            fame_order_cols_stage,
            anc, sees, ssm_c, jnp.asarray(col_pos), tab_b, cnt_a,
            creator_d, jnp.asarray(coin), stake_d,
            jnp.asarray(parents[:, 0]), jnp.asarray(t_rank),
            max_round_d, n_d,
            tot_stake=tot, coin_period=config.coin_period, r_max=r_tight,
            s_max=s_tight, chain=chain, has_forks=has_forks,
            matmul_dtype_name=matmul_dtype_name,
        )
        out = {
            "round": rnd_a,
            "is_witness": wits_a,
            "wit_table": tab_b,
            "wit_count": cnt_a[:r_tight],
            "max_round": max_round_d,
            **stage_b,
        }
        out = jax.tree.map(obs.to_host, out)
    aux = {
        "anc": anc, "sees": sees, "ssm_c": ssm_c,
        "col_pos": col_pos, "n_cols": n_cols, "w_cap": w_cap,
        "n_scans": n_scans, "r_rounds": r_rounds, "s_max": s_scan,
        "overflow_retries": overflow_retries,
    }
    return out, aux


def _unique_famous(fam_events, creators) -> List[int]:
    """Unique famous witnesses of one round: famous witnesses whose
    creator has exactly one famous witness there — the shared commit rule
    of :func:`finalize_order` and the incremental driver (keep the two in
    lock-step: any change here is a consensus-rule change)."""
    by_creator: Dict[int, List[int]] = {}
    for e in fam_events:
        by_creator.setdefault(int(creators[e]), []).append(e)
    return sorted(e for v in by_creator.values() if len(v) == 1 for e in v)


def _whiten_sigs(sigs) -> bytes:
    """XOR-fold the UFW signatures into the round's tiebreak whitener."""
    w = bytes(crypto.SIG_BYTES)
    for s in sigs:
        w = xor_bytes(w, s)
    return w


def _finalize_spanned(packed, out, ts_unique) -> ConsensusResult:
    """:func:`finalize_order` as the engine call's ``swirld.order``
    phase (the host tie-hash and sort)."""
    with obs.span("swirld.order") as sp:
        result = finalize_order(packed, out, ts_unique)
        sp.args["ordered"] = len(result.order)
    return result


def finalize_order(
    packed: PackedDAG, out: Dict[str, np.ndarray], ts_unique: np.ndarray
) -> ConsensusResult:
    """Host post-pass: fame dict, whitened tiebreak, final total order."""
    n = packed.n
    tab = out["wit_table"]
    famous_grid = out["famous"].reshape(tab.shape)
    famous: Dict[int, Optional[bool]] = {}
    r_max, s_max = tab.shape
    ufw_by_round: Dict[int, List[int]] = {}
    for r in range(r_max):
        fam_slots = []
        for s in range(s_max):
            e = int(tab[r, s])
            if e < 0:
                continue
            f = int(famous_grid[r, s])
            famous[e] = None if f < 0 else bool(f)
            if f == 1:
                fam_slots.append(e)
        if fam_slots:
            ufw_by_round[r] = _unique_famous(fam_slots, packed.creator)

    rr = out["round_received"][:n]
    # map timestamp ranks back to the int64 values
    rank = np.clip(out["consensus_ts_rank"][:n], 0, len(ts_unique) - 1)
    cts = np.where(rr >= 0, ts_unique[rank], 0).astype(np.int64)
    whiten_cache: Dict[int, bytes] = {}

    def whiten(r: int) -> bytes:
        w = whiten_cache.get(r)
        if w is None:
            w = _whiten_sigs(packed.sigs[e] for e in ufw_by_round.get(r, []))
            whiten_cache[r] = w
        return w

    received = [
        (int(rr[i]), int(cts[i]), crypto.hash_bytes(whiten(int(rr[i])) + packed.ids[i]), i)
        for i in range(n)
        if rr[i] >= 0
    ]
    received.sort(key=lambda item: (item[0], item[1], item[2]))
    return ConsensusResult(
        n=n,
        round=out["round"][:n],
        is_witness=out["is_witness"][:n],
        famous=famous,
        round_received=rr,
        consensus_ts=cts,
        order=[i for (_r, _t, _h, i) in received],
        max_round=int(out["max_round"]),
    )


# ------------------------------------------- incremental (windowed) stages
#
# Steady-state consensus never re-decides the committed prefix: the driver
# below (:class:`IncrementalConsensus`) carries the visibility slabs, the
# strongly-sees column store, and the per-round decisions on device between
# passes, extends them with only the new-event rows/columns, and prunes the
# decided prefix so every matrix dimension scales with the *undecided
# window* rather than total history.  All stages take the carried slab as a
# donated argument so XLA updates it in place where the backend supports
# donation, and every shape is a session-monotone bucket so the steady
# loop hits a warm jit cache (no per-pass recompiles).
#
# The extension hot path is **pluggable** (:class:`ExtensionKernels`): the
# blockwise boolean-matmul hop of the ancestry extension and the
# strongly-sees block kernel can be swapped for Pallas tile kernels
# (:func:`tpu_swirld.tpu.pallas_kernels.make_extension_kernels`) or the
# mesh-sharded variant (:func:`tpu_swirld.parallel.make_ssm_block_fn_for_
# mesh`); the default XLA implementations and the interpret-mode Pallas
# kernels are bit-identical (0/1 products, f32 accumulation, integer
# thresholds), pinned by ``tests/test_pallas.py``.


@dataclasses.dataclass(frozen=True)
class ExtensionKernels:
    """Kernel bundle for the window-extension hot path.

    ``name`` keys the fused-stage jit cache; ``bmm`` is the boolean-matmul
    hop ``(a, b, dtype) -> bool`` used by the blockwise ancestry
    extension (None = the XLA :func:`_bmm`); ``ssm_block_fn`` matches
    :func:`ssm_block_stage` (None = that stage).
    """

    name: str
    bmm: Optional[object] = None
    ssm_block_fn: Optional[object] = None


XLA_EXTENSION_KERNELS = ExtensionKernels(name="xla")

_extend_vis_stages: Dict = {}


def _ancestry_extend_body(anc, parents, b0, b1, *, block, dt, bmm):
    """Extend the carried ancestry slab with rows for blocks [b0, b1).

    Identical math to :func:`ancestry` resumed over an existing slab:
    rows below ``b0 * block`` are read, not recomputed, so the work is
    O(new rows x window).  A partially filled boundary block is recomputed
    idempotently (same parent rows -> same values).  Parents of pruned
    events are remapped to -1 by the driver; that is exact here because a
    pruned parent's ancestry over the retained columns is all-zero (topo
    order: nothing retained is older than a pruned event).
    """
    n = parents.shape[0]
    n_sq = max(1, math.ceil(math.log2(block)))
    eye = jnp.eye(block, dtype=bool)
    jj = jnp.arange(block)

    def body(k, r):
        s = k * block
        pb = lax.dynamic_slice(parents, (s, 0), (block, 2))
        local = pb - s
        adj = (local[:, 0:1] == jj[None, :]) | (local[:, 1:2] == jj[None, :])
        lc = adj | eye
        for _ in range(n_sq):
            lc = lc | bmm(lc, lc, dt)
        pc = jnp.clip(pb, 0, n - 1)
        ext = (pb >= 0) & (pb < s)
        g = (r[pc[:, 0]] & ext[:, 0:1]) | (r[pc[:, 1]] & ext[:, 1:2])
        rows = bmm(lc, g, dt)
        diag = lax.dynamic_slice(rows, (0, s), (block, block)) | lc
        rows = lax.dynamic_update_slice(rows, diag, (0, s))
        return lax.dynamic_update_slice(r, rows, (s, 0))

    return lax.fori_loop(b0, b1, body, anc)


def make_extend_visibility_stage(kern: ExtensionKernels):
    """Fork-free fused extension: ancestry blocks only (``sees`` aliases
    ``anc``).  One donated jit dispatch per ingest pass."""
    fn = _extend_vis_stages.get((kern.name, "noforks"))
    if fn is None:
        bmm = kern.bmm or _bmm

        @functools.partial(
            jax.jit,
            static_argnames=("block", "matmul_dtype_name"),
            donate_argnums=(0,),
        )
        def extend_visibility_stage(anc, parents, b0, b1, *, block,
                                    matmul_dtype_name):
            dt = (
                jnp.bfloat16 if matmul_dtype_name == "bfloat16"
                else jnp.float32
            )
            return _ancestry_extend_body(
                anc, parents, b0, b1, block=block, dt=dt, bmm=bmm
            )

        fn = extend_visibility_stage
        _extend_vis_stages[(kern.name, "noforks")] = fn
    return fn


def make_extend_visibility_forked_stage(kern: ExtensionKernels):
    """Forked fused extension: ancestry blocks plus fork-aware sees rows
    ``[row0, row0 + rows)`` in one donated jit dispatch.

    Only new sees rows are written: an already-present event never changes
    its visibility (a fork pair only exists once its second member is
    packed, and nothing older descends from it), and old rows over new
    columns are structurally zero (topo order), so extension is exact.
    ``fork_pairs`` are window-remapped; the driver rebases whenever a pair
    member falls below the pruned boundary, so every pair is addressable.
    """
    fn = _extend_vis_stages.get((kern.name, "forked"))
    if fn is None:
        bmm = kern.bmm or _bmm

        @functools.partial(
            jax.jit,
            static_argnames=(
                "block", "rows", "n_members", "matmul_dtype_name"
            ),
            donate_argnums=(0, 1),
        )
        def extend_visibility_forked_stage(
            anc, sees, parents, fork_pairs, creator, b0, b1, row0, *,
            block, rows, n_members, matmul_dtype_name,
        ):
            dt = (
                jnp.bfloat16 if matmul_dtype_name == "bfloat16"
                else jnp.float32
            )
            anc = _ancestry_extend_body(
                anc, parents, b0, b1, block=block, dt=dt, bmm=bmm
            )
            n = anc.shape[0]
            anc_rows = lax.dynamic_slice(anc, (row0, 0), (rows, n))
            mcol = fork_pairs[:, 0]
            a = jnp.clip(fork_pairs[:, 1], 0, n - 1)
            b = jnp.clip(fork_pairs[:, 2], 0, n - 1)
            hit = anc_rows[:, a] & anc_rows[:, b] & (mcol >= 0)[None, :]
            onehot = mcol[:, None] == jnp.arange(n_members)[None, :]
            fseen = bmm(hit, onehot, dt)
            new_rows = anc_rows & ~fseen[:, creator]
            sees = lax.dynamic_update_slice(sees, new_rows, (row0, 0))
            return anc, sees

        fn = extend_visibility_forked_stage
        _extend_vis_stages[(kern.name, "forked")] = fn
    return fn


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def prune_stage(anc, sees, ssm_c, d, n_used, keep_cols):
    """Shift the carried slabs down/left by ``d`` pruned events, zero the
    vacated tail, and gather the surviving witness columns (``keep_cols``
    indexes the old column slots, -1 = vacate).  Capacities are preserved
    so the steady loop keeps a single compiled shape."""
    n = anc.shape[0]
    live = jnp.arange(n) < (n_used - d)
    m2 = live[:, None] & live[None, :]
    anc = jnp.roll(jnp.roll(anc, -d, axis=0), -d, axis=1) & m2
    sees = jnp.roll(jnp.roll(sees, -d, axis=0), -d, axis=1) & m2
    kv = keep_cols >= 0
    kc = jnp.clip(keep_cols, 0, ssm_c.shape[1] - 1)
    ssm_c = jnp.roll(ssm_c, -d, axis=0)[:, kc] & live[:, None] & kv[None, :]
    return anc, sees, ssm_c


@functools.partial(jax.jit, donate_argnums=(0, 1))
def prune_noforks_stage(anc, ssm_c, d, n_used, keep_cols):
    """:func:`prune_stage` for the fork-free fast path: the sees slab is
    an alias of ``anc``, so only two slabs roll."""
    n = anc.shape[0]
    live = jnp.arange(n) < (n_used - d)
    m2 = live[:, None] & live[None, :]
    anc = jnp.roll(jnp.roll(anc, -d, axis=0), -d, axis=1) & m2
    kv = keep_cols >= 0
    kc = jnp.clip(keep_cols, 0, ssm_c.shape[1] - 1)
    ssm_c = jnp.roll(ssm_c, -d, axis=0)[:, kc] & live[:, None] & kv[None, :]
    return anc, ssm_c


@jax.jit
def _copy_slab_stage(anc):
    """Materialize a distinct sees slab from the ancestry slab (the
    fork-free alias ends when the first fork pair arrives)."""
    return anc | False      # an actual op: forces a fresh buffer


@functools.partial(jax.jit, donate_argnums=(0,))
def compact_cols_stage(ssm_c, keep_cols):
    """Gather the surviving witness columns without a row shift — the
    roll-time compaction that keeps retired-round columns from padding
    every ssm block matmul until the next prune."""
    kv = keep_cols >= 0
    kc = jnp.clip(keep_cols, 0, ssm_c.shape[1] - 1)
    return ssm_c[:, kc] & kv[None, :]


@functools.partial(
    jax.jit,
    static_argnames=(
        "tot_stake", "coin_period", "r_max", "s_max", "has_forks",
        "matmul_dtype_name",
    ),
)
def fame_window_stage(sees, ssm_c, col_pos, wit_table, creator, coin, stake,
                      *, tot_stake, coin_period, r_max, s_max, has_forks,
                      matmul_dtype_name):
    """Fame voting over the retained round window only.  Round-window
    locality is exact: votes about a round-r witness only involve rounds
    > r, and the driver's straggler guard rebases whenever a witness
    registers below the window, so rows [0, r_max) are self-contained."""
    dt = jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    return fame_scan(
        wit_table[:r_max], sees, ssm_c, creator, coin, stake, tot_stake,
        coin_period, dt, has_forks=has_forks, col_pos=col_pos,
    )


@functools.partial(jax.jit, static_argnames=("r_max", "s_max", "chain"))
def order_window_stage(anc, wit_table, wit_count, famous, creator,
                       self_parent, t_rank, max_round_local, n_valid,
                       received0, *, r_max, s_max, chain):
    """Order extraction over the first ``r_max`` retained rounds, resuming
    from the carried received flags.  Already-committed rounds re-run as
    no-ops (their received sets are final — new events are never ancestors
    of old witnesses), so ``r_max`` only needs to reach the newly
    fame-complete prefix."""
    return order_scan(
        anc, wit_table[:r_max], wit_count[:r_max],
        famous[: r_max * s_max], creator, self_parent, t_rank,
        max_round_local, n_valid, chain=chain, received0=received0,
    )


# --------------------------------------------------- incremental driver


class IncrementalConsensus:
    """Steady-state consensus driver with carried device state.

    Where :func:`run_consensus` recomputes the full ancestry / sees /
    strongly-sees matrices on every call, this driver keeps them (plus the
    witness table and per-round decisions) alive between passes:

    - :meth:`ingest` appends a gossip delta to the internal
      :class:`~tpu_swirld.packing.Packer`, extends the carried slabs with
      only the new-event rows/columns, resumes the rounds scan from its
      carried state, re-votes fame over the *retained round window* only,
      and extracts the order of newly fame-complete rounds;
    - the **decided prefix is pruned**: once an event is received (and all
      fork-pair members stay above the cut), its row/column is dropped
      from every slab, so matrix work scales with the undecided window
      rather than total history;
    - all static shapes are session-monotone buckets, so after a short
      warmup the steady loop adds **zero new jit-cache entries**, and the
      carried slabs are donated to the extension stages.

    Exactness contract: every pass leaves the committed outputs **bit-
    identical** to a cold :func:`run_consensus` over the full DAG.  Window
    locality is exact for gossip-shaped traffic (new events reference
    recent parents); the cases where it is not are *detected* and answered
    with a transparent full recompute ("rebase"):

    - a new event whose parent was already pruned, or whose parent round
      fell below the retained round window (deep orphan/straggler),
    - a new witness registering at a round at or below the frozen vote
      horizon (it could change a committed fame tally),
    - a new fork pair naming a pruned event,
    - witness-table overflow (round/slot capacity).

    Rebases rebuild the carried state from the batch pipeline, so they
    cost one cold pass and the driver keeps going.
    """

    def __init__(
        self,
        members,
        stake=None,
        config: Optional[SwirldConfig] = None,
        *,
        block: int = 128,
        chunk: int = 256,
        window_bucket: int = 1024,
        prune_min: Optional[int] = None,
        matmul_dtype_name: Optional[str] = None,
        ssm_block_fn=None,
        extension_kernels: Optional[ExtensionKernels] = None,
        storm_threshold: int = 3,
        storm_cooldown: int = 8,
        slab_put=None,
    ):
        if stake is None:
            stake = [1] * len(members)
        self.packer = Packer(members, stake)
        self.config = config or SwirldConfig(n_members=len(members))
        self._block = block
        self._chunk = max(32, chunk)
        self._window_bucket = max(256, window_bucket)
        self._prune_min = (
            prune_min if prune_min is not None else self._window_bucket // 4
        )
        if matmul_dtype_name is None:
            matmul_dtype_name = (
                "float32" if jax.default_backend() == "cpu" else "bfloat16"
            )
        self._mm = matmul_dtype_name
        self._kern = (
            extension_kernels if extension_kernels is not None
            else XLA_EXTENSION_KERNELS
        )
        # the per-pass a-side gather cache only matches the default XLA
        # block kernel; a custom seam (mesh / Pallas) owns its own gathers
        self._cache_blocks = (
            ssm_block_fn is None and self._kern.ssm_block_fn is None
        )
        self._ars_cache = None      # (row0, rows) -> pre-gathered a-side
        self._ars_key = None
        if ssm_block_fn is None:
            base = self._kern.ssm_block_fn or ssm_block_stage
            ssm_block_fn = functools.partial(
                obs.stage_call, "pipeline.ssm_block_stage", base
            )
        self._ssm_block_fn = ssm_block_fn
        # slab placement seam: every from-scratch slab push (rebase,
        # widening) goes through this, so a mesh driver can scatter the
        # window rows to their owning devices instead of replicating
        self._put = slab_put if slab_put is not None else jnp.asarray
        self._stake = np.asarray(stake, dtype=np.int32)
        self._tot = int(self._stake.sum())
        self._m = len(members)

        # global committed outputs (amortized-growth buffers)
        self._round_g = np.zeros((0,), np.int32)
        self._wits_g = np.zeros((0,), bool)
        self._rr_g = np.zeros((0,), np.int32)
        self._cts_g = np.zeros((0,), np.int64)
        self._order: List[int] = []
        self._famous_committed: Dict[int, bool] = {}

        # consensus cursors (global rounds / indices)
        self._initialized = False
        self._n_done = 0            # events consumed from the packer
        self._lo = 0                # pruned prefix length (global index)
        self._r_base = 0            # global round of witness-table row 0
        self._consensus_round = 0   # next round to order (== r_base at rest)
        self._frozen_vote_hi = 0    # votes at rounds < this are committed
        self._max_round = 0
        self._g_done = 0            # fork pairs already vetted

        # session-monotone static shape buckets (recompile hygiene)
        self._w_pad = 0             # window row capacity
        self._rows_hi = 0           # high-water of materialized window rows
        self._wcol_cap = 256        # ssm column capacity
        self._r_cap = 32            # witness-table rows
        self._r_fame = 8            # fame round window
        self._r_ord = 4             # order round window
        self._chain_cap = 32        # self-chain walk depth
        self._k_cap = 8             # member-table columns
        self._g_cap = 0             # fork-pair rows
        self._s_cap = self._m + 1   # witness slots per round

        # telemetry
        self.passes = 0
        self.rebases = 0
        self.recompiles_hint = 0
        self.overflow_heals = 0   # capacity growths absorbed by rebases
        self._rebase_kind = "full"  # how the last rebase was answered
        self.finality = None      # obs.FinalityTracker: per-event
                                  # lifecycle (births at ingest, decided
                                  # at commit — see _stats)
        self.flightrec = None     # obs.FlightRecorder: storm/overflow
                                  # anomalies dump post-mortems
        self.flightrec_label = "incremental"
        # latency-phase attribution: the streaming driver stamps each
        # pass's decided events with "window" / "widened" / "full"
        # (window residency vs archive widening); plain incremental
        # leaves both None (no phase dimension)
        self._latency_phase = None
        self._latency_phase_default = None

        # rebase-storm guard: adversarial ingest (straggler floods, deep
        # orphan replays) can make EVERY pass detect-then-rebase, paying
        # the doomed incremental attempt on top of the full recompute.
        # After `storm_threshold` consecutive detected rebases the driver
        # flips to full-recompute mode for `storm_cooldown` passes
        # (skipping the extension attempt entirely), then re-admits the
        # incremental path with a fresh slate — a hysteresis loop, so
        # thrash can't oscillate pass-by-pass.  storm_threshold <= 0
        # disables the guard (the thrash-measuring control in tests).
        self.storm_threshold = storm_threshold
        self.storm_cooldown = max(1, storm_cooldown)
        self.storm_entries = 0            # times the guard engaged
        self.storm_rebases = 0            # rebases run in storm mode
        self.max_consecutive_rebases = 0  # worst detect-rebase streak
        self._consec_rebases = 0
        self._storm_left = 0

    # ------------------------------------------- capacity growth policy
    #
    # Single source of truth for the next-capacity formulas: the
    # streaming driver's budget pre-checks predict the exact shapes these
    # produce, so any policy change here must stay in one place.

    @staticmethod
    def _next_row_pad(need: int, window_bucket: int) -> int:
        return _bucket(need + window_bucket // 2, window_bucket)

    @staticmethod
    def _next_col_cap(n_cols: int, batch: int, cap: int) -> int:
        return _bucket(max(n_cols + batch, cap * 2), 256)

    @staticmethod
    def _next_k_cap(need: int) -> int:
        # K is a dimension of every gather/block kernel signature, so it
        # must step rarely: 25% headroom on a coarse grain keeps the
        # session to a handful of K values instead of one every 8 events
        # per member (padding is -1 -> masked, exact)
        return _bucket(need + need // 4 + 8, 32)

    # -------------------------------------------------------- public API

    def __len__(self) -> int:
        return self._n_done

    @property
    def window_size(self) -> int:
        return self._n_done - self._lo

    @property
    def pruned_prefix(self) -> int:
        return self._lo

    @property
    def storm_mode(self) -> bool:
        """True while the rebase-storm guard holds the driver in
        full-recompute mode."""
        return self._storm_left > 0

    @property
    def resident_visibility_bytes(self) -> int:
        """Bytes of device-resident visibility state (the anc/sees/ssm
        window slabs; sees aliases anc on a fork-free history and the old
        per-member gather slabs no longer exist) — the quantity the slab
        store's tile budget bounds.  Zero before the first pass."""
        if not self._initialized:
            return 0
        n = int(self._anc_d.nbytes + self._ssm_d.nbytes)
        if self._sees_d is not self._anc_d:
            n += int(self._sees_d.nbytes)
        return n

    # Retirement hooks: no-ops here; :class:`tpu_swirld.store.streaming.
    # StreamingConsensus` overrides them to archive decided rows / rounds
    # instead of discarding them.  Called with the PRE-mutation state.

    def _on_prune(self, d: int, w_used: int) -> None:
        """About to drop window rows [0, d) of [0, w_used)."""

    def _on_roll(self, dr: int) -> None:
        """About to roll witness-table rows [0, dr) out of the window."""

    def _on_rebase(self, packed, out, aux) -> None:
        """A batch rebase decided everything up to the new ``self._lo``;
        ``aux`` still holds the full-DAG device slabs."""

    def _pack_delta(self, events) -> None:
        """Append a gossip delta to the packer.  Seam for the streaming
        driver's decode-overlap path, which substitutes pre-decoded
        ``(event, id)`` pairs produced on a worker thread — the override
        must keep all packer mutation on the calling thread."""
        self.packer.extend(events)

    def ingest(self, events=()) -> Dict:
        """Feed a topo-ordered gossip delta; run one incremental pass.

        Returns a per-pass stats dict: ``new_events``, ``ordered`` (the
        packed indices newly committed to the total order, in order),
        ``window_size``, ``pruned_prefix``, ``rebased``, ``seconds``.
        The pass is one ``swirld.pass`` record on the engine recorder
        (:func:`tpu_swirld.obs.recorder`).
        """
        with obs.call_span(obs.recorder(), "swirld.pass", tally=True) as sp:
            st = self._ingest(events)
            sp.args.update(n_new=st["new_events"], ordered=len(st["ordered"]),
                           rebased=st["rebased"])
        return st

    def _ingest(self, events) -> Dict:
        t0 = time.perf_counter()
        n_before = len(self.packer)
        with obs.span("swirld.pack") as sp:
            self._pack_delta(events)
            n_total = len(self.packer)
            sp.args["events"] = n_total - n_before
        if self.finality is not None and n_total > n_before:
            # birth = the tick this ingest chunk entered the driver; the
            # tracker's clock decides the unit (logical tick vs seconds)
            self.finality.mark_births(n_before, n_total)
        n_new = n_total - self._n_done
        if n_total == 0 or (n_new == 0 and self._initialized):
            return self._stats(n_new, [], t0, rebased=False)
        if not self._initialized:
            # the cold-start build is a rebase mechanically but not a
            # *failed incremental attempt* — it never feeds the guard
            ordered = self._rebase_spanned()
            return self._stats(n_new, ordered, t0, rebased=True,
                               count_storm=False)
        if self._storm_left > 0:
            # storm mode: skip the doomed detect/extend attempt outright
            self._storm_left -= 1
            self.storm_rebases += 1
            if self._storm_left == 0:
                self._consec_rebases = 0   # hysteresis exit: fresh slate
            ordered = self._rebase_spanned()
            return self._stats(n_new, ordered, t0, rebased=True,
                               count_storm=False, storm=True)
        if self._needs_rebase_pre():
            ordered = self._rebase_spanned()
            return self._stats(n_new, ordered, t0, rebased=True)
        ordered, need_rebase = self._extend_pass(n_new)
        if need_rebase:
            ordered = self._rebase_spanned()
            return self._stats(n_new, ordered, t0, rebased=True)
        return self._stats(n_new, ordered, t0, rebased=False)

    def _rebase_spanned(self) -> List[int]:
        """:meth:`_rebase` as the pass's ``swirld.rebase`` phase; ``kind``
        is ``widen`` where the streaming driver re-admitted archived rows,
        else ``full``."""
        with obs.span("swirld.rebase") as sp:
            self._rebase_kind = "full"
            ordered = self._rebase()
            sp.args["kind"] = self._rebase_kind
        return ordered

    def result(self) -> ConsensusResult:
        """Cumulative consensus state — bit-identical to a cold
        :func:`run_consensus` over the same packed DAG."""
        n = self._n_done
        famous: Dict[int, Optional[bool]] = dict(self._famous_committed)
        if self._initialized:
            for k in range(self._r_cap):
                for s in range(self._s_cap):
                    e = int(self._tab_np[k, s])
                    if e < 0:
                        continue
                    f = int(self._famous_np[k, s])
                    famous[self._lo + e] = None if f < 0 else bool(f)
        return ConsensusResult(
            n=n,
            round=self._round_g[:n].copy(),
            is_witness=self._wits_g[:n].copy(),
            famous=famous,
            round_received=self._rr_g[:n].copy(),
            consensus_ts=self._cts_g[:n].copy(),
            order=list(self._order),
            max_round=self._max_round,
            timings={
                "passes": self.passes,
                "rebases": self.rebases,
                "window_size": self.window_size,
                "pruned_prefix": self.pruned_prefix,
                "storm_entries": self.storm_entries,
                "storm_rebases": self.storm_rebases,
                "max_consecutive_rebases": self.max_consecutive_rebases,
            },
        )

    # ------------------------------------------------------ pass plumbing

    def _stats(self, n_new, ordered, t0, *, rebased,
               count_storm=True, storm=False):
        self.passes += 1
        if rebased:
            self.rebases += 1
            if count_storm:
                # a *detected* rebase: an incremental attempt that failed
                self._consec_rebases += 1
                self.max_consecutive_rebases = max(
                    self.max_consecutive_rebases, self._consec_rebases
                )
                if (
                    self.storm_threshold > 0
                    and self._consec_rebases >= self.storm_threshold
                ):
                    self.storm_entries += 1
                    self._storm_left = self.storm_cooldown
                    if self.flightrec is not None:
                        oo = obs.current()
                        self.flightrec.trigger(
                            "rebase_storm", node=self.flightrec_label,
                            detail={
                                "consecutive": self._consec_rebases,
                                "cooldown": self.storm_cooldown,
                            },
                            decided_frontier={
                                self.flightrec_label: {
                                    "decided": len(self._order),
                                    "round": self._consensus_round,
                                },
                            },
                            registry=oo.registry if oo is not None else None,
                        )
        elif n_new > 0:
            self._consec_rebases = 0   # a clean incremental pass
        # a storm-mode pass must report as such even when it was the last
        # one of the cooldown (_storm_left was decremented before _stats)
        in_storm = storm or self._storm_left > 0
        o = obs.current()
        if o is not None:
            g = o.registry
            g.gauge("incremental_window_size").set(self.window_size)
            g.gauge("incremental_pruned_prefix").set(self.pruned_prefix)
            g.gauge("incremental_r_base").set(self._r_base)
            g.gauge("incremental_storm_mode").set(1.0 if in_storm else 0.0)
            g.gauge("incremental_consecutive_rebases").set(
                self._consec_rebases
            )
            g.counter("incremental_passes_total").inc()
            if rebased:
                g.counter("incremental_rebases_total").inc()
            if storm:
                g.counter("incremental_storm_rebases_total").inc()
        fin = self.finality
        if fin is not None and ordered:
            phase = self._latency_phase
            now = fin.now()
            for gi in ordered:
                gi = int(gi)
                fin.record_decided(
                    gi, int(self._round_g[gi]), int(self._rr_g[gi]),
                    now=now, phase=phase,
                )
            fin.set_watermark(
                self.flightrec_label, len(self._order),
                self._consensus_round - 1,
            )
        self._latency_phase = self._latency_phase_default
        return {
            "new_events": int(n_new),
            "ordered": ordered,
            "window_size": self.window_size,
            "pruned_prefix": self.pruned_prefix,
            "rebased": bool(rebased),
            "storm_mode": in_storm,
            "seconds": round(time.perf_counter() - t0, 6),
        }

    def _grow_global(self, n: int) -> None:
        if self._round_g.shape[0] >= n:
            return
        cap = max(n, 2 * max(1, self._round_g.shape[0]))

        def regrow(a, fill, dtype):
            out = np.full((cap,), fill, dtype)
            out[: a.shape[0]] = a
            return out

        self._round_g = regrow(self._round_g, 0, np.int32)
        self._wits_g = regrow(self._wits_g, False, bool)
        self._rr_g = regrow(self._rr_g, -1, np.int32)
        self._cts_g = regrow(self._cts_g, 0, np.int64)

    def _needs_rebase_pre(self) -> bool:
        """Host-side guards that must run before touching device state."""
        p = self.packer
        lo, n0, n1 = self._lo, self._n_done, len(p)
        new_par, _, _, _ = p.window_view(n0, n1)
        live = new_par >= 0
        if live.any() and int(new_par[live].min()) < lo:
            return True          # parent already pruned
        if self._r_base > 0 and (~live[:, 0]).any():
            return True          # late genesis: a round-0 straggler
        # Parent rounds must stay inside the retained round window.  Only
        # events whose parents are BOTH already processed can be checked
        # against the round mirror; events referencing a parent inside
        # this same delta are covered by induction (round >= parent round,
        # and every chain bottoms out in a checked old parent).
        both_old = live[:, 0] & (new_par < n0).all(axis=1)
        if both_old.any():
            pw = np.where(both_old[:, None], new_par - lo, 0)
            r0 = self._rnd_w[pw].max(axis=1)
            if int(r0[both_old].min()) < self._r_base:
                return True
        # new fork pairs must not name pruned events
        if p.n_fork_pairs > self._g_done:
            pairs = p.fork_pairs_view(self._g_done)
            if int(pairs[:, 1:].min()) < lo:
                return True
        return False

    # --------------------------------------------------- capacity buckets

    def _ensure_row_capacity(self, need: int) -> None:
        if need <= self._w_pad:
            return
        new_pad = self._next_row_pad(need, self._window_bucket)
        g = new_pad - self._w_pad
        self._ars_cache = self._ars_key = None
        aliased = self._sees_d is self._anc_d
        self._anc_d = jnp.pad(self._anc_d, ((0, g), (0, g)))
        self._sees_d = (
            self._anc_d if aliased
            else jnp.pad(self._sees_d, ((0, g), (0, g)))
        )
        self._ssm_d = jnp.pad(self._ssm_d, ((0, g), (0, 0)))
        self._grow_mirrors(new_pad)
        self._w_pad = new_pad

    def _grow_mirrors(self, new_pad: int) -> None:
        def regrow(a, fill):
            out = np.full((new_pad,) + a.shape[1:], fill, a.dtype)
            out[: a.shape[0]] = a
            return out

        self._parents_w = regrow(self._parents_w, -1)
        self._creator_w = regrow(self._creator_w, 0)
        self._coin_w = regrow(self._coin_w, 0)
        self._t_w = regrow(self._t_w, 0)
        self._rnd_w = regrow(self._rnd_w, 0)
        self._wits_w = regrow(self._wits_w, False)
        self._recv_w = regrow(self._recv_w, False)
        self._depth_w = regrow(self._depth_w, 0)
        self._colpos_w = regrow(self._colpos_w, -1)

    def _alloc_mirrors(self, w_pad: int) -> None:
        self._parents_w = np.full((w_pad, 2), -1, np.int32)
        self._creator_w = np.zeros((w_pad,), np.int32)
        self._coin_w = np.zeros((w_pad,), np.uint8)
        self._t_w = np.zeros((w_pad,), np.int64)
        self._rnd_w = np.zeros((w_pad,), np.int32)
        self._wits_w = np.zeros((w_pad,), bool)
        self._recv_w = np.zeros((w_pad,), bool)
        self._depth_w = np.zeros((w_pad,), np.int32)
        self._colpos_w = np.full((w_pad,), -1, np.int32)

    def _grow_k(self, need: int) -> None:
        new_k = self._next_k_cap(need)
        out = np.full((self._m, new_k), -1, np.int32)
        out[:, : self._k_cap] = self._mt_np
        self._mt_np = out
        self._k_cap = new_k

    def _rebuild_member_table(self, w_used: int) -> None:
        """Vectorized member-table rebuild over window rows [0, w_used):
        per member, its window events in window (topo) order — identical
        to the old sequential registration loop, O(w log w) numpy."""
        cre = self._creator_w[:w_used].astype(np.int64)
        counts = np.bincount(cre, minlength=self._m)
        kmax = int(counts.max(initial=0))
        if kmax > self._k_cap:
            self._k_cap = self._next_k_cap(kmax)
        self._mt_np = np.full((self._m, self._k_cap), -1, np.int32)
        self._mcount = counts.astype(np.int32)
        if w_used:
            order = np.argsort(cre, kind="stable")
            starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
            kpos = np.arange(w_used, dtype=np.int64) - np.repeat(starts, counts)
            self._mt_np[cre[order], kpos] = order.astype(np.int32)

    def _materialize_sees(self) -> None:
        """Fork-free -> forked transition: give sees its own slab.

        Exact without recomputation: the first fork pair's second member
        is in the *pending* delta (the packer creates a pair when the
        second member arrives), so no already-present row descends from
        the pair — every existing row's fseen is all-zero and its sees
        row equals its ancestry row.  The extension pass then writes the
        new (possibly poisoned) rows on top of the copy."""
        if self._initialized and self._sees_d is self._anc_d:
            self._ars_cache = self._ars_key = None
            self._sees_d = obs.stage_call(
                "pipeline.sees_materialize", _copy_slab_stage, self._anc_d
            )

    def _recompute_depth(self, w_used: int) -> None:
        d = self._depth_w
        par = self._parents_w
        for i in range(w_used):
            sp = par[i, 0]
            d[i] = 1 + (d[sp] if sp >= 0 else 0)
        if int(d[:w_used].max(initial=0)) > self._chain_cap:
            self._chain_cap = _bucket(int(d[:w_used].max()), 32)

    def _fork_pairs_padded(self) -> np.ndarray:
        g = self._fork_np.shape[0]
        if g > self._g_cap:
            self._g_cap = _bucket(g, 8)
        out = np.full((self._g_cap, 3), -1, np.int32)
        out[:g] = self._fork_np
        return out

    # ----------------------------------------------------- column store

    def _add_columns(self, events: List[int]) -> None:
        if not events:
            return
        # coarse grain for the same reason as the batch path: one padded
        # width per pass keeps the block kernel + donated update on a
        # single jit signature (padded cols are -1 -> masked -> exact)
        batch = _bucket(len(events), 64)
        if self._n_cols + batch > self._wcol_cap:
            new_cap = self._next_col_cap(
                self._n_cols, batch, self._wcol_cap
            )
            self._ssm_d = jnp.pad(
                self._ssm_d, ((0, 0), (0, new_cap - self._wcol_cap))
            )
            ce = np.full((new_cap,), -1, np.int32)
            ce[: self._wcol_cap] = self._col_events
            self._col_events = ce
            self._wcol_cap = new_cap
        cols_arr = np.full((batch,), -1, np.int32)
        cols_arr[: len(events)] = events
        # suffix cut: rows below the earliest new witness can never
        # strongly-see it (the slab already holds their exact value, zero)
        if (
            self._cache_blocks
            and self._ars_cache is not None
            and min(events) >= self._ars_key[0]
        ):
            # pass-local fast path: every new witness is a new row, so the
            # pass's cached a-side gather already covers the suffix
            key0, key_rows = self._ars_key
            off, rows_eff = _suffix_rows(
                key0 + key_rows, min(events), key_rows
            )
            row0 = off
            part = obs.stage_call(
                "pipeline.ssm_block_from_rows", ssm_block_from_rows_stage,
                self._ars_cache, self._sees_d, jnp.asarray(self._mt_np),
                jnp.asarray(self._stake), jnp.asarray(cols_arr),
                np.int32(off - key0), rows=rows_eff,
                tot_stake=self._tot, matmul_dtype_name=self._mm,
            )
        else:
            row0, rows_eff = _suffix_rows(
                self._rows_hi, min(events), self._w_pad
            )
            part = self._ssm_block_fn(
                self._sees_d, jnp.asarray(self._mt_np),
                jnp.asarray(self._stake), jnp.asarray(cols_arr),
                np.int32(row0), rows=rows_eff, tot_stake=self._tot,
                matmul_dtype_name=self._mm,
            )
        for j, e in enumerate(events):
            self._colpos_w[e] = self._n_cols + j
            self._col_events[self._n_cols + j] = e
        self._ssm_d = obs.stage_call(
            "pipeline.inc_ssm_update", update_block_stage,
            self._ssm_d, part, np.int32(row0), np.int32(self._n_cols),
        )
        self._n_cols += len(events)

    # ------------------------------------------------------- extend pass

    def _rounds_span_fixpoint(self, parents_d, creator_d, stake_d, n_valid,
                              has_forks, w0, n_pad_new, r_base_d):
        """The incremental rounds scan: spans of up to ``SPAN_CHUNKS``
        chunks per dispatch (``rounds_span_stage``), each run to a
        witness-column fixpoint.  Returns the accepted final carry
        (device tuple ``(rnd, wits, tab, cnt, overflow)``) or ``None``
        on round/slot overflow — the caller rebases, which is exact
        because ``_columns_pass`` likewise commits nothing once its
        sticky overflow bit is set, and regrows the flagged capacity.

        Exactness vs the batch result: every probe re-runs the whole
        span from the SAME host-mirror carry, and a probe is accepted
        only when every witness registered anywhere in its output table
        already had a strongly-sees column for the entire run.  A missing
        column deterministically reads as not-strongly-seen (the scan
        body masks ``col_pos < 0`` — under-promotion only, never
        garbage), so an accepted run never consumed a value the
        fully-informed scan wouldn't produce; its outputs are therefore
        bit-identical to ``_columns_pass`` resuming the same events one
        chunk at a time, and so to :func:`run_consensus`.
        Each failed probe registers >= 1 event whose column is absent
        and ``_add_columns`` makes it present, so columns grow strictly
        monotonically and the loop terminates within span_len probes.
        A ragged tail (n_chunks % SPAN_CHUNKS != 0) gets its own static
        ``k_chunks`` — a session-bounded shape family (< SPAN_CHUNKS
        values).
        """
        chunk = self._chunk
        n_chunks = n_pad_new // chunk
        # host-side carry: every probe uploads fresh device buffers from
        # these, so the donated span stage (carry positions 6-10) never
        # consumes a buffer the retry loop still needs
        carry_h = (self._rnd_w, self._wits_w, self._tab_np, self._cnt_np)
        state = None
        ci = 0
        while ci < n_chunks:
            k = min(SPAN_CHUNKS, n_chunks - ci)
            start = np.int32(w0 + ci * chunk)
            span_len = k * chunk
            for _attempt in range(span_len + 1):
                out = obs.stage_call(
                    "pipeline.rounds_span_stage", rounds_span_stage,
                    parents_d, self._ssm_d, jnp.asarray(self._colpos_w),
                    creator_d, stake_d, np.int32(n_valid),
                    jnp.asarray(carry_h[0]), jnp.asarray(carry_h[1]),
                    jnp.asarray(carry_h[2]), jnp.asarray(carry_h[3]),
                    jnp.zeros((), dtype=jnp.int32), start, r_base_d,
                    tot_stake=self._tot, r_max=self._r_cap,
                    s_max=self._s_cap, has_forks=has_forks,
                    chunk=chunk, k_chunks=k,
                )
                obs.tally("rounds_probes")
                tab = obs.to_host(out[2])
                registered = np.unique(tab[tab >= 0])
                missing = registered[self._colpos_w[registered] < 0]
                if missing.size == 0:
                    state = out
                    obs.tally("rounds_units")
                    break
                self._add_columns([int(e) for e in missing])
                obs.tally("columns_added", len(missing))
            else:
                raise RuntimeError("witness-column span did not converge")
            if int(obs.to_host(state[4])):
                return None
            ci += k
            if ci < n_chunks:
                # next span resumes from this span's accepted carry; pull
                # it to host ONCE per span (copy=True: an owned host
                # array, never a zero-copy view of the device buffer the
                # next probe would donate)
                carry_h = (
                    obs.to_host(state[0], copy=True),
                    obs.to_host(state[1], copy=True),
                    obs.to_host(state[2], copy=True),
                    obs.to_host(state[3], copy=True),
                )
        return state

    def _extend_pass(self, n_new: int) -> Tuple[List[int], bool]:
        """One incremental pass over the ``n_new`` freshly packed events.
        Returns ``(newly_ordered, need_rebase)``."""
        with obs.span("swirld.plan"):
            p = self.packer
            lo = self._lo
            w0 = self._n_done - lo
            n1 = len(p)
            chunk = self._chunk
            n_pad_new = _bucket(n_new, chunk)
            self._ensure_row_capacity(w0 + n_pad_new)
            sl = slice(w0, w0 + n_new)
            gsl = slice(self._n_done, n1)
            par, creator_new, coin_new, t_new = p.window_view(
                self._n_done, n1)
            parw = np.where(par >= 0, par - lo, -1).astype(np.int32)
            self._parents_w[sl] = parw
            self._creator_w[sl] = creator_new
            self._coin_w[sl] = coin_new
            self._t_w[sl] = t_new
            for j in range(n_new):
                sp = parw[j, 0]
                self._depth_w[w0 + j] = 1 + (
                    self._depth_w[sp] if sp >= 0 else 0)
            dmax = int(self._depth_w[: w0 + n_new].max(initial=1))
            if dmax > self._chain_cap:
                self._chain_cap = _bucket(dmax, 32)
            # member-table slots for the new events (host bookkeeping only —
            # the ssm block kernel gathers straight from the sees slab)
            for j in range(n_new):
                m = int(creator_new[j])
                slot = int(self._mcount[m])
                if slot >= self._k_cap:
                    self._grow_k(slot + 1)
                self._mt_np[m, slot] = w0 + j
                self._mcount[m] = slot + 1
            # fork pairs arriving with this delta (window-remapped)
            if p.n_fork_pairs > self._g_done:
                fp = p.fork_pairs_view(self._g_done)
                new_pairs = np.stack(
                    [fp[:, 0], fp[:, 1] - lo, fp[:, 2] - lo], axis=1,
                ).astype(np.int32)
                was_forkless = self._fork_np.shape[0] == 0
                self._fork_np = np.concatenate([self._fork_np, new_pairs])
                self._g_done = p.n_fork_pairs
                if was_forkless:
                    self._materialize_sees()
            has_forks = self._fork_np.shape[0] > 0

            parents_d = jnp.asarray(self._parents_w)
            creator_d = jnp.asarray(self._creator_w)
            stake_d = jnp.asarray(self._stake)
            n_valid = np.int32(w0 + n_new)
            mt_d = jnp.asarray(self._mt_np)
            # round-restricted column suffix: a new row i is only ever
            # queried against witness columns of round >= r0(i) - 1 — the
            # rounds scan asks for round == r0(i) and fame collects votes
            # from the single round below the voter — so columns whose
            # witness round sits entirely below min_i r0(i) - 1 can skip
            # the extension matmul; their block entries keep the slab
            # value (zero), which no reader ever queries for these rows.
            col_lo = 0
            if self._n_cols and n_new:
                lb = np.zeros((n_new,), np.int32)
                rw = self._rnd_w
                for j in range(n_new):
                    p0, p1 = int(parw[j, 0]), int(parw[j, 1])
                    b = 0
                    if p0 >= 0:
                        b = int(rw[p0]) if p0 < w0 else int(lb[p0 - w0])
                    if p1 >= 0:
                        b2 = int(rw[p1]) if p1 < w0 else int(lb[p1 - w0])
                        if b2 > b:
                            b = b2
                    lb[j] = b
                min_lb = int(lb.min())
                if min_lb > 1:
                    ce = self._col_events[: self._n_cols]
                    qm = rw[np.clip(ce, 0, self._w_pad - 1)] >= min_lb - 1
                    first = (int(np.argmax(qm)) if qm.any()
                             else self._n_cols)
                    # block-aligned so the shape family stays the one the
                    # un-cut pass would compile anyway
                    col_lo = (first // 256) * 256
            c_eff = min(
                self._wcol_cap - col_lo,
                _bucket(max(self._n_cols - col_lo, 1), 256),
            )
            cols_d = jnp.asarray(
                self._col_events[col_lo : col_lo + c_eff])

        # ---- device: one fused dispatch extends ancestry + sees, then one
        # ssm block call covers every new row x every live column (the
        # b-side gather happens once per pass, not once per chunk)
        b0 = w0 // self._block
        b1 = -(-(w0 + n_new) // self._block)
        if has_forks:
            self._anc_d, self._sees_d = obs.stage_call(
                "pipeline.inc_extend_vis",
                make_extend_visibility_forked_stage(self._kern),
                self._anc_d, self._sees_d, parents_d,
                jnp.asarray(self._fork_pairs_padded()), creator_d,
                np.int32(b0), np.int32(b1), np.int32(w0),
                block=self._block, rows=n_pad_new, n_members=self._m,
                matmul_dtype_name=self._mm,
            )
        else:
            self._anc_d = obs.stage_call(
                "pipeline.inc_extend_vis",
                make_extend_visibility_stage(self._kern),
                self._anc_d, parents_d, np.int32(b0), np.int32(b1),
                block=self._block, matmul_dtype_name=self._mm,
            )
            self._sees_d = self._anc_d
        if self._cache_blocks:
            # gather the new rows' a-side once; the pass's witness-column
            # adds reuse it (new witnesses are always new rows)
            self._ars_cache = obs.stage_call(
                "pipeline.ssm_gather_rows", ssm_gather_rows_stage,
                self._sees_d, mt_d, np.int32(w0), rows=n_pad_new,
            )
            self._ars_key = (w0, n_pad_new)
            part = obs.stage_call(
                "pipeline.ssm_block_from_rows", ssm_block_from_rows_stage,
                self._ars_cache, self._sees_d, mt_d, stake_d, cols_d,
                np.int32(0), rows=n_pad_new,
                tot_stake=self._tot, matmul_dtype_name=self._mm,
            )
        else:
            part = self._ssm_block_fn(
                self._sees_d, mt_d, stake_d, cols_d, np.int32(w0),
                rows=n_pad_new, tot_stake=self._tot,
                matmul_dtype_name=self._mm,
            )
        self._ssm_d = obs.stage_call(
            "pipeline.inc_ssm_update", update_block_stage,
            self._ssm_d, part, np.int32(w0), np.int32(col_lo),
        )
        self._rows_hi = w0 + n_pad_new

        # ---- resumed rounds scan over the new events only
        with obs.span("swirld.rounds", slots=self._s_cap,
                      forked=has_forks):
            r_base_d = np.int32(self._r_base)
            state = self._rounds_span_fixpoint(
                parents_d, creator_d, stake_d, n_valid, has_forks,
                w0, n_pad_new, r_base_d,
            )
            if state is None:
                # round/slot capacity overflow -> rebase, which self-heals:
                # _columns_pass grows the flagged capacity and the adopted
                # window table inherits it (never a crash)
                return [], True
            # copy=True (np.array, not asarray): device pulls are read-only
            # views, and these mirrors are mutated in place by roll/prune
            rnd_w = obs.to_host(state[0], copy=True)
            wits_w = obs.to_host(state[1], copy=True)
            tab_np = obs.to_host(state[2], copy=True)
            cnt_np = obs.to_host(state[3], copy=True)
            _tally_rounds(self._fork_np.shape[0], self._s_cap,
                          int(cnt_np.max(initial=0)))

        # straggler guard: a witness below the frozen vote horizon could
        # change a committed tally — recompute from scratch instead
        wit_mask = wits_w[sl]
        if wit_mask.any():
            wr = rnd_w[sl][wit_mask]
            if int(wr.min()) < max(self._frozen_vote_hi,
                                   self._consensus_round):
                return [], True
        self._rnd_w = rnd_w
        self._wits_w = wits_w
        self._tab_np = tab_np
        self._cnt_np = cnt_np
        self._max_round = max(
            self._max_round, int(rnd_w[: w0 + n_new].max(initial=0))
        )
        self._grow_global(n1)
        self._round_g[gsl] = rnd_w[sl]
        self._wits_g[gsl] = wit_mask
        self._n_done = n1

        # ---- fame over the retained round window
        with obs.span("swirld.fame"):
            need = self._max_round - self._r_base + 3
            if need > self._r_fame:
                self._r_fame = min(self._r_cap, _bucket(need, 8))
            famous_d, dec_d = obs.stage_call(
                "pipeline.inc_fame", fame_window_stage,
                self._sees_d, self._ssm_d, jnp.asarray(self._colpos_w),
                state[2], creator_d, jnp.asarray(self._coin_w), stake_d,
                tot_stake=self._tot, coin_period=self.config.coin_period,
                r_max=self._r_fame, s_max=self._s_cap, has_forks=has_forks,
                matmul_dtype_name=self._mm,
            )
            fam = np.full((self._r_cap, self._s_cap), -1, np.int8)
            fam[: self._r_fame] = obs.to_host(famous_d).reshape(
                self._r_fame, self._s_cap
            )
            dec = np.full((self._r_cap, self._s_cap), -1, np.int32)
            dec[: self._r_fame] = obs.to_host(dec_d).reshape(
                self._r_fame, self._s_cap
            )
            self._famous_np = fam
            self._dec_np = dec

        # ---- order extraction for newly fame-complete rounds
        with obs.span("swirld.order") as osp:
            k_done = self._consensus_round - self._r_base
            ncomp = 0
            for k in range(self._r_cap):
                valid = self._tab_np[k] >= 0
                if self._cnt_np[k] <= 0:
                    break
                if self._max_round < self._r_base + k + 2:
                    break
                if (fam[k][valid] < 0).any():
                    break
                ncomp = k + 1
            ordered_new: List[int] = []
            if ncomp > k_done:
                if ncomp > self._r_ord:
                    self._r_ord = min(self._r_cap, _bucket(ncomp, 2))
                # the scan masks rounds past the fame-complete prefix, so its
                # cost window only needs to reach ncomp — not the historical
                # high-water mark (which still bounds the bucket family)
                r_ord_eff = min(self._r_ord, max(2, _bucket(ncomp, 2)))
                ts_unique, t_rank = np.unique(self._t_w, return_inverse=True)
                t_rank = t_rank.astype(np.int32).reshape(self._t_w.shape)
                rr_d, ts_d, recv_d = obs.stage_call(
                    "pipeline.inc_order", order_window_stage,
                    self._anc_d, state[2], state[3],
                    jnp.asarray(fam.reshape(-1)), creator_d, parents_d[:, 0],
                    jnp.asarray(t_rank),
                    np.int32(self._max_round - self._r_base),
                    np.int32(n_valid), jnp.asarray(self._recv_w),
                    r_max=r_ord_eff, s_max=self._s_cap,
                    chain=self._chain_cap,
                )
                rr_np = obs.to_host(rr_d)
                tsr_np = obs.to_host(ts_d)
                recv_np = obs.to_host(recv_d, copy=True)
                max_dec = self._frozen_vote_hi
                for k in range(k_done, ncomp):
                    slots = self._tab_np[k]
                    fam_events: List[int] = []
                    for s in range(self._s_cap):
                        e = int(slots[s])
                        if e < 0:
                            continue
                        is_f = int(fam[k, s]) == 1
                        self._famous_committed[lo + e] = is_f
                        if is_f:
                            fam_events.append(e)
                        max_dec = max(max_dec, self._r_base + int(dec[k, s]))
                    ufw = _unique_famous(fam_events, self._creator_w)
                    whiten = _whiten_sigs(p.sig(lo + e) for e in ufw)
                    entries = []
                    for w in np.where(rr_np == k)[0]:
                        gi = lo + int(w)
                        cts = int(ts_unique[tsr_np[w]])
                        tie = crypto.hash_bytes(whiten + p.event_id(gi))
                        entries.append((cts, tie, gi))
                    entries.sort(key=lambda x: (x[0], x[1]))
                    for cts, _tie, gi in entries:
                        self._rr_g[gi] = self._r_base + k
                        self._cts_g[gi] = cts
                        self._order.append(gi)
                        ordered_new.append(gi)
                self._frozen_vote_hi = max_dec
                self._consensus_round = self._r_base + ncomp
                self._recv_w = recv_np
            osp.args["ordered"] = len(ordered_new)

        # ---- advance the round window and prune the decided prefix
        with obs.span("swirld.retire"):
            dr = self._consensus_round - self._r_base
            if dr > 0:
                self._roll_rounds(dr)
            self._maybe_prune()
        return ordered_new, False

    def _roll_rounds(self, dr: int) -> None:
        self._on_roll(dr)

        def roll(a, fill):
            out = np.full_like(a, fill)
            out[:-dr] = a[dr:]
            return out

        self._tab_np = roll(self._tab_np, -1)
        self._cnt_np = roll(self._cnt_np, 0)
        self._famous_np = roll(self._famous_np, -1)
        self._dec_np = roll(self._dec_np, -1)
        self._r_base += dr
        self._maybe_compact_columns()

    def _live_col_mask(self) -> np.ndarray:
        """Which occupied column slots are still queryable: witness rounds
        at or above the committed round window (everything below can never
        be asked again — the straggler guard rebases first)."""
        ce = self._col_events[: self._n_cols]
        valid = ce >= 0
        return valid & (
            self._rnd_w[np.clip(ce, 0, self._w_pad - 1)] >= self._r_base
        )

    def _maybe_compact_columns(self) -> None:
        """Roll-time column compaction: columns of retired rounds keep
        padding every ssm block matmul until the next prune; once they
        outnumber a quarter of the store, gather the live columns left.
        Prune does the same compaction as part of its row shift."""
        live = self._live_col_mask()
        n_live = int(live.sum())
        stale = self._n_cols - n_live
        if stale < 256 or stale * 4 < self._n_cols:
            return
        keep = np.full((self._wcol_cap,), -1, np.int32)
        pos_live = np.where(live)[0]
        keep[: len(pos_live)] = pos_live
        kept_events = self._col_events[pos_live]
        self._ssm_d = obs.stage_call(
            "pipeline.inc_compact_cols", compact_cols_stage,
            self._ssm_d, jnp.asarray(keep),
        )
        self._colpos_w[:] = -1
        ce = np.full((self._wcol_cap,), -1, np.int32)
        ce[: len(kept_events)] = kept_events
        self._colpos_w[kept_events] = np.arange(
            len(kept_events), dtype=np.int32
        )
        self._col_events = ce
        self._n_cols = len(kept_events)

    # ------------------------------------------------------------- prune

    def _maybe_prune(self) -> None:
        w_used = self._n_done - self._lo
        if w_used == 0:
            return
        nr = ~self._recv_w[:w_used]
        d = int(np.argmax(nr)) if nr.any() else w_used
        if self._fork_np.shape[0]:
            d = min(d, int(self._fork_np[:, 1:].min()))
        if d < self._prune_min:
            return
        self._on_prune(d, w_used)
        self._ars_cache = self._ars_key = None
        ce = self._col_events[: self._n_cols]
        live = (
            (ce >= d)
            & (self._rnd_w[np.clip(ce, 0, self._w_pad - 1)] >= self._r_base)
        )
        pos_live = np.where(live)[0]
        keep = np.full((self._wcol_cap,), -1, np.int32)
        keep[: len(pos_live)] = pos_live
        kept_events = self._col_events[pos_live] - d
        if self._fork_np.shape[0]:
            self._anc_d, self._sees_d, self._ssm_d = obs.stage_call(
                "pipeline.inc_prune", prune_stage,
                self._anc_d, self._sees_d, self._ssm_d,
                np.int32(d), np.int32(w_used), jnp.asarray(keep),
            )
        else:
            self._anc_d, self._ssm_d = obs.stage_call(
                "pipeline.inc_prune", prune_noforks_stage,
                self._anc_d, self._ssm_d,
                np.int32(d), np.int32(w_used), jnp.asarray(keep),
            )
            self._sees_d = self._anc_d
        # host mirrors
        w2 = w_used - d
        pw = self._parents_w[d:w_used]
        self._parents_w[:w2] = np.where(pw >= d, pw - d, -1)
        self._parents_w[w2:] = -1

        def roll1(a, fill):
            a[:w2] = a[d:w_used]
            a[w2:] = fill

        roll1(self._creator_w, 0)
        roll1(self._coin_w, 0)
        roll1(self._t_w, 0)
        roll1(self._rnd_w, 0)
        roll1(self._wits_w, False)
        roll1(self._recv_w, False)
        self._recompute_depth(w2)
        # member table + fork pairs + witness table entries shift by d
        self._rebuild_member_table(w2)
        if self._fork_np.shape[0]:
            self._fork_np = np.stack(
                [self._fork_np[:, 0], self._fork_np[:, 1] - d,
                 self._fork_np[:, 2] - d], axis=1,
            )
        tv = self._tab_np >= 0
        self._tab_np = np.where(tv, self._tab_np - d, -1)
        # rebuilt column store positions
        self._colpos_w[:] = -1
        ce2 = np.full((self._wcol_cap,), -1, np.int32)
        ce2[: len(kept_events)] = kept_events
        self._colpos_w[kept_events] = np.arange(
            len(kept_events), dtype=np.int32
        )
        self._col_events = ce2
        self._n_cols = len(kept_events)
        self._lo += d
        self._rows_hi = w2

    # ------------------------------------------------------------ rebase

    def _rebase(self) -> List[int]:
        """Full-recompute fallback: run the batch columns pipeline over the
        whole packed DAG, commit its outputs, and lift the device
        intermediates into fresh carried-window state (then prune)."""
        packed = self.packer.pack()
        n = packed.n
        prev_ordered = len(self._order)
        # witness-slot capacity must match the window table (monotone)
        extras = (
            len(set(packed.fork_pairs[:, 2].tolist()))
            if len(packed.fork_pairs)
            else 0
        )
        self._s_cap = max(self._s_cap, self._m + extras + 1)
        arrays, statics, ts_unique = prepare_inputs(
            packed, self.config, block=self._block, s_max=self._s_cap,
            matmul_dtype_name=self._mm,
        )
        chain = statics["chain"]
        r_rounds = min(statics["r_max"], _bucket(chain + 1, 32))
        out, aux = _columns_pass(
            packed, self.config, arrays["parents"], arrays["creator"],
            arrays["t_rank"], arrays["coin"], arrays["stake"],
            arrays["member_table"],
            n=n, tot=self._tot, block=self._block, r_rounds=r_rounds,
            s_max=self._s_cap, chain=chain, matmul_dtype_name=self._mm,
            # default kernel -> None, so the batch pass keeps its own
            # per-pass a-side gather cache; only a custom backend
            # (mesh / Pallas) overrides the seam
            ssm_block_fn=None if self._cache_blocks else self._ssm_block_fn,
        )
        # adopt any self-healed capacities (overflow retries inside the
        # batch pass grow s_max/r_rounds; the carried window table must
        # match the batch table's slot shape)
        self._s_cap = max(self._s_cap, aux["s_max"])
        heals = int(aux["overflow_retries"])
        self.overflow_heals += heals
        if heals and self.flightrec is not None:
            oo = obs.current()
            self.flightrec.trigger(
                "overflow_heal", node=self.flightrec_label,
                detail={"retries": heals, "s_cap": self._s_cap},
                decided_frontier={
                    self.flightrec_label: {
                        "decided": prev_ordered,
                        "round": self._consensus_round,
                    },
                },
                registry=oo.registry if oo is not None else None,
            )
        result = _finalize_spanned(packed, out, ts_unique)

        # ---- commit everything the batch pass decided
        self._grow_global(n)
        self._round_g[:n] = out["round"][:n]
        self._wits_g[:n] = out["is_witness"][:n]
        self._rr_g[:n] = result.round_received
        self._cts_g[:n] = result.consensus_ts
        self._order = list(result.order)
        self._max_round = int(out["max_round"])
        self._n_done = n
        self._g_done = packed.fork_pairs.shape[0]
        tabf = out["wit_table"]
        r_tight = tabf.shape[0]
        fam = out["famous"].reshape(tabf.shape)
        dec = out["fame_decided_at"].reshape(tabf.shape)
        cntf = out["wit_count"]
        cr = 0
        while cr < r_tight:
            valid = tabf[cr] >= 0
            if cntf[cr] <= 0 or self._max_round < cr + 2:
                break
            if (fam[cr][valid] < 0).any():
                break
            cr += 1
        self._consensus_round = cr
        self._famous_committed = {}
        fv = 0
        for r in range(cr):
            for s in range(tabf.shape[1]):
                e = int(tabf[r, s])
                if e < 0:
                    continue
                self._famous_committed[e] = bool(fam[r, s] == 1)
                fv = max(fv, int(dec[r, s]))
        self._frozen_vote_hi = fv

        # ---- choose the pruned boundary and lift the window
        received = result.round_received >= 0
        nr = ~received
        lo = int(np.argmax(nr)) if nr.any() else n
        if packed.fork_pairs.shape[0]:
            lo = min(lo, int(packed.fork_pairs[:, 1:].min()))
        self._lo = lo
        self._r_base = cr
        with obs.span("swirld.retire"):
            self._on_rebase(packed, out, aux)
        w_used = n - lo
        self._w_pad = max(
            self._w_pad,
            _bucket(w_used + 2 * self._chunk, self._window_bucket),
        )
        r_need = self._max_round - cr + 16
        if r_need > self._r_cap:
            self._r_cap = _bucket(r_need, 16)
        w_pad = self._w_pad
        self._alloc_mirrors(w_pad)
        pg = packed.parents[lo:n].astype(np.int32)
        self._parents_w[:w_used] = np.where(pg >= lo, pg - lo, -1)
        self._creator_w[:w_used] = packed.creator[lo:n]
        self._coin_w[:w_used] = packed.coin[lo:n]
        self._t_w[:w_used] = packed.t[lo:n]
        self._rnd_w[:w_used] = out["round"][lo:n]
        self._wits_w[:w_used] = out["is_witness"][lo:n]
        self._recv_w[:w_used] = received[lo:]
        self._recompute_depth(w_used)
        # member table over the window
        self._rebuild_member_table(w_used)
        # fork pairs, window-remapped (all members >= lo by the cap above)
        if packed.fork_pairs.shape[0]:
            fp = packed.fork_pairs.astype(np.int32)
            self._fork_np = np.stack(
                [fp[:, 0], fp[:, 1] - lo, fp[:, 2] - lo], axis=1
            )
        else:
            self._fork_np = np.zeros((0, 3), np.int32)
        # witness table rows [cr, cr + r_cap), entries window-remapped
        self._tab_np = np.full((self._r_cap, self._s_cap), -1, np.int32)
        self._cnt_np = np.zeros((self._r_cap,), np.int32)
        self._famous_np = np.full((self._r_cap, self._s_cap), -1, np.int8)
        self._dec_np = np.full((self._r_cap, self._s_cap), -1, np.int32)
        hi = min(r_tight, cr + self._r_cap)
        rows = hi - cr
        if rows > 0:
            # the batch pass's fame table may hold fewer (empty-tail)
            # slots than the window's capacity
            sw = tabf.shape[1]
            tw = tabf[cr:hi].astype(np.int32)
            self._tab_np[:rows, :sw] = np.where(tw >= 0, tw - lo, -1)
            self._cnt_np[:rows] = cntf[cr:hi]
            self._famous_np[:rows, :sw] = fam[cr:hi]
            self._dec_np[:rows, :sw] = dec[cr:hi]
        # column store: keep retained-round witness columns
        bat_pos = aux["col_pos"]
        bat_ssm = obs.to_host(aux["ssm_c"])
        kept = [
            (e, int(bat_pos[e]))
            for e in range(lo, n)
            if bat_pos[e] >= 0 and int(out["round"][e]) >= cr
            and bool(out["is_witness"][e])
        ]
        n_cols = len(kept)
        self._wcol_cap = max(self._wcol_cap, _bucket(n_cols + 128, 256))
        ssm_w = np.zeros((w_pad, self._wcol_cap), bool)
        self._col_events = np.full((self._wcol_cap,), -1, np.int32)
        if kept:
            pos_list = [pos for _e, pos in kept]
            ssm_w[:w_used, :n_cols] = bat_ssm[lo:n][:, pos_list]
            for j, (e, _pos) in enumerate(kept):
                self._col_events[j] = e - lo
                self._colpos_w[e - lo] = j
        self._n_cols = n_cols
        # visibility slabs, window-sliced (sees aliases anc while fork-free)
        bat_anc = obs.to_host(aux["anc"])
        anc_w = np.zeros((w_pad, w_pad), bool)
        anc_w[:w_used, :w_used] = bat_anc[lo:n, lo:n]
        self._anc_d = self._put(anc_w)
        if packed.fork_pairs.shape[0]:
            bat_sees = obs.to_host(aux["sees"])
            sees_w = np.zeros((w_pad, w_pad), bool)
            sees_w[:w_used, :w_used] = bat_sees[lo:n, lo:n]
            self._sees_d = self._put(sees_w)
        else:
            self._sees_d = self._anc_d
        self._ssm_d = self._put(ssm_w)
        self._rows_hi = w_used
        self._ars_cache = self._ars_key = None
        self._initialized = True
        return self._order[prev_ordered:]
