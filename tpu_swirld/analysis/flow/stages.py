"""Stage catalog: every jitted consensus stage traced at envelope shapes.

Each :class:`StageSpec` names one jit boundary of the consensus core (the
``obs.stage_call`` name the drivers dispatch it under), and knows how to
build its ``jax.make_jaxpr`` trace at a :class:`~tpu_swirld.analysis.flow.
envelope.ScaleEnvelope`'s shapes together with the *declared input
intervals* — the driver-guaranteed value bounds the abstract interpreter
starts from:

======================  ====================================================
input                   declared interval (driver invariant)
======================  ====================================================
``parents``             ``[-1, N-1]`` — packed parent ids, -1 = genesis
``creator``             ``[0, M-1]`` — packer-validated member index
``stake``               ``[0, stake_max]`` — config-declared per-member cap
``member_table``        ``[-1, N-1]`` — -1 pads unused fork-tip slots
``fork_pairs``          ``[-1, N-1]`` — padded accusation rows
``coin``                ``[0, 1]`` — signature coin *bit* (uint8)
``t_rank``              ``[0, N-1]`` — dense rank of the int64 timestamps
``wit_table``           ``[-1, N-1]`` — -1 = empty witness slot
``wit_count``           ``[0, s_cap]``
``famous``              ``[-1, 1]`` — int8 tri-state
``col_pos`` / ``cols``  ``[-1, C-1]`` / ``[-1, N-1]`` — -1 = no column
``row0`` / ``start``    in-range block starts (``[0, N-rows]`` etc.)
``rnd`` / ``max_round`` ``[0, N-1]`` — a round index never exceeds the
                        event count (each round needs a fresh witness)
======================  ====================================================

Window-engine specs use the window extent ``W = env.rows`` in place of
``N`` for window-local ids (the drivers remap parents/witnesses into the
resident window before dispatch) while *round numbers stay absolute*
(bounded by ``N``).

The catalog is keyed twice: by unique ``spec_id`` for the audit report,
and by ``stage_name`` for the engine-coverage check — a small observed
run of each engine (:func:`observed_stage_names`, the same
``obs.set_stage_observer`` seam as ``jit_audit.runtime_audit``) must find
every dispatched stage name covered by at least one spec, so a new jit
boundary cannot silently escape the audit.

Mesh specs trace ``shard_map`` under whatever mesh the host can build
(often a single CPU device) while the interpreter scales collectives by
the envelope's ``mesh_devices`` — a sound over-approximation of any
smaller real mesh.

Matmul dtype note: specs trace the ``float32`` hop path.  The bfloat16
hop casts the same 0/1 operands (exact in bf16) and accumulates in f32
(``preferred_element_type``), so its value ranges are identical; the
dtype name only selects the cast.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from tpu_swirld.analysis.flow.envelope import ScaleEnvelope

_BOOL = np.dtype(bool)
_I32 = np.dtype(np.int32)
_I8 = np.dtype(np.int8)
_U8 = np.dtype(np.uint8)

_F32 = "float32"


@dataclasses.dataclass(frozen=True)
class ArgDecl:
    """One traced stage argument: shape, dtype, declared value interval
    (``None`` = full dtype range)."""

    shape: Tuple[int, ...]
    dtype: object
    lo: Optional[int] = None
    hi: Optional[int] = None

    def struct(self):
        import jax

        return jax.ShapeDtypeStruct(self.shape, self.dtype)

    @property
    def iv(self):
        return None if self.lo is None else (self.lo, self.hi)


def _arr(shape, dtype=_I32, lo=None, hi=None):
    return ArgDecl(tuple(shape), dtype, lo, hi)


def _mask(shape):
    return ArgDecl(tuple(shape), _BOOL, 0, 1)


def _scalar(lo, hi, dtype=_I32):
    return ArgDecl((), dtype, lo, hi)


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One auditable jit boundary."""

    spec_id: str                 # unique catalog key ("batch.rounds_chunk")
    stage_name: str              # obs.stage_call name this trace covers
    engines: Tuple[str, ...]     # engines that dispatch it
    build: Callable              # env -> (fn, static_kwargs, [ArgDecl])


def trace_spec(spec: StageSpec, env: ScaleEnvelope):
    """``(closed_jaxpr, arg_intervals)`` for one spec at envelope shapes."""
    fn, statics, decls = spec.build(env)
    f = functools.partial(fn, **statics) if statics else fn
    import jax

    closed = jax.make_jaxpr(f)(*[d.struct() for d in decls])
    return closed, [d.iv for d in decls]


# --------------------------------------------------------------------------
# shared shape/interval vocabulary


def _dims(env: ScaleEnvelope):
    """Envelope dimensions as used by the specs (window extents never
    exceed the event count)."""
    N = env.events
    W = min(env.rows, N)
    C = min(env.wcols, N)
    return dict(
        N=N, W=W, C=C,
        M=env.members, K=env.k_cap, G=env.fork_groups,
        R=env.r_cap, S=env.s_cap,
        block=min(env.block, W), chunk=min(env.chunk, W),
        chain=env.chain_cap,
        tot=env.tot_stake, smax=env.stake_max,
    )


# --------------------------------------------------------------------------
# batch engine (full-N shapes)


def _b_visibility(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, M, G = d["N"], d["M"], d["G"]
    return (
        P.visibility_stage,
        dict(n_members=M, block=d["block"], matmul_dtype_name=_F32),
        [
            _arr((N, 2), _I32, -1, N - 1),       # parents
            _arr((N,), _I32, 0, M - 1),          # creator
            _arr((G, 3), _I32, -1, N - 1),       # fork_pairs
        ],
    )


def _b_ancestry(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N = d["N"]
    return (
        P.ancestry_stage,
        dict(block=d["block"], matmul_dtype_name=_F32),
        [_arr((N, 2), _I32, -1, N - 1)],
    )


def _b_ssm_gather_rows(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, M, K = d["N"], d["M"], d["K"]
    return (
        P.ssm_gather_rows_stage,
        dict(rows=N),
        [
            _mask((N, N)),                        # sees
            _arr((M, K), _I32, -1, N - 1),        # member_table
            _scalar(0, 0),                        # row0 (batch gathers all)
        ],
    )


def _ssm_block_decls(n, rows, cb, m, k):
    return [
        _mask((n, n)),                            # sees
        _arr((m, k), _I32, -1, n - 1),            # member_table
        _arr((m,), _I32, 0, None),                # stake (hi filled later)
        _arr((cb,), _I32, -1, n - 1),             # cols
        _scalar(0, n - rows),                     # row0
    ]


def _b_ssm_block(env, k):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, M, C = d["N"], d["M"], d["C"]
    rows = max(256, N // 2)
    decls = _ssm_block_decls(N, rows, C, M, k)
    decls[2] = _arr((M,), _I32, 0, d["smax"])
    return (
        P.ssm_block_stage,
        dict(rows=rows, tot_stake=d["tot"], matmul_dtype_name=_F32),
        decls,
    )


def _b_ssm_block_from_rows(env, k):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, M, C = d["N"], d["M"], d["C"]
    rows = max(256, N // 2)
    return (
        P.ssm_block_from_rows_stage,
        dict(rows=rows, tot_stake=d["tot"], matmul_dtype_name=_F32),
        [
            _mask((M, N, k)),                     # a_r3 (gathered a-side)
            _mask((N, N)),                        # sees
            _arr((M, k), _I32, -1, N - 1),        # member_table
            _arr((M,), _I32, 0, d["smax"]),       # stake
            _arr((C,), _I32, -1, N - 1),          # cols
            _scalar(0, N - rows),                 # row_off
        ],
    )


def _rounds_chunk_decls(n, c, m, r, s, chunk, r_hi):
    return [
        _arr((n, 2), _I32, -1, n - 1),            # parents
        _mask((n, c)),                            # ssm_c
        _arr((n,), _I32, -1, c - 1),              # col_pos
        _arr((n,), _I32, 0, m - 1),               # creator
        None,                                     # stake — filled by caller
        _scalar(0, n),                            # n_valid
        _arr((n,), _I32, 0, r_hi),                # rnd (absolute rounds)
        _mask((n,)),                              # wits
        _arr((r, s), _I32, -1, n - 1),            # tab
        _arr((r,), _I32, 0, s),                   # cnt
        _scalar(0, 3),                            # overflow bits
        _scalar(0, max(n - chunk, 0)),            # start
        _scalar(0, r_hi),                         # r_base
    ]


def _b_rounds_chunk(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, C, M, R, S = d["N"], d["C"], d["M"], d["R"], d["S"]
    decls = _rounds_chunk_decls(N, C, M, R, S, d["chunk"], N - 1)
    decls[4] = _arr((M,), _I32, 0, d["smax"])
    return (
        P.rounds_chunk_stage,
        dict(tot_stake=d["tot"], r_max=R, s_max=S, has_forks=True,
             chunk=d["chunk"]),
        decls,
    )


def _b_fame_order_cols(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, C, M, R, S = d["N"], d["C"], d["M"], d["R"], d["S"]
    return (
        P.fame_order_cols_stage,
        dict(tot_stake=d["tot"], coin_period=env.coin_period, r_max=R,
             s_max=S, chain=d["chain"], has_forks=True,
             matmul_dtype_name=_F32),
        [
            _mask((N, N)),                        # anc
            _mask((N, N)),                        # sees
            _mask((N, C)),                        # ssm_c
            _arr((N,), _I32, -1, C - 1),          # col_pos
            _arr((R, S), _I32, -1, N - 1),        # wit_table
            _arr((R,), _I32, 0, S),               # wit_count
            _arr((N,), _I32, 0, M - 1),           # creator
            _arr((N,), _U8, 0, 1),                # coin
            _arr((M,), _I32, 0, d["smax"]),       # stake
            _arr((N,), _I32, -1, N - 1),          # self_parent
            _arr((N,), _I32, 0, N - 1),           # t_rank
            _scalar(0, N - 1),                    # max_round
            _scalar(0, N),                        # n_valid
        ],
    )


def _b_rounds_stage(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, M, G = d["N"], d["M"], d["G"]
    return (
        P.rounds_stage,
        dict(tot_stake=d["tot"], block=d["block"], r_max=d["R"],
             s_max=d["S"], has_forks=True, matmul_dtype_name=_F32),
        [
            _arr((N, 2), _I32, -1, N - 1),        # parents
            _arr((N,), _I32, 0, M - 1),           # creator
            _arr((M,), _I32, 0, d["smax"]),       # stake
            _arr((G, 3), _I32, -1, N - 1),        # fork_pairs
            _arr((M, d["K"]), _I32, -1, N - 1),   # member_table
            _scalar(0, N),                        # n_valid
        ],
    )


def _b_fame_order_stage(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    N, M, R, S = d["N"], d["M"], d["R"], d["S"]
    return (
        P.fame_order_stage,
        dict(tot_stake=d["tot"], coin_period=env.coin_period, r_max=R,
             s_max=S, chain=d["chain"], has_forks=True,
             matmul_dtype_name=_F32),
        [
            _mask((N, N)),                        # anc
            _mask((N, N)),                        # sees
            _mask((N, N)),                        # ssm (full matrix path)
            _arr((R, S), _I32, -1, N - 1),        # wit_table
            _arr((R,), _I32, 0, S),               # wit_count
            _arr((N,), _I32, 0, M - 1),           # creator
            _arr((N,), _U8, 0, 1),                # coin
            _arr((M,), _I32, 0, d["smax"]),       # stake
            _arr((N,), _I32, -1, N - 1),          # self_parent
            _arr((N,), _I32, 0, N - 1),           # t_rank
            _scalar(0, N - 1),                    # max_round
            _scalar(0, N),                        # n_valid
        ],
    )


# --------------------------------------------------------------------------
# incremental / streaming engines (window shapes)


def _i_extend_vis(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W = d["W"]
    nb = W // d["block"]
    return (
        P.make_extend_visibility_stage(P.XLA_EXTENSION_KERNELS),
        dict(block=d["block"], matmul_dtype_name=_F32),
        [
            _mask((W, W)),                        # anc (donated)
            _arr((W, 2), _I32, -1, W - 1),        # parents (window-remapped)
            _scalar(0, nb),                       # b0
            _scalar(0, nb),                       # b1
        ],
    )


def _i_extend_vis_forked(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, G, M = d["W"], d["G"], d["M"]
    rows = max(256, W // 2)
    nb = W // d["block"]
    return (
        P.make_extend_visibility_forked_stage(P.XLA_EXTENSION_KERNELS),
        dict(block=d["block"], rows=rows, n_members=M,
             matmul_dtype_name=_F32),
        [
            _mask((W, W)),                        # anc
            _mask((W, W)),                        # sees
            _arr((W, 2), _I32, -1, W - 1),        # parents
            _arr((G, 3), _I32, -1, W - 1),        # fork_pairs (remapped)
            _arr((W,), _I32, 0, M - 1),           # creator
            _scalar(0, nb),                       # b0
            _scalar(0, nb),                       # b1
            _scalar(0, W - rows),                 # row0
        ],
    )


def _i_sees_materialize(env):
    from tpu_swirld.tpu import pipeline as P

    W = _dims(env)["W"]
    return P._copy_slab_stage, {}, [_mask((W, W))]


def _i_ssm_gather_rows(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, M, K = d["W"], d["M"], d["K"]
    rows = max(256, W // 2)
    return (
        P.ssm_gather_rows_stage,
        dict(rows=rows),
        [
            _mask((W, W)),
            _arr((M, K), _I32, -1, W - 1),
            _scalar(0, W - rows),
        ],
    )


def _i_ssm_block(env, k):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, M, C = d["W"], d["M"], d["C"]
    rows = max(256, W // 2)
    decls = _ssm_block_decls(W, rows, C, M, k)
    decls[2] = _arr((M,), _I32, 0, d["smax"])
    return (
        P.ssm_block_stage,
        dict(rows=rows, tot_stake=d["tot"], matmul_dtype_name=_F32),
        decls,
    )


def _i_ssm_block_from_rows(env, k):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, M, C = d["W"], d["M"], d["C"]
    rows = max(256, W // 2)
    return (
        P.ssm_block_from_rows_stage,
        dict(rows=rows, tot_stake=d["tot"], matmul_dtype_name=_F32),
        [
            _mask((M, W, k)),                     # a_r3
            _mask((W, W)),                        # sees
            _arr((M, k), _I32, -1, W - 1),        # member_table
            _arr((M,), _I32, 0, d["smax"]),       # stake
            _arr((C,), _I32, -1, W - 1),          # cols
            _scalar(0, W - rows),                 # row_off
        ],
    )


def _i_ssm_update(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, C = d["W"], d["C"]
    rows, cb = max(256, W // 2), min(256, C)
    return (
        P.update_block_stage,
        {},
        [
            _mask((W, C)),                        # ssm_c (donated)
            _mask((rows, cb)),                    # part
            _scalar(0, W - rows),                 # row0
            _scalar(0, C - cb),                   # col0
        ],
    )


def _i_rounds_span(env):
    """Fused K-chunk rounds megadispatch, the incremental rounds scan:
    same carry/decl contract as ``rounds_chunk_stage`` but the scan
    covers ``chunk * k_chunks`` events per dispatch, so the start
    scalar's bound tightens to ``W - chunk*k`` (the driver only launches
    spans that fit the window) and the interval proof must hold over the
    widest fused trip count the driver issues (k = ``SPAN_CHUNKS``).
    The rebase's ``_columns_pass`` dispatches ``rounds_chunk_stage`` at
    batch shapes, which ``batch.rounds_chunk`` audits."""
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, C, M, R, S, N = d["W"], d["C"], d["M"], d["R"], d["S"], d["N"]
    k_chunks = min(P.SPAN_CHUNKS, max(1, W // d["chunk"]))
    decls = _rounds_chunk_decls(
        W, C, M, R, S, d["chunk"] * k_chunks, N - 1
    )
    decls[4] = _arr((M,), _I32, 0, d["smax"])
    return (
        P.rounds_span_stage,
        dict(tot_stake=d["tot"], r_max=R, s_max=S, has_forks=True,
             chunk=d["chunk"], k_chunks=k_chunks),
        decls,
    )


def _i_fame(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, C, M, R, S = d["W"], d["C"], d["M"], d["R"], d["S"]
    return (
        P.fame_window_stage,
        dict(tot_stake=d["tot"], coin_period=env.coin_period, r_max=R,
             s_max=S, has_forks=True, matmul_dtype_name=_F32),
        [
            _mask((W, W)),                        # sees
            _mask((W, C)),                        # ssm_c
            _arr((W,), _I32, -1, C - 1),          # col_pos
            _arr((R, S), _I32, -1, W - 1),        # wit_table
            _arr((W,), _I32, 0, M - 1),           # creator
            _arr((W,), _U8, 0, 1),                # coin
            _arr((M,), _I32, 0, d["smax"]),       # stake
        ],
    )


def _i_order(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, M, R, S, N = d["W"], d["M"], d["R"], d["S"], d["N"]
    return (
        P.order_window_stage,
        dict(r_max=R, s_max=S, chain=d["chain"]),
        [
            _mask((W, W)),                        # anc
            _arr((R, S), _I32, -1, W - 1),        # wit_table
            _arr((R,), _I32, 0, S),               # wit_count
            _arr((R * S,), _I8, -1, 1),           # famous
            _arr((W,), _I32, 0, M - 1),           # creator
            _arr((W,), _I32, -1, W - 1),          # self_parent
            _arr((W,), _I32, 0, N - 1),           # t_rank
            _scalar(0, R),                        # max_round_local
            _scalar(0, W),                        # n_valid
            _mask((W,)),                          # received0
        ],
    )


def _i_compact_cols(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, C = d["W"], d["C"]
    return (
        P.compact_cols_stage,
        {},
        [_mask((W, C)), _arr((C,), _I32, -1, C - 1)],
    )


def _i_prune(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, C = d["W"], d["C"]
    return (
        P.prune_stage,
        {},
        [
            _mask((W, W)),                        # anc
            _mask((W, W)),                        # sees
            _mask((W, C)),                        # ssm_c
            _scalar(0, W),                        # d (pruned count)
            _scalar(0, W),                        # n_used
            _arr((C,), _I32, -1, C - 1),          # keep_cols
        ],
    )


def _i_prune_noforks(env):
    from tpu_swirld.tpu import pipeline as P

    d = _dims(env)
    W, C = d["W"], d["C"]
    return (
        P.prune_noforks_stage,
        {},
        [
            _mask((W, W)),
            _mask((W, C)),
            _scalar(0, W),
            _scalar(0, W),
            _arr((C,), _I32, -1, C - 1),
        ],
    )


# --------------------------------------------------------------------------
# mesh engine (shard_map kernels; traced under the host's mesh, collectives
# scaled by the envelope's mesh_devices via interpret's axis_sizes)


def _mesh(env):
    import jax

    from tpu_swirld.parallel import make_mesh

    return make_mesh(min(env.mesh_devices, len(jax.devices())))


def mesh_axis_sizes(env: ScaleEnvelope) -> Dict[str, int]:
    from tpu_swirld.parallel import MEMBER_AXIS

    return {MEMBER_AXIS: env.mesh_devices}


def _m_ssm_block_row(env):
    from tpu_swirld.parallel import make_row_sharded_block_fn

    d = _dims(env)
    W, M, C = d["W"], d["M"], d["C"]
    mesh = _mesh(env)
    dev = int(mesh.devices.size)
    w = (W // dev) * dev or dev           # rows must split evenly
    rows = max(256, w // 2)
    decls = _ssm_block_decls(w, rows, C, M, d["K"])
    decls[2] = _arr((M,), _I32, 0, d["smax"])
    return (
        make_row_sharded_block_fn(mesh),
        dict(rows=rows, tot_stake=d["tot"], matmul_dtype_name=_F32),
        decls,
    )


def _m_ssm_block_member(env):
    from tpu_swirld.parallel import make_ssm_block_fn_for_mesh

    d = _dims(env)
    W, M, C = d["W"], d["M"], d["C"]
    mesh = _mesh(env)
    rows = max(256, W // 2)
    decls = _ssm_block_decls(W, rows, C, M, d["K"])
    decls[2] = _arr((M,), _I32, 0, d["smax"])
    return (
        make_ssm_block_fn_for_mesh(mesh),
        dict(rows=rows, tot_stake=d["tot"], matmul_dtype_name=_F32),
        decls,
    )


def _m_consensus(env):
    from tpu_swirld.parallel import consensus_fn_for_mesh

    d = _dims(env)
    N, M, G = d["N"], d["M"], d["G"]
    mesh = _mesh(env)
    dev = int(mesh.devices.size)
    m = ((M + dev - 1) // dev) * dev      # pad_members contract
    return (
        consensus_fn_for_mesh(mesh),
        dict(tot_stake=d["tot"], coin_period=env.coin_period,
             block=d["block"], r_max=d["R"], s_max=d["S"],
             chain=d["chain"], has_forks=True, matmul_dtype_name=_F32),
        [
            _arr((N, 2), _I32, -1, N - 1),        # parents
            _arr((N,), _I32, 0, M - 1),           # creator
            _arr((N,), _I32, 0, N - 1),           # t_rank
            _arr((N,), _U8, 0, 1),                # coin
            _arr((m,), _I32, 0, d["smax"]),       # stake (padded)
            _arr((G, 3), _I32, -1, N - 1),        # fork_pairs
            _arr((m, d["K"]), _I32, -1, N - 1),   # member_table (padded)
            _scalar(0, N),                        # n_valid
        ],
    )


# --------------------------------------------------------------------------
# dynamic membership (epoch-boundary member-axis repack)


def _mb_repack(env):
    """The :func:`tpu_swirld.membership.repack.repack_stage` boundary at
    its worst case: one joiner extends the member axis M -> M+1, and the
    member table is as tall as a single creator could make it (K = N —
    one member authored every event).  Values are packed event indices
    (``-1`` padding), so the claim is they stay inside int32 at the
    envelope's event count; stake rides the config-declared cap."""
    from tpu_swirld.membership import repack as MR

    d = _dims(env)
    N, M = d["N"], d["M"]
    return (
        MR.repack_stage,
        dict(n_members_new=M + 1),
        [
            _arr((M, N), _I32, -1, N - 1),       # member_table
            _arr((M + 1,), _I32, 0, d["smax"]),  # stake_new
        ],
    )


# --------------------------------------------------------------------------
# catalog


_INC = ("incremental", "streaming", "mesh")

CATALOG: List[StageSpec] = [
    # batch
    StageSpec("batch.visibility", "pipeline.visibility_stage",
              ("batch",), _b_visibility),
    StageSpec("batch.ancestry", "pipeline.visibility_stage",
              ("batch",), _b_ancestry),
    StageSpec("batch.ssm_gather_rows", "pipeline.ssm_gather_rows",
              ("batch",), _b_ssm_gather_rows),
    StageSpec("batch.ssm_block", "pipeline.ssm_block_stage",
              ("batch",), functools.partial(_b_ssm_block, k=8)),
    StageSpec("batch.ssm_block_gemm", "pipeline.ssm_block_stage",
              ("batch",), functools.partial(_b_ssm_block, k=1)),
    StageSpec("batch.ssm_block_from_rows", "pipeline.ssm_block_from_rows",
              ("batch",), functools.partial(_b_ssm_block_from_rows, k=8)),
    StageSpec("batch.ssm_block_from_rows_gemm",
              "pipeline.ssm_block_from_rows",
              ("batch",), functools.partial(_b_ssm_block_from_rows, k=1)),
    StageSpec("batch.rounds_chunk", "pipeline.rounds_chunk_stage",
              ("batch",), _b_rounds_chunk),
    StageSpec("batch.fame_order_cols", "pipeline.fame_order_cols_stage",
              ("batch",), _b_fame_order_cols),
    StageSpec("batch.rounds", "pipeline.rounds_stage",
              ("batch",), _b_rounds_stage),
    StageSpec("batch.fame_order", "pipeline.fame_order_stage",
              ("batch",), _b_fame_order_stage),
    # incremental / streaming windows
    StageSpec("inc.extend_vis", "pipeline.inc_extend_vis",
              _INC, _i_extend_vis),
    StageSpec("inc.extend_vis_forked", "pipeline.inc_extend_vis",
              _INC, _i_extend_vis_forked),
    StageSpec("inc.sees_materialize", "pipeline.sees_materialize",
              _INC, _i_sees_materialize),
    StageSpec("inc.ssm_gather_rows", "pipeline.ssm_gather_rows",
              _INC, _i_ssm_gather_rows),
    StageSpec("inc.ssm_block", "pipeline.ssm_block_stage",
              _INC, functools.partial(_i_ssm_block, k=8)),
    StageSpec("inc.ssm_block_gemm", "pipeline.ssm_block_stage",
              _INC, functools.partial(_i_ssm_block, k=1)),
    StageSpec("inc.ssm_block_from_rows", "pipeline.ssm_block_from_rows",
              _INC, functools.partial(_i_ssm_block_from_rows, k=8)),
    StageSpec("inc.ssm_block_from_rows_gemm",
              "pipeline.ssm_block_from_rows",
              _INC, functools.partial(_i_ssm_block_from_rows, k=1)),
    StageSpec("inc.ssm_update", "pipeline.inc_ssm_update",
              _INC, _i_ssm_update),
    StageSpec("inc.rounds_span", "pipeline.rounds_span_stage",
              _INC, _i_rounds_span),
    StageSpec("inc.fame", "pipeline.inc_fame", _INC, _i_fame),
    StageSpec("inc.order", "pipeline.inc_order", _INC, _i_order),
    StageSpec("inc.compact_cols", "pipeline.inc_compact_cols",
              _INC, _i_compact_cols),
    StageSpec("inc.prune", "pipeline.inc_prune", _INC, _i_prune),
    StageSpec("inc.prune_noforks", "pipeline.inc_prune",
              _INC, _i_prune_noforks),
    # dynamic membership: every device engine repacks at epoch
    # boundaries (membership.repack.repack_packer dispatches the stage)
    StageSpec("membership.repack", "membership.repack_stage",
              ("batch",) + _INC, _mb_repack),
    # mesh kernels
    StageSpec("mesh.ssm_block_row", "pipeline.ssm_block_mesh",
              ("mesh",), _m_ssm_block_row),
    StageSpec("mesh.ssm_block_member", "pipeline.ssm_block_stage",
              ("mesh",), _m_ssm_block_member),
    StageSpec("mesh.consensus", "pipeline.mesh_consensus",
              ("batch", "mesh"), _m_consensus),
]

ENGINES = ("batch", "incremental", "streaming", "mesh")


def specs_for_engines(engines: Sequence[str]) -> List[StageSpec]:
    eng = set(engines)
    return [s for s in CATALOG if eng & set(s.engines)]


def coverage_map() -> Dict[str, List[str]]:
    """stage_call name -> spec ids that audit it."""
    out: Dict[str, List[str]] = {}
    for s in CATALOG:
        out.setdefault(s.stage_name, []).append(s.spec_id)
    return out


# --------------------------------------------------------------------------
# engine observation (the jit_audit seam): which stage names does each
# engine actually dispatch?  Every observed name must be in the catalog.


def observed_stage_names(
    engine: str,
    *,
    n_members: int = 6,
    n_events: int = 420,
    seed: int = 3,
    collect: Optional[Callable] = None,
) -> List[str]:
    """Run a small real workload of ``engine`` with the stage observer
    installed and return the sorted stage names it dispatched.

    ``collect(name, fn, args, kw)``, when given, additionally receives
    every observed call (the lattice-soundness property test replays
    them through the interpreter).
    """
    from tpu_swirld import obs as obslib
    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.sim import generate_gossip_dag

    members, stake, events, _ = generate_gossip_dag(
        n_members, n_events, seed=seed, n_forkers=1
    )
    cfg = SwirldConfig(n_members=n_members)
    names: set = set()

    def observer(name, fn, args, kw):
        names.add(name)
        if collect is not None:
            collect(name, fn, args, kw)

    obslib.set_stage_observer(observer)
    try:
        if engine == "batch":
            from tpu_swirld.packing import Packer
            from tpu_swirld.tpu.pipeline import run_consensus

            pk = Packer(members, stake)
            pk.extend(events)
            run_consensus(pk.pack(), cfg, block=64)
        else:
            from tpu_swirld.analysis.jit_audit import runtime_audit as _ra

            if engine == "incremental":
                from tpu_swirld.tpu.pipeline import IncrementalConsensus as D
                drv = D(members, stake, cfg, chunk=64,
                        window_bucket=256, prune_min=64)
            elif engine == "streaming":
                from tpu_swirld.store.streaming import StreamingConsensus as D
                drv = D(members, stake, cfg, chunk=64,
                        window_bucket=256, prune_min=64)
            elif engine == "mesh":
                import jax

                from tpu_swirld.parallel import (
                    MeshStreamingConsensus, make_mesh,
                )
                mesh = make_mesh(min(8, len(jax.devices())))
                drv = MeshStreamingConsensus(
                    mesh, members, stake, cfg, chunk=64,
                    window_bucket=256, prune_min=64,
                )
            else:
                raise ValueError(f"unknown engine {engine!r}")
            _ = _ra  # the seam this mirrors; kept for the cross-reference
            for i in range(0, len(events), 140):
                drv.ingest(events[i:i + 140])
    finally:
        obslib.set_stage_observer(None)
    return sorted(names)


def trace_concrete_call(fn, args, kw):
    """Trace one *observed* stage call: ``(closed_jaxpr, arg_intervals,
    concrete_args)`` with intervals taken from the concrete values — the
    soundness property test's input.  Static (non-array) positional args
    become point intervals."""
    import jax

    structs, ivs = [], []
    for a in args:
        arr = np.asarray(a)
        structs.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
        if arr.dtype == np.dtype(bool):
            ivs.append(None)
        elif arr.size:
            ivs.append((int(arr.min()), int(arr.max())))
        else:
            ivs.append(None)
    closed = jax.make_jaxpr(functools.partial(fn, **kw))(*structs)
    return closed, ivs
