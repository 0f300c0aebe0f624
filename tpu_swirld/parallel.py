"""SPMD sharding of the consensus pipeline over a ``jax.sharding.Mesh``.

Two shardings live here, matching the two drivers:

**Batch path (member axis).**  SURVEY.md §7 step 6 / BASELINE config 5:
the strongly-sees computation — the pipeline's FLOP bottleneck, Θ(N²·N)
boolean-matmul work — is sharded over the **member axis**: each device
owns M/D members, computes its members' ∃-z visibility hops as local
(N×K)@(K×N) matmuls, and the stake tallies are aggregated with
``lax.psum`` over the mesh.  Everything else (scans, fame, order) is
cheap and runs replicated.

**Streaming path (window axis).**  The batch sharding replicates the
visibility slabs on every device, which multiplies memory instead of
dividing it — exactly backwards for the streaming driver, whose whole
point is a bounded resident window.  :class:`MeshStreamingConsensus`
therefore **row-shards the window itself**: the ``anc``/``sees``/``ssm``
slabs live as ``P(axis, None)`` shards ((W/D, W) per device), every
from-scratch slab push goes through the driver's ``slab_put`` seam so
rebases and widenings scatter rows straight to their owners, and
:func:`make_row_sharded_block_fn` runs the extension block kernel with
one halo exchange — the gathered member rows each device owns, psum'd to
all — instead of an all-gather of the slab.  Per-device residency is
budgeted by :class:`~tpu_swirld.store.slab.SlabStore` (``n_shards`` /
``device_budget_tiles``).

Gossip stays a host-level concern exactly as in the reference's
in-process network dict; within the mesh, consensus-state reductions
ride ICI collectives inserted by XLA.

Multi-host note: the same ``shard_map`` code runs unchanged over a
multi-host mesh (``jax.distributed.initialize`` + a global device array);
the sharded axis then spans hosts and the psum rides DCN between ICI
domains.  The in-repo tests exercise an 8-device single-host mesh
(``xla_force_host_platform_device_count``), which the driver's
``dryrun_multichip`` hook replays.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_swirld import obs
from tpu_swirld.store.slab import SlabStore
from tpu_swirld.store.streaming import StreamingConsensus
from tpu_swirld.tpu.pipeline import _bmm, consensus_body, member_cols_block

MEMBER_AXIS = "members"

_STATIC = (
    "tot_stake",
    "coin_period",
    "block",
    "r_max",
    "s_max",
    "chain",
    "has_forks",
    "matmul_dtype_name",
)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """A 1-D member-axis mesh over the first ``n_devices`` devices."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    # (the mesh_devices gauge is recorded per run by run_consensus, the
    # point where an ambient Obs is reliably in scope)
    return Mesh(np.array(devs), (MEMBER_AXIS,))


def ssm_matrix_sharded(sees, member_table, stake, tot_stake, dtype, *, mesh):
    """Member-sharded strongly-sees: local matmul hops + psum stake tally.

    ``member_table`` rows and ``stake`` must be padded to a multiple of the
    mesh size (pad rows -1 / stake 0 — they contribute nothing).
    """

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(None, None), P(MEMBER_AXIS, None), P(MEMBER_AXIS)),
        out_specs=P(None, None),
    )
    def f(s, mt, stk):
        n = s.shape[0]

        def body(m, acc):
            idx = mt[m]
            valid = idx >= 0
            idxc = jnp.clip(idx, 0, n - 1)
            a = s[:, idxc] & valid[None, :]
            b = s[idxc, :] & valid[:, None]
            hit = _bmm(a, b, dtype)
            return acc + stk[m] * hit.astype(jnp.int32)

        # the per-device partial tally varies over the member axis; mark the
        # initial carry as varying so the fori_loop carry types line up
        acc0 = jnp.zeros((n, n), dtype=jnp.int32)
        acc0 = lax.pcast(acc0, (MEMBER_AXIS,), to="varying")
        acc = lax.fori_loop(0, mt.shape[0], body, acc0)
        acc = lax.psum(acc, MEMBER_AXIS)
        return 3 * acc > 2 * tot_stake

    return f(sees, member_table, stake)


# Module-level kernel caches.  Keyed on the mesh's PHYSICAL identity
# (platform + device ids + shape + axis names; ids alone repeat across
# platforms), never on the live Mesh object: a
# Mesh-keyed dict pins every mesh a test or bench round ever built —
# along with its compiled executables and device buffers — for the
# process lifetime, and two identical meshes miss each other's entries.
# Bounded FIFO so even a pathological sweep over many layouts stays flat.
_MESH_CACHE_MAX = 8


def _mesh_key(mesh: Mesh):
    return (
        tuple((d.platform, int(d.id)) for d in mesh.devices.flat),
        tuple(mesh.devices.shape),
        tuple(mesh.axis_names),
    )


def _mesh_cache_get(cache: dict, mesh: Mesh, build):
    key = _mesh_key(mesh)
    fn = cache.get(key)
    if fn is None:
        fn = build()
        cache[key] = fn
        while len(cache) > _MESH_CACHE_MAX:
            cache.pop(next(iter(cache)))
    return fn


_mesh_block_fns = {}
_mesh_row_block_fns = {}


def make_ssm_block_fn_for_mesh(mesh: Mesh):
    """Member-sharded strongly-sees *block* — the windowed counterpart of
    :func:`ssm_matrix_sharded`, matching the ``ssm_block_fn`` seam of
    :func:`tpu_swirld.tpu.pipeline.ssm_block_stage` /
    :class:`~tpu_swirld.tpu.pipeline.IncrementalConsensus`.

    Each device owns M/D member-table rows, gathers its members' row/
    column tiles straight from the (replicated) sees slab, computes the
    (rows, K) @ (K, C) ∃-z hops locally, and the int32 stake tallies ride
    one ``lax.psum`` over the member axis.  The member axis is padded to
    a mesh multiple here (pad rows are all-invalid and pad stake is 0, so
    they contribute nothing).  The same kernel serves the row-extension
    pass and the witness-column adds — exactly like the single-device
    stage, so the mesh driver rides every suffix-cut the host applies.
    """
    d = int(mesh.devices.size)

    def build():

        @functools.partial(
            jax.jit,
            static_argnames=("rows", "tot_stake", "matmul_dtype_name"),
        )
        def kernel(sees, member_table, stake, cols, row0, *, rows,
                   tot_stake, matmul_dtype_name):
            dtype = (
                jnp.bfloat16 if matmul_dtype_name == "bfloat16"
                else jnp.float32
            )
            m = member_table.shape[0]
            m_pad = ((m + d - 1) // d) * d
            if m_pad != m:
                member_table = jnp.pad(
                    member_table, ((0, m_pad - m), (0, 0)),
                    constant_values=-1,
                )
                stake = jnp.pad(stake, ((0, m_pad - m),))

            @functools.partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(
                    P(None, None),
                    P(MEMBER_AXIS, None),
                    P(MEMBER_AXIS),
                    P(None),
                    P(),
                ),
                out_specs=P(None, None),
            )
            def f(s, mtl, stkl, colsl, row0l):
                n = s.shape[0]
                ml, k = mtl.shape
                idx = mtl.reshape(-1)
                valid = idx >= 0
                idxc = jnp.clip(idx, 0, n - 1)
                cv = colsl >= 0
                s_rows = lax.dynamic_slice(s, (row0l, 0), (rows, n))
                a_r3 = (
                    (s_rows[:, idxc] & valid[None, :])
                    .reshape(rows, ml, k).transpose(1, 0, 2)
                )
                b_cols = member_cols_block(s, idxc, valid, colsl).reshape(
                    ml, k, colsl.shape[0]
                )

                def body(mm, acc):
                    hit = _bmm(a_r3[mm], b_cols[mm], dtype)
                    return acc + stkl[mm] * hit.astype(jnp.int32)

                acc0 = jnp.zeros((rows, colsl.shape[0]), dtype=jnp.int32)
                acc0 = lax.pcast(acc0, (MEMBER_AXIS,), to="varying")
                acc = lax.fori_loop(0, ml, body, acc0)
                acc = lax.psum(acc, MEMBER_AXIS)
                return (3 * acc > 2 * tot_stake) & cv[None, :]

            return f(
                sees, member_table, stake, cols,
                jnp.asarray(row0, dtype=jnp.int32),
            )

        return kernel

    return _mesh_cache_get(_mesh_block_fns, mesh, build)


def make_row_sharded_block_fn(mesh: Mesh, *, bmm=None):
    """Window-row-sharded strongly-sees block — the streaming mesh's
    extension kernel, matching the ``ssm_block_fn`` seam of
    :func:`tpu_swirld.tpu.pipeline.ssm_block_stage`.

    The sees slab arrives as a ``P(axis, None)`` row shard: each device
    holds ``W/D`` window rows over the full column width, so the resident
    window *divides* across the mesh instead of replicating (the whole
    point of the streaming driver's memory bound).  The block then runs
    with exactly one halo exchange:

    - **b-side (the halo)**: of the ``M*K`` gathered member rows, each is
      resident on exactly one device; every device gathers the rows it
      owns (others masked to zero) and one int8 ``psum`` assembles the
      full ``(M*K, C)`` b-operand everywhere — an all-gather of only the
      K member rows per member, never of the slab.
    - **a-side (local)**: the extension rows ``[row0, row0 + rows)`` are
      gathered by their owning devices only; unowned rows are zero and
      contribute nothing to the stake tally.
    - one int32 ``psum`` sums the per-device tallies (each output row is
      computed by exactly one device), and the strict-2/3 threshold runs
      replicated.

    Exact: masks reproduce the single-device gathers bit-for-bit, and the
    start-index clamp matches ``lax.dynamic_slice`` semantics.  ``bmm``
    swaps the shard-local matmul hop (e.g. the Pallas tile kernel via
    :func:`tpu_swirld.tpu.pallas_kernels.make_extension_kernels`);
    ``None`` = the XLA :func:`~tpu_swirld.tpu.pipeline._bmm`.  Built
    kernels are cached per physical mesh (default ``bmm`` only — a custom
    hop owns its own lifetime)."""
    axis = mesh.axis_names[0]
    local_bmm = bmm if bmm is not None else _bmm

    def build():

        @functools.partial(
            jax.jit,
            static_argnames=("rows", "tot_stake", "matmul_dtype_name"),
        )
        def kernel(sees, member_table, stake, cols, row0, *, rows,
                   tot_stake, matmul_dtype_name):
            dtype = (
                jnp.bfloat16 if matmul_dtype_name == "bfloat16"
                else jnp.float32
            )

            @functools.partial(
                jax.shard_map,
                mesh=mesh,
                in_specs=(
                    P(axis, None),
                    P(None, None),
                    P(None),
                    P(None),
                    P(),
                ),
                out_specs=P(None, None),
            )
            def f(s_loc, mtl, stkl, colsl, row0l):
                n_loc, n = s_loc.shape
                c = colsl.shape[0]
                ml, k = mtl.shape
                dev0 = lax.axis_index(axis) * n_loc
                idx = mtl.reshape(-1)
                valid = idx >= 0
                idxc = jnp.clip(idx, 0, n - 1)
                colsc = jnp.clip(colsl, 0, n - 1)
                cv = colsl >= 0
                # ---- b-side halo: each gathered member row lives on one
                # device; owned contributions psum to the full operand
                loc_b = idxc - dev0
                own_b = (loc_b >= 0) & (loc_b < n_loc) & valid
                b_loc = (
                    s_loc[jnp.clip(loc_b, 0, n_loc - 1)][:, colsc]
                    & own_b[:, None] & cv[None, :]
                )
                b = lax.psum(b_loc.astype(jnp.int8), axis) > 0
                # ---- a-side: local rows only (clamp matches the
                # single-device dynamic_slice start semantics)
                row0c = jnp.clip(row0l, 0, n - rows)
                ridx = row0c - dev0 + jnp.arange(rows)
                rown = (ridx >= 0) & (ridx < n_loc)
                a = (
                    s_loc[jnp.clip(ridx, 0, n_loc - 1)][:, idxc]
                    & valid[None, :] & rown[:, None]
                )
                a_r3 = a.reshape(rows, ml, k).transpose(1, 0, 2)
                b_r3 = b.reshape(ml, k, c)

                def body(mm, acc):
                    hit = local_bmm(a_r3[mm], b_r3[mm], dtype)
                    return acc + stkl[mm] * hit.astype(jnp.int32)

                acc0 = jnp.zeros((rows, c), dtype=jnp.int32)
                acc0 = lax.pcast(acc0, (axis,), to="varying")
                acc = lax.fori_loop(0, ml, body, acc0)
                acc = lax.psum(acc, axis)
                return (3 * acc > 2 * tot_stake) & cv[None, :]

            return f(
                sees, member_table, stake, cols,
                jnp.asarray(row0, dtype=jnp.int32),
            )

        return kernel

    if bmm is not None:
        return build()
    return _mesh_cache_get(_mesh_row_block_fns, mesh, build)


class MeshStreamingConsensus(StreamingConsensus):
    """Streaming consensus with the resident window **row-sharded** over
    a mesh.

    The ``anc``/``sees``/``ssm`` slabs live as ``P(axis, None)`` shards —
    (W/D, ·) rows per device — so device memory is bounded by the
    undecided window *divided by the mesh*, not replicated across it:

    - every from-scratch slab push (cold-start rebase, widening rebase)
      rides the parent's ``slab_put`` seam and scatters rows straight to
      their owning devices;
    - the extension block kernel is :func:`make_row_sharded_block_fn`
      (one b-side halo psum + one stake-tally psum per block);
    - in-place jitted stages (extension writes, donated prune rolls)
      keep the placement via GSPMD propagation; growth paths that drift
      back to replicated are re-pinned after each ingest (counted in
      ``repins`` / the ``mesh_repins`` gauge — steady state is zero);
    - the :class:`~tpu_swirld.store.slab.SlabStore` accounts per-device
      residency (``n_shards=D``) and ``device_tile_budget`` bounds the
      widest shard exactly like the global budget.

    ``window_bucket`` is rounded up to a mesh multiple so every row
    capacity the driver ever allocates splits evenly across devices.
    The archive stays host-global: spills pull decided rows to the host
    exactly as on one device, and widening fetches scatter re-admitted
    rows back through ``slab_put``.
    """

    def __init__(
        self,
        mesh: Mesh,
        members,
        stake=None,
        config=None,
        *,
        tile_budget: Optional[int] = None,
        tile: int = 256,
        device_tile_budget: Optional[int] = None,
        strict_budget: bool = False,
        store: Optional[SlabStore] = None,
        bmm=None,
        pallas: bool = False,
        **kw,
    ):
        self.mesh = mesh
        d = int(mesh.devices.size)
        axis = mesh.axis_names[0]
        self._n_devices = d
        self._nsh = NamedSharding(mesh, P(axis, None))
        self.repins = 0
        wb = max(256, int(kw.pop("window_bucket", 1024)))
        wb = -(-wb // d) * d
        kw["window_bucket"] = wb
        kw.setdefault(
            "slab_put",
            lambda x: jax.device_put(np.asarray(x), self._nsh),
        )
        if pallas and bmm is None:
            # the Pallas MXU hop inside the same halo/psum pairing;
            # interpret-vs-compiled resolves via the capability probe
            # (compiled on TPU/GPU, interpret elsewhere — bit-identical)
            from tpu_swirld.tpu.pallas_kernels import make_mesh_row_block_fn

            kernel = make_mesh_row_block_fn(mesh)
        else:
            kernel = make_row_sharded_block_fn(mesh, bmm=bmm)
        kw.setdefault(
            "ssm_block_fn",
            functools.partial(
                obs.stage_call, "pipeline.ssm_block_mesh", kernel
            ),
        )
        if store is None:
            store = SlabStore(
                tile_budget, tile=tile, strict=strict_budget,
                config=config, n_shards=d,
                device_budget_tiles=device_tile_budget,
            )
        super().__init__(members, stake, config, store=store, **kw)
        self.flightrec_label = "streaming-mesh"

    # ----------------------------------------------------------- placement

    def _pinned(self, arr):
        try:
            ok = arr.sharding.is_equivalent_to(self._nsh, arr.ndim)
        except (AttributeError, TypeError):
            ok = False
        return arr if ok else None

    def _repin(self) -> int:
        """Re-scatter any slab whose placement drifted off the row shard
        (pad growth re-materializes; steady-state extension keeps it)."""
        if not self._initialized:
            return 0
        n = 0
        aliased = self._sees_d is self._anc_d
        if self._pinned(self._anc_d) is None:
            self._anc_d = jax.device_put(self._anc_d, self._nsh)
            n += 1
        if aliased:
            self._sees_d = self._anc_d
        elif self._pinned(self._sees_d) is None:
            self._sees_d = jax.device_put(self._sees_d, self._nsh)
            n += 1
        if self._pinned(self._ssm_d) is None:
            self._ssm_d = jax.device_put(self._ssm_d, self._nsh)
            n += 1
        if n:
            self._ars_cache = self._ars_key = None
            self.repins += n
            o = obs.current()
            if o is not None:
                o.registry.gauge("mesh_repins").set(self.repins)
        return n

    # ------------------------------------------------------------- ingest

    def ingest(self, events=()) -> dict:
        st = super().ingest(events)
        self._repin()
        st["mesh_devices"] = self._n_devices
        st["mesh_repins"] = self.repins
        return st


def streaming_consensus_for_mesh(
    mesh: Mesh, members, stake=None, config=None, **kw
):
    """A :class:`MeshStreamingConsensus` over ``mesh`` — the resident
    window row-sharded across devices, extension blocks running on
    row-local data with one halo exchange and one ``psum`` stake tally
    (and the same suffix cuts / slab donation as the single-device
    driver)."""
    return MeshStreamingConsensus(mesh, members, stake, config, **kw)


_mesh_fns = {}


def consensus_fn_for_mesh(mesh: Mesh):
    """Jitted end-to-end consensus with the SSM phase sharded over ``mesh``."""

    def build():
        def ssm_fn(sees, member_table, stake, tot_stake, dtype):
            return ssm_matrix_sharded(
                sees, member_table, stake, tot_stake, dtype, mesh=mesh
            )

        return functools.partial(jax.jit, static_argnames=_STATIC)(
            functools.partial(consensus_body, ssm_fn=ssm_fn)
        )

    return _mesh_cache_get(_mesh_fns, mesh, build)


def pad_members(member_table: np.ndarray, stake: np.ndarray, n_devices: int):
    """Pad the member axis to a multiple of the mesh size (-1 rows, 0 stake)."""
    m = member_table.shape[0]
    m_pad = ((m + n_devices - 1) // n_devices) * n_devices
    o = obs.current()
    if o is not None:
        o.registry.gauge("mesh_member_pad").set(m_pad - m)
    if m_pad == m:
        return member_table, stake
    extra = m_pad - m
    member_table = np.concatenate(
        [member_table, np.full((extra, member_table.shape[1]), -1, np.int32)]
    )
    stake = np.concatenate([stake, np.zeros((extra,), stake.dtype)])
    return member_table, stake
