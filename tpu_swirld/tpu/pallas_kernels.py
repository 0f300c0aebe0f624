"""Pallas TPU kernels for the consensus pipeline's hot op.

The strongly-sees matrix is the pipeline's FLOP bottleneck (Θ(N²·N/M·M)
boolean-matmul work) and the kernel BASELINE.json's north star names
("batched boolean matrix-power / BFS-style reachability kernel in
Pallas").  The XLA path (:func:`tpu_swirld.tpu.pipeline.ssm_matrix`)
re-gathers the per-member slabs and materializes an N×N int32 tally in
HBM on every member iteration; this kernel instead

- pre-gathers the member slabs ONCE into two dense operands with affine
  block indexing:  ``A[N, M*K]`` ("x sees z", creator-grouped columns) and
  ``B[M*K, N]`` ("z sees w"),
- walks a ``(N/Tm, N/Tn, M)`` grid with the member axis innermost; the
  per-tile stake tally lives in a VMEM scratch accumulator across the
  member steps (TPU grids execute sequentially, so the scratch persists),
- performs each member's ``(Tm,K)@(K,Tn)`` hop on the MXU in bfloat16
  (0/1 products, f32 accumulation — exact), thresholds >0 into the
  int32 stake tally on the VPU, and
- writes the strict-2/3 supermajority bool tile exactly once, on the
  last member step.

HBM traffic: A is read N/Tn times, B N/Tm times, the output written once
— the int32 tally never touches HBM (the XLA path rewrites it M times).

Beyond the full-matrix kernel, this module carries the **window-extension
tile kernels** of the streaming/incremental drivers
(:func:`make_extension_kernels`): :func:`ssm_block_pallas` (strongly-sees
rows-×-columns blocks gathered straight from the resident sees slab — the
``ssm_block_fn`` seam) and :func:`bmm_or_pallas` (the blockwise ancestry
extension's boolean-matmul hop).  All kernels run bit-identically in
interpret mode, which is how CPU runs and the parity tests exercise them.

Correctness is pinned against the XLA stages by interpret-mode parity
tests (``tests/test_pallas.py``), including ragged edge shapes (windows
not tile-aligned, single-event chunks, post-widen shapes).  The kernels
compile for a described v5e chip in ``tests/test_tpu_compile.py`` and run
compiled on the chip in ``chip_smoke.py`` (phase D).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpu_swirld import obs
from tpu_swirld.tpu.pipeline import member_cols_block


@functools.lru_cache(maxsize=None)
def accel_compiled() -> bool:
    """True when the default backend lowers Pallas kernels natively
    (TPU via Mosaic, GPU via Triton).  CPU has no native lowering and
    runs interpret mode — bit-identical, per the parity pin of
    ``tests/test_pallas.py``."""
    return jax.default_backend() in ("tpu", "gpu")


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The capability probe behind every kernel factory's
    ``interpret=None`` default: an explicit True/False wins; None
    compiles on TPU/GPU and falls back to interpret mode on CPU, so the
    same driver construction runs the compiled kernels wherever the
    hardware can and stays exact everywhere else."""
    if interpret is None:
        return not accel_compiled()
    return bool(interpret)


def _ssm_kernel(stake_ref, a_ref, b_ref, out_ref, acc_ref, *, n_members,
                tot_stake):
    m = pl.program_id(2)

    @pl.when(m == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    hit = (
        jnp.dot(a_ref[:], b_ref[:], preferred_element_type=jnp.float32)
        > 0.5
    )
    acc_ref[:] += hit.astype(jnp.int32) * stake_ref[m]

    @pl.when(m == n_members - 1)
    def _():
        out_ref[:] = 3 * acc_ref[:] > 2 * tot_stake


def ssm_matrix_pallas(
    sees: jnp.ndarray,
    member_table: jnp.ndarray,
    stake: jnp.ndarray,
    tot_stake: int,
    matmul_dtype=jnp.bfloat16,
    *,
    tile_m: int = 256,
    tile_n: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Strongly-sees (∃-z rule) as a single Pallas kernel.  Drop-in
    replacement for :func:`tpu_swirld.tpu.pipeline.ssm_matrix` (pass via
    ``run_consensus(..., use_pallas_ssm=True)``).  ``interpret=None``
    resolves via :func:`resolve_interpret` (compiled on TPU/GPU)."""
    interpret = resolve_interpret(interpret)
    n = sees.shape[0]
    n_members, k = member_table.shape
    tile_m = _fit_tile(tile_m, n)
    tile_n = _fit_tile(tile_n, n)
    k_pad = max(128, ((k + 127) // 128) * 128)

    idx = member_table.reshape(-1)
    valid = idx >= 0
    idxc = jnp.clip(idx, 0, n - 1)
    # creator-grouped slabs, padded to (M, k_pad) columns/rows
    a = (sees[:, idxc] & valid[None, :]).astype(matmul_dtype)      # N, M*k
    b = (sees[idxc, :] & valid[:, None]).astype(matmul_dtype)      # M*k, N
    if k_pad != k:
        a = jnp.pad(
            a.reshape(n, n_members, k), ((0, 0), (0, 0), (0, k_pad - k))
        ).reshape(n, n_members * k_pad)
        b = jnp.pad(
            b.reshape(n_members, k, n), ((0, 0), (0, k_pad - k), (0, 0))
        ).reshape(n_members * k_pad, n)

    kernel = functools.partial(
        _ssm_kernel, n_members=n_members, tot_stake=tot_stake
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.bool_),
        grid=(n // tile_m, n // tile_n, n_members),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),          # stake, whole
            pl.BlockSpec(
                (tile_m, k_pad),
                lambda i, j, m: (i, m),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (k_pad, tile_n),
                lambda i, j, m: (m, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile_m, tile_n),
            lambda i, j, m: (i, j),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(stake.astype(jnp.int32), a, b)


def make_ssm_fn(*, interpret: Optional[bool] = None, tile_m: int = 256,
                tile_n: int = 256):
    """Adapter matching the ``ssm_fn`` seam of ``rounds_body``."""
    interpret = resolve_interpret(interpret)

    def ssm_fn(sees, member_table, stake, tot_stake, dtype):
        return ssm_matrix_pallas(
            sees, member_table, stake, tot_stake, dtype,
            tile_m=tile_m, tile_n=tile_n, interpret=interpret,
        )

    return ssm_fn


def _fit_tile(t: int, n: int) -> int:
    """Shrink the requested tile by halving until it divides ``n`` (all
    pipeline shapes are power-of-two-friendly buckets; a non-dividing
    odd ``n`` is rejected rather than searched for exotic divisors)."""
    t = min(t, n)
    while n % t:
        t //= 2
    if t < 8:
        raise ValueError(f"no usable tile for n={n}")
    return t


@functools.partial(
    jax.jit,
    static_argnames=("rows", "tot_stake", "matmul_dtype_name", "tile_m",
                     "tile_n", "interpret"),
)
def ssm_block_pallas(sees, member_table, stake, cols, row0, *, rows,
                     tot_stake, matmul_dtype_name,
                     tile_m: int = 256, tile_n: int = 128,
                     interpret: Optional[bool] = None):
    """Strongly-sees *block* for window rows ``[row0, row0 + rows)`` ×
    column events ``cols`` as one Pallas kernel — the windowed
    counterpart of :func:`ssm_matrix_pallas`, matching the
    ``ssm_block_fn`` seam of :func:`tpu_swirld.tpu.pipeline.
    ssm_block_stage`.

    The row/column gathers read **tiles of the sees slab directly** (the
    one slab the store budgets — no resident per-member gather slabs); the
    kernel then walks a ``(rows/Tm, C/Tn, M)`` grid with the member axis
    innermost, accumulating the per-tile stake tally in VMEM scratch
    exactly as the full-matrix kernel does — the int32 tally never
    touches HBM.
    """
    interpret = resolve_interpret(interpret)   # static: resolved at trace
    matmul_dtype = (
        jnp.bfloat16 if matmul_dtype_name == "bfloat16" else jnp.float32
    )
    n = sees.shape[0]
    n_members, k = member_table.shape
    c = cols.shape[0]
    tile_m = _fit_tile(tile_m, rows)
    tile_n = _fit_tile(tile_n, c)
    k_pad = max(128, ((k + 127) // 128) * 128)
    idx = member_table.reshape(-1)
    valid = idx >= 0
    idxc = jnp.clip(idx, 0, n - 1)
    col_valid = cols >= 0
    sees_rows = jax.lax.dynamic_slice(sees, (row0, 0), (rows, n))
    a = (
        (sees_rows[:, idxc] & valid[None, :])
        .reshape(rows, n_members, k)
    )                                                           # rows, M, K
    b_cols = member_cols_block(sees, idxc, valid, cols).reshape(
        n_members, k, c
    )                                                           # M, K, C
    if k_pad != k:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, k_pad - k)))
        b_cols = jnp.pad(b_cols, ((0, 0), (0, k_pad - k), (0, 0)))
    a = a.reshape(rows, n_members * k_pad).astype(matmul_dtype)
    b_cols = b_cols.reshape(n_members * k_pad, c).astype(matmul_dtype)

    kernel = functools.partial(
        _ssm_kernel, n_members=n_members, tot_stake=tot_stake
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, c), jnp.bool_),
        grid=(rows // tile_m, c // tile_n, n_members),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # stake
            pl.BlockSpec(
                (tile_m, k_pad),
                lambda i, j, m: (i, m),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (k_pad, tile_n),
                lambda i, j, m: (m, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile_m, tile_n),
            lambda i, j, m: (i, j),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.int32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(stake.astype(jnp.int32), a, b_cols)
    return out & col_valid[None, :]


def make_ssm_block_fn(*, interpret: Optional[bool] = None,
                      tile_m: int = 256, tile_n: int = 128):
    """Adapter matching the ``ssm_block_fn`` seam of the incremental /
    streaming drivers (:class:`tpu_swirld.tpu.pipeline.
    IncrementalConsensus`) and of :func:`tpu_swirld.tpu.pipeline.
    _columns_pass`."""
    interpret = resolve_interpret(interpret)

    def ssm_block_fn(sees, member_table, stake, cols, row0, *, rows,
                     tot_stake, matmul_dtype_name):
        return ssm_block_pallas(
            sees, member_table, stake, cols, row0, rows=rows,
            tot_stake=tot_stake, matmul_dtype_name=matmul_dtype_name,
            tile_m=tile_m, tile_n=tile_n, interpret=interpret,
        )

    return ssm_block_fn


def _bmm_kernel(a_ref, b_ref, out_ref):
    out_ref[:] = (
        jnp.dot(a_ref[:], b_ref[:], preferred_element_type=jnp.float32)
        > 0.5
    )


def bmm_or_pallas(a, b, matmul_dtype, *, tile_m: int = 128,
                  tile_n: int = 256, interpret: Optional[bool] = None):
    """Tiled boolean matmul (OR over 0/1 products) as a Pallas kernel —
    the MXU hop of the blockwise ancestry extension (``ExtensionKernels.
    bmm``).  The contraction axis (one event block) rides whole into
    VMEM; the output grid is ``(P/Tm, R/Tn)``.  Exact: 0/1 products with
    f32 accumulation, thresholded at 0.5."""
    interpret = resolve_interpret(interpret)
    p, q = a.shape
    r = b.shape[1]
    try:
        tile_m = _fit_tile(tile_m, p)
        tile_n = _fit_tile(tile_n, r)
    except ValueError:
        # shapes the grid cannot tile — e.g. the forked fused stage's
        # n_members-wide one-hot hop on a small network — take the plain
        # XLA matmul (exact either way; only the hot shapes need the MXU).
        # Counted at trace time, so a hot shape that lands here shows.
        o = obs.current()
        if o is not None:
            o.registry.counter(
                "pallas_bmm_fallback", {"shape": f"{p}x{q}x{r}"}
            ).inc()
        return (
            jnp.matmul(
                a.astype(matmul_dtype), b.astype(matmul_dtype),
                preferred_element_type=jnp.float32,
            )
            > 0.5
        )
    q_pad = max(128, ((q + 127) // 128) * 128)
    am = a.astype(matmul_dtype)
    bm = b.astype(matmul_dtype)
    if q_pad != q:
        am = jnp.pad(am, ((0, 0), (0, q_pad - q)))
        bm = jnp.pad(bm, ((0, q_pad - q), (0, 0)))
    return pl.pallas_call(
        _bmm_kernel,
        out_shape=jax.ShapeDtypeStruct((p, r), jnp.bool_),
        grid=(p // tile_m, r // tile_n),
        in_specs=[
            pl.BlockSpec(
                (tile_m, q_pad), lambda i, j: (i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (q_pad, tile_n), lambda i, j: (0, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (tile_m, tile_n), lambda i, j: (i, j),
            memory_space=pltpu.VMEM,
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
    )(am, bm)


def make_mesh_row_block_fn(mesh, *, interpret: Optional[bool] = None):
    """The row-sharded streaming block kernel
    (:func:`tpu_swirld.parallel.make_row_sharded_block_fn`) with
    :func:`bmm_or_pallas` as the shard-local matmul hop: the halo
    exchange and stake-tally psum stay XLA collectives, while each
    device's ``(rows, K) @ (K, C)`` member hops ride the MXU tile
    kernel.  Exact for the same reason the single-device pairing is
    (0/1 products, f32 accumulation, shared threshold)."""
    from tpu_swirld.parallel import make_row_sharded_block_fn

    interpret = resolve_interpret(interpret)

    def bmm(a, b, dtype):
        return bmm_or_pallas(a, b, dtype, interpret=interpret)

    return make_row_sharded_block_fn(mesh, bmm=bmm)


def make_extension_kernels(*, interpret: Optional[bool] = None,
                           tile_m: int = 256, tile_n: int = 128):
    """The Pallas :class:`~tpu_swirld.tpu.pipeline.ExtensionKernels`
    bundle for the window-extension hot path: the blockwise ancestry
    boolean-matmul hop and the strongly-sees block kernel, both consuming
    sees/ancestry slab tiles directly.  ``interpret=True`` runs the same
    kernels bit-identically off-TPU (the parity pin of
    ``tests/test_pallas.py``)."""
    from tpu_swirld.tpu.pipeline import ExtensionKernels

    interpret = resolve_interpret(interpret)

    def bmm(a, b, dtype):
        return bmm_or_pallas(a, b, dtype, interpret=interpret)

    return ExtensionKernels(
        name=f"pallas{'-interpret' if interpret else ''}",
        bmm=bmm,
        ssm_block_fn=make_ssm_block_fn(
            interpret=interpret, tile_m=tile_m, tile_n=tile_n
        ),
    )
