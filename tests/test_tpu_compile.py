"""The main-path programs compile for a described TPU v5e (``v5e:2x2``).

Nothing runs: the TPU compiler refuses here what the chip would refuse
(VMEM limits, unaligned tiles, programs too large for the device), at no
chip time.  The topology is described only inside the ``topo`` fixture
(never at import): one process at a time may load the TPU library, and
xdist workers all import this file.  Keep every such compile in this one
file, so that one worker loads the library.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from tpu_swirld.config import SwirldConfig
from tpu_swirld.packing import pack_events
from tpu_swirld.sim import generate_gossip_dag
from tpu_swirld.tpu import pallas_kernels as pk
from tpu_swirld.tpu import pipeline


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    return jax.jit(fn, **kw).lower(*args).compile()


# ------------------------------------------------- batch body, config 3/4


@pytest.mark.parametrize("n_forkers", [0, 21], ids=["clean", "forked"])
def test_consensus_body_config3_width(one_chip, n_forkers):
    members, stake, events, _keys = generate_gossip_dag(
        64, 10000, seed=1, n_forkers=n_forkers
    )
    packed = pack_events(events, members, stake)
    assert bool(len(packed.fork_pairs)) == bool(n_forkers)
    arrays, statics, _ts = pipeline.prepare_inputs(
        packed, SwirldConfig(n_members=64), matmul_dtype_name="bfloat16"
    )
    if n_forkers:
        # the worst-case slot capacity (2019 here) makes the forked fame
        # tally ~66 GB, which the compiler refuses; run_consensus gives
        # its fame stage the observed count, at most 192 for this DAG
        assert statics["s_max"] > 2000
        statics["s_max"] = 192
    names = ("parents", "creator", "t_rank", "coin", "stake", "fork_pairs",
             "member_table", "n_valid")
    args = [
        _sds(np.shape(arrays[k]), np.asarray(arrays[k]).dtype, one_chip)
        for k in names
    ]
    c = _compile(functools.partial(pipeline.consensus_body, **statics),
                 *args)
    mem = c.memory_analysis()
    assert statics["has_forks"] == bool(n_forkers)
    # the whole body must fit one v5e's 16 GB with room to spare
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 8e9


# ------------------------------------------------------ Pallas kernels


def _pallas_text(c):
    text = c.as_text()
    assert "tpu_custom_call" in text
    return text


def test_bmm_or_pallas_compiles(one_chip):
    a = _sds((1024, 256), jnp.bool_, one_chip)
    b = _sds((256, 2048), jnp.bool_, one_chip)
    _pallas_text(_compile(
        lambda x, y: pk.bmm_or_pallas(x, y, jnp.bfloat16, interpret=False),
        a, b,
    ))


def test_ssm_matrix_pallas_compiles(one_chip):
    n, m, k = 2048, 64, 40
    c = _compile(
        lambda s, mt, st: pk.ssm_matrix_pallas(
            s, mt, st, 64, jnp.bfloat16, interpret=False
        ),
        _sds((n, n), jnp.bool_, one_chip),
        _sds((m, k), jnp.int32, one_chip),
        _sds((m,), jnp.int32, one_chip),
    )
    _pallas_text(c)


def test_ssm_block_pallas_compiles(one_chip):
    w, m, k, rows, cols = 8192, 256, 64, 1024, 2048
    c = _compile(
        lambda s, mt, st, cl, r0: pk.ssm_block_pallas(
            s, mt, st, cl, r0, rows=rows, tot_stake=m,
            matmul_dtype_name="bfloat16", interpret=False,
        ),
        _sds((w, w), jnp.bool_, one_chip),
        _sds((m, k), jnp.int32, one_chip),
        _sds((m,), jnp.int32, one_chip),
        _sds((cols,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
    )
    _pallas_text(c)


# ------------------------------------- streaming stages at 256 members


def test_ssm_block_stage_256_members(one_chip):
    w, m, k, rows, cols = 16384, 256, 96, 2048, 1024
    c = pipeline.ssm_block_stage.lower(
        _sds((w, w), jnp.bool_, one_chip),
        _sds((m, k), jnp.int32, one_chip),
        _sds((m,), jnp.int32, one_chip),
        _sds((cols,), jnp.int32, one_chip),
        _sds((), jnp.int32, one_chip),
        rows=rows, tot_stake=m, matmul_dtype_name="bfloat16",
    ).compile()
    assert c.memory_analysis().argument_size_in_bytes >= w * w
    assert not _scalar_gathers(c.as_text())


def _scalar_gathers(text):
    return re.findall(r"gather\([^\n]*slice_sizes=\{1,1\}", text)


def test_ssm_block_from_rows_stage_wide256_gathers_slices(one_chip):
    """The b-side gather is two slice gathers at the 256-member window's
    most common block shape.  An element gather lowers to a scalar
    gather fed by an (M*K, C, 2) index array, 3.26e10 bytes accessed
    per call at this shape."""
    w, m, k, rows, cols = 18432, 256, 160, 1024, 512
    c = pipeline.ssm_block_from_rows_stage.lower(
        _sds((m, rows, k), jnp.bool_, one_chip),    # a_r3
        _sds((w, w), jnp.bool_, one_chip),          # sees
        _sds((m, k), jnp.int32, one_chip),          # member_table
        _sds((m,), jnp.int32, one_chip),            # stake
        _sds((cols,), jnp.int32, one_chip),         # cols
        _sds((), jnp.int32, one_chip),              # row_off
        rows=rows, tot_stake=m, matmul_dtype_name="bfloat16",
    ).compile()
    assert not _scalar_gathers(c.as_text())
    cost = c.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["bytes accessed"] < 4e9


def test_rounds_span_stage_256_members(one_chip):
    w, cols, r_max, s_max = 16384, 1024, 32, 257
    i32 = jnp.int32
    c = pipeline.rounds_span_stage.lower(
        _sds((w, 2), i32, one_chip),             # parents
        _sds((w, cols), jnp.bool_, one_chip),    # ssm columns
        _sds((w,), i32, one_chip),               # column position
        _sds((w,), i32, one_chip),               # creator
        _sds((256,), i32, one_chip),             # stake
        _sds((), i32, one_chip),                 # n_valid
        _sds((w,), i32, one_chip),               # rnd
        _sds((w,), jnp.bool_, one_chip),         # wits
        _sds((r_max, s_max), i32, one_chip),     # tab
        _sds((r_max,), i32, one_chip),           # cnt
        _sds((), i32, one_chip),                 # overflow
        _sds((), i32, one_chip),                 # start
        _sds((), i32, one_chip),                 # r_base
        tot_stake=256, r_max=r_max, s_max=s_max, has_forks=False,
        chunk=256, k_chunks=8,
    ).compile()
    assert "while" in c.as_text()


# ------------------------------------------ row-sharded mesh, 4 chips


def test_row_sharded_block_fn_on_four_chips(topo):
    from jax.sharding import Mesh

    from tpu_swirld.parallel import MEMBER_AXIS, make_row_sharded_block_fn

    mesh = Mesh(np.array(topo.devices[:4]), (MEMBER_AXIS,))
    w, m, k, rows, cols = 16384, 256, 96, 2048, 1024
    rep = NamedSharding(mesh, P())
    kernel = make_row_sharded_block_fn(mesh)
    c = kernel.lower(
        _sds((w, w), jnp.bool_, NamedSharding(mesh, P(MEMBER_AXIS, None))),
        _sds((m, k), jnp.int32, rep),
        _sds((m,), jnp.int32, rep),
        _sds((cols,), jnp.int32, rep),
        _sds((), jnp.int32, rep),
        rows=rows, tot_stake=m, matmul_dtype_name="bfloat16",
    ).compile()
    text = c.as_text()
    assert "all-reduce" in text            # halo + stake-tally psums
    # each device holds a quarter of the window, not all of it
    assert c.memory_analysis().argument_size_in_bytes < w * w // 2
