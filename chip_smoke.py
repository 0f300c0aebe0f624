"""Chip smoke: the device consensus engines on one TPU, against the oracle.

    python chip_smoke.py               # phases A-D on one chip
    python chip_smoke.py --four-chips  # only the mesh paths, on four chips

One process.  Each phase prints one line: engine, sizes, parity, compile
seconds, run seconds and the device's ``peak_bytes_in_use`` so far.  The
phases run the BASELINE.json deployments through their normal entry
points:

- A. config 3 (64 members, 10,000 events): the oracle's batch
  ``consensus_pass``, ``run_consensus`` on the default XLA path, and
  ``IncrementalConsensus`` fed in chunks of 1,000.
- B. config 4 (the same with 21 forkers): ``run_consensus`` on the
  forked body.
- C. config-5 width (256 members) through ``StreamingConsensus`` under
  bench.py's default tile budget, with decided-prefix parity against an
  oracle subsample and against ``run_consensus`` over a deeper prefix.
- D. the compiled Pallas kernels on the config-3 DAG: full-matrix
  strongly-sees in ``run_consensus`` and the extension kernels in
  ``IncrementalConsensus``.

Parity is bit-identical: order, round, witness and fame.  A phase that
raises or misses parity ends the script with a non-zero exit.  The last
line is one JSON object naming the device; off a TPU the script exits
non-zero before any phase and prints no result.

This is a smoke run, not a benchmark: its seconds include one-off work
and are not tuned for.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import sys
import time

import numpy as np

import jax

from tpu_swirld import obs
from tpu_swirld.compile_cache import use_compile_cache
from tpu_swirld.config import SwirldConfig
from tpu_swirld.oracle.node import Node
from tpu_swirld.packing import pack_events
from tpu_swirld.sim import generate_gossip_dag, stream_gossip_dag
from tpu_swirld.store import StreamingConsensus
from tpu_swirld.tpu.pallas_kernels import make_extension_kernels
from tpu_swirld.tpu.pipeline import IncrementalConsensus, run_consensus

# BASELINE.json configs[2..4] (seed 1, as bench.py generates them)
BATCH_MEMBERS, BATCH_EVENTS, INC_CHUNK = 64, 10_000, 1000
FORKERS = 21
STREAM_MEMBERS, STREAM_EVENTS, STREAM_CHUNK = 256, 100_000, 2048
STREAM_ORACLE, STREAM_BATCH = 4000, 20_000
# bench.py's default resident tile budget (tiles of 256x256 bools)
TILE_BUDGET, TILE = 65536, 256
# the stages whose compiled text must hold the Pallas kernels in phase D
PALLAS_STAGES = (
    "pipeline.rounds_stage",
    "pipeline.inc_extend_vis",
    "pipeline.ssm_block_stage",
)


# ------------------------------------------------------------- reporting


class _CompileClock:
    """Seconds JAX spent lowering and compiling, from its own monitoring
    events.  Tracing is left out: nested jits trace inside their caller,
    so its events overlap."""

    EVENTS = (
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration


@functools.cache
def _compile_clock() -> _CompileClock:
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    return clock


@contextlib.contextmanager
def timed(out: dict):
    """Fill ``out`` with the compile and the remaining wall seconds of
    the block."""
    clock = _compile_clock()
    c0, t0 = clock.seconds, time.perf_counter()
    yield
    out["compile_s"] = clock.seconds - c0
    out["run_s"] = time.perf_counter() - t0 - out["compile_s"]


def peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def report(phase, engine, sizes, bad, t, **extra):
    """Print the phase's line; raise when parity failed."""
    parts = [f"[{phase}] {engine}", sizes, f"parity={not bad}"]
    if bad:
        parts.append(f"mismatch={','.join(bad)}")
    parts += [f"compile_s={t['compile_s']}", f"run_s={t['run_s']}"]
    parts += [f"{k}={v}" for k, v in extra.items()]
    parts.append(f"peak_bytes_in_use={peak_bytes()}")
    print(" ".join(parts), flush=True)
    if bad:
        raise AssertionError(f"phase {phase} {engine}: {bad} differ")


# ---------------------------------------------------------------- parity


def oracle_pass(members, keys, events):
    """The oracle's batch ``consensus_pass`` over ``events``."""
    node = Node(
        sk=keys[0][1], pk=members[0], network={}, members=members,
        clock=lambda: 0, create_genesis=False,
    )
    node.consensus_pass([ev.id for ev in events if node.add_event(ev)])
    return node


def oracle_mismatch(node, event_id, res):
    """Fields of ``res`` that differ from the oracle over all its events;
    ``event_id(i)`` names the event at result index ``i``."""
    ids = [event_id(i) for i in range(res.n)]
    pos = {eid: i for i, eid in enumerate(ids)}
    bad = []
    if [ids[i] for i in res.order] != node.consensus:
        bad.append("order")
    if any(int(res.round[pos[e]]) != node.round[e] for e in node.order_added):
        bad.append("round")
    if any(
        bool(res.is_witness[pos[e]]) != bool(node.is_witness[e])
        for e in node.order_added
    ):
        bad.append("witness")
    famous = {ids[i]: f for i, f in res.famous.items()}
    want = {w: node.famous[w] for ws in node.wit_list.values() for w in ws}
    if famous != want:
        bad.append("famous")
    return bad


def prefix_mismatch(order, rounds, event_id, res):
    """Decided-prefix parity against a reference that saw only the first
    ``len(rounds)`` events of the stream (bench.py's subsample check):
    its decided ``order`` (event ids) and the ``rounds`` of those events."""
    bad = []
    if [event_id(i) for i in res.order[: len(order)]] != order:
        bad.append("order")
    if list(res.round[: len(rounds)]) != list(rounds):
        bad.append("round")
    return bad


def oracle_prefix(node):
    return node.consensus, [node.round[e] for e in node.order_added]


def result_mismatch(a, b):
    """Fields in which two results over the same events differ."""
    bad = [
        f for f in ("round", "is_witness", "round_received", "consensus_ts")
        if not np.array_equal(getattr(a, f), getattr(b, f))
    ]
    bad += [f for f in ("famous", "order") if getattr(a, f) != getattr(b, f)]
    return bad


def ingest_chunks(inc, events, chunk):
    for i in range(0, len(events), chunk):
        inc.ingest(events[i : i + chunk])
    return inc.result()


# ---------------------------------------------------------------- phases


def phase_a(n_members=BATCH_MEMBERS, n_events=BATCH_EVENTS,
            inc_chunk=INC_CHUNK):
    """Config 3 through the oracle, the batch and the incremental engine."""
    t = {}
    with timed(t):
        members, stake, events, keys = generate_gossip_dag(
            n_members, n_events, seed=1
        )
    sizes = f"members={n_members} events={n_events}"
    print(f"[A] gossip_gen {sizes} seconds={t['run_s'] + t['compile_s']}",
          flush=True)
    with timed(t):
        node = oracle_pass(members, keys, events)
    report("A", "oracle consensus_pass", sizes, [], t,
           ordered=len(node.consensus), max_round=node.max_round)
    packed = pack_events(events, members, stake)
    with timed(t):
        res = run_consensus(packed, node.config)
    report("A", "run_consensus xla", sizes,
           oracle_mismatch(node, packed.ids.__getitem__, res), t)
    with timed(t):
        inc = IncrementalConsensus(members, stake, node.config)
        inc_res = ingest_chunks(inc, events, inc_chunk)
    report("A", "IncrementalConsensus xla", f"{sizes} chunk={inc_chunk}",
           oracle_mismatch(node, inc.packer.event_id, inc_res), t,
           passes=inc.passes, rebases=inc.rebases)
    return {
        "members": members, "stake": stake, "events": events,
        "config": node.config, "packed": packed, "res": res,
        "inc_chunk": inc_chunk, "sizes": sizes,
    }


def phase_b(n_members=BATCH_MEMBERS, n_events=BATCH_EVENTS,
            n_forkers=FORKERS):
    """Config 4: the forked (``has_forks``) batch body against the oracle."""
    members, stake, events, keys = generate_gossip_dag(
        n_members, n_events, seed=1, n_forkers=n_forkers
    )
    sizes = f"members={n_members} events={n_events} forkers={n_forkers}"
    t = {}
    with timed(t):
        node = oracle_pass(members, keys, events)
    packed = pack_events(events, members, stake)
    report("B", "oracle consensus_pass", sizes, [], t,
           fork_pairs=len(packed.fork_pairs), ordered=len(node.consensus))
    if not len(packed.fork_pairs):
        raise AssertionError("phase B: the forked DAG holds no fork pair")
    with timed(t):
        res = run_consensus(packed, node.config)
    report("B", "run_consensus xla forked", sizes,
           oracle_mismatch(node, packed.ids.__getitem__, res), t)


def stream_events(n_members, n_events, chunk):
    """A ``stream_gossip_dag`` stream, generated up front (set-up)."""
    members, stake, keys, chunks = stream_gossip_dag(
        n_members, n_events, chunk, seed=1
    )
    return members, stake, keys, list(chunks)


def streaming_engine(members, stake, config, chunk, mesh=None):
    kw = dict(
        tile_budget=TILE_BUDGET, tile=TILE, ingest_chunk=chunk,
        window_bucket=2048, prune_min=1024,
    )
    if mesh is None:
        return StreamingConsensus(members, stake, config, **kw)
    from tpu_swirld.parallel import MeshStreamingConsensus

    return MeshStreamingConsensus(mesh, members, stake, config, **kw)


def run_stream(inc, chunks):
    for ch in chunks:
        inc.ingest(ch)
    res = inc.result()
    inc.store.close()
    return res


def phase_c(n_members=STREAM_MEMBERS, n_events=STREAM_EVENTS,
            chunk=STREAM_CHUNK, n_oracle=STREAM_ORACLE,
            n_batch=STREAM_BATCH):
    """Config-5 width on one chip: the streaming slab-store engine."""
    if n_events < STREAM_EVENTS:
        print(f"[C] cut: streaming {n_events} of config 5's "
              f"{STREAM_EVENTS} events", flush=True)
    t = {}
    with timed(t):
        members, stake, keys, chunks = stream_events(
            n_members, n_events, chunk
        )
    sizes = f"members={n_members} events={n_events} chunk={chunk}"
    print(f"[C] stream_gen {sizes} seconds={t['run_s'] + t['compile_s']}",
          flush=True)
    cfg = SwirldConfig(n_members=n_members)
    with timed(t):
        inc = streaming_engine(members, stake, cfg, chunk)
        res = run_stream(inc, chunks)
    events = [ev for ch in chunks for ev in ch]
    oracle = oracle_pass(members, keys, events[:n_oracle])
    bad = prefix_mismatch(*oracle_prefix(oracle), inc.packer.event_id, res)
    # 256-member rounds decide late: the oracle prefix checks rounds, the
    # batch engine (oracle-checked in phase A) a prefix deep enough to
    # order events
    batch_packed = pack_events(events[:n_batch], members, stake)
    batch = run_consensus(batch_packed, cfg)
    bad += [f"batch_{f}" for f in prefix_mismatch(
        [batch_packed.ids[i] for i in batch.order], batch.round,
        inc.packer.event_id, res,
    )]
    stats = inc.store.stats()
    if stats["peak_resident_tiles"] > TILE_BUDGET:
        bad.append("tile_budget")
    report("C", "StreamingConsensus xla",
           f"{sizes} tile_budget={TILE_BUDGET} oracle_events={n_oracle} "
           f"batch_events={n_batch}",
           bad, t, ordered=len(res.order),
           oracle_decided=len(oracle.consensus),
           batch_decided=len(batch.order),
           peak_resident_tiles=stats["peak_resident_tiles"])


def _spec(x):
    if isinstance(x, (jax.Array, np.ndarray, np.generic)):
        return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
    return x


def compiled_text(fn, args, kw):
    """The compiled HLO of one stage call, from its recorded shapes."""
    static = {k: v for k, v in kw.items() if not hasattr(v, "dtype")}
    dynamic = {k: v for k, v in kw.items() if hasattr(v, "dtype")}
    return (
        jax.jit(functools.partial(fn, **static))
        .lower(*args, **dynamic).compile().as_text()
    )


def phase_d(a, *, compiled=True):
    """The Pallas kernels on phase A's DAG, bit-identical to phase A.

    ``compiled`` (the chip's case) also requires that the kernels ran
    compiled: the bundle is ``pallas``, each Pallas stage's compiled text
    holds ``tpu_custom_call``, and no hot shape fell back to XLA."""
    seen = {}

    def observe(name, fn, args, kw):
        if name in PALLAS_STAGES and name not in seen:
            seen[name] = (
                fn, [_spec(x) for x in args],
                {k: _spec(v) for k, v in kw.items()},
            )

    kern = make_extension_kernels()
    o = obs.Obs()
    obs.set_stage_observer(observe)
    t_batch, t_inc = {}, {}
    try:
        with obs.enabled(o):
            with timed(t_batch):
                res = run_consensus(
                    a["packed"], a["config"], use_pallas_ssm=True
                )
            with timed(t_inc):
                inc = IncrementalConsensus(
                    a["members"], a["stake"], a["config"],
                    extension_kernels=kern,
                )
                inc_res = ingest_chunks(inc, a["events"], a["inc_chunk"])
    finally:
        obs.set_stage_observer(None)
    fallbacks = {
        dict(m.labels)["shape"]: int(m.value)
        for m in o.registry.metrics() if m.name == "pallas_bmm_fallback"
    }
    missing = [s for s in PALLAS_STAGES if s not in seen]
    if missing:
        raise AssertionError(f"phase D: stages never ran: {missing}")
    kernel_ok = not fallbacks
    if compiled:
        kernel_ok = kernel_ok and kern.name == "pallas" and all(
            "tpu_custom_call" in compiled_text(*seen[s])
            for s in PALLAS_STAGES
        )
    extra = dict(kernels=kern.name, kernels_compiled=kernel_ok,
                 bmm_fallbacks=fallbacks or 0)
    report("D", "run_consensus pallas", a["sizes"],
           result_mismatch(res, a["res"]), t_batch, **extra)
    report("D", "IncrementalConsensus pallas",
           f"{a['sizes']} chunk={a['inc_chunk']}",
           result_mismatch(inc_res, a["res"]), t_inc, **extra)
    if not kernel_ok:
        raise AssertionError(f"phase D: kernels not compiled: {extra}")


def four_chips(n_devices=4, n_members=STREAM_MEMBERS, n_events=20_000,
               chunk=STREAM_CHUNK, n_oracle=2000,
               batch_members=BATCH_MEMBERS, batch_events=BATCH_EVENTS):
    """The mesh paths against their single-device runs: the row-sharded
    streaming window and the member-sharded batch body."""
    from tpu_swirld.parallel import make_mesh

    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise AssertionError(
            f"four chips: {n_devices} devices needed, JAX has {len(devices)}"
        )
    mesh = make_mesh(n_devices)
    members, stake, keys, chunks = stream_events(n_members, n_events, chunk)
    cfg = SwirldConfig(n_members=n_members)
    sizes = f"members={n_members} events={n_events} chunk={chunk}"

    t = {}
    with timed(t):
        mesh_inc = streaming_engine(members, stake, cfg, chunk, mesh=mesh)
        for ch in chunks:
            mesh_inc.ingest(ch)
    window = mesh_inc._anc_d
    rows = {s.device: s.data.shape[0] for s in window.addressable_shards}
    peaks = [peak_bytes(d) for d in devices]
    print(f"[4] window rows per device {[rows.get(d, 0) for d in devices]} "
          f"peak_bytes_in_use per device {peaks}", flush=True)
    mesh_res = mesh_inc.result()
    mesh_inc.store.close()
    spread = all(rows.get(d, 0) > 0 for d in devices) and all(
        p is None or p > 0 for p in peaks[1:]
    )
    with timed({}):
        single = run_stream(
            streaming_engine(members, stake, cfg, chunk), chunks
        )
    events = [ev for ch in chunks for ev in ch]
    oracle = oracle_pass(members, keys, events[:n_oracle])
    bad = result_mismatch(mesh_res, single)
    bad += [f"oracle_{f}" for f in prefix_mismatch(
        *oracle_prefix(oracle), mesh_inc.packer.event_id, mesh_res
    )]
    if not spread:
        bad.append("window_on_every_device")
    report("4", f"MeshStreamingConsensus x{n_devices} vs single", sizes,
           bad, t, ordered=len(mesh_res.order),
           oracle_decided=len(oracle.consensus), repins=mesh_inc.repins)

    members, stake, events, _keys = generate_gossip_dag(
        batch_members, batch_events, seed=1
    )
    packed = pack_events(events, members, stake)
    config = SwirldConfig(n_members=batch_members)
    ref = run_consensus(packed, config)
    with timed(t):
        res = run_consensus(packed, config, mesh=mesh)
    report("4", f"run_consensus mesh x{n_devices} vs unsharded",
           f"members={batch_members} events={batch_events}",
           result_mismatch(res, ref), t)


def device_line(devices) -> str:
    """The result line; exits non-zero when JAX found no TPU."""
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX runs on {d.platform!r}, not on a TPU — "
            f"no result"
        )
    return json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices),
    }})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the mesh paths and their single-device references",
    )
    args = ap.parse_args(argv)
    device_line(jax.devices())        # off the chip: stop before any phase
    print(f"[env] compile cache {use_compile_cache()}", flush=True)
    if args.four_chips:
        four_chips()
    else:
        a = phase_a()
        phase_b()
        phase_c()
        phase_d(a)
    print(device_line(jax.devices()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
