"""Benchmark: events/sec to consensus-order, TPU pipeline vs CPU oracle.

Driver contract: print ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "phases": {...}}
value       = device-pipeline consensus throughput (events/sec)
vs_baseline = speedup over the pure-Python oracle on the same machine
              (BASELINE.json north star: >= 50x on 64 members / 10k events).
phases      = per-phase wall-clock seconds (tpu_swirld.obs spans) PLUS
              per-phase peak-memory high-water marks
              (``mem_<phase>_host_peak_bytes`` via tracemalloc,
              ``mem_<phase>_device_peak_bytes`` via jax.live_arrays()
              sizes), so the headline has per-stage time AND memory
              attribution.  Top-level ``peak_host_bytes`` /
              ``peak_device_bytes`` carry the run-wide maxima for
              scripts/bench_compare.py regression gating.

An *incremental steady-state* section (tpu_swirld.tpu.pipeline.
IncrementalConsensus) additionally ingests the same events in chunks,
reports ev/s per pass and the first(cold)-vs-steady ratio, and publishes
window_size / pruned_prefix in the phases breakdown plus a structured
"incremental" object in the JSON line.

``--stream`` instead runs the BASELINE config-5 shape (256 members /
100k events; override with BENCH_STREAM_*) through the slab-store
streaming driver (tpu_swirld.store.StreamingConsensus) under a stated
resident tile budget (``--tile-budget``): events are generated as a
stream (host memory O(chunk)), decided rows retire to the host archive,
and the decided-prefix order is parity-checked against a pure-Python
oracle over a subsampled prefix.  The JSON line then reports streaming
ev/s, the tile budget, peak resident visibility bytes, and archive
stats — the config-5 acceptance artifact.

All detail goes to stderr.  Environment knobs:
    BENCH_MEMBERS (64)  BENCH_EVENTS (10000)  BENCH_ORACLE_EVENTS (10000)
    BENCH_MEM (1) — 0 disables the tracemalloc/live-array memory monitor.
    BENCH_INC_CHUNK (1000) — incremental ingest chunk; 0 disables the
    incremental section.
    BENCH_STREAM_MEMBERS (256)  BENCH_STREAM_EVENTS (100000)
    BENCH_STREAM_CHUNK (2048)  BENCH_STREAM_ORACLE (4000)
    BENCH_DEFAULT_STREAM_MEMBERS (48)  BENCH_DEFAULT_STREAM_EVENTS (6000)
    BENCH_DEFAULT_STREAM_CHUNK (1024) — the default (no-flags) run's
    always-on scaled-down streaming leg, so stream.evps lands in every
    artifact (0 events disables).
    BENCH_STREAM_REF (20000) — with --mesh: events for the in-run
    single-device reference pass (0 disables); BENCH_STREAM_SINGLE_EVPS
    supplies the reference throughput externally instead (e.g. from a
    prior single-device artifact).
    BENCH_TRACE (unset) — write the full span trace + gauge snapshot to
    this path (JSONL; render with `python -m tpu_swirld.obs report`).

Every leg runs on the platform JAX gives it and logs ``platform``,
``device_kind`` and the device count on stderr.  The CPU is reached only
through an explicit ``JAX_PLATFORMS=cpu`` (with ``XLA_FLAGS=
--xla_force_host_platform_device_count=D`` for ``--mesh D``).  The
compile cache is placed by :mod:`tpu_swirld.compile_cache`.
"""

import argparse
import json
import os
import sys
import time

MEMBERS = int(os.environ.get("BENCH_MEMBERS", "64"))
EVENTS = int(os.environ.get("BENCH_EVENTS", "10000"))
ORACLE_EVENTS = int(os.environ.get("BENCH_ORACLE_EVENTS", "10000"))
INC_CHUNK = int(os.environ.get("BENCH_INC_CHUNK", "1000"))
MEM = os.environ.get("BENCH_MEM", "1") != "0"

STREAM_MEMBERS = int(os.environ.get("BENCH_STREAM_MEMBERS", "256"))
STREAM_EVENTS = int(os.environ.get("BENCH_STREAM_EVENTS", "100000"))
STREAM_CHUNK = int(os.environ.get("BENCH_STREAM_CHUNK", "2048"))
# 256-member rounds fame-complete every ~4k events and ordering starts
# around 10-12k, so the oracle prefix must reach that deep for the
# decided-prefix order parity to be non-vacuous (the JSON reports
# oracle_decided so a too-shallow override is visible)
STREAM_ORACLE = int(os.environ.get("BENCH_STREAM_ORACLE", "12000"))

# always-on streaming leg of the DEFAULT run, config-scaled down so the
# headline stays cheap: every artifact then carries stream.evps for
# bench_compare.py's EXTRA_KEYS gates (previously only --stream artifacts
# had it, so the fused-dispatch path could regress invisibly between
# config-5 soaks).  0 events
# disables the leg; the full config-5 shape remains behind --stream.
# Gossip arrives in batches of 4x the ingest chunk so one ingest call
# spans several deltas — that exercises BOTH the decode-overlap worker
# (multi-slice _chunked_deltas) and the fused rounds scan.
DEFAULT_STREAM_MEMBERS = int(
    os.environ.get("BENCH_DEFAULT_STREAM_MEMBERS", "48")
)
DEFAULT_STREAM_EVENTS = int(
    os.environ.get("BENCH_DEFAULT_STREAM_EVENTS", "6000")
)
DEFAULT_STREAM_CHUNK = int(
    os.environ.get("BENCH_DEFAULT_STREAM_CHUNK", "1024")
)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def lint_stamp():
    """Invariant-lint status of the tree this bench ran from, stamped
    into the artifact: bench_compare.py refuses to gate a BENCH_*.json
    whose tree had findings (a number produced by code that violates the
    determinism/jit/thread invariants is not comparable)."""
    try:
        from tpu_swirld.analysis import lint_paths, lint_summary

        pkg = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tpu_swirld"
        )
        return lint_summary(lint_paths([pkg]))
    except Exception as exc:   # the stamp must never sink a bench run
        return {"error": repr(exc)}


def mc_stamp():
    """Model-checker smoke verdict stamped into the artifact: the small
    vanilla world explored exhaustively (every invariant over every
    interleaving) with the POR+symmetry reduction ratio vs the naive
    baseline.  bench_compare.py refuses to gate a candidate whose stamp
    is dirty — a throughput number from a tree whose consensus core
    violates its own invariant catalog is not comparable."""
    try:
        from tpu_swirld.analysis.mc import mc_smoke

        return mc_smoke()
    except Exception as exc:   # the stamp must never sink a bench run
        return {"error": repr(exc)}


def scale_audit_stamp():
    """Scale-audit verdict of the tree this bench ran from, stamped into
    the artifact: the jaxpr-level interval/dtype flow proof that no
    int32 wraps, no gather/slice reads out of bounds, no narrowing
    loses a value, and no padding sentinel collides with live data at
    the baseline envelope.  bench_compare.py refuses to gate a
    candidate whose stamp is dirty *or missing* — a throughput number
    from kernels that are not provably safe at the declared scale is
    not comparable."""
    try:
        from tpu_swirld.analysis import scale_audit_stamp as stamp

        return stamp("baseline")
    except Exception as exc:   # the stamp must never sink a bench run
        return {"error": repr(exc)}


def jax_env():
    """Start JAX on the platform it picks, place the compile cache, and
    log the device; returns the platform."""
    import jax

    from tpu_swirld.compile_cache import use_compile_cache

    cache = use_compile_cache()
    devs = jax.devices()
    log(f"[env] platform={devs[0].platform} "
        f"device_kind={devs[0].device_kind} devices={len(devs)} "
        f"compile_cache={cache}")
    return devs[0].platform


def _mem_monitor():
    from tpu_swirld.obs import MemoryMonitor

    return MemoryMonitor(enable_host=MEM)


def run_default():
    platform = jax_env()

    from tpu_swirld import obs as obslib
    from tpu_swirld.metrics import Metrics
    from tpu_swirld.obs.finality import FinalityTracker, record_batch_result
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.packing import pack_events
    from tpu_swirld.sim import generate_gossip_dag
    from tpu_swirld.tpu.pipeline import run_consensus

    # one Obs for the whole bench: depth-0 spans become the published
    # "phases" breakdown; the warm-up pipeline run executes with the Obs
    # ambient so stage/compile attribution and pad-waste gauges land in the
    # registry.  The steady (headline) run is spanned but NOT ambient —
    # per-stage sync would perturb the number being published.
    o = obslib.Obs()
    mon = _mem_monitor()

    t0 = time.time()
    with o.tracer.span("gossip_gen"), mon.phase("gossip_gen"):
        members, stake, events, keys = generate_gossip_dag(
            MEMBERS, EVENTS, seed=1
        )
    log(f"[gen] {MEMBERS} members / {EVENTS} events in {time.time()-t0:.1f}s")

    # ---- CPU oracle denominator (batch consensus pass over a prefix) ----
    n_oracle = min(ORACLE_EVENTS, EVENTS)
    node = Node(
        sk=keys[0][1], pk=members[0], network={}, members=members,
        clock=lambda: 0, create_genesis=False,
    )
    new_ids = [ev.id for ev in events[:n_oracle] if node.add_event(ev)]
    node.metrics = Metrics(registry=o.registry)   # per-phase oracle seconds
    t0 = time.time()
    with o.tracer.span("oracle"), mon.phase("oracle"):
        node.consensus_pass(new_ids)
    t_oracle = time.time() - t0
    oracle_evps = n_oracle / t_oracle
    log(f"[oracle] {n_oracle} events in {t_oracle:.2f}s = {oracle_evps:.0f} ev/s "
        f"(ordered {len(node.consensus)}, max_round {node.max_round})")
    # finality lifecycle, oracle engine: rounds-to-decision is exact per
    # event; the single batch pass makes time-to-finality degenerate
    # (every event shares the pass wall-clock), recorded post-hoc so the
    # tracker never perturbs the timed region
    fin_oracle = FinalityTracker("oracle", registry=o.registry)
    for eid in node.consensus:
        fin_oracle.record_decided(
            eid, node.round[eid], node.round_received[eid],
            birth=0.0, now=t_oracle,
        )
    finality = {"oracle": fin_oracle.summary()}

    # ---- device pipeline (full DAG), parity-checked on the oracle prefix --
    t0 = time.time()
    with o.tracer.span("pack"), mon.phase("pack"):
        packed_prefix = pack_events(events[:n_oracle], members, stake)
        packed_full = pack_events(events, members, stake)
    log(f"[pack] {time.time()-t0:.2f}s")

    if n_oracle == EVENTS:
        packed_prefix = packed_full
    res_prefix = run_consensus(packed_prefix, node.config)
    parity = (
        [packed_prefix.ids[i] for i in res_prefix.order] == node.consensus
        and all(
            res_prefix.round[i] == node.round[e]
            for i, e in enumerate(node.order_added)
        )
    )
    log(f"[parity] prefix ({n_oracle} ev) order+rounds identical: {parity}")

    t0 = time.time()
    with obslib.enabled(o):           # stage spans + compile attribution
        with o.tracer.span("pipeline_first"), mon.phase("pipeline_first"):
            res = run_consensus(packed_full, node.config)
    t_compile_and_run = time.time() - t0
    t0 = time.time()
    with o.tracer.span("pipeline"), mon.phase("pipeline"):
        # wall-clock only: no per-stage sync
        res = run_consensus(packed_full, node.config)
    t_steady = time.time() - t0
    pipe_evps = EVENTS / t_steady
    log(f"[pipeline] first {t_compile_and_run:.2f}s, steady {t_steady:.2f}s = "
        f"{pipe_evps:.0f} ev/s (ordered {len(res.order)}, max_round {res.max_round})")
    fin_batch = FinalityTracker("batch", registry=o.registry)
    record_batch_result(fin_batch, res, now=t_steady, birth=0.0)
    finality["batch"] = fin_batch.summary()

    # ---- incremental steady-state mode: chunked ingest, carried state ----
    inc_out = None
    if INC_CHUNK > 0:
        from tpu_swirld.tpu.pipeline import IncrementalConsensus

        inc = IncrementalConsensus(members, stake, node.config)
        # genuine steady-state time-to-finality: births stamp at chunk
        # ingest, decided at the pass that orders them — both on the
        # tracker's wall clock
        inc.finality = FinalityTracker(
            "incremental", clock=time.perf_counter, registry=o.registry
        )
        pass_stats = []
        with o.tracer.span("pipeline_incremental"), \
                mon.phase("pipeline_incremental"):
            for i in range(0, EVENTS, INC_CHUNK):
                t0 = time.time()
                st = inc.ingest(events[i : i + INC_CHUNK])
                dt = time.time() - t0
                pass_stats.append((dt, st))
                mon.sample("pipeline_incremental")
                log(f"[inc] pass {len(pass_stats)-1}: {st['new_events']} ev "
                    f"in {dt:.3f}s = {st['new_events']/dt:.0f} ev/s "
                    f"window={st['window_size']} pruned={st['pruned_prefix']}"
                    f"{' REBASE' if st['rebased'] else ''}")
        inc_res = inc.result()
        inc_parity = inc_res.order == res.order and (
            list(inc_res.round) == list(res.round)
        )
        # steady = back half of the passes (the front half pays compiles
        # + window warmup).  The denominator for the first-vs-steady
        # ratio is the WARM full-recompute pass above (t_steady) — a
        # stricter baseline than a literally cold first pass, which
        # also pays one-off jit compiles.
        steady_half = pass_stats[len(pass_stats) // 2 :]
        warmed_up = len(steady_half) >= 2 and not any(
            s["rebased"] for _dt, s in steady_half
        )
        if not warmed_up:
            log("[inc] too few passes to reach steady state "
                f"({len(pass_stats)} total) — ratio not meaningful; "
                "lower BENCH_INC_CHUNK or raise BENCH_EVENTS")
        ev_steady = sum(s["new_events"] for _dt, s in steady_half)
        t_inc = sum(dt for dt, _s in steady_half)
        inc_evps = ev_steady / t_inc if (t_inc and warmed_up) else 0.0
        full_pass_evps = pipe_evps
        ratio = inc_evps / full_pass_evps if full_pass_evps else 0.0
        log(f"[inc] steady {inc_evps:.0f} ev/s vs warm full-recompute "
            f"pass {full_pass_evps:.0f} ev/s -> first-vs-steady ratio "
            f"{ratio:.2f}x (parity={inc_parity}, rebases={inc.rebases})")
        inc_out = {
            "chunk": INC_CHUNK,
            "passes": inc.passes,
            "rebases": inc.rebases,
            "full_pass_evps": round(full_pass_evps, 1),
            "steady_evps": round(inc_evps, 1),
            "first_vs_steady": round(ratio, 2),
            "window_size": inc.window_size,
            "pruned_prefix": inc.pruned_prefix,
            "parity": bool(inc_parity),
        }
        finality["incremental"] = inc.finality.summary()

    # ---- always-on streaming leg (config-scaled down) ----
    # Ingest through StreamingConsensus so stream.evps lands in EVERY
    # artifact; decided output is parity-checked bit-identically against
    # the batch pipeline over the same events.
    stream_out = None
    if DEFAULT_STREAM_EVENTS > 0:
        from tpu_swirld.config import SwirldConfig
        from tpu_swirld.sim import stream_gossip_dag
        from tpu_swirld.store import StreamingConsensus

        s_cfg = SwirldConfig(n_members=DEFAULT_STREAM_MEMBERS)
        s_members, s_stake, _s_keys, s_chunks = stream_gossip_dag(
            DEFAULT_STREAM_MEMBERS, DEFAULT_STREAM_EVENTS,
            4 * DEFAULT_STREAM_CHUNK, seed=1,
        )
        s_chunks = list(s_chunks)
        s_events = [ev for ch in s_chunks for ev in ch]
        s_packed = pack_events(s_events, s_members, s_stake)
        with o.tracer.span("stream_default_ref"), \
                mon.phase("stream_default_ref"):
            s_ref = run_consensus(s_packed, s_cfg)

        with o.tracer.span("stream_default"), mon.phase("stream_default"):
            eng = StreamingConsensus(
                s_members, s_stake, s_cfg,
                ingest_chunk=DEFAULT_STREAM_CHUNK,
                window_bucket=2048, prune_min=1024,
            )
            t0 = time.time()
            for ch in s_chunks:
                eng.ingest(ch)
            t_s = time.time() - t0
            eng.store.close()
        s_res = eng.result()
        got = [eng.packer.event_id(i) for i in s_res.order]
        want = [s_packed.ids[i] for i in s_ref.order]
        ref_round = {
            s_packed.ids[i]: int(s_ref.round[i]) for i in range(len(s_events))
        }
        s_parity = got == want and all(
            int(s_res.round[i]) == ref_round[eng.packer.event_id(i)]
            for i in range(len(s_events))
        )
        s_evps = DEFAULT_STREAM_EVENTS / t_s
        log(f"[stream-default] {DEFAULT_STREAM_EVENTS} ev x "
            f"{DEFAULT_STREAM_MEMBERS} members in {t_s:.2f}s = "
            f"{s_evps:.0f} ev/s parity={s_parity}")
        stream_out = {
            "evps": round(s_evps, 1),
            "members": DEFAULT_STREAM_MEMBERS,
            "events": DEFAULT_STREAM_EVENTS,
            "chunk": DEFAULT_STREAM_CHUNK,
            "decoded_off_thread": eng.decoded_off_thread,
            "ordered": len(s_res.order),
            "parity": bool(s_parity),
        }

    phases = {k: round(v, 4) for k, v in o.tracer.phase_seconds().items()}
    if inc_out is not None:
        phases["incremental_window_size"] = inc_out["window_size"]
        phases["incremental_pruned_prefix"] = inc_out["pruned_prefix"]
    phases.update(mon.flat())
    log(f"[phases] {json.dumps(phases)}")
    trace_path = os.environ.get("BENCH_TRACE")
    if trace_path:
        o.save(trace_path)
        log(f"[trace] wrote {trace_path} "
            f"(render: python -m tpu_swirld.obs report {trace_path})")

    speedup = pipe_evps / oracle_evps
    out = {
        "metric": (
            f"events/sec to consensus-order @{EVENTS} events x {MEMBERS} "
            f"members ({platform}); order parity={parity}"
        ),
        "value": round(pipe_evps, 1),
        "unit": "events/s",
        "vs_baseline": round(speedup, 2),
        "phases": phases,
        "peak_host_bytes": mon.peak_host_bytes,
        "peak_device_bytes": mon.peak_device_bytes,
    }
    if inc_out is not None:
        out["incremental"] = inc_out
    if stream_out is not None:
        out["stream"] = stream_out
    out["finality"] = {
        eng: {
            k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in s.items()
        }
        for eng, s in finality.items()
    }
    log(f"[finality] {json.dumps(out['finality'])}")
    out["lint"] = lint_stamp()
    out["mc"] = mc_stamp()
    out["scale_audit"] = scale_audit_stamp()
    print(json.dumps(out), flush=True)
    mon.close()
    if not parity or (inc_out is not None and not inc_out["parity"]) \
            or (stream_out is not None and not stream_out["parity"]):
        sys.exit(1)


def run_stream(tile_budget, tile, mesh_n=0, device_tile_budget=None):
    """BASELINE config-5 shape under a stated resident tile budget.

    ``mesh_n > 0`` runs the row-sharded mesh driver
    (:class:`tpu_swirld.parallel.MeshStreamingConsensus`) over that many
    devices instead — on CPU the devices are simulated
    (``xla_force_host_platform_device_count``), so ``scaling_efficiency``
    measures sharding *overhead* (halo + psum + repins) rather than
    hardware speedup; on a real mesh the same number reads as
    speedup/D.  The single-device reference throughput comes from
    BENCH_STREAM_SINGLE_EVPS when set (e.g. the headline of a prior
    single-device artifact), else an in-run single-device pass over the
    first BENCH_STREAM_REF events of the same stream.
    """
    import jax

    platform = jax_env()
    log(f"[env] stream {STREAM_MEMBERS}x{STREAM_EVENTS} chunk={STREAM_CHUNK} "
        f"tile_budget={tile_budget} tile={tile}"
        + (f" mesh={mesh_n} device_tile_budget={device_tile_budget}"
           if mesh_n else ""))

    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.oracle.node import Node
    from tpu_swirld.sim import stream_gossip_dag
    from tpu_swirld.store import StreamingConsensus

    mon = _mem_monitor()
    cfg = SwirldConfig(n_members=STREAM_MEMBERS)
    members, stake, keys, chunks = stream_gossip_dag(
        STREAM_MEMBERS, STREAM_EVENTS, STREAM_CHUNK, seed=1
    )
    # the oracle replays only the subsampled prefix — the streaming
    # driver's decided prefix must be bit-identical over it
    n_oracle = min(STREAM_ORACLE, STREAM_EVENTS)
    oracle = Node(
        sk=keys[0][1], pk=members[0], network={}, members=members,
        clock=lambda: 0, create_genesis=False,
    )
    oracle_buf = []

    if mesh_n:
        from tpu_swirld.parallel import make_mesh, streaming_consensus_for_mesh

        if len(jax.devices()) < mesh_n:
            raise SystemExit(
                f"--mesh {mesh_n}: JAX has {len(jax.devices())} devices"
            )
        mesh = make_mesh(mesh_n)
        inc = streaming_consensus_for_mesh(
            mesh, members, stake, cfg,
            tile_budget=tile_budget, tile=tile,
            device_tile_budget=device_tile_budget,
            ingest_chunk=STREAM_CHUNK, window_bucket=2048, prune_min=1024,
        )
    else:
        inc = StreamingConsensus(
            members, stake, cfg,
            tile_budget=tile_budget, tile=tile,
            ingest_chunk=STREAM_CHUNK, window_bucket=2048, prune_min=1024,
        )
    # finality lifecycle on the stream: births at chunk ingest, decided
    # at the ordering pass; the phase dimension attributes each decided
    # event's latency to window residency vs archive widening vs full
    # rebase (see StreamingConsensus._rebase)
    from tpu_swirld.obs.finality import FinalityTracker

    inc.finality = FinalityTracker("streaming", clock=time.perf_counter)
    n_done = 0
    t_all = time.time()
    with mon.phase("stream"):
        for chunk in chunks:
            if n_done < n_oracle:
                oracle_buf.extend(chunk[: n_oracle - n_done])
            t0 = time.time()
            st = inc.ingest(chunk)
            dt = time.time() - t0
            n_done += len(chunk)
            mon.sample("stream")
            log(f"[stream] {n_done}/{STREAM_EVENTS}: {len(chunk)} ev in "
                f"{dt:.2f}s = {len(chunk)/dt:.0f} ev/s "
                f"window={st['window_size']} pruned={st['pruned_prefix']} "
                f"resident={st['resident_bytes']/1e6:.0f}MB "
                f"archived={st['archived_rows']}"
                f"{' REBASE' if st['rebased'] else ''}")
    t_stream = time.time() - t_all
    stream_evps = n_done / t_stream
    # overlap ratio over the whole run: fraction of the stream wall spent
    # computing rather than blocked behind the archive's spill queue
    # (snapshot stall BEFORE close() — the final flush is off the clock)
    stall = inc.store.archive.stall_seconds
    overlap = max(0.0, min(1.0, (t_stream - stall) / t_stream))
    res = inc.result()
    log(f"[stream] {n_done} ev in {t_stream:.1f}s = {stream_evps:.0f} ev/s; "
        f"ordered {len(res.order)}, max_round {res.max_round}, "
        f"pruned {inc.pruned_prefix}, window {inc.window_size}, "
        f"overlap {overlap:.3f}")
    inc.store.close()       # flush background packing before stats/parity

    with mon.phase("oracle_subsample"):
        new_ids = [ev.id for ev in oracle_buf if oracle.add_event(ev)]
        oracle.consensus_pass(new_ids)
    got = [inc.packer.event_id(i) for i in res.order[: len(oracle.consensus)]]
    order_parity = got == oracle.consensus
    round_parity = all(
        int(res.round[i]) == oracle.round[eid]
        for i, eid in enumerate(oracle.order_added)
    )
    parity = order_parity and round_parity
    log(f"[parity] oracle prefix {n_oracle} ev, decided {len(oracle.consensus)}: "
        f"order={order_parity} rounds={round_parity}")

    stats = inc.store.stats()
    budget_ok = (
        tile_budget is None
        or stats["peak_resident_tiles"] <= tile_budget
    )
    dev_budget_ok = (
        device_tile_budget is None
        or stats["peak_device_tiles"] <= device_tile_budget
    )
    log(f"[store] {json.dumps(stats)} budget_ok={budget_ok}"
        + (f" dev_budget_ok={dev_budget_ok}" if mesh_n else ""))

    mesh_out = None
    if mesh_n:
        # single-device reference for the scaling number: an external
        # artifact headline (BENCH_STREAM_SINGLE_EVPS) or an in-run
        # single-device pass over the stream's first BENCH_STREAM_REF
        # events (0 disables; the soak supplies the external number)
        single_evps = float(
            os.environ.get("BENCH_STREAM_SINGLE_EVPS", "0") or 0
        )
        ref_events = int(os.environ.get("BENCH_STREAM_REF", "20000"))
        ref_used = 0
        if not single_evps and ref_events:
            ref_events = min(ref_events, STREAM_EVENTS)
            _m2, _s2, _k2, ref_chunks = stream_gossip_dag(
                STREAM_MEMBERS, ref_events, STREAM_CHUNK, seed=1
            )
            ref = StreamingConsensus(
                members, stake, cfg,
                tile_budget=tile_budget, tile=tile,
                ingest_chunk=STREAM_CHUNK, window_bucket=2048,
                prune_min=1024,
            )
            t0 = time.time()
            with mon.phase("stream_single_ref"):
                for chunk in ref_chunks:
                    ref.ingest(chunk)
            single_evps = ref_events / (time.time() - t0)
            ref_used = ref_events
            ref.store.close()
            log(f"[mesh] single-device reference: {ref_events} ev = "
                f"{single_evps:.0f} ev/s")
        speedup = stream_evps / single_evps if single_evps else 0.0
        efficiency = speedup / mesh_n if mesh_n else 0.0
        log(f"[mesh] {mesh_n} devices: {stream_evps:.0f} ev/s vs single "
            f"{single_evps:.0f} ev/s -> speedup {speedup:.2f}x, "
            f"scaling efficiency {efficiency:.3f} "
            f"(peak_device_tiles={stats['peak_device_tiles']}, "
            f"repins={inc.repins})")
        mesh_out = {
            "devices": mesh_n,
            "evps": round(stream_evps, 1),
            "single_evps": round(single_evps, 1),
            "single_ref_events": ref_used,
            "speedup_vs_single": round(speedup, 3),
            "scaling_efficiency": round(efficiency, 4),
            "peak_device_tiles": stats["peak_device_tiles"],
            "device_tile_budget": device_tile_budget,
            "device_budget_ok": bool(dev_budget_ok),
            "device_resident_tiles": stats["device_resident_tiles"],
            "peak_resident_tiles": stats["peak_resident_tiles"],
            "budget_overruns": stats["budget_overruns"],
            "repins": inc.repins,
            "parity": bool(parity),
        }
    phases = mon.flat()
    out = {
        "metric": (
            f"streaming events/sec to consensus-order "
            f"@{n_done} events x {STREAM_MEMBERS} members ({platform}, "
            f"config-5 shape, tile budget {tile_budget}); "
            f"oracle-prefix parity={parity}"
        ),
        "value": round(stream_evps, 1),
        "unit": "events/s",
        "vs_baseline": 0.0,
        "phases": phases,
        "peak_host_bytes": mon.peak_host_bytes,
        "peak_device_bytes": mon.peak_device_bytes,
        "stream": {
            "evps": round(stream_evps, 1),
            "overlap_ratio": round(overlap, 4),
            "spill_pack_seconds": stats["spill_pack_seconds"],
            "spill_stall_seconds": stats["spill_stall_seconds"],
            "spill_queue_depth_peak": stats["spill_queue_depth_peak"],
            "members": STREAM_MEMBERS,
            "events": n_done,
            "chunk": STREAM_CHUNK,
            "tile": tile,
            "tile_budget": tile_budget,
            "budget_ok": bool(budget_ok),
            "ordered": len(res.order),
            "max_round": int(res.max_round),
            "window_size": inc.window_size,
            "pruned_prefix": inc.pruned_prefix,
            "peak_resident_visibility_bytes": stats["peak_resident_bytes"],
            "peak_resident_tiles": stats["peak_resident_tiles"],
            "archived_rows": stats["archived_rows"],
            "archive_bytes": stats["archive_bytes"],
            "widen_rebases": inc.widen_rebases,
            "full_rebases": inc.full_rebases,
            "oracle_prefix": n_oracle,
            "oracle_decided": len(oracle.consensus),
            "parity": bool(parity),
        },
        "finality": {
            "streaming": {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in inc.finality.summary().items()
            },
        },
    }
    log(f"[finality] {json.dumps(out['finality'])}")
    if mesh_out is not None:
        out["stream_mesh"] = mesh_out
        out["metric"] = out["metric"].replace(
            "streaming events/sec",
            f"mesh-streaming ({mesh_n} dev) events/sec",
        )
    out["lint"] = lint_stamp()
    out["mc"] = mc_stamp()
    out["scale_audit"] = scale_audit_stamp()
    print(json.dumps(out), flush=True)
    mon.close()
    if not parity or not budget_ok or not dev_budget_ok:
        sys.exit(1)


def run_chaos_overhead():
    """--chaos-overhead: device-pipeline throughput under an active
    equivocation storm vs the same shape fault-free, in one JSON line.

    Two DAGs share (members, stake, seed): the attack DAG runs
    ``f = (n-1)//3`` forking creators at high fork probability (the
    in-budget worst case — fork pairs inflate the witness table and the
    identical-set checks), the clean DAG is fault-free.  Each is packed
    and run through ``run_consensus`` once to compile, then timed, and
    the line reports ``chaos_overhead.{clean_evps, attack_evps, ratio}``
    (ratio = attack/clean, higher is better) so bench_compare.py can
    gate adversary-path overhead like any other throughput number.

    Env knobs: BENCH_CHAOS_MEMBERS (32), BENCH_CHAOS_EVENTS (4000),
    BENCH_CHAOS_FORK_PROB (0.4).
    """
    platform = jax_env()

    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.packing import pack_events
    from tpu_swirld.sim import generate_gossip_dag
    from tpu_swirld.tpu.pipeline import run_consensus

    n_members = int(os.environ.get("BENCH_CHAOS_MEMBERS", "32"))
    n_events = int(os.environ.get("BENCH_CHAOS_EVENTS", "4000"))
    fork_prob = float(os.environ.get("BENCH_CHAOS_FORK_PROB", "0.4"))
    f_budget = (n_members - 1) // 3
    config = SwirldConfig(n_members=n_members)

    legs = {}
    for leg, n_forkers in (("clean", 0), ("attack", f_budget)):
        t0 = time.time()
        members, stake, events, _keys = generate_gossip_dag(
            n_members, n_events, seed=2, n_forkers=n_forkers,
            fork_prob=fork_prob if n_forkers else 0.0,
        )
        packed = pack_events(events, members, stake)
        log(f"[{leg}] {n_members} members / {len(events)} events, "
            f"{int(packed.fork_pairs.shape[0])} fork pairs "
            f"({time.time()-t0:.1f}s gen+pack)")
        run_consensus(packed, config)          # compile + warm
        t0 = time.time()
        res = run_consensus(packed, config)
        dt = time.time() - t0
        legs[leg] = {
            "evps": round(len(events) / dt, 1),
            "fork_pairs": int(packed.fork_pairs.shape[0]),
            "overflow_retries": int(res.timings.get("overflow_retries", 0)),
        }
        log(f"[{leg}] {legs[leg]['evps']:.0f} ev/s")

    ratio = legs["attack"]["evps"] / legs["clean"]["evps"]
    out = {
        "metric": "chaos_overhead_evps",
        "value": legs["attack"]["evps"],
        "unit": "events/sec",
        "platform": platform,
        "chaos_overhead": {
            "clean_evps": legs["clean"]["evps"],
            "attack_evps": legs["attack"]["evps"],
            "ratio": round(ratio, 4),
            "n_members": n_members,
            "n_events": n_events,
            "n_forkers": f_budget,
            "fork_prob": fork_prob,
            "fork_pairs": legs["attack"]["fork_pairs"],
            "overflow_retries": legs["attack"]["overflow_retries"],
        },
        "lint": lint_stamp(),
        "mc": mc_stamp(),
        "scale_audit": scale_audit_stamp(),
    }
    print(json.dumps(out), flush=True)


def run_churn():
    """--churn: dynamic-membership throughput + repack tail latency.

    One canonical multi-epoch schedule (a decided LEAVE then a decided
    JOIN — ``tpu_swirld.membership.sim.churn_schedule``) is replayed
    through the epoch-aware incremental driver and timed end to end:
    ``churn.evps`` is schedule events per second *including* ledger
    bookkeeping, epoch adoption, and any restatements.  The member-axis
    repack stage is then sampled BENCH_CHURN_REPACKS times per epoch
    boundary (fresh packer each trial, so every sample pays the real
    add-member + device-pad cost) and ``churn.repack_p99_s`` is the p99
    across all samples.  ``churn.epochs`` pins that the schedule really
    decided its membership txs — a regression that silently stops
    deciding would otherwise *raise* evps.  bench_compare.py gates evps
    and epochs higher-better and repack_p99_s lower-better.

    Env knobs: BENCH_CHURN_NODES (4), BENCH_CHURN_TURNS (700),
    BENCH_CHURN_SEED (0), BENCH_CHURN_REPACKS (30).
    """
    platform = jax_env()

    from tpu_swirld.membership.engine import run_dynamic
    from tpu_swirld.membership.repack import repack_packer
    from tpu_swirld.membership.sim import churn_schedule
    from tpu_swirld.packing import Packer

    n_nodes = int(os.environ.get("BENCH_CHURN_NODES", "4"))
    turns = int(os.environ.get("BENCH_CHURN_TURNS", "700"))
    seed = int(os.environ.get("BENCH_CHURN_SEED", "0"))
    n_repacks = int(os.environ.get("BENCH_CHURN_REPACKS", "30"))

    t0 = time.time()
    events, members, stake, _sim = churn_schedule(
        n_nodes, seed=seed, turns=turns,
    )
    log(f"[churn] {n_nodes} members / {len(events)} events "
        f"({time.time()-t0:.1f}s gossip gen)")

    # warm (jit compiles in the repack stage), then time the driver
    run_dynamic(events, members, stake, engine="incremental", chunk=64)
    t0 = time.time()
    res = run_dynamic(events, members, stake, engine="incremental",
                      chunk=64)
    dt = time.time() - t0
    evps = len(events) / dt
    epochs = res.epochs
    log(f"[churn] {evps:.0f} ev/s, {epochs} epochs, "
        f"{res.restatements} restatements, {len(res.order)} decided")

    # repack tail: fresh packer per trial so each sample pays the full
    # epoch-boundary cost (registry extension + stake swap + device pad)
    samples = []
    for _ in range(max(1, n_repacks)):
        packer = Packer(list(members), list(stake))
        for epoch in res.ledger.epochs[1:]:
            samples.append(repack_packer(packer, epoch).seconds)
    samples.sort()
    p99 = samples[min(len(samples) - 1, int(len(samples) * 0.99))]
    log(f"[churn] repack p99 {p99*1e3:.2f} ms over {len(samples)} samples")

    out = {
        "metric": "churn_evps",
        "value": round(evps, 1),
        "unit": "events/sec through the epoch-aware driver",
        "platform": platform,
        "churn": {
            "evps": round(evps, 1),
            "repack_p99_s": round(p99, 6),
            "epochs": epochs,
            "decided": len(res.order),
            "restatements": res.restatements,
            "repack_samples": len(samples),
            "n_nodes": n_nodes,
            "turns": turns,
            "events": len(events),
        },
        "lint": lint_stamp(),
        "mc": mc_stamp(),
        "scale_audit": scale_audit_stamp(),
    }
    print(json.dumps(out), flush=True)
    if epochs < 3:
        log(f"[churn] FAIL: schedule decided only {epochs} epochs (< 3)")
        sys.exit(1)


def run_cluster():
    """--cluster: real-process loopback cluster throughput + latency.

    Two legs, one JSON line:

    - **chaos leg** — BENCH_CLUSTER_NODES processes over loopback TCP,
      client traffic at BENCH_CLUSTER_RATE tx/s, one node SIGKILLed at
      30% of the window and restarted from checkpoint + WAL at 50%; the
      verdict (safety vs the oracle replay of the union DAG, liveness
      past the crash window) must be green, and the line reports
      ``cluster.{tx_per_s, submit_p50_s, submit_p99_s}`` — decided
      transactions per second and merged submission→decided wall
      latency — for bench_compare.py to gate;
    - **overload leg** — a small cluster with the admission window
      forced to zero under the same rate: every node must shed
      (``SHED:window``) rather than queue unboundedly.  ``shed == 0``
      means backpressure is broken and the bench exits 1.

    Env knobs: BENCH_CLUSTER_NODES (5), BENCH_CLUSTER_DURATION (6.0 s),
    BENCH_CLUSTER_RATE (300 tx/s), BENCH_CLUSTER_TX_BYTES (64),
    BENCH_CLUSTER_SEED (9).
    """
    import tempfile

    from tpu_swirld.net.cluster import ClusterSpec, run_cluster as _run

    n_nodes = int(os.environ.get("BENCH_CLUSTER_NODES", "5"))
    duration = float(os.environ.get("BENCH_CLUSTER_DURATION", "6.0"))
    rate = float(os.environ.get("BENCH_CLUSTER_RATE", "300"))
    tx_bytes = int(os.environ.get("BENCH_CLUSTER_TX_BYTES", "64"))
    seed = int(os.environ.get("BENCH_CLUSTER_SEED", "9"))
    net = {"gossip_interval_s": 0.005, "checkpoint_every_s": 0.5}

    workdir = tempfile.mkdtemp(prefix="swirld-bench-cluster-")
    log(f"[cluster] {n_nodes} processes, {duration}s @ {rate} tx/s, "
        f"kill -9 node 1 at {duration * 0.3:.1f}s, "
        f"restart at {duration * 0.5:.1f}s ({workdir})")
    verdict = _run(ClusterSpec(
        workdir=os.path.join(workdir, "chaos"),
        n_nodes=n_nodes, seed=seed, duration_s=duration,
        tx_rate=rate, tx_bytes=tx_bytes,
        kill_index=1, kill_at_s=duration * 0.3,
        restart_at_s=duration * 0.5,
        flightrec_dir=os.path.join(workdir, "chaos", "flightrec"),
        net=net,
    ))
    tx = verdict["tx"]
    log(f"[cluster] ok={verdict['ok']} decided_tx={tx['decided']} "
        f"({tx['tx_per_s']:.0f} tx/s) p99="
        f"{tx.get('submit_p99', float('nan')):.3f}s")

    log("[overload] 3 processes, admission window forced to 0 "
        "(every submission must shed, none may queue)")
    overload = _run(ClusterSpec(
        workdir=os.path.join(workdir, "overload"),
        n_nodes=3, seed=seed + 1, duration_s=min(duration, 3.0),
        tx_rate=rate, tx_bytes=tx_bytes,
        net=dict(net, max_undecided=0),
    ))
    shed = overload["tx"]["shed"]
    log(f"[overload] ok={overload['ok']} shed={shed} "
        f"acked={overload['tx']['acked']}")

    out = {
        "metric": "cluster_tx_per_s",
        "value": tx["tx_per_s"],
        "unit": "decided tx/sec",
        "platform": "cpu-processes",
        "cluster": {
            "tx_per_s": tx["tx_per_s"],
            "submit_p50_s": tx.get("submit_p50"),
            "submit_p99_s": tx.get("submit_p99"),
            "tx_submitted": tx["submitted"],
            "tx_acked": tx["acked"],
            "tx_failed": tx["failed"],
            "tx_decided": tx["decided"],
            "n_nodes": n_nodes,
            "duration_s": duration,
            "rate": rate,
            "verdict_ok": verdict["ok"],
            "safety": verdict["safety"],
            "liveness": verdict["liveness"],
            "overload_ok": overload["ok"],
            "overload_shed": shed,
            "wal_torn_tail_recovered":
                verdict["counters"]["wal_torn_tail_recovered"],
            # telemetry-plane artifacts: the merged cross-process trace
            # and the supervisor metrics rollup, so BENCH_r*.json is
            # self-describing for the trajectory tooling
            "merged_trace": (verdict.get("trace") or {}).get("merged"),
            "cross_process_traces":
                (verdict.get("trace") or {}).get("cross_process_traces"),
            "metrics_rollup": (verdict.get("metrics") or {}).get("json"),
            "metrics_prom": (verdict.get("metrics") or {}).get("prom"),
            "metrics_nodes_covered":
                (verdict.get("metrics") or {}).get("nodes_covered"),
        },
        "lint": lint_stamp(),
        "mc": mc_stamp(),
        "scale_audit": scale_audit_stamp(),
    }
    print(json.dumps(out), flush=True)
    if not verdict["ok"] or not overload["ok"]:
        log("[cluster] FAIL: verdict not green")
        sys.exit(1)
    if shed == 0:
        log("[overload] FAIL: zero submissions shed — backpressure "
            "is not engaging")
        sys.exit(1)


def run_soak():
    """--soak: the composed production-day scenario as a gated bench.

    One :func:`tpu_swirld.soak.run_soak` pass — BENCH_SOAK_NODES
    processes through per-link TCP fault proxies, heavy-tailed traffic
    from BENCH_SOAK_CLIENTS concurrent clients, and the smoke window
    composition (1 SIGKILL crash + WAL recovery, 1 partition/heal, 1
    byzantine equivocation storm) scaled to BENCH_SOAK_HORIZON — and one
    JSON line with ``soak.{tx_per_s, submit_p99_s,
    disruptions_survived, verdict_ok}`` for bench_compare.py to gate.
    Exit 1 on a red composite verdict.

    Env knobs: BENCH_SOAK_NODES (4), BENCH_SOAK_HORIZON (8.0 s),
    BENCH_SOAK_RATE (150 tx/s), BENCH_SOAK_CLIENTS (3),
    BENCH_SOAK_SEED (3).
    """
    import dataclasses
    import tempfile

    from tpu_swirld import soak as _soak

    n_nodes = int(os.environ.get("BENCH_SOAK_NODES", "4"))
    horizon = float(os.environ.get("BENCH_SOAK_HORIZON", "8.0"))
    rate = float(os.environ.get("BENCH_SOAK_RATE", "150"))
    clients = int(os.environ.get("BENCH_SOAK_CLIENTS", "3"))
    seed = int(os.environ.get("BENCH_SOAK_SEED", "3"))

    workdir = tempfile.mkdtemp(prefix="swirld-bench-soak-")
    log(f"[soak] {n_nodes} processes through per-link fault proxies, "
        f"{horizon}s @ {rate} tx/s from {clients} clients; "
        f"crash + partition + equivocation storm ({workdir})")
    spec = _soak.default_spec(
        workdir, n_nodes=n_nodes, seed=seed, horizon_s=horizon,
        tx_rate=rate, n_clients=clients,
        net={"gossip_interval_s": 0.005, "checkpoint_every_s": 0.5},
    )
    spec = dataclasses.replace(spec, schedule=_soak.smoke_schedule(spec))
    verdict = _soak.run_soak(spec)
    log(f"[soak] ok={verdict['ok']} "
        f"survived={verdict['disruptions_survived']}"
        f"/{verdict['disruptions_total']} "
        f"tx/s={verdict['tx_per_s']:.0f} "
        f"submit_p99={verdict['submit_p99_s']:.3f}s "
        f"equivocations={verdict['adversary']['equivocations_detected']}")

    out = {
        "metric": "soak_tx_per_s",
        "value": verdict["tx_per_s"],
        "unit": "acked tx/sec under composed faults",
        "platform": "cpu-processes",
        "soak": {
            "tx_per_s": verdict["tx_per_s"],
            "submit_p99_s": verdict["submit_p99_s"],
            "disruptions_survived": verdict["disruptions_survived"],
            "disruptions_total": verdict["disruptions_total"],
            "verdict_ok": verdict["ok"],
            "safety": verdict["safety"],
            "finality": verdict["finality"],
            "accounting_balance_ok":
                verdict["accounting"].get("balance_ok"),
            "shed_rate": verdict["accounting"].get("shed_rate"),
            "net_redials": verdict["counters"]["net_redials"],
            "equivocations_detected":
                verdict["adversary"]["equivocations_detected"],
            "proxy_relayed": verdict["proxy"].get("relayed", 0),
            "proxy_partition_blocked":
                verdict["proxy"].get("partition_blocked", 0),
            "n_nodes": n_nodes,
            "horizon_s": horizon,
            "rate": rate,
        },
        "lint": lint_stamp(),
        "mc": mc_stamp(),
        "scale_audit": scale_audit_stamp(),
    }
    print(json.dumps(out), flush=True)
    if not verdict["ok"]:
        log("[soak] FAIL: composite verdict not green")
        sys.exit(1)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--stream", action="store_true",
        help="run the BASELINE config-5 shape (256 members / 100k events; "
        "BENCH_STREAM_* overrides) through the slab-store streaming "
        "driver under --tile-budget instead of the default bench",
    )
    ap.add_argument(
        "--tile-budget", type=int, default=65536,
        help="resident visibility tile budget for --stream (tiles of "
        "--tile x --tile bools; default 65536 = 4 GB bool ceiling at "
        "tile 256 — the config-5 window peaks around ~2.2 GB); "
        "0 = unbounded (account only)",
    )
    ap.add_argument("--tile", type=int, default=256, help="tile side")
    ap.add_argument(
        "--mesh", type=int, default=0, metavar="D",
        help="with --stream: row-shard the resident window over D devices "
        "(simulated on CPU via xla_force_host_platform_device_count) and "
        "report per-device peak tiles + scaling efficiency in a "
        "stream_mesh JSON object",
    )
    ap.add_argument(
        "--device-tile-budget", type=int, default=0,
        help="with --mesh: per-device resident tile bound (widest row "
        "shard); 0 = unbounded (account only)",
    )
    ap.add_argument(
        "--chaos-overhead", action="store_true",
        help="stamp device-pipeline ev/s with an equivocation storm at "
        "the full f=(n-1)//3 budget vs fault-free into a "
        "chaos_overhead JSON object (BENCH_CHAOS_* overrides); "
        "bench_compare.py gates clean/attack ev/s and their ratio",
    )
    ap.add_argument(
        "--cluster", action="store_true",
        help="run a real-process loopback cluster (socket transport, tx "
        "ingestion, kill -9 + checkpoint/WAL recovery) and stamp decided "
        "tx/s + submission→decided p50/p99 into a cluster JSON object "
        "(BENCH_CLUSTER_* overrides); also runs an overload leg that "
        "must shed load (exit 1 on any verdict failure or zero sheds)",
    )
    ap.add_argument(
        "--churn", action="store_true",
        help="run the dynamic-membership churn leg (a decided leave + "
        "join over one gossip schedule through the epoch-aware driver) "
        "and stamp churn.{evps, repack_p99_s, epochs} "
        "(BENCH_CHURN_* overrides); bench_compare.py gates evps/epochs "
        "higher-better and repack p99 lower-better; exit 1 if the "
        "schedule decides fewer than 3 epochs",
    )
    ap.add_argument(
        "--soak", action="store_true",
        help="run the composed production-day soak (per-link TCP fault "
        "proxies, heavy-tailed traffic, crash + partition + equivocation "
        "storm windows) and stamp acked tx/s, client-observed submit "
        "p99, and disruptions survived into a soak JSON object "
        "(BENCH_SOAK_* overrides); exit 1 on a red composite verdict",
    )
    args = ap.parse_args(argv)
    if args.soak:
        run_soak()
    elif args.churn:
        run_churn()
    elif args.cluster:
        run_cluster()
    elif args.chaos_overhead:
        run_chaos_overhead()
    elif args.stream:
        run_stream(
            args.tile_budget or None, args.tile,
            mesh_n=args.mesh,
            device_tile_budget=args.device_tile_budget or None,
        )
    else:
        run_default()


if __name__ == "__main__":
    main()
