"""Jit-boundary auditor: host syncs, recompiles, and signature drift.

The batch and streaming throughput numbers rest on three jit-boundary
facts: stage bodies never sync to the host, the shape buckets keep the
steady-state loop at zero recompiles, and every stage is called with a
stable abstract signature (a ``weak_type`` or dtype flip on an argument
is a silent recompile even at identical shapes).  This module audits all
three:

- :func:`static_audit` — AST pass over the kernel modules flagging
  host-sync calls *inside jit-decorated bodies*: ``.item()``,
  ``.block_until_ready()``, ``jax.device_get``, ``float()/int()/bool()``
  on tracers, and ``np.asarray``/``np.array`` (a silent device→host
  pull).
- :func:`runtime_audit` — drives a real windowed driver (``--engine``:
  :class:`IncrementalConsensus`, the slab-store
  :class:`StreamingConsensus`, or the row-sharded
  :class:`MeshStreamingConsensus` from the mesh streaming soak) over a
  generated gossip DAG with a signature observer installed on
  ``obs.stage_call``, then reports per-stage steady-state compile counts
  (cross-checked against :func:`tpu_swirld.obs.compile_counts`) and
  abstract-value drift: stages called with the same shapes/statics but
  differing dtype or ``weak_type``.

CLI: ``python -m tpu_swirld.analysis jit-audit`` (exit 1 on any host
sync, steady recompile, or drift).
"""

from __future__ import annotations

import ast
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: kernel modules the static pass covers (relative to the repo root)
_KERNEL_MODULES = (
    "tpu_swirld/tpu/pipeline.py",
    "tpu_swirld/tpu/pallas_kernels.py",
    "tpu_swirld/parallel.py",
)

#: attribute calls that synchronize device→host
_SYNC_ATTRS = {"item", "block_until_ready", "tolist", "copy_to_host_async"}
#: ``mod.fn`` calls that synchronize (or silently pull) device values
_SYNC_MODULE_FNS = {
    ("jax", "device_get"),
    ("np", "asarray"), ("np", "array"),
    ("numpy", "asarray"), ("numpy", "array"),
}


def _is_jitted(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(target, ast.Attribute) and target.attr in (
            "jit", "pmap", "pjit",
        ):
            return True
        if isinstance(target, ast.Name) and target.id in ("jit", "pjit"):
            return True
        # functools.partial(jax.jit, ...)
        if (
            isinstance(dec, ast.Call)
            and dec.args
            and isinstance(dec.args[0], ast.Attribute)
            and dec.args[0].attr == "jit"
        ):
            return True
    return False


def static_audit(root: str = ".") -> List[Dict]:
    """Host-sync calls inside jit-decorated function bodies in the
    kernel modules.  Returns ``[]`` on a clean tree."""
    findings: List[Dict] = []
    for rel in _KERNEL_MODULES:
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            continue
        with open(path, "r", encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef) or not _is_jitted(fn):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                c = node.func
                msg = None
                if isinstance(c, ast.Attribute) and c.attr in _SYNC_ATTRS:
                    msg = f".{c.attr}() inside jitted {fn.name}()"
                elif (
                    isinstance(c, ast.Attribute)
                    and isinstance(c.value, ast.Name)
                    and (c.value.id, c.attr) in _SYNC_MODULE_FNS
                ):
                    msg = (
                        f"{c.value.id}.{c.attr}(...) inside jitted "
                        f"{fn.name}() pulls the tracer to host"
                    )
                elif isinstance(c, ast.Name) and c.id in (
                    "float", "int", "bool",
                ) and node.args:
                    msg = (
                        f"{c.id}(...) on a value inside jitted "
                        f"{fn.name}() forces a host sync"
                    )
                if msg:
                    findings.append({
                        "path": rel, "line": node.lineno,
                        "stage": fn.name, "message": msg,
                    })
    return findings


# ------------------------------------------------------------ signatures


def _abstract(v) -> Tuple:
    """Hashable abstract value of one stage argument: arrays become
    (shape, dtype, weak_type), everything else its static repr."""
    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is not None and dtype is not None:
        weak = bool(getattr(v, "weak_type", False))
        return ("arr", tuple(shape), str(dtype), weak)
    return ("static", repr(v))


def _signature(args, kw) -> Tuple[Tuple, ...]:
    sig = tuple(_abstract(a) for a in args)
    if kw:
        sig += tuple(
            (k, _abstract(v)) for k, v in sorted(kw.items())
        )
    return sig


def _shape_key(sig: Tuple[Tuple, ...]) -> Tuple:
    """Signature with dtype/weak_type erased — two signatures sharing a
    shape key but differing overall are recompile-triggering drift."""
    out = []
    for part in sig:
        if part and part[0] == "arr":
            out.append(("arr", part[1]))
        else:
            out.append(part)
    return tuple(out)


def _find_drift(records: Dict[str, List[Tuple]]) -> List[Dict]:
    """Stages called with identical shapes/statics but differing
    dtype/weak_type — each such cluster is a silent recompile."""
    drift: List[Dict] = []
    for stage, sigs in sorted(records.items()):
        by_shape: Dict[Tuple, set] = {}
        for sig in sigs:
            by_shape.setdefault(_shape_key(sig), set()).add(sig)
        for key, variants in sorted(by_shape.items()):
            if len(variants) > 1:
                drift.append({
                    "stage": stage,
                    "variants": sorted(str(v) for v in variants),
                })
    return drift


def runtime_audit(
    *,
    n_members: int = 8,
    n_events: int = 1200,
    seed: int = 5,
    chunk: int = 128,
    window_bucket: int = 512,
    prune_min: int = 128,
    engine: str = "incremental",
) -> Dict[str, Any]:
    """Drive a real windowed-consensus run with the stage observer
    installed; report steady-state compile counts and signature drift.

    ``engine`` picks the driver under audit: ``"incremental"``
    (:class:`~tpu_swirld.tpu.pipeline.IncrementalConsensus`),
    ``"streaming"`` (:class:`~tpu_swirld.store.streaming.
    StreamingConsensus` — the slab-store retire/fetch stages join the
    observed set), or ``"mesh"`` (:class:`~tpu_swirld.parallel.
    MeshStreamingConsensus` — the row-sharded mesh driver from the
    streaming soak, so halo-exchange and sharded widening stages are
    covered; simulate devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

    Warmup covers the first two thirds of the chunks (shape buckets fill
    there); the audit window is the remainder under a fresh ``Obs`` so
    ``compile_counts`` isolates steady-state recompiles, exactly like the
    tier-1 recompile regression."""
    import functools

    from tpu_swirld import obs as obslib
    from tpu_swirld.config import SwirldConfig
    from tpu_swirld.sim import generate_gossip_dag
    from tpu_swirld.tpu.pipeline import IncrementalConsensus

    if engine == "streaming":
        from tpu_swirld.store.streaming import StreamingConsensus as _Driver
    elif engine == "mesh":
        import jax

        from tpu_swirld.parallel import MeshStreamingConsensus, make_mesh

        mesh = make_mesh(min(8, len(jax.devices())))
        _Driver = functools.partial(MeshStreamingConsensus, mesh)
    elif engine == "incremental":
        _Driver = IncrementalConsensus
    else:
        raise ValueError(f"unknown engine {engine!r}")

    members, stake, events, _keys = generate_gossip_dag(
        n_members, n_events, seed=seed
    )
    cfg = SwirldConfig(n_members=n_members)
    inc = _Driver(
        members, stake, cfg, chunk=chunk,
        window_bucket=window_bucket, prune_min=prune_min,
    )
    chunks = [events[i : i + 250] for i in range(0, len(events), 250)]
    warmup = (2 * len(chunks)) // 3
    for c in chunks[:warmup]:
        inc.ingest(c)

    records: Dict[str, List[Tuple]] = {}

    def observer(name, fn, args, kw):
        records.setdefault(name, []).append(_signature(args, kw))

    o = obslib.Obs()
    obslib.set_stage_observer(observer)
    try:
        with obslib.enabled(o):
            for c in chunks[warmup:]:
                inc.ingest(c)
    finally:
        obslib.set_stage_observer(None)

    steady = obslib.compile_counts(o.registry)
    drift = _find_drift(records)
    # the recompile/drift verdict covers the K-chunk rounds span only if
    # the steady window dispatched one — surface that in the report
    fused_audited = "pipeline.rounds_span_stage" in records
    return {
        "engine": engine,
        "stages_observed": sorted(records),
        "steady_calls": {k: len(v) for k, v in sorted(records.items())},
        "steady_compiles": steady,
        "signature_drift": drift,
        "fused_span_audited": fused_audited,
        "ok": not steady and not drift,
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="python -m tpu_swirld.analysis jit-audit",
        description=__doc__.splitlines()[0],
    )
    ap.add_argument("--root", default=".", help="repo root for the static pass")
    ap.add_argument("--static-only", action="store_true")
    ap.add_argument("--members", type=int, default=8)
    ap.add_argument("--events", type=int, default=1200)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument(
        "--engine", choices=("incremental", "streaming", "mesh"),
        default="incremental",
        help="windowed driver for the runtime pass: incremental "
        "(default), streaming (slab store), or mesh (row-sharded "
        "MeshStreamingConsensus)",
    )
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    report: Dict[str, Any] = {"static": static_audit(args.root)}
    ok = not report["static"]
    if not args.static_only:
        rt = runtime_audit(
            n_members=args.members, n_events=args.events, seed=args.seed,
            engine=args.engine,
        )
        report["runtime"] = rt
        ok = ok and rt["ok"]
    report["ok"] = ok
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        for f in report["static"]:
            print(f"{f['path']}:{f['line']}: {f['message']}")
        if "runtime" in report:
            rt = report["runtime"]
            print(f"stages observed: {len(rt['stages_observed'])}")
            print(f"fused span audited: {rt['fused_span_audited']}")
            print(f"steady-state compiles: {rt['steady_compiles'] or 'none'}")
            for d in rt["signature_drift"]:
                print(f"drift in {d['stage']}: {d['variants']}")
        print("OK" if ok else "FAIL")
    return 0 if ok else 1
