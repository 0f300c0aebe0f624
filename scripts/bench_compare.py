#!/usr/bin/env python
"""Diff two BENCH_*.json artifacts and fail on throughput OR memory
regression.

Usage:
    python scripts/bench_compare.py OLD.json NEW.json [--threshold 0.10]
                                    [--key value]

Compares ``NEW[key]`` against ``OLD[key]`` (default key: ``value``, the
headline events/sec) and exits nonzero when the new number is more than
``threshold`` (default 10%) below the old one.  Also compared, when both
files carry them:

- ``incremental.steady_evps`` and ``stream.evps`` (higher is better — a
  drop >threshold fails, so the streaming config-5 throughput is gated
  exactly like the batch headline);
- the peak-memory metrics ``peak_host_bytes`` / ``peak_device_bytes`` /
  ``stream.peak_resident_visibility_bytes`` (LOWER is better — a rise
  >threshold fails, so a change that silently re-materializes an
  O(N²) slab trips the gate even when throughput improves);
- the finality-latency metrics ``finality.<engine>.ttf_p99`` (p99
  time-to-finality, seconds) and ``finality.<engine>.rtd_mean`` (mean
  rounds-to-decision) for the incremental/batch/streaming engines
  (LOWER is better — deciding the same history later is a latency
  regression even when events/sec holds).

Driver artifacts that wrap the bench line (``{"cmd": ..., "parsed":
{...}}`` — the BENCH_rNN.json files) are unwrapped automatically, so
``bench_compare.py BENCH_r03.json /tmp/BENCH_new.json`` works on the
checked-in history directly.

Everything else (phases, window stats) is printed as an informational
diff.

Opt-in wiring: this is NOT part of tier-1 (bench numbers are machine-
dependent); run it from CI or by hand after a bench run, e.g.::

    python bench.py > /tmp/BENCH_new.json
    python scripts/bench_compare.py BENCH_r03.json /tmp/BENCH_new.json

(A shape-level smoke test lives in tests/test_aux.py so the tool itself
cannot rot.)
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Optional

#: (dotted key, higher_is_better) — memory keys gate in the opposite
#: direction from throughput keys
EXTRA_KEYS = [
    ("incremental.steady_evps", True),
    ("stream.evps", True),
    ("peak_host_bytes", False),
    ("peak_device_bytes", False),
    ("stream.peak_resident_visibility_bytes", False),
    # mesh-streaming artifacts (bench.py --stream --mesh D): throughput
    # and scaling efficiency must not regress, per-device residency and
    # re-pin churn must not grow
    ("stream_mesh.evps", True),
    ("stream_mesh.scaling_efficiency", True),
    ("stream_mesh.peak_device_tiles", False),
    ("stream_mesh.repins", False),
    # adversary-overhead artifacts (bench.py --chaos-overhead): ev/s with
    # an equivocation storm at the full f budget, fault-free ev/s on the
    # same shape, and their ratio (attack/clean — a falling ratio means
    # the adversary path got relatively more expensive)
    ("chaos_overhead.clean_evps", True),
    ("chaos_overhead.attack_evps", True),
    ("chaos_overhead.ratio", True),
    # finality-latency artifacts (the bench `finality` section): p99
    # time-to-finality and mean rounds-to-decision are LOWER-is-better —
    # a change that decides the same history later (more virtual-voting
    # rounds, slower window passes) regresses user-visible latency even
    # when throughput holds
    ("finality.incremental.ttf_p99", False),
    ("finality.incremental.rtd_mean", False),
    ("finality.batch.rtd_mean", False),
    ("finality.streaming.ttf_p99", False),
    ("finality.streaming.rtd_mean", False),
    # real-process cluster artifacts (bench.py --cluster): decided
    # transactions per second across a 5-process loopback cluster, and
    # the merged p99 submission→decided wall latency — throughput must
    # not fall, tail latency must not grow
    ("cluster.tx_per_s", True),
    ("cluster.submit_p99_s", False),
    # production-day soak artifacts (bench.py --soak): acked client
    # tx/s under the composed fault schedule, client-observed p99
    # submit→ack latency, and the number of disruption windows the
    # cluster advanced past — throughput and survival must not fall,
    # tail latency must not grow
    ("soak.tx_per_s", True),
    ("soak.submit_p99_s", False),
    ("soak.disruptions_survived", True),
    # dynamic-membership churn artifacts (bench.py --churn): events/sec
    # through the epoch-aware driver over a multi-epoch schedule (higher
    # is better — a restatement or ledger-bookkeeping slowdown shows up
    # here first), the p99 member-axis repack latency at an epoch
    # boundary (LOWER is better — repack is on the live ingest path),
    # and the epoch count (higher is better: a silently-undecided
    # membership tx would *raise* evps while breaking the semantics)
    ("churn.evps", True),
    ("churn.repack_p99_s", False),
    ("churn.epochs", True),
]

def unwrap(doc: Dict) -> Dict:
    """Driver artifacts wrap the bench JSON line under ``parsed``."""
    if "value" not in doc and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]
    return doc


def _get(d: Dict[str, Any], dotted: str) -> Optional[float]:
    cur: Any = d
    for part in dotted.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    try:
        return float(cur)
    except (TypeError, ValueError):
        return None


def lint_gate(new: Dict) -> Optional[str]:
    """Refuse to gate a candidate produced from a tree with lint
    findings.  bench.py stamps ``lint`` (``tpu_swirld.analysis``
    summary) into every artifact; a stamp with findings means the
    number came from code violating the determinism/jit/thread
    invariants and is not comparable.  Artifacts predating the stamp
    (BENCH_r01–r04) pass with a warning — the gate only hardens going
    forward."""
    lint = new.get("lint")
    if lint is None:
        return None
    if isinstance(lint, dict) and lint.get("clean"):
        return None
    return (
        f"candidate tree had lint findings ({lint!r}); run "
        "scripts/lint.sh, fix, and re-bench before gating"
    )


def mc_gate(new: Dict) -> Optional[str]:
    """Refuse to gate a candidate whose model-checker smoke stamp is
    dirty.  bench.py stamps ``mc`` (``tpu_swirld.analysis.mc``
    ``mc_smoke``: the small world explored exhaustively under the full
    invariant catalog) into every artifact; a stamp that is not ``ok``
    means the consensus core the bench exercised violates its own
    invariants, so the number is not comparable.  Artifacts predating
    the stamp pass with a note — the gate only hardens going forward."""
    mc = new.get("mc")
    if mc is None:
        return None
    if isinstance(mc, dict) and mc.get("ok"):
        return None
    return (
        f"candidate tree failed the model-checker smoke ({mc!r}); run "
        "python -m tpu_swirld.analysis mc, fix, and re-bench before gating"
    )


def scale_audit_gate(new: Dict) -> Optional[str]:
    """Refuse to gate a candidate without a *clean* scale-audit stamp.

    bench.py stamps ``scale_audit`` (the jaxpr-level interval/dtype flow
    proof that the kernels are wrap- and bounds-safe at the baseline
    envelope) into every artifact.  Unlike the lint/mc gates, a missing
    stamp also refuses: the audit ships with the stamp, so "missing"
    can only mean the artifact was produced by a stripped bench or the
    stamp was deleted — either way the number is unvouched."""
    sa = new.get("scale_audit")
    if isinstance(sa, dict) and sa.get("clean"):
        return None
    if sa is None:
        return (
            "candidate carries no scale_audit stamp; re-bench with the "
            "current bench.py (python -m tpu_swirld.analysis scale-audit "
            "proves the kernels wrap- and bounds-safe) before gating"
        )
    return (
        f"candidate tree failed the scale audit ({sa!r}); run "
        "python -m tpu_swirld.analysis scale-audit, fix or justify each "
        "finding, and re-bench before gating"
    )


def soak_gate(new: Dict) -> Optional[str]:
    """Refuse a candidate whose soak run went red.  bench.py --soak
    stamps ``soak.verdict_ok`` — the composite verdict (oracle-replay
    bit-parity, liveness past every disruption window, finality-tail
    budget, zero shed-accounting leaks) over the composed chaos
    scenario.  A red soak means the numbers were measured on a cluster
    that lost safety, liveness, or transactions; they are not
    comparable regardless of how good they look.  Artifacts without the
    stamp (non-soak benches) pass untouched."""
    soak = new.get("soak")
    if not isinstance(soak, dict) or "verdict_ok" not in soak:
        return None
    if soak.get("verdict_ok"):
        return None
    return (
        "candidate's soak verdict is red (soak.verdict_ok false): the "
        "cluster lost safety, liveness, finality budget, or shed "
        "accounting under the composed schedule; replay the minimized "
        "schedule doc from scripts/soak_run.py, fix, and re-bench"
    )


def compare(old: Dict, new: Dict, key: str, threshold: float):
    """Returns (failures, report_lines)."""
    lines = []
    failures = []
    for k, higher_better in [(key, True)] + EXTRA_KEYS:
        ov, nv = _get(old, k), _get(new, k)
        if ov is None or nv is None:
            if k == key:
                failures.append(f"missing key {k!r} in one of the inputs")
            continue
        delta = (nv - ov) / ov if ov else 0.0
        bad = delta < -threshold if higher_better else delta > threshold
        verdict = "ok"
        if bad:
            direction = "below" if higher_better else "above"
            verdict = f"REGRESSION (>{threshold:.0%} {direction} old)"
            failures.append(f"{k}: {ov:.1f} -> {nv:.1f} ({delta:+.1%})")
        lines.append(
            f"{k:<40} {ov:>14.1f} -> {nv:>14.1f}  {delta:+7.1%}  {verdict}"
        )
    op, np_ = old.get("phases") or {}, new.get("phases") or {}
    for k in sorted(set(op) | set(np_)):
        ov, nv = op.get(k), np_.get(k)
        if isinstance(ov, (int, float)) and isinstance(nv, (int, float)):
            lines.append(f"  phase {k:<40} {ov:>12} -> {nv:>12}")
    return failures, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline BENCH json file")
    ap.add_argument("new", help="candidate BENCH json file")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="allowed fractional drop in throughput / rise in "
                         "peak memory (default 0.10 = 10%%)")
    ap.add_argument("--key", default="value",
                    help="headline metric key (default: value)")
    args = ap.parse_args(argv)
    with open(args.old) as f:
        old = unwrap(json.load(f))
    with open(args.new) as f:
        new = unwrap(json.load(f))
    for gate in (lint_gate(new), mc_gate(new), scale_audit_gate(new),
                 soak_gate(new)):
        if gate is not None:
            print(f"\nFAIL: {gate}", file=sys.stderr)
            return 1
    if new.get("lint") is None:
        print("note: candidate carries no lint stamp (pre-analysis "
              "artifact); gating on metrics only", file=sys.stderr)
    if new.get("mc") is None:
        print("note: candidate carries no model-checker stamp "
              "(pre-mc artifact); gating on metrics only", file=sys.stderr)
    failures, lines = compare(old, new, args.key, args.threshold)
    for ln in lines:
        print(ln)
    if failures:
        print("\nFAIL:", "; ".join(failures), file=sys.stderr)
        return 1
    print("\nOK: no throughput or peak-memory regression beyond "
          f"{args.threshold:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
