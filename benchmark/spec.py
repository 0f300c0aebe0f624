"""Finds a cell's pieces by name, from files alone.

``BENCHMARK.json`` names each cell's configuration and traffic mix; the
configuration's file is the one its ``configs`` entry gives, the mix is
``benchmark/traffic/<traffic>.json`` and each per-layer metric's reader is
``benchmark/metrics/<metric>.py``.  A later PR adds a cell, a configuration,
a mix or a metric by adding such files and entries, and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")
DRIVERS = ("replay", "open_loop")  # benchmark/drivers.py


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict            # the configuration file's contents
    traffic: Dict           # the traffic mix's contents
    end_to_end: List[Dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[Dict]
    root: str

    def reader(self, metric: str):
        """The ``read(ctx)`` function of a per-layer metric."""
        path = os.path.join(self.root, "benchmark", "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + re.sub(r"\W", "_", metric), path
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def load_cell(name: str, root: str = ROOT,
              bench: Optional[Dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    wl = _by_name(bench["workloads"], name, "workload")
    cfg = _by_name(bench["configs"], wl["config"], "config")
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, ())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(
        name=name, chips=int(wl["chips"]),
        config=_load_json(root, cfg["file"]),
        traffic=_load_json(
            root, os.path.join("benchmark", "traffic", wl["traffic"] + ".json")
        ),
        end_to_end=e2e, per_layer=per_layer, root=root,
    )


def fork_faults(cfg: Dict) -> List[str]:
    """Where a configuration's forkers break its own BFT guarantee.  The
    run's seed relabels the members, so any ``forkers`` of them may be
    the ones that fork: their stake is taken as the largest it can be."""
    members, stake = int(cfg["members"]), sorted(cfg["stake"], reverse=True)
    f = cfg.get("forkers", 0)
    if isinstance(f, bool) or not isinstance(f, int) or not 0 <= f < members:
        return [f"forkers {f!r} is not a count under members {members}"]
    if 3 * sum(stake[:f]) >= sum(stake):
        return [f"forkers {f} may hold a third of the stake or more"]
    p = cfg.get("fork_prob", 0.05)
    if f and not (isinstance(p, (int, float)) and 0 < p < 1):
        return [f"fork_prob {p!r} is not in (0, 1)"]
    return []


def validate(root: str = ROOT, bench: Optional[Dict] = None) -> List[str]:
    """Every fault found in the benchmark's files (empty when sound)."""
    bench = bench if bench is not None else load_benchmark(root)
    bad: List[str] = []
    cells = [w["name"] for w in bench["workloads"]]
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in bench[kind]:
            if not NAME.match(e["name"]) or e["name"] in seen:
                bad.append(f"{kind}: bad or repeated name {e['name']!r}")
            seen.add(e["name"])
    for c in bench["configs"]:
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
            continue
        cfg = _load_json(root, c["file"])
        if set(c["reduced"]) - set(cfg):
            bad.append(f"config {c['name']}: reduced key not in its file")
        bad += [f"config {c['name']}: {f}" for f in fork_faults(cfg)]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: bad unit or direction")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"metric {m['name']}: unknown cell {w}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e_names:
            bad.append(f"metric {m['name']}: moves unknown {m['moves']}")
        path = os.path.join(root, "benchmark", "metrics", m["name"] + ".py")
        if not os.path.isfile(path):
            bad.append(f"metric {m['name']}: no reader {path}")
    for w in bench["workloads"]:
        try:
            cell = load_cell(w["name"], root, bench)
        except (KeyError, OSError, ValueError) as e:
            bad.append(f"cell {w['name']}: {e}")
            continue
        if cell.traffic.get("driver") not in DRIVERS:
            bad.append(f"cell {w['name']}: unknown driver")
        names = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in names or len(names) < 2 or not cell.per_layer:
            bad.append(f"cell {w['name']}: too few metrics")
    return bad
