"""The harness finds a cell's pieces by name, from files alone."""

import json
import os

from benchmark import spec

from .conftest import ROOT


def test_repo_benchmark_is_sound():
    assert spec.validate() == []
    bench = spec.load_benchmark()
    assert [w["name"] for w in bench["workloads"]] == [
        "council64.catchup", "wide256.catchup", "council64.live"]
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_throwaway_cell_from_test_data_loads_and_validates(toy_root):
    # the toy root holds the repo's files unchanged plus new ones
    for rel in ("benchmark/configs/council64.json",
                "benchmark/traffic/live_sync64.json",
                "benchmark/metrics/compile_s.py"):
        with open(os.path.join(ROOT, rel)) as a, \
                open(os.path.join(toy_root, rel)) as b:
            assert a.read() == b.read()
    assert spec.validate(toy_root) == []
    cell = spec.load_cell("toy8.live", toy_root)
    assert cell.config["members"] == 8
    assert cell.traffic["driver"] == "open_loop"
    names = [m["name"] for m in cell.per_layer]
    assert "toy_events" in names and "pass_ms_p50.live" in names
    read = cell.reader("toy_events")
    assert read(type("Ctx", (), {"counters": {"ordered": 5}})()) == 5
    assert {m["name"] for m in cell.end_to_end} == {
        "sync_p95_ms", "finality_p95_ms", "setup_s"}


def test_validate_reports_a_missing_reader(toy_root, tmp_path):
    bench = spec.load_benchmark(toy_root)
    bench["per_layer"].append(dict(bench["per_layer"][0], name="nowhere"))
    assert any("nowhere" in b for b in spec.validate(toy_root, bench))


def test_config_files_hold_their_reduced_keys():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        assert len(cfg["stake"]) == cfg["members"]
