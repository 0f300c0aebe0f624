"""The harness finds a cell's pieces by name, from files alone."""

import json
import os
import shutil

import pytest

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


@pytest.mark.parametrize("forkers,stake,fork_prob,fault", [
    (0, [1] * 4, None, None),
    (1, [1] * 4, 0.3, None),
    (21, [1] * 64, 0.05, None),
    (22, [1] * 64, 0.05, "third"),        # 66 of 64 * 3
    (1, [1, 1, 1, 3], 0.3, "third"),      # any member may be the forker
    (1, [1] * 3, 0.3, "third"),
    (-1, [1] * 4, None, "count"),
    (4, [1] * 4, None, "count"),
    (1, [1] * 4, 1.0, "fork_prob"),
    (1, [1] * 4, 0, "fork_prob"),
])
def test_forkers_must_stay_under_a_third_of_the_stake(forkers, stake,
                                                      fork_prob, fault):
    cfg = {"members": len(stake), "stake": stake, "forkers": forkers}
    if fork_prob is not None:
        cfg["fork_prob"] = fork_prob
    faults = spec.fork_faults(cfg)
    if fault is None:
        assert faults == []
    else:
        assert len(faults) == 1 and {
            "third": "a third", "count": "not a count",
            "fork_prob": "fork_prob"}[fault] in faults[0]


def test_validate_refuses_a_forked_config_past_its_guarantee(toy_root,
                                                           tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(toy_root, root)
    path = root / "benchmark" / "configs" / "toy8f2.json"
    cfg = json.loads(path.read_text())
    cfg["forkers"] = 3                              # 9 of 8 * 3
    path.write_text(json.dumps(cfg))
    assert any(f.startswith("config toy8f2: forkers 3")
               for f in spec.validate(str(root)))
