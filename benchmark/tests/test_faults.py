"""A whole run with the timed path broken underneath must read
``correct: false``; the same run unbroken reads true.

The harness's look for a chip is skipped (``devices_fn``); everything
else is the run as the driver makes it, at a toy size on the CPU.  Each
fault that these cells can have is planted once: a step that returns its
state unchanged, half of the work left out, and an answer altered where
it is produced, on an honest history and on a forked one.  There is no
exchange between chips to leave out: every cell runs on one chip.
"""

import contextlib
import io
import json

import numpy as np
import pytest

from benchmark import drivers, run


class Wrapped:
    """A streaming engine with one fault planted."""

    def __init__(self, inc, fault):
        self.inc, self.fault, self.store = inc, fault, inc.store
        self.seen = 0

    def ingest(self, events):
        events = list(events)
        self.seen += len(events)
        if self.fault == "unchanged":
            return {"ordered": []}
        if self.fault == "half" and self.seen > 750:
            return {"ordered": []}
        return self.inc.ingest(events)

    def result(self):
        res = self.inc.result()
        if self.fault == "altered":
            res.round = res.round.copy()
            res.round[len(res.round) // 2] += 1
        return res


def broken(fault):
    class Broken(drivers.Program):
        def streaming(self, members):
            return Wrapped(super().streaming(members), fault)

        def batch(self, events, members):
            if fault == "half":
                events = events[: len(events) // 2]
            res = super().batch(events, members)
            if fault == "altered":
                res.order = list(res.order)
                res.order[0], res.order[1] = res.order[1], res.order[0]
            return res
    return Broken


def result_line(toy_root, cpu_devices, cell, program_fn=drivers.Program):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", cell, "--seed", "2147483699",
                         "--seconds", "1.5", "--trace", "0"],
                        root=toy_root, devices_fn=cpu_devices,
                        program_fn=program_fn) == 0
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    return line


@pytest.mark.parametrize("cell", ["toy8.catchup", "toy8.stream", "toy8.live",
                                  "toy8f2.catchup", "toy8f2.stream"])
def test_sound_run_is_correct(toy_root, cpu_devices, cell):
    line = result_line(toy_root, cpu_devices, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert all(c["value"] == 0 == c["limit"] for c in line["checks"].values())
    assert "setup_s" in line["metrics"]
    assert all(np.isfinite(m["value"]) for m in line["metrics"].values())


@pytest.mark.parametrize("cell,fault", [
    ("toy8.catchup", "half"), ("toy8.catchup", "altered"),
    ("toy8.stream", "unchanged"), ("toy8.stream", "half"),
    ("toy8.stream", "altered"),
    ("toy8.live", "unchanged"), ("toy8.live", "altered"),
    ("toy8f2.catchup", "half"), ("toy8f2.catchup", "altered"),
    ("toy8f2.stream", "unchanged"), ("toy8f2.stream", "half"),
    ("toy8f2.stream", "altered"),
])
def test_fault_reads_incorrect(toy_root, cpu_devices, cell, fault):
    line = result_line(toy_root, cpu_devices, cell, broken(fault))
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
