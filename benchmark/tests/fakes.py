"""Fake engines on a fake clock, for the driver arithmetic."""

import dataclasses
from typing import List


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(0.0, dt)


@dataclasses.dataclass
class FakeResult:
    n: int
    order: List[int]


class FakeInc:
    """Orders every event of a delta the moment it is ingested, and takes
    ``per_call`` seconds of the fake clock for each call."""

    def __init__(self, clock, per_call):
        self.clock, self.per_call, self.n = clock, per_call, 0
        self.calls = []
        self.store = self

    def ingest(self, events):
        events = list(events)
        self.clock.t += self.per_call
        self.calls.append(len(events))
        ordered = list(range(self.n, self.n + len(events)))
        self.n += len(events)
        return {"ordered": ordered}

    def result(self):
        return FakeResult(self.n, list(range(self.n)))

    def close(self):
        pass


class FakeProgram:
    def __init__(self, clock, per_call):
        self.clock, self.per_call = clock, per_call
        self.incs = []

    def streaming(self, members):
        self.incs.append(FakeInc(self.clock, self.per_call))
        return self.incs[-1]

    def batch(self, events, members):
        self.clock.t += self.per_call
        return FakeResult(len(events), list(range(len(events))))
