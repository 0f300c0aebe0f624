"""The generator with no forkers draws, byte for byte, the histories that
the cells measured before it took forkers: the digests below were taken
from the generator as it stood then."""

import hashlib

import pytest

from benchmark import drivers, gossip, spec

#: (cell, seed) -> digest of the ids, signatures and parent indices
PINNED = {
    ("council64.catchup", 1): "1eec43ed23c9ad53b45bbcbb362e22e6",
    ("council64.catchup", 2**31 + 11): "7f7850d5789fd707dc86515307f88fa8",
    ("wide256.catchup", 1): "13aa41a1c3c86e85f80a2a130bf502c7",
    ("wide256.catchup", 2**31 + 11): "d00a5c62965d18825261d0ea642646f3",
    ("council64.live", 1): "308382b69d463914fa9b2073df365bfc",
    ("council64.live", 2**31 + 11): "7846df31c7a51a81e91296d1fa343f34",
}


def digest(hist) -> str:
    d = hashlib.blake2b(digest_size=16)
    d.update(b"".join(hist.ids))
    d.update(b"".join(hist.sigs))
    d.update(hist.self_parent.astype("<i4").tobytes())
    d.update(hist.other_parent.astype("<i4").tobytes())
    return d.hexdigest()


@pytest.mark.parametrize("cell_name,seed", sorted(PINNED))
def test_honest_cells_draw_the_histories_they_drew(cell_name, seed):
    bench = spec.load_benchmark()
    cell = spec.load_cell(cell_name, bench=bench)
    cfg, mix = cell.config, cell.traffic
    assert cfg["forkers"] == 0
    n = int(cfg["history_events"])
    if mix["driver"] == "open_loop":
        n = drivers.stream_syncs(mix, bench["run_seconds"]) \
            * int(mix["sync_events"])
    hist = gossip.from_config(cfg, n, seed, int(mix["dag_seed"]))
    assert digest(hist) == PINNED[cell_name, seed]
