"""Tiled slab store: memory-bounded visibility state for streaming consensus.

The batch pipeline materializes ``bool[N, N]`` ancestry/sees slabs — ~10 GB
at BASELINE config 5 scale (256 members / 100k events), which is why that
config was unreachable until event-axis blocking existed.
DAG-BFT systems scale by never holding the whole DAG's reachability state
resident: they commit and garbage-collect a decided prefix so live state is
proportional to the *undecided frontier* (Bullshark, arxiv 2209.05633;
"DAGs for the Masses", arxiv 2506.13998).  This package brings that memory
model to the device engine:

- :class:`~tpu_swirld.store.archive.SlabArchive` — an append-only,
  checkpointable host-side column archive of *decided* ancestry rows
  (zlib-packed bitmaps; sees rows are derived on fetch from the global
  fork-pair ledger, so only one slab is archived).
- :class:`~tpu_swirld.store.slab.SlabStore` — the fixed tile-budget API
  (``resident_tiles`` / ``spill`` / ``fetch``): accounts the device-resident
  window slabs in ``tile``-sized row/column tiles, spills decided rows into
  the archive, fetches archived rows back (reconstructing fork-aware sees),
  and enforces an optional hard budget.
- :class:`~tpu_swirld.store.streaming.StreamingConsensus` — the streaming
  driver: extends :class:`~tpu_swirld.tpu.pipeline.IncrementalConsensus`
  with bounded-chunk ingest, spill-on-prune / spill-on-rebase, and an
  archive-backed **widening rebase** that re-fetches archived tiles when a
  delta references pruned history (instead of recomputing — or crashing on
  — the full DAG).

Peak resident visibility memory becomes O(window²) instead of O(N²): a
config-5-shaped run completes on CPU under a fixed tile budget, with the
decided-prefix order bit-identical to the Python oracle.
"""

from tpu_swirld.store.archive import SlabArchive  # noqa: F401
from tpu_swirld.store.slab import SlabStore, TileBudgetExceeded  # noqa: F401
from tpu_swirld.store.streaming import StreamingConsensus  # noqa: F401

__all__ = [
    "SlabArchive",
    "SlabStore",
    "TileBudgetExceeded",
    "StreamingConsensus",
]
