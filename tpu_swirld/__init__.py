"""tpu_swirld — a TPU-native hashgraph-consensus framework.

A from-scratch reimplementation of the capabilities of the reference
pure-Python hashgraph prototype (upstream layout: ``swirld.py`` /
``utils.py`` / ``viz.py``; see SURVEY.md — the reference mount was empty,
so SURVEY.md + BASELINE.json pin the spec), redesigned TPU-first:

- ``tpu_swirld.oracle`` — the pure-Python reference ``Node`` (events,
  validation, signed gossip sync with orphan/want-list recovery,
  ``divide_rounds`` / ``decide_fame`` / ``find_order``).  It is the
  bit-exactness oracle for the device path.
- ``tpu_swirld.packing`` — dense append-only packer: hash-DAG -> index
  arrays (``parents: int32[N,2]``, creator, seq, timestamps, coin bits,
  fork pairs, per-member tables).
- ``tpu_swirld.tpu`` — the batched JAX/XLA consensus pipeline: blockwise
  boolean-matmul ancestry, fork-aware ``see``, member-hop strongly-see
  (MXU matmuls), witness/round scan, fame fixed point with coin rounds,
  order extraction.  Bit-identical to the oracle (pinned by parity tests
  on every BASELINE config shape).
- ``tpu_swirld.parallel`` — SPMD sharding of the pipeline over a
  ``jax.sharding.Mesh`` member axis with ``psum`` stake aggregation.
- ``tpu_swirld.sim`` — in-process multi-node gossip simulation harness
  (the reference's ``test(n_nodes, n_turns)``), synthetic DAG generation
  at benchmark scale, and two byzantine adversaries (consistent-order
  fork injection + divergent equivocation).
- ``tpu_swirld.store`` — the tiled slab store: a host-side append-only
  archive of decided visibility rows, a fixed tile-budget accounting
  surface (``resident_tiles`` / ``spill`` / ``fetch``), and the
  ``StreamingConsensus`` driver whose resident device memory is bounded
  by the undecided window (BASELINE config 5 at full scale).
- ``tpu_swirld.checkpoint`` — packed-DAG, full-node, and slab-archive
  save/restore (digest-verified).
- ``tpu_swirld.metrics`` — per-phase timers and protocol gauges.
- ``tpu_swirld.viz`` — per-event state export (both backends), JSON /
  Graphviz / ASCII renderers.

Consensus entry points: ``Node.consensus_pass`` (``backend='python'``)
and ``tpu_swirld.tpu.run_consensus`` (``backend='tpu'``) consume the same
gossip-delta / packed-DAG inputs and produce identical ``round`` /
``witness`` / ``famous`` / consensus-order outputs (BASELINE north star).
"""

from tpu_swirld.config import SwirldConfig
from tpu_swirld.oracle.event import Event
from tpu_swirld.oracle.node import Node

__version__ = "0.5.0"

__all__ = ["SwirldConfig", "Node", "Event", "__version__"]
