"""End-to-end tour of tpu_swirld — run with:  python examples/demo.py

Covers the surface a py-swirld user would reach for: the in-process sim,
the consensus outputs, both backends (with bit-parity), byzantine forkers,
visualization export, metrics, and checkpoint/resume.
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# The tour's sharded step needs 8 devices: 8 virtual CPU devices unless
# the caller's environment names another platform.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

from tpu_swirld import viz
from tpu_swirld.checkpoint import load_node, save_node
from tpu_swirld.metrics import Metrics, node_gauges
from tpu_swirld.packing import pack_node
from tpu_swirld.parallel import make_mesh
from tpu_swirld.sim import make_simulation, run_with_divergent_forkers
from tpu_swirld.tpu.pipeline import run_consensus


def main():
    print("== 1. reference-style sim (5 members, 400 gossip turns)")
    sim = make_simulation(5, seed=42)
    sim.nodes[0].metrics = Metrics()
    sim.run(400)
    node = sim.nodes[0]
    print(f"   events={len(node.hg)} ordered={len(node.consensus)} "
          f"max_round={node.max_round}")
    print(f"   gauges: {node_gauges(node)}")
    print(f"   metrics: {node.metrics.snapshot()}")

    print("== 2. device pipeline on the same DAG — bit-identical")
    packed = pack_node(node)
    result = run_consensus(packed, node.config)
    assert [packed.ids[i] for i in result.order] == node.consensus
    print(f"   parity ok; device timings: {result.timings}")

    print("== 3. the same, sharded over an 8-device mesh (psum stake tally)")
    sharded = run_consensus(packed, node.config, mesh=make_mesh(8))
    assert sharded.order == result.order
    print("   sharded == unsharded")

    print("== 4. byzantine equivocation (7 members, 2 divergent forkers)")
    bsim = run_with_divergent_forkers(7, 2, 400, seed=5)
    orders = [n.consensus for n in bsim.nodes]
    m = min(len(o) for o in orders)
    assert m > 0 and all(o[:m] == orders[0][:m] for o in orders)
    forked = sum(
        n.has_fork[f.pk] for n in bsim.nodes for f in bsim.forkers
    )
    print(f"   honest prefix agreement over {m} events; "
          f"fork observations: {forked}")

    print("== 5. visualization export (last rows)")
    lanes = viz.ascii_lanes(node=node, max_height=6)
    print("\n".join("   " + line for line in lanes.splitlines()))

    print("== 6. checkpoint / resume")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "node.swck")
        save_node(path, node)
        restored = load_node(
            path, sk=node.sk, pk=node.pk, network=sim.network
        )
        assert restored.consensus == node.consensus
        print(f"   restored {len(restored.hg)} events, "
              f"{len(restored.consensus)} ordered — bit-identical")

    print("done.")


if __name__ == "__main__":
    main()
