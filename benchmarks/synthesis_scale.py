"""Paper Fig. 11: All-to-All synthesis time vs topology size (2D Mesh and 3D
Hypercube). PCCL's headline scalability claim: tractable growth (O(n^3)),
512-NPU A2A in minutes — vs hours for optimizer-based synthesizers."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import CollectiveRequest, SynthesisEngine
from repro.topology import mesh2d
from repro.topology.generators import grid_hypercube


def run(full: bool = False) -> list[Row]:
    rows = []
    mesh_sides = [3, 4, 5, 6, 8] + ([10, 12, 16] if full else [])
    for side in mesh_sides:
        topo = mesh2d(side, side)
        n = side * side
        alg, us = timed(SynthesisEngine(topo).collective,
                        CollectiveRequest("all_to_all", list(range(n))))
        alg.validate()
        rows.append(Row(
            f"fig11_synthesis_mesh{side}x{side}", us,
            f"npus={n};makespan={alg.makespan};transfers={alg.num_transfers}"))
    cube_sides = [2, 3, 4] + ([5, 6, 8] if full else [])
    for side in cube_sides:
        topo = grid_hypercube(side, 3)
        n = side ** 3
        # fig11 tracks *flat* synthesis scaling; grid_hypercube fabrics are
        # partitioned now, so pin the flat path (fig_hier_* covers hierarchy)
        alg, us = timed(SynthesisEngine(topo).collective, CollectiveRequest(
            "all_to_all", list(range(n)), hierarchy="never"))
        alg.validate()
        rows.append(Row(
            f"fig11_synthesis_cube{side}^3", us,
            f"npus={n};makespan={alg.makespan};transfers={alg.num_transfers}"))
    return rows
