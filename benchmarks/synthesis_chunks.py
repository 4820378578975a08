"""Paper Fig. 12: synthesis time vs collective size (chunks per NPU pair) on
a fixed mesh — scaling in the *collective* dimension rather than topology."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import CollectiveRequest, SynthesisEngine
from repro.topology import mesh2d
from repro.topology.generators import grid_hypercube


def run(full: bool = False) -> list[Row]:
    rows = []
    side = 8 if full else 4
    topo = mesh2d(side, side)
    n = side * side
    chunk_counts = [1, 2, 4] + ([8, 16] if full else [])
    for chunks in chunk_counts:
        alg, us = timed(SynthesisEngine(topo).collective, CollectiveRequest(
            "all_to_all", list(range(n)), chunks=chunks))
        alg.validate()
        rows.append(Row(
            f"fig12_chunks_mesh{side}x{side}_c{chunks}", us,
            f"npus={n};chunks_per_pair={chunks};makespan={alg.makespan}"))
    cube = grid_hypercube(4 if full else 2, 3)
    nn = len(cube.npus)
    for chunks in chunk_counts:
        # flat-path scaling row (hierarchical rows live in fig_hier_*)
        alg, us = timed(SynthesisEngine(cube).collective, CollectiveRequest(
            "all_to_all", list(range(nn)), chunks=chunks, hierarchy="never"))
        alg.validate()
        rows.append(Row(
            f"fig12_chunks_cube_{nn}_c{chunks}", us,
            f"npus={nn};chunks_per_pair={chunks};makespan={alg.makespan}"))
    return rows
