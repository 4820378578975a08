"""Paper Fig. 14: normalized whole-cluster All-to-All bandwidth, PCCL vs the
Direct baseline, as the 2D mesh grows. (TE-CCL comparison is quoted from the
paper — optimizer-based synthesis is out of scope of this repo.)"""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import CollectiveRequest, SynthesisEngine, direct_all_to_all
from repro.topology import mesh2d


def run(full: bool = False) -> list[Row]:
    rows = []
    sides = [3, 4, 5, 6] + ([7, 8] if full else [])
    for side in sides:
        topo = mesh2d(side, side)
        n = side * side
        group = list(range(n))
        alg, us = timed(SynthesisEngine(topo).collective,
                        CollectiveRequest("all_to_all", group))
        alg.validate()
        direct = direct_all_to_all(topo, group)
        # normalized algorithmic bandwidth = payload / time, direct == 1.0
        rel_bw = direct.makespan / alg.makespan
        rows.append(Row(
            f"fig14_a2a_bw_mesh{side}x{side}", us,
            f"npus={n};pccl_rel_bw={rel_bw:.2f};pccl_t={alg.makespan};"
            f"direct_t={direct.makespan}"))
    return rows
