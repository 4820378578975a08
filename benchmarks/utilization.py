"""Paper Fig. 18: network bandwidth utilization over time on an 8x8 mesh,
whole-cluster (PG=64) vs half-cluster (PG=32) All-to-All; the paper reports
PCCL finishing 1.88x faster than Direct for the PG=32 case."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import (
    CollectiveRequest,
    SynthesisEngine,
    direct_all_to_all,
    replay_algorithm,
)
from repro.topology import mesh2d


def run(full: bool = False) -> list[Row]:
    rows = []
    side = 8 if full else 6
    topo = mesh2d(side, side)
    n = side * side
    for pg_size in (n, n // 2):
        group = list(range(pg_size))
        alg, us = timed(SynthesisEngine(topo).collective,
                        CollectiveRequest("all_to_all", group))
        alg.validate()
        direct = direct_all_to_all(topo, group)
        speedup = direct.makespan / alg.makespan
        timeline = replay_algorithm(alg).busy_timeline(topo.num_links, bins=8)
        tl = "|".join(f"{x:.2f}" for x in timeline)
        rows.append(Row(
            f"fig18_util_mesh{side}x{side}_pg{pg_size}", us,
            f"speedup={speedup:.2f};pccl_t={alg.makespan};"
            f"direct_t={direct.makespan};busy_timeline={tl}"))
    return rows
