"""Paper Fig. 16/17: process-group-aware A2A. Concurrent row-sized process
groups on a 2D mesh, jointly synthesized by PCCL vs localized Direct.
Paper reports 2.33-3.03x (mean 2.68x) and the Fig. 17 link-utilization gap:
Direct never touches links outside the group's shortest paths."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import (
    ChunkIds,
    Flow,
    SynthesisEngine,
    all_to_all,
    shortest_path_links,
    simulate_flows,
)
from repro.topology import mesh2d


def _direct_joint(topo, groups):
    """Direct baseline for several concurrent A2A process groups: every
    pairwise chunk rides its shortest path; all groups share the network."""
    ids = ChunkIds()
    flows = []
    for group in groups:
        for c in all_to_all(group, ids=ids):
            flows.append(Flow(c.chunk, c.bytes,
                              shortest_path_links(topo, c.src,
                                                  next(iter(c.dests)))))
    return simulate_flows(topo, flows)


def run(full: bool = False) -> list[Row]:
    rows = []
    sides = [4, 6] + ([8] if full else [])
    for side in sides:
        topo = mesh2d(side, side)
        groups = [[r * side + c for c in range(side)] for r in range(side)]
        ids = ChunkIds()
        named = [(f"row{r}", all_to_all(g, ids=ids))
                 for r, g in enumerate(groups)]
        alg, us = timed(SynthesisEngine(topo).synthesize_joint, named)
        alg.validate()
        direct = _direct_joint(topo, groups)
        speedup = direct.makespan / alg.makespan
        # Fig 17 analogue: fraction of physical links each algorithm touches
        pccl_links = len({t.link for t in alg.transfers})
        direct_links = len({t.link for t in direct.transfers})
        rows.append(Row(
            f"fig16_pg_rows_mesh{side}x{side}", us,
            f"groups={side};speedup={speedup:.2f};"
            f"pccl_links={pccl_links}/{topo.num_links};"
            f"direct_links={direct_links}/{topo.num_links}"))
    return rows
