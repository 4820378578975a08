"""Paper Fig. 19: sensitivity to the number of concurrent process groups.
Fixed mesh, increasing count of size-8 A2A groups: with one group PCCL can
borrow the whole idle network (paper: 3.05x); as groups contend, the
advantage narrows."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import ChunkIds, SynthesisEngine, all_to_all
from repro.topology import mesh2d

from benchmarks.process_group import _direct_joint


def run(full: bool = False) -> list[Row]:
    rows = []
    side = 8 if full else 6
    topo = mesh2d(side, side)
    pg = 8 if full else 6
    max_groups = (side * side) // pg
    counts = [1, 2, max_groups // 2, max_groups]
    counts = sorted({c for c in counts if c >= 1})
    for g in counts:
        groups = [list(range(i * pg, (i + 1) * pg)) for i in range(g)]
        ids = ChunkIds()
        named = [(f"pg{i}", all_to_all(grp, ids=ids))
                 for i, grp in enumerate(groups)]
        alg, us = timed(SynthesisEngine(topo).synthesize_joint, named)
        alg.validate()
        direct = _direct_joint(topo, groups)
        speedup = direct.makespan / alg.makespan
        rows.append(Row(
            f"fig19_ngroups_mesh{side}x{side}_g{g}", us,
            f"groups={g};pg_size={pg};speedup={speedup:.2f};"
            f"pccl_t={alg.makespan};direct_t={direct.makespan}"))
    return rows
