"""Registry amortization: cold synthesis vs cache-hit latency across all
data-parallel rows of a 2D torus mesh (the production scenario: every row of
a (data, model) mesh runs the same collective on an isomorphic process
group). Cold = first row, full TEN/BFS synthesis; hit = remaining rows,
served by automorphism relabeling from the AlgorithmRegistry."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import AlgorithmRegistry, CollectiveRequest, SynthesisEngine
from repro.topology.generators import torus2d


def _rows(side: int) -> list[list[int]]:
    return [[r * side + c for c in range(side)] for r in range(side)]


def run(full: bool = False) -> list[Row]:
    out: list[Row] = []
    sides = [4, 8] + ([16] if full else [])
    for side in sides:
        for kind in ("all_gather", "all_to_all"):
            topo = torus2d(side, side)
            registry = AlgorithmRegistry()
            engine = SynthesisEngine(topo, registry=registry)
            rows = _rows(side)

            cold_alg, cold_us = timed(engine.collective,
                                      CollectiveRequest(kind, rows[0]))
            cold_alg.validate()

            hit_us_total = 0.0
            for row in rows[1:]:
                alg, us = timed(engine.collective, CollectiveRequest(kind, row))
                hit_us_total += us
                assert alg.makespan == cold_alg.makespan
            hit_us = hit_us_total / max(len(rows) - 1, 1)
            speedup = cold_us / hit_us if hit_us else float("inf")
            stats = registry.stats
            out.append(Row(
                f"registry_{kind}_torus{side}x{side}",
                cold_us,
                f"rows={side};cold_us={cold_us:.1f};hit_us={hit_us:.1f};"
                f"speedup={speedup:.1f}x;hits={stats.hits};"
                f"misses={stats.misses};makespan={cold_alg.makespan}",
            ))
    return out
