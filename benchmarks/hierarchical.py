"""Hierarchical synthesis benchmarks (fig_hier_*): the ISSUE-3/4/5 scale
gate.

Four row families:

* ``fig_hier_{ag,a2a,rs,ar}_<n>`` — cold hierarchical synthesis + full
  validation on multi-pod fabrics (the ≥1024-NPU rows are the headline:
  flat synthesis at that size is minutes-to-hours; hierarchical must land
  in seconds — including the reduction collectives, which compose per-pod
  reduce phases via the reversed-fabric trick). ``us_per_call`` is
  synthesis wall time; validation time rides in meta.
* ``fig_hier3_{ag,ar}_<n>`` — the multi-level (rack -> pod -> plane) rows:
  cold synthesis + bulk validation on ``three_level`` fabrics through the
  recursive pipeline. The ≥2048-NPU rows are fabrics the flat path cannot
  touch at all; ``misses`` in meta is the registry-miss count, bounded by
  (phase kinds x levels) + 1 named route regardless of fabric size.
* ``fig_hier_vs_flat_<kind>`` — simulated-makespan ratio hierarchical/flat
  on a fabric small enough for flat synthesis (<= 1.25x for the forward
  collectives, <= 1.0x for the reductions).
* ``fig_hier_reuse`` — registry amortization: N isomorphic pods cost one
  intra/scatter synthesis each.
"""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import AlgorithmRegistry, CollectiveRequest, SynthesisEngine
from repro.topology import multi_pod, three_level


def _cold_row(name: str, topo, kind: str, mode: str = "auto") -> Row:
    reg = AlgorithmRegistry()
    eng = SynthesisEngine(topo, registry=reg)
    alg, us = timed(eng.collective, CollectiveRequest(kind, topo.npus))
    _, val_us = timed(alg.validate, mode)
    n = len(topo.npus)
    return Row(
        name, us,
        f"npus={n};pods={topo.num_pods};levels={topo.partition_depth + 1};"
        f"makespan={alg.makespan};"
        f"transfers={alg.num_transfers};validate_s={val_us / 1e6:.2f};"
        f"total_s={(us + val_us) / 1e6:.2f};misses={reg.stats.misses};"
        f"algo={alg.name}",
    )


def run(full: bool = False) -> list[Row]:
    rows: list[Row] = []

    # -- cold synthesis + validation at scale ------------------------------
    # small pods minimize intra/scatter legs (see ISSUE-3 tuning): 64 pods
    # of 4x4 beats 16 pods of 8x8 by ~2.5x wall-clock at 1024 NPUs
    sizes = [(4, 4, 4, 4)]  # (pods, rows, cols, dci_ports) -> 64 NPUs
    if full:
        sizes += [(16, 4, 4, 4), (64, 4, 4, 4)]  # 256, 1024 NPUs
    for pods, r, c, ports in sizes:
        topo = multi_pod(pods, r, c, unit_links=True, dci_ports_per_pod=ports)
        n = pods * r * c
        rows.append(_cold_row(f"fig_hier_ag_{n}", topo, "all_gather"))
        rows.append(_cold_row(f"fig_hier_a2a_{n}", topo, "all_to_all"))
        rows.append(_cold_row(f"fig_hier_rs_{n}", topo, "reduce_scatter"))
        rows.append(_cold_row(f"fig_hier_ar_{n}", topo, "all_reduce"))

    # -- multi-level (rack -> pod -> plane) recursion at scale -------------
    # (pods, racks, npus_per_rack); bulk validation (the oracle replays
    # millions of transfers in python — the vectorized path is the point)
    sizes3 = [(4, 4, 4)]  # 64 NPUs, quick
    if full:
        sizes3 += [(8, 8, 8), (16, 16, 8)]  # 512, 2048 NPUs
    for pods, racks, k in sizes3:
        topo = three_level(pods, racks, k, unit_links=True)
        n = pods * racks * k
        rows.append(_cold_row(f"fig_hier3_ag_{n}", topo, "all_gather",
                              mode="bulk"))
        rows.append(_cold_row(f"fig_hier3_ar_{n}", topo, "all_reduce",
                              mode="bulk"))

    # -- hierarchical vs flat makespan on a flat-feasible fabric -----------
    topo = multi_pod(2, 4, 8, unit_links=True)
    eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
    for kind in ("all_gather", "all_to_all", "reduce_scatter", "all_reduce"):
        hier, hier_us = timed(eng.collective,
                               CollectiveRequest(kind, topo.npus))
        flat, flat_us = timed(eng.collective, CollectiveRequest(
            kind, group=tuple(topo.npus), hierarchy="never"))
        hier.validate()
        flat.validate()
        rows.append(Row(
            f"fig_hier_vs_flat_{kind}", hier_us,
            f"npus=64;hier_makespan={hier.makespan};"
            f"flat_makespan={flat.makespan};"
            f"ratio={hier.makespan / flat.makespan:.3f};"
            f"flat_synth_us={flat_us:.0f}",
        ))

    # -- chunk-granular (barrier-free) pipelined All-Reduce ----------------
    # quick: the flat-feasible 64-NPU fabric, where ratio (pipelined
    # hierarchical / flat makespan) is the headline (<= 1.05x gate); full:
    # 512/2048 three-level fabrics, where ratio against the sequential
    # (barrier) route shows what killing the RS->AG barrier buys at sizes
    # flat synthesis cannot touch
    topo = multi_pod(2, 4, 8, unit_links=True)
    reg = AlgorithmRegistry()
    eng = SynthesisEngine(topo, registry=reg)
    pipe, us = timed(eng.hierarchical().all_reduce, topo.npus,
                     pipeline=True)
    pipe.validate()
    flat = eng.collective(CollectiveRequest(
        "all_reduce", group=tuple(topo.npus), hierarchy="never"))
    rows.append(Row(
        "fig_hier_pipe_ar_64", us,
        f"npus=64;pods={topo.num_pods};makespan={pipe.makespan};"
        f"transfers={pipe.num_transfers};flat_makespan={flat.makespan};"
        f"ratio={pipe.makespan / flat.makespan:.3f};"
        f"misses={reg.stats.misses};algo={pipe.name}",
    ))
    if full:
        for pods, racks, k in ((8, 8, 8), (16, 16, 8)):
            topo = three_level(pods, racks, k, unit_links=True)
            n = pods * racks * k
            reg = AlgorithmRegistry()
            eng = SynthesisEngine(topo, registry=reg)
            pipe, us = timed(eng.hierarchical().all_reduce, topo.npus,
                             pipeline=True)
            _, val_us = timed(pipe.validate, "bulk")
            seq = SynthesisEngine(
                topo, registry=AlgorithmRegistry()).hierarchical(
            ).all_reduce(topo.npus, pipeline=False)
            rows.append(Row(
                f"fig_hier_pipe_ar_{n}", us,
                f"npus={n};pods={topo.num_pods};makespan={pipe.makespan};"
                f"transfers={pipe.num_transfers};"
                f"seq_makespan={seq.makespan};"
                f"ratio={pipe.makespan / seq.makespan:.3f};"
                f"validate_s={val_us / 1e6:.2f};"
                f"misses={reg.stats.misses};algo={pipe.name}",
            ))

    # -- per-pod plan amortization -----------------------------------------
    pods = 8 if full else 4
    topo = multi_pod(pods, 4, 4, unit_links=True, dci_ports_per_pod=4)
    reg = AlgorithmRegistry()
    eng = SynthesisEngine(topo, registry=reg)
    alg, us = timed(eng.hierarchical().all_gather, topo.npus, pipeline=False)
    alg.validate()
    st = reg.stats.as_dict()
    rows.append(Row(
        "fig_hier_reuse", us,
        f"pods={pods};misses={st['misses']};hits={st['hits']};"
        f"makespan={alg.makespan}",
    ))
    return rows
