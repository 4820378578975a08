"""Paper Fig. 13: All-to-All on the heterogeneous 2D switch topology
(8-NPU nodes with fast local switches joined by a slower spine), PCCL vs the
Direct baseline. Paper reports 1.33x mean speedup.

Also the traffic-engineering rows (``fig_te_*``): hierarchical All-Gather and
All-to-All on multi-pod fabrics whose DCI uplinks have asymmetric bandwidth
(one healthy 100G port plus three degraded 10G ports per pod), comparing the
makespan-aware gateway assignment (``gateway_strategy="te"``) against the
legacy round-robin spread. On a uniform fabric the two tie; under skew,
round-robin keeps pushing an equal chunk share through the slow ports while
TE balances modeled link busy-time, so TE's win measures exactly the
traffic-engineering contribution."""

from __future__ import annotations

from benchmarks.common import Row, timed
from repro.core import (
    AlgorithmRegistry,
    CollectiveRequest,
    SynthesisEngine,
    direct_all_to_all,
)
from repro.topology import multi_pod, two_level_switch

# one healthy 100G uplink + three degraded 10G ports per pod: the skew is
# large enough that the boundary dominates makespan, which is the regime the
# TE assignment targets
_TE_DCI_GBPS = [100.0, 10.0, 10.0, 10.0]


def _te_rows(full: bool) -> list[Row]:
    rows = []
    pod_counts = [4, 8] + ([12] if full else [])
    for pods in pod_counts:
        topo = multi_pod(num_pods=pods, rows=2, cols=4,
                         dci_port_gbps=_TE_DCI_GBPS)
        n = len(topo.npus)
        for kind in ("all_gather", "all_to_all"):
            spans = {}
            us = 0.0
            for strategy in ("round_robin", "te"):
                engine = SynthesisEngine(topo, registry=AlgorithmRegistry(),
                                         gateway_strategy=strategy)
                alg, t = timed(engine.collective, CollectiveRequest(
                    kind, topo.npus, bytes=4.0))
                alg.validate(mode="bulk")
                spans[strategy] = alg.makespan
                if strategy == "te":
                    us = t
            speedup = (spans["round_robin"] / spans["te"]
                       if spans["te"] else 0.0)
            tag = "ag" if kind == "all_gather" else "a2a"
            rows.append(Row(
                f"fig_te_{tag}_{pods}pods", us,
                f"npus={n};pods={pods};makespan={spans['te']:.1f};"
                f"rr_t={spans['round_robin']:.1f};speedup={speedup:.2f}"))
    return rows


def run(full: bool = False) -> list[Row]:
    rows = []
    node_counts = [2, 4] + ([8, 16, 32] if full else [])
    for nodes in node_counts:
        topo = two_level_switch(nodes, npus_per_node=8)
        n = nodes * 8
        group = list(range(n))
        alg, us = timed(SynthesisEngine(topo).collective,
                        CollectiveRequest("all_to_all", group, bytes=128.0))
        alg.validate()
        direct = direct_all_to_all(topo, group, bytes=128.0)
        speedup = direct.makespan / alg.makespan if alg.makespan else 0.0
        rows.append(Row(
            f"fig13_switch2d_{n}npu", us,
            f"npus={n};pccl_t={alg.makespan:.1f};direct_t={direct.makespan:.1f};"
            f"speedup={speedup:.2f}"))
    rows.extend(_te_rows(full))
    return rows
