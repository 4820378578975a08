"""Scale smoke tests (slow tier): the array-backed core must hold the
paper's Fig. 11 trajectory — a 144-NPU mesh All-to-All synthesizes and
validates inside a hard wall-clock budget — and the multi-level
hierarchical pipeline must keep a cold three-level 2048-NPU All-Gather
inside its budget with registry misses bounded independent of fabric
size. Run with ``pytest -m slow`` (a non-blocking CI job does); the quick
tier skips these.
"""

import time

import pytest

from repro.core import AlgorithmRegistry, CollectiveRequest
from repro.core.engine import SynthesisEngine
from repro.topology import mesh2d, three_level

# generous for CI-class machines: the reference loop needs ~15-20s for the
# synthesis alone on a dev box, the event-frontier core ~3-4s
_BUDGET_SECONDS = 120.0

# cold 2048-NPU three-level All-Gather: ~12s synthesis + ~4s bulk
# validation on a dev box; generous headroom for CI-class machines
_HIER3_BUDGET_SECONDS = 300.0

# cold 512-NPU TE-vs-RR comparison: ~7s for both strategies + bulk
# validation on a dev box; generous headroom for CI-class machines
_TE_BUDGET_SECONDS = 180.0


@pytest.mark.slow
def test_mesh12x12_all_to_all_within_budget():
    topo = mesh2d(12, 12)
    n = 144
    t0 = time.perf_counter()
    alg = SynthesisEngine(topo).collective(CollectiveRequest(
        "all_to_all", list(range(n))))
    synth_s = time.perf_counter() - t0
    alg.validate()
    wall_s = time.perf_counter() - t0
    assert len(alg.conditions) == n * (n - 1)
    assert alg.makespan > 0
    assert wall_s < _BUDGET_SECONDS, (
        f"12x12 All-to-All took {wall_s:.1f}s (synthesis {synth_s:.1f}s), "
        f"budget {_BUDGET_SECONDS}s — the scaling regression gate failed"
    )


@pytest.mark.slow
def test_three_level_2048_all_gather_within_budget():
    """Cold multi-level (rack -> pod -> plane) 2048-NPU All-Gather: the
    recursion must synthesize + bulk-validate inside the budget, taking
    the truly hierarchical route, with registry misses bounded by
    (phase kinds x levels) + the named route — independent of fabric
    size (16 pods x 16 racks pay for ~one of each phase kind per level)."""
    topo = three_level(16, 16, 8, unit_links=True)
    n = 2048
    reg = AlgorithmRegistry()
    t0 = time.perf_counter()
    alg = SynthesisEngine(topo, registry=reg).collective(CollectiveRequest(
        "all_gather", topo.npus))
    synth_s = time.perf_counter() - t0
    alg.validate(mode="bulk")
    wall_s = time.perf_counter() - t0
    assert alg.name == "pccl_hier_all_gather"
    assert len(alg.conditions) == n
    assert any("/" in name for name, _, _ in alg.phase_spans), (
        "2048-NPU plan must carry nested (recursive) phase provenance")
    kinds, levels = 3, 3  # intra/inter/scatter x rack/pod/plane
    assert reg.stats.misses <= kinds * levels + 1, (
        f"registry misses {reg.stats.misses} exceed the (kinds x levels) "
        f"bound — per-rack/per-pod plan sharing has regressed")
    assert wall_s < _HIER3_BUDGET_SECONDS, (
        f"three-level 2048-NPU All-Gather took {wall_s:.1f}s (synthesis "
        f"{synth_s:.1f}s), budget {_HIER3_BUDGET_SECONDS}s"
    )


@pytest.mark.slow
def test_three_level_512_te_vs_rr_within_budget():
    """Cold 512-NPU three-level All-Gather under both gateway strategies:
    the traffic-engineered assignment (greedy min-max + refinement over
    512 multicast demands) must stay inside the wall-clock budget — the
    scaling gate for the TE machinery itself — and must land within a few
    percent of round-robin on this uniform fabric (count cycling is
    already load-balanced there; only tie-break alignment differs)."""
    t0 = time.perf_counter()
    spans = {}
    for strategy in ("round_robin", "te"):
        topo = three_level(8, 8, 8, unit_links=True)
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry(),
                              gateway_strategy=strategy)
        alg = eng.collective(CollectiveRequest("all_gather", topo.npus))
        alg.validate(mode="bulk")
        assert alg.name == "pccl_hier_all_gather"
        spans[strategy] = alg.makespan
    wall_s = time.perf_counter() - t0
    assert spans["te"] <= 1.05 * spans["round_robin"], (
        f"TE makespan {spans['te']} strays from round-robin "
        f"{spans['round_robin']} on a uniform 512-NPU fabric")
    assert wall_s < _TE_BUDGET_SECONDS, (
        f"512-NPU TE-vs-RR comparison took {wall_s:.1f}s, budget "
        f"{_TE_BUDGET_SECONDS}s — the TE assignment machinery has "
        f"stopped scaling"
    )


# cold 2048-NPU three-level *pipelined* All-Reduce: ~85s synthesis + ~15s
# bulk validation on a dev box — the chunk-granular junction plus forced
# in-pod replication keeps the barrier-free route inside the same order
# of magnitude as the sequential one
_HIER3_PIPE_AR_BUDGET_SECONDS = 120.0


@pytest.mark.slow
def test_three_level_2048_pipelined_all_reduce_within_budget():
    """Cold multi-level 2048-NPU chunk-granular (pipeline=True) All-Reduce:
    synthesize + bulk-validate inside the budget, with registry misses
    bounded by (phase kinds x levels) + 1 — the release-stripped uniform
    phases keep sharing canonical per-pod plans, and the release-bearing
    scatter/inter phases bypass the registry without churning it."""
    topo = three_level(16, 16, 8, unit_links=True)
    reg = AlgorithmRegistry()
    eng = SynthesisEngine(topo, registry=reg)
    t0 = time.perf_counter()
    alg = eng.hierarchical().all_reduce(topo.npus, pipeline=True)
    synth_s = time.perf_counter() - t0
    alg.validate(mode="bulk")
    wall_s = time.perf_counter() - t0
    assert alg.name == "pccl_hier_all_reduce"
    assert len(alg.conditions) == 2048
    # the chunk-granular junction's release provenance is present
    assert any(n == "all_gather/@release" for n, _, _ in alg.phase_spans)
    kinds, levels = 3, 3
    assert reg.stats.misses <= kinds * levels + 1, (
        f"registry misses {reg.stats.misses} exceed the (kinds x levels) "
        f"bound — pipelined phases are churning the registry")
    assert wall_s < _HIER3_PIPE_AR_BUDGET_SECONDS, (
        f"three-level 2048-NPU pipelined All-Reduce took {wall_s:.1f}s "
        f"(synthesis {synth_s:.1f}s), budget "
        f"{_HIER3_PIPE_AR_BUDGET_SECONDS}s"
    )
