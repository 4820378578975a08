"""Differential harness for every synthesis routing path.

Four routes now produce schedules for the same named collectives — flat
per-chunk search, hierarchical phase composition (pipelined and
sequential), time-reversed reduction synthesis, and pipelined flat
All-Reduce — across every partitioned generator family. This suite pins
their equivalence systematically instead of by spot checks:

* every route's plan fulfils the *identical* per-chunk final conditions
  (same chunk -> same source/contributors -> same destination set);
* every plan passes validation under both the vectorized ``mode="bulk"``
  path and the reference ``mode="oracle"`` replay;
* on multi-level fabrics, the 2-level view (top partition only) and the
  3-level view (full partition tree) of the *same physical fabric* agree
  with each other and with flat synthesis;
* the registry never serves a plan cached for one partition-tree view to
  a request made under another (the partition-fingerprint regression).
"""

import pytest

from repro.core import (AlgorithmRegistry, CollectiveAlgorithm,
                        CollectiveRequest, SynthesisEngine, replay_algorithm)
from repro.core.conditions import Condition, ReduceCondition
from repro.core.hierarchy import HierarchicalSynthesizer, HierarchyError
from repro.topology import multi_pod, three_level, two_level_switch
from repro.topology.generators import grid_hypercube

KINDS = ("all_gather", "all_to_all", "reduce_scatter", "all_reduce")

# every partitioned generator family, small enough for oracle validation
FABRICS = {
    "multi_pod": lambda: multi_pod(2, 2, 4, unit_links=True,
                                   dci_ports_per_pod=4),
    "two_level_switch": lambda: two_level_switch(3, npus_per_node=4),
    "grid_hypercube": lambda: grid_hypercube(4, 2),
    "three_level": lambda: three_level(2, 2, 3, unit_links=True),
}


def _delivery(alg):
    """Per-chunk final conditions: (chunk, src-or-srcs, dests), sorted —
    the contract every routing path must agree on."""
    out = []
    for c in alg.conditions:
        if isinstance(c, ReduceCondition):
            out.append((c.chunk, tuple(sorted(c.srcs)),
                        tuple(sorted(c.dests))))
        else:
            out.append((c.chunk, c.src, tuple(sorted(c.dests))))
    return sorted(out)


def _routes(eng, kind, group):
    """Every routing path that can produce this collective on this engine's
    fabric: name -> algorithm. 'hier' may legitimately be a flat fallback
    (e.g. reductions on shared-device fabrics) — the equivalence claims
    hold either way."""
    routes = {
        "flat": eng.collective(
            CollectiveRequest(kind, group=tuple(group), hierarchy="never")),
        "hier": eng.collective(CollectiveRequest(
            kind, group)),  # auto: pipelined where safe
    }
    if kind == "all_reduce":
        routes["flat_pipelined"] = eng.collective(CollectiveRequest(
            "all_reduce", group=tuple(group), pipelined=True,
            hierarchy="never"))
    # the sequential (registry-shareable) hierarchical regime
    h = HierarchicalSynthesizer(SynthesisEngine(eng.topology,
                                                registry=eng.registry))
    try:
        routes["hier_sequential"] = getattr(h, kind)(group, pipeline=False)
    except HierarchyError:
        pass  # fabric family cannot take this path (e.g. in-forest guard)
    return routes


class TestRoutingPathEquivalence:
    """Flat vs hierarchical (pipelined and sequential) vs time-reversed vs
    pipelined plans: identical per-chunk final conditions, and every plan
    validates under both the bulk path and the oracle."""

    @pytest.mark.parametrize("fabric_name", sorted(FABRICS))
    @pytest.mark.parametrize("kind", KINDS)
    def test_differential(self, fabric_name, kind):
        topo = FABRICS[fabric_name]()
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
        group = topo.npus
        routes = _routes(eng, kind, group)
        assert "hier" in routes and "flat" in routes
        ref = _delivery(routes["flat"])
        ref_completion = set(replay_algorithm(routes["flat"]).completion)
        for name, alg in routes.items():
            assert _delivery(alg) == ref, (
                f"{fabric_name}/{kind}: route {name} fulfils different "
                f"final conditions than flat synthesis")
            alg.validate(mode="oracle")
            alg.validate(mode="bulk")
            # replay agrees: the same chunk set completes on every route
            assert set(replay_algorithm(alg).completion) == ref_completion

    @pytest.mark.parametrize("fabric_name", ["multi_pod", "three_level"])
    @pytest.mark.parametrize("kind", KINDS)
    def test_hier_route_actually_taken(self, fabric_name, kind):
        """On switch-boundary-free fabrics the auto route must really be
        hierarchical — a silent flat fallback would turn the differential
        suite into flat-vs-flat."""
        topo = FABRICS[fabric_name]()
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
        alg = eng.collective(CollectiveRequest(kind, topo.npus))
        assert alg.name.startswith("pccl_hier")


class TestTwoVsThreeLevel:
    """The same physical fabric viewed with a depth-1 partition (pods only)
    and with the full depth-2 tree (pods of racks) must fulfil identical
    final conditions — recursion changes the decomposition, never the
    contract."""

    def _views(self):
        deep = three_level(2, 2, 3, unit_links=True)
        shallow = three_level(2, 2, 3, unit_links=True)
        shallow.set_partition([p[0] for p in deep.partition_paths])
        assert shallow.partition_depth == 1 and deep.partition_depth == 2
        return shallow, deep

    @pytest.mark.parametrize("kind", KINDS)
    def test_depth_views_agree(self, kind):
        shallow, deep = self._views()
        a2 = SynthesisEngine(shallow, registry=AlgorithmRegistry()).collective(
            CollectiveRequest(kind, shallow.npus))
        a3 = SynthesisEngine(deep, registry=AlgorithmRegistry()).collective(
            CollectiveRequest(kind, deep.npus))
        assert _delivery(a2) == _delivery(a3)
        for alg in (a2, a3):
            alg.validate(mode="oracle")
            alg.validate(mode="bulk")

    def test_three_level_view_recurses(self):
        _, deep = self._views()
        alg = SynthesisEngine(deep).collective(CollectiveRequest(
            "all_gather", deep.npus))
        nested = [n for n, _, _ in alg.phase_spans if "/" in n]
        assert any(n.startswith("intra:") and "/inter" in n for n in nested), (
            "3-level view must decompose pod intra phases into nested "
            "rack/boundary phases")
        shallow_alg = SynthesisEngine(self._views()[0]).collective(CollectiveRequest(
            "all_gather", deep.npus))
        assert not any("/" in n for n, _, _ in shallow_alg.phase_spans)


class TestPartitionTreeRegistryKeys:
    """Registry route keys must encode the full partition-tree fingerprint:
    the topology *structure* hash is partition-blind, so a cached 2-level
    plan would otherwise be served verbatim for a 3-level view of the same
    fabric (regression test for the route-param key fix)."""

    def test_fingerprint_differs_by_tree(self):
        deep = three_level(2, 2, 3, unit_links=True)
        shallow = three_level(2, 2, 3, unit_links=True)
        shallow.set_partition([p[0] for p in deep.partition_paths])
        from repro.core import topology_fingerprint

        assert topology_fingerprint(shallow) == topology_fingerprint(deep)
        assert (shallow.partition_fingerprint()
                != deep.partition_fingerprint())

    @pytest.mark.parametrize("kind", KINDS)
    def test_cached_two_level_plan_not_served_for_three_level(self, kind):
        deep = three_level(2, 2, 3, unit_links=True)
        shallow = three_level(2, 2, 3, unit_links=True)
        shallow.set_partition([p[0] for p in deep.partition_paths])
        reg = AlgorithmRegistry()
        SynthesisEngine(shallow, registry=reg).collective(
            CollectiveRequest(kind, shallow.npus))
        misses = reg.stats.misses
        alg = SynthesisEngine(deep, registry=reg).collective(
            CollectiveRequest(kind, deep.npus))
        assert reg.stats.misses > misses, (
            f"{kind}: the 3-level view was served the cached 2-level plan")
        alg.validate()

    def test_leaf_phase_key_carries_sub_partition(self):
        """The per-phase keys distinguish partitioned from unpartitioned
        views of the same sub-fabric too: a pod synthesized flat (as a
        2-level leaf) must not satisfy the recursive (3-level) request for
        the same structural pod."""
        deep = three_level(2, 2, 3, unit_links=True)
        pod = deep.pod_subtopology(0).topology
        flat_pod = three_level(2, 2, 3, unit_links=True).pod_subtopology(
            0).topology
        flat_pod.set_partition([-1] * flat_pod.num_nodes)
        from repro.core import topology_fingerprint

        assert topology_fingerprint(pod) == topology_fingerprint(flat_pod)
        assert (pod.partition_fingerprint()
                != flat_pod.partition_fingerprint())


class TestPlannerRoutesThreeLevel:
    def test_mesh_planner_recursive_route(self):
        from repro.launch.sharding import MeshCollectivePlanner

        topo = three_level(2, 2, 4, unit_links=True)
        pl = MeshCollectivePlanner(
            topo, {"pod": 2, "rack": 2, "model": 4},
            registry=AlgorithmRegistry())
        assert pl.hierarchy_levels() == 3
        assert pl.spans_pods("pod")
        assert not pl.spans_pods("model")
        alg = pl.algorithm("all_gather", "pod", 0)
        assert alg.name == "pccl_hier_all_gather"
        alg.validate()

    def test_spanning_generic_conditions(self):
        """spanning() is public: arbitrary condition sets decompose too."""
        topo = three_level(2, 2, 4, unit_links=True)
        eng = SynthesisEngine(topo)
        conds = [
            Condition(0, 0, frozenset([5, 9, 13])),   # multicast, 3 pods
            Condition(1, 4, frozenset([2])),          # cross-rack
            Condition(2, 8, frozenset([15, 3])),      # cross-pod pair
        ]
        alg = eng.hierarchical().spanning(conds)
        alg.validate(mode="oracle")
        assert _delivery(alg) == _delivery(
            eng.synthesize(conds, name="flat"))

    def test_spanning_honours_releases(self):
        """A condition's release must survive every phase — in particular a
        chunk whose source IS its egress gateway reaches the inter phase
        with no intra barrier before it (regression: the inter/scatter
        builders used to drop the release, scheduling boundary transfers
        before the chunk existed)."""
        topo = multi_pod(2, 2, 2, unit_links=True, dci_ports_per_pod=2)
        eng = SynthesisEngine(topo)
        gw = topo.gateways(0)[0]
        remote = topo.pod_npus(1)[1]
        conds = [Condition(0, gw, frozenset([remote]), release=5.0),
                 Condition(1, topo.pod_npus(0)[1],
                           frozenset([remote]), release=3.0)]
        alg = eng.hierarchical().spanning(conds)
        alg.validate(mode="oracle")
        assert min(t.start for t in alg.transfers if t.chunk == 0) >= 5.0
        assert min(t.start for t in alg.transfers if t.chunk == 1) >= 3.0


class TestPipelinedAllReduceJunction:
    """Barrier vs chunk-granular All-Reduce junction: the two routes fulfil
    identical per-chunk final conditions and both pass bulk + oracle
    validation; the per-chunk junction can only tighten the makespan."""

    @pytest.mark.parametrize("fabric_name",
                             ["multi_pod", "two_level_switch",
                              "three_level"])
    def test_barrier_vs_chunk_granular(self, fabric_name):
        topo = FABRICS[fabric_name]()
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
        h = eng.hierarchical()
        try:
            barrier = h.all_reduce(topo.npus, pipeline=False)
        except HierarchyError:
            # shared-device boundaries fail the in-forest guard: the
            # engine route resolves the fallback; flat is the reference
            barrier = eng.collective(CollectiveRequest(
                "all_reduce", group=tuple(topo.npus), hierarchy="never"))
        try:
            pipe = h.all_reduce(topo.npus, pipeline=True)
        except HierarchyError:
            # switch-boundary fabrics refuse the forced pipeline; the auto
            # engine route resolves the regime itself and must agree
            pipe = eng.collective(CollectiveRequest("all_reduce", topo.npus))
        assert _delivery(pipe) == _delivery(barrier)
        for alg in (pipe, barrier):
            alg.validate(mode="bulk")
            alg.validate(mode="oracle")
        assert pipe.makespan <= barrier.makespan
        # barrier plans never carry the junction's release provenance
        assert not any("@release" in n for n, _, _ in barrier.phase_spans)

    def test_chunk_granular_release_provenance(self):
        """The pipelined junction records its per-chunk release envelope as
        a nested provenance span (invisible to top_phase_spans)."""
        topo = FABRICS["multi_pod"]()
        h = SynthesisEngine(topo, registry=AlgorithmRegistry()).hierarchical()
        alg = h.all_reduce(topo.npus, pipeline=True)
        spans = {n: (lo, hi) for n, lo, hi in alg.phase_spans}
        assert "all_gather/@release" in spans
        lo, hi = spans["all_gather/@release"]
        assert 0.0 < lo <= hi
        assert [n for n, _, _ in alg.top_phase_spans()] == [
            "reduce_scatter", "all_gather"]

    @pytest.mark.parametrize("fabric_name", ["multi_pod", "three_level"])
    def test_pre_release_corruption_flips_bulk(self, fabric_name):
        """Moving a single gather-half copy to before its chunk's reduce
        completion must flip bulk validation (and the oracle)."""
        import dataclasses

        topo = FABRICS[fabric_name]()
        h = SynthesisEngine(topo, registry=AlgorithmRegistry()).hierarchical()
        alg = h.all_reduce(topo.npus, pipeline=True)
        alg.validate(mode="bulk")
        ts = list(alg.transfers)
        # the last copy transfer starts strictly after its chunk's
        # assembly; yank it to t=0, before the chunk was even reduced
        idx = max((i for i, t in enumerate(ts) if not t.reduce),
                  key=lambda i: ts[i].start)
        assert ts[idx].start > 0.0
        dt = ts[idx].end - ts[idx].start
        ts[idx] = dataclasses.replace(ts[idx], start=0.0, end=dt)
        bad = CollectiveAlgorithm(alg.topology, alg.conditions, ts,
                                  name=alg.name)
        with pytest.raises(AssertionError):
            bad.validate(mode="bulk")
        with pytest.raises(AssertionError):
            bad.validate(mode="oracle")

    def test_pre_release_spanning_corruption_flips_bulk(self):
        """A plain released condition: a single transfer moved before the
        condition's release floor must flip bulk validation."""
        import dataclasses

        topo = multi_pod(2, 2, 2, unit_links=True, dci_ports_per_pod=2)
        eng = SynthesisEngine(topo)
        remote = topo.pod_npus(1)[1]
        conds = [Condition(0, topo.pod_npus(0)[1], frozenset([remote]),
                           release=5.0)]
        alg = eng.hierarchical().spanning(conds)
        alg.validate(mode="bulk")
        ts = list(alg.transfers)
        idx = min(range(len(ts)), key=lambda i: ts[i].start)
        dt = ts[idx].end - ts[idx].start
        ts[idx] = dataclasses.replace(ts[idx], start=0.0, end=dt)
        bad = CollectiveAlgorithm(alg.topology, alg.conditions, ts,
                                  name=alg.name)
        with pytest.raises(AssertionError):
            bad.validate(mode="bulk")
