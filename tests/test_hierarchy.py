"""Tests for the hierarchical synthesis pipeline: phase composition
(PhasePlan), chunk-delivery equivalence against flat synthesis, makespan
bounds, per-pod plan reuse through the registry, and the launch-layer
routing."""

import pytest

from repro.core import (
    AlgorithmRegistry,
    ChunkIds,
    CollectiveRequest,
    Condition,
    HierarchicalSynthesizer,
    HierarchyError,
    PhasePlan,
    PhaseSpec,
    SynthesisEngine,
    phase_breakdown,
    replay_algorithm,
)
from repro.topology import multi_pod, ring, star_switch, two_level_switch
from repro.topology.generators import grid_hypercube

# hierarchical simulated makespan must stay within this factor of flat
# synthesis on fabrics where flat is feasible (ISSUE-3 acceptance bound)
_MAKESPAN_BOUND = 1.25


def _delivery(alg):
    """(chunk, src-or-srcs, dests) per condition — the delivery contract."""
    return sorted((c.chunk, c.src, tuple(sorted(c.dests)))
                  for c in alg.conditions)


class TestPhasePlan:
    def test_two_phase_chain(self):
        topo = ring(4)
        eng = SynthesisEngine(topo)
        c1 = [Condition(0, 0, frozenset([1]))]
        c2 = [Condition(1, 1, frozenset([2]))]
        alg = eng.synthesize_plan(PhasePlan(
            [PhaseSpec("a", conds=c1),
             PhaseSpec("b", conds=c2, after=("a",))],
            conditions=c1 + c2, name="chain"))
        alg.validate()
        bd = phase_breakdown(alg)
        assert bd["b"]["start"] >= bd["a"]["end"]

    def test_algorithm_phase_shifted_to_floor(self):
        topo = ring(4)
        eng = SynthesisEngine(topo)
        pre = eng.synthesize([Condition(0, 0, frozenset([1]))])
        alg = eng.synthesize_plan(PhasePlan(
            [PhaseSpec("x", conds=[Condition(1, 0, frozenset([1]))]),
             PhaseSpec("y", algorithm=pre, after=("x",),
                       chunk_map={0: 2})],
            conditions=[Condition(1, 0, frozenset([1])),
                        Condition(2, 0, frozenset([1]))]))
        alg.validate()
        ys = [t for t in alg.transfers if t.chunk == 2]
        assert min(t.start for t in ys) >= phase_breakdown(alg)["x"]["end"]

    def test_preload_from_shifted_phase_occupies_real_window(self):
        """Preloading a floor-shifted algorithm phase must commit its
        *effective* (shifted) occupancy, not its local times — otherwise a
        later phase schedules into the shifted window and congests."""
        topo = ring(4)
        eng = SynthesisEngine(topo)
        pre = eng.synthesize([Condition(1, 0, frozenset([1]))])
        alg = eng.synthesize_plan(PhasePlan(
            [PhaseSpec("a", conds=[Condition(0, 0, frozenset([2]))]),
             PhaseSpec("b", algorithm=pre, after=("a",)),
             PhaseSpec("c", conds=[Condition(2, 0, frozenset([1]))],
                       after=("a",), preload_from=("b",))],
            conditions=[Condition(0, 0, frozenset([2])),
                        Condition(1, 0, frozenset([1])),
                        Condition(2, 0, frozenset([1]))]))
        alg.validate()

    def test_duplicate_phase_name_rejected(self):
        eng = SynthesisEngine(ring(4))
        c = [Condition(0, 0, frozenset([1]))]
        with pytest.raises(ValueError, match="duplicate"):
            eng.synthesize_plan(PhasePlan(
                [PhaseSpec("a", conds=c), PhaseSpec("a", conds=c)],
                conditions=c))

    def test_unknown_dependency_rejected(self):
        eng = SynthesisEngine(ring(4))
        c = [Condition(0, 0, frozenset([1]))]
        with pytest.raises(ValueError, match="unknown"):
            eng.synthesize_plan(PhasePlan(
                [PhaseSpec("a", conds=c, after=("missing",))],
                conditions=c))

    def test_preload_from_cross_topology_rejected(self):
        topo = multi_pod(2, 2, 2, unit_links=True)
        eng = SynthesisEngine(topo)
        sub = topo.pod_subtopology(0)
        with pytest.raises(ValueError, match="different topology"):
            eng.synthesize_plan(PhasePlan(
                [PhaseSpec("a", conds=[Condition(0, 0, frozenset([1]))]),
                 PhaseSpec("b",
                           conds=[Condition(1, 0, frozenset([1]))],
                           topology=sub.topology, node_map=sub.nodes,
                           link_map=sub.links, preload_from=("a",))],
                conditions=[]))

    def test_all_reduce_still_composes(self):
        # the refactor of all-reduce onto PhasePlan keeps its contract
        eng = SynthesisEngine(ring(4))
        alg = eng.collective(CollectiveRequest("all_reduce", list(range(4))))
        alg.validate()
        assert [n for n, _, _ in alg.phase_spans] == \
            ["reduce_scatter", "all_gather"]


class TestDifferentialEquivalence:
    """Flat and hierarchical synthesis must fulfil the same conditions with
    every chunk delivered; hierarchical makespan stays within the bound."""

    @pytest.fixture(scope="class")
    def fabric(self):
        return multi_pod(2, 4, 8, unit_links=True)

    @pytest.mark.parametrize("kind", ["all_gather", "all_to_all"])
    def test_chunk_delivery_equivalence(self, fabric, kind):
        eng = SynthesisEngine(fabric, registry=AlgorithmRegistry())
        hier = eng.collective(CollectiveRequest(kind, fabric.npus))
        flat = eng.collective(CollectiveRequest(
            kind, group=tuple(fabric.npus), hierarchy="never"))
        assert hier.name.startswith("pccl_hier")
        hier.validate()  # every chunk delivered per its conditions
        flat.validate()
        assert _delivery(hier) == _delivery(flat)
        # replay agrees: same chunks complete, none missing
        assert set(replay_algorithm(hier).completion) == \
            set(replay_algorithm(flat).completion)

    @pytest.mark.parametrize("kind", ["all_gather", "all_to_all"])
    def test_makespan_within_bound(self, fabric, kind):
        eng = SynthesisEngine(fabric, registry=AlgorithmRegistry())
        hier = eng.collective(CollectiveRequest(kind, fabric.npus))
        flat = eng.collective(CollectiveRequest(
            kind, group=tuple(fabric.npus), hierarchy="never"))
        assert hier.makespan <= _MAKESPAN_BOUND * flat.makespan, (
            f"{kind}: hierarchical {hier.makespan} vs flat {flat.makespan}"
        )

    def test_sequential_regime_also_valid(self, fabric):
        h = HierarchicalSynthesizer(SynthesisEngine(fabric))
        for kind in ("all_gather", "all_to_all"):
            alg = getattr(h, kind)(fabric.npus, pipeline=False)
            alg.validate()
            names = [n for n, _, _ in alg.phase_spans]
            assert "inter" in names and any(
                n.startswith("intra:") for n in names)


class TestFabricFamilies:
    def test_heterogeneous_multi_pod(self):
        topo = multi_pod(2, 4, 4, dci_ports_per_pod=4)  # real alpha-beta
        eng = SynthesisEngine(topo)
        alg = eng.collective(CollectiveRequest("all_gather", topo.npus))
        assert alg.name == "pccl_hier_all_gather"
        alg.validate()

    def test_two_level_switch_ports(self):
        # pods whose boundary ports are switches: gateways fall back to the
        # NPUs behind the port, pipelining is refused (shared links)
        topo = two_level_switch(3, npus_per_node=4)
        h = HierarchicalSynthesizer(SynthesisEngine(topo))
        alg = h.all_to_all(list(range(12)))
        alg.validate()
        with pytest.raises(HierarchyError, match="pipeline"):
            h.all_to_all(list(range(12)), pipeline=True)

    def test_grid_hypercube_planes(self):
        topo = grid_hypercube(4, 3)  # 64 NPUs, 4 plane-pods, no switch
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
        for kind in ("all_gather", "all_to_all"):
            alg = eng.collective(CollectiveRequest(kind, topo.npus))
            assert alg.name.startswith("pccl_hier")
            alg.validate()

    def test_subgroup_spanning_pods(self):
        topo = multi_pod(2, 4, 8, unit_links=True)
        group = list(range(8, 24)) + list(range(40, 56))  # interior rows
        eng = SynthesisEngine(topo)
        alg = eng.collective(CollectiveRequest("all_gather", group))
        alg.validate()
        assert len(alg.conditions) == len(group)

    def test_single_pod_group_stays_flat(self):
        topo = multi_pod(2, 4, 8, unit_links=True)
        eng = SynthesisEngine(topo)
        alg = eng.collective(CollectiveRequest(
            "all_gather", list(range(32))))  # pod 0 only
        assert alg.name == "pccl_all_gather"
        alg.validate()

    def test_unpartitioned_fabric_stays_flat(self):
        eng = SynthesisEngine(ring(8))
        alg = eng.collective(CollectiveRequest("all_to_all", list(range(8))))
        assert alg.name == "pccl_all_to_all"
        with pytest.raises(HierarchyError):
            HierarchicalSynthesizer(eng).all_to_all(list(range(8)))


class TestPodPlanReuse:
    def test_isomorphic_pods_cost_one_synthesis(self):
        topo = multi_pod(4, 4, 4, unit_links=True, dci_ports_per_pod=4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        eng.hierarchical().all_gather(topo.npus, pipeline=False)
        # phases: intra x4 (1 miss + 3 hits), inter (1 miss),
        # scatter x4 (1 miss + 3 hits)
        assert reg.stats.misses == 3
        assert reg.stats.hits == 6

    def test_disk_roundtrip_of_pod_plans(self, tmp_path):
        topo = multi_pod(2, 2, 4, unit_links=True, dci_ports_per_pod=4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg1 = SynthesisEngine(topo, registry=reg1).hierarchical().all_gather(
            topo.npus, pipeline=False)
        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg2 = SynthesisEngine(topo, registry=reg2).hierarchical().all_gather(
            topo.npus, pipeline=False)
        alg2.validate()
        assert reg2.stats.misses == 0 and reg2.stats.disk_hits > 0
        assert alg2.makespan == alg1.makespan


class TestHierarchicalReductions:
    """Reduce-Scatter/All-Reduce via per-phase time reversal: delivery
    contract and reduction algebra against the oracle, makespan no worse
    than flat, reversal invariants, registry reuse, and fallbacks."""

    @pytest.fixture(scope="class")
    def fabric(self):
        return multi_pod(2, 4, 8, unit_links=True)

    def _reduction_state(self, alg):
        """Independent replay of the reduction algebra: contributions held
        per (node, chunk) after executing the schedule in time order."""
        holdings = {}
        for c in alg.conditions:
            for s in c.srcs:
                holdings[(s, c.chunk)] = frozenset([s])
        full = {c.chunk: c.srcs for c in alg.conditions}
        for t in sorted(alg.transfers, key=lambda t: t.start):
            held = holdings[(t.src, t.chunk)]
            if t.reduce:
                prev = holdings.get((t.dst, t.chunk), frozenset())
                assert not (prev & held), "double-counted contribution"
                holdings[(t.dst, t.chunk)] = prev | held
                if held != full[t.chunk]:
                    del holdings[(t.src, t.chunk)]
            else:
                holdings[(t.dst, t.chunk)] = held
        return holdings, full

    def test_reduce_scatter_matches_oracle_state(self, fabric):
        eng = SynthesisEngine(fabric, registry=AlgorithmRegistry())
        hier = eng.collective(CollectiveRequest("reduce_scatter", fabric.npus))
        assert hier.name == "pccl_hier_reduce_scatter"
        hier.validate(mode="oracle")
        assert all(t.reduce for t in hier.transfers)
        # every owner ends with exactly the full contribution set
        holdings, full = self._reduction_state(hier)
        for c in hier.conditions:
            for d in c.dests:
                assert holdings[(d, c.chunk)] == full[c.chunk]
        # same ownership contract as the flat route
        flat = eng.collective(CollectiveRequest(
            "reduce_scatter", group=tuple(fabric.npus), hierarchy="never"))
        assert flat.name == "pccl_reduce_scatter"
        flat.validate(mode="oracle")
        key = lambda a: sorted(
            (c.chunk, tuple(sorted(c.srcs)), tuple(sorted(c.dests)))
            for c in a.conditions)
        assert key(hier) == key(flat)

    def test_all_reduce_composes_rs_then_ag(self, fabric):
        eng = SynthesisEngine(fabric, registry=AlgorithmRegistry())
        alg = eng.collective(CollectiveRequest("all_reduce", fabric.npus))
        assert alg.name == "pccl_hier_all_reduce"
        alg.validate(mode="oracle")
        assert [n for n, _, _ in alg.top_phase_spans()] == \
            ["reduce_scatter", "all_gather"]
        # sub-phase provenance rides along as nested "parent/child" spans
        nested = [n for n, _, _ in alg.phase_spans if "/" in n]
        assert any(n.startswith("reduce_scatter/") for n in nested)
        assert any(n.startswith("all_gather/") for n in nested)
        bd = phase_breakdown(alg)
        assert bd["all_gather"]["start"] >= bd["reduce_scatter"]["end"]

    @pytest.mark.parametrize("kind", ["reduce_scatter", "all_reduce"])
    def test_makespan_not_worse_than_flat(self, fabric, kind):
        eng = SynthesisEngine(fabric, registry=AlgorithmRegistry())
        hier = eng.collective(CollectiveRequest(kind, fabric.npus))
        flat = eng.collective(CollectiveRequest(
            kind, group=tuple(fabric.npus), hierarchy="never"))
        assert hier.makespan <= flat.makespan, (
            f"{kind}: hierarchical {hier.makespan} vs flat {flat.makespan}")

    def test_reversal_invariants(self, fabric):
        """The reduction is an in-forest: per chunk each device forwards
        its partial at most once, and a forward never precedes a merged
        partial's arrival — the invariants time reversal promises."""
        eng = SynthesisEngine(fabric)
        alg = eng.hierarchical().reduce_scatter(fabric.npus)
        sent = set()
        arrivals = {}
        for t in alg.transfers:
            arrivals.setdefault((t.chunk, t.dst), []).append(t.end)
        for t in alg.transfers:
            assert (t.chunk, t.src) not in sent
            sent.add((t.chunk, t.src))
            for end in arrivals.get((t.chunk, t.src), ()):
                assert t.start >= end - 1e-9
        # reversal round-trip of the phase provenance: reversed spans run
        # scatter (leaf reduce) -> inter -> intra (final fold)
        names = [n for n, _, _ in alg.phase_spans]
        assert names.index("inter") > 0
        assert any(n.startswith("scatter:") for n in names)

    def test_sequential_regime_and_registry_reuse(self):
        topo = multi_pod(4, 4, 4, unit_links=True, dci_ports_per_pod=4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        alg = eng.hierarchical().reduce_scatter(topo.npus, pipeline=False)
        alg.validate()
        # reversed-fabric phases share plans exactly like the forward ones:
        # intra x4 (1 miss + 3 hits), inter (1 miss), scatter x4 (1 + 3)
        assert reg.stats.misses == 3
        assert reg.stats.hits == 6

    def test_grid_hypercube_reductions(self):
        topo = grid_hypercube(4, 3)  # 64 NPUs, 4 plane-pods, no switch
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
        for kind in ("reduce_scatter", "all_reduce"):
            alg = eng.collective(CollectiveRequest(kind, topo.npus))
            assert alg.name.startswith("pccl_hier")
            alg.validate()

    def test_shared_device_fabric_falls_back_flat(self):
        # two_level_switch pods share their local switches with the
        # boundary fabric: the reversed composition would double-forward
        # partials, so the in-forest guard routes reductions to flat
        topo = two_level_switch(3, npus_per_node=4)
        eng = SynthesisEngine(topo)
        alg = eng.collective(CollectiveRequest(
            "reduce_scatter", list(range(12))))
        assert alg.name == "pccl_reduce_scatter"
        alg.validate()
        with pytest.raises(HierarchyError, match="in-forest"):
            eng.hierarchical().reduce_scatter(list(range(12)))

    def test_subgroup_spanning_pods(self, fabric):
        group = list(range(8, 24)) + list(range(40, 56))
        eng = SynthesisEngine(fabric)
        alg = eng.collective(CollectiveRequest("all_reduce", group))
        assert alg.name == "pccl_hier_all_reduce"
        alg.validate(mode="oracle")

    def test_single_pod_group_stays_flat(self, fabric):
        eng = SynthesisEngine(fabric)
        alg = eng.collective(CollectiveRequest(
            "reduce_scatter", list(range(32))))  # pod 0 only
        assert alg.name == "pccl_reduce_scatter"
        alg.validate()

    def test_planner_routes_reductions(self):
        from repro.launch.sharding import MeshCollectivePlanner

        topo = multi_pod(2, 4, 8, unit_links=True)
        pl = MeshCollectivePlanner(
            topo, {"pod": 2, "data": 4, "model": 8},
            registry=AlgorithmRegistry())
        alg = pl.algorithm("reduce_scatter", "pod", 1)
        assert alg.name == "pccl_hier_reduce_scatter"
        alg.validate()
        ar = pl.algorithm("all_reduce", "pod", 0)
        assert ar.name == "pccl_hier_all_reduce"
        flat = pl.algorithm("reduce_scatter", "model", 0)
        assert flat.name == "pccl_reduce_scatter"


class TestPathReplication:
    def test_replicated_runs_stay_valid(self):
        topo = ring(6)
        eng = SynthesisEngine(topo)
        ids = ChunkIds()
        conds = [Condition(ids.next(), 0, frozenset([3]))
                 for _ in range(12)]
        rep = eng.synthesize(conds, replicate=True)
        ref = eng.synthesize(conds)
        rep.validate()
        ref.validate()
        assert rep.makespan == ref.makespan  # serial runs pack identically

    def test_replication_gated_off_on_limited_switch(self):
        topo = star_switch(4, buffer_limit=1)
        eng = SynthesisEngine(topo)
        ids = ChunkIds()
        conds = [Condition(ids.next(), 0, frozenset([2]))
                 for _ in range(4)]
        alg = eng.synthesize(conds, replicate=True)  # silently full search
        alg.validate()

    def test_flat_default_unchanged(self):
        # replicate defaults off: flat named collectives are byte-stable
        topo = ring(5)
        a = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(5))))
        b = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(5))))
        assert [(t.chunk, t.link, t.start) for t in a.transfers] == \
            [(t.chunk, t.link, t.start) for t in b.transfers]


class TestPlannerRouting:
    def test_pod_spanning_axis_routes_hierarchically(self):
        from repro.launch.sharding import MeshCollectivePlanner

        topo = multi_pod(2, 4, 8, unit_links=True)
        pl = MeshCollectivePlanner(
            topo, {"pod": 2, "data": 4, "model": 8},
            registry=AlgorithmRegistry())
        assert pl.spans_pods("pod")
        assert not pl.spans_pods("model")
        alg = pl.algorithm("all_gather", "pod", 3)
        assert alg.name == "pccl_hier_all_gather"
        alg.validate()
        flat = pl.algorithm("all_gather", "model", 0)
        assert flat.name == "pccl_all_gather"


class TestHierarchyAlwaysPolicy:
    def test_always_on_unpartitioned_raises(self):
        eng = SynthesisEngine(ring(8))
        for kind in ("all_gather", "all_to_all", "reduce_scatter",
                     "all_reduce"):
            with pytest.raises(HierarchyError, match="no partition"):
                eng.collective(CollectiveRequest(
                    kind, group=tuple(range(8)), hierarchy="always"))

    def test_always_not_served_cached_auto_fallback(self):
        """An auto call that fell back to flat must not satisfy a later
        hierarchy="always" call through the registry: "always" re-attempts
        the hierarchical route and raises on infeasibility."""
        topo = two_level_switch(3, npus_per_node=4)
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
        group = list(range(12))
        auto = eng.collective(CollectiveRequest(
            "reduce_scatter", group))  # in-forest guard -> flat fallback
        assert auto.name == "pccl_reduce_scatter"
        with pytest.raises(HierarchyError):
            eng.collective(CollectiveRequest(
                "reduce_scatter", group=tuple(group), hierarchy="always"))
