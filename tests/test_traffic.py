"""Inter-pod traffic engineering: the min-max gateway assigner, the
CommSketch constraint surface, and their integration contracts.

Four claims:

1. **The assigner balances time, not counts.** On a hand-built boundary
   star with one fast and one slow uplink, greedy min-max assignment beats
   the count-balanced round-robin spread, the exact refinement pass never
   raises the peak, and ``better_of`` adopts a strictly better reference
   assignment wholesale (the never-worse guarantee).
2. **Sketches are hard constraints.** Gateway affinities confine a pod's
   boundary traffic to the named gateways, node/link exclusions keep every
   transfer off the excluded hardware, port caps bound the distinct
   gateways a pod opens — and an unsatisfiable sketch raises
   ``SketchInfeasibleError`` through the engine's named entry points
   instead of silently falling back to an unconstrained (flat or legacy)
   plan.
3. **The registry never cross-serves strategies.** A plan cached under
   round-robin must miss for a TE request, and an unconstrained plan must
   miss for a sketch-constrained one (and vice versa): the strategy and
   sketch fingerprint are part of the route/phase key.
4. **Nearest-gateway resolution is memoized.** Bulk All-to-Alls resolve
   the same (pod, node) pair once; the per-gateway BFS row count is pinned
   so an accidental cache bypass shows up as a counted regression.
"""

import pytest

from repro.core import (
    AlgorithmRegistry,
    CollectiveRequest,
    CommSketch,
    SketchInfeasibleError,
    SynthesisEngine,
    TrafficEngineer,
)
from repro.core.hierarchy import HierarchyError
from repro.topology import multi_pod
from repro.topology.topology import NodeType, Topology

KINDS = ["all_gather", "all_to_all", "reduce_scatter", "all_reduce"]


def _unit_pod(num_pods=2):
    return multi_pod(num_pods, 2, 4, unit_links=True, dci_ports_per_pod=4)


def _uplinks(topo, p):
    """[(link id, gateway npu)] for pod p's uplinks to the DCI switch."""
    gws = set(topo.gateways(p))
    return [(l.id, l.src) for l in topo.links
            if l.src in gws and topo.nodes[l.dst].type == NodeType.SWITCH]


class TestCommSketch:
    def test_normalization_order_independent(self):
        a = CommSketch(gateway_affinity={1: [7, 3], 0: [2]},
                       max_pod_ports=[(1, 2), (0, 1)])
        b = CommSketch(gateway_affinity=[(0, (2,)), (1, (3, 7))],
                       max_pod_ports={0: 1, 1: 2})
        assert a == b
        assert a.fingerprint() == b.fingerprint()
        assert a.allowed_gateways(1) == (3, 7)
        assert a.allowed_gateways(5) is None
        assert a.port_cap(0) == 1
        assert a.port_cap(9) is None

    def test_fingerprint_distinguishes_constraints(self):
        prints = {
            CommSketch().fingerprint(),
            CommSketch(gateway_affinity={0: [2]}).fingerprint(),
            CommSketch(exclude_nodes=[4]).fingerprint(),
            CommSketch(exclude_links=[4]).fingerprint(),
            CommSketch(max_pod_ports={0: 1}).fingerprint(),
        }
        assert len(prints) == 5

    def test_excludes_hardware(self):
        assert not CommSketch(max_pod_ports={0: 2}).excludes_hardware
        assert CommSketch(exclude_nodes=[1]).excludes_hardware
        assert CommSketch(exclude_links=[1]).excludes_hardware


def _star_boundary():
    """A boundary fabric in miniature: pod-0 gateways g_fast/g_slow uplink
    to a switch (beta 1.0 vs 4.0), pod-1 gateways h0/h1 downlink at beta
    1.0. Returns (topology, identity to_local, node ids)."""
    t = Topology("te_star")
    g_fast, g_slow, h0, h1 = t.add_npus(4)
    sw = t.add_node(NodeType.SWITCH)
    t.add_bidir_link(g_fast, sw, 0.0, 1.0)
    t.add_bidir_link(g_slow, sw, 0.0, 4.0)
    t.add_bidir_link(h0, sw, 0.0, 1.0)
    t.add_bidir_link(h1, sw, 0.0, 1.0)
    to_local = {n: n for n in range(t.num_nodes)}
    return t, to_local, (g_fast, g_slow, h0, h1)


class TestTrafficEngineerUnit:
    def test_min_max_beats_round_robin_counts(self):
        t, to_local, (g_fast, g_slow, h0, h1) = _star_boundary()
        te = TrafficEngineer(t, to_local)
        for k in range(4):
            te.assign(k, 0, [g_fast, g_slow], {1: [h0, h1]}, 1.0)
        te.refine()
        # count-balanced RR: 2 chunks through the beta-4 uplink = peak 8
        rr = [(g_fast if k % 2 == 0 else g_slow,
               {1: h0 if k % 2 == 0 else h1}) for k in range(4)]
        assert te.simulate(rr) == pytest.approx(8.0)
        # time-balanced: worst uplink carries at most all-fast (4) units
        assert te.peak() <= 4.0 + 1e-9
        assert not te.better_of(rr)  # RR is worse: never adopted

    def test_refine_never_raises_peak(self):
        t, to_local, (g_fast, g_slow, h0, h1) = _star_boundary()
        te = TrafficEngineer(t, to_local)
        for k in range(6):
            te.assign(k, 0, [g_fast, g_slow], {1: [h0, h1]}, 1.0)
        before = te.peak()
        te.refine()
        assert te.peak() <= before + 1e-12

    def test_better_of_adopts_superior_reference(self):
        t, to_local, (g_fast, g_slow, h0, h1) = _star_boundary()
        te = TrafficEngineer(t, to_local)
        # force every demand through the slow uplink
        for k in range(3):
            te.assign(k, 0, [g_slow], {1: [h0]}, 1.0)
        assert te.peak() == pytest.approx(12.0)
        ref = [(g_fast, {1: h0})] * 3
        assert te.better_of(ref)
        assert te.peak() == pytest.approx(3.0)
        assert [e for _, e, _ in te.assignments()] == [g_fast] * 3

    def test_route_deterministic_and_memoized(self):
        t, to_local, (g_fast, g_slow, h0, h1) = _star_boundary()
        cache = {}
        te = TrafficEngineer(t, to_local, route_cache=cache)
        cost, links = te.route(g_fast, h0)
        assert cost == pytest.approx(2.0)  # beta-1 up + beta-1 down
        assert te.route(g_fast, h0) == (cost, links)
        assert cache[(g_fast, h0)] == (cost, links)
        assert te.route(g_fast, g_fast) == (0.0, ())

    def test_unroutable_demand_raises(self):
        t = Topology("te_island")
        a, b = t.add_npus(2)  # no links at all
        te = TrafficEngineer(t, {a: a, b: b})
        with pytest.raises(ValueError):
            te.assign(0, 0, [a], {1: [b]}, 1.0)

    def test_port_cap_reuses_open_gateway(self):
        t, to_local, (g_fast, g_slow, h0, h1) = _star_boundary()
        te = TrafficEngineer(t, to_local,
                             sketch=CommSketch(max_pod_ports={1: 1}))
        picks = set()
        for k in range(4):
            _, ing = te.assign(k, 0, [g_fast, g_slow], {1: [h0, h1]}, 1.0)
            picks.add(ing[1])
        assert len(picks) == 1  # pod 1 opened exactly one ingress port


class TestSketchConstraints:
    def test_affinity_confines_boundary_traffic(self):
        topo = _unit_pod()
        allow = {p: [_uplinks(topo, p)[1][1]] for p in range(2)}
        sk = CommSketch(gateway_affinity=allow)
        alg = SynthesisEngine(topo, registry=AlgorithmRegistry(),
                              sketch=sk).collective(CollectiveRequest(
                                  "all_gather", topo.npus))
        alg.validate(mode="oracle")
        for p in range(2):
            used = {src for lid, src in _uplinks(topo, p)
                    if any(tr.link == lid for tr in alg.transfers)}
            assert used <= set(allow[p])

    def test_link_exclusion_keeps_traffic_off(self):
        topo = _unit_pod()
        banned = set()
        for lid, src in _uplinks(topo, 0)[:2]:
            banned.add(lid)
            # ban both directions of the uplink
            banned.update(l.id for l in topo.links
                          if l.dst == src
                          and topo.nodes[l.src].type == NodeType.SWITCH)
        sk = CommSketch(exclude_links=banned)
        alg = SynthesisEngine(topo, registry=AlgorithmRegistry(),
                              sketch=sk).collective(CollectiveRequest(
                                  "all_to_all", topo.npus))
        alg.validate(mode="oracle")
        assert not {tr.link for tr in alg.transfers} & banned

    def test_node_exclusion_drops_adjacent_boundary_links(self):
        topo = _unit_pod()
        victim = _uplinks(topo, 0)[0][1]
        boundary = set(topo.boundary_subtopology().links)
        adjacent = {l.id for l in topo.links
                    if l.id in boundary and victim in (l.src, l.dst)}
        assert adjacent
        sk = CommSketch(exclude_nodes=[victim])
        alg = SynthesisEngine(topo, registry=AlgorithmRegistry(),
                              sketch=sk).collective(CollectiveRequest(
                                  "all_gather", topo.npus))
        alg.validate(mode="oracle")
        assert not {tr.link for tr in alg.transfers} & adjacent

    def test_port_cap_bounds_distinct_gateways(self):
        topo = _unit_pod()
        sk = CommSketch(max_pod_ports={0: 1, 1: 1})
        alg = SynthesisEngine(topo, registry=AlgorithmRegistry(),
                              sketch=sk).collective(CollectiveRequest(
                                  "all_gather", topo.npus))
        alg.validate(mode="oracle")
        for p in range(2):
            used = {src for lid, src in _uplinks(topo, p)
                    if any(tr.link == lid for tr in alg.transfers)}
            assert len(used) <= 1

    @pytest.mark.parametrize("kind", KINDS)
    def test_infeasible_sketch_raises_through_engine(self, kind):
        """An unsatisfiable sketch must surface, not degrade to a flat or
        unconstrained plan — on every named entry point."""
        topo = _unit_pod()
        non_gateway = topo.npus[len(topo.npus) // 2 - 1]
        assert non_gateway not in topo.gateways(0)
        eng = SynthesisEngine(
            topo, registry=AlgorithmRegistry(),
            sketch=CommSketch(gateway_affinity={0: [non_gateway]}))
        with pytest.raises(SketchInfeasibleError):
            eng.collective(CollectiveRequest(kind, topo.npus))

    def test_exclusion_starving_a_pod_is_infeasible(self):
        topo = _unit_pod()
        sk = CommSketch(exclude_nodes=[src for _, src in _uplinks(topo, 0)])
        eng = SynthesisEngine(topo, registry=AlgorithmRegistry(), sketch=sk)
        with pytest.raises(SketchInfeasibleError):
            eng.collective(CollectiveRequest("all_gather", topo.npus))

    def test_sketch_is_not_a_hierarchy_error(self):
        # HierarchyError triggers the engine's silent flat fallback; an
        # infeasible sketch must never ride that path
        assert not issubclass(SketchInfeasibleError, HierarchyError)
        assert issubclass(SketchInfeasibleError, ValueError)


class TestRegistryStrategyKeys:
    """Strategy and sketch fingerprint are registry key components: plans
    synthesized under one gateway policy must never be served to another."""

    def test_rr_cached_plan_misses_for_te(self):
        topo = _unit_pod()
        reg = AlgorithmRegistry()
        SynthesisEngine(topo, registry=reg,
                        gateway_strategy="round_robin").collective(CollectiveRequest(
                            "all_gather", topo.npus))
        misses = reg.stats.misses
        SynthesisEngine(topo, registry=reg,
                        gateway_strategy="te").collective(CollectiveRequest(
                            "all_gather", topo.npus))
        assert reg.stats.misses > misses, (
            "TE request was served the cached round-robin plan")

    def test_unconstrained_plan_misses_for_sketch(self):
        topo = _unit_pod()
        reg = AlgorithmRegistry()
        SynthesisEngine(topo, registry=reg).collective(CollectiveRequest(
            "all_gather", topo.npus))
        misses = reg.stats.misses
        gw = _uplinks(topo, 0)[0][1]
        alg = SynthesisEngine(
            topo, registry=reg,
            sketch=CommSketch(gateway_affinity={0: [gw]}),
        ).collective(CollectiveRequest("all_gather", topo.npus))
        assert reg.stats.misses > misses, (
            "sketch-constrained request was served the unconstrained plan")
        alg.validate(mode="oracle")

    def test_sketch_plan_misses_for_unconstrained(self):
        topo = _unit_pod()
        reg = AlgorithmRegistry()
        gw = _uplinks(topo, 0)[0][1]
        SynthesisEngine(
            topo, registry=reg,
            sketch=CommSketch(gateway_affinity={0: [gw]}),
        ).collective(CollectiveRequest("all_gather", topo.npus))
        misses = reg.stats.misses
        SynthesisEngine(topo, registry=reg).collective(CollectiveRequest(
            "all_gather", topo.npus))
        assert reg.stats.misses > misses, (
            "unconstrained request was served the sketch-constrained plan")

    def test_same_strategy_hits(self):
        topo = _unit_pod()
        reg = AlgorithmRegistry()
        a = SynthesisEngine(topo, registry=reg,
                            gateway_strategy="te").collective(CollectiveRequest(
                                "all_gather", topo.npus))
        misses = reg.stats.misses
        b = SynthesisEngine(topo, registry=reg,
                            gateway_strategy="te").collective(CollectiveRequest(
                                "all_gather", topo.npus))
        assert reg.stats.misses == misses
        assert a.makespan == b.makespan


class TestNearestGatewayMemoized:
    def test_bfs_row_count_pinned(self, monkeypatch):
        """Resolving every (pod, node) pair twice must run at most one
        node->gateway BFS row per (pod, gateway): the per-pair results and
        the per-gateway distance rows are both cached."""
        from repro.topology.topology import Topology as T

        calls = {"n": 0}
        orig = T.hop_distances_to

        def counted(self, *a, **kw):
            calls["n"] += 1
            return orig(self, *a, **kw)

        monkeypatch.setattr(T, "hop_distances_to", counted)
        topo = _unit_pod()
        h = SynthesisEngine(topo).hierarchical()
        for _ in range(2):
            for p in range(topo.num_pods):
                for n in topo.npus:
                    if topo.partition[n] == p:
                        h._nearest_gateway(p, n)
        per_pod_gws = len(topo.gateways(0))
        assert calls["n"] <= topo.num_pods * per_pod_gws, (
            f"{calls['n']} BFS rows for {topo.num_pods} pods x "
            f"{per_pod_gws} gateways — nearest-gateway memoization regressed")
        again = calls["n"]
        for p in range(topo.num_pods):
            for n in topo.npus:
                if topo.partition[n] == p:
                    h._nearest_gateway(p, n)
        assert calls["n"] == again  # fully warm: zero new BFS rows
