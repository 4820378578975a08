"""Tests for the alpha-beta queuing simulator and baseline collectives,
anchored on analytically known completion times."""

import pytest

from repro.core import (
    CollectiveRequest,
    Flow,
    SynthesisEngine,
    collective_bandwidth,
    direct_all_gather,
    direct_all_to_all,
    replay_algorithm,
    ring_all_gather,
    shortest_path_links,
    simulate_flows,
)
from repro.topology import line, mesh2d, ring, torus2d
from repro.topology.topology import Topology


class TestShortestPath:
    def test_line(self):
        topo = line(4)
        route = shortest_path_links(topo, 0, 3)
        assert len(route) == 3
        assert topo.links[route[0]].src == 0
        assert topo.links[route[-1]].dst == 3

    def test_weighted_prefers_fast_detour(self):
        topo = Topology("weighted")
        topo.add_npus(3)
        topo.add_link(0, 2, alpha=0.0, beta=10.0)  # slow direct
        topo.add_link(0, 1, alpha=0.0, beta=1.0)
        topo.add_link(1, 2, alpha=0.0, beta=1.0)
        route = shortest_path_links(topo, 0, 2, chunk_bytes=1.0)
        assert len(route) == 2  # detour via 1 wins (2 < 10)


class TestSimulator:
    def test_single_flow_timing(self):
        topo = line(3)
        route = shortest_path_links(topo, 0, 2)
        res = simulate_flows(topo, [Flow(0, 1.0, route)])
        assert res.makespan == pytest.approx(2.0)  # two unit hops

    def test_fifo_contention(self):
        # two chunks over the same single link serialize
        topo = Topology("one_link")
        topo.add_npus(2)
        topo.add_link(0, 1, alpha=0.0, beta=1.0)
        res = simulate_flows(topo, [Flow(0, 1.0, [0]), Flow(1, 1.0, [0])])
        assert res.makespan == pytest.approx(2.0)
        assert sorted(res.completion.values()) == [pytest.approx(1.0), pytest.approx(2.0)]

    def test_release_time(self):
        topo = line(2)
        res = simulate_flows(topo, [Flow(0, 1.0, [0], release=5.0)])
        assert res.completion[0] == pytest.approx(6.0)

    def test_store_and_forward(self):
        # same chunk cannot be on two hops at once
        topo = line(3)
        route = shortest_path_links(topo, 0, 2)
        res = simulate_flows(topo, [Flow(0, 2.0, route)])
        assert res.makespan == pytest.approx(4.0)

    def test_busy_timeline_shape(self):
        topo = ring(4)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", [0, 1, 2, 3]))
        res = replay_algorithm(alg)
        timeline = res.busy_timeline(topo.num_links, bins=10)
        assert len(timeline) == 10
        assert all(0.0 <= x <= 1.0 + 1e-9 for x in timeline)
        # unidirectional ring AG keeps every link busy the whole time
        assert timeline[0] == pytest.approx(1.0)

    def test_busy_timeline_start_at_makespan(self):
        # regression: a transfer starting exactly at the makespan used to
        # index bin `bins` (IndexError); both bin indices must clamp.
        from repro.core import SimResult, Transfer

        res = SimResult(
            makespan=4.0,
            completion={0: 4.0, 1: 4.0},
            link_busy={0: 4.0},
            transfers=[
                Transfer(0, 0, 0, 1, 0.0, 4.0),
                Transfer(1, 1, 1, 2, 4.0, 4.0),  # starts at the makespan
            ],
        )
        timeline = res.busy_timeline(num_links=2, bins=8)
        assert len(timeline) == 8
        assert all(0.0 <= x <= 1.0 + 1e-9 for x in timeline)


class TestBaselines:
    def test_direct_a2a_mesh(self):
        topo = mesh2d(3, 3)
        res = direct_all_to_all(topo, list(range(9)))
        assert len(res.completion) == 72
        assert res.makespan > 0

    def test_pccl_beats_direct_on_mesh(self):
        # the paper's central claim (Fig 14/16)
        topo = mesh2d(4, 4)
        pccl = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(16))))
        direct = direct_all_to_all(topo, list(range(16)))
        assert pccl.makespan < direct.makespan

    def test_pccl_process_group_speedup(self):
        # process group = one mesh row; PCCL borrows other rows' links
        topo = mesh2d(4, 4)
        group = [0, 1, 2, 3]
        pccl = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", group))
        pccl.validate()
        direct = direct_all_to_all(topo, group)
        assert pccl.makespan <= direct.makespan

    def test_ring_ag_on_ring_matches_pccl(self):
        # on the actual ring topology the logical ring baseline is optimal,
        # PCCL must match it (both n-1 steps)
        topo = ring(6)
        base = ring_all_gather(topo, list(range(6)))
        pccl = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(6))))
        assert base.makespan == pytest.approx(pccl.makespan) == 5.0

    def test_ring_ag_unaware_on_torus_loses(self):
        # paper Fig 3b: topology-unaware ring underutilizes richer networks
        topo = torus2d(3, 3)
        base = ring_all_gather(topo, list(range(9)))
        pccl = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(9))))
        assert pccl.makespan < base.makespan

    def test_direct_ag(self):
        topo = mesh2d(3, 3)
        res = direct_all_gather(topo, list(range(9)))
        assert res.makespan > 0

    def test_bandwidth_metric(self):
        topo = ring(4)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", [0, 1, 2, 3]))
        res = replay_algorithm(alg)
        bw = collective_bandwidth(res, payload_bytes=4.0)
        assert bw == pytest.approx(4.0 / 3.0)
