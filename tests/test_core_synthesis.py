"""Unit tests for the PCCL core synthesizer (paper §4, Algorithms 1-3)."""

import pytest

from repro.core import (
    ChunkIds,
    CollectiveRequest,
    Condition,
    SynthesisEngine,
    all_gather,
    all_to_all,
    all_to_allv,
    broadcast,
    direct_all_to_all,
    gather,
    multicast,
    point_to_point,
    scatter,
    order_conditions,
)
from repro.core.pathfinding import bfs_cont, bfs_int
from repro.core.ten import TEN
from repro.topology import (
    hypercube,
    line,
    mesh2d,
    ring,
    star_switch,
    torus2d,
    two_level_switch,
)
from repro.topology.topology import Topology


class TestTENOps:
    """Algorithm 1: NextDevices / Available / NextAvailableTime analogues."""

    def test_earliest_free_empty(self):
        ten = TEN(ring(4))
        assert ten.earliest_free(0, 0.0, 1.0) == 0.0
        assert ten.earliest_free(0, 2.5, 1.0) == 2.5

    def test_earliest_free_after_commit(self):
        ten = TEN(ring(4))
        ten.commit(0, 0.0, 1.0)
        assert ten.earliest_free(0, 0.0, 1.0) == pytest.approx(1.0)
        # gap fitting: commit [2,3) -> a 1.0 transfer fits at [1,2)
        ten.commit(0, 2.0, 3.0)
        assert ten.earliest_free(0, 0.0, 1.0) == pytest.approx(1.0)
        assert ten.earliest_free(0, 0.0, 1.5) == pytest.approx(3.0)

    def test_commit_overlap_raises(self):
        ten = TEN(ring(4))
        ten.commit(0, 0.0, 2.0)
        with pytest.raises(AssertionError):
            ten.commit(0, 1.0, 1.5)

    def test_int_mode(self):
        ten = TEN(ring(4))
        assert ten.free_int(0, 0)
        ten.commit_int(0, 0)
        assert not ten.free_int(0, 0)
        assert ten.earliest_free_int(0, 0) == 1
        with pytest.raises(AssertionError):
            ten.commit_int(0, 0)


class TestBFS:
    """Algorithm 2 over unit-time TENs."""

    def test_single_hop(self):
        topo = ring(4)
        res = bfs_int(TEN(topo), Condition(0, 0, frozenset([1])))
        assert len(res.transfers) == 1
        t = res.transfers[0]
        assert (t.src, t.dst, t.start, t.end) == (0, 1, 0.0, 1.0)

    def test_multi_hop_unidirectional(self):
        topo = ring(4)  # 0->1->2->3->0
        res = bfs_int(TEN(topo), Condition(0, 0, frozenset([3])))
        assert res.reached[3] == 3.0
        assert len(res.transfers) == 3

    def test_multicast_tree_pruning(self):
        # paper Fig 6: BFS may visit extra nodes; pruning keeps only useful paths
        topo = mesh2d(3, 3)
        res = bfs_int(TEN(topo), Condition(0, 4, frozenset([0, 8])))
        # every retained transfer lies on a path to 0 or 8
        nodes = {t.dst for t in res.transfers} | {4}
        assert 0 in nodes and 8 in nodes
        # retained tree has exactly |path edges| <= visited edges
        assert len(res.transfers) <= 4

    def test_busy_links_route_around(self):
        topo = line(3)  # 0<->1<->2
        ten = TEN(topo)
        # occupy link 0->1 at t=0 (link id 0)
        ten.commit_int(0, 0)
        res = bfs_int(ten, Condition(0, 0, frozenset([2])))
        # must wait: 0->1 at t=1, 1->2 at t=2 => arrival 3
        assert res.reached[2] == 3.0

    def test_unreachable_raises(self):
        topo = Topology("disc")
        topo.add_npus(2)  # no links
        with pytest.raises(AssertionError):
            bfs_int(TEN(topo), Condition(0, 0, frozenset([1])))

    def test_continuous_matches_int_on_homogeneous(self):
        topo = mesh2d(3, 3)
        cond = Condition(0, 0, frozenset(range(9)))
        res_i = bfs_int(TEN(topo), cond)
        res_c = bfs_cont(TEN(topo), cond)
        assert res_i.reached == res_c.reached


class TestConditionBuilders:
    def test_counts(self):
        g = [0, 1, 2, 3]
        assert len(all_gather(g)) == 4
        assert len(all_to_all(g)) == 12
        assert len(scatter(g, 0)) == 3
        assert len(gather(g, 0)) == 3
        assert len(broadcast(g, 2)) == 1
        assert len(point_to_point(0, 3)) == 1
        assert len(multicast(0, [1, 2])) == 1

    def test_all_to_allv_counts(self):
        g = [0, 1]
        conds = all_to_allv(g, [[0, 3], [1, 0]])
        assert len(conds) == 4
        froms = sorted((c.src, next(iter(c.dests))) for c in conds)
        assert froms == [(0, 1), (0, 1), (0, 1), (1, 0)]

    def test_unique_chunk_ids_joint(self):
        ids = ChunkIds()
        a = all_gather([0, 1], ids=ids)
        b = all_to_all([2, 3], ids=ids)
        chunks = [c.chunk for c in a + b]
        assert len(set(chunks)) == len(chunks)

    def test_chunkids_split_shares_counter(self):
        parent = ChunkIds()
        c1, c2 = parent.split(2)
        ids = [c1.next(), c2.next(), parent.next(), c1.next()]
        assert ids == sorted(set(ids)), "split allocators must never collide"

    def test_chunkids_split_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            ChunkIds().split(0)

    def test_joint_with_split_allocators(self):
        topo = mesh2d(3, 3)
        v_ids, ag_ids = ChunkIds().split(2)
        v = all_to_all([0, 1, 2], ids=v_ids)
        ag = all_gather([6, 7, 8], ids=ag_ids)
        alg = SynthesisEngine(topo).synthesize_joint([("a2a", v), ("ag", ag)])
        alg.validate()

    def test_ordering_longest_first(self):
        topo = ring(8)
        conds = all_to_all(list(range(8)))
        ordered = order_conditions(topo, conds)
        dists = [
            (next(iter(c.dests)) - c.src) % 8 for c in ordered
        ]  # unidirectional hop distance
        assert dists == sorted(dists, reverse=True)


class TestSynthesis:
    def test_ring_all_gather_optimal(self):
        # paper Fig 3a: unidirectional ring AG in exactly n-1 steps
        for n in (3, 4, 7):
            alg = SynthesisEngine(ring(n)).collective(CollectiveRequest(
                "all_gather", list(range(n))))
            alg.validate()
            assert alg.makespan == n - 1

    def test_all_gather_every_topology(self):
        for topo in (line(5), mesh2d(3, 4), torus2d(3, 3), hypercube(3)):
            group = topo.npus
            alg = SynthesisEngine(topo).collective(CollectiveRequest(
                "all_gather", group))
            alg.validate()

    def test_all_to_all_mesh(self):
        topo = mesh2d(4, 4)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(16))))
        alg.validate()
        # beats Direct baseline on the same topology (paper Fig 14)
        direct = direct_all_to_all(topo, list(range(16)))
        assert alg.makespan < direct.makespan

    def test_scatter_gather_broadcast(self):
        topo = mesh2d(3, 3)
        for conds in (
            scatter(list(range(9)), 4),
            gather(list(range(9)), 0),
            broadcast(list(range(9)), 8),
        ):
            alg = SynthesisEngine(topo).synthesize(conds)
            alg.validate()

    def test_process_group_uses_outside_links(self):
        # AG among 3 corner NPUs of a 3x3 mesh must route via others
        topo = mesh2d(3, 3)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", [0, 2, 8]))
        alg.validate()
        touched = {t.src for t in alg.transfers} | {t.dst for t in alg.transfers}
        assert touched - {0, 2, 8}, "expected out-of-group forwarding"

    def test_release_times_respected(self):
        topo = ring(4)
        conds = [Condition(0, 0, frozenset([1]), release=5.0)]
        alg = SynthesisEngine(topo).synthesize(conds)
        alg.validate()
        assert alg.transfers[0].start >= 5.0

    def test_joint_process_groups(self):
        # paper Fig 15: All-to-Allv (pg0) + All-Gather (pg1) on a 3x3 mesh
        topo = mesh2d(3, 3)
        ids = ChunkIds()
        v = all_to_allv([0, 1, 2], [[0, 2, 2], [1, 0, 1], [1, 1, 0]], ids=ids)
        ag = all_gather([6, 7, 8], ids=ids)
        alg = SynthesisEngine(topo).synthesize_joint([("pg0", v), ("pg1", ag)])
        alg.validate()

    def test_joint_duplicate_chunks_rejected(self):
        topo = mesh2d(2, 2)
        a = all_gather([0, 1])  # fresh ids starting at 0
        b = all_gather([2, 3])  # also starting at 0 -> collision
        with pytest.raises(ValueError):
            SynthesisEngine(topo).synthesize_joint([("a", a), ("b", b)])


class TestReductions:
    def test_reduce(self):
        topo = mesh2d(3, 3)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "reduce", list(range(9)), root=4))
        alg.validate()

    def test_reduce_scatter(self):
        for topo in (ring(4, bidirectional=True), mesh2d(3, 3), hypercube(3)):
            alg = SynthesisEngine(topo).collective(CollectiveRequest(
                "reduce_scatter", topo.npus))
            alg.validate()

    def test_all_reduce(self):
        topo = ring(8, bidirectional=True)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_reduce", list(range(8))))
        alg.validate()

    def test_all_reduce_pipelined_not_slower(self):
        topo = mesh2d(4, 4)
        base = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_reduce", list(range(16)), pipelined=False))
        pipe = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_reduce", list(range(16)), pipelined=True))
        base.validate()
        pipe.validate()
        assert pipe.makespan <= base.makespan

    def test_reduce_process_group(self):
        topo = mesh2d(3, 3)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "reduce_scatter", [0, 4, 8]))
        alg.validate()


class TestSwitches:
    def test_star_switch_all_gather(self):
        topo = star_switch(4)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", [0, 1, 2, 3]))
        alg.validate()

    def test_star_switch_no_multicast_serializes(self):
        topo = star_switch(4, multicast=False)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", [0, 1, 2, 3]))
        alg.validate()
        mc = star_switch(4, multicast=True)
        alg_mc = SynthesisEngine(mc).collective(CollectiveRequest(
            "all_gather", [0, 1, 2, 3]))
        alg_mc.validate()
        assert alg.makespan >= alg_mc.makespan

    def test_buffer_limit_respected(self):
        topo = star_switch(6, buffer_limit=1)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(6))))
        alg.validate()  # validator enforces the limit

    def test_two_level_switch_hetero(self):
        topo = two_level_switch(2, npus_per_node=4)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(8)), bytes=512.0))
        alg.validate()
        # intra-node chunks finish before cross-node ones on average
        intra = [t for t in alg.transfers if t.start == 0.0]
        assert intra


class TestHeterogeneous:
    def test_alpha_beta_timing(self):
        # paper Fig 9: two links of different alpha/beta
        topo = Topology("hetero2")
        topo.add_npus(3)
        topo.add_link(0, 1, alpha=2.0, beta=0.5)
        topo.add_link(1, 2, alpha=1.0, beta=2.0)
        alg = SynthesisEngine(topo).synthesize(
            [Condition(0, 0, frozenset([2]), bytes=4.0)])
        alg.validate()
        # 0->1: 2 + 4*0.5 = 4; 1->2: 1 + 4*2 = 9 => makespan 13
        assert alg.makespan == pytest.approx(13.0)

    def test_hetero_congestion_interval(self):
        # paper Fig 10: second chunk on the same link starts after the first's interval
        topo = Topology("one_link")
        topo.add_npus(2)
        topo.add_link(0, 1, alpha=1.0, beta=1.0)
        conds = [
            Condition(0, 0, frozenset([1]), bytes=2.0),
            Condition(1, 0, frozenset([1]), bytes=2.0),
        ]
        alg = SynthesisEngine(topo).synthesize(conds)
        alg.validate()
        spans = sorted((t.start, t.end) for t in alg.transfers)
        assert spans[0][1] <= spans[1][0] + 1e-9
        assert alg.makespan == pytest.approx(6.0)

    def test_fast_path_equals_slow_path(self):
        topo = mesh2d(3, 3)
        conds = all_to_all(list(range(9)))
        fast = SynthesisEngine(topo).synthesize(conds, mode="int")
        slow = SynthesisEngine(topo).synthesize(conds, mode="cont")
        fast.validate()
        slow.validate()
        assert fast.makespan == pytest.approx(slow.makespan)
