"""Differential tests: the batched event-frontier ``bfs_int`` must be
bit-identical to the reference per-timestep scan ``bfs_int_ref`` — same
pruned transfers, same arrivals, same reached times, same makespans — on
every topology class and on random pre-committed TEN states (the acceptance
gate for the array-backed synthesis core)."""

import pytest

from repro.core import CollectiveRequest, all_gather, all_to_all
from repro.core.conditions import ChunkIds, Condition
from repro.core.engine import SynthesisEngine
from repro.core.pathfinding import bfs_cont, bfs_int, bfs_int_ref
from repro.core.ten import TEN
from repro.topology import (
    hypercube,
    line,
    mesh2d,
    ring,
    star_switch,
    torus2d,
)
from repro.topology.topology import Topology


def assert_same(ra, rb, ctx=""):
    assert ra.transfers == rb.transfers, ctx
    assert ra.arrivals == rb.arrivals, ctx
    assert ra.reached == rb.reached, ctx


def run_differential(topo, conds):
    """Drive a full greedy synthesis, comparing both searches per condition
    on identical TEN states (commits follow the reference result)."""
    engine = SynthesisEngine(topo)
    ten_ref, ten_new = TEN(topo), TEN(topo)
    for c in engine.order_conditions(conds):
        ra = bfs_int_ref(ten_ref, c)
        rb = bfs_int(ten_new, c)
        assert_same(ra, rb, ctx=f"{topo.name}: {c}")
        engine._commit(ten_ref, ra, True)
        engine._commit(ten_new, rb, True)


TOPOLOGIES = [
    pytest.param(lambda: ring(6), id="ring6"),
    pytest.param(lambda: ring(5, bidirectional=True), id="ring5bidir"),
    pytest.param(lambda: line(5), id="line5"),
    pytest.param(lambda: mesh2d(3, 4), id="mesh3x4"),
    pytest.param(lambda: mesh2d(5, 5), id="mesh5x5"),
    pytest.param(lambda: torus2d(4, 4), id="torus4x4"),
    pytest.param(lambda: hypercube(3), id="hypercube3"),
    pytest.param(lambda: star_switch(5), id="star5"),
    pytest.param(lambda: star_switch(5, multicast=False), id="star5serial"),
    pytest.param(lambda: star_switch(6, buffer_limit=1), id="star6buf1"),
    pytest.param(
        lambda: star_switch(6, buffer_limit=2, multicast=False),
        id="star6buf2serial",
    ),
]


@pytest.mark.parametrize("make", TOPOLOGIES)
def test_all_to_all_differential(make):
    topo = make()
    run_differential(topo, all_to_all(topo.npus))


@pytest.mark.parametrize("make", TOPOLOGIES)
def test_all_gather_differential(make):
    topo = make()
    run_differential(topo, all_gather(topo.npus))


def test_process_group_differential():
    # conditions routed through out-of-group NPUs
    topo = mesh2d(3, 3)
    run_differential(topo, all_gather([0, 2, 8]))
    run_differential(topo, all_to_all([0, 4, 8]))


def test_release_times_differential():
    topo = mesh2d(3, 3)
    ids = ChunkIds()
    conds = [
        Condition(ids.next(), 0, frozenset([8]), release=3.0),
        Condition(ids.next(), 8, frozenset([0]), release=0.0),
        Condition(ids.next(), 4, frozenset([0, 8]), release=1.0),
    ]
    run_differential(topo, conds)


def test_synthesized_algorithms_identical():
    """Whole-pipeline check: identical transfer schedules and makespans."""
    import repro.core.engine as eng

    topo = mesh2d(4, 4)
    group = list(range(16))
    new_alg = SynthesisEngine(topo).collective(CollectiveRequest(
        "all_to_all", group))
    orig = eng.bfs_int
    eng.bfs_int = bfs_int_ref
    try:
        ref_alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", group))
    finally:
        eng.bfs_int = orig
    assert new_alg.transfers == ref_alg.transfers
    assert new_alg.makespan == ref_alg.makespan


def test_unreachable_raises_same():
    topo = Topology("disc")
    topo.add_npus(2)  # no links
    cond = Condition(0, 0, frozenset([1]))
    with pytest.raises(AssertionError, match="unreachable"):
        bfs_int_ref(TEN(topo), cond)
    with pytest.raises(AssertionError, match="unreachable"):
        bfs_int(TEN(topo), cond)


def test_continuous_still_matches_on_homogeneous():
    topo = mesh2d(3, 3)
    cond = Condition(0, 0, frozenset(range(9)))
    assert bfs_int(TEN(topo), cond).reached == bfs_cont(TEN(topo), cond).reached
