"""Property-based tests (hypothesis): every synthesized algorithm on every
random topology satisfies the full validation oracle — postconditions met,
congestion-free, causal, alpha-beta-timed, switch-legal."""

import pytest

hypothesis = pytest.importorskip("hypothesis")

import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core import (
    ChunkIds,
    CollectiveRequest,
    Condition,
    SynthesisEngine,
    all_gather,
    all_to_all,
)
from repro.topology.topology import NodeType, Topology

# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------

@st.composite
def connected_topologies(draw, max_npus=8, hetero=False, switches=False):
    """Random strongly-connected topology: a random ring backbone (guarantees
    strong connectivity) plus random extra links; optional hetero alpha/beta
    and switch nodes."""
    n = draw(st.integers(min_value=2, max_value=max_npus))
    topo = Topology("prop")
    topo.add_npus(n)
    perm = draw(st.permutations(list(range(n))))

    def ab():
        if not hetero:
            return 0.0, 1.0
        alpha = draw(st.sampled_from([0.0, 0.5, 1.0, 2.0]))
        beta = draw(st.sampled_from([0.25, 0.5, 1.0, 2.0]))
        return alpha, beta

    for i in range(n):
        a, b = ab()
        topo.add_link(perm[i], perm[(i + 1) % n], a, b)
    extra = draw(st.integers(min_value=0, max_value=2 * n))
    for _ in range(extra):
        u = draw(st.integers(min_value=0, max_value=n - 1))
        v = draw(st.integers(min_value=0, max_value=n - 1))
        if u != v and not any(l.dst == v for l in topo.out_links(u)):
            a, b = ab()
            topo.add_link(u, v, a, b)
    if switches:
        # hang a switch connecting a random subset bidirectionally
        sw = topo.add_node(
            NodeType.SWITCH,
            buffer_limit=draw(st.sampled_from([None, 1, 2, 4])),
            multicast=draw(st.booleans()),
        )
        members = draw(
            st.lists(st.integers(min_value=0, max_value=n - 1), min_size=2,
                     max_size=n, unique=True)
        )
        for m in members:
            a, b = ab()
            topo.add_bidir_link(m, sw, a, b)
    return topo


@st.composite
def groups_of(draw, topo):
    npus = topo.npus
    k = draw(st.integers(min_value=2, max_value=len(npus)))
    return draw(st.permutations(npus))[:k]


# ---------------------------------------------------------------------------
# properties
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_all_gather_valid_on_random_topology(data):
    topo = data.draw(connected_topologies())
    group = data.draw(groups_of(topo))
    alg = SynthesisEngine(topo).synthesize(all_gather(list(group)))
    alg.validate()


@settings(max_examples=40, deadline=None)
@given(data=st.data())
def test_all_to_all_valid_on_random_topology(data):
    topo = data.draw(connected_topologies(max_npus=6))
    group = data.draw(groups_of(topo))
    alg = SynthesisEngine(topo).synthesize(all_to_all(list(group)))
    alg.validate()


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_hetero_random_topology(data):
    topo = data.draw(connected_topologies(max_npus=6, hetero=True))
    group = data.draw(groups_of(topo))
    bytes_ = data.draw(st.sampled_from([0.5, 1.0, 3.0]))
    alg = SynthesisEngine(topo).synthesize(
        all_gather(list(group), bytes=bytes_))
    alg.validate()


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_switch_random_topology(data):
    topo = data.draw(connected_topologies(max_npus=6, switches=True))
    group = data.draw(groups_of(topo))
    alg = SynthesisEngine(topo).synthesize(all_gather(list(group)))
    alg.validate()


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_reductions_random_topology(data):
    topo = data.draw(connected_topologies(max_npus=6))
    group = data.draw(groups_of(topo))
    rs = SynthesisEngine(topo).collective(CollectiveRequest(
        "reduce_scatter", list(group)))
    rs.validate()
    ar = SynthesisEngine(topo).collective(CollectiveRequest(
        "all_reduce", list(group), pipelined=data.draw(st.booleans())))
    ar.validate()


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_joint_groups_random(data):
    topo = data.draw(connected_topologies(max_npus=8))
    npus = list(topo.npus)
    if len(npus) < 4:
        return
    half = len(npus) // 2
    ids = ChunkIds()
    g1, g2 = npus[:half], npus[half:]
    alg = SynthesisEngine(topo).synthesize_joint(
        [("g1", all_gather(g1, ids=ids)), ("g2", all_to_all(g2, ids=ids))])
    alg.validate()


@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_arbitrary_conditions_random(data):
    """Custom collectives: arbitrary pre/postconditions (paper abstract)."""
    topo = data.draw(connected_topologies(max_npus=7))
    npus = list(topo.npus)
    ids = ChunkIds()
    n_conds = data.draw(st.integers(min_value=1, max_value=6))
    conds = []
    for _ in range(n_conds):
        src = data.draw(st.sampled_from(npus))
        dests = data.draw(
            st.lists(st.sampled_from(npus), min_size=1, max_size=len(npus),
                     unique=True)
        )
        conds.append(Condition(ids.next(), src, frozenset(dests)))
    alg = SynthesisEngine(topo).synthesize(conds)
    alg.validate()
    # postcondition double-check outside the oracle
    for c in conds:
        reached = {c.src} | {t.dst for t in alg.transfers if t.chunk == c.chunk}
        assert c.dests <= reached
