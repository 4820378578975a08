"""Property tests for multi-level hierarchical synthesis.

Two claims, over randomized nested fabrics (depth 1-3, uneven pod/rack
sizes, optional degenerate partitions):

1. **Never silently wrong.** A random nested partition spec either
   synthesizes a schedule that passes full validation, or raises
   :class:`HierarchyError` — in which case the engine's ``hierarchy="auto"``
   route falls back to flat synthesis, whose schedule also validates and
   fulfils the identical final conditions. There is no third outcome.
2. **Validation has teeth.** A single-transfer mutation of a synthesized
   schedule (corrupted duration, unknown chunk, dropped delivery, premature
   start) flips ``validate(mode="bulk")`` to invalid — the oracle the
   differential claims rest on is not vacuously accepting.

Cases are generated from a ``random.Random`` seed, so the same generator
serves two harnesses: hypothesis drives the seed space (with its database
and shrinking) when installed, and a fixed seed sweep runs otherwise — the
gate never silently skips.
"""

import random

import pytest

from repro.core import AlgorithmRegistry, CollectiveRequest, SynthesisEngine
from repro.core.algorithm import CollectiveAlgorithm, Transfer
from repro.core.conditions import Condition
from repro.core.hierarchy import HierarchyError
from repro.topology.topology import NodeType, Topology

try:
    import hypothesis.strategies as st
    from hypothesis import given, settings

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - CI installs hypothesis
    HAVE_HYPOTHESIS = False


def _gen_fabric(rng: random.Random):
    """A random nested fabric: leaf groups of 1-4 NPUs on a bidirectional
    ring, joined at each level by a switch the child gateways uplink to.
    Depth 1-3, uneven arities. Returns the partitioned topology."""
    depth = rng.randint(1, 3)

    def gen_spec(d):
        if d == 0:
            return rng.randint(1, 4)  # leaf: NPU count
        return [gen_spec(d - 1) for _ in range(rng.randint(1, 3))]

    spec = gen_spec(depth)
    if isinstance(spec, int):  # degenerate: a single flat leaf group
        spec = [spec]
        depth = 1

    topo = Topology("prop")

    def build(node_spec, path):
        """Build one subtree; returns (gateway npu id, member npu ids)."""
        if isinstance(node_spec, int):
            ids = topo.add_npus(node_spec)
            for n in ids:
                paths[n] = tuple(path)
            if node_spec == 2:
                topo.add_bidir_link(ids[0], ids[1])
            elif node_spec > 2:
                for i in range(node_spec):
                    topo.add_bidir_link(ids[i], ids[(i + 1) % node_spec])
            return ids[0], ids
        gws, members = [], []
        for i, child in enumerate(node_spec):
            g, m = build(child, path + [i])
            gws.append(g)
            members.extend(m)
        sw = topo.add_node(NodeType.SWITCH)
        paths[sw] = tuple(path) + (-1,) if path else (-1,)
        for g in gws:
            topo.add_bidir_link(g, sw)
        return gws[0], members

    paths: dict[int, tuple] = {}
    build(spec, [])
    # occasionally corrupt the partition to exercise the error/fallback
    # path: truncate a random NPU's path or mark it shared
    pod_of = [paths[n] for n in range(topo.num_nodes)]
    if rng.random() < 0.25 and len(topo.npus) > 2:
        victim = rng.choice(topo.npus)
        pod_of[victim] = (-1,) if rng.random() < 0.5 else \
            pod_of[victim][:max(1, len(pod_of[victim]) - 1)]
    try:
        topo.set_partition(pod_of)
    except ValueError:
        # corruption may break density — set_partition legally refuses;
        # degrade to the top level, or to no partition at all
        try:
            topo.set_partition([p[0] for p in pod_of])
        except ValueError:
            pass
    return topo


def check_synthesis_seed(seed: int) -> None:
    """Claim 1: valid schedule, or HierarchyError + validating fallback."""
    rng = random.Random(seed)
    topo = _gen_fabric(rng)
    group = topo.npus
    eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
    kind = rng.choice(["all_gather", "all_to_all", "reduce_scatter",
                       "all_reduce"])
    try:
        hier = getattr(eng.hierarchical(), kind)(group)
    except HierarchyError:
        hier = None  # the legal refusal: fall back flat below
    if hier is not None:
        hier.validate(mode="oracle")
    auto = eng.collective(CollectiveRequest(
        kind, group))  # auto route: hier or flat fallback
    auto.validate(mode="oracle")
    flat = eng.collective(
        CollectiveRequest(kind, group=tuple(group), hierarchy="never"))
    key = lambda a: sorted(
        (c.chunk, tuple(sorted(getattr(c, "srcs", [getattr(c, "src", -1)]))),
         tuple(sorted(c.dests)))
        for c in a.conditions)
    assert key(auto) == key(flat)
    if hier is not None:
        assert key(hier) == key(flat)


def _corrupt(alg: CollectiveAlgorithm, rng: random.Random):
    """One guaranteed-invalid single-transfer mutation, or None if this
    schedule offers no target for the drawn mutation kind."""
    ts = list(alg.transfers)
    if not ts:
        return None
    k = rng.randrange(len(ts))
    t = ts[k]
    kind = rng.choice(["duration", "unknown_chunk", "drop", "early"])
    if kind == "duration":
        ts[k] = Transfer(t.chunk, t.link, t.src, t.dst, t.start,
                         t.end + 0.5, t.reduce)
    elif kind == "unknown_chunk":
        bogus = max(c.chunk for c in alg.conditions) + 1
        ts[k] = Transfer(bogus, t.link, t.src, t.dst, t.start, t.end,
                         t.reduce)
    elif kind == "drop":
        # drop the sole delivery of some (chunk, dest) pair
        arrivals: dict[tuple[int, int], list[int]] = {}
        for i, x in enumerate(ts):
            arrivals.setdefault((x.chunk, x.dst), []).append(i)
        dest_of = {}
        for c in alg.conditions:
            for d in c.dests:
                dest_of.setdefault(c.chunk, set()).add(d)
        victims = [i for (ch, d), idx in arrivals.items()
                   if len(idx) == 1 and d in dest_of.get(ch, ())
                   for i in idx]
        if not victims:
            return None
        ts.pop(rng.choice(victims))
    else:  # early: an origin transfer starts before its chunk's release
        origins = [i for i, x in enumerate(ts)
                   if x.start <= min(r.start for r in ts
                                     if r.chunk == x.chunk)]
        i = rng.choice(origins)
        t = ts[i]
        ts[i] = Transfer(t.chunk, t.link, t.src, t.dst, t.start - 1.0,
                         t.end - 1.0, t.reduce)
        # shifting the earliest transfer of a release-0 chunk one step
        # earlier lands it before the release — always a violation
        rel = {c.chunk: c.release for c in alg.conditions}
        if ts[i].start >= rel[t.chunk]:
            return None
    return CollectiveAlgorithm(alg.topology, list(alg.conditions), ts,
                               name=alg.name)


def check_release_floor_seed(seed: int) -> None:
    """Claim 3: per-chunk release floors are only ever *raised* through
    phase composition. Whatever regime ``spanning()`` resolves, a
    condition's release survives every phase kind it crosses — intra
    resolution, the boundary inter phase, and the per-pod scatter — so no
    transfer of a chunk ever starts below the caller's floor."""
    rng = random.Random(seed)
    topo = _gen_fabric(rng)
    if topo.partition is None:
        return
    eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
    npus = topo.npus
    conds = []
    for ck in range(rng.randint(1, 6)):
        src = rng.choice(npus)
        others = [n for n in npus if n != src]
        if not others:
            return
        dests = rng.sample(others, rng.randint(1, min(4, len(others))))
        conds.append(Condition(ck, src, frozenset(dests),
                               release=float(rng.randint(0, 8))))
    try:
        alg = eng.hierarchical().spanning(conds)
    except HierarchyError:
        return  # legal refusal (single pod, missing gateways, ...)
    alg.validate(mode="oracle")
    rel = {c.chunk: c.release for c in conds}
    for t in alg.transfers:
        assert t.start >= rel[t.chunk], (
            f"chunk {t.chunk}: transfer at {t.start} starts below the "
            f"caller's release {rel[t.chunk]} — a phase lowered the floor")


def check_corruption_seed(seed: int) -> None:
    """Claim 2: a single-transfer mutation flips bulk validation."""
    rng = random.Random(seed)
    topo = _gen_fabric(rng)
    eng = SynthesisEngine(topo, registry=AlgorithmRegistry())
    kind = rng.choice(["all_gather", "all_to_all"])
    alg = eng.collective(CollectiveRequest(kind, topo.npus))
    alg.validate(mode="bulk")  # the uncorrupted schedule passes
    bad = _corrupt(alg, rng)
    if bad is None:
        return  # no target for the drawn mutation on this schedule
    with pytest.raises(AssertionError):
        bad.validate(mode="bulk")


if HAVE_HYPOTHESIS:

    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**32 - 1))
    def test_random_nested_partition_synthesis(seed):
        check_synthesis_seed(seed)

    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**32 - 1))
    def test_random_corruption_flips_bulk_validation(seed):
        check_corruption_seed(seed)

    @settings(max_examples=60, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=2**32 - 1))
    def test_random_release_floors_never_lowered(seed):
        check_release_floor_seed(seed)

else:  # seed-sweep fallback: same generator, fixed seeds

    @pytest.mark.parametrize("seed", range(0, 60))
    def test_random_nested_partition_synthesis(seed):
        check_synthesis_seed(seed)

    @pytest.mark.parametrize("seed", range(1000, 1060))
    def test_random_corruption_flips_bulk_validation(seed):
        check_corruption_seed(seed)

    @pytest.mark.parametrize("seed", range(2000, 2060))
    def test_random_release_floors_never_lowered(seed):
        check_release_floor_seed(seed)
