"""The CollectiveRequest API and the unified PCCLError surface.

Three contracts:

1. **Validation.** A :class:`CollectiveRequest` is a frozen value object
   that rejects malformed descriptions at construction (unknown kind,
   non-positive bytes, root on a non-reduce, ...), so every downstream
   layer can trust a request it receives.
2. **One plan per description.** Every way of naming a collective (a
   request to the engine, a kind name or a request to
   ``MeshCollectivePlanner``) reaches the same registry entry, program and
   buffer plan, and the registry's params tuples stay pinned so on-disk
   cache entries keep serving.
3. **Error surface.** Every domain error derives from :class:`PCCLError`,
   and the silent flat-fallback rules hold: ``HierarchyError`` is advisory
   (the auto route may fall back flat), ``SketchInfeasibleError`` and
   ``FabricDegradedError`` are hard (no fallback may swallow them).
"""

import numpy as np
import pytest

from repro.comms.primitives import synthesize_program
from repro.core import (
    AlgorithmRegistry,
    ChunkIds,
    CollectiveRequest,
    FabricDegradedError,
    HierarchyError,
    PCCLError,
    SketchInfeasibleError,
    SynthesisEngine,
)
from repro.core import conditions as cnd
from repro.core.engine import time_reversed
from repro.core.translate import to_ppermute_program
from repro.topology import mesh2d, multi_pod, ring, torus2d


def _same_schedule(a, b) -> bool:
    ca, cb = a.columns, b.columns
    return all(
        np.array_equal(getattr(ca, f), getattr(cb, f))
        for f in ("chunk", "link", "src", "dst", "start", "end", "reduce"))


class TestRequestValidation:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="kind"):
            CollectiveRequest("all_gatherr", group=(0, 1))

    def test_nonpositive_bytes_rejected(self):
        with pytest.raises(ValueError, match="bytes"):
            CollectiveRequest("all_gather", group=(0, 1), bytes=0.0)

    def test_chunks_below_one_rejected(self):
        with pytest.raises(ValueError, match="chunks"):
            CollectiveRequest("all_gather", group=(0, 1), chunks=0)

    def test_bad_hierarchy_rejected(self):
        with pytest.raises(ValueError, match="hierarchy"):
            CollectiveRequest("all_gather", group=(0, 1), hierarchy="maybe")

    def test_reduce_requires_root_in_group(self):
        with pytest.raises(ValueError, match="root"):
            CollectiveRequest("reduce", group=(0, 1))
        with pytest.raises(ValueError, match="root"):
            CollectiveRequest("reduce", group=(0, 1), root=7)
        req = CollectiveRequest("reduce", group=(0, 1), root=1)
        assert req.root == 1

    def test_root_on_non_reduce_rejected(self):
        with pytest.raises(ValueError, match="root"):
            CollectiveRequest("all_gather", group=(0, 1), root=0)

    def test_pipelined_only_for_all_reduce(self):
        with pytest.raises(ValueError, match="pipelined"):
            CollectiveRequest("all_gather", group=(0, 1), pipelined=True)
        CollectiveRequest("all_reduce", group=(0, 1), pipelined=True)

    def test_sketch_must_quack(self):
        with pytest.raises(TypeError, match="sketch"):
            CollectiveRequest("all_gather", group=(0, 1), sketch=object())

    def test_frozen_and_group_normalized(self):
        req = CollectiveRequest("all_gather",
                                group=np.asarray([2, 0, 1], np.int64))
        assert req.group == (2, 0, 1)
        assert all(type(n) is int for n in req.group)
        with pytest.raises(AttributeError):
            req.bytes = 2.0

    def test_with_group_binds_without_mutation(self):
        base = CollectiveRequest("all_gather", bytes=2.0)
        bound = base.with_group([3, 4, 5])
        assert base.group == () and bound.group == (3, 4, 5)
        assert bound.bytes == 2.0

    def test_fingerprint_identity(self):
        a = CollectiveRequest("all_gather", group=(0, 1), bytes=2.0)
        b = CollectiveRequest("all_gather", group=(0, 1), bytes=2.0)
        c = CollectiveRequest("all_gather", group=(0, 1), bytes=3.0)
        assert a.fingerprint() == b.fingerprint()
        assert a.fingerprint() != c.fingerprint()
        assert a.fingerprint() != a.with_group((0, 2)).fingerprint()


class TestLegacyShimEquivalence:
    """The request forms that replaced the named shims and module wrappers
    ask for the plans those gave."""

    def test_reduce_request_carries_root(self):
        topo = torus2d(4, 4)
        got = SynthesisEngine(topo).collective(CollectiveRequest(
            "reduce", group=tuple(range(4)), root=2))
        got.validate()
        # the reduce synthesized by hand: a broadcast from the root on the
        # reversed fabric, time-reversed onto the forward one
        rconds = cnd.reduce(list(range(4)), 2, ids=ChunkIds(0))
        ref = SynthesisEngine(topo)
        bcast = ref.synthesize(cnd.gather_view(rconds, tag="rev_bcast"),
                               name="pccl_reduce",
                               topology=ref.reversed_topology())
        want = time_reversed(topo, bcast, rconds)
        assert _same_schedule(got, want)
        assert {d for c in got.conditions for d in c.dests} == {2}

    @pytest.mark.parametrize("req, route, params", [
        (CollectiveRequest("all_gather", (0, 1, 2, 3), bytes=2.0, chunks=2),
         (False, False, None), (2.0, 2, (False, False, None))),
        (CollectiveRequest("all_to_all", (0, 1, 2, 3), chunks=3),
         (False, False, None), (1.0, 3, (False, False, None))),
        (CollectiveRequest("reduce_scatter", (4, 5, 6, 7), bytes=0.5),
         (True, False, "pf", "te", None),
         (0.5, 1, (True, False, "pf", "te", None))),
        (CollectiveRequest("all_reduce", (0, 1, 2, 3), bytes=6.25,
                           pipelined=True),
         (False, False, None), (6.25, True, (False, False, None))),
        (CollectiveRequest("reduce", (3, 1, 2), root=2, bytes=4.0),
         None, (4.0, 2)),
    ], ids=["all_gather", "all_to_all", "reduce_scatter", "all_reduce",
            "reduce"])
    def test_registry_params_are_pinned(self, req, route, params):
        """The registry keys (and the on-disk cache entries written under
        them) hold these tuples; a change here orphans every cached plan."""
        assert req.registry_params(route) == params

    def test_empty_group_request_rejected_at_synthesis(self):
        eng = SynthesisEngine(torus2d(4, 4), registry=AlgorithmRegistry())
        with pytest.raises(ValueError, match="empty group"):
            eng.collective(CollectiveRequest("all_gather"))


class TestPlannerRequestPath:
    @pytest.fixture(scope="class")
    def planner(self):
        from repro.launch.sharding import MeshCollectivePlanner

        topo = multi_pod(2, 2, 4, unit_links=True, dci_ports_per_pod=4)
        return MeshCollectivePlanner(topo, {"data": 4, "model": 4},
                                     registry=AlgorithmRegistry())

    def test_request_and_legacy_agree(self, planner):
        """The string form of ``algorithm`` is the request form at
        ``nbytes`` with every other field at its default."""
        via_name = planner.algorithm("all_gather", "model", 0, nbytes=2.0)
        via_req = planner.algorithm(
            CollectiveRequest("all_gather", bytes=2.0), "model", 0)
        assert _same_schedule(via_name, via_req)

    def test_request_with_tuning_kwargs_rejected(self, planner):
        with pytest.raises(TypeError, match="hierarchy"):
            planner.algorithm(CollectiveRequest("all_gather"), "model", 0,
                              hierarchy="never")
        with pytest.raises(TypeError, match="chunks_per_npu"):
            planner.algorithm("all_gather", "model", 0, chunks_per_npu=2)


class TestProgramRequestPath:
    """``MeshCollectivePlanner.program`` and ``synthesize_program`` on a
    request are one way in: the same program and the same cached plan."""

    @pytest.mark.parametrize("kind, nbytes", [
        ("all_gather", 1.4140625),
        ("all_to_all", 0.5),
        ("reduce_scatter", 2.0),
        ("all_reduce", 6.25),
    ])
    def test_planner_program_is_the_request_program(self, kind, nbytes):
        from repro.launch.sharding import MeshCollectivePlanner

        planner = MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                        registry=AlgorithmRegistry())
        prog, plan = planner.program(kind, "x", 0, nbytes=nbytes)
        req = CollectiveRequest(kind, (0, 1, 2, 3), bytes=nbytes,
                                pipelined=kind == "all_reduce")
        prog2, plan2 = synthesize_program(planner.topo, req,
                                          registry=planner.registry)
        # one cache entry, not two equal ones
        assert prog2 is prog and plan2 is plan
        fresh = SynthesisEngine(mesh2d(2, 2)).collective(req)
        assert to_ppermute_program(fresh).digest() == prog.digest()

    @pytest.mark.parametrize("bad, err, match", [
        (CollectiveRequest("reduce", (0, 1, 2, 3), root=0), ValueError,
         "not executable"),
        (CollectiveRequest("all_gather"), ValueError, "explicit group"),
        (("all_gather", (0, 1, 2, 3)), TypeError, "CollectiveRequest"),
    ], ids=["reduce", "empty_group", "not_a_request"])
    def test_synthesize_program_rejects(self, bad, err, match):
        with pytest.raises(err, match=match):
            synthesize_program(mesh2d(2, 2), bad,
                               registry=AlgorithmRegistry())


class TestErrorSurface:
    def test_hierarchy_of_domain_errors(self):
        assert issubclass(HierarchyError, PCCLError)
        assert issubclass(SketchInfeasibleError, PCCLError)
        assert issubclass(FabricDegradedError, PCCLError)
        # the load-bearing distinction: a sketch violation must never ride
        # the HierarchyError flat-fallback path
        assert not issubclass(SketchInfeasibleError, HierarchyError)
        assert not issubclass(FabricDegradedError, HierarchyError)
        # catchable with stdlib idioms at serving boundaries
        assert issubclass(FabricDegradedError, RuntimeError)
        assert issubclass(HierarchyError, ValueError)

    def test_auto_route_may_swallow_hierarchy_error(self):
        # ring has no partition: the hierarchical route refuses, auto
        # falls back flat — the advisory end of the contract
        eng = SynthesisEngine(ring(4), registry=AlgorithmRegistry())
        alg = eng.collective(CollectiveRequest(
            "all_gather", group=tuple(range(4))))
        alg.validate()
        assert alg.name == "pccl_all_gather"

    def test_pinned_route_raises_catchable_as_pccl_error(self):
        eng = SynthesisEngine(ring(4), registry=AlgorithmRegistry())
        with pytest.raises(PCCLError):
            eng.collective(CollectiveRequest(
                "all_gather", group=tuple(range(4)), hierarchy="always"))
