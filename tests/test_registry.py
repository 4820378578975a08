"""Tests for the SynthesisEngine / AlgorithmRegistry stack: fingerprinting,
automorphism canonicalization, cache-hit relabeling, disk persistence, the
comms plan cache, and the launch-layer mesh planner."""

import numpy as np
import pytest

from repro.core import (
    AlgorithmRegistry,
    ChunkIds,
    CollectiveRequest,
    SynthesisEngine,
    all_gather,
    all_to_all,
    canonicalize_group,
    enumerate_automorphisms,
    from_msccl_json,
    is_automorphism,
    to_msccl_json,
    topology_fingerprint,
)
from repro.core import engine as engine_mod
from repro.core.registry import invert_permutation, relabel_algorithm
from repro.topology import hypercube, mesh2d, ring, torus2d


def torus_rows(rows, cols):
    return [[r * cols + c for c in range(cols)] for r in range(rows)]


def _rewrite_npz(path, mutate):
    """Load an npz entry, apply ``mutate(arrays)``, write it back."""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    mutate(arrays)
    with open(path, "wb") as f:
        np.savez(f, **arrays)


def _set(key, value):
    def mutate(arrays):
        arrays[key] = value
    return mutate


# name -> in-place corruption of an on-disk .npz plan entry
NPZ_CORRUPTIONS = {
    "garbage": lambda p: p.write_bytes(b"this is not a zip archive"),
    "empty": lambda p: p.write_bytes(b""),
    "truncated": lambda p: p.write_bytes(p.read_bytes()[:73]),
    "wrong-dtype": lambda p: _rewrite_npz(
        p, lambda a: a.update(t_start=a["t_start"].astype(np.float32))),
    "wrong-length": lambda p: _rewrite_npz(
        p, lambda a: a.update(t_link=a["t_link"][:-1])),
    "missing-column": lambda p: _rewrite_npz(
        p, lambda a: a.pop("t_chunk")),
    "bad-schema": lambda p: _rewrite_npz(
        p, _set("schema", np.array([999], np.int64))),
    "foreign-fingerprint": lambda p: _rewrite_npz(
        p, _set("fingerprint", np.array(["deadbeef"]))),
    "bad-indptr": lambda p: _rewrite_npz(
        p, lambda a: a.update(
            c_dests_indptr=a["c_dests_indptr"][::-1].copy())),
}


class TestAutomorphisms:
    def test_generators_verify(self):
        for topo in (ring(5), torus2d(3, 4), mesh2d(3, 3), hypercube(3)):
            assert topo.automorphism_generators
            for g in topo.automorphism_generators:
                assert is_automorphism(topo, g), topo.name

    def test_bogus_permutation_rejected(self):
        topo = torus2d(3, 3)
        assert not is_automorphism(topo, list(range(8)))  # wrong length
        perm = list(range(9))
        perm[0], perm[4] = perm[4], perm[0]  # not a torus symmetry? it is!
        # a single transposition of non-equivalent positions on a mesh2d:
        mesh = mesh2d(2, 3)
        p = list(range(6))
        p[0], p[1] = p[1], p[0]  # corner <-> edge-center: degree mismatch
        assert not is_automorphism(mesh, p)

    def test_closure_size_torus(self):
        topo = torus2d(4, 4)
        autos = enumerate_automorphisms(topo)
        assert len(autos) == 16  # 4 row-shifts x 4 col-shifts

    def test_rows_share_canonical_form(self):
        topo = torus2d(4, 4)
        canons = {canonicalize_group(topo, row)[0]
                  for row in torus_rows(4, 4)}
        assert len(canons) == 1
        canon, perm = canonicalize_group(topo, torus_rows(4, 4)[2])
        assert canon == (0, 1, 2, 3)
        assert is_automorphism(topo, perm)

    def test_fingerprint_name_independent(self):
        a, b = torus2d(3, 3), torus2d(3, 3)
        b.name = "renamed"
        assert topology_fingerprint(a) == topology_fingerprint(b)
        assert topology_fingerprint(a) != topology_fingerprint(torus2d(3, 4))


class TestRegistry:
    def test_isomorphic_rows_hit_without_bfs(self, monkeypatch):
        """Acceptance: the second (isomorphic) lookup performs no BFS and the
        relabeled algorithm validates with the cold makespan."""
        topo = torus2d(4, 4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        rows = torus_rows(4, 4)

        cold = eng.collective(CollectiveRequest("all_gather", rows[0]))
        cold.validate()
        assert reg.stats.misses == 1

        def boom(*a, **k):  # any BFS call on the hit path is a bug
            raise AssertionError("BFS ran on a registry hit")

        monkeypatch.setattr(engine_mod, "bfs_int", boom)
        monkeypatch.setattr(engine_mod, "bfs_cont", boom)
        for row in rows[1:]:
            alg = eng.collective(CollectiveRequest("all_gather", row))
            alg.validate()
            assert alg.makespan == cold.makespan
            # delivered to the requested group, not the canonical one
            for c in alg.conditions:
                assert c.dests == frozenset(row)
        assert reg.stats.hits == 3
        assert reg.stats.misses == 1

    def test_distinct_shapes_do_not_alias(self):
        topo = torus2d(4, 4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        eng.collective(CollectiveRequest("all_gather", torus_rows(4, 4)[0]))
        eng.collective(CollectiveRequest(
            "all_gather", group=tuple(torus_rows(4, 4)[0]),
            bytes=2.0))  # different params
        eng.collective(CollectiveRequest(
            "all_to_all", torus_rows(4, 4)[0]))  # different kind
        eng.collective(CollectiveRequest(
            "all_gather", [0, 5, 10, 15]))  # diagonal: different canonical group
        assert reg.stats.misses == 4

    def test_reductions_and_allreduce_cached(self):
        topo = torus2d(4, 4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        rows = torus_rows(4, 4)
        cold_rs = eng.collective(CollectiveRequest("reduce_scatter", rows[0]))
        cold_ar = eng.collective(CollectiveRequest(
            "all_reduce", group=tuple(rows[0]), pipelined=True))
        hit_rs = eng.collective(CollectiveRequest("reduce_scatter", rows[3]))
        hit_ar = eng.collective(CollectiveRequest(
            "all_reduce", group=tuple(rows[3]), pipelined=True))
        for alg in (cold_rs, cold_ar, hit_rs, hit_ar):
            alg.validate()
        assert hit_rs.makespan == cold_rs.makespan
        assert hit_ar.makespan == cold_ar.makespan
        assert reg.stats.misses == 2 and reg.stats.hits == 2

    def test_chunk_ids_follow_caller_allocator(self):
        topo = torus2d(4, 4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        ids = ChunkIds(100)
        alg = eng.collective(CollectiveRequest(
            "all_gather", torus_rows(4, 4)[1]), ids=ids)
        assert sorted(c.chunk for c in alg.conditions) == list(range(100, 104))
        alg.validate()

    def test_lru_eviction(self):
        topo = torus2d(4, 4)
        reg = AlgorithmRegistry(max_entries=1)
        eng = SynthesisEngine(topo, registry=reg)
        eng.collective(CollectiveRequest("all_gather", torus_rows(4, 4)[0]))
        eng.collective(CollectiveRequest(
            "all_to_all", torus_rows(4, 4)[0]))  # evicts the all_gather
        eng.collective(CollectiveRequest(
            "all_gather", torus_rows(4, 4)[0]))  # re-synthesizes
        assert reg.stats.misses == 3
        assert reg.stats.evictions == 2

    def test_disk_persistence_roundtrip(self, tmp_path):
        topo = torus2d(4, 4)
        rows = torus_rows(4, 4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg1 = SynthesisEngine(topo, registry=reg1).collective(CollectiveRequest(
            "all_gather", rows[0]))
        assert list(tmp_path.glob("*.npz"))
        assert reg1.stats.bytes_stored > 0
        # fresh registry, same dir: served from disk, no synthesis
        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg2 = SynthesisEngine(topo, registry=reg2).collective(CollectiveRequest(
            "all_gather", rows[1]))
        alg2.validate()
        assert reg2.stats.disk_hits == 1 and reg2.stats.misses == 0
        assert reg2.stats.bytes_loaded > 0
        assert alg2.makespan == alg1.makespan

    def test_disk_roundtrip_is_exact(self, tmp_path):
        """Disk-served plans are transfer-for-transfer identical to the
        plan that was stored (fields, order, phase spans)."""
        topo = torus2d(4, 4)
        rows = torus_rows(4, 4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg1 = SynthesisEngine(topo, registry=reg1).collective(CollectiveRequest(
            "all_gather", rows[0]))
        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg2 = SynthesisEngine(topo, registry=reg2).collective(CollectiveRequest(
            "all_gather", rows[0]))
        assert list(alg2.transfers) == list(alg1.transfers)
        assert alg2.conditions == alg1.conditions
        assert alg2.phase_spans == alg1.phase_spans

    @pytest.mark.parametrize("corrupt", list(NPZ_CORRUPTIONS),
                             ids=list(NPZ_CORRUPTIONS))
    def test_corrupt_disk_entry_resynthesized(self, tmp_path, corrupt):
        """A corrupt/truncated/wrong-dtype/wrong-shape on-disk plan must be
        skipped (and replaced), never raise out of get_or_synthesize."""
        topo = torus2d(4, 4)
        rows = torus_rows(4, 4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        SynthesisEngine(topo, registry=reg1).collective(CollectiveRequest(
            "all_gather", rows[0]))
        (entry,) = tmp_path.glob("*.npz")
        NPZ_CORRUPTIONS[corrupt](entry)

        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg = SynthesisEngine(topo, registry=reg2).collective(CollectiveRequest(
            "all_gather", rows[0]))
        alg.validate()
        assert reg2.stats.disk_hits == 0 and reg2.stats.misses == 1
        # the bad entry was replaced by the fresh plan
        reg3 = AlgorithmRegistry(cache_dir=str(tmp_path))
        SynthesisEngine(topo, registry=reg3).collective(CollectiveRequest(
            "all_gather", rows[0]))
        assert reg3.stats.disk_hits == 1

    def test_truncated_disk_entry_resynthesized(self, tmp_path):
        """Half-written file from a killed process: same contract."""
        topo = torus2d(4, 4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        SynthesisEngine(topo, registry=reg1).collective(CollectiveRequest(
            "all_gather", torus_rows(4, 4)[0]))
        (entry,) = tmp_path.glob("*.npz")
        entry.write_bytes(entry.read_bytes()[: len(entry.read_bytes()) // 2])
        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg = SynthesisEngine(topo, registry=reg2).collective(CollectiveRequest(
            "all_gather", torus_rows(4, 4)[1]))
        alg.validate()
        assert reg2.stats.misses == 1

    def test_legacy_json_entry_migrated_to_npz(self, tmp_path):
        """Pre-npz .json entries still load, and are migrated in place."""
        topo = torus2d(4, 4)
        rows = torus_rows(4, 4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        # rows[0] is its own canonical form, so the returned algorithm is
        # exactly what a legacy registry would have serialized
        alg = SynthesisEngine(topo, registry=reg1).collective(CollectiveRequest(
            "all_gather", rows[0]))
        (npz,) = tmp_path.glob("*.npz")
        npz.with_suffix(".json").write_text(to_msccl_json(alg),
                                            encoding="utf-8")
        npz.unlink()

        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg2 = SynthesisEngine(topo, registry=reg2).collective(CollectiveRequest(
            "all_gather", rows[1]))
        alg2.validate()
        assert reg2.stats.disk_hits == 1 and reg2.stats.misses == 0
        assert alg2.makespan == alg.makespan
        # one-way migration: npz rewritten, json retired
        assert list(tmp_path.glob("*.npz"))
        assert not list(tmp_path.glob("*.json"))
        # and the migrated entry serves the next registry
        reg3 = AlgorithmRegistry(cache_dir=str(tmp_path))
        SynthesisEngine(topo, registry=reg3).collective(CollectiveRequest(
            "all_gather", rows[0]))
        assert reg3.stats.disk_hits == 1 and reg3.stats.misses == 0

    def test_corrupt_legacy_json_dropped(self, tmp_path):
        """A broken legacy .json entry is removed and resynthesized."""
        topo = torus2d(4, 4)
        rows = torus_rows(4, 4)
        reg1 = AlgorithmRegistry(cache_dir=str(tmp_path))
        SynthesisEngine(topo, registry=reg1).collective(CollectiveRequest(
            "all_gather", rows[0]))
        (npz,) = tmp_path.glob("*.npz")
        npz.with_suffix(".json").write_text("{ not json", encoding="utf-8")
        npz.unlink()

        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        alg = SynthesisEngine(topo, registry=reg2).collective(CollectiveRequest(
            "all_gather", rows[0]))
        alg.validate()
        assert reg2.stats.misses == 1
        assert not list(tmp_path.glob("*.json"))

    def test_relabel_preserves_validity_on_reduce(self):
        topo = torus2d(4, 4)
        eng = SynthesisEngine(topo)
        alg = eng.collective(CollectiveRequest(
            "reduce_scatter", torus_rows(4, 4)[0]))
        shift = topo.automorphism_generators[0]  # row translation
        relabeled = relabel_algorithm(alg, shift)
        relabeled.validate()
        assert relabeled.makespan == alg.makespan
        back = relabel_algorithm(relabeled, invert_permutation(shift))
        back.validate()
        assert [t.link for t in back.transfers] == [t.link for t in alg.transfers]


class TestTranslateRoundtrip:
    def test_msccl_json_roundtrip(self):
        topo = torus2d(3, 3)
        eng = SynthesisEngine(topo)
        for alg in (eng.collective(CollectiveRequest(
            "all_gather", list(range(9)))),
                    eng.collective(CollectiveRequest(
                        "all_reduce", list(range(9))))):
            rt = from_msccl_json(to_msccl_json(alg), topo)
            rt.validate()
            assert rt.makespan == alg.makespan
            assert rt.num_transfers == alg.num_transfers

    def test_roundtrip_rejects_missing_conditions(self):
        topo = ring(4)
        with pytest.raises(ValueError):
            from_msccl_json('{"gpus": []}', topo)


class TestJointSynthesis:
    def test_duplicate_chunk_rejection(self):
        topo = mesh2d(2, 2)
        with pytest.raises(ValueError, match="duplicate chunk"):
            SynthesisEngine(topo).synthesize_joint(
                [("a", all_gather([0, 1])), ("b", all_gather([2, 3]))])

    def test_multi_group_congestion_freedom(self):
        """Two process groups synthesized jointly never overlap on a link —
        checked explicitly here, beyond the validator."""
        topo = torus2d(4, 4)
        ids = ChunkIds()
        g1 = [0, 1, 2, 3]
        g2 = [12, 13, 14, 15]
        alg = SynthesisEngine(topo).synthesize_joint(
            [("pg0", all_gather(g1, ids=ids)), ("pg1", all_to_all(g2, ids=ids))])
        alg.validate()
        by_link: dict = {}
        for t in alg.transfers:
            for other in by_link.setdefault(t.link, []):
                assert not t.overlaps(other), f"congestion: {t} vs {other}"
            by_link[t.link].append(t)
        # both groups' postconditions satisfied
        tags = {c.tag for c in alg.conditions}
        assert tags == {"pg0", "pg1"}

    def test_registry_algorithms_compose_into_joint(self):
        """Registry-returned chunk numbering composes with a shared ChunkIds
        allocator (renumber_chunks path)."""
        topo = torus2d(4, 4)
        reg = AlgorithmRegistry()
        eng = SynthesisEngine(topo, registry=reg)
        ids = ChunkIds()
        a = eng.collective(CollectiveRequest(
            "all_gather", [0, 1, 2, 3]), ids=ids)
        b = eng.collective(CollectiveRequest(
            "all_gather", [8, 9, 10, 11]), ids=ids)  # registry hit, remapped
        chunks = [c.chunk for c in a.conditions] + [c.chunk for c in b.conditions]
        assert len(set(chunks)) == 8
        assert reg.stats.hits == 1


class TestCommsPlanCache:
    def test_plan_cache_hit_on_repeat(self):
        from repro.comms.executor import clear_plan_cache, plan_buffers_cached
        from repro.core import to_ppermute_program
        from repro.tracing import counters

        clear_plan_cache()
        topo = ring(4, bidirectional=True)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(4))))
        prog = to_ppermute_program(alg)
        before = counters()
        p1 = plan_buffers_cached(prog, "fp-1")
        p2 = plan_buffers_cached(prog, "fp-1")
        after = counters()
        assert p1 is p2
        assert [after.get(k, 0) - before.get(k, 0)
                for k in ("plan_cache.hit", "plan_cache.miss")] == [1, 1]
        clear_plan_cache()

    def test_synthesize_program_reuses_plan(self):
        from repro.comms.primitives import _PROGRAM_CACHE, synthesize_program
        from repro.tracing import counters

        topo = ring(4, bidirectional=True)
        spec = CollectiveRequest("all_gather", (0, 1, 2, 3))
        reg = AlgorithmRegistry()
        prog1, plan1 = synthesize_program(topo, spec, registry=reg)
        before = counters()
        # repeated identical collective: plan served from the executor cache
        prog2, plan2 = synthesize_program(topo, spec, registry=reg)
        assert plan2 is plan1 and prog2 is prog1
        after = counters()
        assert (after["plan_cache.hit"]
                == before.get("plan_cache.hit", 0) + 1)
        assert (after["program_cache.hit"]
                == before.get("program_cache.hit", 0) + 1)
        # even after the program cache is dropped, the plan survives
        _PROGRAM_CACHE.clear()
        _, plan3 = synthesize_program(topo, spec, registry=reg)
        assert plan3 is plan1
        # and the re-translation got its algorithm from the registry, no BFS
        assert reg.stats.hits >= 1


class TestCacheHygiene:
    def test_topology_mutation_invalidates_memoized_state(self):
        topo = ring(4)
        fp1 = topology_fingerprint(topo)
        autos1 = enumerate_automorphisms(topo)
        assert len(autos1) == 4
        topo.add_link(0, 2)  # chord: breaks the ring symmetry
        fp2 = topology_fingerprint(topo)
        assert fp2 != fp1
        # rotations are no longer automorphisms of the chorded graph
        assert len(enumerate_automorphisms(topo)) == 1

    def test_engines_are_collected_with_their_topology(self):
        import gc
        import weakref

        from repro.comms.primitives import synthesize_program

        topo = ring(4, bidirectional=True)
        reg = AlgorithmRegistry()
        synthesize_program(topo, CollectiveRequest("all_gather", (0, 1, 2, 3)),
                           registry=reg)
        ref = weakref.ref(topo)
        del topo
        gc.collect()
        assert ref() is None, "engine cache kept the topology alive"


class TestMeshPlanner:
    def test_axis_groups_and_amortization(self):
        from repro.launch.sharding import MeshCollectivePlanner

        topo = torus2d(4, 4)
        reg = AlgorithmRegistry()
        pl = MeshCollectivePlanner(topo, {"data": 4, "model": 4}, registry=reg)
        assert pl.axis_groups("model")[0] == [0, 1, 2, 3]
        assert pl.axis_groups("data")[0] == [0, 4, 8, 12]
        stats = pl.warm(("all_gather",))
        # 2 axes x 4 groups = 8 lookups, 2 cold syntheses
        assert stats["misses"] == 2
        assert stats["hits"] == 6
        alg = pl.algorithm("all_gather", "data", 2)
        alg.validate()

    def test_size_mismatch_rejected(self):
        from repro.launch.sharding import MeshCollectivePlanner

        with pytest.raises(ValueError):
            MeshCollectivePlanner(torus2d(4, 4), {"data": 4, "model": 8})

    def test_joint_synthesis_split_allocators(self):
        from repro.launch.sharding import MeshCollectivePlanner

        pl = MeshCollectivePlanner(torus2d(4, 4), {"data": 4, "model": 4})
        # two model-axis rows run different collectives over one shared TEN;
        # chunk ids come from one ChunkIds.split() family (no collisions)
        alg = pl.joint([("all_gather", "model", 0),
                        ("all_to_all", "model", 2)])
        alg.validate()
        chunks = [c.chunk for c in alg.conditions]
        assert len(set(chunks)) == len(chunks)

    def test_joint_rejects_reductions(self):
        from repro.launch.sharding import MeshCollectivePlanner

        pl = MeshCollectivePlanner(torus2d(4, 4), {"data": 4, "model": 4})
        with pytest.raises(ValueError):
            pl.joint([("all_reduce", "model", 0)])
