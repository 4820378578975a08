"""Tests for the multi-tenant plan service: planner memoization, warm()
prefetch accounting, eviction/bytes metrics, and cross-process safety of a
shared ``PCCL_CACHE_DIR`` under concurrent readers + a churning writer."""

import subprocess
import sys

import pytest

from repro.core import (AlgorithmRegistry, CollectiveRequest, PlanService,
                        SynthesisEngine)
from repro.topology import torus2d

AXES = {"data": 4, "model": 4}


def torus_rows(rows, cols):
    return [[r * cols + c for c in range(cols)] for r in range(rows)]


class TestPlanService:
    def test_warm_prefetch_hit_accounting(self):
        svc = PlanService(registry=AlgorithmRegistry())
        topo = torus2d(4, 4)
        stats = svc.warm(topo, AXES, kinds=("all_gather",))
        # 2 axes x 4 groups = 8 lookups, one cold synthesis per axis
        assert stats["misses"] == 2
        assert stats["hits"] == 6
        # a second warm of the same working set is all hits
        stats = svc.warm(topo, AXES, kinds=("all_gather",))
        assert stats["misses"] == 2
        assert stats["hits"] == 14
        m = svc.metrics()
        assert m["warm_requested"] == 2 and m["warm_completed"] == 2
        assert m["warm_failed"] == 0
        assert m["entries"] == 2 and m["planners"] == 1

    def test_background_warm_and_drain(self):
        with PlanService(registry=AlgorithmRegistry()) as svc:
            topo = torus2d(4, 4)
            fut = svc.warm(topo, AXES, kinds=("all_gather",), block=False)
            svc.drain()
            assert fut.done()
            assert fut.result()["misses"] == 2
            # the prefetched working set serves plan() as pure hits
            before = svc.metrics()["misses"]
            alg = svc.plan(topo, AXES, "all_gather", "data", 3)
            alg.validate()
            assert svc.metrics()["misses"] == before

    def test_planner_memoized_per_topology_and_axes(self):
        svc = PlanService(registry=AlgorithmRegistry())
        topo = torus2d(4, 4)
        p1 = svc.planner(topo, AXES)
        p2 = svc.planner(topo, AXES)
        assert p1 is p2
        p3 = svc.planner(topo, {"data": 2, "model": 8})
        assert p3 is not p1
        assert svc.metrics()["planners"] == 2

    def test_eviction_metrics(self):
        svc = PlanService(registry=AlgorithmRegistry(max_entries=1))
        topo = torus2d(4, 4)
        svc.plan(topo, AXES, "all_gather", "data")
        svc.plan(topo, AXES, "all_to_all", "data")  # evicts the all_gather
        svc.plan(topo, AXES, "all_gather", "data")  # re-synthesizes
        m = svc.metrics()
        assert m["evictions"] == 2
        assert m["misses"] == 3
        assert m["entries"] == 1

    def test_disk_byte_metrics(self, tmp_path):
        svc = PlanService(cache_dir=str(tmp_path))
        topo = torus2d(4, 4)
        svc.warm(topo, AXES, kinds=("all_gather",))
        m = svc.metrics()
        assert m["bytes_stored"] > 0 and m["bytes_loaded"] == 0
        # a second tenant (fresh service, same dir) loads instead of storing
        svc2 = PlanService(cache_dir=str(tmp_path))
        svc2.warm(topo, AXES, kinds=("all_gather",))
        m2 = svc2.metrics()
        assert m2["disk_hits"] == 2 and m2["misses"] == 0
        assert m2["bytes_loaded"] > 0


# Each worker makes `iters` passes over the shared cache dir with a fresh
# registry per pass (forcing the disk path); the writer additionally retires
# every entry before each pass, so readers race against unlink + atomic
# rewrite. Any exception (partial read, crash on a half-visible entry) fails
# the worker.
_STRESS_WORKER = """
import os, sys
from repro.core import AlgorithmRegistry, CollectiveRequest, SynthesisEngine
from repro.topology import torus2d

cache, role, iters = sys.argv[1], sys.argv[2], int(sys.argv[3])
topo = torus2d(4, 4)
rows = [[r * 4 + c for c in range(4)] for r in range(4)]
expected = {}
for i in range(iters):
    if role == "writer":
        for f in os.listdir(cache):
            if f.endswith(".npz"):
                try:
                    os.remove(os.path.join(cache, f))
                except OSError:
                    pass
    reg = AlgorithmRegistry(cache_dir=cache)
    eng = SynthesisEngine(topo, registry=reg)
    nbytes = float(i % 2 + 1)
    alg = eng.collective(CollectiveRequest(
        "all_gather", group=tuple(rows[i % 4]), bytes=nbytes))
    alg.validate()
    key = nbytes
    if key in expected:
        assert alg.makespan == expected[key], "nondeterministic plan"
    expected[key] = alg.makespan
print("ok")
"""


@pytest.mark.slow
def test_shared_cache_dir_concurrent_readers_one_writer(tmp_path):
    """Three reader processes + one writer churning a shared PCCL_CACHE_DIR:
    nobody may crash, and every served plan must validate."""
    cache = tmp_path / "cache"
    cache.mkdir()
    procs = []
    for role, iters in (("writer", 30), ("reader", 40), ("reader", 40),
                        ("reader", 40)):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _STRESS_WORKER, str(cache), role,
             str(iters)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"worker failed:\n{err}"
        assert out.strip() == "ok"
    # the survivors on disk are valid, loadable entries
    reg = AlgorithmRegistry(cache_dir=str(cache))
    eng = SynthesisEngine(torus2d(4, 4), registry=reg)
    eng.collective(CollectiveRequest("all_gather", [0, 1, 2, 3])).validate()


class TestDiskEviction:
    """Size-capped disk-tier LRU: the shared cache dir stays under
    ``max_disk_bytes``, stalest entries (by manifest access time) go
    first, and the sweep survives corrupt manifests and races."""

    def _store(self, reg, nbytes):
        import os
        before = {f for f in os.listdir(reg.cache_dir)
                  if f.endswith(".npz")}
        eng = SynthesisEngine(torus2d(4, 4), registry=reg)
        eng.collective(CollectiveRequest(
            "all_gather", group=tuple(range(16)), bytes=nbytes))
        after = {f for f in os.listdir(reg.cache_dir)
                 if f.endswith(".npz")}
        new = after - before
        return next(iter(new)) if new else None

    def test_size_capped_lru(self, tmp_path):
        import os
        probe = AlgorithmRegistry(cache_dir=str(tmp_path))
        self._store(probe, 1.0)
        one = probe.stats.bytes_stored
        assert one > 0
        cap = int(one * 2.5)
        reg = AlgorithmRegistry(cache_dir=str(tmp_path),
                                max_disk_bytes=cap)
        for b in (2.0, 3.0, 4.0):
            self._store(reg, b)
        m = reg.stats.as_dict()
        assert m["disk_evictions"] >= 1
        assert 0 < m["disk_bytes"] <= cap
        files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
        assert sum(os.path.getsize(tmp_path / f) for f in files) <= cap

    def test_lru_prefers_stale_entries(self, tmp_path):
        import time

        big = 1 << 40
        reg = AlgorithmRegistry(cache_dir=str(tmp_path),
                                max_disk_bytes=big)
        a = self._store(reg, 1.0)
        time.sleep(0.01)
        b = self._store(reg, 2.0)
        one = reg.stats.bytes_stored // 2
        time.sleep(0.01)
        # a fresh tenant loads entry A from disk: A is now *fresher* than B
        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path),
                                 max_disk_bytes=big)
        self._store(reg2, 1.0)
        assert reg2.stats.disk_hits == 1
        time.sleep(0.01)
        # a capped store forces a sweep: B (stalest) goes, A survives
        reg3 = AlgorithmRegistry(cache_dir=str(tmp_path),
                                 max_disk_bytes=int(one * 2.5))
        c = self._store(reg3, 3.0)
        assert reg3.stats.disk_evictions >= 1
        assert (tmp_path / a).exists(), "recently-loaded entry was evicted"
        assert not (tmp_path / b).exists(), "stalest entry survived the cap"
        assert c is not None and (tmp_path / c).exists()

    def test_cache_max_bytes_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PCCL_CACHE_MAX_BYTES", "12345")
        assert AlgorithmRegistry(
            cache_dir=str(tmp_path)).max_disk_bytes == 12345
        monkeypatch.setenv("PCCL_CACHE_MAX_BYTES", "not-a-number")
        assert AlgorithmRegistry(
            cache_dir=str(tmp_path)).max_disk_bytes is None
        monkeypatch.delenv("PCCL_CACHE_MAX_BYTES")
        assert AlgorithmRegistry(
            cache_dir=str(tmp_path), max_disk_bytes=7).max_disk_bytes == 7

    def test_sweep_tolerates_corruption_and_races(self, tmp_path):
        import os
        probe = AlgorithmRegistry(cache_dir=str(tmp_path))
        first = self._store(probe, 1.0)
        one = probe.stats.bytes_stored
        reg = AlgorithmRegistry(cache_dir=str(tmp_path),
                                max_disk_bytes=int(one * 1.5))
        # a killed writer left a corrupt manifest; a concurrent evictor
        # removed an entry behind our back
        (tmp_path / "manifest.json").write_text("{definitely not json")
        os.remove(tmp_path / first)
        self._store(reg, 2.0)
        self._store(reg, 3.0)
        m = reg.stats.as_dict()
        assert m["disk_bytes"] <= int(one * 1.5)
        # the dir is still serviceable: a fresh tenant loads what survived
        reg2 = AlgorithmRegistry(cache_dir=str(tmp_path))
        eng = SynthesisEngine(torus2d(4, 4), registry=reg2)
        eng.collective(CollectiveRequest(
            "all_gather", group=tuple(range(16)), bytes=3.0)).validate()

    def test_metrics_expose_disk_eviction_counters(self, tmp_path):
        svc = PlanService(cache_dir=str(tmp_path), max_disk_bytes=1 << 40)
        topo = torus2d(4, 4)
        svc.warm(topo, AXES, kinds=("all_gather",))
        m = svc.metrics()
        assert m["disk_evictions"] == 0
        assert m["disk_bytes"] > 0  # the sweep ran and measured the dir
