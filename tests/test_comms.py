"""Comms layer tests.

The multi-device executor needs >1 host device, and jax locks the device
count at first init — so the numerical selftest runs in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8. Pure-function pieces
(translation, buffer planning, compression) are tested in-process.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.comms.executor import plan_buffers
from repro.core import CollectiveRequest, SynthesisEngine, to_ppermute_program
from repro.topology import ring, torus2d


class TestTranslation:
    def test_rounds_are_permutations(self):
        topo = torus2d(3, 3)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", list(range(9))))
        prog = to_ppermute_program(alg)
        for rnd in prog.rounds:
            srcs = [s.src for s in rnd]
            dsts = [s.dst for s in rnd]
            assert len(srcs) == len(set(srcs)), "src appears twice in a round"
            assert len(dsts) == len(set(dsts)), "dst appears twice in a round"

    def test_rounds_preserve_transfer_count(self):
        topo = ring(6, bidirectional=True)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(6))))
        prog = to_ppermute_program(alg)
        assert sum(len(r) for r in prog.rounds) == alg.num_transfers

    def test_rounds_causal(self):
        """A chunk is never sent by a device before a round in which that
        device held/received it."""
        topo = torus2d(3, 3)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_reduce", list(range(9))))
        prog = to_ppermute_program(alg)
        holders = {c: set(h) for c, h in prog.chunk_holders.items()}
        for rnd in prog.rounds:
            for s in rnd:
                assert s.src in holders[s.chunk], f"premature send {s}"
            for s in rnd:
                holders[s.chunk].add(s.dst)

    def test_buffer_plan_slots(self):
        topo = ring(4, bidirectional=True)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(4))))
        prog = to_ppermute_program(alg)
        plan = plan_buffers(prog)
        assert plan.num_slots >= 4  # every device ends with all 4 chunks
        # every destination has a slot for its chunk
        for chunk, dests in prog.chunk_dests.items():
            for d in dests:
                assert (d, chunk) in plan.slot_of


def _simulate(plan, buf):
    """Numpy interpreter with the exact executor semantics: per round every
    device sends buf[send_slot]; non-destinations receive ppermute zeros;
    the received value lands at recv_slot (the trash slot for
    non-receivers), added when is_reduce else overwriting."""
    n = plan.num_devices
    dev = np.arange(n)
    for rt in plan.rounds:
        sent = buf[dev, rt.send_slot]
        got = np.zeros_like(sent)
        for s, d in rt.perm:
            got[d] = sent[s]
        old = buf[dev, rt.recv_slot]
        new = np.where(rt.is_reduce[:, None], old + got, got)
        buf[dev, rt.recv_slot] = new
    return buf


class TestSwitchUnrolling:
    """Switch-riding schedules (multi_pod DCI and friends) lower to direct
    NPU-to-NPU ppermute programs; numerics checked with the numpy
    interpreter so tier-1 covers them without a multi-device jax."""

    def _topo(self):
        from repro.topology.generators import multi_pod

        return multi_pod(2, 2, 2, unit_links=True, dci_ports_per_pod=2)

    def _alg(self, kind, topo, **kw):
        from repro.core import CollectiveRequest, SynthesisEngine

        n = len(topo.npus)
        req = CollectiveRequest(kind, group=tuple(range(n)),
                                hierarchy="always", **kw)
        alg = SynthesisEngine(topo).collective(req)
        alg.validate()
        return alg

    def test_strict_mode_still_raises(self):
        topo = self._topo()
        alg = self._alg("all_gather", topo)
        with pytest.raises(ValueError, match="NPU-to-NPU"):
            to_ppermute_program(alg, unroll_switches=False)

    def test_unrolled_endpoints_are_devices(self):
        topo = self._topo()
        for kind in ("all_gather", "reduce_scatter", "all_reduce",
                     "all_to_all"):
            prog = to_ppermute_program(self._alg(kind, topo))
            for rnd in prog.rounds:
                for s in rnd:
                    assert 0 <= s.src < prog.num_devices
                    assert 0 <= s.dst < prog.num_devices
                    assert s.src != s.dst

    def test_unrolled_rounds_causal(self):
        topo = self._topo()
        prog = to_ppermute_program(self._alg("all_reduce", topo))
        holders = {c: set(h) for c, h in prog.chunk_holders.items()}
        for rnd in prog.rounds:
            for s in rnd:
                assert s.src in holders[s.chunk], f"premature send {s}"
            for s in rnd:
                holders[s.chunk].add(s.dst)

    def test_all_gather_numerics_through_dci(self):
        topo = self._topo()
        n = len(topo.npus)
        prog = to_ppermute_program(self._alg("all_gather", topo))
        plan = plan_buffers(prog)
        chunk_of = {src: c for c, src in prog.chunk_srcs.items()}
        rng = np.random.default_rng(3)
        x = rng.standard_normal((n, 2))
        buf = np.zeros((n, plan.buffer_slots, 2))
        for d in range(n):
            buf[d, plan.slot_of[(d, chunk_of[d])]] = x[d]
        buf = _simulate(plan, buf)
        for d in range(n):
            for src in range(n):
                got = buf[d, plan.slot_of[(d, chunk_of[src])]]
                np.testing.assert_array_equal(got, x[src])

    def test_all_reduce_numerics_through_dci(self):
        topo = self._topo()
        n = len(topo.npus)
        prog = to_ppermute_program(self._alg("all_reduce", topo))
        plan = plan_buffers(prog)
        chunks = sorted(prog.chunk_holders)
        rng = np.random.default_rng(4)
        x = rng.standard_normal((n, len(chunks), 2))
        buf = np.zeros((n, plan.buffer_slots, 2))
        for ci, c in enumerate(chunks):
            for d in range(n):
                got = plan.slot_of.get((d, c))
                if got is not None:
                    buf[d, got] = x[d, ci]
        buf = _simulate(plan, buf)
        for ci, c in enumerate(chunks):
            want = x[:, ci].sum(axis=0)
            for d in range(n):
                np.testing.assert_allclose(
                    buf[d, plan.slot_of[(d, c)]], want, atol=1e-9)


class TestPlanCache:
    def _prog(self, n):
        topo = ring(n, bidirectional=True)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(n))))
        return to_ppermute_program(alg)

    def test_colliding_fingerprints_do_not_cross_serve(self):
        """Regression: two distinct programs handed the same caller
        fingerprint must each get their own plan (the cache also keys on
        the program's structural digest)."""
        from repro.comms import clear_plan_cache, plan_buffers_cached

        clear_plan_cache()
        p4, p6 = self._prog(4), self._prog(6)
        a = plan_buffers_cached(p4, "same-fp")
        b = plan_buffers_cached(p6, "same-fp")
        assert a.num_devices == 4
        assert b.num_devices == 6
        # and both entries still hit
        assert plan_buffers_cached(p4, "same-fp") is a
        assert plan_buffers_cached(p6, "same-fp") is b

    def test_digest_distinguishes_programs(self):
        p4, p4b, p6 = self._prog(4), self._prog(4), self._prog(6)
        assert p4.digest() == p4b.digest()
        assert p4.digest() != p6.digest()

    def test_hit_miss_stats(self):
        from repro.comms import clear_plan_cache, plan_buffers_cached
        from repro.tracing import counters

        clear_plan_cache()
        p = self._prog(5)
        before = counters()
        plan_buffers_cached(p, "fp")
        plan_buffers_cached(p, "fp")
        after = counters()
        assert [after.get(k, 0) - before.get(k, 0)
                for k in ("plan_cache.hit", "plan_cache.miss")] == [1, 1]

    def test_thread_safety_under_eviction_churn(self, monkeypatch):
        """Many threads sharing a tiny cache: every served plan must match
        its program, and no internal state corruption may raise."""
        from concurrent.futures import ThreadPoolExecutor

        from repro.comms import executor as ex

        monkeypatch.setattr(ex, "_PLAN_CACHE_MAX", 4)
        ex.clear_plan_cache()
        progs = [self._prog(n) for n in (4, 5, 6, 7, 8, 9)]

        def worker(i):
            for j in range(40):
                k = (i * 7 + j) % len(progs)
                p = progs[k]
                plan = ex.plan_buffers_cached(p, f"fp{k}")
                assert plan.num_devices == p.num_devices
                for c, dests in p.chunk_dests.items():
                    assert (dests[0], c) in plan.slot_of
            return True

        with ThreadPoolExecutor(max_workers=8) as pool:
            assert all(pool.map(worker, range(16)))
        ex.clear_plan_cache()


@pytest.mark.slow
class TestMultiDeviceExecutor:
    def test_selftest_subprocess(self):
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        root = os.path.join(os.path.dirname(__file__), "..")
        env["PYTHONPATH"] = os.path.join(root, "src")
        res = subprocess.run(
            [sys.executable, "-m", "repro.comms.selftest"],
            capture_output=True,
            text=True,
            env=env,
            timeout=600,
        )
        assert res.returncode == 0, res.stdout + res.stderr
        assert "ALL PASS" in res.stdout


class TestCompression:
    def test_int8_roundtrip_error_feedback(self):
        import jax.numpy as jnp

        from repro.comms import ef_int8_compress, ef_int8_decompress

        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(128,)).astype(np.float32))
        r = jnp.zeros_like(g)
        total_in, total_out = jnp.zeros_like(g), jnp.zeros_like(g)
        for _ in range(50):
            q, scale, r = ef_int8_compress(g, r)
            total_in = total_in + g
            total_out = total_out + ef_int8_decompress(q, scale)
        # error feedback keeps the long-run sum faithful
        drift = np.abs(np.asarray(total_out + r - total_in)).max()
        assert drift < 1e-3

    def test_topk_roundtrip(self):
        import jax.numpy as jnp

        from repro.comms import topk_compress, topk_decompress

        g = jnp.asarray(np.arange(16, dtype=np.float32) - 8.0)
        r = jnp.zeros_like(g)
        vals, idx, r2 = topk_compress(g, r, k=4)
        dec = topk_decompress(vals, idx, (16,))
        # top-4 magnitudes survive; the rest land in the residual
        assert np.count_nonzero(np.asarray(dec)) == 4
        np.testing.assert_allclose(np.asarray(dec + r2), np.asarray(g), atol=1e-6)
