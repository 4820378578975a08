"""Differential tests: the vectorized validation path must accept and
reject exactly what the reference oracle does on its eligible class
(plain conditions, unconstrained switches)."""

import dataclasses

import pytest

from repro.core import (
    AlgorithmRegistry,
    CollectiveAlgorithm,
    CollectiveRequest,
    SynthesisEngine,
)
from repro.topology import multi_pod, ring, star_switch, torus2d


@pytest.fixture(scope="module")
def algs():
    t1 = torus2d(3, 3)
    eng = SynthesisEngine(t1)
    t2 = multi_pod(2, 2, 4, unit_links=True, dci_ports_per_pod=4)
    e2 = SynthesisEngine(t2, registry=AlgorithmRegistry())
    return [
        eng.collective(CollectiveRequest("all_gather", list(range(9)))),
        eng.collective(CollectiveRequest("all_to_all", list(range(9)))),
        e2.collective(CollectiveRequest(
            "all_gather", t2.npus)),  # hierarchical, stitched phases
        e2.collective(CollectiveRequest("all_to_all", t2.npus)),
    ]


def _mutate(alg, idx, **kw):
    ts = list(alg.transfers)
    ts[idx] = dataclasses.replace(ts[idx], **kw)
    return CollectiveAlgorithm(alg.topology, alg.conditions, ts,
                               name=alg.name)


def _drop_last_delivery(alg):
    """Remove the final transfer of some chunk: its destination is never
    reached (post-condition failure)."""
    ts = list(alg.transfers)
    last = {}
    for i, t in enumerate(ts):
        last[t.chunk] = i
    del ts[last[ts[-1].chunk]]
    return CollectiveAlgorithm(alg.topology, alg.conditions, ts,
                               name=alg.name)


class TestBulkMatchesOracle:
    @pytest.mark.parametrize("i", range(4))
    def test_valid_schedules_accepted(self, algs, i):
        alg = algs[i]
        alg.validate(mode="oracle")
        alg.validate(mode="bulk")

    @pytest.mark.parametrize("i", range(4))
    def test_congestion_rejected(self, algs, i):
        alg = algs[i]
        # move one transfer onto another's slot on the same link
        a, b = None, None
        by_link = {}
        for k, t in enumerate(alg.transfers):
            if t.link in by_link:
                a, b = by_link[t.link], k
                break
            by_link[t.link] = k
        assert a is not None
        broken = _mutate(
            alg, b,
            start=alg.transfers[a].start,
            end=alg.transfers[a].start
            + (alg.transfers[b].end - alg.transfers[b].start),
        )
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    @pytest.mark.parametrize("i", range(4))
    def test_bad_duration_rejected(self, algs, i):
        alg = algs[i]
        broken = _mutate(alg, 0, end=alg.transfers[0].end + 0.5)
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    @pytest.mark.parametrize("i", range(4))
    def test_causality_violation_rejected(self, algs, i):
        alg = algs[i]
        # find a forwarding transfer (sender is not the chunk's origin) and
        # pull it before the chunk could have arrived
        origin = {c.chunk: c.src for c in alg.conditions}
        k = next(j for j, t in enumerate(alg.transfers)
                 if t.src != origin[t.chunk])
        t = alg.transfers[k]
        broken = _mutate(alg, k, start=t.start - t.end,
                         end=t.start - t.end + (t.end - t.start))
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    @pytest.mark.parametrize("i", range(4))
    def test_missing_delivery_rejected(self, algs, i):
        alg = algs[i]
        broken = _drop_last_delivery(alg)
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    def test_wrong_link_endpoints_rejected(self):
        alg = SynthesisEngine(ring(4)).collective(CollectiveRequest(
            "all_gather", list(range(4))))
        t = alg.transfers[0]
        broken = _mutate(alg, 0, dst=(t.dst + 1) % 4)
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    def test_release_violation_rejected(self):
        import dataclasses as dc

        alg = SynthesisEngine(ring(4)).collective(CollectiveRequest(
            "all_gather", list(range(4))))
        conds = [dc.replace(c, release=5.0) for c in alg.conditions]
        broken = CollectiveAlgorithm(alg.topology, conds,
                                     list(alg.transfers), name=alg.name)
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    def test_bulk_refuses_constrained_switches(self):
        topo = star_switch(4, buffer_limit=1)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_gather", list(range(4))))
        alg.validate(mode="oracle")
        with pytest.raises(ValueError, match="bulk validation"):
            alg.validate(mode="bulk")
        alg.validate()  # auto falls back to the oracle

    def test_bulk_refuses_reduce_flag_on_plain_chunk(self):
        # a reduce-flagged copy of a plain chunk is a nonstandard schedule:
        # the oracle judges it with its full replay, bulk stays out
        alg = SynthesisEngine(ring(4)).collective(CollectiveRequest(
            "all_gather", list(range(4))))
        weird = _mutate(alg, 0, reduce=True)
        with pytest.raises(ValueError, match="bulk validation"):
            weird.validate(mode="bulk")

    def test_bulk_validates_reductions(self):
        # reductions in the in-forest normal form now take the bulk path
        for alg in (SynthesisEngine(ring(4)).collective(CollectiveRequest(
            "all_reduce", list(range(4)))),
                    SynthesisEngine(ring(4)).collective(CollectiveRequest(
                        "reduce_scatter", list(range(4))))):
            alg.validate(mode="oracle")
            alg.validate(mode="bulk")

    def test_bulk_empty_transfers(self):
        """Zero transfers: clean post-condition rejection (not IndexError)
        for missing deliveries, acceptance when every dest is the origin."""
        from repro.core import Condition

        topo = ring(4)
        undelivered = CollectiveAlgorithm(
            topo, [Condition(0, 0, frozenset([1]))], [])
        with pytest.raises(AssertionError, match="never reached"):
            undelivered.validate(mode="bulk")
        with pytest.raises(AssertionError):
            undelivered.validate(mode="oracle")
        trivial = CollectiveAlgorithm(
            topo, [Condition(0, 0, frozenset([0]))], [])
        trivial.validate(mode="bulk")
        trivial.validate(mode="oracle")


class TestBulkReductionDifferential:
    """Reduction schedules (flat reversed-gather and hierarchical composed):
    the bulk in-forest checks must accept what the oracle accepts and reject
    every corruption class the oracle rejects."""

    @pytest.fixture(scope="class")
    def ralgs(self):
        eng = SynthesisEngine(ring(4))
        t2 = multi_pod(2, 2, 4, unit_links=True, dci_ports_per_pod=4)
        e2 = SynthesisEngine(t2, registry=AlgorithmRegistry())
        return [
            eng.collective(CollectiveRequest(
                "reduce_scatter", list(range(4)))),
            eng.collective(CollectiveRequest("all_reduce", list(range(4)))),
            e2.collective(CollectiveRequest(
                "reduce_scatter", t2.npus)),  # hierarchical, time-reversed phases
            e2.collective(CollectiveRequest("all_reduce", t2.npus)),
        ]

    @staticmethod
    def _both_reject(broken):
        with pytest.raises(AssertionError):
            broken.validate(mode="oracle")
        with pytest.raises(AssertionError):
            broken.validate(mode="bulk")

    @pytest.mark.parametrize("i", range(4))
    def test_valid_accepted(self, ralgs, i):
        ralgs[i].validate(mode="oracle")
        ralgs[i].validate(mode="bulk")

    @pytest.mark.parametrize("i", range(4))
    def test_double_partial_send_rejected(self, ralgs, i):
        alg = ralgs[i]
        t = next(t for t in alg.transfers if t.reduce)
        dup = dataclasses.replace(t, start=t.start + 1000, end=t.end + 1000)
        self._both_reject(CollectiveAlgorithm(
            alg.topology, alg.conditions, list(alg.transfers) + [dup],
            name=alg.name))

    @pytest.mark.parametrize("i", range(4))
    def test_partial_copy_rejected(self, ralgs, i):
        # stripping the reduce flag turns a partial forward into an illegal
        # copy of partially-reduced state
        alg = ralgs[i]
        k = next(j for j, t in enumerate(alg.transfers) if t.reduce)
        self._both_reject(_mutate(alg, k, reduce=False))

    @pytest.mark.parametrize("i", range(4))
    def test_missing_contribution_rejected(self, ralgs, i):
        # dropping a chunk's final merge leaves its contribution stranded
        alg = ralgs[i]
        last, li = {}, {}
        for j, t in enumerate(alg.transfers):
            if t.reduce and (t.chunk not in last or t.end > last[t.chunk]):
                last[t.chunk], li[t.chunk] = t.end, j
        drop = li[min(li)]
        ts = [t for j, t in enumerate(alg.transfers) if j != drop]
        self._both_reject(CollectiveAlgorithm(
            alg.topology, alg.conditions, ts, name=alg.name))

    @pytest.mark.parametrize("i", range(4))
    def test_forward_before_merge_rejected(self, ralgs, i):
        # a merge point forwarding before a child partial arrives loses it
        alg = ralgs[i]
        recv = {(t.chunk, t.dst) for t in alg.transfers if t.reduce}
        k = next(j for j, t in enumerate(alg.transfers)
                 if t.reduce and (t.chunk, t.src) in recv)
        t = alg.transfers[k]
        self._both_reject(_mutate(alg, k, start=t.start - 100,
                                  end=t.end - 100))

    @staticmethod
    def _agree(alg):
        """Both paths must return the same verdict; return it."""
        res = {}
        for mode in ("oracle", "bulk"):
            try:
                alg.validate(mode=mode)
                res[mode] = True
            except AssertionError:
                res[mode] = False
        assert res["oracle"] == res["bulk"], res
        return res["oracle"]

    def test_nonstandard_but_valid_schedules_defer_to_oracle(self):
        """Outside the in-forest normal form the bulk path must hand the
        verdict to the oracle, not structurally reject: a node that
        assembled the full set may legally hold it while reduce-forwarding
        or copying it onward."""
        from repro.core import ReduceCondition, Transfer
        from repro.topology import Topology

        t = Topology("chain")
        t.add_npus(3)
        l01 = t.add_link(0, 1)
        l12 = t.add_link(1, 2)
        fwd = CollectiveAlgorithm(
            t, [ReduceCondition(0, frozenset([0, 1]), frozenset([1]))],
            [Transfer(0, l01, 0, 1, 0.0, 1.0, reduce=True),
             Transfer(0, l12, 1, 2, 1.0, 2.0, reduce=True)])
        assert self._agree(fwd)  # dest holds full set despite forwarding
        copy = CollectiveAlgorithm(
            t, [ReduceCondition(0, frozenset([0, 1]), frozenset([1, 2]))],
            [Transfer(0, l01, 0, 1, 0.0, 1.0, reduce=True),
             Transfer(0, l12, 1, 2, 1.0, 2.0, reduce=False)])
        assert self._agree(copy)  # mid-chain full-set holder may copy

    @pytest.mark.parametrize("i", range(2))
    def test_single_transfer_mutation_fuzz(self, ralgs, i):
        """Every single-transfer mutation of a flat reduction (flip the
        reduce flag, retime either way, drop) gets the same verdict from
        both paths."""
        base = ralgs[i]
        for k in range(len(base.transfers)):
            tr = base.transfers[k]
            muts = [
                dataclasses.replace(tr, reduce=not tr.reduce),
                dataclasses.replace(tr, start=tr.start - 2, end=tr.end - 2),
                dataclasses.replace(tr, start=tr.start + 7, end=tr.end + 7),
            ]
            for m in muts:
                ts = list(base.transfers)
                ts[k] = m
                self._agree(CollectiveAlgorithm(
                    base.topology, base.conditions, ts, name="mut"))
            ts = [x for j, x in enumerate(base.transfers) if j != k]
            self._agree(CollectiveAlgorithm(
                base.topology, base.conditions, ts, name="drop"))
