"""Differential tests for the columnar plan representation.

``CollectiveAlgorithm`` stores its schedule as parallel numpy columns with
lazy per-row ``Transfer`` views. Every vectorized kernel here is compared
bit-for-bit against an in-test reference written the way the old per-object
code worked — same sort key, same arithmetic, same iteration order — on all
four routing paths: flat, hierarchical (multi-pod), multi-level + time
reversal (reductions), and traffic-engineered (CommSketch). The npz
persistence round-trip is held to the same standard: transfer order, every
field, conditions, and phase spans must come back identical.
"""

from operator import attrgetter

import numpy as np
import pytest

from repro.core import (
    AlgorithmRegistry,
    CollectiveAlgorithm,
    CollectiveRequest,
    CommSketch,
    SynthesisEngine,
    Transfer,
    TransferColumns,
    TransferList,
    load_plan_npz,
    save_plan_npz,
    topology_fingerprint,
)
from repro.core.conditions import ChunkIds
from repro.core.registry import (
    invert_permutation,
    relabel_algorithm,
    renumber_chunks,
)
from repro.topology import multi_pod, torus2d
from repro.topology.generators import three_level

SORT_KEY = attrgetter("start", "chunk", "link")


def _routes():
    """(name, algorithm) for every routing path in the synthesis stack."""
    flat = SynthesisEngine(torus2d(4, 4), registry=AlgorithmRegistry())
    pods = SynthesisEngine(
        multi_pod(2, 2, 4, unit_links=True, dci_ports_per_pod=4),
        registry=AlgorithmRegistry())
    deep = SynthesisEngine(three_level(2, 2, 2, unit_links=True),
                           registry=AlgorithmRegistry())
    te = SynthesisEngine(
        multi_pod(2, 2, 4, unit_links=True, dci_ports_per_pod=4),
        registry=AlgorithmRegistry(),
        sketch=CommSketch(max_pod_ports={0: 1, 1: 1}))
    return [
        ("flat_ag", flat.collective(CollectiveRequest(
            "all_gather", list(range(16))))),
        ("flat_a2a", flat.collective(CollectiveRequest(
            "all_to_all", [0, 1, 2, 3]))),
        ("flat_rs", flat.collective(CollectiveRequest(
            "reduce_scatter", list(range(16))))),
        ("hier_ag", pods.collective(CollectiveRequest(
            "all_gather", pods.topology.npus))),
        ("hier_ar", pods.collective(CollectiveRequest(
            "all_reduce", pods.topology.npus))),
        ("hier3_ag", deep.collective(CollectiveRequest(
            "all_gather", deep.topology.npus))),
        ("hier3_rs", deep.collective(CollectiveRequest(
            "reduce_scatter", deep.topology.npus))),
        ("te_ag", te.collective(CollectiveRequest(
            "all_gather", te.topology.npus))),
    ]


ROUTES = _routes()
IDS = [name for name, _ in ROUTES]


@pytest.mark.parametrize("alg", [a for _, a in ROUTES], ids=IDS)
class TestScheduleIdentity:
    def test_sort_matches_object_sort(self, alg):
        """Column order == the old __post_init__'s object sort."""
        assert list(alg.transfers) == sorted(alg.transfers, key=SORT_KEY)

    def test_object_ingestion_roundtrip(self, alg):
        """Rebuilding from plain Transfer objects reproduces the schedule
        bit-for-bit: fields, order, and phase spans."""
        objs = [Transfer(t.chunk, t.link, t.src, t.dst, t.start, t.end,
                         t.reduce) for t in alg.transfers]
        rebuilt = CollectiveAlgorithm(
            alg.topology, list(alg.conditions), objs, name=alg.name,
            phase_spans=list(alg.phase_spans))
        assert rebuilt == alg
        assert list(rebuilt.transfers) == list(alg.transfers)
        assert rebuilt.phase_spans == alg.phase_spans

    def test_npz_roundtrip_bit_identical(self, alg, tmp_path):
        path = str(tmp_path / "plan.npz")
        save_plan_npz(path, alg, topology_fingerprint(alg.topology))
        for use_mmap in (True, False):
            back = load_plan_npz(path, alg.topology, use_mmap=use_mmap)
            assert list(back.transfers) == list(alg.transfers)
            assert back.conditions == alg.conditions
            assert back.phase_spans == alg.phase_spans
            assert back.name == alg.name
            back.validate()

    def test_vectorized_metrics_match_reference(self, alg):
        """makespan / link_busy_time / link_utilization / total_bytes_moved
        against the old per-object loops."""
        release = min((c.release for c in alg.conditions), default=0.0)
        ref_makespan = max((t.end for t in alg.transfers),
                           default=release) - release
        assert alg.makespan == ref_makespan

        busy: dict[int, float] = {}
        for t in alg.transfers:  # same accumulation order as np.add.at
            busy[t.link] = busy.get(t.link, 0.0) + (t.end - t.start)
        assert alg.link_busy_time() == busy

        if ref_makespan > 0 and busy:
            ref_util = {l: b / ref_makespan for l, b in busy.items()}
            assert alg.link_utilization() == ref_util

        sizes = {c.chunk: c.bytes for c in alg.conditions}
        ref_total = sum(sizes[t.chunk] for t in alg.transfers)
        assert alg.total_bytes_moved() == pytest.approx(ref_total)

    def test_time_reversal_primitive(self, alg):
        """Columnar time reversal == the old per-object construction."""
        cols = alg.columns
        pivot = float(cols.end.max()) if len(cols) else 0.0
        rev = cols.time_reversed(pivot)
        ref = [Transfer(t.chunk, t.link, t.dst, t.src,
                        pivot - t.end, pivot - t.start, reduce=True)
               for t in alg.transfers]
        assert list(TransferList(rev)) == ref

    def test_relabel_identity_roundtrip(self, alg):
        """Relabeling through an automorphism and back is lossless and the
        forward image matches a per-object reference relabel."""
        topo = alg.topology
        gens = [g for g in getattr(topo, "automorphism_generators", [])]
        if not gens:
            pytest.skip("no symmetry generators on this fabric")
        perm = list(gens[0])
        fwd = relabel_algorithm(alg, perm)

        from repro.core.registry import _link_map
        links = _link_map(topo, perm)
        ref = sorted((Transfer(t.chunk, links[t.link], perm[t.src],
                               perm[t.dst], t.start, t.end, t.reduce)
                      for t in alg.transfers), key=SORT_KEY)
        assert list(fwd.transfers) == ref

        back = relabel_algorithm(fwd, invert_permutation(perm))
        assert list(back.transfers) == list(alg.transfers)
        assert back.conditions == alg.conditions

    def test_renumber_chunks_matches_reference(self, alg):
        ids = ChunkIds(1000)
        out = renumber_chunks(alg, ids)
        mapping = {}
        nxt = 1000
        for c in alg.conditions:  # same allocation order as renumber_chunks
            mapping[c.chunk] = nxt
            nxt += 1
        ref = [Transfer(mapping.get(t.chunk, t.chunk), t.link, t.src, t.dst,
                        t.start, t.end, t.reduce) for t in alg.transfers]
        assert list(out.transfers) == ref
        assert [c.chunk for c in out.conditions] == \
            [mapping[c.chunk] for c in alg.conditions]


class TestTransferListApi:
    def setup_method(self):
        eng = SynthesisEngine(torus2d(3, 3), registry=AlgorithmRegistry())
        self.alg = eng.collective(CollectiveRequest(
            "all_gather", list(range(9))))
        self.tl = self.alg.transfers

    def test_sequence_semantics(self):
        tl = self.tl
        assert isinstance(tl, TransferList)
        n = len(tl)
        assert n == self.alg.num_transfers
        assert isinstance(tl[0], Transfer)
        assert tl[-1] == tl[n - 1]
        assert list(tl[2:5]) == list(tl)[2:5]
        assert tl == list(tl)
        assert tl + [tl[0]] == list(tl) + [tl[0]]
        with pytest.raises(IndexError):
            tl[n]

    def test_rows_are_plain_python_scalars(self):
        t = self.tl[0]
        assert type(t.chunk) is int and type(t.link) is int
        assert type(t.src) is int and type(t.dst) is int
        assert type(t.start) is float and type(t.end) is float
        assert type(t.reduce) is bool

    def test_columns_are_read_only_after_mmap_load(self, tmp_path):
        path = str(tmp_path / "p.npz")
        save_plan_npz(path, self.alg, topology_fingerprint(self.alg.topology))
        back = load_plan_npz(path, self.alg.topology)
        for name in ("chunk", "link", "src", "dst", "start", "end",
                     "reduce"):
            arr = getattr(back.columns, name)
            assert not arr.flags.writeable
        # and the arrays are views over the file, not copies
        base = back.columns.chunk.base
        while base is not None:
            if isinstance(base, memoryview):
                base = base.obj
                continue
            if type(base).__name__ == "mmap":
                break
            base = getattr(base, "base", None)
        assert type(base).__name__ == "mmap"

    def test_concat_and_shift(self):
        cols = self.alg.columns
        shifted = cols.shifted(2.5)
        assert np.array_equal(shifted.start, cols.start + 2.5)
        both = TransferColumns.concat([cols, shifted])
        assert len(both) == 2 * len(cols)
        assert np.array_equal(both.chunk[:len(cols)], cols.chunk)
