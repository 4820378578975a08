"""Tests for the condition builders — notably ``all_to_allv``, which drives
MoE expert-parallel dispatch and previously had no coverage."""


from repro.core import ChunkIds, SynthesisEngine, all_to_allv
from repro.topology import ring, torus2d


class TestAllToAllv:
    def test_dict_counts(self):
        group = [0, 1, 2]
        counts = {(0, 1): 2, (1, 2): 1, (2, 0): 3}
        conds = all_to_allv(group, counts)
        by_pair = {}
        for c in conds:
            (dst,) = c.dests
            by_pair[(c.src, dst)] = by_pair.get((c.src, dst), 0) + 1
        assert by_pair == counts

    def test_matrix_counts(self):
        group = [5, 7, 9]  # non-contiguous NPU ids: matrix is by group index
        counts = [
            [0, 1, 2],
            [3, 0, 0],
            [1, 1, 0],
        ]
        conds = all_to_allv(group, counts)
        by_pair = {}
        for c in conds:
            (dst,) = c.dests
            by_pair[(c.src, dst)] = by_pair.get((c.src, dst), 0) + 1
        assert by_pair == {(5, 7): 1, (5, 9): 2, (7, 5): 3, (9, 5): 1,
                           (9, 7): 1}

    def test_zero_count_pairs_skipped(self):
        conds = all_to_allv([0, 1, 2], {(0, 1): 0, (1, 2): 2})
        assert len(conds) == 2
        assert all(next(iter(c.dests)) == 2 for c in conds)

    def test_diagonal_ignored(self):
        # self-sends carry no network traffic in either count form
        assert all_to_allv([0, 1], {(0, 0): 5, (0, 1): 1}) != []
        assert len(all_to_allv([0, 1], {(0, 0): 5, (0, 1): 1})) == 1
        assert len(all_to_allv([0, 1], [[4, 0], [0, 4]])) == 0

    def test_chunk_ids_unique_and_allocator_shared(self):
        ids = ChunkIds(100)
        a = all_to_allv([0, 1, 2], {(0, 1): 3, (2, 1): 2}, ids=ids)
        b = all_to_allv([0, 1, 2], {(1, 0): 2}, ids=ids)
        chunks = [c.chunk for c in a + b]
        assert len(chunks) == len(set(chunks)) == 7
        assert min(chunks) == 100  # drawn from the caller's allocator

    def test_deterministic_order(self):
        counts = {(2, 0): 1, (0, 1): 2, (1, 2): 1}
        c1 = all_to_allv([0, 1, 2], dict(counts))
        c2 = all_to_allv([0, 1, 2], dict(reversed(list(counts.items()))))
        assert [(c.src, tuple(c.dests)) for c in c1] == \
            [(c.src, tuple(c.dests)) for c in c2]

    def test_bytes_and_tag_propagate(self):
        conds = all_to_allv([0, 1], {(0, 1): 2}, bytes=4.0, tag="moe")
        assert all(c.bytes == 4.0 and c.tag == "moe" for c in conds)

    def test_synthesizes_and_validates(self):
        topo = torus2d(3, 3)
        counts = {(i, j): (i + j) % 3 for i in range(9) for j in range(9)
                  if i != j}
        conds = all_to_allv(list(range(9)), counts)
        alg = SynthesisEngine(topo).synthesize(conds)
        alg.validate()
        delivered = {c.chunk for c in alg.conditions}
        assert len(delivered) == sum(counts.values())

    def test_empty_counts(self):
        assert all_to_allv([0, 1, 2], {}) == []
        conds = all_to_allv(list(range(4)), [[0] * 4 for _ in range(4)])
        assert conds == []

    def test_ring_delivery(self):
        topo = ring(4)
        conds = all_to_allv([0, 1, 2, 3], {(0, 2): 2, (3, 1): 1})
        alg = SynthesisEngine(topo).synthesize(conds)
        alg.validate()
        assert alg.makespan >= 2.0  # two hops minimum on the ring
