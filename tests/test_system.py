"""End-to-end behaviour tests for the PCCL system: synthesize -> validate ->
translate -> evaluate, on the production pod topology."""


from repro.core import (
    ChunkIds,
    CollectiveRequest,
    SynthesisEngine,
    all_to_all,
    all_to_allv,
    direct_all_to_all,
    replay_algorithm,
    to_msccl_json,
    to_ppermute_program,
)
from repro.topology import tpu_v5e_pod, mesh2d


class TestEndToEnd:
    def test_pod_row_all_to_all(self):
        """A2A over one 'model axis' row of an 8x8 pod slice: synthesize,
        validate, translate to a ppermute program."""
        topo = tpu_v5e_pod(8, 8)
        row = list(range(8))
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", row, bytes=1.0))
        alg.validate()
        prog = to_ppermute_program(alg)
        assert prog.num_rounds >= 1
        sends = [s for r in prog.rounds for s in r]
        assert len(sends) == alg.num_transfers

    def test_pod_concurrent_row_groups(self):
        """Every row of a 4x4 pod runs its own A2A concurrently (the EP
        scenario of paper Fig 16/19), synthesized jointly."""
        topo = tpu_v5e_pod(4, 4)
        ids = ChunkIds()
        groups = []
        for r in range(4):
            row = [r * 4 + c for c in range(4)]
            groups.append((f"row{r}", all_to_all(row, ids=ids, bytes=1.0)))
        alg = SynthesisEngine(topo).synthesize_joint(groups)
        alg.validate()

    def test_process_group_speedup_claim(self):
        """Paper Fig 16: PG-aware PCCL vs Direct on 2D mesh, PG size = width.
        The paper reports 2.33-3.03x; we assert a sound >1.15x on 6x6."""
        topo = mesh2d(6, 6)
        group = list(range(6))  # one row
        pccl = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", group))
        pccl.validate()
        direct = direct_all_to_all(topo, group)
        speedup = direct.makespan / pccl.makespan
        assert speedup > 1.15, f"speedup {speedup:.2f}"

    def test_msccl_json_export(self):
        import json

        topo = mesh2d(3, 3)
        alg = SynthesisEngine(topo).collective(CollectiveRequest(
            "all_to_all", [0, 1, 2]))
        doc = json.loads(to_msccl_json(alg))
        assert doc["num_npus"] == 9
        ops = [o for g in doc["gpus"] for o in g["ops"]]
        assert any(o["op"] == "send" for o in ops)
        assert any(o["op"] == "recv" for o in ops)

    def test_moe_dispatch_alltoallv(self):
        """MoE expert dispatch = All-to-Allv with imbalanced counts (paper §2.1)."""
        topo = tpu_v5e_pod(4, 4)
        ep_group = [0, 1, 2, 3]
        counts = [[0, 3, 1, 1], [2, 0, 2, 1], [1, 1, 0, 3], [1, 2, 1, 0]]
        conds = all_to_allv(ep_group, counts)
        alg = SynthesisEngine(topo).synthesize(conds)
        alg.validate()
        replay = replay_algorithm(alg)
        assert replay.makespan == alg.makespan
